#!/usr/bin/env python3
"""The quickest proof that petals_tpu still serves on the chip.

    python chip_smoke.py                  # one chip, one process (the driver's run)
    python chip_smoke.py --chips 4        # four pinned run_server processes, CPU client
    python chip_smoke.py --chips 4 --tp   # one process, Server(num_tp_devices=4)

It refuses to run unless JAX's backend is a TPU, names each phase as it starts,
and stops at the first phase that fails (no phase is caught, retried or
skipped). The last line of stdout is ``{"ok": true, "device": {...}}``.

Single process, in order:

``device``      backend must be "tpu"; prints device kind, count, versions and
                where the compile cache lives.
``checkpoint``  Llama-2-7B widths cut to 8 layers, random bf16 weights from a
                fixed seed, written straight to safetensors + config.json under
                ``.chip_smoke/`` (reused when the manifest matches).
``kernels``     every Pallas kernel the serving code can select on a TPU,
                compiled by Mosaic (interpret=INTERPRET) at the smoke model's
                shapes plus one GQA shape, against its XLA reference.
``serve``       DHT bootstrap + ``Server(model)`` with its defaults + the
                normal client (``AutoDistributedModelForCausalLM``): greedy
                generation server-side and client-stepped, a repeat (token
                identical), a prompt-sharing request (prefix-cache hit), four
                concurrent sessions (batched paged decode + mixed step), a
                stateless forward, and last-position logits against a float32
                XLA-only forward of the same weights on the same chip.
``programs``    the autotune's two timings, and a Mosaic custom call in the
                lowered text of the prefill, paged-decode and mixed steps.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import signal
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
WORK_DIR = ROOT / ".chip_smoke"  # git-ignored: checkpoints and child logs
SEED = 20260926

# Llama-2-7B as published, cut by depth only
WIDTHS = dict(
    hidden_size=4096, intermediate_size=11008, num_attention_heads=32,
    num_key_value_heads=32, head_dim=128, vocab_size=32000,
)
PROMPT_LEN, NEW_TOKENS = 128, 32
# bf16 span (flash/paged Pallas attention) vs float32 XLA-only reference:
# max |diff| over max |ref| of one position's logits, allowed per layer of
# depth. bf16 rounding measured 2.5e-2..2.8e-2 at 8 layers and 3.1e-2..5.2e-2
# at 16 on the v5e (bounds 6e-2 and 1.2e-1); a wrong attention kernel lands
# near 1.
LOGITS_REL_BOUND_PER_LAYER = 7.5e-3
KERNEL_REL_BOUND = 2e-2
# Mosaic, always. No flag reaches this: only a CPU debug driver that imports
# the module flips it to rehearse the kernel phase in the Pallas interpreter.
INTERPRET = False


def say(msg: str) -> None:
    print(msg, flush=True)


@contextlib.contextmanager
def phase(name: str):
    say(f"== phase {name}")
    t0 = time.perf_counter()
    yield
    say(f"== phase {name} ok in {time.perf_counter() - t0:.1f}s")


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


# ------------------------------------------------------------------ checkpoint


def _bf16_bits(x: np.ndarray) -> np.ndarray:
    """float32 -> bfloat16 bit patterns (round to nearest), numpy only."""
    return ((x.view(np.uint32) + np.uint32(0x8000)) >> np.uint32(16)).astype(np.uint16)


def _write_safetensors(path: Path, tensors: dict) -> None:
    """{name: uint16 bf16 bit patterns} -> one safetensors file."""
    header, offset = {}, 0
    for name, arr in tensors.items():
        header[name] = {
            "dtype": "BF16", "shape": list(arr.shape),
            "data_offsets": [offset, offset + arr.nbytes],
        }
        offset += arr.nbytes
    blob = json.dumps(header, separators=(",", ":")).encode()
    blob += b" " * (-len(blob) % 8)
    with open(path, "wb") as f:
        f.write(len(blob).to_bytes(8, "little"))
        f.write(blob)
        for arr in tensors.values():
            f.write(arr.tobytes())


def _layer_tensors(widths: dict, layer: int) -> dict:
    h, m = widths["hidden_size"], widths["intermediate_size"]
    hq, hkv, d = widths["num_attention_heads"], widths["num_key_value_heads"], widths["head_dim"]
    rng = np.random.default_rng([SEED, layer])

    def w(out_dim, in_dim):  # HF layout [out, in]
        return _bf16_bits(rng.standard_normal((out_dim, in_dim), dtype=np.float32) * np.float32(0.02))

    ones = _bf16_bits(np.ones((h,), np.float32))
    p = f"model.layers.{layer}."
    return {
        p + "input_layernorm.weight": ones,
        p + "self_attn.q_proj.weight": w(hq * d, h),
        p + "self_attn.k_proj.weight": w(hkv * d, h),
        p + "self_attn.v_proj.weight": w(hkv * d, h),
        p + "self_attn.o_proj.weight": w(h, hq * d),
        p + "post_attention_layernorm.weight": ones,
        p + "mlp.gate_proj.weight": w(m, h),
        p + "mlp.up_proj.weight": w(m, h),
        p + "mlp.down_proj.weight": w(h, m),
    }


def _client_tensors(widths: dict) -> dict:
    h, v = widths["hidden_size"], widths["vocab_size"]
    rng = np.random.default_rng([SEED, 10_000])

    def w(rows, cols):
        return _bf16_bits(rng.standard_normal((rows, cols), dtype=np.float32) * np.float32(0.02))

    return {
        "model.embed_tokens.weight": w(v, h),
        "model.norm.weight": _bf16_bits(np.ones((h,), np.float32)),
        "lm_head.weight": w(v, h),
    }


def ensure_checkpoint(n_layers: int, widths: dict = WIDTHS) -> str:
    """Write (or reuse) the seeded checkpoint; returns its directory."""
    from concurrent.futures import ThreadPoolExecutor

    path = WORK_DIR / f"llama-{widths['hidden_size']}w-{n_layers}l"
    manifest = {"seed": SEED, "n_layers": n_layers, "widths": widths, "format": 1}
    manifest_path = path / "chip_smoke_manifest.json"
    if manifest_path.exists() and json.loads(manifest_path.read_text()) == manifest:
        say(f"checkpoint reused: {path}")
        return str(path)
    path.mkdir(parents=True, exist_ok=True)
    manifest_path.unlink(missing_ok=True)  # a half-written directory never matches
    config = {
        "architectures": ["LlamaForCausalLM"], "model_type": "llama",
        **{k: v for k, v in widths.items() if k != "head_dim"},
        "num_hidden_layers": n_layers, "hidden_act": "silu", "rms_norm_eps": 1e-5,
        "rope_theta": 10000.0, "max_position_embeddings": 4096,
        "tie_word_embeddings": False, "attention_bias": False, "mlp_bias": False,
        "torch_dtype": "bfloat16", "bos_token_id": 1, "eos_token_id": 2,
    }
    (path / "config.json").write_text(json.dumps(config, indent=1))
    weight_map = {}

    def write(index: int) -> dict:
        tensors = _client_tensors(widths) if index == n_layers else _layer_tensors(widths, index)
        fname = f"model-{index:05d}.safetensors"
        _write_safetensors(path / fname, tensors)
        return dict.fromkeys(tensors, fname)

    t0 = time.perf_counter()
    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as pool:
        for names in pool.map(write, range(n_layers + 1)):
            weight_map.update(names)
    (path / "model.safetensors.index.json").write_text(
        json.dumps({"metadata": {}, "weight_map": weight_map})
    )
    manifest_path.write_text(json.dumps(manifest))
    nbytes = sum(f.stat().st_size for f in path.glob("*.safetensors"))
    say(f"checkpoint written: {path} ({nbytes / 2**30:.2f} GiB in {time.perf_counter() - t0:.1f}s)")
    return str(path)


def prompt_ids(length: int, salt: int = 0) -> np.ndarray:
    rng = np.random.default_rng([SEED, 20_000 + salt])
    return rng.integers(3, WIDTHS["vocab_size"], (1, length), dtype=np.int64)


# ------------------------------------------------------------------ device


def require_tpu(want_chips: int):
    """The one gate: no accelerator, no phases. Returns jax, the device JSON
    of the last stdout line, and the live compile-cache event counts."""
    import jax

    from petals_tpu.utils.compile_cache import count_cache_events, enable_compilation_cache

    backend = jax.default_backend()
    if backend != "tpu":
        sys.stderr.write(
            f"chip_smoke: JAX backend is {backend!r} ({jax.devices()[0].device_kind}), not a TPU; "
            f"this script never runs its phases off the chip\n"
        )
        raise SystemExit(2)
    cache_dir = enable_compilation_cache()
    cache_counts = count_cache_events()
    devices = jax.devices()
    check(len(devices) >= want_chips, f"need {want_chips} chip(s), JAX sees {len(devices)}")
    import jaxlib
    import libtpu

    entries = len(list(Path(cache_dir).glob("*"))) if cache_dir and Path(cache_dir).is_dir() else 0
    say(
        f"backend tpu: {len(devices)} x {devices[0].device_kind}; jax {jax.__version__}, "
        f"jaxlib {jaxlib.__version__}, libtpu {libtpu.__version__}"
    )
    say(f"compile cache: {cache_dir} ({entries} entries at start)")
    device_json = {
        "platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices),
    }
    return jax, device_json, cache_counts


# ------------------------------------------------------------------ kernels


def _rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(np.isfinite(got).all(), "non-finite kernel output")
    return float(np.abs(got - want).max() / (np.abs(want).max() + 1e-9))


def _kernel_case(name: str, got, want, bound: float = KERNEL_REL_BOUND) -> None:
    err = _rel_err(got, want)
    say(f"  {name}: rel err {err:.2e}")
    check(err < bound, f"kernel {name} diverged from its XLA reference: {err:.3e} >= {bound}")


def kernel_phase(jax) -> None:
    import jax.numpy as jnp

    from petals_tpu.ops import quant as Q
    from petals_tpu.ops.attention import attend_reference
    from petals_tpu.ops.flash_attention import flash_attend
    from petals_tpu.ops.paged_attention import (
        PagedPool, paged_attend, paged_prefill_attend, quantize_kv_rows,
    )
    from petals_tpu.ops.paged_flash_attention import (
        composed_paged_attend, paged_flash_prefill_attend,
    )

    d = WIDTHS["head_dim"]
    key = jax.random.PRNGKey(SEED)

    def normal(i, shape, scale):
        return jax.random.normal(jax.random.fold_in(key, i), shape, jnp.bfloat16) * scale

    # --- flash attention (prefill / stateless forward path)
    for q_len, kv_len, hq, hkv, window, alibi in (
        (256, 256, 32, 32, None, False),  # the smoke model: MHA prefill
        (128, 256, 32, 8, None, True),  # GQA chunk at an offset + ALiBi
        (256, 256, 32, 8, 64, False),  # GQA + sliding window
    ):
        q, k, v = (
            normal(1, (1, q_len, hq, d), 0.3), normal(2, (1, kv_len, hkv, d), 0.3),
            normal(3, (1, kv_len, hkv, d), 0.3),
        )
        slopes = jnp.asarray(np.geomspace(0.25, 0.004, hq), jnp.float32) if alibi else None
        kw = dict(
            q_offset=kv_len - q_len, kv_length=kv_len, alibi_slopes=slopes, sliding_window=window
        )
        _kernel_case(
            f"flash_attend q{q_len} kv{kv_len} {hq}/{hkv} window={window} alibi={alibi}",
            flash_attend(q, k, v, interpret=INTERPRET, **kw), attend_reference(q, k, v, **kw),
        )

    # --- paged attention: the decode walk and the chunked-prefill kernel, every page encoding
    lanes, max_pages, page = 8, 16, 64
    n_pages = lanes * max_pages
    rng = np.random.default_rng(SEED)
    tables = rng.permutation(n_pages).astype(np.int32).reshape(lanes, max_pages)
    positions = np.asarray([0, 63, 64, 200, 511, 700, 1000, 1023], np.int32)
    for lane, pos in enumerate(positions):  # pages past the frontier are holes
        tables[lane, pos // page + 1:] = -1
    tables_j, positions_j = jnp.asarray(tables), jnp.asarray(positions)
    chunk, chunk_pos, n_valid = 128, 192, 100  # a ragged prefill bucket in lane 7's table
    for hq, hkv in ((32, 32), (32, 8)):
        k_fp, v_fp = normal(4, (n_pages, page, hkv, d), 0.3), normal(5, (n_pages, page, hkv, d), 0.3)
        q1, qc = normal(6, (lanes, 1, hq, d), 0.3), normal(7, (1, chunk, hq, d), 0.3)
        for kv_quant in ("none", "int8", "nf4a"):
            if kv_quant == "none":
                kp, vp = k_fp, v_fp
            else:
                kp = PagedPool(*quantize_kv_rows(k_fp, kv_quant))
                vp = PagedPool(*quantize_kv_rows(v_fp, kv_quant))
            _kernel_case(
                f"composed_paged_attend {hq}/{hkv} pages={kv_quant}",
                composed_paged_attend(q1, kp, vp, tables_j, q_offset=positions_j, kv_length=positions_j + 1),
                paged_attend(q1, kp, vp, tables_j, positions_j),
            )
            row = tables_j[7]
            got = paged_flash_prefill_attend(qc, kp, vp, row, chunk_pos, n_valid, interpret=INTERPRET)
            want = paged_prefill_attend(qc, kp, vp, row, chunk_pos, n_valid)
            _kernel_case(
                f"paged_flash_prefill_attend {hq}/{hkv} pages={kv_quant}",
                got[:, :n_valid], want[:, :n_valid],
            )

    # --- quantized matmuls at the smoke model's FFN shape, plain and span-stacked
    n_in, n_out = WIDTHS["hidden_size"], WIDTHS["intermediate_size"]
    w = normal(8, (n_in, n_out), 0.02)
    for kind in ("nf4a", "int4", "nf4", "int8"):
        qw = Q.quantize(w, kind)
        dense = Q.dequantize(qw, jnp.bfloat16)
        stacked = Q.StackedQuantLinear(
            kind, jnp.stack([qw.data * 0, qw.data]), jnp.stack([qw.scales, qw.scales]),
            jnp.int32(1), n_in, n_out,
        )
        plain_fn, stacked_fn = (
            (Q.int8_matmul_pallas, Q.int8_matmul_pallas_stacked) if kind == "int8"
            else (Q.packed4_matmul_pallas, Q.packed4_matmul_pallas_stacked)
        )
        for m in (1, 200):  # the decode kernel and the prefill kernel
            x = normal(9 + m, (m, n_in), 0.1)
            want = (x @ dense).astype(jnp.float32)
            _kernel_case(f"{kind} matmul M={m}", plain_fn(x, qw, interpret=INTERPRET), want)
            _kernel_case(f"{kind} matmul M={m} stacked", stacked_fn(x, stacked, interpret=INTERPRET), want)


# ------------------------------------------------------------------ serve


class LoopThread:
    """An asyncio loop on its own thread: the DHT bootstrap and the server
    live there, the blocking client drives them from the main thread."""

    def __init__(self):
        import asyncio

        self.loop = asyncio.new_event_loop()
        self._thread = threading.Thread(target=self._run, name="chip-smoke-swarm", daemon=True)
        self._thread.start()

    def _run(self) -> None:
        import asyncio

        asyncio.set_event_loop(self.loop)
        self.loop.run_forever()

    def run(self, coro, timeout: float = 900.0):
        import asyncio

        return asyncio.run_coroutine_threadsafe(coro, self.loop).result(timeout)

    def stop(self) -> None:
        self.loop.call_soon_threadsafe(self.loop.stop)
        self._thread.join(timeout=30)
        check(not self._thread.is_alive(), "swarm loop thread did not stop")


def reference_logits(jax, model_dir: str, ids: np.ndarray) -> np.ndarray:
    """float32, XLA-only forward of the checkpoint on the default device: no
    Pallas, no flash, highest matmul precision, one block resident at a time.
    Returns logits [batch, seq, vocab]."""
    import jax.numpy as jnp

    from petals_tpu.client.from_pretrained import load_client_params
    from petals_tpu.server.from_pretrained import get_block_config, load_block_params

    family, cfg = get_block_config(model_dir)
    with jax.default_matmul_precision("highest"):
        client = load_client_params(model_dir, dtype=jnp.float32, family=family, cfg=cfg)
        hidden = family.client_embed(client, ids, cfg).astype(jnp.float32)
        block = jax.jit(
            lambda p, h: family.block_apply(p, h, None, 0, cfg, use_flash=False)[0]
        )
        for i in range(cfg.num_hidden_layers):
            params = load_block_params(model_dir, i, dtype=jnp.float32, family=family, cfg=cfg)
            hidden = block(params, hidden)
        logits = family.client_head(client, hidden, cfg)
    return np.asarray(logits, np.float32)


def logits_check(name: str, got: np.ndarray, want: np.ndarray, n_layers: int) -> None:
    got = np.asarray(got, np.float32).reshape(-1)
    check(np.isfinite(got).all(), f"{name}: non-finite logits")
    check(got.shape == want.shape, f"{name}: logits shape {got.shape} != {want.shape}")
    err = float(np.abs(got - want).max() / np.abs(want).max())
    bound = LOGITS_REL_BOUND_PER_LAYER * n_layers
    agree = int(got.argmax()) == int(want.argmax())
    say(f"  {name}: rel err vs float32 XLA reference {err:.2e} (bound {bound:.1e}), argmax agrees: {agree}")
    check(err < bound, f"{name}: logits off the reference by {err:.3e} (bound {bound:.1e})")


def drive_client(model, *, server_side: bool, concurrent: bool) -> None:
    """The generation requests of the smoke, through the public client surface."""
    from concurrent.futures import ThreadPoolExecutor

    vocab = model.cfg.vocab_size
    prompt = prompt_ids(PROMPT_LEN)

    def generate(name, ids, **kw):
        t0 = time.perf_counter()
        tokens = np.asarray(model.generate(ids, max_new_tokens=NEW_TOKENS, **kw))
        check(tokens.shape == (1, ids.shape[1] + NEW_TOKENS), f"{name}: shape {tokens.shape}")
        check((tokens[:, : ids.shape[1]] == ids).all(), f"{name}: prompt not echoed")
        check(((tokens >= 0) & (tokens < vocab)).all(), f"{name}: token out of range")
        say(f"  {name}: {NEW_TOKENS} tokens in {time.perf_counter() - t0:.2f}s -> {tokens[0, -8:].tolist()}")
        return tokens

    first = generate("generate", prompt)
    again = generate("generate again", prompt)
    check((first == again).all(), "repeated greedy request produced different tokens")
    # an empty processor list keeps logits on the client: one RPC per token
    stepped = generate("generate client-stepped", prompt, logits_processor=[])
    stepped_again = generate("generate client-stepped again", prompt, logits_processor=[])
    check((stepped == stepped_again).all(), "repeated client-stepped request produced different tokens")
    if server_side:
        same = int((first[0, PROMPT_LEN:] == stepped[0, PROMPT_LEN:]).sum())
        say(f"  server-side vs client-stepped: {same}/{NEW_TOKENS} tokens equal")
    # a longer prompt that starts with the first one: its first segment is cached
    generate("generate shared prefix", np.concatenate([prompt, prompt_ids(32, salt=1)], axis=1))

    if not concurrent:
        return
    # one session decoding while three more of different lengths arrive:
    # their prefills ride the mixed step, then all four decode as a batch
    decoding = threading.Event()

    class FirstTokens:  # the streamer protocol: .put(tokens), .end()
        puts = 0

        def put(self, _tokens):
            self.puts += 1
            if self.puts >= 4:  # the prompt, then three decoded tokens
                decoding.set()

        def end(self):
            decoding.set()

    def session(n, salt, **kw):
        return np.asarray(model.generate(
            prompt_ids(n, salt=salt), max_new_tokens=NEW_TOKENS, logits_processor=[], **kw
        ))

    lengths = (160, 40, 96, 200)
    with ThreadPoolExecutor(max_workers=len(lengths)) as pool:
        futures = [pool.submit(session, lengths[0], 10, streamer=FirstTokens())]
        while not decoding.wait(1.0):
            check(not futures[0].done(), "the first concurrent session ended before it streamed")
        futures += [pool.submit(session, n, 10 + i) for i, n in enumerate(lengths[1:], 1)]
        for n, future in zip(lengths, futures):
            tokens = future.result(timeout=600)
            check(tokens.shape == (1, n + NEW_TOKENS), f"concurrent-{n}: shape {tokens.shape}")
    say(f"  four concurrent sessions (prompts {lengths}) finished")


def session_check(model, name: str, ids: np.ndarray, ref: np.ndarray, max_length: int) -> None:
    """One inference session: prefill ``ids[:, :-1]``, then one decode step,
    the last-position logits of both against the reference's."""
    depth = model.cfg.num_hidden_layers
    with model.inference_session(max_length=max_length) as session:
        h = session.step(np.asarray(model.embed(ids[:, :PROMPT_LEN])))
        logits_check(
            f"{name} prefill, last position", model.lm_logits(h[:, -1:])[0, 0],
            ref[PROMPT_LEN - 1], depth,
        )
        h = session.step(np.asarray(model.embed(ids[:, PROMPT_LEN:], with_prompts=False)))
        logits_check(f"{name} decode step", model.lm_logits(h[:, -1:])[0, 0], ref[PROMPT_LEN], depth)


def logits_checks(jax, model, model_dir: str) -> None:
    """Last-position logits through the swarm against the float32 reference,
    on prompts nothing else has sent (so nothing comes from the prefix
    cache), each followed by one more token for a decode step."""
    vocab = model.cfg.vocab_size
    ids = np.concatenate([prompt_ids(PROMPT_LEN + 1, salt=2 + row) for row in range(2)])
    ref = reference_logits(jax, model_dir, ids)
    logits = np.asarray(model.forward(ids[:1]), np.float32)
    check(logits.shape == (1, PROMPT_LEN + 1, vocab), f"forward: logits shape {logits.shape}")
    check(np.isfinite(logits).all(), "forward: non-finite logits")
    logits_check(
        "stateless forward, last position", logits[0, -1], ref[0, -1], model.cfg.num_hidden_layers
    )
    # a pooled session (paged lane: the mixed step, then the batched decode)
    # and one too long for a lane (private dense cache: flash prefill)
    session_check(model, "lane session", ids[:1], ref[0], PROMPT_LEN + NEW_TOKENS)
    session_check(model, "private-cache session", ids[1:2], ref[1], 2048)


def serve_phase(jax, model_dir: str, *, tp: int = 1) -> list:
    """Bootstrap + one default Server + the normal client, in this process.
    Returns the observatory's program records for the programs phase."""
    from petals_tpu import AutoDistributedModelForCausalLM
    from petals_tpu.dht import DHTNode
    from petals_tpu.server.server import Server
    from petals_tpu.telemetry.observatory import get_observatory

    swarm = LoopThread()
    state = {}

    async def boot():
        state["bootstrap"] = await DHTNode.create(host="127.0.0.1")
        kwargs = {"num_tp_devices": tp} if tp > 1 else {}
        state["server"] = Server(model_dir, initial_peers=[state["bootstrap"].own_addr], **kwargs)
        await state["server"].start()

    async def teardown():
        if "server" in state:
            await state["server"].shutdown()
        if "bootstrap" in state:
            await state["bootstrap"].shutdown()

    model = None
    paged = tp == 1  # a TP mesh keeps the dense lane pool (ROADMAP B7)
    try:
        swarm.run(boot())
        server = state["server"]
        batcher = server.handler.batcher
        check(server.num_blocks == server.cfg.num_hidden_layers, "server did not take the whole model")
        check(batcher is not None, "default server came up without continuous batching")
        if not paged:
            _check_tp_sharding(jax, server, tp)
        else:
            check(batcher.page_size == 64, f"default pool is not paged at 64: {batcher.page_size}")
            check(server.handler.server_gen_params is not None, "server-side generation is off")
            check(server.handler.prefix_cache is not None, "prefix cache is off")

        model = AutoDistributedModelForCausalLM.from_pretrained(
            model_dir, initial_peers=[state["bootstrap"].own_addr.to_string()]
        )
        drive_client(model, server_side=True, concurrent=True)
        logits_checks(jax, model, model_dir)

        stats = dict(batcher.stats)
        say(f"  batcher stats: {stats}")
        check(stats["gen_steps"] > 0, "no server-side generation step ran in the lane pool")
        check(stats["max_batch"] >= 2, "decode sessions never coalesced into one batched step")
        if paged:
            check(stats["mixed_steps"] > 0, "no prefill chunk rode a mixed step")
            pc = server.handler.prefix_cache.stats
            say(f"  prefix cache stats: {pc}")
            check(pc["hits"] >= 1, "the prompt-sharing request did not hit the prefix cache")
        records = get_observatory().programs()
        ran = sorted({r.fn for r in records})
        say(f"  compiled programs: {ran}")
        want = {"forward", "inference_step"} | (
            {"paged_decode", "paged_gen_decode", "paged_mixed_step"} if paged
            else {"batched_decode", "batched_gen_decode"}
        )
        check(want <= set(ran), f"step programs missing from the compiled set: {sorted(want - set(ran))}")
        anomalies = [r.fn for r in records if r.anomaly]
        check(not anomalies, f"steady step programs recompiled after warm-up: {anomalies}")
        return get_observatory().programs()
    finally:
        if model is not None:
            model.close()
        swarm.run(teardown(), timeout=120)
        swarm.stop()


def _check_tp_sharding(jax, server, tp: int) -> None:
    """Every big span leaf really lives on ``tp`` chips, a quarter each."""
    checked = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(server.backend.params):
        if leaf.ndim < 3:  # [n_blocks, hidden] norms stay replicated
            continue
        devices = leaf.sharding.device_set
        check(len(devices) == tp, f"{jax.tree_util.keystr(path)} sits on {len(devices)} device(s)")
        shard = leaf.addressable_shards[0].data.nbytes
        check(
            abs(shard * tp - leaf.nbytes) <= 0.01 * leaf.nbytes,
            f"{jax.tree_util.keystr(path)}: {shard} bytes per device of {leaf.nbytes}",
        )
        checked += 1
    check(checked > 0, "no sharded span leaf found")
    say(f"  {checked} span leaves sharded over {tp} devices, 1/{tp} of their bytes on each")


def programs_phase(records: list, *, paged: bool) -> None:
    """'The kernel path' must contain a kernel: find the Mosaic custom call
    in the lowered step programs."""
    from petals_tpu.telemetry.observatory import get_observatory

    obs = get_observatory()
    # the private-cache prefill takes the flash kernel; a decode row's walk
    # over this pool (32 kv heads of 128) and a prompt's chunk are kernels too
    must = ["inference_step"] + (["paged_decode", "paged_mixed_step"] if paged else [])
    for fn in must:
        texts = [obs.lowered_text(r) for r in records if r.fn == fn]
        check(texts, f"no compiled {fn} program was recorded")
        with_kernel = sum("tpu_custom_call" in t for t in texts)
        say(f"  {fn}: {with_kernel}/{len(texts)} compiled program(s) contain a Mosaic custom call")
        check(with_kernel > 0, f"{fn} never lowered with a Mosaic custom call")


def single_process(args) -> dict:
    t_start = time.perf_counter()
    with phase("device"):
        jax, device_json, cache_counts = require_tpu(4 if args.tp else 1)
    with phase("checkpoint"):
        model_dir = ensure_checkpoint(16 if args.tp else 8)
    if not args.tp:
        with phase("kernels"):
            kernel_phase(jax)
    with phase("serve"):
        records = serve_phase(jax, model_dir, tp=4 if args.tp else 1)
    with phase("programs"):
        programs_phase(records, paged=not args.tp)
    say(
        f"compile cache traffic: {cache_counts['requests']} compilations consulted it, "
        f"{cache_counts['hits']} hits, {cache_counts['writes']} new entries"
    )
    say(f"wall time {time.perf_counter() - t_start:.1f}s")
    return device_json


# ------------------------------------------------------------------ four chips, four servers

def chip_pin_env(chip: int) -> dict:
    """libtpu reads these before it touches a chip: the process sees exactly
    one. All three are needed (libtpu 0.0.34): TPU_VISIBLE_CHIPS alone makes
    concurrent processes collide on /tmp/libtpu_lockfile; no port variable is."""
    return {
        "TPU_VISIBLE_CHIPS": str(chip),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }


def _wait_for_line(log_path: Path, proc: subprocess.Popen, needle: str, timeout: float) -> str:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        text = log_path.read_text(errors="replace") if log_path.exists() else ""
        for line in text.splitlines():
            if needle in line:
                return line
        check(proc.poll() is None, f"{log_path.name}: process exited {proc.returncode}:\n{text[-3000:]}")
        time.sleep(0.5)
    raise TimeoutError(f"{log_path.name}: no {needle!r} within {timeout:.0f}s:\n{text[-3000:]}")


def swarm_of_four(args) -> dict:
    """A JAX-free parent: bootstrap and client children on the CPU, four
    run_server children each pinned to one chip, each a quarter of 16 layers."""
    check("jax" not in sys.modules, "the swarm parent must stay off JAX")
    n_layers, n_servers = 16, 4
    with phase("checkpoint"):
        model_dir = ensure_checkpoint(n_layers)
    logs = WORK_DIR / "logs"
    logs.mkdir(parents=True, exist_ok=True)
    base_env = {**os.environ, "PYTHONPATH": str(ROOT), "PYTHONUNBUFFERED": "1"}
    cpu_env = {**base_env, "JAX_PLATFORMS": "cpu"}
    children = []

    def spawn(name: str, cmd: list, env: dict) -> tuple:
        log_path = logs / f"{name}.log"
        log_file = open(log_path, "w")
        proc = subprocess.Popen(
            [sys.executable, *cmd], env=env, stdout=log_file, stderr=subprocess.STDOUT, cwd=ROOT
        )
        children.append((name, proc, log_file))
        return proc, log_path

    try:
        with phase("bootstrap"):
            proc, log_path = spawn(
                "bootstrap", ["-m", "petals_tpu.cli.run_dht", "--host", "127.0.0.1", "--no_relay"], cpu_env
            )
            addr = _wait_for_line(log_path, proc, "/", 120).strip().split()[-1]
            say(f"  bootstrap at {addr}")
        with phase("servers"):
            per = n_layers // n_servers
            started = []
            for chip in range(n_servers):
                started.append(spawn(
                    f"server{chip}",
                    ["-m", "petals_tpu.cli.run_server", model_dir, "--host", "127.0.0.1",
                     "--initial_peers", addr, "--block_indices", f"{chip * per}:{(chip + 1) * per}",
                     "--identity_seed", f"chip-smoke-{chip}"],
                    {**base_env, **chip_pin_env(chip)},
                ))
            seen = []
            for chip, (proc, log_path) in enumerate(started):
                _wait_for_line(log_path, proc, "Server ready", 900)
                line = _wait_for_line(log_path, proc, "JAX backend", 5)
                say(f"  server{chip}: {line.split('] ', 2)[-1]}")
                check("JAX backend tpu: 1 x" in line, f"server{chip} is not on exactly one TPU chip")
                held = line.split("chips ", 1)[1].split(";")[0]
                check(held.count("/dev/") == 1, f"server{chip} holds {held}, not one chip's device file")
                seen.append(held)
            check(len(set(seen)) == n_servers, f"servers share a chip: {seen}")
            kind = line.split(" x ", 1)[1].split(", chips", 1)[0]
        with phase("client"):
            proc, log_path = spawn(
                "client", [str(ROOT / "chip_smoke.py"), "--role", "client", "--initial_peers", addr,
                           "--model_dir", model_dir], cpu_env,
            )
            code = proc.wait(timeout=900)
            text = log_path.read_text(errors="replace")
            say(text if len(text) < 6000 else text[-6000:])
            check(code == 0, f"client child exited {code}")
    finally:
        for name, proc, log_file in reversed(children):
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
        for name, proc, log_file in children:
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=30)
            log_file.close()
    return {"platform": "tpu", "kind": kind, "count": n_servers}


def client_role(args) -> None:
    """The CPU client of the four-server chain (a child of ``--chips 4``)."""
    import jax

    check(jax.default_backend() == "cpu", "the chain's client must not take a chip")
    from petals_tpu import AutoDistributedModelForCausalLM

    model = AutoDistributedModelForCausalLM.from_pretrained(
        args.model_dir, initial_peers=[args.initial_peers]
    )
    try:
        remote = model.remote
        spans = remote.runtime.run(remote.sequence_manager.make_sequence(), timeout=120)
        say(f"  route: {[(s.start, s.end) for s in spans]}")
        check(len(spans) == 4, f"expected a four-hop chain, got {len(spans)} hop(s)")
        drive_client(model, server_side=False, concurrent=False)
        logits_checks(jax, model, args.model_dir)
    finally:
        model.close()


# ------------------------------------------------------------------ entry


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--tp", action="store_true", help="with --chips 4: one process, tensor parallel")
    parser.add_argument("--deadline", type=int, default=1150, help="abort after this many seconds")
    parser.add_argument("--role", choices=("client",), default=None, help=argparse.SUPPRESS)
    parser.add_argument("--initial_peers", default=None, help=argparse.SUPPRESS)
    parser.add_argument("--model_dir", default=None, help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    if args.tp and args.chips != 4:
        parser.error("--tp needs --chips 4")

    def on_deadline(_sig, _frame):
        raise TimeoutError(f"chip_smoke: still running after {args.deadline}s")

    signal.signal(signal.SIGALRM, on_deadline)
    signal.alarm(args.deadline)

    if args.role == "client":
        client_role(args)
        return 0
    device = swarm_of_four(args) if args.chips == 4 and not args.tp else single_process(args)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
