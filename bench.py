"""Benchmarks on one real TPU chip (to be replaced by ROADMAP S0's cells file
and runner; kept honest and runnable until then).

``python bench.py`` is a JAX-free parent that runs every on-chip row in its
own ``--row`` child, one after another: a chip belongs to one process at a
time. A child aborts unless its backend is a TPU, a row that fails stops the
run with a non-zero exit, and no number is printed that this run did not
measure. ``--gate`` / ``--row`` drive the count-based CPU gate rows.

Primary (the ONE stdout JSON line): single-stream
autoregressive decode through the FULL stack (client -> RPC -> handler ->
priority queue -> stacked-span scan on TPU -> KV cache in HBM -> back) on a
Llama-2-7B-shaped span, mirroring the reference harness
(benchmarks/benchmark_inference.py:44-68 — tok/s, 1 token per step, real
session). The reference baseline is 6 tok/s single-stream for Llama-2-70B over
an Internet swarm of consumer GPUs (README.md:86).

North-star shape benchmarks (stderr + chiprun_out/bench_details.json), on-device:
- 70B-block-shaped (hidden 8192, GQA 64/8) bf16 span decode: tok/s, p50 step
  latency, HBM bandwidth utilisation (decode is weight-bandwidth-bound).
- NF4-quantized 70B-shaped span decode via the fused Pallas dequant-matmul.
- Long-context (8k) prefill through the flash-attention kernel: tok/s + MFU.
"""

import asyncio
import functools
import gc
import json
import os
import statistics
import sys
import time

import numpy as np

N_BLOCKS = 8  # 7B-shaped blocks resident in HBM (~3.2 GB bf16) + KV budget
WARMUP_STEPS = 5
MEASURE_STEPS = 30
PREFILL_TOKENS = 128
MAX_LENGTH = 256
BASELINE_TOK_S = 6.0  # reference: Llama-2-70B, Internet swarm (README.md:86)

# Published per-chip peaks (Google Cloud documentation, "TPU v5e": 819 GB/s HBM,
# 197 bf16 TFLOP/s), keyed by jax device_kind. A device that is not here has no
# roofline to divide by: the row fails instead of borrowing v5e's.
PEAKS_BY_DEVICE_KIND = {
    "TPU v5 lite": {"hbm_gbs": 819.0, "bf16_tflops": 197.0},
    "TPU v5e": {"hbm_gbs": 819.0, "bf16_tflops": 197.0},
}


def device_peaks() -> dict:
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in PEAKS_BY_DEVICE_KIND:
        raise RuntimeError(
            f"no published peaks for device kind {kind!r}; bandwidth/MFU shares "
            f"are only defined for {sorted(PEAKS_BY_DEVICE_KIND)}"
        )
    return PEAKS_BY_DEVICE_KIND[kind]


def llama7b_cfg(n_blocks=N_BLOCKS):
    from petals_tpu.models.llama.config import LlamaBlockConfig

    return LlamaBlockConfig(
        hidden_size=4096,
        num_attention_heads=32,
        num_key_value_heads=32,
        head_dim=128,
        intermediate_size=11008,
        num_hidden_layers=n_blocks,
        rms_norm_eps=1e-5,
        vocab_size=32000,
    )


def llama70b_cfg(n_blocks):
    from petals_tpu.models.llama.config import LlamaBlockConfig

    return LlamaBlockConfig(
        hidden_size=8192,
        num_attention_heads=64,
        num_key_value_heads=8,
        head_dim=128,
        intermediate_size=28672,
        num_hidden_layers=n_blocks,
        rms_norm_eps=1e-5,
        vocab_size=128256,
    )


def random_params(cfg, n_blocks, dtype, quant=None):
    import jax
    import jax.numpy as jnp

    from petals_tpu.models.llama.block import block_param_shapes
    from petals_tpu.utils.convert_block import convert_block_params

    shapes = block_param_shapes(cfg, dtype)
    key = jax.random.PRNGKey(0)

    if not quant:
        # stacked leaves in one jit: no transient per-block copies in HBM
        @jax.jit
        def init_stacked(key):
            params = {}
            for name, sds in sorted(shapes.items()):
                key, sub = jax.random.split(key)
                params[name] = jax.random.normal(sub, (n_blocks, *sds.shape), dtype) * 0.02
            return params

        stacked = init_stacked(key)
        jax.block_until_ready(stacked)
        return stacked

    @jax.jit
    def init(key):
        params = {}
        for name, sds in sorted(shapes.items()):
            key, sub = jax.random.split(key)
            params[name] = jax.random.normal(sub, sds.shape, dtype) * 0.02
        return params

    per_block = []
    for b in range(n_blocks):
        key, sub = jax.random.split(key)
        block = convert_block_params(init(sub), "llama", quant, fuse=True)
        jax.block_until_ready(block)  # bound the dense-block transient
        per_block.append(block)
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_block)
    jax.block_until_ready(stacked)
    return stacked


def params_bytes(params) -> int:
    import jax

    from petals_tpu.ops.quant import QuantizedLinear

    total = 0
    for leaf in jax.tree_util.tree_leaves(
        params, is_leaf=lambda x: isinstance(x, QuantizedLinear)
    ):
        if isinstance(leaf, QuantizedLinear):
            total += leaf.nbytes
        else:
            total += leaf.size * leaf.dtype.itemsize
    return total


def bench_device_decode(cfg, *, quant=None, label="", batches=3, steps=25):
    """On-device span decode: p50 step latency + weight-stream bandwidth."""
    import jax
    import jax.numpy as jnp

    from petals_tpu.models.registry import get_family
    from petals_tpu.server.backend import TransformerBackend
    from petals_tpu.server.memory_cache import MemoryCache

    n_blocks = cfg.num_hidden_layers
    dtype = jnp.bfloat16
    t0 = time.perf_counter()
    params = random_params(cfg, n_blocks, dtype, quant=quant)
    init_s = time.perf_counter() - t0
    weight_bytes = params_bytes(params)

    backend = TransformerBackend(
        get_family("llama"), cfg, params,
        first_block=0, n_blocks=n_blocks,
        memory_cache=MemoryCache(None), compute_dtype=dtype,
    )
    kd, vd = backend.cache_descriptors(1, MAX_LENGTH, 0, n_blocks)
    kv = (kd.make_zeros(), vd.make_zeros())

    rng = np.random.RandomState(0)
    prefill = rng.randn(1, PREFILL_TOKENS, cfg.hidden_size).astype(np.float32) * 0.02
    step_h = rng.randn(1, 1, cfg.hidden_size).astype(np.float32) * 0.02

    _, kv = backend.inference_step(prefill, kv, 0)
    pos = PREFILL_TOKENS
    out = None
    for _ in range(WARMUP_STEPS):
        out, kv = backend.inference_step(step_h, kv, pos)
        pos += 1
    jax.block_until_ready(out)

    per_step = []
    for _ in range(batches):
        t0 = time.perf_counter()
        for _ in range(steps):
            out, kv = backend.inference_step(step_h, kv, pos)
            pos += 1
        jax.block_until_ready(out)
        elapsed = time.perf_counter() - t0
        per_step.append(elapsed / steps)

    p50 = statistics.median(per_step)
    gbs = weight_bytes / p50 / 1e9
    result = {
        "label": label,
        "n_blocks": n_blocks,
        "quant": quant or "bf16",
        "weight_gb": round(weight_bytes / 2**30, 2),
        "decode_tok_s": round(1.0 / p50, 2),
        "p50_step_ms": round(p50 * 1e3, 3),
        "weight_stream_gb_s": round(gbs, 1),
        "hbm_bw_pct": round(100.0 * gbs / device_peaks()["hbm_gbs"], 1),
        "param_init_s": round(init_s, 1),
    }
    del params, backend, kv, out
    gc.collect()
    return result


def bench_moe_dispatch(seq=2048, *, runs=3):
    """Mixtral-8x7B-shaped MoE layer at prefill: dense all-experts vs sparse
    ragged_dot dispatch (FLOPs ratio = num_experts / top_k = 4x). The
    sparse path's bench row."""
    import jax
    import jax.numpy as jnp

    from petals_tpu.models.moe import moe_apply

    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 5)
    h, m, E, top_k = 4096, 14336, 8, 2
    params = {
        "gate": jax.random.normal(ks[0], (h, E), jnp.bfloat16) * 0.2,
        "w1": jax.random.normal(ks[1], (E, h, m), jnp.bfloat16) * 0.02,
        "w2": jax.random.normal(ks[2], (E, m, h), jnp.bfloat16) * 0.02,
        "w3": jax.random.normal(ks[3], (E, h, m), jnp.bfloat16) * 0.02,
    }
    x = jax.random.normal(ks[4], (1, seq, h), jnp.bfloat16) * 0.3
    jax.block_until_ready(params)

    fns = {
        mode: jax.jit(functools.partial(moe_apply, top_k=top_k, renormalize=True, grouped=(mode == "sparse")))
        for mode in ("dense", "sparse")
    }
    times = {}
    for mode, fn in fns.items():
        jax.block_until_ready(fn(params, x))  # compile
        best = float("inf")
        for _ in range(runs):
            t0 = time.perf_counter()
            out = fn(params, x)
            jax.block_until_ready(out)
            best = min(best, time.perf_counter() - t0)
        times[mode] = best
    # useful assignment flops (top-k only): 3 matmuls over N*k rows
    flops_sparse = (
        2 * seq * top_k * 3 * h * m
    )
    result = {
        "label": f"moe_prefill_{seq}",
        "dense_ms": round(times["dense"] * 1e3, 1),
        "sparse_ms": round(times["sparse"] * 1e3, 1),
        "speedup": round(times["dense"] / times["sparse"], 2),
        "flops_ratio_expected": round(E / top_k, 1),
        "sparse_tflops_useful": round(flops_sparse / times["sparse"] / 1e12, 1),
    }
    del params, x, fns
    gc.collect()
    return result


def bench_batched_decode(cfg, batch_sizes=(1, 8, 32), *, steps=20):
    """Aggregate decode throughput vs batch size on one span: decode is
    weight-bandwidth-bound, so batching multiplies tok/s almost for free until
    the MXU starts to matter (the serving-throughput story; the reference's
    task pools never batch across requests, reference task_pool.py:35-36)."""
    import jax
    import jax.numpy as jnp

    from petals_tpu.models.registry import get_family
    from petals_tpu.server.backend import TransformerBackend
    from petals_tpu.server.memory_cache import MemoryCache

    n_blocks = cfg.num_hidden_layers
    dtype = jnp.bfloat16
    params = random_params(cfg, n_blocks, dtype)
    backend = TransformerBackend(
        get_family("llama"), cfg, params,
        first_block=0, n_blocks=n_blocks,
        memory_cache=MemoryCache(None), compute_dtype=dtype,
    )
    rng = np.random.RandomState(0)
    rows = []
    for batch in batch_sizes:
        kd, vd = backend.cache_descriptors(batch, MAX_LENGTH, 0, n_blocks)
        kv = (kd.make_zeros(), vd.make_zeros())
        prefill = rng.randn(batch, PREFILL_TOKENS, cfg.hidden_size).astype(np.float32) * 0.02
        step_h = rng.randn(batch, 1, cfg.hidden_size).astype(np.float32) * 0.02
        _, kv = backend.inference_step(prefill, kv, 0)
        pos = PREFILL_TOKENS
        out = None
        for _ in range(3):
            out, kv = backend.inference_step(step_h, kv, pos)
            pos += 1
        jax.block_until_ready(out)
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(steps):
                out, kv = backend.inference_step(step_h, kv, pos)
                pos += 1
            jax.block_until_ready(out)
            best = min(best, (time.perf_counter() - t0) / steps)
        rows.append({
            "batch": batch,
            "step_ms": round(best * 1e3, 3),
            "tok_s": round(batch / best, 1),
        })
        del kv, out
        # keep MAX_LENGTH-token caches from accumulating across batch sizes
        gc.collect()
    result = {"label": "decode_7b_batched", "n_blocks": n_blocks, "rows": rows}
    del params, backend
    gc.collect()
    return result


def bench_flash_prefill(cfg, seq, *, runs=3):
    """Long-context prefill through the Pallas flash kernel: tok/s + MFU."""
    import jax
    import jax.numpy as jnp

    from petals_tpu.models.registry import get_family
    from petals_tpu.server.backend import TransformerBackend
    from petals_tpu.server.memory_cache import MemoryCache

    n_blocks = cfg.num_hidden_layers
    dtype = jnp.bfloat16
    params = random_params(cfg, n_blocks, dtype)
    backend = TransformerBackend(
        get_family("llama"), cfg, params,
        first_block=0, n_blocks=n_blocks,
        memory_cache=MemoryCache(None), compute_dtype=dtype,
        use_flash=True, max_chunk_size_bytes=1 << 30,
    )
    kd, vd = backend.cache_descriptors(1, seq, 0, n_blocks)

    rng = np.random.RandomState(0)
    # resident on device, in compute dtype, BEFORE timing: the 256 MB f32
    # host array would otherwise be uploaded inside every timed run
    hidden = jax.device_put(
        jnp.asarray(rng.randn(1, seq, cfg.hidden_size).astype(np.float32) * 0.02, dtype)
    )
    jax.block_until_ready(hidden)

    kv = (kd.make_zeros(), vd.make_zeros())
    out, kv = backend.inference_step(hidden, kv, 0)  # compile
    jax.block_until_ready(out)
    del kv

    times = []
    for _ in range(runs):
        kv = (kd.make_zeros(), vd.make_zeros())
        jax.block_until_ready(kv)
        t0 = time.perf_counter()
        out, kv = backend.inference_step(hidden, kv, 0)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
        del kv
    t = statistics.median(times)

    # matmul flops/block: 2*seq*(qkvo + mlp) params; attention: qk + av, causal
    h, m = cfg.hidden_size, cfg.intermediate_size
    qkvo = h * (cfg.num_attention_heads * cfg.head_dim)
    qkvo += 2 * h * (cfg.num_key_value_heads * cfg.head_dim)
    qkvo += (cfg.num_attention_heads * cfg.head_dim) * h
    mlp = 3 * h * m
    matmul_flops = 2 * seq * (qkvo + mlp)
    attn_flops = 2 * 2 * cfg.num_attention_heads * cfg.head_dim * seq * seq / 2
    flops = n_blocks * (matmul_flops + attn_flops)
    tflops = flops / t / 1e12
    result = {
        "label": f"prefill_{seq}_flash",
        "n_blocks": n_blocks,
        "seq": seq,
        "prefill_s": round(t, 3),
        "prefill_tok_s": round(seq / t, 0),
        "tflops": round(tflops, 1),
        "mfu_pct": round(100.0 * tflops / device_peaks()["bf16_tflops"], 1),
    }
    del params, backend, out
    gc.collect()
    return result


async def run_server_gen_bench(gen_chunk=32, chunks=4):
    """Server-side (device-resident) greedy generation e2e: the full-span
    server runs sample->embed->span->sample as ONE jitted scan per chunk and
    returns token ids — one RPC (and one host<->device sync) per CHUNK
    instead of per token. Same span/server/wire as the e2e row, so the
    tok_s ratio is the measured value of the feature."""
    import jax
    import jax.numpy as jnp

    from petals_tpu.data_structures import CHAIN_DELIMITER, make_uid
    from petals_tpu.models.registry import get_family
    from petals_tpu.rpc import RpcClient
    from petals_tpu.rpc.serialization import serialize_array
    from petals_tpu.rpc.server import RpcServer
    from petals_tpu.server.backend import TransformerBackend
    from petals_tpu.server.handler import TransformerHandler
    from petals_tpu.server.memory_cache import MemoryCache

    cfg = llama7b_cfg()
    family = get_family("llama")
    dtype = jnp.bfloat16

    t0 = time.perf_counter()
    params = random_params(cfg, N_BLOCKS, dtype)
    init_s = time.perf_counter() - t0
    key = jax.random.PRNGKey(7)
    client_params = {
        "embed": jax.random.normal(key, (cfg.vocab_size, cfg.hidden_size), jnp.float32) * 0.02,
        "norm": jnp.ones((cfg.hidden_size,), jnp.float32),
        "head": jax.random.normal(key, (cfg.hidden_size, cfg.vocab_size), jnp.float32) * 0.02,
    }

    memory_cache = MemoryCache(2 << 30)
    backend = TransformerBackend(
        family, cfg, params,
        first_block=0, n_blocks=N_BLOCKS,
        memory_cache=memory_cache, compute_dtype=dtype,
    )
    handler = TransformerHandler(
        backend, dht_prefix="bench", memory_cache=memory_cache, batching=False,
        server_gen_params=client_params,
    )
    server = RpcServer()
    handler.register(server)
    await server.start()
    client = await RpcClient.connect("127.0.0.1", server.port)
    uids = CHAIN_DELIMITER.join(make_uid("bench", i) for i in range(N_BLOCKS))

    rng = np.random.RandomState(0)
    prefill = rng.randn(1, PREFILL_TOKENS, cfg.hidden_size).astype(np.float32) * 0.02
    tok_hidden = rng.randn(1, 1, cfg.hidden_size).astype(np.float32) * 0.02

    try:
        stream = await client.open_stream("ptu.inference")
        await stream.send({
            "uids": uids,
            "max_length": PREFILL_TOKENS + gen_chunk * (chunks + 2) + 8,
            "batch_size": 1,
        })
        await stream.recv(timeout=120)

        # prefill + first chunk (compiles the gen program)
        t0 = time.perf_counter()
        await stream.send({
            "tensors": {"hidden": serialize_array(prefill)}, "gen_tokens": gen_chunk,
        })
        reply = await stream.recv(timeout=900)
        warm_s = time.perf_counter() - t0
        assert len(reply["tokens"]) == gen_chunk, reply

        chunk_times = []
        total_tokens = 0
        for _ in range(chunks):
            t0 = time.perf_counter()
            await stream.send({
                "tensors": {"hidden": serialize_array(tok_hidden)},
                "gen_tokens": gen_chunk,
            })
            reply = await stream.recv(timeout=600)
            chunk_times.append(time.perf_counter() - t0)
            total_tokens += len(reply["tokens"])
        await stream.end()
    finally:
        await client.close()
        await server.stop()
        handler.shutdown()

    p50_chunk = statistics.median(chunk_times)
    tok_s = gen_chunk / p50_chunk
    result = {
        "label": "e2e_server_gen",
        "n_blocks": N_BLOCKS,
        "gen_chunk": gen_chunk,
        "p50_chunk_ms": round(p50_chunk * 1e3, 1),
        "ms_per_token": round(p50_chunk / gen_chunk * 1e3, 2),
        "tok_s": round(tok_s, 2),
        "warmup_s": round(warm_s, 1),
        "param_init_s": round(init_s, 1),
        "tokens": total_tokens,
    }
    del params, backend, memory_cache, client_params
    gc.collect()
    return result


async def run_e2e_bench():
    import jax
    import jax.numpy as jnp

    from petals_tpu.data_structures import CHAIN_DELIMITER, make_uid
    from petals_tpu.models.registry import get_family
    from petals_tpu.rpc import RpcClient
    from petals_tpu.rpc.serialization import deserialize_array, serialize_array
    from petals_tpu.rpc.server import RpcServer
    from petals_tpu.server.backend import TransformerBackend
    from petals_tpu.server.handler import TransformerHandler
    from petals_tpu.server.memory_cache import MemoryCache

    cfg = llama7b_cfg()
    family = get_family("llama")
    dtype = jnp.bfloat16

    t0 = time.perf_counter()
    params = random_params(cfg, N_BLOCKS, dtype)
    load_s = time.perf_counter() - t0

    memory_cache = MemoryCache(2 << 30)
    backend = TransformerBackend(
        family, cfg, params,
        first_block=0, n_blocks=N_BLOCKS,
        memory_cache=memory_cache, compute_dtype=dtype,
    )
    # batching=False: this row is the SINGLE-STREAM latency headline, kept on
    # the classic private-cache path so it stays comparable across rounds;
    # the batched path has its own continuous_batching_e2e row
    handler = TransformerHandler(
        backend, dht_prefix="bench", memory_cache=memory_cache, batching=False
    )
    server = RpcServer()
    handler.register(server)
    await server.start()

    client = await RpcClient.connect("127.0.0.1", server.port)
    uids = CHAIN_DELIMITER.join(make_uid("bench", i) for i in range(N_BLOCKS))

    rng = np.random.RandomState(0)
    hidden_prefill = rng.randn(1, PREFILL_TOKENS, cfg.hidden_size).astype(np.float32) * 0.02
    step_hidden = rng.randn(1, 1, cfg.hidden_size).astype(np.float32) * 0.02

    stream = await client.open_stream("ptu.inference")
    await stream.send({"uids": uids, "max_length": MAX_LENGTH, "batch_size": 1})
    await stream.recv(timeout=120)

    t0 = time.perf_counter()
    await stream.send({"tensors": {"hidden": serialize_array(hidden_prefill)}})
    await stream.recv(timeout=600)
    prefill_s = time.perf_counter() - t0

    async def one_step():
        await stream.send({"tensors": {"hidden": serialize_array(step_hidden)}})
        reply = await stream.recv(timeout=600)
        return deserialize_array(reply["tensors"]["hidden"])

    for _ in range(WARMUP_STEPS):
        await one_step()

    step_times = []
    for _ in range(MEASURE_STEPS):
        t0 = time.perf_counter()
        await one_step()
        step_times.append(time.perf_counter() - t0)
    await stream.end()
    await client.close()
    await server.stop()
    handler.shutdown()

    p50 = statistics.median(step_times)
    mean = sum(step_times) / len(step_times)

    # Server-side compute rate without the per-step device->host sync.
    kd, vd = backend.cache_descriptors(1, MAX_LENGTH, 0, N_BLOCKS)
    kv = (kd.make_zeros(), vd.make_zeros())
    _, kv = backend.inference_step(hidden_prefill, kv, 0)

    out = None
    for i in range(3):
        out, kv = backend.inference_step(step_hidden, kv, PREFILL_TOKENS + i)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for i in range(MEASURE_STEPS):
        out, kv = backend.inference_step(step_hidden, kv, PREFILL_TOKENS + 3 + i)
    jax.block_until_ready(out)
    device_step = (time.perf_counter() - t0) / MEASURE_STEPS
    pos = PREFILL_TOKENS + 3 + MEASURE_STEPS

    # --- breakdown of the e2e-vs-bandwidth gap ---
    # (a) jitted graph only: pre-staged device args, no wrapper work
    span_params = backend.params_for(None)
    hidden_dev = jax.device_put(jnp.asarray(step_hidden, dtype))
    prompts_dev = jnp.zeros((N_BLOCKS, 1, 0, cfg.hidden_size), dtype)
    hypo_dev = jnp.zeros((1,), jnp.int32)
    k_stack, v_stack = kv
    for i in range(3):  # settle the trace for this arg signature
        out, k_stack, v_stack = backend._inference_step_fn(
            span_params, k_stack, v_stack, hidden_dev,
            np.int32(pos + i), np.int32(1), np.int32(pos + i + 1), prompts_dev, hypo_dev,
            with_prompts=False, with_hypo=False, padded=False,
        )
    jax.block_until_ready(out)
    pos += 3
    t0 = time.perf_counter()
    for i in range(MEASURE_STEPS):
        out, k_stack, v_stack = backend._inference_step_fn(
            span_params, k_stack, v_stack, hidden_dev,
            np.int32(pos + i), np.int32(1), np.int32(pos + i + 1), prompts_dev, hypo_dev,
            with_prompts=False, with_hypo=False, padded=False,
        )
    jax.block_until_ready(out)
    jit_step = (time.perf_counter() - t0) / MEASURE_STEPS
    kv = (k_stack, v_stack)

    # (b) bare matmul chain at the same shapes: the weight-streaming bound as
    # this chip actually achieves it for 7B-sized matmuls. NOTE: the q+k+v sum
    # assumes MHA (wq/wk/wv same output dim) — true for the 7B config this
    # bench hard-codes; a GQA config would need concatenation instead.
    # weights must ride as jit ARGUMENTS: a closure capture here embeds the
    # whole span (3.2 GB at 7B shapes) as XLA constants of the program
    @functools.partial(jax.jit, static_argnames=("n",))
    def chain(v, ws, n):
        def body(carry, xs):
            wq, wk, wv, wo, wg, wu, wd = xs
            a = carry @ wq + carry @ wk + carry @ wv  # every weight streamed
            carry = a @ wo
            b = (carry @ wg) * (carry @ wu)
            carry = b @ wd
            return carry * 1e-2, None

        carry = v
        for _ in range(n):
            carry, _ = jax.lax.scan(body, carry, ws)
        return carry

    chain_ws = tuple(span_params[nm] for nm in ("wq", "wk", "wv", "wo", "wg", "wu", "wd"))
    x1 = jax.device_put(jnp.asarray(step_hidden[:, 0], dtype))
    t_chain = {}
    for n in (1, 3):
        jax.block_until_ready(chain(x1, chain_ws, n=n))
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            o = chain(x1, chain_ws, n=n)
            jax.block_until_ready(o)
            best = min(best, time.perf_counter() - t0)
        t_chain[n] = best
    chain_step = max((t_chain[3] - t_chain[1]) / 2, 1e-9)

    result = {
        "tok_s": 1.0 / mean,
        "step_ms": mean * 1e3,
        "p50_step_ms": p50 * 1e3,
        "device_step_ms": device_step * 1e3,
        "jit_step_ms": jit_step * 1e3,  # jitted graph alone (device args)
        "matmul_chain_ms": chain_step * 1e3,  # bare weight-streaming bound
        "prefill_s": prefill_s,
        "param_init_s": load_s,
        "weight_gb": round(params_bytes(params) / 2**30, 2),
    }
    del params, backend, kv, out, memory_cache, span_params, k_stack, v_stack
    gc.collect()
    return result


async def run_continuous_batching_bench(concurrent=8, steps=20, prefill=32):
    """Aggregate decode throughput of N concurrent sessions vs the same N run
    serially, through the FULL stack (client -> RPC -> handler -> lane pool ->
    one coalesced device step). The reference never batches across requests
    (reference task_pool.py:35-36), so its aggregate == single-stream; the
    bar set for it was >=5x serial aggregate."""
    import jax.numpy as jnp

    from petals_tpu.data_structures import CHAIN_DELIMITER, make_uid
    from petals_tpu.models.registry import get_family
    from petals_tpu.rpc import RpcClient
    from petals_tpu.rpc.serialization import serialize_array
    from petals_tpu.rpc.server import RpcServer
    from petals_tpu.server.backend import TransformerBackend
    from petals_tpu.server.handler import TransformerHandler
    from petals_tpu.server.memory_cache import MemoryCache

    cfg = llama7b_cfg()
    family = get_family("llama")
    dtype = jnp.bfloat16
    params = random_params(cfg, N_BLOCKS, dtype)

    memory_cache = MemoryCache(4 << 30)
    backend = TransformerBackend(
        family, cfg, params,
        first_block=0, n_blocks=N_BLOCKS,
        memory_cache=memory_cache, compute_dtype=dtype,
    )
    handler = TransformerHandler(
        backend, dht_prefix="bench", memory_cache=memory_cache,
        batching=True, batch_lanes=concurrent, batch_max_length=MAX_LENGTH,
    )
    server = RpcServer()
    handler.register(server)
    await server.start()
    client = await RpcClient.connect("127.0.0.1", server.port)
    uids = CHAIN_DELIMITER.join(make_uid("bench", i) for i in range(N_BLOCKS))

    rng = np.random.RandomState(0)
    prefill_h = rng.randn(1, prefill, cfg.hidden_size).astype(np.float32) * 0.02
    step_h = rng.randn(1, 1, cfg.hidden_size).astype(np.float32) * 0.02

    async def drive(barrier=None):
        stream = await client.open_stream("ptu.inference")
        await stream.send({"uids": uids, "max_length": MAX_LENGTH, "batch_size": 1})
        await stream.recv(timeout=120)
        await stream.send({"tensors": {"hidden": serialize_array(prefill_h)}})
        await stream.recv(timeout=600)
        if barrier is not None:
            await barrier.wait()
        t0 = time.perf_counter()
        for _ in range(steps):
            await stream.send({"tensors": {"hidden": serialize_array(step_h)}})
            await stream.recv(timeout=600)
        elapsed = time.perf_counter() - t0
        await stream.end()
        return elapsed

    # warm both compiled programs (batched flush of 1 happens during serial)
    await drive()

    t0 = time.perf_counter()
    serial_elapsed = 0.0
    for _ in range(concurrent):
        serial_elapsed += await drive()
    serial_wall = time.perf_counter() - t0
    serial_tok_s = concurrent * steps / serial_elapsed

    barrier = asyncio.Event()
    tasks = [asyncio.create_task(drive(barrier)) for _ in range(concurrent)]
    await asyncio.sleep(0.05)
    barrier.set()
    t0 = time.perf_counter()
    await asyncio.gather(*tasks)
    conc_wall = time.perf_counter() - t0
    conc_tok_s = concurrent * steps / conc_wall

    stats = dict(handler.batcher.stats) if handler.batcher else {}
    await client.close()
    await server.stop()
    handler.shutdown()
    result = {
        "label": "continuous_batching_e2e",
        "concurrent": concurrent,
        "steps": steps,
        "serial_agg_tok_s": round(serial_tok_s, 1),
        "concurrent_agg_tok_s": round(conc_tok_s, 1),
        "speedup": round(conc_tok_s / serial_tok_s, 2),
        "serial_wall_s": round(serial_wall, 2),
        "concurrent_wall_s": round(conc_wall, 2),
        "batcher_stats": stats,
    }
    del params, backend, memory_cache
    gc.collect()
    return result


async def run_prefix_cache_bench(prefill=512, *, cfg=None, n_blocks=None):
    """Time-to-first-token with a shared prompt prefix: two sessions send the
    SAME prefill; the second must hit the content-addressed prefix cache
    (server/prefix_cache.py) and skip its prefill compute. The reference
    recomputes every prompt, so its ratio is ~1.0 by construction."""
    import jax.numpy as jnp

    from petals_tpu.data_structures import CHAIN_DELIMITER, make_uid
    from petals_tpu.models.registry import get_family
    from petals_tpu.rpc import RpcClient
    from petals_tpu.rpc.serialization import serialize_array
    from petals_tpu.rpc.server import RpcServer
    from petals_tpu.server.backend import TransformerBackend
    from petals_tpu.server.handler import TransformerHandler
    from petals_tpu.server.memory_cache import MemoryCache

    cfg = cfg or llama7b_cfg(n_blocks or N_BLOCKS)
    n = cfg.num_hidden_layers
    family = get_family("llama")
    dtype = jnp.bfloat16
    params = random_params(cfg, n, dtype)
    memory_cache = MemoryCache(4 << 30)
    backend = TransformerBackend(
        family, cfg, params, first_block=0, n_blocks=n,
        memory_cache=memory_cache, compute_dtype=dtype,
    )
    handler = TransformerHandler(
        backend, dht_prefix="bench", memory_cache=memory_cache, batching=False,
    )
    server = RpcServer()
    handler.register(server)
    await server.start()
    client = await RpcClient.connect("127.0.0.1", server.port)
    uids = CHAIN_DELIMITER.join(make_uid("bench", i) for i in range(n))
    rng = np.random.RandomState(0)
    prefill_h = rng.randn(1, prefill, cfg.hidden_size).astype(np.float32) * 0.02
    try:
        async def one_prefill():
            stream = await client.open_stream("ptu.inference")
            await stream.send({"uids": uids, "max_length": prefill + 32, "batch_size": 1})
            await stream.recv(timeout=300)
            t0 = time.perf_counter()
            await stream.send({"tensors": {"hidden": serialize_array(prefill_h)}})
            await stream.recv(timeout=600)
            elapsed = time.perf_counter() - t0
            await stream.end()
            return elapsed

        async def wait_stored():
            for _ in range(100):  # stores run off the reply path
                if handler.prefix_cache.summary()["segments"] > 0:
                    return
                await asyncio.sleep(0.1)
            # fail LOUD: a silent timeout here would fake the miss/hit split
            raise RuntimeError("prefix store did not land within 10s")

        t_warm = await one_prefill()  # compile
        await wait_stored()  # let the warm store LAND before clearing, or it
        handler.prefix_cache.clear()  # would repopulate and fake the miss
        t_miss = await one_prefill()  # stores segments (asynchronously)
        await wait_stored()
        t_hit = await one_prefill()  # seeds from cache, computes only the tail
        stats = handler.prefix_cache.summary()
    finally:
        await client.close()
        await server.stop()
        handler.shutdown()
    result = {
        "label": "prefix_cache_ttft",
        "prefill_tokens": prefill,
        "miss_prefill_ms": round(t_miss * 1e3, 1),
        "hit_prefill_ms": round(t_hit * 1e3, 1),
        "speedup": round(t_miss / max(t_hit, 1e-9), 2),
        "hit_tokens": stats.get("hit_tokens", 0),
    }
    del params, backend, memory_cache
    gc.collect()
    return result


def llama405b_span_cfg(n_blocks=1):
    """405B-shaped span: the real per-hop activation and per-block weight
    sizes of the north star (shape constants live in rehearsal_405b)."""
    from benchmarks.rehearsal_405b import llama405b_cfg

    return llama405b_cfg(n_layers=n_blocks)


async def run_chain_hop_bench(cfg=None, *, quant="int4", steps=15, prefill=16,
                              max_length=64):
    """Measured 405B-chain feasibility: TWO span servers in this process
    (sharing its chip), each serving 405B-SHAPED quantized
    blocks, chained through the REAL stack — client -> server A -> reply +
    rpc_push -> server B -> reply — measuring what the rehearsal previously
    assumed: per-hop serialize/transfer/deserialize at hidden=16384 and the
    per-token chain overhead beyond device compute. The resulting
    hop_software_ms feeds rehearsal_405b's projection as a same-round
    measured input (plus an assumed DCN wire latency, reported separately)."""
    import jax
    import jax.numpy as jnp

    from petals_tpu.data_structures import CHAIN_DELIMITER, make_uid
    from petals_tpu.models.registry import get_family
    from petals_tpu.rpc import RpcClient
    from petals_tpu.rpc.serialization import deserialize_array, serialize_array
    from petals_tpu.rpc.server import RpcServer
    from petals_tpu.server.backend import TransformerBackend
    from petals_tpu.server.handler import TransformerHandler
    from petals_tpu.server.memory_cache import MemoryCache

    cfg = cfg or llama405b_span_cfg()
    family = get_family("llama")
    dtype = jnp.bfloat16
    n = cfg.num_hidden_layers

    # ---- wire micro-costs at the real activation shape [1, 1, hidden] ----
    act = np.random.RandomState(0).randn(1, 1, cfg.hidden_size).astype(np.float32)
    t0 = time.perf_counter()
    reps = 50
    for _ in range(reps):
        wire = serialize_array(act)
    ser_ms = (time.perf_counter() - t0) / reps * 1e3
    t0 = time.perf_counter()
    for _ in range(reps):
        deserialize_array(wire)
    deser_ms = (time.perf_counter() - t0) / reps * 1e3
    wire_bytes = len(wire) if isinstance(wire, (bytes, bytearray)) else len(wire.get("data", b""))

    # ---- two span servers, chained; cleanup in finally: a mid-bench failure
    # must not leak servers/streams/params into the rest of the run ----
    servers, handlers, clients, backends = [], [], [], []
    streams = []
    try:
        t0 = time.perf_counter()
        for s in range(2):
            params = random_params(cfg, n, dtype, quant=quant)
            memcache = MemoryCache(4 << 30)
            backend = TransformerBackend(
                family, cfg, params, first_block=0, n_blocks=n,
                memory_cache=memcache, compute_dtype=dtype,
            )
            handler = TransformerHandler(
                backend, dht_prefix=f"span{s}", memory_cache=memcache, batching=False,
            )
            server = RpcServer()
            handler.register(server)
            await server.start()
            servers.append(server)
            handlers.append(handler)
            backends.append(backend)
            clients.append(await RpcClient.connect("127.0.0.1", server.port))
        init_s = time.perf_counter() - t0

        rng = np.random.RandomState(0)
        prefill_h = rng.randn(1, prefill, cfg.hidden_size).astype(np.float32) * 0.02
        step_h = rng.randn(1, 1, cfg.hidden_size).astype(np.float32) * 0.02

        uids = [CHAIN_DELIMITER.join(make_uid(f"span{s}", i) for i in range(n)) for s in range(2)]
        # B first (gets a session id A can push to), then A with push_to=B
        stream_b = await clients[1].open_stream("ptu.inference")
        streams.append(stream_b)
        await stream_b.send({
            "uids": uids[1], "max_length": max_length, "batch_size": 1,
            "session_id": "chain-bench-b",
        })
        await stream_b.recv(timeout=600)
        # push addresses are "host:port/peerhex" (PeerAddr.to_string); direct
        # dials ignore the peer id, so an ephemeral identity fills the slot
        from petals_tpu.dht.identity import Identity

        peer_hex = Identity.generate().peer_id.to_string()
        stream_a = await clients[0].open_stream("ptu.inference")
        streams.append(stream_a)
        await stream_a.send({
            "uids": uids[0], "max_length": max_length, "batch_size": 1,
            "push_to": {
                "addr": f"127.0.0.1:{servers[1].port}/{peer_hex}",
                "session_id": "chain-bench-b",
            },
        })
        await stream_a.recv(timeout=600)

        async def chain_token(hidden, step_id):
            """client -> A; A replies AND pushes to B; B's reply closes the token."""
            await stream_a.send({
                "tensors": {"hidden": serialize_array(hidden)}, "step_id": step_id,
            })
            reply_a = await stream_a.recv(timeout=600)
            reply_b = await stream_b.recv(timeout=600)
            return deserialize_array(reply_b["tensors"]["hidden"]), reply_a, reply_b

        out, _, _ = await chain_token(prefill_h, "p0")
        for i in range(3):  # warmup (compile both spans' decode)
            out, _, _ = await chain_token(step_h, f"w{i}")

        t0 = time.perf_counter()
        for i in range(steps):
            out, _, _ = await chain_token(step_h, f"s{i}")
        chain_step_ms = (time.perf_counter() - t0) / steps * 1e3

        # device-only step per span at the same position (cached executables)
        dev_ms = []
        for backend in backends:
            kd, vd = backend.cache_descriptors(1, max_length, 0, n)
            kv = (kd.make_zeros(), vd.make_zeros())
            _, kv = backend.inference_step(prefill_h, kv, 0)
            o = None
            for i in range(3):
                o, kv = backend.inference_step(step_h, kv, prefill + i)
            jax.block_until_ready(o)
            t0 = time.perf_counter()
            for i in range(10):
                o, kv = backend.inference_step(step_h, kv, prefill + 3 + i)
            jax.block_until_ready(o)
            dev_ms.append((time.perf_counter() - t0) / 10 * 1e3)
            del kv, o

    finally:
        import contextlib as _ctx

        for stream in streams:
            with _ctx.suppress(Exception):
                await stream.end()
        for c in clients:
            with _ctx.suppress(Exception):
                await c.close()
        for s in servers:
            with _ctx.suppress(Exception):
                await s.stop()
        for h in handlers:
            h.shutdown()

    device_total_ms = sum(dev_ms)
    # software cost of ONE hop (serialize + framing + loopback + queue +
    # deserialize), measured as the chain's per-token overhead over device
    # compute, split over the 2 hops (client->A and A->B-push). A difference
    # of two similar measurements is noise-limited: floor it at the directly
    # measured serialize + deserialize cost rather than reporting a confident 0.0
    hop_software_ms = max((chain_step_ms - device_total_ms) / 2, ser_ms + deser_ms)
    result = {
        "label": "chain_hop_405b_shapes",
        "hidden_size": cfg.hidden_size,
        "quant": quant or "bf16",
        "blocks_per_span": n,
        "serialize_ms": round(ser_ms, 3),
        "deserialize_ms": round(deser_ms, 3),
        "wire_bytes_per_activation": wire_bytes,
        "chain_step_ms": round(chain_step_ms, 3),
        "device_ms_per_span": [round(d, 3) for d in dev_ms],
        "hop_software_ms": round(hop_software_ms, 3),
        "chain_tok_s": round(1000.0 / chain_step_ms, 2),
        "param_init_s": round(init_s, 1),
    }
    del backends, handlers
    gc.collect()
    return result


def _heavy_row_registry():
    """name -> zero-arg callable for every on-chip row. Each runs in its OWN
    ``--row`` process: the chip belongs to one process at a time, and a fresh
    process is a fresh HBM heap for the next multi-GiB row."""
    return {
        "e2e_8xllama7b": lambda: {
            k: round(v, 3) for k, v in asyncio.run(run_e2e_bench()).items()
        },
        "decode_70b_bf16": lambda: bench_device_decode(
            llama70b_cfg(6), label="decode_70b_bf16"),
        "decode_70b_nf4": lambda: bench_device_decode(
            llama70b_cfg(10), quant="nf4", label="decode_70b_nf4"),
        "decode_70b_nf4a": lambda: bench_device_decode(
            llama70b_cfg(10), quant="nf4a", label="decode_70b_nf4a"),
        "decode_70b_int4": lambda: bench_device_decode(
            llama70b_cfg(10), quant="int4", label="decode_70b_int4"),
        "decode_70b_nf4a_o": lambda: bench_device_decode(
            llama70b_cfg(10), quant="nf4a+o", label="decode_70b_nf4a_o"),
        "prefill_8k_flash": lambda: bench_flash_prefill(llama70b_cfg(2), 8192),
        "decode_7b_batched": lambda: bench_batched_decode(llama7b_cfg()),
        "continuous_batching_e2e": lambda: asyncio.run(
            run_continuous_batching_bench()),
        "prefix_cache_ttft": lambda: asyncio.run(run_prefix_cache_bench()),
        "chain_hop_405b_shapes": lambda: asyncio.run(run_chain_hop_bench()),
        "e2e_server_gen": lambda: asyncio.run(run_server_gen_bench()),
        "e2e_server_gen_sampling": lambda: __import__(
            "benchmarks.bench_server_gen_sampling", fromlist=["run_bench"]
        ).run_bench(),
        "e2e_paged_decode": lambda: __import__(
            "benchmarks.bench_paged_decode", fromlist=["run_bench"]
        ).run_bench(),
        "e2e_spec_decode": lambda: __import__(
            "benchmarks.bench_spec_decode", fromlist=["run_bench"]
        ).run_bench(),
        "e2e_mixed_prefill_decode": lambda: __import__(
            "benchmarks.bench_mixed_prefill_decode", fromlist=["run_bench"]
        ).run_bench(),
        "e2e_preemption_oversubscription": lambda: __import__(
            "benchmarks.bench_preemption", fromlist=["run_bench"]
        ).run_bench(),
        "e2e_kv_quant_capacity": lambda: __import__(
            "benchmarks.bench_kv_quant_capacity", fromlist=["run_bench"]
        ).run_bench(),
        "e2e_radix_prefix_tree": lambda: __import__(
            "benchmarks.bench_radix_prefix", fromlist=["run_bench"]
        ).run_bench(),
        "quant_quality": lambda: __import__(
            "benchmarks.quant_quality", fromlist=["quality_report"]
        ).quality_report(include_model_tier=False),
        "moe_prefill_2048": bench_moe_dispatch,
    }


def _tiny_gate_cfg():
    """A deliberately tiny Llama shape: the gate rows measure the BATCHING
    MACHINERY (queue -> flush loop -> jitted step), not the matmuls, so they
    must run in seconds on a CI CPU."""
    from petals_tpu.models.llama.config import LlamaBlockConfig

    return LlamaBlockConfig(
        hidden_size=64,
        num_attention_heads=4,
        num_key_value_heads=4,
        head_dim=16,
        intermediate_size=128,
        num_hidden_layers=2,
        rms_norm_eps=1e-5,
        vocab_size=128,
    )


def bench_gate_decode(page_size, label, *, lanes=2, steps=40):
    """CPU-runnable gate row: drive ``steps`` batched decode ticks through a
    real DecodeBatcher (dense pool when ``page_size`` is None, paged
    otherwise) so the STEP_DENSE / STEP_PAGED / STEP_MIXED histograms and the
    batcher counters carry this build's scheduling cost. The attached
    telemetry blob is what ``--gate`` diffs against the committed baseline."""
    import jax.numpy as jnp

    from petals_tpu.models.registry import get_family
    from petals_tpu.server.backend import TransformerBackend
    from petals_tpu.server.batching import DecodeBatcher
    from petals_tpu.server.memory_cache import MemoryCache
    from petals_tpu.server.task_queue import PriorityTaskQueue

    cfg = _tiny_gate_cfg()
    n_blocks = cfg.num_hidden_layers
    params = random_params(cfg, n_blocks, jnp.float32)
    backend = TransformerBackend(
        get_family("llama"), cfg, params,
        first_block=0, n_blocks=n_blocks,
        memory_cache=MemoryCache(None), compute_dtype=jnp.float32,
        use_flash=False,
    )
    rng = np.random.RandomState(0)
    prefill = rng.randn(1, 8, cfg.hidden_size).astype(np.float32) * 0.02
    step_h = rng.randn(1, 1, cfg.hidden_size).astype(np.float32) * 0.02

    async def run():
        queue = PriorityTaskQueue()
        queue.start()
        batcher = DecodeBatcher(
            backend, backend.memory_cache, queue,
            n_lanes=lanes, max_length=128, page_size=page_size,
        )
        try:
            # distinct peer ids per lane so the resource ledger attributes
            # page-seconds per tenant — the conservation check below is what
            # makes metering regressions fail ``--gate``
            lane_ids = [
                await batcher.acquire_lane(peer_id=f"{label}-peer-{i}")
                for i in range(lanes)
            ]
            pos = 0
            if page_size:  # paged pool: prefill rides the mixed step
                for lane in lane_ids:
                    await batcher.prefill_lane(lane, prefill, 0)
                pos = prefill.shape[1]
            # a couple of warmup ticks so jit compilation stays out of the
            # measured histogram tail (the gate compares means, but cheap
            # insurance against a CI cold-start owning the blob)
            for _ in range(3):
                await asyncio.gather(
                    *(batcher.step(lane, step_h, pos) for lane in lane_ids)
                )
                pos += 1
            t0 = time.perf_counter()
            for _ in range(steps):
                await asyncio.gather(
                    *(batcher.step(lane, step_h, pos) for lane in lane_ids)
                )
                pos += 1
            wall = time.perf_counter() - t0
            # achieved-vs-roofline utilization: program flops (XLA
            # cost_analysis via the observatory) over the measured mean step
            # time. On CPU these are ESTIMATES — utilization stays null
            # unless PETALS_TPU_PEAK_TFLOPS declares a real peak (on-chip).
            from petals_tpu.telemetry.observatory import get_observatory

            step_fn = "paged_decode" if page_size else "batched_decode"
            roofline = get_observatory().roofline(step_fn, wall / steps)
            # attribution conservation: per-session page-seconds (plus the
            # unattributed remainder) must equal the pool occupancy integral.
            # A metering regression here fails the row, and therefore --gate.
            from petals_tpu.telemetry.ledger import get_ledger

            ledger = get_ledger()
            snap = ledger.snapshot(k=lanes)
            if page_size:
                attributed = ledger.attributed_page_seconds()
                pool_s = snap["pool_page_seconds"]
                drift = abs(attributed + snap["unattributed_page_seconds"] - pool_s)
                assert drift <= 0.05 * pool_s + 1e-3, (
                    f"ledger attribution leak: attributed={attributed:.6f} "
                    f"unattributed={snap['unattributed_page_seconds']:.6f} "
                    f"pool={pool_s:.6f}"
                )
            # token conservation holds on BOTH pools: every decode tick bills
            # exactly one token per lane (3 warmup ticks included)
            billed = sum(
                t.get("decode_tokens", 0)
                for peer, t in ledger.peer_totals().items()
                if peer.startswith(f"{label}-peer-")
            )
            assert billed == lanes * (steps + 3), (
                f"ledger token leak: billed {billed}, ran {lanes * (steps + 3)}"
            )
            return {
                "label": label,
                "lanes": lanes,
                "steps": steps,
                "wall_s": round(wall, 3),
                "step_ms": round(1000.0 * wall / steps, 3),
                "roofline": roofline,
                "ledger": _ledger_blob(),
            }
        finally:
            await batcher.close()
            queue.shutdown()

    result = asyncio.run(run())
    del params, backend
    gc.collect()
    return result


def bench_gate_fingerprint(label, *, lanes=2, steps=40):
    """CPU-runnable gate row for the integrity fingerprint plane: the same
    tiny-config batched decode as gate_decode_dense run fp-OFF then fp-ON
    (ops/fingerprint.py — one FP_DIM projection fused into the batched step
    plus a per-tick host copy of the digest), in ONE row so the overhead is
    a same-process A/B. ``with_fp`` is a static argname, so BOTH compiled
    variants must warm up inside the observatory's warmup budget — a
    compile during the measured phases lands in ``compile_anomalies`` and
    fails ``--gate`` via the baseline's clean failure counters. The <=2%
    overhead budget is an ON-CHIP bar (not measured on the current chip: run
    this row there); CPU walls at hidden=64 are
    scheduler-noise-dominated, so the in-row assertion is a loose
    structural ceiling, not the 2% bar."""
    import jax.numpy as jnp

    from petals_tpu.models.registry import get_family
    from petals_tpu.ops import fingerprint as fp_ops
    from petals_tpu.server.backend import TransformerBackend
    from petals_tpu.server.batching import DecodeBatcher
    from petals_tpu.server.memory_cache import MemoryCache
    from petals_tpu.server.task_queue import PriorityTaskQueue

    cfg = _tiny_gate_cfg()
    n_blocks = cfg.num_hidden_layers
    params = random_params(cfg, n_blocks, jnp.float32)
    backend = TransformerBackend(
        get_family("llama"), cfg, params,
        first_block=0, n_blocks=n_blocks,
        memory_cache=MemoryCache(None), compute_dtype=jnp.float32,
        use_flash=False,
    )
    rng = np.random.RandomState(0)
    step_h = rng.randn(1, 1, cfg.hidden_size).astype(np.float32) * 0.02

    async def run():
        queue = PriorityTaskQueue()
        queue.start()
        batcher = DecodeBatcher(
            backend, backend.memory_cache, queue,
            n_lanes=lanes, max_length=128, page_size=None,
        )
        try:
            lane_ids = [
                await batcher.acquire_lane(peer_id=f"{label}-peer-{i}")
                for i in range(lanes)
            ]
            pos = 0

            async def tick(n):
                nonlocal pos
                t0 = time.perf_counter()
                for _ in range(n):
                    await asyncio.gather(
                        *(batcher.step(lane, step_h, pos) for lane in lane_ids)
                    )
                    pos += 1
                return time.perf_counter() - t0

            # warm BOTH static variants while the steady-state executable
            # set is still open (observatory warmup budget, default 8
            # calls): compiling the second variant after the budget would
            # — correctly — count as a recompile anomaly
            fp_ops.set_enabled(False)
            await tick(2)
            fp_ops.set_enabled(True)
            await tick(2)
            fp = batcher.pop_step_fp(lane_ids[0])
            assert fp is not None and len(fp) == fp_ops.FP_DIM, (
                f"fp-on step produced no fused fingerprint: {fp!r}"
            )

            fp_ops.set_enabled(False)
            wall_off = await tick(steps)
            fp_ops.set_enabled(True)
            wall_on = await tick(steps)

            overhead_pct = 100.0 * (wall_on - wall_off) / max(wall_off, 1e-9)
            # structural ceiling only: catches a per-tick recompile or an
            # accidentally O(hidden^2) digest, not single-digit CPU jitter
            assert wall_on <= wall_off * 2.0 + 0.25, (
                f"fingerprinting doubled the decode step: "
                f"off={wall_off:.3f}s on={wall_on:.3f}s ({overhead_pct:.1f}%)"
            )
            return {
                "label": label,
                "lanes": lanes,
                "steps": steps,
                "fp_dim": fp_ops.FP_DIM,
                "off_step_ms": round(1000.0 * wall_off / steps, 3),
                "on_step_ms": round(1000.0 * wall_on / steps, 3),
                "overhead_pct": round(overhead_pct, 2),
                "overhead_budget_pct_onchip": 2.0,
            }
        finally:
            await batcher.close()
            queue.shutdown()

    prev = fp_ops.enabled()
    try:
        result = asyncio.run(run())
    finally:
        fp_ops.set_enabled(prev)
    del params, backend
    gc.collect()
    return result


def bench_gate_paged_kernel(label, *, lanes=2, steps=12):
    """CPU-runnable gate row for the fused paged-attention path: the
    production ``paged_decode_step`` driven directly (no batcher — this row
    measures the DISPATCH, not the flush loop) under both forced paths of
    PETALS_TPU_PAGED_KERNEL on a PERMUTED table layout, in ONE row so the
    A/B is same-process. ``kernel_path`` rides the step as a static argname,
    so BOTH compiled variants must warm up inside the observatory's warmup
    budget — a flip-triggered recompile during the measured phases would
    land in ``compile_anomalies``, which this row additionally asserts stays
    ZERO across the measured ticks (the env flip is a retrace to an
    already-warm executable, never a steady-state recompile). The pallas arm
    runs in INTERPRET mode on CPU, so the per-arm walls are structural, not
    decision-grade — the on-chip verdict comes from the autotune and
    benchmarks/ablate_paged_attention.py run on the chip."""
    import jax.numpy as jnp

    from petals_tpu.models.registry import get_family
    from petals_tpu.server.backend import TransformerBackend
    from petals_tpu.server.memory_cache import MemoryCache
    from petals_tpu.telemetry import instruments as tm

    cfg = _tiny_gate_cfg()
    n_blocks = cfg.num_hidden_layers
    params = random_params(cfg, n_blocks, jnp.float32)
    backend = TransformerBackend(
        get_family("llama"), cfg, params,
        first_block=0, n_blocks=n_blocks,
        memory_cache=MemoryCache(None), compute_dtype=jnp.float32,
        use_flash=False,
    )
    rng = np.random.RandomState(0)
    PS, MAX_PAGES = 16, 4
    n_pages = lanes * MAX_PAGES + 2  # oversubscribed: permutation has slack
    hkv, hd = cfg.num_key_value_heads, cfg.head_dim
    # permuted tables: the layout where the XLA arm pays a real page gather
    tables = rng.permutation(n_pages)[: lanes * MAX_PAGES].astype(np.int32)
    tables = tables.reshape(lanes, MAX_PAGES)
    kp = jnp.asarray(rng.randn(n_blocks, n_pages, PS, hkv, hd).astype(np.float32) * 0.02)
    vp = jnp.asarray(rng.randn(n_blocks, n_pages, PS, hkv, hd).astype(np.float32) * 0.02)
    kp_host, vp_host = np.asarray(kp), np.asarray(vp)
    step_h = rng.randn(lanes, 1, cfg.hidden_size).astype(np.float32) * 0.02
    pos = PS  # one resident page of (random) history per lane

    env_prev = os.environ.get("PETALS_TPU_PAGED_KERNEL")

    def tick(n, pools):
        nonlocal pos
        t0 = time.perf_counter()
        for _ in range(n):
            out, pools = backend.paged_decode_step(
                step_h, pools, np.full(lanes, pos, np.int32), tables
            )
            pos += 1
        return time.perf_counter() - t0, out, pools

    try:
        # warm BOTH static kernel_path variants while the steady-state
        # executable set is still open (observatory warmup budget)
        os.environ["PETALS_TPU_PAGED_KERNEL"] = "xla"
        _, _, pools = tick(1, (kp, vp))
        os.environ["PETALS_TPU_PAGED_KERNEL"] = "pallas"
        _, _, pools = tick(1, pools)

        # path parity on identical inputs: the two compiled variants must
        # agree (the kernel-vs-reference exactness lane proper is -m kernel)
        parity = {}
        for mode in ("xla", "pallas"):
            os.environ["PETALS_TPU_PAGED_KERNEL"] = mode
            p = pos
            _, out, _ = tick(1, (jnp.asarray(kp_host), jnp.asarray(vp_host)))
            pos = p  # same position for both arms
            parity[mode] = np.asarray(out)
        pos += 1
        np.testing.assert_allclose(
            parity["pallas"], parity["xla"], atol=1e-4, rtol=0,
            err_msg="paged kernel path diverged from the XLA path",
        )

        anomalies_before = sum(
            c.value for _v, c in tm.COMPILE_ANOMALIES.children()
        )
        os.environ["PETALS_TPU_PAGED_KERNEL"] = "xla"
        wall_xla, _, pools = tick(steps, pools)
        os.environ["PETALS_TPU_PAGED_KERNEL"] = "pallas"
        wall_pallas, _, pools = tick(steps, pools)
        anomalies = sum(
            c.value for _v, c in tm.COMPILE_ANOMALIES.children()
        ) - anomalies_before
        assert anomalies == 0, (
            f"paged kernel A/B caused {anomalies} post-warmup recompile "
            f"anomalies — the env flip must resolve to already-warm "
            f"executables"
        )
        import jax

        return {
            "label": label,
            "lanes": lanes,
            "steps": steps,
            "layout": "permuted",
            "xla_step_ms": round(1000.0 * wall_xla / steps, 3),
            "pallas_step_ms": round(1000.0 * wall_pallas / steps, 3),
            "pallas_interpret": jax.default_backend() != "tpu",
            "post_warmup_compile_anomalies": anomalies,
        }
    finally:
        if env_prev is None:
            os.environ.pop("PETALS_TPU_PAGED_KERNEL", None)
        else:
            os.environ["PETALS_TPU_PAGED_KERNEL"] = env_prev
        del params, backend
        gc.collect()


def bench_gate_spec_decode(label, *, lanes=2, tokens=24, spec_k=4):
    """CPU-runnable gate row for the speculative decode path: a cooperative
    draft (the span's own tiny fp32 weights, window covering the whole
    context) drives full pooled generations and the row asserts the three
    invariants speculation must never lose — (a) the emitted stream is
    bit-identical to plain decode, greedy AND fixed-seed sampling alike,
    (b) zero post-warmup compile anomalies across draft propose + verify,
    (c) the ledger bills exactly one decode token per emitted token. The
    telemetry blob pins the ``spec`` step_duration variant and the
    spec_proposed/spec_accepted counters into the committed baseline, so a
    build that silently stops speculating (or starts recompiling) fails
    ``--gate``."""
    import jax
    import jax.numpy as jnp

    from petals_tpu.models.registry import get_family
    from petals_tpu.server.backend import TransformerBackend
    from petals_tpu.server.batching import DecodeBatcher
    from petals_tpu.server.memory_cache import MemoryCache
    from petals_tpu.server.spec_decode import DraftModel
    from petals_tpu.server.task_queue import PriorityTaskQueue
    from petals_tpu.telemetry import instruments as tm
    from petals_tpu.telemetry.ledger import get_ledger

    cfg = _tiny_gate_cfg()
    family = get_family("llama")
    n_blocks = cfg.num_hidden_layers
    params = random_params(cfg, n_blocks, jnp.float32)
    # the draft unrolls per-block (LIST layout); the span scans the stack
    blocks = [
        {name: leaf[i] for name, leaf in params.items()} for i in range(n_blocks)
    ]
    key = jax.random.PRNGKey(7)
    client_params = {
        "embed": jax.random.normal(
            key, (cfg.vocab_size, cfg.hidden_size), jnp.float32) * 0.02,
        "norm": jnp.ones((cfg.hidden_size,), jnp.float32),
        "head": jax.random.normal(
            key, (cfg.hidden_size, cfg.vocab_size), jnp.float32) * 0.02,
    }
    backend = TransformerBackend(
        family, cfg, params,
        first_block=0, n_blocks=n_blocks,
        memory_cache=MemoryCache(None), compute_dtype=jnp.float32,
        use_flash=False,
    )
    draft = DraftModel(
        family, cfg, blocks, client_params,
        spec_k=spec_k, window=48, compute_dtype=jnp.float32,
    )
    rng = np.random.RandomState(5)
    contexts = [
        [int(t) for t in rng.randint(0, cfg.vocab_size, 6)] for _ in range(lanes)
    ]
    # lane 0 greedy, lane 1 fixed-seed sampled: parity must hold for both
    samplings = [{"context": ctx} for ctx in contexts]
    if lanes > 1:
        samplings[1] = {
            "do_sample": True, "temperature": 0.8, "top_k": 10,
            "seed": 1234, "offset": 0, "context": contexts[1],
        }

    async def run():
        queue = PriorityTaskQueue()
        queue.start()
        batcher = DecodeBatcher(
            backend, backend.memory_cache, queue,
            n_lanes=lanes, max_length=64, page_size=8,
            gen_params=client_params, draft_model=draft, spec_k=spec_k,
        )

        async def one(i, peer_prefix):
            hidden = np.asarray(family.client_embed(
                client_params, np.asarray([contexts[i]], np.int32), cfg
            ), np.float32)
            lane = await batcher.acquire_lane(
                timeout=120, peer_id=f"{peer_prefix}-{i}"
            )
            try:
                out = await batcher.prefill_lane(lane, hidden, 0)
                toks = await batcher.generate_lane(
                    lane, np.asarray(out[:, -1:]), len(contexts[i]),
                    tokens, samplings[i],
                )
            finally:
                batcher.release_lane(lane)
            return np.asarray(toks)

        async def gen_all(peer_prefix):
            return await asyncio.gather(
                *(one(i, peer_prefix) for i in range(lanes))
            )

        try:
            s0 = dict(batcher.stats)
            spec_streams = await gen_all(f"{label}-warm")  # compiles
            batcher.draft = None
            plain_streams = await gen_all(f"{label}-plain")
            batcher.draft = draft
            for s, p in zip(spec_streams, plain_streams):
                np.testing.assert_array_equal(
                    s, p, err_msg="spec stream diverged from plain decode"
                )
            anomalies_before = sum(
                c.value for _v, c in tm.COMPILE_ANOMALIES.children()
            )
            t0 = time.perf_counter()
            timed_streams = await gen_all(f"{label}-peer")
            wall = time.perf_counter() - t0
            for s, p in zip(timed_streams, plain_streams):
                np.testing.assert_array_equal(
                    s, p, err_msg="post-warmup spec stream diverged"
                )
            anomalies = sum(
                c.value for _v, c in tm.COMPILE_ANOMALIES.children()
            ) - anomalies_before
            assert anomalies == 0, (
                f"speculative decode caused {anomalies} post-warmup "
                f"recompile anomalies — draft propose / verify must resolve "
                f"to already-warm executables"
            )
            sd = {k: batcher.stats[k] - s0[k] for k in batcher.stats}
            assert sd["spec_steps"] > 0 and sd["spec_proposed"] > 0, sd
            # one decode token billed per emitted token, across all three
            # generation rounds (spec and plain alike)
            ledger = get_ledger()
            billed = sum(
                t.get("decode_tokens", 0)
                for peer, t in ledger.peer_totals().items()
                if peer.startswith(f"{label}-")
            )
            assert billed == 3 * lanes * (tokens - 1), (
                f"ledger token leak: billed {billed}, "
                f"emitted {3 * lanes * (tokens - 1)}"
            )
            return {
                "label": label,
                "lanes": lanes,
                "tokens": tokens,
                "spec_k": spec_k,
                "wall_s": round(wall, 3),
                "tok_s": round(lanes * tokens / wall, 2),
                "spec_steps": sd["spec_steps"],
                "acceptance_rate": round(
                    sd["spec_accepted"] / max(sd["spec_proposed"], 1), 4
                ),
                "post_warmup_compile_anomalies": anomalies,
                "ledger": _ledger_blob(),
            }
        finally:
            await batcher.close()
            queue.shutdown()

    result = asyncio.run(run())
    del params, backend, draft
    gc.collect()
    return result


def bench_gate_kv_quant(label, *, lanes=2, steps=24):
    """CPU-runnable gate row for the quantized paged KV pool: the acceptance
    geometry (head_dim=128) run fp vs nf4a on real DecodeBatchers. Asserts
    the two deterministic claims — (a) at a FIXED cache byte budget the nf4a
    pool admits >=3.5x the sessions of the fp pool (both admission loops run
    the real 4-descriptor allocator, not arithmetic), and (b) decode over
    quantized pages causes ZERO post-warmup recompile anomalies. The fp/nf4a
    step walls ride the blob as structural numbers (CPU timing is not
    decision-grade; the throughput verdict is the e2e_kv_quant_capacity row
    on-chip), and the pinned steps_paged/compiles counters make a build that
    silently stops exercising the quantized path fail ``--gate``."""
    import jax.numpy as jnp

    from petals_tpu.models.llama.config import LlamaBlockConfig
    from petals_tpu.models.registry import get_family
    from petals_tpu.server.backend import TransformerBackend
    from petals_tpu.server.batching import DecodeBatcher
    from petals_tpu.server.memory_cache import AllocationFailed, MemoryCache
    from petals_tpu.server.task_queue import PriorityTaskQueue
    from petals_tpu.telemetry import instruments as tm

    # head_dim=128 is the geometry the capacity claim is calibrated on: the
    # nf4a wire row (d/2 codes + 4 scale bytes) clears 3.5x only once the
    # fp16/bf16 row is 2*d bytes wide
    cfg = LlamaBlockConfig(
        hidden_size=256, num_attention_heads=2, num_key_value_heads=2,
        head_dim=128, intermediate_size=128, num_hidden_layers=2,
        rms_norm_eps=1e-5, vocab_size=128,
    )
    n_blocks = cfg.num_hidden_layers
    family = get_family("llama")
    params = random_params(cfg, n_blocks, jnp.float32)

    def make_backend(kind):
        return TransformerBackend(
            family, cfg, params,
            first_block=0, n_blocks=n_blocks,
            memory_cache=MemoryCache(None), compute_dtype=jnp.float32,
            use_flash=False, kv_quant_type=kind,
        )

    backend_fp = make_backend("none")
    backend_q = make_backend("nf4a")
    fp_token = backend_fp.cache_bytes_per_token()
    q_token = backend_q.kv_bytes_per_token()
    assert fp_token / q_token >= 3.5, (
        f"nf4a pool must be >=3.5x denser than fp per token: "
        f"fp={fp_token}B quant={q_token}B"
    )

    PS = 16  # sessions hold one page each, so pages are the binding budget
    budget = 48 * fp_token * PS  # what 48 fp pages cost
    pages = {"fp": budget // (fp_token * PS), "quant": budget // (q_token * PS)}

    rng = np.random.RandomState(0)
    step_h = rng.randn(1, 1, cfg.hidden_size).astype(np.float32) * 0.02

    async def run():
        queue = PriorityTaskQueue()
        queue.start()
        try:
            async def admitted(backend, n_pages):
                # real allocator admission at the shared byte budget: one
                # page of live context per session, lane pool sized so pages
                # (not lanes) push back
                batcher = DecodeBatcher(
                    backend, backend.memory_cache, queue,
                    n_lanes=int(n_pages) + 2, max_length=4 * PS,
                    page_size=PS, n_pages=int(n_pages),
                )
                sessions = []
                try:
                    while True:
                        try:
                            lane = await batcher.acquire_lane(timeout=0.5)
                        except (AllocationFailed, asyncio.TimeoutError):
                            break
                        try:
                            await batcher.prepare_write(lane, 0, PS, timeout=0.5)
                        except (AllocationFailed, asyncio.TimeoutError):
                            batcher.release_lane(lane)
                            break
                        sessions.append(lane)
                    return len(sessions)
                finally:
                    for lane in sessions:
                        batcher.release_lane(lane)
                    await batcher.close()

            sessions_fp = await admitted(backend_fp, pages["fp"])
            sessions_q = await admitted(backend_q, pages["quant"])
            assert sessions_q >= 3.5 * sessions_fp, (
                f"fixed-budget admission: nf4a admitted {sessions_q} vs fp "
                f"{sessions_fp} — expected >=3.5x"
            )

            async def timed_decode(backend):
                batcher = DecodeBatcher(
                    backend, backend.memory_cache, queue,
                    n_lanes=lanes, max_length=128, page_size=PS,
                )
                try:
                    lane = await batcher.acquire_lane(timeout=30)
                    pos = 0
                    for _ in range(3):  # warm both compile variants
                        await batcher.step(lane, step_h, pos)
                        pos += 1
                    t0 = time.perf_counter()
                    for _ in range(steps):
                        await batcher.step(lane, step_h, pos)
                        pos += 1
                    wall = time.perf_counter() - t0
                    batcher.release_lane(lane)
                    return wall
                finally:
                    await batcher.close()

            wall_fp = await timed_decode(backend_fp)
            anomalies_before = sum(
                c.value for _v, c in tm.COMPILE_ANOMALIES.children()
            )
            wall_q = await timed_decode(backend_q)
            anomalies = sum(
                c.value for _v, c in tm.COMPILE_ANOMALIES.children()
            ) - anomalies_before
            assert anomalies == 0, (
                f"quantized-pool decode caused {anomalies} post-warmup "
                f"recompile anomalies — dequant rides inside the already-warm "
                f"paged step"
            )
            return {
                "label": label,
                "kv_quant": "nf4a",
                "bytes_per_token_fp": int(fp_token),
                "bytes_per_token_quant": int(q_token),
                "capacity_ratio": round(fp_token / q_token, 2),
                "sessions_fp": sessions_fp,
                "sessions_quant": sessions_q,
                "session_ratio": round(sessions_q / max(sessions_fp, 1), 2),
                "fp_step_ms": round(1000.0 * wall_fp / steps, 3),
                "quant_step_ms": round(1000.0 * wall_q / steps, 3),
                "post_warmup_compile_anomalies": anomalies,
            }
        finally:
            queue.shutdown()

    result = asyncio.run(run())
    del params, backend_fp, backend_q
    gc.collect()
    return result


def _gate_row_registry():
    """Rows cheap enough for the CI perf gate (seconds each on CPU). Run via
    the same ``--row`` child protocol as the heavy rows so each gets a fresh
    process and therefore clean per-row histograms."""
    return {
        "gate_decode_dense": lambda: bench_gate_decode(None, "gate_decode_dense"),
        "gate_decode_paged": lambda: bench_gate_decode(16, "gate_decode_paged"),
        "gate_fingerprint_overhead": lambda: bench_gate_fingerprint(
            "gate_fingerprint_overhead"
        ),
        "gate_paged_kernel": lambda: bench_gate_paged_kernel("gate_paged_kernel"),
        "gate_spec_decode": lambda: bench_gate_spec_decode("gate_spec_decode"),
        "gate_kv_quant": lambda: bench_gate_kv_quant("gate_kv_quant"),
        "gate_radix_cache": lambda: __import__(
            "benchmarks.bench_radix_prefix", fromlist=["gate_bench"]
        ).gate_bench("gate_radix_cache"),
        "gate_disagg_handoff": lambda: __import__(
            "benchmarks.bench_disagg", fromlist=["gate_bench"]
        ).gate_bench("gate_disagg_handoff"),
    }


def _telemetry_counters() -> dict:
    """Monotonic totals of the batcher-mirroring counters
    (telemetry.instruments); the per-row DELTA of these shows which compiled
    step variants a row actually exercised and at what volume."""
    from petals_tpu.telemetry import instruments as tm

    return {
        "steps_dense": tm.STEPS_DENSE.value,
        "steps_paged": tm.STEPS_PAGED.value,
        "steps_mixed": tm.STEPS_MIXED.value,
        "steps_gen": tm.STEPS_GEN.value,
        "steps_spec": tm.STEPS_SPEC.value,
        "spec_proposed": tm.SPEC_PROPOSED.value,
        "spec_accepted": tm.SPEC_ACCEPTED.value,
        "decode_tokens": tm.DECODE_TOKENS.value,
        "preemptions": tm.PREEMPTIONS.value,
        "alloc_failed": tm.ALLOC_FAILED.value,
        "swap_out_bytes": tm.SWAP_OUT_BYTES.value,
        "swap_in_bytes": tm.SWAP_IN_BYTES.value,
        # compiled-program observatory: total compilations across tracked
        # functions (the gate holds rows to the baseline's executable count)
        # and post-warmup steady-state recompiles (must stay zero)
        "compiles": sum(c.value for _v, c in tm.COMPILES.children()),
        "compile_anomalies": sum(
            c.value for _v, c in tm.COMPILE_ANOMALIES.children()
        ),
    }


def _ledger_blob() -> dict:
    """Ledger efficiency summary for a bench row: useful work per unit of
    HBM residency (tokens per page-second) and how evenly the row's tenants
    split the pool (per-peer share spread). Process-cumulative, like the
    step histograms — heavy rows run in fresh subprocesses."""
    from petals_tpu.telemetry.ledger import get_ledger

    ledger = get_ledger()
    snap = ledger.snapshot(k=5)
    totals = ledger.peer_totals()
    tokens = sum(
        t.get("prefill_tokens", 0) + t.get("decode_tokens", 0)
        for t in totals.values()
    )
    page_s = snap["pool_page_seconds"]
    shares = [t["share"] for t in snap["top"]]
    return {
        "page_s": page_s,
        "unattributed_page_s": snap["unattributed_page_seconds"],
        "tokens_billed": int(tokens),
        "tokens_per_page_s": round(tokens / page_s, 2) if page_s > 1e-9 else None,
        "share_spread": round(max(shares) - min(shares), 4) if shares else None,
        "peers": snap["peers"],
        "noisy_events": snap["noisy_events"],
    }


def _telemetry_blob(before: dict) -> dict:
    """Per-row telemetry attachment: counter deltas since ``before`` plus a
    step-duration histogram summary. Histograms are process-cumulative, so
    heavy rows (fresh subprocess each) see only their own steps; in-process
    rows see the run so far — the counters_delta is the per-row signal."""
    from petals_tpu.telemetry import instruments as tm

    after = _telemetry_counters()
    delta = {k: round(after[k] - before.get(k, 0), 3) for k in after}
    steps = {}
    for variant, child in (("dense", tm.STEP_DENSE), ("paged", tm.STEP_PAGED),
                           ("mixed", tm.STEP_MIXED), ("gen", tm.STEP_GEN),
                           ("spec", tm.STEP_SPEC)):
        snap = child.snapshot()
        if not snap["count"]:
            continue
        steps[variant] = {
            "count": snap["count"],
            "mean_ms": round(1000.0 * snap["sum"] / snap["count"], 3),
            "p50_ms": round(1000.0 * child.quantile(0.5), 3),
            "p99_ms": round(1000.0 * child.quantile(0.99), 3),
        }
    return {"counters_delta": delta, "step_duration": steps}


def _device_blob() -> dict:
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices),
    }


def _run_single_row(name: str, *, on_chip: bool = False) -> None:
    """--row child: run ONE registry row and print its JSON on the LAST
    stdout line (stderr streams through for progress). ``--on_chip`` (what the
    parent of a full run passes) aborts unless the backend is a TPU."""
    import jax

    from petals_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    if on_chip and jax.default_backend() != "tpu":
        sys.stderr.write(
            f"[bench] row {name}: JAX backend is {jax.default_backend()!r}, not a TPU; "
            f"an on-chip row never runs off the chip\n"
        )
        sys.exit(2)
    fn = {**_heavy_row_registry(), **_gate_row_registry()}[name]
    before = _telemetry_counters()
    result = fn()
    if isinstance(result, dict):
        result["telemetry"] = _telemetry_blob(before)
        result["device"] = _device_blob()
    print(json.dumps(result), flush=True)


def _run_gate(argv) -> None:
    """Perf-regression gate (CI lane): ``--gate BENCH_GATE_CPU.json`` re-runs
    every baseline row in a fresh ``--row`` subprocess (clean per-row
    histograms), diffs each row's telemetry blob against the committed
    baseline via telemetry.gate, and exits non-zero on regression.
    ``--gate_update BENCH_GATE_CPU.json`` rewrites the baseline from this
    build instead of diffing; ``--gate_tolerance X`` overrides the stored
    relative tolerance (current may be up to (1+X) times the baseline)."""
    import subprocess

    from petals_tpu.telemetry.gate import DEFAULT_TOLERANCE, gate_report

    update = "--gate_update" in argv
    flag = "--gate_update" if update else "--gate"
    try:
        path = argv[argv.index(flag) + 1]
    except IndexError:
        sys.stderr.write(f"[gate] {flag} requires a baseline path\n")
        sys.exit(2)
    tolerance = None
    if "--gate_tolerance" in argv:
        tolerance = float(argv[argv.index("--gate_tolerance") + 1])

    if update:
        row_names = sorted(_gate_row_registry())
        baseline = None
    else:
        try:
            with open(path, "r", encoding="utf-8") as f:
                baseline = json.load(f)
        except (OSError, ValueError) as e:
            sys.stderr.write(f"[gate] cannot load baseline {path}: {e}\n")
            sys.exit(2)
        row_names = sorted(baseline.get("rows") or {})
        if not row_names:
            sys.stderr.write(f"[gate] baseline {path} has no rows\n")
            sys.exit(2)

    results = {}
    for name in row_names:
        sys.stderr.write(f"[gate] running row {name}\n")
        row = None
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--row", name],
                stdout=subprocess.PIPE, text=True, timeout=600,
            )
        except subprocess.TimeoutExpired:
            sys.stderr.write(f"[gate] row {name} timed out\n")
            results[name] = None
            continue
        if proc.returncode == 0:
            for line in reversed((proc.stdout or "").strip().splitlines()):
                try:
                    row = json.loads(line)
                    break
                except ValueError:
                    continue
        if row is None:
            sys.stderr.write(f"[gate] row {name} failed (rc={proc.returncode})\n")
        results[name] = row

    if update:
        missing = [
            n for n, r in results.items()
            if not isinstance(r, dict) or not r.get("telemetry")
        ]
        if missing:
            sys.stderr.write(f"[gate] cannot update baseline, rows failed: {missing}\n")
            sys.exit(1)
        baseline = {
            "tolerance": tolerance if tolerance is not None else DEFAULT_TOLERANCE,
            "rows": {
                name: {"label": row.get("label", name), "telemetry": row["telemetry"]}
                for name, row in results.items()
            },
        }
        with open(path, "w", encoding="utf-8") as f:
            json.dump(baseline, f, indent=2, sort_keys=True)
            f.write("\n")
        sys.stderr.write(f"[gate] baseline updated: {path}\n")
        print(json.dumps({"gate": "updated", "rows": sorted(results)}), flush=True)
        return

    failures = gate_report(baseline, results, tolerance=tolerance)
    for name, problems in sorted(failures.items()):
        for problem in problems:
            sys.stderr.write(f"[gate] FAIL {name}: {problem}\n")
    verdict = {
        "gate": "fail" if failures else "pass",
        "rows": sorted(results),
        "failures": failures,
    }
    print(json.dumps(verdict), flush=True)
    if failures:
        sys.exit(1)
    sys.stderr.write(f"[gate] pass: {len(results)} rows within tolerance\n")


# the on-chip run, in order: (row, label, timeout seconds). One child each,
# strictly one after another.
ON_CHIP_ROWS = (
    # single-stream e2e decode on the 7B span: the ONE metric line
    ("e2e_8xllama7b", "e2e 7B-span", 900),
    # 70B-shaped bf16 span: 6 blocks = 10.3 GB of weights on the chip
    ("decode_70b_bf16", "70B-shape bf16", 600),
    # quantized 70B-shaped spans, 10 blocks each (fused Pallas dequant)
    ("decode_70b_nf4", "70B-shape nf4", 600),
    ("decode_70b_nf4a", "70B-shape nf4a", 600),  # the 4-bit serving default
    ("decode_70b_int4", "70B-shape int4", 600),
    ("decode_70b_nf4a_o", "70B-shape nf4a+o", 600),
    ("prefill_8k_flash", "8k flash prefill", 600),
    ("decode_7b_batched", "batched decode", 600),
    ("continuous_batching_e2e", "continuous batching", 600),
    ("prefix_cache_ttft", "prefix cache", 600),
    ("chain_hop_405b_shapes", "405B chain hops", 900),
    ("e2e_server_gen", "server-side generation", 900),
    ("e2e_server_gen_sampling", "pooled server-gen sampling", 900),
    ("e2e_paged_decode", "paged KV decode", 900),
    ("e2e_mixed_prefill_decode", "mixed prefill+decode", 900),
    ("quant_quality", "quant quality", 600),
    ("moe_prefill_2048", "moe dispatch", 600),
)
DETAILS_PATH = os.path.join("chiprun_out", "bench_details.json")


def _run_on_chip(rows=ON_CHIP_ROWS) -> None:
    """The full run: a parent that never touches JAX (a parent that did would
    hold the chip and every child would fail or land on the CPU), one
    ``--row --on_chip`` child per row, one at a time. The first row that fails
    ends the run with its exit code; nothing is printed for a row that did
    not finish."""
    import subprocess

    details = {}
    os.makedirs(os.path.dirname(DETAILS_PATH), exist_ok=True)
    for name, label, timeout in rows:
        assert "jax" not in sys.modules, "bench.py's parent must stay off JAX"
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--row", name, "--on_chip"],
            stdout=subprocess.PIPE, text=True, timeout=timeout,
        )
        if proc.returncode != 0:
            sys.stderr.write(f"[bench] row {name} failed (rc={proc.returncode}); stopping\n")
            sys.exit(proc.returncode)
        details[name] = json.loads(proc.stdout.strip().splitlines()[-1])
        print(f"# {label}: {json.dumps(details[name])}", file=sys.stderr)
        with open(DETAILS_PATH, "w") as f:
            json.dump(details, f, indent=2)
        if name == "e2e_8xllama7b":
            print(json.dumps({
                "metric": f"single_stream_decode_tok_s_{N_BLOCKS}xllama7b_blocks_e2e",
                "value": round(details[name]["tok_s"], 2),
                "unit": "tok/s",
                "vs_baseline": round(details[name]["tok_s"] / BASELINE_TOK_S, 2),
                "device": details[name]["device"],
            }), flush=True)


def main():
    if "--row" in sys.argv:
        _run_single_row(
            sys.argv[sys.argv.index("--row") + 1], on_chip="--on_chip" in sys.argv
        )
    elif "--gate" in sys.argv or "--gate_update" in sys.argv:
        _run_gate(sys.argv)
    else:
        _run_on_chip()


if __name__ == "__main__":
    main()
