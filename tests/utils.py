"""Tiny random HF checkpoints saved to disk — the test swarm's "models"
(zero-egress stand-in for the reference CI's bloom-560m / TinyLlama downloads,
reference .github/workflows/run-tests.yaml:10-20).

Builds are memoized per pytest RUN: constructing + saving a torch model costs
~1-2 s and the suite requests the same handful of configurations from dozens
of module fixtures. The first build lands in a shared per-run cache dir and
later requests copy the saved files into the caller's tmpdir (~ms) — callers
still own a private, mutable checkpoint (several tests edit theirs)."""

import asyncio
import functools
import os
import shutil

import numpy as np
import torch


def _model_build_cache(builder):
    """Memoize a make_tiny_*(tmpdir, **kw) builder: build once per kwargs
    into the shared cache, then copy into each caller's tmpdir."""

    @functools.wraps(builder)
    def wrapped(tmpdir: str, **kwargs) -> str:
        cache_root = os.environ.get("PETALS_TPU_TEST_MODEL_CACHE")
        if not cache_root:
            return builder(tmpdir, **kwargs)
        key = builder.__name__ + "--" + "-".join(
            f"{k}={kwargs[k]}" for k in sorted(kwargs)
        )
        cached = os.path.join(cache_root, key)
        if not os.path.isdir(cached):
            # builders return <tmpdir>/<model-name>; build under a pid-unique
            # dir and atomically rename onto the key — concurrent processes
            # (subprocess swarms share the env) may race, and the loser just
            # keeps the winner's identical bytes (deterministic seeds)
            build_dir = os.path.join(cache_root, f"{key}.build.{os.getpid()}")
            built = builder(build_dir, **kwargs)
            try:
                os.rename(built, cached)
            except OSError:
                pass  # another process won the race
            shutil.rmtree(build_dir, ignore_errors=True)
        want = os.path.join(tmpdir, os.path.basename(cached))
        if not os.path.isdir(want):
            os.makedirs(tmpdir, exist_ok=True)
            shutil.copytree(cached, want)
        return want

    return wrapped


@_model_build_cache
def make_tiny_llama(
    tmpdir: str, *, n_layers: int = 4, vocab: int = 128, biased: bool = False,
    kv_heads: int = 2,
) -> str:
    from transformers import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(
        vocab_size=vocab,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=n_layers,
        num_attention_heads=4,
        num_key_value_heads=kv_heads,
        max_position_embeddings=256,
        rms_norm_eps=1e-6,
        rope_theta=10000.0,
        tie_word_embeddings=False,
        attention_bias=biased,
        mlp_bias=biased,
    )
    torch.manual_seed(0)
    model = LlamaForCausalLM(cfg).eval()
    if biased:  # random biases (default init is zeros, which would hide bugs)
        with torch.no_grad():
            for name, p in model.named_parameters():
                if name.endswith(".bias"):
                    p.normal_(0, 0.1)
    path = os.path.join(tmpdir, "tiny-llama-biased" if biased else "tiny-llama")
    model.save_pretrained(path, safe_serialization=True)
    return path


@_model_build_cache
def make_tiny_llama_cls(
    tmpdir: str, *, n_layers: int = 4, vocab: int = 128, num_labels: int = 3
) -> str:
    from transformers import LlamaConfig, LlamaForSequenceClassification

    cfg = LlamaConfig(
        vocab_size=vocab,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=n_layers,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=256,
        rms_norm_eps=1e-6,
        rope_theta=10000.0,
        num_labels=num_labels,
        pad_token_id=0,
    )
    torch.manual_seed(3)
    model = LlamaForSequenceClassification(cfg).eval()
    path = os.path.join(tmpdir, "tiny-llama-cls")
    model.save_pretrained(path, safe_serialization=True)
    return path


@_model_build_cache
def make_tiny_bloom_cls(
    tmpdir: str, *, n_layers: int = 3, vocab: int = 128, num_labels: int = 3
) -> str:
    from transformers import BloomConfig, BloomForSequenceClassification

    cfg = BloomConfig(
        vocab_size=vocab,
        hidden_size=64,
        n_head=4,
        n_layer=n_layers,
        layer_norm_epsilon=1e-5,
        num_labels=num_labels,
        pad_token_id=0,
    )
    torch.manual_seed(5)
    model = BloomForSequenceClassification(cfg).eval()
    path = os.path.join(tmpdir, "tiny-bloom-cls")
    model.save_pretrained(path, safe_serialization=True)
    return path


@_model_build_cache
def make_tiny_bloom(tmpdir: str, *, n_layers: int = 3, vocab: int = 128) -> str:
    from transformers import BloomConfig, BloomForCausalLM

    cfg = BloomConfig(
        vocab_size=vocab,
        hidden_size=64,
        n_head=4,
        n_layer=n_layers,
        layer_norm_epsilon=1e-5,
        tie_word_embeddings=True,
    )
    torch.manual_seed(1)
    model = BloomForCausalLM(cfg).eval()
    path = os.path.join(tmpdir, "tiny-bloom")
    model.save_pretrained(path, safe_serialization=True)
    return path


@_model_build_cache
def make_tiny_falcon(tmpdir: str, *, variant: str = "new", n_layers: int = 3, vocab: int = 128, head_dim: int = 16, heads: int = 4, kv_heads: int = 2) -> str:
    """variant: "new" (40b-style GQA dual-LN), "7b" (MQA parallel), "rw" (MHA alibi serial).
    ``head_dim`` 64 is Falcon-40B's own: four heads of it over two kv heads."""
    from transformers import FalconConfig, FalconForCausalLM

    common = dict(
        vocab_size=vocab,
        hidden_size=heads * head_dim,
        num_hidden_layers=n_layers,
        num_attention_heads=heads,
        layer_norm_epsilon=1e-5,
    )
    if variant == "new":
        cfg = FalconConfig(
            **common, new_decoder_architecture=True, num_kv_heads=kv_heads, multi_query=False,
            parallel_attn=True, bias=False, alibi=False,
        )
    elif variant == "7b":
        cfg = FalconConfig(
            **common, new_decoder_architecture=False, multi_query=True,
            parallel_attn=True, bias=False, alibi=False,
        )
    elif variant == "rw":
        cfg = FalconConfig(
            **common, new_decoder_architecture=False, multi_query=False,
            parallel_attn=False, bias=True, alibi=True,
        )
    else:
        raise ValueError(variant)
    torch.manual_seed(3)
    model = FalconForCausalLM(cfg).eval()
    path = os.path.join(tmpdir, f"tiny-falcon-{variant}" + (f"-d{head_dim}" if head_dim != 16 else "") + (f"-h{heads}x{kv_heads}" if (heads, kv_heads) != (4, 2) else ""))
    model.save_pretrained(path, safe_serialization=True)
    return path


@_model_build_cache
def make_tiny_mixtral(tmpdir: str, *, n_layers: int = 2, vocab: int = 128) -> str:
    from transformers import MixtralConfig, MixtralForCausalLM

    cfg = MixtralConfig(
        vocab_size=vocab,
        hidden_size=64,
        intermediate_size=96,
        num_hidden_layers=n_layers,
        num_attention_heads=4,
        num_key_value_heads=2,
        num_local_experts=4,
        num_experts_per_tok=2,
        rms_norm_eps=1e-6,
        sliding_window=None,
    )
    torch.manual_seed(4)
    model = MixtralForCausalLM(cfg).eval()
    path = os.path.join(tmpdir, "tiny-mixtral")
    model.save_pretrained(path, safe_serialization=True)
    return path


@_model_build_cache
def make_tiny_olmoe(tmpdir: str, *, n_layers: int = 2, vocab: int = 128) -> str:
    from transformers import OlmoeConfig, OlmoeForCausalLM

    cfg = OlmoeConfig(
        vocab_size=vocab,
        hidden_size=64,
        intermediate_size=64,  # one expert's width (a multiple of the 4-bit block of 64)
        num_hidden_layers=n_layers,
        num_attention_heads=4,
        num_key_value_heads=4,
        num_experts=8,
        num_experts_per_tok=3,
        norm_topk_prob=False,
        max_position_embeddings=256,
        rms_norm_eps=1e-5,
        rope_theta=10000.0,
        tie_word_embeddings=False,
    )
    torch.manual_seed(11)
    model = OlmoeForCausalLM(cfg).eval()
    with torch.no_grad():  # norm weights initialise to ones, which would hide a missing or misplaced QK-norm vector
        for name, p in model.named_parameters():
            if name.endswith("norm.weight"):
                p.uniform_(0.5, 1.5)
    path = os.path.join(tmpdir, "tiny-olmoe")
    model.save_pretrained(path, safe_serialization=True)
    return path


@_model_build_cache
def make_tiny_qwen2(tmpdir: str, *, n_layers: int = 4, vocab: int = 128, tied: bool = True) -> str:
    from transformers import Qwen2Config, Qwen2ForCausalLM

    cfg = Qwen2Config(
        vocab_size=vocab,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=n_layers,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=256,
        rms_norm_eps=1e-6,
        rope_theta=10000.0,
        use_sliding_window=False,
        tie_word_embeddings=tied,  # the 0.5B/1.5B checkpoints tie
    )
    torch.manual_seed(5)
    model = Qwen2ForCausalLM(cfg).eval()
    with torch.no_grad():  # default bias init is zeros, which would hide bugs
        for name, p in model.named_parameters():
            if name.endswith(".bias"):
                p.normal_(0, 0.1)
    path = os.path.join(tmpdir, "tiny-qwen2")
    model.save_pretrained(path, safe_serialization=True)
    return path


@_model_build_cache
def make_tiny_gemma2(tmpdir: str, *, n_layers: int = 4, vocab: int = 128) -> str:
    """Gemma-2: alternating sliding/full attention (window 6 so tests cross
    the window edge), attention + final logit soft-capping, four post-norms,
    tied head."""
    from transformers import Gemma2Config, Gemma2ForCausalLM

    cfg = Gemma2Config(
        vocab_size=vocab,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=n_layers,
        num_attention_heads=4,
        num_key_value_heads=2,
        head_dim=16,
        max_position_embeddings=256,
        rms_norm_eps=1e-6,
        rope_theta=10000.0,
        sliding_window=6,
        attn_logit_softcapping=50.0,
        final_logit_softcapping=30.0,
        query_pre_attn_scalar=16,
        tie_word_embeddings=True,
        attn_implementation="eager",  # softcapping requires the eager path
    )
    torch.manual_seed(9)
    model = Gemma2ForCausalLM(cfg).eval()
    path = os.path.join(tmpdir, "tiny-gemma2")
    model.save_pretrained(path, safe_serialization=True)
    return path


@_model_build_cache
def make_tiny_phi3(tmpdir: str, *, n_layers: int = 4, vocab: int = 128) -> str:
    """Phi-3 with LongRoPE: original window 64 << max 256, so tests that run
    past position 64 exercise the long-factor selection and attention scale
    exactly where HF switches them."""
    from transformers import Phi3Config, Phi3ForCausalLM

    head_dim = 16
    cfg = Phi3Config(
        vocab_size=vocab,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=n_layers,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=256,
        original_max_position_embeddings=64,
        rms_norm_eps=1e-6,
        rope_theta=10000.0,
        rope_scaling={
            "type": "longrope",  # Phi3Config validates exactly this key set
            "short_factor": [1.0 + 0.05 * i for i in range(head_dim // 2)],
            "long_factor": [2.0 + 0.3 * i for i in range(head_dim // 2)],
        },
        sliding_window=None,
        tie_word_embeddings=False,
        pad_token_id=0,  # Phi3Config defaults to 32000, outside the tiny vocab
    )
    torch.manual_seed(8)
    model = Phi3ForCausalLM(cfg).eval()
    path = os.path.join(tmpdir, "tiny-phi3")
    model.save_pretrained(path, safe_serialization=True)
    return path


@_model_build_cache
def make_tiny_mistral(tmpdir: str, *, n_layers: int = 4, vocab: int = 128, window: int = 6) -> str:
    from transformers import MistralConfig, MistralForCausalLM

    cfg = MistralConfig(
        vocab_size=vocab,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=n_layers,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=256,
        rms_norm_eps=1e-6,
        rope_theta=10000.0,
        sliding_window=window,  # small so tests actually cross the window edge
        tie_word_embeddings=False,
    )
    torch.manual_seed(6)
    model = MistralForCausalLM(cfg).eval()
    path = os.path.join(tmpdir, "tiny-mistral")
    model.save_pretrained(path, safe_serialization=True)
    return path


@_model_build_cache
def make_tiny_gemma(tmpdir: str, *, n_layers: int = 4, vocab: int = 128) -> str:
    from transformers import GemmaConfig, GemmaForCausalLM

    cfg = GemmaConfig(
        vocab_size=vocab,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=n_layers,
        num_attention_heads=4,
        num_key_value_heads=2,
        head_dim=16,  # explicit, like the real checkpoints (256 on 7B)
        max_position_embeddings=256,
        rms_norm_eps=1e-6,
        rope_theta=10000.0,
        hidden_act="gelu_pytorch_tanh",
    )
    torch.manual_seed(7)
    model = GemmaForCausalLM(cfg).eval()
    path = os.path.join(tmpdir, "tiny-gemma")
    model.save_pretrained(path, safe_serialization=True)
    return path


def multihost_child_env(repo_root: str | None = None) -> dict:
    """Env for multi-host subprocess swarms: CPU-only (any accelerator plugin
    dir is REPLACED out of PYTHONPATH — plugins force-override JAX_PLATFORMS
    at import time), one virtual device per process.

    The suite's shared jit compilation cache (tests/conftest.py) is STRIPPED:
    two jax.distributed processes sharing one on-disk cache can wedge a
    lockstep group at its first collective (observed: a leader hung >300 s in
    a trivial forward when earlier swarm tests had populated the dir — likely
    a partially-written entry from a killed worker). Children pay cold
    compiles; only the in-process suite shares the cache."""
    root = repo_root or os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {
        **os.environ,
        "PYTHONPATH": root,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
    }
    for var in (
        "JAX_COMPILATION_CACHE_DIR",
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
        "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES",
    ):
        env.pop(var, None)
    return env


def spawn_multihost_pair(
    model: str,
    *,
    num_blocks: int = 4,
    leader_args: tuple = (),
    worker_args: tuple = (),
    ready_timeout: float = 300.0,
    env: dict | None = None,
):
    """Start a run_server leader + run_worker pair over a 2-process tp mesh
    and wait for the leader's announce address. Returns (leader_proc,
    worker_proc, addr); both stdouts are drained by daemon reader threads
    from the start (callers must terminate both). The announce-line protocol
    lives here.

    Readiness is watched through a queue fed by the leader's reader thread,
    so ``ready_timeout`` is enforced even when the leader stops logging
    without exiting (e.g. blocked in jax.distributed.initialize because the
    worker died at startup) — a blocking readline would hang past any
    deadline there."""
    import queue as _queue
    import socket
    import subprocess
    import sys
    import threading
    import time

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    coord = f"127.0.0.1:{port}"
    env = env or multihost_child_env()
    span = ["--first_block", "0", "--num_blocks", str(num_blocks),
            "--coordinator_address", coord, "--num_hosts", "2"]
    leader = subprocess.Popen(
        [sys.executable, "-m", "petals_tpu.cli.run_server", model,
         *span, "--host", "127.0.0.1", *leader_args],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    worker = subprocess.Popen(
        [sys.executable, "-m", "petals_tpu.cli.run_worker", model,
         *span, "--host_index", "1", *worker_args],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    lines_q: "_queue.Queue[str]" = _queue.Queue()
    ready = threading.Event()  # once set, the reader discards (pure drain) —
    # enqueueing for the leader's whole life would grow memory unboundedly

    def read_leader():
        for line in leader.stdout:
            if not ready.is_set():
                lines_q.put(line)
        lines_q.put("")  # EOF sentinel

    threading.Thread(target=read_leader, daemon=True).start()
    threading.Thread(  # drain from the start: a full pipe deadlocks the child
        target=lambda: [None for _ in worker.stdout], daemon=True
    ).start()

    addr, lines = None, []
    deadline = time.time() + ready_timeout
    while time.time() < deadline:
        try:
            line = lines_q.get(timeout=min(5.0, max(deadline - time.time(), 0.1)))
        except _queue.Empty:
            if leader.poll() is not None:
                break
            continue
        if not line:
            break  # EOF
        lines.append(line)
        if "announce address:" in line:
            addr = line.rsplit("announce address:", 1)[1].strip()
            break
    ready.set()
    if not addr:
        for p in (leader, worker):
            p.kill()
        raise RuntimeError(
            "multihost leader never became ready:\n" + "".join(lines[-25:])
        )
    return leader, worker, addr


def stop_multihost_pair(leader, worker, timeout: float = 30.0) -> None:
    import subprocess

    leader.terminate()
    try:
        leader.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        leader.kill()
    try:
        worker.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        worker.kill()


async def drive_coalescing_sessions(
    addr: str,
    model: str,
    *,
    num_blocks: int = 4,
    n_sessions: int = 4,
    n_steps: int = 6,
    prefill: int = 4,
    concurrent: bool = True,
    seed: int = 3,
):
    """Drive N raw RPC decode sessions against a span leader. When
    ``concurrent``, each round's sends are all issued BEFORE any reply is
    awaited, so the leader's lane pool genuinely coalesces. Returns
    (elapsed_decode_seconds, ptu.info dict)."""
    import time as _time

    import numpy as np
    from transformers import AutoConfig

    from petals_tpu.data_structures import CHAIN_DELIMITER, make_uid
    from petals_tpu.rpc import RpcClient
    from petals_tpu.rpc.serialization import deserialize_array, serialize_array
    from petals_tpu.server.server import default_dht_prefix

    hsz = AutoConfig.from_pretrained(model).hidden_size
    host, port = addr.rsplit("/", 1)[0].rsplit(":", 1)
    uids = CHAIN_DELIMITER.join(
        make_uid(default_dht_prefix(model), i) for i in range(num_blocks)
    )
    rng = np.random.RandomState(seed)
    c = await RpcClient.connect(host, int(port))
    try:
        streams = []
        for _ in range(n_sessions):
            s = await c.open_stream("ptu.inference")
            await s.send({
                "uids": uids, "max_length": prefill + n_steps + 8, "batch_size": 1,
            })
            await s.recv(timeout=60)
            await s.send({"tensors": {"hidden": serialize_array(
                rng.randn(1, prefill, hsz).astype(np.float32) * 0.1)}})
            await s.recv(timeout=300)
            streams.append(s)
        # one UNTIMED decode round per mode: the first coalesced step pays
        # the batched-program XLA compile, and timing it would bias the
        # batched-vs-serial ratio toward whichever mode ran second
        warm = rng.randn(1, 1, hsz).astype(np.float32) * 0.1
        for s in streams:
            await s.send({"tensors": {"hidden": serialize_array(warm)}})
        for s in streams:
            await s.recv(timeout=300)
        t0 = _time.perf_counter()
        if concurrent:
            for _ in range(n_steps):
                step = rng.randn(1, 1, hsz).astype(np.float32) * 0.1
                for s in streams:  # all sends before any recv -> coalescing
                    await s.send({"tensors": {"hidden": serialize_array(step)}})
                for s in streams:
                    out = deserialize_array(
                        (await s.recv(timeout=300))["tensors"]["hidden"]
                    )
                    assert np.isfinite(out).all()
        else:
            for s in streams:
                for _ in range(n_steps):
                    step = rng.randn(1, 1, hsz).astype(np.float32) * 0.1
                    await s.send({"tensors": {"hidden": serialize_array(step)}})
                    deserialize_array((await s.recv(timeout=300))["tensors"]["hidden"])
        elapsed = _time.perf_counter() - t0
        for s in streams:
            await s.end()
        return elapsed, await c.call("ptu.info", {}, timeout=30)
    finally:
        await c.close()


async def steps_booked(batcher) -> None:
    """Wait until the compute thread has booked every decode step started so
    far: a launched step's counters move after its replies
    (``DecodeBatcher._finish_batch``, queued by ``_book`` in the loop's turn
    behind the replies'), so a test that reads ``batcher.stats`` behind an
    awaited step lets that turn come and the compute queue run empty."""
    from petals_tpu.server.task_queue import PRIORITY_BARRIER

    while batcher._flights:
        await asyncio.sleep(0)
    await asyncio.sleep(0)
    await batcher.queue.submit(lambda: None, priority=PRIORITY_BARRIER)


def record_step_annotations(monkeypatch) -> list:
    """Replace the TraceAnnotation the server stack resolved at import
    (utils/tracing.py) by a recorder. Returns the list it appends to:
    ("open", name, args) and ("close", name) in the order they happen."""
    from petals_tpu.utils import tracing

    events: list = []

    class Recorder:
        def __init__(self, name, **args):
            self.name, self.args = name, args

        def __enter__(self):
            events.append(("open", self.name, self.args))
            return self

        def __exit__(self, *exc_info):
            events.append(("close", self.name))
            return False

    monkeypatch.setattr(tracing, "_TraceAnnotation", Recorder)
    return events


def recorded_steps(events: list) -> list:
    """Split a recorder's events into steps: one (args, [names inside
    ``ptu.step`` in order of opening]) per ``ptu.step``, asserting that every
    annotation closed before the next opened and all of them inside the step."""
    steps, stack = [], []
    for event in events:
        kind, name = event[0], event[1]
        if not name.startswith("ptu.step"):
            continue
        if kind == "open":
            if name == "ptu.step":
                assert not stack, stack
                steps.append((event[2], []))
            else:
                assert stack == ["ptu.step"], (name, stack)  # inside the step, no phase open
                steps[-1][1].append(name)
            stack.append(name)
        else:
            assert stack and stack[-1] == name, (name, stack)
            stack.pop()
    assert not stack, stack
    return steps


TINY_EXAONE_MOE = {  # the keys K-EXAONE publishes, at a toy size: kinds D-L, S-L, S-L, S-G, S-L
    "model_type": "exaone_moe", "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 128, "moe_intermediate_size": 32, "num_hidden_layers": 5, "first_k_dense_replace": 1,
    "layer_types": ["sliding_attention", "sliding_attention", "sliding_attention", "full_attention", "sliding_attention"],
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"], "sliding_window": 8, "sliding_window_pattern": "LLLG",
    "num_experts": 16, "num_experts_per_tok": 4, "num_shared_experts": 1, "n_group": 1, "topk_group": 1,
    "scoring_func": "sigmoid", "norm_topk_prob": True, "routed_scaling_factor": 2.5, "hidden_act": "silu",
    "rms_norm_eps": 1e-5, "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "num_nextn_predict_layers": 1, "max_position_embeddings": 256, "tie_word_embeddings": False, "vocab_size": 128,
}


def tiny_exaone_moe_tensors(config: dict, seed: int = 13) -> dict:
    """Seeded float32 tensors under the HF names of every layer of ``config``
    (all the routed experts), the embedding, the final norm and the head.
    Norm vectors and the router's bias are drawn, not ones and zeros, so a
    missing or misplaced one shows."""
    rng = np.random.RandomState(seed)
    h, hq, hkv, d = (config[k] for k in ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim"))
    m, md = config["moe_intermediate_size"], config["intermediate_size"]
    routed = (config.get("expert_share") or {}).get("routed", config["num_experts"])
    normal = lambda *shape: (rng.standard_normal(shape) * 0.1).astype(np.float32)
    norm = lambda n: rng.uniform(0.5, 1.5, n).astype(np.float32)
    tensors = {"model.embed_tokens.weight": normal(config["vocab_size"], h), "model.norm.weight": norm(h),
               "lm_head.weight": normal(config["vocab_size"], h)}
    for i, mlp in enumerate(config["mlp_layer_types"]):
        p = f"model.layers.{i}."
        tensors.update({
            p + "input_layernorm.weight": norm(h), p + "post_attention_layernorm.weight": norm(h),
            p + "self_attn.q_proj.weight": normal(hq * d, h), p + "self_attn.k_proj.weight": normal(hkv * d, h),
            p + "self_attn.v_proj.weight": normal(hkv * d, h), p + "self_attn.o_proj.weight": normal(h, hq * d),
            p + "self_attn.q_norm.weight": norm(d), p + "self_attn.k_norm.weight": norm(d),
        })
        if mlp == "dense":
            tensors.update({p + "mlp.gate_proj.weight": normal(md, h), p + "mlp.up_proj.weight": normal(md, h),
                            p + "mlp.down_proj.weight": normal(h, md)})
            continue
        tensors[p + "mlp.gate.weight"] = normal(routed, h) * 5  # scores spread over (0, 1)
        tensors[p + "mlp.gate.e_score_correction_bias"] = normal(routed)
        for e in [*range(routed), "shared"]:
            q = p + ("mlp.shared_experts." if e == "shared" else f"mlp.experts.{e}.")
            tensors.update({q + "gate_proj.weight": normal(m, h), q + "up_proj.weight": normal(m, h), q + "down_proj.weight": normal(h, m)})
    return tensors


@_model_build_cache
def make_tiny_exaone_moe(tmpdir: str, *, held: int = 16, first: int = 0) -> str:
    """A K-EXAONE checkpoint at a toy size, written by hand (the installed
    transformers has no class for ``exaone_moe``): every routed expert is in
    the file, and a server of this directory holds ``held`` of the 16 from
    ``first`` on (``expert_share``; all of them by default)."""
    import json

    from safetensors.numpy import save_file

    config = dict(TINY_EXAONE_MOE)
    if held != 16 or first:
        config.update(num_experts=held, expert_share={"routed": 16, "first": first})
    path = os.path.join(tmpdir, f"tiny-exaone-moe-{held}-{first}")
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(config, f)
    save_file(tiny_exaone_moe_tensors(config), os.path.join(path, "model.safetensors"))
    return path


TINY_OLMO_HYBRID = {  # the keys Olmo-Hybrid publishes, at a toy size: two periods of three linear layers and a full one
    "model_type": "olmo_hybrid", "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
    "intermediate_size": 128, "num_hidden_layers": 8, "hidden_act": "silu", "attention_bias": False,
    "layer_types": (["linear_attention"] * 3 + ["full_attention"]) * 2,
    "linear_num_key_heads": 4, "linear_num_value_heads": 4, "linear_key_head_dim": 8, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True, "rope_parameters": {"rope_theta": None},
    "rms_norm_eps": 1e-6, "max_position_embeddings": 256, "tie_word_embeddings": False, "vocab_size": 128,
}


def tiny_olmo_hybrid_tensors(config: dict, seed: int = 17) -> dict:
    """Seeded float32 tensors under the HF names of every layer of ``config``
    (``assumed.tensor_names`` of perf/configs/olmo-hybrid-7b-span16.json), the
    embedding, the final norm and the head. Norm vectors are drawn, not ones,
    so a missing or misplaced one shows; A and the step spread alpha over
    about 0.3-0.99."""
    rng = np.random.RandomState(seed)
    h, hq, hkv, m = (config[k] for k in ("hidden_size", "num_attention_heads", "num_key_value_heads", "intermediate_size"))
    d = h // hq
    heads, d_k, d_v, taps = (config[k] for k in ("linear_num_value_heads", "linear_key_head_dim", "linear_value_head_dim", "linear_conv_kernel_dim"))
    normal = lambda *shape: (rng.standard_normal(shape) * 0.1).astype(np.float32)
    norm = lambda n: rng.uniform(0.5, 1.5, n).astype(np.float32)
    tensors = {"model.embed_tokens.weight": normal(config["vocab_size"], h), "model.norm.weight": norm(h),
               "lm_head.weight": normal(config["vocab_size"], h)}
    for i, kind in enumerate(config["layer_types"]):
        p = f"model.layers.{i}."
        tensors.update({
            p + "post_attention_layernorm.weight": norm(h), p + "post_feedforward_layernorm.weight": norm(h),
            p + "mlp.gate_proj.weight": normal(m, h), p + "mlp.up_proj.weight": normal(m, h), p + "mlp.down_proj.weight": normal(h, m),
        })
        if kind == "full_attention":
            tensors.update({
                p + "self_attn.q_proj.weight": normal(hq * d, h), p + "self_attn.k_proj.weight": normal(hkv * d, h),
                p + "self_attn.v_proj.weight": normal(hkv * d, h), p + "self_attn.o_proj.weight": normal(h, hq * d),
                p + "self_attn.q_norm.weight": norm(hq * d), p + "self_attn.k_norm.weight": norm(hkv * d),
            })
            continue
        q = p + "linear_attn."
        dt = np.exp(rng.uniform(np.log(0.001), np.log(0.1), heads))
        tensors.update({
            q + "q_proj.weight": normal(heads * d_k, h), q + "k_proj.weight": normal(heads * d_k, h),
            q + "v_proj.weight": normal(heads * d_v, h), q + "g_proj.weight": normal(heads * d_v, h),
            q + "a_proj.weight": normal(heads, h), q + "b_proj.weight": normal(heads, h), q + "o_proj.weight": normal(h, heads * d_v),
            q + "conv1d.weight": (rng.standard_normal((heads * (2 * d_k + d_v), 1, taps)) * 0.4).astype(np.float32),
            q + "A_log": np.log(rng.uniform(1, 16, heads)).astype(np.float32),
            q + "dt_bias": (dt + np.log(-np.expm1(-dt))).astype(np.float32), q + "o_norm.weight": norm(d_v),
        })
    return tensors


@_model_build_cache
def make_tiny_olmo_hybrid(tmpdir: str) -> str:
    """An Olmo-Hybrid checkpoint at a toy size, written by hand (the installed
    transformers has no class for ``olmo_hybrid``)."""
    import json

    from safetensors.numpy import save_file

    path = os.path.join(tmpdir, "tiny-olmo-hybrid")
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(TINY_OLMO_HYBRID, f)
    save_file(tiny_olmo_hybrid_tensors(TINY_OLMO_HYBRID), os.path.join(path, "model.safetensors"))
    return path


TINY_KEYE_VL2 = {  # the keys Keye-VL-2.0's language model publishes, at a toy size: a selection of 16 positions a row
    "model_type": "KeyeVL2", "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 128, "moe_intermediate_size": 32, "num_experts": 8, "num_local_experts": 8, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [], "num_hidden_layers": 4, "hidden_act": "silu",
    "attention_bias": False, "rms_norm_eps": 1e-6, "rope_theta": 10000000,
    "rope_scaling": {"mrope_section": [2, 3, 3], "rope_type": "default", "type": "default"},
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 4, "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 16},
    "sliding_window": None, "use_sliding_window": False, "max_window_layers": 4, "max_position_embeddings": 512,
    "tie_word_embeddings": False, "vocab_size": 128,
}


def tiny_keye_vl2_tensors(config: dict, seed: int = 23) -> dict:
    """Seeded float32 tensors under the names of perf/configs/keye-vl2-30b-a3b-span5.json's
    ``assumed.tensor_names`` for every layer of ``config``, the embedding, the
    final norm and the head. Norm vectors (the indexer's layer norm's bias
    too) are drawn, not ones and zeros, so a missing or misplaced one shows."""
    rng = np.random.RandomState(seed)
    h, hq, hkv, d = (config[k] for k in ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim"))
    m, n_experts, sa = config["moe_intermediate_size"], config["num_experts"], config["sa_config"]
    heads, d_idx = sa["indexer_num_heads"], sa["indexer_head_dim"]
    normal = lambda *shape: (rng.standard_normal(shape) * 0.1).astype(np.float32)
    norm = lambda n: rng.uniform(0.5, 1.5, n).astype(np.float32)
    tensors = {"model.embed_tokens.weight": normal(config["vocab_size"], h), "model.norm.weight": norm(h),
               "lm_head.weight": normal(config["vocab_size"], h)}
    for i in range(config["num_hidden_layers"]):
        p = f"model.layers.{i}."
        tensors.update({
            p + "input_layernorm.weight": norm(h), p + "post_attention_layernorm.weight": norm(h),
            p + "self_attn.q_proj.weight": normal(hq * d, h), p + "self_attn.k_proj.weight": normal(hkv * d, h),
            p + "self_attn.v_proj.weight": normal(hkv * d, h), p + "self_attn.o_proj.weight": normal(h, hq * d),
            p + "self_attn.q_norm.weight": norm(d), p + "self_attn.k_norm.weight": norm(d),
            p + "self_attn.indexer.wq.weight": normal(heads * d_idx, h) * 3, p + "self_attn.indexer.wk.weight": normal(d_idx, h),
            p + "self_attn.indexer.weights_proj.weight": normal(heads, h),
            p + "self_attn.indexer.k_norm.weight": norm(d_idx), p + "self_attn.indexer.k_norm.bias": normal(d_idx),
            p + "mlp.gate.weight": normal(n_experts, h) * 3,
        })
        for e in range(n_experts):
            q = p + f"mlp.experts.{e}."
            tensors.update({q + "gate_proj.weight": normal(m, h), q + "up_proj.weight": normal(m, h), q + "down_proj.weight": normal(h, m)})
    return tensors


def make_tiny_keye_vl2(tmpdir: str, **overrides) -> str:
    """A Keye-VL-2.0 (text) checkpoint at a toy size, written by hand (the
    installed transformers has no class for ``KeyeVL2``)."""
    import json

    from safetensors.numpy import save_file

    config = {**TINY_KEYE_VL2, **overrides}
    path = os.path.join(tmpdir, "tiny-keye-vl2")
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(config, f)
    save_file(tiny_keye_vl2_tensors(config), os.path.join(path, "model.safetensors"))
    return path


TINY_DEEPSEEK_V3 = {  # the keys Kanana-2-30B-A3B publishes (transformers' deepseek_v3), at a toy size: kinds D, S, S, S
    "model_type": "deepseek_v3", "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 8,
    "kv_lora_rank": 32, "q_lora_rank": None, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "qk_head_dim": 24, "v_head_dim": 16,
    "intermediate_size": 128, "moe_intermediate_size": 32, "num_hidden_layers": 4, "first_k_dense_replace": 1, "moe_layer_freq": 1,
    "n_routed_experts": 16, "num_experts_per_tok": 4, "n_shared_experts": 2, "n_group": 1, "topk_group": 1, "topk_method": "noaux_tc",
    "scoring_func": "sigmoid", "norm_topk_prob": True, "routed_scaling_factor": 2.448, "hidden_act": "silu", "attention_bias": False,
    "rms_norm_eps": 1e-6, "rope_theta": 1000000, "rope_scaling": None, "rope_interleave": True,
    "max_position_embeddings": 256, "tie_word_embeddings": False, "vocab_size": 128,
}


def tiny_deepseek_v3_tensors(config: dict, seed: int = 29) -> dict:
    """Seeded float32 tensors under transformers' ``deepseek_v3`` names of
    every layer of ``config``, the embedding, the final norm and the head.
    Norm vectors and the router's bias are drawn, not ones and zeros, so a
    missing or misplaced one shows."""
    rng = np.random.RandomState(seed)
    h, heads, dn, dr, dv, latent = (config[k] for k in ("hidden_size", "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
                                                        "v_head_dim", "kv_lora_rank"))
    m, md, n, shared = config["moe_intermediate_size"], config["intermediate_size"], config["n_routed_experts"], config["n_shared_experts"]
    normal = lambda *shape: (rng.standard_normal(shape) * 0.1).astype(np.float32)
    norm = lambda width: rng.uniform(0.5, 1.5, width).astype(np.float32)
    tensors = {"model.embed_tokens.weight": normal(config["vocab_size"], h), "model.norm.weight": norm(h),
               "lm_head.weight": normal(config["vocab_size"], h)}
    for i in range(config["num_hidden_layers"]):
        p = f"model.layers.{i}."
        tensors.update({
            p + "input_layernorm.weight": norm(h), p + "post_attention_layernorm.weight": norm(h),
            p + "self_attn.q_proj.weight": normal(heads * (dn + dr), h) * 3, p + "self_attn.kv_a_proj_with_mqa.weight": normal(latent + dr, h) * 3,
            p + "self_attn.kv_a_layernorm.weight": norm(latent), p + "self_attn.kv_b_proj.weight": normal(heads * (dn + dv), latent) * 3,
            p + "self_attn.o_proj.weight": normal(h, heads * dv),
        })
        if i < config["first_k_dense_replace"]:
            tensors.update({p + "mlp.gate_proj.weight": normal(md, h), p + "mlp.up_proj.weight": normal(md, h),
                            p + "mlp.down_proj.weight": normal(h, md)})
            continue
        tensors[p + "mlp.gate.weight"] = normal(n, h) * 5  # scores spread over (0, 1)
        tensors[p + "mlp.gate.e_score_correction_bias"] = normal(n)
        for e in range(n):
            q = p + f"mlp.experts.{e}."
            tensors.update({q + "gate_proj.weight": normal(m, h), q + "up_proj.weight": normal(m, h), q + "down_proj.weight": normal(h, m)})
        q = p + "mlp.shared_experts."
        tensors.update({q + "gate_proj.weight": normal(m * shared, h), q + "up_proj.weight": normal(m * shared, h),
                        q + "down_proj.weight": normal(h, m * shared)})
    return tensors


def make_tiny_deepseek_v3(tmpdir: str, **overrides) -> str:
    """A ``deepseek_v3`` checkpoint at a toy size, written by hand under
    transformers' names (tests/test_deepseek_v3.py loads the same tensors
    into transformers' own layer)."""
    import json

    from safetensors.numpy import save_file

    config = {**TINY_DEEPSEEK_V3, **overrides}
    path = os.path.join(tmpdir, "tiny-deepseek-v3")
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(config, f)
    save_file(tiny_deepseek_v3_tensors(config), os.path.join(path, "model.safetensors"))
    return path


TINY_LONGCAT_FLASH = {  # the keys LongCat-Flash-Chat publishes (transformers' longcat_flash), at a toy size: 2 double layers
    "model_type": "longcat_flash", "hidden_size": 64, "num_attention_heads": 4, "num_layers": 2, "num_hidden_layers": 4,
    "q_lora_rank": 32, "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16, "head_dim": 8,
    "ffn_hidden_size": 128, "expert_ffn_hidden_size": 32, "n_routed_experts": 8, "zero_expert_num": 4, "zero_expert_type": "identity",
    "moe_topk": 3, "routed_scaling_factor": 6.0, "hidden_act": "silu", "attention_bias": False, "router_bias": False,
    "mla_scale_q_lora": True, "mla_scale_kv_lora": True, "attention_method": "MLA", "rms_norm_eps": 1e-5, "rope_theta": 10000000,
    "rope_scaling": None, "max_position_embeddings": 256, "tie_word_embeddings": False, "vocab_size": 128,
}


def tiny_longcat_flash_tensors(config: dict, seed: int = 31) -> dict:
    """Seeded float32 tensors under transformers' ``longcat_flash`` names of
    every double layer of ``config`` (ALL the FFN experts that exist, whatever
    share a directory holds), the embedding, the final norm and the head. Norm
    vectors and the router's bias are drawn, not ones and zeros, so a missing
    or misplaced one shows; the bias at the scale of a softmax score over the
    router's width, so that it moves picks and fixes none."""
    rng = np.random.RandomState(seed)
    h, heads, dn, dr, dv, latent, rq = (config[k] for k in ("hidden_size", "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
                                                            "v_head_dim", "kv_lora_rank", "q_lora_rank"))
    m, me, zeros = config["ffn_hidden_size"], config["expert_ffn_hidden_size"], config["zero_expert_num"]
    n = (config.get("expert_share") or {}).get("routed", config["n_routed_experts"])
    normal = lambda *shape: (rng.standard_normal(shape) * 0.1).astype(np.float32)
    norm = lambda width: rng.uniform(0.5, 1.5, width).astype(np.float32)
    tensors = {"model.embed_tokens.weight": normal(config["vocab_size"], h), "model.norm.weight": norm(h),
               "lm_head.weight": normal(config["vocab_size"], h)}
    for i in range(config["num_layers"]):
        p = f"model.layers.{i}."
        for j in (0, 1):
            a = p + f"self_attn.{j}."
            tensors.update({
                p + f"input_layernorm.{j}.weight": norm(h), p + f"post_attention_layernorm.{j}.weight": norm(h),
                a + "q_a_proj.weight": normal(rq, h) * 3, a + "q_a_layernorm.weight": norm(rq), a + "q_b_proj.weight": normal(heads * (dn + dr), rq),
                a + "kv_a_proj_with_mqa.weight": normal(latent + dr, h) * 3, a + "kv_a_layernorm.weight": norm(latent),
                a + "kv_b_proj.weight": normal(heads * (dn + dv), latent), a + "o_proj.weight": normal(h, heads * dv),
                p + f"mlps.{j}.gate_proj.weight": normal(m, h), p + f"mlps.{j}.up_proj.weight": normal(m, h),
                p + f"mlps.{j}.down_proj.weight": normal(h, m),
            })
        tensors[p + "mlp.router.classifier.weight"] = normal(n + zeros, h) * 3
        tensors[p + "mlp.router.e_score_correction_bias"] = normal(n + zeros) * 0.3
        for e in range(n):
            q = p + f"mlp.experts.{e}."
            tensors.update({q + "gate_proj.weight": normal(me, h) * 3, q + "up_proj.weight": normal(me, h) * 3, q + "down_proj.weight": normal(h, me) * 3})
    return tensors


def make_tiny_longcat_flash(tmpdir: str, *, held: int = 8, first: int = 0, **overrides) -> str:
    """A ``longcat_flash`` checkpoint at a toy size, written by hand under
    transformers' names (tests/test_longcat_flash.py loads the same tensors
    into transformers' own layer). All 8 FFN experts are in the file, and a
    server of this directory holds ``held`` of them from ``first`` on
    (``expert_share``; all of them by default)."""
    import json

    from safetensors.numpy import save_file

    config = {**TINY_LONGCAT_FLASH, **overrides}
    tensors = tiny_longcat_flash_tensors(config)
    if held != 8 or first:
        config.update(n_routed_experts=held, expert_share={"routed": 8, "first": first})
    path = os.path.join(tmpdir, f"tiny-longcat-flash-{held}-{first}")
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(config, f)
    save_file(tensors, os.path.join(path, "model.safetensors"))
    return path


TINY_QWEN3_NEXT = {  # the keys Qwen3-Next publishes, at a toy size: two periods of three linear layers and a full one
    "model_type": "qwen3_next", "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "partial_rotary_factor": 0.25, "rope_theta": 10000000, "rope_scaling": None, "intermediate_size": 128,
    "num_hidden_layers": 8, "full_attention_interval": 4, "hidden_act": "silu", "attention_bias": False,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4, "linear_key_head_dim": 8, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32, "num_experts": 16,
    "num_experts_per_tok": 4, "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [],
    "rms_norm_eps": 1e-6, "max_position_embeddings": 256, "tie_word_embeddings": False, "vocab_size": 128,
}


def qwen3_next_layer_types(config: dict) -> list:
    interval = config["full_attention_interval"]
    return ["linear_attention" if (i + 1) % interval else "full_attention" for i in range(config["num_hidden_layers"])]


def tiny_qwen3_next_tensors(config: dict, seed: int = 23) -> dict:
    """Seeded float32 tensors under transformers' names of every layer of
    ``config`` (every routed expert, whatever share a server of it holds), the
    embedding, the final norm and the head. Zero-centred norm vectors are
    drawn around 0 and the delta rule's output norm around 1, so a missing,
    misplaced or unfolded one shows; A and the step spread alpha over about
    0.3-0.99."""
    rng = np.random.RandomState(seed)
    h, hq, hkv, d = (config[k] for k in ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim"))
    hk, hv, d_k, d_v, taps = (config[k] for k in ("linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim",
                                                  "linear_value_head_dim", "linear_conv_kernel_dim"))
    m, ms = config["moe_intermediate_size"], config["shared_expert_intermediate_size"]
    routed = (config.get("expert_share") or {}).get("routed", config["num_experts"])
    normal = lambda *shape: (rng.standard_normal(shape) * 0.1).astype(np.float32)
    centred = lambda n: rng.uniform(-0.5, 0.5, n).astype(np.float32)
    tensors = {"model.embed_tokens.weight": normal(config["vocab_size"], h), "model.norm.weight": centred(h),
               "lm_head.weight": normal(config["vocab_size"], h)}
    for i, kind in enumerate(qwen3_next_layer_types(config)):
        p = f"model.layers.{i}."
        tensors.update({
            p + "input_layernorm.weight": centred(h), p + "post_attention_layernorm.weight": centred(h),
            p + "mlp.gate.weight": normal(routed, h) * 5.0,  # a router that prefers some experts, as a trained one does
            p + "mlp.shared_expert.gate_proj.weight": normal(ms, h), p + "mlp.shared_expert.up_proj.weight": normal(ms, h),
            p + "mlp.shared_expert.down_proj.weight": normal(h, ms), p + "mlp.shared_expert_gate.weight": normal(1, h) * 3.0,
        })
        for e in range(routed):
            q = p + f"mlp.experts.{e}."
            tensors.update({q + "gate_proj.weight": normal(m, h), q + "up_proj.weight": normal(m, h), q + "down_proj.weight": normal(h, m)})
        if kind == "full_attention":
            q = p + "self_attn."
            tensors.update({
                q + "q_proj.weight": normal(hq * 2 * d, h), q + "k_proj.weight": normal(hkv * d, h),
                q + "v_proj.weight": normal(hkv * d, h), q + "o_proj.weight": normal(h, hq * d),
                q + "q_norm.weight": centred(d), q + "k_norm.weight": centred(d),
            })
            continue
        q = p + "linear_attn."
        dt = np.exp(rng.uniform(np.log(0.001), np.log(0.1), hv))
        tensors.update({
            q + "in_proj_qkvz.weight": normal(2 * hk * d_k + 2 * hv * d_v, h), q + "in_proj_ba.weight": normal(2 * hv, h),
            q + "conv1d.weight": (rng.standard_normal((2 * hk * d_k + hv * d_v, 1, taps)) * 0.4).astype(np.float32),
            q + "A_log": np.log(rng.uniform(1, 16, hv)).astype(np.float32),
            q + "dt_bias": (dt + np.log(-np.expm1(-dt))).astype(np.float32),
            q + "norm.weight": rng.uniform(0.5, 1.5, d_v).astype(np.float32), q + "out_proj.weight": normal(h, hv * d_v),
        })
    return tensors


@_model_build_cache
def make_tiny_qwen3_next(tmpdir: str, *, held: int = 16, first: int = 0, head_dim: int = TINY_QWEN3_NEXT["head_dim"]) -> str:
    """A Qwen3-Next checkpoint at a toy size, written by hand under
    transformers' names (tests/test_qwen3_next.py loads the same tensors into
    transformers' own ``Qwen3NextDecoderLayer``): every routed expert is in the
    file, and a server of this directory holds ``held`` of the 16 from
    ``first`` on (``expert_share``; all of them by default). ``head_dim``: the
    full layers' (128 is a head of whole lanes, as the published 256 is)."""
    import json

    from safetensors.numpy import save_file

    config = dict(TINY_QWEN3_NEXT, head_dim=head_dim)
    if held != 16 or first:
        config.update(num_experts=held, expert_share={"routed": 16, "first": first})
    path = os.path.join(tmpdir, f"tiny-qwen3-next-{held}-{first}-{head_dim}")
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(config, f)
    save_file(tiny_qwen3_next_tensors(config), os.path.join(path, "model.safetensors"))
    return path


TINY_JAMBA = {  # the keys AI21-Jamba2-3B publishes, at a toy size: mamba and attention layers in turns, one kv head
    "model_type": "jamba", "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 1, "intermediate_size": 128,
    "num_hidden_layers": 4, "attn_layer_period": 2, "attn_layer_offset": 1, "expert_layer_period": 2, "expert_layer_offset": 1,
    "num_experts": 1, "num_experts_per_tok": 1, "hidden_act": "silu", "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_dt_rank": 8, "mamba_conv_bias": True, "mamba_proj_bias": False, "use_mamba_kernels": False, "sliding_window": None,
    "rms_norm_eps": 1e-6, "max_position_embeddings": 256, "tie_word_embeddings": True, "vocab_size": 128, "num_logits_to_keep": 1,
}


def jamba_layer_types(config: dict) -> list:
    period, offset = config["attn_layer_period"], config["attn_layer_offset"]
    return ["attention" if i % period == offset else "mamba" for i in range(config["num_hidden_layers"])]


def tiny_jamba_tensors(config: dict, seed: int = 31) -> dict:
    """Seeded float32 tensors under transformers' names of every layer of
    ``config``, the embedding and the final norm (the head is tied). Norm
    vectors, ``D``, ``A_log`` and both biases are drawn, not their
    initial values, so a missing, misplaced or turned one shows; the step's
    bias spreads the decays over about 0.2-0.999 a position."""
    rng = np.random.RandomState(seed)
    h, hq, hkv, m = (config[k] for k in ("hidden_size", "num_attention_heads", "num_key_value_heads", "intermediate_size"))
    d, inner = h // hq, config["mamba_expand"] * h
    n, taps, rank = (config[k] for k in ("mamba_d_state", "mamba_d_conv", "mamba_dt_rank"))
    normal = lambda *shape: (rng.standard_normal(shape) * 0.1).astype(np.float32)
    norm = lambda size: rng.uniform(0.5, 1.5, size).astype(np.float32)
    tensors = {"model.embed_tokens.weight": normal(config["vocab_size"], h), "model.final_layernorm.weight": norm(h)}
    for i, kind in enumerate(jamba_layer_types(config)):
        p = f"model.layers.{i}."
        tensors.update({
            p + "input_layernorm.weight": norm(h), p + "pre_ff_layernorm.weight": norm(h),
            p + "feed_forward.gate_proj.weight": normal(m, h), p + "feed_forward.up_proj.weight": normal(m, h),
            p + "feed_forward.down_proj.weight": normal(h, m),
        })
        if kind == "attention":
            q = p + "self_attn."
            tensors.update({q + "q_proj.weight": normal(hq * d, h), q + "k_proj.weight": normal(hkv * d, h),
                            q + "v_proj.weight": normal(hkv * d, h), q + "o_proj.weight": normal(h, hq * d)})
            continue
        q = p + "mamba."
        dt = np.exp(rng.uniform(np.log(0.001), np.log(0.1), inner))
        tensors.update({
            q + "in_proj.weight": normal(2 * inner, h), q + "x_proj.weight": normal(rank + 2 * n, inner),
            q + "dt_proj.weight": normal(inner, rank), q + "dt_proj.bias": (dt + np.log(-np.expm1(-dt))).astype(np.float32),
            q + "conv1d.weight": (rng.standard_normal((inner, 1, taps)) * 0.4).astype(np.float32), q + "conv1d.bias": normal(inner),
            q + "A_log": np.log(rng.uniform(1, 16, (inner, n))).astype(np.float32), q + "D": norm(inner),
            q + "out_proj.weight": normal(h, inner), q + "dt_layernorm.weight": norm(rank),
            q + "b_layernorm.weight": norm(n), q + "c_layernorm.weight": norm(n),
        })
    return tensors


@_model_build_cache
def make_tiny_jamba(tmpdir: str, **overrides) -> str:
    """A Jamba checkpoint at a toy size, written by hand under transformers'
    names (tests/test_jamba.py loads the same tensors into transformers' own
    decoder layers of both kinds)."""
    import json

    from safetensors.numpy import save_file

    config = {**TINY_JAMBA, **overrides}
    path = os.path.join(tmpdir, "tiny-jamba" + "".join(f"-{k}-{v}" for k, v in sorted(overrides.items())))
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(config, f)
    save_file(tiny_jamba_tensors(config), os.path.join(path, "model.safetensors"))
    return path


TINY_XING4_0 = {  # the keys Xing4.0-29B-A4B publishes, at a toy size: kinds D, D, S, S under a stream of four rows
    "model_type": "xing4_0", "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4, "num_hidden_layers": 4,
    "first_k_dense_replace": 2, "q_lora_rank": 24, "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "intermediate_size": 128, "moe_intermediate_size": 32, "n_routed_experts": 8, "num_experts_per_tok": 2, "n_shared_experts": 1,
    "n_group": 1, "topk_group": 1, "topk_method": "noaux_tc", "scoring_func": "sigmoid", "norm_topk_prob": True,
    "routed_scaling_factor": 2, "moe_layer_freq": 1, "ep_size": 1, "hidden_act": "silu", "attention_bias": False,
    "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30, "rms_norm_eps": 1e-5, "rope_theta": 10000,
    # a window of 32 puts the ramp's two correction dims inside the toy rotary's four frequencies
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 32, "type": "yarn"},
    "max_position_embeddings": 256, "tie_word_embeddings": False, "vocab_size": 128,
}


def tiny_xing4_0_tensors(config: dict, seed: int = 37) -> dict:
    """Seeded float32 tensors of every layer of ``config`` under transformers'
    ``deepseek_v3`` names for the sub-layers (a low-rank query) and
    ``attn_hc.*`` / ``mlp_hc.*`` for the two hyper-connections of a layer, the
    embedding, the final norm and the head. The three ``phi`` at a scale that
    makes the coefficients' logits of order 1 (a unit-RMS row of ``n*C``
    values against std 0.1), ``alpha`` and ``b`` drawn, so that the mixes move
    with the input and a missing or misplaced tensor shows."""
    tensors = tiny_deepseek_v3_tensors(config, seed)
    rng = np.random.RandomState(seed + 1)
    h, heads, dn, dr, rq, n = (config[k] for k in ("hidden_size", "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
                                                   "q_lora_rank", "hc_mult"))
    normal = lambda *shape: (rng.standard_normal(shape) * 0.1).astype(np.float32)
    for i in range(config["num_hidden_layers"]):
        p = f"model.layers.{i}."
        del tensors[p + "self_attn.q_proj.weight"]
        tensors.update({p + "self_attn.q_a_proj.weight": normal(rq, h) * 3, p + "self_attn.q_a_layernorm.weight": rng.uniform(0.5, 1.5, rq).astype(np.float32),
                        p + "self_attn.q_b_proj.weight": normal(heads * (dn + dr), rq) * 3})
        for wrap in ("attn_hc.", "mlp_hc."):
            for c, rows in (("pre", n), ("post", n), ("res", n * n)):
                tensors[p + wrap + f"phi_{c}.weight"] = normal(rows, n * h)
                tensors[p + wrap + f"alpha_{c}"] = rng.uniform(0.3, 0.9, 1).astype(np.float32)
                tensors[p + wrap + f"b_{c}"] = normal(n, n) if c == "res" else normal(n)
    return tensors


def make_tiny_xing4_0(tmpdir: str, **overrides) -> str:
    """An ``xing4_0`` checkpoint at a toy size, written by hand (transformers
    has no class for the model_type; tests/test_xing4_0.py loads the
    sub-layers' tensors into transformers' ``deepseek_v3`` modules)."""
    import json

    from safetensors.numpy import save_file

    config = {**TINY_XING4_0, **overrides}
    path = os.path.join(tmpdir, "tiny-xing4-0")
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(config, f)
    save_file(tiny_xing4_0_tensors(config), os.path.join(path, "model.safetensors"))
    return path


TINY_SMALLTHINKER = {  # the keys SmallThinker publishes, at a toy size: two periods of G L L L, a window of 8
    "model_type": "smallthinker", "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "moe_ffn_hidden_size": 32, "moe_num_primary_experts": 8, "moe_num_active_primary_experts": 3,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True, "num_hidden_layers": 8,
    "rope_layout": [0, 1, 1, 1] * 2, "sliding_window_layout": [0, 1, 1, 1] * 2, "sliding_window_size": 8,
    "rope_scaling": None, "rope_theta": 1500000, "rms_norm_eps": 1e-6, "max_position_embeddings": 256,
    "tie_word_embeddings": False, "vocab_size": 128,
}


def tiny_smallthinker_tensors(config: dict, seed: int = 41) -> dict:
    """Seeded float32 tensors under the HF names of every layer of ``config``, the embedding, the final norm and the
    head. Norm vectors are drawn, not ones, so a missing or misplaced one shows; the router's weights are large
    enough that its choice is no tie."""
    rng = np.random.RandomState(seed)
    h, hq, hkv, d = (config[k] for k in ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim"))
    m, n_experts = config["moe_ffn_hidden_size"], config["moe_num_primary_experts"]
    normal = lambda *shape: (rng.standard_normal(shape) * 0.1).astype(np.float32)
    norm = lambda n: rng.uniform(0.5, 1.5, n).astype(np.float32)
    tensors = {"model.embed_tokens.weight": normal(config["vocab_size"], h), "model.norm.weight": norm(h),
               "lm_head.weight": normal(config["vocab_size"], h)}
    for i in range(config["num_hidden_layers"]):
        p = f"model.layers.{i}."
        tensors.update({
            p + "input_layernorm.weight": norm(h), p + "post_attention_layernorm.weight": norm(h),
            p + "self_attn.q_proj.weight": normal(hq * d, h), p + "self_attn.k_proj.weight": normal(hkv * d, h),
            p + "self_attn.v_proj.weight": normal(hkv * d, h), p + "self_attn.o_proj.weight": normal(h, hq * d),
            p + "block_sparse_moe.primary_router.weight": normal(n_experts, h) * 5,
        })
        for e in range(n_experts):
            q = p + f"block_sparse_moe.experts.{e}."
            tensors.update({q + "gate.weight": normal(m, h), q + "up.weight": normal(m, h), q + "down.weight": normal(h, m)})
    return tensors


@_model_build_cache
def make_tiny_smallthinker(tmpdir: str, **overrides) -> str:
    """A SmallThinker checkpoint at a toy size, written by hand (the installed transformers has no class for
    ``smallthinker``)."""
    import json

    from safetensors.numpy import save_file

    config = {**TINY_SMALLTHINKER, **overrides}
    path = os.path.join(tmpdir, "tiny-smallthinker" + "".join(f"-{k}-{v}" for k, v in sorted(overrides.items())))
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(config, f)
    save_file(tiny_smallthinker_tensors(config), os.path.join(path, "model.safetensors"))
    return path


# ---------------------------------------------------------------------------
# what a lane holds for a span's blocks (server/span_cache.py), as the tests ask a backend for it


def published_span_cache(name: str, tmp_path):
    """``(SpanCache, blocks)`` of the benchmark's configuration ``perf/configs/<name>.json`` on shapes alone (no weights, no
    backend), bfloat16 pages as a chip serves them."""
    import json
    from pathlib import Path

    import jax.numpy as jnp

    from perf.config import load as load_config
    from petals_tpu.models.registry import span_runs
    from petals_tpu.server.from_pretrained import get_block_config
    from petals_tpu.server.span_cache import SpanCache

    config = load_config(Path(__file__).resolve().parents[1] / "perf" / "configs" / f"{name}.json", name)
    (Path(tmp_path) / "config.json").write_text(json.dumps(config["config"]))
    family, cfg = get_block_config(str(tmp_path))
    blocks = config["server_args"]["num_blocks"]
    return SpanCache(family, cfg, span_runs(family.span_kinds(cfg, 0, blocks)), cache_dtype=jnp.bfloat16), blocks


def lane_pools(backend, n_pages: int, page_size: int, n_lanes: int = 1, *, start: int = 0, end=None) -> tuple:
    """``(the page pools' descriptors, those of the pools beside them)`` of a paged lane pool over blocks [start, end)
    of ``backend``'s span (default: to its end): ``SpanCache.pool_descriptors``, split where the step programs split it."""
    cache = backend.cache
    descs = cache.pool_descriptors(n_pages, page_size, n_lanes, start, backend.n_blocks if end is None else end)
    n = len(descs) - cache.pools_beside_pages
    return descs[:n], descs[n:]


def counted(backend, n_lanes: int, max_pages: int, page_size: int, last, chunk=None, held=None) -> dict:
    """What ``LanePool.count_step`` adds for one step of a pool of these shapes whose first lanes are live at the
    positions ``last`` (the rest idle), with a mixed step's ``chunk`` (first position, tokens) on lane 0; the lanes hold
    ``held`` pages each (default: none)."""
    import numpy as np

    pool = backend.cache.lane_pool(n_lanes, max_pages, page_size)
    positions = np.full(n_lanes, pool.max_length, np.int32)
    positions[: len(last)] = last
    stats = pool.new_stats()
    pool.count_step(stats, positions, np.zeros(n_lanes, np.int64) if held is None else np.asarray(held, np.int64),
                    chunk=None if chunk is None else (0, *chunk))
    return stats
