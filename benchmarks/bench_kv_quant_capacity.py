"""Quantized paged KV pool vs the fp paged pool, at a FIXED cache byte budget.

The fp paged pool already decoupled admission from max_length (see
bench_paged_decode.py); the quantized pool (ops/paged_attention.py codecs +
in-kernel dequant in ops/paged_flash_attention.py) shrinks what each RESIDENT
token costs: int8 stores d code bytes + 4 scale bytes per head row, nf4a
packs two codes per byte (d/2 + 4). This row measures both halves of that
trade on the real DecodeBatcher machinery (no RPC):

1. admission capacity — sessions holding SESSION_TOKENS of live context
   each, admitted until the page pool pushes back, fp vs nf4a at the same
   byte budget (the in-kernel-dequant capacity claim; >=3.5x at head_dim 128
   against a bf16 pool, asserted because it is deterministic arithmetic
   exercised through the real 4-descriptor allocator);
2. single-stream decode tok/s — dequant rides inside the fused kernel (or
   its XLA twin), so per-token latency must stay within ~10% of the fp pool
   (reported, not asserted: on CPU the walls are structural — the on-chip
   verdict is not measured yet, ROADMAP S3).

Runs on whatever backend jax provides (CPU included), like the other
composition rows: overhead there, chip throughput on TPU.
"""

from __future__ import annotations

import asyncio
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

N_BLOCKS = 4  # enough blocks to make the per-step program non-trivial
MAX_LENGTH = 512  # per-lane table capacity (pages bind first, not this)
SESSION_TOKENS = 64  # live context per admitted session (= one page)
PAGE_SIZE = 64
BUDGET_FP_PAGES = 24  # the byte budget = what 24 fp pages cost
KV_QUANT = "nf4a"
WARM_STEPS = 3
MEASURE_STEPS = 16


async def _admit_sessions(batcher, n_tokens: int, timeout: float = 0.5) -> list:
    """Admit sessions each holding ``n_tokens`` of context until the lane
    list or the page pool pushes back; returns the admitted lanes."""
    from petals_tpu.server.memory_cache import AllocationFailed

    admitted = []
    while True:
        try:
            lane = await batcher.acquire_lane(timeout=timeout)
        except (AllocationFailed, asyncio.TimeoutError):
            return admitted
        try:
            await batcher.prepare_write(lane, 0, n_tokens, timeout=timeout)
        except (AllocationFailed, asyncio.TimeoutError):
            batcher.release_lane(lane)
            return admitted
        admitted.append(lane)


async def _timed_single_stream(batcher, hidden) -> float:
    """tok/s of one session decoding alone (warm steps excluded)."""
    lane = await batcher.acquire_lane(timeout=30)
    try:
        pos = 0
        for _ in range(WARM_STEPS):
            await batcher.step(lane, hidden, pos)
            pos += 1
        t0 = time.perf_counter()
        for _ in range(MEASURE_STEPS):
            await batcher.step(lane, hidden, pos)
            pos += 1
        return MEASURE_STEPS / (time.perf_counter() - t0)
    finally:
        batcher.release_lane(lane)


async def _run() -> dict:
    import jax.numpy as jnp
    import numpy as np

    import bench as _bench  # 7B-shape cfg + random param builder (defs only)
    from petals_tpu.models.registry import get_family
    from petals_tpu.server.backend import TransformerBackend
    from petals_tpu.server.batching import DecodeBatcher
    from petals_tpu.server.memory_cache import MemoryCache
    from petals_tpu.server.task_queue import PriorityTaskQueue
    from petals_tpu.telemetry import instruments as tm

    cfg = _bench.llama7b_cfg()
    family = get_family("llama")
    dtype = jnp.bfloat16

    t0 = time.perf_counter()
    params = _bench.random_params(cfg, N_BLOCKS, dtype)
    init_s = time.perf_counter() - t0

    def make_backend(kind):
        return TransformerBackend(
            family, cfg, params,
            first_block=0, n_blocks=N_BLOCKS,
            memory_cache=MemoryCache(None), compute_dtype=dtype,
            kv_quant_type=kind,
        )

    backend_fp = make_backend("none")
    backend_q = make_backend(KV_QUANT)
    fp_token = backend_fp.cache_bytes_per_token()  # bf16 pool, wire == HBM
    q_token = backend_q.kv_bytes_per_token()  # codes + scales, wire bytes
    capacity_ratio = fp_token / q_token
    assert capacity_ratio >= 3.5, (
        f"{KV_QUANT} pool must be >=3.5x denser than the bf16 pool per "
        f"token: fp={fp_token}B quant={q_token}B"
    )
    budget = BUDGET_FP_PAGES * fp_token * PAGE_SIZE
    pages_fp = budget // (fp_token * PAGE_SIZE)
    pages_q = budget // (q_token * PAGE_SIZE)

    queue = PriorityTaskQueue()
    queue.start()
    rng = np.random.RandomState(0)
    hidden = rng.randn(1, 1, cfg.hidden_size).astype(np.float32) * 0.02

    try:
        async def admitted(backend, n_pages):
            batcher = DecodeBatcher(
                backend, backend.memory_cache, queue,
                n_lanes=int(n_pages) + 2, max_length=MAX_LENGTH,
                page_size=PAGE_SIZE, n_pages=int(n_pages),
            )
            lanes = await _admit_sessions(batcher, SESSION_TOKENS)
            n = len(lanes)
            for lane in lanes:
                batcher.release_lane(lane)
            await batcher.close()
            return n

        sessions_fp = await admitted(backend_fp, pages_fp)
        sessions_q = await admitted(backend_q, pages_q)
        assert sessions_q >= 3.5 * sessions_fp, (
            f"fixed-budget admission: {KV_QUANT} admitted {sessions_q} vs "
            f"fp {sessions_fp} — expected >=3.5x"
        )

        async def timed(backend):
            batcher = DecodeBatcher(
                backend, backend.memory_cache, queue,
                n_lanes=2, max_length=MAX_LENGTH, page_size=PAGE_SIZE,
            )
            tok_s = await _timed_single_stream(batcher, hidden)
            await batcher.close()
            return tok_s

        fp_tok_s = await timed(backend_fp)
        anomalies_before = sum(
            c.value for _v, c in tm.COMPILE_ANOMALIES.children()
        )
        q_tok_s = await timed(backend_q)
        anomalies = sum(
            c.value for _v, c in tm.COMPILE_ANOMALIES.children()
        ) - anomalies_before
        assert anomalies == 0, (
            f"quantized-pool decode caused {anomalies} post-warmup recompile "
            f"anomalies"
        )
    finally:
        queue.shutdown()

    return {
        "label": "e2e_kv_quant_capacity",
        "kv_quant": KV_QUANT,
        "n_blocks": N_BLOCKS,
        "budget_mib": round(budget / 2**20, 1),
        "session_tokens": SESSION_TOKENS,
        "page_size": PAGE_SIZE,
        "bytes_per_token_fp": int(fp_token),
        "bytes_per_token_quant": int(q_token),
        "capacity_ratio": round(capacity_ratio, 2),
        "sessions_fp": sessions_fp,
        "sessions_quant": sessions_q,
        "session_ratio": round(sessions_q / max(sessions_fp, 1), 2),
        "fp_tok_s": round(fp_tok_s, 2),
        "quant_tok_s": round(q_tok_s, 2),
        "tok_s_ratio": round(q_tok_s / fp_tok_s, 3),
        "post_warmup_compile_anomalies": anomalies,
        "param_init_s": round(init_s, 1),
    }


def run_bench() -> dict:
    return asyncio.run(_run())


if __name__ == "__main__":
    from petals_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    import json

    print(json.dumps(run_bench(), indent=2))
