"""Paged KV cache vs the dense lane pool, at a FIXED cache byte budget.

The dense lane pool charges every admitted session ``max_length`` tokens of
KV up front, so the budget caps concurrency at n_lanes regardless of how
much context sessions actually use. The paged pool (ops/paged_attention.py)
charges one page at admission and grows page-by-page, so the same bytes
admit as many sessions as their LIVE context fits. This row measures both
halves of that trade on the real DecodeBatcher machinery (no RPC):

1. admission capacity — sessions holding SESSION_TOKENS of context each,
   admitted until the pool pushes back, dense vs paged at the same budget
   (the paper's concurrency claim; expected ~max_length/SESSION_TOKENS x);
2. single-stream decode tok/s — the paged identity fast path compiles to
   the dense program modulo reshapes, so per-token latency must stay within
   a few percent (the "paging costs nothing when you don't need it" claim).

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
MAX_LENGTH = 1024  # dense lane length (the up-front admission charge)
SESSION_TOKENS = 128  # live context per admitted session
PAGE_SIZE = 64
DENSE_LANES = 4  # the byte budget = what 4 dense lanes cost
WARM_STEPS = 3
MEASURE_STEPS = 16


async def _admit_sessions(batcher, n_tokens: int, timeout: float = 0.5) -> list:
    """Admit sessions each holding ``n_tokens`` of context until the lane
    list or the page pool pushes back; returns the admitted lanes.
    (prepare_write is a no-op on a dense batcher — there, the whole lane was
    already charged at acquire time, which is exactly the point.)"""
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

    cfg = _bench.llama7b_cfg()
    family = get_family("llama")
    dtype = jnp.bfloat16

    t0 = time.perf_counter()
    params = _bench.random_params(cfg, N_BLOCKS, dtype)
    init_s = time.perf_counter() - t0

    hkv = getattr(cfg, "num_key_value_heads", cfg.num_attention_heads)
    token_bytes = 2 * N_BLOCKS * hkv * cfg.head_dim * jnp.dtype(dtype).itemsize
    budget_tokens = DENSE_LANES * MAX_LENGTH  # the fixed cache budget
    n_pages = budget_tokens // PAGE_SIZE
    paged_lanes = budget_tokens // SESSION_TOKENS

    memory_cache = MemoryCache(4 * budget_tokens * token_bytes)  # both pools + slack
    backend = TransformerBackend(
        family, cfg, params,
        first_block=0, n_blocks=N_BLOCKS,
        memory_cache=memory_cache, compute_dtype=dtype,
    )
    queue = PriorityTaskQueue()
    queue.start()
    rng = np.random.RandomState(0)
    hidden = rng.randn(1, 1, cfg.hidden_size).astype(np.float32) * 0.02

    try:
        # --- dense: admission is capped by lanes == budget / max_length
        dense = DecodeBatcher(
            backend, memory_cache, queue,
            n_lanes=DENSE_LANES, max_length=MAX_LENGTH,
        )
        dense_lanes = await _admit_sessions(dense, SESSION_TOKENS)
        sessions_dense = len(dense_lanes)
        for lane in dense_lanes:
            dense.release_lane(lane)
        dense_tok_s = await _timed_single_stream(dense, hidden)
        await dense.close()

        # --- paged capacity: same bytes as a page pool, lanes sized to the
        # budget at SESSION_TOKENS each; admission only (no stepping — the
        # pooled step's cost scales with the static lane count, so stepping
        # here would measure lane count, not paging)
        paged_cap = DecodeBatcher(
            backend, memory_cache, queue,
            n_lanes=paged_lanes, max_length=MAX_LENGTH,
            page_size=PAGE_SIZE, n_pages=n_pages,
        )
        paged_lanes_used = await _admit_sessions(paged_cap, SESSION_TOKENS)
        sessions_paged = len(paged_lanes_used)
        paged_stats = paged_cap.paged_summary()
        for lane in paged_lanes_used:
            paged_cap.release_lane(lane)
        await paged_cap.close()

        # --- paged decode parity: SAME lane count as dense, same byte
        # budget, so the only difference is the paging machinery (the
        # identity fast path should compile to the dense program)
        paged = DecodeBatcher(
            backend, memory_cache, queue,
            n_lanes=DENSE_LANES, max_length=MAX_LENGTH,
            page_size=PAGE_SIZE, n_pages=n_pages,
        )
        paged_tok_s = await _timed_single_stream(paged, hidden)
        await paged.close()
    finally:
        queue.shutdown()

    return {
        "label": "e2e_paged_decode",
        "n_blocks": N_BLOCKS,
        "budget_mib": round(budget_tokens * token_bytes / 2**20, 1),
        "session_tokens": SESSION_TOKENS,
        "page_size": PAGE_SIZE,
        "sessions_dense": sessions_dense,
        "sessions_paged": sessions_paged,
        "session_ratio": round(sessions_paged / max(sessions_dense, 1), 2),
        "dense_tok_s": round(dense_tok_s, 2),
        "paged_tok_s": round(paged_tok_s, 2),
        "tok_s_ratio": round(paged_tok_s / dense_tok_s, 3),
        "pages_allocated": (paged_stats or {}).get("pages_allocated"),
        "param_init_s": round(init_s, 1),
    }


def run_bench() -> dict:
    return asyncio.run(_run())


if __name__ == "__main__":
    from petals_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    import json

    print(json.dumps(run_bench(), indent=2))
