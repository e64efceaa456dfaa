"""Decode throughput retention during a long prefill: mixed step vs the
exclusive-chunk path, on the real DecodeBatcher machinery (no RPC).

A 2k-token prefill lands while other sessions are decoding. The exclusive
path (Sarathi-style chunks) lets decode steps run BETWEEN chunk tasks but
pays lane extract/insert round-trips and stalls decode for each chunk's
duration. The mixed step folds a bucketed prefill chunk INTO the batched
decode program, so every tick advances all decoding lanes AND the prefill.
This row measures what decode sessions actually see:

1. isolated_tok_s — aggregate decode tok/s with no prefill in flight;
2. mixed_tok_s / excl_tok_s — the same sessions' aggregate tok/s measured
   over the window a 2048-token prefill is in flight, via prefill_lane
   (mixed) and run_exclusive_chunks (exclusive);
3. retention = during / isolated for each path, plus the prefill's own
   completion time (the tentpole's decode-never-starves claim is
   retention_mixed; the acceptance bar is >= 0.70 on a real chip).

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

N_BLOCKS = 2  # enough to make the per-step program non-trivial
MAX_LENGTH = 2560  # lane length: 2048 prefill + decode headroom (40 pages)
PAGE_SIZE = 64
N_LANES = 4  # 2 decode + 1 prefill + 1 spare
PREFILL_TOKENS = 2048
PREFILL_BUDGET = 128  # mixed-step budget: 16 ticks for the 2k prefill
CHUNK_TOKENS = 128  # exclusive chunks sized to match the mixed budget
DECODE_SESSIONS = 2
DECODE_CONTEXT = 128  # live context each decode session holds
WARM_STEPS = 3
MEASURE_STEPS = 12


async def _decode_until(batcher, lanes, positions, hidden, stop_event) -> tuple:
    """All decode sessions step concurrently until ``stop_event`` is set;
    returns (total tokens completed, elapsed seconds)."""

    async def one(i):
        n = 0
        while not stop_event.is_set():
            await batcher.step(lanes[i], hidden, positions[i])
            positions[i] += 1
            n += 1
        return n

    t0 = time.perf_counter()
    counts = await asyncio.gather(*[one(i) for i in range(len(lanes))])
    return sum(counts), time.perf_counter() - t0


async def _timed_decode(batcher, lanes, positions, hidden) -> float:
    """Aggregate decode tok/s with nothing else in flight."""
    for _ in range(WARM_STEPS):
        await asyncio.gather(*[
            _step_one(batcher, lanes, positions, hidden, i)
            for i in range(len(lanes))
        ])
    t0 = time.perf_counter()
    for _ in range(MEASURE_STEPS):
        await asyncio.gather(*[
            _step_one(batcher, lanes, positions, hidden, i)
            for i in range(len(lanes))
        ])
    return len(lanes) * MEASURE_STEPS / (time.perf_counter() - t0)


async def _step_one(batcher, lanes, positions, hidden, i):
    await batcher.step(lanes[i], hidden, positions[i])
    positions[i] += 1


def _chunk_fns(backend, prefill, plan):
    """Exclusive-path chunk closures, exactly as the handler builds them."""
    import numpy as np

    fns, off = [], 0
    for clen in plan:
        def fn(kv, temp, chunk=prefill[:, off : off + clen], pos=off):
            out, kv2 = backend.inference_step(chunk, kv, pos, handles=temp)
            return np.asarray(out), kv2
        fns.append(fn)
        off += clen
    return fns


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
    n_pages = N_LANES * (MAX_LENGTH // PAGE_SIZE)

    memory_cache = MemoryCache(4 * n_pages * PAGE_SIZE * token_bytes)
    backend = TransformerBackend(
        family, cfg, params,
        first_block=0, n_blocks=N_BLOCKS,
        memory_cache=memory_cache, compute_dtype=dtype,
    )
    # size the exclusive chunks to the mixed budget, apples to apples
    while True:
        plan = backend.chunk_plan(
            1, PREFILL_TOKENS, kv_buf_len=MAX_LENGTH, page_size=PAGE_SIZE
        )
        if max(plan) <= CHUNK_TOKENS or backend.max_chunk_size_bytes < 4096:
            break
        backend.max_chunk_size_bytes //= 2

    queue = PriorityTaskQueue()
    queue.start()
    rng = np.random.RandomState(0)
    hidden = rng.randn(1, 1, cfg.hidden_size).astype(np.float32) * 0.02
    ctx = rng.randn(1, DECODE_CONTEXT, cfg.hidden_size).astype(np.float32) * 0.02
    prefill = rng.randn(1, PREFILL_TOKENS, cfg.hidden_size).astype(np.float32) * 0.02

    batcher = DecodeBatcher(
        backend, memory_cache, queue,
        n_lanes=N_LANES, max_length=MAX_LENGTH,
        page_size=PAGE_SIZE, n_pages=n_pages,
        prefill_token_budget=PREFILL_BUDGET,
    )
    try:
        # decode sessions, each seeded with DECODE_CONTEXT tokens of context
        lanes, positions = [], []
        for _ in range(DECODE_SESSIONS):
            lane = await batcher.acquire_lane(timeout=60)
            await batcher.prefill_lane(lane, ctx, 0)
            lanes.append(lane)
            positions.append(DECODE_CONTEXT)

        # warm every program the timed sections hit: the mixed step at the
        # PREFILL_BUDGET bucket, the decode-only step, and the exclusive
        # extract/chunk/insert cycle
        warm = await batcher.acquire_lane(timeout=60)
        await batcher.prefill_lane(warm, prefill[:, :PREFILL_BUDGET], 0)
        warm_plan = backend.chunk_plan(
            1, CHUNK_TOKENS * 2, kv_buf_len=MAX_LENGTH, page_size=PAGE_SIZE,
            start=PREFILL_BUDGET,
        )
        await batcher.run_exclusive_chunks(
            warm,
            _chunk_fns(backend, prefill[:, : CHUNK_TOKENS * 2], warm_plan),
            write_range=(PREFILL_BUDGET, PREFILL_BUDGET + CHUNK_TOKENS * 2),
        )
        batcher.release_lane(warm)

        isolated_tok_s = await _timed_decode(batcher, lanes, positions, hidden)

        # --- mixed: the 2k prefill rides the batched step via prefill_lane
        lane_p = await batcher.acquire_lane(timeout=60)
        stop = asyncio.Event()

        async def mixed_prefill():
            t0 = time.perf_counter()
            await batcher.prefill_lane(lane_p, prefill, 0)
            stop.set()
            return time.perf_counter() - t0

        pf_task = asyncio.create_task(mixed_prefill())
        toks, window = await _decode_until(batcher, lanes, positions, hidden, stop)
        mixed_prefill_s = await pf_task
        mixed_tok_s = toks / window
        batcher.release_lane(lane_p)

        # --- exclusive: the same prefill through run_exclusive_chunks
        lane_p = await batcher.acquire_lane(timeout=60)
        stop = asyncio.Event()

        async def excl_prefill():
            t0 = time.perf_counter()
            await batcher.run_exclusive_chunks(
                lane_p, _chunk_fns(backend, prefill, plan),
                write_range=(0, PREFILL_TOKENS),
            )
            stop.set()
            return time.perf_counter() - t0

        pf_task = asyncio.create_task(excl_prefill())
        toks, window = await _decode_until(batcher, lanes, positions, hidden, stop)
        excl_prefill_s = await pf_task
        excl_tok_s = toks / window
        batcher.release_lane(lane_p)

        stats = dict(batcher.stats)
    finally:
        await batcher.close()
        queue.shutdown()

    return {
        "label": "e2e_mixed_prefill_decode",
        "n_blocks": N_BLOCKS,
        "prefill_tokens": PREFILL_TOKENS,
        "prefill_budget": PREFILL_BUDGET,
        "chunk_tokens": int(max(plan)),
        "decode_sessions": DECODE_SESSIONS,
        "isolated_tok_s": round(isolated_tok_s, 2),
        "mixed_tok_s": round(mixed_tok_s, 2),
        "excl_tok_s": round(excl_tok_s, 2),
        "retention_mixed": round(mixed_tok_s / isolated_tok_s, 3),
        "retention_excl": round(excl_tok_s / isolated_tok_s, 3),
        "mixed_prefill_s": round(mixed_prefill_s, 2),
        "excl_prefill_s": round(excl_prefill_s, 2),
        "mixed_steps": stats.get("mixed_steps"),
        "exclusive_chunks": stats.get("exclusive_chunks"),
        "param_init_s": round(init_s, 1),
    }


def run_bench() -> dict:
    return asyncio.run(_run())


if __name__ == "__main__":
    from petals_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    import json

    print(json.dumps(run_bench(), indent=2))
