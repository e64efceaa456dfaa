"""Preemptive scheduling vs fail-and-retry on an oversubscribed page pool.

Before the session scheduler, a full page pool ended a session hard: the
step raised AllocationFailed and the client's only recourse was to release
the lane, re-admit, and rebuild its context (the classic Petals retry
path). The scheduler instead suspends an IDLE victim lane to the host-RAM
swap tier and transparently resumes it on its next step, so oversubscribed
sessions stall briefly rather than dying.

This row drives BOTH strategies over the real DecodeBatcher machinery (no
RPC) at 2x oversubscription — N_SESSIONS sessions whose peak page demand is
twice the pool — with an INTERACTIVE load shape: each session decodes
DECODE_TOKENS in bursts of BURST_TOKENS separated by THINK_S of client
think-time (the chat pattern Petals actually serves). Think-time is what
makes the comparison meaningful: a thinking session holds its pages while
doing nothing — exactly the hoarding the swap tier exists to break — and
an all-hot workload at 2x oversubscription just thrashes any arbiter.
Reports aggregate decode tok/s plus mean/p99 per-token stall:

- "preempt": swap tier enabled (lru policy). Expected: zero
  AllocationFailed, every stall bounded by one swap-out + swap-in.
- "retry": swap disabled. On AllocationFailed the session releases its
  lane, re-admits, and re-RUNS its whole prefill (through the real
  mixed-step prefill path) before continuing — the recovery cost a real
  client pays when its server-side KV is dropped.

Unlike the throughput rows this one runs SCALED-DOWN block shapes: the
quantity under test is scheduling dynamics (stalls, preemptions, retries),
and the churning batch compositions would otherwise spend the whole run
recompiling 7B-shape programs. Runs on whatever backend jax provides (CPU
included), like the other composition rows.
"""

from __future__ import annotations

import asyncio
import os
import random
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

N_BLOCKS = 4  # enough blocks to make the per-step program non-trivial
MAX_LENGTH = 512
PAGE_SIZE = 64
SESSION_TOKENS = 384  # mean prefill context per session (~6 pages)
DECODE_TOKENS = 24
N_SESSIONS = 8
OVERSUBSCRIPTION = 2  # pool holds 1/2 of the sessions' peak page demand
PACING_S = 0.01  # client-side gap between steps (sampling, network turnaround)
BURST_TOKENS = 8  # tokens decoded per interactive burst
THINK_S = (0.25, 0.45)  # client think-time between bursts (uniform range)


def _session_tokens(i: int) -> int:
    """Per-session prefill length, staggered around SESSION_TOKENS. Identical
    page-aligned contexts make every session cross a page boundary on the
    SAME decode step — in retry mode all of them then fail, release, and
    re-soak the pool in lockstep, a stable livelock no real workload has."""
    return SESSION_TOKENS - 28 + 8 * i


async def _rebuild(batcher, hidden, n_tokens: int) -> int:
    """Admit a lane, allocate ``n_tokens`` of context, and RUN the prefill
    for it — the fail-and-retry client's full recovery loop. The compute is
    charged (via the real mixed-step prefill path), not just the page
    allocation: a session whose KV was dropped must re-run every lost token
    through the span."""
    import numpy as np

    from petals_tpu.server.memory_cache import AllocationFailed

    while True:
        try:
            lane = await batcher.acquire_lane(timeout=1.0)
        except (AllocationFailed, asyncio.TimeoutError):
            await asyncio.sleep(random.uniform(0.02, 0.15))
            continue
        if n_tokens <= 1:
            return lane
        try:
            await batcher.prepare_write(lane, 0, n_tokens, timeout=1.0)
            seq = np.broadcast_to(hidden, (1, n_tokens, hidden.shape[-1]))
            await batcher.prefill_lane(lane, seq, 0)
            return lane
        except (AllocationFailed, asyncio.TimeoutError):
            batcher.release_lane(lane)
            # jittered backoff: deterministic sleeps keep failing sessions
            # synchronized, re-fighting over the same pages forever
            await asyncio.sleep(random.uniform(0.02, 0.15))


async def _session(batcher, hidden, stalls: list, n_tokens: int, *, retry: bool) -> dict:
    """One paced decode session; returns its failure/retry counts. Stall =
    wall time from 'client wants the next token' to 'token arrived',
    including any swap-in (preempt mode) or release/re-admit/re-prefill
    recovery (retry mode)."""
    from petals_tpu.server.memory_cache import AllocationFailed

    lane = await _rebuild(batcher, hidden, n_tokens)
    pos, retries, failures = n_tokens, 0, 0
    for tok in range(DECODE_TOKENS):
        if tok > 0 and tok % BURST_TOKENS == 0:
            # end of a burst: the client reads the output and types — the
            # session holds its context but steps nothing
            await asyncio.sleep(random.uniform(*THINK_S))
        else:
            await asyncio.sleep(PACING_S)
        t0 = time.perf_counter()
        while True:
            try:
                await batcher.step(lane, hidden, pos)
                break
            except AllocationFailed:
                failures += 1
                if not retry:
                    raise
                retries += 1
                # the session's server-side KV is gone: release what's left,
                # re-admit, and re-run the whole prefill so far
                batcher.release_lane(lane)
                lane = await _rebuild(batcher, hidden, pos)
        stalls.append(time.perf_counter() - t0)
        pos += 1
    batcher.release_lane(lane)
    return {"retries": retries, "failures": failures}


async def _run_mode(backend, memory_cache, queue, hidden, n_pages, *, retry: bool):
    from petals_tpu.server.batching import DecodeBatcher

    batcher = DecodeBatcher(
        backend, memory_cache, queue,
        n_lanes=N_SESSIONS, max_length=MAX_LENGTH,
        page_size=PAGE_SIZE, n_pages=n_pages,
        # each strategy gets its natural allocation patience: retry WANTS
        # prompt failure (that is the strategy), preemption waits for a
        # victim to go idle between steps
        alloc_timeout=0.3 if retry else 10.0,
        swap_host_bytes=0 if retry else 1 << 29,
    )
    stalls: list = []
    t0 = time.perf_counter()
    results = await asyncio.gather(
        *(_session(batcher, hidden, stalls, _session_tokens(i), retry=retry)
          for i in range(N_SESSIONS))
    )
    wall = time.perf_counter() - t0
    summary = batcher._scheduler.summary()
    await batcher.close()

    import numpy as np

    total_tokens = N_SESSIONS * DECODE_TOKENS
    return {
        "tok_s": round(total_tokens / wall, 2),
        "stall_mean_ms": round(float(np.mean(stalls)) * 1e3, 1),
        "stall_p99_ms": round(float(np.percentile(stalls, 99)) * 1e3, 1),
        "retries": sum(r["retries"] for r in results),
        "alloc_failures": sum(r["failures"] for r in results),
        "preemptions": summary["preemptions"],
        "swap_ins": summary["swap_ins"],
    }


async def _run() -> dict:
    import jax.numpy as jnp
    import numpy as np

    import bench as _bench  # random param builder (defs only)
    from petals_tpu.models.llama.config import LlamaBlockConfig
    from petals_tpu.models.registry import get_family
    from petals_tpu.server.backend import TransformerBackend
    from petals_tpu.server.memory_cache import MemoryCache
    from petals_tpu.server.task_queue import PriorityTaskQueue

    cfg = LlamaBlockConfig(
        hidden_size=512,
        num_attention_heads=8,
        num_key_value_heads=8,
        head_dim=64,
        intermediate_size=1024,
        num_hidden_layers=N_BLOCKS,
        rms_norm_eps=1e-5,
        vocab_size=1024,
    )
    family = get_family("llama")
    dtype = jnp.bfloat16

    t0 = time.perf_counter()
    params = _bench.random_params(cfg, N_BLOCKS, dtype)
    init_s = time.perf_counter() - t0

    total_peak_pages = sum(
        -(-(_session_tokens(i) + DECODE_TOKENS) // PAGE_SIZE)
        for i in range(N_SESSIONS)
    )
    n_pages = total_peak_pages // OVERSUBSCRIPTION

    memory_cache = MemoryCache(None)
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
        preempt = await _run_mode(
            backend, memory_cache, queue, hidden, n_pages, retry=False
        )
        retry = await _run_mode(
            backend, memory_cache, queue, hidden, n_pages, retry=True
        )
    finally:
        queue.shutdown()

    return {
        "label": "e2e_preemption_oversubscription",
        "n_blocks": N_BLOCKS,
        "sessions": N_SESSIONS,
        "page_size": PAGE_SIZE,
        "n_pages": n_pages,
        "oversubscription": OVERSUBSCRIPTION,
        "decode_tokens": DECODE_TOKENS,
        "preempt": preempt,
        "retry": retry,
        "tok_s_ratio": round(preempt["tok_s"] / max(retry["tok_s"], 1e-9), 2),
        "p99_stall_ratio": round(
            retry["stall_p99_ms"] / max(preempt["stall_p99_ms"], 1e-9), 2
        ),
        "param_init_s": round(init_s, 1),
    }


def run_bench() -> dict:
    return asyncio.run(_run())


if __name__ == "__main__":
    from petals_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    import json

    print(json.dumps(run_bench(), indent=2))
