"""Continuous batching under multi-host lockstep: the round-5 composition
bench (VERDICT r4 next-round #3).

Spawns a REAL 2-process tp span (run_server leader + run_worker, CPU devices,
loopback) and drives N concurrent decode sessions through the RPC stack from
one event loop, the sends of each round issued before any reply is awaited so
the leader's lane pool actually coalesces. Reports aggregate decode
throughput, the coalescing evidence (max_batch / mean batch), and the serial
baseline (same sessions, one at a time) for the speedup ratio.

Runs entirely on CPU subprocesses (children get JAX_PLATFORMS=cpu before
Python starts, so none of them takes a chip) — it measures COMPOSITION
overhead (broadcast + collectives + batching), not chip throughput.
"""

from __future__ import annotations

import asyncio
import os
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

N_SESSIONS = 4
N_STEPS = 24
PREFILL = 8


async def _drive(addr: str, model: str, *, concurrent: bool) -> dict:
    # shared protocol driver (tests/utils.py) — one definition of the
    # session-open/prefill/coalescing-round wire exchange
    from tests.utils import drive_coalescing_sessions

    elapsed, info = await drive_coalescing_sessions(
        addr, model, n_sessions=N_SESSIONS, n_steps=N_STEPS,
        prefill=PREFILL, concurrent=concurrent, seed=0,
    )
    return {
        "tok_s": N_SESSIONS * N_STEPS / elapsed,
        "stats": info.get("continuous_batching") or {},
    }


def run_bench(model: str | None = None) -> dict:
    from tests.utils import make_tiny_llama, spawn_multihost_pair, stop_multihost_pair

    if model is None:
        model = make_tiny_llama(tempfile.mkdtemp())
    # shared spawn helper (tests/utils.py): one definition of the leader
    # announce protocol + CPU child env for tests AND benchmarks
    leader, worker, addr = spawn_multihost_pair(
        model, leader_args=("--throughput", "7.0")
    )
    try:
        conc = asyncio.run(_drive(addr, model, concurrent=True))
        serial = asyncio.run(_drive(addr, model, concurrent=False))
        stats = conc["stats"]
        return {
            "sessions": N_SESSIONS,
            "steps_per_session": N_STEPS,
            "aggregate_tok_s_batched": round(conc["tok_s"], 2),
            "aggregate_tok_s_serial": round(serial["tok_s"], 2),
            "batched_vs_serial": round(conc["tok_s"] / max(serial["tok_s"], 1e-9), 2),
            "max_batch": stats.get("max_batch"),
            "batched_steps": stats.get("batched_steps"),
            "batched_tokens": stats.get("batched_tokens"),
        }
    finally:
        stop_multihost_pair(leader, worker, timeout=20)


if __name__ == "__main__":
    from petals_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    import json

    print(json.dumps(run_bench(), indent=2))
