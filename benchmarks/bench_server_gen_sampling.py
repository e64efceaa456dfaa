"""Batched multi-session server-side SAMPLING generation on the lane pool.

Companion row to bench.py's e2e_server_gen (same 7B-shaped span, same wire):
N concurrent sessions each ask the server for 32-token sampled chunks
(temperature/top-k/top-p warping compiled into the decode loop, per-session
PRNG seed), and every token of every session advances through ONE compiled
pooled-gen program over the shared DecodeBatcher lanes. Reports aggregate
tok/s, the per-chunk p50, and the coalescing evidence (max_gen_lanes /
gen_steps) — the measured value of multi-tenant server-gen over running the
same sessions one at a time.

Runs on whatever mesh jax provides (CPU included) — like the greedy row it
measures composition overhead there, chip throughput on TPU.
"""

from __future__ import annotations

import asyncio
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

N_SESSIONS = 3
GEN_CHUNK = 16
CHUNKS = 1  # timed chunks per session (one warm chunk compiles the program)
PREFILL_TOKENS = 64  # smaller than the greedy row: pooled steps pay batchx cost


async def _run(n_sessions: int, gen_chunk: int, chunks: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import bench as _bench  # 7B-shape cfg + random param builder (defs only)
    from petals_tpu.data_structures import CHAIN_DELIMITER, make_uid
    from petals_tpu.models.registry import get_family
    from petals_tpu.rpc import RpcClient
    from petals_tpu.rpc.server import RpcServer
    from petals_tpu.rpc.serialization import serialize_array
    from petals_tpu.server.backend import TransformerBackend
    from petals_tpu.server.handler import TransformerHandler
    from petals_tpu.server.memory_cache import MemoryCache

    cfg = _bench.llama7b_cfg()
    family = get_family("llama")
    dtype = jnp.bfloat16
    n_blocks = _bench.N_BLOCKS
    prefill_tokens = PREFILL_TOKENS

    t0 = time.perf_counter()
    params = _bench.random_params(cfg, n_blocks, dtype)
    init_s = time.perf_counter() - t0
    key = jax.random.PRNGKey(7)
    client_params = {
        "embed": jax.random.normal(key, (cfg.vocab_size, cfg.hidden_size), jnp.float32) * 0.02,
        "norm": jnp.ones((cfg.hidden_size,), jnp.float32),
        "head": jax.random.normal(key, (cfg.hidden_size, cfg.vocab_size), jnp.float32) * 0.02,
    }

    max_length = prefill_tokens + gen_chunk * (chunks + 2) + 8

    memory_cache = MemoryCache(2 << 30)
    backend = TransformerBackend(
        family, cfg, params,
        first_block=0, n_blocks=n_blocks,
        memory_cache=memory_cache, compute_dtype=dtype,
    )
    handler = TransformerHandler(
        backend, dht_prefix="bench", memory_cache=memory_cache,
        batching=True, batch_lanes=n_sessions,  # every pooled step pays for all lanes
        batch_max_length=max_length,  # size lanes to the bench, not the 1024 default
        step_timeout=900.0,  # CPU warm chunk (compile + prefill) outlives the 5 min default
        server_gen_params=client_params,
    )
    server = RpcServer()
    handler.register(server)
    await server.start()
    client = await RpcClient.connect("127.0.0.1", server.port)
    uids = CHAIN_DELIMITER.join(make_uid("bench", i) for i in range(n_blocks))

    rng = np.random.RandomState(0)
    prefill = rng.randn(1, prefill_tokens, cfg.hidden_size).astype(np.float32) * 0.02
    tok_hidden = rng.randn(1, 1, cfg.hidden_size).astype(np.float32) * 0.02

    def sampling_for(session, chunk_idx):
        # per-session PRNG stream; offset advances by the draws already taken
        return {
            "do_sample": True, "temperature": 0.8, "top_k": 40, "top_p": 0.95,
            "repetition_penalty": 1.0, "seed": 1000 + session,
            "offset": chunk_idx * gen_chunk,
        }

    barrier = asyncio.Event()
    round_times = [[] for _ in range(n_sessions)]
    warm_state = {"done": 0, "t0": None}

    async def drive(session):
        stream = await client.open_stream("ptu.inference")
        await stream.send({"uids": uids, "max_length": max_length, "batch_size": 1})
        await stream.recv(timeout=120)
        # prefill + first sampled chunk compiles the pooled-gen program
        await stream.send({
            "tensors": {"hidden": serialize_array(prefill)},
            "gen_tokens": gen_chunk, "gen_sampling": sampling_for(session, 0),
        })
        reply = await stream.recv(timeout=900)
        assert len(reply["tokens"]) == gen_chunk, reply
        warm_state["done"] += 1
        if warm_state["done"] == n_sessions:  # last one in releases everyone
            warm_state["t0"] = time.perf_counter()
            barrier.set()
        await barrier.wait()
        tokens = 0
        for j in range(chunks):
            t0 = time.perf_counter()
            await stream.send({
                "tensors": {"hidden": serialize_array(tok_hidden)},
                "gen_tokens": gen_chunk,
                "gen_sampling": sampling_for(session, 1 + j),
            })
            reply = await stream.recv(timeout=600)
            round_times[session].append(time.perf_counter() - t0)
            tokens += len(reply["tokens"])
        await stream.end()
        return tokens

    try:
        per_session_tokens = await asyncio.gather(*(drive(s) for s in range(n_sessions)))
        elapsed = time.perf_counter() - warm_state["t0"]  # timed chunks only
        stats = dict(handler.batcher.stats) if handler.batcher else {}
    finally:
        await client.close()
        await server.stop()
        handler.shutdown()

    total_tokens = sum(per_session_tokens)
    all_rounds = [t for per in round_times for t in per]
    p50_chunk = statistics.median(all_rounds)
    return {
        "label": "e2e_server_gen_sampling",
        "n_blocks": n_blocks,
        "sessions": n_sessions,
        "gen_chunk": gen_chunk,
        "p50_chunk_ms": round(p50_chunk * 1e3, 1),
        "aggregate_tok_s": round(total_tokens / elapsed, 2),
        "tokens": total_tokens,
        "max_gen_lanes": stats.get("max_gen_lanes"),
        "gen_steps": stats.get("gen_steps"),
        "gen_lane_tokens": stats.get("gen_lane_tokens"),
        "param_init_s": round(init_s, 1),
    }


def run_bench(n_sessions: int = N_SESSIONS, gen_chunk: int = GEN_CHUNK,
              chunks: int = CHUNKS) -> dict:
    return asyncio.run(_run(n_sessions, gen_chunk, chunks))


if __name__ == "__main__":
    from petals_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    import json

    print(json.dumps(run_bench(), indent=2))
