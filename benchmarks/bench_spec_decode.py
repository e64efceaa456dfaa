"""End-to-end speculative decoding benchmark: spec vs plain decode tok/s.

Drives a real DecodeBatcher (the pooled lane machinery, not a mock) through
full generations in both modes and reports:

- single-stream tok/s, plain vs spec (the latency-bound regime speculation
  targets: one lane cannot fill a batch, so each verify step amortizes the
  per-dispatch overhead over k+1 tokens)
- 8-lane aggregate tok/s, plain vs spec (throughput regime: speculation must
  at least not regress when batching already amortizes dispatch)
- acceptance rate (accepted / proposed, from the batcher's own counters)
- draft overhead: draft_seconds as a fraction of billed compute_seconds,
  straight from the per-tenant resource ledger

The draft is COOPERATIVE: the same tiny weights as the target span, fp32,
with a window covering the whole context — so acceptance approaches 1 and
the run measures the machinery's ceiling, not a particular draft model's
quality. Output parity (spec stream bit-identical to plain, greedy and
fixed-seed sampling alike) is asserted, and the single-stream speedup is
gated at >= 1.5x — the ISSUE's acceptance bar for k=4 on CPU.

Run directly (``python benchmarks/bench_spec_decode.py``) or as the
``e2e_spec_decode`` row of ``bench.py``.
"""

import asyncio
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import bench as _bench  # noqa: E402

SPEC_K = 4
DRAFT_WINDOW = 48
GEN_TOKENS = 48
CTX_LEN = 8
LANES = 8
TIMED_ROUNDS = 3


def _build(cfg, jnp):
    """One backend + cooperative draft + pooled batcher, tiny enough that a
    CI CPU runs the whole matrix in seconds."""
    import jax

    from petals_tpu.models.registry import get_family
    from petals_tpu.server.backend import TransformerBackend
    from petals_tpu.server.batching import DecodeBatcher
    from petals_tpu.server.memory_cache import MemoryCache
    from petals_tpu.server.spec_decode import DraftModel
    from petals_tpu.server.task_queue import PriorityTaskQueue

    family = get_family("llama")
    n_blocks = cfg.num_hidden_layers
    params = _bench.random_params(cfg, n_blocks, jnp.float32)
    # the draft unrolls its block loop over a per-block LIST; the span scans
    # over the stacked leaves — same weights, two layouts
    blocks = [
        {name: leaf[i] for name, leaf in params.items()} for i in range(n_blocks)
    ]
    key = jax.random.PRNGKey(7)
    client_params = {
        "embed": jax.random.normal(key, (cfg.vocab_size, cfg.hidden_size), jnp.float32) * 0.02,
        "norm": jnp.ones((cfg.hidden_size,), jnp.float32),
        "head": jax.random.normal(key, (cfg.hidden_size, cfg.vocab_size), jnp.float32) * 0.02,
    }
    backend = TransformerBackend(
        family, cfg, params,
        first_block=0, n_blocks=n_blocks,
        memory_cache=MemoryCache(None), compute_dtype=jnp.float32,
        use_flash=False,
    )
    draft = DraftModel(
        family, cfg, blocks, client_params,
        spec_k=SPEC_K, window=DRAFT_WINDOW, compute_dtype=jnp.float32,
    )
    queue = PriorityTaskQueue()
    queue.start()
    batcher = DecodeBatcher(
        backend, backend.memory_cache, queue,
        n_lanes=LANES, max_length=128, page_size=8,
        gen_params=client_params, draft_model=draft, spec_k=SPEC_K,
    )
    return batcher, queue, client_params


def _embed(batcher, ctx):
    emb = batcher.backend.family.client_embed(
        batcher.gen_params, np.asarray([ctx], np.int32), batcher.backend.cfg
    )
    return np.asarray(emb, np.float32)


async def _generate(batcher, ctx, n_tokens, sampling, peer_id):
    """One full session: admit -> prefill -> server-side generate -> bill."""
    hidden = _embed(batcher, ctx)
    lane = await batcher.acquire_lane(timeout=120, peer_id=peer_id)
    try:
        out = await batcher.prefill_lane(lane, hidden, 0)
        toks = await batcher.generate_lane(
            lane, np.asarray(out[:, -1:]), len(ctx), n_tokens, sampling
        )
        usage = batcher.pop_usage_delta(lane) or {}
    finally:
        batcher.release_lane(lane)
    return np.asarray(toks), usage


def _merge(total, usage):
    for k, v in usage.items():
        if k in ("acceptance_rate", "tokens_per_compute_second"):
            continue
        total[k] = total.get(k, 0) + v


async def _run(batcher):
    rng = np.random.RandomState(11)
    contexts = [
        [int(t) for t in rng.randint(0, batcher.backend.cfg.vocab_size, CTX_LEN)]
        for _ in range(LANES)
    ]
    # the cooperative draft conditions on the prompt via sampling["context"];
    # greedy semantics otherwise (the tests cover sampled-mode parity)
    sampling = [{"context": ctx} for ctx in contexts]
    streams = {}
    result = {}

    for mode in ("plain", "spec"):
        batcher.draft = batcher._draft if mode == "spec" else None
        # warmup: compile prefill/decode/propose/verify outside the timers
        await _generate(batcher, contexts[0], GEN_TOKENS, sampling[0], f"{mode}-warm")

        s0 = dict(batcher.stats)
        usage = {}
        t0 = time.perf_counter()
        for r in range(TIMED_ROUNDS):
            toks, u = await _generate(
                batcher, contexts[0], GEN_TOKENS, sampling[0], f"{mode}-single"
            )
            _merge(usage, u)
            if r == 0:
                streams[mode] = toks
        single_wall = time.perf_counter() - t0
        single_tps = TIMED_ROUNDS * GEN_TOKENS / single_wall

        t0 = time.perf_counter()
        multi = await asyncio.gather(*(
            _generate(batcher, contexts[i], GEN_TOKENS, sampling[i], f"{mode}-lane-{i}")
            for i in range(LANES)
        ))
        multi_wall = time.perf_counter() - t0
        for i, (toks, u) in enumerate(multi):
            _merge(usage, u)
            streams[f"{mode}-lane-{i}"] = toks
        multi_tps = LANES * GEN_TOKENS / multi_wall

        sd = {k: batcher.stats[k] - s0[k] for k in batcher.stats}
        row = {
            "single_tok_s": round(single_tps, 2),
            "single_ms_per_tok": round(1000.0 * single_wall / (TIMED_ROUNDS * GEN_TOKENS), 3),
            f"{LANES}lane_tok_s": round(multi_tps, 2),
            "gen_steps": sd["gen_steps"],
            "spec_steps": sd["spec_steps"],
        }
        if mode == "spec":
            assert sd["spec_steps"] > 0, "spec mode never took the spec path"
            assert sd["spec_proposed"] > 0
            row["acceptance_rate"] = round(sd["spec_accepted"] / sd["spec_proposed"], 4)
            compute = float(usage.get("compute_seconds", 0.0))
            draft = float(usage.get("draft_seconds", 0.0))
            assert 0.0 < draft < compute, (draft, compute)
            row["draft_overhead"] = round(draft / compute, 4)
        result[mode] = row

    # distribution preservation: speculation must be invisible in the output
    np.testing.assert_array_equal(streams["spec"], streams["plain"])
    for i in range(LANES):
        np.testing.assert_array_equal(
            streams[f"spec-lane-{i}"], streams[f"plain-lane-{i}"]
        )

    speedup = result["spec"]["single_tok_s"] / result["plain"]["single_tok_s"]
    result["single_stream_speedup"] = round(speedup, 3)
    assert speedup >= 1.5, (
        f"single-stream spec speedup {speedup:.2f}x < 1.5x "
        f"(spec {result['spec']['single_tok_s']} tok/s vs "
        f"plain {result['plain']['single_tok_s']} tok/s)"
    )
    return result


def run_bench():
    import jax.numpy as jnp

    cfg = _bench._tiny_gate_cfg()
    batcher, queue, _client = _build(cfg, jnp)
    # stash the draft so _run can toggle modes without rebuilding programs
    batcher._draft = batcher.draft

    async def main():
        try:
            return await _run(batcher)
        finally:
            await batcher.close()
            queue.shutdown()

    result = asyncio.run(main())
    result["spec_k"] = SPEC_K
    result["gen_tokens"] = GEN_TOKENS
    return {"spec_decode": result}


if __name__ == "__main__":
    from petals_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    import json

    print(json.dumps(run_bench(), indent=2))
