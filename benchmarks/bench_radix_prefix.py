"""Radix prefix tree vs flat LRU on a seeded multi-tenant prompt-tree trace.

Two rows share one trace recipe (petals_tpu.traffic.generator prompt trees:
a swarm-shared system prompt, per-tenant preambles, branching few-shot
variants with a hot lineage, random user turns):

- ``gate_radix_cache`` (CPU perf gate, seconds): drives the cache LAYER
  directly — segment_keys over token-derived hidden states, probe/put per
  session — so the tokens-saved claim is deterministic and cheap enough to
  pin in BENCH_GATE_CPU.json. Asserts radix saves >= 2x the flat baseline's
  prefill tokens at the SAME byte budgets and that the replay causes zero
  post-warmup compile anomalies.

- ``e2e_radix_prefix_tree`` (heavy row, fresh process): the same trace
  replayed through a real server (RpcServer + TransformerHandler +
  RpcClient), radix config vs flat-LRU config at the same budgets, measuring
  per-session TTFT. Gates on prefill tokens saved >= 2x flat and TTFT p99
  no worse.

Both configs get identical host/device byte budgets and an identical
HostSwapPool — the flat policy simply cannot use the swap tier or the
economics eviction, which is the point of the comparison.
"""

import asyncio
import gc
import time

import numpy as np

SEED = 2026
TENANTS = 4

# every tree level is exactly one hash segment (SEGMENT_TOKENS) so the
# prompt tree maps 1:1 onto radix nodes; the 64-token suffix never fills a
# segment and is recomputed by every session (as user turns are in practice)
def _trace_config(duration_s=600.0):
    from petals_tpu.server.prefix_cache import SEGMENT_TOKENS
    from petals_tpu.traffic.generator import TrafficConfig

    return TrafficConfig(
        seed=SEED,
        duration_s=duration_s,
        base_rate=0.4,
        wave_amplitude=0.5,
        tenants=TENANTS,
        shared_prefix_len=SEGMENT_TOKENS,  # swarm-shared system prompt
        prompt_prefix_len=SEGMENT_TOKENS,  # per-tenant tool preamble
        prompt_suffix_len=64,  # random user turn (never a full segment)
        tree_branching=(2, 2, 2),  # three levels of few-shot variants
        tree_segment_len=SEGMENT_TOKENS,
        tree_hot_bias=0.5,  # one hot lineage per tenant, cold bushy rest
        vocab_size=512,
        min_new_tokens=2,
        max_new_tokens=8,
    )


def _token_rows(vocab_size, hidden, seed=SEED):
    """Fixed token-id -> hidden-row table: prompts sharing a token prefix
    share a hidden prefix, so the hash chain sees the tree. (The real system
    gets this for free — hidden states are deterministic in the prompt.)"""
    rng = np.random.RandomState(seed)
    return (rng.randn(vocab_size, hidden) * 0.02).astype(np.float32)


def _hidden_for(prompt, rows):
    return rows[np.asarray(prompt, dtype=np.int64)][None, :, :]


# --------------------------------------------------------------- gate row


def gate_bench(label, *, n_sessions=64):
    """CPU gate: replay the trace against the cache layer under both
    policies at identical budgets; pin the tokens-saved ratio."""
    from petals_tpu.server.memory_cache import HostSwapPool
    from petals_tpu.server.prefix_cache import (
        SEGMENT_TOKENS,
        RadixPrefixCache,
        segment_keys,
    )
    from petals_tpu.telemetry import instruments as tm

    cfg = _trace_config()
    from petals_tpu.traffic.generator import TrafficGenerator

    plans = TrafficGenerator(cfg).schedule()[:n_sessions]
    assert len(plans) >= 24, f"trace too short: {len(plans)} sessions"

    HIDDEN = 8  # hashing input width only; k/v shapes are independent
    rows = _token_rows(cfg.vocab_size, HIDDEN)

    # one segment's synthetic span tensors (shape-stable, content ignored:
    # the cache keys on the hash chain, not on these arrays)
    N_BLOCKS, HKV, HEAD = 1, 1, 4
    rng = np.random.RandomState(SEED)

    def span_arrays(n_segments):
        t = n_segments * SEGMENT_TOKENS
        k = rng.randn(N_BLOCKS, 1, t, HKV, HEAD).astype(np.float32)
        v = rng.randn(N_BLOCKS, 1, t, HKV, HEAD).astype(np.float32)
        out = rng.randn(1, t, HIDDEN).astype(np.float32)
        return k, v, out

    k1, v1, o1 = span_arrays(1)
    seg_bytes = k1.nbytes + v1.nbytes + o1.nbytes

    # budgets: the hot working set alone (shared root + 4 tenants' hot
    # lineages = 17 segments) does NOT fit the 8-segment host budget — flat
    # LRU must thrash on it, while radix spills cold nodes into its half of
    # the 96-segment swap pool (total capacity 56 of the trace's 61 distinct
    # segments) and keeps every hot node probe-able
    host_budget = 8 * seg_bytes
    swap_budget = 96 * seg_bytes

    def replay(policy):
        pool = HostSwapPool(swap_budget)
        cache = RadixPrefixCache(
            host_budget, policy=policy, swap_pool=pool, swap_frac=0.5
        )
        prefill_total = 0
        for plan in plans:
            hidden = _hidden_for(plan.prompt, rows)
            keys = segment_keys(hidden, salt="bench:0:2")
            hits = cache.probe(keys)
            prefill_total += hidden.shape[1]
            if hits < len(keys):
                k, v, out = span_arrays(len(keys) - hits)
                cache.put(keys, hits, k, v, out, tenant=f"tenant-{plan.tenant}")
        summary = cache.summary()
        # invariant: pool accounting round-trips (nothing leaks on clear)
        cache.clear()
        assert pool.cache_bytes_in_use == 0, "swap accounting leaked"
        return summary, prefill_total

    anomalies_before = sum(c.value for _v, c in tm.COMPILE_ANOMALIES.children())
    t0 = time.perf_counter()
    flat, prefill_tokens = replay("lru")
    radix, _ = replay("radix")
    wall = time.perf_counter() - t0
    anomalies = (
        sum(c.value for _v, c in tm.COMPILE_ANOMALIES.children())
        - anomalies_before
    )

    saved_ratio = radix["hit_tokens"] / max(flat["hit_tokens"], 1)
    assert saved_ratio >= 2.0, (
        f"radix must save >=2x the flat baseline's prefill tokens at the "
        f"same budgets: radix={radix['hit_tokens']} flat={flat['hit_tokens']} "
        f"({saved_ratio:.2f}x)"
    )
    assert anomalies == 0, (
        f"trace replay caused {anomalies} post-warmup compile anomalies — "
        f"the cache layer must not touch compiled code"
    )
    return {
        "label": label,
        "sessions": len(plans),
        "tenants": TENANTS,
        "prefill_tokens_offered": prefill_tokens,
        "flat_hit_tokens": flat["hit_tokens"],
        "radix_hit_tokens": radix["hit_tokens"],
        "tokens_saved_ratio": round(saved_ratio, 2),
        "radix_demotions": radix["demotions"],
        "radix_promotions": radix["promotions"],
        "radix_swap_evictions": radix["swap_evictions"],
        "flat_evictions": flat["evictions"],
        "radix_evictions": radix["evictions"],
        "replay_wall_ms": round(1000.0 * wall, 1),
        "post_warmup_compile_anomalies": anomalies,
    }


# -------------------------------------------------------------- heavy row


async def _replay_server(policy, plans, rows, *, cfg, budgets):
    """One server config (fresh backend + handler + cache) replaying the
    whole trace; returns (per-session TTFT list, cache summary)."""
    import jax.numpy as jnp

    from petals_tpu.data_structures import CHAIN_DELIMITER, make_uid
    from petals_tpu.models.registry import get_family
    from petals_tpu.rpc import RpcClient
    from petals_tpu.rpc.serialization import serialize_array
    from petals_tpu.rpc.server import RpcServer
    from petals_tpu.server.backend import TransformerBackend
    from petals_tpu.server.handler import TransformerHandler
    from petals_tpu.server.memory_cache import HostSwapPool, MemoryCache
    from petals_tpu.server.prefix_cache import RadixPrefixCache

    from bench import random_params

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
    # identical budgets for both configs; only the policy differs — the
    # swap pool exists for both, the flat baseline just cannot use it
    handler.prefix_cache = RadixPrefixCache(
        budgets["host"],
        device_max_bytes=budgets["device"],
        policy=policy,
        swap_pool=HostSwapPool(budgets["swap"]),
        swap_frac=0.5,
    )
    server = RpcServer()
    handler.register(server)
    await server.start()
    client = await RpcClient.connect("127.0.0.1", server.port)
    uids = CHAIN_DELIMITER.join(make_uid("bench", i) for i in range(n))

    async def settle_stores(timeout=10.0):
        """Stores land off the reply path; wait for the segment count to go
        quiet so the next session sees this one's stores (the trace is a
        sequence of distinct sessions, not a burst)."""
        deadline = time.monotonic() + timeout
        last = -1
        while time.monotonic() < deadline:
            cur = handler.prefix_cache.summary()["stored_segments"]
            if cur == last:
                return
            last = cur
            await asyncio.sleep(0.15)
        raise RuntimeError("prefix stores did not settle within the deadline")

    ttfts = []
    try:
        for plan in plans:
            hidden = _hidden_for(plan.prompt, rows)
            stream = await client.open_stream("ptu.inference")
            await stream.send({
                "uids": uids,
                "max_length": hidden.shape[1] + 8,
                "batch_size": 1,
            })
            await stream.recv(timeout=300)
            t0 = time.perf_counter()
            await stream.send({"tensors": {"hidden": serialize_array(hidden)}})
            await stream.recv(timeout=600)
            ttfts.append(time.perf_counter() - t0)
            await stream.end()
            await settle_stores()
        summary = handler.prefix_cache.summary()
    finally:
        await client.close()
        await server.stop()
        handler.shutdown()
    del params, backend, memory_cache
    gc.collect()
    return ttfts, summary


def _p99(samples):
    xs = sorted(samples)
    return xs[min(len(xs) - 1, int(0.99 * len(xs)))]


def _span_cfg():
    """A 1B-ish 2-block span: big enough that a ~700-token cold prefill
    visibly dominates TTFT (the quantity the radix-vs-flat split measures),
    small enough that the 2 x 48-session replay finishes in minutes on one
    CI CPU core — the full 7B shape (`bench.llama7b_cfg(8)`) takes hours
    there and adds nothing to the cache economics, which are
    shape-independent (budgets scale from the cfg below). Pass
    ``cfg=llama7b_cfg(...)`` on real silicon (revival step 10/10)."""
    from petals_tpu.models.llama.config import LlamaBlockConfig

    return LlamaBlockConfig(
        hidden_size=256,
        num_attention_heads=4,
        num_key_value_heads=4,
        head_dim=64,
        intermediate_size=704,
        num_hidden_layers=2,
        rms_norm_eps=1e-5,
        vocab_size=512,
    )


def run_bench(*, cfg=None, n_sessions=48, duration_s=600.0):
    """e2e heavy row: the seeded 4-tenant prompt-tree trace against a real
    server, radix vs flat-LRU at the same byte budgets."""
    import jax.numpy as jnp

    from petals_tpu.traffic.generator import TrafficGenerator

    cfg = cfg or _span_cfg()
    tcfg = _trace_config(duration_s)
    plans = TrafficGenerator(tcfg).schedule()[:n_sessions]
    assert len(plans) >= 16, f"trace too short: {len(plans)} sessions"
    rows = _token_rows(tcfg.vocab_size, cfg.hidden_size)

    # one segment's stored footprint for THIS model shape: k/v slices are
    # [n_blocks, 1, SEG, hkv, d] in the compute dtype plus the fp32 out row
    from petals_tpu.server.prefix_cache import SEGMENT_TOKENS

    hkv = getattr(cfg, "num_key_value_heads", cfg.num_attention_heads)
    head = getattr(cfg, "head_dim", cfg.hidden_size // cfg.num_attention_heads)
    kv_itemsize = jnp.dtype(jnp.bfloat16).itemsize
    seg_bytes = (
        2 * cfg.num_hidden_layers * SEGMENT_TOKENS * hkv * head * kv_itemsize
        + SEGMENT_TOKENS * cfg.hidden_size * 4
    )
    budgets = {
        "host": 8 * seg_bytes,  # the 17-segment hot working set must spill
        "swap": 96 * seg_bytes,
        "device": 8 * seg_bytes,
    }

    flat_ttft, flat = asyncio.run(
        _replay_server("lru", plans, rows, cfg=cfg, budgets=budgets)
    )
    radix_ttft, radix = asyncio.run(
        _replay_server("radix", plans, rows, cfg=cfg, budgets=budgets)
    )

    saved_ratio = radix["hit_tokens"] / max(flat["hit_tokens"], 1)
    p99_flat, p99_radix = _p99(flat_ttft), _p99(radix_ttft)
    assert saved_ratio >= 2.0, (
        f"radix must save >=2x flat's prefill tokens on the seeded trace: "
        f"radix={radix['hit_tokens']} flat={flat['hit_tokens']}"
    )
    assert p99_radix <= 1.10 * p99_flat, (
        f"radix TTFT p99 regressed vs the flat baseline: "
        f"{1e3 * p99_radix:.1f}ms vs {1e3 * p99_flat:.1f}ms"
    )
    return {
        "label": "e2e_radix_prefix_tree",
        "sessions": len(plans),
        "tenants": TENANTS,
        "flat_hit_tokens": flat["hit_tokens"],
        "radix_hit_tokens": radix["hit_tokens"],
        "tokens_saved_ratio": round(saved_ratio, 2),
        "flat_ttft_p50_ms": round(1e3 * sorted(flat_ttft)[len(flat_ttft) // 2], 1),
        "radix_ttft_p50_ms": round(1e3 * sorted(radix_ttft)[len(radix_ttft) // 2], 1),
        "flat_ttft_p99_ms": round(1e3 * p99_flat, 1),
        "radix_ttft_p99_ms": round(1e3 * p99_radix, 1),
        "radix_demotions": radix["demotions"],
        "radix_promotions": radix["promotions"],
        "radix_device_segments": radix["device_segments"],
        "flat_evictions": flat["evictions"],
        "radix_evictions": radix["evictions"],
    }


if __name__ == "__main__":
    from petals_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    import json

    print(json.dumps(run_bench(), indent=2))
