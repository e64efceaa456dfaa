"""The gated delta rule's one-step form alone on the real chip, plain against
kernel, over a state pool of the two ``ctx2k`` cells' sizes (8 lanes; 6 linear
layers of 32 heads of [128, 128] for ``qwen3next80b-ctx2k``, 12 of 30 heads of
[96, 192] for ``olmohybrid7b-ctx2k``), with 4 and 8 of the lanes live:

    chiprun -- python3 benchmarks/ablate_gated_delta_step.py [qwen3-next | olmo-hybrid ...]

``plain`` is what a step ran before PR 49 and runs off the chip: a layer's
states sliced out of the pool, ``gated_delta_step`` between the two ``where``s
(fresh lanes, idle lanes), the layer written back whole; ``kernel`` is
ops/linear_attention.py ``_step_kernel`` on the pool where it lies, at every
grouping of a lane's heads into grid steps (``heads_a_step``; the row marked
``chosen`` is ``step_kernel_heads``'s). One trip is every layer of the pool
once (a ``lax.scan`` that carries the pool, as ``backend._scan_paged_span``
does); the trips run inside one jitted loop at two lengths and a trip's time is
the difference, so that it reads the device and not the host's dispatch. The
pool is made ``DEEPER`` times as deep as the cell's and ``ms`` is a trip's time
over that factor: a pool of the cell's own 100 MB (Qwen3-Next) is found again
on the chip from trip to trip, and the kernel then reads 164% of the floor
(PR 49's first call); in a step 6.5 GiB of weights pass between two uses of it.
``floor_ms`` is the live lanes' matrices read once and written once at 819
GB/s (perf/linattn.py ``one_step_bytes``), ``share`` that floor over the time;
``off`` the kernel's largest difference from the plain form over the largest
value, states and outputs, after one trip from the same pool. Rows go to
``chiprun_out/ablate_gated_delta_step.jsonl``. On the CPU the numbers mean
nothing, the kernel is interpreted and the sizes are cut to a toy."""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

HBM_BYTES_PER_S = 819e9  # perf/peaks.json's v5e
SHAPES = {"qwen3-next": (6, 32, 128, 128), "olmo-hybrid": (12, 30, 96, 192)}  # linear layers, value heads, d_k, d_v
TOYS = {"qwen3-next": (2, 4, 16, 128), "olmo-hybrid": (2, 6, 8, 192)}
DEEPER = {"qwen3-next": 8, "olmo-hybrid": 4}  # 805 MB and 849 MB of pool


def main(names) -> None:
    from petals_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from petals_tpu.ops import linear_attention as la

    on_chip = jax.default_backend() == "tpu"
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    lanes, reps = (8, 20) if on_chip else (4, 1)

    def trip(pool, xs, live, fresh, path, heads_a_step):
        def layer(carry, x):
            pool, seen = carry
            slot, q, k, v, g, beta = x
            state, out = la.gated_delta_pooled(la.StatePool((pool,), slot), q, k, v, g, beta, live=live, fresh=fresh, path=path, heads_a_step=heads_a_step)
            return (state.leaves[0], seen + jnp.where(live[:, None, None], out, 0.0).sum()), None

        return jax.lax.scan(layer, (pool, jnp.float32(0)), xs)[0]

    def timed(pool, xs, live, fresh, path, heads_a_step) -> float:
        def loop(trips, pool, xs, live, fresh):
            return jax.lax.fori_loop(0, trips, lambda _, carry: trip(carry[0], xs, live, fresh, path, heads_a_step), (pool, jnp.float32(0)))

        f = jax.jit(loop)
        jax.block_until_ready(f(1, pool, xs, live, fresh))
        took = []
        for trips in (reps, 2 * reps):
            start = time.perf_counter()
            jax.block_until_ready(f(trips, pool, xs, live, fresh))
            took.append(time.perf_counter() - start)
        return (took[1] - took[0]) / reps * 1e3

    with open(os.path.join(out_dir, "ablate_gated_delta_step.jsonl"), "a") as sink:
        for name in names:
            layers, heads, d_k, d_v = (SHAPES if on_chip else TOYS)[name]
            deeper = DEEPER[name] if on_chip else 1
            depth = layers * deeper
            ks = jax.random.split(jax.random.PRNGKey(0), 6)
            pool = jax.random.normal(ks[0], (depth, lanes, heads, d_k, d_v), jnp.float32)
            q, k = (jax.random.normal(key, (depth, lanes, heads, d_k), jnp.float32) * d_k**-0.5 for key in ks[1:3])
            v = jax.random.normal(ks[3], (depth, lanes, heads, d_v), jnp.float32)
            g, beta = -jax.random.uniform(ks[4], (depth, lanes, heads)), jax.random.uniform(ks[5], (depth, lanes, heads))
            xs = (jnp.arange(depth, dtype=jnp.int32), q, k, v, g, beta)
            one = jax.jit(trip, static_argnames=("path", "heads_a_step"))
            chosen = la.step_kernel_heads(heads, d_k, d_v)
            groupings = [n for n in range(1, heads + 1) if heads % n == 0]
            for n_live in sorted({lanes // 2, lanes}):
                live = jnp.asarray(np.arange(lanes) % (lanes // n_live) == 0)  # every other lane, or all
                fresh = jnp.zeros((lanes,), bool)
                want_pool, want_seen = one(pool, xs, live, fresh, path="plain", heads_a_step=None)
                floor_ms = 2 * n_live * layers * heads * d_k * d_v * 4 / HBM_BYTES_PER_S * 1e3
                for path, heads_a_step in [("plain", None)] + [("kernel", n) for n in groupings]:
                    row = {"shape": name, "layers": layers, "state": [heads, d_k, d_v], "pool_layers": depth, "lanes": lanes, "live": n_live,
                           "path": path, "heads_a_step": heads_a_step, "chosen": heads_a_step == chosen, "floor_ms": floor_ms}
                    try:
                        row["ms"] = timed(pool, xs, live, fresh, path, heads_a_step) / deeper
                        row["share"] = 100.0 * floor_ms / row["ms"]
                        if path == "kernel":
                            got_pool, got_seen = one(pool, xs, live, fresh, path=path, heads_a_step=heads_a_step)
                            row["off"] = max(
                                float(jnp.abs(got_pool - want_pool).max() / jnp.abs(want_pool).max()),
                                float(jnp.abs(got_seen - want_seen) / jnp.abs(want_seen)),
                            )
                    except Exception as e:  # what the compiler refused, in its own words
                        row["error"] = f"{type(e).__name__}: {str(e)[:400]}"
                    print(json.dumps(row), flush=True)
                    sink.write(json.dumps(row) + "\n")
                    sink.flush()


if __name__ == "__main__":
    main([a for a in sys.argv[1:] if a in SHAPES] or list(SHAPES))
