"""Flash-attention head-to-head on the real chip (VERDICT r3 weak/next #8):
our prefix-cache GQA kernel (ops/flash_attention.py) vs jax's official pallas
flash_attention (and a tile sweep of ours), at the bench's shapes.

Notes going in:
- The official kernel has NO native GQA: q/k/v must share a head count, so at
  GQA shapes its k/v are repeated to the q head count before the call —
  paying group_size x the KV bandwidth + repeat materialization. Ours reads
  each kv head once per group. The COVERAGE "~8% behind" figure was measured
  head-to-head; this script shows per-shape where the gap lives and whether a
  different tile pair closes it.
- Chip required, one process: ``chiprun -- python benchmarks/ablate_flash.py``.
  Results go to stdout only (the July BENCH_DETAILS.json record is deleted).
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def hard_sync(x):
    import jax
    import jax.numpy as jnp

    np.asarray(jax.device_get(jnp.ravel(x)[:1]))


def _time_slope(call, q, k, v, runs=5, n_lo=1, n_hi=4):
    """Per-call time via the chained-slope method (a single dispatch is mostly
    dispatch floor): jit n chained kernel calls (attention output feeds the next call's q) and
    take (t(n_hi) - t(n_lo)) / (n_hi - n_lo)."""
    import jax

    def timed(n):
        def chained(q, k, v):
            out = q
            for _ in range(n):
                out = call(out, k, v)
            return out

        fn = jax.jit(chained)
        hard_sync(fn(q, k, v))  # compile
        best = float("inf")
        for _ in range(runs):
            t0 = time.perf_counter()
            out = fn(q, k, v)
            hard_sync(out)
            best = min(best, time.perf_counter() - t0)
        return best

    return max((timed(n_hi) - timed(n_lo)) / (n_hi - n_lo), 1e-9)


def attention_flops(seq, hq, d, causal=True):
    f = 2 * 2 * hq * d * seq * seq
    return f / 2 if causal else f


def bench_shape(seq, hq, hkv, d=128, runs=5):
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu import flash_attention as jfa

    from petals_tpu.ops.flash_attention import flash_attend

    key = jax.random.PRNGKey(0)
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (1, seq, hq, d), jnp.bfloat16) * 0.1
    k = jax.random.normal(kk, (1, seq, hkv, d), jnp.bfloat16) * 0.1
    v = jax.random.normal(kv_, (1, seq, hkv, d), jnp.bfloat16) * 0.1
    flops = attention_flops(seq, hq, d)
    rows = []

    # ours, tile sweep
    for bq, bkv in ((512, 1024), (512, 512), (256, 1024), (1024, 1024), (512, 2048)):
        try:
            call = lambda q, k, v, bq=bq, bkv=bkv: flash_attend(
                q, k, v, q_offset=0, kv_length=seq, block_q=bq, block_kv=bkv
            )
            t = _time_slope(call, q, k, v, runs=runs)
            rows.append({
                "impl": f"ours_{bq}x{bkv}", "ms": round(t * 1e3, 3),
                "tflops": round(flops / t / 1e12, 1),
            })
        except Exception as e:
            rows.append({"impl": f"ours_{bq}x{bkv}", "error": repr(e)[:120]})

    # official: layout [b, heads, seq, d]; GQA repeats kv to hq heads
    qT = jnp.swapaxes(q, 1, 2)
    kT = jnp.swapaxes(k, 1, 2)
    vT = jnp.swapaxes(v, 1, 2)
    if hkv != hq:
        kT = jnp.repeat(kT, hq // hkv, axis=1)
        vT = jnp.repeat(vT, hq // hkv, axis=1)
    for bq, bk in ((512, 1024), (256, 512), (512, 512)):
        try:
            bs = jfa.BlockSizes(
                block_q=min(bq, seq), block_k_major=min(bk, seq),
                block_k=min(bk, seq), block_b=1,
            )
            call = lambda q, k, v, bs=bs: jfa.flash_attention(
                q, k, v, causal=True, sm_scale=d**-0.5, block_sizes=bs
            )
            t = _time_slope(call, qT, kT, vT, runs=runs)
            rows.append({
                "impl": f"jax_flash_{bq}x{bk}", "ms": round(t * 1e3, 3),
                "tflops": round(flops / t / 1e12, 1),
            })
        except Exception as e:
            rows.append({"impl": f"jax_flash_{bq}x{bk}", "error": repr(e)[:120]})

    return {"seq": seq, "hq": hq, "hkv": hkv, "rows": rows}


def main():
    # 70B GQA prefill (the bench's flash row) and an MHA head-to-head
    for seq, hq, hkv in ((8192, 64, 8), (8192, 32, 32), (4096, 64, 8)):
        r = bench_shape(seq, hq, hkv)
        print(json.dumps(r), flush=True)


if __name__ == "__main__":
    from petals_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    main()
