"""Paged-attention head-to-head on the real chip: the fused ragged kernel
(ops/paged_flash_attention.py) vs the XLA-composed gather_pages +
attend_reference it replaced, across lane counts x table layouts x occupancy.

Notes going in:
- The XLA arm pays a [n_lanes * max_pages] page gather (a materialized dense
  view of the pool) before every attention call; the kernel reads pages
  straight from the pool via block-table-driven BlockSpecs and skips
  unallocated / out-of-window pages entirely. The interesting axes are table
  layout (identity tables let XLA's gather degenerate to a reshape) and
  occupancy (holey tables shrink the kernel's working set but not XLA's).
- Each chain link perturbs the pool (kp * (1 + j/128)) so XLA cannot hoist
  the loop-invariant gather out of the chain — both arms pay the same extra
  elementwise pass, the slope difference is gather + attention only.
- On CPU the kernel runs in interpret mode: orders of magnitude slower and
  NOT decision-grade — rows are tagged "interpret" so nobody reads them as a
  verdict. For one: ``chiprun -- python benchmarks/ablate_paged_attention.py``
  (one process holds the chip). Results go to stdout only (the July
  BENCH_DETAILS.json record is deleted).
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


def _perturb(pool, f):
    """Per-link pool perturbation that survives quantized pools: scaling a
    row scales its absmax, so multiplying the SCALES is the exact quantized
    counterpart of multiplying a dense pool."""
    from petals_tpu.ops.paged_attention import PagedPool

    if isinstance(pool, PagedPool):
        return PagedPool(pool.codes, pool.scales * f)
    return pool * f


def _time_slope(call, q, kp, vp, tables, pos, runs=3, n_lo=2, n_hi=8):
    """Per-call time via the chained-slope method (a single dispatch is
    mostly dispatch floor): jit n chained calls (output feeds the next q, pool
    perturbed per link to defeat gather hoisting) and take
    (t(n_hi) - t(n_lo)) / (n_hi - n_lo)."""
    from petals_tpu.telemetry.observatory import tracked_jit

    def timed(n):
        def chained(q, kp, vp, tables, pos):
            out = q
            for j in range(n):
                f = 1.0 + j / 128.0
                out = call(out * 1e-2 + q, _perturb(kp, f), _perturb(vp, f),
                           tables, pos)
            return out

        fn = tracked_jit(chained, name="paged_ablate_chain")
        hard_sync(fn(q, kp, vp, tables, pos))  # compile
        best = float("inf")
        for _ in range(runs):
            t0 = time.perf_counter()
            out = fn(q, kp, vp, tables, pos)
            hard_sync(out)
            best = min(best, time.perf_counter() - t0)
        return best

    return max((timed(n_hi) - timed(n_lo)) / (n_hi - n_lo), 1e-9)


def _make_tables(layout, n_lanes, max_pages, rng):
    """identity | permuted (full) | holey (permuted, ~50% occupancy)."""
    n_pages = n_lanes * max_pages
    if layout == "identity":
        return np.arange(n_pages, dtype=np.int32).reshape(n_lanes, max_pages)
    perm = rng.permutation(n_pages).astype(np.int32).reshape(n_lanes, max_pages)
    if layout == "holey":
        perm[:, max(1, max_pages // 2):] = -1
    return perm


def bench_shape(n_lanes, max_pages, page_size, hkv, group, d=128, runs=3):
    import jax
    import jax.numpy as jnp

    from petals_tpu.ops.attention import attend_reference
    from petals_tpu.ops.paged_attention import gather_pages
    from petals_tpu.ops.paged_flash_attention import paged_flash_attend

    interpret = jax.default_backend() != "tpu"
    hq = hkv * group
    n_pages = n_lanes * max_pages
    rng = np.random.default_rng(0)
    key = jax.random.PRNGKey(0)
    kq, kk, kv_ = jax.random.split(key, 3)
    dtype = jnp.float32 if interpret else jnp.bfloat16
    q = jax.random.normal(kq, (n_lanes, 1, hq, d), dtype) * 0.1
    kp = jax.random.normal(kk, (n_pages, page_size, hkv, d), dtype) * 0.1
    vp = jax.random.normal(kv_, (n_pages, page_size, hkv, d), dtype) * 0.1

    # PETALS_TPU_KV_QUANT=int8|nf4a: run the same sweep over a QUANTIZED
    # pool — the pallas arm dequantizes in-tile, the XLA arm pays
    # gather + dequantize-then-attend (its bit-compatible twin), so the
    # slope difference is the in-kernel-dequant HBM-vs-ALU trade.
    kv_quant = os.environ.get("PETALS_TPU_KV_QUANT", "none")
    if kv_quant != "none":
        from petals_tpu.ops.paged_attention import PagedPool, quantize_kv_rows

        kp = PagedPool(*quantize_kv_rows(kp.astype(jnp.float32), kv_quant))
        vp = PagedPool(*quantize_kv_rows(vp.astype(jnp.float32), kv_quant))

    def arm_pallas(q, kp, vp, tables, pos):
        return paged_flash_attend(q, kp, vp, tables, pos, interpret=interpret)

    def arm_xla(q, kp, vp, tables, pos):
        k = gather_pages(kp, tables)
        v = gather_pages(vp, tables)
        return attend_reference(q, k, v, q_offset=pos, kv_length=pos + 1)

    rows = []
    for layout in ("identity", "permuted", "holey"):
        tables = _make_tables(layout, n_lanes, max_pages, rng)
        occupancy = int((tables >= 0).sum(axis=1).min())
        pos = jnp.full((n_lanes,), occupancy * page_size - 1, jnp.int32)
        tb = jnp.asarray(tables)
        for impl, call in (("pallas", arm_pallas), ("xla", arm_xla)):
            try:
                t = _time_slope(call, q, kp, vp, tb, pos, runs=runs)
                rows.append({
                    "impl": impl, "layout": layout, "ms": round(t * 1e3, 3),
                    **({"interpret": True} if impl == "pallas" and interpret else {}),
                })
            except Exception as e:
                rows.append({
                    "impl": impl, "layout": layout, "error": repr(e)[:120],
                })
    return {
        "n_lanes": n_lanes, "max_pages": max_pages, "page_size": page_size,
        "hkv": hkv, "group": group, "d": d, "rows": rows,
        **({"kv_quant": kv_quant} if kv_quant != "none" else {}),
    }


def main():
    import jax

    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu:
        print(json.dumps({"note": (
            "CPU run: the pallas arm is INTERPRET mode — structural smoke "
            "only, timings are not decision-grade"
        )}), flush=True)
    # 70B-ish decode pool shapes (lane sweep) + one small-page config
    shapes = (
        (8, 16, 128, 8, 8),
        (32, 16, 128, 8, 8),
        (64, 16, 128, 8, 8),
        (32, 64, 32, 8, 8),
    ) if on_tpu else (
        (2, 3, 8, 2, 2),  # tiny: interpret mode is ~1000x slower
    )
    for n_lanes, max_pages, page_size, hkv, group in shapes:
        r = bench_shape(n_lanes, max_pages, page_size, hkv, group,
                        d=128 if on_tpu else 16)
        print(json.dumps(r), flush=True)


if __name__ == "__main__":
    from petals_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    main()
