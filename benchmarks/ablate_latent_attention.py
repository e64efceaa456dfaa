"""The latent attention's two walks on the real chip, one layer's call at the
cell ``kanana2-ctx32k``'s shapes (8 lanes, tables of 512 pages of 64, a latent
row of 512 + 64 under 32 heads of 128 + 64 | 128), at several block widths:

    chiprun -- python3 benchmarks/ablate_latent_attention.py [--decode-only] [ctx ...]

``decode`` is the whole decode call of a layer (the absorb, the walk over the
lanes' latent rows where they lie, the way back out of the latent space) on
the composed walk at its ``DECODE_BLOCK_ROWS`` (PR 42 swept 256 to 32,768 here:
the constant's comment has the rows); ``decode_kernel`` the same call on the
fused kernel at ``DECODE_KERNEL_PAGES`` of 8 to 64 table slots a grid step
(PR 43's first two calls also split a block's pass into runs of 256 to 4,096
positions: the constant's comment has those rows), with ``off`` the
largest difference of its answer from the composed walk's over the largest
answer; a context of 0 stands for eight ragged lanes of 16,400 to 30,720
positions (mean 24.5k, as the cell's decode steps see) and an idle ninth;
``chunk`` a chunk of 2,048 rows from ``ctx - 2048`` on (the walk that expands
a block of positions to keys and values) at ``CHUNK_BLOCK_ROWS`` of 64, 128
and 256 (PR 42's first two calls had 256 to 4,096 and 128, 256, 512). A stage's repetitions run inside one jitted loop at two lengths and
its time is the difference a trip, so that it reads the device and not the
host's dispatch (benchmarks/ablate_sparse_attention.py says how). ``floor_ms``
is the larger of the bytes a call must read (every latent row a row meets,
once, at 1,152 B) over 819 GB/s and its flops at the cheaper form's count a
(row, position) pair (2 x 32 x 320) over 197 TFLOP/s. Rows go to
``chiprun_out/ablate_latent_attention.jsonl``. On the CPU the numbers mean
nothing and the sizes are cut to a toy."""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

HBM_BYTES_PER_S = 819e9
BF16_FLOPS_PER_S = 197e12  # perf/peaks.json's v5e


def main(contexts) -> None:
    from petals_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from petals_tpu.ops import latent_attention as la
    from petals_tpu.ops.paged_attention import PagedKV

    on_chip = jax.default_backend() == "tpu"
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    lanes, max_pages, ps, heads, dn, dr, dv, latent, chunk_rows, reps = (
        (8, 512, 64, 32, 128, 64, 128, 512, 2048, 20) if on_chip else (4, 8, 16, 4, 16, 64, 16, 32, 32, 2)
    )
    decode_only = "--decode-only" in contexts
    contexts = [int(c) for c in contexts if c != "--decode-only"] or ([16384, 24576, 30720, 0] if on_chip else [100, 0])
    ragged = (16400, 18000, 20480, 23000, 24576, 27000, 29500, 30720) if on_chip else (100, 7, 64, 128)
    n_pages = lanes * max_pages
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    dtype = jnp.bfloat16
    (c_rows, c_width), (pe_rows, pe_width) = la.latent_pool_rows(ps, latent, dr)
    c_pool = jax.random.normal(ks[0], (n_pages, c_rows, c_width), dtype)
    pe_pool = jax.random.normal(ks[1], (n_pages, pe_rows, pe_width), dtype)
    tables = jnp.asarray(np.random.default_rng(0).permutation(n_pages).astype(np.int32).reshape(lanes, max_pages))
    w_uk, w_uv = jax.random.normal(ks[2], (heads, dn, latent), dtype) * 0.05, jax.random.normal(ks[3], (heads, latent, dv), dtype) * 0.05
    scale = (dn + dr) ** -0.5

    def timed(fn, *args) -> float:
        def loop(trips, zero, first, *rest):
            def a_trip(_, dep):
                out = fn(first + (jnp.where(jnp.isfinite(dep), dep, 0.0) * zero).astype(first.dtype), *rest)
                return out.astype(jnp.float32).sum()

            return jax.lax.fori_loop(0, trips, a_trip, jnp.float32(0))

        f = jax.jit(loop)
        jax.block_until_ready(f(1, 0.0, *args))
        took = []
        for trips in (reps, 2 * reps):
            start = time.perf_counter()
            jax.block_until_ready(f(trips, 0.0, *args))
            took.append(time.perf_counter() - start)
        return (took[1] - took[0]) / reps * 1e3

    pair_flops, row_bytes = 2 * heads * (dn + dr + dv), (latent + dr) * 2
    with open(os.path.join(out_dir, "ablate_latent_attention.jsonl"), "a") as sink:
        for ctx in contexts:
            held = [ctx] * lanes if ctx else list(ragged)
            pos = jnp.asarray(held, jnp.int32) - 1
            q_nope, q_pe = jax.random.normal(ks[4], (lanes, 1, heads, dn), dtype), jax.random.normal(ks[5], (lanes, 1, heads, dr), dtype)

            def decode(path):
                def call(qn, qp, cp, pp, tb):
                    u = la.latent_decode_attend(la.absorb_queries(qn, w_uk), qp, PagedKV(cp, tb), PagedKV(pp, tb), pos, scale=scale, path=path)
                    return la.expand_outputs(u, w_uv)

                return call

            args = (q_nope, q_pe, c_pool, pe_pool, tables)
            floor = max(sum(held) * row_bytes / HBM_BYTES_PER_S, sum(held) * pair_flops / BF16_FLOPS_PER_S) * 1e3
            row = {"stage": "decode", "ctx": ctx, "block_rows": la.DECODE_BLOCK_ROWS, "ms": timed(decode("composed"), *args), "floor_ms": floor,
                   "timed": "loop", "device": jax.devices()[0].device_kind}
            print(json.dumps(row), flush=True)
            sink.write(json.dumps(row) + "\n")
            want = np.asarray(jax.jit(decode("composed"))(*args), np.float32)
            for pages in (8, 16, 24, 32, 48, 64) if on_chip else (2, 3):
                la.DECODE_KERNEL_PAGES = pages
                got = np.asarray(jax.jit(decode("kernel"))(*args), np.float32)
                row = {"stage": "decode_kernel", "ctx": ctx, "pages": pages, "ms": timed(decode("kernel"), *args), "floor_ms": floor,
                       "off": float(np.abs(got - want).max() / np.abs(want).max()), "timed": "loop", "device": jax.devices()[0].device_kind}
                print(json.dumps(row), flush=True)
                sink.write(json.dumps(row) + "\n")
            if decode_only or ctx < chunk_rows:
                continue
            first = ctx - chunk_rows
            c_nope, c_pe = jax.random.normal(ks[6], (1, chunk_rows, heads, dn), dtype), jax.random.normal(ks[7], (1, chunk_rows, heads, dr), dtype)

            def chunk(qn, qp, cp, pp, tb):
                return la.latent_chunk_attend(qn, qp, w_uk, w_uv, PagedKV(cp, tb[:1]), PagedKV(pp, tb[:1]), jnp.int32(first), jnp.int32(chunk_rows), scale=scale)

            pairs = chunk_rows * first + chunk_rows * (chunk_rows + 1) // 2
            floor = max(ctx * row_bytes / HBM_BYTES_PER_S, pairs * pair_flops / BF16_FLOPS_PER_S) * 1e3
            for block in (64, 128, 256) if on_chip else (16, 32):
                la.CHUNK_BLOCK_ROWS = block
                row = {"stage": "chunk", "ctx": ctx, "rows": chunk_rows, "block_rows": block, "ms": timed(chunk, c_nope, c_pe, c_pool, pe_pool, tables),
                       "floor_ms": floor, "timed": "loop", "device": jax.devices()[0].device_kind}
                print(json.dumps(row), flush=True)
                sink.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main(sys.argv[1:])
