"""The sparse attention's three device stages on the real chip, one layer's
call at the cell ``keyevl2-ctx32k``'s shapes (8 lanes, tables of 512 pages of
64, 4 kv heads of 128 under 32 query heads, an index row of 64 scored by 16
heads, 2,048 positions kept), each alone and the whole call, beside the walk
over every page that a dense attention would make:

    chiprun -- python3 benchmarks/ablate_sparse_attention.py [--decode-only] [ctx ...]

A stage's repetitions run inside one jitted loop (the pools ride as
arguments, nothing is donated; each trip's first argument is nudged by the
trip before's result times a zero the compiler cannot see, so no trip is
hoisted or dropped, and what a stage wrote is summed into that result: one
more read of it). The loop runs at two lengths and a stage's time is the
difference a trip, so that it reads the device and not the host's dispatch.
Until PR 40 a stage was timed as back-to-back jitted calls, and every row of
``chiprun_out/ablate_sparse_attention.jsonl`` without a ``timed`` key was
taken so: of those, the stages under ~0.25 ms read the host's dispatch and
not the chip (``index_score`` 0.237 | 0.269 for 0.165 in the step,
``select_top_k`` 0.369 | 0.377 for 0.195, ``select_bisect`` 0.226 | 0.259),
and ``sparse_decode`` (0.863 | 0.940 for 0.926 in the step) and the rows of a
millisecond and more were the device's. ``floor_ms`` is the larger
of the bytes a stage must read over 819 GB/s and its flops over 197 TFLOP/s
(a stage's need is what it must read once and write once: a selection reads
its scores and writes a mask or indices, whatever passes it makes over them
meanwhile, and a chunk's whole call reads the lane's index keys, keys and
values once: intermediate scores are no need, so no share can pass 100). A
prompt chunk's stages are timed at the budget's 2,048 rows
and at 512. The decode call's stages: ``index_score``, ``select_rows`` (one
sort of (score, position) pairs and the chosen positions' pool rows read off
the table by a compare), ``fetch`` (the chosen rows of keys and of values)
and ``fetch_attend`` (the fetch and both dots), beside what they replaced:
``select_top_k`` with ``lookup`` (the chosen positions' pages out of the
tables, a scalar at a time), ``fetch_filled_attend`` (``jnp.take``'s default
mode, whose fill selects every fetched row and has the dots copy them), and
the way not taken, ``sort_rows_stable`` (the pool rows riding a stable sort
of one key: compiled as a sort of three operands).
On the CPU the numbers mean nothing and the sizes are cut to a toy.
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

REPS = 50
HBM_BYTES_PER_S = 819e9
BF16_FLOPS_PER_S = 197e12  # perf/peaks.json's v5e


def main(contexts) -> None:
    from petals_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from petals_tpu.ops import paged_flash_attention as pfa
    from petals_tpu.ops import sparse_attention as sa
    from petals_tpu.ops.paged_attention import PagedKV

    on_chip = jax.default_backend() == "tpu"
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    sink_path = os.path.join(out_dir, "ablate_sparse_attention.jsonl")
    lanes, max_pages, ps, hkv, d, group, heads, d_idx, topk, chunks = (
        (8, 512, 64, 4, 128, 8, 16, 64, 2048, (2048, 512)) if on_chip else (4, 8, 16, 2, 32, 2, 4, 16, 32, (16,))
    )
    if "--decode-only" in contexts:  # leave a prompt chunk's stages out
        chunks, contexts = (), [c for c in contexts if c != "--decode-only"]
    if "--index-block" in contexts:  # positions a block of the index keys' walk: ops/sparse_attention.py INDEX_BLOCK_ROWS
        at = contexts.index("--index-block")
        sa.INDEX_BLOCK_ROWS = int(contexts[at + 1])
        contexts = contexts[:at] + contexts[at + 2:]
    contexts = [int(c) for c in contexts] or ([16384, 30000] if on_chip else [100])
    n_pages, max_length = lanes * max_pages, max_pages * ps
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    dtype = jnp.bfloat16
    k_pool = jax.random.normal(ks[0], (n_pages, ps, hkv, d), dtype)
    v_pool = jax.random.normal(ks[1], (n_pages, ps, hkv, d), dtype)
    i_pool = jax.random.normal(ks[2], (n_pages, *sa.index_pool_row(ps, d_idx)), dtype)  # as the server stores it
    tables = jnp.asarray(np.random.default_rng(0).permutation(n_pages).astype(np.int32).reshape(lanes, max_pages))
    q = jax.random.normal(ks[3], (lanes, 1, hkv * group, d), dtype)
    q_idx = jax.random.normal(ks[4], (lanes, 1, heads, d_idx), dtype)
    w_idx = jax.random.normal(ks[5], (lanes, 1, heads), jnp.float32)

    def timed(fn, *args, reps=REPS) -> float:
        def loop(trips, zero, first, *rest):
            def a_trip(_, dep):
                out = fn(first + (jnp.where(jnp.isfinite(dep), dep, 0.0) * zero).astype(first.dtype), *rest)
                return sum(leaf.astype(jnp.float32).sum() for leaf in jax.tree_util.tree_leaves(out))

            return jax.lax.fori_loop(0, trips, a_trip, jnp.float32(0))

        f = jax.jit(loop)
        jax.block_until_ready(f(1, 0.0, *args))
        took = []
        for trips in (reps, 2 * reps):
            start = time.perf_counter()
            jax.block_until_ready(f(trips, 0.0, *args))
            took.append(time.perf_counter() - start)
        return (took[1] - took[0]) / reps * 1e3

    rows = []
    for ctx in contexts:
        pos = jnp.full((lanes,), ctx - 1, jnp.int32)
        kv_len = pos + 1
        walk = lambda qi, wi, ip, tb: sa._walk_index_scores(qi, wi, PagedKV(ip, tb), kv_len, ps)
        scores = jax.jit(walk)(q_idx, w_idx, i_pool, tables)
        seen = jnp.arange(max_length)[None, None, :] < ctx
        top_k = lambda s: jax.lax.top_k(jnp.where(seen[0], s, -jnp.inf), topk)[1]
        chosen = jax.jit(top_k)(scores[:, 0])
        select = lambda s, tb: sa.select_rows(s, kv_len, tb, ps, topk)
        picked, taken = jax.jit(select)(scores[:, 0], tables)
        fetch = lambda r, kp, vp: (sa._take_rows(kp, r, hkv), sa._take_rows(vp, r, hkv))
        fetch_filled = lambda r, kp, vp: tuple(jnp.take(p.reshape(-1, hkv, d), r, axis=0) for p in (kp, vp))  # jnp.take's default mode
        row_bytes, attend_flops = 2 * hkv * d * 2, 4 * lanes * min(topk, ctx) * hkv * group * d
        kv_bytes = lanes * min(topk, ctx) * row_bytes
        grouped = lambda k, v: sa._grouped_attend(q, k, v, taken[:, None, :], d**-0.5)

        def sort_rows_stable(s, tb):  # the way not taken: the pool rows ride a stable sort of one key, which is compiled as a sort of three operands
            lane_rows = (tb[:, :, None] * ps + jnp.arange(ps, dtype=jnp.int32)).reshape(lanes, max_length)
            keys = jnp.where(seen[0], sa._ordered_keys(s), jnp.uint32(0))
            return jax.lax.sort((~keys, lane_rows), dimension=1, is_stable=True, num_keys=1)[1][:, :topk]

        # name -> (program, arguments, bytes it must read, flops it must do, repetitions)
        stages = {
            "index_score": (walk, (q_idx, w_idx, i_pool, tables), lanes * ctx * d_idx * 2, 2 * lanes * ctx * heads * d_idx, REPS),
            "select_rows": (select, (scores[:, 0], tables), lanes * (max_length + 2 * topk) * 4, 0, REPS),
            "fetch": (fetch, (picked, k_pool, v_pool), kv_bytes, 0, REPS),
            "fetch_attend": (lambda r, kp, vp: grouped(*fetch(r, kp, vp)), (picked, k_pool, v_pool), kv_bytes, attend_flops, REPS),
            "select_top_k": (top_k, (scores[:, 0],), lanes * (max_length + 2 * topk) * 4, 0, REPS),
            "lookup": (lambda c, tb: jnp.take_along_axis(tb, c // ps, axis=1) * ps + c % ps, (chosen, tables), lanes * (max_pages + 2 * topk) * 4, 0, REPS),
            "sort_rows_stable": (sort_rows_stable, (scores[:, 0], tables), lanes * (max_length + 2 * topk) * 4, 0, REPS),
            "fetch_filled_attend": (lambda r, kp, vp: grouped(*fetch_filled(r, kp, vp)), (picked, k_pool, v_pool), kv_bytes, attend_flops, REPS),
            "select_bisect": (lambda s: sa.select_mask(s, jnp.broadcast_to(seen, s.shape), topk), (scores,), lanes * max_length * 5, 0, REPS),
            "sparse_decode": (
                lambda qi, qv, wi, kp, vp, ip, tb: sa.sparse_decode_attend(
                    qv, qi, wi, PagedKV(kp, tb), PagedKV(vp, tb), PagedKV(ip, tb), pos, topk=topk),
                (q_idx, q, w_idx, k_pool, v_pool, i_pool, tables),
                lanes * (ctx * d_idx * 2 + min(topk, ctx) * 2 * hkv * d * 2),
                2 * lanes * ctx * heads * d_idx + 4 * lanes * min(topk, ctx) * hkv * group * d, REPS,
            ),
            "dense_walk": (
                lambda qv, kp, vp, tb: pfa.composed_paged_attend(qv, kp, vp, tb, q_offset=pos, kv_length=kv_len),
                (q, k_pool, v_pool, tables), lanes * ctx * 2 * hkv * d * 2, 4 * lanes * ctx * hkv * group * d, REPS,
            ),
        }
        for chunk in chunks:
            cq = jax.random.normal(ks[6], (1, chunk, hkv * group, d), dtype)
            cq_idx = jax.random.normal(ks[7], (1, chunk, heads, d_idx), dtype)
            cw_idx = jax.random.normal(ks[5], (1, chunk, heads), jnp.float32)
            cscores = jax.random.normal(ks[0], (chunk, max_length), jnp.float32)
            causal = jnp.arange(max_length)[None, :] <= (ctx - chunk + jnp.arange(chunk))[:, None]
            stages.update({
                f"chunk{chunk}_index_score": (
                    lambda qi, wi, ip, tb: sa._walk_index_scores(qi, wi, PagedKV(ip, tb), jnp.full((1,), ctx, jnp.int32), ps),
                    (cq_idx, cw_idx, i_pool, tables[:1]), ctx * d_idx * 2 + chunk * max_length * 4, 2 * chunk * ctx * heads * d_idx, 5),
                f"chunk{chunk}_select": (lambda s, c: sa.select_mask(s, c, topk), (cscores, causal), chunk * max_length * 5, 0, 5),
                f"sparse_chunk{chunk}": (
                    lambda qi, qv, wi, kp, vp, ip, tb: sa.sparse_chunk_attend(
                        qv, qi, wi, PagedKV(kp, tb), PagedKV(vp, tb), PagedKV(ip, tb), jnp.int32(ctx - chunk), jnp.int32(chunk), topk=topk),
                    (cq_idx, cq, cw_idx, k_pool, v_pool, i_pool, tables[:1]),
                    ctx * (d_idx * 2 + 2 * hkv * d * 2),
                    2 * chunk * ctx * heads * d_idx + 4 * chunk * ctx * hkv * group * d, 5,
                ),
            })
        for name, (fn, args, nbytes, flops, reps) in stages.items():
            try:
                ms = timed(fn, *args, reps=reps)
            except Exception as e:  # one stage that does not compile must not hide the others
                rows.append({"ctx": ctx, "stage": name, "error": repr(e)[:300]})
                print(rows[-1], flush=True)
                continue
            floor = max(nbytes / HBM_BYTES_PER_S, flops / BF16_FLOPS_PER_S) * 1e3
            rows.append({"ctx": ctx, "stage": name, "ms": round(ms, 4), "read_mb": round(nbytes / 1e6, 2), "gflop": round(flops / 1e9, 3),
                         "floor_ms": round(floor, 4), "roofline_pct": round(100 * floor / ms, 1), "timed": "in_one_loop",
                         "device": jax.devices()[0].device_kind})
            print(rows[-1], flush=True)
    with open(sink_path, "a") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main(sys.argv[1:])
