"""The sparse attention's three device stages on the real chip, one layer's
call at the cell ``keyevl2-ctx32k``'s shapes (8 lanes, tables of 512 pages of
64, 4 kv heads of 128 under 32 query heads, an index row of 64 scored by 16
heads, 2,048 positions kept), each alone and the whole call, beside the walk
over every page that a dense attention would make:

    chiprun -- python3 benchmarks/ablate_sparse_attention.py [ctx ...]

A stage is timed as the mean of back-to-back calls of its own jitted program
(the pools ride as arguments, nothing is donated). ``floor_ms`` is the larger
of the bytes a stage must read over 819 GB/s and its flops over 197 TFLOP/s
(a stage's need is what it must read once and write once: a selection reads
its scores and writes a mask or indices, whatever passes it makes over them
meanwhile, and a chunk's whole call reads the lane's index keys, keys and
values once: intermediate scores are no need, so no share can pass 100). A
prompt chunk's stages are timed at the budget's 2,048 rows
and at 512. On the CPU the numbers mean nothing and the sizes are cut to a toy.
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

REPS = 20
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
        f = jax.jit(fn)
        jax.block_until_ready(f(*args))
        start = time.perf_counter()
        out = None
        for _ in range(reps):
            out = f(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - start) / reps * 1e3

    rows = []
    for ctx in contexts:
        pos = jnp.full((lanes,), ctx - 1, jnp.int32)
        kv_len = pos + 1
        walk = lambda qi, wi, ip, tb: sa._walk_index_scores(qi, wi, PagedKV(ip, tb), kv_len, ps)
        scores = jax.jit(walk)(q_idx, w_idx, i_pool, tables)
        seen = jnp.arange(max_length)[None, None, :] < ctx
        # name -> (program, arguments, bytes it must read, flops it must do, repetitions)
        stages = {
            "index_score": (walk, (q_idx, w_idx, i_pool, tables), lanes * ctx * d_idx * 2, 2 * lanes * ctx * heads * d_idx, REPS),
            "select_top_k": (lambda s: jax.lax.top_k(jnp.where(seen[0], s[:, 0], -jnp.inf), topk), (scores,), lanes * (max_length + 2 * topk) * 4, 0, REPS),
            "select_bisect": (lambda s: sa.select_mask(s, jnp.broadcast_to(seen, s.shape), topk), (scores,), lanes * max_length * 5, 0, REPS),
            "sparse_decode": (
                lambda qv, qi, wi, kp, vp, ip, tb: sa.sparse_decode_attend(
                    qv, qi, wi, PagedKV(kp, tb), PagedKV(vp, tb), PagedKV(ip, tb), pos, topk=topk),
                (q, q_idx, w_idx, k_pool, v_pool, i_pool, tables),
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
                    lambda qv, qi, wi, kp, vp, ip, tb: sa.sparse_chunk_attend(
                        qv, qi, wi, PagedKV(kp, tb), PagedKV(vp, tb), PagedKV(ip, tb), jnp.int32(ctx - chunk), jnp.int32(chunk), topk=topk),
                    (cq, cq_idx, cw_idx, k_pool, v_pool, i_pool, tables[:1]),
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
                         "floor_ms": round(floor, 4), "roofline_pct": round(100 * floor / ms, 1),
                         "device": jax.devices()[0].device_kind})
            print(rows[-1], flush=True)
    with open(sink_path, "a") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main(sys.argv[1:])
