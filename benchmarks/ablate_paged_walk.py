"""A decode row's attention over paged keys and values on the real chip: the
composed walk in blocks of table slots (ops/paged_flash_attention.py
``composed_paged_attend``) at every block width, the walk as ONE kernel that
reads each lane's own pages where they lie (``path="kernel"``, PR 45; a folded
row of fewer than 4 kv heads since PR 53, of 4 since PR 65) at 1 to 32 pages a
block (up to 2 MB a pool), and the path both replaced (gather every slot of every
lane, ``attend_reference`` over the dense view), one layer's call at the
cells' pool geometries and at lengths their traffic gives the lanes.

    chiprun -- python3 benchmarks/ablate_paged_walk.py [shape ...] [--stages dense,walk,kernel] [--fold]

What ``WALK_BLOCK_BYTES`` (PERF.md section 5, PR 36) and
``WALK_KERNEL_BLOCK_BYTES`` (PR 45) were set from. ``--stages`` keeps the
variants it names (``walk`` every width, ``walk1`` that one). ``--fold`` hands
the pools over folded (``[.., hkv * d]``) whatever the storage rule says of
their row: what the kernel would make of a pool no server stores that way
(Mixtral's and K-EXAONE's 8 x 128 as rows of 1,024); the rows it prints are
named ``<shape>+folded``. A call is
timed as the slope between chains of 2 and 10 calls in one program, each link
fed the last one's output and its tables made to wait for it, so that XLA can
neither drop a link nor gather once for all of them; the pools ride as jit
arguments. ``live MB`` is what the lanes hold of keys and values at those
lengths, ``floor ms`` that over 819 GB/s. On the CPU the numbers mean nothing
and the sizes are cut to a toy.
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# lanes, table slots, page size, kv heads (as the cache keeps them), head dim, q heads a kv head, the lanes' lengths
SHAPES = {
    "olmo-hybrid-7b": (8, 40, 64, 32, 128, 1, (1150, 1300, 1500, 1700, 1850, 2000, 2150, 2300)),  # ctx2k: 1,024-2,560
    "olmoe-1b-7b": (8, 16, 64, 16, 128, 1, (90, 130, 170, 210, 250, 290, 330, 370)),  # saturated: 64-128 in, 256 out
    "mixtral-8x7b": (8, 16, 64, 8, 128, 4, (90, 130, 170, 210, 250, 290, 330, 370)),  # chat: 16-120 in, 16-160 out
    "falcon-40b": (8, 16, 64, 8, 64, 16, (90, 130, 170, 210, 250, 290, 330, 370)),
    "k-exaone-236b": (8, 16, 64, 8, 128, 8, (90, 130, 170, 210, 250, 290, 330, 370)),
    "olmo-hybrid-7b-full": (8, 40, 64, 32, 128, 1, (2559,) * 8),  # every lane at the table's end
    "olmo-hybrid-7b-one": (8, 40, 64, 32, 128, 1, (2300,) + (0,) * 7),  # one live lane, seven on the idle sentinel (a length of 0)
    "qwen3-next-80b": (8, 40, 64, 2, 256, 8, (1150, 1300, 1500, 1700, 1850, 2000, 2150, 2300)),  # ctx2k; a folded row of 512: pages of 64 KB
    "qwen3-next-80b-four": (8, 40, 64, 2, 256, 8, (1150, 0, 1500, 0, 1850, 0, 2300, 0)),  # its usual step: four of eight lanes live
    # ctx16k, 16 lanes of 2k-15k: a full layer's table of 256 slots, and a windowed layer's cut to the 65 its window of 4,096 reaches
    "smallthinker-21b": (16, 256, 64, 4, 128, 7, (2500, 3300, 4100, 4900, 5700, 6500, 7300, 8100, 8900, 9700, 10500, 11300, 12100, 12900, 13700, 14500)),
    "smallthinker-21b-window": (16, 65, 64, 4, 128, 7, (2500, 3300) + (4160,) * 14),
    "jamba2-3b": (8, 40, 64, 1, 128, 20, (1150, 1300, 1500, 1700, 1850, 2000, 2150, 2300)),  # ctx2k; a folded row of 128: pages of 16 KB
}
LINKS = (2, 10)
FEED = 2.0 ** -10


def main(names, stages=("dense", "walk", "kernel"), fold: bool = False) -> None:
    from petals_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from petals_tpu.ops import paged_attention as pa
    from petals_tpu.ops import paged_flash_attention as pfa
    from petals_tpu.ops.attention import attend_reference

    on_chip = jax.default_backend() == "tpu"
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    sink_path = os.path.join(out_dir, "ablate_paged_walk.jsonl")

    def dense(q, kp, vp, tb, pos):  # the path the walk replaced
        hkv = pa.pool_geometry(kp, q.shape[-1])[2]
        return attend_reference(q, pa.gather_pages(kp, tb, hkv), pa.gather_pages(vp, tb, hkv), q_offset=pos, kv_length=pos + 1)

    def walk(q, kp, vp, tb, pos):
        return pfa.composed_paged_attend(q, kp, vp, tb, q_offset=pos, kv_length=pos + 1, path="composed")

    def kernel(q, kp, vp, tb, pos):
        return pfa.composed_paged_attend(q, kp, vp, tb, q_offset=pos, kv_length=pos + 1, path="kernel")

    def wanted(variant: str) -> bool:
        return any(variant == stage or variant.rstrip("0123456789") == stage for stage in stages)

    def timed(call, q, kp, vp, tb, pos) -> float:
        def chain(n):
            def f(qv, k, v, t, p):
                a = qv
                for _ in range(n):
                    wait = (a.ravel()[0].astype(jnp.float32) * 0.0).astype(jnp.int32)  # the tables wait for the link before
                    a = call(qv + a * FEED, k, v, t + wait, p)
                return a
            return jax.jit(f)
        ts = {}
        for n in LINKS:
            f = chain(n)
            jax.block_until_ready(f(q, kp, vp, tb, pos))
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                for _ in range(5):
                    out = f(q, kp, vp, tb, pos)
                jax.block_until_ready(out)
                best = min(best, (time.perf_counter() - t0) / 5)
            ts[n] = best
        return (ts[LINKS[1]] - ts[LINKS[0]]) / (LINKS[1] - LINKS[0]) * 1e3

    for name in names:
        n_lanes, max_pages, page_size, hkv, d, group, lengths = SHAPES[name]
        if not on_chip:
            hkv, max_pages, lengths = min(hkv, 16), 4, tuple(min(n, 200) for n in lengths)  # a toy the kernel still takes
        n_pages = n_lanes * max_pages
        rng = np.random.default_rng(0)
        tables = rng.permutation(n_pages).astype(np.int32).reshape(n_lanes, max_pages)
        idle = np.asarray(lengths) == 0
        pos = np.where(idle, max_pages * page_size, np.asarray(lengths) - 1).astype(np.int32)
        held = np.where(idle, 0, -(-(pos + 1) // page_size))
        for lane in range(n_lanes):
            tables[lane, held[lane]:] = -1
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(kq, (n_lanes, 1, hkv * group, d), jnp.bfloat16)
        kp = jax.random.normal(kk, (n_pages, page_size, hkv, d), jnp.bfloat16)
        vp = jax.random.normal(kv, (n_pages, page_size, hkv, d), jnp.bfloat16)
        # the pools in the form a server stores them in (folded at a head_dim of 64, and up to 4 kv heads), or folded as asked
        row = (hkv * d,) if fold else pa.stored_row(hkv, d)
        unfolded = (q, kp, vp, jnp.asarray(tables), jnp.asarray(pos))
        args = (q, pa.fold_rows(kp, row), pa.fold_rows(vp, row), *unfolded[3:])
        a_slot = n_lanes * page_size * hkv * d * 2
        live_mb = 2 * int(np.where(idle, 0, pos + 1).sum()) * hkv * d * 2 / 1e6
        want = np.asarray(jax.jit(dense)(*args), np.float32)[~idle]
        rows = [("dense", None, timed(dense, *args))] if wanted("dense") else []
        widths = sorted({w for w in (1, 2, 4, 8, 16, 32, 64, max_pages) if w <= max_pages})
        for block in widths:
            if not wanted(f"walk{block}"):
                continue
            pfa.WALK_BLOCK_BYTES = (1 << (block - 1).bit_length()) * a_slot  # the whole row: the power of two over it
            pfa.WALK_MAX_TRIPS = max_pages  # every width as asked, whatever the table's
            assert pfa.walk_block_pages(n_lanes, max_pages, page_size, hkv, d) == block
            got = np.asarray(jax.jit(lambda *a: walk(*a))(*args), np.float32)[~idle]  # a new program a width
            err = float(np.max(np.abs(got - want)))
            rows.append((f"walk{block}", err, timed(walk, *args)))
            if len(row) == 1 and block == 1:  # what the fold costs the walk: the same walk over pools of [hkv, d] rows
                rows.append(("walk1-rows-of-hkv-d", None, timed(walk, *unfolded)))
        # the kernel, where it takes the pool as it is stored, at 1 to 32 pages of one lane a block (up to 2 MB a pool)
        takes = pfa.walk_kernel_unsupported(args[1], q.shape, tables.shape) is None
        for block in [b for b in (1, 2, 4, 8, 16, 32) if b * page_size * hkv * d * 2 <= 2 << 20 and b <= max_pages] if takes else ():
            if not wanted(f"kernel{block}") or (not on_chip and block > 1):
                continue
            pfa.WALK_KERNEL_BLOCK_BYTES = block * page_size * hkv * d * 2
            got = np.asarray(jax.jit(lambda *a: kernel(*a))(*args), np.float32)[~idle]
            rows.append((f"kernel{block}", float(np.max(np.abs(got - want))) if got.size else 0.0, timed(kernel, *args)))
        for variant, err, ms in rows:
            line = {"shape": name + "+folded" * fold, "variant": variant, "ms": round(ms, 4), "max_err": err, "live_mb": round(live_mb, 1),
                    "floor_ms": round(live_mb / 819e3 * 1e3, 4), "longest_slots": int(held.max()), "device": jax.devices()[0].device_kind}
            print(json.dumps(line), flush=True)
            with open(sink_path, "a") as sink:
                print(json.dumps(line), file=sink)


if __name__ == "__main__":
    import argparse

    parser = argparse.ArgumentParser()
    parser.add_argument("shapes", nargs="*", default=list(SHAPES))
    parser.add_argument("--stages", default="dense,walk,kernel")
    parser.add_argument("--fold", action="store_true")
    cli = parser.parse_args()
    main(cli.shapes, tuple(cli.stages.split(",")), cli.fold)
