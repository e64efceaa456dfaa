"""Attention over a paged pool (ops/paged_flash_attention.py): a decode row's
walk, composed and as one kernel (interpreted on the CPU), and the fused
chunked-prefill kernel (interpreted), each against the reference
(gather_pages + attend_reference, or NumPy) across table layouts
(dense/identity, permuted, holey), ragged lengths (position 0, page
boundaries), ALiBi, sliding windows and GQA ratios; and the dispatch's table:
which call reaches which implementation."""

import numpy as np
import pytest

import jax.numpy as jnp

from petals_tpu.ops import paged_flash_attention as pfa
from petals_tpu.ops.attention import attend, attend_reference
from petals_tpu.ops.paged_attention import (
    PagedKV,
    fold_rows,
    gather_pages,
    identity_tables,
    paged_prefill_attend,
    stored_row,
)
from petals_tpu.ops.paged_flash_attention import paged_flash_prefill_attend
from tests.utils import lane_pools

pytestmark = pytest.mark.kernel

# the online-softmax accumulation order differs from the reference's one-shot
# softmax; f32 agreement lands ~1e-6 at these shapes
TOL = 2e-5


def _rand_pool(rng, n_pages, ps, hkv, d):
    k = jnp.asarray(rng.standard_normal((n_pages, ps, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((n_pages, ps, hkv, d)), jnp.float32)
    return k, v


def _holey_permuted(rng, n_lanes, max_pages, n_pages, used_slots):
    """A permuted table where each lane keeps only ``used_slots[l]`` slots
    allocated (the rest are -1 holes)."""
    tables = np.full((n_lanes, max_pages), -1, np.int32)
    free = list(rng.permutation(n_pages))
    for l in range(n_lanes):
        for s in range(used_slots[l]):
            tables[l, s] = free.pop()
    return tables


def test_gather_pages_zeroes_unallocated_slots():
    """The XLA fallback's dense view must read -1 slots as ZEROS — never page
    0's live bytes (the old behaviour clipped -1 to page 0)."""
    n_pages, ps, hkv, d = 4, 4, 1, 8
    pool = jnp.full((n_pages, ps, hkv, d), 7.0, jnp.float32)  # page 0 is "live"
    tables = jnp.asarray(np.array([[2, -1], [-1, -1]], np.int32))
    dense = np.asarray(gather_pages(pool, tables))
    assert dense.shape == (2, 2 * ps, hkv, d)
    np.testing.assert_array_equal(dense[0, :ps], 7.0)  # allocated slot reads through
    np.testing.assert_array_equal(dense[0, ps:], 0.0)  # hole -> zeros
    np.testing.assert_array_equal(dense[1], 0.0)


# ------------------------------------------------- the composed path's walk

# a lane a position (None: an idle lane, at the sentinel max_length), kv heads 2 unless ``hkv`` says; ``block``: table
# slots a block of the walk (the rule gives the whole row at these toy sizes, so the cases set the bytes it goes by)
WALK_CASES = {
    "ragged-16slots-g1-d64": dict(max_pages=16, group=1, d=64, block=4, positions=[127, 3, 7, 0]),  # the table's end beside one page
    "ragged-40slots-g4-d128": dict(max_pages=40, group=4, d=128, block=8, positions=[319, 5, 64, None]),
    "g16-d64-idle-between": dict(max_pages=16, group=16, d=64, block=2, positions=[50, None, 9, 100]),
    "holes-inside-the-length": dict(max_pages=16, group=4, d=64, block=4, positions=[90, 33, 8, 70], holes=[(0, 2), (3, 0)]),
    "identity-tables": dict(max_pages=16, group=1, d=128, block=8, positions=[64, 63, None, 1], identity=True),
    "one-block-is-the-row": dict(max_pages=16, group=4, d=64, block=16, positions=[127, 0, None, 40]),
    "width-no-multiple-of-the-block": dict(max_pages=10, group=1, d=64, block=4, positions=[79, 31, 32, None]),
    "every-lane-idle": dict(max_pages=16, group=1, d=64, block=4, positions=[None, None, None, None]),
    "window128": dict(max_pages=16, group=4, d=64, block=4, ps=16, window=128, positions=[255, 10, 130, None]),
    "int8-pool": dict(max_pages=16, group=4, d=64, block=4, positions=[127, 3, None, 77], kv_quant="int8"),
    "nf4a-pool": dict(max_pages=40, group=1, d=128, block=8, positions=[200, 319, 15, None], kv_quant="nf4a"),
    "alibi": dict(max_pages=16, group=4, d=64, block=4, positions=[100, 3, None, 31], alibi=True),
    "softcap-traced-window": dict(max_pages=16, group=1, d=64, block=4, positions=[100, 3, None, 31], softcap=30.0, traced_window=20),
    # position 0, a page's last row, a page's first row, mid-page, on the dense layout
    "identity-tables-at-0-a-page-s-last-and-a-page-s-first": dict(max_pages=4, group=2, d=32, block=2, ps=16, positions=[0, 15, 32, 53], identity=True),
    # 7 pages for 3 lanes of 4 slots: the lanes' pages lie scattered, the slots they do not fill are holes
    "oversubscribed-permuted-pool-with-holes": dict(max_pages=4, group=4, d=16, block=2, positions=[23, 15, 8], pool=7),
    # somebody else's live page in every slot past a lane's end, inside the block that is read: bit the same answer
    "garbage-pages-past-a-lane-s-end": dict(max_pages=4, group=2, d=16, block=4, positions=[11, 15], garbage=True),
    **{f"{g}-query-heads-a-kv-head": dict(max_pages=3, group=g, hkv=8 // g, d=16, block=2, positions=[16, 23]) for g in (1, 2, 4, 8)},
    **{f"alibi-window-{w}": dict(max_pages=4, group=2, d=16, block=2, positions=[0, 15, 31], alibi=True, window=w) for w in (None, 5, 20)},
}


@pytest.mark.parametrize("case", WALK_CASES.values(), ids=WALK_CASES.keys())
def test_decode_row_walks_its_lane_s_pages_and_gives_the_dense_view_s_answer(case, monkeypatch):
    """``composed_paged_attend`` for a decode row (per-lane positions, one
    query row a lane): the walk over the table in blocks of slots with a
    running softmax against ``attend_reference`` over ``gather_pages`` of the
    whole table, at float32-accumulation tolerance. And the walk ends with the
    block that holds the longest LIVE lane's last row: every slot past that
    block points at a page of NaN, in every lane's row, the idle lanes' too,
    and no NaN comes out (a weight of zero times NaN is NaN)."""
    from petals_tpu.ops.paged_attention import PagedPool, quantize_kv_rows

    n_lanes, hkv, ps = len(case["positions"]), case.get("hkv", 2), case.get("ps", 8)
    max_pages, group, d, block = case["max_pages"], case["group"], case["d"], case["block"]
    rng = np.random.default_rng(11)
    n_pages = case.get("pool", n_lanes * max_pages) + 1  # the last one is the page of NaN
    kp, vp = _rand_pool(rng, n_pages, ps, hkv, d)
    kp, vp = kp.at[-1].set(jnp.nan), vp.at[-1].set(jnp.nan)
    idle = np.asarray([p is None for p in case["positions"]])
    pos = np.asarray([max_pages * ps if p is None else p for p in case["positions"]], np.int32)
    held = np.where(idle, 0, pos // ps + 1)
    if case.get("identity"):
        tables = identity_tables(n_lanes, max_pages).copy()
    elif "pool" in case:
        tables = _holey_permuted(rng, n_lanes, max_pages, n_pages - 1, held)
    else:
        tables = rng.permutation(n_pages - 1).astype(np.int32).reshape(n_lanes, max_pages)
    for lane in range(n_lanes):
        tables[lane, held[lane]:] = -1  # a lane holds the pages its rows fill
    for lane, slot in case.get("holes", ()):
        tables[lane, slot] = -1
    clean = tables.copy()
    window = case.get("window")
    if window is None:  # (under a static window each lane's row is cut to its own reach first: no common last block)
        walked = -(-int(held.max()) // block) * block
        tables[:, walked:] = n_pages - 1
    kind = case.get("kv_quant")
    if kind:
        kp, vp = PagedPool(*quantize_kv_rows(kp, kind)), PagedPool(*quantize_kv_rows(vp, kind))
    q = jnp.asarray(rng.standard_normal((n_lanes, 1, hkv * group, d)), jnp.float32)
    kw = dict(q_offset=jnp.asarray(pos), kv_length=jnp.asarray(pos) + 1, sliding_window=window)
    if case.get("alibi"):
        kw["alibi_slopes"] = jnp.asarray(rng.standard_normal(hkv * group) * 0.1, jnp.float32)
    if case.get("softcap"):
        kw.update(logit_softcap=case["softcap"], sliding_window=jnp.int32(case["traced_window"]))

    monkeypatch.setattr(pfa, "WALK_BLOCK_BYTES", block * n_lanes * ps * hkv * d * jnp.dtype(kp.dtype).itemsize)
    width = pfa.window_pages(window, 1, ps, max_pages)
    assert pfa.walk_block_pages(n_lanes, width, ps, hkv, d, jnp.dtype(kp.dtype).itemsize) == min(block, width)
    got = np.asarray(pfa.composed_paged_attend(q, kp, vp, jnp.asarray(tables), **kw))
    want = attend_reference(q, gather_pages(kp, jnp.asarray(clean)), gather_pages(vp, jnp.asarray(clean)), **kw)
    assert np.isfinite(got).all(), "the walk read past the block of the longest live lane's last row"
    # a quantised pool reads as bf16, and the weights meet V in V's dtype
    np.testing.assert_allclose(got[~idle], np.asarray(want)[~idle], atol=1e-2 if kind else TOL, rtol=0)
    np.testing.assert_array_equal(got[idle], 0.0)  # an idle lane attends to nothing
    if case.get("garbage"):
        other_s = np.where(tables < 0, clean[0, 0], tables)  # lane 0's first page, live and finite, where the holes were
        assert (other_s != tables).any() and walked > held.min()
        np.testing.assert_array_equal(got, np.asarray(pfa.composed_paged_attend(q, kp, vp, jnp.asarray(other_s), **kw)))


def test_walk_block_follows_the_shapes_it_sees():
    """The block's width at the cells' pools (8 lanes, pages of 64, bf16): one
    page; smaller pages go several a block, never more than there are."""
    for hkv, d in ((32, 128), (16, 128), (8, 128), (8, 64)):  # olmo-hybrid-7b, olmoe-1b-7b, mixtral / k-exaone, falcon
        assert pfa.walk_block_pages(8, 40, 64, hkv, d) == 1
    assert pfa.walk_block_pages(8, 64, 16, 8, 128) == 2 and pfa.walk_block_pages(8, 64, 16, 8, 64) == 4
    assert pfa.walk_block_pages(2, 3, 16, 8, 64) == 3 and pfa.walk_block_pages(8, 64, 16, 8, 64, itemsize=4) == 2
    assert pfa.walk_pages(36, 4) == 36 and pfa.walk_pages(6, 8) == 8 and pfa.walk_pages(0, 8) == 0 and pfa.walk_pages(37, 4) == 40


# ------------------------------------------------- the walk as one kernel

# n_lanes 4, pages of 16 rows, 16 kv heads of 128 unless a case says another ``hkv`` / ``d``; a lane's position, or None for
# an idle lane. ``block``: table slots of one lane a grid step takes (the cases set the bytes the rule goes by). A
# ``folded-`` case hands the kernel the pool as the storage rule keeps a row of up to 4 kv heads (``stored_row``: ``[.., hkv * d]``)
KERNEL_WALK_CASES = {
    "lanes-shorter-than-a-block": dict(max_pages=8, block=4, positions=[5, 20, 40, 0]),
    "ending-on-a-block-s-and-on-a-page-s-last-position": dict(max_pages=8, block=2, positions=[31, 15, 63, 47]),
    "an-idle-lane-among-live-ones": dict(max_pages=8, block=2, positions=[50, None, 9, 100]),
    "no-live-lane": dict(max_pages=8, block=2, positions=[None, None, None, None]),
    "blocks-that-do-not-divide-the-table": dict(max_pages=10, block=4, positions=[159, 31, 32, None]),
    "window128-table-cut-to-its-reach": dict(max_pages=16, block=2, window=128, positions=[255, 10, 130, None]),
    "window128-whole-table-blocks-before-its-reach": dict(max_pages=9, block=1, window=128, positions=[143, 130, 20, None]),
    "4-query-heads-a-kv-head": dict(max_pages=8, block=2, group=4, positions=[100, 3, None, 77]),
    "8-query-heads-a-kv-head": dict(max_pages=8, block=4, group=8, positions=[127, 64, 1, None]),
    "32-kv-heads": dict(max_pages=8, block=2, hkv=32, positions=[90, None, 33, 8]),
    "float32-pool": dict(max_pages=8, block=2, hkv=8, dtype="float32", positions=[100, 3, None, 77]),
    "identity-tables-at-0-a-page-s-last-and-a-page-s-first": dict(max_pages=8, block=2, identity=True, positions=[0, 15, 32, 53]),
    "garbage-pages-past-a-lane-s-end": dict(max_pages=8, block=4, garbage=True, positions=[19, 40, 5, None]),
    "2-query-heads-a-kv-head": dict(max_pages=8, block=2, group=2, positions=[16, 23, 100, None]),
    "folded-2-kv-heads-of-256-ragged-lanes": dict(max_pages=8, block=2, hkv=2, d=256, group=8, positions=[100, 3, None, 77]),
    "folded-2-kv-heads-of-128": dict(max_pages=8, block=4, hkv=2, d=128, group=4, positions=[127, 64, 1, None]),
    "folded-1-kv-head-of-128-under-20-query-heads": dict(max_pages=8, block=4, hkv=1, d=128, group=20, positions=[19, 40, 5, 90]),
    "folded-1-kv-head-of-256": dict(max_pages=8, block=2, hkv=1, d=256, group=2, positions=[50, None, 9, 100]),
    "folded-3-kv-heads-of-128-float32": dict(max_pages=8, block=2, hkv=3, d=128, group=2, dtype="float32", positions=[31, 15, 63, 47]),
    "folded-2-kv-heads-of-256-3-query-heads-a-kv-head": dict(max_pages=8, block=2, hkv=2, d=256, group=3, positions=[100, 3, None, 77]),
    "folded-a-fresh-lane-at-position-0": dict(max_pages=8, block=4, hkv=2, d=256, group=8, positions=[0, 20, None, 0]),
    "folded-no-live-lane": dict(max_pages=8, block=2, hkv=2, d=256, group=8, positions=[None, None, None, None]),
    "folded-blocks-that-do-not-divide-the-table": dict(max_pages=10, block=4, hkv=2, d=256, group=8, positions=[159, 31, 32, None]),
    "folded-window128-table-cut-to-its-reach": dict(max_pages=16, block=2, hkv=2, d=256, group=8, window=128, positions=[255, 10, 130, None]),
    "folded-window128-whole-table": dict(max_pages=9, block=1, hkv=1, d=128, group=20, window=128, positions=[143, 130, 20, None]),
    "folded-garbage-pages-past-a-lane-s-end": dict(max_pages=8, block=4, hkv=2, d=256, group=8, garbage=True, positions=[19, 40, 5, None]),
    "folded-identity-tables": dict(max_pages=8, block=2, hkv=1, d=128, group=4, identity=True, positions=[0, 15, 32, 53]),
    # SmallThinker's row (PR 65): 28 query heads over 4 kv heads of 128, a full layer's walk and a windowed layer's, an idle lane in each
    "folded-4-kv-heads-of-128-under-28-query-heads": dict(max_pages=8, block=4, hkv=4, d=128, group=7, positions=[100, 3, None, 77]),
    "folded-4-kv-heads-of-128-under-28-query-heads-window128": dict(max_pages=16, block=2, hkv=4, d=128, group=7, window=128, positions=[255, 10, 130, None]),
    "folded-4-kv-heads-of-128-under-28-query-heads-window128-whole-table": dict(max_pages=9, block=1, hkv=4, d=128, group=7, window=128, positions=[143, None, 20, 130]),
}


def _numpy_decode_rows(q, kp, vp, tables, positions, idle, window, ps):
    """One query row a lane over its table's pages in float32 NumPy: the
    softmax over the positions in sight whole, no blocks."""
    q, kp, vp = (np.asarray(a, np.float32) for a in (q, kp, vp))
    n_lanes, _, hq, d = q.shape
    group = hq // kp.shape[2]
    out = np.zeros((n_lanes, 1, hq, d), np.float32)
    for lane in np.flatnonzero(~idle):
        at = np.arange(0 if window is None else max(positions[lane] - window + 1, 0), positions[lane] + 1)
        pages = tables[lane, at // ps]
        assert (pages >= 0).all()
        k, v = kp[pages, at % ps], vp[pages, at % ps]  # [positions, hkv, d]
        s = np.einsum("kgd,skd->kgs", q[lane, 0].reshape(-1, group, d), k) * d**-0.5
        p = np.exp(s - s.max(axis=-1, keepdims=True))
        out[lane, 0] = np.einsum("kgs,skd->kgd", p / p.sum(axis=-1, keepdims=True), v).reshape(hq, d)
    return out


@pytest.mark.parametrize("name,case", KERNEL_WALK_CASES.items(), ids=KERNEL_WALK_CASES.keys())
def test_decode_walk_kernel_reads_each_lane_s_own_pages_and_gives_numpy_s_answer(name, case, monkeypatch):
    """``composed_paged_attend(path="kernel")`` (interpreted off the chip) for
    a decode row against float32 NumPy over permuted tables: every page nobody
    owns, page 0 among them, and every slot past a lane's own last page's
    block, holds NaN, so a block read past a lane's end, or a hole read from a
    page of somebody else's, shows (a weight of zero times NaN is NaN). The
    composed walk, handed the same call with NaN out of its reach, agrees.
    Both are handed the pool as the storage rule keeps its row: a row of up to
    4 kv heads folded, its heads the column blocks of the kernel's
    matrix."""
    n_lanes, ps, d = 4, 16, case.get("d", 128)
    max_pages, block, group, hkv = case["max_pages"], case["block"], case.get("group", 1), case.get("hkv", 16)
    dtype, window = jnp.dtype(case.get("dtype", "bfloat16")), case.get("window")
    rng = np.random.default_rng(17)
    n_pages = n_lanes * max_pages + 8
    kp, vp = (jnp.asarray(rng.standard_normal((n_pages, ps, hkv, d)), dtype) for _ in range(2))
    idle = np.asarray([p is None for p in case["positions"]])
    pos = np.asarray([max_pages * ps if p is None else p for p in case["positions"]], np.int32)
    held = np.where(idle, 0, pos // ps + 1)
    owned = rng.permutation(np.arange(1, n_pages)).astype(np.int32)[: n_lanes * max_pages].reshape(n_lanes, max_pages)  # page 0 is nobody's
    if case.get("identity"):
        owned = identity_tables(n_lanes, max_pages)
    tables = np.where(np.arange(max_pages)[None, :] < held[:, None], owned, -1).astype(np.int32)
    nobody_s = np.setdiff1d(np.arange(n_pages), tables[tables >= 0])
    kp, vp = kp.at[nobody_s].set(jnp.nan), vp.at[nobody_s].set(jnp.nan)
    q = jnp.asarray(rng.standard_normal((n_lanes, 1, hkv * group, d)), dtype)
    want = _numpy_decode_rows(q, kp, vp, tables, pos, idle, window, ps)
    row = stored_row(hkv, d)
    assert (len(row) == 1) == name.startswith("folded-"), row
    kp, vp = fold_rows(kp, row), fold_rows(vp, row)
    past = tables.copy()  # every slot past the block of a lane's own last page points at a page of NaN
    for lane in range(n_lanes):
        past[lane, -(-held[lane] // block) * block:] = nobody_s[-1]
    kw = dict(q_offset=jnp.asarray(pos), kv_length=jnp.asarray(pos) + 1, sliding_window=window)

    monkeypatch.setattr(pfa, "WALK_KERNEL_BLOCK_BYTES", block * ps * hkv * d * dtype.itemsize)
    width = pfa.window_pages(window, 1, ps, max_pages)
    assert pfa.walk_kernel_block_pages(width, ps, hkv, d, dtype.itemsize) == min(block, width)
    assert pfa.walk_kernel_unsupported(kp, q.shape, (n_lanes, width), window=window) is None
    cut = width < max_pages  # each lane's row is then cut to its own reach first: the slots past it are never handed over
    got = np.asarray(pfa.composed_paged_attend(q, kp, vp, jnp.asarray(tables if cut else past), path="kernel", **kw), np.float32)
    assert np.isfinite(got).all(), "the kernel read a block past a lane's own end, or a page nobody owns"
    tol = TOL if dtype == jnp.float32 else 2e-2  # bfloat16: the weights meet V in V's dtype, the answer is rounded to it
    np.testing.assert_allclose(got[~idle], want[~idle], atol=tol, rtol=0)
    np.testing.assert_array_equal(got[idle], 0.0)
    composed = np.asarray(pfa.composed_paged_attend(q, kp.at[nobody_s].set(0), vp.at[nobody_s].set(0), jnp.asarray(tables), path="composed", **kw), np.float32)
    np.testing.assert_allclose(got, composed, atol=tol, rtol=0)
    if case.get("garbage"):  # another lane's live page in the holes of a lane's last block, in place of its own: bit the same
        other_s = np.where((past < 0) & ~idle[:, None], tables[np.flatnonzero(~idle)[-1], 0], past)
        assert (other_s != past).any()
        np.testing.assert_array_equal(got, np.asarray(pfa.composed_paged_attend(q, kp, vp, jnp.asarray(other_s), path="kernel", **kw), np.float32))


def _pool_like(shape, dtype=jnp.bfloat16):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype)


WALK_PATH_CASES = {
    "plain-rows-of-hkv-d": (dict(), None),
    "float32": (dict(pool=_pool_like((9, 64, 8, 128), jnp.float32)), None),
    "static-window": (dict(window=128), None),
    "folded-8-kv-heads-of-64": (dict(pool=_pool_like((9, 64, 8 * 64)), q=(8, 1, 128, 64)), "heads of 64"),  # Falcon's: two heads share 128 lanes
    "folded-2-kv-heads-of-256": (dict(pool=_pool_like((9, 64, 2 * 256)), q=(8, 1, 16, 256)), None),  # Qwen3-Next's
    "folded-1-kv-head-of-128-under-20-query-heads": (dict(pool=_pool_like((9, 64, 128)), q=(8, 1, 20, 128)), None),  # Jamba's
    "folded-float32-static-window": (dict(pool=_pool_like((9, 64, 2 * 128), jnp.float32), q=(8, 1, 8, 128), window=128), None),
    "folded-1-kv-head-of-64": (dict(pool=_pool_like((9, 64, 64)), q=(8, 1, 71, 64)), "folded row of 64"),  # half the lanes
    "folded-3-kv-heads-of-128": (dict(pool=_pool_like((9, 64, 3 * 128)), q=(8, 1, 12, 128)), None),
    "folded-4-kv-heads-of-128-under-28-query-heads": (dict(pool=_pool_like((9, 64, 4 * 128)), q=(16, 1, 28, 128), tables=(16, 256)), None),  # SmallThinker's full layers
    "folded-4-kv-heads-of-128-static-window-of-4096": (dict(pool=_pool_like((9, 64, 4 * 128)), q=(16, 1, 28, 128), tables=(16, 65), window=4096), None),  # its windowed ones
    "rows-of-4-kv-heads-of-128": (dict(pool=_pool_like((9, 64, 4, 128)), q=(16, 1, 28, 128), tables=(16, 256)), "sublanes"),  # as a span with an index row keeps them
    "folded-4-kv-heads-of-128-int8-codes": (dict(pool=_pool_like((9, 64, 4 * 128), jnp.int8), scales=(9, 64, 4), q=(16, 1, 28, 128)), "quantised"),
    "folded-a-query-of-another-head-dim": (dict(pool=_pool_like((9, 64, 2 * 256)), q=(8, 1, 16, 384)), "folded row of 512"),
    "folded-pages-of-8-rows": (dict(pool=_pool_like((9, 8, 2 * 256)), q=(8, 1, 16, 256)), "sublanes"),
    "folded-alibi": (dict(pool=_pool_like((9, 64, 2 * 128)), q=(8, 1, 8, 128), alibi=True), "ALiBi"),
    "folded-query-heads-that-do-not-divide": (dict(pool=_pool_like((9, 64, 2 * 256)), q=(8, 1, 15, 256)), "query rows"),
    "quantised-pool": (dict(quantised=True), "quantised"),
    "head-dim-64-unfolded": (dict(pool=_pool_like((9, 64, 16, 64)), q=(8, 1, 16, 64)), "head_dim"),
    "float16": (dict(pool=_pool_like((9, 64, 16, 128), jnp.float16)), "float16"),
    "pages-of-8-rows": (dict(pool=_pool_like((9, 8, 16, 128))), "sublanes"),
    "8-kv-heads-of-bfloat16": (dict(pool=_pool_like((9, 64, 8, 128)), q=(8, 1, 32, 128)), "sublanes"),  # half a tile: the compiled step copies the pool
    "alibi": (dict(alibi=True), "ALiBi"),
    "soft-cap": (dict(softcap=True), "soft cap"),
    "traced-window": (dict(window="traced"), "traced window"),
    "two-query-rows": (dict(q=(8, 2, 16, 128)), "query rows"),
    "tables-at-the-scalar-memory-budget": (dict(tables=(8, (512 << 10) // (4 * 8))), None),
    "tables-over-the-scalar-memory-budget": (dict(tables=(64, 4096)), "scalar memory"),  # 1 MiB: all the v5e has
}


@pytest.mark.parametrize("case", WALK_PATH_CASES.values(), ids=WALK_PATH_CASES.keys())
def test_decode_walk_path_follows_what_the_call_shows(case, monkeypatch):
    """Which walk a decode row takes is a static function of the pool's stored
    form and the call (``decode_walk_path``): the kernel on a TPU backend for a
    plain pool of whole tiles, rows of ``[hkv, d]`` or folded, under the walk's
    own masks, the composed walk for everything else and everywhere off the
    chip."""
    import jax

    from petals_tpu.ops.paged_attention import PagedPool

    kw, why = case
    pool = kw.get("pool", _pool_like((9, 64, 16, 128)))
    if kw.get("quantised"):
        pool = PagedPool(_pool_like((9, 64, 16, 128), jnp.int8), _pool_like((9, 64, 16), jnp.float32))
    if "scales" in kw:  # a quantised pool whose codes the case gives
        pool = PagedPool(pool, _pool_like(kw["scales"], jnp.float32))
    window = jax.numpy.int32(20) if kw.get("window") == "traced" else kw.get("window")
    args = dict(alibi=kw.get("alibi", False), softcap=kw.get("softcap", False), window=window)
    q_shape = kw.get("q", (8, 1, 16, 128))
    tables_shape = kw.get("tables", (8, 40))
    reason = pfa.walk_kernel_unsupported(pool, q_shape, tables_shape, **args)
    assert (reason is None) == (why is None) and (why is None or why in reason), reason
    assert pfa.decode_walk_path(pool, q_shape, tables_shape, **args) == "composed"  # this backend is no TPU
    monkeypatch.setattr(pfa, "_on_tpu", lambda: True)
    assert pfa.decode_walk_path(pool, q_shape, tables_shape, **args) == ("kernel" if why is None else "composed")


def _tiny_families() -> dict:
    """A builder of a toy checkpoint directory a registered family (and a
    second one where a family's attention differs by its configuration)."""
    from tests import utils

    return {
        "llama": utils.make_tiny_llama, "mistral": utils.make_tiny_mistral, "qwen2": utils.make_tiny_qwen2, "phi3": utils.make_tiny_phi3,
        "gemma": utils.make_tiny_gemma, "gemma2": utils.make_tiny_gemma2, "bloom": utils.make_tiny_bloom, "falcon": utils.make_tiny_falcon,
        "falcon-rw": lambda tmp: utils.make_tiny_falcon(tmp, variant="rw"), "mixtral": utils.make_tiny_mixtral, "olmoe": utils.make_tiny_olmoe,
        "exaone_moe": utils.make_tiny_exaone_moe, "olmo_hybrid": utils.make_tiny_olmo_hybrid, "KeyeVL2": utils.make_tiny_keye_vl2,
        "KeyeVL2-table-of-one-page": utils.make_tiny_keye_vl2,  # 16 positions, as many as a row chooses: the plain call
        "deepseek_v3": utils.make_tiny_deepseek_v3, "qwen3_next": utils.make_tiny_qwen3_next, "jamba": utils.make_tiny_jamba,
        "longcat_flash": utils.make_tiny_longcat_flash, "xing4_0": utils.make_tiny_xing4_0, "smallthinker": utils.make_tiny_smallthinker,
    }


def test_every_registered_family_has_a_case_of_the_attention_s_guard():
    from petals_tpu.models.registry import known_families

    assert {name.split("-")[0] for name in _tiny_families()} == set(known_families()), "a new family: give the next test a toy of it"


@pytest.mark.parametrize("name", _tiny_families())
def test_the_decode_walk_the_backend_counts_is_the_one_its_step_is_handed(name, tmp_path, monkeypatch):
    """``backend.decode_walks`` asks ``decode_walk_path`` with what the family
    DECLARES its blocks hand their attention (``ModelFamily.block_attention``,
    ``block_window``) and the pool's form out of the descriptors; the step
    asks it with what the blocks DO hand over, at trace time. Both askings are
    recorded here, over a whole toy model's decode step on a backend that says
    it is a TPU: they have to be the same calls, whatever the family: its
    ALiBi bias, its soft cap, a window that is an array, a static one's cut, a
    folded pool. The counters that say which walk ran rest on nothing else."""
    import functools

    import jax

    from petals_tpu.models.registry import span_runs
    from petals_tpu.server.backend import TransformerBackend
    from petals_tpu.server.from_pretrained import get_block_config

    family, cfg = get_block_config(_tiny_families()[name](str(tmp_path)))
    depth, lanes, slots, page_size = cfg.num_hidden_layers, 2, 1 if name.endswith("one-page") else 4, 16
    aval = jax.ShapeDtypeStruct
    runs = tuple(
        {leaf: aval((length, *a.shape), a.dtype) for leaf, a in family.param_shapes_for(cfg, kind, jnp.float32).items()}
        for kind, _, length in span_runs(family.span_kinds(cfg, 0, depth))
    )
    backend = TransformerBackend(family, cfg, runs[0] if len(runs) == 1 else runs, first_block=0, n_blocks=depth, memory_cache=None,
                                 compute_dtype=jnp.float32, use_flash=False)
    asked = []

    def recorded(pool, q_shape, tables_shape, *, alibi, softcap, window):
        window = window if window is None or isinstance(window, int) else "an array"
        asked.append((type(pool).__name__ == "PagedPool", tuple(pool.shape[1:]), str(pool.dtype), q_shape[0], q_shape[1], q_shape[3],
                      tuple(tables_shape), alibi, softcap, window))
        return "composed"

    monkeypatch.setattr(pfa, "_on_tpu", lambda: True)
    monkeypatch.setattr(pfa, "decode_walk_path", recorded)
    declared = backend.cache.lane_pool(lanes, slots, page_size).walks
    counted, asked[:] = set(asked), []
    assert len(declared) == len(counted)

    descs = lane_pools(backend, lanes * slots, page_size, end=depth)[0]
    pools = [aval(d.shape, d.dtype) for d in descs[:2]]
    # the lanes' rows and positions in backend.pack_lanes' form, then the tables
    avals = [backend.params, *pools, aval((lanes, backend.hidden_size + 1), jnp.int32), aval((lanes, slots), jnp.int32)]
    if backend.cache.state_layers:
        avals.append(tuple(aval(d.shape, d.dtype) for d in lane_pools(backend, 1, 1, lanes)[1]))
    if backend.cache.index_row is not None:
        avals.append(tuple(aval(d.shape, d.dtype) for d in lane_pools(backend, lanes * slots, page_size)[1]))
    jax.eval_shape(functools.partial(backend._paged_decode_fn.__wrapped__, with_fp=False), *avals)
    assert set(asked) == counted, (sorted(map(str, asked)), sorted(map(str, counted)))
    # a latent row's walk is its own (ops/latent_attention.py); a row that chooses its positions fetches them one by one
    assert bool(counted) == (name not in ("deepseek_v3", "KeyeVL2", "longcat_flash", "xing4_0")), "the step's attention never reached the decode walk"
    extras = {"bloom": (True, False, None), "falcon-rw": (True, False, None), "gemma2": (False, True, "an array")}
    assert {call[-3:] for call in counted} <= {extras.get(name, (False, False, call[-1])) for call in counted}, counted


# ------------------------------------------------------------ prefill parity


@pytest.mark.parametrize(
    "chunk_pos,n_valid,window",
    [(0, 24, None), (8, 17, None), (8, 17, 9), (16, 5, None), (0, 0, None)],
)
def test_prefill_parity(chunk_pos, n_valid, window):
    rng = np.random.default_rng(5)
    max_pages, ps, hkv, group, d = 6, 8, 2, 4, 16
    hq = hkv * group
    B = 24  # padded bucket
    n_pages = 12
    kp, vp = _rand_pool(rng, n_pages, ps, hkv, d)
    q = jnp.asarray(rng.standard_normal((1, B, hq, d)), jnp.float32)
    trow = jnp.asarray(
        _holey_permuted(rng, 1, max_pages, n_pages, [5])[0]
    )
    slopes = jnp.asarray(rng.standard_normal(hq) * 0.1, jnp.float32)
    cp, nv = jnp.int32(chunk_pos), jnp.int32(n_valid)
    out = paged_flash_prefill_attend(
        q, kp, vp, trow, cp, nv,
        alibi_slopes=slopes, sliding_window=window, interpret=True,
    )
    ref = paged_prefill_attend(
        q, kp, vp, trow, cp, nv,
        alibi_slopes=slopes, sliding_window=window,
    )
    # padded-tail rows are garbage-but-unread in BOTH paths; compare valid rows
    np.testing.assert_allclose(
        np.asarray(out)[:, :n_valid], np.asarray(ref)[:, :n_valid],
        atol=TOL, rtol=0,
    )


def test_parity_when_several_heads_share_a_block():
    """Heads narrower than 128 lanes ride ``128 // d`` to a KV block (the
    shape Mosaic needs for d=64 families): each grid step must slice ITS
    head out of the block."""
    rng = np.random.default_rng(11)
    n_lanes, max_pages, ps, hkv, group, d = 3, 4, 8, 4, 2, 64
    assert pfa._kv_heads_per_block(hkv, d) == 2
    hq = hkv * group
    n_pages = 16
    kp, vp = _rand_pool(rng, n_pages, ps, hkv, d)
    tables = jnp.asarray(_holey_permuted(rng, n_lanes, max_pages, n_pages, [3, 2, 1]))

    qc = jnp.asarray(rng.standard_normal((1, 16, hq, d)), jnp.float32)
    cp, nv = jnp.int32(8), jnp.int32(13)
    out = paged_flash_prefill_attend(qc, kp, vp, tables[0], cp, nv, interpret=True)
    ref = paged_prefill_attend(qc, kp, vp, tables[0], cp, nv)
    np.testing.assert_allclose(
        np.asarray(out)[:, :13], np.asarray(ref)[:, :13], atol=TOL, rtol=0
    )


# ------------------------------------------------------- the dispatch's table


def _dispatch_case(rows="decode", platform="tpu", d=128, **call):
    return dict(rows=rows, platform=platform, d=d, call=call)


# {decode row, chunk} x {cpu, tpu} x {a class Mosaic takes, head_dim 80}, then what the prefill kernel cannot express
DISPATCH_CASES = {
    **{
        f"{rows}-{platform}-d{d}": _dispatch_case(rows, platform, d)
        for rows in ("decode", "chunk") for platform in ("cpu", "tpu") for d in (128, 80)
    },
    "verify-rows-tpu": _dispatch_case("verify"),
    "chunk-tpu-soft-cap": _dispatch_case("chunk", logit_softcap=30.0),
    "chunk-tpu-traced-window": _dispatch_case("chunk", sliding_window="traced"),
    "chunk-tpu-non-causal": _dispatch_case("chunk", causal=False),
    "chunk-tpu-no-kv-length": _dispatch_case("chunk", kv_length=None),
}


@pytest.mark.parametrize("case", DISPATCH_CASES.values(), ids=DISPATCH_CASES.keys())
def test_dispatch_sends_a_decode_row_to_the_walk_and_a_chunk_to_the_kernel_where_it_can_run(case, monkeypatch, caplog):
    """``paged_attend_dispatch``'s table, the callees replaced by recorders:
    per-lane positions (a decode row, a verify's rows) reach
    ``composed_paged_attend`` on every platform, with the span's pool as the
    step carries it (``own_layer`` is never asked); a scalar position (a
    chunk) reaches ``paged_flash_prefill_attend`` with the block's own layer
    on a TPU, for a call the kernel can express and a class Mosaic can tile,
    and a class it cannot is warned of once."""
    import logging

    rows, d, call = case["rows"], case["d"], dict(case["call"])
    lanes, hkv, ps, max_pages = (3 if rows != "chunk" else 1), 2, 8, 4
    q_len = {"decode": 1, "verify": 3, "chunk": 16}[rows]
    pool = jnp.zeros((lanes * max_pages, ps, hkv, d), jnp.float32)
    tables = jnp.asarray(identity_tables(lanes, max_pages))
    q = jnp.zeros((lanes, q_len, hkv, d), jnp.float32)
    pos = jnp.int32(8) if rows == "chunk" else jnp.asarray([8, 9, 10], jnp.int32)
    if call.get("sliding_window") == "traced":
        call["sliding_window"] = jnp.int32(20)
    call.setdefault("kv_length", pos + q_len)
    reached, own_layers = [], []

    def recorder(name):
        def record(q, *args, **kw):
            reached.append(name)
            return jnp.zeros_like(q)
        return record

    def own_layer(self):
        own_layers.append(self)
        return self

    monkeypatch.setattr(pfa, "_platform", lambda: case["platform"])
    monkeypatch.setattr(pfa, "_WARNED_UNSUPPORTED", set())
    monkeypatch.setattr(pfa, "composed_paged_attend", recorder("composed"))
    monkeypatch.setattr(pfa, "paged_flash_prefill_attend", recorder("prefill kernel"))
    monkeypatch.setattr(PagedKV, "own_layer", own_layer)
    logging.getLogger("petals_tpu").propagate = True
    try:
        with caplog.at_level(logging.WARNING, logger="petals_tpu"):
            for _ in range(2):
                attend(q, PagedKV(pool, tables), PagedKV(pool, tables), q_offset=pos, **call)
    finally:
        logging.getLogger("petals_tpu").propagate = False

    kernel = rows == "chunk" and case["platform"] == "tpu" and d == 128 and not case["call"]
    assert reached == ["prefill kernel" if kernel else "composed"] * 2
    assert len(own_layers) == (4 if kernel else 0)  # keys and values, twice
    refused = rows == "chunk" and case["platform"] == "tpu" and d == 80
    assert sum("prefill kernel excluded" in r.message for r in caplog.records) == (1 if refused else 0)


def test_unsupported_shape_class_is_gated_off_the_kernel():
    """A head width Mosaic cannot tile (neither a lane multiple nor packing
    evenly into 128 lanes) is refused by a static predicate, decided before
    any compile; the classes the cells' pools have are taken."""
    assert pfa.paged_kernel_unsupported(2, 16) is not None  # the tiny interpreter shape
    assert pfa.paged_kernel_unsupported(8, 64) is None  # two d=64 heads to a block
    assert pfa.paged_kernel_unsupported(32, 128, "nf4a") is None  # two packed heads
    assert pfa.paged_kernel_unsupported(1, 64) is None  # MQA: the block is the whole row


def test_dispatch_forces_xla_for_softcap_and_traced_window():
    """Kernel-inexpressible requests (gemma2's logit softcap, traced
    effective window) compose from XLA: a decode row then takes the walk, the
    gather/attend sandwich's math summed block by block."""
    rng = np.random.default_rng(7)
    n_lanes, max_pages, ps, hkv, d = 2, 2, 8, 2, 16
    n_pages = n_lanes * max_pages
    kp, vp = _rand_pool(rng, n_pages, ps, hkv, d)
    tables = jnp.asarray(identity_tables(n_lanes, max_pages))
    k_kv, v_kv = PagedKV(kp, tables), PagedKV(vp, tables)
    q = jnp.asarray(rng.standard_normal((n_lanes, 1, hkv, d)), jnp.float32)
    pos = jnp.asarray([ps, ps + 3], jnp.int32)
    traced_window = jnp.int32(1000)  # gemma2-style traced effective window
    out = attend(
        q, k_kv, v_kv, q_offset=pos, kv_length=pos + 1,
        sliding_window=traced_window, logit_softcap=30.0,
    )
    k_dense, v_dense = gather_pages(kp, tables), gather_pages(vp, tables)
    ref = attend_reference(
        q, k_dense, v_dense, q_offset=pos, kv_length=pos + 1,
        sliding_window=traced_window, logit_softcap=30.0,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=TOL, rtol=0)
