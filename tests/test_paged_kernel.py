"""Fused ragged paged-attention kernel (ops/paged_flash_attention.py), run in
interpret mode on CPU: parity vs the XLA-composed reference
(gather_pages + attend_reference) across table layouts (dense/identity,
permuted, holey), ragged lengths (position 0, page boundaries), ALiBi,
sliding windows, GQA ratios, and chunked prefill; the autotune/dispatch
decision unit (env override, CPU fallback); and the fingerprint interplay
(the fused digest must survive the kernel path)."""

import numpy as np
import pytest

import jax.numpy as jnp

from petals_tpu.ops import paged_flash_attention as pfa
from petals_tpu.ops.attention import attend, attend_reference
from petals_tpu.ops.paged_attention import (
    PagedKV,
    gather_pages,
    identity_tables,
    paged_attend,
    paged_prefill_attend,
)
from petals_tpu.ops.paged_flash_attention import (
    paged_flash_attend,
    paged_flash_prefill_attend,
)
from tests.utils import make_tiny_llama

pytestmark = pytest.mark.kernel

# the online-softmax accumulation order differs from the reference's one-shot
# softmax; f32 agreement lands ~1e-6 at these shapes
TOL = 2e-5


@pytest.fixture(scope="module")
def model_path(tmp_path_factory):
    return make_tiny_llama(str(tmp_path_factory.mktemp("models")))


@pytest.fixture(autouse=True)
def _fresh_autotune():
    pfa.reset_paged_autotune()
    yield
    pfa.reset_paged_autotune()


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


# ------------------------------------------------------------- decode parity


def test_decode_parity_identity_and_ragged():
    """Identity tables (the dense layout) at ragged positions including 0 and
    page boundaries: kernel vs the XLA-composed reference, and vs
    attend_reference on the true dense buffer."""
    rng = np.random.default_rng(0)
    n_lanes, max_pages, ps, hkv, group, d = 4, 4, 16, 2, 2, 32
    hq = hkv * group
    kp, vp = _rand_pool(rng, n_lanes * max_pages, ps, hkv, d)
    q = jnp.asarray(rng.standard_normal((n_lanes, 1, hq, d)), jnp.float32)
    tables = jnp.asarray(identity_tables(n_lanes, max_pages))
    # position 0, page-boundary-1, page boundary, mid-page
    pos = jnp.asarray([0, ps - 1, 2 * ps, 3 * ps + 5], jnp.int32)

    out = paged_flash_attend(q, kp, vp, tables, pos, interpret=True)
    ref = paged_attend(q, kp, vp, tables, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=TOL, rtol=0)

    # identity gather == the dense buffer: the kernel also matches plain
    # attend_reference on the dense view (one attention path, dense included)
    k_dense = kp.reshape(n_lanes, max_pages * ps, hkv, d)
    v_dense = vp.reshape(n_lanes, max_pages * ps, hkv, d)
    dense = attend_reference(q, k_dense, v_dense, q_offset=pos, kv_length=pos + 1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense), atol=TOL, rtol=0)


def test_decode_parity_permuted_and_holey():
    rng = np.random.default_rng(1)
    n_lanes, max_pages, ps, hkv, group, d = 3, 4, 8, 2, 4, 16
    hq = hkv * group
    n_pages = 20  # oversubscribed pool, scattered pages
    kp, vp = _rand_pool(rng, n_pages, ps, hkv, d)
    q = jnp.asarray(rng.standard_normal((n_lanes, 1, hq, d)), jnp.float32)
    pos = np.array([3 * ps - 1, 2 * ps - 1, ps], np.int32)
    used = [-(-int(p + 1) // ps) for p in pos]
    tables = _holey_permuted(rng, n_lanes, max_pages, n_pages, used)

    out = paged_flash_attend(
        q, kp, vp, jnp.asarray(tables), jnp.asarray(pos), interpret=True
    )
    ref = paged_attend(q, kp, vp, jnp.asarray(tables), jnp.asarray(pos))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=TOL, rtol=0)


def test_kernel_bit_identical_under_holes():
    """Unallocated (-1) slots beyond the ragged frontier must not influence
    the kernel AT ALL: pointing those slots at garbage pages instead must
    yield BIT-identical output (the kernel never fetches either)."""
    rng = np.random.default_rng(2)
    n_lanes, max_pages, ps, hkv, group, d = 2, 4, 8, 2, 2, 16
    hq = hkv * group
    n_pages = 16
    kp, vp = _rand_pool(rng, n_pages, ps, hkv, d)
    q = jnp.asarray(rng.standard_normal((n_lanes, 1, hq, d)), jnp.float32)
    pos = jnp.asarray([ps + 3, 2 * ps - 1], jnp.int32)  # lanes use 2 slots each

    holey = _holey_permuted(rng, n_lanes, max_pages, n_pages, [2, 2])
    garbage = holey.copy()
    garbage[garbage < 0] = 15  # a live page full of other-tenant bytes

    out_holey = np.asarray(
        paged_flash_attend(q, kp, vp, jnp.asarray(holey), pos, interpret=True)
    )
    out_garbage = np.asarray(
        paged_flash_attend(q, kp, vp, jnp.asarray(garbage), pos, interpret=True)
    )
    np.testing.assert_array_equal(out_holey, out_garbage)


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


@pytest.mark.parametrize("group", [1, 2, 4, 8])
def test_decode_gqa_ratios(group):
    rng = np.random.default_rng(3)
    hq = 8
    hkv = hq // group
    n_lanes, max_pages, ps, d = 2, 3, 8, 16
    n_pages = n_lanes * max_pages
    kp, vp = _rand_pool(rng, n_pages, ps, hkv, d)
    q = jnp.asarray(rng.standard_normal((n_lanes, 1, hq, d)), jnp.float32)
    perm = rng.permutation(n_pages).astype(np.int32).reshape(n_lanes, max_pages)
    pos = jnp.asarray([2 * ps, 3 * ps - 1], jnp.int32)
    out = paged_flash_attend(q, kp, vp, jnp.asarray(perm), pos, interpret=True)
    ref = paged_attend(q, kp, vp, jnp.asarray(perm), pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=TOL, rtol=0)


@pytest.mark.parametrize("window", [None, 5, 20])
def test_decode_alibi_and_window(window):
    rng = np.random.default_rng(4)
    n_lanes, max_pages, ps, hkv, group, d = 3, 4, 8, 2, 2, 16
    hq = hkv * group
    n_pages = n_lanes * max_pages
    kp, vp = _rand_pool(rng, n_pages, ps, hkv, d)
    q = jnp.asarray(rng.standard_normal((n_lanes, 1, hq, d)), jnp.float32)
    perm = rng.permutation(n_pages).astype(np.int32).reshape(n_lanes, max_pages)
    pos = jnp.asarray([0, 2 * ps - 1, 4 * ps - 1], jnp.int32)
    slopes = jnp.asarray(rng.standard_normal(hq) * 0.1, jnp.float32)
    out = paged_flash_attend(
        q, kp, vp, jnp.asarray(perm), pos,
        alibi_slopes=slopes, sliding_window=window, interpret=True,
    )
    ref = paged_attend(
        q, kp, vp, jnp.asarray(perm), pos,
        alibi_slopes=slopes, sliding_window=window,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=TOL, rtol=0)


# ------------------------------------------------- the composed path's walk

# n_lanes 4, kv heads 2; a lane's position, or None for an idle lane (the sentinel max_length). ``block``: table
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

    n_lanes, hkv, ps = 4, 2, case.get("ps", 8)
    max_pages, group, d, block = case["max_pages"], case["group"], case["d"], case["block"]
    rng = np.random.default_rng(11)
    n_pages = n_lanes * max_pages + 1  # the last one is the page of NaN
    kp, vp = _rand_pool(rng, n_pages, ps, hkv, d)
    kp, vp = kp.at[-1].set(jnp.nan), vp.at[-1].set(jnp.nan)
    idle = np.asarray([p is None for p in case["positions"]])
    pos = np.asarray([max_pages * ps if p is None else p for p in case["positions"]], np.int32)
    held = np.where(idle, 0, pos // ps + 1)
    if case.get("identity"):
        tables = identity_tables(n_lanes, max_pages).copy()
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


def test_walk_block_follows_the_shapes_it_sees():
    """The block's width at the cells' pools (8 lanes, pages of 64, bf16): one
    page; smaller pages go several a block, never more than there are."""
    for hkv, d in ((32, 128), (16, 128), (8, 128), (8, 64)):  # olmo-hybrid-7b, olmoe-1b-7b, mixtral / k-exaone, falcon
        assert pfa.walk_block_pages(8, 40, 64, hkv, d) == 1
    assert pfa.walk_block_pages(8, 64, 16, 8, 128) == 2 and pfa.walk_block_pages(8, 64, 16, 8, 64) == 4
    assert pfa.walk_block_pages(2, 3, 16, 8, 64) == 3 and pfa.walk_block_pages(8, 64, 16, 8, 64, itemsize=4) == 2
    assert pfa.walk_pages(36, 4) == 36 and pfa.walk_pages(6, 8) == 8 and pfa.walk_pages(0, 8) == 0 and pfa.walk_pages(37, 4) == 40


# ------------------------------------------------- the walk as one kernel

# n_lanes 4, pages of 16 rows, head_dim 128; a lane's position, or None for an idle lane. ``block``: table slots of one
# lane a grid step takes (the cases set the bytes the rule goes by)
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


@pytest.mark.parametrize("case", KERNEL_WALK_CASES.values(), ids=KERNEL_WALK_CASES.keys())
def test_decode_walk_kernel_reads_each_lane_s_own_pages_and_gives_numpy_s_answer(case, monkeypatch):
    """``composed_paged_attend(path="kernel")`` (interpreted off the chip) for
    a decode row against float32 NumPy over permuted tables: every page nobody
    owns, page 0 among them, and every slot past a lane's own last page's
    block, holds NaN, so a block read past a lane's end, or a hole read from a
    page of somebody else's, shows (a weight of zero times NaN is NaN). The
    composed walk, handed the same call with NaN out of its reach, agrees."""
    n_lanes, ps, d = 4, 16, 128
    max_pages, block, group, hkv = case["max_pages"], case["block"], case.get("group", 1), case.get("hkv", 16)
    dtype, window = jnp.dtype(case.get("dtype", "bfloat16")), case.get("window")
    rng = np.random.default_rng(17)
    n_pages = n_lanes * max_pages + 8
    kp, vp = (jnp.asarray(rng.standard_normal((n_pages, ps, hkv, d)), dtype) for _ in range(2))
    idle = np.asarray([p is None for p in case["positions"]])
    pos = np.asarray([max_pages * ps if p is None else p for p in case["positions"]], np.int32)
    held = np.where(idle, 0, pos // ps + 1)
    owned = rng.permutation(np.arange(1, n_pages)).astype(np.int32)[: n_lanes * max_pages].reshape(n_lanes, max_pages)  # page 0 is nobody's
    tables = np.where(np.arange(max_pages)[None, :] < held[:, None], owned, -1).astype(np.int32)
    nobody_s = np.setdiff1d(np.arange(n_pages), tables[tables >= 0])
    kp, vp = kp.at[nobody_s].set(jnp.nan), vp.at[nobody_s].set(jnp.nan)
    past = tables.copy()  # every slot past the block of a lane's own last page points at a page of NaN
    for lane in range(n_lanes):
        past[lane, -(-held[lane] // block) * block:] = nobody_s[-1]
    q = jnp.asarray(rng.standard_normal((n_lanes, 1, hkv * group, d)), dtype)
    kw = dict(q_offset=jnp.asarray(pos), kv_length=jnp.asarray(pos) + 1, sliding_window=window)

    monkeypatch.setattr(pfa, "WALK_KERNEL_BLOCK_BYTES", block * ps * hkv * d * dtype.itemsize)
    width = pfa.window_pages(window, 1, ps, max_pages)
    assert pfa.walk_kernel_block_pages(width, ps, hkv, d, dtype.itemsize) == min(block, width)
    assert pfa.walk_kernel_unsupported(kp, q.shape, (n_lanes, width), window=window) is None
    cut = width < max_pages  # each lane's row is then cut to its own reach first: the slots past it are never handed over
    got = np.asarray(pfa.composed_paged_attend(q, kp, vp, jnp.asarray(tables if cut else past), path="kernel", **kw), np.float32)
    want = _numpy_decode_rows(q, kp, vp, tables, pos, idle, window, ps)
    assert np.isfinite(got).all(), "the kernel read a block past a lane's own end, or a page nobody owns"
    tol = TOL if dtype == jnp.float32 else 2e-2  # bfloat16: the weights meet V in V's dtype, the answer is rounded to it
    np.testing.assert_allclose(got[~idle], want[~idle], atol=tol, rtol=0)
    np.testing.assert_array_equal(got[idle], 0.0)
    composed = np.asarray(pfa.composed_paged_attend(q, kp.at[nobody_s].set(0), vp.at[nobody_s].set(0), jnp.asarray(tables), path="composed", **kw), np.float32)
    np.testing.assert_allclose(got, composed, atol=tol, rtol=0)


def _pool_like(shape, dtype=jnp.bfloat16):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype)


WALK_PATH_CASES = {
    "plain-rows-of-hkv-d": (dict(), None),
    "float32": (dict(pool=_pool_like((9, 64, 8, 128), jnp.float32)), None),
    "static-window": (dict(window=128), None),
    "folded-pool": (dict(pool=_pool_like((9, 64, 8 * 64)), q=(8, 1, 128, 64)), "folded"),
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
    plain pool of rows of ``[hkv, d]`` of whole tiles under the walk's own
    masks, the composed walk for everything else and everywhere off the
    chip."""
    import jax

    from petals_tpu.ops.paged_attention import PagedPool

    kw, why = case
    pool = kw.get("pool", _pool_like((9, 64, 16, 128)))
    if kw.get("quantised"):
        pool = PagedPool(_pool_like((9, 64, 16, 128), jnp.int8), _pool_like((9, 64, 16), jnp.float32))
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
        "deepseek_v3": utils.make_tiny_deepseek_v3,
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
    declared = backend.decode_walks(lanes, slots, page_size)
    counted, asked[:] = set(asked), []
    assert len(declared) == len(counted)

    descs = backend.paged_cache_descriptors(lanes * slots, page_size, 0, depth)
    pools = [aval(d.shape, d.dtype) for d in descs[:2]]
    avals = [backend.params, *pools, aval((lanes, 1, cfg.hidden_size), jnp.float32), aval((lanes,), jnp.int32), aval((lanes, slots), jnp.int32)]
    if backend.state_layers:
        avals.append(tuple(aval(d.shape, d.dtype) for d in backend.state_cache_descriptors(lanes)))
    if backend.index_row is not None:
        avals.append(tuple(aval(d.shape, d.dtype) for d in backend.index_cache_descriptors(lanes * slots, page_size)))
    jax.eval_shape(functools.partial(backend._paged_decode_fn.__wrapped__, kernel_path="xla", with_fp=False), *avals)
    assert set(asked) == counted, (sorted(map(str, asked)), sorted(map(str, counted)))
    # a latent row's walk is its own (ops/latent_attention.py); a row that chooses its positions fetches them one by one
    assert bool(counted) == (name not in ("deepseek_v3", "KeyeVL2")), "the step's attention never reached the decode walk"
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
    head out of the block, for decode and for the prefill twin."""
    rng = np.random.default_rng(11)
    n_lanes, max_pages, ps, hkv, group, d = 3, 4, 8, 4, 2, 64
    assert pfa._kv_heads_per_block(hkv, d) == 2
    hq = hkv * group
    n_pages = 16
    kp, vp = _rand_pool(rng, n_pages, ps, hkv, d)
    pos = np.array([3 * ps - 1, ps + 2, 0], np.int32)
    used = [-(-int(p + 1) // ps) for p in pos]
    tables = jnp.asarray(_holey_permuted(rng, n_lanes, max_pages, n_pages, used))
    q = jnp.asarray(rng.standard_normal((n_lanes, 1, hq, d)), jnp.float32)
    out = paged_flash_attend(q, kp, vp, tables, jnp.asarray(pos), interpret=True)
    ref = paged_attend(q, kp, vp, tables, jnp.asarray(pos))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=TOL, rtol=0)

    qc = jnp.asarray(rng.standard_normal((1, 16, hq, d)), jnp.float32)
    cp, nv = jnp.int32(8), jnp.int32(13)
    out = paged_flash_prefill_attend(qc, kp, vp, tables[0], cp, nv, interpret=True)
    ref = paged_prefill_attend(qc, kp, vp, tables[0], cp, nv)
    np.testing.assert_allclose(
        np.asarray(out)[:, :13], np.asarray(ref)[:, :13], atol=TOL, rtol=0
    )


# ------------------------------------------------- autotune / dispatch unit


def test_kernel_mode_env_override(monkeypatch):
    monkeypatch.delenv(pfa._ENV_VAR, raising=False)
    assert pfa.kernel_mode() == "auto"
    key = pfa.shape_class(2, 4, 8, 2, 16, None)
    # CPU + auto: guaranteed XLA fallback
    assert pfa.decide_paged_kernel("decode", key) is False
    assert pfa.resolve_paged_kernel_path("decode", key) == "xla"
    monkeypatch.setenv(pfa._ENV_VAR, "pallas")
    assert pfa.decide_paged_kernel("decode", key) is True
    monkeypatch.setenv(pfa._ENV_VAR, "xla")
    assert pfa.decide_paged_kernel("decode", key) is False
    monkeypatch.setenv(pfa._ENV_VAR, "bogus")
    with pytest.raises(ValueError):
        pfa.kernel_mode()


def test_autotune_decision_cache(monkeypatch):
    """On (fake) TPU in auto mode the cached per-shape decision is honored;
    untuned shapes default to the kernel and prefill inherits the decode
    decision for its shape class."""
    monkeypatch.delenv(pfa._ENV_VAR, raising=False)
    monkeypatch.setattr(pfa, "_platform", lambda: "tpu")
    key = pfa.shape_class(2, 4, 8, 2, 128, None)
    other = pfa.shape_class(8, 4, 8, 2, 128, None)
    assert pfa.decide_paged_kernel("decode", key) is True  # untuned default
    pfa.set_paged_kernel_decision("decode", key, False)
    assert pfa.decide_paged_kernel("decode", key) is False
    assert pfa.decide_paged_kernel("prefill", key) is False  # inherits decode
    assert pfa.decide_paged_kernel("decode", other) is True  # per-shape
    # maybe_autotune is a no-op for an already-decided class (returns it)
    assert (
        pfa.maybe_autotune_paged_attention(
            n_lanes=2, max_pages=4, page_size=8, hkv=2, d=128
        )
        is False
    )


@pytest.mark.parametrize(
    "pallas_ms, xla_ms, kernel",
    [
        (0.448, 0.452, False),  # Falcon-40B's class on the v5e: a tie in the harness
        (0.452, 0.448, False),  # ... and the same tie read the other way round
        (0.41, 0.45, False),  # faster, but inside the margin
        (0.40, 0.45, True),  # faster by more than the margin
        (0.50, 0.27, False),  # Mixtral-8x7B's class: the composed path by far
    ],
)
def test_autotune_tie_goes_to_the_composed_path(pallas_ms, xla_ms, kernel):
    """Two starts of one server must run the same step program: timings the
    harness cannot tell apart give the composed path, whichever reads lower,
    and the kernel takes a class only by ``KERNEL_MUST_WIN_BY``."""
    assert pfa.kernel_wins(pallas_ms * 1e-3, xla_ms * 1e-3) is kernel


def test_unsupported_shape_class_is_gated_off_the_kernel(monkeypatch, caplog):
    """On a TPU in auto mode a head width Mosaic cannot tile (neither a lane
    multiple nor packing evenly into 128 lanes) composes from XLA by a static
    predicate — decided before any compile, warned once, never autotuned;
    the explicit override still reaches the kernel (interpreter tests)."""
    import logging

    monkeypatch.delenv(pfa._ENV_VAR, raising=False)
    monkeypatch.setattr(pfa, "_platform", lambda: "tpu")
    monkeypatch.setattr(pfa, "_WARNED_UNSUPPORTED", set())
    key = pfa.shape_class(2, 4, 8, 2, 16, None)  # the tiny interpreter shape
    assert pfa.paged_kernel_unsupported(key) is not None
    for packed in (
        pfa.shape_class(8, 16, 64, 8, 64, None),  # two d=64 heads to a block
        pfa.shape_class(8, 16, 64, 32, 128, None, "nf4a"),  # two packed heads
        pfa.shape_class(8, 16, 64, 1, 64, None),  # MQA: the block is the whole row
    ):
        assert pfa.paged_kernel_unsupported(packed) is None
    logging.getLogger("petals_tpu").propagate = True
    try:
        with caplog.at_level(logging.WARNING, logger="petals_tpu"):
            assert pfa.decide_paged_kernel("decode", key) is False
            assert pfa.decide_paged_kernel("prefill", key) is False
    finally:
        logging.getLogger("petals_tpu").propagate = False
    assert sum("excluded for shape class" in r.message for r in caplog.records) == 1
    assert pfa.maybe_autotune_paged_attention(
        n_lanes=2, max_pages=4, page_size=8, hkv=2, d=16
    ) is False
    assert pfa._AUTOTUNE == {}  # gated, not tuned
    monkeypatch.setenv(pfa._ENV_VAR, "pallas")
    assert pfa.decide_paged_kernel("decode", key) is True


def test_autotune_noop_off_tpu(monkeypatch):
    """CPU: maybe_autotune must not time anything and must leave the decision
    at the guaranteed XLA fallback."""
    monkeypatch.delenv(pfa._ENV_VAR, raising=False)
    assert (
        pfa.maybe_autotune_paged_attention(
            n_lanes=2, max_pages=4, page_size=8, hkv=2, d=16
        )
        is False
    )
    assert pfa._AUTOTUNE == {}  # nothing recorded: not tuned, just fallback


def test_dispatch_env_override_decode_and_prefill(monkeypatch):
    """attend() on a PagedKV honors the env override at trace time: pallas
    and xla paths agree numerically for both the decode (vector positions)
    and prefill (scalar position) contracts."""
    rng = np.random.default_rng(6)
    n_lanes, max_pages, ps, hkv, group, d = 2, 3, 8, 2, 2, 16
    hq = hkv * group
    n_pages = n_lanes * max_pages
    kp, vp = _rand_pool(rng, n_pages, ps, hkv, d)
    perm = rng.permutation(n_pages).astype(np.int32).reshape(n_lanes, max_pages)
    k_kv, v_kv = PagedKV(kp, jnp.asarray(perm)), PagedKV(vp, jnp.asarray(perm))

    q = jnp.asarray(rng.standard_normal((n_lanes, 1, hq, d)), jnp.float32)
    pos = jnp.asarray([ps + 1, 2 * ps - 1], jnp.int32)
    outs = {}
    for mode in ("pallas", "xla"):
        monkeypatch.setenv(pfa._ENV_VAR, mode)
        outs[mode] = np.asarray(
            attend(q, k_kv, v_kv, q_offset=pos, kv_length=pos + 1)
        )
    np.testing.assert_allclose(outs["pallas"], outs["xla"], atol=TOL, rtol=0)

    B, nv, cp = 16, 11, 0
    qc = jnp.asarray(rng.standard_normal((1, B, hq, d)), jnp.float32)
    k1, v1 = PagedKV(kp, jnp.asarray(perm[:1])), PagedKV(vp, jnp.asarray(perm[:1]))
    outs = {}
    for mode in ("pallas", "xla"):
        monkeypatch.setenv(pfa._ENV_VAR, mode)
        outs[mode] = np.asarray(
            attend(qc, k1, v1, q_offset=jnp.int32(cp), kv_length=jnp.int32(cp + nv))
        )[:, :nv]
    np.testing.assert_allclose(outs["pallas"], outs["xla"], atol=TOL, rtol=0)


def test_dispatch_forces_xla_for_softcap_and_traced_window():
    """Kernel-inexpressible requests (gemma2's logit softcap, traced
    effective window) must compose from XLA even under forced pallas: a
    decode row then takes the walk, the gather/attend sandwich's math summed
    block by block."""
    rng = np.random.default_rng(7)
    n_lanes, max_pages, ps, hkv, d = 2, 2, 8, 2, 16
    n_pages = n_lanes * max_pages
    kp, vp = _rand_pool(rng, n_pages, ps, hkv, d)
    tables = jnp.asarray(identity_tables(n_lanes, max_pages))
    k_kv, v_kv = PagedKV(kp, tables), PagedKV(vp, tables)
    q = jnp.asarray(rng.standard_normal((n_lanes, 1, hkv, d)), jnp.float32)
    pos = jnp.asarray([ps, ps + 3], jnp.int32)
    import os

    os.environ[pfa._ENV_VAR] = "pallas"
    try:
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
    finally:
        os.environ.pop(pfa._ENV_VAR, None)


# -------------------------------------------------- backend step integration


def _tiny_backend(model_path):
    import jax

    from petals_tpu.server.backend import TransformerBackend
    from petals_tpu.server.from_pretrained import get_block_config, load_block_params
    from petals_tpu.server.memory_cache import MemoryCache

    family, cfg = get_block_config(model_path)
    per_block = [
        load_block_params(model_path, i, dtype=jnp.float32, family=family, cfg=cfg)
        for i in range(2)
    ]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_block)
    return TransformerBackend(
        family, cfg, stacked, first_block=0, n_blocks=2,
        memory_cache=MemoryCache(None), compute_dtype=jnp.float32, use_flash=False,
    ), cfg


def _seeded_paged_state(backend, cfg, rng, L, PS, MAX_PAGES):
    """Prefill some per-lane history through the exclusive path, then scatter
    it into a page pool under a permuted table."""
    MAXLEN = PS * MAX_PAGES
    positions = np.array([5, 0, 2 * PS], np.int32)[:L]
    hidden = rng.standard_normal((L, 1, cfg.hidden_size)).astype(np.float32) * 0.1
    kd, vd = backend.cache_descriptors(1, MAXLEN, 0, 2)
    lanes_kv = []
    for l in range(L):
        kv = (kd.make_zeros(), vd.make_zeros())
        if positions[l]:
            pre = rng.standard_normal((1, positions[l], cfg.hidden_size)).astype(np.float32) * 0.1
            _, kv = backend.inference_step(pre, kv, 0)
        lanes_kv.append((np.asarray(kv[0]), np.asarray(kv[1])))
    k_dense = np.concatenate([kv[0] for kv in lanes_kv], axis=1)
    v_dense = np.concatenate([kv[1] for kv in lanes_kv], axis=1)

    n_pages = L * MAX_PAGES + 4
    tables = np.full((L, MAX_PAGES), -1, np.int32)
    free = list(np.random.default_rng(99).permutation(n_pages))
    for l in range(L):
        n_slots = max(1, -(-int(positions[l] + 1) // PS))
        for s in range(n_slots):
            tables[l, s] = free.pop()
    n_blocks, _, _, hkv, hd = k_dense.shape
    kp = np.zeros((n_blocks, n_pages, PS, hkv, hd), np.float32)
    vp = np.zeros_like(kp)
    for l in range(L):
        for s in range(MAX_PAGES):
            page = tables[l, s]
            if page < 0:
                continue
            kp[:, page] = k_dense[:, l, s * PS : (s + 1) * PS]
            vp[:, page] = v_dense[:, l, s * PS : (s + 1) * PS]
    return hidden, jnp.asarray(kp), jnp.asarray(vp), positions, tables


def test_paged_decode_step_env_parity(model_path, monkeypatch):
    """The production paged decode step under PETALS_TPU_PAGED_KERNEL=pallas
    (interpret-mode kernel inside the jitted scan) matches the xla path —
    the static kernel_path argument retraces between modes on ONE backend."""
    backend, cfg = _tiny_backend(model_path)
    rng = np.random.default_rng(8)
    hidden, kp, vp, positions, tables = _seeded_paged_state(
        backend, cfg, rng, L=3, PS=8, MAX_PAGES=4
    )
    kp_host, vp_host = np.asarray(kp), np.asarray(vp)
    outs = {}
    for mode in ("xla", "pallas"):
        monkeypatch.setenv(pfa._ENV_VAR, mode)
        # the step donates the pool buffers: each mode gets its own copy
        out, _ = backend.paged_decode_step(
            hidden, (jnp.asarray(kp_host), jnp.asarray(vp_host)), positions, tables
        )
        outs[mode] = np.asarray(out)
    np.testing.assert_allclose(outs["pallas"], outs["xla"], atol=1e-4, rtol=0)


def test_fingerprint_survives_kernel_path(model_path, monkeypatch):
    """with_fp interplay: the fused integrity digest computed INSIDE the
    kernel-path program must match the digest the client re-derives from the
    step's output rows (the PR 8 verification contract)."""
    from petals_tpu.ops import fingerprint as fp_ops

    backend, cfg = _tiny_backend(model_path)
    rng = np.random.default_rng(9)
    hidden, kp, vp, positions, tables = _seeded_paged_state(
        backend, cfg, rng, L=3, PS=8, MAX_PAGES=4
    )
    monkeypatch.setenv(pfa._ENV_VAR, "pallas")
    fp_ops.set_enabled(True)
    try:
        out, _ = backend.paged_decode_step(hidden, (kp, vp), positions, tables)
        fp = backend._last_step_fp
        assert fp is not None
        proj = fp_ops.projection(cfg.hidden_size)
        rederived = fp_ops.fingerprint_rows(jnp.asarray(out)[:, -1, :], proj)
        np.testing.assert_allclose(
            np.asarray(fp), np.asarray(rederived), atol=fp_ops.TOL_EXACT, rtol=0
        )
    finally:
        fp_ops.set_enabled(False)
