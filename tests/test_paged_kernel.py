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
