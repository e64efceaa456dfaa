"""Quantized paged KV pool tier (``--kv_quant_type``): int8 / packed-nf4a
codec error bounds and np/jnp bit-compatibility, the decode walk's and the
prefill kernel's parity with the gather on quantized pages (identity / permuted / holey tables, GQA, windows,
prefill), requantization idempotence on the check-in paths, swap and
migration byte-exactness of packed pages, COW forks, capacity accounting
(wire bytes per token, descriptor contract, ledger pricing), the calibrated
``kv_quant`` fingerprint band through a real backend step, zero post-warmup
compile anomalies, and canary quorum probing of a quantized-pool replica."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from petals_tpu.ops.paged_attention import (
    KV_QUANT_KINDS,
    PagedKV,
    PagedPool,
    dequantize_kv,
    dequantize_kv_np,
    gather_pages,
    identity_tables,
    kv_wire_bytes_per_token,
    paged_attend,
    paged_prefill_attend,
    paged_update_kv,
    quantize_kv_rows,
    quantize_kv_rows_np,
    stored_row,
)
from petals_tpu.ops.paged_flash_attention import composed_paged_attend, paged_flash_prefill_attend
from tests.utils import lane_pools, make_tiny_llama

pytestmark = pytest.mark.kvquant

KINDS = ("int8", "nf4a")

# Max |x - decode(encode(x))| relative to the row's absmax. int8: half an
# LSB of a 254-step grid (~0.002), with rounding slack. nf4a: half the
# widest inter-code gap (~0.111) plus the 0.9698-codebook-edge clip (~0.03).
RT_BOUND = {"int8": 0.005, "nf4a": 0.145}
# Kernel-vs-XLA agreement on IDENTICAL quantized pages: not quant noise
# (both paths decode the same codes) but dequant-grid noise — the XLA
# reference materializes the dequantized pool at the pool's logical bf16
# dtype while the kernel dequantizes in f32 registers, so values land on
# the bf16 grid (~0.4% relative) before attention accumulates them.
KERNEL_TOL = 2e-2


def _rows(rng, shape):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


def _quant_pools(rng, n_pages, ps, hkv, d, kind):
    kf = _rows(rng, (n_pages, ps, hkv, d))
    vf = _rows(rng, (n_pages, ps, hkv, d))
    return PagedPool(*quantize_kv_rows(kf, kind)), PagedPool(*quantize_kv_rows(vf, kind))


def _holey_permuted(rng, n_lanes, max_pages, n_pages, used_slots):
    tables = np.full((n_lanes, max_pages), -1, np.int32)
    free = list(rng.permutation(n_pages))
    for l in range(n_lanes):
        for s in range(used_slots[l]):
            tables[l, s] = free.pop()
    return tables


@pytest.fixture(scope="module")
def model_path(tmp_path_factory):
    return make_tiny_llama(str(tmp_path_factory.mktemp("models")))


# ------------------------------------------------------------- codec bounds


@pytest.mark.parametrize("kind", KINDS)
def test_roundtrip_error_bounds(kind):
    rng = np.random.default_rng(0)
    rows = _rows(rng, (64, 4, 16)) * jnp.asarray(
        10.0 ** rng.uniform(-3, 2, (64, 1, 1)), jnp.float32
    )  # spread row scales over 5 decades: per-row absmax must track each
    codes, scales = quantize_kv_rows(rows, kind)
    deq = np.asarray(dequantize_kv(codes, scales, kind, jnp.float32), np.float64)
    ref = np.asarray(rows, np.float64)
    absmax = np.abs(ref).max(axis=-1, keepdims=True)
    rel = np.abs(deq - ref) / np.maximum(absmax, 1e-8)
    assert rel.max() <= RT_BOUND[kind], f"{kind}: {rel.max()}"


@pytest.mark.parametrize("kind", KINDS)
def test_zero_rows_decode_to_exact_zero(kind):
    codes, scales = quantize_kv_rows(jnp.zeros((3, 2, 8), jnp.float32), kind)
    deq = np.asarray(dequantize_kv(codes, scales, kind, jnp.float32))
    np.testing.assert_array_equal(deq, 0.0)


@pytest.mark.parametrize("kind", KINDS)
def test_np_jnp_codec_bit_match(kind):
    """The numpy twins (migration pack/unpack, host snapshots) must produce
    the SAME bytes as the jitted encoder — a migrated page re-enters a pool
    that compares it byte-for-byte."""
    rng = np.random.default_rng(1)
    rows = rng.standard_normal((16, 2, 3, 8)).astype(np.float32)
    c_np, s_np = quantize_kv_rows_np(rows, kind)
    c_j, s_j = quantize_kv_rows(jnp.asarray(rows), kind)
    np.testing.assert_array_equal(c_np, np.asarray(c_j))
    np.testing.assert_allclose(s_np, np.asarray(s_j), rtol=1e-6, atol=0)
    d_np = dequantize_kv_np(c_np, s_np, kind)
    d_j = np.asarray(dequantize_kv(c_j, s_j, kind, jnp.float32))
    np.testing.assert_allclose(d_np, d_j, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("kind", KINDS)
def test_requantization_bounded_one_step(kind):
    """Check-in paths (scatter_lane_pages, spec-verify lane chunks)
    requantize a dequantized buffer. int8 is exactly idempotent (the absmax
    element pins the scale); nf4a drifts at most one further quant step."""
    rng = np.random.default_rng(2)
    rows = _rows(rng, (32, 4, 16))
    c1, s1 = quantize_kv_rows(rows, kind)
    deq1 = dequantize_kv(c1, s1, kind, jnp.float32)
    c2, s2 = quantize_kv_rows(deq1, kind)
    if kind == "int8":
        np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-6)
    deq2 = np.asarray(dequantize_kv(c2, s2, kind, jnp.float32), np.float64)
    absmax = np.abs(np.asarray(rows, np.float64)).max(axis=-1, keepdims=True)
    drift = np.abs(deq2 - np.asarray(deq1, np.float64)) / np.maximum(absmax, 1e-8)
    assert drift.max() <= RT_BOUND[kind]


# ------------------------------------------------------- capacity accounting


def test_wire_bytes_per_token_and_capacity_ratio():
    """The acceptance geometry (hkv=8, d=128, bf16 baseline): nf4a must clear
    the >=3.5x fixed-byte-budget capacity gate; int8 lands ~1.94x."""
    none = kv_wire_bytes_per_token(8, 128, "none", 2)
    i8 = kv_wire_bytes_per_token(8, 128, "int8", 2)
    nf = kv_wire_bytes_per_token(8, 128, "nf4a", 2)
    assert (none, i8, nf) == (2048, 1056, 544)
    assert none / nf >= 3.5
    assert none / i8 >= 1.9


@pytest.mark.parametrize("kind", ("none",) + KINDS)
def test_backend_descriptors_and_bytes(model_path, kind):
    backend, cfg = _tiny_backend(model_path, kind)
    descs = lane_pools(backend, 6, 8, end=2)[0]
    hkv, d = backend.num_kv_heads, backend.head_dim
    if kind == "none":
        assert len(descs) == 2
        assert descs[0].shape == (2, 6, 8, *stored_row(hkv, d))  # a row under 128 lanes is stored folded
        assert backend.cache.kv_bytes_per_token() == backend.cache.cache_bytes_per_token()
        return
    assert len(descs) == 4
    d_store = d if kind == "int8" else d // 2
    assert descs[0].shape == descs[1].shape == (2, 6, 8, *stored_row(hkv, d_store))
    assert descs[2].shape == descs[3].shape == (2, 6, 8, hkv)
    assert jnp.dtype(descs[2].dtype) == jnp.float32
    assert backend.cache.kv_bytes_per_token() < backend.cache.cache_bytes_per_token()
    # the descriptor bytes ARE the advertised wire bytes: the whole 4-array
    # pool divided by its token capacity equals kv_bytes_per_token
    total = sum(t.nbytes for t in descs)
    assert total == backend.cache.kv_bytes_per_token() * 6 * 8


def test_backend_rejects_bad_kv_quant(model_path):
    with pytest.raises(ValueError):
        _tiny_backend(model_path, "int4")


def test_ledger_surfaces_kv_cost():
    from petals_tpu.telemetry.ledger import ResourceLedger

    ledger = ResourceLedger()
    snap = ledger.snapshot()
    assert snap["kv_quant"] == "none" and snap["kv_bytes_per_token"] is None
    ledger.set_kv_cost("nf4a", 544 * 2)
    snap = ledger.snapshot()
    assert snap["kv_quant"] == "nf4a" and snap["kv_bytes_per_token"] == 1088


# ------------------------------------------------------- kernel / XLA parity


@pytest.mark.parametrize("kind", KINDS)
def test_decode_parity_identity_tables(kind):
    rng = np.random.default_rng(3)
    n_lanes, max_pages, ps, hkv, group, d = 4, 4, 16, 2, 2, 32
    hq = hkv * group
    kp, vp = _quant_pools(rng, n_lanes * max_pages, ps, hkv, d, kind)
    q = _rows(rng, (n_lanes, 1, hq, d))
    tables = jnp.asarray(identity_tables(n_lanes, max_pages))
    pos = jnp.asarray([0, ps - 1, 2 * ps, 3 * ps + 5], jnp.int32)
    out = composed_paged_attend(q, kp, vp, tables, q_offset=pos, kv_length=pos + 1)
    ref = paged_attend(q, kp, vp, tables, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=KERNEL_TOL, rtol=0)


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("group", [1, 4])
def test_decode_parity_permuted_holey_gqa(kind, group):
    rng = np.random.default_rng(4)
    hq = 8
    hkv = hq // group
    n_lanes, max_pages, ps, d = 3, 4, 8, 16
    n_pages = 20
    kp, vp = _quant_pools(rng, n_pages, ps, hkv, d, kind)
    q = _rows(rng, (n_lanes, 1, hq, d))
    pos = np.array([3 * ps - 1, 2 * ps - 1, ps], np.int32)
    used = [-(-int(p + 1) // ps) for p in pos]
    tables = jnp.asarray(_holey_permuted(rng, n_lanes, max_pages, n_pages, used))
    out = composed_paged_attend(q, kp, vp, tables, q_offset=jnp.asarray(pos), kv_length=jnp.asarray(pos) + 1)
    ref = paged_attend(q, kp, vp, tables, jnp.asarray(pos))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=KERNEL_TOL, rtol=0)


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("window", [None, 7])
def test_decode_parity_alibi_window(kind, window):
    rng = np.random.default_rng(5)
    n_lanes, max_pages, ps, hkv, group, d = 3, 4, 8, 2, 2, 16
    hq = hkv * group
    kp, vp = _quant_pools(rng, n_lanes * max_pages, ps, hkv, d, kind)
    q = _rows(rng, (n_lanes, 1, hq, d))
    perm = rng.permutation(n_lanes * max_pages).astype(np.int32).reshape(n_lanes, max_pages)
    pos = jnp.asarray([0, 2 * ps - 1, 4 * ps - 1], jnp.int32)
    slopes = jnp.asarray(rng.standard_normal(hq) * 0.1, jnp.float32)
    out = composed_paged_attend(
        q, kp, vp, jnp.asarray(perm), q_offset=pos, kv_length=pos + 1,
        alibi_slopes=slopes, sliding_window=window,
    )
    ref = paged_attend(
        q, kp, vp, jnp.asarray(perm), pos, alibi_slopes=slopes, sliding_window=window
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=KERNEL_TOL, rtol=0)


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("chunk_pos,n_valid,window", [(0, 24, None), (8, 17, 9)])
def test_prefill_parity(kind, chunk_pos, n_valid, window):
    rng = np.random.default_rng(6)
    max_pages, ps, hkv, group, d = 6, 8, 2, 4, 16
    hq = hkv * group
    B, n_pages = 24, 12
    kp, vp = _quant_pools(rng, n_pages, ps, hkv, d, kind)
    q = _rows(rng, (1, B, hq, d))
    trow = jnp.asarray(_holey_permuted(rng, 1, max_pages, n_pages, [5])[0])
    slopes = jnp.asarray(rng.standard_normal(hq) * 0.1, jnp.float32)
    cp, nv = jnp.int32(chunk_pos), jnp.int32(n_valid)
    out = paged_flash_prefill_attend(
        q, kp, vp, trow, cp, nv,
        alibi_slopes=slopes, sliding_window=window, interpret=True,
    )
    ref = paged_prefill_attend(
        q, kp, vp, trow, cp, nv, alibi_slopes=slopes, sliding_window=window
    )
    np.testing.assert_allclose(
        np.asarray(out)[:, :n_valid], np.asarray(ref)[:, :n_valid],
        atol=2 * KERNEL_TOL, rtol=0,
    )


@pytest.mark.parametrize("kind", KINDS)
def test_gather_pages_quantized_holes_read_zero(kind):
    rng = np.random.default_rng(7)
    n_pages, ps, hkv, d = 4, 4, 1, 8
    pool = PagedPool(*quantize_kv_rows(_rows(rng, (n_pages, ps, hkv, d)) + 3.0, kind))
    tables = jnp.asarray(np.array([[2, -1], [-1, -1]], np.int32))
    dense = np.asarray(gather_pages(pool, tables))
    assert dense.shape == (2, 2 * ps, hkv, d)
    expect = np.asarray(dequantize_kv(pool.codes, pool.scales, kind, pool.dtype))
    np.testing.assert_array_equal(dense[0, :ps], expect[2])
    np.testing.assert_array_equal(dense[0, ps:], 0.0)
    np.testing.assert_array_equal(dense[1], 0.0)


@pytest.mark.parametrize("kind", KINDS)
def test_spec_verify_lane_chunk_stream_consistency(kind):
    """The speculative-verify write shape (scatter_lane_chunk_rows via
    paged_update_kv) on a quantized pool: the candidate rows land encoded,
    read back within the single-quantization bound, and a rollback rewrite
    of the same rows is deterministic (same bytes both times)."""
    rng = np.random.default_rng(8)
    n_lanes, max_pages, ps, hkv, d, seq = 2, 3, 8, 2, 16, 3
    n_pages = n_lanes * max_pages
    kp, vp = _quant_pools(rng, n_pages, ps, hkv, d, kind)
    tables = jnp.asarray(identity_tables(n_lanes, max_pages))
    k_kv, v_kv = PagedKV(kp, tables), PagedKV(vp, tables)
    pos = jnp.asarray([2, ps - 1], jnp.int32)
    k_new = _rows(rng, (n_lanes, seq, hkv, d))
    v_new = _rows(rng, (n_lanes, seq, hkv, d))
    k1, v1, _ = paged_update_kv(k_kv, v_kv, k_new, v_new, pos)
    k2, v2, _ = paged_update_kv(k_kv, v_kv, k_new, v_new, pos)  # rollback replay
    np.testing.assert_array_equal(np.asarray(k1.pool.codes), np.asarray(k2.pool.codes))
    np.testing.assert_array_equal(np.asarray(v1.pool.scales), np.asarray(v2.pool.scales))
    # the written rows read back within one quant step of the candidates
    dense = np.asarray(gather_pages(k1.pool, tables), np.float64)
    ref = np.asarray(k_new, np.float64)
    for l in range(n_lanes):
        p0 = int(pos[l])
        got = dense[l, p0 : p0 + seq]
        absmax = np.abs(ref[l]).max(axis=-1, keepdims=True)
        rel = np.abs(got - ref[l]) / np.maximum(absmax, 1e-8)
        assert rel.max() <= RT_BOUND[kind]


# -------------------------------------------------- swap / migration / COW


@pytest.mark.parametrize("kind", KINDS)
def test_swap_roundtrip_byte_exact(model_path, kind):
    """Preemption swap-out -> host tier -> swap-in must reproduce the packed
    pages BYTE-exactly (codes and scales), including onto relocated slots."""
    backend, _ = _tiny_backend(model_path, kind)
    rng = np.random.default_rng(9)
    n_pages, ps = 8, 4
    kp, vp = _quant_pools(
        rng, n_pages, ps, backend.num_kv_heads, backend.head_dim, kind
    )
    kp = jax.tree_util.tree_map(lambda a: jnp.broadcast_to(a, (2, *a.shape)), kp)
    vp = jax.tree_util.tree_map(lambda a: jnp.broadcast_to(a, (2, *a.shape)), vp)
    pages = jnp.asarray([1, 5, 6], jnp.int32)
    k_pg, v_pg = backend._swap_out_pages_fn(kp, vp, pages)
    host = jax.tree_util.tree_map(np.asarray, (k_pg, v_pg))
    want_k = jax.tree_util.tree_map(lambda a: np.asarray(a)[:, [1, 5, 6]], kp)
    np.testing.assert_array_equal(host[0].codes, want_k.codes)
    np.testing.assert_array_equal(host[0].scales, want_k.scales)
    # swap back in onto RELOCATED pages of a zeroed pool
    zk = jax.tree_util.tree_map(lambda a: jnp.zeros_like(a), kp)
    zv = jax.tree_util.tree_map(lambda a: jnp.zeros_like(a), vp)
    dst = jnp.asarray([0, 2, 7], jnp.int32)
    nk, nv = backend._swap_in_pages_fn(zk, zv, host[0], host[1], dst)
    np.testing.assert_array_equal(
        np.asarray(nk.codes)[:, [0, 2, 7]], host[0].codes
    )
    np.testing.assert_array_equal(
        np.asarray(nv.scales)[:, [0, 2, 7]], host[1].scales
    )
    # untouched slots stayed zero: nothing was re-inflated or re-encoded
    np.testing.assert_array_equal(np.asarray(nk.codes)[:, 1], 0)


@pytest.mark.parametrize("kind", KINDS)
def test_cow_fork_copies_bytes_verbatim(model_path, kind):
    backend, _ = _tiny_backend(model_path, kind)
    rng = np.random.default_rng(10)
    kp, vp = _quant_pools(rng, 6, 4, backend.num_kv_heads, backend.head_dim, kind)
    kp = jax.tree_util.tree_map(lambda a: jnp.broadcast_to(a, (2, *a.shape)), kp)
    vp = jax.tree_util.tree_map(lambda a: jnp.broadcast_to(a, (2, *a.shape)), vp)
    src_codes = np.asarray(kp.codes)[:, 3].copy()
    src_scales = np.asarray(kp.scales)[:, 3].copy()
    nk, nv = backend._copy_page_fn(kp, vp, jnp.int32(3), jnp.int32(0))
    np.testing.assert_array_equal(np.asarray(nk.codes)[:, 0], src_codes)
    np.testing.assert_array_equal(np.asarray(nk.scales)[:, 0], src_scales)
    assert isinstance(nv, PagedPool)


@pytest.mark.parametrize("kind", KINDS)
def test_migration_pack_wire_unpack_byte_exact(kind):
    """The migration wire (handler.py): dense snapshot -> numpy pack ->
    serialize -> deserialize -> position slice -> dequantize. The packed
    arrays survive the wire byte-exactly, the slice commutes with decode,
    and the wire is >=3.5x (nf4a) / ~1.9x (int8) smaller than the snapshot."""
    from petals_tpu.rpc.serialization import (
        CompressionType,
        deserialize_array,
        serialize_array,
    )

    rng = np.random.default_rng(11)
    n_blocks, batch, position, hkv, d = 2, 1, 12, 8, 128
    snap = rng.standard_normal((n_blocks, batch, position, hkv, d)).astype(np.float32)
    codes, scales = quantize_kv_rows_np(snap, kind)
    # lossy float codecs must pass integer codes through verbatim
    wire_codes = deserialize_array(serialize_array(codes, CompressionType.FLOAT16))
    wire_scales = deserialize_array(serialize_array(scales, CompressionType.NONE))
    np.testing.assert_array_equal(wire_codes, codes)
    np.testing.assert_array_equal(wire_scales, scales)
    wire_bytes = 2 * (codes.nbytes + scales.nbytes)  # k and v sides
    fp_bytes = 2 * snap.astype(np.float16).nbytes  # bf16-width fp wire
    assert fp_bytes / wire_bytes >= (3.5 if kind == "nf4a" else 1.9)
    # adopt path: slice the packed entry along the position axis, then decode
    cut = 7
    sliced = dequantize_kv_np(wire_codes[:, :, :cut], wire_scales[:, :, :cut], kind)
    full = dequantize_kv_np(wire_codes, wire_scales, kind)
    np.testing.assert_array_equal(sliced, full[:, :, :cut])


# ----------------------------------------- backend step: band + no recompile


def _tiny_backend(model_path, kind="none", n_blocks=2):
    from petals_tpu.server.backend import TransformerBackend
    from petals_tpu.server.from_pretrained import get_block_config, load_block_params
    from petals_tpu.server.memory_cache import MemoryCache

    family, cfg = get_block_config(model_path)
    per_block = [
        load_block_params(model_path, i, dtype=jnp.float32, family=family, cfg=cfg)
        for i in range(n_blocks)
    ]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_block)
    return TransformerBackend(
        family, cfg, stacked, first_block=0, n_blocks=n_blocks,
        memory_cache=MemoryCache(None), compute_dtype=jnp.float32,
        use_flash=False, kv_quant_type=kind,
    ), cfg


def _seeded_paged_state(backend, cfg, rng, L, PS, MAX_PAGES):
    positions = np.array([5, 0, 2 * PS], np.int32)[:L]
    hidden = rng.standard_normal((L, 1, cfg.hidden_size)).astype(np.float32) * 0.1
    kd, vd = backend.cache_descriptors(1, PS * MAX_PAGES, 0, 2)
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
    return hidden, kp, vp, positions, tables


@pytest.mark.parametrize("kind", KINDS)
def test_backend_step_within_kv_quant_band_no_recompile(model_path, kind):
    """The production paged decode step on a quantized pool: output within
    the calibrated kv_quant fingerprint band of the fp-pool step, and the
    second step with the same shapes triggers ZERO compile anomalies (the
    PagedPool pytree must not perturb the steady-state program cache)."""
    from petals_tpu.ops import fingerprint as fp_ops
    from petals_tpu.telemetry.observatory import get_observatory

    fp_backend, cfg = _tiny_backend(model_path, "none")
    q_backend, _ = _tiny_backend(model_path, kind)
    rng = np.random.default_rng(12)
    hidden, kp, vp, positions, tables = _seeded_paged_state(
        fp_backend, cfg, rng, L=3, PS=8, MAX_PAGES=4
    )
    out_fp, _ = fp_backend.paged_decode_step(
        hidden, (jnp.asarray(kp), jnp.asarray(vp)), positions, tables
    )
    out_fp = np.asarray(out_fp)

    def qpools():
        return (
            PagedPool(*quantize_kv_rows(jnp.asarray(kp), kind)),
            PagedPool(*quantize_kv_rows(jnp.asarray(vp), kind)),
        )

    out_q, new_pools = q_backend.paged_decode_step(hidden, qpools(), positions, tables)
    out_q = np.asarray(out_q)
    assert isinstance(new_pools[0], PagedPool)  # writes stayed quantized
    band = fp_ops.tolerance_for("none", kind)
    scale = np.abs(out_fp).max()
    assert np.abs(out_q - out_fp).max() <= band * scale, (
        f"{kind}: {np.abs(out_q - out_fp).max() / scale} > {band}"
    )
    # steady state: the same shapes again must not compile anything new
    before = get_observatory().compile_stats()["anomalies"]
    out2, _ = q_backend.paged_decode_step(hidden, qpools(), positions, tables)
    np.testing.assert_array_equal(np.asarray(out2), out_q)  # deterministic
    assert get_observatory().compile_stats()["anomalies"] == before


@pytest.mark.parametrize("kind", KINDS)
def test_lane_gather_scatter_roundtrip(model_path, kind):
    """Exclusive-op checkout/check-in on a quantized pool: gather decodes,
    scatter re-encodes; an untouched check-in drifts at most one quant step
    and int8 is byte-identical."""
    backend, _ = _tiny_backend(model_path, kind)
    rng = np.random.default_rng(13)
    hkv, d = backend.num_kv_heads, backend.head_dim
    n_pages, ps, max_pages = 10, 4, 3
    kp, vp = _quant_pools(rng, n_pages, ps, hkv, d, kind)
    kp = jax.tree_util.tree_map(lambda a: jnp.broadcast_to(a, (2, *a.shape)), kp)
    vp = jax.tree_util.tree_map(lambda a: jnp.broadcast_to(a, (2, *a.shape)), vp)
    trow = jnp.asarray([4, 7, -1], jnp.int32)
    k_buf, v_buf = backend._paged_lane_gather_fn(kp, vp, trow)
    assert k_buf.shape == (2, 1, max_pages * ps, hkv, d)
    nk, nv = backend._paged_lane_scatter_fn(
        jax.tree_util.tree_map(jnp.copy, kp), jax.tree_util.tree_map(jnp.copy, vp),
        k_buf, v_buf, trow,
    )
    if kind == "int8":
        np.testing.assert_array_equal(
            np.asarray(nk.codes)[:, [4, 7]], np.asarray(kp.codes)[:, [4, 7]]
        )
    got = np.asarray(
        dequantize_kv(nk.codes, nk.scales, kind, jnp.float32), np.float64
    )[:, [4, 7]]
    want = np.asarray(
        dequantize_kv(kp.codes, kp.scales, kind, jnp.float32), np.float64
    )[:, [4, 7]]
    absmax = np.maximum(np.abs(want).max(axis=-1, keepdims=True), 1e-8)
    assert (np.abs(got - want) / absmax).max() <= RT_BOUND[kind]


# ------------------------------------------------------------- canary quorum


def test_canary_quorum_tolerates_quantized_pool_replica():
    """A replica serving from a quantized pool diverges within the kv_quant
    band — the widened quorum tolerance must NOT quarantine it; a replica
    with corrupted scales diverges far beyond the band and must be."""
    from petals_tpu.telemetry.integrity import CanaryProber, QuarantineRegistry

    base = np.array([0.5, -1.5, 2.0, 0.8], np.float32)
    within_band = base * 1.05  # ~5% drift: inside tolerance_for("none","int8")
    corrupted = base * 2.5  # scales corruption: far outside every band
    fps = {"fp1": base, "fp2": base, "quantized": within_band}
    reg = QuarantineRegistry(window_s=60.0)
    prober = CanaryProber(lambda peer, fb, nb: fps[peer], quarantine=reg)
    report = prober.probe_span(
        (0, 4), ["fp1", "fp2", "quantized"], quant="none", kv_quant="int8"
    )
    assert report["outliers"] == [] and report["quorum"] == 3
    assert not reg.is_quarantined("quantized")
    # the SAME drift without the kv_quant widening IS an outlier
    report = prober.probe_span((0, 4), ["fp1", "fp2", "quantized"], quant="none")
    assert report["outliers"] == ["quantized"]
    reg.release("quantized")
    fps["quantized"] = corrupted
    report = prober.probe_span(
        (0, 4), ["fp1", "fp2", "quantized"], quant="none", kv_quant="int8"
    )
    assert report["outliers"] == ["quantized"]
    assert reg.is_quarantined("quantized")


def test_kv_quant_kinds_frozen():
    assert KV_QUANT_KINDS == ("none", "int8", "nf4a")
    with pytest.raises(ValueError):
        quantize_kv_rows(jnp.zeros((1, 2)), "nf4")
    with pytest.raises(ValueError):
        dequantize_kv_np(np.zeros((1, 2), np.int8), np.zeros((1,), np.float32), "bogus")


def test_quantized_helpers_lint_clean():
    """swarmlint coverage of the quantized pool path: the codec helpers and
    the in-kernel dequant module must carry zero unsuppressed findings (they
    run inside tracked_jit step programs, so a tracer-safety or untracked-jit
    slip here would corrupt every compiled variant), and the tracer-safety
    rule must actually fire on the canonical misuse — host branching on a
    dequantized traced value inside a jitted step."""
    import os

    from petals_tpu.analysis import check_paths, check_source, unsuppressed

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    findings = unsuppressed(check_paths([
        os.path.join(repo, "petals_tpu", "ops", "paged_attention.py"),
        os.path.join(repo, "petals_tpu", "ops", "paged_flash_attention.py"),
    ]))
    assert not findings, "\n".join(f.format() for f in findings)

    bad = (
        "from petals_tpu.ops.paged_attention import dequantize_kv\n"
        "from petals_tpu.telemetry.observatory import tracked_jit\n"
        "@tracked_jit(name='f', steady=True)\n"
        "def f(codes, scales):\n"
        "    if scales > 0:\n"
        "        codes = codes + 1\n"
        "    return dequantize_kv(codes, scales, 'int8')\n"
    )
    hits = {
        f.rule for f in unsuppressed(check_source(bad, "server/snippet.py"))
    }
    assert "tracer-safety" in hits
