"""Attention over a paged KV pool: what a ``PagedKV`` in a step program reaches
through ops/attention.py ``attend`` (``paged_attend_dispatch``). Dense is the
identity block table, so the dense-shaped steps take the same code.

A DECODE row (per-lane positions) always takes ``composed_paged_attend``. One
query row a lane walks its lane's pages in blocks of table slots with a
running softmax, and ``decode_walk_path`` picks the walk from the call's own
shapes and dtypes: on a TPU, over a plain pool of whole tiles, ONE Pallas
kernel a layer (``_walk_kernel``): the span's pools stay in HBM as the layer
loop carries them, each live lane's pages of ALL kv heads are copied block by
block into one of two buffers under the block before, and a block is met as
one matrix as it lies (rows of ``[hkv, d]``: ``[rows * hkv, d]`` with the
off-head columns masked; a folded row of up to 4 kv heads: ``[rows, hkv *
d]``, the heads its column blocks), so nothing is relaid and no layer is
sliced out; everywhere else (off the chip, a quantised pool, half a tile of kv
heads, a folded row of heads under 128 lanes, ALiBi, a soft cap, a traced
window) a ``fori_loop`` in plain ``jax.numpy`` (``_walk_decode_rows``), which
is also the kernel's reference. A verify's
rows and a non-causal call gather their pages and run ``attend_reference``.

A prompt's CHUNK (a scalar position, one lane's table) takes the fused
chunked-prefill kernel (``paged_flash_prefill_attend``) where the platform is
a TPU, the call is one the kernel can express (causal, a static window or
none, no soft cap) and Mosaic can tile the pool's class
(``paged_kernel_unsupported``: a static predicate with one WARNING a class,
never a caught compile failure); else ``composed_paged_attend``'s gather. The
kernel walks the block table directly: the KV BlockSpec index maps read the
lane's table (scalar-prefetched into SMEM) and fetch pages straight from the
pool, so no dense view is made, and pages beyond the chunk's causal frontier,
beyond the sliding window, or unallocated (-1) are never fetched (their DMA is
redirected to a repeated block index, which Pallas elides). Mosaic takes the
pool only through a lane-trailing VIEW (``[n_pages, page_size, hkv * d]``, see
the notes above ``_kv_heads_per_block``); of a pool of rows of ``[hkv, d]``
that view is a physical relayout under TPU tiling, so the dispatch hands the
kernel the block's own layer (``PagedKV.own_layer``), not the span's pool. A
pool whose row is under 128 lanes (head_dim 64) is STORED lane-trailing
(ops/paged_attention.py ``stored_row``): ``_pool_views`` of it is the array
itself, and the kernel and the composed path read the form off the leaf
(``pool_geometry``).

Nothing here is timed at start and nothing is read from the environment. Off
the chip only the composed path runs unless a test asks for a kernel by name
(``paged_flash_prefill_attend(..., interpret=True)``,
``composed_paged_attend(..., path="kernel")``), so tier-1 CPU runs never
depend on interpret-mode Mosaic semantics by accident.

The prefill kernel's structure is lifted from ops/flash_attention.py:
online-softmax m/l/acc scratch carried across the innermost (arbitrary) grid
axis, a shared "needed" predicate between the kernel's @pl.when skip and the
index map's DMA-elision redirect, and an interior/edge tile split so
fully-visible pages skip mask construction. It mirrors the reference contract
in ops/paged_attention.py (``paged_prefill_attend``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from petals_tpu.ops.quant import NF4A_A, NF4A_B
from petals_tpu.telemetry.observatory import tracked_jit

LANES = 128
NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _platform() -> str:
    # indirection so the dispatch's table test can fake a TPU
    return jax.default_backend()


def paged_kernel_unsupported(hkv: int, d: int, kv_quant: str = "none") -> Optional[str]:
    """Why Mosaic cannot take a pool of ``hkv`` kv heads of ``d`` in this
    encoding, or None if it can — the static gate in front of the prefill
    kernel (flash_supported's twin). A KV block must end in a lane multiple or
    the whole [hkv * d_store] row (see the view notes above
    ``_kv_heads_per_block``): head widths that neither are a multiple of 128
    nor pack evenly into 128 lanes are composed from XLA."""
    d_store = _kv_store_dim(d, kv_quant)
    hb = _kv_heads_per_block(hkv, d_store)
    if (hb * d_store) % LANES and hkv != 1:
        return (
            f"{hkv} kv heads of {d_store} stored lanes ({kv_quant} pages, head_dim {d}) "
            f"do not tile into {LANES}-lane blocks"
        )
    return None


_WARNED_UNSUPPORTED: set = set()


# ---------------------------------------------------------------------------
# in-tile dequant: quantized pages expand to f32 in VMEM right after the DMA
# ---------------------------------------------------------------------------
#
# The scale factoring keeps the per-element dequant work near zero: scores
# are computed against the RAW codes and the per-row kv scale multiplies the
# [*, page_size] score matrix afterwards (one mul per score, not per
# element); on the value side the scale folds into the softmax weights
# BEFORE the pv dot. nf4a pages are split-half packed (byte j = dims j and
# j + d/2), so K decodes as two half-width dots against the query halves and
# V as two half-width pv dots concatenated along the head dim — no lane-axis
# interleave relayout, which Mosaic would refuse. Mosaic constraints honored
# throughout: uint8 widens to int32 before nibble ops (no 8-bit shifts), and
# everything runs in f32 — quant.py's decode kernels measured bf16
# elementwise at ~2x f32 on the VPU, so f32 dots win once dequant is fused.


def _nf4a_poly(codes_f32):
    """codes (0..15, f32) -> UNSCALED cubic code values; the caller folds
    ``scale * NF4A_B`` in at score/weight granularity."""
    dl = codes_f32 - 7.5
    kk = jnp.float32(NF4A_A / NF4A_B)
    return dl * (kk + dl * dl)


def _quant_k_scores(q, k_raw, ks_row, kv_quant, head_dim):
    """Scores against a quantized K page. q [m, head_dim] (any float dtype),
    k_raw [page_size, d_store] raw codes, ks_row [1, page_size] f32 per-row
    scales -> s [m, page_size] f32 with the kv scales folded in (attention
    scale NOT applied)."""
    qf = q.astype(jnp.float32)
    if kv_quant == "int8":
        kc = k_raw.astype(jnp.int32).astype(jnp.float32)
        s = jax.lax.dot_general(
            qf, kc, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        return s * ks_row
    c = k_raw.astype(jnp.int32)
    p_lo = _nf4a_poly((c & 0x0F).astype(jnp.float32))
    p_hi = _nf4a_poly(((c >> 4) & 0x0F).astype(jnp.float32))
    half = head_dim // 2
    s = jax.lax.dot_general(
        qf[:, :half], p_lo, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    s = s + jax.lax.dot_general(
        qf[:, half:], p_hi, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    return s * (ks_row * jnp.float32(NF4A_B))


def _quant_pv(p, v_raw, vs_row, kv_quant, head_dim):
    """Weighted-value accumulation against a quantized V page. p
    [m, page_size] f32 softmax weights, v_raw [page_size, d_store] raw
    codes, vs_row [1, page_size] f32 -> pv [m, head_dim] f32."""
    if kv_quant == "int8":
        vc = v_raw.astype(jnp.int32).astype(jnp.float32)
        return jax.lax.dot_general(
            p * vs_row, vc, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
    c = v_raw.astype(jnp.int32)
    p_lo = _nf4a_poly((c & 0x0F).astype(jnp.float32))
    p_hi = _nf4a_poly(((c >> 4) & 0x0F).astype(jnp.float32))
    ps_ = p * (vs_row * jnp.float32(NF4A_B))
    pv_lo = jax.lax.dot_general(
        ps_, p_lo, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    pv_hi = jax.lax.dot_general(
        ps_, p_hi, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    return jnp.concatenate([pv_lo, pv_hi], axis=1)


def _kv_store_dim(head_dim: int, kv_quant: str) -> int:
    """Last-axis extent of the stored codes: nf4a packs two dims per byte."""
    return head_dim // 2 if kv_quant == "nf4a" else head_dim


# Mosaic block rule: a block's last two dims must be multiples of (8, 128) or
# span the whole array dims. A [n_pages, page_size, hkv, d] pool blocked one
# head at a time ends in (1, d) and is refused, so the wrapper hands the
# kernel VIEWS with page_size/lanes trailing:
#   codes/values  [n_pages, page_size, hkv * d_store], block (1, page_size,
#                 hb * d_store) at (page, 0, h // hb) — heads narrower than
#                 128 lanes ride ``hb`` to a block and the tile slices its own;
#   scales        [n_pages, hkv, page_size] (transposed), block (1, hkv,
#                 page_size) — the tile reads row h, already lane-major.


def _kv_heads_per_block(num_kv_heads: int, d_store: int) -> int:
    """kv heads per KV block: 128 // d_store when that tiles the heads, else
    one (which Mosaic takes only if d_store is a lane multiple or hkv == 1 —
    ``paged_kernel_unsupported`` is the static gate; the interpreter takes
    any)."""
    per = LANES // d_store if d_store < LANES and LANES % d_store == 0 else 1
    return per if num_kv_heads % per == 0 else 1


def _pool_views(k_pool, v_pool, quantized: bool):
    """The pool operands in kernel order — k, [ks], v, [vs] — as the
    lane-trailing views described above. A pool stored folded
    (ops/paged_attention.py ``stored_row``) IS its view: no reshape, no
    relayout."""
    def codes_view(a):
        return a.reshape(a.shape[0], a.shape[1], -1)

    if not quantized:
        return [codes_view(k_pool), codes_view(v_pool)]
    return [
        codes_view(k_pool.codes), k_pool.scales.transpose(0, 2, 1),
        codes_view(v_pool.codes), v_pool.scales.transpose(0, 2, 1),
    ]


def _head_block(ref, t: int, d_store: int):
    """[page_size, d_store] of the t-th head in a kv block ref
    [1, page_size, hb * d_store] (raw codes if quantized)."""
    return ref[0, :, t * d_store:(t + 1) * d_store]


def _tile_branches(tile, needed, interior, kv_head, heads_per_block: int):
    """Run ``tile(masked, t)`` for this grid step: interior pages skip the
    mask work, and when a kv block carries several heads the head's slot
    ``t = kv_head % heads_per_block`` picks a statically sliced variant (a
    dynamic lane offset is not something Mosaic slices by)."""
    for t in range(heads_per_block):
        mine = needed if heads_per_block == 1 else needed & (kv_head % heads_per_block == t)
        pl.when(mine & interior)(functools.partial(tile, False, t))
        pl.when(mine & jnp.logical_not(interior))(functools.partial(tile, True, t))


# ---------------------------------------------------------------------------
# chunked-prefill kernel: grid (hq, num_q_blocks, max_pages), one lane
# ---------------------------------------------------------------------------


def _prefill_page_needed(
    page, q_block_start, block_q, slot_start, kv_len, page_size, sliding_window
):
    """Does any (q row, kv position) pair of this (q block, page) tile need
    computing? Shared by the kernel skip and the kv index map redirect."""
    needed = (
        (page >= 0)
        & (slot_start <= q_block_start + block_q - 1)  # causal frontier
        & (slot_start < kv_len)
    )
    if sliding_window is not None:
        needed &= slot_start + page_size - 1 > q_block_start - sliding_window
    return needed


def _prefill_kernel(
    # scalar prefetch
    table_row_ref,  # int32[max_pages]
    info_ref,  # int32[2] = (chunk_pos, kv_len)
    slopes_ref,  # float32[num_q_heads]
    # then, positionally: inputs / outputs / scratch —
    #   q_ref [1, block_q, head_dim];
    #   k_ref [1, page_size, hb * d_store] (raw codes if quantized);
    #   ks_ref [1, hkv, page_size] f32 (quantized pools only);
    #   v_ref / vs_ref likewise; o_ref [1, block_q, head_dim];
    #   m/l_scratch [block_q, LANES] f32, acc_scratch [block_q, head_dim] f32
    *refs,
    scale: float,
    block_q: int,
    page_size: int,
    max_pages: int,
    group: int,
    head_dim: int,
    heads_per_block: int,
    use_alibi: bool,
    sliding_window: Optional[int] = None,
    kv_quant: str = "none",
):
    if kv_quant == "none":
        q_ref, k_ref, v_ref, o_ref, m_scratch, l_scratch, acc_scratch = refs
        ks_ref = vs_ref = None
    else:
        (q_ref, k_ref, ks_ref, v_ref, vs_ref, o_ref,
         m_scratch, l_scratch, acc_scratch) = refs
    h = pl.program_id(0)
    kv_head = h // group
    d_store = _kv_store_dim(head_dim, kv_quant)
    qi = pl.program_id(1)
    j = pl.program_id(2)

    chunk_pos = info_ref[0]
    kv_len = info_ref[1]
    page = table_row_ref[j]

    @pl.when(j == 0)
    def _init():
        m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    q_block_start = chunk_pos + qi * block_q
    slot_start = j * page_size
    needed = _prefill_page_needed(
        page, q_block_start, block_q, slot_start, kv_len, page_size, sliding_window
    )

    interior = (slot_start + page_size - 1 <= q_block_start) & (
        slot_start + page_size <= kv_len
    )
    if sliding_window is not None:
        interior &= slot_start >= q_block_start + block_q - sliding_window

    def _tile(masked: bool, t: int):
        q = q_ref[0]  # [block_q, head_dim]
        if kv_quant == "none":
            k = _head_block(k_ref, t, d_store)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )  # [block_q, page_size]
        else:
            ks_row = ks_ref[0, pl.ds(kv_head, 1), :]  # [1, page_size]
            s = _quant_k_scores(
                q, _head_block(k_ref, t, d_store), ks_row, kv_quant, head_dim
            )
        s = s * scale

        kv_pos_row = slot_start + jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1)
        if use_alibi:
            s = s + slopes_ref[h] * kv_pos_row.astype(jnp.float32)

        if masked:
            kv_pos = slot_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, page_size), 1
            )
            q_pos = q_block_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, page_size), 0
            )
            mask = (kv_pos <= q_pos) & (kv_pos < kv_len)
            if sliding_window is not None:
                mask &= kv_pos > q_pos - sliding_window
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scratch[...]
        l_prev = l_scratch[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))

        alpha = jnp.exp(m_prev[:, :1] - m_new[:, :1])
        p = jnp.exp(s - m_new[:, :1])
        if masked:
            p = jnp.where(mask, p, 0.0)

        l_new = alpha * l_prev[:, :1] + jnp.sum(p, axis=1, keepdims=True)

        acc = acc_scratch[...]
        if kv_quant == "none":
            v = _head_block(v_ref, t, d_store)
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        else:
            vs_row = vs_ref[0, pl.ds(kv_head, 1), :]
            pv = _quant_pv(
                p, _head_block(v_ref, t, d_store), vs_row, kv_quant, head_dim
            )
        acc_scratch[...] = acc * alpha + pv

        m_scratch[...] = m_new
        l_scratch[...] = jnp.broadcast_to(l_new, l_scratch.shape)

    _tile_branches(_tile, needed, interior, kv_head, heads_per_block)

    @pl.when(j == max_pages - 1)
    def _finalize():
        # a chunk_pos==0, n_valid==0 bucket leaves l == 0 -> exact zeros
        l = l_scratch[:, :1]
        out = acc_scratch[...] / jnp.maximum(l, 1e-30)
        o_ref[0] = out.astype(o_ref.dtype)


@tracked_jit(
    name="paged_flash_prefill_attend",
    static_argnames=("scale", "sliding_window", "block_q", "interpret"),
)
def paged_flash_prefill_attend(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    table_row: jnp.ndarray,
    chunk_pos: jnp.ndarray,
    n_valid: jnp.ndarray,
    *,
    alibi_slopes: Optional[jnp.ndarray] = None,
    sliding_window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Fused ragged paged-attention CHUNKED PREFILL: same contract as
    ops/paged_attention.py paged_prefill_attend. q [1, chunk, hq, d] (padded
    to a bucket); table_row [max_pages] int32; chunk_pos scalar int32
    (absolute position of the chunk's first token); n_valid scalar int32
    (padded-tail rows produce garbage-but-unread outputs, as in the
    reference). The chunk's KV must already be scattered into the pages.
    Quantized pools ride as codes + scales, exactly as in the decode twin."""
    from petals_tpu.ops.paged_attention import PagedPool, pool_geometry

    quantized = isinstance(k_pool, PagedPool)
    kv_quant = k_pool.kind if quantized else "none"
    batch, q_len, num_q_heads, head_dim = q.shape
    n_pages, page_size, num_kv_heads, d_store = pool_geometry(k_pool, head_dim)  # in either stored form
    if batch != 1:
        raise ValueError(f"prefill kernel serves one lane's chunk, got batch={batch}")
    assert num_q_heads % num_kv_heads == 0, (num_q_heads, num_kv_heads)
    group = num_q_heads // num_kv_heads
    max_pages = table_row.shape[0]
    if scale is None:
        scale = head_dim**-0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    block_q = min(block_q or 256, _round_up(q_len, 8))
    q_pad = _round_up(q_len, block_q) - q_len
    if q_pad:
        q = jnp.pad(q, ((0, 0), (0, q_pad), (0, 0), (0, 0)))
    padded_q_len = q.shape[1]
    num_q_blocks = padded_q_len // block_q

    # kernel layout [heads, seq, head_dim]: blocked (seq, head_dim) trailing
    qt = q[0].transpose(1, 0, 2)

    table_arr = jnp.asarray(table_row, jnp.int32)
    pos = jnp.asarray(chunk_pos, jnp.int32).reshape(())
    info = jnp.stack([pos, pos + jnp.asarray(n_valid, jnp.int32).reshape(())])
    if alibi_slopes is None:
        slopes = jnp.zeros((num_q_heads,), jnp.float32)
        use_alibi = False
    else:
        slopes = alibi_slopes.astype(jnp.float32)
        use_alibi = True

    hb = _kv_heads_per_block(num_kv_heads, d_store)

    grid = (num_q_heads, num_q_blocks, max_pages)

    kernel = functools.partial(
        _prefill_kernel,
        scale=scale,
        block_q=block_q,
        page_size=page_size,
        max_pages=max_pages,
        group=group,
        head_dim=head_dim,
        heads_per_block=hb,
        use_alibi=use_alibi,
        sliding_window=sliding_window,
        kv_quant=kv_quant,
    )

    def live_page(qi, j, table_row_ref, info_ref, slopes_ref):
        page = table_row_ref[j]
        needed = _prefill_page_needed(
            page, info_ref[0] + qi * block_q, block_q,
            j * page_size, info_ref[1], page_size, sliding_window,
        )
        return jax.lax.select(needed, page, 0)

    kv_spec = pl.BlockSpec(
        (1, page_size, hb * d_store),
        lambda h, qi, j, *pf: (live_page(qi, j, *pf), 0, h // group // hb),
    )
    scale_spec = pl.BlockSpec(
        (1, num_kv_heads, page_size), lambda h, qi, j, *pf: (live_page(qi, j, *pf), 0, 0)
    )
    in_specs = [
        pl.BlockSpec((1, block_q, head_dim), lambda h, qi, j, *pf: (h, qi, 0)),
        *([kv_spec, scale_spec, kv_spec, scale_spec] if quantized else [kv_spec, kv_spec]),
    ]
    operands = [qt, *_pool_views(k_pool, v_pool, quantized)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, block_q, head_dim), lambda h, qi, j, *pf: (h, qi, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, head_dim), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(table_arr, info, slopes, *operands)

    out = out.transpose(1, 0, 2)[None]
    if q_pad:
        out = out[:, :q_len]
    return out


# ---------------------------------------------------------------------------
# dispatch: what attend() calls on a PagedKV
# ---------------------------------------------------------------------------


def paged_attend_dispatch(
    q: jnp.ndarray,
    k_kv,
    v_kv,
    *,
    q_offset,
    kv_length,
    alibi_slopes: Optional[jnp.ndarray] = None,
    sliding_window=None,
    scale: Optional[float] = None,
    causal: bool = True,
    logit_softcap: Optional[float] = None,
) -> jnp.ndarray:
    """Route a PagedKV attention call (TRACE time, inside the step program).

    The position's rank tells the two contracts apart: per-lane [n] vectors
    are decode rows (ragged kv_length = position + 1; a verify's several rows
    a lane among them) and go to ``composed_paged_attend``, always; a scalar
    is one lane's chunked-prefill bucket and takes the fused prefill kernel on
    a TPU, unless the call is one the kernel cannot express — gemma2's logit
    softcap and its TRACED effective window, non-causal, no kv_length — or
    Mosaic cannot tile the pool's class (``paged_kernel_unsupported``)."""
    from petals_tpu.ops.paged_attention import kv_quant_kind_of, pool_geometry

    pos = jnp.asarray(q_offset, jnp.int32)
    expressible = (
        logit_softcap is None
        and causal
        and (sliding_window is None or isinstance(sliding_window, int))
        and kv_length is not None
    )
    if pos.ndim == 0 and expressible and _platform() == "tpu":
        # the LOGICAL geometry, whichever form the pool is stored in
        _, _, hkv, _ = pool_geometry(k_kv.pool, q.shape[-1])
        cls = (hkv, q.shape[-1], kv_quant_kind_of(k_kv.pool))
        reason = paged_kernel_unsupported(*cls)
        if reason is None:
            # the kernel relays the pool it is handed (_pool_views): give it the
            # block's own layer, not the span's pool the step's loop carries
            k_kv, v_kv = k_kv.own_layer(), v_kv.own_layer()
            kv_len = jnp.asarray(kv_length, jnp.int32).reshape(())
            return paged_flash_prefill_attend(
                q, k_kv.pool, v_kv.pool, k_kv.tables[0], pos, kv_len - pos,
                alibi_slopes=alibi_slopes, sliding_window=sliding_window,
                scale=scale,
            )
        if cls not in _WARNED_UNSUPPORTED:
            _WARNED_UNSUPPORTED.add(cls)
            from petals_tpu.utils.logging import get_logger

            get_logger(__name__).warning(
                f"paged-attention prefill kernel excluded for (kv heads, head_dim, pages) {cls}: {reason}; "
                f"a prompt's chunk composes from XLA (gather + attend_reference)"
            )
    return composed_paged_attend(
        q, k_kv.pool, v_kv.pool, k_kv.tables, q_offset=pos, kv_length=kv_length,
        alibi_slopes=alibi_slopes, sliding_window=sliding_window,
        scale=scale, causal=causal, logit_softcap=logit_softcap,
    )


def window_pages(window, q_len: int, page_size: int, max_pages: int) -> int:
    """Table slots a lane's ``q_len`` query rows can reach on the XLA-composed
    path: every slot, or under a STATIC window (and a causal mask) the few
    pages the first row's window and the last row can reach between them
    (3 of 16 for a decode row, a window of 128 and pages of 64). A prompt's
    chunk and a verify's rows gather that many; a decode row walks them in
    blocks, as far as the longest live lane (``walk_pages``)."""
    if not isinstance(window, int) or window <= 0:
        return max_pages
    return min((q_len - 1 + window - 1 + page_size - 1) // page_size + 1, max_pages)


# Keys (or values) a block of the decode walk holds at once, all lanes'. Small:
# on the v5e a block of one page of 64 rows a lane is gathered into fast memory
# and meets the dots there as it is stored, where a wider one is first written
# out again in float32 (and, at a head_dim of 64, relaid), and a block past
# the longest lane's end is read for nothing; a loop trip's fixed cost is 3-5
# us, what 2-4 MB take to read. Set by benchmarks/ablate_paged_walk.py: one
# page a block is the best or within 0.01 ms a layer of it at every cell's
# pool (PERF.md section 5, PR 36); pages smaller than the cells' go several a
# block.
WALK_BLOCK_BYTES = 512 << 10
# and the most trips a walk takes over a table: past it a block is as many slots as keep the walk within it. A trip gathers
# a page a lane whatever the block's bytes say, and over a table of hundreds of slots the trips are the walk: 16 lanes of
# 4 kv heads of 128 kept as rows of [4, 128] at contexts of 2.5k-14.5k (a table of 256 slots, 227 walked) took 2.85 ms a
# layer at one page a trip, 2.15 at four, 2.04 at eight, 2.40 at 32; a window's 65 slots 0.82 at one, 0.69 at two, 0.64 at
# four (benchmarks/ablate_paged_walk.py smallthinker-21b smallthinker-21b-window, PR 64). That pool is stored folded since
# PR 65 and its walks are the kernel's; the bound stays for what still takes the composed walk over a wide table (a
# quantised pool, ALiBi, a soft cap, a traced window, and everything off the chip). No table of 64 slots or fewer, every
# other cell's, is touched
WALK_MAX_TRIPS = 64


def walk_block_pages(n_lanes: int, width: int, page_size: int, hkv: int, d: int, itemsize: int = 2) -> int:
    """Table slots a block of the decode walk takes, from the shapes alone:
    the largest power of two whose pages, over all lanes, stay within
    ``WALK_BLOCK_BYTES`` a side, at least one and at most the ``width`` slots
    there are to walk (one page of 64 rows at every cell's 8 lanes and 8-32
    kv heads; four pages of 16 rows at 8 kv heads of 128); and over a table
    wider than ``WALK_MAX_TRIPS`` slots as many as keep the trips within that
    (four pages over 16-lane tables of 256 slots, two over a window's 65)."""
    a_slot = n_lanes * page_size * hkv * d * itemsize
    block = 1
    while block * 2 * a_slot <= WALK_BLOCK_BYTES:
        block *= 2
    while block * WALK_MAX_TRIPS < width:  # a table of hundreds of slots: no more than ``WALK_MAX_TRIPS`` trips
        block *= 2
    return min(block, width)


def walk_pages(needed: int, block: int) -> int:
    """Table slots a lane is read over by a decode walk whose longest live
    lane needs ``needed`` of them: whole blocks. The batcher counts a step's
    pages by this, the walk's trip count is this over ``block``."""
    return _round_up(needed, block)


def pages_walked(walks: tuple, last, page_size: int, n_lanes: int) -> tuple:
    """``(read, by the kernel)``: table slots a decode step's programs read
    over a span's layers and all lanes, its live lanes at the positions
    ``last`` (a numpy array), and those of them the kernel's grid fetched.
    ``walks`` is ``((window, layers, block, cut, path), ...)``, a distinct
    attention call of the span's layers (server/span_cache.py
    ``LanePool.walks``). Each layer walks its table (the slots in its window's
    reach, if ``cut``) in blocks: the composed walk every lane of the pool's
    up to the block that holds the longest lane's last row, the kernel each
    live lane from the block of its first position in sight to its own last
    one (``composed_paged_attend``, whose arithmetic this is). For the
    batcher's ``attn_pages_gathered`` / ``attn_pages_kernel``, a step, on the
    host's serial part: a reduction or two over the lanes and integer
    arithmetic a walk."""
    read = by_kernel = 0
    longest = -1
    for window, layers, block, cut, path in walks:
        if path == "kernel" and not window:
            # a lane reads the blocks up to its last row's: ``last // (block * page_size) + 1`` of them
            walked = layers * block * (int((last // (block * page_size)).sum()) + last.size)
            by_kernel += walked
        elif path == "kernel":
            first = np.maximum(last - (window - 1), 0) // page_size  # a lane's first slot in sight
            walked = walk_pages(last // page_size + 1 - (first if cut else 0), block)  # as the walk is handed its table
            if not cut:
                walked = walked - first // block * block  # whole blocks before the window's reach
            walked = layers * int(walked.sum())
            by_kernel += walked
        else:
            if longest < 0:
                longest = int(last.max())
            needed = longest // page_size + 1
            if cut:  # each lane's slots count from its own window's first one
                needed = int(np.max(last // page_size - np.maximum(last - (window - 1), 0) // page_size)) + 1
            walked = layers * n_lanes * walk_pages(needed, block)
        read += walked
    return read, by_kernel


def _walk_decode_rows(
    q, k_pool, v_pool, tables, *, q_pos, kv_len, alibi_slopes, sliding_window, scale, logit_softcap,
):
    """One query row a lane over its table's pages, in blocks of slots with a
    running softmax: ``attend_reference`` over ``gather_pages`` without the
    dense view. q [n_lanes, 1, hq, d]; ``q_pos`` / ``kv_len`` [n_lanes] count
    from the table's first slot, and a lane of ``kv_len`` 0 (idle) attends to
    nothing and answers zeros. The walk ends with the block that holds the
    longest lane's last row: its trip count is data, the program is one.

    A block's pages are gathered as the pool stores them (a quantised pool
    dequantises to bf16, holes read zeros) and meet the dots in that dtype,
    products summed in float32; max, sum and output run in float32, and the
    weights are cast to V's dtype for their dot as the prefill kernel's are."""
    from petals_tpu.ops.paged_attention import gather_pages, pool_geometry

    n_lanes, width = tables.shape
    d = q.shape[-1]
    _, page_size, hkv, _ = pool_geometry(k_pool, d)
    group = q.shape[2] // hkv
    block = walk_block_pages(n_lanes, width, page_size, hkv, d, jnp.dtype(k_pool.dtype).itemsize)
    rows = block * page_size
    tables = jnp.pad(tables, ((0, 0), (0, -width % block)), constant_values=-1)  # whole blocks; the rest are holes
    qg = q.reshape(n_lanes, hkv, group, d)
    scale = d**-0.5 if scale is None else scale
    q_pos, kv_len = q_pos[:, None, None, None], kv_len[:, None, None, None]
    slopes = None if alibi_slopes is None else alibi_slopes.astype(jnp.float32).reshape(hkv, group, 1)

    def a_block(i, carry):
        m, l, acc = carry
        cols = jax.lax.dynamic_slice_in_dim(tables, i * block, block, axis=1)
        k = gather_pages(k_pool, cols, hkv)  # [n_lanes, rows, hkv, d]
        v = gather_pages(v_pool, cols, hkv)
        s = jnp.einsum("bkgd,bskd->bkgs", qg, k, preferred_element_type=jnp.float32) * scale
        if logit_softcap is not None:
            s = jnp.tanh(s / logit_softcap) * logit_softcap
        kv_pos = i * rows + jnp.arange(rows, dtype=jnp.int32)
        if slopes is not None:
            s = s + slopes * kv_pos.astype(jnp.float32)
        mask = (kv_pos < kv_len) & (kv_pos <= q_pos)
        if sliding_window is not None:
            mask = mask & (kv_pos > q_pos - sliding_window)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None]) * mask
        alpha = jnp.exp(m - m_new)
        pv = jnp.einsum("bkgs,bskd->bkgd", p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        return m_new, l * alpha + p.sum(axis=-1), acc * alpha[..., None] + pv

    heads = (n_lanes, hkv, group)
    init = (jnp.full(heads, NEG_INF, jnp.float32), jnp.zeros(heads, jnp.float32), jnp.zeros((*heads, d), jnp.float32))
    trips = jnp.minimum((jnp.max(kv_len) + rows - 1) // rows, tables.shape[1] // block)
    _, l, acc = jax.lax.fori_loop(0, trips, a_block, init)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(n_lanes, 1, hkv * group, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# the decode walk as one kernel: each lane's own pages read where they lie
# ---------------------------------------------------------------------------

# Bytes of keys (or values) ONE lane's block of the kernel's walk holds in a
# buffer: a grid step copies that many whole pages of all kv heads out of each
# pool and multiplies them in one pass, with the next live block's copies in
# flight under it (two buffers a pool: 2 MB of fast memory in all, beside the
# step's weight prefetches). A layer's call on the v5e, ms, at 1 | 2 | 4 | 8
# pages a block (benchmarks/ablate_paged_walk.py, PR 45, call 2; the composed
# walk and the bytes' floor beside them): 8 lanes of 1,150-2,300 positions over
# 32 kv heads (pages of 512 KB) 0.315 | 0.317 | 0.329 | 0.350 (0.807, 0.279);
# every lane at 2,559 0.448 | 0.447 | 0.450 | 0.450 (0.929, 0.410); one live
# lane of 2,300 and seven idle 0.065 | 0.059 | 0.054 | 0.063 (0.639, 0.046);
# 8 lanes of 90-370 over 16 kv heads (pages of 256 KB) 0.028 | 0.023 | 0.021 |
# 0.040 (0.080, 0.018). A step's fixed cost (~0.35 us: the scalars' reads, the
# accumulators' trip through scratch) is paid a block, live or skipped, and a
# lane's last block is copied and multiplied whole: half a megabyte is the best
# or within 0.005 ms of it at each. Folded rows (PR 53, one call of the same script; pages a block in brackets): 8 lanes
# of 1,150-2,300 over 2 kv heads of 256 (pages of 64 KB) 0.131 (1) | 0.051 (4) | 0.042 (8: half a megabyte) | 0.049 (16)
# | 0.061 (32) (0.171, 0.035), four of them live 0.018 at 8 and 0.017 at 16 (0.173, 0.017); over one kv head of 128
# (pages of 16 KB) 0.121 (1) | 0.021 (8) | 0.015 (16) | 0.013 (32: half a megabyte) (0.611 a page a trip, 0.009).
WALK_KERNEL_BLOCK_BYTES = 512 << 10
WALK_KERNEL_VMEM_SLACK_BYTES = 16 << 20  # a block's scores and weights, the accumulators, the kernel's own temporaries
# The lanes' tables ride into the kernel as prefetched scalars, ``n_lanes * slots`` int32 in scalar memory, of which the
# v5e has 1 MiB a program: half of it compiles (8 lanes of 16,384 slots, compile-only for the v5e, PR 45), all of it
# does not ("Ran out of memory in memory space smem ... Exceeded smem capacity by 2.6K"). A call with wider tables (64
# lanes of 128k positions in pages of 64 reach it) takes the composed walk, where it would fail to compile at start.
WALK_KERNEL_TABLE_BYTES = 512 << 10


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    # off the chip the walk's kernel runs in the Pallas interpreter; a compile-only test patches this (and ``_on_tpu``)
    return jax.default_backend() != "tpu"


def _sublanes(dtype) -> int:
    """Rows of the chip's tile of ``dtype``: 8 of 32 bits, 16 of 16."""
    return 32 // jnp.dtype(dtype).itemsize


def walk_kernel_block_pages(width: int, page_size: int, hkv: int, d: int, itemsize: int = 2) -> int:
    """Table slots of one lane a grid step of the kernel's walk takes, from
    the shapes alone: the largest power of two whose pages stay within
    ``WALK_KERNEL_BLOCK_BYTES`` a pool, at least one and at most the ``width``
    slots there are to walk."""
    a_page = page_size * hkv * d * itemsize
    block = 1
    while block * 2 * a_page <= WALK_KERNEL_BLOCK_BYTES:
        block *= 2
    return min(block, width)


def walk_kernel_unsupported(k_pool, q_shape, tables_shape, *, alibi: bool = False, softcap: bool = False, window=None) -> Optional[str]:
    """Why the decode walk's kernel cannot take this call, or None: a static
    predicate on the pool's stored form and the call's shape. The kernel copies
    whole pages out of the pool as it is stored and meets a block of them as
    ONE matrix of whole tiles, so it takes a plain pool in either form the
    storage rule gives it (ops/paged_attention.py ``stored_row``): rows of
    ``[hkv, d]``, ``d`` whole lanes and ``hkv`` whole sublane tiles of the
    dtype, met as ``[page_size * hkv, d]``; or folded rows of ``hkv * d`` (up
    to 4 kv heads where a server made the pool: Qwen3-Next's 2 x 256, Jamba's
    1 x 128, SmallThinker's 4 x 128 under 28 query heads), ``d`` whole lanes,
    met as ``[page_size, hkv * d]`` with a row's heads as column blocks. A
    page's rows are whole sublane tiles either way.
    Still refused, and left to the composed walk: a folded
    row of heads under 128 lanes (a head_dim of 64: two heads share a tile's
    lanes), a quantised pool (the gather dequantises its codes; the
    kernel has no scales), pages of another dtype, rows of ``[hkv, d]`` of
    half a tile of kv heads (8 of bfloat16: the compiled step would copy the
    pool whole in front of the kernel), ALiBi, a soft cap and a traced window
    (the kernel knows the walk's own masks and nothing else), several query
    rows a lane, and tables wider than the scalar memory they are prefetched
    into (as the walk is handed them, cut to a window's reach). ``k_pool`` is
    the pool or anything with its ``shape`` and ``dtype``."""
    from petals_tpu.ops.paged_attention import PagedPool

    if isinstance(k_pool, PagedPool):
        return "a quantised pool: its codes are dequantised by the gather"
    if jnp.dtype(k_pool.dtype) not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return f"pages of {jnp.dtype(k_pool.dtype).name}"
    page_size, d = k_pool.shape[1], q_shape[-1]
    tile = _sublanes(k_pool.dtype)
    if len(k_pool.shape) == 3:  # stored folded: a row's heads are column blocks of the block's one matrix
        width = k_pool.shape[2]
        hkv = width // d
        if d % LANES or width % d:
            # heads under 128 lanes share a tile's lanes, and the last step keeps a query head's own block of columns in
            # whole lanes. The one configuration that stores such a row (Falcon-40B) gains nothing by a step that takes
            # them apart: its layers are ONE loop over pools that fit the chip's fast memory, and compiled for the v5e
            # that loop stages both pools whole through it around the call, every layer (PERF.md section 7)
            return f"a folded row of {width} holds heads of {d}, no whole blocks of {LANES} lanes"
    else:
        _, _, hkv, width = k_pool.shape
        if d % LANES or width != d:
            return f"a head_dim of {width} is no multiple of {LANES}"
        if hkv % tile:
            # 8 kv heads of bfloat16 are half a tile: the pool then lives on the device in a layout of its own and the
            # compiled step COPIES it whole in front of the kernel, every layer (tests/test_kernels_lower_tpu.py)
            return f"{hkv} kv heads are no multiple of the {tile} sublanes of {jnp.dtype(k_pool.dtype).name}"
    if page_size % tile:
        return f"a page's {page_size} rows are no multiple of the {tile} sublanes of {jnp.dtype(k_pool.dtype).name}"
    if q_shape[1] != 1 or q_shape[2] % hkv:
        return f"{q_shape[1]} query rows a lane of {q_shape[2]} heads"
    if alibi or softcap:
        return "ALiBi or a soft cap on the scores"
    if window is not None and not isinstance(window, int):
        return "a traced window"
    if 4 * tables_shape[0] * tables_shape[1] > WALK_KERNEL_TABLE_BYTES:
        return f"tables of {tuple(tables_shape)} slots are over the {WALK_KERNEL_TABLE_BYTES >> 10} KiB of scalar memory the kernel prefetches them into"
    return None


def decode_walk_path(k_pool, q_shape, tables_shape, *, alibi: bool = False, softcap: bool = False, window=None) -> str:
    """``"kernel"`` on a TPU backend for a call the kernel takes
    (``walk_kernel_unsupported``: a plain bfloat16 or float32 pool of whole
    tiles, rows of ``[hkv, d]`` or a folded row of up to 4 kv heads of
    whole lanes, one query row a lane under the walk's own masks),
    ``"composed"`` everywhere else: off the chip, and for what the kernel still
    refuses, each for a reason of its own: a quantised pool (no scales in the
    kernel), 8 kv heads of bfloat16 (half a tile: the compiled step would copy
    the pool), a folded row of heads under 128 lanes (the last step keeps
    whole lanes), ALiBi, a soft cap, a traced window, tables over the scalar
    memory. What a decode row's walk in ``composed_paged_attend`` runs and what
    the batcher's counters count (server/span_cache.py ``LanePool.walks``) follow
    from this alone."""
    unsupported = walk_kernel_unsupported(k_pool, q_shape, tables_shape, alibi=alibi, softcap=softcap, window=window)
    return "kernel" if _on_tpu() and unsupported is None else "composed"


def _walk_kernel(tables_ref, starts_ref, ends_ref, q_ref, cols_ref, row_head_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, state, m_ref, l_ref, acc_ref, *,
                 pages: int, scale: float, dot_in_f32: bool):
    lane, block = pl.program_id(0), pl.program_id(1)
    n_lanes, page_size = ends_ref.shape[0], k_hbm.shape[1]
    slots = tables_ref.shape[0] // n_lanes
    rows = pages * page_size
    start, end = starts_ref[lane], ends_ref[lane]  # the lane's row sees the positions [start, end) of its table

    def start_copies(of_lane, of_block, slot):
        """Start the page copies of ``of_lane``'s ``of_block`` into buffer ``slot``: a loop (a step program holds the
        kernel once a run of layers, and copies written out are lowered one by one at every start)."""

        def a_page(i, _):
            page = tables_ref[of_lane * slots + of_block * pages + i]
            to = pl.ds(pl.multiple_of(i * page_size, page_size), page_size)
            pltpu.make_async_copy(k_hbm.at[page], k_buf.at[slot, to], sems.at[0, slot]).start()
            pltpu.make_async_copy(v_hbm.at[page], v_buf.at[slot, to], sems.at[1, slot]).start()
            return _

        jax.lax.fori_loop(0, pages, a_page, 0)

    def wait_for_copies(slot):
        """One wait a pool: a DMA semaphore counts bytes, so the whole buffer's answers for all its pages' copies."""
        pltpu.make_async_copy(k_buf.at[slot], k_buf.at[slot], sems.at[0, slot]).wait()
        pltpu.make_async_copy(v_buf.at[slot], v_buf.at[slot], sems.at[1, slot]).wait()

    @pl.when((lane == 0) & (block == 0))
    def _():
        state[0] = 0  # the buffer the next live block is (or will be) copied into
        state[1] = 0  # whether its copies were started by the block before it

    @pl.when(block == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # blocks past the lane's own end, or wholly before its window's reach, copy nothing and multiply nothing
    @pl.when((block * rows < end) & ((block + 1) * rows > start))
    def _():
        slot = state[0]

        @pl.when(state[1] == 0)
        def _():
            start_copies(lane, block, slot)

        # the next live block's copies fly under this block's dots: this lane's next one, or the next live lane's first
        after = n_lanes
        for other in reversed(range(n_lanes)):
            after = jnp.where((other > lane) & (ends_ref[other] > 0), other, after)
        last = (block + 1) * rows >= end
        next_lane = jnp.where(last, after, lane)
        next_block = jnp.where(last, starts_ref[jnp.minimum(after, n_lanes - 1)] // rows, block + 1)

        @pl.when(next_lane < n_lanes)
        def _():
            start_copies(next_lane, next_block, 1 - slot)

        state[0] = 1 - slot
        state[1] = (next_lane < n_lanes).astype(jnp.int32)
        wait_for_copies(slot)

        def dot(a, b, contract):
            if dot_in_f32:  # interpret mode: CPU XLA has no bf16 x bf16 -> f32 dot
                a, b = a.astype(jnp.float32), b.astype(jnp.float32)
            return jax.lax.dot_general(a, b, (contract, ((), ())), preferred_element_type=jnp.float32)

        # A page lies as [page_size, hkv, d]: the block is met as the rows of ONE matrix [rows * hkv, d] (whole tiles: no
        # relayout), every query head against every kv head's columns, and a column that is another kv head's is masked
        # like a position out of sight: its weight is exactly zero, so the same matrix of values takes the weights as
        # they are. The matrix unit has the room: at 32 kv heads the dots are hidden under the copies (PERF.md section 5).
        # A FOLDED page lies as [page_size, hkv * d] and the block is the matrix [rows, hkv * d] as it is: a column is a
        # position, the query rows come with zeros in the other kv heads' column blocks (the wrapper's), so a score sums
        # its own head's products alone, and the weights meet all heads' values, of which the last step keeps a row's own.
        col_pos = block * rows + cols_ref[0:1, :]  # [1, columns]: a column's position, and its kv head
        col_head = jnp.where((col_pos >= start) & (col_pos < end), cols_ref[1:2, :], -1)
        mask = col_head == row_head_ref[...]  # [hq, columns]: a query head's own kv head, in sight
        k = k_buf[slot].reshape(-1, k_buf.shape[-1])
        v = v_buf[slot].reshape(-1, v_buf.shape[-1])
        s = jnp.where(mask, dot(q_ref[...], k, ((1,), (1,))) * scale, NEG_INF)
        m = m_ref[:, :1]
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_ref[:, :1] * alpha + p.sum(axis=-1, keepdims=True), l_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha + dot(p.astype(v.dtype), v, ((1,), (0,)))

    @pl.when(block == pl.num_programs(1) - 1)
    def _():
        out = acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)
        hq, d = o_ref.shape
        if out.shape[1] != d:  # a folded row's heads side by side: a query head keeps its own kv head's block of columns
            group = hq // (out.shape[1] // d)
            column, row = (jax.lax.broadcasted_iota(jnp.int32, out.shape, axis) for axis in (1, 0))
            out = jnp.where(column // d == row // group, out, 0.0)
            out = functools.reduce(jnp.add, [out[:, at:at + d] for at in range(0, out.shape[1], d)])  # the others are zero now
        o_ref[...] = out.astype(o_ref.dtype)


# A jit of its own: a server warms ten step programs at every start, and the compile cache keeps executables, no traces:
# under its own jit the kernel's body is traced once a process and lowered once a program (ops/latent_attention.py, PR 43).
@functools.partial(tracked_jit, name="paged_decode_walk", static_argnames=("scale", "sliding_window", "pages", "interpret"))
def _walk_decode_rows_kernel(q, k_pool, v_pool, tables, q_pos, kv_len, *, scale: float, sliding_window: Optional[int], pages: int,
                             interpret: bool):
    """``_walk_decode_rows`` as ONE Pallas call: grid (lane, block of ``pages``
    table slots), both pools left in HBM as they are handed over (the span's,
    as the layer loop carries them, rows of ``[hkv, d]`` or folded) and the
    tables and each lane's reach prefetched as scalars. A live block's pages, all kv heads of them, are
    copied into one of two VMEM buffers a pool by the block before it, under
    that block's dots; ``m``, ``l`` and ``acc`` ride in scratch across a
    lane's blocks. Each live lane is read to its OWN last block. The
    arithmetic is the walk's: products of the pool's dtype summed in float32,
    max / sum / output in float32, the weights cast to V's dtype."""
    from petals_tpu.ops.paged_attention import pool_geometry

    n_lanes, _, hq, d = q.shape
    _, page_size, hkv, _ = pool_geometry(k_pool, d)
    row = k_pool.shape[2:]
    folded = len(row) == 1  # [n_pages, page_size, hkv * d]: a block's matrix is [rows, hkv * d], a column a position
    width = tables.shape[1]
    rows = pages * page_size
    # the positions a lane's row sees, [start, end): its own length, the causal mask and the window's reach in two numbers
    ends = jnp.minimum(kv_len, q_pos + 1)
    starts = jnp.zeros_like(ends) if sliding_window is None else jnp.maximum(q_pos - (sliding_window - 1), 0)
    ends = jnp.where(starts < ends, ends, 0)  # an idle lane, or one that sees nothing: no live block
    # a hole (past a lane's end in its last block) reads the page of the lane's first position in sight: its own, and
    # masked; a page nobody owns may hold anything, and a weight of zero times NaN is NaN
    own = jnp.take_along_axis(tables, jnp.clip(starts // page_size, 0, width - 1)[:, None], axis=1)
    tables = jnp.pad(tables, ((0, 0), (0, -width % pages)), constant_values=-1)
    tables = jnp.where(tables < 0, jnp.maximum(own, 0), tables)
    grid = (n_lanes, tables.shape[1] // pages)
    tables = tables.reshape(-1)
    apart = 1 if folded else hkv  # kv heads that are columns of their own in a block's matrix
    column = np.arange(rows * apart, dtype=np.int32)  # constants of the program: nothing to compute a layer
    cols = np.stack([column // apart, column % apart])  # a column's position in the block, and its kv head
    row_head = (np.arange(hq, dtype=np.int32) // (hq // apart))[:, None]  # a query head's kv head
    q_rows = q[:, 0].astype(jnp.promote_types(q.dtype, k_pool.dtype))
    if folded and hkv > 1:  # a query head's row across the folded row: its own kv head's columns, zeros in the others'
        its_own = (np.arange(hq) // (hq // hkv))[:, None] == np.arange(hkv)[None, :]
        q_rows = jnp.where(its_own[None, :, :, None], q_rows[:, :, None, :], 0).reshape(n_lanes, hq, hkv * d)
    itemsize = jnp.dtype(k_pool.dtype).itemsize
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, hq, q_rows.shape[-1]), lambda lane, block, *_: (lane, 0, 0)),
            pl.BlockSpec(cols.shape, lambda lane, block, *_: (0, 0)),
            pl.BlockSpec(row_head.shape, lambda lane, block, *_: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((None, hq, d), lambda lane, block, *_: (lane, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, rows, *row), k_pool.dtype),
            pltpu.VMEM((2, rows, *row), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((2,), jnp.int32),
            pltpu.VMEM((hq, LANES), jnp.float32),
            pltpu.VMEM((hq, LANES), jnp.float32),
            pltpu.VMEM((hq, row[-1]), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_walk_kernel, pages=pages, scale=scale, dot_in_f32=interpret),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_lanes, hq, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=4 * rows * hkv * d * itemsize + WALK_KERNEL_VMEM_SLACK_BYTES,
        ),
        interpret=interpret,
        name="paged_decode_walk",
    )(tables, starts, ends, q_rows, cols, row_head, k_pool, v_pool)
    return out[:, None]


def composed_paged_attend(
    q, k_pool, v_pool, tables, *, q_offset, kv_length, alibi_slopes=None, sliding_window=None,
    scale=None, causal: bool = True, logit_softcap=None, path: Optional[str] = None,
):
    """Every decode row's attention over a paged pool, and a chunk's where
    the prefill kernel does not take it (``paged_attend_dispatch``). A decode
    row (per-lane positions, one query row a lane, causal) walks its lane's
    pages in blocks of slots, as they are stored: what it reads follows the
    lanes' lengths, not the table's width, and agrees with the dense program
    to float32 rounding, not to the bit. On a TPU, over a plain pool of whole
    tiles (``walk_kernel_unsupported``), the walk is ONE kernel that reads each live lane's own pages where
    they lie, to that lane's own end (``_walk_decode_rows_kernel``); everywhere
    else, and as the kernel's reference, a ``fori_loop`` over blocks of every
    lane up to the longest live lane's last one (``_walk_decode_rows``).
    ``decode_walk_path`` says which from what the call shows; ``path`` names
    one for a test (off the chip the kernel is interpreted). An idle lane (at
    the sentinel ``max_length``) counts as empty.
    A prompt's chunk, a verify's rows and a non-causal call gather the lanes'
    pages into a dense view and run ``attend_reference`` over it. Under a
    static window either sees only the pages a lane's rows can reach: its
    table row is cut to the ``window_pages`` slots from the first row's window
    on, and positions are counted from that slot's first one (the masks are
    differences of positions, so the shift changes nothing; ALiBi is a
    difference too, but its path is left as it was)."""
    from petals_tpu.ops.attention import attend_reference
    from petals_tpu.ops.paged_attention import gather_pages, pool_geometry

    n_lanes, max_pages = tables.shape
    _, page_size, hkv, _ = pool_geometry(k_pool, q.shape[-1])
    pos = jnp.asarray(q_offset, jnp.int32)
    walk = pos.ndim == 1 and q.shape[1] == 1 and causal and kv_length is not None
    live = pos < max_pages * page_size  # the idle sentinel is max_length
    reach = window_pages(sliding_window, q.shape[1], page_size, max_pages)
    if reach < max_pages and causal and alibi_slopes is None and kv_length is not None:
        first = jnp.maximum(pos - (sliding_window - 1), 0) // page_size  # first slot in reach: scalar or [n_lanes]
        slots = jnp.broadcast_to(first, (n_lanes,))[:, None] + jnp.arange(reach, dtype=jnp.int32)[None, :]
        taken = jnp.take_along_axis(tables, jnp.clip(slots, 0, max_pages - 1), axis=1)
        tables = jnp.where(slots < max_pages, taken, -1)
        q_offset = pos - first * page_size
        kv_length = jnp.asarray(kv_length, jnp.int32) - first * page_size
    if walk:
        kv_len = jnp.where(live, jnp.broadcast_to(jnp.asarray(kv_length, jnp.int32), (n_lanes,)), 0)
        q_pos = jnp.asarray(q_offset, jnp.int32)
        if path is None:
            path = decode_walk_path(
                k_pool, q.shape, tables.shape, alibi=alibi_slopes is not None, softcap=logit_softcap is not None, window=sliding_window
            )
        if path == "kernel":
            pages = walk_kernel_block_pages(tables.shape[1], page_size, hkv, q.shape[-1], jnp.dtype(k_pool.dtype).itemsize)
            # the scope holds the copies too; the kernel is ``paged_decode_walk`` in a trace
            with jax.named_scope("ptu.attn.paged_decode"):
                return _walk_decode_rows_kernel(
                    q, k_pool, v_pool, tables, q_pos, kv_len, scale=float(q.shape[-1] ** -0.5 if scale is None else scale),
                    sliding_window=sliding_window, pages=pages, interpret=_interpret(),
                )
        return _walk_decode_rows(
            q, k_pool, v_pool, tables, q_pos=q_pos, kv_len=kv_len,
            alibi_slopes=alibi_slopes, sliding_window=sliding_window, scale=scale, logit_softcap=logit_softcap,
        )
    k = gather_pages(k_pool, tables, hkv)
    v = gather_pages(v_pool, tables, hkv)
    return attend_reference(
        q, k, v, q_offset=q_offset, kv_length=kv_length,
        alibi_slopes=alibi_slopes, sliding_window=sliding_window,
        scale=scale, causal=causal, logit_softcap=logit_softcap,
    )


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m
