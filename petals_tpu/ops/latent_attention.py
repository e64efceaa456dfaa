"""Latent attention (DeepSeek-V2's multi-head latent attention, as
``deepseek_v3`` configs size it): a position caches ONE row for all heads,
``[c | k_pe]``: the normed latent ``c`` of ``kv_lora_rank`` values that every
head's key and value are linear in, and one rotated key of
``qk_rope_head_dim`` that every head shares.

    k[s, h] = [c_s W_UK[h] | k_pe_s]        v[s, h] = c_s W_UV[h]
    score[h, t, s] = (q_nope[t, h] . k_nope[s, h] + q_pe[t, h] . k_pe_s) * scale      (s <= t)

On the lane pool the row lives in pages under the lane's block tables, in the
place of keys and values (server/span_cache.py ``latent_row``): ``c`` a position
a row of its pool, ``k_pe`` (64 wide, under the chip's 128 lanes) several
positions to a row of its own, as an index row is stored
(ops/sparse_attention.py ``index_pool_row``). Neither pool holds a key or a
value of any head.

Three forms, one a call shape:

- ``latent_decode_attend``: one query row a lane, ABSORBED. The query is taken
  into the latent space once (``q' = q_nope W_UK[h]``, ``absorb_queries``),
  the lanes' rows are met where they lie with the heads as the rows of one
  matrix product a lane (``score = q' . c + q_pe . k_pe``, ``u = sum p c``), a
  running softmax, and the result leaves the latent space once (``o = u
  W_UV[h]``, ``expand_outputs``). No key or value is ever made. On a TPU the
  walk is ONE Pallas kernel a layer (``_decode_kernel``: each lane's own pages
  copied out of the two pools in blocks, the next block's copies in flight
  under this block's dots); everywhere else, and as the kernel's reference, a
  ``fori_loop`` in plain ``jax.numpy`` over blocks of every lane up to the
  longest live lane's last page (``decode_path`` says which).
- ``latent_chunk_attend``: a prompt chunk's rows over one lane's table,
  EXPANDED: a block of positions' ``c`` becomes keys and values of every
  head inside the walk (``c W_UK``, ``c W_UV``: a third of the absorbed
  form's flops a (row, position) pair once a chunk has more rows than a head
  has latent values to amortise over), a running softmax, the scores never
  whole.
- ``latent_attend_dense``: a whole sequence with no cache (the stateless
  forward and backward passes), expanded, the ``[seq, seq]`` scores whole.

Scores are products of the stored dtype summed in float32; max, sum and
output of the softmax run in float32, the weights cast to the values' dtype
for their dot, as the other walks' are (ops/paged_flash_attention.py).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from petals_tpu.ops.paged_attention import PagedKV, scatter_chunk_rows, scatter_token_rows
from petals_tpu.ops.sparse_attention import _block_rows, _padded_blocks, index_pool_row, scatter_index_rows

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)

# Positions a lane a block of the COMPOSED decode walk meets at once (off the chip, and the kernel's reference;
# ``DECODE_KERNEL_PAGES`` is the kernel's). A trip's fixed cost (the pages' fetch set up, two dots, the softmax's
# update) is paid once a block whatever its width: a layer's call at 8 lanes x 24,576 positions on the v5e took
# 1.01, 0.80, 0.70-0.72, 0.64-0.66 and 0.50-0.62 ms at 256, 512, 1024, 2048 and 4096, 0.68 at 8192, and 1.6-1.9
# from 16,384 on, where a block no longer fits the chip's fast memory (its bytes need 0.28;
# benchmarks/ablate_latent_attention.py, PR 42, three calls). 4,096 rows of 576 bf16 values are 4.7 MB a
# lane, 38 MB over eight lanes, and the block's float32 scores [lanes, 32, 4096] 4 MB; the walk reads every lane
# in whole blocks up to the longest live lane's last one, so a wider block also reads more past the lanes' ends.
DECODE_BLOCK_ROWS = 4096
# Positions a block of a chunk's walk expands and attends over at once. A chunk of 2,048 rows holds its float32
# scores against a block in 32 heads x 2,048 x block x 4 B, and the walk is bound by moving them, not by the MXU:
# at a context of 24,576 a layer's call took 19.1, 30.1 and 46.7 ms at 128, 256 and 512 (33, 67 and 134 MB of
# scores; the pairs' flops need 5.0). 128 positions' keys and values of 32 heads are 2 MB.
CHUNK_BLOCK_ROWS = 128
LANES, SUBLANES = 128, 16  # the chip's lanes, and the sublane tile of bfloat16 rows
# Table slots of ONE lane a grid step of the decode kernel copies (a page of latents is 64 KB, of rotated keys 8 KB)
# and multiplies in one pass. A layer's whole call (absorb, walk, expand) on the v5e at 8 lanes of 16,384 | 24,576 |
# 30,720 positions and at eight ragged lanes of 16,400-30,720 (mean 24.5k), ms, where the rows' bytes need 0.18 |
# 0.28 | 0.35 | 0.27 and the composed walk took 0.42 | 0.63 | 0.82 | 0.84 (benchmarks/ablate_latent_attention.py, PR 43,
# call 6, the kernel as it stands): 8 pages 0.30 | 0.43 | 0.53 | 0.41; 16 0.24 | 0.36 | 0.45 | 0.35; 24 0.23 | 0.33 |
# 0.41 | 0.33; **32 0.22 | 0.33 | 0.40 | 0.31**; 48 0.23 | 0.31 | 0.39 | 0.31; 64 0.20 | 0.30 | 0.42 | 0.33 (call 3, the
# copies written out and each waited for, read the same to 0.02 at every width). A step's fixed cost (64 copies
# issued, the scalars' reads, the accumulators' trip through scratch) is paid once a block, and a lane's last block is
# read and multiplied whole, so past 32-48 the lanes' ends cost more than the steps saved. The losers inside a block:
# the same pass cut into runs of 256 | 512 | 1,024 positions under a loop took 0.56 | 0.40 | 0.34 at 32 pages and ragged
# lanes (calls 1-2; 0.31 in one pass: the dots want their 2,048 columns at once); the copies as a loop with a wait a
# copy 0.35 (call 4: a block's 2.4 MB arrive in 3 us, so 64 waits are seen), with one wait a pool 0.32 (call 5; 0.31
# with the loop's body written out 4, 8 or 32 times: nothing). 2,048 rows of 512 + 64 bf16 values are 2.25 MB a buffer,
# 4.5 MB for the two.
DECODE_KERNEL_PAGES = 32
KERNEL_VMEM_SLACK_BYTES = 16 << 20  # a block's halves, scores and weights, the accumulators, the kernel's own temporaries


def latent_pool_rows(page_size: int, latent: int, rope: int) -> tuple:
    """``((rows a page, width), (rows a page, width))`` of the two pools a
    latent row is stored in: ``c`` a position a row, ``k_pe`` as an index row
    of its width is (several positions to a row of 128 where it is narrower)."""
    return (page_size, latent), index_pool_row(page_size, rope)


def scatter_latent_rows(c_kv: PagedKV, pe_kv: PagedKV, c_new, pe_new, position, n_valid) -> tuple:
    """Write the fresh rows ``c_new`` [batch, seq, latent] and ``pe_new``
    [batch, seq, rope] into their pages, in place: per-lane ``position``
    [n_lanes] with one row a lane (the idle sentinel drops), or a scalar
    ``position`` with a single lane's chunk of which ``n_valid`` rows are
    real. Returns the two ``PagedKV``."""
    pos = jnp.asarray(position, jnp.int32)
    page_size, seq = c_kv.pool.shape[1], c_new.shape[1]
    if pos.ndim == 1:
        if seq != 1:
            raise NotImplementedError("latent rows: per-lane positions with more than one row a lane are not written")
        c_pool = scatter_token_rows(c_kv.pool, c_new[:, 0], c_kv.tables, pos)
    else:
        n = jnp.asarray(seq if n_valid is None else n_valid, jnp.int32)
        offs = jnp.arange(seq, dtype=jnp.int32)
        where = jnp.where(offs < n, pos + offs, jnp.int32(c_kv.max_length))  # padded rows: one past the end, dropped
        c_pool = scatter_chunk_rows(c_kv.pool, c_new[0], c_kv.tables[0], where)
        if page_size // pe_kv.pool.shape[1] > 1:
            return c_kv._replace(pool=c_pool), _scatter_folded_chunk(pe_kv, pe_new[0], pos, n, page_size)
    return c_kv._replace(pool=c_pool), scatter_index_rows(pe_kv, pe_new, position, n_valid, page_size)


def _scatter_folded_chunk(pe_kv: PagedKV, new, pos, n, page_size: int) -> PagedKV:
    """A chunk's rotated keys ``new`` [seq, width] (``n`` of them real, from
    position ``pos``) into ONE lane's pages of a pool that stores ``fold``
    positions to a row, a whole pool row at a time: the rows the chunk touches
    are fetched, the chunk's positions laid over them (a row's other
    positions, before the chunk or past its real rows, keep what they held)
    and written back, one gather and one scatter of about ``seq // fold`` rows.
    ``scatter_index_rows`` writes a position at a time at a column offset,
    which the chip runs as a loop of as many trips as the chunk has rows: 3 ms
    a layer of a mixed step at 2,048 rows (a quarter of its device time; PR 42,
    the first traced run)."""
    pool, table = pe_kv.pool, pe_kv.tables[0]
    seq, width = new.shape
    rows_a_page, fold = pool.shape[1], page_size // pool.shape[1]
    # the rows the chunk can touch, counted in positions' order (row g holds positions g * fold ..): a chunk that starts
    # inside a row ends inside one more
    touched = pos // fold + jnp.arange((seq + fold - 2) // fold + 1, dtype=jnp.int32)
    at = touched[:, None] * fold + jnp.arange(fold, dtype=jnp.int32)[None, :] - pos  # [rows, fold]: the chunk's row that lands there
    fresh = (at >= 0) & (at < n)
    first = touched * fold  # a row's first position: it says the page
    slot = jnp.clip(first // page_size, 0, table.shape[0] - 1)
    page = jnp.where(first // page_size < table.shape[0], table[slot], -1)
    n_rows = pool.shape[0] * rows_a_page
    flat = pool.reshape(n_rows, fold * width)  # the pool's own rows: a view that splits them would relay the pool whole
    row = jnp.where((page >= 0) & fresh.any(axis=1), page * rows_a_page + (first % page_size) // fold, n_rows)  # one past the end: dropped
    held = jnp.take(flat, row, axis=0, mode="clip").reshape(-1, fold, width)
    laid = jnp.where(fresh[..., None], jnp.take(new, jnp.clip(at, 0, seq - 1), axis=0).astype(pool.dtype), held)
    return pe_kv._replace(pool=flat.at[row].set(laid.reshape(-1, fold * width), mode="drop", unique_indices=True).reshape(pool.shape))


def absorb_queries(q_nope, w_uk):
    """``q' = q_nope W_UK[h]``: q_nope [.., H, dn], w_uk [H, dn, C] -> [.., H, C]."""
    with jax.named_scope("ptu.attn.latent_absorb"):
        return jnp.einsum("...hd,hdc->...hc", q_nope, w_uk.astype(q_nope.dtype))


def expand_outputs(u, w_uv):
    """``o = u W_UV[h]``: u [.., H, C], w_uv [H, C, dv] -> [.., H, dv]."""
    with jax.named_scope("ptu.attn.latent_absorb"):
        return jnp.einsum("...hc,hcv->...hv", u, w_uv.astype(u.dtype))


def decode_reads(n_lanes: int, max_pages: int, page_size: int, contexts, *, kernel: bool) -> int:
    """Latent rows one layer's ``latent_decode_attend`` reads for live lanes
    that see ``contexts`` positions each, of a pool of ``n_lanes`` (the
    walks' arithmetic, for the batcher's counters). The kernel: each live
    lane's own pages in whole blocks of ``DECODE_KERNEL_PAGES`` up to its own
    end. The composed walk: every lane of the pool in whole blocks of
    ``DECODE_BLOCK_ROWS`` up to the longest live lane's."""
    if not len(contexts):
        return 0
    rows = _block_rows(max_pages, page_size, DECODE_KERNEL_PAGES * page_size if kernel else DECODE_BLOCK_ROWS)
    table = -(-max_pages * page_size // rows) * rows
    if kernel:
        return sum(min(-(-int(ctx) // rows) * rows, table) for ctx in contexts)
    return n_lanes * min(-(-int(max(contexts)) // rows) * rows, table)


def chunk_reads(max_pages: int, page_size: int, first: int, take: int) -> int:
    """Positions one layer's ``latent_chunk_attend`` expands (each read once)
    for a chunk of ``take`` rows from position ``first``: whole blocks up to
    the chunk's last row."""
    rows = _block_rows(max_pages, page_size, CHUNK_BLOCK_ROWS)
    return min(-(-(first + take) // rows) * rows, -(-max_pages * page_size // rows) * rows)


def _pe_dots(q_pe, pe_rows, fold: int):
    """``q_pe . k_pe`` with the keys as their pool stores them: q_pe [n, H,
    dr], pe_rows [n, R // fold, fold * dr] -> float32 [n, H, R]. With ``fold``
    > 1 the query is laid block-diagonally over a row's positions, so the
    rows are met as they lie (ops/sparse_attention.py ``index_scores``)."""
    pe_rows = pe_rows.astype(q_pe.dtype)
    if fold == 1:
        return jnp.einsum("nhd,nrd->nhr", q_pe, pe_rows, preferred_element_type=jnp.float32)
    n, heads, dr = q_pe.shape
    q_rows = (q_pe[:, :, None, None, :] * jnp.eye(fold, dtype=q_pe.dtype)[:, :, None]).reshape(n, heads, fold, fold * dr)
    dots = jnp.einsum("nhfc,nrc->nhrf", q_rows, pe_rows, preferred_element_type=jnp.float32)
    return dots.reshape(n, heads, -1)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def decode_kernel_unsupported(c_row: tuple, pe_row: tuple, dtype) -> Optional[str]:
    """Why the decode kernel cannot take pools whose pages are ``c_row`` and
    ``pe_row`` (rows a page, width) of ``dtype`` as they are stored, or None:
    Mosaic copies whole pages out of both pools, so a row is whole multiples
    of the chip's 128 lanes and a page's rows of the sublane tile, and the
    kernel tells a pool row's two positions apart by its halves."""
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return f"rows of {jnp.dtype(dtype).name}: the halves are told apart in bfloat16 or float32"
    if pe_row[1] != LANES or 2 * pe_row[0] != c_row[0]:
        return f"the rotated keys' pool row is not two positions to a row of {LANES}: {pe_row} a page of {c_row[0]} positions"
    if c_row[1] % LANES:
        return f"a latent width of {c_row[1]} is no multiple of {LANES}"
    if pe_row[0] % SUBLANES:
        return f"a page's {pe_row[0]} rows of rotated keys are no multiple of {SUBLANES}"
    return None


def decode_path(c_row: tuple, pe_row: tuple, dtype) -> str:
    """``"kernel"`` on a TPU backend for pools the kernel takes as they are
    stored, ``"composed"`` everywhere else: what ``latent_decode_attend`` runs
    and ``decode_reads`` counts follows from this alone."""
    return "kernel" if _on_tpu() and decode_kernel_unsupported(c_row, pe_row, dtype) is None else "composed"


def latent_decode_attend(q_abs, q_pe, c_kv: PagedKV, pe_kv: PagedKV, positions, *, scale: float, path: Optional[str] = None):
    """One query row a lane at ``positions`` [n] (its own row already in the
    pages; the idle sentinel ``max_length`` attends to nothing and answers
    zeros), absorbed: q_abs [n, 1, H, C], q_pe [n, 1, H, dr] -> ``u`` [n, 1,
    H, C], the softmax's weights over the lane's latent rows (``expand_outputs``
    makes the heads' outputs of it). ``path`` is ``decode_path``'s answer
    unless a test names one (off the chip the kernel is interpreted)."""
    max_pages, page_size = c_kv.tables.shape[1], c_kv.pool.shape[1]
    pos = jnp.asarray(positions, jnp.int32)
    kv_len = jnp.where(pos < max_pages * page_size, pos + 1, 0)
    if path is None:
        path = decode_path(c_kv.pool.shape[1:], pe_kv.pool.shape[1:], c_kv.pool.dtype)
    # the scope holds the pages' fetch too: a `while` carries no name in a trace, what its body runs does
    with jax.named_scope("ptu.attn.latent_decode"):
        if path == "kernel":
            u = _decode_kernel_walk(q_abs[:, 0], q_pe[:, 0], c_kv, pe_kv, kv_len, scale=scale, pages=min(DECODE_KERNEL_PAGES, max_pages),
                                    interpret=not _on_tpu())
        else:
            u = _decode_composed_walk(q_abs[:, 0], q_pe[:, 0], c_kv, pe_kv, kv_len, scale)
        return u[:, None].astype(q_abs.dtype)


def _decode_composed_walk(q_abs, q_pe, c_kv: PagedKV, pe_kv: PagedKV, kv_len, scale: float):
    """The walk as plain ``jax.numpy``, the kernel's reference and what runs
    off the chip: a ``fori_loop`` over blocks of ``DECODE_BLOCK_ROWS``
    positions of EVERY lane up to the longest live lane's last one, a block's
    pages copied out of the pools and then multiplied. q_abs [n, H, C], q_pe
    [n, H, dr], kv_len [n] -> float32 [n, H, C]."""
    n_lanes, heads, latent = q_abs.shape
    page_size = c_kv.pool.shape[1]
    fold = page_size // pe_kv.pool.shape[1]
    tables, block, rows = _padded_blocks(c_kv.tables, page_size, DECODE_BLOCK_ROWS)
    kv_len = kv_len[:, None, None]

    def a_block(i, carry):
        m, l, acc = carry
        cols = jax.lax.dynamic_slice_in_dim(tables, i * block, block, axis=1)
        # a hole reads page 0: nobody sees past kv_len
        c = jnp.take(c_kv.pool, cols, axis=0, mode="clip").reshape(n_lanes, rows, latent)
        pe = jnp.take(pe_kv.pool, cols, axis=0, mode="clip").reshape(n_lanes, rows // fold, pe_kv.pool.shape[2])
        s = jnp.einsum("nhc,nrc->nhr", q_abs, c.astype(q_abs.dtype), preferred_element_type=jnp.float32)
        s = (s + _pe_dots(q_pe, pe, fold)) * scale
        mask = (i * rows + jnp.arange(rows, dtype=jnp.int32)) < kv_len
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None]) * mask
        alpha = jnp.exp(m - m_new)
        pc = jnp.einsum("nhr,nrc->nhc", p.astype(c.dtype), c, preferred_element_type=jnp.float32)
        return m_new, l * alpha + p.sum(axis=-1), acc * alpha[..., None] + pc

    init = (jnp.full((n_lanes, heads), NEG_INF, jnp.float32), jnp.zeros((n_lanes, heads), jnp.float32),
            jnp.zeros((n_lanes, heads, latent), jnp.float32))
    trips = jnp.minimum((jnp.max(kv_len) + rows - 1) // rows, tables.shape[1] // block)
    _, l, acc = jax.lax.fori_loop(0, trips, a_block, init)
    return acc / jnp.maximum(l, 1e-30)[..., None]


def _halves(c_ref, slot, count: int):
    """The ``count`` latent rows of the block in buffer ``slot``, the even
    positions and then the odd ones: the order a block's rotated keys stand in
    when their pool rows' two halves are met one after the other. Rows of 32
    bits are read with a stride of two. Two bfloat16 rows share a sublane's
    32-bit words (rows 2k and 2k + 1, the even one in the low half): the words
    are split by a shift and a mask, each half a bfloat16 already."""
    if c_ref.dtype.itemsize == 4:
        return jnp.concatenate([c_ref[slot, pl.ds(odd, count // 2, stride=2), :] for odd in (0, 1)], axis=0)
    words = pltpu.bitcast(c_ref[slot], jnp.uint32)  # [count // 2, C]
    even = jax.lax.bitcast_convert_type(words << 16, jnp.float32)
    odd = jax.lax.bitcast_convert_type(words & jnp.uint32(0xFFFF0000), jnp.float32)
    return jnp.concatenate([even, odd], axis=0).astype(c_ref.dtype)


def _live(block, rows: int, kv_len):
    """Whether a lane that sees ``kv_len`` positions sees any of its ``block`` of ``rows``: the kernel's grid skips the others."""
    return block * rows < kv_len


def _decode_kernel(tables_ref, len_ref, q_ref, q_pe_ref, c_hbm, pe_hbm, o_ref, c_buf, pe_buf, sems, state, m_ref, l_ref, acc_ref, *,
                   pages: int, scale: float, dot_in_f32: bool):
    lane, block = pl.program_id(0), pl.program_id(1)
    n_lanes, page_size, heads = len_ref.shape[0], c_hbm.shape[1], q_ref.shape[0]
    slots = tables_ref.shape[0] // n_lanes
    rows = pages * page_size
    kv_len = len_ref[lane]

    def start_copies(of_lane, of_block, slot):
        """Start the page copies of ``of_lane``'s ``of_block`` into buffer ``slot``. A loop, not ``pages`` copies written out:
        every step program holds the kernel twice, and lowering 192 copies one by one was 2 s of each program's start."""

        def a_page(i, _):
            page = jnp.maximum(tables_ref[of_lane * slots + of_block * pages + i], 0)  # a hole reads page 0: nobody sees past kv_len
            c_to = c_buf.at[slot, pl.ds(pl.multiple_of(i * page_size, page_size), page_size)]
            pe_to = pe_buf.at[slot, pl.ds(pl.multiple_of(i * (page_size // 2), page_size // 2), page_size // 2)]
            pltpu.make_async_copy(c_hbm.at[page], c_to, sems.at[0, slot]).start()
            pltpu.make_async_copy(pe_hbm.at[page], pe_to, sems.at[1, slot]).start()
            return _

        jax.lax.fori_loop(0, pages, a_page, 0)

    def wait_for_copies(slot):
        """Wait for a block's copies into buffer ``slot``: a DMA semaphore counts bytes, so one wait a pool for the whole
        buffer's answers for all its pages' copies."""
        pltpu.make_async_copy(c_buf.at[slot], c_buf.at[slot], sems.at[0, slot]).wait()
        pltpu.make_async_copy(pe_buf.at[slot], pe_buf.at[slot], sems.at[1, slot]).wait()

    @pl.when((lane == 0) & (block == 0))
    def _():
        state[0] = 0  # the buffer the next live block is (or will be) copied into
        state[1] = 0  # whether its copies were started by the block before it

    @pl.when(block == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(_live(block, rows, kv_len))  # a lane's blocks past its own end copy nothing and multiply nothing
    def _():
        slot = state[0]

        @pl.when(state[1] == 0)
        def _():
            start_copies(lane, block, slot)

        # the next live block's copies fly under this block's dots: this lane's next one, or the next live lane's first
        after = n_lanes
        for other in reversed(range(n_lanes)):
            after = jnp.where((other > lane) & (len_ref[other] > 0), other, after)
        ends = (block + 1) * rows >= kv_len
        next_lane, next_block = jnp.where(ends, after, lane), jnp.where(ends, 0, block + 1)

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

        # the block in one pass: its even positions, then its odd ones (a column's position in the block says the mask)
        half = rows // 2
        column = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
        mask = block * rows + jnp.where(column < half, 2 * column, 2 * (column - half) + 1) < kv_len
        c = _halves(c_buf, slot, rows)  # [rows, C]
        both = dot(q_pe_ref[...], pe_buf[slot], ((1,), (1,)))  # [2H, half]: the heads against a pool row's first half, then its second
        s = (dot(q_ref[...], c, ((1,), (1,))) + jnp.concatenate([both[:heads], both[heads:]], axis=1)) * scale
        s = jnp.where(mask, s, NEG_INF)
        m = m_ref[:, :1]
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_ref[:, :1] * alpha + p.sum(axis=-1, keepdims=True), l_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha + dot(p.astype(c.dtype), c, ((1,), (0,)))

    @pl.when(block == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)).astype(o_ref.dtype)


# A jit of its own: every step program holds the walk twice (the dense run's layer, the sparse run's loop) and a server
# warms ten of them at every start, where the compile cache keeps executables and no traces: under its own jit the
# kernel's body is traced once a process and lowered once a program (`setup_s` 88-93 s -> 64-69, the parent's 61-67).
@functools.partial(jax.jit, static_argnames=("scale", "pages", "interpret"))
def _decode_kernel_walk(q_abs, q_pe, c_kv: PagedKV, pe_kv: PagedKV, kv_len, *, scale: float, pages: int, interpret: bool):
    """The walk as ONE Pallas call: grid (lane, block of ``pages`` table
    slots), both pools left in HBM as the layer loop carries them and the
    tables and lengths prefetched as scalars. A live block's pages are copied
    into one of two VMEM buffers by the block before it, under that block's
    dots; ``m``, ``l`` and ``acc`` ride in scratch across a lane's blocks. The
    rotated keys are never interleaved: a block's positions are scored as the
    even ones, then the odd ones (``_halves``), against the first and the
    second half of their pool rows, with the heads' queries laid out twice
    (``[[q_pe, 0], [0, q_pe]]``: one dot for both halves). q_abs [n, H, C],
    q_pe [n, H, dr], kv_len [n] -> float32 [n, H, C]."""
    n_lanes, heads, latent = q_abs.shape
    max_pages, page_size = c_kv.tables.shape[1], c_kv.pool.shape[1]
    if pe_kv.pool.shape[1] * 2 != page_size or pe_kv.pool.shape[2] != 2 * q_pe.shape[2]:
        raise NotImplementedError(f"the decode kernel meets rotated keys two positions to a pool row, not pages of {pe_kv.pool.shape[1:]}")
    rows = pages * page_size
    tables = jnp.pad(c_kv.tables, ((0, 0), (0, -max_pages % pages)), constant_values=-1)
    nothing = jnp.zeros_like(q_pe)
    q_both = jnp.concatenate([jnp.concatenate([q_pe, nothing], axis=2), jnp.concatenate([nothing, q_pe], axis=2)], axis=1)  # [n, 2H, 2dr]
    itemsize = c_kv.pool.dtype.itemsize
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_lanes, tables.shape[1] // pages),
        in_specs=[
            pl.BlockSpec((None, heads, latent), lambda lane, block, *_: (lane, 0, 0)),
            pl.BlockSpec((None, 2 * heads, q_both.shape[2]), lambda lane, block, *_: (lane, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((None, heads, latent), lambda lane, block, *_: (lane, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, rows, latent), c_kv.pool.dtype),
            pltpu.VMEM((2, rows // 2, pe_kv.pool.shape[2]), pe_kv.pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((2,), jnp.int32),
            pltpu.VMEM((heads, LANES), jnp.float32),
            pltpu.VMEM((heads, LANES), jnp.float32),
            pltpu.VMEM((heads, latent), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_decode_kernel, pages=pages, scale=scale, dot_in_f32=interpret),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_lanes, heads, latent), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=2 * rows * (latent + pe_kv.pool.shape[2] // 2) * itemsize + KERNEL_VMEM_SLACK_BYTES,
        ),
        interpret=interpret,
        name="latent_decode_walk",
    )(tables.reshape(-1), kv_len, q_abs, q_both, c_kv.pool, pe_kv.pool)


def latent_chunk_attend(q_nope, q_pe, w_uk, w_uv, c_kv: PagedKV, pe_kv: PagedKV, position, n_valid, *, scale: float):
    """A prompt chunk's rows over ONE lane's table (scalar ``position``, the
    chunk's rows already in the pages, ``n_valid`` of them real), expanded:
    q_nope [1, B, H, dn], q_pe [1, B, H, dr], w_uk [H, dn, C], w_uv [H, C, dv]
    -> [1, B, H, dv]. Padded rows answer garbage nobody reads."""
    _, B, heads, _ = q_nope.shape
    page_size, latent = c_kv.pool.shape[1], c_kv.pool.shape[2]
    rope, dv = q_pe.shape[-1], w_uv.shape[-1]
    tables, block, rows = _padded_blocks(c_kv.tables, page_size, CHUNK_BLOCK_ROWS)
    pos = jnp.asarray(position, jnp.int32)
    chunk_end = pos + jnp.asarray(B if n_valid is None else n_valid, jnp.int32)
    q_pos = (pos + jnp.arange(B, dtype=jnp.int32))[:, None]
    q_nope, q_pe = q_nope[0], q_pe[0]
    w_uk, w_uv = w_uk.astype(q_nope.dtype), w_uv.astype(q_nope.dtype)

    def a_block(i, carry):
        m, l, acc = carry
        cols = jax.lax.dynamic_slice_in_dim(tables, i * block, block, axis=1)[0]
        c = jnp.take(c_kv.pool, cols, axis=0, mode="clip").reshape(rows, latent).astype(q_nope.dtype)
        pe = jnp.take(pe_kv.pool, cols, axis=0, mode="clip").reshape(rows, rope).astype(q_pe.dtype)  # unfolded: a block's worth
        with jax.named_scope("ptu.attn.latent_expand"):
            k_nope = jnp.einsum("rc,hdc->rhd", c, w_uk)
            v = jnp.einsum("rc,hcv->rhv", c, w_uv)
        s = jnp.einsum("thd,rhd->htr", q_nope, k_nope, preferred_element_type=jnp.float32)
        s = (s + jnp.einsum("thd,rd->htr", q_pe, pe, preferred_element_type=jnp.float32)) * scale
        kv_pos = i * rows + jnp.arange(rows, dtype=jnp.int32)[None, :]
        mask = ((kv_pos <= q_pos) & (kv_pos < chunk_end))[None]
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None]) * mask
        alpha = jnp.exp(m - m_new)
        pv = jnp.einsum("htr,rhv->htv", p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        return m_new, l * alpha + p.sum(axis=-1), acc * alpha[..., None] + pv

    with jax.named_scope("ptu.attn.latent_chunk"):
        init = (jnp.full((heads, B), NEG_INF, jnp.float32), jnp.zeros((heads, B), jnp.float32), jnp.zeros((heads, B, dv), jnp.float32))
        trips = jnp.minimum((chunk_end + rows - 1) // rows, tables.shape[1] // block)
        _, l, acc = jax.lax.fori_loop(0, trips, a_block, init)
        out = acc / jnp.maximum(l, 1e-30)[..., None]  # [H, B, dv]
        return out.transpose(1, 0, 2)[None].astype(q_nope.dtype)


def latent_attend_dense(q_nope, q_pe, c, k_pe, w_uk, w_uv, *, scale: float):
    """A whole sequence from position 0 with no cache, expanded: q_nope [b, s,
    H, dn], q_pe [b, s, H, dr], c [b, s, C], k_pe [b, s, dr] -> [b, s, H, dv].
    Padding, where there is any, follows the real rows, which never see it."""
    seq = q_nope.shape[1]
    with jax.named_scope("ptu.attn.latent_expand"):
        k_nope = jnp.einsum("bsc,hdc->bshd", c, w_uk.astype(c.dtype))
        v = jnp.einsum("bsc,hcv->bshv", c, w_uv.astype(c.dtype))
    s = jnp.einsum("bthd,bshd->bhts", q_nope, k_nope, preferred_element_type=jnp.float32)
    s = (s + jnp.einsum("bthd,bsd->bhts", q_pe, k_pe.astype(q_pe.dtype), preferred_element_type=jnp.float32)) * scale
    at = jnp.arange(seq, dtype=jnp.int32)
    s = jnp.where((at[None, :] <= at[:, None])[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhts,bshv->bthv", p.astype(v.dtype), v, preferred_element_type=jnp.float32).astype(q_nope.dtype)
