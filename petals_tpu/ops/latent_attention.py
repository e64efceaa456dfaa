"""Latent attention (DeepSeek-V2's multi-head latent attention, as
``deepseek_v3`` configs size it): a position caches ONE row for all heads,
``[c | k_pe]``: the normed latent ``c`` of ``kv_lora_rank`` values that every
head's key and value are linear in, and one rotated key of
``qk_rope_head_dim`` that every head shares.

    k[s, h] = [c_s W_UK[h] | k_pe_s]        v[s, h] = c_s W_UV[h]
    score[h, t, s] = (q_nope[t, h] . k_nope[s, h] + q_pe[t, h] . k_pe_s) * scale      (s <= t)

On the lane pool the row lives in pages under the lane's block tables, in the
place of keys and values (server/backend.py ``latent_row``): ``c`` a position
a row of its pool, ``k_pe`` (64 wide, under the chip's 128 lanes) several
positions to a row of its own, as an index row is stored
(ops/sparse_attention.py ``index_pool_row``). Neither pool holds a key or a
value of any head.

Three forms, one a call shape:

- ``latent_decode_attend``: one query row a lane, ABSORBED. The query is taken
  into the latent space once (``q' = q_nope W_UK[h]``, ``absorb_queries``),
  the lanes' rows are met where they lie in blocks of table slots up to the
  longest live lane's last page with the heads as the rows of one matrix
  product a lane (``score = q' . c + q_pe . k_pe``, ``u = sum p c``), a
  running softmax, and the result leaves the latent space once (``o = u
  W_UV[h]``, ``expand_outputs``). No key or value is ever made.
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

import jax
import jax.numpy as jnp

from petals_tpu.ops.paged_attention import PagedKV, scatter_chunk_rows, scatter_token_rows
from petals_tpu.ops.sparse_attention import _block_rows, _padded_blocks, index_pool_row, scatter_index_rows

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)

# Positions a lane a block of the decode walk meets at once. A trip's fixed cost (the pages' fetch set up, two
# dots, the softmax's update) is paid once a block whatever its width: a layer's call at 8 lanes x 24,576 positions
# on the v5e took 1.01, 0.80, 0.70-0.72, 0.64-0.66 and 0.50-0.62 ms at 256, 512, 1024, 2048 and 4096, 0.68 at
# 8192, and 1.6-1.9 from 16,384 on, where a block no longer fits the chip's fast memory (its bytes need 0.28;
# benchmarks/ablate_latent_attention.py, PR 42, three calls). 4,096 rows of 576 bf16 values are 4.7 MB a
# lane, 38 MB over eight lanes, and the block's float32 scores [lanes, 32, 4096] 4 MB; the walk reads every lane
# in whole blocks up to the longest live lane's last one, so a wider block also reads more past the lanes' ends.
DECODE_BLOCK_ROWS = 4096
# Positions a block of a chunk's walk expands and attends over at once. A chunk of 2,048 rows holds its float32
# scores against a block in 32 heads x 2,048 x block x 4 B, and the walk is bound by moving them, not by the MXU:
# at a context of 24,576 a layer's call took 19.1, 30.1 and 46.7 ms at 128, 256 and 512 (33, 67 and 134 MB of
# scores; the pairs' flops need 5.0). 128 positions' keys and values of 32 heads are 2 MB.
CHUNK_BLOCK_ROWS = 128


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


def decode_reads(n_lanes: int, max_pages: int, page_size: int, longest: int) -> int:
    """Latent rows one layer's ``latent_decode_attend`` reads over ``n_lanes``
    lanes whose longest live one sees ``longest`` positions: every lane of the
    pool in whole blocks up to the longest lane's (the walk's arithmetic, for
    the batcher's counters)."""
    rows = _block_rows(max_pages, page_size, DECODE_BLOCK_ROWS)
    return n_lanes * min(-(-longest // rows) * rows, -(-max_pages * page_size // rows) * rows)


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


def latent_decode_attend(q_abs, q_pe, c_kv: PagedKV, pe_kv: PagedKV, positions, *, scale: float):
    """One query row a lane at ``positions`` [n] (its own row already in the
    pages; the idle sentinel ``max_length`` attends to nothing and answers
    zeros), absorbed: q_abs [n, 1, H, C], q_pe [n, 1, H, dr] -> ``u`` [n, 1,
    H, C], the softmax's weights over the lane's latent rows (``expand_outputs``
    makes the heads' outputs of it)."""
    n_lanes, max_pages = c_kv.tables.shape
    page_size, latent = c_kv.pool.shape[1], c_kv.pool.shape[2]
    fold = page_size // pe_kv.pool.shape[1]
    heads = q_abs.shape[2]
    tables, block, rows = _padded_blocks(c_kv.tables, page_size, DECODE_BLOCK_ROWS)
    pos = jnp.asarray(positions, jnp.int32)
    kv_len = jnp.where(pos < max_pages * page_size, pos + 1, 0)[:, None, None]
    q_abs, q_pe = q_abs[:, 0], q_pe[:, 0]

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

    # the scope holds the pages' fetch too: a `while` carries no name in a trace, what its body runs does
    with jax.named_scope("ptu.attn.latent_decode"):
        init = (jnp.full((n_lanes, heads), NEG_INF, jnp.float32), jnp.zeros((n_lanes, heads), jnp.float32),
                jnp.zeros((n_lanes, heads, latent), jnp.float32))
        trips = jnp.minimum((jnp.max(kv_len) + rows - 1) // rows, tables.shape[1] // block)
        _, l, acc = jax.lax.fori_loop(0, trips, a_block, init)
        return (acc / jnp.maximum(l, 1e-30)[..., None])[:, None].astype(q_abs.dtype)


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
