"""Learned sparse attention: an indexer scores every cached position for a
query row, the ``topk`` of largest score are kept, and softmax attention runs
over those alone (the "lightning indexer" of DeepSeek-V3.2-Exp's
``inference/model.py``, as Keye-VL-2.0's ``sa_config`` sizes it).

    score[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])          (s <= t)
    S_t         = the min(topk, t + 1) positions of largest score,
                  ties broken by the lower position
    out_t       = softmax over s in S_t of (q_t . k_s) * scale, times v_s

``kI`` is one row of ``index_dim`` a position. On the lane pool it lives in
pages under the lane's block tables, a third ``PagedKV`` beside keys and
values (server/backend.py ``_scan_paged_span``), and lives and dies with the
page. The selection is exact: no approximate top-k, none by page or block.

Three forms, one a call shape:

- ``sparse_decode_attend``: one query row a lane. The lanes' index keys are
  scored where they lie, in blocks of table slots up to the longest live
  lane's last page; one sort of (score, position) pairs takes the set and
  the chosen positions' pool rows are read off the lane's table by a compare
  (``select_rows``); only those rows of keys and of values leave their pages
  (two row gathers, in bounds by construction and taken so), and both dots
  read them as the gathers left them. Between the pool and the dots nothing
  runs that is not the scoring, the sort, a fetch or a dot: no whole-table
  view of keys and values, no pool copied, no fetched row selected against
  a fill value or relaid.
- ``sparse_chunk_attend``: a prompt chunk's rows over one lane's table. Each
  row's set becomes a mask (``select_mask``), and attention walks the lane's
  pages in blocks under it with a running softmax; a chunk whose rows all
  see at most ``topk`` positions skips the scoring (the set is everything).
- ``sparse_attend_dense``: a whole sequence with no cache (the stateless
  forward and backward passes): the full ``[seq, seq]`` score matrix, the
  same mask, masked attention.

Scores are products of the stored dtype summed in float32; max, sum and
output of the softmax run in float32, the weights cast to V's dtype for
their dot, as the decode walk's are (ops/paged_flash_attention.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from petals_tpu.ops.paged_attention import LANES, PagedKV, gather_pages, pool_geometry, unfold_rows

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)

# Positions a block of a walk scores or attends over at once, a lane: the
# index keys of 2,048 positions are 256 KB a lane, and a chunk of 512 rows
# holds its float32 logits against 1,024 positions in 64 MB at 32 heads.
INDEX_BLOCK_ROWS = 2048
CHUNK_BLOCK_ROWS = 1024
CHUNK_ROWS = 512  # a chunk's rows that select and attend at once (``sparse_chunk_attend``)


def index_fold(page_size: int, width: int) -> int:
    """How many positions' index rows share one row of the index pool: a row
    of ``width`` under the chip's 128 lanes is stored ``128 // width``
    positions to a row, ``[n_pages, page_size // fold, fold * width]``, the
    positions of a page in order (row ``r`` holds positions ``r * fold ..``).
    Stored a position a row, a pool of width 64 lives on the device with the
    page index minor and every step relays it whole on its way in and out
    (the compile-only guard met it: tests/test_kernels_lower_tpu.py), as a
    page pool of head_dim 64 did until it was folded over its heads
    (``paged_attention.stored_row``); an index row has one head, so it folds
    over positions."""
    fold = LANES // width if width < LANES and LANES % width == 0 else 1
    return fold if page_size % fold == 0 else 1


def index_pool_row(page_size: int, width: int) -> tuple:
    """``(rows a page, row width)`` of the index pool (``index_fold``)."""
    fold = index_fold(page_size, width)
    return page_size // fold, width * fold


def scatter_index_rows(i_kv: PagedKV, new, position, n_valid, page_size: int) -> PagedKV:
    """Write the fresh index rows ``new`` [batch, seq, width] into their
    pages, in place: per-lane ``position`` [n_lanes] with one row a lane (the
    idle sentinel drops), or a scalar ``position`` with a single lane's chunk
    of which ``n_valid`` rows are real. A position's row lands in its page's
    row ``slot // fold`` at column ``(slot % fold) * width``."""
    pool, tables = i_kv.pool, i_kv.tables
    batch, seq, width = new.shape
    rows_a_page, fold = pool.shape[1], page_size // pool.shape[1]
    max_length = tables.shape[1] * page_size
    pos = jnp.asarray(position, jnp.int32)
    if pos.ndim == 1:
        if seq != 1:
            raise NotImplementedError("index rows: per-lane positions with more than one row a lane are not written")
        lane, where, live = jnp.arange(batch, dtype=jnp.int32), pos, pos < max_length
    else:
        offs = jnp.arange(seq, dtype=jnp.int32)
        lane, where = jnp.zeros(seq, jnp.int32), pos + offs
        live = (offs < jnp.asarray(seq if n_valid is None else n_valid, jnp.int32)) & (where < max_length)
    where = jnp.where(live, where, 0)
    page = tables[lane, where // page_size]
    slot = where % page_size
    n_rows = pool.shape[0] * rows_a_page
    row = jnp.where(live & (page >= 0), page * rows_a_page + slot // fold, n_rows)  # one past the end: dropped
    flat = pool.reshape(n_rows, pool.shape[2])
    flat = jax.lax.scatter(
        flat, jnp.stack([row, (slot % fold) * width], axis=-1), new.reshape(batch * seq, width).astype(pool.dtype),
        jax.lax.ScatterDimensionNumbers(update_window_dims=(1,), inserted_window_dims=(0,), scatter_dims_to_operand_dims=(0, 1)),
        unique_indices=True, mode="drop",
    )
    return i_kv._replace(pool=flat.reshape(pool.shape))


def index_scores(q_idx, w_idx, k_idx, fold: int = 1):
    """``score[.., t, s]``: q_idx [b, t, H, dI], w_idx [b, t, H] (float32,
    already scaled), k_idx [b, s, dI] -> float32 [b, t, s], unmasked. With
    ``fold`` > 1 the keys come as the index pool stores them, ``fold``
    positions to a row ([b, s // fold, fold * dI]), and are met as they lie:
    the query is laid block-diagonally over the row's positions."""
    k_idx, w_idx = k_idx.astype(q_idx.dtype), w_idx.astype(jnp.float32)
    if fold == 1:
        dots = jnp.einsum("bthd,bsd->bths", q_idx, k_idx, preferred_element_type=jnp.float32)
        return jnp.einsum("bths,bth->bts", jax.nn.relu(dots), w_idx)
    b, t, heads, d = q_idx.shape
    q_rows = (q_idx[:, :, :, None, None, :] * jnp.eye(fold, dtype=q_idx.dtype)[:, :, None]).reshape(b, t, heads, fold, fold * d)
    dots = jnp.einsum("bthfc,brc->bthrf", q_rows, k_idx, preferred_element_type=jnp.float32)
    return jnp.einsum("bthrf,bth->btrf", jax.nn.relu(dots), w_idx).reshape(b, t, -1)


def _ordered_keys(scores):
    """float32 -> uint32 whose unsigned order is the floats' (``-inf`` lowest;
    ``-0.0`` counted as ``0.0``)."""
    bits = jax.lax.bitcast_convert_type(scores + 0.0, jnp.int32)
    keys = bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))  # signed order
    return jax.lax.bitcast_convert_type(keys, jnp.uint32) ^ jnp.uint32(0x80000000)


def kth_largest_key(keys, k: int):
    """The ``k``-th largest of ``keys`` uint32 [..., L], kept as [..., 1]:
    exact, by bisection on the bits from the top, 32 passes that each count
    the keys at or above a candidate. A sort of the row would do (``lax.top_k``
    is one on the TPU), but a prompt chunk's rows are thousands of rows of
    tens of thousands of scores, and a pass over them is a read."""

    def a_bit(i, prefix):
        cand = prefix | (jnp.uint32(0x80000000) >> i.astype(jnp.uint32))
        enough = (keys >= cand).sum(-1, keepdims=True, dtype=jnp.int32) >= k
        return jnp.where(enough, cand, prefix)

    return jax.lax.fori_loop(0, 32, a_bit, jnp.zeros((*keys.shape[:-1], 1), jnp.uint32))


def select_mask(scores, valid, topk: int):
    """The selection as a mask: scores [..., L] float32, ``valid`` bool of the
    same shape (the positions a row may see) -> bool [..., L], True at the
    ``min(topk, valid's count)`` valid positions of largest score, ties broken
    by the lower position. Exact: everything above the k-th largest score is
    in, and of the scores equal to it as many as still fit, from the lowest
    position up (a count along the row, run only where some row has more
    equals than room)."""
    with jax.named_scope("ptu.attn.select"):
        if scores.shape[-1] <= topk:
            return valid
        keys = jnp.where(valid, _ordered_keys(scores), jnp.uint32(0))  # what a row may not see: below -inf
        kth = kth_largest_key(keys, topk)
        at_least = (keys >= kth) & valid  # a row that sees under topk positions: kth is 0, every valid one
        crowded = jnp.any(at_least.sum(-1, dtype=jnp.int32) > topk)

        def by_position():
            above = keys > kth
            ties = at_least & ~above
            room = topk - above.sum(-1, keepdims=True, dtype=jnp.int32)
            return above | (ties & (jnp.cumsum(ties, axis=-1, dtype=jnp.int32) <= room))

        return jax.lax.cond(crowded, by_position, lambda: at_least)


def _grouped_attend(q, k, v, mask, scale):
    """Softmax attention of q [b, t, hq, d] over k, v [b, s, hkv, d] under
    ``mask`` [b, t, s], whole: float32 logits, weights in V's dtype."""
    batch, q_len, hq, d = q.shape
    hkv = k.shape[2]
    qg = q.reshape(batch, q_len, hkv, hq // hkv, d)
    s = jnp.einsum("btkgd,bskd->bkgts", qg, k.astype(q.dtype), preferred_element_type=jnp.float32) * scale
    m = mask[:, None, None]
    s = jnp.where(m, s, NEG_INF)
    p = jnp.exp(s - s.max(axis=-1, keepdims=True)) * m
    p = p / jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bkgts,bskd->btkgd", p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    return out.reshape(batch, q_len, hq, d).astype(q.dtype)


def sparse_attend_dense(q, k, v, q_idx, w_idx, k_idx, *, topk: int, scale=None):
    """A whole sequence from position 0 with no cache: q [b, s, hq, d], k, v
    [b, s, hkv, d], q_idx [b, s, H, dI], w_idx [b, s, H], k_idx [b, s, dI].
    The selection carries no gradient (a choice of positions has none); the
    attention over the chosen positions does."""
    seq = q.shape[1]
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    pos = jnp.arange(seq, dtype=jnp.int32)
    causal = jnp.broadcast_to(pos[None, :] <= pos[:, None], (q.shape[0], seq, seq))
    with jax.named_scope("ptu.attn.index_score"):
        scores = jax.lax.stop_gradient(index_scores(q_idx, w_idx, k_idx))
    mask = select_mask(scores, causal, topk)
    with jax.named_scope("ptu.attn.sparse_attend"):
        return _grouped_attend(q, k, v, mask, scale)


# ------------------------------------------------------------------ the lane pool: decode rows


def _block_pages(max_pages: int, page_size: int, block_rows: int) -> int:
    """Table slots a block of a walk covers: ``block_rows`` positions' worth, at least one, at most the table."""
    return min(max(block_rows // page_size, 1), max_pages)


def _block_rows(max_pages: int, page_size: int, block_rows: int) -> int:
    return _block_pages(max_pages, page_size, block_rows) * page_size


def decode_reads(n_lanes: int, max_pages: int, page_size: int, topk: int, longest: int) -> tuple:
    """(index rows scored, rows of keys and values fetched) by one layer's
    ``sparse_decode_attend`` over ``n_lanes`` lanes whose longest live one
    sees ``longest`` positions: the arithmetic of the programs below, for the
    batcher's counters. Every lane of the pool is scored in whole blocks up
    to the longest lane's, and every lane fetches ``topk`` rows."""
    rows = _block_rows(max_pages, page_size, INDEX_BLOCK_ROWS)
    scored = min(-(-longest // rows) * rows, -(-max_pages * page_size // rows) * rows)
    return n_lanes * scored, n_lanes * min(topk, max_pages * page_size)


def chunk_reads(max_pages: int, page_size: int, topk: int, first: int, take: int, bucket: int) -> tuple:
    """As ``decode_reads`` for one layer's ``sparse_chunk_attend`` of a chunk
    of ``take`` rows from position ``first``, padded to ``bucket``: (index
    rows scored, query row x index row pairs, rows of keys and values
    fetched). The chunk goes through in runs of ``CHUNK_ROWS``; a run scores,
    in whole blocks, the positions up to its last row's (none if that is
    within ``topk``) and walks the lane's pages that far under its mask."""
    i_rows = _block_rows(max_pages, page_size, INDEX_BLOCK_ROWS)
    c_rows = _block_rows(max_pages, page_size, CHUNK_BLOCK_ROWS)
    run_rows = CHUNK_ROWS if bucket % CHUNK_ROWS == 0 else bucket
    scored = pairs = read = 0
    for start in range(first, first + bucket, run_rows):
        kv_len = min(first + take, start + run_rows)
        run_scored = -(-kv_len // i_rows) * i_rows if kv_len > topk else 0
        scored, pairs, read = scored + run_scored, pairs + run_rows * run_scored, read + -(-kv_len // c_rows) * c_rows
    return scored, pairs, read


def _padded_blocks(tables, page_size: int, block_rows: int):
    """``tables`` padded with holes to whole blocks of ``block_rows``
    positions: (tables, slots a block, positions a block)."""
    width = tables.shape[1]
    block = _block_pages(width, page_size, block_rows)
    return jnp.pad(tables, ((0, 0), (0, -width % block)), constant_values=-1), block, block * page_size


def _walk_index_scores(q_idx, w_idx, i_kv: PagedKV, kv_len, page_size: int):
    """The lanes' index keys scored where they lie: q_idx [n, t, H, dI],
    w_idx [n, t, H] over the pages of ``i_kv`` ([n, max_pages] tables), in
    blocks of slots up to the longest lane's ``kv_len`` [n]; float32 [n, t,
    max_length], unmasked where it was walked and ``-inf`` past the walk."""
    n_lanes, max_pages = i_kv.tables.shape
    fold = page_size // i_kv.pool.shape[1]
    tables, block, rows = _padded_blocks(i_kv.tables, page_size, INDEX_BLOCK_ROWS)
    length = tables.shape[1] * page_size

    def a_block(i, scores):
        cols = jax.lax.dynamic_slice_in_dim(tables, i * block, block, axis=1)
        pages = jnp.take(i_kv.pool, cols, axis=0, mode="clip")  # a hole reads page 0: nobody sees past kv_len
        k_idx = pages.reshape(n_lanes, rows // fold, pages.shape[-1])
        return jax.lax.dynamic_update_slice_in_dim(scores, index_scores(q_idx, w_idx, k_idx, fold), i * rows, axis=2)

    # the scope holds the pages' fetch too: a `while` carries no name in a trace, what its body runs does
    with jax.named_scope("ptu.attn.index_score"):
        trips = jnp.minimum((jnp.max(kv_len) + rows - 1) // rows, tables.shape[1] // block)
        scores = jnp.full((n_lanes, q_idx.shape[1], length), -jnp.inf, jnp.float32)
        return jax.lax.fori_loop(0, trips, a_block, scores)[:, :, : max_pages * page_size]


def _take_rows(pool, flat_idx, hkv: int):
    """Token rows out of a plain page pool by flat ``page * page_size + slot``
    index [n, k] -> [n, k, hkv, d], read where they lie (the pool viewed as
    rows is a bitcast, as ``_flat_scatter``'s view is). An index below 0 (a
    hole) reads row 0, which its caller masks: ``clip`` is XLA's own gather,
    where the default mode checks every index and selects every fetched row
    against a fill value, and that select left the rows in a layout the
    attention's dots had them copied out of (32 MB a layer on the v5e); as
    the gather leaves them the dots read them through a bitcast."""
    rows = jnp.take(pool.reshape(pool.shape[0] * pool.shape[1], *pool.shape[2:]), flat_idx, axis=0, mode="clip")
    return unfold_rows(rows, hkv) if rows.ndim == 3 else rows


def select_rows(scores, kv_len, tables, page_size: int, topk: int):
    """A decode row's set as the pool rows to fetch: scores [n, max_length]
    float32 of which a lane sees the first ``kv_len`` [n], ``tables`` [n,
    max_pages] -> (rows int32 [n, k], taken bool [n, k]), ``k`` the smaller
    of ``topk`` and the table's length; a row that is not taken may be below
    0. One sort of (score, position) pairs, largest score first and the
    lower position among equals, the scores ordered as ``select_mask`` orders
    them: both are keys, so no two pairs are equal and the order is the same
    whatever the sort does with equals. A chosen position's page is then
    read off the lane's table by comparing its slot with every slot of the
    table, [n, max_pages, k] compares and no gather (looked up a scalar at a
    time, 16,384 of them took 0.132 ms on the v5e; compared, 0.007). A lane
    that sees fewer than ``k`` takes them all and nothing it does not see."""
    with jax.named_scope("ptu.attn.select"):
        n_lanes, length = scores.shape
        position = jnp.broadcast_to(jnp.arange(length, dtype=jnp.int32), (n_lanes, length))
        keys = jnp.where(position < kv_len[:, None], _ordered_keys(scores), jnp.uint32(0))  # what a lane does not see: below -inf
        _, chosen = jax.lax.sort((~keys, position), dimension=1, is_stable=False, num_keys=2)
        chosen = chosen[:, : min(topk, length)]
        slots = jnp.arange(tables.shape[1], dtype=jnp.int32)[None, :, None]
        page = jnp.where(chosen[:, None, :] // page_size == slots, tables[:, :, None], 0).sum(axis=1)
        taken = (position[:, : chosen.shape[1]] < kv_len[:, None]) & (page >= 0)
        return page * page_size + chosen % page_size, taken


def sparse_decode_attend(q, q_idx, w_idx, k_kv: PagedKV, v_kv: PagedKV, i_kv: PagedKV, positions, *, topk: int, scale=None):
    """One query row a lane at ``positions`` [n] (its own row already in the
    pages; the idle sentinel ``max_length`` attends to nothing and answers
    zeros): q [n, 1, hq, d], q_idx [n, 1, H, dI], w_idx [n, 1, H]."""
    max_pages = k_kv.tables.shape[1]
    d = q.shape[-1]
    _, page_size, hkv, _ = pool_geometry(k_kv.pool, d)
    max_length = max_pages * page_size
    scale = d**-0.5 if scale is None else scale
    pos = jnp.asarray(positions, jnp.int32)
    kv_len = jnp.where(pos < max_length, pos + 1, 0)
    scores = _walk_index_scores(q_idx, w_idx, i_kv, kv_len, page_size)[:, 0]  # [n, max_length]
    rows, taken = select_rows(scores, kv_len, k_kv.tables, page_size, topk)
    with jax.named_scope("ptu.attn.sparse_attend"):
        k = _take_rows(k_kv.pool, rows, hkv)  # [n, topk, hkv, d]: the chosen positions alone leave their pages
        v = _take_rows(v_kv.pool, rows, hkv)
        return _grouped_attend(q, k, v, taken[:, None, :], scale)


# ------------------------------------------------------------------ the lane pool: a prompt's chunk


def sparse_chunk_attend(q, q_idx, w_idx, k_kv: PagedKV, v_kv: PagedKV, i_kv: PagedKV, position, n_valid, *, topk: int, scale=None):
    """A prompt chunk's rows over ONE lane's table (scalar ``position``, the
    chunk's rows already in the pages, ``n_valid`` of them real): q [1, B,
    hq, d], q_idx [1, B, H, dI], w_idx [1, B, H]. Each row keeps its own set
    over the cached positions and the chunk's own up to itself; padded rows
    answer garbage nobody reads. A chunk of more than ``CHUNK_ROWS`` rows
    goes through in runs of that many, one after the other: a run's scores
    against a whole table are what the selection's 32 passes read, and at
    512 rows of 32,768 (67 MB) they stay in the chip's fast memory, where at
    2,048 they cross HBM every pass (measured: 0.85-2.1 ms against 20.9)."""
    _, B, hq, d = q.shape
    _, page_size, hkv, _ = pool_geometry(k_kv.pool, d)
    max_length = k_kv.tables.shape[1] * page_size
    scale = d**-0.5 if scale is None else scale
    pos = jnp.asarray(position, jnp.int32)
    chunk_end = pos + jnp.asarray(B if n_valid is None else n_valid, jnp.int32)
    kv_pos = jnp.arange(max_length, dtype=jnp.int32)
    tables, block, rows = _padded_blocks(k_kv.tables, page_size, CHUNK_BLOCK_ROWS)
    group = hq // hkv
    run_rows = CHUNK_ROWS if B % CHUNK_ROWS == 0 else B

    def a_run(run):
        first, qg, q_idx, w_idx = run  # qg [R, hkv, group, d], q_idx [R, H, dI], w_idx [R, H]
        q_pos = first + jnp.arange(run_rows, dtype=jnp.int32)
        kv_len = jnp.minimum(chunk_end, first + run_rows)  # what the run's last row sees, at most
        causal = (kv_pos[None, :] <= q_pos[:, None]) & (kv_pos[None, :] < kv_len)  # [R, max_length]

        def chosen():
            scores = _walk_index_scores(q_idx[None], w_idx[None], i_kv, kv_len[None], page_size)[0]
            return select_mask(scores, causal, topk)

        # a run that ends within topk positions: every row's set is everything it sees, bit for bit what
        # the selection would give, and the scoring is skipped (the batcher counts such rows as dense)
        mask = jax.lax.cond(kv_len > topk, chosen, lambda: causal) if max_length > topk else causal
        mask = jnp.pad(mask, ((0, 0), (0, tables.shape[1] * page_size - max_length)))

        def a_block(i, carry):
            m, l, acc = carry
            cols = jax.lax.dynamic_slice_in_dim(tables, i * block, block, axis=1)
            k = gather_pages(k_kv.pool, cols, hkv)[0]  # [rows, hkv, d]
            v = gather_pages(v_kv.pool, cols, hkv)[0]
            s = jnp.einsum("tkgd,skd->kgts", qg, k.astype(qg.dtype), preferred_element_type=jnp.float32) * scale
            keep = jax.lax.dynamic_slice_in_dim(mask, i * rows, rows, axis=1)[None, None]
            s = jnp.where(keep, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None]) * keep
            alpha = jnp.exp(m - m_new)
            pv = jnp.einsum("kgts,skd->kgtd", p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            return m_new, l * alpha + p.sum(axis=-1), acc * alpha[..., None] + pv

        with jax.named_scope("ptu.attn.sparse_attend"):
            heads = (hkv, group, run_rows)
            init = (jnp.full(heads, NEG_INF, jnp.float32), jnp.zeros(heads, jnp.float32), jnp.zeros((*heads, d), jnp.float32))
            trips = jnp.minimum((kv_len + rows - 1) // rows, tables.shape[1] // block)
            _, l, acc = jax.lax.fori_loop(0, trips, a_block, init)
            out = acc / jnp.maximum(l, 1e-30)[..., None]  # [hkv, group, R, d]
            return out.transpose(2, 0, 1, 3).reshape(run_rows, hq, d).astype(q.dtype)

    cut = lambda t: t[0].reshape(B // run_rows, run_rows, *t.shape[2:])
    firsts = pos + jnp.arange(0, B, run_rows, dtype=jnp.int32)
    out = jax.lax.map(a_run, (firsts, cut(q.reshape(1, B, hkv, group, d)), cut(q_idx), cut(w_idx)))
    return out.reshape(1, B, hq, d)
