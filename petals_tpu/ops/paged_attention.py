"""Paged KV-cache plumbing: page pools, block tables, and the ragged
paged-attention decode path ("Ragged Paged Attention", arXiv:2604.15464 —
the TPU-native rendition of vLLM's PagedAttention layout).

Layout contract (per span block):

- page pool      [n_pages, page_size, kv_heads, head_dim] x2 (k, v) — ONE
  shared slab in HBM, budgeted through MemoryCache like the dense lane pool.
  STORED in the layout its step computes in (``stored_row``, the one rule):
  a row under the chip's 128 lanes (head_dim 64; int8 codes of it; nf4a's
  packed bytes of a head_dim up to 128), or of up to 4 kv heads where the
  span's decode rows walk its pages, keeps the kv heads folded into it,
  [n_pages, page_size, kv_heads * d_store] — the same bytes in the same
  order. Handed over as rows of [kv_heads, 64] such a pool lives on the
  device with the PAGE index minor, and every step program relaid both
  pools whole on the way in and on the way out (Falcon-40B: four copies of
  42 MB a step, PERF.md section 6, PR 38). Who makes a pool asks the rule
  (server/span_cache.py ``pool_descriptors``); every consumer here
  reads the form off the leaf it is handed: the scatters fold the new rows
  (``_flat_scatter``, ``scatter_lane_pages``), ``gather_pages`` unfolds the
  pages it took (never the pool), ``pool_geometry`` answers (n_pages,
  page_size, hkv, d_store) for the kernels and the dispatch. A pool of
  8 kv heads of 128 and more keeps its shape and its programs. Off the
  device everything keeps rows of [kv_heads, d]: swap entries, snapshots
  and imports, the wire (server/backend.py ``pool_to_wire`` /
  ``wire_to_pool``: a reshape of the host's copy).
- block table    [n_lanes, max_pages] int32 — page index per (lane, slot);
  ``-1`` marks an unallocated slot. ``max_pages * page_size == max_length``
  (the batcher rounds max_length up to a page multiple).
- ragged lengths per lane ride the existing position vector: attention masks
  with ``kv_length = position + 1``, so whatever a read pulls from
  unallocated/stale pages carries an exact 0.0 weight and contributes
  nothing. Pool content is always finite (zero-init, only ever written with
  computed values), so paged decode agrees with the dense path to float32
  rounding: the same products, summed block by block.

One attention path: the step programs no longer materialize a dense view in
front of attention. The (pool, tables) pair rides through the model family's
block code as a ``PagedKV`` pytree standing in for the dense KV buffer;
``models/common.py update_kv_cache`` scatters the new rows straight into the
pool and ``ops/attention.py attend`` dispatches in
ops/paged_flash_attention.py (``paged_attend_dispatch``). A decode row (one
query row a lane) walks its lane's table in blocks of slots with a running
softmax (``composed_paged_attend`` there: one kernel over the pages where they
lie on a TPU where the pool's form allows, else each block gathered as the
pool stores it, ``gather_pages`` over the block's columns, up to the longest
live lane's last page): it reads what the lanes hold, not the table's width,
and writes no float32 copy of it. A prompt's chunk takes the fused prefill
kernel on a TPU; off it, and a verify's rows everywhere, gather the whole row
(or a static window's reach of it) into a dense view for
``attend_reference``, as the reference entry points kept in this module do.
Dense is just the identity block table (lane i owns pages [i*max_pages,
(i+1)*max_pages)): the identity gather yields byte-identical values to the
dense reshape, so the dense-view form is bit-exact with the dense program and
the walk within float32 rounding of it, and the allocator still prefers
identity pages so page reads stay streaming.
Sessions joining/leaving mutate TABLE VALUES, never shapes — one compiled
program, no recompiles, which is the whole reason the dense lane pool
existed (server/batching.py module docstring).

Scatter safety: invalid writes (idle-lane sentinel position, unallocated
slot) are routed to flat index ``n_pages * page_size`` — one past the pool —
and dropped by ``mode="drop"``, mirroring the dense path's out-of-range
sentinel convention (models/common.py update_kv_cache).

Quantized pools (``--kv_quant_type int8|nf4a``): the pool may instead be a
``PagedPool`` — per-row quantized codes plus a sibling f32 absmax-scale
array, carried together as one pytree that stands in wherever a plain pool
array rides (scan xs, donation, MemoryCache buffers, swap entries). Every
write path quantizes rows on the way in (per-(token, kv-head) absmax over
the head dim) and every read path — the prefill kernel's tile loop
(ops/paged_flash_attention.py) or the XLA ``gather_pages`` twin here —
dequantizes on the way out, so decode/mixed/spec-verify steps never touch
an fp pool. int8 stores one byte per element; nf4a packs two 4-bit codes
per byte in SPLIT-HALF order (byte j holds dims j and j + d/2, so the
decoded halves concatenate along the head dim with no interleave
relayout), reusing the NF4A cubic code map of ops/quant.py. Unallocated
slots gather with ZERO scales, so holes still read as exact zeros.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from petals_tpu.ops.attention import attend_reference
from petals_tpu.ops.quant import NF4A_A, NF4A_B, NF4A_CODE

KV_QUANT_KINDS = ("none", "int8", "nf4a")

#: a row of fewer elements than a vector register's 128 lanes is stored folded
LANES = 128
FOLDED_ROW_HEADS = 4  # a pool row of up to this many kv heads is stored folded where its pages are walked (``stored_row``)


def stored_row(hkv: int, d_store: int, row_fetch: bool = False) -> Tuple[int, ...]:
    """THE storage rule of a page pool: the trailing dims a pool leaf keeps a
    token row in, ``[..., n_pages, page_size, *stored_row]``, from the leaf's
    own ``[hkv, d_store]`` (``d_store``: the head dim of values and int8
    codes, half of it for nf4a's packed bytes).

    A minor dim under the chip's 128 lanes is not a layout a step computes
    in: XLA keeps such an array with another dim minor (a bf16 pool of
    head_dim 64 with the PAGE index minor) and the program that is handed it
    row-major relays it whole on the way in and on the way out, every step.
    So such a leaf is stored with the kv heads folded into the row,
    ``(hkv * d_store,)``: the same bytes in the same order, the layout the
    step programs are handed is the one they compute in, and the prefill
    kernel's lane-trailing view (ops/paged_flash_attention.py
    ``_pool_views``) is the array itself. So is a row of up to
    ``FOLDED_ROW_HEADS`` heads, whatever their width. Under 4, because two kv
    heads of 256 are two rows of a tile of 16: the pool of ``[..., 64, 2,
    256]`` was kept in a layout of the compiler's own and both pools copied
    whole in ``ENTRY`` on the way in and on the way out of every step program
    (PR 48, compiled for the v5e at 2 x 256). At 4, because a page of ``[64,
    4, 128]`` is a quarter of a bfloat16 tile a row: handed over as it lies it
    moves nothing, but the decode walk's kernel cannot meet it as one matrix
    of whole tiles and the composed walk's gather of 64 one-tile rows a page
    ran at a sixth of the bandwidth its bytes need (12 walks of a 25 ms step,
    PERF.md section 6, PRs 64 and 65); folded, ``[64, 512]``, a page is that
    matrix as it lies. ``row_fetch`` (a span whose decode rows fetch SINGLE
    rows out of the pool and walk no page: one with an index row,
    ops/sparse_attention.py ``_take_rows``) stops the rule under 4 as it
    stood: there a row of ``[4, 128]`` is a tile of its own, 1 KB in one
    piece, and in a folded bfloat16 pool two positions share a tile's packed
    sublanes. Any other row (8 kv heads and more) keeps ``(hkv, d_store)``.
    Scales stay ``[..., hkv]`` either way.

    Every consumer reads the form off the leaf it is handed (``pool_geometry``,
    ``fold_rows`` / ``unfold_rows``); only who MAKES a pool asks this function
    (server/span_cache.py ``pool_row``)."""
    up_to = FOLDED_ROW_HEADS - 1 if row_fetch else FOLDED_ROW_HEADS
    return (hkv * d_store,) if d_store < LANES or hkv <= up_to else (hkv, d_store)


def unfold_rows(a, hkv: int):
    """Folded rows ``[..., hkv * d]`` as ``[..., hkv, d]`` (jax or numpy)."""
    return a.reshape(*a.shape[:-1], hkv, a.shape[-1] // hkv)


def fold_rows(rows, row: Tuple[int, ...]):
    """Rows ``[..., hkv, d_store]`` as a leaf stores them, ``row`` being the
    leaf's own trailing dims: folded for a folded leaf, as they are for one
    that keeps ``(hkv, d_store)`` (jax or numpy)."""
    return rows.reshape(*rows.shape[:-2], *row)


class PagedPool(NamedTuple):
    """A quantized page pool: per-row codes plus their absmax scales.

    ``codes`` is int8 ``[..., n_pages, page_size, hkv, d]`` (kind "int8") or
    uint8 ``[..., n_pages, page_size, hkv, d // 2]`` with two split-half
    codes per byte (kind "nf4a"), the last two dims folded into one where
    ``stored_row`` says so (``[..., hkv * d_store]``: a head's codes are
    still contiguous, so its nf4a halves still are); ``scales`` is float32
    ``[..., n_pages, page_size, hkv]`` — one scale per (token row, kv head),
    in either form, and so the one leaf that always says ``hkv``.
    A NamedTuple, so it is a JAX pytree: it rides scan xs / donation /
    MemoryCache buffers wherever a plain pool array does, and its ``shape``/
    ``dtype`` properties answer the LOGICAL (dequantized) geometry so shape-
    reading call sites (step programs, kernel dispatch) stay unchanged."""

    codes: jnp.ndarray
    scales: jnp.ndarray

    @property
    def kind(self) -> str:
        return "int8" if np.dtype(self.codes.dtype) == np.int8 else "nf4a"

    @property
    def shape(self) -> Tuple[int, ...]:
        """Logical (dequantized) shape ``[..., hkv, d]``, whichever form the
        codes are stored in: the packed nf4a byte axis doubles."""
        lead = self.scales.shape
        d = math.prod(self.codes.shape[len(lead) - 1:]) // lead[-1]
        if np.dtype(self.codes.dtype) == np.uint8:
            d *= 2
        return (*lead, d)

    @property
    def ndim(self) -> int:
        return self.scales.ndim + 1

    @property
    def dtype(self):
        """Logical dtype: rows dequantize to bf16 (the compute dtype of the
        quantized-pool path; ``hidden.astype(k_pool.dtype)`` in the step
        programs must see a float type, never the storage int type)."""
        return jnp.bfloat16

    @property
    def nbytes(self) -> int:
        """WIRE bytes — what swap/migration accounting bills."""
        return int(self.codes.nbytes) + int(self.scales.nbytes)

    def is_deleted(self) -> bool:
        return self.codes.is_deleted() or self.scales.is_deleted()


#: a pool operand: the plain fp array or its quantized stand-in
PoolLike = Union[jnp.ndarray, PagedPool]


def kv_quant_kind_of(pool) -> str:
    """"none" for a plain array pool, else the PagedPool's quant kind."""
    return pool.kind if isinstance(pool, PagedPool) else "none"


def kv_wire_bytes_per_token(hkv: int, d: int, kind: str, fp_itemsize: int = 2) -> int:
    """Stored bytes per token row for ONE side (k or v) of ONE block."""
    if kind == "int8":
        return hkv * (d + 4)  # 1 byte/elem + f32 scale per (row, head)
    if kind == "nf4a":
        return hkv * (d // 2 + 4)  # packed nibbles + f32 scale
    return hkv * d * fp_itemsize


# --------------------------------------------------------------- quant codec


def quantize_kv_rows(rows: jnp.ndarray, kind: str):
    """Encode rows ``[..., d]`` -> ``(codes [..., d_store], scales [...])``
    with a per-row absmax scale over the last (head-dim) axis.

    int8: symmetric, ``scale = absmax / 127`` (ops/quant.py _encode_int8's
    convention at row granularity). nf4a: the stored scale IS the absmax and
    codes index the cubic NF4A code map via midpoint counting — 15 fused
    compare+adds, the same O(1)-memory encode as ops/quant.py _encode_4bit —
    then split-half packed (byte j = dims j | (j + d/2) << 4)."""
    if kind not in ("int8", "nf4a"):
        raise ValueError(f"kv quant kind must be int8|nf4a, got {kind!r}")
    rows_f = rows.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(rows_f), axis=-1)
    if kind == "int8":
        scale = jnp.maximum(absmax, 1e-8) / 127.0
        codes = jnp.clip(jnp.round(rows_f / scale[..., None]), -127, 127)
        return codes.astype(jnp.int8), scale
    scale = absmax
    normed = rows_f / jnp.maximum(absmax, 1e-8)[..., None]
    midpoints = (NF4A_CODE[:-1] + NF4A_CODE[1:]) / 2.0
    codes = jnp.zeros(normed.shape, jnp.uint8)
    for m in midpoints.tolist():
        codes += (normed > m).astype(jnp.uint8)
    half = rows.shape[-1] // 2
    return (codes[..., :half] | (codes[..., half:] << 4)).astype(jnp.uint8), scale


def dequantize_kv(codes: jnp.ndarray, scales: jnp.ndarray, kind: str,
                  dtype=jnp.bfloat16) -> jnp.ndarray:
    """Decode ``(codes [..., d_store], scales [...])`` back to rows
    ``[..., d]``. nf4a decodes arithmetically (the gather-free cubic map:
    ``v = scale * (A*dl + B*dl^3)``, ``dl = code - 7.5``) and un-packs the
    split halves by concatenation along the head dim. A ZERO scale decodes
    every element to exactly 0.0 — unallocated slots and never-written rows
    (zero-init pools) stay exact zeros through the round trip."""
    sf = scales[..., None].astype(jnp.float32)
    if kind == "int8":
        return (codes.astype(jnp.float32) * sf).astype(dtype)
    if kind != "nf4a":
        raise ValueError(f"kv quant kind must be int8|nf4a, got {kind!r}")
    c = codes.astype(jnp.int32)

    def poly(p):
        dl = p.astype(jnp.float32) - 7.5
        return dl * (NF4A_A + NF4A_B * dl * dl)

    vals = jnp.concatenate([poly(c & 0x0F), poly((c >> 4) & 0x0F)], axis=-1)
    return (vals * sf).astype(dtype)


def quantize_kv_rows_np(rows: np.ndarray, kind: str):
    """Numpy twin of ``quantize_kv_rows`` for host-side work (migration wire
    packing). Same math, same bit layout."""
    rows_f = np.asarray(rows, np.float32)
    absmax = np.max(np.abs(rows_f), axis=-1)
    if kind == "int8":
        scale = np.maximum(absmax, 1e-8) / 127.0
        codes = np.clip(np.round(rows_f / scale[..., None]), -127, 127)
        return codes.astype(np.int8), scale.astype(np.float32)
    if kind != "nf4a":
        raise ValueError(f"kv quant kind must be int8|nf4a, got {kind!r}")
    scale = absmax.astype(np.float32)
    normed = rows_f / np.maximum(absmax, 1e-8)[..., None]
    midpoints = (NF4A_CODE[:-1] + NF4A_CODE[1:]) / 2.0
    codes = np.zeros(normed.shape, np.uint8)
    for m in midpoints:
        codes += (normed > m).astype(np.uint8)
    half = rows.shape[-1] // 2
    return (codes[..., :half] | (codes[..., half:] << 4)).astype(np.uint8), scale


def dequantize_kv_np(codes: np.ndarray, scales: np.ndarray, kind: str,
                     dtype=np.float32) -> np.ndarray:
    """Numpy twin of ``dequantize_kv`` (swap-entry assembly, kv adopt)."""
    sf = np.asarray(scales, np.float32)[..., None]
    if kind == "int8":
        return (np.asarray(codes, np.float32) * sf).astype(dtype)
    if kind != "nf4a":
        raise ValueError(f"kv quant kind must be int8|nf4a, got {kind!r}")
    c = np.asarray(codes).astype(np.int32)

    def poly(p):
        dl = p.astype(np.float32) - 7.5
        return dl * (NF4A_A + NF4A_B * dl * dl)

    vals = np.concatenate([poly(c & 0x0F), poly((c >> 4) & 0x0F)], axis=-1)
    return (vals * sf).astype(dtype)


class PagedKV(NamedTuple):
    """One attention side (k or v) of a block's paged cache: the shared page
    pool plus the per-lane block tables. A NamedTuple, so it is automatically
    a JAX pytree and rides through ``block_apply``'s kv tuple / lax.scan
    carries unchanged; ``update_kv_cache`` and ``attend`` recognise it by
    isinstance and route to the paged scatter / paged attention dispatch instead
    of the dense buffer code.

    The pool is in whichever form ``stored_row`` gave it: ``[n_pages,
    page_size, hkv, d]`` or, for a row under the 128 lanes or of up to 4 kv
    heads, ``[n_pages, page_size, hkv * d]``. The scatter folds the new rows
    to the pool's own row and the gather unfolds the pages it took
    (``gather_pages``), so block code and attention see ``[.., hkv, d]`` rows
    either way.

    Inside a step program the pool a block sees is the WHOLE SPAN's, every
    layer's pages end to end (``[n_layers * n_pages, page_size, hkv, d]``, a
    bitcast of the stacked pool the layer loop carries and updates in place),
    and ``tables`` are the lanes' block tables shifted by the layer's first
    page (holes stay -1): the scatter lands a layer's rows in that layer's
    pages and the gather reads them from there, without a layer ever being
    sliced out of the pool or written back into it (server/backend.py
    ``_scan_paged_span``). ``layer`` then names the block's own stretch of
    that pool, ``(first_page, n_pages)``; it is None for a pool that holds
    one block alone."""

    pool: PoolLike  # [n_pages, page_size, *stored_row] array, or a PagedPool
    tables: jnp.ndarray  # [n_lanes, max_pages] int32; -1 = unallocated slot
    layer: Optional[Tuple] = None  # (first page: int32 scalar, pages a layer: int)

    @property
    def quant_kind(self) -> str:
        return kv_quant_kind_of(self.pool)

    @property
    def page_size(self) -> int:
        return self.pool.shape[1]

    @property
    def max_length(self) -> int:
        return self.tables.shape[1] * self.pool.shape[1]

    @property
    def shape(self) -> Tuple[int, ...]:
        """Dense-equivalent shape [n_lanes, max_length, hkv, d] — family block
        code reads ``k_all.shape[1]`` for the buffer length (e.g. gemma2's
        effective-window computation), so the stand-in must answer it. (A
        plain pool stored folded cannot say ``hkv`` and answers its own row,
        ``[n_lanes, max_length, hkv * d]``; who needs the heads has a query
        beside it: ``pool_geometry``.)"""
        return (self.tables.shape[0], self.max_length, *self.pool.shape[2:])

    @property
    def dtype(self):
        return self.pool.dtype

    def own_layer(self) -> "PagedKV":
        """The block's own pages as a pool of their own, with the tables it
        was given before the shift: what a consumer takes that would
        otherwise walk or relay every layer of the span's pool (the prefill
        kernel relays the pool it is handed into a lane-trailing view)."""
        if self.layer is None:
            return self
        first_page, n_pages = self.layer
        pool = jax.tree_util.tree_map(  # a PagedPool leaf by leaf
            lambda a: jax.lax.dynamic_slice_in_dim(a, first_page, n_pages, axis=0), self.pool
        )
        return PagedKV(pool, jnp.where(self.tables >= 0, self.tables - first_page, -1))


def max_pages_for(max_length: int, page_size: int) -> int:
    """Table slots per lane: max_length rounded UP to whole pages."""
    return -(-int(max_length) // int(page_size))


def identity_tables(n_lanes: int, max_pages: int) -> np.ndarray:
    """The contiguous layout: lane i owns pages [i*max_pages, (i+1)*max_pages)."""
    return np.arange(n_lanes * max_pages, dtype=np.int32).reshape(n_lanes, max_pages)


def tables_are_contiguous(tables: np.ndarray, n_pages: int) -> bool:
    """Host-side fast-path check: every ALLOCATED slot holds its identity
    page (unallocated ``-1`` slots are fine — the dense program never reads
    them unmasked nor writes them, see module docstring). Only possible when
    the pool is exactly lane-sized."""
    n_lanes, max_pages = tables.shape
    if n_pages != n_lanes * max_pages:
        return False
    ident = np.arange(n_lanes * max_pages, dtype=np.int32).reshape(n_lanes, max_pages)
    return bool(np.all((tables == ident) | (tables < 0)))


def pool_geometry(pool: PoolLike, head_dim: int) -> Tuple[int, int, int, int]:
    """``(n_pages, page_size, hkv, d_store)`` of one block's pool (or of the
    span's, layers end to end) in either stored form. A quantized pool's
    scales say ``hkv``; a plain pool that is stored folded has only its row's
    width, and ``head_dim`` (the query's) says how many heads that is."""
    if isinstance(pool, PagedPool):
        leaf, hkv = pool.codes, pool.scales.shape[-1]
    else:
        leaf, hkv = pool, pool.shape[2] if pool.ndim == 4 else pool.shape[2] // head_dim
    return leaf.shape[0], leaf.shape[1], hkv, math.prod(leaf.shape[2:]) // hkv


def _gather_pages_arr(pool: jnp.ndarray, tables: jnp.ndarray) -> jnp.ndarray:
    """gather_pages over ONE array (any trailing rank — works for a value
    pool [n_pages, ps, hkv, d], a codes pool [n_pages, ps, hkv, d_store],
    either of them folded to [n_pages, ps, hkv * d_store], and a scales pool
    [n_pages, ps, hkv]): the pages as the leaf stores them."""
    n_pages, page_size = pool.shape[0], pool.shape[1]
    n_lanes, max_pages = tables.shape
    flat = tables.reshape(-1)
    safe = jnp.clip(flat, 0, n_pages - 1)
    pages = jnp.take(pool, safe, axis=0)  # [n_lanes*max_pages, ps, *rest]
    hole_mask = (flat >= 0).reshape(-1, *([1] * (pool.ndim - 1)))
    pages = jnp.where(hole_mask, pages, jnp.zeros((), pool.dtype))
    return pages.reshape(n_lanes, max_pages * page_size, *pool.shape[2:])


def gather_pages(pool: PoolLike, tables: jnp.ndarray, hkv: Optional[int] = None) -> jnp.ndarray:
    """Materialize the dense per-lane view of one block's page pool.

    pool [n_pages, page_size, hkv, d] + tables [n_lanes, max_pages] ->
    [n_lanes, max_pages * page_size, hkv, d]. A pool stored folded
    ([n_pages, page_size, hkv * d], ``stored_row``) gives the same view: the
    pages are taken as they lie and only what was taken is unfolded (a decode
    walk's block is a page a lane, in fast memory; the pool itself is never
    reshaped). A plain folded pool needs ``hkv`` for that; a quantized one's
    scales say it. Unallocated slots (-1) read as
    ZEROS: they must not surface page 0's live bytes into a lane that does
    not own that page (attention masks them to 0.0 weight either way, but
    the dense view escapes attention — kv export, debug dumps — so the
    fallback path must never alias another tenant's content). The prefill
    kernel skips -1 slots entirely; behind a lane's length both give a
    weight of exactly zero. ``tables`` may be a block of the table's columns
    (a decode row's walk, ops/paged_flash_attention.py): the view is then
    that block's, [n_lanes, block * page_size, hkv, d].

    A quantized ``PagedPool`` gathers codes AND scales (holes zero both, so
    a -1 slot dequantizes to exact zeros) and returns the dense bf16 view —
    the bit-compatible XLA twin of the kernel's in-tile dequant."""
    if isinstance(pool, PagedPool):
        codes = _gather_pages_arr(pool.codes, tables)
        scales = _gather_pages_arr(pool.scales, tables)
        if codes.ndim == scales.ndim:  # folded: [n_lanes, rows, hkv * d_store]
            codes = unfold_rows(codes, scales.shape[-1])
        return dequantize_kv(codes, scales, pool.kind, pool.dtype)
    view = _gather_pages_arr(pool, tables)
    if view.ndim == 3:  # folded: [n_lanes, rows, hkv * d]
        if hkv is None:
            raise ValueError(f"a plain pool stored folded ({pool.shape}) does not say its kv heads: pass hkv")
        view = unfold_rows(view, hkv)
    return view


def _flat_scatter(pool: jnp.ndarray, rows: jnp.ndarray, flat_idx: jnp.ndarray) -> jnp.ndarray:
    """Scatter ``rows [n, *rest]`` into ``pool [n_pages, ps, *rest]`` at flat
    (page*ps + slot) indices; index ``n_pages*ps`` is one-past-the-end and
    drops. Rank-generic: serves value pools, codes pools, and scales pools,
    and rows ``[n, hkv, d_store]`` are folded to the row of a pool that is
    stored folded (``[n_pages, ps, hkv * d_store]``)."""
    n_pages, page_size = pool.shape[0], pool.shape[1]
    flat = pool.reshape(n_pages * page_size, *pool.shape[2:])
    rows = rows.reshape(rows.shape[0], *pool.shape[2:])
    flat = flat.at[flat_idx].set(rows.astype(pool.dtype), mode="drop")
    return flat.reshape(pool.shape)


def _scatter_rows(pool: PoolLike, rows: jnp.ndarray, flat_idx: jnp.ndarray) -> PoolLike:
    """Row scatter, quantizing on the way in when the pool is a PagedPool:
    rows [n, hkv, d] encode to (codes [n, hkv, d_store], scales [n, hkv])
    and both leaves scatter at the same flat indices."""
    if isinstance(pool, PagedPool):
        codes, scales = quantize_kv_rows(rows, pool.kind)
        return PagedPool(
            _flat_scatter(pool.codes, codes, flat_idx),
            _flat_scatter(pool.scales, scales, flat_idx),
        )
    return _flat_scatter(pool, rows, flat_idx)


def _pool_geometry(pool: PoolLike) -> Tuple[int, int]:
    """(n_pages, page_size) — identical for plain and quantized pools."""
    return pool.shape[0], pool.shape[1]


def scatter_token_rows(
    pool: PoolLike, rows: jnp.ndarray, tables: jnp.ndarray, positions: jnp.ndarray
) -> PoolLike:
    """Write each lane's freshly computed token row into its page.

    pool [n_pages, ps, hkv, d]; rows [n_lanes, hkv, d]; positions [n_lanes]
    (idle sentinel = max_length). Invalid lanes (sentinel position or
    unallocated slot) route to the one-past-the-end flat index and drop.
    Quantized pools encode each row (per-(lane, head) absmax) before the
    scatter — the pool never holds fp rows."""
    n_pages, page_size = _pool_geometry(pool)
    max_pages = tables.shape[1]
    slot = positions // page_size
    in_range = (positions >= 0) & (slot < max_pages)
    slot_c = jnp.clip(slot, 0, max_pages - 1)
    page = jnp.take_along_axis(tables, slot_c[:, None], axis=1)[:, 0]
    valid = in_range & (page >= 0)
    flat_idx = jnp.where(valid, page * page_size + positions % page_size, n_pages * page_size)
    return _scatter_rows(pool, rows, flat_idx)


def scatter_chunk_rows(
    pool: PoolLike, rows: jnp.ndarray, table_row: jnp.ndarray, positions: jnp.ndarray
) -> PoolLike:
    """Write a prefill chunk's freshly computed KV rows into ONE lane's pages.

    pool [n_pages, ps, hkv, d]; rows [chunk, hkv, d]; table_row [max_pages];
    positions [chunk] int32 (absolute token positions; padded rows carry the
    idle sentinel >= max_pages*ps). Invalid rows (sentinel position or
    unallocated slot) route to the one-past-the-end flat index and drop —
    the same convention as scatter_token_rows, just many rows into one lane."""
    n_pages, page_size = _pool_geometry(pool)
    max_pages = table_row.shape[0]
    slot = positions // page_size
    in_range = (positions >= 0) & (slot < max_pages)
    slot_c = jnp.clip(slot, 0, max_pages - 1)
    page = jnp.take(table_row, slot_c)
    valid = in_range & (page >= 0)
    flat_idx = jnp.where(valid, page * page_size + positions % page_size, n_pages * page_size)
    return _scatter_rows(pool, rows, flat_idx)


def scatter_lane_chunk_rows(
    pool: PoolLike, rows: jnp.ndarray, tables: jnp.ndarray, positions: jnp.ndarray
) -> PoolLike:
    """Write a short run of freshly computed rows into EVERY lane's pages at
    once — the speculative-verify write shape: each lane lands ``seq``
    candidate rows starting at its own position.

    pool [n_pages, ps, hkv, d]; rows [n_lanes, seq, hkv, d]; tables
    [n_lanes, max_pages]; positions [n_lanes] int32 (idle sentinel =
    max_length drops ALL of that lane's rows, since every offset lands past
    the table). Invalid rows route to the one-past-the-end flat index and
    drop — scatter_chunk_rows batched over lanes."""
    n_pages, page_size = _pool_geometry(pool)
    n_lanes, max_pages = tables.shape
    seq = rows.shape[1]
    pos = positions[:, None] + jnp.arange(seq, dtype=jnp.int32)[None, :]  # [n_lanes, seq]
    slot = pos // page_size
    in_range = (pos >= 0) & (slot < max_pages)
    slot_c = jnp.clip(slot, 0, max_pages - 1)
    page = jnp.take_along_axis(tables, slot_c, axis=1)  # [n_lanes, seq]
    valid = in_range & (page >= 0)
    flat_idx = jnp.where(valid, page * page_size + pos % page_size, n_pages * page_size)
    return _scatter_rows(
        pool, rows.reshape(n_lanes * seq, *rows.shape[2:]), flat_idx.reshape(-1)
    )


def scatter_lane_pages(
    pool: PoolLike, lane_pages: jnp.ndarray, table_row: jnp.ndarray
) -> PoolLike:
    """Write a whole lane-shaped buffer back into its pages (the exclusive-op
    check-in: prefill chunks, prefix seeding). lane_pages [max_pages, ps,
    hkv, d]; unallocated slots (-1) drop. Shared (copy-on-write) pages in
    the row receive exactly the bytes that were gathered out of them — the
    write range itself was made exclusive by prepare_write first. (On a
    quantized pool the check-in REQUANTIZES the dequantized buffer; rows the
    exclusive op didn't touch round-trip within one quant step, which the
    kv_quant fingerprint band absorbs.)"""
    n_pages = pool.shape[0]
    safe = jnp.where(table_row >= 0, table_row, n_pages)
    if isinstance(pool, PagedPool):
        codes, scales = quantize_kv_rows(lane_pages, pool.kind)
        codes = fold_rows(codes, pool.codes.shape[2:])
        return PagedPool(
            pool.codes.at[safe].set(codes.astype(pool.codes.dtype), mode="drop"),
            pool.scales.at[safe].set(scales.astype(pool.scales.dtype), mode="drop"),
        )
    return pool.at[safe].set(fold_rows(lane_pages, pool.shape[2:]).astype(pool.dtype), mode="drop")


def paged_update_kv(
    k_kv: "PagedKV",
    v_kv: "PagedKV",
    k_new: jnp.ndarray,
    v_new: jnp.ndarray,
    position,
    n_valid=None,
):
    """The PagedKV arm of ``models/common.py update_kv_cache``: scatter the
    freshly computed rows straight into the page pools (no dense detour) and
    return the updated PagedKV pair plus the valid kv length.

    Three write shapes, mirroring the dense helper's branches:
    - per-lane decode: ``position`` is a [n_lanes] vector, k_new/v_new are
      [n_lanes, 1, hkv, d] — one token row per lane (idle sentinel positions
      drop inside scatter_token_rows).
    - per-lane chunk (speculative verify): ``position`` is a [n_lanes]
      vector, k_new/v_new are [n_lanes, seq, hkv, d] with seq > 1 — every
      lane lands ``seq`` candidate rows starting at its own position
      (scatter_lane_chunk_rows; idle sentinel positions drop every row).
    - chunked prefill: ``position`` is a scalar, k_new/v_new are
      [1, chunk, hkv, d] with ``n_valid`` real rows — the single lane's
      table row is ``tables[0]`` (the step builder wraps it as [1, max_pages]).
    """
    pos = jnp.asarray(position, jnp.int32)
    tables = k_kv.tables
    if pos.ndim == 1:
        if n_valid is not None:
            raise ValueError(
                f"per-lane paged writes take no n_valid (got n_valid={n_valid})"
            )
        seq = k_new.shape[1]
        if seq == 1:
            k_pool = scatter_token_rows(k_kv.pool, k_new[:, 0], tables, pos)
            v_pool = scatter_token_rows(v_kv.pool, v_new[:, 0], tables, pos)
        else:
            k_pool = scatter_lane_chunk_rows(k_kv.pool, k_new, tables, pos)
            v_pool = scatter_lane_chunk_rows(v_kv.pool, v_new, tables, pos)
        return k_kv._replace(pool=k_pool), v_kv._replace(pool=v_pool), pos + seq
    if k_new.shape[0] != 1 or tables.shape[0] != 1:
        raise ValueError(
            "scalar-position paged writes are single-lane chunks: "
            f"got batch={k_new.shape[0]}, table rows={tables.shape[0]}"
        )
    seq = k_new.shape[1]
    n = jnp.asarray(seq if n_valid is None else n_valid, jnp.int32)
    offs = jnp.arange(seq, dtype=jnp.int32)
    # padded tail rows route to the one-past-the-end sentinel and drop
    write_pos = jnp.where(offs < n, pos + offs, jnp.int32(k_kv.max_length))
    k_pool = scatter_chunk_rows(k_kv.pool, k_new[0], tables[0], write_pos)
    v_pool = scatter_chunk_rows(v_kv.pool, v_new[0], tables[0], write_pos)
    return k_kv._replace(pool=k_pool), v_kv._replace(pool=v_pool), pos + n


def paged_attend(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    tables: jnp.ndarray,
    positions: jnp.ndarray,
    *,
    alibi_slopes: Optional[jnp.ndarray] = None,
    sliding_window: Optional[int] = None,
) -> jnp.ndarray:
    """Standalone ragged paged-attention decode reference: gather each lane's
    pages into a dense view and attend with per-lane ragged lengths
    (kv_length = position + 1). q [n_lanes, 1, hq, d]; k/v_pool [n_pages,
    ps, hkv, d]; tables [n_lanes, max_pages]; positions [n_lanes] int32.
    The production decode step fuses this same gather in front of the model
    family's block code (server/backend.py _paged_decode_fn); this entry
    point is the kernel-level contract the parity tests pin down."""
    hkv = pool_geometry(k_pool, q.shape[-1])[2]
    k = gather_pages(k_pool, tables, hkv)
    v = gather_pages(v_pool, tables, hkv)
    pos = jnp.asarray(positions, jnp.int32)
    return attend_reference(
        q, k, v, q_offset=pos, kv_length=pos + q.shape[1],
        alibi_slopes=alibi_slopes, sliding_window=sliding_window,
    )


def paged_prefill_attend(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    table_row: jnp.ndarray,
    chunk_pos: jnp.ndarray,
    n_valid: jnp.ndarray,
    *,
    alibi_slopes: Optional[jnp.ndarray] = None,
    sliding_window: Optional[int] = None,
) -> jnp.ndarray:
    """Standalone ragged paged-PREFILL reference: causal attention for one
    lane's variable-length chunk against that lane's block table, with the
    chunk's KV already scattered into the pages (scatter_chunk_rows).

    q [1, chunk, hq, d] (padded to a bucket); table_row [max_pages];
    chunk_pos scalar int32 (absolute position of the chunk's first token);
    n_valid scalar int32 (real tokens in the chunk; padded tail is masked
    out via kv_length and produces garbage-but-unread outputs). The
    production mixed step fuses this gather in front of the model family's
    block code (server/backend.py _paged_mixed_step_fn); this entry point is
    the kernel-level contract the mixed parity tests pin down."""
    hkv = pool_geometry(k_pool, q.shape[-1])[2]
    k = gather_pages(k_pool, table_row[None], hkv)
    v = gather_pages(v_pool, table_row[None], hkv)
    pos = jnp.asarray(chunk_pos, jnp.int32).reshape(1)
    kv_len = pos + jnp.asarray(n_valid, jnp.int32).reshape(1)
    return attend_reference(
        q, k, v, q_offset=pos, kv_length=kv_len,
        alibi_slopes=alibi_slopes, sliding_window=sliding_window,
    )
