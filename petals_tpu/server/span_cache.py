"""SpanCache: what a lane holds for each block of a span, said once.

A family declares a block's cache through ``ModelFamily``'s hooks (``block_window``, ``block_state``, ``block_index``,
``block_latent``, ``block_sublayers``, ``block_attention``). This module is the only code of the server that asks them and
the only code that knows which combinations are served. From their answers for a span it derives, once, at the backend's
start: the LAYOUT (``SpanCache``'s fields: plain data the step programs read at trace time as ``backend.cache.<field>``);
what is REFUSED, at start (``_declared``: one validation) and later (``refuse``); the POOLS a paged lane pool allocates
(``pool_descriptors``) and the BYTES a token and a lane cost; and what a paged step READS (``lane_pool`` ->
``LanePool.count_step``: the batcher's per-layer counters, from the step's shapes and positions alone).

A family of a kind of cache that exists touches nothing here. A new kind of cache is a row of ``CONTENTS``, its fields,
its pools, its counters, and the step program that carries it (server/backend.py).
"""

from __future__ import annotations

import collections
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from petals_tpu.models.registry import ModelFamily
from petals_tpu.ops import latent_attention, linear_attention, paged_attention, sparse_attention
from petals_tpu.ops import paged_flash_attention as pfa
from petals_tpu.server.memory_cache import TensorDescriptor

# why a path that handles keys and values alone refuses a span with an index row (``SpanCache.refuse``)
INDEX_ROWS_RIDE = (
    "only the paged lane pool's decode, generation and mixed steps carry the index rows' pages; a cache without "
    "them would choose from nothing"
)
# and why one refuses a span whose positions cache a latent row in place of keys and values
LATENT_ROWS_RIDE = (
    "only the paged lane pool's decode, generation and mixed steps carry the latent rows' pages; every other "
    "cache is laid out for keys and values a head, which such a span never makes"
)

# why a path that ships, stores, cuts back or adopts a lane's pages refuses a paged lane pool whose windowed layers give
# pages back (``SpanCache.refuse(..., paged=True)``)
GROUPS_RIDE = (
    "only the paged lane pool's decode, generation and mixed steps carry pages by kind of layer; a windowed layer's "
    "pages behind its window have gone back to the pool, so a lane's cache is no longer whole keys and values a layer"
)


# one kind of thing a lane holds for a block beside or in place of pages of keys and values, as a refusal speaks of it: the
# ``ModelFamily`` accessor that declares it a kind of block; the thing, with its article; a span that holds it; (SpanCache) ->
# this span's own numbers of it, for the brackets of a later refusal; why what is laid out for keys and values alone refuses it
# (None: the caller's own reason); why a tp mesh does not carry it; why quantised pages do not
_Content = collections.namedtuple("_Content", "hook one span detail rides mesh packed")

# in the order a span's content is looked for; at most one is served in a span (``_declared``)
CONTENTS = {
    # a state holds a whole history at one position and cannot be cut back to an earlier one, so what needs that, and the
    # cache paths that do not carry a state at all, are refused with the reason the caller gives
    "state": _Content(
        "state_for", "a recurrent state", "a span with a recurrent state",
        lambda c: f"{len(c.state_layers)} of its {c.n_blocks} blocks keep one", None,
        "the paged lane pool alone carries the state, and a mesh falls back to the dense one",
        "the state is float32 and has no packed form",
    ),
    # a third page pool that only the paged lane pool's decode, generation and mixed steps are handed
    "index": _Content(
        "index_for", "an index row", "a span whose positions cache an index row beside their keys and values",
        lambda c: f"{c.index_row[0]} wide, the key a learned sparse attention scores", INDEX_ROWS_RIDE,
        "the row has one head, and the dense lane pool a mesh falls back to has no place for it",
        "the selection fetches single rows of the pool, which has no packed form for that yet",
    ),
    # pages of another shape than keys' and values'
    "latent": _Content(
        "latent_for", "a latent row", "a span whose positions cache a latent row in place of their keys and values",
        lambda c: f"{' + '.join(map(str, c.latent_row))} wide, one for all heads", LATENT_ROWS_RIDE,
        "the row is one for all heads, and the dense lane pool a mesh falls back to is laid out for keys and values a head",
        "the pages' packed forms are of keys and values a head, with a scale a head",
    ),
}


def _refusal(family: str, what: str, span: str, why: str) -> NotImplementedError:
    """Every refusal's one sentence: the family by name, what was asked, the span's content, the reason."""
    return NotImplementedError(f"{family}: {what} is not served for {span}: {why}")


def cache_kv_heads(cfg) -> int:
    """The kv heads a cache keeps a position: a family may keep more than it publishes (heads of zeros, for the device's layout)."""
    return getattr(cfg, "cache_kv_heads", None) or getattr(cfg, "num_key_value_heads", cfg.num_attention_heads)


def _declared(family: ModelFamily, cfg, runs, kv_quant_type: str, mesh) -> Tuple[dict, int]:
    """``({content: (what the span's kinds of block declare of it, the kinds that do)}, cache rows a position a block)``,
    the first empty for a span that caches keys and values alone: the ONE validation of what a span's blocks declare. What
    cannot be served yet is refused here, by the family's name and with the reason, not served wrong: kinds that disagree,
    two contents in one span, a tp mesh, packed pages, more than one row a block of anything but latent rows."""
    found = {}
    for name, content in CONTENTS.items():
        by_kind = [getattr(family, content.hook)(cfg, kind) for kind, _, _ in runs]
        distinct = {d for d in by_kind if d is not None}
        if not distinct:
            continue
        # a state's blocks stand among blocks that keep keys and values; a row is one of every block's positions
        if len(distinct) > 1 or (name != "state" and None in by_kind):
            why = f"its pool is laid out once for the span, whose kinds declare {len(set(by_kind))} different ones"
            raise _refusal(family.name, "a disagreement between kinds of block", content.span, why)
        if found:
            why = "each is carried by step programs of its own, which hand the pool beside the pages to one of them"
            raise _refusal(family.name, f"{CONTENTS[next(iter(found))].one} in the same span", content.span, why)
        if mesh is not None:
            raise _refusal(family.name, "a tp mesh", content.span, content.mesh)
        if kv_quant_type != "none":
            raise _refusal(family.name, f"kv_quant_type {kv_quant_type!r}", content.span, content.packed)
        found[name] = distinct.pop(), frozenset(kind for (kind, _, _), d in zip(runs, by_kind) if d is not None)
    # ModelFamily.block_sublayers: the attention sub-layers of a block, each with pages of its own
    counts = {family.sublayers_for(cfg, kind) for kind, _, _ in runs}
    if counts != {1} and (len(counts) > 1 or min(counts) < 1 or "latent" not in found):
        raise _refusal(
            family.name, f"more than one cache row a position a block ({sorted(counts)} sub-layers)",
            "a span whose blocks do not all keep the same number of latent rows",
            "keys' and values' pages, a state and an index row are laid out one layer a block",
        )
    return found, min(counts)


class SpanCache:
    """What a lane holds for each block of one span of ``runs`` (``registry.span_runs``: (kind, start in the span, length)) of
    ``family`` at ``cfg`` (the module's docstring); raises ``NotImplementedError`` for what is declared and not served.
    Frozen once built: the step programs read the fields at trace time."""

    def __init__(self, family: ModelFamily, cfg, runs, *, cache_dtype, kv_quant_type: str = "none", mesh=None):
        found, block_rows = _declared(family, cfg, runs, kv_quant_type, mesh)
        kinds = [kind for kind, _, length in runs for _ in range(length)]
        self.family, self.n_blocks = family.name, len(kinds)  # the family's name: for a refusal
        self.cache_dtype = cache_dtype = jnp.dtype(cache_dtype)
        self.kv_quant_type = kv_quant_type  # the paged pool's encoding: none | int8 | nf4a
        self.kv_heads, self.head_dim = cache_kv_heads(cfg), cfg.head_dim  # of a cache of keys and values
        # a family that declares its layers' static windows: one per block of the span (None: full attention); None for every
        # other family
        self.layer_windows = None if family.block_window is None else tuple(family.block_window(cfg, kind) for kind in kinds)
        # what each block hands its decode walk beside the plain call: (its static window, the family's own or the one of
        # ``cfg``; the names out of ``ATTENTION_EXTRAS`` that ModelFamily.block_attention declares)
        window = getattr(cfg, "sliding_window", None)
        windows = self.layer_windows or (window if isinstance(window, int) and window > 0 else None,) * len(kinds)
        self.walk_calls = tuple((window, family.attention_for(cfg, kind)) for window, kind in zip(windows, kinds))
        # what a lane holds for each block: pages of keys and values, or, for a kind whose family declares a state
        # (ModelFamily.block_state), a state of fixed size in a pool of its own beside the pages. kv_layers / state_layers: the
        # span's blocks of either sort, in order; slots[i]: block i's place among its own sort, which is its layer in its pool;
        # state_kinds: the kinds of block that keep a state; lane_state: a state's leaves, (shape, dtype)
        state, self.state_kinds = found.get("state", ((), frozenset()))
        self.state_layers = tuple(i for i, kind in enumerate(kinds) if kind in self.state_kinds)
        self.kv_layers = tuple(i for i, kind in enumerate(kinds) if kind not in self.state_kinds)
        self.slots = tuple((self.state_layers if kind in self.state_kinds else self.kv_layers).index(i) for i, kind in enumerate(kinds))
        self.lane_state = tuple((tuple(shape), jnp.dtype(dtype or cache_dtype)) for shape, dtype in state)
        # what a position caches BESIDE its key and value in the span's blocks (ModelFamily.block_index): an index row, (width,
        # dtype), kept in a third page pool under the lanes' tables, and ``index_keep``, the positions a row's selection keeps;
        # None for a span without one. Every block that keeps keys and values then keeps one
        self.index_row = self.index_keep = None
        if "index" in found:
            width, dtype, keep = found["index"][0]
            self.index_row, self.index_keep = (int(width), jnp.dtype(dtype or cache_dtype)), int(keep)
        # what a position caches IN PLACE of its key and value in the span's blocks (ModelFamily.block_latent): one row for all
        # heads, (latent width, rotated key's width), kept where the pages of keys and values would lie: the first pool holds
        # the latents, the second the rotated keys (``pool_descriptors``); None for a span that caches keys and values
        self.latent_row = tuple(int(width) for width in found["latent"][0]) if "latent" in found else None
        # how many cache rows a position a block keeps (ModelFamily.block_sublayers): the page pools hold ``page_layers`` layers
        # of pages, a block's one after the other, and everything that multiplies by layers of pages multiplies by that
        self.block_rows = block_rows
        # PAGE GROUPS: the blocks that keep keys and values, grouped by static window (None: full attention), as ((window,
        # (block, ...)), ...), the full group first. A paged lane pool keeps a pool, an allocator and lane tables a group, and
        # a windowed group gives a lane's pages back as its window moves past them (server/batching.py). More than one
        # group only for a family that declares its layers' windows, over plain pages of keys and values one row a block:
        # any other span has ONE group, and allocates, counts and compiles what it did before groups. group_slots[i]:
        # block i's (group, layer in that group's pool), None for a block without pages
        by_kind = bool(self.layer_windows) and not found and kv_quant_type == "none" and block_rows == 1
        by_window = collections.defaultdict(list)
        for i in self.kv_layers:
            by_window[self.layer_windows[i] if by_kind else None].append(i)
        if len(by_window) == 1:
            by_window = {None: list(self.kv_layers)}  # one kind of layer: one pool under one table, every page kept
        order = sorted(by_window, key=lambda w: (w is not None, -(w or 0)))
        self.page_groups = tuple((w, tuple(by_window[w])) for w in order)
        self.group_slots = tuple(
            next(((g, blocks.index(i)) for g, (_, blocks) in enumerate(self.page_groups) if i in blocks), None) for i in range(len(kinds))
        )
        self.page_layers = len(self.kv_layers) * block_rows

    def __setattr__(self, name, value):
        if "page_layers" in self.__dict__:  # the last field ``__init__`` sets
            raise AttributeError(f"SpanCache is frozen: {name}")
        super().__setattr__(name, value)

    # ------------------------------------------------------------- what is refused later

    @functools.cached_property
    def content(self) -> Optional[str]:
        """The key of ``CONTENTS`` for what the span's lanes hold beside or in place of pages of keys and values; None: keys
        and values alone."""
        return "state" if self.state_layers else "index" if self.index_row is not None else "latent" if self.latent_row is not None else None

    @property
    def paged_only(self) -> bool:
        """The span has content that only the paged lane pool carries: it has no private cache and no dense pool."""
        return self.content is not None

    @property
    def grouped(self) -> bool:
        """The span's layers keep pages in more than one group (``page_groups``): a paged lane pool gives the windowed
        groups' pages back, and what needs a lane's whole cache is refused there (``refuse(..., paged=True)``)."""
        return len(self.page_groups) > 1

    def refuse(self, what: str, why: str, *, paged: bool = False) -> None:
        """Raise for ``what`` if the span holds more than keys and values: what cuts a cache back to an earlier position and
        the cache paths that carry keys and values alone are refused by what the family declares, a state with the caller's
        ``why``, a row with its own reason (``CONTENTS``). ``paged``: ``what`` is asked of a PAGED lane pool, which for a
        span of more than one page group has given pages back: what ships, stores, cuts back or adopts a lane's pages is
        refused there (``GROUPS_RIDE``); the same span's private caches and dense pool keep every position and serve it."""
        if self.content is not None:
            content = CONTENTS[self.content]
            raise _refusal(self.family, what, f"{content.span} ({content.detail(self)})", content.rides or why)
        if paged and self.grouped:
            raise _refusal(self.family, what, f"a span whose layers keep pages in groups by window ({self._groups_detail()})", GROUPS_RIDE)

    def _groups_detail(self) -> str:
        return ", ".join(f"{len(blocks)} {'full' if window is None else f'of window {window}'}" for window, blocks in self.page_groups)

    def prefix_cache_refusal(self, paged: bool = False) -> Optional[str]:
        """None, or why this span stores no prefix, as a sentence for the log. A hit seeds a session's cache cut to the
        prefix's end, and a state cannot be cut back; a stored prefix is keys and values a head (a snapshot, or pinned pages a
        hit adopts and forks through paths laid out for them), and carries neither the index rows a span with a learned sparse
        attention caches beside them nor a latent row in their place. ``paged``: the server keeps a paged lane pool, where a
        span of more than one page group has no whole prefix to store: a windowed layer's is gone once the window passed it."""
        if self.content is None:
            if paged and self.grouped:
                return (f"Prefix cache off for a span whose layers keep pages in groups by window ({self._groups_detail()}): "
                        f"a stored prefix of a windowed layer is gone once the window passed it")
            return None
        content = CONTENTS[self.content]
        cannot = "which cannot be cut back to a stored prefix" if self.content == "state" else "which a stored prefix does not carry"
        return f"Prefix cache off for {content.span} ({content.detail(self)}), {cannot}"

    # ------------------------------------------------------------- the pools

    @functools.cached_property
    def pool_row(self) -> tuple:
        """The trailing dims the page pool's values (or codes) keep a token row in: ``(hkv, d_store)``, or ``(hkv *
        d_store,)`` where the rule folds it (ops/paged_attention.py ``stored_row``). Fixed at start. The rule is told what the
        span's decode rows do with the pool: walk their lanes' pages, or, with an index row, fetch the rows they chose one by
        one (4 kv heads of 128 then stay a row of ``[4, 128]``, a tile of its own)."""
        if self.latent_row is not None:  # one row for all heads, in two pools (``pool_descriptors``)
            return (sum(self.latent_row),)
        d_store = self.head_dim // 2 if self.kv_quant_type == "nf4a" else self.head_dim
        return paged_attention.stored_row(self.kv_heads, d_store, row_fetch=self.index_row is not None)

    def group_pages(self, n_lanes: int, max_pages: int, page_size: int, chunk: int, n_pages: Optional[int] = None) -> tuple:
        """Pages a group for a paged lane pool of these shapes, ``n_pages`` the full group's (None: a lane's whole table,
        every lane). A windowed group gets as many lanes' worth: a lane there holds at most the pages its window reaches
        and those of a prompt's chunk of up to ``chunk`` positions being fed (``lane_pages``), never less than one lane's."""
        full = n_lanes * max_pages if n_pages is None else int(n_pages)
        a_lane = [self.lane_pages(window, max_pages, page_size, chunk) for window, _ in self.page_groups]
        return tuple(full if window is None else max(-(-full * pages // max_pages), pages) for (window, _), pages in zip(self.page_groups, a_lane))

    @staticmethod
    def lane_pages(window: Optional[int], max_pages: int, page_size: int, chunk: int) -> int:
        """The most pages a lane holds in a group of ``window`` when a step starts: its whole table in a full group; in a
        windowed one the pages the first row's window and the last row of a chunk of ``chunk`` positions reach between
        them (``ceil(window / page) + 1`` pages and one chunk: ops/paged_flash_attention.py ``window_pages``)."""
        return pfa.window_pages(window, max(int(chunk), 1), page_size, max_pages)

    def pool_descriptors(self, n_pages, page_size: int, n_lanes: int, start: int, end: int) -> tuple:
        """Descriptors of everything a PAGED lane pool of blocks [start, end) allocates, in the order the step programs take
        it: the page pools, then the state pool's leaves, then the index pool.

        ``n_pages`` a TUPLE, one number a page group (``group_pages``): a pair (k, v) a group, in the groups' order, each as
        deep as the group's blocks in [start, end); the step programs are then handed the first pair as their pools, the
        others where a state pool would ride, and block tables a group (server/backend.py ``_scan_paged_span``). A number:
        one pool for every block that keeps keys and values, under one table a lane, as before groups.

        The page pools, unquantized: (k, v), each [n, n_pages, page_size, hkv, d] in cache_dtype. Quantized (kv_quant_type !=
        none): (k_codes, v_codes, k_scales, v_scales) — the codes in the storage dtype (int8, or uint8 with two
        split-half-packed dims per byte for nf4a) and f32 absmax scales per (page row, kv head). A values or codes leaf whose
        row is under the chip's 128 lanes (head_dim 64; 128 too for nf4a's packed half), or of up to 4 kv heads, is stored
        with the kv heads folded into it, [n, n_pages, page_size, hkv * d_store] (``pool_row``; the rule:
        ops/paged_attention.py ``stored_row``). The
        paged path is gated to mesh-less single-host servers (server/batching.py), so no sharding rides these. The pools are
        as deep as the blocks of [start, end) that keep keys and values: a block with a state of its own has no pages.

        The STATE pool beside the pages: one descriptor a leaf of the family's state, ``[state layers, n_lanes, *shape]``;
        none for a span whose blocks all keep keys and values. The INDEX pool beside the page pools of keys and values, ``[kv
        layers, n_pages, *row]``: a page of it is a page of theirs, under the same block tables, its positions' rows of
        ``width`` stored as ops/sparse_attention.py ``index_pool_row`` says (a row under the chip's 128 lanes: several
        positions to a row of 128); none for a span without an index row."""
        if isinstance(n_pages, (tuple, list)):
            assert len(n_pages) == len(self.page_groups) and self.content is None, (n_pages, self.page_groups, self.content)
            return tuple(
                TensorDescriptor((sum(start <= i < end for i in blocks), int(pages), page_size, *self.pool_row), self.cache_dtype)
                for pages, (_, blocks) in zip(n_pages, self.page_groups) for _ in range(2)
            )
        n = sum(start <= i < end for i in self.kv_layers) * self.block_rows
        if self.latent_row is not None:
            # a latent row in place of keys and values: the latents a position a row, and the rotated keys stored
            # as an index row of their width is (ops/latent_attention.py ``latent_pool_rows``); stored once
            rows = latent_attention.latent_pool_rows(page_size, *self.latent_row)
            return tuple(TensorDescriptor((n, n_pages, *row), self.cache_dtype) for row in rows)
        shape = (n, n_pages, page_size, *self.pool_row)
        if self.kv_quant_type == "none":
            pools = [TensorDescriptor(shape, self.cache_dtype)] * 2
        else:
            codes = TensorDescriptor(shape, jnp.int8 if self.kv_quant_type == "int8" else jnp.uint8)
            pools = [codes, codes] + [TensorDescriptor((n, n_pages, page_size, self.kv_heads), jnp.float32)] * 2
        pools += [TensorDescriptor((len(self.state_layers), n_lanes, *shape), dtype) for shape, dtype in self.lane_state]
        if self.index_row is not None:
            width, dtype = self.index_row
            pools.append(TensorDescriptor((len(self.kv_layers), n_pages, *sparse_attention.index_pool_row(page_size, width)), dtype))
        return tuple(pools)

    @property
    def pools_beside_pages(self) -> int:
        """How many of ``pool_descriptors``' entries come after the page pools: the state's leaves, or the index pool."""
        return len(self.lane_state) + (self.index_row is not None)

    # ------------------------------------------------------------- the bytes

    def index_bytes_per_token(self) -> int:
        """What a position caches across the span beside its keys and values: its index rows. 0 for a span without one."""
        return 0 if self.index_row is None else len(self.kv_layers) * self.index_row[0] * self.index_row[1].itemsize

    def state_bytes_per_lane(self) -> int:
        """What a lane holds whatever its context: its states over the span's state layers. 0 for a span without one."""
        return len(self.state_layers) * sum(int(np.prod(shape)) * dtype.itemsize for shape, dtype in self.lane_state)

    def cache_bytes_per_token(self) -> int:
        """LOGICAL (dense fp) bytes per token across the span's blocks that keep keys and values — sizes the dense lane cache
        and stays the fp baseline for capacity ratios. A lane's fixed part is ``state_bytes_per_lane``. A span that caches a
        latent row in place of keys and values: that row's bytes, stored once."""
        if self.latent_row is not None:
            return self.page_layers * sum(self.latent_row) * self.cache_dtype.itemsize
        return 2 * len(self.kv_layers) * self.kv_heads * self.head_dim * self.cache_dtype.itemsize + self.index_bytes_per_token()

    def kv_bytes_per_token(self) -> int:
        """WIRE bytes per token across the span: what the paged pool, host swap, and migration actually store/ship per token.
        Equals cache_bytes_per_token when kv_quant_type == none."""
        if self.latent_row is not None:
            return self.cache_bytes_per_token()
        per_head = paged_attention.kv_wire_bytes_per_token(self.kv_heads, self.head_dim, self.kv_quant_type, self.cache_dtype.itemsize)
        return 2 * len(self.kv_layers) * per_head + self.index_bytes_per_token()

    def lane_bytes(self, max_length: int) -> int:
        """What a lane of ``max_length`` positions costs on the device: its pages in the blocks that keep keys and values
        (quantized pool pages cost wire bytes on device too, packed codes + f32 scales, so a budget affords ~4x the lanes),
        and the lane's fixed part, its states."""
        per_token = self.cache_bytes_per_token() if self.kv_quant_type == "none" else self.kv_bytes_per_token()
        if self.grouped:  # a windowed group's layers hold what their window reaches (a chunk being fed besides: ``lane_pages``)
            a_layer = per_token // len(self.kv_layers)
            return sum(len(blocks) * a_layer * min(max_length, window or max_length) for window, blocks in self.page_groups)
        return per_token * max_length + self.state_bytes_per_lane()

    def lane_pool(self, n_lanes: int, max_pages: int, page_size: int, grouped: bool = False) -> "LanePool":
        """The counters of a paged lane pool of these shapes over this span; ``grouped``: one that keeps a pool and lane
        tables a page group (``page_groups``) and gives the windowed groups' pages back."""
        return LanePool(self, n_lanes, max_pages, page_size, grouped)


# the keys a content opens in the batcher's ``stats`` (``LanePool.new_stats``), every one from the shapes a step is started
# with. A state: rows times state layers by the form their step gave them (the one-step form a decode row, the chunked form a
# prompt chunk; linattn_kernel_tokens: of the one-step form's, those whose state the kernel moved once where it lies in the
# pool, 0 where the plain form runs: ``LanePool.state_step`` says which), and, summed step by step over the lanes that fed
# rows, the bytes of state and of pages they hold
_STATE_KEYS = ("linattn_recurrent_tokens", "linattn_kernel_tokens", "linattn_chunk_tokens", "state_bytes_held", "kv_bytes_held")
# an index row, all times the span's layers: rows whose context was over / at most the selection's size, index rows their
# scoring read (and query row x index row pairs it scored), positions of keys and values the step's programs fetched against
# those the rows' lanes held, and, summed step by step, the bytes of index rows and of keys and values those lanes' pages hold
_SPARSE_KEYS = ("sparse_rows_selected", "sparse_rows_dense", "sparse_index_rows_scored", "sparse_score_pairs", "sparse_kv_rows_read",
                "sparse_kv_rows_held")
# a latent row, all times the span's layers of pages: latent rows the decode rows' walks read against those their lanes held;
# rows that took the absorbed form (a decode row) and the expanded one (a chunk's); positions a chunk's walk expanded against
# those its lane held; (row, position) pairs scored; and, summed step by step, the bytes of latent rows the lanes that fed
# rows hold
_LATENT_KEYS = ("latent_rows_read", "latent_rows_held", "latent_rows_absorbed", "latent_rows_expanded", "latent_positions_expanded",
                "latent_positions_held", "latent_score_pairs")
_CONTENT_KEYS = {None: (), "state": _STATE_KEYS, "index": (*_SPARSE_KEYS, "index_bytes_held", "kv_bytes_held"),
                 "latent": (*_LATENT_KEYS, "latent_bytes_held")}


class LanePool:
    """What the paged step programs of one lane pool read, counted on the host from a step's shapes and the positions it is
    started with: the batcher's per-layer counters (``new_stats`` opens them, ``count_step`` adds a step's). What is fixed with
    the pool's geometry is asked once, here (``page_bytes``, ``state_bytes``, ``walks``, ``state_step``, ``selects``)."""

    def __init__(self, cache: SpanCache, n_lanes: int, max_pages: int, page_size: int, grouped: bool = False):
        from petals_tpu.server.backend import bucket_length  # how a step program pads a prompt's chunk

        self.cache, self.n_lanes, self.max_pages, self.page_size = cache, n_lanes, max_pages, page_size
        # a pool that keeps pages by group (``SpanCache.page_groups``): the bytes of one page of each group's pool, all its layers
        self.grouped = bool(grouped) and cache.grouped
        a_layer_page = 2 * cache.kv_heads * cache.head_dim * cache.cache_dtype.itemsize * page_size
        self.group_page_bytes = tuple(len(blocks) * a_layer_page for _, blocks in cache.page_groups)
        self._bucket = bucket_length
        self.max_length = max_pages * page_size
        # WIRE bytes per page: quantized pools swap/reserve packed bytes, so the host-swap budget, ledger swap meters, and
        # victim sizing all bill what actually moves (kv_bytes_per_token == cache_bytes_per_token for unquantized backends)
        self.page_bytes = cache.kv_bytes_per_token() * page_size
        # what a lane holds whatever its context: its slot in the state pool. 0 for a span without a recurrent state
        self.state_bytes = cache.state_bytes_per_lane()
        # a span with an index row: its rows choose positions only where a table can pass the selection's size
        # (models/keye_vl2/block.py); a table that cannot pass ``index_keep`` positions is attended to whole
        self.selects = cache.index_row is not None and self.max_length > cache.index_keep
        # ((window, layers), ...): the static windows of the span's blocks that keep keys and values (None: full attention)
        # and how many blocks have each
        self._window_layers = tuple(collections.Counter(cache.walk_calls[i][0] for i in cache.kv_layers).items())
        self.walks = self._decode_walks()
        # a latent row: whether the decode rows' walk is the kernel (each live lane to its own end) or the composed one
        rows = latent_attention.latent_pool_rows(page_size, *cache.latent_row) if cache.latent_row else None
        self.latent_kernel = rows is not None and latent_attention.decode_path(*rows, cache.cache_dtype) == "kernel"
        self.state_step = None
        if cache.lane_state:
            # ``"kernel"`` or ``"plain"``: what the lane pool's decode rows run in the span's state layers, the kernel that
            # moves each live lane's matrices once where they lie in the state pool or the plain form on a layer's slice of
            # it. ops/linear_attention.py ``gated_delta_step_path`` is asked here as ``gated_delta_pooled`` asks it in the
            # step: with the pool as the step programs carry it (``pool_descriptors``) and one row a lane
            leaves = tuple(jax.ShapeDtypeStruct((len(cache.state_layers), n_lanes, *shape), dtype) for shape, dtype in cache.lane_state)
            self.state_step = linear_attention.gated_delta_step_path(linear_attention.StatePool(leaves, 0), 1)
        # a family that declares its layers' windows: the windowed layers' windows, and the last position each lane fed (for
        # the batcher's occupancy_info, read back through ``window_pages``)
        self.windows = [w for w in (cache.layer_windows or ()) if w]
        self.lane_pos = np.zeros(n_lanes, np.int64)
        self.lane_first = np.zeros(n_lanes, np.int64)  # the first position of the rows it fed then (a chunk's; a decode row's own)

    def new_stats(self) -> dict:
        """The counters this span's content opens, zeroed: the keys of the batcher's ``stats`` that ``count_step`` adds to.
        On every paged pool, the table slots the step programs read against those they are handed (attn_pages_kernel: of the
        slots read, those the decode walk's kernel fetched, each live lane to its own end; 0 where the composed walk runs:
        ``walks`` says which). For a family that declares its layers' windows only: of the pages the decoding lanes hold in
        windowed layers those their windows still reach (summed over steps). And the content's own (``_CONTENT_KEYS``)."""
        keys = ("attn_pages_gathered", "attn_pages_tabled", "attn_pages_kernel", *(("window_pages_held", "window_pages_in_reach") if self.windows else ()))
        # a pool that keeps pages by group: pages its windowed groups gave back (the batcher adds them where it frees them),
        # and, summed step by step over the lanes that fed rows, the bytes their pages hold against what they would hold
        # with every page kept, and the (row, cached position) pairs the step's rows score, a layer
        grouped = ("window_pages_released", "kv_bytes_held", "kv_bytes_unfreed", "attn_score_pairs") if self.grouped else ()
        return dict.fromkeys((*keys, *grouped, *_CONTENT_KEYS[self.cache.content]), 0)

    def count_step(self, stats: dict, positions, lane_held, *, seq: int = 1, chunk=None, group_held=None) -> None:
        """Add one paged step's counters to ``stats`` (the batcher's, on its compute thread), every one from the step's shapes
        and the positions it was started with: ``lanes``, the lanes that fed a row, is reckoned once, and the pages a lane
        holds are ``lane_held``'s, kept where the tables are written, so that nothing here walks the tables. ``seq`` is a
        verify's rows a lane, ``chunk`` the (lane, first position, tokens) of a mixed step's prompt chunk, ``group_held``
        a grouped pool's pages a lane a group (``lane_held`` is then its first group's)."""
        lanes = np.flatnonzero(positions < self.max_length)  # the idle sentinel is max_length
        self._attention_reads(stats, positions, lanes, lane_held, seq, chunk, group_held)
        content = self.cache.content
        if content is None:
            return
        # the pages the lanes that fed a row and a mixed step's chunk's lane hold
        pages = int(lane_held[lanes].sum()) + (0 if chunk is None else int(lane_held[chunk[0]]))
        if content == "state":
            self._state_reads(stats, int(lanes.size), pages, chunk)
        elif content == "index":
            self._sparse_reads(stats, positions[lanes], pages, chunk)
        else:
            self._latent_reads(stats, positions[lanes], pages, chunk)

    def window_pages(self, lanes, lane_held, group_held=None) -> Tuple[int, int]:
        """(held, in reach): the pages ``lanes`` hold, once a windowed layer of the span, and those of them a layer's window
        still reaches from the last position the lane fed. In a pool of one group the rest are held until the session ends;
        a grouped pool (``group_held``: its pages a lane, a group) holds in a windowed group what the rows a lane last fed
        reach between them, a chunk's first row's window to its last row, and has given the rest back."""
        if group_held is not None:
            first, pos, total, reach = self.lane_first[lanes], self.lane_pos[lanes], 0, 0
            for (window, blocks), held in zip(self.cache.page_groups, group_held):
                if window is None:
                    continue
                pages = pos // self.page_size - np.maximum(first - window + 1, 0) // self.page_size + 1
                total += int(held[lanes].sum()) * len(blocks)
                reach += int(np.minimum(pages, held[lanes]).sum()) * len(blocks)
            return total, reach
        held, pos, reach = lane_held[lanes], self.lane_pos[lanes], 0
        for window in self.windows:
            pages = pos // self.page_size - np.maximum(pos - window + 1, 0) // self.page_size + 1
            reach += int(np.minimum(pages, held).sum())
        return int(held.sum()) * len(self.windows), reach

    # ------------------------------------------------------------- the attention over pages

    def _attention_reads(self, stats, positions, lanes, lane_held, seq, chunk, group_held=None) -> None:
        """The attention counters of one paged step, from the positions the step was started with: of the table slots its
        programs are handed (every lane of the pool's, a layer that keeps keys and values), those they read. A decode row's
        walk reads whole blocks up to the longest live lane's last page, for every lane; a verify's ``seq`` rows and the
        ``chunk`` of a mixed step, at its bucket, gather the slots in reach. For a family that declares its layers' windows,
        the window counters besides."""
        cache, layers = self.cache, self.cache.page_layers
        last = positions[lanes] + (seq - 1)
        by_kernel = 0
        if seq > 1:
            read = self.n_lanes * self._pages_gathered(seq)
        elif self.selects or cache.latent_row is not None or not last.size:
            # the chosen positions' rows are fetched one by one, a latent row's walk counts itself (each lane to its own
            # end where the kernel runs): ``_sparse_reads`` / ``_latent_reads`` add their pages' worth
            read = 0
        else:
            read, by_kernel = pfa.pages_walked(self.walks, last, self.page_size, self.n_lanes)
        stats["attn_pages_gathered"] += read
        stats["attn_pages_kernel"] += by_kernel
        stats["attn_pages_tabled"] += self.n_lanes * self.max_pages * layers
        if chunk is not None:
            lane, first, take = chunk
            if cache.latent_row is not None:  # the chunk's walk ends with the block that holds its last row
                stats["attn_pages_gathered"] += layers * latent_attention.chunk_reads(self.max_pages, self.page_size, first, take) // self.page_size
            elif not self.selects:
                stats["attn_pages_gathered"] += self._pages_gathered(self._bucket(take))
            stats["attn_pages_tabled"] += self.max_pages * layers
        if not self.windows:
            return
        last = last.astype(np.int64)
        began = positions[lanes].astype(np.int64)
        if chunk is not None:
            lanes, last, began = np.append(lanes, lane), np.append(last, first + take - 1), np.append(began, first)
        self.lane_pos[lanes], self.lane_first[lanes] = last, began
        held, reach = self.window_pages(lanes, lane_held, group_held)
        stats["window_pages_held"] += held
        stats["window_pages_in_reach"] += reach
        if group_held is not None:
            # the pages up to each lane's last row, in every group; a row at position p scores min(p + 1, window) cached
            # positions in a layer: a decode row its own, a chunk's rows theirs
            unfreed = int((last // self.page_size + 1).sum())
            rows = began[: began.size - (chunk is not None)] + 1
            of_chunk = () if chunk is None else np.arange(first, first + take, dtype=np.int64) + 1
            for (window, blocks), nbytes, held in zip(self.cache.page_groups, self.group_page_bytes, group_held):
                stats["kv_bytes_held"] += int(held[lanes].sum()) * nbytes
                stats["kv_bytes_unfreed"] += unfreed * nbytes
                cap = window or self.max_length
                stats["attn_score_pairs"] += (int(np.minimum(rows, cap).sum()) + int(np.minimum(of_chunk, cap).sum())) * len(blocks)

    def _pages_gathered(self, q_len: int) -> int:
        """Table slots one lane's ``q_len`` rows gather over the span's layers where a paged step program makes the dense view
        (a prompt's chunk, a verify's rows; ops/paged_flash_attention.py ``window_pages``: a windowed layer gathers the pages
        in its reach, a full one its whole table row)."""
        return sum(layers * pfa.window_pages(w, q_len, self.page_size, self.max_pages) for w, layers in self._window_layers)

    def _decode_walks(self) -> tuple:
        """``((window, layers, block, cut, path), ...)``: how a decode step's programs walk the lane pool's tables, a distinct
        attention call of the span's layers: which walk runs (ops/paged_flash_attention.py ``decode_walk_path``: the kernel
        that reads each lane's own pages, or the composed walk), the block's width in slots (``walk_kernel_block_pages`` /
        ``walk_block_pages``) and whether the table row is first cut to the window's reach. ``decode_walk_path`` is asked here
        as ``composed_paged_attend`` asks it in the step: with the pool's form as a step's attention is handed it
        (``pool_descriptors``) and with what the family says its blocks hand their attention beside the plain call
        (``ModelFamily.block_attention``). Fixed with the pool's geometry: asked once."""
        cache, n_lanes, max_pages, page_size = self.cache, self.n_lanes, self.max_pages, self.page_size
        if cache.latent_row is not None:  # ops/latent_attention.py ``decode_reads`` counts its own walk: ``_latent_reads``
            return ()
        if self.selects:
            return ()  # every decode row fetches the positions it chose, a row each (ops/sparse_attention.py): no walk runs
        quantised = cache.kv_quant_type != "none"
        itemsize = 2 if quantised else cache.cache_dtype.itemsize  # a quantised pool reads as bf16
        hkv, d = cache.kv_heads, cache.head_dim
        pool = jax.ShapeDtypeStruct((1, page_size, *cache.pool_row), cache.cache_dtype)
        pool = paged_attention.PagedPool(pool, pool) if quantised else pool
        walks = []
        for (window, extra), layers in collections.Counter(cache.walk_calls[i] for i in cache.kv_layers).items():
            if "traced_window" in extra:  # the walk is handed an array: it cuts nothing and masks by it
                window, handed = None, jax.ShapeDtypeStruct((), jnp.int32)
            else:
                handed = window
            width = pfa.window_pages(window, 1, page_size, max_pages)
            path = pfa.decode_walk_path(
                pool, (n_lanes, 1, hkv, d), (n_lanes, width), alibi="alibi" in extra, softcap="softcap" in extra, window=handed
            )
            if path == "kernel":
                block = pfa.walk_kernel_block_pages(width, page_size, hkv, d, itemsize)
            else:
                block = pfa.walk_block_pages(n_lanes, width, page_size, hkv, d, itemsize)
            walks.append((window, layers, block, width < max_pages, path))
        return tuple(walks)

    # ------------------------------------------------------------- what the lanes hold beside or in place of keys and values

    def _state_reads(self, stats, rows: int, pages: int, chunk) -> None:
        """``_STATE_KEYS``: every lane that fed a row (``rows`` of them) took the one-step form in each state layer, the ``chunk``
        of a mixed step the chunked form; and what those lanes hold, their slots in the state pool and their ``pages``."""
        layers = len(self.cache.state_layers)
        stats["linattn_recurrent_tokens"] += rows * layers
        if self.state_step == "kernel":
            stats["linattn_kernel_tokens"] += rows * layers
        if chunk is not None:
            stats["linattn_chunk_tokens"] += int(chunk[2]) * layers
        stats["state_bytes_held"] += (rows + (chunk is not None)) * self.state_bytes
        stats["kv_bytes_held"] += pages * self.page_bytes

    def _sparse_reads(self, stats, last: np.ndarray, pages: int, chunk) -> None:
        """``_SPARSE_KEYS``: what the live lanes' rows at positions ``last`` and the ``chunk`` of a mixed step made the programs
        score and fetch (ops/sparse_attention.py has the arithmetic), and the bytes those lanes' ``pages`` hold."""
        n_lanes, max_pages, page_size = self.n_lanes, self.max_pages, self.page_size
        topk, layers, selects = self.cache.index_keep, len(self.cache.kv_layers), self.selects
        contexts = [int(p) + 1 for p in last]
        scored = read = pairs = 0
        if contexts:
            scored, read = sparse_attention.decode_reads(n_lanes, max_pages, page_size, topk, max(contexts)) if selects else (0, sum(contexts))
            pairs = scored  # one query row a lane
        held, over, rows = sum(contexts), sum(c > topk for c in contexts), len(contexts)
        if chunk is not None:
            _, first, take = chunk
            c_scored, c_pairs, c_read = (
                sparse_attention.chunk_reads(max_pages, page_size, topk, first, take, self._bucket(take)) if selects else (0, 0, first + take)
            )
            scored, pairs, read, held = scored + c_scored, pairs + c_pairs, read + c_read, held + first + take
            over, rows = over + max(first + take - max(first, topk), 0), rows + take
        for key, n in zip(_SPARSE_KEYS, (over, rows - over, scored, pairs, read, held)):
            stats[key] += n * layers
        if selects:
            stats["attn_pages_gathered"] += -(-read * layers // page_size)
        index = pages * page_size * self.cache.index_bytes_per_token()
        stats["index_bytes_held"] += index
        stats["kv_bytes_held"] += pages * self.page_bytes - index

    def _latent_reads(self, stats, last: np.ndarray, pages: int, chunk) -> None:
        """``_LATENT_KEYS``: what the live lanes' rows at positions ``last`` (the absorbed form) and the ``chunk`` of a mixed step
        (the expanded one) made the programs read and score (ops/latent_attention.py has the arithmetic), and the bytes of
        latent rows those lanes' ``pages`` hold."""
        layers, la = self.cache.page_layers, latent_attention
        contexts = [int(p) + 1 for p in last]
        read = la.decode_reads(self.n_lanes, self.max_pages, self.page_size, contexts, kernel=self.latent_kernel)
        pairs = sum(contexts)
        expanded = held = rows = 0
        if chunk is not None:
            _, first, rows = chunk
            expanded, held = la.chunk_reads(self.max_pages, self.page_size, first, rows), first + rows
            pairs += rows * first + rows * (rows + 1) // 2  # each row of the chunk against the positions up to its own
        for key, n in zip(_LATENT_KEYS, (read, sum(contexts), len(contexts), rows, expanded, held, pairs)):
            stats[key] += n * layers
        stats["attn_pages_gathered"] += read * layers // self.page_size
        stats["latent_bytes_held"] += pages * self.page_bytes
