"""Model-family registry (counterpart of reference src/petals/utils/auto_config.py:22-52,
which dispatches on HF ``config.model_type``).

Each family registers a ``ModelFamily`` describing how to build block configs,
apply a block, and map HF checkpoint tensors to our parameter trees, and what
the framework does with its leaves: how they shard under tensor parallelism,
which are quantized and fused, which a LoRA adapter targets. Those facts are
data on the family, set where it is registered, and ``parallel/tp.py``,
``utils/convert_block.py`` and ``utils/peft.py`` read them through
``get_family``; a family built with ``dataclasses.replace`` over another
(mistral, qwen2, phi3, gemma over llama) inherits them with no line of its
own. What a lane holds for a block (pages of keys and values, a state, an
index row, a latent row, how many rows a block) is declared through the
``block_*`` hooks below and read by ``server/span_cache.py`` alone
(``SpanCache``: the layout, what is refused, the pools, the bytes, the
counters). So a new family of an existing kind of cache touches
``models/<family>/``, one import in ``models/__init__.py``, and the
benchmark's own data files; a new KIND of cache touches
``server/span_cache.py`` (a row of ``CONTENTS``, its fields, pools and
counters) and the step program that carries it (``server/backend.py``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Hashable, Mapping, Optional

_FAMILIES: Dict[str, "ModelFamily"] = {}
ATTENTION_EXTRAS = frozenset({"alibi", "softcap", "traced_window"})  # what ``ModelFamily.block_attention`` may name


@dataclasses.dataclass(frozen=True)
class ModelFamily:
    """Everything the framework needs to serve/consume one model family."""

    name: str  # HF model_type, e.g. "llama"
    config_from_hf: Callable[[Any], Any]  # HF PretrainedConfig -> BlockConfig
    block_apply: Callable  # (params, hidden, kv, position, cfg, ...) -> (hidden, kv)
    hf_block_prefixes: tuple  # checkpoint prefixes of block i, with {i} placeholder
    hf_to_block_params: Callable  # (dict[str, np.ndarray], cfg) -> params pytree
    block_param_shapes: Optional[Callable] = None  # cfg -> pytree of jax.ShapeDtypeStruct
    # a block with routed experts (models/moe.py): cfg -> MoeDims
    moe_dims: Optional[Callable] = None
    # cfg -> {leaf: PartitionSpec} for the stacked block leaves over the "tp"
    # mesh axis (parallel/tp.py). None: the family declares none, and a TP
    # mesh is refused by name
    tp_pspecs: Optional[Callable] = None
    # the big matmul leaves that quantize (utils/convert_block.py); norms,
    # biases and routers stay dense
    quantizable_leaves: frozenset = frozenset()
    # (fused_w, parts, fused_b, bias_parts): leaves merged into one matmul
    # each for quantized single-chip serving (utils/convert_block.py)
    fuse_groups: tuple = ()
    # HF projection name -> the leaf a LoRA adapter wraps, or None for a
    # projection this build cannot wrap (utils/peft.py)
    lora_targets: Mapping = dataclasses.field(default_factory=dict)
    # leaf NAMES whose loaded dtype is preserved by the param casters (e.g.
    # gemma's (1+w)-folded norms must stay float32 for the fold to be exact
    # under bf16 serving; rms_norm upcasts anyway, so this is free)
    cast_exempt: tuple = ()
    # Client-side (embeddings + final norm + LM head), filled by model.py modules:
    hf_client_prefixes: tuple = ()  # checkpoint prefixes of client-held tensors
    hf_to_client_params: Optional[Callable] = None  # (dict, cfg) -> params pytree
    client_embed: Optional[Callable] = None  # (params, input_ids, cfg) -> hidden
    client_head: Optional[Callable] = None  # (params, hidden, cfg) -> logits (f32)
    client_norm: Optional[Callable] = None  # (params, hidden, cfg) -> final-norm'd hidden
    # Sequence classification (reference models/*/model.py *ForSequenceClassification):
    hf_cls_prefixes: tuple = ()  # checkpoint prefixes incl. the score head
    hf_to_cls_params: Optional[Callable] = None  # (dict, cfg) -> params pytree
    cls_head: Optional[Callable] = None  # (params, hidden, cfg) -> per-position label logits
    # block_apply accepts ring_mesh= for sequence-parallel attention on the
    # stateless (no-KV) path; ALiBi bias and sliding windows ride the ring
    # on global positions (ops/ring_attention.py)
    supports_ring_attention: bool = False
    # A family whose blocks are not all alike (a dense first layer before
    # expert layers, windowed and full attention in turns): (cfg, a block's
    # ABSOLUTE index in the model) -> a hashable kind. Blocks of one kind
    # share a parameter tree and a program; ``block_apply`` then takes the
    # kind as ``kind=``, and ``block_param_shapes``, ``hf_to_block_params``
    # and ``moe_dims`` as a further argument (``moe_dims``: None for a kind
    # without experts). None: every block is of one kind, and the four are
    # called without. Callers go through the ``*_for`` methods below.
    block_kind: Optional[Callable] = None
    # (cfg, kind) -> the STATIC window of that kind's attention in positions,
    # None for full attention. A family that declares it has its gathered and
    # held pages counted for the batcher (server/span_cache.py ``LanePool``)
    block_window: Optional[Callable] = None
    # (cfg, kind) -> what a lane holds for a block of that kind IN PLACE of pages of keys and values: a
    # state of fixed size whatever the context, as ``((shape, dtype), ...)`` a lane (dtype None: the
    # cache's own); None for a kind that caches keys and values. The framework keeps the states in a
    # pool of their own beside the pages, ``[that kind's layers, lanes, *shape]`` a leaf, and hands a
    # block its lanes' slices as ``kv``; a row at position 0 starts from zeros. A state cannot be cut
    # back to an earlier position, so what needs that (a rollback, a reused prefix, speculative
    # verify) is refused for a family that declares one (server/span_cache.py ``SpanCache.refuse``; ``state_layers``)
    block_state: Optional[Callable] = None
    # (cfg, kind) -> what a position caches BESIDE its key and value in a block of that kind: an index row,
    # ``(width, dtype, keep)`` (dtype None: the cache's own), that a learned sparse attention scores to choose the
    # ``keep`` cached positions a row attends to (ops/sparse_attention.py); None for a kind without one. Unlike a
    # state it grows with the context: the framework keeps it in pages of its own under the lane's block
    # tables, written, freed and reused with the pages of keys and values, and hands a block ``(k, v, index)``
    # as its ``kv``. Only the paged lane pool's decode, generation and mixed steps carry it; what does not
    # (a private cache, the dense pool, swap, snapshots, a stored prefix, speculative verify, quantised
    # pages, a tp mesh) is refused for a family that declares one (server/span_cache.py ``SpanCache``: ``index_row``)
    block_index: Optional[Callable] = None
    # (cfg, kind) -> what a position caches IN PLACE of its key and value in a block of that kind: a latent row,
    # ``(latent width, rotated key's width)``, one for all heads, that every head's key and value are linear in
    # (ops/latent_attention.py); None for a kind that caches keys and values. The framework keeps it where the
    # pages of keys and values would lie, under the same block tables (the latent a position a row of the first
    # pool, the rotated key in the second, stored as an index row of its width is), and hands a block ``(c, k_pe)``
    # as its ``kv``. Only the paged lane pool's decode, generation and mixed steps carry it; what does not (a
    # private cache, the dense pool, swap, snapshots, a stored prefix, speculative verify, quantised pages, a tp
    # mesh) is refused for a family that declares one (server/span_cache.py ``SpanCache``: ``latent_row``)
    block_latent: Optional[Callable] = None
    # (cfg, kind) -> how many cache rows a position a block of that kind keeps: the number of attention
    # SUB-LAYERS in it, each with a cache of its own (a checkpoint layer that is two attentions and two
    # feed-forwards around one expert layer cannot be split on the wire). None: one, every other family's. The
    # page pools then hold that many layers of pages a block, one after the other, and ``block_apply`` is handed a
    # tuple of that many ``kv``, one a sub-layer in order, each over the span's pools with its own layer's tables:
    # a sub-layer that writes hands the next the pools it wrote (``PagedKV._replace(pool=...)``) and the block
    # returns the tuple of what each returned. Served for a span whose positions cache a latent row
    # (server/span_cache.py ``SpanCache``: ``block_rows``)
    block_sublayers: Optional[Callable] = None
    # (cfg, kind) -> what a block of that kind hands its attention BEYOND the query, the cache, the causal mask and a
    # static window, as names out of ``ATTENTION_EXTRAS``: "alibi" (a bias a head on the scores), "softcap" (a soft
    # cap on them), "traced_window" (a window that is an array, a layer's own out of its parameters, where
    # ``block_window`` / ``cfg.sliding_window`` is a number the program knows). None: the plain call, nearly every
    # family's. What can only take the plain call goes by this and by nothing else of a family: the decode walk's
    # kernel over plain pages (ops/paged_flash_attention.py ``walk_kernel_unsupported``), so the path the batcher's
    # counters count is the one the step runs (server/span_cache.py ``LanePool.walks``;
    # tests/test_paged_kernel.py holds every registered family's block to what it declares here)
    block_attention: Optional[Callable] = None
    # cfg -> what crosses the wire between two blocks where that is NOT a row of ``cfg.hidden_size``: ``(width, mixes)``,
    # the width of the hidden state a block takes and hands on (a residual stream of several rows, flat: the rows
    # ARE the state and cannot be collapsed at a span's edge) and how many times a block mixes it (its wrapped
    # sub-layers, which the batcher counts). None: ``cfg.hidden_size``, every other family's. Everything that sizes a
    # buffer, checks a frame or probes a server reads the width through ``stream_for`` (server/backend.py
    # ``hidden_size``); what cannot carry a state wider than the model yet (deep prompts, a tp mesh, quantised
    # weights, an adapter) is refused for a family that declares one
    block_stream: Optional[Callable] = None

    def kind_of(self, cfg, block_index: int) -> Hashable:
        return None if self.block_kind is None else self.block_kind(cfg, block_index)

    def span_kinds(self, cfg, first_block: int, n_blocks: int) -> list:
        return [self.kind_of(cfg, i) for i in range(first_block, first_block + n_blocks)]

    def apply_for(self, kind: Hashable) -> Callable:
        return self.block_apply if kind is None else functools.partial(self.block_apply, kind=kind)

    def param_shapes_for(self, cfg, kind: Hashable, *dtype):
        return self.block_param_shapes(cfg, *_kind_args(kind), *dtype)

    def block_params_for(self, tensors: dict, cfg, kind: Hashable, **kw):
        return self.hf_to_block_params(tensors, cfg, *_kind_args(kind), **kw)

    def moe_dims_for(self, cfg, kind: Hashable):
        return None if self.moe_dims is None else self.moe_dims(cfg, *_kind_args(kind))

    def state_for(self, cfg, kind: Hashable) -> Optional[tuple]:
        return None if self.block_state is None else self.block_state(cfg, kind)

    def index_for(self, cfg, kind: Hashable) -> Optional[tuple]:
        return None if self.block_index is None else self.block_index(cfg, kind)

    def latent_for(self, cfg, kind: Hashable) -> Optional[tuple]:
        return None if self.block_latent is None else self.block_latent(cfg, kind)

    def sublayers_for(self, cfg, kind: Hashable) -> int:
        return 1 if self.block_sublayers is None else int(self.block_sublayers(cfg, kind))

    def stream_for(self, cfg) -> tuple:
        """``(the width of the hidden state between two blocks, the mixes of it a block)``."""
        if self.block_stream is None:
            return int(cfg.hidden_size), 0
        width, mixes = self.block_stream(cfg)
        return int(width), int(mixes)

    def attention_for(self, cfg, kind: Hashable) -> frozenset:
        extras = frozenset(() if self.block_attention is None else self.block_attention(cfg, kind))
        if not extras <= ATTENTION_EXTRAS:
            raise ValueError(f"{self.name}: block_attention names {sorted(extras - ATTENTION_EXTRAS)}, not of {sorted(ATTENTION_EXTRAS)}")
        return extras


def _kind_args(kind: Hashable) -> tuple:
    """What a family's per-block functions take after ``cfg``: the kind, for a family that has kinds."""
    return () if kind is None else (kind,)


def register_family(family: ModelFamily) -> ModelFamily:
    _FAMILIES[family.name] = family
    return family


def get_family(model_type: str) -> ModelFamily:
    if model_type not in _FAMILIES:
        raise KeyError(
            f"Unsupported model family {model_type!r}; known: {sorted(_FAMILIES)}"
        )
    return _FAMILIES[model_type]


def span_runs(kinds: list) -> list:
    """``[(kind, start, length), ...]``: the runs of consecutive blocks of one
    kind in a span's ``kinds``, ``start`` counted from the span's first block."""
    runs = []
    for i, kind in enumerate(kinds):
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, i, 1])
    return [tuple(run) for run in runs]


def kind_label(kind: Hashable) -> str:
    """A kind as a word for a named scope or a log line."""
    return "-".join(map(str, kind)) if isinstance(kind, tuple) else str(kind)


def known_families() -> tuple:
    return tuple(sorted(_FAMILIES))
