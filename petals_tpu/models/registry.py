"""Model-family registry (counterpart of reference src/petals/utils/auto_config.py:22-52,
which dispatches on HF ``config.model_type``).

Each family registers a ``ModelFamily`` describing how to build block configs,
apply a block, and map HF checkpoint tensors to our parameter trees.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

_FAMILIES: Dict[str, "ModelFamily"] = {}


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
    # Underlying block architecture ("" -> same as name). Derived families
    # built via dataclasses.replace (qwen2/mistral over llama) inherit it, so
    # architecture-keyed tables (quantizable leaves, fuse groups in
    # utils/convert_block.py) resolve without per-alias entries.
    block_arch: str = ""
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


def register_family(family: ModelFamily) -> ModelFamily:
    _FAMILIES[family.name] = family
    return family


def get_family(model_type: str) -> ModelFamily:
    if model_type not in _FAMILIES:
        raise KeyError(
            f"Unsupported model family {model_type!r}; known: {sorted(_FAMILIES)}"
        )
    return _FAMILIES[model_type]


def known_families() -> tuple:
    return tuple(sorted(_FAMILIES))
