"""K-EXAONE block config (``config.json`` of LGAI-EXAONE/K-EXAONE-236B-A23B,
model_type ``exaone_moe``; the installed transformers has no such class, so
the keys are read as published; the reference has no such family).

Two added keys say which share of each layer's routed experts a server holds:
``num_experts`` is what its model directory HOLDS, and ``expert_share:
{"routed": 128, "first": 0}`` gives the router's width and which of the
routed experts the first held one is. Without ``expert_share`` a server holds
all it routes over."""

from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

from petals_tpu.utils.logging import get_logger

logger = get_logger(__name__)

SLIDING, FULL = "sliding_attention", "full_attention"
DENSE, SPARSE = "dense", "sparse"


@functools.cache
def _say_unserved(nextn: int) -> None:
    """Once a process and a count."""
    logger.info(f"exaone_moe: num_nextn_predict_layers {nextn} is not served: the prediction layer sits behind "
                f"the head, no server holds it and the client does not draft with it")


@dataclasses.dataclass(frozen=True)
class ExaoneMoeBlockConfig:
    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    intermediate_size: int  # the dense layers' width
    moe_intermediate_size: int  # one expert's width
    num_hidden_layers: int
    num_experts: int  # held here
    num_experts_routed: int  # the router's width
    first_expert: int  # which of the routed the first held one is
    num_experts_per_tok: int
    num_shared_experts: int
    norm_topk_prob: bool
    routed_scaling_factor: float
    rms_norm_eps: float
    rope_theta: float
    sliding_window: int
    layer_types: Tuple[str, ...]  # per block: sliding_attention | full_attention
    mlp_layer_types: Tuple[str, ...]  # per block: dense | sparse
    vocab_size: int = 153600
    tie_word_embeddings: bool = False

    @classmethod
    def from_hf_config(cls, hf_config) -> "ExaoneMoeBlockConfig":
        get = lambda key, default=None: getattr(hf_config, key, default)
        # what the block does not compute is refused here, at load, not served wrong
        for key in ("n_group", "topk_group"):
            if get(key, 1) != 1:
                raise NotImplementedError(f"exaone_moe: {key} {get(key)!r} is not supported (published: 1, no group limit)")
        if get("scoring_func", "sigmoid") != "sigmoid":
            raise NotImplementedError(f"exaone_moe: scoring_func {get('scoring_func')!r} is not supported (published: sigmoid)")
        if get("hidden_act", "silu") != "silu":
            raise NotImplementedError(f"exaone_moe: hidden_act {get('hidden_act')!r} is not supported (published: silu)")
        rope = dict(get("rope_parameters") or {})
        scaling = get("rope_scaling") or (rope if rope.get("rope_type", "default") != "default" else None)
        if scaling:
            raise NotImplementedError(f"exaone_moe: rope_scaling {scaling!r} is not supported (published: rope_type default)")
        n = hf_config.num_hidden_layers
        layer_types = get("layer_types")
        if layer_types is None:  # "LLLG": L a sliding layer, G a full one
            pattern = get("sliding_window_pattern", "LLLG")
            layer_types = [SLIDING if pattern[i % len(pattern)] == "L" else FULL for i in range(n)]
        mlp_types = get("mlp_layer_types")
        if mlp_types is None:
            mlp_types = [DENSE if i < get("first_k_dense_replace", 0) else SPARSE for i in range(n)]
        if len(layer_types) < n or len(mlp_types) < n:
            raise ValueError(f"exaone_moe: layer_types / mlp_layer_types name fewer than num_hidden_layers {n} layers")
        if set(layer_types) - {SLIDING, FULL} or set(mlp_types) - {DENSE, SPARSE}:
            raise NotImplementedError(f"exaone_moe: unknown layer type in {sorted(set(layer_types) | set(mlp_types))}")
        share = dict(get("expert_share") or {})
        held, routed, first = hf_config.num_experts, share.get("routed", hf_config.num_experts), share.get("first", 0)
        if not 0 <= first <= first + held <= routed:
            raise ValueError(f"exaone_moe: experts [{first}, {first + held}) are not among the {routed} routed over")
        nextn = get("num_nextn_predict_layers", 0)
        if nextn:
            _say_unserved(nextn)
        return cls(
            hidden_size=hf_config.hidden_size,
            num_attention_heads=hf_config.num_attention_heads,
            num_key_value_heads=hf_config.num_key_value_heads,
            head_dim=get("head_dim") or hf_config.hidden_size // hf_config.num_attention_heads,
            intermediate_size=hf_config.intermediate_size,
            moe_intermediate_size=hf_config.moe_intermediate_size,
            num_hidden_layers=n,
            num_experts=held,
            num_experts_routed=routed,
            first_expert=first,
            num_experts_per_tok=hf_config.num_experts_per_tok,
            num_shared_experts=get("num_shared_experts", 0),
            norm_topk_prob=bool(get("norm_topk_prob", True)),
            routed_scaling_factor=float(get("routed_scaling_factor", 1.0)),
            rms_norm_eps=hf_config.rms_norm_eps,
            rope_theta=float(rope.get("rope_theta", get("rope_theta", 1e6))),
            sliding_window=int(get("sliding_window") or 0),
            layer_types=tuple(layer_types[:n]),
            mlp_layer_types=tuple(mlp_types[:n]),
            vocab_size=hf_config.vocab_size,
            tie_word_embeddings=get("tie_word_embeddings", False),
        )
