"""Qwen3-Next block config (``config.json`` of Qwen/Qwen3-Next-80B-A3B-Instruct,
model_type ``qwen3_next``; the layer is transformers 4.57.6
``models/qwen3_next/modeling_qwen3_next.py``; the reference has no such family).

Two added keys say which share of each layer's routed experts a server holds,
as K-EXAONE's do: ``num_experts`` is what its model directory HOLDS, and
``expert_share: {"routed": 512, "first": 0}`` gives the router's width and
which of the routed experts the first held one is. Without ``expert_share`` a
server holds all it routes over."""

from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

from petals_tpu.utils.logging import get_logger

logger = get_logger(__name__)

LINEAR, FULL = "linear_attention", "full_attention"


@functools.cache
def _say_unserved() -> None:
    """Once a process."""
    logger.info("qwen3_next: the multi-token prediction layer (mtp.*) is not served: it sits behind the head, "
                "no server holds it and the client does not draft with it")


@dataclasses.dataclass(frozen=True)
class Qwen3NextBlockConfig:
    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    rotary_dim: int  # the first dims of a head that are rotated (``partial_rotary_factor`` of ``head_dim``)
    rope_theta: float
    moe_intermediate_size: int  # one expert's width
    shared_expert_intermediate_size: int
    num_hidden_layers: int
    num_experts: int  # held here
    num_experts_routed: int  # the router's width
    first_expert: int  # which of the routed the first held one is
    num_experts_per_tok: int
    norm_topk_prob: bool
    rms_norm_eps: float
    layer_types: Tuple[str, ...]  # per block: linear_attention | full_attention
    linear_num_key_heads: int
    linear_num_value_heads: int  # one state each; a key head serves value heads / key heads of them
    linear_key_head_dim: int
    linear_value_head_dim: int
    linear_conv_kernel_dim: int
    vocab_size: int = 151936
    tie_word_embeddings: bool = False

    @classmethod
    def from_hf_config(cls, hf_config) -> "Qwen3NextBlockConfig":
        get = lambda key, default=None: getattr(hf_config, key, default)
        # what the block does not compute is refused here, at load, not served wrong
        if get("mlp_only_layers"):
            raise NotImplementedError(f"qwen3_next: mlp_only_layers {get('mlp_only_layers')!r} is not supported (published: [], every layer routes)")
        if get("decoder_sparse_step", 1) != 1:
            raise NotImplementedError(f"qwen3_next: decoder_sparse_step {get('decoder_sparse_step')!r} is not supported (published: 1, every layer routes)")
        if get("rope_scaling"):
            raise NotImplementedError(f"qwen3_next: rope_scaling {get('rope_scaling')!r} is not supported (published: null)")
        if get("attention_bias", False):
            raise NotImplementedError("qwen3_next: attention_bias true is not supported (published: false)")
        if get("hidden_act", "silu") != "silu":
            raise NotImplementedError(f"qwen3_next: hidden_act {get('hidden_act')!r} is not supported (published: silu)")
        key_heads, value_heads = hf_config.linear_num_key_heads, hf_config.linear_num_value_heads
        if value_heads % key_heads:
            raise NotImplementedError(
                f"qwen3_next: linear_num_value_heads {value_heads} is no multiple of linear_num_key_heads {key_heads} "
                f"(published: 32 and 16, a key head serves two value heads)"
            )
        n = hf_config.num_hidden_layers
        layer_types = get("layer_types")
        if layer_types is None:
            interval = get("full_attention_interval", 4)
            layer_types = [LINEAR if (i + 1) % interval else FULL for i in range(n)]
        if len(layer_types) < n:
            raise ValueError(f"qwen3_next: layer_types names fewer than num_hidden_layers {n} layers")
        if set(layer_types) - {LINEAR, FULL}:
            raise NotImplementedError(f"qwen3_next: unknown layer type in {sorted(set(layer_types))}")
        share = dict(get("expert_share") or {})
        held, routed, first = hf_config.num_experts, share.get("routed", hf_config.num_experts), share.get("first", 0)
        if not 0 <= first <= first + held <= routed:
            raise ValueError(f"qwen3_next: experts [{first}, {first + held}) are not among the {routed} routed over")
        _say_unserved()
        head_dim = get("head_dim") or hf_config.hidden_size // hf_config.num_attention_heads
        return cls(
            hidden_size=hf_config.hidden_size,
            num_attention_heads=hf_config.num_attention_heads,
            num_key_value_heads=hf_config.num_key_value_heads,
            head_dim=head_dim,
            rotary_dim=int(head_dim * get("partial_rotary_factor", 1.0)),
            rope_theta=float(get("rope_theta", 10000.0)),
            moe_intermediate_size=hf_config.moe_intermediate_size,
            shared_expert_intermediate_size=hf_config.shared_expert_intermediate_size,
            num_hidden_layers=n,
            num_experts=held,
            num_experts_routed=routed,
            first_expert=first,
            num_experts_per_tok=hf_config.num_experts_per_tok,
            norm_topk_prob=bool(get("norm_topk_prob", True)),
            rms_norm_eps=hf_config.rms_norm_eps,
            layer_types=tuple(layer_types[:n]),
            linear_num_key_heads=key_heads,
            linear_num_value_heads=value_heads,
            linear_key_head_dim=hf_config.linear_key_head_dim,
            linear_value_head_dim=hf_config.linear_value_head_dim,
            linear_conv_kernel_dim=hf_config.linear_conv_kernel_dim,
            vocab_size=hf_config.vocab_size,
            tie_word_embeddings=get("tie_word_embeddings", False),
        )
