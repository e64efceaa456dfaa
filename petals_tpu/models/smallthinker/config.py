"""SmallThinker block config (``config.json`` of PowerInfer/SmallThinker-21BA3B-Instruct, model_type ``smallthinker``;
the installed transformers has no such class, so the keys are read as published; the reference has no such family).

Which layers rotate and which are windowed is read per layer from ``rope_layout`` and ``sliding_window_layout`` (1: yes),
not from a period."""

from __future__ import annotations

import dataclasses
from typing import Tuple


@dataclasses.dataclass(frozen=True)
class SmallThinkerBlockConfig:
    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    moe_ffn_hidden_size: int  # one expert's width
    num_hidden_layers: int
    num_experts: int
    num_experts_per_tok: int
    norm_topk_prob: bool
    rms_norm_eps: float
    rope_theta: float
    sliding_window_size: int
    rope_layout: Tuple[int, ...]  # per block: 1 rotates q and k, 0 has no positional signal but the causal mask
    sliding_window_layout: Tuple[int, ...]  # per block: 1 attends within ``sliding_window_size`` positions
    vocab_size: int = 151936
    tie_word_embeddings: bool = False

    @classmethod
    def from_hf_config(cls, hf_config) -> "SmallThinkerBlockConfig":
        get = lambda key, default=None: getattr(hf_config, key, default)
        # what the block does not compute is refused here, at load, not served wrong
        if get("rope_scaling"):
            raise NotImplementedError(f"smallthinker: rope_scaling {get('rope_scaling')!r} is not supported (published: null)")
        if not get("moe_primary_router_apply_softmax", True):
            raise NotImplementedError("smallthinker: moe_primary_router_apply_softmax false is not supported (published: true)")
        n = hf_config.num_hidden_layers
        rope, sliding = get("rope_layout"), get("sliding_window_layout")
        if rope is None or sliding is None or len(rope) < n or len(sliding) < n:
            raise ValueError(f"smallthinker: rope_layout / sliding_window_layout name fewer than num_hidden_layers {n} layers")
        window = int(get("sliding_window_size") or 0)
        if any(sliding[:n]) and window <= 0:
            raise ValueError("smallthinker: sliding_window_layout names windowed layers and sliding_window_size is not set")
        return cls(
            hidden_size=hf_config.hidden_size,
            num_attention_heads=hf_config.num_attention_heads,
            num_key_value_heads=hf_config.num_key_value_heads,
            head_dim=get("head_dim") or hf_config.hidden_size // hf_config.num_attention_heads,
            moe_ffn_hidden_size=hf_config.moe_ffn_hidden_size,
            num_hidden_layers=n,
            num_experts=hf_config.moe_num_primary_experts,
            num_experts_per_tok=hf_config.moe_num_active_primary_experts,
            norm_topk_prob=bool(get("norm_topk_prob", True)),
            rms_norm_eps=hf_config.rms_norm_eps,
            rope_theta=float(get("rope_theta", 1.5e6)),
            sliding_window_size=window,
            rope_layout=tuple(int(bool(r)) for r in rope[:n]),
            sliding_window_layout=tuple(int(bool(s)) for s in sliding[:n]),
            vocab_size=hf_config.vocab_size,
            tie_word_embeddings=get("tie_word_embeddings", False),
        )
