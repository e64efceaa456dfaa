"""OLMoE family block config (HF ``OlmoeConfig``, model_type ``olmoe``; the
reference has no such family)."""

from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class OlmoeBlockConfig:
    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    intermediate_size: int  # one expert's width
    num_hidden_layers: int
    num_experts: int
    num_experts_per_tok: int
    norm_topk_prob: bool
    rms_norm_eps: float
    rope_theta: float = 10000.0
    clip_qkv: Optional[float] = None
    vocab_size: int = 50304
    tie_word_embeddings: bool = False

    @classmethod
    def from_hf_config(cls, hf_config) -> "OlmoeBlockConfig":
        # what the block below does not compute is refused here, at load, not served wrong
        if getattr(hf_config, "rope_scaling", None):
            raise NotImplementedError(f"olmoe: rope_scaling {hf_config.rope_scaling!r} is not supported (published: null)")
        if getattr(hf_config, "attention_bias", False):
            raise NotImplementedError("olmoe: attention_bias true is not supported (published: false)")
        if getattr(hf_config, "hidden_act", "silu") != "silu":
            raise NotImplementedError(f"olmoe: hidden_act {hf_config.hidden_act!r} is not supported (published: silu)")
        return cls(
            hidden_size=hf_config.hidden_size,
            num_attention_heads=hf_config.num_attention_heads,
            num_key_value_heads=hf_config.num_key_value_heads,
            head_dim=hf_config.hidden_size // hf_config.num_attention_heads,
            intermediate_size=hf_config.intermediate_size,
            num_hidden_layers=hf_config.num_hidden_layers,
            num_experts=hf_config.num_experts,
            num_experts_per_tok=hf_config.num_experts_per_tok,
            norm_topk_prob=bool(getattr(hf_config, "norm_topk_prob", False)),
            rms_norm_eps=hf_config.rms_norm_eps,
            rope_theta=getattr(hf_config, "rope_theta", 10000.0),
            clip_qkv=getattr(hf_config, "clip_qkv", None),
            vocab_size=hf_config.vocab_size,
            tie_word_embeddings=getattr(hf_config, "tie_word_embeddings", False),
        )
