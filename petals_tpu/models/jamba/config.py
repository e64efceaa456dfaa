"""Jamba block config (``config.json`` of ai21labs/AI21-Jamba2-3B, model_type
``jamba``; transformers 4.57.6 ``models/jamba/configuration_jamba.py``; the
reference has no such family)."""

from __future__ import annotations

import dataclasses
from typing import Tuple

MAMBA, ATTENTION = "mamba", "attention"


@dataclasses.dataclass(frozen=True)
class JambaBlockConfig:
    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    intermediate_size: int
    num_hidden_layers: int
    rms_norm_eps: float
    layer_types: Tuple[str, ...]  # per block: mamba | attention
    mamba_d_inner: int  # channels of the state-space mixer: ``mamba_expand`` x ``hidden_size``
    mamba_d_state: int
    mamba_d_conv: int
    mamba_dt_rank: int
    vocab_size: int = 65536
    tie_word_embeddings: bool = True

    @classmethod
    def from_hf_config(cls, hf_config) -> "JambaBlockConfig":
        get = lambda key, default=None: getattr(hf_config, key, default)
        # what the block does not compute is refused here, at load, not served wrong
        if get("hidden_act", "silu") != "silu":
            raise NotImplementedError(f"jamba: hidden_act {get('hidden_act')!r} is not supported (published: silu)")
        if get("num_experts", 1) > 1:
            raise NotImplementedError(
                f"jamba: num_experts {get('num_experts')} is not supported: every layer's feed-forward is served dense "
                f"(published: 1); the routed feed-forward of the family's larger models is not served yet"
            )
        if get("sliding_window") is not None:
            raise NotImplementedError(
                f"jamba: sliding_window {get('sliding_window')!r} is not supported: the attention layers are served over "
                f"the whole context (published: null)"
            )
        if get("mamba_proj_bias", False):
            raise NotImplementedError("jamba: mamba_proj_bias true is not supported (published: false)")
        if not get("mamba_conv_bias", True):
            raise NotImplementedError("jamba: mamba_conv_bias false is not supported (published: true)")
        n = hf_config.num_hidden_layers
        period, offset = hf_config.attn_layer_period, hf_config.attn_layer_offset
        return cls(
            hidden_size=hf_config.hidden_size,
            num_attention_heads=hf_config.num_attention_heads,
            num_key_value_heads=get("num_key_value_heads") or hf_config.num_attention_heads,
            head_dim=hf_config.hidden_size // hf_config.num_attention_heads,
            intermediate_size=hf_config.intermediate_size,
            num_hidden_layers=n,
            rms_norm_eps=hf_config.rms_norm_eps,
            layer_types=tuple(ATTENTION if i % period == offset else MAMBA for i in range(n)),
            mamba_d_inner=hf_config.mamba_expand * hf_config.hidden_size,
            mamba_d_state=hf_config.mamba_d_state,
            mamba_d_conv=hf_config.mamba_d_conv,
            mamba_dt_rank=hf_config.mamba_dt_rank,  # JambaConfig has worked "auto" out
            vocab_size=hf_config.vocab_size,
            tie_word_embeddings=get("tie_word_embeddings", True),
        )
