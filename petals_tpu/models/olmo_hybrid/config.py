"""Olmo-Hybrid block config (``config.json`` of allenai/Olmo-Hybrid-7B,
model_type ``olmo_hybrid``; the installed transformers has no such class, so
the keys are read as published; the reference has no such family)."""

from __future__ import annotations

import dataclasses
from typing import Tuple

LINEAR, FULL = "linear_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class OlmoHybridBlockConfig:
    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    intermediate_size: int
    num_hidden_layers: int
    rms_norm_eps: float
    layer_types: Tuple[str, ...]  # per block: linear_attention | full_attention
    linear_num_heads: int  # key heads and value heads, one each a state
    linear_key_head_dim: int
    linear_value_head_dim: int
    linear_conv_kernel_dim: int
    linear_allow_neg_eigval: bool  # beta in (0, 2), so a state's eigenvalues reach -1
    vocab_size: int = 100352
    tie_word_embeddings: bool = False

    @property
    def cache_kv_heads(self) -> int:
        """The kv heads a full layer's CACHE holds: ``num_key_value_heads`` rounded up to the TPU's
        tile of 8 rows, the heads past the published ones all zeros. A pool of ``[..., page_size, 30,
        128]`` lives on the device with the heads outermost (30 rows do not fill a tile), and every step
        program then relays both pools whole on its way in and out (629 MB each at this model's cell:
        tests/test_kernels_lower_tpu.py); 32 heads keep the pool in the layout the programs write."""
        return -(-self.num_key_value_heads // 8) * 8

    @classmethod
    def from_hf_config(cls, hf_config) -> "OlmoHybridBlockConfig":
        get = lambda key, default=None: getattr(hf_config, key, default)
        # what the block does not compute is refused here, at load, not served wrong
        if get("hidden_act", "silu") != "silu":
            raise NotImplementedError(f"olmo_hybrid: hidden_act {get('hidden_act')!r} is not supported (published: silu)")
        if get("attention_bias", False):
            raise NotImplementedError("olmo_hybrid: attention_bias true is not supported (published: false)")
        rope = dict(get("rope_parameters") or {})
        theta = get("rope_theta") if rope.get("rope_theta") is None else rope["rope_theta"]
        if theta is not None or get("rope_scaling"):
            raise NotImplementedError(
                f"olmo_hybrid: rope_theta {theta!r} is not supported: the full-attention layers are served without "
                f"rotary embeddings, as the published config.json has it (rope_theta null)"
            )
        if (get("num_key_value_heads") or hf_config.num_attention_heads) != hf_config.num_attention_heads:
            raise NotImplementedError(
                f"olmo_hybrid: num_key_value_heads {get('num_key_value_heads')} != num_attention_heads "
                f"{hf_config.num_attention_heads} is not supported (published: 30 and 30)"
            )
        if get("linear_num_key_heads") != get("linear_num_value_heads"):
            raise NotImplementedError(
                f"olmo_hybrid: linear_num_key_heads {get('linear_num_key_heads')} != linear_num_value_heads "
                f"{get('linear_num_value_heads')} is not supported (published: 30 and 30, a state a head)"
            )
        n = hf_config.num_hidden_layers
        layer_types = get("layer_types")
        if layer_types is None or len(layer_types) < n:
            raise ValueError(f"olmo_hybrid: layer_types names fewer than num_hidden_layers {n} layers")
        if set(layer_types) - {LINEAR, FULL}:
            raise NotImplementedError(f"olmo_hybrid: unknown layer type in {sorted(set(layer_types))}")
        return cls(
            hidden_size=hf_config.hidden_size,
            num_attention_heads=hf_config.num_attention_heads,
            num_key_value_heads=get("num_key_value_heads") or hf_config.num_attention_heads,
            head_dim=get("head_dim") or hf_config.hidden_size // hf_config.num_attention_heads,
            intermediate_size=hf_config.intermediate_size,
            num_hidden_layers=n,
            rms_norm_eps=hf_config.rms_norm_eps,
            layer_types=tuple(layer_types[:n]),
            linear_num_heads=hf_config.linear_num_value_heads,
            linear_key_head_dim=hf_config.linear_key_head_dim,
            linear_value_head_dim=hf_config.linear_value_head_dim,
            linear_conv_kernel_dim=hf_config.linear_conv_kernel_dim,
            linear_allow_neg_eigval=bool(get("linear_allow_neg_eigval", False)),
            vocab_size=hf_config.vocab_size,
            tie_word_embeddings=get("tie_word_embeddings", False),
        )
