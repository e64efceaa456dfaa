"""``deepseek_v3`` block config (``config.json`` as transformers'
``DeepseekV3Config`` reads it; Kanana-2-30B-A3B publishes under this
model_type; the reference has no such family)."""

from __future__ import annotations

import dataclasses
import functools

from petals_tpu.utils.logging import get_logger

logger = get_logger(__name__)

DENSE, SPARSE = "dense", "sparse"


@functools.cache
def _say_unserved(who: str, nextn: int) -> None:
    """Once a process, a family and a count."""
    logger.info(f"{who}: num_nextn_predict_layers {nextn} is not served: the prediction layer sits behind "
                f"the head, no server holds it and the client does not draft with it")


@dataclasses.dataclass(frozen=True)
class DeepseekV3BlockConfig:
    hidden_size: int
    num_attention_heads: int
    kv_lora_rank: int  # the latent a position caches, all heads' keys and values are linear in it
    qk_nope_head_dim: int  # a head's part of q and k that carries no position
    qk_rope_head_dim: int  # the rotated part: a head's of q, ONE of k for all heads
    v_head_dim: int
    head_dim: int  # as published: the rotary's width (what the cache holds is ``kv_lora_rank + qk_rope_head_dim`` a position)
    intermediate_size: int  # the dense layers' width
    moe_intermediate_size: int  # one expert's width
    num_hidden_layers: int
    first_k_dense_replace: int  # the model's first layers that are dense
    num_experts: int
    num_experts_per_tok: int
    num_shared_experts: int
    norm_topk_prob: bool
    routed_scaling_factor: float
    rms_norm_eps: float
    rope_theta: float
    rope_interleave: bool  # the checkpoint's rotated columns pair (2j, 2j + 1); folded to halves at load
    vocab_size: int = 129280
    tie_word_embeddings: bool = False

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @classmethod
    def from_hf_config(cls, hf_config) -> "DeepseekV3BlockConfig":
        get = lambda key, default=None: getattr(hf_config, key, default)
        refuse_unserved(get, "deepseek_v3")
        if get("rope_scaling"):
            # yarn rescales the frequencies and, through mscale, the softmax's scale
            raise NotImplementedError(f"deepseek_v3: rope_scaling {get('rope_scaling')!r} is not supported (served: null)")
        if get("q_lora_rank") is not None:
            raise NotImplementedError(
                f"deepseek_v3: q_lora_rank {get('q_lora_rank')!r} is not supported (served: null, one q matrix; a low-rank "
                f"q is two matrices and a norm between them)"
            )
        if get("hc_mult", 1) not in (None, 1):
            raise NotImplementedError(
                f"deepseek_v3: hc_mult {get('hc_mult')!r} is not supported (served: a residual stream of one; a stream of "
                f"several rows under hyper-connections is model_type xing4_0)"
            )
        return cls(**published_fields(hf_config, "deepseek_v3"))


def refuse_unserved(get, who: str) -> None:
    """What no block over ``deepseek_v3``'s sub-layers computes (this family's, and ``xing4_0``'s around the same
    attention and feed-forward) is refused here, at load, by the family's name, not served wrong."""
    for key in ("n_group", "topk_group"):
        if get(key, 1) != 1:
            raise NotImplementedError(f"{who}: {key} {get(key)!r} is not supported (served: 1, no group limit)")
    if get("scoring_func", "sigmoid") != "sigmoid":
        raise NotImplementedError(f"{who}: scoring_func {get('scoring_func')!r} is not supported (served: sigmoid)")
    if get("topk_method", "noaux_tc") != "noaux_tc":
        raise NotImplementedError(f"{who}: topk_method {get('topk_method')!r} is not supported (served: noaux_tc)")
    if get("hidden_act", "silu") != "silu":
        raise NotImplementedError(f"{who}: hidden_act {get('hidden_act')!r} is not supported (served: silu)")
    if get("attention_bias", False):
        raise NotImplementedError(f"{who}: attention_bias true is not supported (served: false)")
    if get("moe_layer_freq", 1) != 1:
        raise NotImplementedError(f"{who}: moe_layer_freq {get('moe_layer_freq')!r} is not supported (served: 1)")


def published_fields(hf_config, who: str) -> dict:
    """``DeepseekV3BlockConfig``'s fields from the published keys; says once that a prediction layer is not served."""
    get = lambda key, default=None: getattr(hf_config, key, default)
    nextn = get("num_nextn_predict_layers", 0)
    if nextn:
        _say_unserved(who, nextn)
    return dict(
        hidden_size=hf_config.hidden_size,
        num_attention_heads=hf_config.num_attention_heads,
        kv_lora_rank=hf_config.kv_lora_rank,
        qk_nope_head_dim=hf_config.qk_nope_head_dim,
        qk_rope_head_dim=hf_config.qk_rope_head_dim,
        v_head_dim=hf_config.v_head_dim,
        head_dim=get("head_dim") or hf_config.qk_rope_head_dim,
        intermediate_size=hf_config.intermediate_size,
        moe_intermediate_size=hf_config.moe_intermediate_size,
        num_hidden_layers=hf_config.num_hidden_layers,
        first_k_dense_replace=int(get("first_k_dense_replace", 0)),
        num_experts=hf_config.n_routed_experts,
        num_experts_per_tok=hf_config.num_experts_per_tok,
        num_shared_experts=int(get("n_shared_experts", 0) or 0),
        norm_topk_prob=bool(get("norm_topk_prob", True)),
        routed_scaling_factor=float(get("routed_scaling_factor", 1.0)),
        rms_norm_eps=hf_config.rms_norm_eps,
        rope_theta=float(get("rope_theta", 10000.0)),
        rope_interleave=bool(get("rope_interleave", True)),
        vocab_size=hf_config.vocab_size,
        tie_word_embeddings=get("tie_word_embeddings", False),
    )
