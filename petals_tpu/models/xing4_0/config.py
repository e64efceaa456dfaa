"""``xing4_0`` block config (``config.json`` as Xing4.0-29B-A4B publishes it;
transformers has no class for the model_type, so the published keys come as
attributes; the reference has no such family).

The sub-layers are ``deepseek_v3``'s (its attention with a low-rank query
under yarn, its dense and expert feed-forwards), so the config IS that
family's, with what the residual path adds: ``hc_mult`` rows of stream, mixed
around every sub-layer by manifold-constrained hyper-connections (models/
xing4_0/block.py)."""

from __future__ import annotations

import dataclasses
from typing import Optional

from petals_tpu.models.deepseek_v3.config import DeepseekV3BlockConfig, published_fields, refuse_unserved
from petals_tpu.ops.rotary import yarn_mscale


@dataclasses.dataclass(frozen=True)
class Xing40BlockConfig(DeepseekV3BlockConfig):
    q_lora_rank: int = 0  # the query's low rank: q_a_proj, a norm, q_b_proj
    rope_scaling: Optional[tuple] = None  # the published dict's items, sorted (yarn), with the window it extends
    softmax_mscale: float = 1.0  # yarn's ``mscale(factor, mscale_all_dim)^2`` on the softmax's scale
    hc_mult: int = 4  # rows of the residual stream
    hc_sinkhorn_iters: int = 20  # rounds of (rows, then columns) that make the residual mix doubly stochastic
    hc_eps: float = 1e-6  # Sinkhorn's denominator guard
    hc_res_clamp: tuple = (-30.0, 30.0)  # on the residual mix's logits, before ``exp``

    # q_a_layernorm and kv_a_layernorm are constructed without ``eps``: the class default, not ``rms_norm_eps``
    # (transformers 4.57.6 models/deepseek_v3/modeling_deepseek_v3.py DeepseekV3Attention.__init__)
    latent_norm_eps = 1e-6

    @property
    def stream_width(self) -> int:
        return self.hc_mult * self.hidden_size

    @classmethod
    def from_hf_config(cls, hf_config) -> "Xing40BlockConfig":
        get = lambda key, default=None: getattr(hf_config, key, default)
        refuse_unserved(get, "xing4_0")
        hc_mult = get("hc_mult")
        if hc_mult is None or int(hc_mult) < 2:
            raise NotImplementedError(
                f"xing4_0: hc_mult {hc_mult!r} is not supported (served: a residual stream of two rows or more; a stream of one "
                f"with these sub-layers is model_type deepseek_v3)"
            )
        if get("q_lora_rank") is None:
            raise NotImplementedError("xing4_0: q_lora_rank null is not supported (served: a low-rank query, as the model publishes)")
        scaling, mscale = dict(get("rope_scaling") or {}), 1.0
        if scaling:
            rope_type = scaling.get("rope_type", scaling.get("type"))
            if rope_type != "yarn":
                raise NotImplementedError(f"xing4_0: rope_scaling of type {rope_type!r} is not supported (served: yarn, or null)")
            # transformers' fallback for the window yarn extends, made explicit (ops/rotary.py asks for the key)
            scaling["original_max_position_embeddings"] = scaling.get("original_max_position_embeddings") or hf_config.max_position_embeddings
            if scaling.get("mscale_all_dim"):  # DeepseekV3Attention: the softmax's scale times mscale * mscale
                mscale = yarn_mscale(float(scaling["factor"]), float(scaling["mscale_all_dim"])) ** 2
        return cls(
            **published_fields(hf_config, "xing4_0"),
            q_lora_rank=int(hf_config.q_lora_rank),
            rope_scaling=tuple(sorted(scaling.items())) or None,
            softmax_mscale=mscale,
            hc_mult=int(hc_mult),
            hc_sinkhorn_iters=int(get("hc_sinkhorn_iters", 20)),
            hc_eps=float(get("hc_eps", 1e-6)),
            hc_res_clamp=(float(get("mhc_h_res_clamp_min", -30.0)), float(get("mhc_h_res_clamp_max", 30.0))),
        )
