"""Gemma-2 decoder block as a pure jitted JAX function (9th family; beyond
the reference's four). A genuinely different architecture from gemma/llama
(reference has no analogue; HF Gemma2DecoderLayer is the parity target):

- FOUR (1+w)-folded RMSNorms per block: pre/post attention and pre/post MLP,
  with the post-norms applied to the sublayer OUTPUT before the residual add.
- Attention logit soft-capping: tanh(l/cap)*cap before masking (ops/attention
  attend_reference; the flash kernel has no softcap rule, so this family
  always takes the XLA attention path).
- Alternating per-layer sliding windows (layer_types): the window rides the
  params as a per-block int32 leaf ``attn_window`` (0 = full attention) so
  the span scan stays UNIFORM — the mask math is pure arithmetic on a traced
  scalar, with 0 mapped to a never-excluding horizon.
- Query scale from query_pre_attn_scalar (not head_dim).
- GeGLU MLP (tanh-approx GELU), llama-style leaf names; supports the fused
  wqkv/wgu quantized-serving leaves like the llama block.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from petals_tpu.models.common import (
    ACTIVATIONS,
    KVCache,
    absolute_positions,
    mm,
    project_heads,
    rms_norm,
    update_kv_cache,
)
from petals_tpu.models.gemma2.config import Gemma2BlockConfig
from petals_tpu.ops.attention import attend
from petals_tpu.ops.rotary import apply_rotary, rotary_tables


def block_attention(cfg: Gemma2BlockConfig, kind=None) -> tuple:
    """What the block hands its attention beyond the plain call (``ModelFamily.block_attention``): a
    window that is an array, each block's own out of its parameters, and the soft cap on the scores."""
    return ("traced_window",) + (("softcap",) if cfg.attn_logit_softcapping is not None else ())


def block_apply(
    params: dict,
    hidden_states: jnp.ndarray,  # [batch, seq, hidden]
    kv: Optional[KVCache],
    position,  # int32 scalar (or [batch] vector: per-lane batched decode)
    cfg: Gemma2BlockConfig,
    *,
    use_flash: bool = False,  # accepted for the uniform contract; never flash
    n_valid=None,
    tp_mesh=None,
) -> Tuple[jnp.ndarray, Optional[KVCache]]:
    batch, seq, _ = hidden_states.shape
    hq, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim

    residual = hidden_states
    x = rms_norm(hidden_states, params["ln1"], cfg.rms_norm_eps)

    if "wqkv" in params:  # fused quantized serving (the llama block's FUSE_GROUPS)
        qkv = mm(x, params["wqkv"])
        q = qkv[..., : hq * d]
        k = qkv[..., hq * d : (hq + hkv) * d]
        v = qkv[..., (hq + hkv) * d :]
    else:
        q = project_heads(x, params["wq"])
        k = project_heads(x, params["wk"])
        v = project_heads(x, params["wv"])
    q = q.reshape(batch, seq, hq, d)
    k = k.reshape(batch, seq, hkv, d)
    v = v.reshape(batch, seq, hkv, d)

    positions = absolute_positions(position, batch, seq)
    cos, sin = rotary_tables(positions, d, theta=cfg.rope_theta)
    q = apply_rotary(q, cos, sin)
    k = apply_rotary(k, cos, sin)

    k_all, v_all, kv_length = update_kv_cache(kv, k, v, position, n_valid)
    # per-block window: 0 means full attention — mapped to a horizon longer
    # than the buffer, so the (traced) window mask never excludes anything
    window = jnp.asarray(params["attn_window"], jnp.int32)
    window_eff = jnp.where(window > 0, window, jnp.int32(k_all.shape[1] + seq + 1))
    attn = attend(
        q, k_all, v_all,
        q_offset=position, kv_length=kv_length,
        sliding_window=window_eff,
        scale=float(cfg.query_pre_attn_scalar) ** -0.5,
        logit_softcap=cfg.attn_logit_softcapping,
        use_flash=False, tp_mesh=tp_mesh,
    )
    attn = mm(attn.reshape(batch, seq, hq * d), params["wo"])
    attn = rms_norm(attn, params["ln1_post"], cfg.rms_norm_eps)
    hidden_states = residual + attn

    residual = hidden_states
    x = rms_norm(hidden_states, params["ln2_pre"], cfg.rms_norm_eps)
    if "wgu" in params:  # fused quantized serving
        gu = mm(x, params["wgu"])
        gate = gu[..., : cfg.intermediate_size]
        up = gu[..., cfg.intermediate_size :]
    else:
        gate = mm(x, params["wg"])
        up = mm(x, params["wu"])
    mlp = mm(ACTIVATIONS[cfg.hidden_act](gate) * up, params["wd"])
    mlp = rms_norm(mlp, params["ln2_post"], cfg.rms_norm_eps)
    hidden_states = residual + mlp

    new_kv = (k_all, v_all) if kv is not None else None
    return hidden_states, new_kv


# ----------------------------------------------------------------------------------
# HF checkpoint mapping (weights stored torch-style [out, in]; we keep [in, out])
# ----------------------------------------------------------------------------------

_HF_BLOCK_PREFIXES = ("model.layers.{i}.",)


from petals_tpu.models.gemma import _fold_norm  # same (1+w) fold as gemma v1


def hf_to_block_params(
    tensors: dict, cfg: Gemma2BlockConfig, block_index: int
) -> dict:
    # block_index is REQUIRED (no default): if the loader's signature-based
    # dispatch ever regresses to the 2-arg call, this raises instead of
    # silently stamping layer 0's window onto every block
    def t(name):
        return np.ascontiguousarray(np.asarray(tensors[name]).T)

    window = (
        cfg.sliding_window
        if cfg.layer_types[block_index] == "sliding_attention"
        else 0
    )
    return {
        "ln1": _fold_norm(tensors["input_layernorm.weight"]),
        "ln1_post": _fold_norm(tensors["post_attention_layernorm.weight"]),
        "ln2_pre": _fold_norm(tensors["pre_feedforward_layernorm.weight"]),
        "ln2_post": _fold_norm(tensors["post_feedforward_layernorm.weight"]),
        "wq": t("self_attn.q_proj.weight"),
        "wk": t("self_attn.k_proj.weight"),
        "wv": t("self_attn.v_proj.weight"),
        "wo": t("self_attn.o_proj.weight"),
        "wg": t("mlp.gate_proj.weight"),
        "wu": t("mlp.up_proj.weight"),
        "wd": t("mlp.down_proj.weight"),
        "attn_window": np.asarray(window, np.int32),
    }


def block_param_shapes(cfg: Gemma2BlockConfig, dtype=jnp.bfloat16) -> dict:
    import jax

    h, hq, hkv, d, m = (
        cfg.hidden_size,
        cfg.num_attention_heads,
        cfg.num_key_value_heads,
        cfg.head_dim,
        cfg.intermediate_size,
    )
    S = jax.ShapeDtypeStruct
    return {
        "ln1": S((h,), jnp.float32),
        "ln1_post": S((h,), jnp.float32),
        "ln2_pre": S((h,), jnp.float32),
        "ln2_post": S((h,), jnp.float32),
        "wq": S((h, hq * d), dtype),
        "wk": S((h, hkv * d), dtype),
        "wv": S((h, hkv * d), dtype),
        "wo": S((hq * d, h), dtype),
        "wg": S((h, m), dtype),
        "wu": S((h, m), dtype),
        "wd": S((m, h), dtype),
        "attn_window": S((), jnp.int32),
    }
