"""BLOOM decoder block as a pure jitted JAX function.

Capability parity with the reference's WrappedBloomBlock
(/root/reference/src/petals/models/bloom/block.py:15-45): ALiBi attention with
the canonical KV cache. The reference's "Bloom cache layout" permutes are gone —
all families share [batch, seq, kv_heads, head_dim].
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from petals_tpu.models.bloom.config import BloomBlockConfig
from petals_tpu.models.common import (
    ATTN_LEAVES,
    ATTN_PSPECS,
    COL_BIAS,
    COL_SPLIT,
    KVCache,
    QKV_BIAS_PSPECS,
    ROW_SPLIT,
    gelu_tanh,
    layer_norm,
    leaf_pspecs,
    mm,
    project_heads,
    update_kv_cache,
)
from petals_tpu.models.registry import ModelFamily, register_family
from petals_tpu.ops.alibi import build_alibi_slopes
from petals_tpu.ops.attention import attend_maybe_ring


def block_apply(
    params: dict,
    hidden_states: jnp.ndarray,  # [batch, seq, hidden]
    kv: Optional[KVCache],
    position,
    cfg: BloomBlockConfig,
    *,
    use_flash: bool = False,
    tp_mesh=None,
    n_valid=None,  # dynamic count of real (non-padding) tokens in this chunk
    ring_mesh=None,  # "sp" mesh: ring attention (stateless path) or q-sharded prefill (cached)
) -> Tuple[jnp.ndarray, Optional[KVCache]]:
    batch, seq, _ = hidden_states.shape
    h, d = cfg.num_attention_heads, cfg.head_dim

    ln1 = layer_norm(hidden_states, params["ln1_w"], params["ln1_b"], cfg.layer_norm_epsilon)
    residual = ln1 if cfg.apply_residual_connection_post_layernorm else hidden_states

    q = (project_heads(ln1, params["wq"]) + params["bq"]).reshape(batch, seq, h, d)
    k = (project_heads(ln1, params["wk"]) + params["bk"]).reshape(batch, seq, h, d)
    v = (project_heads(ln1, params["wv"]) + params["bv"]).reshape(batch, seq, h, d)

    k_all, v_all, kv_length = update_kv_cache(kv, k, v, position, n_valid)
    slopes = build_alibi_slopes(h)
    attn = attend_maybe_ring(
        q, k_all, v_all, kv=kv, position=position, n_valid=n_valid,
        kv_length=kv_length, ring_mesh=ring_mesh, use_flash=use_flash,
        tp_mesh=tp_mesh, alibi_slopes=slopes,
    )
    attn = mm(attn.reshape(batch, seq, h * d), params["wo"]) + params["bo"]
    hidden_states = attn + residual

    ln2 = layer_norm(hidden_states, params["ln2_w"], params["ln2_b"], cfg.layer_norm_epsilon)
    residual = ln2 if cfg.apply_residual_connection_post_layernorm else hidden_states
    mlp = mm(gelu_tanh(mm(ln2, params["w_up"]) + params["b_up"]), params["w_down"]) + params["b_down"]
    hidden_states = mlp + residual

    new_kv = (k_all, v_all) if kv is not None else None
    return hidden_states, new_kv


# ----------------------------------------------------------------------------------
# HF checkpoint mapping
# ----------------------------------------------------------------------------------

# BLOOM checkpoints ship blocks as "h.{i}." (bare) or "transformer.h.{i}." (full model)
_HF_BLOCK_PREFIXES = ("h.{i}.", "transformer.h.{i}.")


def hf_to_block_params(tensors: dict, cfg: BloomBlockConfig) -> dict:
    """De-interleave BLOOM's fused per-head QKV ([heads, 3, dim] packing —
    see HF BloomAttention._split_heads) into separate projections."""
    h, d = cfg.num_attention_heads, cfg.head_dim
    hidden = cfg.hidden_size

    qkv_w = np.asarray(tensors["self_attention.query_key_value.weight"])  # [3*hidden, hidden]
    qkv_b = np.asarray(tensors["self_attention.query_key_value.bias"])  # [3*hidden]
    qkv_w = qkv_w.reshape(h, 3, d, hidden)  # out axis is (heads, 3, dim)
    qkv_b = qkv_b.reshape(h, 3, d)

    def w_of(j):  # -> [hidden_in, h*d_out]
        return np.ascontiguousarray(qkv_w[:, j].reshape(h * d, hidden).T)

    def b_of(j):
        return np.ascontiguousarray(qkv_b[:, j].reshape(h * d))

    def t(name):
        return np.ascontiguousarray(np.asarray(tensors[name]).T)

    return {
        "ln1_w": np.asarray(tensors["input_layernorm.weight"]),
        "ln1_b": np.asarray(tensors["input_layernorm.bias"]),
        "wq": w_of(0),
        "bq": b_of(0),
        "wk": w_of(1),
        "bk": b_of(1),
        "wv": w_of(2),
        "bv": b_of(2),
        "wo": t("self_attention.dense.weight"),
        "bo": np.asarray(tensors["self_attention.dense.bias"]),
        "ln2_w": np.asarray(tensors["post_attention_layernorm.weight"]),
        "ln2_b": np.asarray(tensors["post_attention_layernorm.bias"]),
        "w_up": t("mlp.dense_h_to_4h.weight"),
        "b_up": np.asarray(tensors["mlp.dense_h_to_4h.bias"]),
        "w_down": t("mlp.dense_4h_to_h.weight"),
        "b_down": np.asarray(tensors["mlp.dense_4h_to_h.bias"]),
    }


def block_param_shapes(cfg: BloomBlockConfig, dtype=jnp.bfloat16) -> dict:
    import jax

    h = cfg.hidden_size
    S = jax.ShapeDtypeStruct
    return {
        "ln1_w": S((h,), dtype),
        "ln1_b": S((h,), dtype),
        "wq": S((h, h), dtype),
        "bq": S((h,), dtype),
        "wk": S((h, h), dtype),
        "bk": S((h,), dtype),
        "wv": S((h, h), dtype),
        "bv": S((h,), dtype),
        "wo": S((h, h), dtype),
        "bo": S((h,), dtype),
        "ln2_w": S((h,), dtype),
        "ln2_b": S((h,), dtype),
        "w_up": S((h, 4 * h), dtype),
        "b_up": S((4 * h,), dtype),
        "w_down": S((4 * h, h), dtype),
        "b_down": S((h,), dtype),
    }


TP_PSPECS = {
    "ln1_w": P(), "ln1_b": P(), "ln2_w": P(), "ln2_b": P(),
    **ATTN_PSPECS, **QKV_BIAS_PSPECS, "bo": P(),
    "w_up": COL_SPLIT, "b_up": COL_BIAS, "w_down": ROW_SPLIT, "b_down": P(),
}

FAMILY = register_family(
    ModelFamily(
        name="bloom",
        config_from_hf=BloomBlockConfig.from_hf_config,
        block_apply=block_apply,
        hf_block_prefixes=_HF_BLOCK_PREFIXES,
        hf_to_block_params=hf_to_block_params,
        block_param_shapes=block_param_shapes,
        tp_pspecs=leaf_pspecs(block_param_shapes, TP_PSPECS),
        quantizable_leaves=ATTN_LEAVES | {"w_up", "w_down"},
        lora_targets={
            "query_key_value": None,  # fused qkv unsupported
            "dense": "wo", "dense_h_to_4h": "w_up", "dense_4h_to_h": "w_down",
        },
        supports_ring_attention=True,
        block_attention=lambda cfg, kind=None: ("alibi",),  # every block's scores take the slopes' bias
    )
)
