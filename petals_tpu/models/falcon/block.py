"""Falcon decoder block as a pure jitted JAX function.

Capability parity with the reference's WrappedFalconBlock + optimized layers
(/root/reference/src/petals/models/falcon/block.py:34-480): fused-QKV
de-interleave (all three generations), parallel-attention residual structure,
GQA without the reference's KV expand/collapse permutes (the canonical cache
layout keeps true kv heads; our attention op does the grouping). The
reference's CUDA-graphed rotary/split kernels are unnecessary — the step is a
single XLA program.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from petals_tpu.models.common import (
    ATTN_LEAVES,
    ATTN_PSPECS,
    COL_BIAS,
    COL_SPLIT,
    KVCache,
    QKV_BIAS_PSPECS,
    ROW_SPLIT,
    absolute_positions,
    layer_norm,
    leaf_pspecs,
    mm,
    project_heads,
    update_kv_cache,
)
from petals_tpu.models.falcon.config import FalconBlockConfig
from petals_tpu.models.registry import ModelFamily, register_family
from petals_tpu.ops.alibi import build_alibi_slopes
from petals_tpu.ops.attention import attend_maybe_ring
from petals_tpu.ops.rotary import apply_rotary, rotary_tables


def _activation(x: jnp.ndarray, name: str) -> jnp.ndarray:
    if name == "gelu":
        return jax.nn.gelu(x.astype(jnp.float32), approximate=False).astype(x.dtype)
    if name in ("gelu_pytorch_tanh", "gelu_new"):
        return jax.nn.gelu(x.astype(jnp.float32), approximate=True).astype(x.dtype)
    if name == "relu":
        return jax.nn.relu(x)
    raise NotImplementedError(f"Falcon activation {name!r} is not supported")


def block_attention(cfg: FalconBlockConfig, kind=None) -> tuple:
    """What the block hands its attention beyond the plain call (``ModelFamily.block_attention``): the
    RW generation's ALiBi bias in place of the rotary embedding."""
    return ("alibi",) if cfg.alibi else ()


def block_apply(
    params: dict,
    hidden_states: jnp.ndarray,  # [batch, seq, hidden]
    kv: Optional[KVCache],
    position,
    cfg: FalconBlockConfig,
    *,
    use_flash: bool = False,
    tp_mesh=None,
    n_valid=None,
    ring_mesh=None,  # "sp" mesh: ring attention (stateless path) or q-sharded prefill (cached)
) -> Tuple[jnp.ndarray, Optional[KVCache]]:
    batch, seq, _ = hidden_states.shape
    hq, hkv, d = cfg.num_attention_heads, cfg.num_kv_heads, cfg.head_dim
    residual = hidden_states

    # HF gates the dual-LN layout on new_decoder_architecture + num_ln==2 only
    # (parallel_attn is NOT consulted there)
    if cfg.new_decoder_architecture and cfg.num_ln_in_parallel_attn == 2:
        attn_ln = layer_norm(hidden_states, params["ln_attn_w"], params["ln_attn_b"], cfg.layer_norm_epsilon)
        mlp_ln = layer_norm(hidden_states, params["ln_mlp_w"], params["ln_mlp_b"], cfg.layer_norm_epsilon)
    else:
        attn_ln = layer_norm(hidden_states, params["ln1_w"], params["ln1_b"], cfg.layer_norm_epsilon)
        mlp_ln = attn_ln  # parallel single-LN case; serial case overwritten below

    q = project_heads(attn_ln, params["wq"])
    k = project_heads(attn_ln, params["wk"])
    v = project_heads(attn_ln, params["wv"])
    if cfg.bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = q.reshape(batch, seq, hq, d)
    k = k.reshape(batch, seq, hkv, d)
    v = v.reshape(batch, seq, hkv, d)

    alibi_slopes = None
    if "alibi" in block_attention(cfg):
        # Falcon scales (scores + alibi) jointly by 1/sqrt(d) — unlike BLOOM,
        # where the bias is added unscaled — so pre-scale the slopes here.
        alibi_slopes = build_alibi_slopes(hq) * (d**-0.5)
    else:
        positions = absolute_positions(position, batch, seq)
        cos, sin = rotary_tables(positions, d, theta=cfg.rope_theta)
        q = apply_rotary(q, cos, sin)
        k = apply_rotary(k, cos, sin)

    k_all, v_all, kv_length = update_kv_cache(kv, k, v, position, n_valid)
    attn = attend_maybe_ring(
        q, k_all, v_all, kv=kv, position=position, n_valid=n_valid,
        kv_length=kv_length, ring_mesh=ring_mesh, use_flash=use_flash,
        tp_mesh=tp_mesh, alibi_slopes=alibi_slopes,
    )
    attn = mm(attn.reshape(batch, seq, hq * d), params["wo"])
    if cfg.bias:
        attn = attn + params["bo"]

    # serial residual structure applies only to old-architecture checkpoints
    # (HF skips it entirely when new_decoder_architecture is set)
    if not cfg.new_decoder_architecture and not cfg.parallel_attn:
        residual = residual + attn
        mlp_ln = layer_norm(residual, params["ln2_w"], params["ln2_b"], cfg.layer_norm_epsilon)

    # HF FalconMLP: dense_h_to_4h -> ACT2FN[config.activation] -> dense_4h_to_h
    mlp = mm(mlp_ln, params["w_up"])
    if cfg.bias:
        mlp = mlp + params["b_up"]
    mlp = _activation(mlp, cfg.activation)
    mlp = mm(mlp, params["w_down"])
    if cfg.bias:
        mlp = mlp + params["b_down"]

    if cfg.new_decoder_architecture or cfg.parallel_attn:
        mlp = mlp + attn

    out = mlp + residual
    new_kv = (k_all, v_all) if kv is not None else None
    return out, new_kv


# ----------------------------------------------------------------------------------
# HF checkpoint mapping
# ----------------------------------------------------------------------------------

_HF_BLOCK_PREFIXES = ("transformer.h.{i}.", "h.{i}.")


def hf_to_block_params(tensors: dict, cfg: FalconBlockConfig) -> dict:
    hq, hkv, d = cfg.num_attention_heads, cfg.num_kv_heads, cfg.head_dim
    hidden = cfg.hidden_size

    qkv_w = np.asarray(tensors["self_attention.query_key_value.weight"])  # [out, hidden]
    group = hq // hkv

    if cfg.new_decoder_architecture:
        # out axis = (hkv, group + 2, d): per kv-group queries then k then v
        w = qkv_w.reshape(hkv, group + 2, d, hidden)
        wq = w[:, :-2].reshape(hq * d, hidden)
        wk = w[:, -2].reshape(hkv * d, hidden)
        wv = w[:, -1].reshape(hkv * d, hidden)
    elif cfg.multi_query:
        # out axis = (hq + 2, d): all queries, then one k, one v
        w = qkv_w.reshape(hq + 2, d, hidden)
        wq = w[:-2].reshape(hq * d, hidden)
        wk = w[-2].reshape(d, hidden)
        wv = w[-1].reshape(d, hidden)
    else:
        # out axis = (hq, 3, d): per-head q,k,v interleave (falcon-rw)
        w = qkv_w.reshape(hq, 3, d, hidden)
        wq = w[:, 0].reshape(hq * d, hidden)
        wk = w[:, 1].reshape(hq * d, hidden)
        wv = w[:, 2].reshape(hq * d, hidden)

    def t(arr):
        return np.ascontiguousarray(arr.T)

    params = {
        "wq": t(wq),
        "wk": t(wk),
        "wv": t(wv),
        "wo": t(np.asarray(tensors["self_attention.dense.weight"])),
        "w_up": t(np.asarray(tensors["mlp.dense_h_to_4h.weight"])),
        "w_down": t(np.asarray(tensors["mlp.dense_4h_to_h.weight"])),
    }

    if cfg.new_decoder_architecture and cfg.num_ln_in_parallel_attn == 2:
        params["ln_attn_w"] = np.asarray(tensors["ln_attn.weight"])
        params["ln_attn_b"] = np.asarray(tensors["ln_attn.bias"])
        params["ln_mlp_w"] = np.asarray(tensors["ln_mlp.weight"])
        params["ln_mlp_b"] = np.asarray(tensors["ln_mlp.bias"])
    else:
        params["ln1_w"] = np.asarray(tensors["input_layernorm.weight"])
        params["ln1_b"] = np.asarray(tensors["input_layernorm.bias"])
        if not cfg.parallel_attn and not cfg.new_decoder_architecture:
            params["ln2_w"] = np.asarray(tensors["post_attention_layernorm.weight"])
            params["ln2_b"] = np.asarray(tensors["post_attention_layernorm.bias"])

    if cfg.bias:
        qkv_b = np.asarray(tensors["self_attention.query_key_value.bias"])
        if cfg.new_decoder_architecture:
            b = qkv_b.reshape(hkv, group + 2, d)
            bq, bk, bv = b[:, :-2].reshape(-1), b[:, -2].reshape(-1), b[:, -1].reshape(-1)
        elif cfg.multi_query:
            b = qkv_b.reshape(hq + 2, d)
            bq, bk, bv = b[:-2].reshape(-1), b[-2], b[-1]
        else:
            b = qkv_b.reshape(hq, 3, d)
            bq, bk, bv = b[:, 0].reshape(-1), b[:, 1].reshape(-1), b[:, 2].reshape(-1)
        params.update(
            bq=bq,
            bk=bk,
            bv=bv,
            bo=np.asarray(tensors["self_attention.dense.bias"]),
            b_up=np.asarray(tensors["mlp.dense_h_to_4h.bias"]),
            b_down=np.asarray(tensors["mlp.dense_4h_to_h.bias"]),
        )
    return params


def block_param_shapes(cfg: FalconBlockConfig, dtype=jnp.bfloat16) -> dict:
    h, hq, hkv, d, f = cfg.hidden_size, cfg.num_attention_heads, cfg.num_kv_heads, cfg.head_dim, cfg.ffn_hidden_size
    S = jax.ShapeDtypeStruct
    shapes = {
        "wq": S((h, hq * d), dtype),
        "wk": S((h, hkv * d), dtype),
        "wv": S((h, hkv * d), dtype),
        "wo": S((hq * d, h), dtype),
        "w_up": S((h, f), dtype),
        "w_down": S((f, h), dtype),
    }
    if cfg.new_decoder_architecture and cfg.num_ln_in_parallel_attn == 2:
        shapes.update(
            ln_attn_w=S((h,), dtype), ln_attn_b=S((h,), dtype),
            ln_mlp_w=S((h,), dtype), ln_mlp_b=S((h,), dtype),
        )
    else:
        shapes.update(ln1_w=S((h,), dtype), ln1_b=S((h,), dtype))
        if not cfg.parallel_attn and not cfg.new_decoder_architecture:
            shapes.update(ln2_w=S((h,), dtype), ln2_b=S((h,), dtype))
    if cfg.bias:
        shapes.update(
            bq=S((hq * d,), dtype), bk=S((hkv * d,), dtype), bv=S((hkv * d,), dtype),
            bo=S((h,), dtype), b_up=S((f,), dtype), b_down=S((h,), dtype),
        )
    return shapes


TP_PSPECS = {
    **ATTN_PSPECS, **QKV_BIAS_PSPECS, "bo": P(),
    "w_up": COL_SPLIT, "b_up": COL_BIAS, "w_down": ROW_SPLIT, "b_down": P(),
    "ln_attn_w": P(), "ln_attn_b": P(), "ln_mlp_w": P(), "ln_mlp_b": P(),
    "ln1_w": P(), "ln1_b": P(), "ln2_w": P(), "ln2_b": P(),
}

FAMILY = register_family(
    ModelFamily(
        name="falcon",
        config_from_hf=FalconBlockConfig.from_hf_config,
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
        block_attention=block_attention,
    )
)
