"""Llama decoder block as a pure jitted JAX function.

Capability parity with the reference's WrappedLlamaBlock
(/root/reference/src/petals/models/llama/block.py:225-300): uniform block
contract over a KV cache with GQA and RoPE. The reference's CUDA-graph rotary
and its bloom<->llama cache permutes are unnecessary here — the whole step is
one XLA program and the framework has a single canonical KV layout
[batch, seq, kv_heads, head_dim].
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from petals_tpu.models.common import (
    ACTIVATIONS,
    ATTN_LEAVES,
    ATTN_PSPECS,
    COL_BIAS,
    COL_SPLIT,
    HF_ATTN_LORA_TARGETS,
    KVCache,
    QKV_BIAS_PSPECS,
    ROW_SPLIT,
    absolute_positions,
    leaf_pspecs,
    mm,
    project_heads,
    rms_norm,
    update_kv_cache,
)
from petals_tpu.models.llama.config import LlamaBlockConfig
from petals_tpu.models.registry import ModelFamily, register_family
from petals_tpu.ops.attention import attend_maybe_ring
from petals_tpu.ops.rotary import apply_rotary, rotary_tables


def block_apply(
    params: dict,
    hidden_states: jnp.ndarray,  # [batch, seq, hidden]
    kv: Optional[KVCache],
    position,  # int32 scalar (or [batch] vector: per-lane batched decode): tokens already cached
    cfg: LlamaBlockConfig,
    *,
    use_flash: bool = False,
    n_valid=None,  # dynamic count of real (non-padding) tokens in this chunk
    n_total=None,  # final sequence length when known up front (longrope factor selection)
    ring_mesh=None,  # "sp" mesh: ring attention (stateless path) or q-sharded prefill (cached)
    tp_mesh=None,  # serving path: run the flash kernel per TP head-shard
) -> Tuple[jnp.ndarray, Optional[KVCache]]:
    batch, seq, _ = hidden_states.shape
    hq, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim

    residual = hidden_states
    x = rms_norm(hidden_states, params["ln1"], cfg.rms_norm_eps)

    if "wqkv" in params:  # fused quantized serving (FUSE_GROUPS below)
        qkv = mm(x, params["wqkv"])
        if cfg.attention_bias or cfg.qkv_bias:
            qkv = qkv + params["bqkv"]
        q = qkv[..., : hq * d]
        k = qkv[..., hq * d : (hq + hkv) * d]
        v = qkv[..., (hq + hkv) * d :]
    else:
        q = project_heads(x, params["wq"])
        k = project_heads(x, params["wk"])
        v = project_heads(x, params["wv"])
        if cfg.attention_bias or cfg.qkv_bias:
            q = q + params["bq"]
            k = k + params["bk"]
            v = v + params["bv"]
    q = q.reshape(batch, seq, hq, d)
    k = k.reshape(batch, seq, hkv, d)
    v = v.reshape(batch, seq, hkv, d)

    positions = absolute_positions(position, batch, seq)
    cos, sin = rotary_tables(
        positions, d, theta=cfg.rope_theta, rope_scaling=cfg.rope_scaling_dict,
        n_valid=n_valid,  # longrope's switch must see the REAL chunk length
        n_total=n_total,  # ...or the full prompt length when it is known up front
    )
    q = apply_rotary(q, cos, sin)
    k = apply_rotary(k, cos, sin)

    k_all, v_all, kv_length = update_kv_cache(kv, k, v, position, n_valid)
    attn = attend_maybe_ring(
        q, k_all, v_all, kv=kv, position=position, n_valid=n_valid,
        kv_length=kv_length, ring_mesh=ring_mesh, use_flash=use_flash, tp_mesh=tp_mesh,
        sliding_window=cfg.sliding_window,  # mistral; None for llama/qwen2
    )
    attn = mm(attn.reshape(batch, seq, hq * d), params["wo"])
    if cfg.attention_bias:
        attn = attn + params["bo"]
    hidden_states = residual + attn

    residual = hidden_states
    x = rms_norm(hidden_states, params["ln2"], cfg.rms_norm_eps)
    if "wgu" in params:  # fused quantized serving
        gu = mm(x, params["wgu"])
        if cfg.mlp_bias:
            gu = gu + params["bgu"]
        gate = gu[..., : cfg.intermediate_size]
        up = gu[..., cfg.intermediate_size :]
    else:
        gate = mm(x, params["wg"])
        up = mm(x, params["wu"])
        if cfg.mlp_bias:
            gate = gate + params["bg"]
            up = up + params["bu"]
    mlp = mm(ACTIVATIONS[cfg.hidden_act](gate) * up, params["wd"])
    if cfg.mlp_bias:
        mlp = mlp + params["bd"]
    hidden_states = residual + mlp

    new_kv = (k_all, v_all) if kv is not None else None
    return hidden_states, new_kv


# ----------------------------------------------------------------------------------
# HF checkpoint mapping (weights stored torch-style [out, in]; we keep [in, out])
# ----------------------------------------------------------------------------------

_HF_BLOCK_PREFIXES = ("model.layers.{i}.",)


def hf_to_block_params(tensors: dict, cfg: LlamaBlockConfig) -> dict:
    """Map one block's HF tensors (names relative to the block prefix) to our tree."""

    def t(name):
        return np.ascontiguousarray(np.asarray(tensors[name]).T)

    params = {
        "ln1": np.asarray(tensors["input_layernorm.weight"]),
        "wq": t("self_attn.q_proj.weight"),
        "wk": t("self_attn.k_proj.weight"),
        "wv": t("self_attn.v_proj.weight"),
        "wo": t("self_attn.o_proj.weight"),
        "ln2": np.asarray(tensors["post_attention_layernorm.weight"]),
        "wg": t("mlp.gate_proj.weight"),
        "wu": t("mlp.up_proj.weight"),
        "wd": t("mlp.down_proj.weight"),
    }
    if cfg.attention_bias or cfg.qkv_bias:
        params["bq"] = np.asarray(tensors["self_attn.q_proj.bias"])
        params["bk"] = np.asarray(tensors["self_attn.k_proj.bias"])
        params["bv"] = np.asarray(tensors["self_attn.v_proj.bias"])
    if cfg.attention_bias:
        params["bo"] = np.asarray(tensors["self_attn.o_proj.bias"])
    if cfg.mlp_bias:
        params["bg"] = np.asarray(tensors["mlp.gate_proj.bias"])
        params["bu"] = np.asarray(tensors["mlp.up_proj.bias"])
        params["bd"] = np.asarray(tensors["mlp.down_proj.bias"])
    return params


def block_param_shapes(cfg: LlamaBlockConfig, dtype=jnp.bfloat16) -> dict:
    import jax

    h, hq, hkv, d, m = (
        cfg.hidden_size,
        cfg.num_attention_heads,
        cfg.num_key_value_heads,
        cfg.head_dim,
        cfg.intermediate_size,
    )
    S = jax.ShapeDtypeStruct
    shapes = {
        "ln1": S((h,), dtype),
        "wq": S((h, hq * d), dtype),
        "wk": S((h, hkv * d), dtype),
        "wv": S((h, hkv * d), dtype),
        "wo": S((hq * d, h), dtype),
        "ln2": S((h,), dtype),
        "wg": S((h, m), dtype),
        "wu": S((h, m), dtype),
        "wd": S((m, h), dtype),
    }
    if cfg.attention_bias or cfg.qkv_bias:
        shapes["bq"] = S((hq * d,), dtype)
        shapes["bk"] = S((hkv * d,), dtype)
        shapes["bv"] = S((hkv * d,), dtype)
    if cfg.attention_bias:
        shapes["bo"] = S((h,), dtype)
    if cfg.mlp_bias:
        shapes["bg"] = S((m,), dtype)
        shapes["bu"] = S((m,), dtype)
        shapes["bd"] = S((h,), dtype)
    return shapes


TP_PSPECS = {
    "ln1": P(), "ln2": P(), **ATTN_PSPECS, **QKV_BIAS_PSPECS, "bo": P(),
    "wg": COL_SPLIT, "wu": COL_SPLIT, "wd": ROW_SPLIT,
    "bg": COL_BIAS, "bu": COL_BIAS, "bd": P(),
}
# Leaves fused into one matmul each for quantized single-chip serving: every
# Pallas custom call carries a fixed launch/boundary cost (~0.2 ms in the July
# 2026 v5e record; not measured on the current chip), so 7 calls/block -> 4 speeds up
# decode. Fusion happens on the DENSE weights before quantization: 4-bit/int8
# scales are per-output-column, so the fused quantization is bit-identical to
# quantizing separately. Biases (qwen2) fuse alongside.
FUSE_GROUPS = (
    ("wqkv", ("wq", "wk", "wv"), "bqkv", ("bq", "bk", "bv")),
    ("wgu", ("wg", "wu"), "bgu", ("bg", "bu")),
)

FAMILY = register_family(
    ModelFamily(
        name="llama",
        config_from_hf=LlamaBlockConfig.from_hf_config,
        block_apply=block_apply,
        hf_block_prefixes=_HF_BLOCK_PREFIXES,
        hf_to_block_params=hf_to_block_params,
        block_param_shapes=block_param_shapes,
        tp_pspecs=leaf_pspecs(block_param_shapes, TP_PSPECS),
        quantizable_leaves=ATTN_LEAVES | {"wg", "wu", "wd"},
        fuse_groups=FUSE_GROUPS,
        lora_targets={**HF_ATTN_LORA_TARGETS, "gate_proj": "wg", "up_proj": "wu", "down_proj": "wd"},
        supports_ring_attention=True,
    )
)
