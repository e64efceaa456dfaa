"""Mixtral (MoE) decoder block as a pure jitted JAX function.

Capability parity with the reference's WrappedMixtralBlock
(/root/reference/src/petals/models/mixtral/block.py:13-113): all experts live
on the hosting server (no cross-server expert parallelism, matching the
reference), GQA attention with optional sliding window, top-k softmax routing.

The expert layer is models/moe.py (shared with olmoe and exaone_moe): HF-exact
routing with the kept weights renormalised, and three dispatches chosen by
``moe.grouped_dispatch`` from what the call shows. At Mixtral's 8 experts of
top 2 that is the grouped ``ragged_dot`` for a prompt chunk of 8 tokens or
more; below that "hit" in a step program (the experts the step's live rows
reach, read out of the stacked run: two live lanes reach 3.5 of 8) and the
all-experts einsum anywhere else.
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
    HF_ATTN_LORA_TARGETS,
    KVCache,
    absolute_positions,
    leaf_pspecs,
    mm,
    project_heads,
    rms_norm,
    update_kv_cache,
)
from petals_tpu.models.mixtral.config import MixtralBlockConfig
from petals_tpu.models.moe import EXPERT_LEAVES, EXPERT_PSPECS, MoeDims, choose_dispatch, moe_apply
from petals_tpu.models.registry import ModelFamily, register_family
from petals_tpu.ops.attention import attend_maybe_ring
from petals_tpu.ops.rotary import apply_rotary, rotary_tables


def moe_dims(cfg: MixtralBlockConfig) -> MoeDims:
    return MoeDims(cfg.num_local_experts, cfg.num_experts_per_tok, cfg.hidden_size, cfg.intermediate_size)


def block_apply(
    params: dict,
    hidden_states: jnp.ndarray,
    kv: Optional[KVCache],
    position,
    cfg: MixtralBlockConfig,
    *,
    use_flash: bool = False,
    tp_mesh=None,
    n_valid=None,
    ring_mesh=None,  # "sp" mesh: ring attention (stateless path) or q-sharded prefill (cached)
    live_rows=None,  # bool [batch] from a lane pool's step: the rows that are not idle lanes (None: all)
) -> Tuple[jnp.ndarray, Optional[KVCache]]:
    batch, seq, _ = hidden_states.shape
    hq, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim

    residual = hidden_states
    x = rms_norm(hidden_states, params["ln1"], cfg.rms_norm_eps)
    q = project_heads(x, params["wq"]).reshape(batch, seq, hq, d)
    k = project_heads(x, params["wk"]).reshape(batch, seq, hkv, d)
    v = project_heads(x, params["wv"]).reshape(batch, seq, hkv, d)

    positions = absolute_positions(position, batch, seq)
    cos, sin = rotary_tables(positions, d, theta=cfg.rope_theta)
    q = apply_rotary(q, cos, sin)
    k = apply_rotary(k, cos, sin)

    k_all, v_all, kv_length = update_kv_cache(kv, k, v, position, n_valid)
    attn = attend_maybe_ring(
        q, k_all, v_all, kv=kv, position=position, n_valid=n_valid,
        kv_length=kv_length, ring_mesh=ring_mesh, use_flash=use_flash,
        tp_mesh=tp_mesh, sliding_window=cfg.sliding_window,
    )
    hidden_states = residual + mm(attn.reshape(batch, seq, hq * d), params["wo"])

    residual = hidden_states
    x = rms_norm(hidden_states, params["ln2"], cfg.rms_norm_eps)
    # under an ep/tp mesh the dense einsums carry the expert shardings; ragged groups and the hit kernel don't
    dispatch = choose_dispatch(params, moe_dims(cfg), seq, mesh=tp_mesh is not None or ring_mesh is not None)
    hidden_states = residual + moe_apply(
        params, x, top_k=cfg.num_experts_per_tok, renormalize=True, dispatch=dispatch, live_rows=live_rows
    )

    new_kv = (k_all, v_all) if kv is not None else None
    return hidden_states, new_kv


# ----------------------------------------------------------------------------------
# HF checkpoint mapping
# ----------------------------------------------------------------------------------

_HF_BLOCK_PREFIXES = ("model.layers.{i}.",)


def hf_to_block_params(tensors: dict, cfg: MixtralBlockConfig) -> dict:
    def t(name):
        return np.ascontiguousarray(np.asarray(tensors[name]).T)

    E = cfg.num_local_experts
    w1 = np.stack([t(f"block_sparse_moe.experts.{e}.w1.weight") for e in range(E)])
    w2 = np.stack([t(f"block_sparse_moe.experts.{e}.w2.weight") for e in range(E)])
    w3 = np.stack([t(f"block_sparse_moe.experts.{e}.w3.weight") for e in range(E)])
    return {
        "ln1": np.asarray(tensors["input_layernorm.weight"]),
        "wq": t("self_attn.q_proj.weight"),
        "wk": t("self_attn.k_proj.weight"),
        "wv": t("self_attn.v_proj.weight"),
        "wo": t("self_attn.o_proj.weight"),
        "ln2": np.asarray(tensors["post_attention_layernorm.weight"]),
        "gate": t("block_sparse_moe.gate.weight"),
        "w1": w1,
        "w2": w2,
        "w3": w3,
    }


def block_param_shapes(cfg: MixtralBlockConfig, dtype=jnp.bfloat16) -> dict:
    h, hq, hkv, d, m, E = (
        cfg.hidden_size,
        cfg.num_attention_heads,
        cfg.num_key_value_heads,
        cfg.head_dim,
        cfg.intermediate_size,
        cfg.num_local_experts,
    )
    S = jax.ShapeDtypeStruct
    return {
        "ln1": S((h,), dtype),
        "wq": S((h, hq * d), dtype),
        "wk": S((h, hkv * d), dtype),
        "wv": S((h, hkv * d), dtype),
        "wo": S((hq * d, h), dtype),
        "ln2": S((h,), dtype),
        "gate": S((h, E), dtype),
        "w1": S((E, h, m), dtype),
        "w2": S((E, m, h), dtype),
        "w3": S((E, h, m), dtype),
    }


TP_PSPECS = {"ln1": P(), "ln2": P(), **ATTN_PSPECS, **EXPERT_PSPECS}

FAMILY = register_family(
    ModelFamily(
        name="mixtral",
        config_from_hf=MixtralBlockConfig.from_hf_config,
        block_apply=block_apply,
        hf_block_prefixes=_HF_BLOCK_PREFIXES,
        hf_to_block_params=hf_to_block_params,
        block_param_shapes=block_param_shapes,
        tp_pspecs=leaf_pspecs(block_param_shapes, TP_PSPECS),
        quantizable_leaves=ATTN_LEAVES | EXPERT_LEAVES,
        lora_targets=HF_ATTN_LORA_TARGETS,
        moe_dims=moe_dims,
        supports_ring_attention=True,
    )
)
