"""OLMoE decoder block as a pure jitted JAX function (HF ``modeling_olmoe.py``,
departures none; the reference has no such family).

What sets it apart from Mixtral's block, whose attention plumbing and expert
dispatch (models/moe.py) it shares:

- QK-norm: an RMS norm over the WHOLE q and k projections (all heads' outputs
  together), before they are split into heads and rotated. Under a tp mesh the
  projections are column-sharded and the norm's mean spans the shards; GSPMD
  inserts that all-reduce (parallel/tp.py).
- many small experts (64 of width 1024, 8 a token in OLMoE-1B-7B) and a router
  whose kept weights are NOT renormalised (``norm_topk_prob`` false): a token's
  expert outputs are weighted by their share of the 64-way softmax mass. Of
  ``moe.grouped_dispatch``'s three, a step's decode rows take "hit" (8 lanes
  reach 41.6 of the 64) and a chunk the all-experts einsum up to ~810 tokens.
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
    HF_ATTN_LORA_TARGETS,
    KVCache,
    absolute_positions,
    leaf_pspecs,
    mm,
    project_heads,
    rms_norm,
    update_kv_cache,
)
from petals_tpu.models.moe import EXPERT_LEAVES, EXPERT_PSPECS, MoeDims, choose_dispatch, moe_apply
from petals_tpu.models.olmoe.config import OlmoeBlockConfig
from petals_tpu.models.registry import ModelFamily, register_family
from petals_tpu.ops.attention import attend_maybe_ring
from petals_tpu.ops.rotary import apply_rotary, rotary_tables


def moe_dims(cfg: OlmoeBlockConfig) -> MoeDims:
    return MoeDims(cfg.num_experts, cfg.num_experts_per_tok, cfg.hidden_size, cfg.intermediate_size)


def block_apply(
    params: dict,
    hidden_states: jnp.ndarray,
    kv: Optional[KVCache],
    position,
    cfg: OlmoeBlockConfig,
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
    q, k, v = (project_heads(x, params[name]) for name in ("wq", "wk", "wv"))
    with jax.named_scope("ptu.attn.qk_norm"):
        q = rms_norm(q, params["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.rms_norm_eps)
    if cfg.clip_qkv is not None:
        q, k, v = (jnp.clip(t, -cfg.clip_qkv, cfg.clip_qkv) for t in (q, k, v))
    q = q.reshape(batch, seq, hq, d)
    k = k.reshape(batch, seq, hkv, d)
    v = v.reshape(batch, seq, hkv, d)

    positions = absolute_positions(position, batch, seq)
    cos, sin = rotary_tables(positions, d, theta=cfg.rope_theta)
    q = apply_rotary(q, cos, sin)
    k = apply_rotary(k, cos, sin)

    k_all, v_all, kv_length = update_kv_cache(kv, k, v, position, n_valid)
    attn = attend_maybe_ring(
        q, k_all, v_all, kv=kv, position=position, n_valid=n_valid,
        kv_length=kv_length, ring_mesh=ring_mesh, use_flash=use_flash,
        tp_mesh=tp_mesh,
    )
    hidden_states = residual + mm(attn.reshape(batch, seq, hq * d), params["wo"])

    residual = hidden_states
    x = rms_norm(hidden_states, params["ln2"], cfg.rms_norm_eps)
    # under an ep/tp mesh the dense einsums carry the expert shardings; ragged groups and the hit kernel don't
    dispatch = choose_dispatch(params, moe_dims(cfg), seq, mesh=tp_mesh is not None or ring_mesh is not None)
    hidden_states = residual + moe_apply(
        params, x, top_k=cfg.num_experts_per_tok, renormalize=cfg.norm_topk_prob, dispatch=dispatch, live_rows=live_rows
    )

    new_kv = (k_all, v_all) if kv is not None else None
    return hidden_states, new_kv


# ----------------------------------------------------------------------------------
# HF checkpoint mapping
# ----------------------------------------------------------------------------------

_HF_BLOCK_PREFIXES = ("model.layers.{i}.",)


def hf_to_block_params(tensors: dict, cfg: OlmoeBlockConfig) -> dict:
    def t(name):
        return np.ascontiguousarray(np.asarray(tensors[name]).T)

    def stack(proj):  # 3 x num_experts tensors a layer, stacked [E, in, out] as Mixtral's are
        return np.stack([t(f"mlp.experts.{e}.{proj}.weight") for e in range(cfg.num_experts)])

    return {
        "ln1": np.asarray(tensors["input_layernorm.weight"]),
        "wq": t("self_attn.q_proj.weight"),
        "wk": t("self_attn.k_proj.weight"),
        "wv": t("self_attn.v_proj.weight"),
        "wo": t("self_attn.o_proj.weight"),
        "q_norm": np.asarray(tensors["self_attn.q_norm.weight"]),
        "k_norm": np.asarray(tensors["self_attn.k_norm.weight"]),
        "ln2": np.asarray(tensors["post_attention_layernorm.weight"]),
        "gate": t("mlp.gate.weight"),
        "w1": stack("gate_proj"),
        "w2": stack("down_proj"),
        "w3": stack("up_proj"),
    }


def block_param_shapes(cfg: OlmoeBlockConfig, dtype=jnp.bfloat16) -> dict:
    h, hq, hkv, d, m, E = (
        cfg.hidden_size,
        cfg.num_attention_heads,
        cfg.num_key_value_heads,
        cfg.head_dim,
        cfg.intermediate_size,
        cfg.num_experts,
    )
    S = jax.ShapeDtypeStruct
    return {
        "ln1": S((h,), dtype),
        "wq": S((h, hq * d), dtype),
        "wk": S((h, hkv * d), dtype),
        "wv": S((h, hkv * d), dtype),
        "wo": S((hq * d, h), dtype),
        "q_norm": S((hq * d,), dtype),
        "k_norm": S((hkv * d,), dtype),
        "ln2": S((h,), dtype),
        "gate": S((h, E), dtype),
        "w1": S((E, h, m), dtype),
        "w2": S((E, m, h), dtype),
        "w3": S((E, h, m), dtype),
    }


TP_PSPECS = {
    "ln1": P(), "ln2": P(), **ATTN_PSPECS, **EXPERT_PSPECS,
    # QK-norm runs over the whole column-sharded q and k projections: its
    # mean spans the shards, which GSPMD sums over ICI like the row-parallel
    # psums; the norm vectors shard with the columns
    "q_norm": COL_BIAS, "k_norm": COL_BIAS,
}

FAMILY = register_family(
    ModelFamily(
        name="olmoe",
        config_from_hf=OlmoeBlockConfig.from_hf_config,
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
