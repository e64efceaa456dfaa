"""K-EXAONE decoder block as a pure jitted JAX function (``exaone_moe``; the
installed transformers has no class for it, so the attention follows the
family's own ``Exaone4Attention`` and the expert layer the DeepSeek-V3 router
its config keys name; the reference has no such family).

Its blocks are not all alike, so the family tells the framework each block's
KIND, the pair (MLP kind, attention kind) read at the block's absolute index:

- attention: RMS norm over each HEAD of q and of k (QK-norm, vectors of
  ``head_dim``); in a ``sliding_attention`` layer rotary embeddings and a
  window of ``sliding_window`` positions, in a ``full_attention`` layer
  neither (no positional signal but the causal mask);
- MLP: ``dense`` is a SwiGLU of ``intermediate_size`` (the model's first
  layer); ``sparse`` is a sigmoid router over ``num_experts_routed`` experts
  whose top k are chosen by score + ``e_score_correction_bias`` and weighed by
  score, renormalised and scaled by ``routed_scaling_factor``, of which this
  server holds ``num_experts`` from ``first_expert`` on, beside one shared
  expert that every token takes (models/moe.py; of ``moe.grouped_dispatch``'s
  three a step's decode rows take "hit", the held experts its live rows
  reach, and every chunk the all-experts einsum).

Pre-norm: ``h = x + attn(ln1(x)); y = h + mlp(ln2(h))``.
"""

from __future__ import annotations

import contextlib
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from petals_tpu.models.common import (
    KVCache,
    absolute_positions,
    mm,
    project_heads,
    rms_norm,
    silu,
    update_kv_cache,
)
from petals_tpu.models.exaone_moe.config import DENSE, SLIDING, ExaoneMoeBlockConfig
from petals_tpu.models.moe import MoeDims, choose_dispatch, moe_apply
from petals_tpu.models.registry import ModelFamily, register_family
from petals_tpu.ops.attention import attend
from petals_tpu.ops.rotary import apply_rotary, rotary_tables


def block_kind(cfg: ExaoneMoeBlockConfig, block_index: int) -> tuple:
    """(``dense`` | ``sparse``, ``sliding`` | ``full``) of the model's block
    ``block_index``: a server that starts at block 17 reads its own."""
    return (cfg.mlp_layer_types[block_index], "sliding" if cfg.layer_types[block_index] == SLIDING else "full")


def block_window(cfg: ExaoneMoeBlockConfig, kind: tuple) -> Optional[int]:
    return cfg.sliding_window if kind[1] == "sliding" and cfg.sliding_window else None


def moe_dims(cfg: ExaoneMoeBlockConfig, kind: tuple) -> Optional[MoeDims]:
    if kind[0] == DENSE:
        return None
    return MoeDims(cfg.num_experts, cfg.num_experts_per_tok, cfg.hidden_size, cfg.moe_intermediate_size,
                   routed=cfg.num_experts_routed, first=cfg.first_expert)


def block_apply(
    params: dict,
    hidden_states: jnp.ndarray,
    kv: Optional[KVCache],
    position,
    cfg: ExaoneMoeBlockConfig,
    *,
    kind: tuple,
    use_flash: bool = False,
    tp_mesh=None,
    n_valid=None,
    live_rows=None,  # bool [batch] from a lane pool's step: the rows that are not idle lanes (None: all)
) -> Tuple[jnp.ndarray, Optional[KVCache]]:
    mlp_kind, attn_kind = kind
    batch, seq, _ = hidden_states.shape
    hq, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim

    residual = hidden_states
    x = rms_norm(hidden_states, params["ln1"], cfg.rms_norm_eps)
    q, k, v = (project_heads(x, params[name]) for name in ("wq", "wk", "wv"))
    q = q.reshape(batch, seq, hq, d)
    k = k.reshape(batch, seq, hkv, d)
    v = v.reshape(batch, seq, hkv, d)
    with jax.named_scope("ptu.attn.qk_norm"):  # over each head
        q = rms_norm(q, params["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.rms_norm_eps)

    window = block_window(cfg, kind)
    if attn_kind == "sliding":
        positions = absolute_positions(position, batch, seq)
        cos, sin = rotary_tables(positions, d, theta=cfg.rope_theta)
        q = apply_rotary(q, cos, sin)
        k = apply_rotary(k, cos, sin)

    k_all, v_all, kv_length = update_kv_cache(kv, k, v, position, n_valid)
    with jax.named_scope("ptu.attn.window") if window else contextlib.nullcontext():
        attn = attend(
            q, k_all, v_all, q_offset=position, kv_length=kv_length,
            sliding_window=window, use_flash=use_flash, tp_mesh=tp_mesh,
        )
    hidden_states = residual + mm(attn.reshape(batch, seq, hq * d), params["wo"])

    residual = hidden_states
    x = rms_norm(hidden_states, params["ln2"], cfg.rms_norm_eps)
    if mlp_kind == DENSE:
        mlp = mm(silu(mm(x, params["wg"])) * mm(x, params["wu"]), params["wd"])
    else:
        mlp = moe_apply(
            params, x, top_k=cfg.num_experts_per_tok, renormalize=cfg.norm_topk_prob,
            dispatch=choose_dispatch(params, moe_dims(cfg, kind), seq, mesh=tp_mesh is not None),
            scoring="sigmoid", scale=cfg.routed_scaling_factor, first=cfg.first_expert, live_rows=live_rows,
        )
    hidden_states = residual + mlp

    new_kv = (k_all, v_all) if kv is not None else None
    return hidden_states, new_kv


# ----------------------------------------------------------------------------------
# HF checkpoint mapping
# ----------------------------------------------------------------------------------

_HF_BLOCK_PREFIXES = ("model.layers.{i}.",)


def hf_to_block_params(tensors: dict, cfg: ExaoneMoeBlockConfig, kind: tuple) -> dict:
    """The held experts ``[first_expert, first_expert + num_experts)`` are
    sliced out of the published ``mlp.experts.{e}``; the router and its bias
    stay as wide as published."""

    def t(name):
        return np.ascontiguousarray(np.asarray(tensors[name]).T)

    params = {
        "ln1": np.asarray(tensors["input_layernorm.weight"]),
        "wq": t("self_attn.q_proj.weight"),
        "wk": t("self_attn.k_proj.weight"),
        "wv": t("self_attn.v_proj.weight"),
        "wo": t("self_attn.o_proj.weight"),
        "q_norm": np.asarray(tensors["self_attn.q_norm.weight"]),
        "k_norm": np.asarray(tensors["self_attn.k_norm.weight"]),
        "ln2": np.asarray(tensors["post_attention_layernorm.weight"]),
    }
    if kind[0] == DENSE:
        params.update(wg=t("mlp.gate_proj.weight"), wu=t("mlp.up_proj.weight"), wd=t("mlp.down_proj.weight"))
        return params
    held = range(cfg.first_expert, cfg.first_expert + cfg.num_experts)

    def stack(proj):
        return np.stack([t(f"mlp.experts.{e}.{proj}.weight") for e in held])

    params.update(
        gate=t("mlp.gate.weight"),
        gate_bias=np.asarray(tensors["mlp.gate.e_score_correction_bias"], np.float32),
        w1=stack("gate_proj"), w2=stack("down_proj"), w3=stack("up_proj"),
    )
    if cfg.num_shared_experts:
        params.update(
            ws1=t("mlp.shared_experts.gate_proj.weight"),
            ws2=t("mlp.shared_experts.down_proj.weight"),
            ws3=t("mlp.shared_experts.up_proj.weight"),
        )
    return params


def block_param_shapes(cfg: ExaoneMoeBlockConfig, kind: tuple, dtype=jnp.bfloat16) -> dict:
    h, hq, hkv, d = cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    S = jax.ShapeDtypeStruct
    shapes = {
        "ln1": S((h,), dtype),
        "wq": S((h, hq * d), dtype),
        "wk": S((h, hkv * d), dtype),
        "wv": S((h, hkv * d), dtype),
        "wo": S((hq * d, h), dtype),
        "q_norm": S((d,), dtype),
        "k_norm": S((d,), dtype),
        "ln2": S((h,), dtype),
    }
    if kind[0] == DENSE:
        m = cfg.intermediate_size
        shapes.update(wg=S((h, m), dtype), wu=S((h, m), dtype), wd=S((m, h), dtype))
        return shapes
    m, E = cfg.moe_intermediate_size, cfg.num_experts
    shapes.update(
        gate=S((h, cfg.num_experts_routed), dtype), gate_bias=S((cfg.num_experts_routed,), jnp.float32),
        w1=S((E, h, m), dtype), w2=S((E, m, h), dtype), w3=S((E, h, m), dtype),
    )
    if cfg.num_shared_experts:
        ms = m * cfg.num_shared_experts
        shapes.update(ws1=S((h, ms), dtype), ws2=S((ms, h), dtype), ws3=S((h, ms), dtype))
    return shapes


# tp_pspecs, quantizable_leaves and lora_targets are not declared: a span of
# more than one kind of block is not sharded, quantized or adapted yet, and
# parallel/tp.py, utils/convert_block.py and utils/peft.py refuse the family
# by name (tests/test_exaone_moe.py)
FAMILY = register_family(
    ModelFamily(
        name="exaone_moe",
        config_from_hf=ExaoneMoeBlockConfig.from_hf_config,
        block_apply=block_apply,
        hf_block_prefixes=_HF_BLOCK_PREFIXES,
        hf_to_block_params=hf_to_block_params,
        block_param_shapes=block_param_shapes,
        moe_dims=moe_dims,
        block_kind=block_kind,
        block_window=block_window,
        cast_exempt=("gate_bias",),
    )
)
