"""SmallThinker decoder block as a pure jitted JAX function (``smallthinker``; the installed transformers has no class for
it, so the block follows the published configuration and description; the reference has no such family).

Its blocks are of kinds, the pair (rotary | ``nope``, ``sliding`` | ``full``) read at the block's absolute index from
``rope_layout`` and ``sliding_window_layout``: a layer with rotary embeddings and a window of ``sliding_window_size``
positions, or full attention with no positional signal but the causal mask.

- The ROUTER reads the layer's INPUT, before the input norm and before attention (``moe.moe_route`` on ``h``), and the
  experts are fed the normed state after attention (``moe.moe_experts`` on ``m``): routing and application are two calls.
- The experts are ReGLU: ``(relu(m Wgate) * (m Wup)) Wdown``, 64 of them, the top 6 kept, their softmax weights
  renormalised over the kept; no shared expert, no bias, no QK-norm.

Pre-norm: ``h1 = h + attn(ln1(h)); out = h1 + experts(ln2(h1), routed on h)``.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from petals_tpu.models.common import KVCache, absolute_positions, mm, project_heads, rms_norm, update_kv_cache
from petals_tpu.models.moe import MoeDims, choose_dispatch, moe_experts, moe_route
from petals_tpu.models.registry import ModelFamily, register_family
from petals_tpu.models.smallthinker.config import SmallThinkerBlockConfig
from petals_tpu.ops.attention import attend
from petals_tpu.ops.rotary import apply_rotary, rotary_tables


def block_kind(cfg: SmallThinkerBlockConfig, block_index: int) -> tuple:
    """(``rope`` | ``nope``, ``sliding`` | ``full``) of the model's block ``block_index``."""
    return ("rope" if cfg.rope_layout[block_index] else "nope", "sliding" if cfg.sliding_window_layout[block_index] else "full")


def block_window(cfg: SmallThinkerBlockConfig, kind: tuple) -> Optional[int]:
    return cfg.sliding_window_size if kind[1] == "sliding" else None


def moe_dims(cfg: SmallThinkerBlockConfig, kind: tuple) -> MoeDims:
    return MoeDims(cfg.num_experts, cfg.num_experts_per_tok, cfg.hidden_size, cfg.moe_ffn_hidden_size)


def block_apply(
    params: dict,
    hidden_states: jnp.ndarray,
    kv: Optional[KVCache],
    position,
    cfg: SmallThinkerBlockConfig,
    *,
    kind: tuple,
    use_flash: bool = False,
    tp_mesh=None,
    n_valid=None,
    live_rows=None,  # bool [batch] from a lane pool's step: the rows that are not idle lanes (None: all)
) -> Tuple[jnp.ndarray, Optional[KVCache]]:
    batch, seq, _ = hidden_states.shape
    hq, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim

    residual = hidden_states
    # the router reads the layer's input as it came, before the norm and the attention
    routed = moe_route(params, hidden_states, top_k=cfg.num_experts_per_tok, renormalize=cfg.norm_topk_prob)
    x = rms_norm(hidden_states, params["ln1"], cfg.rms_norm_eps)
    q, k, v = (project_heads(x, params[name]) for name in ("wq", "wk", "wv"))
    q = q.reshape(batch, seq, hq, d)
    k = k.reshape(batch, seq, hkv, d)
    v = v.reshape(batch, seq, hkv, d)

    if kind[0] == "rope":
        positions = absolute_positions(position, batch, seq)
        cos, sin = rotary_tables(positions, d, theta=cfg.rope_theta)
        q = apply_rotary(q, cos, sin)
        k = apply_rotary(k, cos, sin)

    window = block_window(cfg, kind)
    k_all, v_all, kv_length = update_kv_cache(kv, k, v, position, n_valid)
    with jax.named_scope("ptu.attn.window" if window else "ptu.attn.full"):
        attn = attend(
            q, k_all, v_all, q_offset=position, kv_length=kv_length,
            sliding_window=window, use_flash=use_flash, tp_mesh=tp_mesh,
        )
    hidden_states = residual + mm(attn.reshape(batch, seq, hq * d), params["wo"])

    residual = hidden_states
    x = rms_norm(hidden_states, params["ln2"], cfg.rms_norm_eps)
    mlp = moe_experts(
        params, x, *routed, dispatch=choose_dispatch(params, moe_dims(cfg, kind), seq, mesh=tp_mesh is not None),
        live_rows=live_rows, activation="relu",
    )
    hidden_states = residual + mlp

    new_kv = (k_all, v_all) if kv is not None else None
    return hidden_states, new_kv


# ----------------------------------------------------------------------------------
# HF checkpoint mapping
# ----------------------------------------------------------------------------------

_HF_BLOCK_PREFIXES = ("model.layers.{i}.",)


def hf_to_block_params(tensors: dict, cfg: SmallThinkerBlockConfig, kind: tuple) -> dict:
    def t(name):
        return np.ascontiguousarray(np.asarray(tensors[name]).T)

    def stack(proj):
        return np.stack([t(f"block_sparse_moe.experts.{e}.{proj}.weight") for e in range(cfg.num_experts)])

    return {
        "ln1": np.asarray(tensors["input_layernorm.weight"]),
        "wq": t("self_attn.q_proj.weight"),
        "wk": t("self_attn.k_proj.weight"),
        "wv": t("self_attn.v_proj.weight"),
        "wo": t("self_attn.o_proj.weight"),
        "ln2": np.asarray(tensors["post_attention_layernorm.weight"]),
        "gate": t("block_sparse_moe.primary_router.weight"),
        "w1": stack("gate"), "w2": stack("down"), "w3": stack("up"),
    }


def block_param_shapes(cfg: SmallThinkerBlockConfig, kind: tuple, dtype=jnp.bfloat16) -> dict:
    h, hq, hkv, d = cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    m, E = cfg.moe_ffn_hidden_size, cfg.num_experts
    S = jax.ShapeDtypeStruct
    return {
        "ln1": S((h,), dtype),
        "wq": S((h, hq * d), dtype),
        "wk": S((h, hkv * d), dtype),
        "wv": S((h, hkv * d), dtype),
        "wo": S((hq * d, h), dtype),
        "ln2": S((h,), dtype),
        "gate": S((h, E), dtype),
        "w1": S((E, h, m), dtype), "w2": S((E, m, h), dtype), "w3": S((E, h, m), dtype),
    }


# tp_pspecs, quantizable_leaves and lora_targets are not declared: a span of more than one kind of block is not sharded,
# quantized or adapted yet, and parallel/tp.py, utils/convert_block.py and utils/peft.py refuse the family by name
FAMILY = register_family(
    ModelFamily(
        name="smallthinker",
        config_from_hf=SmallThinkerBlockConfig.from_hf_config,
        block_apply=block_apply,
        hf_block_prefixes=_HF_BLOCK_PREFIXES,
        hf_to_block_params=hf_to_block_params,
        block_param_shapes=block_param_shapes,
        moe_dims=moe_dims,
        block_kind=block_kind,
        block_window=block_window,
    )
)
