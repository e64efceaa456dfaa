"""Qwen3-Next decoder block as a pure jitted JAX function (``qwen3_next``;
transformers 4.57.6 ``models/qwen3_next/modeling_qwen3_next.py``
``Qwen3NextDecoderLayer``; the reference has no such family).

Its blocks are of two KINDS, read from ``layer_types`` at the block's absolute
index, three ``linear_attention`` to every ``full_attention``; BOTH end in the
expert layer:

- ``linear_attention``: the gated delta rule (models/gated_delta.py, the mixer
  this family shares with olmo_hybrid), 16 key heads under 32 value heads. It
  caches no keys and values: a lane holds, a layer, a float32 state a value
  head and the conv's last rows, which ``block_state`` declares to the
  framework.
- ``full_attention``: softmax attention over cached keys and values whose
  output a sigmoid gate scales before ``o_proj`` (``q_proj`` gives a head its
  query and its gate side by side; taken apart at load), an RMS norm over each
  HEAD of q and of k, and rotary embeddings over the first ``rotary_dim`` dims
  of a head only.
- the expert layer (models/moe.py): a softmax router over
  ``num_experts_routed`` experts, the top k renormalised, of which this server
  holds ``num_experts`` from ``first_expert`` on, beside a shared expert that
  every token takes scaled by ``sigmoid(x w_sg)``.

Pre-norm: ``h = x + mixer(ln1(x)); y = h + moe(ln2(h))``. Every norm but the
delta rule's output norm is zero-centred as published, ``rms(x) * (1 + w)``;
the 1 is folded into the weight at load, in float32 (``cast_exempt``), as
Gemma's is.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from petals_tpu.models.common import absolute_positions, mm, project_heads, rms_norm, update_kv_cache
from petals_tpu.models.gated_delta import MixerDims, gated_delta_mixer, state_shapes
from petals_tpu.models.moe import MoeDims, choose_dispatch, moe_apply
from petals_tpu.models.qwen3_next.config import FULL, LINEAR, Qwen3NextBlockConfig
from petals_tpu.models.registry import ModelFamily, register_family
from petals_tpu.ops.attention import attend
from petals_tpu.ops.rotary import apply_rotary, rotary_tables


def block_kind(cfg: Qwen3NextBlockConfig, block_index: int) -> str:
    return cfg.layer_types[block_index]


def mixer_dims(cfg: Qwen3NextBlockConfig) -> MixerDims:
    return MixerDims(cfg.linear_num_key_heads, cfg.linear_num_value_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim,
                     cfg.linear_conv_kernel_dim)


def block_state(cfg: Qwen3NextBlockConfig, kind: str) -> Optional[tuple]:
    return state_shapes(mixer_dims(cfg)) if kind == LINEAR else None


def moe_dims(cfg: Qwen3NextBlockConfig, kind: str) -> MoeDims:
    return MoeDims(cfg.num_experts, cfg.num_experts_per_tok, cfg.hidden_size, cfg.moe_intermediate_size,
                   routed=cfg.num_experts_routed, first=cfg.first_expert)


def _full_attention(params: dict, x: jnp.ndarray, kv, position, cfg, n_valid, use_flash, tp_mesh):
    batch, seq, _ = x.shape
    hq, hkv, d, rd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim, cfg.rotary_dim
    q, k, v = (project_heads(x, params[name]) for name in ("wq", "wk", "wv"))
    q = q.reshape(batch, seq, hq, d)
    k = k.reshape(batch, seq, hkv, d)
    v = v.reshape(batch, seq, hkv, d)
    with jax.named_scope("ptu.attn.qk_norm"):  # over each head
        q = rms_norm(q, params["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.rms_norm_eps)
    cos, sin = rotary_tables(absolute_positions(position, batch, seq), rd, theta=cfg.rope_theta)
    q, k = (jnp.concatenate([apply_rotary(t[..., :rd], cos, sin), t[..., rd:]], axis=-1) for t in (q, k))
    k_all, v_all, kv_length = update_kv_cache(kv, k, v, position, n_valid)
    attn = attend(q, k_all, v_all, q_offset=position, kv_length=kv_length, use_flash=use_flash, tp_mesh=tp_mesh)
    with jax.named_scope("ptu.attn.out_gate"):
        gate = jax.nn.sigmoid(mm(x, params["wqg"]).astype(jnp.float32))
        attn = (attn.reshape(batch, seq, hq * d).astype(jnp.float32) * gate).astype(x.dtype)
    return mm(attn, params["wo"]), ((k_all, v_all) if kv is not None else None)


def block_apply(
    params: dict,
    hidden_states: jnp.ndarray,
    kv,  # full_attention: (k, v) as every family's; linear_attention: the lanes' state, block_state's leaves
    position,
    cfg: Qwen3NextBlockConfig,
    *,
    kind: str,
    use_flash: bool = False,
    tp_mesh=None,
    n_valid=None,
    live_rows=None,  # bool [batch] from a lane pool's step: the rows that are not idle lanes (None: all)
) -> Tuple[jnp.ndarray, Optional[tuple]]:
    x = rms_norm(hidden_states, params["ln1"], cfg.rms_norm_eps)
    if kind == LINEAR:
        mixed, new_kv = gated_delta_mixer(params, x, kv, position, mixer_dims(cfg), cfg.rms_norm_eps, n_valid, live_rows)
    else:
        mixed, new_kv = _full_attention(params, x, kv, position, cfg, n_valid, use_flash, tp_mesh)
    hidden_states = hidden_states + mixed
    x = rms_norm(hidden_states, params["ln2"], cfg.rms_norm_eps)
    mlp = moe_apply(
        params, x, top_k=cfg.num_experts_per_tok, renormalize=cfg.norm_topk_prob,
        dispatch=choose_dispatch(params, moe_dims(cfg, kind), x.shape[1], mesh=tp_mesh is not None),
        first=cfg.first_expert, live_rows=live_rows,
    )
    return hidden_states + mlp, new_kv


# ----------------------------------------------------------------------------------
# HF checkpoint mapping
# ----------------------------------------------------------------------------------

_HF_BLOCK_PREFIXES = ("model.layers.{i}.",)
ZERO_CENTRED = ("ln1", "ln2", "q_norm", "k_norm")  # served as 1 + w, folded at load in float32


def _t(w) -> np.ndarray:
    """A Linear's [out, in] as the served [in, out]."""
    return np.ascontiguousarray(np.asarray(w).T)


def split_qkvz(w: np.ndarray, dims: MixerDims) -> dict:
    """``in_proj_qkvz.weight`` [key heads x (2 d_k + 2 r d_v), h], a key head's
    q, k and its ``r`` value heads' v and z side by side
    (``fix_query_key_value_ordering``), as the mixer's ``wq`` / ``wk`` / ``wv``
    / ``wz``: all heads' q, then k, then v, then z, each [h, heads x d]."""
    hk, r, d_k, d_v = dims.key_heads, dims.heads // dims.key_heads, dims.d_k, dims.d_v
    w = np.asarray(w).reshape(hk, 2 * d_k + 2 * r * d_v, -1)
    parts = np.split(w, (d_k, 2 * d_k, 2 * d_k + r * d_v), axis=1)
    return {name: _t(part.reshape(-1, w.shape[-1])) for name, part in zip(("wq", "wk", "wv", "wz"), parts)}


def split_ba(w: np.ndarray, dims: MixerDims) -> dict:
    """``in_proj_ba.weight`` [key heads x 2 r, h], a key head's ``r`` b and
    ``r`` a side by side, as ``wb`` / ``wa`` [h, value heads]."""
    hk, r = dims.key_heads, dims.heads // dims.key_heads
    w = np.asarray(w).reshape(hk, 2 * r, -1)
    return {"wb": _t(w[:, :r].reshape(-1, w.shape[-1])), "wa": _t(w[:, r:].reshape(-1, w.shape[-1]))}


def hf_to_block_params(tensors: dict, cfg: Qwen3NextBlockConfig, kind: str) -> dict:
    """The held experts ``[first_expert, first_expert + num_experts)`` are
    sliced out of the published ``mlp.experts.{e}``; the router stays as wide
    as published."""
    params = {"ln1": tensors["input_layernorm.weight"], "ln2": tensors["post_attention_layernorm.weight"]}
    if kind == LINEAR:
        dims, p = mixer_dims(cfg), "linear_attn."
        params.update(split_qkvz(tensors[p + "in_proj_qkvz.weight"], dims))
        params.update(split_ba(tensors[p + "in_proj_ba.weight"], dims))
        params.update(
            conv=_t(np.asarray(tensors[p + "conv1d.weight"])[:, 0, :]),  # a depthwise Conv1d's [channels, 1, taps] as [taps, channels]
            a_log=np.asarray(tensors[p + "A_log"]), dt_bias=np.asarray(tensors[p + "dt_bias"]),
            o_norm=np.asarray(tensors[p + "norm.weight"]), wo=_t(tensors[p + "out_proj.weight"]),
        )
    else:
        hq, d, p = cfg.num_attention_heads, cfg.head_dim, "self_attn."
        q_and_gate = np.asarray(tensors[p + "q_proj.weight"]).reshape(hq, 2 * d, -1)  # a head's query, then its gate
        params.update(
            wq=_t(q_and_gate[:, :d].reshape(hq * d, -1)), wqg=_t(q_and_gate[:, d:].reshape(hq * d, -1)),
            wk=_t(tensors[p + "k_proj.weight"]), wv=_t(tensors[p + "v_proj.weight"]), wo=_t(tensors[p + "o_proj.weight"]),
            q_norm=tensors[p + "q_norm.weight"], k_norm=tensors[p + "k_norm.weight"],
        )
    for name in ZERO_CENTRED:
        if name in params:
            params[name] = np.asarray(params[name], np.float32) + 1.0
    held = range(cfg.first_expert, cfg.first_expert + cfg.num_experts)

    def stack(proj):
        return np.stack([_t(tensors[f"mlp.experts.{e}.{proj}.weight"]) for e in held])

    params.update(
        gate=_t(tensors["mlp.gate.weight"]), w1=stack("gate_proj"), w2=stack("down_proj"), w3=stack("up_proj"),
        ws1=_t(tensors["mlp.shared_expert.gate_proj.weight"]), ws2=_t(tensors["mlp.shared_expert.down_proj.weight"]),
        ws3=_t(tensors["mlp.shared_expert.up_proj.weight"]), wsg=_t(tensors["mlp.shared_expert_gate.weight"]),
    )
    return params


def block_param_shapes(cfg: Qwen3NextBlockConfig, kind: str, dtype=jnp.bfloat16) -> dict:
    h, m, ms, E = cfg.hidden_size, cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size, cfg.num_experts
    S = jax.ShapeDtypeStruct
    shapes = {
        "ln1": S((h,), dtype), "ln2": S((h,), dtype), "gate": S((h, cfg.num_experts_routed), dtype),
        "w1": S((E, h, m), dtype), "w2": S((E, m, h), dtype), "w3": S((E, h, m), dtype),
        "ws1": S((h, ms), dtype), "ws2": S((ms, h), dtype), "ws3": S((h, ms), dtype), "wsg": S((h, 1), dtype),
    }
    if kind == FULL:
        hq, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        shapes.update(
            wq=S((h, hq * d), dtype), wqg=S((h, hq * d), dtype), wk=S((h, hkv * d), dtype), wv=S((h, hkv * d), dtype),
            wo=S((hq * d, h), dtype), q_norm=S((d,), dtype), k_norm=S((d,), dtype),
        )
        return shapes
    dims = mixer_dims(cfg)
    shapes.update(
        wq=S((h, dims.key_heads * dims.d_k), dtype), wk=S((h, dims.key_heads * dims.d_k), dtype),
        wv=S((h, dims.heads * dims.d_v), dtype), wz=S((h, dims.heads * dims.d_v), dtype),
        wa=S((h, dims.heads), dtype), wb=S((h, dims.heads), dtype), wo=S((dims.heads * dims.d_v, h), dtype),
        conv=S((dims.taps, dims.channels), dtype), a_log=S((dims.heads,), dtype), dt_bias=S((dims.heads,), dtype),
        o_norm=S((dims.d_v,), dtype),
    )
    return shapes


# tp_pspecs, quantizable_leaves and lora_targets are not declared: a span with a recurrent state is not
# sharded, quantized or adapted yet, and parallel/tp.py, utils/convert_block.py and utils/peft.py refuse
# the family by name (tests/test_qwen3_next.py)
FAMILY = register_family(
    ModelFamily(
        name="qwen3_next",
        config_from_hf=Qwen3NextBlockConfig.from_hf_config,
        block_apply=block_apply,
        hf_block_prefixes=_HF_BLOCK_PREFIXES,
        hf_to_block_params=hf_to_block_params,
        block_param_shapes=block_param_shapes,
        moe_dims=moe_dims,
        block_kind=block_kind,
        block_state=block_state,
        cast_exempt=("a_log", "dt_bias", *ZERO_CENTRED, "norm"),
    )
)
