"""Olmo-Hybrid decoder block as a pure jitted JAX function (``olmo_hybrid``; the
installed transformers has no class for it: the linear-attention layer follows
``Qwen3NextGatedDeltaNet``, whose config keys it shares, and the full layer
and the MLP ``modeling_olmo3.py``; the reference has no such family).

Its blocks are of two KINDS, read from ``layer_types`` at the block's absolute
index, three ``linear_attention`` to every ``full_attention``:

- ``full_attention``: softmax attention over cached keys and values, an RMS
  norm over the WHOLE projected q and k (all heads together, as OLMoE's), no
  rotary embedding (``rope_theta`` is null as published): the causal mask is
  the only positional signal.
- ``linear_attention``: the gated delta rule (models/gated_delta.py, the mixer
  this family shares with qwen3_next, over ops/linear_attention.py). It
  caches no keys and values. A lane holds, a layer, a STATE of fixed size
  whatever the context: a float32 matrix of ``d_k x d_v`` a head, and the last
  ``K - 1`` rows of the short conv's input. ``block_state`` declares both to
  the framework, which keeps them in a pool beside the pages and hands a block
  its lanes' slices as ``kv``; nothing outside this file and ops/ knows what
  they mean. A row at position 0 starts from a zero state, so a lane that a
  new session takes needs no clearing. A state cannot be cut back to an
  earlier position: the framework refuses what would need it.

Norms sit on each sublayer's OUTPUT: ``h = x + n1(mixer(x)); y = h + n2(mlp(h))``.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from petals_tpu.models.common import mm, project_heads, rms_norm, silu, update_kv_cache
from petals_tpu.models.gated_delta import MixerDims, gated_delta_mixer, state_shapes
from petals_tpu.models.olmo_hybrid.config import FULL, LINEAR, OlmoHybridBlockConfig
from petals_tpu.models.registry import ModelFamily, register_family
from petals_tpu.ops.attention import attend


def block_kind(cfg: OlmoHybridBlockConfig, block_index: int) -> str:
    return cfg.layer_types[block_index]


def mixer_dims(cfg: OlmoHybridBlockConfig) -> MixerDims:
    """As many key heads as value heads, beta doubled where ``linear_allow_neg_eigval``."""
    return MixerDims(cfg.linear_num_heads, cfg.linear_num_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim,
                     cfg.linear_conv_kernel_dim, 2.0 if cfg.linear_allow_neg_eigval else 1.0)


def block_state(cfg: OlmoHybridBlockConfig, kind: str) -> Optional[tuple]:
    """What a lane holds for a block of ``kind`` in place of pages of keys and
    values: ``((shape, dtype), ...)`` a lane, dtype None for the cache's own.
    None for a block that keeps keys and values."""
    return state_shapes(mixer_dims(cfg)) if kind == LINEAR else None


def _full_attention(params: dict, x: jnp.ndarray, kv, position, cfg, n_valid, use_flash, tp_mesh):
    batch, seq, _ = x.shape
    hq, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    q, k, v = (project_heads(x, params[name]) for name in ("wq", "wk", "wv"))
    with jax.named_scope("ptu.attn.qk_norm"):  # over all heads together
        q = rms_norm(q, params["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.rms_norm_eps)
    # heads of zeros up to the cache's count (cfg.cache_kv_heads says why); as many kv heads as heads, so
    # q gets them too, they attend over zeros and give zeros, and are cut off again before wo
    spare = ((0, 0), (0, 0), (0, cfg.cache_kv_heads - hkv), (0, 0))
    q, k, v = (jnp.pad(t.reshape(batch, seq, heads, d), spare) for t, heads in ((q, hq), (k, hkv), (v, hkv)))
    k_all, v_all, kv_length = update_kv_cache(kv, k, v, position, n_valid)
    attn = attend(q, k_all, v_all, q_offset=position, kv_length=kv_length, use_flash=use_flash, tp_mesh=tp_mesh)
    return mm(attn[:, :, :hq].reshape(batch, seq, hq * d), params["wo"]), ((k_all, v_all) if kv is not None else None)


def block_apply(
    params: dict,
    hidden_states: jnp.ndarray,
    kv,  # full_attention: (k, v) as every family's; linear_attention: the lanes' state, block_state's leaves
    position,
    cfg: OlmoHybridBlockConfig,
    *,
    kind: str,
    use_flash: bool = False,
    tp_mesh=None,
    n_valid=None,
    live_rows=None,  # bool [batch] from a lane pool's step: the rows that are not idle lanes (None: all)
) -> Tuple[jnp.ndarray, Optional[tuple]]:
    if kind == LINEAR:
        mixed, new_kv = gated_delta_mixer(params, hidden_states, kv, position, mixer_dims(cfg), cfg.rms_norm_eps, n_valid, live_rows)
    else:
        mixed, new_kv = _full_attention(params, hidden_states, kv, position, cfg, n_valid, use_flash, tp_mesh)
    hidden_states = hidden_states + rms_norm(mixed, params["ln1"], cfg.rms_norm_eps)
    mlp = mm(silu(mm(hidden_states, params["wg"])) * mm(hidden_states, params["wu"]), params["wd"])
    return hidden_states + rms_norm(mlp, params["ln2"], cfg.rms_norm_eps), new_kv


# ----------------------------------------------------------------------------------
# HF checkpoint mapping
# ----------------------------------------------------------------------------------

_HF_BLOCK_PREFIXES = ("model.layers.{i}.",)

# leaf -> HF name under the layer's prefix; matrices are stored [out, in] and served [in, out]
_MLP = {"wg": "mlp.gate_proj.weight", "wu": "mlp.up_proj.weight", "wd": "mlp.down_proj.weight"}
_MATRICES = {
    FULL: {"wq": "self_attn.q_proj.weight", "wk": "self_attn.k_proj.weight", "wv": "self_attn.v_proj.weight",
           "wo": "self_attn.o_proj.weight", **_MLP},
    LINEAR: {"wq": "linear_attn.q_proj.weight", "wk": "linear_attn.k_proj.weight", "wv": "linear_attn.v_proj.weight",
             "wz": "linear_attn.g_proj.weight", "wa": "linear_attn.a_proj.weight", "wb": "linear_attn.b_proj.weight",
             "wo": "linear_attn.o_proj.weight", **_MLP},
}
_NORMS = {"ln1": "post_attention_layernorm.weight", "ln2": "post_feedforward_layernorm.weight"}
_VECTORS = {
    FULL: {"q_norm": "self_attn.q_norm.weight", "k_norm": "self_attn.k_norm.weight", **_NORMS},
    LINEAR: {"a_log": "linear_attn.A_log", "dt_bias": "linear_attn.dt_bias", "o_norm": "linear_attn.o_norm.weight", **_NORMS},
}


def hf_to_block_params(tensors: dict, cfg: OlmoHybridBlockConfig, kind: str) -> dict:
    params = {leaf: np.ascontiguousarray(np.asarray(tensors[name]).T) for leaf, name in _MATRICES[kind].items()}
    params.update({leaf: np.asarray(tensors[name]) for leaf, name in _VECTORS[kind].items()})
    if kind == LINEAR:  # a depthwise Conv1d's [channels, 1, taps] as [taps, channels]
        params["conv"] = np.ascontiguousarray(np.asarray(tensors["linear_attn.conv1d.weight"])[:, 0, :].T)
    return params


def block_param_shapes(cfg: OlmoHybridBlockConfig, kind: str, dtype=jnp.bfloat16) -> dict:
    h, m = cfg.hidden_size, cfg.intermediate_size
    S = jax.ShapeDtypeStruct
    shapes = {"ln1": S((h,), dtype), "ln2": S((h,), dtype), "wg": S((h, m), dtype), "wu": S((h, m), dtype), "wd": S((m, h), dtype)}
    if kind == FULL:
        hq, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        shapes.update(
            wq=S((h, hq * d), dtype), wk=S((h, hkv * d), dtype), wv=S((h, hkv * d), dtype), wo=S((hq * d, h), dtype),
            q_norm=S((hq * d,), dtype), k_norm=S((hkv * d,), dtype),
        )
        return shapes
    heads, d_k, d_v = cfg.linear_num_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim
    shapes.update(
        wq=S((h, heads * d_k), dtype), wk=S((h, heads * d_k), dtype), wv=S((h, heads * d_v), dtype),
        wz=S((h, heads * d_v), dtype), wa=S((h, heads), dtype), wb=S((h, heads), dtype), wo=S((heads * d_v, h), dtype),
        conv=S((cfg.linear_conv_kernel_dim, mixer_dims(cfg).channels), dtype),
        a_log=S((heads,), dtype), dt_bias=S((heads,), dtype), o_norm=S((d_v,), dtype),
    )
    return shapes


# tp_pspecs, quantizable_leaves and lora_targets are not declared: a span with a recurrent state is not
# sharded, quantized or adapted yet, and parallel/tp.py, utils/convert_block.py and utils/peft.py refuse
# the family by name (tests/test_olmo_hybrid.py)
FAMILY = register_family(
    ModelFamily(
        name="olmo_hybrid",
        config_from_hf=OlmoHybridBlockConfig.from_hf_config,
        block_apply=block_apply,
        hf_block_prefixes=_HF_BLOCK_PREFIXES,
        hf_to_block_params=hf_to_block_params,
        block_param_shapes=block_param_shapes,
        block_kind=block_kind,
        block_state=block_state,
        cast_exempt=("a_log", "dt_bias"),
    )
)
