"""Jamba decoder block as a pure jitted JAX function (``jamba``; transformers
4.57.6 ``models/jamba/modeling_jamba.py`` ``JambaMambaDecoderLayer`` /
``JambaAttentionDecoderLayer``; the reference has no such family).

Its blocks are of two KINDS, ``attention`` where ``i % attn_layer_period ==
attn_layer_offset`` at the block's absolute index and ``mamba`` otherwise;
both end in a dense SwiGLU (``num_experts`` 1: a routed feed-forward is
refused at load):

- ``mamba``: Mamba-1's selective state-space mixer (ops/selective_scan.py)
  with Jamba's own RMS norms on ``dt``, ``B`` and ``C``: ``[u ; z] =
  in_proj(x)``; ``u = silu(conv(u) + bias)``, a depthwise causal conv of
  ``d_conv`` taps; ``[dt ; B ; C] = x_proj(u)``, each normed; ``dt =
  softplus(dt_proj(dt))`` with a bias; the scan over ``A = -exp(A_log)``, ``B``,
  ``C``, ``D``; ``out_proj(y * silu(z))``. It caches no keys and values. A lane
  holds, a layer, a STATE of fixed size whatever the context: float32 ``[d_state,
  d_inner]`` (the checkpoint's ``[d_inner, d_state]`` turned, so that the
  channels lie along the chip's lanes and the pool's bytes are the declared
  ones) and the last ``d_conv - 1`` rows of the conv's input. ``block_state``
  declares both to the framework, which keeps them in a pool beside the pages
  and hands a block its lanes' slices as ``kv``. A row at position 0 starts
  from a zero state, so a lane that a new session takes needs no clearing. A
  state cannot be cut back to an earlier position: the framework refuses what
  would need it.
- ``attention``: softmax attention over cached keys and values, every query
  head over the ONE kv head (multi-query), no bias, no rotary embedding and
  no other position signal: the causal mask is the only one.

Pre-norm, plain weights: ``h = x + mixer(ln1(x)); y = h + mlp(ln2(h))``.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from petals_tpu.models.common import mm, project_heads, rms_norm, silu, update_kv_cache
from petals_tpu.models.jamba.config import ATTENTION, MAMBA, JambaBlockConfig
from petals_tpu.models.registry import ModelFamily, register_family
from petals_tpu.ops.attention import attend
from petals_tpu.ops.linear_attention import StatePool, causal_conv
from petals_tpu.ops.selective_scan import selective_scan, selective_scan_pooled


def block_kind(cfg: JambaBlockConfig, block_index: int) -> str:
    return cfg.layer_types[block_index]


def block_state(cfg: JambaBlockConfig, kind: str) -> Optional[tuple]:
    """What a lane holds for a block of ``kind`` in place of pages of keys and
    values: ``((shape, dtype), ...)`` a lane, dtype None for the cache's own.
    None for a block that keeps keys and values."""
    if kind != MAMBA:
        return None
    return (((cfg.mamba_d_state, cfg.mamba_d_inner), jnp.float32), ((cfg.mamba_d_conv - 1, cfg.mamba_d_inner), None))


def _mamba(params: dict, x: jnp.ndarray, state, position, cfg: JambaBlockConfig, n_valid, live_rows):
    """The mixer over ``x`` [batch, seq, hidden] from ``state`` on: (its
    output, the state after it). ``state`` None: a whole sequence from its
    start, no state handed back. ``state`` a ``StatePool``: a lane pool's
    step, one row a lane, the states read and written where they lie in the
    pool, which is what comes back. Else ``block_state``'s leaves, a chunk's
    lane."""
    batch, seq, _ = x.shape
    inner, n, rank = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank
    pooled = isinstance(state, StatePool)
    u, z = jnp.split(mm(x, params["w_in"]), 2, axis=-1)
    if state is None:  # no cache: a whole sequence from its start
        matrix = jnp.zeros((batch, n, inner), jnp.float32)
        tail = jnp.zeros((batch, cfg.mamba_d_conv - 1, inner), u.dtype)
    else:
        fresh = jnp.broadcast_to(jnp.asarray(position, jnp.int32) == 0, (batch,))
        held_tail = state.read(1) if pooled else state[1]
        tail = jnp.where(fresh[:, None, None], jnp.zeros((), held_tail.dtype), held_tail)
        if not pooled:
            matrix = jnp.where(fresh[:, None, None], 0.0, state[0])
    with jax.named_scope("ptu.ssm.conv"):
        u, tail = causal_conv(u, tail, params["conv"], n_valid, bias=params["conv_b"])
        u = u.astype(x.dtype)
    dt, b, c = jnp.split(mm(u, params["w_x"]), (rank, rank + n), axis=-1)
    dt, b, c = (rms_norm(t, params[name], cfg.rms_norm_eps) for t, name in ((dt, "dt_norm"), (b, "b_norm"), (c, "c_norm")))
    dt = jax.nn.softplus(mm(dt, params["w_dt"]).astype(jnp.float32) + params["dt_b"].astype(jnp.float32))
    a = -jnp.exp(params["a_log"].astype(jnp.float32))
    if pooled:
        assert seq == 1, "a lane pool's step hands its state pooled with one row a lane"
        live = jnp.ones((batch,), bool) if live_rows is None else live_rows
        state, y = selective_scan_pooled(state, u[:, 0], dt[:, 0], a, b[:, 0], c[:, 0], params["d"], live=live, fresh=fresh)
        y = y[:, None]
    else:
        matrix, y = selective_scan(matrix, u, dt, a, b, c, params["d"], n_valid)
    with jax.named_scope("ptu.ssm.gate"):
        y = (y * silu(z.astype(jnp.float32))).astype(x.dtype)
    out = mm(y, params["w_out"])
    if state is None:
        return out, None
    if live_rows is not None:  # an idle lane's state stays as it was
        if not pooled:
            matrix = jnp.where(live_rows[:, None, None], matrix, state[0])
        tail = jnp.where(live_rows[:, None, None], tail, held_tail)
    tail = tail.astype(held_tail.dtype)
    return out, (state.write(1, tail) if pooled else (matrix, tail))


def _attention(params: dict, x: jnp.ndarray, kv, position, cfg: JambaBlockConfig, n_valid, use_flash, tp_mesh):
    batch, seq, _ = x.shape
    hq, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    q, k, v = (project_heads(x, params[name]).reshape(batch, seq, heads, d) for name, heads in (("wq", hq), ("wk", hkv), ("wv", hkv)))
    k_all, v_all, kv_length = update_kv_cache(kv, k, v, position, n_valid)
    attn = attend(q, k_all, v_all, q_offset=position, kv_length=kv_length, use_flash=use_flash, tp_mesh=tp_mesh)
    return mm(attn.reshape(batch, seq, hq * d), params["wo"]), ((k_all, v_all) if kv is not None else None)


def block_apply(
    params: dict,
    hidden_states: jnp.ndarray,
    kv,  # attention: (k, v) as every family's; mamba: the lanes' state, block_state's leaves
    position,
    cfg: JambaBlockConfig,
    *,
    kind: str,
    use_flash: bool = False,
    tp_mesh=None,
    n_valid=None,
    live_rows=None,  # bool [batch] from a lane pool's step: the rows that are not idle lanes (None: all)
) -> Tuple[jnp.ndarray, Optional[tuple]]:
    x = rms_norm(hidden_states, params["ln1"], cfg.rms_norm_eps)
    if kind == MAMBA:
        mixed, new_kv = _mamba(params, x, kv, position, cfg, n_valid, live_rows)
    else:
        mixed, new_kv = _attention(params, x, kv, position, cfg, n_valid, use_flash, tp_mesh)
    hidden_states = hidden_states + mixed
    x = rms_norm(hidden_states, params["ln2"], cfg.rms_norm_eps)
    mlp = mm(silu(mm(x, params["wg"])) * mm(x, params["wu"]), params["wd"])
    return hidden_states + mlp, new_kv


# ----------------------------------------------------------------------------------
# HF checkpoint mapping
# ----------------------------------------------------------------------------------

_HF_BLOCK_PREFIXES = ("model.layers.{i}.",)

# leaf -> HF name under the layer's prefix; matrices are stored [out, in] and served [in, out]
_MLP = {"wg": "feed_forward.gate_proj.weight", "wu": "feed_forward.up_proj.weight", "wd": "feed_forward.down_proj.weight"}
_MATRICES = {
    ATTENTION: {"wq": "self_attn.q_proj.weight", "wk": "self_attn.k_proj.weight", "wv": "self_attn.v_proj.weight",
                "wo": "self_attn.o_proj.weight", **_MLP},
    # A_log [d_inner, d_state] is turned with them: the state's layout, channels minor
    MAMBA: {"w_in": "mamba.in_proj.weight", "w_x": "mamba.x_proj.weight", "w_dt": "mamba.dt_proj.weight",
            "w_out": "mamba.out_proj.weight", "a_log": "mamba.A_log", **_MLP},
}
_NORMS = {"ln1": "input_layernorm.weight", "ln2": "pre_ff_layernorm.weight"}
_VECTORS = {
    ATTENTION: _NORMS,
    MAMBA: {"conv_b": "mamba.conv1d.bias", "dt_b": "mamba.dt_proj.bias", "d": "mamba.D", "dt_norm": "mamba.dt_layernorm.weight",
            "b_norm": "mamba.b_layernorm.weight", "c_norm": "mamba.c_layernorm.weight", **_NORMS},
}


def hf_to_block_params(tensors: dict, cfg: JambaBlockConfig, kind: str) -> dict:
    params = {leaf: np.ascontiguousarray(np.asarray(tensors[name]).T) for leaf, name in _MATRICES[kind].items()}
    params.update({leaf: np.asarray(tensors[name]) for leaf, name in _VECTORS[kind].items()})
    if kind == MAMBA:  # a depthwise Conv1d's [channels, 1, taps] as [taps, channels]
        params["conv"] = np.ascontiguousarray(np.asarray(tensors["mamba.conv1d.weight"])[:, 0, :].T)
    return params


def block_param_shapes(cfg: JambaBlockConfig, kind: str, dtype=jnp.bfloat16) -> dict:
    h, m = cfg.hidden_size, cfg.intermediate_size
    S = jax.ShapeDtypeStruct
    shapes = {"ln1": S((h,), dtype), "ln2": S((h,), dtype), "wg": S((h, m), dtype), "wu": S((h, m), dtype), "wd": S((m, h), dtype)}
    if kind == ATTENTION:
        hq, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        shapes.update(wq=S((h, hq * d), dtype), wk=S((h, hkv * d), dtype), wv=S((h, hkv * d), dtype), wo=S((hq * d, h), dtype))
        return shapes
    inner, n, rank = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank
    shapes.update(
        w_in=S((h, 2 * inner), dtype), w_x=S((inner, rank + 2 * n), dtype), w_dt=S((rank, inner), dtype), w_out=S((inner, h), dtype),
        conv=S((cfg.mamba_d_conv, inner), dtype), conv_b=S((inner,), dtype), dt_b=S((inner,), dtype),
        a_log=S((n, inner), dtype), d=S((inner,), dtype),
        dt_norm=S((rank,), dtype), b_norm=S((n,), dtype), c_norm=S((n,), dtype),
    )
    return shapes


# tp_pspecs, quantizable_leaves and lora_targets are not declared: a span with a recurrent state is not
# sharded, quantized or adapted yet, and parallel/tp.py, utils/convert_block.py and utils/peft.py refuse
# the family by name (tests/test_jamba.py)
FAMILY = register_family(
    ModelFamily(
        name="jamba",
        config_from_hf=JambaBlockConfig.from_hf_config,
        block_apply=block_apply,
        hf_block_prefixes=_HF_BLOCK_PREFIXES,
        hf_to_block_params=hf_to_block_params,
        block_param_shapes=block_param_shapes,
        block_kind=block_kind,
        block_state=block_state,
        cast_exempt=("a_log", "d", "dt_b"),
    )
)
