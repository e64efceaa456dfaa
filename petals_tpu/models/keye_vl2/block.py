"""Keye-VL-2.0 language-model decoder block as a pure jitted JAX function
(``KeyeVL2``; the installed transformers has no class for it; the reference
has no such family). Every size of the language model is Qwen3-30B-A3B's, so
the attention plumbing follows ``modeling_qwen3_moe.py`` (an RMS norm a head
on q and k before the rotary, rotate-half over the whole head), and the
expert layer is models/moe.py's: 128 experts of SwiGLU width 768, a softmax
over all of them, the top 8 kept and renormalised, no shared expert.

What sets it apart is WHICH cached positions a row attends to: an indexer
(``sa_config``; DeepSeek-V3.2-Exp's ``inference/model.py`` ``Indexer`` is the
one published description of the mechanism, read with the q taken from the
normed hidden state because this model has no low-rank q) projects the row
to 16 heads of 64 and a weight a head, every position to ONE key of 64 under
a layer norm, both under the rotary, and keeps the 2,048 positions of largest
``sum_j w_j relu(qI_j . kI_s)`` (ops/sparse_attention.py). The index key is a
third thing a position caches beside its key and value: ``block_index``
declares its row to the framework, which keeps it in pages under the lane's
tables and hands the block ``(k, v, index)`` as its ``kv``. Noted departures
from V3.2's indexer: no Hadamard rotation of qI and kI (orthogonal, the dot
products are the same) and no float8 scores (a precision the configuration
does not state; the cache's dtype is served).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from petals_tpu.models.common import (
    absolute_positions,
    layer_norm,
    mm,
    project_heads,
    rms_norm,
    update_kv_cache,
)
from petals_tpu.models.keye_vl2.config import KeyeVL2BlockConfig
from petals_tpu.models.moe import MoeDims, choose_dispatch, moe_apply
from petals_tpu.models.registry import ModelFamily, register_family
from petals_tpu.ops.attention import attend
from petals_tpu.ops.paged_attention import PagedKV, pool_geometry
from petals_tpu.ops.rotary import apply_rotary, rotary_tables
from petals_tpu.ops.sparse_attention import (
    scatter_index_rows,
    sparse_attend_dense,
    sparse_chunk_attend,
    sparse_decode_attend,
)

INDEX_NORM_EPS = 1e-6  # V3.2's LayerNorm on the index key (assumed: config.json names none)


def moe_dims(cfg: KeyeVL2BlockConfig) -> MoeDims:
    return MoeDims(cfg.num_experts, cfg.num_experts_per_tok, cfg.hidden_size, cfg.intermediate_size)


def block_index(cfg: KeyeVL2BlockConfig, kind=None) -> tuple:
    """What a position caches beside its key and value: ``(width, dtype,
    keep)`` of the index row, dtype None for the cache's own, ``keep`` the
    positions a row attends to."""
    return (cfg.index_dim, None, cfg.index_topk)


def block_apply(
    params: dict,
    hidden_states: jnp.ndarray,
    kv,  # None, or (k, v, index): three PagedKV over the lane pool's pages
    position,
    cfg: KeyeVL2BlockConfig,
    *,
    use_flash: bool = False,
    tp_mesh=None,
    n_valid=None,
    live_rows=None,  # bool [batch] from a lane pool's step: the rows that are not idle lanes (None: all)
) -> Tuple[jnp.ndarray, Optional[tuple]]:
    batch, seq, _ = hidden_states.shape
    hq, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    heads, d_idx, topk = cfg.index_heads, cfg.index_dim, cfg.index_topk

    residual = hidden_states
    x = rms_norm(hidden_states, params["ln1"], cfg.rms_norm_eps)
    q, k, v = (project_heads(x, params[name]) for name in ("wq", "wk", "wv"))
    q = q.reshape(batch, seq, hq, d)
    k = k.reshape(batch, seq, hkv, d)
    v = v.reshape(batch, seq, hkv, d)
    with jax.named_scope("ptu.attn.qk_norm"):  # a head at a time, over head_dim
        q = rms_norm(q, params["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.rms_norm_eps)
    positions = absolute_positions(position, batch, seq)
    cos, sin = rotary_tables(positions, d, theta=cfg.rope_theta)
    q = apply_rotary(q, cos, sin)
    k = apply_rotary(k, cos, sin)

    with jax.named_scope("ptu.attn.index_project"):
        q_idx = project_heads(x, params["iq"]).reshape(batch, seq, heads, d_idx)
        k_idx = layer_norm(mm(x, params["ik"]), params["ik_norm"], params["ik_bias"], INDEX_NORM_EPS)
        cos_i, sin_i = rotary_tables(positions, d_idx, theta=cfg.rope_theta)
        q_idx = apply_rotary(q_idx, cos_i, sin_i)
        k_idx = apply_rotary(k_idx[:, :, None, :], cos_i, sin_i)  # one index head a position
        w_idx = mm(x, params["iw"]).astype(jnp.float32) * (heads * d_idx) ** -0.5

    if kv is None:  # a whole sequence, no cache: the stateless forward and backward passes
        attn = sparse_attend_dense(q, k, v, q_idx, w_idx, k_idx[:, :, 0], topk=topk)
        new_kv = None
    else:
        if len(kv) != 3 or not isinstance(kv[2], PagedKV):
            raise NotImplementedError(
                "KeyeVL2: a cache without the index keys' pages is not served: only the paged lane pool carries them"
            )
        k_all, v_all, kv_length = update_kv_cache(kv[:2], k, v, position, n_valid)
        i_all = scatter_index_rows(kv[2], k_idx[:, :, 0], position, n_valid, pool_geometry(k_all.pool, d)[1])
        if k_all.max_length <= topk:  # a table that cannot pass topk positions: the set is always everything
            attn = attend(q, k_all, v_all, q_offset=position, kv_length=kv_length, use_flash=use_flash, tp_mesh=tp_mesh)
        elif jnp.ndim(position) == 1:
            if seq != 1:
                raise NotImplementedError("KeyeVL2: per-lane positions with more than one row a lane (speculative verify) are not served")
            attn = sparse_decode_attend(q, q_idx, w_idx, k_all, v_all, i_all, position, topk=topk)
        else:
            attn = sparse_chunk_attend(q, q_idx, w_idx, k_all, v_all, i_all, position, n_valid, topk=topk)
        new_kv = (k_all, v_all, i_all)
    hidden_states = residual + mm(attn.reshape(batch, seq, hq * d), params["wo"])

    residual = hidden_states
    x = rms_norm(hidden_states, params["ln2"], cfg.rms_norm_eps)
    dispatch = choose_dispatch(params, moe_dims(cfg), seq, mesh=tp_mesh is not None)
    hidden_states = residual + moe_apply(
        params, x, top_k=cfg.num_experts_per_tok, renormalize=cfg.norm_topk_prob, dispatch=dispatch, live_rows=live_rows
    )
    return hidden_states, new_kv


# ----------------------------------------------------------------------------------
# HF checkpoint mapping (the catalog gives no tensor names: these are the configuration's
# ``assumed.tensor_names``, Qwen3-MoE's with V3.2's indexer under ``self_attn.indexer``)
# ----------------------------------------------------------------------------------

_HF_BLOCK_PREFIXES = ("model.layers.{i}.",)

# leaf -> name under the layer's prefix; matrices are stored [out, in] and served [in, out]
_MATRICES = {
    "wq": "self_attn.q_proj.weight", "wk": "self_attn.k_proj.weight", "wv": "self_attn.v_proj.weight",
    "wo": "self_attn.o_proj.weight", "iq": "self_attn.indexer.wq.weight", "ik": "self_attn.indexer.wk.weight",
    "iw": "self_attn.indexer.weights_proj.weight", "gate": "mlp.gate.weight",
}
_VECTORS = {
    "ln1": "input_layernorm.weight", "ln2": "post_attention_layernorm.weight",
    "q_norm": "self_attn.q_norm.weight", "k_norm": "self_attn.k_norm.weight",
    "ik_norm": "self_attn.indexer.k_norm.weight", "ik_bias": "self_attn.indexer.k_norm.bias",
}
_EXPERTS = {"w1": "gate_proj", "w2": "down_proj", "w3": "up_proj"}


def hf_to_block_params(tensors: dict, cfg: KeyeVL2BlockConfig) -> dict:
    def t(name):
        return np.ascontiguousarray(np.asarray(tensors[name]).T)

    params = {leaf: t(name) for leaf, name in _MATRICES.items()}
    params.update({leaf: np.asarray(tensors[name]) for leaf, name in _VECTORS.items()})
    for leaf, proj in _EXPERTS.items():  # 3 x num_experts tensors a layer, stacked [E, in, out]
        params[leaf] = np.stack([t(f"mlp.experts.{e}.{proj}.weight") for e in range(cfg.num_experts)])
    return params


def block_param_shapes(cfg: KeyeVL2BlockConfig, dtype=jnp.bfloat16) -> dict:
    h, hq, hkv, d, m, E = (
        cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim, cfg.intermediate_size, cfg.num_experts,
    )
    heads, d_idx = cfg.index_heads, cfg.index_dim
    S = jax.ShapeDtypeStruct
    return {
        "ln1": S((h,), dtype), "ln2": S((h,), dtype),
        "wq": S((h, hq * d), dtype), "wk": S((h, hkv * d), dtype), "wv": S((h, hkv * d), dtype), "wo": S((hq * d, h), dtype),
        "q_norm": S((d,), dtype), "k_norm": S((d,), dtype),
        "iq": S((h, heads * d_idx), dtype), "ik": S((h, d_idx), dtype), "iw": S((h, heads), dtype),
        "ik_norm": S((d_idx,), dtype), "ik_bias": S((d_idx,), dtype),
        "gate": S((h, E), dtype), "w1": S((E, h, m), dtype), "w2": S((E, m, h), dtype), "w3": S((E, h, m), dtype),
    }


# tp_pspecs, quantizable_leaves and lora_targets are not declared: a span whose pages carry index keys is served
# on one chip's paged lane pool, unsharded and unquantized (an adapter's session would take a private cache), and
# parallel/tp.py, utils/convert_block.py and utils/peft.py refuse the family by name (tests/test_keye_vl2.py)
FAMILY = register_family(
    ModelFamily(
        name="KeyeVL2",
        config_from_hf=KeyeVL2BlockConfig.from_hf_config,
        block_apply=block_apply,
        hf_block_prefixes=_HF_BLOCK_PREFIXES,
        hf_to_block_params=hf_to_block_params,
        block_param_shapes=block_param_shapes,
        moe_dims=moe_dims,
        block_index=block_index,
    )
)
