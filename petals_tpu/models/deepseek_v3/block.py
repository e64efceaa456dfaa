"""``deepseek_v3`` decoder block as a pure jitted JAX function (transformers'
``modeling_deepseek_v3.py``; Kanana-2-30B-A3B publishes under it; the
reference has no such family).

Attention is multi-head LATENT attention (ops/latent_attention.py): the normed
row projects to a query of ``qk_nope_head_dim + qk_rope_head_dim`` a head, and
ONCE, for all heads, to ``[c | k_pe]``: a latent of ``kv_lora_rank`` under an
RMS norm, which every head's key and value are linear in (``wuk``, ``wuv``,
the two halves of ``kv_b_proj``), and one key of ``qk_rope_head_dim`` under
the rotary that every head shares. That row is what a position caches IN
PLACE of keys and values: ``block_latent`` declares it to the framework, which
keeps it in pages under the lane's tables and hands the block ``(c, k_pe)``
as its ``kv``. A decode row takes the absorbed form over those rows, a
prompt's chunk the expanded one, the stateless passes the expanded one with
no cache: which, follows from the call's shape.

The checkpoint's rotated columns come in pairs ``(2j, 2j + 1)``
(``rope_interleave``; transformers' ``apply_rotary_pos_emb_interleave``
de-interleaves q and k to halves and rotates half). The same permutation on
both sides of a dot product leaves it alone, so it is folded into the rope
columns of ``wq`` and ``wkva`` at load, and the served path rotates half.

Blocks are of two kinds, read at the block's absolute index: ``dense`` (a
SwiGLU of ``intermediate_size``; the model's first ``first_k_dense_replace``
layers) and ``sparse`` (models/moe.py: a sigmoid router over
``n_routed_experts``, the top k of score + ``e_score_correction_bias``, weighed
by score, renormalised and scaled by ``routed_scaling_factor``, beside one
shared SwiGLU of ``n_shared_experts x moe_intermediate_size`` that every token
takes).

Pre-norm: ``h = x + attn(ln1(x)); y = h + mlp(ln2(h))``.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from petals_tpu.models.common import absolute_positions, mm, project_heads, rms_norm, silu
from petals_tpu.models.deepseek_v3.config import DENSE, SPARSE, DeepseekV3BlockConfig
from petals_tpu.models.moe import MoeDims, choose_dispatch, moe_apply
from petals_tpu.models.registry import ModelFamily, register_family
from petals_tpu.ops.latent_attention import (
    absorb_queries,
    expand_outputs,
    latent_attend_dense,
    latent_chunk_attend,
    latent_decode_attend,
    scatter_latent_rows,
)
from petals_tpu.ops.paged_attention import PagedKV
from petals_tpu.ops.rotary import apply_rotary, rotary_tables


class LatentDims(NamedTuple):
    """The static shapes and numbers of one latent attention (``latent_attention``)."""

    heads: int
    nope: int  # qk_nope_head_dim
    rope: int  # qk_rope_head_dim
    value: int  # v_head_dim
    latent: int  # kv_lora_rank
    rope_theta: float
    norm_eps: float  # of the norm over the latent, and over the low-rank query where there is one
    q_scale: float = 1.0  # on the whole query (a low-rank query's ``sqrt(hidden / q_lora_rank)``)
    kv_scale: float = 1.0  # on the normed latent BEFORE ``kv_b_proj`` (``sqrt(hidden / kv_lora_rank)``): the cached row is the scaled one
    rope_scaling: Optional[tuple] = None  # the published ``rope_scaling``'s items (ops/rotary.py: yarn); its softmax ``mscale^2`` rides ``q_scale``


def latent_attention(params: dict, x: jnp.ndarray, kv, position, dims: LatentDims, *, n_valid=None, who: str = "deepseek_v3"):
    """One multi-head latent attention over the normed rows ``x`` [batch, seq,
    hidden], its output projection included: ``(out [batch, seq, hidden],
    new_kv)``. ``kv`` is None (a whole sequence, no cache) or ``(c, k_pe)``,
    two ``PagedKV`` over the lane pool's pages. The query is one matrix
    (``wq``) or, where ``params`` has ``wqa``, low-rank: ``wqb(rms(x wqa,
    q_norm))``. ``dims.q_scale`` multiplies both parts of the query, so it is
    taken into the softmax's scale (the scores are its only readers);
    ``dims.kv_scale`` multiplies the normed latent before anything reads it,
    so the row a position caches is the scaled one and both forms meet it as
    they meet any other."""
    batch, seq, _ = x.shape
    heads, dn, dr, dv, latent = dims.heads, dims.nope, dims.rope, dims.value, dims.latent
    scale = dims.q_scale * (dn + dr) ** -0.5
    if "wqa" in params:
        q = project_heads(rms_norm(mm(x, params["wqa"]), params["q_norm"], dims.norm_eps), params["wqb"])
    else:
        q = project_heads(x, params["wq"])
    q = q.reshape(batch, seq, heads, dn + dr)
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    row = mm(x, params["wkva"])  # [b, s, latent + dr]: one row for all heads
    c = rms_norm(row[..., :latent], params["kv_norm"], dims.norm_eps)
    if dims.kv_scale != 1.0:
        c = (c.astype(jnp.float32) * dims.kv_scale).astype(c.dtype)
    cos, sin = rotary_tables(
        absolute_positions(position, batch, seq), dr, theta=dims.rope_theta,
        rope_scaling=None if dims.rope_scaling is None else dict(dims.rope_scaling),
    )
    q_pe = apply_rotary(q_pe, cos, sin)
    k_pe = apply_rotary(row[..., None, latent:], cos, sin)[:, :, 0]

    if kv is None:  # a whole sequence, no cache: the stateless forward and backward passes
        attn = latent_attend_dense(q_nope, q_pe, c, k_pe, params["wuk"], params["wuv"], scale=scale)
        new_kv = None
    else:
        if len(kv) != 2 or not isinstance(kv[0], PagedKV):
            raise NotImplementedError(
                f"{who}: a cache without the latent rows' pages is not served: only the paged lane pool carries them"
            )
        c_kv, pe_kv = scatter_latent_rows(kv[0], kv[1], c, k_pe, position, n_valid)
        if jnp.ndim(position) == 1:  # one row a lane: absorbed, no key or value is made
            u = latent_decode_attend(absorb_queries(q_nope, params["wuk"]), q_pe, c_kv, pe_kv, position, scale=scale)
            attn = expand_outputs(u, params["wuv"])
        else:  # a prompt's chunk over one lane's table: expanded a block of positions at a time
            attn = latent_chunk_attend(q_nope, q_pe, params["wuk"], params["wuv"], c_kv, pe_kv, position, n_valid, scale=scale)
        new_kv = (c_kv, pe_kv)
    return mm(attn.reshape(batch, seq, heads * dv), params["wo"]), new_kv


def latent_dims(cfg: DeepseekV3BlockConfig) -> LatentDims:
    return LatentDims(
        cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank,
        cfg.rope_theta, cfg.rms_norm_eps,
    )


def block_kind(cfg: DeepseekV3BlockConfig, block_index: int) -> str:
    return DENSE if block_index < cfg.first_k_dense_replace else SPARSE


def block_latent(cfg: DeepseekV3BlockConfig, kind=None) -> tuple:
    """What a position caches in place of its key and value: ``(latent width,
    rotated key's width)`` of the one row all heads share."""
    return (cfg.kv_lora_rank, cfg.qk_rope_head_dim)


def moe_dims(cfg: DeepseekV3BlockConfig, kind: str) -> Optional[MoeDims]:
    if kind == DENSE:
        return None
    return MoeDims(cfg.num_experts, cfg.num_experts_per_tok, cfg.hidden_size, cfg.moe_intermediate_size)


def feed_forward(params: dict, x: jnp.ndarray, cfg: DeepseekV3BlockConfig, kind: str, *, tp_mesh=None, live_rows=None) -> jnp.ndarray:
    """The block's feed-forward half over the normed rows ``x``: a dense
    layer's SwiGLU, or a sparse layer's routed experts beside the shared one
    (``xing4_0``'s too, inside its stream's wrap)."""
    if kind == DENSE:
        return mm(silu(mm(x, params["wg"])) * mm(x, params["wu"]), params["wd"])
    return moe_apply(
        params, x, top_k=cfg.num_experts_per_tok, renormalize=cfg.norm_topk_prob,
        dispatch=choose_dispatch(params, moe_dims(cfg, kind), x.shape[1], mesh=tp_mesh is not None),
        scoring="sigmoid", scale=cfg.routed_scaling_factor, live_rows=live_rows,
    )


def block_apply(
    params: dict,
    hidden_states: jnp.ndarray,
    kv,  # None, or (c, k_pe): two PagedKV over the lane pool's pages
    position,
    cfg: DeepseekV3BlockConfig,
    *,
    kind: str,
    use_flash: bool = False,
    tp_mesh=None,
    n_valid=None,
    live_rows=None,  # bool [batch] from a lane pool's step: the rows that are not idle lanes (None: all)
) -> Tuple[jnp.ndarray, Optional[tuple]]:
    x = rms_norm(hidden_states, params["ln1"], cfg.rms_norm_eps)
    attn, new_kv = latent_attention(params, x, kv, position, latent_dims(cfg), n_valid=n_valid)
    hidden_states = hidden_states + attn
    x = rms_norm(hidden_states, params["ln2"], cfg.rms_norm_eps)
    return hidden_states + feed_forward(params, x, cfg, kind, tp_mesh=tp_mesh, live_rows=live_rows), new_kv


# ----------------------------------------------------------------------------------
# HF checkpoint mapping
# ----------------------------------------------------------------------------------

_HF_BLOCK_PREFIXES = ("model.layers.{i}.",)


def rope_halves(width: int) -> np.ndarray:
    """The columns of an interleaved rotary block in the order rotate-half
    takes them: the even ones, then the odd ones."""
    return np.concatenate([np.arange(0, width, 2), np.arange(1, width, 2)])


def fold_rope_columns(w: np.ndarray, plain: int) -> np.ndarray:
    """``w`` [.., plain + rope] with its last ``rope`` columns, the checkpoint's
    pairs ``(2j, 2j + 1)``, put in rotate-half's order; the first ``plain`` as they are."""
    return np.concatenate([w[..., :plain], w[..., plain:][..., rope_halves(w.shape[-1] - plain)]], axis=-1)


def attention_params(tensors: dict, cfg: DeepseekV3BlockConfig) -> dict:
    """The leaves ``latent_attention`` reads, from ``self_attn.*`` of a layer:
    one query matrix (``wq``) or, where the checkpoint has ``q_a_proj``, the
    low-rank query's two and the norm between them (``xing4_0``'s)."""
    heads, dn, dr, dv, latent = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank

    def t(name):
        return np.ascontiguousarray(np.asarray(tensors[name]).T)

    low_rank = "self_attn.q_a_proj.weight" in tensors
    wq, wkva = t("self_attn.q_b_proj.weight" if low_rank else "self_attn.q_proj.weight"), t("self_attn.kv_a_proj_with_mqa.weight")
    if cfg.rope_interleave:  # pairs (2j, 2j + 1) to halves, on both sides of q_pe . k_pe
        wq = np.ascontiguousarray(fold_rope_columns(wq.reshape(-1, heads, dn + dr), dn).reshape(-1, heads * (dn + dr)))
        wkva = np.ascontiguousarray(fold_rope_columns(wkva, latent))
    wkvb = np.asarray(tensors["self_attn.kv_b_proj.weight"]).reshape(heads, dn + dv, latent)  # a head: [k_nope | v] x latent
    query = {"wqa": t("self_attn.q_a_proj.weight"), "q_norm": np.asarray(tensors["self_attn.q_a_layernorm.weight"]), "wqb": wq} if low_rank else {"wq": wq}
    return {
        **query,
        "wkva": wkva,
        "kv_norm": np.asarray(tensors["self_attn.kv_a_layernorm.weight"]),
        "wuk": np.ascontiguousarray(wkvb[:, :dn]),  # [H, dn, latent]
        "wuv": np.ascontiguousarray(wkvb[:, dn:].transpose(0, 2, 1)),  # [H, latent, dv]
        "wo": t("self_attn.o_proj.weight"),
    }


def hf_to_block_params(tensors: dict, cfg: DeepseekV3BlockConfig, kind: str) -> dict:
    return {
        "ln1": np.asarray(tensors["input_layernorm.weight"]),
        **attention_params(tensors, cfg),
        "ln2": np.asarray(tensors["post_attention_layernorm.weight"]),
        **feed_forward_params(tensors, cfg, kind),
    }


def feed_forward_params(tensors: dict, cfg: DeepseekV3BlockConfig, kind: str) -> dict:
    """The leaves ``feed_forward`` reads, from ``mlp.*`` of a layer of ``kind``."""

    def t(name):
        return np.ascontiguousarray(np.asarray(tensors[name]).T)

    if kind == DENSE:
        return dict(wg=t("mlp.gate_proj.weight"), wu=t("mlp.up_proj.weight"), wd=t("mlp.down_proj.weight"))

    def stack(proj):
        return np.stack([t(f"mlp.experts.{e}.{proj}.weight") for e in range(cfg.num_experts)])

    params = dict(
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


def attention_shapes(cfg: DeepseekV3BlockConfig, dtype=jnp.bfloat16) -> dict:
    """``attention_params``' leaves: a low-rank query's where ``cfg`` has a ``q_lora_rank``."""
    h, heads, dn, dr, dv, latent = (
        cfg.hidden_size, cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank,
    )
    S, rq = jax.ShapeDtypeStruct, getattr(cfg, "q_lora_rank", None)
    query = {"wqa": S((h, rq), dtype), "q_norm": S((rq,), dtype), "wqb": S((rq, heads * (dn + dr)), dtype)} if rq else {"wq": S((h, heads * (dn + dr)), dtype)}
    return {
        **query, "wkva": S((h, latent + dr), dtype), "kv_norm": S((latent,), dtype), "wuk": S((heads, dn, latent), dtype),
        "wuv": S((heads, latent, dv), dtype), "wo": S((heads * dv, h), dtype),
    }


def block_param_shapes(cfg: DeepseekV3BlockConfig, kind: str, dtype=jnp.bfloat16) -> dict:
    S = jax.ShapeDtypeStruct
    return {"ln1": S((cfg.hidden_size,), dtype), **attention_shapes(cfg, dtype), "ln2": S((cfg.hidden_size,), dtype),
            **feed_forward_shapes(cfg, kind, dtype)}


def feed_forward_shapes(cfg: DeepseekV3BlockConfig, kind: str, dtype=jnp.bfloat16) -> dict:
    h, S = cfg.hidden_size, jax.ShapeDtypeStruct
    if kind == DENSE:
        m = cfg.intermediate_size
        return dict(wg=S((h, m), dtype), wu=S((h, m), dtype), wd=S((m, h), dtype))
    m, E = cfg.moe_intermediate_size, cfg.num_experts
    shapes = dict(
        gate=S((h, E), dtype), gate_bias=S((E,), jnp.float32),
        w1=S((E, h, m), dtype), w2=S((E, m, h), dtype), w3=S((E, h, m), dtype),
    )
    if cfg.num_shared_experts:
        ms = m * cfg.num_shared_experts
        shapes.update(ws1=S((h, ms), dtype), ws2=S((ms, h), dtype), ws3=S((h, ms), dtype))
    return shapes


# tp_pspecs, quantizable_leaves and lora_targets are not declared: a span whose pages carry latent rows is served
# on one chip's paged lane pool, unsharded and unquantized (an adapter's session would take a private cache), and
# parallel/tp.py, utils/convert_block.py and utils/peft.py refuse the family by name (tests/test_deepseek_v3.py)
FAMILY = register_family(
    ModelFamily(
        name="deepseek_v3",
        config_from_hf=DeepseekV3BlockConfig.from_hf_config,
        block_apply=block_apply,
        hf_block_prefixes=_HF_BLOCK_PREFIXES,
        hf_to_block_params=hf_to_block_params,
        block_param_shapes=block_param_shapes,
        moe_dims=moe_dims,
        block_kind=block_kind,
        block_latent=block_latent,
        cast_exempt=("gate_bias",),
    )
)
