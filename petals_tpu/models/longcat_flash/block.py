"""``longcat_flash`` decoder block as a pure jitted JAX function
(transformers' ``modeling_longcat_flash.py``; LongCat-Flash-Chat publishes
under it; the reference has no such family).

One block is one ``model.layers.{i}`` of the checkpoint, a DOUBLE layer: two
latent attentions and two dense feed-forwards around one shortcut-connected
expert layer, whose result is computed from the first half's normed row and
added after the second, so the block cannot be cut in two on the wire:

    a = x + MLA_0(ln(x));  n = ln(a);  s = MoE(n);  b = a + FFN_0(n)
    c = b + MLA_1(ln(b));  y = c + FFN_1(ln(c)) + s

The expert branch and ``FFN_0`` read the same normed row and are independent
until the last add. On the model's deployment that is where the experts'
exchange hides; here the order of the two is the compiler's. The whole branch
(router, experts, identities) runs under the named scope
``ptu.scmoe.shortcut``, so a trace shows where it ran.

Each attention is ``deepseek_v3``'s (``latent_attention`` there, shared) with
a low-rank query (``q_a_proj``, a norm, ``q_b_proj``) and the two published
scales: ``sqrt(hidden / q_lora_rank)`` on the whole query, taken into the
softmax's scale, and ``sqrt(hidden / kv_lora_rank)`` on the normed latent
BEFORE ``kv_b_proj``. The scale is NOT folded into ``kv_b_proj`` at load: the
row a position caches is the SCALED normed latent, rounded to the cache's
dtype as transformers rounds it before ``kv_b_proj``, beside the rotated key,
which is not scaled. A position so caches TWO rows a block, one an attention
(``block_sublayers``): the framework hands the block one ``(c, k_pe)`` pair a
sub-layer, each over its own layer of pages, and the second attention writes
the pools the first one wrote.

The checkpoint's rotated columns come in pairs ``(2j, 2j + 1)``
(``apply_rotary_pos_emb_interleave``); the permutation is folded into the
rope columns of ``q_b_proj`` and ``kv_a_proj_with_mqa`` at load, as
``deepseek_v3`` folds it, and the served path rotates half.

The router (models/moe.py, the third rule) is ``n_routed_experts +
zero_expert_num`` wide: a softmax in float32, the top k of score +
``e_score_correction_bias`` chosen, weighed by score, not renormalised, times
``routed_scaling_factor``. Its last ``zero_expert_num`` outputs are identity
experts: a pick of one is a weight on the token itself."""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from petals_tpu.models.common import mm, rms_norm, silu
from petals_tpu.models.deepseek_v3.block import LatentDims, fold_rope_columns, latent_attention
from petals_tpu.models.longcat_flash.config import LongcatFlashBlockConfig
from petals_tpu.models.moe import MoeDims, choose_dispatch, moe_apply
from petals_tpu.models.registry import ModelFamily, register_family

SUBLAYERS = 2  # a block holds the leaves of an attention and of a dense feed-forward twice, as ``<leaf>_0`` and ``<leaf>_1``


def block_latent(cfg: LongcatFlashBlockConfig, kind=None) -> tuple:
    """What a position caches in place of a key and a value, ONCE an attention:
    ``(latent width, rotated key's width)``."""
    return (cfg.kv_lora_rank, cfg.qk_rope_head_dim)


def block_sublayers(cfg: LongcatFlashBlockConfig, kind=None) -> int:
    """The attentions of a block, each with a latent row a position of its own."""
    return SUBLAYERS


def moe_dims(cfg: LongcatFlashBlockConfig) -> MoeDims:
    """The router's width counts the identities; ``routed - identities`` experts exist, ``experts`` of them here."""
    return MoeDims(cfg.num_experts, cfg.moe_topk, cfg.hidden_size, cfg.expert_ffn_hidden_size,
                   routed=cfg.router_width, first=cfg.first_expert, identities=cfg.zero_expert_num)


def latent_dims(cfg: LongcatFlashBlockConfig) -> LatentDims:
    return LatentDims(
        cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank,
        cfg.rope_theta, cfg.latent_norm_eps, q_scale=cfg.q_scale, kv_scale=cfg.kv_scale,
    )


def _sub(params: dict, j: int) -> dict:
    """Sub-layer ``j``'s leaves under their own names."""
    tail = f"_{j}"
    return {name[: -len(tail)]: leaf for name, leaf in params.items() if name.endswith(tail)}


def shortcut_experts(params: dict, x: jnp.ndarray, cfg: LongcatFlashBlockConfig, *, tp_mesh=None, live_rows=None) -> jnp.ndarray:
    """The shortcut branch over the normed rows ``x``: what the held experts
    and the identities give each token (``LongcatFlashMoE``)."""
    with jax.named_scope("ptu.scmoe.shortcut"):
        return moe_apply(
            params, x, top_k=cfg.moe_topk, renormalize=False,
            dispatch=choose_dispatch(params, moe_dims(cfg), x.shape[1], mesh=tp_mesh is not None),
            scoring="softmax_bias", scale=cfg.routed_scaling_factor, first=cfg.first_expert,
            identities=cfg.zero_expert_num, live_rows=live_rows,
        )


def _ffn(p: dict, x: jnp.ndarray) -> jnp.ndarray:
    return mm(silu(mm(x, p["wg"])) * mm(x, p["wu"]), p["wd"])


def block_apply(
    params: dict,
    hidden_states: jnp.ndarray,
    kv,  # None, or ((c, k_pe), (c, k_pe)): two PagedKV a sub-layer over the lane pool's pages
    position,
    cfg: LongcatFlashBlockConfig,
    *,
    use_flash: bool = False,
    tp_mesh=None,
    n_valid=None,
    live_rows=None,  # bool [batch] from a lane pool's step: the rows that are not idle lanes (None: all)
) -> Tuple[jnp.ndarray, Optional[tuple]]:
    if kv is not None and len(kv) != SUBLAYERS:
        raise NotImplementedError(
            "longcat_flash: a cache without one pair of latent rows' pages a sub-layer is not served: only the paged lane pool carries them"
        )
    dims = latent_dims(cfg)
    first, second = _sub(params, 0), _sub(params, 1)

    attn, kv_0 = latent_attention(
        first, rms_norm(hidden_states, first["ln1"], cfg.rms_norm_eps), None if kv is None else kv[0], position, dims,
        n_valid=n_valid, who="longcat_flash",
    )
    a = hidden_states + attn
    n = rms_norm(a, first["ln2"], cfg.rms_norm_eps)
    shortcut = shortcut_experts(params, n, cfg, tp_mesh=tp_mesh, live_rows=live_rows)
    b = a + _ffn(first, n)

    # the second attention's pages lie in the pools the first one wrote
    kv_1 = None if kv is None else tuple(mine._replace(pool=written.pool) for mine, written in zip(kv[1], kv_0))
    attn, kv_1 = latent_attention(
        second, rms_norm(b, second["ln1"], cfg.rms_norm_eps), kv_1, position, dims, n_valid=n_valid, who="longcat_flash",
    )
    c = b + attn
    y = c + _ffn(second, rms_norm(c, second["ln2"], cfg.rms_norm_eps)) + shortcut
    return y, None if kv is None else (kv_0, kv_1)


# ----------------------------------------------------------------------------------
# HF checkpoint mapping
# ----------------------------------------------------------------------------------

_HF_BLOCK_PREFIXES = ("model.layers.{i}.",)


def hf_to_block_params(tensors: dict, cfg: LongcatFlashBlockConfig) -> dict:
    heads, dn, dr, dv, latent = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank

    def t(name):
        return np.ascontiguousarray(np.asarray(tensors[name]).T)

    params = {}
    for j in range(SUBLAYERS):
        a = f"self_attn.{j}."
        # the rope columns' pairs (2j, 2j + 1) to halves, on both sides of q_pe . k_pe
        wqb = fold_rope_columns(t(a + "q_b_proj.weight").reshape(-1, heads, dn + dr), dn).reshape(-1, heads * (dn + dr))
        wkva = fold_rope_columns(t(a + "kv_a_proj_with_mqa.weight"), latent)
        wkvb = np.asarray(tensors[a + "kv_b_proj.weight"]).reshape(heads, dn + dv, latent)  # a head: [k_nope | v] x latent
        sub = {
            "ln1": np.asarray(tensors[f"input_layernorm.{j}.weight"]),
            "wqa": t(a + "q_a_proj.weight"),
            "q_norm": np.asarray(tensors[a + "q_a_layernorm.weight"]),
            "wqb": np.ascontiguousarray(wqb),
            "wkva": np.ascontiguousarray(wkva),
            "kv_norm": np.asarray(tensors[a + "kv_a_layernorm.weight"]),
            "wuk": np.ascontiguousarray(wkvb[:, :dn]),  # [H, dn, latent]
            "wuv": np.ascontiguousarray(wkvb[:, dn:].transpose(0, 2, 1)),  # [H, latent, dv]
            "wo": t(a + "o_proj.weight"),
            "ln2": np.asarray(tensors[f"post_attention_layernorm.{j}.weight"]),
            "wg": t(f"mlps.{j}.gate_proj.weight"),
            "wu": t(f"mlps.{j}.up_proj.weight"),
            "wd": t(f"mlps.{j}.down_proj.weight"),
        }
        params.update({f"{name}_{j}": leaf for name, leaf in sub.items()})

    held = range(cfg.first_expert, cfg.first_expert + cfg.num_experts)

    def stack(proj):
        return np.stack([t(f"mlp.experts.{e}.{proj}.weight") for e in held])

    params.update(
        gate=t("mlp.router.classifier.weight"),
        gate_bias=np.asarray(tensors["mlp.router.e_score_correction_bias"], np.float32),
        w1=stack("gate_proj"), w2=stack("down_proj"), w3=stack("up_proj"),
    )
    return params


def block_param_shapes(cfg: LongcatFlashBlockConfig, dtype=jnp.bfloat16) -> dict:
    h, heads, dn, dr, dv, latent, rq = (
        cfg.hidden_size, cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank,
        cfg.q_lora_rank,
    )
    S = jax.ShapeDtypeStruct
    m, me, E = cfg.ffn_hidden_size, cfg.expert_ffn_hidden_size, cfg.num_experts
    sub = {
        "ln1": S((h,), dtype), "wqa": S((h, rq), dtype), "q_norm": S((rq,), dtype), "wqb": S((rq, heads * (dn + dr)), dtype),
        "wkva": S((h, latent + dr), dtype), "kv_norm": S((latent,), dtype), "wuk": S((heads, dn, latent), dtype),
        "wuv": S((heads, latent, dv), dtype), "wo": S((heads * dv, h), dtype),
        "ln2": S((h,), dtype), "wg": S((h, m), dtype), "wu": S((h, m), dtype), "wd": S((m, h), dtype),
    }
    shapes = {f"{name}_{j}": leaf for j in range(SUBLAYERS) for name, leaf in sub.items()}
    shapes.update(
        gate=S((h, cfg.router_width), dtype), gate_bias=S((cfg.router_width,), jnp.float32),
        w1=S((E, h, me), dtype), w2=S((E, me, h), dtype), w3=S((E, h, me), dtype),
    )
    return shapes


# tp_pspecs, quantizable_leaves and lora_targets are not declared: a span whose pages carry latent rows is served
# on one chip's paged lane pool, unsharded and unquantized, as deepseek_v3's is, and parallel/tp.py,
# utils/convert_block.py and utils/peft.py refuse the family by name (tests/test_longcat_flash.py)
FAMILY = register_family(
    ModelFamily(
        name="longcat_flash",
        config_from_hf=LongcatFlashBlockConfig.from_hf_config,
        block_apply=block_apply,
        hf_block_prefixes=_HF_BLOCK_PREFIXES,
        hf_to_block_params=hf_to_block_params,
        block_param_shapes=block_param_shapes,
        moe_dims=moe_dims,
        block_latent=block_latent,
        block_sublayers=block_sublayers,
        cast_exempt=("gate_bias",),
    )
)
