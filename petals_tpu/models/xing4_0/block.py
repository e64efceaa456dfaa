"""``xing4_0`` decoder block as a pure jitted JAX function (Xing4.0-29B-A4B
publishes under it; transformers has no such class and the reference no such
family).

The sub-layers are ``deepseek_v3``'s, shared and not copied
(models/deepseek_v3/block.py): ``latent_attention`` with a low-rank query
(``q_a_proj``, a norm, ``q_b_proj``) under yarn (ops/rotary.py; the softmax's
``mscale^2`` rides ``LatentDims.q_scale``), and ``feed_forward``, a SwiGLU in
the model's first ``first_k_dense_replace`` layers and routed experts beside a
shared one after. What differs is the residual path: the hidden state is a
STREAM of ``n = hc_mult`` rows of ``C = hidden_size``, and each sub-layer ``F``
is wrapped by manifold-constrained hyper-connections (Xie et al., arXiv
2512.24880, over Zhu et al., arXiv 2409.19606). With ``X`` [n, C] the stream of
one position:

    x  = rms(vec(X))                                   over all n*C values, float32
    Hp = sigmoid(a_pre  * (x @ phi_pre)  + b_pre)      [n]: what the sub-layer reads of each row
    Hq = 2 * sigmoid(a_post * (x @ phi_post) + b_post) [n]: where its output goes
    M  = exp(clip(a_res * mat(x @ phi_res) + b_res))   [n, n], then ``hc_sinkhorn_iters`` rounds of
         M / (M.sum(-1) + hc_eps); M / (M.sum(-2) + hc_eps): doubly stochastic
    u  = Hp @ X;   X' = M @ X + outer(Hq, F(norm(u)))

Two wraps a block (attention, then feed-forward), coefficients of their own
each. The stream crosses the wire FLAT, ``[batch, seq, n*C]``
(``block_stream``: the framework sizes every buffer and frame by it), and is
never reshaped here: row ``k`` is the columns ``[k*C, (k+1)*C)``, a slice at a
multiple of the lane width, so no wrap transposes or relays 14,336 values.

The coefficients (the norm, the three products as ONE ``[n*C, 2n + n*n]``
matrix, sigmoid, ``exp``, Sinkhorn) are float32 whatever the compute dtype,
laid out ``[coefficient, batch, seq]`` so that a row or a column of ``M`` is a
slab and Sinkhorn's sums are adds of slabs, no reduction over a 4-wide minor
dimension. ``u`` and ``X'`` are sums of ``n`` (``n + 1``) products accumulated
in float32 from the float32 coefficients and rounded once to the compute
dtype. Three named scopes say where a trace finds them: ``ptu.hc.coef``,
``ptu.hc.sinkhorn``, ``ptu.hc.mix``."""

from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from petals_tpu.models.common import rms_norm
from petals_tpu.models.deepseek_v3.block import (
    LatentDims,
    attention_params,
    attention_shapes,
    block_kind,
    block_latent,
    feed_forward,
    feed_forward_params,
    feed_forward_shapes,
    latent_attention,
    moe_dims,
)
from petals_tpu.models.registry import ModelFamily, register_family
from petals_tpu.models.xing4_0.config import Xing40BlockConfig

WRAPS = ("attn", "mlp")  # a block's wrapped sub-layers, in order: leaves ``hc_phi_<wrap>``, ``hc_alpha_<wrap>``, ``hc_bias_<wrap>``
COEFFICIENTS = ("pre", "post", "res")  # a wrap's three products, in the order of the fused matrix's columns: n, n and n*n of them


def block_stream(cfg: Xing40BlockConfig) -> tuple:
    """What crosses the wire between two blocks: ``(hc_mult x hidden_size, the wraps of a block)``."""
    return cfg.stream_width, len(WRAPS)


def latent_dims(cfg: Xing40BlockConfig) -> LatentDims:
    return LatentDims(
        cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank,
        cfg.rope_theta, cfg.latent_norm_eps, q_scale=cfg.softmax_mscale, rope_scaling=cfg.rope_scaling,
    )


def _over(slabs) -> jnp.ndarray:
    return functools.reduce(jnp.add, slabs)


def stream_coefficients(phi, alpha, bias, stream: jnp.ndarray, cfg: Xing40BlockConfig) -> tuple:
    """``(Hp [n, b, s], Hq [n, b, s], M [n, n, b, s])`` of one wrap over the
    flat ``stream`` [b, s, n*C], float32: ``M[m, k]`` weighs row ``k`` in row ``m``."""
    n = cfg.hc_mult
    with jax.named_scope("ptu.hc.coef"):
        x = stream.astype(jnp.float32)
        x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + cfg.rms_norm_eps)
        logits = jnp.einsum("bsk,kj->jbs", x, phi.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
        alpha = jnp.concatenate([jnp.broadcast_to(alpha.astype(jnp.float32)[i], (w,)) for i, w in enumerate((n, n, n * n))])
        logits = logits * alpha[:, None, None] + bias.astype(jnp.float32)[:, None, None]
        pre, post = jax.nn.sigmoid(logits[:n]), 2.0 * jax.nn.sigmoid(logits[n : 2 * n])
        mix = jnp.exp(jnp.clip(logits[2 * n :], *cfg.hc_res_clamp)).reshape(n, n, *logits.shape[1:])
    def sinkhorn_round(_, mix):  # rows, then columns; sums of slabs
        mix = mix / (_over([mix[:, k] for k in range(n)])[:, None] + cfg.hc_eps)
        return mix / (_over([mix[m] for m in range(n)])[None] + cfg.hc_eps)

    with jax.named_scope("ptu.hc.sinkhorn"):  # a loop, not forty copies of its body: a step program holds it 16 times
        mix = jax.lax.fori_loop(0, cfg.hc_sinkhorn_iters, sinkhorn_round, mix)
    return pre, post, mix


def stream_wrap(params: dict, wrap: str, stream: jnp.ndarray, sublayer: Callable, cfg: Xing40BlockConfig):
    """One sub-layer inside its hyper-connection: ``(X' flat, what the sub-layer returned beside its output)``.
    ``sublayer`` takes ``u`` [b, s, C] and returns ``(F(norm(u)), extra)``."""
    n, width = cfg.hc_mult, cfg.hidden_size
    pre, post, mix = stream_coefficients(params[f"hc_phi_{wrap}"], params[f"hc_alpha_{wrap}"], params[f"hc_bias_{wrap}"], stream, cfg)

    def rows() -> list:  # the stream's rows, sliced where they lie
        return [stream[..., k * width : (k + 1) * width].astype(jnp.float32) for k in range(n)]

    with jax.named_scope("ptu.hc.mix"):
        u = _over([pre[k][..., None] * row for k, row in enumerate(rows())]).astype(stream.dtype)
    out, extra = sublayer(u)
    with jax.named_scope("ptu.hc.mix"):
        out, read = out.astype(jnp.float32), rows()
        mixed = [_over([mix[m, k][..., None] * read[k] for k in range(n)]) + post[m][..., None] * out for m in range(n)]
        return jnp.concatenate(mixed, axis=-1).astype(stream.dtype), extra


def block_apply(
    params: dict,
    hidden_states: jnp.ndarray,  # the stream, flat: [batch, seq, hc_mult x hidden]
    kv,  # None, or (c, k_pe): two PagedKV over the lane pool's pages
    position,
    cfg: Xing40BlockConfig,
    *,
    kind: str,
    use_flash: bool = False,
    tp_mesh=None,
    n_valid=None,
    live_rows=None,  # bool [batch] from a lane pool's step: the rows that are not idle lanes (None: all)
) -> Tuple[jnp.ndarray, Optional[tuple]]:
    if hidden_states.shape[-1] != cfg.stream_width:
        raise ValueError(
            f"xing4_0: a block takes the residual stream flat, [batch, seq, {cfg.hc_mult} x {cfg.hidden_size} = "
            f"{cfg.stream_width}], got {tuple(hidden_states.shape)}"
        )

    def attention(u):
        return latent_attention(params, rms_norm(u, params["ln1"], cfg.rms_norm_eps), kv, position, latent_dims(cfg),
                                n_valid=n_valid, who="xing4_0")

    def mlp(u):
        return feed_forward(params, rms_norm(u, params["ln2"], cfg.rms_norm_eps), cfg, kind, tp_mesh=tp_mesh, live_rows=live_rows), None

    stream, new_kv = stream_wrap(params, "attn", hidden_states, attention, cfg)
    stream, _ = stream_wrap(params, "mlp", stream, mlp, cfg)
    return stream, new_kv


# ----------------------------------------------------------------------------------
# HF checkpoint mapping
# ----------------------------------------------------------------------------------

_HF_BLOCK_PREFIXES = ("model.layers.{i}.",)
_HF_WRAPS = {"attn": "attn_hc.", "mlp": "mlp_hc."}  # the configuration's ``assumed.tensor_names`` (perf/configs/) says why these


def hf_to_block_params(tensors: dict, cfg: Xing40BlockConfig, kind: str) -> dict:
    params = {
        "ln1": np.asarray(tensors["input_layernorm.weight"]),
        **attention_params(tensors, cfg),  # the low-rank query's leaves: the checkpoint has ``q_a_proj``
        "ln2": np.asarray(tensors["post_attention_layernorm.weight"]),
        **feed_forward_params(tensors, cfg, kind),
    }
    for wrap, p in _HF_WRAPS.items():  # the three products as one matrix, columns [pre | post | res, row-major]; Linear layout [out, in]
        params[f"hc_phi_{wrap}"] = np.ascontiguousarray(np.concatenate([np.asarray(tensors[p + f"phi_{c}.weight"]) for c in COEFFICIENTS]).T)
        params[f"hc_alpha_{wrap}"] = np.concatenate([np.asarray(tensors[p + f"alpha_{c}"], np.float32).reshape(1) for c in COEFFICIENTS])
        params[f"hc_bias_{wrap}"] = np.concatenate([np.asarray(tensors[p + f"b_{c}"], np.float32).reshape(-1) for c in COEFFICIENTS])
    return params


def block_param_shapes(cfg: Xing40BlockConfig, kind: str, dtype=jnp.bfloat16) -> dict:
    S, h, n = jax.ShapeDtypeStruct, cfg.hidden_size, cfg.hc_mult
    shapes = {"ln1": S((h,), dtype), **attention_shapes(cfg, dtype), "ln2": S((h,), dtype), **feed_forward_shapes(cfg, kind, dtype)}
    for wrap in WRAPS:
        shapes.update({f"hc_phi_{wrap}": S((n * h, 2 * n + n * n), dtype), f"hc_alpha_{wrap}": S((3,), jnp.float32),
                       f"hc_bias_{wrap}": S((2 * n + n * n,), jnp.float32)})
    return shapes


# tp_pspecs, quantizable_leaves and lora_targets are not declared: a span whose pages carry latent rows is served on one
# chip's paged lane pool, unsharded and unquantized, as deepseek_v3's is, and a stream wider than the model has no
# partition, no fused quantised form and no adapter target yet: parallel/tp.py, utils/convert_block.py and utils/peft.py
# refuse the family by name (tests/test_xing4_0.py)
FAMILY = register_family(
    ModelFamily(
        name="xing4_0",
        config_from_hf=Xing40BlockConfig.from_hf_config,
        block_apply=block_apply,
        hf_block_prefixes=_HF_BLOCK_PREFIXES,
        hf_to_block_params=hf_to_block_params,
        block_param_shapes=block_param_shapes,
        moe_dims=moe_dims,
        block_kind=block_kind,
        block_latent=block_latent,
        block_stream=block_stream,
        cast_exempt=("gate_bias", *(f"hc_{leaf}_{wrap}" for leaf in ("alpha", "bias") for wrap in WRAPS)),
    )
)
