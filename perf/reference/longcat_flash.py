"""``longcat_flash`` block (LongCat-Flash-Chat publishes under it), as one
chip of a server computes it: one ``model.layers.{i}`` of the checkpoint, a
double layer, the EXPANDED attention only.

    a = x + MLA_0(rms(x, input_layernorm.0));  n = rms(a, post_attention_layernorm.0)
    s = MoE(n);  b = a + FFN_0(n)
    c = b + MLA_1(rms(b, input_layernorm.1));  y = c + FFN_1(rms(c, post_attention_layernorm.1)) + s

``FFN_j`` is a SwiGLU of ``ffn_hidden_size`` (``mlps.{j}``). ``MLA_j``
(transformers' ``LongcatFlashMLA``, ``self_attn.{j}``): ``q =
q_b_proj(rms(q_a_proj(x), q_a_layernorm, 1e-6))`` as heads of ``[q_nope |
q_pe]``, times ``sqrt(hidden / q_lora_rank)``; ``kv_a_proj_with_mqa(x) = [c |
k_pe]``, ``c = rms(c, kv_a_layernorm, 1e-6) * sqrt(hidden / kv_lora_rank)``;
``kv_b_proj(c)`` as heads of ``[k_nope | v]``; the rotary over ``q_pe`` (a
head) and ``k_pe`` (ONE head for all) in the published form: pairs ``(2j, 2j +
1)`` de-interleaved to halves, then rotate-half; ``score = (q_nope . k_nope +
q_pe . k_pe) / sqrt(qk_nope_head_dim + qk_rope_head_dim)``, causal softmax,
``o_proj``. ``MoE`` (``LongcatFlashMoE``): ``p = softmax(n W_c)`` over the
``expert_share.routed + zero_expert_num`` outputs of the router, the top
``moe_topk`` of ``p + e_score_correction_bias`` chosen, weighed
``routed_scaling_factor * p_e``, not renormalised; expert ``e <
expert_share.routed`` is a SwiGLU of ``expert_ffn_hidden_size``, of which the
``n_routed_experts`` this chip HOLDS (from ``expert_share.first`` on) are run
and the others' parts left to the chips that hold them; expert ``e >=
expert_share.routed`` is the identity, added for every token.

Sources of what ``config.json`` does not settle are in the configuration's
``assumed``."""

import jax
import jax.numpy as jnp

from perf.reference.deepseek_v3 import rotary

# What benchmarks/prove_scmoe_matters.py sets, one at a time, to show that the limits below see it (each has to come out
# NOT correct): the router's product in bfloat16, a scale left out, the kept weights renormalised, the identities left
# out. None: the published block.
CONTROLS = ("bf16_router", "no_q_scale", "no_kv_scale", "renormalised", "no_identities")
CONTROL = None

LATENT_NORM_EPS = 1e-6  # q_a_layernorm, kv_a_layernorm: constructed without eps (modeling_longcat_flash.py:311, :319)

# Measured through the 4 blocks (8 attentions, 8 dense feed-forwards, 4 expert layers) of longcat-flash-span4-ep32 on
# the v5e, bf16 weights, activations and cache against this float32 reference (perf/prove_correct.py, PR 56; the
# numbers and the controls' readings are in PERF.md section 6, PR 56, with their files under chiprun_out/).
#
# First call, 6 seeds x 105 rows: median row 3.55e-2..3.95e-2 a seed, worst row 6.0e-2 of 630, prefill rows (expanded)
# as decode rows (absorbed). 9e-3 a block, twice the other families' 2e-3 a layer for a block of two layers: the two
# published scales make the attention's logits 2 x 3.46 times what the same weights give without them (a standard
# deviation of 2.5 at these weights), so the softmax multiplies bf16's rounding of q and of the cached row by as much.
# No row stands apart: a flipped pick at the router's boundary moves a row by 6 p of one expert's output, p ~ 1.2e-2,
# about 0.02 of a row's largest value and under what rounding moves it (the expert branch is a small share of the
# block by the deployment's own arithmetic; two boundary picks in three are absent experts'), so no margin is stated
# and no position allowed outside. The MEDIAN bound is twice the worst seed's median, the ROW bound 2.5 times the
# worst row seen. What each has to see, and does or does not, is benchmarks/prove_scmoe_matters.py's to show.
ROW_BOUND_PER_LAYER = 0.15 / 4
MEDIAN_BOUND_PER_LAYER = 8e-2 / 4
TIE_MARGIN = 0.0
POSITIONS_ALLOWED_OUTSIDE = 0


def held_share(hf: dict) -> tuple:
    """(held, exist, first): the FFN experts this chip holds of those the model has."""
    share = hf.get("expert_share") or {}
    return hf["n_routed_experts"], share.get("routed", hf["n_routed_experts"]), share.get("first", 0)


def _dims(hf: dict) -> tuple:
    return (hf["hidden_size"], hf["num_attention_heads"], hf["qk_nope_head_dim"], hf["qk_rope_head_dim"], hf["v_head_dim"],
            hf["kv_lora_rank"], hf["q_lora_rank"])


def layer_params(hf: dict) -> dict:
    """Matrix parameters of one block (perf/costs.py says what the keys
    mean): both attentions, both dense feed-forwards and the router run for
    every token; the identities have no parameter. costs.py knows a cache of
    ``2 x kv_heads x head_dim`` values a position and an attention of ``4 x
    q_heads x head_dim`` flops a (row, position) pair. A position here caches
    TWO rows of ``kv_lora_rank + qk_rope_head_dim`` values (2 x 576: 2,304 B),
    and the cheaper of the two forms (the expanded one) computes, in both
    attentions, ``2 x 2 x heads x (qk_head_dim + v_head_dim)`` flops a pair
    (81,920). So the shape is stated as ``deepseek_v3``'s one row is, doubled:
    2 kv heads of half a cached row (2 x 2 x 288 x 2 B = 2,304 B a position,
    exactly) under the published 64 query heads: 4 x 64 x 288 = 73,728 flops
    a pair, 10% UNDER the cheaper form's, never over."""
    h, heads, dn, dr, dv, latent, rq = _dims(hf)
    row = latent + dr
    assert row % 2 == 0 and 4 * heads * (row // 2) <= 2 * 2 * heads * (dn + dr + dv)
    attn = h * rq + rq * heads * (dn + dr) + h * row + latent * heads * (dn + dv) + heads * dv * h
    held, exist, _ = held_share(hf)
    routed = exist + hf.get("zero_expert_num", 0)
    return {"attn": 2 * attn, "dense": 2 * 3 * h * hf["ffn_hidden_size"] + h * routed, "expert": 3 * h * hf["expert_ffn_hidden_size"],
            "experts": held, "experts_routed": routed, "top_k": hf["moe_topk"], "hidden": h, "q_heads": heads, "kv_heads": 2,
            "head_dim": row // 2}


def _rms_norm(x, weight, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * weight


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate.T) * (x @ up.T)) @ down.T


def attention(hf: dict, w: dict, j: int, x):
    """``MLA_j`` over the normed rows ``x`` [seq, hidden], expanded."""
    h, heads, dn, dr, dv, latent, rq = _dims(hf)
    seq, theta, p = x.shape[0], hf["rope_theta"], f"self_attn.{j}."
    q = _rms_norm(x @ w[p + "q_a_proj.weight"].T, w[p + "q_a_layernorm.weight"], LATENT_NORM_EPS) @ w[p + "q_b_proj.weight"].T
    q = q.reshape(seq, heads, dn + dr) * (1.0 if CONTROL == "no_q_scale" else jnp.sqrt(jnp.float32(h / rq)))
    row = x @ w[p + "kv_a_proj_with_mqa.weight"].T  # [seq, latent + dr]: one row for all heads
    c = _rms_norm(row[:, :latent], w[p + "kv_a_layernorm.weight"], LATENT_NORM_EPS)
    c = c * (1.0 if CONTROL == "no_kv_scale" else jnp.sqrt(jnp.float32(h / latent)))
    q_pe, k_pe = rotary(q[..., dn:], theta, True), rotary(row[:, None, latent:], theta, True)
    kv = (c @ w[p + "kv_b_proj.weight"].T).reshape(seq, heads, dn + dv)
    q = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe, (seq, heads, dr))], axis=-1)
    logits = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(dn + dr))
    mask = jnp.arange(seq)[None, :] <= jnp.arange(seq)[:, None]
    probs = jax.nn.softmax(jnp.where(mask[None], logits, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", probs, kv[..., dn:]).reshape(seq, heads * dv) @ w[p + "o_proj.weight"].T


def experts(hf: dict, w: dict, n):
    """The shortcut branch over the normed rows ``n`` [seq, hidden]: ``(what
    the held experts and the identities give, margin)``."""
    held, exist, first = held_share(hf)
    zeros, top_k = hf.get("zero_expert_num", 0), hf["moe_topk"]
    assert hf.get("zero_expert_type", "identity") == "identity" and not hf.get("router_bias")
    logits = n @ w["mlp.router.classifier.weight"].T  # [seq, exist + zeros], float32 as published
    if CONTROL == "bf16_router":
        logits = (n.astype(jnp.bfloat16) @ w["mlp.router.classifier.weight"].T.astype(jnp.bfloat16)).astype(jnp.float32)
    scores = jax.nn.softmax(logits, axis=-1)
    choice = scores + w["mlp.router.e_score_correction_bias"]
    order = jnp.argsort(-choice, axis=-1)
    ranked = jnp.take_along_axis(choice, order, axis=-1)
    # the last pick kept against the first one dropped, as a share of the position's largest score, where one of
    # the two is computed here (a held expert or an identity); a flip between two absent experts changes nothing here
    at_boundary = order[:, top_k - 1 : top_k + 1]
    here = (((at_boundary >= first) & (at_boundary < first + held)) | (at_boundary >= exist)).any(-1)
    margin = jnp.where(here, (ranked[:, top_k - 1] - ranked[:, top_k]) / scores.max(-1), jnp.inf)
    top_i = order[:, :top_k]
    top_s = jnp.take_along_axis(scores, top_i, axis=-1)  # the bias chooses, it does not weigh; not renormalised
    if CONTROL == "renormalised":
        top_s = top_s / top_s.sum(-1, keepdims=True)
    weights = (jax.nn.one_hot(top_i, exist + zeros) * (hf["routed_scaling_factor"] * top_s)[..., None]).sum(1)  # [seq, exist + zeros]
    y = (0.0 if CONTROL == "no_identities" else weights[:, exist:].sum(-1, keepdims=True)) * n  # the identities: every chip computes them alike
    for e in range(first, first + held):  # the held share; the other chips' parts are left out
        p = f"mlp.experts.{e}."
        y = y + weights[:, e : e + 1] * _swiglu(n, w[p + "gate_proj.weight"], w[p + "up_proj.weight"], w[p + "down_proj.weight"])
    return y, margin


def block(hf: dict, w: dict, x):
    eps = hf["rms_norm_eps"]
    assert hf.get("hidden_act", "silu") == "silu" and not hf.get("rope_scaling") and not hf.get("attention_bias")
    a = x + attention(hf, w, 0, _rms_norm(x, w["input_layernorm.0.weight"], eps))
    n = _rms_norm(a, w["post_attention_layernorm.0.weight"], eps)
    s, margin = experts(hf, w, n)
    b = a + _swiglu(n, w["mlps.0.gate_proj.weight"], w["mlps.0.up_proj.weight"], w["mlps.0.down_proj.weight"])
    c = b + attention(hf, w, 1, _rms_norm(b, w["input_layernorm.1.weight"], eps))
    r = _rms_norm(c, w["post_attention_layernorm.1.weight"], eps)
    return c + _swiglu(r, w["mlps.1.gate_proj.weight"], w["mlps.1.up_proj.weight"], w["mlps.1.down_proj.weight"]) + s, margin
