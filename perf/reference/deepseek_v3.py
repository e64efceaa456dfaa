"""``deepseek_v3`` block (Kanana-2-30B-A3B publishes under it), by kind of
layer (``dense`` | ``sparse``), the EXPANDED form only: every position's
latent becomes keys and values of every head, and plain causal attention runs
over them.

Pre-norm: ``h = x + Attn(n1(x)); y = h + F(n2(h))``. Attention
(transformers' ``DeepseekV3Attention`` with ``q_lora_rank`` null): ``q = a
Wq`` as heads of ``[q_nope | q_pe]``; ``a Wkva = [c | k_pe]``, ``c`` under an
RMS norm (``kv_a_layernorm``); the rotary over ``q_pe`` (a head) and ``k_pe``
(ONE head for all), in the published form: pairs ``(2j, 2j + 1)``
de-interleaved to halves, then rotate-half
(``apply_rotary_pos_emb_interleave``); ``c Wkvb`` as heads of ``[k_nope |
v]``; ``score = (q_nope . k_nope + q_pe . k_pe) / sqrt(qk_nope_head_dim +
qk_rope_head_dim)``, causal softmax, ``o = sum p v``, ``concat(o) Wo``. ``F``
of a dense layer is a SwiGLU of ``intermediate_size``. ``F`` of a sparse
layer: ``s = sigmoid(n W_g)`` over ``n_routed_experts``, the top k of ``s +
e_score_correction_bias`` chosen, weighed ``routed_scaling_factor * s_i / (sum
over the chosen of s + 1e-20)``, plus ONE shared SwiGLU of ``n_shared_experts x
moe_intermediate_size`` for every token.

Sources of what ``config.json`` does not settle are in the configuration's
``assumed``."""

import jax
import jax.numpy as jnp

from perf.reference import rotate_half_rotary

# Measured through the 6 layers of kanana2-30b-a3b-span6 on the v5e, bf16 weights, activations and cache against
# this float32 reference (perf/prove_correct.py, PR 42: one call, 12 seeds x 105 rows, seeds 4200000001-12).
#
# Rows where no router flipped (1,014 of 1,260): median 1.12e-2 (per seed 1.03e-2..1.26e-2 over the rows compared;
# 1.9e-3 a layer, the other families' figure), worst 1.82e-2, decode rows (absorbed) as prefill rows (expanded).
# The router is a discontinuity: the top 6 of 128 are renormalised and scaled by 2.448, so where the served path
# picks the other expert at the boundary the row lands 0.07-0.33 off (246 rows, a fifth of all: median 0.13, nine
# in ten under 0.23). 128 scores lie close: the 6th and 7th of ``s + bias`` are within 0.001 of the largest score
# at one of the five expert layers for 34% of the positions, and two flips in three happen there (the margins of
# the flipped rows: median 0.0005, nine in ten under 0.0017, one at 0.0047: moved by a flipped position it
# attends to). No margin separates them all and keeps a quarter of the rows (0.003 keeps 27%, 15% of a kind in one
# seed), so the limits divide the work: TIE_MARGIN 0.0015 leaves out the half of the rows where seven flips in
# eight are (47%; 37-69% of a kind compared in every seed); the ROW bound takes a flip in and sits under a row that
# read the wrong thing: the worst compared row of the 12 seeds is 0.239 (of 669 compared: 34 over 0.05, 3 over 0.2;
# the bound, 0.29, is the most tests/perf allows a family, "a wrong kernel lands at 0.3..1": a lane reading
# another's pages or a shifted rotary lands near 1); the two positions allowed outside are for a flip's tail (nine
# flips in ten are under 0.23; none was outside); and the MEDIAN bound, twice the worst seed's median, is what
# holds the arithmetic: a flip moves a fifth of the rows and no median.
#
# One precision lower comes out not correct: the reference itself with float8 (e4m3) weights and layer inputs (2
# seeds, CPU, the published widths, the check's 144 positions) is 0.57-0.63 off in the median row, 24 times the
# median bound (and every compared row is outside the row bound too); with bf16 weights and layer inputs 6.8e-3..
# 7.6e-3 in the median, inside, with the same flips (0.16-0.28) at margins under 0.001.
ROW_BOUND_PER_LAYER = 0.29 / 6
MEDIAN_BOUND_PER_LAYER = 2.4e-2 / 6
TIE_MARGIN = 0.0015
POSITIONS_ALLOWED_OUTSIDE = 2


def layer_kinds(hf: dict) -> list:
    dense = hf.get("first_k_dense_replace", 0)
    return ["dense" if i < dense else "sparse" for i in range(max(hf["num_hidden_layers"], dense))]


def _dims(hf: dict) -> tuple:
    return hf["hidden_size"], hf["num_attention_heads"], hf["qk_nope_head_dim"], hf["qk_rope_head_dim"], hf["v_head_dim"], hf["kv_lora_rank"]


def layer_params(hf: dict, kind: str) -> dict:
    """Matrix parameters of one layer of ``kind`` (perf/costs.py says what the
    keys mean). costs.py knows a cache of ``2 x kv_heads x head_dim`` values a
    position and an attention of ``4 x q_heads x head_dim`` flops a (row,
    position) pair. A position here caches ``kv_lora_rank + qk_rope_head_dim``
    values ONCE (576: 1,152 B), and the cheaper of the two forms (the
    expanded one) computes ``2 x heads x (qk_head_dim + v_head_dim)`` flops a
    pair (20,480) beside the expansion. So the shape is stated as 2 kv heads
    of a quarter of the cached row (2 x 2 x 144 x 2 B = 1,152 B a position,
    exactly), under the published 32 query heads: 4 x 32 x 144 = 18,432 flops
    a pair, 10% UNDER the cheaper form's, never over (PERF.md section 7 asks
    for a latent term in costs.py)."""
    h, heads, dn, dr, dv, latent = _dims(hf)
    row = latent + dr
    assert row % 4 == 0 and 4 * heads * (row // 4) <= 2 * heads * (dn + dr + dv)
    attn = h * heads * (dn + dr) + h * row + latent * heads * (dn + dv) + heads * dv * h
    out = {"attn": attn, "hidden": h, "q_heads": heads, "kv_heads": 2, "head_dim": row // 4}
    if kind == "dense":
        return {**out, "dense": 3 * h * hf["intermediate_size"], "expert": 0, "experts": 0, "top_k": 0}
    expert = 3 * h * hf["moe_intermediate_size"]
    n = hf["n_routed_experts"]
    return {**out, "dense": h * n + hf.get("n_shared_experts", 0) * expert, "expert": expert, "experts": n,
            "top_k": hf["num_experts_per_tok"]}


def _rms_norm(x, weight, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * weight


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate.T) * (x @ up.T)) @ down.T


def rotary(x, theta: float, interleave: bool):
    """x [seq, heads, d] at positions 0..seq-1: transformers'
    ``apply_rotary_pos_emb_interleave`` (pairs (2j, 2j + 1) to halves, then
    rotate half) where ``interleave``, else rotate half as it lies."""
    if interleave:
        seq, heads, d = x.shape
        x = x.reshape(seq, heads, d // 2, 2).swapaxes(-1, -2).reshape(seq, heads, d)
    return rotate_half_rotary(x, theta)


def block(hf: dict, w: dict, x, kind: str, *, rows: int = 0):
    """``rows``: attend in blocks of that many rows (it must divide the
    sequence), each against the whole sequence under its rows of the causal
    mask, so that a long sequence fits: the same sums, the score matrix never
    whole. 0: the whole [seq, seq] matrix at once."""
    h, heads, dn, dr, dv, latent = _dims(hf)
    eps, theta = hf["rms_norm_eps"], hf["rope_theta"]
    assert hf.get("n_group", 1) == 1 and hf.get("topk_group", 1) == 1 and hf.get("scoring_func", "sigmoid") == "sigmoid"
    assert hf.get("hidden_act", "silu") == "silu" and not hf.get("rope_scaling") and not hf.get("attention_bias")
    assert hf.get("q_lora_rank") is None and hf.get("moe_layer_freq", 1) == 1 and hf.get("topk_method", "noaux_tc") == "noaux_tc"
    seq = x.shape[0]
    a = _rms_norm(x, w["input_layernorm.weight"], eps)
    q = (a @ w["self_attn.q_proj.weight"].T).reshape(seq, heads, dn + dr)
    row = a @ w["self_attn.kv_a_proj_with_mqa.weight"].T  # [seq, latent + dr]: one row for all heads
    c = _rms_norm(row[:, :latent], w["self_attn.kv_a_layernorm.weight"], eps)
    interleave = hf.get("rope_interleave", True)
    q_pe, k_pe = rotary(q[..., dn:], theta, interleave), rotary(row[:, None, latent:], theta, interleave)
    kv = (c @ w["self_attn.kv_b_proj.weight"].T).reshape(seq, heads, dn + dv)
    q = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe, (seq, heads, dr))], axis=-1)
    v = kv[..., dn:]

    def attend(first, q_rows):
        logits = jnp.einsum("qhd,khd->hqk", q_rows, k) / jnp.sqrt(jnp.float32(dn + dr))
        mask = jnp.arange(seq)[None, :] <= (first + jnp.arange(q_rows.shape[0]))[:, None]
        probs = jax.nn.softmax(jnp.where(mask[None], logits, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v).reshape(q_rows.shape[0], heads * dv)

    if rows:
        assert seq % rows == 0, (seq, rows)
        attn = jax.lax.map(lambda b: attend(*b), (jnp.arange(0, seq, rows), q.reshape(seq // rows, rows, heads, dn + dr)))
        attn = attn.reshape(seq, heads * dv)
    else:
        attn = attend(0, q)
    x = x + attn @ w["self_attn.o_proj.weight"].T
    r = _rms_norm(x, w["post_attention_layernorm.weight"], eps)
    if kind == "dense":
        y = _swiglu(r, w["mlp.gate_proj.weight"], w["mlp.up_proj.weight"], w["mlp.down_proj.weight"])
        return x + y, jnp.full(seq, jnp.inf)
    n, top_k = hf["n_routed_experts"], hf["num_experts_per_tok"]
    scores = jax.nn.sigmoid(r @ w["mlp.gate.weight"].T)  # [seq, n], float32 as the published router
    choice = scores + w["mlp.gate.e_score_correction_bias"]
    order = jnp.argsort(-choice, axis=-1)
    ranked = jnp.take_along_axis(choice, order, axis=-1)
    # the last expert kept against the first one dropped (the sixth and seventh ``s + bias``), as a share of
    # the position's largest score
    margin = (ranked[:, top_k - 1] - ranked[:, top_k]) / scores.max(-1)
    top_i = order[:, :top_k]
    top_s = jnp.take_along_axis(scores, top_i, axis=-1)  # the bias chooses, it does not weigh
    if hf.get("norm_topk_prob", True):
        top_s = top_s / (top_s.sum(-1, keepdims=True) + 1e-20)
    weights = (jax.nn.one_hot(top_i, n) * (hf["routed_scaling_factor"] * top_s)[..., None]).sum(1)  # [seq, n]
    y = jnp.zeros_like(x)
    for e in range(n):
        p = f"mlp.experts.{e}."
        y = y + weights[:, e : e + 1] * _swiglu(r, w[p + "gate_proj.weight"], w[p + "up_proj.weight"], w[p + "down_proj.weight"])
    if hf.get("n_shared_experts"):
        p = "mlp.shared_experts."
        y = y + _swiglu(r, w[p + "gate_proj.weight"], w[p + "up_proj.weight"], w[p + "down_proj.weight"])
    return x + y, margin
