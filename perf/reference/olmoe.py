"""OLMoE block (HF ``modeling_olmoe.py``): RMS norm, attention over 16 heads
with an RMS norm over the WHOLE q and k projections before the heads are
split (QK-norm) and rotary embeddings, then a sparse mixture of 64 small
SwiGLU experts. The router takes a softmax over all experts and keeps the top
k with their softmax mass as it is (``norm_topk_prob`` false: NOT divided by
their sum); every expert is run densely here and the unselected ones get
weight zero."""

import jax
import jax.numpy as jnp

from perf.reference import causal_gqa_attention, rotate_half_rotary

# Measured through 8 layers of OLMoE-1B-7B on the v5e, bf16 weights, activations
# and cache against float32 (perf/prove_correct.py, PR 26): 10 seeds x 105 rows
# on the weights of perf/weights/olmoe.py as they stand, every row compared:
# per-seed median row 1.03e-2..1.27e-2 (1.6e-3 a layer), worst row 2.60e-2
# (3.3e-3 a layer), decode rows as prefill rows (medians 1.1385e-2 and 1.138e-2
# over all 1050); 10 seeds on an earlier draw of the same weights' statistics:
# 1.04e-2..1.20e-2 and 2.34e-2. The bounds are twice the worst seed's median
# (2.54e-2) and 2.5 times the worst row (6.5e-2), rounded down: 2.4e-2 and
# 5.6e-2 over the 8 layers, a fifth of the 0.3 where a wrong kernel lands.
#
# No TIE_MARGIN and no POSITIONS_ALLOWED_OUTSIDE: every row is compared and none
# may be outside, though the router flips all the time. With 64 experts the 8th
# and 9th logits are close: every one of the 1050 rows had a margin under 0.05
# at some layer and 42% one under 0.002, so the served bf16 router can pick another
# 8th expert in nearly any row. It does not matter: the top-k weights are not
# renormalised, so the expert that flips carries ~0.03 of the 64-way softmax
# mass (Mixtral's carries ~0.5 of two) and its output differs from the other
# candidate's by less than bf16's own error. Rows at a margin under 0.002: median
# 1.23e-2, worst 2.60e-2; rows at 0.01 or more: 1.07e-2 and 1.54e-2.
#
# One precision lower comes out not correct: the reference itself run with
# float8 (e4m3) weights and layer inputs is 0.11-0.12 off in the median row and
# 0.16-0.19 in the worst, every row outside (2 seeds, CPU); with bf16 layer
# inputs it is 6.7e-3..7.5e-3 and 1.5e-2, inside.
ROW_BOUND_PER_LAYER = 7e-3
MEDIAN_BOUND_PER_LAYER = 3e-3


def layer_params(hf: dict) -> dict:
    """Matrix parameters of one layer (perf/costs.py says what the keys mean).
    ``intermediate_size`` is one expert's width (HF ``OlmoeConfig``)."""
    h, hq, hkv = hf["hidden_size"], hf["num_attention_heads"], hf["num_key_value_heads"]
    d = h // hq
    n = hf["num_experts"]
    return {"attn": h * (hq + 2 * hkv) * d + hq * d * h, "dense": h * n, "expert": 3 * h * hf["intermediate_size"],
            "experts": n, "top_k": hf["num_experts_per_tok"], "hidden": h, "q_heads": hq, "kv_heads": hkv, "head_dim": d}


def _rms_norm(x, weight, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * weight


def block(hf: dict, w: dict, x):
    h, hq, hkv = hf["hidden_size"], hf["num_attention_heads"], hf["num_key_value_heads"]
    d, group, eps, theta = h // hq, hq // hkv, hf["rms_norm_eps"], hf["rope_theta"]
    n_experts, top_k = hf["num_experts"], hf["num_experts_per_tok"]
    assert hf.get("clip_qkv") is None and not hf.get("rope_scaling") and not hf.get("attention_bias")
    assert not hf.get("norm_topk_prob"), "the reference keeps the top-k weights as the softmax gave them"
    seq = x.shape[0]
    a = _rms_norm(x, w["input_layernorm.weight"], eps)
    q = _rms_norm(a @ w["self_attn.q_proj.weight"].T, w["self_attn.q_norm.weight"], eps)  # over all hq * d outputs
    k = _rms_norm(a @ w["self_attn.k_proj.weight"].T, w["self_attn.k_norm.weight"], eps)
    q = rotate_half_rotary(q.reshape(seq, hq, d), theta)
    k = rotate_half_rotary(k.reshape(seq, hkv, d), theta)
    v = (a @ w["self_attn.v_proj.weight"].T).reshape(seq, hkv, d)
    attn = causal_gqa_attention(q.reshape(seq, hkv, group, d), k, v).reshape(seq, hq * d)
    x = x + attn @ w["self_attn.o_proj.weight"].T
    r = _rms_norm(x, w["post_attention_layernorm.weight"], eps)
    logits = r @ w["mlp.gate.weight"].T
    ranked = jnp.sort(logits, axis=-1)[:, ::-1]
    # the last expert kept against the first one dropped, as a share of the position's largest logit
    # (perf/reference/mixtral.py): recorded with every row, though no row is left out for it
    margin = (ranked[:, top_k - 1] - ranked[:, top_k]) / jnp.abs(logits).max(-1)
    top_p, top_i = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    weights = (jax.nn.one_hot(top_i, n_experts) * top_p[..., None]).sum(1)  # [seq, experts]
    y = jnp.zeros_like(x)
    for e in range(n_experts):
        p = f"mlp.experts.{e}."
        up = jax.nn.silu(r @ w[p + "gate_proj.weight"].T) * (r @ w[p + "up_proj.weight"].T)
        y = y + weights[:, e : e + 1] * (up @ w[p + "down_proj.weight"].T)
    return x + y, margin
