"""Mixtral block: RMS norm, grouped-query attention with rotary embeddings
(no window in 8x7B-v0.1), then a sparse mixture of SwiGLU experts. The router
takes a softmax over all experts, keeps the top k and renormalises them to
sum to one (the published rule); every expert is run densely here and the
unselected ones get weight zero."""

import jax
import jax.numpy as jnp

from perf.reference import causal_gqa_attention, rotate_half_rotary

# Measured through 2 layers of Mixtral-8x7B on the v5e over 16 seeds x 97 rows
# (perf/prove_correct.py, PR 23). The served block takes the router's logits in
# bf16, as the published model does, which moves the routing weights by a
# percent or two: in seeds where no position's routing flipped, the median row
# is off by 1.0e-2..1.4e-2 and the worst by 2.2e-2, ten times Falcon's figure.
#
# The router is a discontinuity. Where the last expert kept and the first one
# dropped are closer than the served path's error in their logits, the served
# block may pick the other one without any fault, and that row lands 0.16..1.3
# off. Every such row had a margin (that gap over the position's largest logit)
# under 0.031; TIE_MARGIN leaves those rows out (35-40% of the rows of a
# two-layer span).
#
# A flip in a layer that is not the last also moves that position's keys and
# values in the layers after it, so every later position that attends to it is
# moved too, by up to 9.6e-2 where a head puts much of its weight there (random
# weights make sharp heads), and the median of such a seed rises to 2.8e-2. That
# perturbation can in turn flip a later layer's router at margins of a few
# percent. Hence a row bound far above the unflipped error (twice the worst
# compared row seen), two positions allowed outside it, and the median held to
# twice the worst seed's. A wrong kernel, a dropped expert or a lane reading
# another's pages moves every row to 0.3..1 and fails all three.
#
# Proved again on the weights of perf/weights (7 seeds x 105 rows, PR 23 after
# the refusal), limits unchanged: per-seed median 1.0e-2..2.3e-2, worst compared
# row 7.6e-2, 16-44% of rows left out; the flipped rows seen (0.51, 0.62) at
# margins under 0.006.
ROW_BOUND_PER_LAYER = 1e-1
MEDIAN_BOUND_PER_LAYER = 3e-2
TIE_MARGIN = 0.05
POSITIONS_ALLOWED_OUTSIDE = 2


def layer_params(hf: dict) -> dict:
    """Matrix parameters of one layer (perf/costs.py says what the keys mean)."""
    h, hq, hkv = hf["hidden_size"], hf["num_attention_heads"], hf["num_key_value_heads"]
    d = hf.get("head_dim") or h // hq
    n = hf["num_local_experts"]
    return {"attn": h * (hq + 2 * hkv) * d + hq * d * h, "dense": h * n, "expert": 3 * h * hf["intermediate_size"],
            "experts": n, "top_k": hf["num_experts_per_tok"], "hidden": h, "q_heads": hq, "kv_heads": hkv, "head_dim": d}


def _rms_norm(x, weight, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * weight


def block(hf: dict, w: dict, x):
    h, hq, hkv = hf["hidden_size"], hf["num_attention_heads"], hf["num_key_value_heads"]
    d = hf.get("head_dim") or h // hq
    group, eps, theta = hq // hkv, hf["rms_norm_eps"], hf["rope_theta"]
    n_experts, top_k = hf["num_local_experts"], hf["num_experts_per_tok"]
    assert not hf.get("sliding_window"), "the reference attends over the whole sequence"
    seq = x.shape[0]
    a = _rms_norm(x, w["input_layernorm.weight"], eps)
    q = rotate_half_rotary((a @ w["self_attn.q_proj.weight"].T).reshape(seq, hq, d), theta)
    k = rotate_half_rotary((a @ w["self_attn.k_proj.weight"].T).reshape(seq, hkv, d), theta)
    v = (a @ w["self_attn.v_proj.weight"].T).reshape(seq, hkv, d)
    attn = causal_gqa_attention(q.reshape(seq, hkv, group, d), k, v).reshape(seq, hq * d)
    x = x + attn @ w["self_attn.o_proj.weight"].T
    r = _rms_norm(x, w["post_attention_layernorm.weight"], eps)
    logits = r @ w["block_sparse_moe.gate.weight"].T
    ranked = jnp.sort(logits, axis=-1)[:, ::-1]
    # the last expert kept against the first one dropped, as a share of the position's largest logit:
    # a bf16 logit is off by a share of its size, not by an amount
    margin = (ranked[:, top_k - 1] - ranked[:, top_k]) / jnp.abs(logits).max(-1)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, top_k)
    top_p = top_p / top_p.sum(-1, keepdims=True)
    weights = (jax.nn.one_hot(top_i, n_experts) * top_p[..., None]).sum(1)  # [seq, experts]
    y = jnp.zeros_like(x)
    for e in range(n_experts):
        p = f"block_sparse_moe.experts.{e}."
        up = jax.nn.silu(r @ w[p + "w1.weight"].T) * (r @ w[p + "w3.weight"].T)
        y = y + weights[:, e : e + 1] * (up @ w[p + "w2.weight"].T)
    return x + y, margin
