"""Falcon-40B/180B block ("new decoder architecture"): two layer norms of the
same input feed attention and the MLP in parallel, grouped-query attention
with rotary embeddings, exact GELU, no biases; out = x + attn + mlp."""

import jax
import jax.numpy as jnp

from perf.reference import causal_gqa_attention, rotate_half_rotary

# bf16 weights, activations and cache against float32, through 5 layers of
# Falcon-40B on the v5e over 4 seeds x 105 rows on the weights of perf/weights
# (perf/prove_correct.py and one cell run, PR 23 after the refusal): the median
# row 9.1e-3..9.5e-3 (1.9e-3 a layer), the worst row 1.42e-2 (2.8e-3 a layer),
# decode rows no different from prefill rows. Twice the median and 2.5 times
# the worst row. A dense block decides nothing: no tie margin, every row
# compared, none allowed outside.
ROW_BOUND_PER_LAYER = 7e-3
MEDIAN_BOUND_PER_LAYER = 4e-3


def layer_params(hf: dict) -> dict:
    """Matrix parameters of one layer (perf/costs.py says what the keys mean)."""
    h, hq, hkv = hf["hidden_size"], hf["num_attention_heads"], hf["num_kv_heads"]
    d = h // hq
    ffn = hf.get("ffn_hidden_size") or 4 * h
    return {"attn": h * (hq + 2 * hkv) * d + hq * d * h, "dense": 2 * h * ffn, "expert": 0, "experts": 0, "top_k": 0,
            "hidden": h, "q_heads": hq, "kv_heads": hkv, "head_dim": d}


def _layer_norm(x, weight, bias, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * weight + bias


def block(hf: dict, w: dict, x):
    h, hq, hkv = hf["hidden_size"], hf["num_attention_heads"], hf["num_kv_heads"]
    d, group, eps = h // hq, hq // hkv, hf["layer_norm_epsilon"]
    theta = hf.get("rope_theta", 10000.0)
    seq = x.shape[0]
    attn_in = _layer_norm(x, w["ln_attn.weight"], w["ln_attn.bias"], eps)
    mlp_in = _layer_norm(x, w["ln_mlp.weight"], w["ln_mlp.bias"], eps)
    # fused rows are laid out per kv group: its `group` query heads, its key, its value
    qkv = (attn_in @ w["self_attention.query_key_value.weight"].T).reshape(seq, hkv, group + 2, d)
    q = rotate_half_rotary(qkv[:, :, :group].reshape(seq, hq, d), theta).reshape(seq, hkv, group, d)
    k = rotate_half_rotary(qkv[:, :, group], theta)
    v = qkv[:, :, group + 1]
    attn = causal_gqa_attention(q, k, v).reshape(seq, hq * d) @ w["self_attention.dense.weight"].T
    mlp = jax.nn.gelu(mlp_in @ w["mlp.dense_h_to_4h.weight"].T, approximate=False) @ w["mlp.dense_4h_to_h.weight"].T
    return x + attn + mlp, jnp.full(seq, jnp.inf)
