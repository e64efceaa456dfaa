"""Olmo-Hybrid layer tensors under their HF names by kind of layer, in the
served block's layout, and a span of both kinds as the server holds it.

``A_log``, ``dt_bias`` and the conv's taps are not drawn like a matrix (the
configuration's ``assumed.weights``): A uniform in 1-16 and the step dt
log-uniform in 0.001-0.1, ``dt_bias`` its inverse softplus, as the rule's
authors initialise them, so that alpha spreads and the state carries weight
over the check's 128 positions. A transcendental function rounds differently
on the CPU and on the chip, and the two sides' bits must agree: both come
from tables of 256 values computed here on the host, indexed by hashed
bits."""

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

from perf.weights import _fmix

_STEPS = (np.arange(256) + 0.5) / 256
_DT = np.exp(np.log(0.001) + _STEPS * (np.log(0.1) - np.log(0.001)))
A_LOG_TABLE = np.log(1.0 + 15.0 * _STEPS).astype(np.float32).astype(ml_dtypes.bfloat16)
DT_BIAS_TABLE = (_DT + np.log(-np.expm1(-_DT))).astype(np.float32).astype(ml_dtypes.bfloat16)
CONV_SCALE = 16.0  # a power of two, exact in bfloat16: taps of std 0.32, as wide as a 4-tap conv's usual start


def _bytes(draws, n: int, layer, salt: int):
    """``n`` integers in 0..255, a stream of their own a layer and salt:
    ``Draws.normal``'s hash, its top byte kept."""
    key = _fmix(_fmix(jnp.uint32(draws.seed) + jnp.asarray(layer).astype(jnp.uint32) * jnp.uint32(0x9E3779B9))
                + jnp.uint32((salt * 0x7F4A7C15 + 1) % 2**32))
    return (_fmix(_fmix(jax.lax.iota(jnp.uint32, n)) + key) >> 24).astype(jnp.int32)


def layer_tensors(hf: dict, layer, draws, kind: str) -> dict:
    h, m = hf["hidden_size"], hf["intermediate_size"]
    tensors = {
        "post_attention_layernorm.weight": draws.const((h,), 1.0),
        "post_feedforward_layernorm.weight": draws.const((h,), 1.0),
        "mlp.gate_proj.weight": draws.normal((m, h), layer, 10),
        "mlp.down_proj.weight": draws.normal((h, m), layer, 11),
        "mlp.up_proj.weight": draws.normal((m, h), layer, 12),
    }
    if kind == "full_attention":
        hq, hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
        d = hf.get("head_dim") or h // hq
        tensors.update({
            "self_attn.q_proj.weight": draws.normal((hq * d, h), layer, 0),
            "self_attn.k_proj.weight": draws.normal((hkv * d, h), layer, 1),
            "self_attn.v_proj.weight": draws.normal((hkv * d, h), layer, 2),
            "self_attn.o_proj.weight": draws.normal((h, hq * d), layer, 3),
            "self_attn.q_norm.weight": draws.const((hq * d,), 1.0),
            "self_attn.k_norm.weight": draws.const((hkv * d,), 1.0),
        })
        return tensors
    heads, d_k, d_v, taps = hf["linear_num_value_heads"], hf["linear_key_head_dim"], hf["linear_value_head_dim"], hf["linear_conv_kernel_dim"]
    channels = heads * (2 * d_k + d_v)
    tensors.update({
        "linear_attn.q_proj.weight": draws.normal((heads * d_k, h), layer, 0),
        "linear_attn.k_proj.weight": draws.normal((heads * d_k, h), layer, 1),
        "linear_attn.v_proj.weight": draws.normal((heads * d_v, h), layer, 2),
        "linear_attn.o_proj.weight": draws.normal((h, heads * d_v), layer, 3),
        "linear_attn.g_proj.weight": draws.normal((heads * d_v, h), layer, 4),
        "linear_attn.a_proj.weight": draws.normal((heads, h), layer, 5),
        "linear_attn.b_proj.weight": draws.normal((heads, h), layer, 6),
        "linear_attn.conv1d.weight": draws.normal((channels, 1, taps), layer, 7) * jnp.bfloat16(CONV_SCALE),
        "linear_attn.A_log": jnp.asarray(A_LOG_TABLE)[_bytes(draws, heads, layer, 8)],
        "linear_attn.dt_bias": jnp.asarray(DT_BIAS_TABLE)[_bytes(draws, heads, layer, 9)],
        "linear_attn.o_norm.weight": draws.const((d_v,), 1.0),
    })
    return tensors


def block_params(hf: dict, t: dict, kind: str) -> dict:
    """petals_tpu/models/olmo_hybrid/block.py ``hf_to_block_params``."""
    params = {
        "ln1": t["post_attention_layernorm.weight"], "ln2": t["post_feedforward_layernorm.weight"],
        "wg": t["mlp.gate_proj.weight"].T, "wu": t["mlp.up_proj.weight"].T, "wd": t["mlp.down_proj.weight"].T,
    }
    if kind == "full_attention":
        p = "self_attn."
        return {**params, "wq": t[p + "q_proj.weight"].T, "wk": t[p + "k_proj.weight"].T, "wv": t[p + "v_proj.weight"].T,
                "wo": t[p + "o_proj.weight"].T, "q_norm": t[p + "q_norm.weight"], "k_norm": t[p + "k_norm.weight"]}
    p = "linear_attn."
    return {**params, "wq": t[p + "q_proj.weight"].T, "wk": t[p + "k_proj.weight"].T, "wv": t[p + "v_proj.weight"].T,
            "wz": t[p + "g_proj.weight"].T, "wa": t[p + "a_proj.weight"].T, "wb": t[p + "b_proj.weight"].T,
            "wo": t[p + "o_proj.weight"].T, "conv": t[p + "conv1d.weight"][:, 0, :].T,
            "a_log": t[p + "A_log"], "dt_bias": t[p + "dt_bias"], "o_norm": t[p + "o_norm.weight"]}


def span_tree(hf: dict, runs: list) -> tuple:
    """``Server._load_span_params`` for a span of more than one kind: one
    stacked tree per run of consecutive blocks of one kind, in order."""
    return tuple(tree for _, tree in runs)
