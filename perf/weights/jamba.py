"""Jamba layer tensors under their HF names by kind of layer, in the served
block's layout, and a span of both kinds as the server holds it.

``A_log``, ``D``, ``dt_proj.bias`` and the conv's taps are not drawn like a
matrix (the configuration's ``assumed.weights``): ``A_log = log(1..d_state)``
in every channel and ``D = 1``, the model's own initialisation
(transformers 4.57.6 ``models/jamba/modeling_jamba.py:600-604``);
``dt_proj.bias`` the inverse softplus of a step log-uniform in 0.001-0.1 from
the host-made table perf/weights/olmo_hybrid.py uses (a transcendental
function rounds differently on the CPU and on the chip, and the two sides'
bits must agree), so that the decays run from about 0.2 (dt 0.1, A -16) to
0.999 a position and the state carries weight over the check's 128 positions;
the conv's taps as wide as there, its bias 0."""

import jax.numpy as jnp
import numpy as np

from perf.weights.olmo_hybrid import CONV_SCALE, DT_BIAS_TABLE, _bytes


def layer_tensors(hf: dict, layer, draws, kind: str) -> dict:
    h, m = hf["hidden_size"], hf["intermediate_size"]
    tensors = {
        "input_layernorm.weight": draws.const((h,), 1.0),
        "pre_ff_layernorm.weight": draws.const((h,), 1.0),
        "feed_forward.gate_proj.weight": draws.normal((m, h), layer, 10),
        "feed_forward.down_proj.weight": draws.normal((h, m), layer, 11),
        "feed_forward.up_proj.weight": draws.normal((m, h), layer, 12),
    }
    if kind == "attention":
        hq, hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
        d = h // hq
        tensors.update({
            "self_attn.q_proj.weight": draws.normal((hq * d, h), layer, 0),
            "self_attn.k_proj.weight": draws.normal((hkv * d, h), layer, 1),
            "self_attn.v_proj.weight": draws.normal((hkv * d, h), layer, 2),
            "self_attn.o_proj.weight": draws.normal((h, hq * d), layer, 3),
        })
        return tensors
    inner, n, taps, rank = hf["mamba_expand"] * h, hf["mamba_d_state"], hf["mamba_d_conv"], hf["mamba_dt_rank"]
    a_log = np.log(np.arange(1, n + 1, dtype=np.float32))  # made on the host: the chip's log rounds otherwise
    tensors.update({
        "mamba.in_proj.weight": draws.normal((2 * inner, h), layer, 0),
        "mamba.x_proj.weight": draws.normal((rank + 2 * n, inner), layer, 1),
        "mamba.dt_proj.weight": draws.normal((inner, rank), layer, 2),
        "mamba.out_proj.weight": draws.normal((h, inner), layer, 3),
        "mamba.conv1d.weight": draws.normal((inner, 1, taps), layer, 4) * jnp.bfloat16(CONV_SCALE),
        "mamba.conv1d.bias": draws.const((inner,), 0.0),
        "mamba.dt_proj.bias": jnp.asarray(DT_BIAS_TABLE)[_bytes(draws, inner, layer, 5)],
        "mamba.A_log": jnp.broadcast_to(jnp.asarray(a_log, jnp.bfloat16), (inner, n)),
        "mamba.D": draws.const((inner,), 1.0),
        "mamba.dt_layernorm.weight": draws.const((rank,), 1.0),
        "mamba.b_layernorm.weight": draws.const((n,), 1.0),
        "mamba.c_layernorm.weight": draws.const((n,), 1.0),
    })
    return tensors


def block_params(hf: dict, t: dict, kind: str) -> dict:
    """petals_tpu/models/jamba/block.py ``hf_to_block_params``."""
    p = "feed_forward."
    params = {
        "ln1": t["input_layernorm.weight"], "ln2": t["pre_ff_layernorm.weight"],
        "wg": t[p + "gate_proj.weight"].T, "wu": t[p + "up_proj.weight"].T, "wd": t[p + "down_proj.weight"].T,
    }
    if kind == "attention":
        p = "self_attn."
        return {**params, "wq": t[p + "q_proj.weight"].T, "wk": t[p + "k_proj.weight"].T, "wv": t[p + "v_proj.weight"].T,
                "wo": t[p + "o_proj.weight"].T}
    p = "mamba."
    return {**params, "w_in": t[p + "in_proj.weight"].T, "w_x": t[p + "x_proj.weight"].T, "w_dt": t[p + "dt_proj.weight"].T,
            "w_out": t[p + "out_proj.weight"].T, "a_log": t[p + "A_log"].T, "conv": t[p + "conv1d.weight"][:, 0, :].T,
            "conv_b": t[p + "conv1d.bias"], "dt_b": t[p + "dt_proj.bias"], "d": t[p + "D"],
            "dt_norm": t[p + "dt_layernorm.weight"], "b_norm": t[p + "b_layernorm.weight"], "c_norm": t[p + "c_layernorm.weight"]}


def span_tree(hf: dict, runs: list) -> tuple:
    """``Server._load_span_params`` for a span of more than one kind: one
    stacked tree per run of consecutive blocks of one kind, in order."""
    return tuple(tree for _, tree in runs)
