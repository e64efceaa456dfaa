"""Jamba block (``jamba``; transformers 4.57.6 ``models/jamba/modeling_jamba.py``),
by kind of layer: ``attention`` where ``i % attn_layer_period ==
attn_layer_offset``, else ``mamba`` (``configuration_jamba.py:215-220``).

Both kinds, pre-norm with plain weights (``:948-973``, ``:1031-1053``):
``a = x + mixer(rms(x, input_layernorm)); y = a + W_down(silu(n W_gate) * (n
W_up))`` with ``n = rms(a, pre_ff_layernorm)``, ``rms(x, w) = x / sqrt(mean(x^2)
+ eps) * w`` (``:159-173``). ``num_experts`` is 1: every feed-forward is dense.

``mamba`` (``JambaMambaMixer.slow_forward``, ``:725-806``), d_inner = mamba_expand
x hidden_size channels, a state of d_state a channel, from a zero state, one
position at a time as a ``lax.scan``:

    [u_t ; z_t] = x_t W_in;  u_t = silu(sum_j w_j * u_(t-3+j) + conv_bias), zeros before the sequence
    [dt_t ; B_t ; C_t] = u_t W_x, each RMS-normed with its own weight;  dt_t = softplus(dt_t W_dt + dt_bias)
    S_t[c, n] = exp(dt_t[c] A[c, n]) S_(t-1)[c, n] + dt_t[c] B_t[n] u_t[c];   A = -exp(A_log)
    y_t[c] = sum_n S_t[c, n] C_t[n] + D[c] u_t[c];   out_t = (y_t * silu(z_t)) W_out

``attention`` (``:274-364``): ``num_attention_heads`` heads of ``hidden_size /
num_attention_heads`` over ``num_key_value_heads`` kv heads, no bias, causal
softmax attention, NO rotary embedding and no other position signal.

Nothing routes, so every position's margin is infinite and there is no
``TIE_MARGIN``. ``layer_params`` gives a mamba layer ``q_heads`` and
``kv_heads`` 0: it caches no keys and values, and perf/costs.py has no term for
a state, so the floor of a step leaves out the state's read and write (2 x
0.33 MB a lane a layer; 0.14 GB of about 5.9 GB a decode step at 8 lanes and 26
such layers) and ``step_roofline_share`` reads about 2% LOW in a cell of this
family, never high (perf/ssm.py has the state's bytes)."""

import jax
import jax.numpy as jnp

# Measured through the 28 layers of jamba2-3b-span28 on the v5e, bf16 weights, activations and pages, the state
# float32, against float32 (perf/prove_correct.py and the check of a traced run, PR 52, my chip runs, call 1: 9
# seeds x 105 rows): per-seed median row 2.90e-2..3.21e-2 (prefill 3.00e-2..3.21e-2, decode 2.90e-2..3.09e-2),
# worst row of all 945 4.65e-2, decode rows as prefill rows, the same session sent twice the same bytes in every
# seed; 1.1e-3 a layer in the median, half the other families' figure: 26 of the 28 mixers are element-wise
# float32 work between two matrix products and only the two attention layers have a softmax. The span's limits
# as the other families': twice the worst median (6.4e-2) and 2.5 times the worst row (0.116), stated over its
# 28 layers. The proof runs' checks on twelve more seeds (calls A-C from the final tree's export) read medians
# 2.87e-2..3.23e-2 and a worst row of 4.89e-2; perf/prove_chunks.py's rows (1,536 + 32 positions over three mixed
# steps, 2 seeds) medians 3.01e-2..3.15e-2, worst 4.77e-2, and its control (the state dropped at position 512)
# 0.97-1.05 in the rows after the boundary, 8.3 times the row bound.
#
# One precision lower comes out not correct: the reference itself with float8 (e4m3) weights and layer inputs
# (benchmarks/prove_scan_matters.py, the published widths, the check's 144 positions) is 0.376-0.402 off in the
# median row (5.9 times the bound, 12 times the bf16 reading) and 0.49-0.54 in the worst, every row outside the
# row bound. So does the reference with the scan's own term left out of every mamba layer (``DROP_STATE_TERM``: y =
# D u): 0.388-0.413 in the median, 0.63-0.73 in the worst, so the tolerance cannot hide the scan.
ROW_BOUND_PER_LAYER = 0.116 / 28
MEDIAN_BOUND_PER_LAYER = 6.4e-2 / 28

MAMBA, ATTENTION = "mamba", "attention"
DROP_STATE_TERM = False  # a control, never set in a run that counts: y = D u, the scan's own term left out (PERF.md, PR 52)


def layer_kinds(hf: dict) -> list:
    period, offset = hf["attn_layer_period"], hf["attn_layer_offset"]
    return [ATTENTION if i % period == offset else MAMBA for i in range(hf["num_hidden_layers"])]


def _dims(hf: dict) -> tuple:
    """(d_inner, d_state, conv taps, dt rank) of the state-space mixer."""
    return hf["mamba_expand"] * hf["hidden_size"], hf["mamba_d_state"], hf["mamba_d_conv"], hf["mamba_dt_rank"]


def layer_params(hf: dict, kind: str) -> dict:
    """Matrix parameters of one layer of ``kind`` (perf/costs.py says what the
    keys mean). A mamba layer's mixer is all under ``attn``; it has no cached
    keys and values, hence no heads for costs.py to count them by."""
    h, mlp = hf["hidden_size"], 3 * hf["hidden_size"] * hf["intermediate_size"]
    hq, hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    d = h // hq
    if kind == ATTENTION:
        return {"attn": h * (hq + 2 * hkv) * d + hq * d * h, "dense": mlp, "expert": 0, "experts": 0, "top_k": 0,
                "hidden": h, "q_heads": hq, "kv_heads": hkv, "head_dim": d}
    inner, n, taps, rank = _dims(hf)
    mixer = h * 2 * inner + inner * (rank + 2 * n) + rank * inner + inner * h + inner * (taps + n)
    return {"attn": mixer, "dense": mlp, "expert": 0, "experts": 0, "top_k": 0,
            "hidden": h, "q_heads": 0, "kv_heads": 0, "head_dim": d}


def _rms_norm(x, weight, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * weight


def _attention(hf: dict, w: dict, x):
    h, hq, hkv = hf["hidden_size"], hf["num_attention_heads"], hf["num_key_value_heads"]
    d, seq = h // hq, x.shape[0]
    assert hf.get("sliding_window") is None
    q = (x @ w["self_attn.q_proj.weight"].T).reshape(seq, hkv, hq // hkv, d)
    k = (x @ w["self_attn.k_proj.weight"].T).reshape(seq, hkv, d)
    v = (x @ w["self_attn.v_proj.weight"].T).reshape(seq, hkv, d)
    scores = jnp.einsum("qhgd,khd->hgqk", q, k) / jnp.sqrt(jnp.float32(d))
    scores = jnp.where(jnp.tril(jnp.ones((seq, seq), bool))[None, None], scores, -jnp.inf)
    attn = jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(scores, axis=-1), v)
    return attn.reshape(seq, hq * d) @ w["self_attn.o_proj.weight"].T


def _mamba(hf: dict, w: dict, x):
    inner, n, taps, rank = _dims(hf)
    eps, seq, p = hf["rms_norm_eps"], x.shape[0], "mamba."
    assert hf.get("mamba_conv_bias", True) and not hf.get("mamba_proj_bias", False)
    u, z = jnp.split(x @ w[p + "in_proj.weight"].T, 2, axis=-1)
    fed = jnp.pad(u, ((taps - 1, 0), (0, 0)))  # zeros before the sequence
    kernel = w[p + "conv1d.weight"][:, 0, :]  # [channels, taps]
    u = jax.nn.silu(sum(kernel[:, j] * fed[j : j + seq] for j in range(taps)) + w[p + "conv1d.bias"])
    dt, b, c = jnp.split(u @ w[p + "x_proj.weight"].T, (rank, rank + n), axis=-1)
    dt, b, c = (_rms_norm(t, w[p + f"{name}_layernorm.weight"], eps) for t, name in ((dt, "dt"), (b, "b"), (c, "c")))
    dt = jax.nn.softplus(dt @ w[p + "dt_proj.weight"].T + w[p + "dt_proj.bias"])
    a = -jnp.exp(w[p + "A_log"])  # [channels, d_state]

    def position(state, row):  # state [channels, d_state]
        u_t, dt_t, b_t, c_t = row
        state = jnp.exp(dt_t[:, None] * a) * state + (dt_t * u_t)[:, None] * b_t[None, :]
        return state, state @ c_t

    _, y = jax.lax.scan(position, jnp.zeros((inner, n), jnp.float32), (u, dt, b, c))
    y = (0.0 if DROP_STATE_TERM else y) + w[p + "D"] * u
    return (y * jax.nn.silu(z)) @ w[p + "out_proj.weight"].T


def block(hf: dict, w: dict, x, kind: str):
    assert hf.get("hidden_act", "silu") == "silu" and hf.get("num_experts", 1) == 1
    eps = hf["rms_norm_eps"]
    n = _rms_norm(x, w["input_layernorm.weight"], eps)
    x = x + (_mamba(hf, w, n) if kind == MAMBA else _attention(hf, w, n))
    n = _rms_norm(x, w["pre_ff_layernorm.weight"], eps)
    p = "feed_forward."
    mlp = (jax.nn.silu(n @ w[p + "gate_proj.weight"].T) * (n @ w[p + "up_proj.weight"].T)) @ w[p + "down_proj.weight"].T
    return x + mlp, jnp.full(x.shape[0], jnp.inf)
