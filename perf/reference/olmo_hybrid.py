"""Olmo-Hybrid block (``olmo_hybrid``), by kind of layer: ``linear_attention``
or ``full_attention``, from ``layer_types``.

Both kinds: ``h = x + n1(mixer(x)); y = h + n2(mlp(h))``, the norms on each
sublayer's OUTPUT (OLMo 2/3's placement), ``mlp(h) = W_down(silu(h W_gate) *
(h W_up))``.

``full_attention``: 30 heads of 128 and as many kv heads, no bias, an RMS norm
over the whole projected q and the whole projected k, causal softmax attention,
NO rotary embedding (``rope_theta`` null as published).

``linear_attention``, the gated delta rule, H = 30 heads of d_k = 96, d_v = 192,
one position at a time as a ``lax.scan``:

    u_t = [x_t W_q ; x_t W_k ; x_t W_v];  c_t = silu(sum_j w_j * u_(t-3+j)), zeros before the sequence
    q_t, k_t, v_t = c_t split by head;  q_t = l2norm(q_t) / sqrt(d_k);  k_t = l2norm(k_t)   (eps 1e-6)
    beta_t = 2 sigmoid(x_t W_b);  alpha_t = exp(-exp(A_log) softplus(x_t W_a + dt_bias))
    S' = alpha_t S_(t-1);  S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T;  o_t = S_t^T q_t     (S_0 = 0, d_k x d_v a head)
    y_t = W_o [ rmsnorm over d_v (o_t) * silu(x_t W_g) ]

Sources of what ``config.json`` does not settle are in the configuration's
``assumed``. Nothing routes, so every position's margin is infinite and there
is no ``TIE_MARGIN``.

``layer_params`` gives a linear layer ``q_heads`` and ``kv_heads`` 0: it caches
no keys and values, and perf/costs.py has no term for a state, so the floor of
a step leaves out the state's read and write (2 x 2.21 MB a lane a layer; 0.43
GB of about 8.1 GB a decode step at 8 lanes and 12 such layers) and
``step_roofline_share`` reads about 5% LOW in a cell of this family, never
high. Teaching costs.py a state term is a ``benchmark`` issue's (PERF.md
section 7)."""

import jax
import jax.numpy as jnp

# Measured through the 16 layers of olmo-hybrid-7b-span16 on the v5e, bf16 weights, activations and pages, the
# state float32, against float32 (perf/prove_correct.py, PR 35, my chip runs: 16 seeds x 105 rows, the tree
# as committed): per-seed median row 3.22e-2..3.65e-2 (prefill 3.34e-2..3.65e-2, decode 3.22e-2..3.65e-2),
# worst row of all 1680 5.18e-2, decode rows as prefill rows, the same session sent twice the same bytes in
# every seed. perf/prove_chunks.py's rows (a prompt of 1,536 over three mixed steps, then 32 decode steps, alone
# and beside three decoding sessions, 2 seeds): median 3.0e-2..3.2e-2, worst 5.31e-2. The span's limits as
# the other families': twice the worst median (7.3e-2) and 2.5 times the worst row (0.13), stated over its
# 16 layers. 2.2e-3 a layer in the median, the other families' figure, though the residual stream is never
# normalised on its way IN to a sublayer: the norms sit on the outputs, and every sublayer adds a unit-RMS
# vector that carries its own bf16 error at full scale. (At a hidden size of 128 on the CPU the same path
# reads 0.4-0.5% a layer, so tests/perf's toy server computes in float32.)
#
# One precision lower comes out not correct: the reference itself with float8 (e4m3) weights and layer
# inputs (2 seeds, CPU, the published widths) is 0.467-0.490 off in the median row (6.4 times the bound, 13
# times the bf16 reading) and 0.68-0.81 in the worst; 27-38 of a kind's 32-40 rows outside the row bound.
# A state dropped at a chunk's boundary (perf/prove_chunks.py's control) lands 1.34-1.36 off in the rows
# that follow the boundary, 10 times the row bound, and the median over a thousand positions later is still
# 4.8e-2..7.0e-2.
ROW_BOUND_PER_LAYER = 0.13 / 16
MEDIAN_BOUND_PER_LAYER = 7.3e-2 / 16

LINEAR, FULL = "linear_attention", "full_attention"


def layer_kinds(hf: dict) -> list:
    return list(hf["layer_types"])


def layer_params(hf: dict, kind: str) -> dict:
    """Matrix parameters of one layer of ``kind`` (perf/costs.py says what the
    keys mean). A linear layer's mixer is all under ``attn``; it has no
    cached keys and values, hence no heads for costs.py to count them by."""
    h, mlp = hf["hidden_size"], 3 * hf["hidden_size"] * hf["intermediate_size"]
    if kind == FULL:
        hq, hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
        d = hf.get("head_dim") or h // hq
        return {"attn": h * (hq + 2 * hkv) * d + hq * d * h, "dense": mlp, "expert": 0, "experts": 0, "top_k": 0,
                "hidden": h, "q_heads": hq, "kv_heads": hkv, "head_dim": d}
    heads, d_k, d_v = hf["linear_num_value_heads"], hf["linear_key_head_dim"], hf["linear_value_head_dim"]
    channels = heads * (2 * d_k + d_v)
    mixer = h * channels + 2 * h * heads * d_v + 2 * h * heads + hf["linear_conv_kernel_dim"] * channels
    return {"attn": mixer, "dense": mlp, "expert": 0, "experts": 0, "top_k": 0,
            "hidden": h, "q_heads": 0, "kv_heads": 0, "head_dim": d_v}


def _rms_norm(x, weight, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * weight


def _l2_norm(x, eps=1e-6):
    return x / jnp.sqrt((x * x).sum(-1, keepdims=True) + eps)


def _full_attention(hf: dict, w: dict, x):
    h, hq, hkv, eps = hf["hidden_size"], hf["num_attention_heads"], hf["num_key_value_heads"], hf["rms_norm_eps"]
    d, seq = hf.get("head_dim") or h // hq, x.shape[0]
    assert hf["rope_parameters"]["rope_theta"] is None and not hf.get("attention_bias")
    q = _rms_norm(x @ w["self_attn.q_proj.weight"].T, w["self_attn.q_norm.weight"], eps).reshape(seq, hkv, hq // hkv, d)
    k = _rms_norm(x @ w["self_attn.k_proj.weight"].T, w["self_attn.k_norm.weight"], eps).reshape(seq, hkv, d)
    v = (x @ w["self_attn.v_proj.weight"].T).reshape(seq, hkv, d)
    scores = jnp.einsum("qhgd,khd->hgqk", q, k) / jnp.sqrt(jnp.float32(d))
    scores = jnp.where(jnp.tril(jnp.ones((seq, seq), bool))[None, None], scores, -jnp.inf)
    attn = jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(scores, axis=-1), v)
    return attn.reshape(seq, hq * d) @ w["self_attn.o_proj.weight"].T


def _linear_attention(hf: dict, w: dict, x):
    heads, d_k, d_v = hf["linear_num_value_heads"], hf["linear_key_head_dim"], hf["linear_value_head_dim"]
    taps, seq = hf["linear_conv_kernel_dim"], x.shape[0]
    assert hf["linear_num_key_heads"] == heads
    p = "linear_attn."
    u = jnp.concatenate([x @ w[p + f"{name}_proj.weight"].T for name in ("q", "k", "v")], axis=-1)
    fed = jnp.pad(u, ((taps - 1, 0), (0, 0)))  # zeros before the sequence
    kernel = w[p + "conv1d.weight"][:, 0, :]  # [channels, taps]
    c = jax.nn.silu(sum(kernel[:, j] * fed[j : j + seq] for j in range(taps)))
    q, k, v = jnp.split(c, (heads * d_k, 2 * heads * d_k), axis=-1)
    q = _l2_norm(q.reshape(seq, heads, d_k)) / jnp.sqrt(jnp.float32(d_k))
    k = _l2_norm(k.reshape(seq, heads, d_k))
    v = v.reshape(seq, heads, d_v)
    beta = jax.nn.sigmoid(x @ w[p + "b_proj.weight"].T) * (2.0 if hf["linear_allow_neg_eigval"] else 1.0)
    alpha = jnp.exp(-jnp.exp(w[p + "A_log"]) * jax.nn.softplus(x @ w[p + "a_proj.weight"].T + w[p + "dt_bias"]))

    def position(state, row):  # state [heads, d_k, d_v]
        q_t, k_t, v_t, alpha_t, beta_t = row
        state = state * alpha_t[:, None, None]
        delta = (v_t - jnp.einsum("hkv,hk->hv", state, k_t)) * beta_t[:, None]
        state = state + k_t[:, :, None] * delta[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, o = jax.lax.scan(position, jnp.zeros((heads, d_k, d_v), jnp.float32), (q, k, v, alpha, beta))
    gate = jax.nn.silu(x @ w[p + "g_proj.weight"].T).reshape(seq, heads, d_v)
    o = _rms_norm(o, w[p + "o_norm.weight"], hf["rms_norm_eps"]) * gate
    return o.reshape(seq, heads * d_v) @ w[p + "o_proj.weight"].T


def block(hf: dict, w: dict, x, kind: str):
    assert hf.get("hidden_act", "silu") == "silu"
    eps = hf["rms_norm_eps"]
    mixed = _linear_attention(hf, w, x) if kind == LINEAR else _full_attention(hf, w, x)
    x = x + _rms_norm(mixed, w["post_attention_layernorm.weight"], eps)
    mlp = (jax.nn.silu(x @ w["mlp.gate_proj.weight"].T) * (x @ w["mlp.up_proj.weight"].T)) @ w["mlp.down_proj.weight"].T
    return x + _rms_norm(mlp, w["post_feedforward_layernorm.weight"], eps), jnp.full(x.shape[0], jnp.inf)
