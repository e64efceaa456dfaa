"""Qwen3-Next block (``qwen3_next``; transformers 4.57.6
``models/qwen3_next/modeling_qwen3_next.py``), by kind of layer,
``linear_attention`` or ``full_attention`` (layer ``i`` is full where
``(i + 1) % full_attention_interval == 0``), as one chip of a server computes
it.

Both kinds: ``h = x + mixer(n1(x)); y = h + moe(n2(h))``, the norms on each
sublayer's INPUT and zero-centred, ``rms(x) * (1 + w)``, as every norm here but
the delta rule's output norm.

``full_attention``: ``q_proj`` gives each of the 16 heads its query and its
gate side by side (``view [16, 2 x 256]``); an RMS norm over each HEAD of q and
of k (2 kv heads of 256); rotate-half rotary embeddings at ``rope_theta`` over
the FIRST ``partial_rotary_factor x head_dim`` (64) dims of a head, the rest
passed through; causal softmax attention, scale ``head_dim^-0.5``;
``o = W_o (attn * sigmoid(gate))``.

``linear_attention``, the gated delta rule, 16 key heads of d_k = 128 under
H = 32 value heads of d_v = 128, one position at a time as a ``lax.scan``:

    [q ; k ; v ; z] = in_proj_qkvz viewed [16, 128 + 128 + 2 x 128 + 2 x 128];  [b ; a] = in_proj_ba viewed [16, 2 + 2]
    u_t = [q ; k ; v] of all heads;  c_t = silu(sum_j w_j * u_(t-3+j)), zeros before the sequence
    q_t = l2norm(q_t) / sqrt(d_k);  k_t = l2norm(k_t)   (eps 1e-6), each key head repeated for its 2 value heads
    beta_t = sigmoid(b_t);  alpha_t = exp(-exp(A_log) softplus(a_t + dt_bias))
    S' = alpha_t S_(t-1);  S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T;  o_t = S_t^T q_t     (S_0 = 0, d_k x d_v a head)
    y_t = W_out [ rmsnorm over d_v (o_t) * w_norm * silu(z_t) ]     (this norm's weight is plain)

``moe``: ``p = softmax(n W_gate)`` over the ``expert_share.routed`` experts the
router routes over, the top k kept and divided by their sum; of the kept, the
``num_experts`` this chip HOLDS (from ``expert_share.first`` on) are run and the
others' parts left to the chips that hold them; plus ``sigmoid(n w_sg) *
SwiGLU(n)``, the shared expert, once. The multi-token-prediction layer is not
served and not here.

Sources of what ``config.json`` does not settle are in the configuration's
``assumed``.

``layer_params`` gives a linear layer ``q_heads`` and ``kv_heads`` 0, as
olmo_hybrid.py does and for the same reason: perf/costs.py has no term for a
state (2 x 2.10 MB a lane a layer)."""

import jax
import jax.numpy as jnp

from perf.reference import causal_gqa_attention, rotate_half_rotary

# Measured through the 8 layers of qwen3-next-80b-a3b-span8-ep4 on the v5e, bf16 weights, activations and pages, the
# state float32, against float32 (PR 48, my chip runs: perf/prove_correct.py on 8 seeds and the cell's own check in
# 11 runs on 11 more, 105 rows a seed, EVERY row compared): per-seed median row 1.82e-2..2.26e-2 (prefill
# 1.82e-2..2.26e-2, decode 1.82e-2..2.06e-2; 2.5e-3 a layer, the other families' figure), worst row of all 1,995
# 4.43e-2, decode rows as prefill rows, the same session sent twice the same bytes in every seed.
# perf/prove_chunks.py's rows (a prompt of 1,536 over three mixed steps, then 32 decode steps, alone and beside three
# decoding sessions, 2 seeds; 2,304 + 32 positions on a third): median 1.84e-2..1.98e-2, worst 3.31e-2. The span's
# limits as the other families': twice the worst median (4.6e-2) and 2.5 times the worst row (0.11), stated over
# its 8 layers.
#
# No TIE_MARGIN and no POSITIONS_ALLOWED_OUTSIDE, as OLMoE and for its reason, though the router flips all the time:
# with 512 experts the 10th and 11th logits are close, and 67-74% of a run's 144 positions have, at some layer, a
# boundary at which a held expert stands with a margin under 0.005 (``margin`` below; a tie margin of that size
# would leave out three rows in four). It does not matter: the kept weights are renormalised over TEN experts, the
# one that flips carries ~0.07 of them, a quarter of the assignments fall on this chip at all, and the gated shared
# expert and the residual carry the row: no row of 1,995 stood out (worst 4.43e-2 against a median of 2.0e-2).
#
# One precision lower comes out not correct: the reference itself with float8 (e4m3) weights and layer inputs (2
# seeds, CPU, the published widths) is 0.80-0.86 off in the median row (17 times the bound, 40 times the bf16 reading)
# and 0.89-1.05 in the worst, all 48 compared rows outside the row bound: weights of std 0.02 lie among e4m3's
# subnormals. A state dropped at a chunk's boundary (perf/prove_chunks.py's control) lands 1.34-1.45 off in the
# rows that follow the boundary, 12 times the row bound.
ROW_BOUND_PER_LAYER = 0.11 / 8
MEDIAN_BOUND_PER_LAYER = 4.6e-2 / 8
TIE_MARGIN = 0.0
POSITIONS_ALLOWED_OUTSIDE = 0

LINEAR, FULL = "linear_attention", "full_attention"


def layer_kinds(hf: dict) -> list:
    if hf.get("layer_types"):
        return list(hf["layer_types"])
    interval = hf.get("full_attention_interval", 4)
    return [LINEAR if (i + 1) % interval else FULL for i in range(hf["num_hidden_layers"])]


def held_share(hf: dict) -> tuple:
    """(held, routed, first): the experts this chip holds of those the router routes over."""
    share = hf.get("expert_share") or {}
    return hf["num_experts"], share.get("routed", hf["num_experts"]), share.get("first", 0)


def mixer_sizes(hf: dict) -> tuple:
    """(key heads, value heads, d_k, d_v, taps) of a linear layer."""
    return (hf["linear_num_key_heads"], hf["linear_num_value_heads"], hf["linear_key_head_dim"], hf["linear_value_head_dim"],
            hf["linear_conv_kernel_dim"])


def layer_params(hf: dict, kind: str) -> dict:
    """Matrix parameters of one layer of ``kind`` (perf/costs.py says what the
    keys mean). The router, the shared expert and its gate run for every
    token. A linear layer's mixer is all under ``attn``; it has no cached keys
    and values, hence no heads for costs.py to count them by."""
    h = hf["hidden_size"]
    held, routed, _ = held_share(hf)
    moe = {"dense": h * routed + 3 * h * hf["shared_expert_intermediate_size"] + h, "expert": 3 * h * hf["moe_intermediate_size"],
           "experts": held, "experts_routed": routed, "top_k": hf["num_experts_per_tok"], "hidden": h}
    if kind == FULL:
        hq, hkv, d = hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"]
        return {**moe, "attn": h * (2 * hq + 2 * hkv) * d + hq * d * h, "q_heads": hq, "kv_heads": hkv, "head_dim": d}
    hk, hv, d_k, d_v, taps = mixer_sizes(hf)
    channels = 2 * hk * d_k + hv * d_v
    mixer = h * (channels + hv * d_v) + 2 * h * hv + taps * channels + hv * d_v * h
    return {**moe, "attn": mixer, "q_heads": 0, "kv_heads": 0, "head_dim": d_v}


def _rms(x, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps)


def _norm(x, weight, eps):
    """Zero-centred: ``Qwen3NextRMSNorm``."""
    return _rms(x, eps) * (1.0 + weight)


def _l2_norm(x, eps=1e-6):
    return x / jnp.sqrt((x * x).sum(-1, keepdims=True) + eps)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate.T) * (x @ up.T)) @ down.T


def _full_attention(hf: dict, w: dict, x):
    hq, hkv, d, eps = hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"], hf["rms_norm_eps"]
    seq, rd, p = x.shape[0], int(hf["head_dim"] * hf["partial_rotary_factor"]), "self_attn."
    q_and_gate = (x @ w[p + "q_proj.weight"].T).reshape(seq, hq, 2 * d)
    q, gate = q_and_gate[..., :d], q_and_gate[..., d:].reshape(seq, hq * d)
    q = _norm(q, w[p + "q_norm.weight"], eps)
    k = _norm((x @ w[p + "k_proj.weight"].T).reshape(seq, hkv, d), w[p + "k_norm.weight"], eps)
    v = (x @ w[p + "v_proj.weight"].T).reshape(seq, hkv, d)
    q, k = (jnp.concatenate([rotate_half_rotary(t[..., :rd], hf["rope_theta"]), t[..., rd:]], axis=-1) for t in (q, k))
    attn = causal_gqa_attention(q.reshape(seq, hkv, hq // hkv, d), k, v).reshape(seq, hq * d)
    return (attn * jax.nn.sigmoid(gate)) @ w[p + "o_proj.weight"].T


def _linear_attention(hf: dict, w: dict, x):
    hk, hv, d_k, d_v, taps = mixer_sizes(hf)
    r, seq, p = hv // hk, x.shape[0], "linear_attn."
    qkvz = (x @ w[p + "in_proj_qkvz.weight"].T).reshape(seq, hk, 2 * d_k + 2 * r * d_v)
    q, k, v, z = jnp.split(qkvz, (d_k, 2 * d_k, 2 * d_k + r * d_v), axis=-1)
    ba = (x @ w[p + "in_proj_ba.weight"].T).reshape(seq, hk, 2 * r)
    b, a = ba[..., :r].reshape(seq, hv), ba[..., r:].reshape(seq, hv)
    u = jnp.concatenate([t.reshape(seq, -1) for t in (q, k, v)], axis=-1)  # all heads' q, then k, then v
    fed = jnp.pad(u, ((taps - 1, 0), (0, 0)))  # zeros before the sequence
    kernel = w[p + "conv1d.weight"][:, 0, :]  # [channels, taps]
    c = jax.nn.silu(sum(kernel[:, j] * fed[j : j + seq] for j in range(taps)))
    q, k, v = jnp.split(c, (hk * d_k, 2 * hk * d_k), axis=-1)
    q = jnp.repeat(_l2_norm(q.reshape(seq, hk, d_k)) / jnp.sqrt(jnp.float32(d_k)), r, axis=1)  # a key head: r consecutive value heads
    k = jnp.repeat(_l2_norm(k.reshape(seq, hk, d_k)), r, axis=1)
    v = v.reshape(seq, hv, d_v)
    beta = jax.nn.sigmoid(b)
    alpha = jnp.exp(-jnp.exp(w[p + "A_log"]) * jax.nn.softplus(a + w[p + "dt_bias"]))

    def position(state, row):  # state [heads, d_k, d_v]
        q_t, k_t, v_t, alpha_t, beta_t = row
        state = state * alpha_t[:, None, None]
        delta = (v_t - jnp.einsum("hkv,hk->hv", state, k_t)) * beta_t[:, None]
        state = state + k_t[:, :, None] * delta[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, o = jax.lax.scan(position, jnp.zeros((hv, d_k, d_v), jnp.float32), (q, k, v, alpha, beta))
    o = _rms(o, hf["rms_norm_eps"]) * w[p + "norm.weight"] * jax.nn.silu(z.reshape(seq, hv, d_v))
    return o.reshape(seq, hv * d_v) @ w[p + "out_proj.weight"].T


def _moe(hf: dict, w: dict, r):
    """(this chip's part of the expert layer over ``r`` [seq, h], each position's margin)."""
    held, routed, first = held_share(hf)
    top_k = hf["num_experts_per_tok"]
    logits = r @ w["mlp.gate.weight"].T  # [seq, routed]
    order = jnp.argsort(-logits, axis=-1)
    ranked = jnp.take_along_axis(logits, order, axis=-1)
    # the last expert kept against the first one dropped, as a share of the position's largest logit, where
    # one of the two is held here; elsewhere this chip's part does not depend on the boundary (exaone_moe.py)
    at_boundary = order[:, top_k - 1 : top_k + 1]
    held_there = ((at_boundary >= first) & (at_boundary < first + held)).any(-1)
    margin = jnp.where(held_there, (ranked[:, top_k - 1] - ranked[:, top_k]) / jnp.abs(logits).max(-1), jnp.inf)
    top_p, top_i = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    if hf.get("norm_topk_prob", True):
        top_p = top_p / top_p.sum(-1, keepdims=True)
    weights = (jax.nn.one_hot(top_i, routed) * top_p[..., None]).sum(1)[:, first : first + held]  # [seq, held]
    gate, up, down = (jnp.stack([w[f"mlp.experts.{e}.{proj}_proj.weight"] for e in range(first, first + held)])
                      for proj in ("gate", "up", "down"))  # the held share; the other chips' parts are left out
    inner = jax.nn.silu(jnp.einsum("sh,emh->esm", r, gate)) * jnp.einsum("sh,emh->esm", r, up)
    y = jnp.einsum("esm,ehm,se->sh", inner, down, weights)
    p = "mlp.shared_expert."
    shared = _swiglu(r, w[p + "gate_proj.weight"], w[p + "up_proj.weight"], w[p + "down_proj.weight"])
    return y + jax.nn.sigmoid(r @ w["mlp.shared_expert_gate.weight"].T) * shared, margin


def block(hf: dict, w: dict, x, kind: str):
    assert hf.get("hidden_act", "silu") == "silu" and not hf.get("rope_scaling") and not hf.get("attention_bias")
    assert not hf.get("mlp_only_layers") and hf.get("decoder_sparse_step", 1) == 1
    eps = hf["rms_norm_eps"]
    a = _norm(x, w["input_layernorm.weight"], eps)
    x = x + (_linear_attention(hf, w, a) if kind == LINEAR else _full_attention(hf, w, a))
    y, margin = _moe(hf, w, _norm(x, w["post_attention_layernorm.weight"], eps))
    return x + y, margin
