"""``xing4_0`` block (Xing4.0-29B-A4B publishes under it), by kind of layer
(``dense`` | ``sparse``), over the residual STREAM: ``x`` is ``[seq, hc_mult x
hidden_size]``, a position's ``n = hc_mult`` rows of ``C = hidden_size`` laid
flat, row ``k`` the columns ``[kC, (k + 1)C)``. The EXPANDED attention only.

Each of a layer's two sub-layers ``F`` (attention, then feed-forward, each
under its own pre-norm as in ``deepseek_v3``) is wrapped by manifold-
constrained hyper-connections (Xie et al., arXiv 2512.24880, over Zhu et al.,
arXiv 2409.19606), with coefficients of its own (``attn_hc.*``, ``mlp_hc.*``).
With ``X`` [n, C] one position's stream:

    x  = rms(vec(X))                                   over all n*C values (eps: rms_norm_eps)
    Hp = sigmoid(a_pre  * (x @ phi_pre)  + b_pre)      [n]
    Hq = 2 * sigmoid(a_post * (x @ phi_post) + b_post) [n]
    M  = exp(clip(a_res * mat(x @ phi_res) + b_res, mhc_h_res_clamp_min, mhc_h_res_clamp_max))   [n, n]
    hc_sinkhorn_iters times:  M = M / (M.sum(-1) + hc_eps);  M = M / (M.sum(-2) + hc_eps)
    u  = Hp @ X                                        [C]: what the sub-layer reads
    X' = M @ X + outer(Hq, F(norm(u)))                 [n, C]: what goes on

The stream's entry (the embedding repeated ``n`` times) and exit (the sum of
its rows before the final norm) are the client's and no part of a block.

Attention is transformers' ``DeepseekV3Attention`` with ``q_lora_rank`` set
and yarn: ``q = q_b_proj(rms(q_a_proj(a), q_a_layernorm))`` as heads of
``[q_nope | q_pe]``; ``kv_a_proj_with_mqa(a) = [c | k_pe]``, ``c`` under
``kv_a_layernorm``; ``kv_b_proj(c)`` as heads of ``[k_nope | v]``; the rotary
over ``q_pe`` (a head) and ``k_pe`` (ONE head for all) in the published form
(pairs ``(2j, 2j + 1)`` de-interleaved to halves, then rotate-half) at yarn's
frequencies: each ``1 / theta^(2i/d)`` blended with that over ``factor`` by
the linear ramp between the correction dims of ``beta_fast`` and ``beta_slow``
over ``original_max_position_embeddings``, cos and sin times ``mscale(factor,
mscale) / mscale(factor, mscale_all_dim)``; ``score = (q_nope . k_nope + q_pe .
k_pe) * mscale(factor, mscale_all_dim)^2 / sqrt(qk_nope_head_dim +
qk_rope_head_dim)``, causal softmax, ``o_proj``. The feed-forward is
``DeepseekV3MLP`` (a SwiGLU of ``intermediate_size``; the first
``first_k_dense_replace`` layers) or ``DeepseekV3MoE``: ``s = sigmoid(r W_g)``
over ``n_routed_experts``, the top k of ``s + e_score_correction_bias`` chosen,
weighed ``routed_scaling_factor * s_i / (sum over the chosen of s + 1e-20)``,
plus ONE shared SwiGLU of ``n_shared_experts x moe_intermediate_size``.

Sources of what ``config.json`` and the two papers do not settle are in the
configuration's ``assumed``."""

import math

import jax
import jax.numpy as jnp

LATENT_NORM_EPS = 1e-6  # q_a_layernorm, kv_a_layernorm: constructed without eps (modeling_deepseek_v3.py DeepseekV3Attention.__init__)

# Measured through the 8 layers (16 wraps) of xing4-29b-a4b-span8 on the v5e, bf16 weights, activations and cache against
# this float32 reference (perf/prove_correct.py, PR 59: two calls, 8 + 16 seeds x 105 rows, seeds 2147483659-857 and
# 2147484201-271; every row's error and margin are in chiprun_out/correct_xing4-29b-saturated.jsonl of each call; the first call
# ran under a margin of 0.0015 and its rows were judged again under 0.002 from that file, the second call and 13 cell runs under 0.002).
#
# Rows where no router flipped (1,946 of 2,520): median 1.20e-2..1.47e-2 a seed over the rows compared (1.5e-3..1.8e-3 a
# layer, the other families' figure: the stream's sixteen mixes add nothing a row shows), worst 2.2e-2, decode rows
# (absorbed) as prefill rows (expanded). The router is a discontinuity, and a larger one than deepseek_v3.py's: the top 4
# of 64 are renormalised and doubled, the wrap puts the expert layer's output on the stream times Hq (up to 2), and up to
# five expert layers follow, so where the served path picks the other expert at the boundary the row lands 0.06-0.67 off
# (574 rows, 23%: median 0.21, nine in ten under 0.37). 64 scores lie close: the margins of the flipped rows have a median
# of 0.0007, three in four are under 0.0015, nine in ten under 0.0024, the largest 0.0048 (a row moved by a flipped
# position it attends to). No margin separates them all and keeps a quarter of the rows (0.005 keeps 26% and under 15% of
# a kind in one seed), so the limits divide the work as deepseek_v3.py's do. TIE_MARGIN 0.002 leaves out the 45% of the
# rows where six flips in seven are (34% and more of a kind compared in every seed, mean 54%: the floor is 25%, 3-4 standard
# deviations below; at 0.0015 a seed had two positions outside in one kind, the most a family may allow, at 0.0025 a kind
# of one seed kept 28%). The ROW bound, 0.29, the most tests/perf allows a family ("a wrong kernel lands at 0.3..1": a lane
# reading another's pages, another row of the stream or a shifted rotary lands near 1), takes most kept flips in (85 kept
# of 1,375 compared rows; 6 over 0.29, never two in one kind of one seed; the worst 0.479); the two positions allowed
# outside are for that tail. The MEDIAN bound, twice the worst seed's median, is what holds the arithmetic: a flip moves
# a twentieth of the compared rows and no median.
#
# One precision lower comes out not correct: the reference itself with float8 (e4m3) weights and layer inputs (2 seeds,
# CPU, the published widths, the check's positions) is 0.87-0.95 off in the median row, 29-32 times the median bound (and
# every compared row is outside the row bound too); with bf16 weights and layer inputs 6.2e-3..7.3e-3 in the median,
# inside, no position outside.
ROW_BOUND_PER_LAYER = 0.29 / 8
MEDIAN_BOUND_PER_LAYER = 3.0e-2 / 8
TIE_MARGIN = 0.002
POSITIONS_ALLOWED_OUTSIDE = 2


def layer_kinds(hf: dict) -> list:
    dense = hf.get("first_k_dense_replace", 0)
    return ["dense" if i < dense else "sparse" for i in range(max(hf["num_hidden_layers"], dense))]


def _dims(hf: dict) -> tuple:
    return (hf["hidden_size"], hf["num_attention_heads"], hf["qk_nope_head_dim"], hf["qk_rope_head_dim"], hf["v_head_dim"],
            hf["kv_lora_rank"], hf["q_lora_rank"], hf["hc_mult"])


def layer_params(hf: dict, kind: str) -> dict:
    """Matrix parameters of one layer of ``kind`` (perf/costs.py says what the
    keys mean). ``hidden`` is what crosses the wire between two blocks, the
    stream, ``hc_mult x hidden_size`` wide: the harness sizes its inputs by it.
    The three ``phi`` of both wraps (2 x n*C x (2n + n*n)) run for every token
    and count as ``dense``. The latent row is stated as ``deepseek_v3``'s is:
    a position caches ``kv_lora_rank + qk_rope_head_dim`` values ONCE (576:
    1,152 B), stated as 2 kv heads of a quarter of it under the published 32
    query heads: 4 x 32 x 144 = 18,432 flops a (row, position) pair, 10% UNDER
    the expanded form's ``2 x heads x (qk_head_dim + v_head_dim)``, never over."""
    h, heads, dn, dr, dv, latent, rq, n = _dims(hf)
    row = latent + dr
    assert row % 4 == 0 and 4 * heads * (row // 4) <= 2 * heads * (dn + dr + dv)
    attn = h * rq + rq * heads * (dn + dr) + h * row + latent * heads * (dn + dv) + heads * dv * h
    wraps = 2 * n * h * (2 * n + n * n)
    out = {"attn": attn, "hidden": n * h, "q_heads": heads, "kv_heads": 2, "head_dim": row // 4}
    if kind == "dense":
        return {**out, "dense": wraps + 3 * h * hf["intermediate_size"], "expert": 0, "experts": 0, "top_k": 0}
    expert = 3 * h * hf["moe_intermediate_size"]
    routed = hf["n_routed_experts"]
    return {**out, "dense": wraps + h * routed + hf.get("n_shared_experts", 0) * expert, "expert": expert, "experts": routed,
            "top_k": hf["num_experts_per_tok"]}


def _rms_norm(x, weight, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * weight


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate.T) * (x @ up.T)) @ down.T


def mscale(factor: float, m: float = 1.0) -> float:
    """transformers' ``yarn_get_mscale``."""
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn(hf: dict) -> tuple:
    """``(inv_freq [d/2], what cos and sin are multiplied by, what the softmax's
    scale is multiplied by)`` of the rotary over ``qk_rope_head_dim``:
    transformers' ``_compute_yarn_parameters`` and ``DeepseekV3Attention``'s
    ``mscale * mscale``; ``rope_scaling`` null: the plain frequencies, 1, 1."""
    d, theta, s = hf["qk_rope_head_dim"], float(hf["rope_theta"]), hf.get("rope_scaling")
    freqs = theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if not s:
        return 1.0 / freqs, 1.0, 1.0
    assert s.get("rope_type", s.get("type")) == "yarn", s
    factor, window = float(s["factor"]), s.get("original_max_position_embeddings") or hf["max_position_embeddings"]
    if s.get("attention_factor") is not None:
        on_tables = float(s["attention_factor"])
    elif s.get("mscale") and s.get("mscale_all_dim"):
        on_tables = mscale(factor, s["mscale"]) / mscale(factor, s["mscale_all_dim"])
    else:
        on_tables = mscale(factor)
    on_softmax = mscale(factor, s["mscale_all_dim"]) ** 2 if s.get("mscale_all_dim") else 1.0

    def correction_dim(turns):  # the dim whose frequency turns ``turns`` times over the pretrained window
        return d * math.log(window / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low, high = correction_dim(s.get("beta_fast") or 32), correction_dim(s.get("beta_slow") or 1)
    if s.get("truncate", True):
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, d - 1)
    if low == high:
        high += 0.001
    kept = 1.0 - jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0)
    return (1.0 / (factor * freqs)) * (1.0 - kept) + (1.0 / freqs) * kept, on_tables, on_softmax


def rotary(x, inv_freq, on_tables: float, interleave: bool):
    """x [seq, heads, d] at positions 0..seq-1: transformers'
    ``apply_rotary_pos_emb_interleave`` (pairs (2j, 2j + 1) to halves, then
    rotate half) where ``interleave``, else rotate half as it lies."""
    seq, heads, d = x.shape
    if interleave:
        x = x.reshape(seq, heads, d // 2, 2).swapaxes(-1, -2).reshape(seq, heads, d)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)[:, None, :] * on_tables
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)[:, None, :] * on_tables
    return x * cos + jnp.concatenate([-x[..., d // 2 :], x[..., : d // 2]], axis=-1) * sin


def attention(hf: dict, w: dict, a):
    """``DeepseekV3Attention`` (low-rank query, yarn) over the normed rows ``a`` [seq, hidden], expanded."""
    h, heads, dn, dr, dv, latent, rq, _ = _dims(hf)
    seq, eps, interleave = a.shape[0], LATENT_NORM_EPS, hf.get("rope_interleave", True)
    inv_freq, on_tables, on_softmax = yarn(hf)
    q = _rms_norm(a @ w["self_attn.q_a_proj.weight"].T, w["self_attn.q_a_layernorm.weight"], eps) @ w["self_attn.q_b_proj.weight"].T
    q = q.reshape(seq, heads, dn + dr)
    row = a @ w["self_attn.kv_a_proj_with_mqa.weight"].T  # [seq, latent + dr]: one row for all heads
    c = _rms_norm(row[:, :latent], w["self_attn.kv_a_layernorm.weight"], eps)
    q_pe, k_pe = rotary(q[..., dn:], inv_freq, on_tables, interleave), rotary(row[:, None, latent:], inv_freq, on_tables, interleave)
    kv = (c @ w["self_attn.kv_b_proj.weight"].T).reshape(seq, heads, dn + dv)
    q = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe, (seq, heads, dr))], axis=-1)
    logits = jnp.einsum("qhd,khd->hqk", q, k) * (on_softmax / jnp.sqrt(jnp.float32(dn + dr)))
    mask = jnp.arange(seq)[None, :] <= jnp.arange(seq)[:, None]
    probs = jax.nn.softmax(jnp.where(mask[None], logits, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", probs, kv[..., dn:]).reshape(seq, heads * dv) @ w["self_attn.o_proj.weight"].T


def feed_forward(hf: dict, w: dict, r, kind: str):
    """``(DeepseekV3MLP | DeepseekV3MoE over the normed rows r [seq, hidden], margin [seq])``."""
    seq = r.shape[0]
    if kind == "dense":
        return _swiglu(r, w["mlp.gate_proj.weight"], w["mlp.up_proj.weight"], w["mlp.down_proj.weight"]), jnp.full(seq, jnp.inf)
    n, top_k = hf["n_routed_experts"], hf["num_experts_per_tok"]
    scores = jax.nn.sigmoid(r @ w["mlp.gate.weight"].T)  # [seq, n], float32 as the published router
    choice = scores + w["mlp.gate.e_score_correction_bias"]
    order = jnp.argsort(-choice, axis=-1)
    ranked = jnp.take_along_axis(choice, order, axis=-1)
    # the last expert kept against the first one dropped, as a share of the position's largest score
    margin = (ranked[:, top_k - 1] - ranked[:, top_k]) / scores.max(-1)
    top_i = order[:, :top_k]
    top_s = jnp.take_along_axis(scores, top_i, axis=-1)  # the bias chooses, it does not weigh
    if hf.get("norm_topk_prob", True):
        top_s = top_s / (top_s.sum(-1, keepdims=True) + 1e-20)
    weights = (jax.nn.one_hot(top_i, n) * (hf["routed_scaling_factor"] * top_s)[..., None]).sum(1)  # [seq, n]
    y = jnp.zeros_like(r)
    for e in range(n):
        p = f"mlp.experts.{e}."
        y = y + weights[:, e : e + 1] * _swiglu(r, w[p + "gate_proj.weight"], w[p + "up_proj.weight"], w[p + "down_proj.weight"])
    if hf.get("n_shared_experts"):
        p = "mlp.shared_experts."
        y = y + _swiglu(r, w[p + "gate_proj.weight"], w[p + "up_proj.weight"], w[p + "down_proj.weight"])
    return y, margin


def coefficients(hf: dict, w: dict, p: str, X):
    """One wrap's ``(Hp [seq, n], Hq [seq, n], M [seq, n, n])`` from the stream ``X`` [seq, n, C]; ``p`` is ``attn_hc.`` or ``mlp_hc.``."""
    seq, n, _ = X.shape
    flat = X.reshape(seq, -1)
    x = flat / jnp.sqrt((flat * flat).mean(-1, keepdims=True) + hf["rms_norm_eps"])
    Hp = jax.nn.sigmoid(w[p + "alpha_pre"] * (x @ w[p + "phi_pre.weight"].T) + w[p + "b_pre"])
    Hq = 2.0 * jax.nn.sigmoid(w[p + "alpha_post"] * (x @ w[p + "phi_post.weight"].T) + w[p + "b_post"])
    logits = w[p + "alpha_res"] * (x @ w[p + "phi_res.weight"].T).reshape(seq, n, n) + w[p + "b_res"]
    M = jnp.exp(jnp.clip(logits, hf["mhc_h_res_clamp_min"], hf["mhc_h_res_clamp_max"]))
    for _ in range(hf["hc_sinkhorn_iters"]):
        M = M / (M.sum(-1, keepdims=True) + hf["hc_eps"])
        M = M / (M.sum(-2, keepdims=True) + hf["hc_eps"])
    return Hp, Hq, M


def wrap(hf: dict, w: dict, p: str, X, sublayer):
    """``X' = M @ X + outer(Hq, F(u))`` with ``u = Hp @ X``; ``sublayer`` is ``u -> (F(norm(u)), margin)``."""
    Hp, Hq, M = coefficients(hf, w, p, X)
    out, margin = sublayer(jnp.einsum("sn,snc->sc", Hp, X))
    return jnp.einsum("smn,snc->smc", M, X) + Hq[:, :, None] * out[:, None, :], margin


def block(hf: dict, w: dict, x, kind: str):
    h, n, eps = hf["hidden_size"], hf["hc_mult"], hf["rms_norm_eps"]
    assert hf.get("n_group", 1) == 1 and hf.get("topk_group", 1) == 1 and hf.get("scoring_func", "sigmoid") == "sigmoid"
    assert hf.get("hidden_act", "silu") == "silu" and not hf.get("attention_bias") and hf.get("moe_layer_freq", 1) == 1
    assert hf.get("topk_method", "noaux_tc") == "noaux_tc" and n >= 2 and x.shape[-1] == n * h
    seq = x.shape[0]
    X = x.reshape(seq, n, h)
    X, _ = wrap(hf, w, "attn_hc.", X, lambda u: (attention(hf, w, _rms_norm(u, w["input_layernorm.weight"], eps)), None))
    X, margin = wrap(hf, w, "mlp_hc.", X, lambda u: feed_forward(hf, w, _rms_norm(u, w["post_attention_layernorm.weight"], eps), kind))
    return X.reshape(seq, n * h), margin
