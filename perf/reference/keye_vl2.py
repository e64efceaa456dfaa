"""Keye-VL-2.0's language-model block (``KeyeVL2``), a whole sequence at once.

    h = rmsnorm(x);  q = h W_q (hq heads of d), k = h W_k, v = h W_v (hkv heads of d)
    q, k: an RMS norm a head over d, then the rotary (rotate-half over the whole head, theta of the config)
    the indexer (``sa_config``; DeepSeek-V3.2-Exp ``inference/model.py`` ``Indexer``, its q taken from h):
        qI = h W_iq (H heads of dI);  kI = layernorm(h W_ik) (ONE head of dI);  both under the rotary (whole head)
        w = h W_iw / sqrt(H dI)
        score[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])                    s <= t
        S_t = the min(topk, t + 1) positions of largest score (ties: the lower position, as ``lax.top_k``)
    attention: softmax over s in S_t of q_t . k_s / sqrt(d), hq / hkv q heads a kv head, then W_o; residual
    rmsnorm, a softmax router over all experts, the top k kept and renormalised, SwiGLU experts; residual

The selection is a mask built from the full [seq, seq] score matrix and
``jax.lax.top_k``. What ``config.json`` does not settle (the per-head norms,
the layer norm on kI, both rotaries, the scale) is in the configuration's
``assumed``, each with its source. Left out, as noted departures from V3.2:
the Hadamard rotation of qI and kI (orthogonal: the dot products are the
same) and float8 scores (a precision the configuration does not state).
``mrope_section`` splits the rotary's frequency pairs over three position ids;
a text position carries the same id in all three, which is the plain rotary:
image tokens' ids are not modelled.

``margin`` is the router's (the 8th against the 9th expert, as olmoe.py). The
selection gives none: among thousands of candidates the ``topk``-th and the
next score are near-tied at nearly every row, so the served bf16 scores choose
a slightly different set, and the bounds absorb it (measured below).

``layer_params``: ``attn`` holds the indexer's projections; ``window`` is
``topk``, so perf/costs.py counts ``topk`` positions of keys and values a lane
and nothing for the index keys' read or the scoring: ``step_roofline_share``
can read low in a cell of this family, never over 100."""

import jax
import jax.numpy as jnp

from perf.reference import rotate_half_rotary

# Measured through the 5 layers of keye-vl2-30b-a3b-span5 on the v5e, bf16 weights, activations, pages and index
# keys, against float32 (my chip runs, PR 39). perf/prove_correct.py, 12 seeds x 105 rows at 104-144 positions
# (every row's set is everything it sees): per-seed median row 8.6e-3..1.06e-2 (1.8e-3..2.1e-3 a layer, the other
# families' figure), decode rows as prefill rows, the same session sent twice the same bytes in every seed; the
# worst row of a seed 3.1e-2..6.65e-2, four to six times its median, where OLMoE's is two: the top 8 of 128 are
# renormalised, so a router near-tie that falls the other way in bf16 swaps an eighth of a row's expert mass
# (rows whose margin is 0.01 or more: worst 2.6e-2; 0.005 or more: 3.2e-2; under 0.002: up to 6.65e-2; the
# bf16-rounded reference on the CPU shows the same tail, 3.4e-2..5.2e-2). No tie margin: seven rows in eight have a
# margin under 0.01 at one of the five layers and two in three one under 0.005, so leaving them out would leave the
# check an eighth or a third of its rows; the row bound takes the flips in.
# perf/prove_long.py, where the selection leaves positions out (one session of 16,384 fresh rows in 8 mixed steps
# of 2,048 and 32 decode steps beside two decoding sessions; the last 32 prompt rows and every decode row): median
# 2.14e-2 (prefill) and 1.68e-2 (decode), worst 8.8e-2 (seed 3900000021), 1.85e-2 / 1.83e-2 and 8.5e-2 (seed
# 3900000053, from the committed files, under these limits: correct); at 4,096 + 32 positions 1.35e-2 / 1.42e-2
# and 6.0e-2 (seed 3900000002). The median doubles from 144 positions to 16k because the served scores are products
# of bf16 and the 2,048th and 2,049th of 16k scores are near-tied at nearly every row: from float32 inputs bf16
# operands choose 99.66-99.95% of the reference's first-layer set (2 to 7 of 2,048 positions swapped), more in the
# layers after it, whose inputs already differ; a swapped position is worth about 1/2048 of a row's attention.
# Limits over the 5 layers: median 3.0e-2 (2.8 times the check's worst median, 1.4 times the long proof's), row 0.13
# (twice the check's worst row of 1,365, 1.5 times the long proof's worst).
#
# One precision lower comes out not correct, by the median: the reference itself with float8 (e4m3) weights and
# layer inputs (2 seeds, CPU, the published widths, the check's 144 positions) is 0.101-0.102 off in the median row
# (3.4 times the bound) and 0.150-0.175 in the worst; with bf16 weights and inputs 5.8e-3..6.3e-3, inside.
# perf/prove_long.py's controls at 16,384 + 32 positions: a reference that rounds its SCORES to float8's three
# mantissa bits reads 3.67e-2 / 3.72e-2 in the median row (4.13e-2 / 4.05e-2 on the second seed), not correct by
# the median bound 1.2-1.4 times over (worst 6.8e-2, 8.6e-2); one that keeps the top 1,024 reads 0.105 / 0.109
# (0.106 / 0.111; 3.6 times over; worst 0.153).
ROW_BOUND_PER_LAYER = 0.13 / 5
MEDIAN_BOUND_PER_LAYER = 3.0e-2 / 5

INDEX_NORM_EPS = 1e-6


def _sa(hf: dict) -> tuple:
    sa = hf["sa_config"]
    assert sa["indexer_num_kv_heads"] == 1
    return sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"]


def layer_params(hf: dict) -> dict:
    """Matrix parameters of one layer (perf/costs.py says what the keys mean)."""
    h, hq, hkv, d = hf["hidden_size"], hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"]
    heads, d_idx, topk = _sa(hf)
    n = hf["num_experts"]
    indexer = h * heads * d_idx + h * d_idx + h * heads
    return {"attn": h * (hq + 2 * hkv) * d + hq * d * h + indexer, "dense": h * n, "expert": 3 * h * hf["moe_intermediate_size"],
            "experts": n, "top_k": hf["num_experts_per_tok"], "hidden": h, "q_heads": hq, "kv_heads": hkv, "head_dim": d,
            "window": topk}


def _rms_norm(x, weight, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * weight


def index_parts(hf: dict, w: dict, a) -> tuple:
    """The indexer's projections of the normed rows ``a``: qI [seq, H, dI],
    kI [seq, dI] and the heads' weights [seq, H], scaled."""
    heads, d_idx, _ = _sa(hf)
    seq, theta = a.shape[0], hf["rope_theta"]
    q_idx = rotate_half_rotary((a @ w["self_attn.indexer.wq.weight"].T).reshape(seq, heads, d_idx), theta)
    k_idx = a @ w["self_attn.indexer.wk.weight"].T
    mean = k_idx.mean(-1, keepdims=True)
    k_idx = (k_idx - mean) / jnp.sqrt(((k_idx - mean) ** 2).mean(-1, keepdims=True) + INDEX_NORM_EPS)
    k_idx = k_idx * w["self_attn.indexer.k_norm.weight"] + w["self_attn.indexer.k_norm.bias"]
    k_idx = rotate_half_rotary(k_idx[:, None, :], theta)[:, 0]
    weights = (a @ w["self_attn.indexer.weights_proj.weight"].T) / jnp.sqrt(jnp.float32(heads * d_idx))
    return q_idx, k_idx, weights


def index_scores(q_idx, k_idx, weights):
    """[rows, seq] scores of the rows ``q_idx``, ``weights`` against every position's ``k_idx``, unmasked."""
    return jnp.einsum("tj,tjs->ts", weights, jax.nn.relu(jnp.einsum("tjd,sd->tjs", q_idx, k_idx)))


def selection(scores, topk: int, first: int = 0):
    """bool [rows, seq]: the chosen positions of rows ``first + 0 ..`` of the
    sequence, each among the positions up to its own."""
    rows, seq = scores.shape
    causal = jnp.arange(seq)[None, :] <= (first + jnp.arange(rows))[:, None]
    if seq <= topk:
        return causal
    _, chosen = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), topk)
    picked = jnp.zeros((rows, seq), bool).at[jnp.arange(rows)[:, None], chosen].set(True)
    return picked & causal  # a row that sees under topk positions picked them all, and some it does not see


def block(hf: dict, w: dict, x, *, choose=selection, rows: int = 0):
    """``rows``: attend in blocks of that many rows (it must divide the
    sequence), each against the whole sequence under its rows of the mask, so
    that a long sequence fits: the same sums, the score matrix never whole.
    0: the whole [seq, seq] matrix at once."""
    h, hq, hkv, d = hf["hidden_size"], hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"]
    group, eps, theta = hq // hkv, hf["rms_norm_eps"], hf["rope_theta"]
    n_experts, top_k = hf["num_experts"], hf["num_experts_per_tok"]
    assert not hf.get("attention_bias") and hf.get("norm_topk_prob") and not hf.get("use_sliding_window")
    assert (hf.get("rope_scaling") or {}).get("rope_type", "default") == "default"
    seq = x.shape[0]
    a = _rms_norm(x, w["input_layernorm.weight"], eps)
    q = _rms_norm((a @ w["self_attn.q_proj.weight"].T).reshape(seq, hq, d), w["self_attn.q_norm.weight"], eps)
    k = _rms_norm((a @ w["self_attn.k_proj.weight"].T).reshape(seq, hkv, d), w["self_attn.k_norm.weight"], eps)
    q, k = rotate_half_rotary(q, theta).reshape(seq, hkv, group, d), rotate_half_rotary(k, theta)
    v = (a @ w["self_attn.v_proj.weight"].T).reshape(seq, hkv, d)
    q_idx, k_idx, w_idx = index_parts(hf, w, a)

    def attend(first, q_rows, q_idx_rows, w_idx_rows):
        mask = choose(index_scores(q_idx_rows, k_idx, w_idx_rows), _sa(hf)[2], first)
        logits = jnp.einsum("qhgd,khd->hgqk", q_rows, k) / jnp.sqrt(jnp.float32(d))
        probs = jax.nn.softmax(jnp.where(mask[None, None], logits, -jnp.inf), axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", probs, v).reshape(q_rows.shape[0], hq * d)

    if rows:
        assert seq % rows == 0, (seq, rows)
        cut = lambda t: t.reshape(seq // rows, rows, *t.shape[1:])
        attn = jax.lax.map(lambda b: attend(b[0], *b[1:]), (jnp.arange(0, seq, rows), cut(q), cut(q_idx), cut(w_idx))).reshape(seq, hq * d)
    else:
        attn = attend(0, q, q_idx, w_idx)
    x = x + attn @ w["self_attn.o_proj.weight"].T
    r = _rms_norm(x, w["post_attention_layernorm.weight"], eps)
    router = r @ w["mlp.gate.weight"].T
    ranked = jnp.sort(router, axis=-1)[:, ::-1]
    # the last expert kept against the first one dropped, as a share of the position's largest logit
    margin = (ranked[:, top_k - 1] - ranked[:, top_k]) / jnp.abs(router).max(-1)
    top_p, top_i = jax.lax.top_k(jax.nn.softmax(router, axis=-1), top_k)
    top_p = top_p / top_p.sum(-1, keepdims=True)  # norm_topk_prob
    weights = (jax.nn.one_hot(top_i, n_experts) * top_p[..., None]).sum(1)  # [seq, experts]
    y = jnp.zeros_like(x)
    for e in range(n_experts):
        p = f"mlp.experts.{e}."
        up = jax.nn.silu(r @ w[p + "gate_proj.weight"].T) * (r @ w[p + "up_proj.weight"].T)
        y = y + weights[:, e : e + 1] * (up @ w[p + "down_proj.weight"].T)
    return x + y, margin
