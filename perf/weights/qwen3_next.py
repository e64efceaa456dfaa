"""Qwen3-Next layer tensors under their HF names by kind of layer, in the served
block's layout, and a span of both kinds as the server holds it.

Zero-centred norm vectors (``rms(x) * (1 + w)``) are 0 and the delta rule's
plain output norm 1, so every norm scales by 1. ``A_log``, ``dt_bias`` and the
conv's taps are drawn as Olmo-Hybrid's are and for the same reasons
(perf/weights/olmo_hybrid.py: tables computed on the host, indexed by hashed
bits)."""

import jax.numpy as jnp

from perf.reference.qwen3_next import held_share, mixer_sizes
from perf.weights.olmo_hybrid import A_LOG_TABLE, CONV_SCALE, DT_BIAS_TABLE, _bytes


def layer_tensors(hf: dict, layer, draws, kind: str) -> dict:
    h, m, ms = hf["hidden_size"], hf["moe_intermediate_size"], hf["shared_expert_intermediate_size"]
    held, routed, first = held_share(hf)
    tensors = {
        "input_layernorm.weight": draws.const((h,), 0.0),
        "post_attention_layernorm.weight": draws.const((h,), 0.0),
        "mlp.gate.weight": draws.normal((routed, h), layer, 13),
        "mlp.shared_expert_gate.weight": draws.normal((1, h), layer, 14),
    }
    # one stream a projection and not one an expert (perf/weights/olmoe.py): the held experts are its
    # first runs of m x h elements, named by their place among the routed
    for salt, proj, shape, shared in ((10, "gate_proj", (m, h), (ms, h)), (11, "down_proj", (h, m), (h, ms)), (12, "up_proj", (m, h), (ms, h))):
        whole = draws.normal((held, *shape), layer, salt)
        for e in range(held):
            tensors[f"mlp.experts.{first + e}.{proj}.weight"] = whole[e]
        tensors[f"mlp.shared_expert.{proj}.weight"] = draws.normal(shared, layer, salt + 10)
    if kind == "full_attention":
        hq, hkv, d = hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"]
        tensors.update({
            "self_attn.q_proj.weight": draws.normal((hq * 2 * d, h), layer, 0),
            "self_attn.k_proj.weight": draws.normal((hkv * d, h), layer, 1),
            "self_attn.v_proj.weight": draws.normal((hkv * d, h), layer, 2),
            "self_attn.o_proj.weight": draws.normal((h, hq * d), layer, 3),
            "self_attn.q_norm.weight": draws.const((d,), 0.0),
            "self_attn.k_norm.weight": draws.const((d,), 0.0),
        })
        return tensors
    hk, hv, d_k, d_v, taps = mixer_sizes(hf)
    channels = 2 * hk * d_k + hv * d_v
    tensors.update({
        "linear_attn.in_proj_qkvz.weight": draws.normal((channels + hv * d_v, h), layer, 0),
        "linear_attn.in_proj_ba.weight": draws.normal((2 * hv, h), layer, 1),
        "linear_attn.out_proj.weight": draws.normal((h, hv * d_v), layer, 3),
        "linear_attn.conv1d.weight": draws.normal((channels, 1, taps), layer, 7) * jnp.bfloat16(CONV_SCALE),
        "linear_attn.A_log": jnp.asarray(A_LOG_TABLE)[_bytes(draws, hv, layer, 8)],
        "linear_attn.dt_bias": jnp.asarray(DT_BIAS_TABLE)[_bytes(draws, hv, layer, 9)],
        "linear_attn.norm.weight": draws.const((d_v,), 1.0),
    })
    return tensors


def block_params(hf: dict, t: dict, kind: str) -> dict:
    """petals_tpu/models/qwen3_next/block.py ``hf_to_block_params``: the fused
    projections taken apart, the zero-centred norms with their 1 folded in."""
    held, _, first = held_share(hf)
    h = hf["hidden_size"]

    def stack(proj: str):
        return jnp.swapaxes(jnp.stack([t[f"mlp.experts.{e}.{proj}.weight"] for e in range(first, first + held)]), 1, 2)

    p = "mlp.shared_expert."
    params = {
        "ln1": 1 + t["input_layernorm.weight"], "ln2": 1 + t["post_attention_layernorm.weight"], "gate": t["mlp.gate.weight"].T,
        "w1": stack("gate_proj"), "w2": stack("down_proj"), "w3": stack("up_proj"), "ws1": t[p + "gate_proj.weight"].T,
        "ws2": t[p + "down_proj.weight"].T, "ws3": t[p + "up_proj.weight"].T, "wsg": t["mlp.shared_expert_gate.weight"].T,
    }
    if kind == "full_attention":
        hq, d, p = hf["num_attention_heads"], hf["head_dim"], "self_attn."
        q_and_gate = t[p + "q_proj.weight"].reshape(hq, 2 * d, h)  # a head's query, then its gate
        return {**params, "wq": q_and_gate[:, :d].reshape(hq * d, h).T, "wqg": q_and_gate[:, d:].reshape(hq * d, h).T,
                "wk": t[p + "k_proj.weight"].T, "wv": t[p + "v_proj.weight"].T, "wo": t[p + "o_proj.weight"].T,
                "q_norm": 1 + t[p + "q_norm.weight"], "k_norm": 1 + t[p + "k_norm.weight"]}
    hk, hv, d_k, d_v, _ = mixer_sizes(hf)
    r, p = hv // hk, "linear_attn."
    qkvz = t[p + "in_proj_qkvz.weight"].reshape(hk, 2 * d_k + 2 * r * d_v, h)  # a key head's q, k, its value heads' v and z
    wq, wk, wv, wz = (part.reshape(-1, h).T for part in jnp.split(qkvz, (d_k, 2 * d_k, 2 * d_k + r * d_v), axis=1))
    ba = t[p + "in_proj_ba.weight"].reshape(hk, 2 * r, h)
    return {**params, "wq": wq, "wk": wk, "wv": wv, "wz": wz, "wb": ba[:, :r].reshape(hv, h).T, "wa": ba[:, r:].reshape(hv, h).T,
            "wo": t[p + "out_proj.weight"].T, "conv": t[p + "conv1d.weight"][:, 0, :].T, "a_log": t[p + "A_log"],
            "dt_bias": t[p + "dt_bias"], "o_norm": t[p + "norm.weight"]}


def span_tree(hf: dict, runs: list) -> tuple:
    """``Server._load_span_params`` for a span of more than one kind: one
    stacked tree per run of consecutive blocks of one kind, in order."""
    return tuple(tree for _, tree in runs)
