"""K-EXAONE layer tensors under their HF names by kind of layer, in the served
block's layout, and a span of more than one kind as the server holds it."""

import jax.numpy as jnp

from perf.reference.exaone_moe import held_share


def layer_tensors(hf: dict, layer, draws, kind: tuple) -> dict:
    h, hq, hkv, d = hf["hidden_size"], hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"]
    tensors = {
        "input_layernorm.weight": draws.const((h,), 1.0),
        "self_attn.q_proj.weight": draws.normal((hq * d, h), layer, 0),
        "self_attn.k_proj.weight": draws.normal((hkv * d, h), layer, 1),
        "self_attn.v_proj.weight": draws.normal((hkv * d, h), layer, 2),
        "self_attn.o_proj.weight": draws.normal((h, hq * d), layer, 3),
        "self_attn.q_norm.weight": draws.const((d,), 1.0),
        "self_attn.k_norm.weight": draws.const((d,), 1.0),
        "post_attention_layernorm.weight": draws.const((h,), 1.0),
    }
    if kind[0] == "dense":
        m = hf["intermediate_size"]
        tensors.update({
            "mlp.gate_proj.weight": draws.normal((m, h), layer, 10),
            "mlp.down_proj.weight": draws.normal((h, m), layer, 11),
            "mlp.up_proj.weight": draws.normal((m, h), layer, 12),
        })
        return tensors
    held, routed, first = held_share(hf)
    m = hf["moe_intermediate_size"]
    tensors["mlp.gate.weight"] = draws.normal((routed, h), layer, 4)
    # drawn like a weight, not left at zero, so that it changes which experts are chosen
    tensors["mlp.gate.e_score_correction_bias"] = draws.normal((routed,), layer, 5)
    # one stream a projection and not one an expert (perf/weights/olmoe.py): the held experts are its
    # first runs of m x h elements, named by their place among the routed
    for salt, proj, shape in ((10, "gate_proj", (m, h)), (11, "down_proj", (h, m)), (12, "up_proj", (m, h))):
        whole = draws.normal((held, *shape), layer, salt)
        for e in range(held):
            tensors[f"mlp.experts.{first + e}.{proj}.weight"] = whole[e]
        if hf.get("num_shared_experts"):
            ms = m * hf["num_shared_experts"]
            tensors[f"mlp.shared_experts.{proj}.weight"] = draws.normal((ms, h) if shape == (m, h) else (h, ms), layer, salt + 10)
    return tensors


def block_params(hf: dict, t: dict, kind: tuple) -> dict:
    """petals_tpu/models/exaone_moe/block.py ``hf_to_block_params``."""
    params = {
        "ln1": t["input_layernorm.weight"],
        "wq": t["self_attn.q_proj.weight"].T,
        "wk": t["self_attn.k_proj.weight"].T,
        "wv": t["self_attn.v_proj.weight"].T,
        "wo": t["self_attn.o_proj.weight"].T,
        "q_norm": t["self_attn.q_norm.weight"],
        "k_norm": t["self_attn.k_norm.weight"],
        "ln2": t["post_attention_layernorm.weight"],
    }
    if kind[0] == "dense":
        return {**params, "wg": t["mlp.gate_proj.weight"].T, "wu": t["mlp.up_proj.weight"].T, "wd": t["mlp.down_proj.weight"].T}
    held, _, first = held_share(hf)

    def stack(proj: str):
        return jnp.swapaxes(jnp.stack([t[f"mlp.experts.{e}.{proj}.weight"] for e in range(first, first + held)]), 1, 2)

    params.update(gate=t["mlp.gate.weight"].T, gate_bias=t["mlp.gate.e_score_correction_bias"],
                  w1=stack("gate_proj"), w2=stack("down_proj"), w3=stack("up_proj"))
    if hf.get("num_shared_experts"):
        params.update(ws1=t["mlp.shared_experts.gate_proj.weight"].T, ws2=t["mlp.shared_experts.down_proj.weight"].T,
                      ws3=t["mlp.shared_experts.up_proj.weight"].T)
    return params


def span_tree(hf: dict, runs: list) -> tuple:
    """``Server._load_span_params`` for a span of more than one kind: one
    stacked tree per run of consecutive blocks of one kind, in order (the
    backend reads the kinds and where each run starts from the family)."""
    return tuple(tree for _, tree in runs)
