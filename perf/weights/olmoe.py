"""OLMoE layer tensors under their HF names, and in the served block's layout."""

import jax.numpy as jnp


def layer_tensors(hf: dict, layer, draws) -> dict:
    h, hq, hkv = hf["hidden_size"], hf["num_attention_heads"], hf["num_key_value_heads"]
    d, m, n_experts = h // hq, hf["intermediate_size"], hf["num_experts"]
    tensors = {
        "input_layernorm.weight": draws.const((h,), 1.0),
        "self_attn.q_proj.weight": draws.normal((hq * d, h), layer, 0),
        "self_attn.k_proj.weight": draws.normal((hkv * d, h), layer, 1),
        "self_attn.v_proj.weight": draws.normal((hkv * d, h), layer, 2),
        "self_attn.o_proj.weight": draws.normal((h, hq * d), layer, 3),
        "self_attn.q_norm.weight": draws.const((hq * d,), 1.0),
        "self_attn.k_norm.weight": draws.const((hkv * d,), 1.0),
        "post_attention_layernorm.weight": draws.const((h,), 1.0),
        "mlp.gate.weight": draws.normal((n_experts, h), layer, 4),
    }
    # one stream a projection and not one an expert: 64 consecutive runs of m x h elements of it are the
    # experts' tensors (3 draws a layer to compile where one an expert made 192: the server child made its
    # 8 layers on the chip in 326 s cold that way, and in 29 s this way; PR 26)
    for salt, proj, shape in ((10, "gate_proj", (m, h)), (11, "down_proj", (h, m)), (12, "up_proj", (m, h))):
        whole = draws.normal((n_experts, *shape), layer, salt)
        for e in range(n_experts):
            tensors[f"mlp.experts.{e}.{proj}.weight"] = whole[e]
    return tensors


def block_params(hf: dict, t: dict) -> dict:
    """petals_tpu/models/olmoe/block.py ``hf_to_block_params``."""
    experts = range(hf["num_experts"])

    def stack(proj: str):
        return jnp.swapaxes(jnp.stack([t[f"mlp.experts.{e}.{proj}.weight"] for e in experts]), 1, 2)

    return {
        "ln1": t["input_layernorm.weight"],
        "wq": t["self_attn.q_proj.weight"].T,
        "wk": t["self_attn.k_proj.weight"].T,
        "wv": t["self_attn.v_proj.weight"].T,
        "wo": t["self_attn.o_proj.weight"].T,
        "q_norm": t["self_attn.q_norm.weight"],
        "k_norm": t["self_attn.k_norm.weight"],
        "ln2": t["post_attention_layernorm.weight"],
        "gate": t["mlp.gate.weight"].T,
        "w1": stack("gate_proj"),
        "w2": stack("down_proj"),
        "w3": stack("up_proj"),
    }
