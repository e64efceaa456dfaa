"""Mixtral layer tensors under their HF names, and in the served block's layout."""

import jax.numpy as jnp


def layer_tensors(hf: dict, layer, draws) -> dict:
    h, hq, hkv = hf["hidden_size"], hf["num_attention_heads"], hf["num_key_value_heads"]
    d = hf.get("head_dim") or h // hq
    m, n_experts = hf["intermediate_size"], hf["num_local_experts"]
    tensors = {
        "input_layernorm.weight": draws.const((h,), 1.0),
        "self_attn.q_proj.weight": draws.normal((hq * d, h), layer, 0),
        "self_attn.k_proj.weight": draws.normal((hkv * d, h), layer, 1),
        "self_attn.v_proj.weight": draws.normal((hkv * d, h), layer, 2),
        "self_attn.o_proj.weight": draws.normal((h, hq * d), layer, 3),
        "post_attention_layernorm.weight": draws.const((h,), 1.0),
        "block_sparse_moe.gate.weight": draws.normal((n_experts, h), layer, 4),
    }
    for e in range(n_experts):
        q = f"block_sparse_moe.experts.{e}."
        tensors[q + "w1.weight"] = draws.normal((m, h), layer, 10 + 3 * e)
        tensors[q + "w2.weight"] = draws.normal((h, m), layer, 11 + 3 * e)
        tensors[q + "w3.weight"] = draws.normal((m, h), layer, 12 + 3 * e)
    return tensors


def block_params(hf: dict, t: dict) -> dict:
    """petals_tpu/models/mixtral/block.py ``hf_to_block_params``."""
    experts = range(hf["num_local_experts"])

    def stack(w: str):
        return jnp.stack([t[f"block_sparse_moe.experts.{e}.{w}.weight"].T for e in experts])

    return {
        "ln1": t["input_layernorm.weight"],
        "wq": t["self_attn.q_proj.weight"].T,
        "wk": t["self_attn.k_proj.weight"].T,
        "wv": t["self_attn.v_proj.weight"].T,
        "wo": t["self_attn.o_proj.weight"].T,
        "ln2": t["post_attention_layernorm.weight"],
        "gate": t["block_sparse_moe.gate.weight"].T,
        "w1": stack("w1"),
        "w2": stack("w2"),
        "w3": stack("w3"),
    }
