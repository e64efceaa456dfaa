"""Keye-VL-2.0 layer tensors under the names of the configuration's
``assumed.tensor_names``, and in the served block's layout. The indexer's
layer norm is drawn at weight 1 and bias 0, every matrix as the rest."""

import jax.numpy as jnp


def layer_tensors(hf: dict, layer, draws) -> dict:
    h, hq, hkv, d = hf["hidden_size"], hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"]
    m, n_experts, sa = hf["moe_intermediate_size"], hf["num_experts"], hf["sa_config"]
    heads, d_idx = sa["indexer_num_heads"], sa["indexer_head_dim"]
    tensors = {
        "input_layernorm.weight": draws.const((h,), 1.0),
        "self_attn.q_proj.weight": draws.normal((hq * d, h), layer, 0),
        "self_attn.k_proj.weight": draws.normal((hkv * d, h), layer, 1),
        "self_attn.v_proj.weight": draws.normal((hkv * d, h), layer, 2),
        "self_attn.o_proj.weight": draws.normal((h, hq * d), layer, 3),
        "self_attn.q_norm.weight": draws.const((d,), 1.0),
        "self_attn.k_norm.weight": draws.const((d,), 1.0),
        "self_attn.indexer.wq.weight": draws.normal((heads * d_idx, h), layer, 5),
        "self_attn.indexer.wk.weight": draws.normal((d_idx, h), layer, 6),
        "self_attn.indexer.weights_proj.weight": draws.normal((heads, h), layer, 7),
        "self_attn.indexer.k_norm.weight": draws.const((d_idx,), 1.0),
        "self_attn.indexer.k_norm.bias": draws.const((d_idx,), 0.0),
        "post_attention_layernorm.weight": draws.const((h,), 1.0),
        "mlp.gate.weight": draws.normal((n_experts, h), layer, 4),
    }
    # one stream a projection, its consecutive runs the experts' tensors (perf/weights/olmoe.py says why)
    for salt, proj, shape in ((10, "gate_proj", (m, h)), (11, "down_proj", (h, m)), (12, "up_proj", (m, h))):
        whole = draws.normal((n_experts, *shape), layer, salt)
        for e in range(n_experts):
            tensors[f"mlp.experts.{e}.{proj}.weight"] = whole[e]
    return tensors


def block_params(hf: dict, t: dict) -> dict:
    """petals_tpu/models/keye_vl2/block.py ``hf_to_block_params``."""
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
        "iq": t["self_attn.indexer.wq.weight"].T,
        "ik": t["self_attn.indexer.wk.weight"].T,
        "iw": t["self_attn.indexer.weights_proj.weight"].T,
        "ik_norm": t["self_attn.indexer.k_norm.weight"],
        "ik_bias": t["self_attn.indexer.k_norm.bias"],
        "ln2": t["post_attention_layernorm.weight"],
        "gate": t["mlp.gate.weight"].T,
        "w1": stack("gate_proj"),
        "w2": stack("down_proj"),
        "w3": stack("up_proj"),
    }
