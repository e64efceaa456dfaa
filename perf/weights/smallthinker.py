"""SmallThinker layer tensors under their HF names by kind of layer (every kind has the same tensors), in the served
block's layout, and a span of more than one kind as the server holds it."""

import jax.numpy as jnp


def layer_tensors(hf: dict, layer, draws, kind: tuple) -> dict:
    h, hq, hkv, d = hf["hidden_size"], hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"]
    m, n_experts = hf["moe_ffn_hidden_size"], hf["moe_num_primary_experts"]
    tensors = {
        "input_layernorm.weight": draws.const((h,), 1.0),
        "self_attn.q_proj.weight": draws.normal((hq * d, h), layer, 0),
        "self_attn.k_proj.weight": draws.normal((hkv * d, h), layer, 1),
        "self_attn.v_proj.weight": draws.normal((hkv * d, h), layer, 2),
        "self_attn.o_proj.weight": draws.normal((h, hq * d), layer, 3),
        "post_attention_layernorm.weight": draws.const((h,), 1.0),
        "block_sparse_moe.primary_router.weight": draws.normal((n_experts, h), layer, 4),
    }
    # one stream a projection and not one an expert (perf/weights/olmoe.py): the experts are its runs of m x h elements
    for salt, proj, shape in ((10, "gate", (m, h)), (11, "down", (h, m)), (12, "up", (m, h))):
        whole = draws.normal((n_experts, *shape), layer, salt)
        for e in range(n_experts):
            tensors[f"block_sparse_moe.experts.{e}.{proj}.weight"] = whole[e]
    return tensors


def block_params(hf: dict, t: dict, kind: tuple) -> dict:
    """petals_tpu/models/smallthinker/block.py ``hf_to_block_params``."""

    def stack(proj: str):
        experts = range(hf["moe_num_primary_experts"])
        return jnp.swapaxes(jnp.stack([t[f"block_sparse_moe.experts.{e}.{proj}.weight"] for e in experts]), 1, 2)

    return {
        "ln1": t["input_layernorm.weight"],
        "wq": t["self_attn.q_proj.weight"].T,
        "wk": t["self_attn.k_proj.weight"].T,
        "wv": t["self_attn.v_proj.weight"].T,
        "wo": t["self_attn.o_proj.weight"].T,
        "ln2": t["post_attention_layernorm.weight"],
        "gate": t["block_sparse_moe.primary_router.weight"].T,
        "w1": stack("gate"), "w2": stack("down"), "w3": stack("up"),
    }


def span_tree(hf: dict, runs: list) -> tuple:
    """``Server._load_span_params`` for a span of more than one kind: one stacked tree per run of consecutive blocks of
    one kind, in order (the backend reads the kinds and where each run starts from the family)."""
    return tuple(tree for _, tree in runs)
