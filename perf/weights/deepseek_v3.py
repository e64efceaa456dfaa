"""``deepseek_v3`` layer tensors under transformers' names by kind of layer
(the configuration's ``assumed.tensor_names``), in the served block's layout,
and a span of more than one kind as the server holds it."""

import jax.numpy as jnp


def _dims(hf: dict) -> tuple:
    return hf["hidden_size"], hf["num_attention_heads"], hf["qk_nope_head_dim"], hf["qk_rope_head_dim"], hf["v_head_dim"], hf["kv_lora_rank"]


def layer_tensors(hf: dict, layer, draws, kind: str) -> dict:
    h, heads, dn, dr, dv, latent = _dims(hf)
    tensors = {
        "input_layernorm.weight": draws.const((h,), 1.0),
        "self_attn.q_proj.weight": draws.normal((heads * (dn + dr), h), layer, 0),
        "self_attn.kv_a_proj_with_mqa.weight": draws.normal((latent + dr, h), layer, 1),
        "self_attn.kv_a_layernorm.weight": draws.const((latent,), 1.0),
        "self_attn.kv_b_proj.weight": draws.normal((heads * (dn + dv), latent), layer, 2),
        "self_attn.o_proj.weight": draws.normal((h, heads * dv), layer, 3),
        "post_attention_layernorm.weight": draws.const((h,), 1.0),
    }
    if kind == "dense":
        m = hf["intermediate_size"]
        tensors.update({
            "mlp.gate_proj.weight": draws.normal((m, h), layer, 10),
            "mlp.down_proj.weight": draws.normal((h, m), layer, 11),
            "mlp.up_proj.weight": draws.normal((m, h), layer, 12),
        })
        return tensors
    n, m = hf["n_routed_experts"], hf["moe_intermediate_size"]
    tensors["mlp.gate.weight"] = draws.normal((n, h), layer, 4)
    # drawn like a weight, not left at zero, so that it changes which experts are chosen (K-EXAONE's is)
    tensors["mlp.gate.e_score_correction_bias"] = draws.normal((n,), layer, 5)
    # one stream a projection, its consecutive runs the experts' tensors (perf/weights/olmoe.py says why)
    for salt, proj, shape in ((10, "gate_proj", (m, h)), (11, "down_proj", (h, m)), (12, "up_proj", (m, h))):
        whole = draws.normal((n, *shape), layer, salt)
        for e in range(n):
            tensors[f"mlp.experts.{e}.{proj}.weight"] = whole[e]
        if hf.get("n_shared_experts"):
            ms = m * hf["n_shared_experts"]
            tensors[f"mlp.shared_experts.{proj}.weight"] = draws.normal((ms, h) if shape == (m, h) else (h, ms), layer, salt + 10)
    return tensors


def block_params(hf: dict, t: dict, kind: str) -> dict:
    """petals_tpu/models/deepseek_v3/block.py ``hf_to_block_params``."""
    h, heads, dn, dr, dv, latent = _dims(hf)
    wq, wkva = t["self_attn.q_proj.weight"].T, t["self_attn.kv_a_proj_with_mqa.weight"].T
    if hf.get("rope_interleave", True):  # pairs (2j, 2j + 1) to halves, on both sides of q_pe . k_pe
        order = jnp.concatenate([jnp.arange(0, dr, 2), jnp.arange(1, dr, 2)])
        wq = wq.reshape(h, heads, dn + dr)
        wq = jnp.concatenate([wq[..., :dn], wq[..., dn:][..., order]], axis=-1).reshape(h, heads * (dn + dr))
        wkva = jnp.concatenate([wkva[:, :latent], wkva[:, latent:][:, order]], axis=-1)
    wkvb = t["self_attn.kv_b_proj.weight"].reshape(heads, dn + dv, latent)
    params = {
        "ln1": t["input_layernorm.weight"],
        "wq": wq,
        "wkva": wkva,
        "kv_norm": t["self_attn.kv_a_layernorm.weight"],
        "wuk": wkvb[:, :dn],
        "wuv": jnp.swapaxes(wkvb[:, dn:], 1, 2),
        "wo": t["self_attn.o_proj.weight"].T,
        "ln2": t["post_attention_layernorm.weight"],
    }
    if kind == "dense":
        return {**params, "wg": t["mlp.gate_proj.weight"].T, "wu": t["mlp.up_proj.weight"].T, "wd": t["mlp.down_proj.weight"].T}

    def stack(proj: str):
        return jnp.swapaxes(jnp.stack([t[f"mlp.experts.{e}.{proj}.weight"] for e in range(hf["n_routed_experts"])]), 1, 2)

    params.update(gate=t["mlp.gate.weight"].T, gate_bias=t["mlp.gate.e_score_correction_bias"],
                  w1=stack("gate_proj"), w2=stack("down_proj"), w3=stack("up_proj"))
    if hf.get("n_shared_experts"):
        params.update(ws1=t["mlp.shared_experts.gate_proj.weight"].T, ws2=t["mlp.shared_experts.down_proj.weight"].T,
                      ws3=t["mlp.shared_experts.up_proj.weight"].T)
    return params


def span_tree(hf: dict, runs: list) -> tuple:
    """``Server._load_span_params`` for a span of more than one kind: one
    stacked tree per run of consecutive blocks of one kind, in order (the
    backend reads the kinds and where each run starts from the family)."""
    return tuple(tree for _, tree in runs)
