"""``xing4_0`` layer tensors by kind of layer, under transformers'
``deepseek_v3`` names for the sub-layers and the configuration's
``assumed.tensor_names`` for the two hyper-connections, in the served block's
layout, and a span of more than one kind as the server holds it."""

import jax.numpy as jnp

from perf.weights import deepseek_v3 as sublayers

HC_ALPHA = 0.4  # a_pre = a_post = a_res (the configuration's ``assumed.weights`` says why)
WRAPS = ("attn_hc.", "mlp_hc.")


def _dims(hf: dict) -> tuple:
    return (hf["hidden_size"], hf["num_attention_heads"], hf["qk_nope_head_dim"], hf["qk_rope_head_dim"], hf["v_head_dim"],
            hf["kv_lora_rank"], hf["q_lora_rank"], hf["hc_mult"])


def layer_tensors(hf: dict, layer, draws, kind: str) -> dict:
    h, heads, dn, dr, _, _, rq, n = _dims(hf)
    # the norms, kv_a, kv_b, o and the feed-forward of the kind as ``deepseek_v3`` draws them (the sub-layers are its);
    # its one query matrix is not this model's (a tensor nobody reads is no part of a jitted program)
    tensors = sublayers.layer_tensors(hf, layer, draws, kind)
    del tensors["self_attn.q_proj.weight"]
    tensors.update({
        "self_attn.q_a_proj.weight": draws.normal((rq, h), layer, 0),
        "self_attn.q_a_layernorm.weight": draws.const((rq,), 1.0),
        "self_attn.q_b_proj.weight": draws.normal((heads * (dn + dr), rq), layer, 6),
    })
    for k, p in enumerate(WRAPS):  # salts 30.., 40..: a stream a tensor
        for j, (c, rows) in enumerate((("pre", n), ("post", n), ("res", n * n))):
            tensors[p + f"phi_{c}.weight"] = draws.normal((rows, n * h), layer, 30 + 10 * k + j)
            tensors[p + f"alpha_{c}"] = draws.const((1,), HC_ALPHA)
            tensors[p + f"b_{c}"] = draws.normal((n, n) if c == "res" else (n,), layer, 33 + 10 * k + j)
    return tensors


def block_params(hf: dict, t: dict, kind: str) -> dict:
    """petals_tpu/models/xing4_0/block.py ``hf_to_block_params``."""
    h, heads, dn, dr, dv, latent, rq, n = _dims(hf)
    wqb, wkva = t["self_attn.q_b_proj.weight"].T, t["self_attn.kv_a_proj_with_mqa.weight"].T
    if hf.get("rope_interleave", True):  # pairs (2j, 2j + 1) to halves, on both sides of q_pe . k_pe
        order = jnp.concatenate([jnp.arange(0, dr, 2), jnp.arange(1, dr, 2)])
        wqb = wqb.reshape(rq, heads, dn + dr)
        wqb = jnp.concatenate([wqb[..., :dn], wqb[..., dn:][..., order]], axis=-1).reshape(rq, heads * (dn + dr))
        wkva = jnp.concatenate([wkva[:, :latent], wkva[:, latent:][:, order]], axis=-1)
    wkvb = t["self_attn.kv_b_proj.weight"].reshape(heads, dn + dv, latent)
    params = {
        "ln1": t["input_layernorm.weight"],
        "wqa": t["self_attn.q_a_proj.weight"].T,
        "q_norm": t["self_attn.q_a_layernorm.weight"],
        "wqb": wqb,
        "wkva": wkva,
        "kv_norm": t["self_attn.kv_a_layernorm.weight"],
        "wuk": wkvb[:, :dn],
        "wuv": jnp.swapaxes(wkvb[:, dn:], 1, 2),
        "wo": t["self_attn.o_proj.weight"].T,
        "ln2": t["post_attention_layernorm.weight"],
    }
    for wrap, p in zip(("attn", "mlp"), WRAPS):  # the three products as one matrix, columns [pre | post | res, row-major]
        params[f"hc_phi_{wrap}"] = jnp.concatenate([t[p + f"phi_{c}.weight"].T for c in ("pre", "post", "res")], axis=1)
        params[f"hc_alpha_{wrap}"] = jnp.concatenate([t[p + f"alpha_{c}"].reshape(1) for c in ("pre", "post", "res")])
        params[f"hc_bias_{wrap}"] = jnp.concatenate([t[p + f"b_{c}"].reshape(-1) for c in ("pre", "post", "res")])
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
