"""Falcon (new decoder architecture) layer tensors under their HF names, and
in the served block's layout."""


def layer_tensors(hf: dict, layer, draws) -> dict:
    assert hf.get("new_decoder_architecture") and not hf.get("bias"), "only the Falcon-40B/180B layout is made"
    h, hq, hkv = hf["hidden_size"], hf["num_attention_heads"], hf["num_kv_heads"]
    d = h // hq
    ffn = hf.get("ffn_hidden_size") or 4 * h
    return {
        "ln_attn.weight": draws.const((h,), 1.0),
        "ln_attn.bias": draws.const((h,), 0.0),
        "ln_mlp.weight": draws.const((h,), 1.0),
        "ln_mlp.bias": draws.const((h,), 0.0),
        "self_attention.query_key_value.weight": draws.normal(((hq + 2 * hkv) * d, h), layer, 0),
        "self_attention.dense.weight": draws.normal((h, hq * d), layer, 1),
        "mlp.dense_h_to_4h.weight": draws.normal((ffn, h), layer, 2),
        "mlp.dense_4h_to_h.weight": draws.normal((h, ffn), layer, 3),
    }


def block_params(hf: dict, t: dict) -> dict:
    """petals_tpu/models/falcon/block.py ``hf_to_block_params``, new decoder architecture with two layer norms."""
    h, hq, hkv = hf["hidden_size"], hf["num_attention_heads"], hf["num_kv_heads"]
    d, group = h // hq, hq // hkv
    # fused rows are laid out per kv group: its `group` query heads, its key, its value
    w = t["self_attention.query_key_value.weight"].reshape(hkv, group + 2, d, h)
    return {
        "wq": w[:, :-2].reshape(hq * d, h).T,
        "wk": w[:, -2].reshape(hkv * d, h).T,
        "wv": w[:, -1].reshape(hkv * d, h).T,
        "wo": t["self_attention.dense.weight"].T,
        "w_up": t["mlp.dense_h_to_4h.weight"].T,
        "w_down": t["mlp.dense_4h_to_h.weight"].T,
        "ln_attn_w": t["ln_attn.weight"],
        "ln_attn_b": t["ln_attn.bias"],
        "ln_mlp_w": t["ln_mlp.weight"],
        "ln_mlp_b": t["ln_mlp.bias"],
    }
