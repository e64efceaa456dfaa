"""``longcat_flash`` block tensors under transformers' names (the
configuration's ``assumed.tensor_names``), and in the served block's layout."""

import jax.numpy as jnp

from perf.reference.longcat_flash import _dims, held_share

# The router's bias against the scores it moves: a softmax over 768 outputs is about 1.3e-3 an output and about 1e-2
# for a chosen one, so a bias drawn like a weight (std 0.02, as the sigmoid configurations draw theirs against scores
# in (0, 1)) would choose the same experts for every token. A weight's draw times 2**-7 (std 1.56e-4) moves picks and
# fixes none (the configuration's ``assumed.weights`` has the shares). A power of two, because the product has to be the
# same bits on the chip and on the reference's CPU: times 0.01 in float32, rounded once, the two differed in the last
# place of 9 of a layer's 768 values (PR 56, the first chip call: the weights' checksums 2995662426 against 2995662435).
BIAS_SCALE = 2.0**-7


def layer_tensors(hf: dict, layer, draws) -> dict:
    h, heads, dn, dr, dv, latent, rq = _dims(hf)
    m, me = hf["ffn_hidden_size"], hf["expert_ffn_hidden_size"]
    held, exist, first = held_share(hf)
    routed = exist + hf.get("zero_expert_num", 0)
    tensors = {}
    for j in (0, 1):
        a, salt = f"self_attn.{j}.", 20 * j
        tensors.update({
            f"input_layernorm.{j}.weight": draws.const((h,), 1.0),
            a + "q_a_proj.weight": draws.normal((rq, h), layer, salt),
            a + "q_a_layernorm.weight": draws.const((rq,), 1.0),
            a + "q_b_proj.weight": draws.normal((heads * (dn + dr), rq), layer, salt + 1),
            a + "kv_a_proj_with_mqa.weight": draws.normal((latent + dr, h), layer, salt + 2),
            a + "kv_a_layernorm.weight": draws.const((latent,), 1.0),
            a + "kv_b_proj.weight": draws.normal((heads * (dn + dv), latent), layer, salt + 3),
            a + "o_proj.weight": draws.normal((h, heads * dv), layer, salt + 4),
            f"post_attention_layernorm.{j}.weight": draws.const((h,), 1.0),
            f"mlps.{j}.gate_proj.weight": draws.normal((m, h), layer, salt + 5),
            f"mlps.{j}.down_proj.weight": draws.normal((h, m), layer, salt + 6),
            f"mlps.{j}.up_proj.weight": draws.normal((m, h), layer, salt + 7),
        })
    tensors["mlp.router.classifier.weight"] = draws.normal((routed, h), layer, 8)
    tensors["mlp.router.e_score_correction_bias"] = draws.normal((routed,), layer, 9) * jnp.bfloat16(BIAS_SCALE)  # exact: an exponent's shift
    # one stream a projection and not one an expert (perf/weights/olmoe.py): the held experts are its
    # first runs of me x h elements, named by their place among the experts that exist
    for salt, proj, shape in ((10, "gate_proj", (me, h)), (11, "down_proj", (h, me)), (12, "up_proj", (me, h))):
        whole = draws.normal((held, *shape), layer, salt)
        for e in range(held):
            tensors[f"mlp.experts.{first + e}.{proj}.weight"] = whole[e]
    return tensors


def block_params(hf: dict, t: dict) -> dict:
    """petals_tpu/models/longcat_flash/block.py ``hf_to_block_params``."""
    h, heads, dn, dr, dv, latent, rq = _dims(hf)
    order = jnp.concatenate([jnp.arange(0, dr, 2), jnp.arange(1, dr, 2)])  # pairs (2j, 2j + 1) to halves, on both sides of q_pe . k_pe
    params = {}
    for j in (0, 1):
        a = f"self_attn.{j}."
        wqb = t[a + "q_b_proj.weight"].T.reshape(rq, heads, dn + dr)
        wqb = jnp.concatenate([wqb[..., :dn], wqb[..., dn:][..., order]], axis=-1).reshape(rq, heads * (dn + dr))
        wkva = t[a + "kv_a_proj_with_mqa.weight"].T
        wkvb = t[a + "kv_b_proj.weight"].reshape(heads, dn + dv, latent)
        params.update({
            f"ln1_{j}": t[f"input_layernorm.{j}.weight"],
            f"wqa_{j}": t[a + "q_a_proj.weight"].T,
            f"q_norm_{j}": t[a + "q_a_layernorm.weight"],
            f"wqb_{j}": wqb,
            f"wkva_{j}": jnp.concatenate([wkva[:, :latent], wkva[:, latent:][:, order]], axis=-1),
            f"kv_norm_{j}": t[a + "kv_a_layernorm.weight"],
            f"wuk_{j}": wkvb[:, :dn],
            f"wuv_{j}": jnp.swapaxes(wkvb[:, dn:], 1, 2),
            f"wo_{j}": t[a + "o_proj.weight"].T,
            f"ln2_{j}": t[f"post_attention_layernorm.{j}.weight"],
            f"wg_{j}": t[f"mlps.{j}.gate_proj.weight"].T,
            f"wu_{j}": t[f"mlps.{j}.up_proj.weight"].T,
            f"wd_{j}": t[f"mlps.{j}.down_proj.weight"].T,
        })
    held, _, first = held_share(hf)

    def stack(proj: str):
        return jnp.swapaxes(jnp.stack([t[f"mlp.experts.{e}.{proj}.weight"] for e in range(first, first + held)]), 1, 2)

    params.update(gate=t["mlp.router.classifier.weight"].T, gate_bias=t["mlp.router.e_score_correction_bias"],
                  w1=stack("gate_proj"), w2=stack("down_proj"), w3=stack("up_proj"))
    return params
