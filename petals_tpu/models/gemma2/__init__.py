"""Gemma-2 family registration (see block.py for the architecture notes).

Client surface: sqrt(hidden)-scaled embeddings (like gemma), folded final
norm, TIED head with final logit soft-capping — tanh(logits/cap)*cap, the
HF Gemma2ForCausalLM lm-head behavior."""

from __future__ import annotations

import jax.numpy as jnp

from petals_tpu.models.client_common import (
    LLAMA_STYLE_CLIENT_PREFIXES,
    llama_style_client_norm,
    llama_style_hf_to_client_params,
)
from petals_tpu.models.gemma2 import block as block_mod
from petals_tpu.models.gemma2.config import Gemma2BlockConfig
from petals_tpu.models.llama import block as llama_block
from petals_tpu.models.registry import ModelFamily, register_family


def hf_to_client_params(tensors: dict, cfg) -> dict:
    params = llama_style_hf_to_client_params(tensors, cfg)
    params["norm"] = block_mod._fold_norm(params["norm"])
    return params


from petals_tpu.models.gemma import client_embed  # same sqrt(hidden) scaling


def client_head(params: dict, hidden, cfg):
    normed = llama_style_client_norm(params, hidden, cfg)
    logits = jnp.dot(
        normed.astype(jnp.float32),
        params["head"].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    cap = cfg.final_logit_softcapping
    if cap:
        logits = jnp.tanh(logits / cap) * cap
    return logits


FAMILY = register_family(
    ModelFamily(
        name="gemma2",
        config_from_hf=Gemma2BlockConfig.from_hf_config,
        block_apply=block_mod.block_apply,
        hf_block_prefixes=block_mod._HF_BLOCK_PREFIXES,
        hf_to_block_params=block_mod.hf_to_block_params,
        block_param_shapes=block_mod.block_param_shapes,
        # the llama block's matmul leaves under the same names; no TP specs
        # declared (tp_pspecs stays None: a tp mesh is refused by name)
        quantizable_leaves=llama_block.FAMILY.quantizable_leaves,
        fuse_groups=llama_block.FAMILY.fuse_groups,
        hf_client_prefixes=LLAMA_STYLE_CLIENT_PREFIXES,
        hf_to_client_params=hf_to_client_params,
        client_embed=client_embed,
        client_head=client_head,
        client_norm=llama_style_client_norm,
        # folded (1+w) norms stay float32 through serving-dtype casts (exact
        # fold; rms_norm upcasts anyway) and the per-block window leaf is an
        # int32 scalar, not a weight
        cast_exempt=("ln1", "ln1_post", "ln2_pre", "ln2_post", "norm", "attn_window"),
        supports_ring_attention=False,  # softcap has no ring/flash rule
        block_attention=block_mod.block_attention,
    )
)
