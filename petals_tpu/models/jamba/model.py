"""Client-side Jamba pieces: embeddings, the final RMS norm
(``model.final_layernorm``, a plain weight) and the head, which the published
model ties to the embeddings; the embed/norm/head functions are Llama's
(models/client_common.py). No sequence classification head is served."""

from __future__ import annotations

import dataclasses

import petals_tpu.models.jamba.block as block_mod
from petals_tpu.models.client_common import (
    llama_style_client_embed,
    llama_style_client_head,
    llama_style_client_norm,
    llama_style_hf_to_client_params,
)
from petals_tpu.models.registry import register_family

CLIENT_PREFIXES = ("model.embed_tokens.", "model.final_layernorm.", "lm_head.")


def hf_to_client_params(tensors: dict, cfg) -> dict:
    return llama_style_hf_to_client_params({**tensors, "model.norm.weight": tensors["model.final_layernorm.weight"]}, cfg)


FAMILY = register_family(
    dataclasses.replace(
        block_mod.FAMILY,
        hf_client_prefixes=CLIENT_PREFIXES,
        hf_to_client_params=hf_to_client_params,
        client_embed=llama_style_client_embed,
        client_head=llama_style_client_head,
        client_norm=llama_style_client_norm,
    )
)
