"""Client-side SmallThinker pieces: the same embed/norm/head layout as Llama
(``model.embed_tokens`` / ``model.norm`` / ``lm_head``, RMS norm, untied
head), shared via models/client_common.py. No sequence classification head is published."""

from __future__ import annotations

import dataclasses

import petals_tpu.models.smallthinker.block as block_mod
from petals_tpu.models.client_common import (
    LLAMA_STYLE_CLIENT_PREFIXES,
    llama_style_client_embed,
    llama_style_client_head,
    llama_style_client_norm,
    llama_style_hf_to_client_params,
)
from petals_tpu.models.registry import register_family

FAMILY = register_family(
    dataclasses.replace(
        block_mod.FAMILY,
        hf_client_prefixes=LLAMA_STYLE_CLIENT_PREFIXES,
        hf_to_client_params=llama_style_hf_to_client_params,
        client_embed=llama_style_client_embed,
        client_head=llama_style_client_head,
        client_norm=llama_style_client_norm,
    )
)
