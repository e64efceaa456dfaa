"""Client-side Qwen3-Next pieces: the same embed/norm/head layout as Llama
(``model.embed_tokens`` / ``model.norm`` / ``lm_head``, untied head), shared
via models/client_common.py, but that the final RMS norm is zero-centred as
every norm of the model (``rms(x) * (1 + w)``): the 1 is folded into the
weight at load, in float32, as Gemma's is. The published multi-token
prediction layer sits behind the head and is not served (config.py says so
once at load); no sequence classification head is published."""

from __future__ import annotations

import dataclasses

import numpy as np

import petals_tpu.models.qwen3_next.block as block_mod
from petals_tpu.models.client_common import (
    LLAMA_STYLE_CLIENT_PREFIXES,
    llama_style_client_embed,
    llama_style_client_head,
    llama_style_client_norm,
    llama_style_hf_to_client_params,
)
from petals_tpu.models.registry import register_family


def hf_to_client_params(tensors: dict, cfg) -> dict:
    params = llama_style_hf_to_client_params(tensors, cfg)
    params["norm"] = np.asarray(params["norm"], np.float32) + 1.0
    return params


FAMILY = register_family(
    dataclasses.replace(
        block_mod.FAMILY,
        hf_client_prefixes=LLAMA_STYLE_CLIENT_PREFIXES,
        hf_to_client_params=hf_to_client_params,
        client_embed=llama_style_client_embed,
        client_head=llama_style_client_head,
        client_norm=llama_style_client_norm,
    )
)
