"""Client-side ``xing4_0`` pieces: Llama's tensors (``model.embed_tokens`` /
``model.norm`` / ``lm_head``, RMS norm, untied head) around the stream's
entry and exit, the Hyper-Connections paper's: the stream enters as the
embedding repeated ``hc_mult`` times and leaves as the sum of its rows before
the final norm. Both are here, on the client, because the rows ARE the state
between any two blocks: no server may collapse them at its span's edge."""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

import petals_tpu.models.xing4_0.block as block_mod
from petals_tpu.models.client_common import (
    LLAMA_STYLE_CLIENT_PREFIXES,
    llama_style_client_embed,
    llama_style_client_head,
    llama_style_client_norm,
    llama_style_hf_to_client_params,
)
from petals_tpu.models.registry import register_family


def client_embed(params: dict, input_ids, cfg):
    """[batch, seq, hc_mult x hidden]: every row of the stream starts as the token's embedding."""
    return jnp.tile(llama_style_client_embed(params, input_ids, cfg), cfg.hc_mult)


def stream_exit(hidden, cfg):
    """The sum of the stream's rows: [batch, seq, hidden] of the flat [batch, seq, hc_mult x hidden], in float32."""
    hidden = jnp.asarray(hidden)
    return hidden.astype(jnp.float32).reshape(*hidden.shape[:-1], cfg.hc_mult, cfg.hidden_size).sum(axis=-2)


def client_norm(params: dict, hidden, cfg):
    return llama_style_client_norm(params, stream_exit(hidden, cfg), cfg)


def client_head(params: dict, hidden, cfg):
    return llama_style_client_head(params, stream_exit(hidden, cfg), cfg)


FAMILY = register_family(
    dataclasses.replace(
        block_mod.FAMILY,
        hf_client_prefixes=LLAMA_STYLE_CLIENT_PREFIXES,
        hf_to_client_params=llama_style_hf_to_client_params,
        client_embed=client_embed,
        client_head=client_head,
        client_norm=client_norm,
    )
)
