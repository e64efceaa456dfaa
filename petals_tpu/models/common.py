"""Shared numerics for all model families (norms, activations, KV-cache plumbing).

All normalizations run in float32 and cast back, matching HF torch semantics
closely enough for the 1e-4 (f32) / 1e-3 (bf16) exactness bars used by the
reference test suite (reference tests/test_block_exact_match.py:78-108).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from petals_tpu.parallel.tp import COL

KVCache = Tuple[jnp.ndarray, jnp.ndarray]  # (k, v): [batch, max_len, kv_heads, head_dim]

# What every family with separate q/k/v/o projections declares on its
# ModelFamily (models/registry.py). Tensor parallelism is Megatron-style:
# input projections split on the output (head) axis, output projections on
# the input axis, norms replicated; XLA then inserts the psums over ICI.
# Leaves carry a leading layer axis (the span stack), so weight specs are
# (None, <in>, <out>).
COL_SPLIT, ROW_SPLIT, COL_BIAS = P(None, None, COL), P(None, COL, None), P(None, COL)
ATTN_PSPECS = {"wq": COL_SPLIT, "wk": COL_SPLIT, "wv": COL_SPLIT, "wo": ROW_SPLIT}
QKV_BIAS_PSPECS = {"bq": COL_BIAS, "bk": COL_BIAS, "bv": COL_BIAS}
ATTN_LEAVES = frozenset(ATTN_PSPECS)
HF_ATTN_LORA_TARGETS = {"q_proj": "wq", "k_proj": "wk", "v_proj": "wv", "o_proj": "wo"}


def leaf_pspecs(block_param_shapes, table: dict):
    """A family's ``tp_pspecs``: the specs of exactly the leaves a config
    produces, by name from ``table`` (which leaves exist is decided once, in
    ``block_param_shapes``; a leaf the table forgot is a KeyError here, not a
    silently replicated weight)."""
    return lambda cfg: {name: table[name] for name in block_param_shapes(cfg)}


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x: jnp.ndarray, weight: jnp.ndarray, bias: jnp.ndarray, eps: float) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def gelu_tanh(x: jnp.ndarray) -> jnp.ndarray:
    """BLOOM/Falcon GeLU (tanh approximation, matches HF BloomGelu)."""
    xf = x.astype(jnp.float32)
    out = 0.5 * xf * (1.0 + jnp.tanh(0.79788456 * xf * (1.0 + 0.044715 * xf * xf)))
    return out.astype(x.dtype)


def silu(x: jnp.ndarray) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    return (xf * jax.nn.sigmoid(xf)).astype(x.dtype)


def gelu_exact(x: jnp.ndarray) -> jnp.ndarray:
    """Exact (erf) GeLU — HF ACT2FN["gelu"]; jax.nn.gelu defaults to the TANH
    approximation, which would diverge up to ~1e-2 near |x|~2."""
    xf = x.astype(jnp.float32)
    return jax.nn.gelu(xf, approximate=False).astype(x.dtype)


ACTIVATIONS = {"silu": silu, "gelu_tanh": gelu_tanh, "gelu": gelu_exact}


def mm(x: jnp.ndarray, w) -> jnp.ndarray:
    """Matmul dispatching on dense / quantized / LoRA-wrapped weights."""
    from petals_tpu.ops.quant import (
        OutlierQuantLinear,
        QuantizedLinear,
        StackedQuantLinear,
        quant_matmul,
    )
    from petals_tpu.utils.peft import LoraLinear

    if isinstance(w, LoraLinear):
        base = mm(x, w.base)
        delta = (x @ w.lora_a.astype(x.dtype)) @ w.lora_b.astype(x.dtype)
        return base + delta * w.scaling
    if isinstance(w, (QuantizedLinear, StackedQuantLinear, OutlierQuantLinear)):
        return quant_matmul(x, w)
    return x @ w


def project_heads(x: jnp.ndarray, w) -> jnp.ndarray:
    """``mm`` for a projection whose product is about to be split into heads
    (q, k, v): the product is handed over in the matmul's own layout.

    Attention wants its operands with the heads minor. Left alone, XLA's
    layout assignment carries that wish back through the reshape into the dot
    and pays for it on the WEIGHT: inside the step's scan it slices the
    layer's matrix out of the stacked span and transposes the copy, every
    layer of every step (Falcon-40B: 134 MB twice a layer, 27% of the decode
    loop on a v5e). The barrier stops the wish at the product, so the relayout
    falls on the activation and the dot reads the stacked weight in place.
    The arithmetic is as written, and on the CPU the bits are ``mm``'s; the
    TPU's decode program used to hand q to the rotary in the dot's float32
    and now rounds it to the activations' dtype first, as the code says.
    ``tests/test_kernels_lower_tpu.py`` holds the compiled decode step to
    reading its weights in place."""
    return jax.lax.optimization_barrier(mm(x, w))


def absolute_positions(position, batch: int, seq: int) -> jnp.ndarray:
    """[batch, seq] absolute positions for this chunk's tokens.

    ``position`` is a scalar (all rows share a history length — the classic
    session step) or a [batch] vector (per-lane positions: continuous batching
    coalesces many sessions at different decode depths into one step)."""
    pos = jnp.asarray(position, jnp.int32)
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos[None], (batch,))
    return pos[:, None] + jnp.arange(seq, dtype=jnp.int32)[None, :]


def update_kv_cache(
    kv: Optional[KVCache], k_new: jnp.ndarray, v_new: jnp.ndarray, position, n_valid=None
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Write k_new/v_new ([b, s, hkv, d]) into the cache at ``position``.

    Returns (k_all, v_all, kv_length) to attend over. With kv=None (training
    forward without a cache) the freshly computed k/v are used directly.

    ``position`` may be a [batch] vector (per-lane positions, continuous
    batching): each row writes at its own offset and kv_length comes back as
    a vector. Rows whose position is >= the buffer length are DROPPED — the
    out-of-range sentinel is how the batched step marks idle lanes.

    ``n_valid`` (dynamic scalar) marks how many of the ``s`` new tokens are
    real — the tail may be padding from shape bucketing. Padding IS written
    into the buffer past the valid region, but kv_length masks it out of
    attention and the next chunk overwrites it.
    """
    seq = k_new.shape[1]
    if kv is None:
        n = seq if n_valid is None else n_valid
        return k_new, v_new, jnp.asarray(n, jnp.int32)
    k_buf, v_buf = kv

    # paged cache: the kv tuple carries (pool, block-table) pairs instead of
    # dense buffers — scatter the new rows straight into the pages (no dense
    # detour) and hand the PagedKV pair on to attend()'s fused dispatch
    from petals_tpu.ops.paged_attention import PagedKV, paged_update_kv

    if isinstance(k_buf, PagedKV):
        return paged_update_kv(k_buf, v_buf, k_new, v_new, position, n_valid)
    pos = jnp.asarray(position, jnp.int32)

    if pos.ndim == 1:  # per-lane write (continuous batching across sessions)
        batch = k_new.shape[0]
        buf_len = k_buf.shape[1]
        offsets = jnp.arange(seq, dtype=jnp.int32)
        idx = pos[:, None] + offsets[None, :]  # [b, s]
        if n_valid is not None:
            idx = jnp.where(offsets[None, :] < jnp.asarray(n_valid, jnp.int32), idx, buf_len)
        # rows at/past the buffer end (idle-lane sentinel or overflow) drop
        b_idx = jnp.arange(batch, dtype=jnp.int32)[:, None]
        k_buf = k_buf.at[b_idx, idx].set(k_new.astype(k_buf.dtype), mode="drop")
        v_buf = v_buf.at[b_idx, idx].set(v_new.astype(v_buf.dtype), mode="drop")
        n = seq if n_valid is None else jnp.asarray(n_valid, jnp.int32)
        return k_buf, v_buf, pos + n

    if n_valid is None:
        # Unpadded write: the caller guarantees position + seq <= buffer length
        # (validated at the handler; a concrete int is also checked here because
        # a clamped dynamic_update_slice would silently corrupt the cache).
        if isinstance(position, int) and position + seq > k_buf.shape[1]:
            raise ValueError(
                f"KV cache overflow: position {position} + {seq} new tokens > "
                f"buffer length {k_buf.shape[1]}"
            )
        k_buf = jax.lax.dynamic_update_slice(k_buf, k_new.astype(k_buf.dtype), (0, pos, 0, 0))
        v_buf = jax.lax.dynamic_update_slice(v_buf, v_new.astype(v_buf.dtype), (0, pos, 0, 0))
        return k_buf, v_buf, pos + seq

    # Bucket-padded write: dynamic_update_slice would CLAMP the start index if
    # position + padded_len overran the buffer (corrupting the prefix), so the
    # padded tail is routed out-of-bounds and dropped by a scatter instead.
    n = jnp.asarray(n_valid, jnp.int32)
    offsets = jnp.arange(seq, dtype=jnp.int32)
    idx = jnp.where(offsets < n, pos + offsets, k_buf.shape[1])  # OOB => dropped
    k_buf = k_buf.at[:, idx].set(k_new.astype(k_buf.dtype), mode="drop")
    v_buf = v_buf.at[:, idx].set(v_new.astype(v_buf.dtype), mode="drop")
    return k_buf, v_buf, pos + n
