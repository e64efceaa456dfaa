"""Rotary position embeddings (RoPE), TPU-native.

Equivalent capability to the reference's CUDA-graphed rotary for 1-token decode
(/root/reference/src/petals/models/llama/block.py:37-93) — under ``jax.jit`` the
whole decode step is one fused XLA program, so no graph-capture machinery is
needed.

Convention matches HF Llama ("rotate_half"): the head dim is split into two
halves [x1, x2]; rotated = [x1*cos - x2*sin, x2*cos + x1*sin].
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax.numpy as jnp


def rotary_tables(
    positions: jnp.ndarray,  # [batch, seq] absolute positions (int32)
    head_dim: int,
    theta: float = 10000.0,
    scaling_factor: Optional[float] = None,
    rope_scaling: Optional[dict] = None,
    n_valid=None,  # real (non-padding) token count of this chunk, [b] or scalar
    n_total=None,  # FINAL sequence length when known up front (chunked prefill)
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Compute cos/sin tables [batch, seq, head_dim] for the given positions.

    ``rope_scaling`` supports HF-style dicts with rope_type "linear",
    "llama3", "longrope" or "yarn" (others raise NotImplementedError). Computation
    is float32 throughout for parity with HF. ``n_valid``/``n_total`` only
    matter to "longrope", whose factor selection depends on the REAL
    sequence length — padded bucket tails must not count, and a chunked
    prefill whose final length is already known must select from THAT
    length (``n_total``) so every chunk matches HF's single full forward.
    """
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    table_scale = 1.0

    if rope_scaling is not None:
        rope_type = rope_scaling.get("rope_type", rope_scaling.get("type", "default"))
        if rope_type == "linear":
            inv_freq = inv_freq / rope_scaling["factor"]
        elif rope_type == "llama3":
            inv_freq = _llama3_scale_inv_freq(inv_freq, rope_scaling)
        elif rope_type == "longrope":
            inv_freq, table_scale = _longrope_inv_freq(
                inv_freq, positions, rope_scaling, n_valid, n_total
            )
        elif rope_type == "yarn":
            inv_freq, table_scale = _yarn_inv_freq(head_dim, theta, rope_scaling)
        elif rope_type in ("default", None):
            pass
        else:
            raise NotImplementedError(f"rope_type={rope_type!r} is not supported yet")
    elif scaling_factor is not None:
        inv_freq = inv_freq / scaling_factor

    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # [b, s, d/2]
    emb = jnp.concatenate([angles, angles], axis=-1)  # [b, s, d]
    return jnp.cos(emb) * table_scale, jnp.sin(emb) * table_scale


def _longrope_inv_freq(
    inv_freq: jnp.ndarray, positions: jnp.ndarray, cfg: dict, n_valid=None,
    n_total=None,
):
    """Phi-3 LongRoPE (mirrors HF's _compute_longrope_parameters): per-dim
    extension factors — ``long_factor`` once the runtime sequence extends
    past the pretrained window, ``short_factor`` inside it — plus a fixed
    attention scaling on the tables.

    The selection is PER ROW and uses the real sequence end:
    - per row: pooled batched decode carries per-lane positions (idle lanes
      hold the out-of-range sentinel), and one deep lane must not flip a
      shallow lane's factors;
    - real end: prefill chunks are padded to power-of-two buckets, and the
      padded tail must not trip the switch — ``n_valid`` (the chunk's real
      token count; rows ascend from positions[:, 0]) overrides the padded
      maximum when given.

    When the FINAL prompt length is already known (``n_total``, e.g. a
    chunked server-side prefill of a fully materialized prompt), it
    overrides both branches below: every chunk selects factors from the
    final length, matching HF's single full forward over the whole prompt.
    Without ``n_total`` this traces HF's per-forward dynamic re-selection
    instead: a CACHED sequence crossing the boundary switches tables for
    NEW positions only, exactly like HF's cache path (the remaining
    cache-vs-forward quirk is confined to sequences that only cross the
    boundary during cached decode — the same quirk HF has).
    config_from_hf injects ``factor`` and
    ``original_max_position_embeddings`` from the top-level HF config.
    Returns (inv_freq [b, 1, d/2], table_scale)."""
    short = jnp.asarray(cfg["short_factor"], jnp.float32)
    long = jnp.asarray(cfg["long_factor"], jnp.float32)
    orig = float(cfg["original_max_position_embeddings"])
    factor = float(cfg.get("factor") or 1.0)
    attention_factor = cfg.get("attention_factor")
    if attention_factor is None:
        attention_factor = (
            1.0 if factor <= 1.0 else math.sqrt(1 + math.log(factor) / math.log(orig))
        )
    if n_total is not None:
        seq_len = jnp.broadcast_to(
            jnp.asarray(n_total, positions.dtype), positions.shape[:1]
        )
    elif n_valid is not None:
        seq_len = positions[:, 0] + jnp.broadcast_to(
            jnp.asarray(n_valid, positions.dtype), positions.shape[:1]
        )
    else:
        seq_len = jnp.max(positions, axis=-1) + 1
    use_long = (seq_len > orig)[:, None, None]  # [b, 1, 1]
    ext = jnp.where(use_long, long[None, None, :], short[None, None, :])
    return inv_freq / ext, float(attention_factor)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature: ``0.1 mscale ln(factor) + 1`` past a factor of 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_inv_freq(dim: int, theta: float, cfg: dict):
    """YaRN (mirrors HF's ``_compute_yarn_parameters``): each frequency is
    blended between ``1 / theta^(2i/d)`` (kept: it turns more than
    ``beta_fast`` times over the pretrained window) and that over ``factor``
    (interpolated: fewer than ``beta_slow`` turns) by a linear ramp between
    the two correction dims. The tables are scaled by ``attention_factor``,
    or by ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)`` where
    both are given, else by ``mscale(factor)``. The softmax's own ``mscale^2``
    of a ``deepseek_v3`` attention is the caller's. ``original_max_position_
    embeddings`` must be in ``cfg`` (HF falls back to the model's
    ``max_position_embeddings``: a family's config puts it in).
    Returns (inv_freq [d/2], table_scale)."""
    factor = float(cfg["factor"])
    attention_factor = cfg.get("attention_factor")
    if attention_factor is None:
        mscale, mscale_all_dim = cfg.get("mscale"), cfg.get("mscale_all_dim")
        if mscale and mscale_all_dim:
            attention_factor = yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)
        else:
            attention_factor = yarn_mscale(factor)
    beta_fast, beta_slow = cfg.get("beta_fast") or 32, cfg.get("beta_slow") or 1
    window = cfg["original_max_position_embeddings"]

    def correction_dim(turns: float) -> float:  # the dim whose frequency turns ``turns`` times over the window
        return dim * math.log(window / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low, high = correction_dim(beta_fast), correction_dim(beta_slow)
    if cfg.get("truncate", True):
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, dim - 1)
    if low == high:
        high += 0.001  # no singularity
    kept = 1.0 - jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0)
    freqs = theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    return (1.0 / (factor * freqs)) * (1.0 - kept) + (1.0 / freqs) * kept, float(attention_factor)


def _llama3_scale_inv_freq(inv_freq: jnp.ndarray, cfg: dict) -> jnp.ndarray:
    """Llama-3.1 NTK-by-parts frequency scaling (mirrors HF's _compute_llama3_parameters)."""
    factor = cfg["factor"]
    low_freq_factor = cfg["low_freq_factor"]
    high_freq_factor = cfg["high_freq_factor"]
    old_context_len = cfg["original_max_position_embeddings"]

    low_freq_wavelen = old_context_len / low_freq_factor
    high_freq_wavelen = old_context_len / high_freq_factor

    wavelen = 2 * jnp.pi / inv_freq
    smooth = (old_context_len / wavelen - low_freq_factor) / (high_freq_factor - low_freq_factor)
    smoothed = (1 - smooth) / factor * inv_freq + smooth * inv_freq
    scaled = jnp.where(wavelen > low_freq_wavelen, inv_freq / factor, inv_freq)
    is_medium = (wavelen <= low_freq_wavelen) & (wavelen >= high_freq_wavelen)
    return jnp.where(is_medium, smoothed, scaled)


def apply_rotary(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """Apply rotary embedding.

    x: [batch, seq, heads, head_dim]; cos/sin: [batch, seq, head_dim].
    Rotation happens in float32; result is cast back to x.dtype.
    """
    xf = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    x1, x2 = xf[..., :half], xf[..., half:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    return (xf * cos + rotated * sin).astype(x.dtype)
