"""Attention with prefix KV cache — the hot op of the server.

Canonical layouts (TPU-friendly, head_dim last so lanes stay 128-aligned):
- q:    [batch, q_len, num_q_heads, head_dim]
- k, v: [batch, kv_len, num_kv_heads, head_dim]  (GQA: num_q_heads % num_kv_heads == 0)

Semantics: query position i has absolute position ``q_offset + i`` and may attend
to kv positions ``j`` with ``j <= q_offset + i`` and ``j < kv_length`` (the valid
prefix of a preallocated cache buffer). This one op covers prefill (q_len == kv
written so far), chunked prefill (q_offset > 0), and decode (q_len == 1).

Replaces the reference's torch SDPA / CUDA-graph paths
(/root/reference/src/petals/models/falcon/block.py:233-244,
 /root/reference/src/petals/models/llama/block.py:92-95). A Pallas
flash-attention kernel (petals_tpu/ops/flash_attention.py) is used on TPU for
long sequences; this XLA einsum path is the numerics reference and the
small-shape fallback (XLA already fuses it well at decode shapes).

ALiBi follows BLOOM's definition: bias[h, j] = slopes[h] * j (a function of the
absolute kv position only — matches HF ``build_alibi_tensor`` with a full mask).
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


def _attend_sharded(
    q, k, v, mesh, *, q_offset, kv_length, alibi_slopes, sliding_window,
    use_flash, shard_seq: bool = False, scale=None,
):
    """Sharded attention dispatch over a device mesh.

    Heads shard over a "tp" axis when present (Megatron layout, parallel/tp.py
    — the math is per-head, so no cross-shard comms; shard_map gives Mosaic
    the per-device view GSPMD cannot derive for a custom call). With
    ``shard_seq`` the QUERY sequence additionally shards over the "sp" axis —
    the KV-cached prefill path, where each device attends its query shard
    against the replicated cache with a rank-adjusted ``q_offset``."""
    import jax
    from jax.sharding import PartitionSpec as P

    head_axis = "tp" if mesh.shape.get("tp", 1) > 1 else None
    seq_axis = "sp" if shard_seq else None
    qspec = P(None, seq_axis, head_axis, None)
    kvspec = P(None, None, head_axis, None)
    use_alibi = alibi_slopes is not None
    slopes = (
        alibi_slopes if use_alibi else jnp.zeros((q.shape[2],), jnp.float32)
    )
    if kv_length is None:
        kv_length = k.shape[1]

    def per_shard(q_, k_, v_, q_offset_, kv_length_, slopes_):
        if shard_seq:
            q_offset_ = q_offset_ + jax.lax.axis_index("sp") * q_.shape[1]
        return attend(
            q_, k_, v_,
            q_offset=q_offset_,
            kv_length=kv_length_,
            alibi_slopes=slopes_ if use_alibi else None,
            sliding_window=sliding_window,
            scale=scale,
            use_flash=use_flash,  # per-device: the Mosaic kernel needs no GSPMD rule here
        )

    # check_vma off: the Mosaic custom call inside has no replication rule
    fn = jax.shard_map(
        per_shard,
        mesh=mesh,
        in_specs=(qspec, kvspec, kvspec, P(), P(), P(head_axis)),
        out_specs=qspec,
        check_vma=False,
    )
    return fn(
        q, k, v,
        jnp.asarray(q_offset, jnp.int32), jnp.asarray(kv_length, jnp.int32), slopes,
    )


def attend(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    q_offset: jnp.ndarray | int = 0,
    kv_length: Optional[jnp.ndarray | int] = None,
    alibi_slopes: Optional[jnp.ndarray] = None,
    sliding_window: Optional[int] = None,
    scale: Optional[float] = None,
    causal: bool = True,
    use_flash: bool = False,
    tp_mesh=None,
    logit_softcap: Optional[float] = None,  # forces the XLA path (no flash rule)
) -> jnp.ndarray:
    """Multi-head attention with causal masking over a prefix-valid KV buffer.

    Args:
      q: [b, sq, hq, d]; k/v: [b, skv, hkv, d] — skv is the *buffer* length.
      q_offset: absolute position of q[:, 0] (scalar, may be traced).
      kv_length: number of valid kv positions (defaults to skv).
      alibi_slopes: [hq] BLOOM-style slopes, or None.
      sliding_window: if set, queries attend only to the last `sliding_window`
        positions (Mixtral). Applied on absolute positions.
      scale: softmax scale; default 1/sqrt(d).
      causal: apply causal mask (True for all served models).
      use_flash: route to the Pallas flash kernel when shapes allow.
      tp_mesh: tensor-parallel Mesh with a "tp" axis — heads are sharded over
        it, so the Mosaic kernel (no GSPMD rule) runs per-shard via shard_map.
    """
    # paged KV: the (pool, block-table) pair rides through the family block
    # as a dense-buffer stand-in; route to the decode walk, the prefill kernel
    # or the gather (ops/paged_flash_attention.py). Import is local —
    # paged_attention imports attend_reference from this module at load time.
    from petals_tpu.ops.paged_attention import PagedKV

    if isinstance(k, PagedKV):
        from petals_tpu.ops.paged_flash_attention import paged_attend_dispatch

        return paged_attend_dispatch(
            q, k, v,
            q_offset=q_offset, kv_length=kv_length,
            alibi_slopes=alibi_slopes, sliding_window=sliding_window,
            scale=scale, causal=causal, logit_softcap=logit_softcap,
        )
    # per-lane positions ([batch] vectors, continuous batching) run the XLA
    # path: decode shapes never route to the flash kernel anyway, and the
    # Mosaic kernel takes scalar offsets only
    vector_pos = (
        getattr(jnp.asarray(q_offset), "ndim", 0) > 0
        or (kv_length is not None and getattr(jnp.asarray(kv_length), "ndim", 0) > 0)
    )
    if use_flash and causal and not vector_pos and logit_softcap is None:
        from petals_tpu.ops.flash_attention import flash_attend, flash_supported

        if flash_supported(q, k, v, sliding_window=sliding_window):
            if tp_mesh is not None:
                return _attend_sharded(
                    q, k, v, tp_mesh,
                    q_offset=q_offset, kv_length=kv_length,
                    alibi_slopes=alibi_slopes, sliding_window=sliding_window,
                    scale=scale, use_flash=True,
                )
            return flash_attend(
                q,
                k,
                v,
                q_offset=q_offset,
                kv_length=kv_length,
                alibi_slopes=alibi_slopes,
                sliding_window=sliding_window,
                scale=scale,
            )
    return attend_reference(
        q,
        k,
        v,
        q_offset=q_offset,
        kv_length=kv_length,
        alibi_slopes=alibi_slopes,
        sliding_window=sliding_window,
        scale=scale,
        causal=causal,
        logit_softcap=logit_softcap,
    )


def attend_maybe_ring(
    q: jnp.ndarray,
    k_all: jnp.ndarray,
    v_all: jnp.ndarray,
    *,
    kv,  # the block's incoming cache (None on the stateless training path)
    position,
    n_valid,
    kv_length,
    ring_mesh,
    use_flash: bool = False,
    tp_mesh=None,
    alibi_slopes: Optional[jnp.ndarray] = None,
    sliding_window: Optional[int] = None,
) -> jnp.ndarray:
    """The one attention dispatch every family block uses: sequence-parallel
    attention when a mesh with an "sp" axis is given — a K/V-rotating ring on
    the stateless full-sequence path (K/V never materialize whole per device),
    QUERY-sequence sharding on the KV-cached path (the cache must end up
    replicated for tp-only decode anyway, so each device attends its query
    shard against the replicated buffer; rotating K/V would add ICI traffic
    for zero memory benefit) — plain ``attend`` otherwise. Centralised so the
    preconditions are enforced in exactly one place."""
    if ring_mesh is not None and kv is None:
        if n_valid is not None or not isinstance(position, int) or position != 0:
            raise ValueError(
                "ring attention serves the stateless full-sequence path: "
                "position must be literal 0 and n_valid None (no padded chunks)"
            )
        from petals_tpu.ops.ring_attention import ring_attention_sharded

        return ring_attention_sharded(
            q, k_all, v_all, ring_mesh,
            alibi_slopes=alibi_slopes, sliding_window=sliding_window,
        )
    if ring_mesh is not None and kv is not None:
        sp = ring_mesh.shape.get("sp", 1)
        seq = q.shape[1]
        if sp > 1 and seq > 1 and seq % sp == 0:
            # KV-cached prefill under sequence parallelism: queries shard over
            # "sp", the cache buffer stays replicated. Composes with chunked
            # prefill (dynamic position/kv_length) and padded buckets (padding
            # rows are masked by kv_length and sliced away by the caller).
            return _attend_sharded(
                q, k_all, v_all, ring_mesh,
                q_offset=position, kv_length=kv_length,
                alibi_slopes=alibi_slopes, sliding_window=sliding_window,
                use_flash=use_flash, shard_seq=True,
            )
        # decode (seq == 1) and indivisible chunks fall through to tp-only
    return attend(
        q, k_all, v_all,
        q_offset=position, kv_length=kv_length,
        alibi_slopes=alibi_slopes, sliding_window=sliding_window,
        use_flash=use_flash, tp_mesh=tp_mesh,
    )


def attend_reference(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    q_offset: jnp.ndarray | int = 0,
    kv_length: Optional[jnp.ndarray | int] = None,
    alibi_slopes: Optional[jnp.ndarray] = None,
    sliding_window: Optional[int] = None,
    scale: Optional[float] = None,
    causal: bool = True,
    logit_softcap: Optional[float] = None,  # gemma-2: tanh(l/cap)*cap pre-mask
) -> jnp.ndarray:
    batch, q_len, num_q_heads, head_dim = q.shape
    _, kv_buf_len, num_kv_heads, _ = k.shape
    assert num_q_heads % num_kv_heads == 0, (num_q_heads, num_kv_heads)
    group = num_q_heads // num_kv_heads
    if scale is None:
        scale = head_dim**-0.5
    if kv_length is None:
        kv_length = kv_buf_len

    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    # [b, hq, sq, skv] logits via GQA grouping: fold q heads as (hkv, group)
    qg = qf.reshape(batch, q_len, num_kv_heads, group, head_dim)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, kf) * scale
    logits = logits.reshape(batch, num_q_heads, q_len, kv_buf_len)

    if logit_softcap is not None:
        logits = jnp.tanh(logits / logit_softcap) * logit_softcap

    kv_pos = jnp.arange(kv_buf_len, dtype=jnp.int32)
    if alibi_slopes is not None:
        bias = alibi_slopes[:, None, None] * kv_pos.astype(jnp.float32)[None, None, :]
        logits = logits + bias[None]

    # q_offset / kv_length may be scalars (one shared history length) or
    # [batch] vectors (per-lane positions, continuous batching); reshape(-1)
    # gives a length-1-or-batch leading axis that broadcasts either way
    q_off = jnp.asarray(q_offset, jnp.int32).reshape(-1, 1)  # [1|b, 1]
    q_pos = q_off + jnp.arange(q_len, dtype=jnp.int32)[None, :]  # [1|b, q]
    kv_len = jnp.asarray(kv_length, jnp.int32).reshape(-1, 1, 1)  # [1|b, 1, 1]
    mask = kv_pos[None, None, :] < kv_len  # [1|b, 1, skv]
    if causal:
        mask = mask & (kv_pos[None, None, :] <= q_pos[:, :, None])
    if sliding_window is not None:
        mask = mask & (kv_pos[None, None, :] > q_pos[:, :, None] - sliding_window)
    mask = jnp.broadcast_to(mask, (mask.shape[0], q_len, kv_buf_len))

    logits = jnp.where(mask[:, None], logits, DEFAULT_MASK_VALUE)
    weights = jnp.exp(logits - logits.max(axis=-1, keepdims=True))
    weights = weights * mask[:, None]
    weights = weights / jnp.maximum(weights.sum(axis=-1, keepdims=True), 1e-30)

    wg = weights.reshape(batch, num_kv_heads, group, q_len, kv_buf_len)
    out = jnp.einsum("bkgqs,bskd->bqkgd", wg, vf)
    return out.reshape(batch, q_len, num_q_heads, head_dim).astype(q.dtype)
