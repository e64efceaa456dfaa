"""Ring attention: sequence-parallel causal attention over a device mesh axis.

The reference has NO sequence/context parallelism (SURVEY.md §2.2 — its long
-sequence story is chunked prefill only); this build treats long context as
first-class: activations are sharded along the sequence axis over the "sp"
mesh axis, and K/V shards rotate around the ring via ``lax.ppermute`` while
each device folds every visiting block into a flash-style online softmax. HBM
per device stays O(seq / ring_size); the ICI ring carries one K/V shard per
step, overlapped by XLA with the local compute.

Use ``ring_attend`` inside ``shard_map`` (see ``ring_attention_sharded`` for
the wrapped version used by tests and the training dry-run).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def ring_attend(
    q: jnp.ndarray,  # [b, s_local, hq, d] — this device's query shard
    k: jnp.ndarray,  # [b, s_local, hkv, d] — this device's K shard
    v: jnp.ndarray,
    *,
    axis_name: str = "sp",
    scale: Optional[float] = None,
    alibi_slopes: Optional[jnp.ndarray] = None,  # [hq_local] BLOOM-style slopes
    sliding_window: Optional[int] = None,  # Mixtral window, on GLOBAL positions
) -> jnp.ndarray:
    """Causal attention across the full (sharded) sequence. Call under
    shard_map with q/k/v sharded on the sequence axis over ``axis_name``.
    ALiBi bias and sliding windows follow ops/attention.py semantics on
    GLOBAL positions, so every family's attention can ride the ring."""
    batch, s_local, hq, d = q.shape
    hkv = k.shape[2]
    group = hq // hkv
    if scale is None:
        scale = d**-0.5

    n_ring = jax.lax.axis_size(axis_name)
    my_rank = jax.lax.axis_index(axis_name)
    q_pos = my_rank * s_local + jnp.arange(s_local, dtype=jnp.int32)  # global positions

    qf = q.astype(jnp.float32)

    def fold(carry, kv_block, source_rank):
        m_prev, l_prev, acc = carry
        k_blk, v_blk = kv_block
        kv_pos = source_rank * s_local + jnp.arange(s_local, dtype=jnp.int32)

        qg = qf.reshape(batch, s_local, hkv, group, d)
        logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, k_blk.astype(jnp.float32)) * scale
        logits = logits.reshape(batch, hq, s_local, s_local)
        if alibi_slopes is not None:
            # bias is a function of the absolute kv position only (BLOOM
            # build_alibi_tensor semantics, ops/attention.py:19-21), unscaled
            bias = alibi_slopes[:, None, None] * kv_pos.astype(jnp.float32)[None, None, :]
            logits = logits + bias[None]

        mask = kv_pos[None, :] <= q_pos[:, None]  # causal over GLOBAL positions
        if sliding_window is not None:
            mask = mask & (kv_pos[None, :] > q_pos[:, None] - sliding_window)
        logits = jnp.where(mask[None, None], logits, NEG_INF)

        m_cur = logits.max(axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new[..., None])
        p = jnp.where(mask[None, None], p, 0.0)
        l_new = alpha * l_prev + p.sum(axis=-1)

        pg = p.reshape(batch, hkv, group, s_local, s_local)
        pv = jnp.einsum("bkgqs,bskd->bkgqd", pg, v_blk.astype(jnp.float32))
        pv = pv.reshape(batch, hq, s_local, d)
        acc = acc * alpha[..., None] + pv
        return (m_new, l_new, acc)

    m0 = jnp.full((batch, hq, s_local), NEG_INF, jnp.float32)
    l0 = jnp.zeros((batch, hq, s_local), jnp.float32)
    acc0 = jnp.zeros((batch, hq, s_local, d), jnp.float32)

    def ring_step(i, state):
        (k_blk, v_blk), carry = state
        source_rank = (my_rank - i) % n_ring
        carry = fold(carry, (k_blk, v_blk), source_rank)
        # rotate: receive the previous rank's shard (so next iteration holds
        # the shard that started i+1 ranks behind us)
        perm = [(j, (j + 1) % n_ring) for j in range(n_ring)]
        k_next = jax.lax.ppermute(k_blk, axis_name, perm)
        v_next = jax.lax.ppermute(v_blk, axis_name, perm)
        return ((k_next, v_next), carry)

    (_, (m, l, acc)) = jax.lax.fori_loop(0, n_ring, ring_step, ((k, v), (m0, l0, acc0)))

    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # [b, s_local, hq, d]


def ring_attention_sharded(
    q: jnp.ndarray,  # [b, seq, hq, d] — full arrays (sharded by the caller's jit)
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    *,
    axis_name: str = "sp",
    alibi_slopes: Optional[jnp.ndarray] = None,  # [hq]
    sliding_window: Optional[int] = None,
) -> jnp.ndarray:
    """shard_map wrapper: shards the sequence axis over ``axis_name`` and runs
    the ring. seq must divide the axis size. When the mesh also has a "tp"
    axis, heads ride it (Megatron layout) — the ring math is per-head, so tp
    and sp compose with no extra collectives; ALiBi slopes shard with the
    heads."""
    head_axis = "tp" if "tp" in mesh.axis_names and mesh.shape["tp"] > 1 else None
    spec = P(None, axis_name, head_axis, None)
    # one shard_map for both cases: placeholder slopes when None, dropped
    # inside the per-shard fn (the _flash_sharded pattern, ops/attention.py)
    use_alibi = alibi_slopes is not None
    slopes = alibi_slopes if use_alibi else jnp.zeros((q.shape[2],), jnp.float32)

    def per_shard(q_, k_, v_, slopes_):
        return ring_attend(
            q_, k_, v_, axis_name=axis_name,
            alibi_slopes=slopes_ if use_alibi else None,
            sliding_window=sliding_window,
        )

    # check_vma off: the flash kernel inside has no replication rule
    fn = jax.shard_map(
        per_shard,
        mesh=mesh,
        in_specs=(spec, spec, spec, P(head_axis)),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v, slopes)
