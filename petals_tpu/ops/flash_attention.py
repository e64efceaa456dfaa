"""Pallas TPU flash attention with prefix KV cache support.

Online-softmax tiled attention over a preallocated KV buffer of which only the
first ``kv_length`` positions are valid. Supports GQA (kv heads shared by query
head groups), BLOOM-style ALiBi bias, and Mixtral-style sliding windows (tiles
beyond the window frontier are skipped like tiles beyond the causal frontier).
Used for prefill / chunked prefill (q_len >= 8, i.e. anything above decode
shapes); the XLA reference path in petals_tpu/ops/attention.py covers decode
(q_len < 8), where the op is bandwidth-bound and XLA fusion is already
optimal. Causal masking is always applied — non-causal requests must use the
XLA path (attend() enforces this).

Replaces the reference's torch SDPA path
(/root/reference/src/petals/models/falcon/block.py:233-244) with a TPU-first
kernel: blocks of Q stay resident in VMEM while KV blocks stream through,
skipping fully-masked tiles (beyond the causal frontier or past kv_length).
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from petals_tpu.telemetry.observatory import tracked_jit


LANES = 128
NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)

# Measured on v5e (8k GQA prefill): 512x1024 tiles run ~5x faster than 128x128
# (27% vs 6% MFU) — the wrapper still caps/halves these to fit small shapes.


def _block_env(name: str, default: int, multiple: int, pow2_multiple: bool = False) -> int:
    raw = os.environ.get(name)
    if raw is None:
        return default
    try:
        val = int(raw)
    except ValueError:
        raise ValueError(f"{name}={raw!r} is not an integer") from None
    if val <= 0 or val % multiple != 0:
        raise ValueError(f"{name}={val} must be a positive multiple of {multiple}")
    if pow2_multiple and (val // multiple) & (val // multiple - 1):
        # the kv fit loop halves block_kv until it divides kv_buf_len; a
        # non-power-of-two multiple (e.g. 384) would never reconcile and
        # collapse to 1
        raise ValueError(f"{name}={val} must be {multiple} times a power of two")
    return val


DEFAULT_BLOCK_Q = _block_env("PETALS_TPU_FLASH_BLOCK_Q", 512, 8)
DEFAULT_BLOCK_KV = _block_env("PETALS_TPU_FLASH_BLOCK_KV", 1024, LANES, pow2_multiple=True)
_TILES_FROM_ENV = (
    "PETALS_TPU_FLASH_BLOCK_Q" in os.environ or "PETALS_TPU_FLASH_BLOCK_KV" in os.environ
)
# v5e VMEM is ~16 MiB/core. The 512x1024 defaults are tuned for head_dim 128 —
# wider heads grow the k/v tiles and the [block_q, head_dim] accumulators, so
# the wrapper shrinks the DEFAULT tiles instead of failing Mosaic VMEM
# allocation (explicit env/arg tile choices are respected as given). The
# budget is calibrated to the estimator below such that the measured-good
# 512x1024 tiles at head_dim 128 are EXACTLY preserved (the estimator is a
# worst-case model, not an exact accounting, hence > 16 MiB).
_VMEM_TILE_BUDGET = 17 * 2**20


def _fit_tiles_to_vmem(block_q: int, block_kv: int, head_dim: int) -> tuple:
    def est(bq, bkv):
        # f32 working set: q/o/acc tiles [bq, head_dim] x3, k+v tiles
        # [bkv, head_dim] x2, s/p/iota tiles [bq, bkv] x3; x2 for Mosaic's
        # pipelining double-buffer
        return 4 * 2 * (3 * bq * head_dim + 2 * bkv * head_dim + 3 * bq * bkv)

    # halve block_kv only while the result stays a multiple of LANES (the
    # lane-aligned s/p tile invariant; halving also preserves divisibility of
    # kv_buf_len), then shrink block_q
    while block_kv % (2 * LANES) == 0 and est(block_q, block_kv) > _VMEM_TILE_BUDGET:
        block_kv //= 2
    while block_q > 8 and est(block_q, block_kv) > _VMEM_TILE_BUDGET:
        block_q //= 2
    return block_q, block_kv


def _tile_needed(q_block_start, kv_block_start, block_q, block_kv, kv_length, sliding_window):
    """Does any (q row, kv col) pair of this tile need computing? Shared by the
    kernel's skip predicate and kv_index_map's DMA-elision redirect — the two
    MUST agree, or a skipped-but-fetched tile silently computes on tile-0 data."""
    # causal frontier: last q row is q_block_start + block_q - 1
    needed = (kv_block_start <= q_block_start + block_q - 1) & (kv_block_start < kv_length)
    if sliding_window is not None:
        # window frontier: the FIRST q row only sees kv > q_block_start - window
        needed &= kv_block_start + block_kv - 1 > q_block_start - sliding_window
    return needed


def _kernel(
    # scalar prefetch
    q_offset_ref,  # int32[1]
    kv_length_ref,  # int32[1]
    slopes_ref,  # float32[num_q_heads]
    # inputs (layout [batch, heads, seq, head_dim] inside the kernel)
    q_ref,  # [1, 1, block_q, head_dim]
    k_ref,  # [1, 1, block_kv, head_dim]
    v_ref,  # [1, 1, block_kv, head_dim]
    # outputs
    o_ref,  # [1, 1, block_q, head_dim]
    # scratch
    m_scratch,  # [block_q, LANES] f32
    l_scratch,  # [block_q, LANES] f32
    acc_scratch,  # [block_q, head_dim] f32
    *,
    scale: float,
    block_q: int,
    block_kv: int,
    num_kv_blocks: int,
    use_alibi: bool,
    sliding_window: Optional[int] = None,
):
    h = pl.program_id(1)
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    q_offset = q_offset_ref[0]
    kv_length = kv_length_ref[0]

    @pl.when(kj == 0)
    def _init():
        m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    q_block_start = q_offset + qi * block_q
    kv_block_start = kj * block_kv
    block_needed = _tile_needed(
        q_block_start, kv_block_start, block_q, block_kv, kv_length, sliding_window
    )

    # Interior tiles sit fully inside every row's visible range: no row of this
    # tile touches the causal frontier, the kv_length tail, or the window edge.
    # They skip mask construction entirely — on an 8k prefill that removes the
    # VPU mask work from ~87% of tiles, which otherwise rivals the softmax cost.
    interior = (kv_block_start + block_kv - 1 <= q_block_start) & (
        kv_block_start + block_kv <= kv_length
    )
    if sliding_window is not None:
        # most restrictive row is the LAST one: it only sees kv > its pos - window
        interior &= kv_block_start >= q_block_start + block_q - sliding_window

    def _tile(masked: bool):
        # keep q/k/v in their storage dtype (bf16): the MXU's bf16 path with
        # f32 accumulate is ~4x the f32 rate, and accuracy comes from the
        # preferred_element_type=f32 accumulator, not from widening the inputs
        q = q_ref[0, 0]  # [bq, d]
        k = k_ref[0, 0]  # [bkv, d]
        v = v_ref[0, 0]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bq, bkv] f32
        s = s * scale

        # ALiBi bias is a row vector: lane-aligned broadcast, cheap on the VPU.
        kv_pos_row = kv_block_start + jax.lax.broadcasted_iota(jnp.int32, (1, block_kv), 1)
        if use_alibi:
            s = s + slopes_ref[h] * kv_pos_row.astype(jnp.float32)

        if masked:
            # Full 2-D iotas: Mosaic lowers these to native vector iotas,
            # which beats broadcasting a [bq, 1] column across lanes.
            kv_pos = kv_block_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1
            )
            q_pos = q_block_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0
            )
            mask = (kv_pos <= q_pos) & (kv_pos < kv_length)
            if sliding_window is not None:
                mask &= kv_pos > q_pos - sliding_window  # Mixtral window semantics
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scratch[...]  # [bq, LANES] (all lanes equal)
        l_prev = l_scratch[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)  # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))  # [bq, LANES]

        alpha = jnp.exp(m_prev[:, :1] - m_new[:, :1])  # [bq, 1]
        p = jnp.exp(s - m_new[:, :1])  # [bq, bkv]
        if masked:
            p = jnp.where(mask, p, 0.0)

        l_new = alpha * l_prev[:, :1] + jnp.sum(p, axis=1, keepdims=True)  # [bq, 1]

        acc = acc_scratch[...]
        # p in the storage dtype for the MXU bf16 path (standard flash trick;
        # the accumulator stays f32)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scratch[...] = acc * alpha + pv

        m_scratch[...] = m_new
        l_scratch[...] = jnp.broadcast_to(l_new, l_scratch.shape)

    @pl.when(block_needed & interior)
    def _compute_interior():
        _tile(masked=False)

    @pl.when(block_needed & jnp.logical_not(interior))
    def _compute_edge():
        _tile(masked=True)

    @pl.when(kj == num_kv_blocks - 1)
    def _finalize():
        l = l_scratch[:, :1]
        out = acc_scratch[...] / jnp.maximum(l, 1e-30)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def flash_supported(q, k, v, *, sliding_window: Optional[int] = None) -> bool:
    """Cheap static check whether the Pallas kernel handles these shapes."""
    if sliding_window is not None and sliding_window <= 0:
        return False
    batch, q_len, num_q_heads, head_dim = q.shape
    _, kv_buf_len, num_kv_heads, _ = k.shape
    if q_len < 8:  # decode path: XLA fusion is better
        return False
    if kv_buf_len % LANES != 0:
        return False
    return True


@tracked_jit(
    name="flash_attend",
    static_argnames=("scale", "block_q", "block_kv", "interpret", "sliding_window"),
)
def flash_attend(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    q_offset: jnp.ndarray | int = 0,
    kv_length: Optional[jnp.ndarray | int] = None,
    alibi_slopes: Optional[jnp.ndarray] = None,
    sliding_window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_kv: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    batch, q_len, num_q_heads, head_dim = q.shape
    _, kv_buf_len, num_kv_heads, _ = k.shape
    assert num_q_heads % num_kv_heads == 0
    group = num_q_heads // num_kv_heads
    if scale is None:
        scale = head_dim**-0.5
    if kv_length is None:
        kv_length = kv_buf_len
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    explicit_tiles = block_q is not None or block_kv is not None or _TILES_FROM_ENV
    block_q = min(block_q or DEFAULT_BLOCK_Q, _round_up(q_len, 8))
    block_kv = min(block_kv or DEFAULT_BLOCK_KV, kv_buf_len)
    while kv_buf_len % block_kv != 0:  # kv_buf_len is a multiple of 128 (flash_supported)
        block_kv //= 2
    if not explicit_tiles:
        block_q, block_kv = _fit_tiles_to_vmem(block_q, block_kv, head_dim)

    # Pad q to a multiple of block_q; padded rows are sliced away afterwards.
    q_pad = _round_up(q_len, block_q) - q_len
    if q_pad:
        q = jnp.pad(q, ((0, 0), (0, q_pad), (0, 0), (0, 0)))
    padded_q_len = q.shape[1]

    # Kernel layout: [batch, heads, seq, head_dim] so the blocked axes are the
    # trailing (seq, head_dim) pair — TPU requires whole-dim blocks elsewhere.
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    num_q_blocks = padded_q_len // block_q
    num_kv_blocks = kv_buf_len // block_kv

    q_offset_arr = jnp.asarray(q_offset, jnp.int32).reshape(1)
    kv_length_arr = jnp.asarray(kv_length, jnp.int32).reshape(1)
    if alibi_slopes is None:
        slopes = jnp.zeros((num_q_heads,), jnp.float32)
        use_alibi = False
    else:
        slopes = alibi_slopes.astype(jnp.float32)
        use_alibi = True

    grid = (batch, num_q_heads, num_q_blocks, num_kv_blocks)

    kernel = functools.partial(
        _kernel,
        scale=scale,
        block_q=block_q,
        block_kv=block_kv,
        num_kv_blocks=num_kv_blocks,
        use_alibi=use_alibi,
        sliding_window=sliding_window,
    )

    def kv_index_map(b, h, qi, kj, q_offset_ref, kv_length_ref, slopes_ref):
        # Redirect the DMA of tiles the kernel will skip (beyond the causal
        # frontier / kv_length tail / before the window edge) to tile 0, which
        # the next q row starts from anyway. Pallas elides copies whose block
        # index repeats, so skipped tiles cost no HBM traffic and no pipeline
        # stall — without this, causal masking still fetched every tile.
        needed = _tile_needed(
            q_offset_ref[0] + qi * block_q, kj * block_kv,
            block_q, block_kv, kv_length_ref[0], sliding_window,
        )
        return (b, h // group, jax.lax.select(needed, kj, 0), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (1, 1, block_q, head_dim), lambda b, h, qi, kj, *prefetch: (b, h, qi, 0)
            ),
            pl.BlockSpec((1, 1, block_kv, head_dim), kv_index_map),
            pl.BlockSpec((1, 1, block_kv, head_dim), kv_index_map),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, head_dim), lambda b, h, qi, kj, *prefetch: (b, h, qi, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, head_dim), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q_offset_arr, kv_length_arr, slopes, qt, kt, vt)

    out = out.transpose(0, 2, 1, 3)
    if q_pad:
        out = out[:, :q_len]
    return out


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m
