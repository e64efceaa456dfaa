"""The "hit" expert dispatch's kernel (models/moe.py): gated experts (SwiGLU, or ReGLU: one static argument) read out
of a run's STACKED weights (``w1`` / ``w3`` [L, E, h, m], ``w2`` [L, E, m, h])
for the experts a decode step's live rows reach, and no others.

One Pallas call over the grid ``(slot, tile of m)``. The prefetched scalars
are the layer, the expert of every slot and the number of slots in use; the
weights' index maps return ``(layer, slot_expert[s], ...)``, so the pipeline
DMAs tile by tile, double-buffered, straight out of the stack: no operand is
a slice of it. Every row rides every hit expert (the call is bound by the
read; a few rows of MXU work are free) and the combine weight of (slot, row),
zero where the row did not choose the expert, does the selecting. Slots past
``n_hit`` map to the block the last real step read (an unchanged block index
issues no DMA) and skip their compute.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128  # a tile of the expert width is a multiple of the lane width
ROW_TILE = 16  # rows are padded to the bf16 sublane tile
WEIGHT_TILES_BYTES = 48 << 20  # the three weight tiles of a grid step, double-buffered, may take this much VMEM
VMEM_SLACK_BYTES = 8 << 20  # rows, accumulator, the kernel's own temporaries


def tile_width(hidden: int, width: int, itemsize: int) -> int:
    """The widest tile of the expert width ``m`` (a multiple of 128 that
    divides it, or all of it) whose three weight tiles fit
    ``WEIGHT_TILES_BYTES`` double-buffered: Mixtral's 4096 x 14336 in bf16
    takes 1024, K-EXAONE's 6144 x 2048 takes 512, OLMoE's 2048 x 1024 is one
    tile."""
    fits = max(LANES, WEIGHT_TILES_BYTES // (2 * 3 * hidden * itemsize))
    if width <= fits or width % LANES:
        return width
    return max(t for t in range(LANES, fits + 1, LANES) if width % t == 0)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _kernel(layer_ref, slot_ref, n_hit_ref, x_ref, cw_ref, w1_ref, w3_ref, w2_ref, o_ref, *, dot_in_f32: bool, activation: str):
    del layer_ref, slot_ref  # the index maps' business
    s, j = pl.program_id(0), pl.program_id(1)

    @pl.when((s == 0) & (j == 0))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(s < n_hit_ref[0])
    def _():
        x, w1, w3, w2 = x_ref[...], w1_ref[...], w3_ref[...], w2_ref[...]
        if dot_in_f32:  # interpret mode: CPU XLA has no bf16 x bf16 -> f32 dot
            x, w1, w3, w2 = (a.astype(jnp.float32) for a in (x, w1, w3, w2))
        gate = jnp.dot(x, w1, preferred_element_type=jnp.float32)
        up = jnp.dot(x, w3, preferred_element_type=jnp.float32)
        gate = jnp.maximum(gate, 0.0) if activation == "relu" else gate * jax.nn.sigmoid(gate)  # relu | silu
        g = (gate * up * cw_ref[...]).astype(x.dtype)  # act(gate) * up, [rows, tile] x [rows, 1]
        o_ref[...] += jnp.dot(g, w2, preferred_element_type=jnp.float32)


def hit_experts(x, w1, w3, w2, layer, slot_expert, n_hit, combine, *, activation: str = "silu", interpret=None):
    """``sum over s < n_hit of (act(x @ w1[layer, e_s]) * (x @ w3[layer, e_s])
    * combine[s][:, None]) @ w2[layer, e_s]`` in float32, ``e_s =
    slot_expert[s]``, ``act`` the static ``activation`` (``silu`` | ``relu``).

    x: [rows, h]; w1, w3: [L, E, h, m]; w2: [L, E, m, h]; layer, n_hit: int32
    scalars; slot_expert: int32 [S], every entry a held expert, those past
    ``n_hit`` repeating the last one in use; combine: float32 [S, rows].
    Returns float32 [rows, h]."""
    if interpret is None:
        interpret = _interpret()
    rows, h = x.shape
    m = w1.shape[-1]
    n_slots = slot_expert.shape[0]
    tile = tile_width(h, m, w1.dtype.itemsize)
    n_tiles = m // tile
    padded = -(-rows // ROW_TILE) * ROW_TILE
    if padded != rows:
        x = jnp.pad(x, ((0, padded - rows), (0, 0)))
        combine = jnp.pad(combine, ((0, 0), (0, padded - rows)))

    def tile_of(s, j, n_hit_ref):  # a padded slot: the last tile, which the last real step read
        return jnp.where(s < n_hit_ref[0], j, n_tiles - 1)

    def wide(s, j, layer_ref, slot_ref, n_hit_ref):
        return layer_ref[0], slot_ref[s], 0, tile_of(s, j, n_hit_ref)

    def tall(s, j, layer_ref, slot_ref, n_hit_ref):
        return layer_ref[0], slot_ref[s], tile_of(s, j, n_hit_ref), 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_slots, n_tiles),
        in_specs=[
            pl.BlockSpec((padded, h), lambda s, j, *_: (0, 0)),
            pl.BlockSpec((None, padded, 1), lambda s, j, *_: (s, 0, 0)),
            pl.BlockSpec((None, None, h, tile), wide),
            pl.BlockSpec((None, None, h, tile), wide),
            pl.BlockSpec((None, None, tile, h), tall),
        ],
        out_specs=pl.BlockSpec((padded, h), lambda s, j, *_: (0, 0)),
    )
    weight_tiles = 2 * 3 * h * tile * w1.dtype.itemsize
    out = pl.pallas_call(
        functools.partial(_kernel, dot_in_f32=interpret, activation=activation),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((padded, h), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=weight_tiles + VMEM_SLACK_BYTES,
        ),
        interpret=interpret,
        name="moe_hit_experts",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), slot_expert.astype(jnp.int32), jnp.asarray(n_hit, jnp.int32).reshape(1),
        x, combine.astype(jnp.float32)[:, :, None], w1, w3, w2,
    )
    return out[:rows]
