"""Seeded random weights, made where they are used and never written down.

A tensor's elements are a pure function of the configuration's
``weights_seed`` (never ``--seed``), the layer, a per-tensor ``salt`` and the
element's index, in 32-bit integer arithmetic that every backend computes
alike: the server child makes its whole span on the chip in one jitted call
(``span_params``), and the reference makes the same bits layer by layer on the
CPU (``layer_tensors`` of ``perf/weights/<family>.py``, under the published
HF names and layouts). Nothing is read from or written to a disk, so set-up
does not hang on the host's disk and page cache (PERF.md section 6: reading
6 GiB of checkpoint made ``setup_s`` differ by a quarter between hosts).

One file per family (``perf/weights/<family>.py``, found by name) gives

``layer_tensors(hf, layer, draws)``  the layer's tensors under their HF names;
``block_params(hf, tensors)``        those tensors in the layout the served block
    takes: a mirror, in ``jax.numpy``, of the family's ``hf_to_block_params``
    in ``petals_tpu/models/`` (which works in numpy on the host). tests/perf
    holds the two together at a toy size.

A family whose layers are not all alike (``layer_kinds`` in
``perf/reference/<family>.py``) is given each layer's kind as a further
argument of both, and also gives

``span_tree(hf, runs)``  what ``Server._load_span_params`` returns for a span
    of more than one kind, from ``runs``: ``[(first_block, stacked tree), ...]``,
    one entry per run of consecutive blocks of one kind.
"""

from __future__ import annotations

import importlib
import math

STD = 0.02
_BYTES_STD = math.sqrt(4 * (256**2 - 1) / 12)  # of the sum of four uniform bytes


def _fmix(i):
    """MurmurHash3's 32-bit finalizer: a bijection of uint32 whose outputs pass for random bits."""
    import jax.numpy as jnp

    i = i ^ (i >> 16)
    i = i * jnp.uint32(0x85EBCA6B)
    i = i ^ (i >> 13)
    i = i * jnp.uint32(0xC2B2AE35)
    return i ^ (i >> 16)


class Draws:
    """``normal(shape, layer, salt)`` and ``const(shape, value)`` as bfloat16
    ``jax`` arrays; ``layer`` may be traced.

    Element ``i`` of a tensor is the sum of the four bytes of
    ``fmix(fmix(i) + key(seed, layer, salt))``, centred and scaled to ``STD``:
    near normal (bounded at 3.45 sigma, kurtosis 2.7), every layer and tensor
    a stream of its own, and bit for bit the same on the CPU and on the chip
    (integer operations, one float32 product of an exact integer, one
    rounding to bfloat16)."""

    def __init__(self, seed: int):
        self.seed = int(seed) % 2**32

    def normal(self, shape: tuple, layer, salt: int):
        import jax
        import jax.numpy as jnp

        key = _fmix(_fmix(jnp.uint32(self.seed) + jnp.asarray(layer).astype(jnp.uint32) * jnp.uint32(0x9E3779B9))
                    + jnp.uint32((salt * 0x7F4A7C15 + 1) % 2**32))
        bits = _fmix(_fmix(jax.lax.iota(jnp.uint32, math.prod(shape))) + key)
        total = (bits & 0xFF) + ((bits >> 8) & 0xFF) + ((bits >> 16) & 0xFF) + (bits >> 24)
        centred = (total.astype(jnp.int32) - 510).astype(jnp.float32)
        return (centred * jnp.float32(STD / _BYTES_STD)).astype(jnp.bfloat16).reshape(shape)

    @staticmethod
    def const(shape: tuple, value: float):
        import jax.numpy as jnp

        return jnp.full(shape, value, jnp.bfloat16)


def family_of(name: str):
    return importlib.import_module(f"perf.weights.{name}")


def checksum(tensors: dict):
    """The bfloat16 bit patterns of a layer's tensors, summed modulo 2**32:
    what the chip made against what the reference's CPU made."""
    import jax
    import jax.numpy as jnp

    total = jnp.uint32(0)
    for name in sorted(tensors):
        total = total + jax.lax.bitcast_convert_type(tensors[name], jnp.uint16).astype(jnp.uint32).sum(dtype=jnp.uint32)
    return total


def span_params(config: dict, first_block: int, num_blocks: int, dtype) -> tuple:
    """The span's parameters as ``Server._load_span_params`` returns them
    (each leaf stacked over the blocks; for a span of more than one kind of
    layer, what the family's ``span_tree`` makes of its runs), made on the
    default device in one jitted call, and the checksum of the first block's
    HF tensors."""
    import jax
    import jax.numpy as jnp

    from perf.reference import kinds_of

    family, hf = family_of(config["family"]), config["config"]
    kinds = kinds_of(config["family"], hf) or [()] * (first_block + num_blocks)
    starts = [i for i in range(first_block, first_block + num_blocks) if i == first_block or kinds[i] != kinds[i - 1]]

    def make():
        draws = Draws(config["weights_seed"])
        stacked = []
        for start, end in zip(starts, [*starts[1:], first_block + num_blocks]):
            layers = [family.layer_tensors(hf, i, draws, *kinds[start]) for i in range(start, end)]
            blocks = [family.block_params(hf, t, *kinds[start]) for t in layers]
            stacked.append(jax.tree_util.tree_map(lambda *xs: jnp.stack(xs).astype(dtype), *blocks))
            if start == first_block:
                first = checksum(layers[0])
        return stacked, first

    stacked, first = jax.jit(make)()
    return stacked[0] if len(starts) == 1 else family.span_tree(hf, list(zip(starts, stacked))), int(first)
