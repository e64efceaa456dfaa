"""Turn vanilla block params into the served artifact: quantization (+ LoRA
adapters are installed by the peft module)
(counterpart of reference src/petals/utils/convert_block.py:25-115 — the
freeze/TP-wrap steps are implicit here: JAX params are immutable and TP is a
sharding applied at backend construction).
"""

from __future__ import annotations

import enum
from typing import Dict, Set

import jax
import jax.numpy as jnp

from petals_tpu.ops.quant import QuantizedLinear, quantize
from petals_tpu.utils.logging import get_logger

logger = get_logger(__name__)


class QuantType(str, enum.Enum):
    NONE = "none"
    INT8 = "int8"  # LLM.int8-class weight-only quantization
    NF4 = "nf4"  # QLoRA-style 4-bit normal float (gather-bound decode on TPU)
    NF4A = "nf4a"  # NF4-fitted cubic levels, gather-free decode: the 4-bit serving default
    INT4 = "int4"  # blockwise affine 4-bit: uniform levels (ops/quant.py)
    # +o: top in/64 outlier input channels kept dense bf16 (4.5 bits/param;
    # ~+5-6 dB output SNR in the outlier-channel regime trained transformers
    # live in — the reference's int8 outlier threshold, applied at 4 bits)
    NF4A_O = "nf4a+o"
    INT4_O = "int4+o"


# The big matmul weights of each family (norms/biases/router stay dense).
QUANTIZABLE_LEAVES: Dict[str, Set[str]] = {
    "llama": {"wq", "wk", "wv", "wo", "wg", "wu", "wd"},
    "bloom": {"wq", "wk", "wv", "wo", "w_up", "w_down"},
    "falcon": {"wq", "wk", "wv", "wo", "w_up", "w_down"},
    # expert stacks (w1/w2/w3) carry >90% of Mixtral's params — quantized
    # per-expert (3-D leaves), unlike the reference which also quantizes them
    "mixtral": {"wq", "wk", "wv", "wo", "w1", "w2", "w3"},
    # the same leaves; the two QK-norm vectors stay dense like every norm
    "olmoe": {"wq", "wk", "wv", "wo", "w1", "w2", "w3"},
    "gemma2": {"wq", "wk", "wv", "wo", "wg", "wu", "wd"},
}


# Leaves fused into one matmul each for quantized single-chip serving: every
# Pallas custom call carries a fixed launch/boundary cost (~0.2 ms in the July
# 2026 v5e record; not measured on the current chip), so 7 calls/block -> 4 speeds up
# decode. Fusion happens on the DENSE weights before quantization: 4-bit/int8
# scales are per-output-column, so the fused quantization is bit-identical to
# quantizing separately. Biases (qwen2) fuse alongside.
_FUSE_GROUPS: Dict[str, tuple] = {
    "llama": (
        ("wqkv", ("wq", "wk", "wv"), "bqkv", ("bq", "bk", "bv")),
        ("wgu", ("wg", "wu"), "bgu", ("bg", "bu")),
    ),
    "gemma2": (
        ("wqkv", ("wq", "wk", "wv"), "bqkv", ("bq", "bk", "bv")),
        ("wgu", ("wg", "wu"), "bgu", ("bg", "bu")),
    ),
}


def _block_arch(family_name: str) -> str:
    """Resolve a family name to the block architecture keying the tables above
    (qwen2/mistral are llama-architecture blocks registered under their own
    model_type; quantization must not silently no-op for them)."""
    if family_name in QUANTIZABLE_LEAVES:
        return family_name
    from petals_tpu.models import registry

    try:
        family = registry.get_family(family_name)
    except KeyError:
        return family_name
    return family.block_arch or family.name


def convert_block_params(
    params: dict, family_name: str, quant_type: QuantType, *, fuse: bool = False
) -> dict:
    """Quantize one (unstacked) block's matmul weights in place of dense leaves.

    ``fuse=True`` additionally merges qkv / gate+up into single leaves (llama
    family, which qwen2/mistral share). Callers must keep it off under tensor
    parallelism (the fused output axis breaks the per-leaf PartitionSpecs) and
    when hosting LoRA adapters (they target the unfused leaf names).
    """
    quant_type = QuantType(quant_type)
    if quant_type == QuantType.NONE:
        return params
    arch = _block_arch(family_name)
    if fuse:
        for fused_w, parts, fused_b, bias_parts in _FUSE_GROUPS.get(arch, ()):
            if all(p in params for p in parts):
                params = dict(params)
                fused = jnp.concatenate([jnp.asarray(params.pop(p)) for p in parts], axis=1)
                params[fused_w] = fused
                if all(b in params for b in bias_parts):
                    params[fused_b] = jnp.concatenate(
                        [jnp.asarray(params.pop(b)) for b in bias_parts], axis=0
                    )
    quantizable = QUANTIZABLE_LEAVES.get(arch, set()) | {"wqkv", "wgu"}
    out = {}
    n_quantized = 0
    leaf_names = sorted(params)  # the pop-loop empties params; keep for errors
    # consume OUR view of the dict leaf by leaf so each dense weight can be
    # freed as soon as its quantized form exists — at 405B shapes the dense
    # block alone is ~6.4 GiB, and holding every dense leaf until the loop
    # ends (while packed leaves accumulate) is part of what pushed
    # quantize-at-load past the 16 GiB chip (see _encode_4bit_chunked). Only
    # helps when the caller drops its own reference, which the load paths do.
    params = dict(params)
    for name in list(params):
        leaf = params.pop(name)
        ndim = getattr(leaf, "ndim", 0)
        if name in quantizable and ndim == 2:
            out[name] = quantize(jnp.asarray(leaf), quant_type.value)
            n_quantized += 1
        elif name in quantizable and ndim == 3:  # expert stacks [E, in, out]
            # expert stacks use the BASE kind: models/moe.py slices
            # experts itself and the outlier side-arrays don't ride that path
            base = quant_type.value[:-2] if quant_type.value.endswith("+o") else quant_type.value
            per_expert = [quantize(jnp.asarray(leaf[e]), base) for e in range(leaf.shape[0])]
            out[name] = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_expert)
            n_quantized += 1
        else:
            out[name] = leaf
    if not n_quantized:
        # A silent no-op here would serve dense weights while the operator
        # believes the model is quantized (wrong memory footprint AND
        # throughput advert) — refuse instead.
        detail = f"family {family_name!r}" if family_name == arch else (
            f"family {family_name!r} (block arch {arch!r})"
        )
        from petals_tpu.models import registry

        known = registry.known_families()
        hint = (
            "QUANTIZABLE_LEAVES needs an entry for this block architecture"
            if family_name in known
            else f"family is not registered (known: {list(known)})"
        )
        raise ValueError(
            f"quant_type={quant_type.value!r} requested but no quantizable "
            f"leaves matched for {detail} (leaves: {leaf_names}); {hint}"
        )
    return out


def block_size_bytes(params: dict) -> int:
    from petals_tpu.ops.quant import OutlierQuantLinear

    total = 0
    for leaf in params.values():
        if isinstance(leaf, (QuantizedLinear, OutlierQuantLinear)):
            total += leaf.nbytes
        else:
            total += leaf.size * leaf.dtype.itemsize
    return total
