"""Turn vanilla block params into the served artifact: quantization (+ LoRA
adapters are installed by the peft module)
(counterpart of reference src/petals/utils/convert_block.py:25-115 — the
freeze/TP-wrap steps are implicit here: JAX params are immutable and TP is a
sharding applied at backend construction).
"""

from __future__ import annotations

import enum

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


def convert_block_params(
    params: dict, family_name: str, quant_type: QuantType, *, fuse: bool = False
) -> dict:
    """Quantize one (unstacked) block's matmul weights in place of dense leaves.

    Which leaves quantize, and which fuse, is the family's own declaration
    (``ModelFamily.quantizable_leaves`` / ``fuse_groups``). ``fuse=True``
    additionally merges qkv / gate+up into single leaves where the family
    declares the groups (the llama block and those built over it). Callers
    must keep it off under tensor parallelism (the fused output axis breaks
    the per-leaf PartitionSpecs) and when hosting LoRA adapters (they target
    the unfused leaf names).
    """
    quant_type = QuantType(quant_type)
    if quant_type == QuantType.NONE:
        return params
    from petals_tpu.models import registry

    known = registry.known_families()
    # an unregistered name has no leaves to quantize: refused below, like a
    # family whose declaration matches nothing in this block
    quantizable, fuse_groups = set(), ()
    if family_name in known:
        family = registry.get_family(family_name)
        quantizable, fuse_groups = set(family.quantizable_leaves), family.fuse_groups
    quantizable.update(group[0] for group in fuse_groups)
    if fuse:
        for fused_w, parts, fused_b, bias_parts in fuse_groups:
            if all(p in params for p in parts):
                params = dict(params)
                fused = jnp.concatenate([jnp.asarray(params.pop(p)) for p in parts], axis=1)
                params[fused_w] = fused
                if all(b in params for b in bias_parts):
                    params[fused_b] = jnp.concatenate(
                        [jnp.asarray(params.pop(b)) for b in bias_parts], axis=0
                    )
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
        hint = (
            "its ModelFamily declares no quantizable_leaves that this block holds"
            if family_name in known
            else f"family is not registered (known: {list(known)})"
        )
        raise ValueError(
            f"quant_type={quant_type.value!r} requested but no quantizable "
            f"leaves matched for family {family_name!r} (leaves: {leaf_names}); {hint}"
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
