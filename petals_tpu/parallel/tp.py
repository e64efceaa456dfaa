"""Tensor-parallel shardings for stacked span parameters
(counterpart of the reference's per-block TP configs,
src/petals/utils/convert_block.py:118-135 + backend.py:88-99, re-expressed as
jax.sharding PartitionSpecs). Which leaf splits how is each family's own
declaration (``ModelFamily.tp_pspecs``; the shared Megatron-style pieces are
in models/common.py); this module holds the axis name, the checks and the
placement.
"""

from __future__ import annotations

from typing import Dict

from jax.sharding import NamedSharding, PartitionSpec as P

COL = "tp"  # axis name used for head/ffn splits


def span_param_pspecs(family_name: str, cfg) -> Dict[str, P]:
    """PartitionSpecs for one family's stacked block params, as the family
    declares them (``ModelFamily.tp_pspecs``, set in ``models/<family>/``)."""
    from petals_tpu.models.registry import get_family

    declared = get_family(family_name).tp_pspecs
    if declared is None:
        raise KeyError(
            f"No TP spec for family {family_name!r}: its ModelFamily declares no "
            f"tp_pspecs, so it cannot be served over a tensor-parallel mesh"
        )
    return declared(cfg)


def kv_cache_pspec() -> P:
    """KV stacks [n_blocks, batch, max_len, kv_heads, head_dim]: shard heads."""
    return P(None, None, None, COL, None)


def quant_leaf_pspecs(q, spec: P):
    """(data_spec, scales_spec) for a QuantizedLinear whose *dense* weight spec
    is ``spec`` (leading stack/expert axes + trailing [in, out]).

    The quantized layouts follow the dense axes directly (the reference
    quantizes after its TP wrap, convert_block.py:25-73 — same composition,
    expressed as shardings):
    - int8: data int8 [..., in, out] shards like the dense weight; scales f32
      [..., out] drop the input axis.
    - nf4/nf4a/int4: data uint8 [..., in/2, out] and scales bf16 [..., in/64, out]
      both follow the dense spec — packed rows and absmax blocks track the
      input axis, so an input-axis (row) split lands whole blocks per shard.
    """
    s = tuple(spec)
    if q.kind == "int8":
        return P(*s), P(*s[:-2], s[-1])
    return P(*s), P(*s)


def validate_tp_divisibility(params, mesh, specs, *, num_kv_heads: int = None) -> None:
    """Fail fast with a clear message instead of an opaque GSPMD error at
    session-open time."""
    from petals_tpu.ops.quant import NF4_BLOCK, QuantizedLinear

    tp_size = mesh.shape.get(COL, 1)
    if tp_size == 1:
        return
    if num_kv_heads is not None and num_kv_heads % tp_size != 0:
        raise ValueError(
            f"num_key_value_heads={num_kv_heads} is not divisible by the tensor-"
            f"parallel axis size {tp_size}; use a smaller tp mesh for this model"
        )
    for name, leaf in params.items():
        spec = specs[name]
        is_quant = isinstance(leaf, QuantizedLinear)
        shape = leaf.shape  # QuantizedLinear.shape is the logical [..., in, out]
        for dim, axis in enumerate(tuple(spec)):
            if axis != COL:
                continue
            if shape[dim] % tp_size != 0:
                raise ValueError(
                    f"Parameter {name!r} dim {dim} (size {shape[dim]}) is not "
                    f"divisible by the tensor-parallel axis size {tp_size}"
                )
            if is_quant and leaf.kind in ("nf4", "nf4a", "int4") and dim == len(shape) - 2:
                # input-axis split: every shard must hold whole absmax blocks
                blocks = leaf.data.shape[-2] * 2 // NF4_BLOCK
                if blocks % tp_size != 0:
                    raise ValueError(
                        f"{leaf.kind} parameter {name!r} has {blocks} absmax blocks, "
                        f"not divisible by the tensor-parallel axis size {tp_size}"
                    )


def shard_span_params(params, mesh, family_name: str, cfg):
    """device_put the stacked params with TP shardings over ``mesh``."""
    import jax

    from petals_tpu.ops.quant import OutlierQuantLinear, QuantizedLinear

    if any(
        isinstance(v, OutlierQuantLinear)
        for v in jax.tree_util.tree_leaves(
            params, is_leaf=lambda x: isinstance(x, OutlierQuantLinear)
        )
    ):
        raise NotImplementedError(
            "outlier-augmented quantization ('+o' kinds) does not compose "
            "with tensor-parallel meshes yet — the outlier side arrays have "
            "no PartitionSpecs; use the base kind (nf4a/int4) under TP"
        )
    specs = span_param_pspecs(family_name, cfg)
    validate_tp_divisibility(
        params, mesh, specs,
        num_kv_heads=getattr(cfg, "num_key_value_heads", cfg.num_attention_heads),
    )
    out = {}
    for name, leaf in params.items():
        if isinstance(leaf, QuantizedLinear):
            data_spec, scales_spec = quant_leaf_pspecs(leaf, specs[name])
            out[name] = QuantizedLinear(
                leaf.kind,
                jax.device_put(leaf.data, NamedSharding(mesh, data_spec)),
                jax.device_put(leaf.scales, NamedSharding(mesh, scales_spec)),
                leaf.in_features,
                leaf.out_features,
            )
        else:
            out[name] = jax.device_put(leaf, NamedSharding(mesh, specs[name]))
    return out
