"""Tensor-parallel shardings for stacked span parameters
(counterpart of the reference's per-block TP configs,
src/petals/utils/convert_block.py:118-135 + backend.py:88-99, re-expressed as
jax.sharding PartitionSpecs — Megatron-style: attention/MLP input projections
split on the output (head) axis, output projections split on the input axis,
norms replicated; XLA then inserts the psums over ICI).

All leaf shapes have a leading layer axis (the span stack), so weight specs
are (None, <in>, <out>).
"""

from __future__ import annotations

from typing import Dict

from jax.sharding import NamedSharding, PartitionSpec as P

COL = "tp"  # axis name used for head/ffn splits


def span_param_pspecs(family_name: str, cfg) -> Dict[str, P]:
    """PartitionSpecs for one family's stacked block params."""
    if family_name == "llama":
        specs = {
            "ln1": P(),
            "wq": P(None, None, COL),
            "wk": P(None, None, COL),
            "wv": P(None, None, COL),
            "wo": P(None, COL, None),
            "ln2": P(),
            "wg": P(None, None, COL),
            "wu": P(None, None, COL),
            "wd": P(None, COL, None),
        }
        if getattr(cfg, "attention_bias", False):
            specs.update(bq=P(None, COL), bk=P(None, COL), bv=P(None, COL), bo=P())
        if getattr(cfg, "mlp_bias", False):
            specs.update(bg=P(None, COL), bu=P(None, COL), bd=P())
        return specs
    if family_name == "bloom":
        return {
            "ln1_w": P(),
            "ln1_b": P(),
            "wq": P(None, None, COL),
            "bq": P(None, COL),
            "wk": P(None, None, COL),
            "bk": P(None, COL),
            "wv": P(None, None, COL),
            "bv": P(None, COL),
            "wo": P(None, COL, None),
            "bo": P(),
            "ln2_w": P(),
            "ln2_b": P(),
            "w_up": P(None, None, COL),
            "b_up": P(None, COL),
            "w_down": P(None, COL, None),
            "b_down": P(),
        }
    if family_name == "falcon":
        specs = {
            "wq": P(None, None, COL),
            "wk": P(None, None, COL),
            "wv": P(None, None, COL),
            "wo": P(None, COL, None),
            "w_up": P(None, None, COL),
            "w_down": P(None, COL, None),
        }
        if cfg.new_decoder_architecture and cfg.num_ln_in_parallel_attn == 2:
            specs.update(ln_attn_w=P(), ln_attn_b=P(), ln_mlp_w=P(), ln_mlp_b=P())
        else:
            specs.update(ln1_w=P(), ln1_b=P())
            if not cfg.parallel_attn and not cfg.new_decoder_architecture:
                specs.update(ln2_w=P(), ln2_b=P())
        if cfg.bias:
            specs.update(
                bq=P(None, COL), bk=P(None, COL), bv=P(None, COL),
                bo=P(), b_up=P(None, COL), b_down=P(),
            )
        return specs
    if family_name in ("mixtral", "olmoe"):
        specs = {
            "ln1": P(),
            "wq": P(None, None, COL),
            "wk": P(None, None, COL),
            "wv": P(None, None, COL),
            "wo": P(None, COL, None),
            "ln2": P(),
            "gate": P(),
            # experts: shard the expert axis — expert parallelism over the mesh
            # (goes beyond the reference, which keeps experts unsharded)
            "w1": P(None, COL, None, None),
            "w2": P(None, COL, None, None),
            "w3": P(None, COL, None, None),
        }
        if family_name == "olmoe":
            # QK-norm runs over the whole column-sharded q and k projections:
            # its mean spans the shards, which GSPMD sums over ICI like the
            # row-parallel psums; the norm vectors shard with the columns
            specs.update(q_norm=P(None, COL), k_norm=P(None, COL))
        return specs
    raise KeyError(f"No TP spec for family {family_name!r}")


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
