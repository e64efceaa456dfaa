"""Server-side multi-tenant LoRA adapters
(counterpart of reference src/petals/utils/peft.py:31-283).

Many adapters stay resident on a server; each request picks one by name
(reference's context-var pattern becomes a pytree argument, as planned in
SURVEY.md §7.9 — functional JAX has no thread-local "active adapter").

- ``load_adapter(path, family, cfg, block_range)`` reads a PEFT-format
  checkpoint (adapter_config.json + adapter_model.safetensors) and returns
  per-block {leaf_name: LoraDelta} maps for the blocks this server hosts.
- ``apply_adapter(stacked_params, adapter)`` wraps the affected leaves in
  ``LoraLinear`` pytree nodes; ``models.common.mm`` applies
  ``y = x @ W + (x @ A) @ B * scaling`` — same arrays, new structure, so
  switching between same-rank adapters never recompiles.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from petals_tpu.utils.logging import get_logger

logger = get_logger(__name__)

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class LoraLinear:
    """Base weight + low-rank delta; consumed by models.common.mm."""

    base: object  # dense array or QuantizedLinear
    lora_a: jnp.ndarray  # [in, r]
    lora_b: jnp.ndarray  # [r, out]
    scaling: float

    def tree_flatten(self):
        return (self.base, self.lora_a, self.lora_b), (self.scaling,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        base, lora_a, lora_b = children
        return cls(base, lora_a, lora_b, aux[0])


@dataclasses.dataclass
class LoadedAdapter:
    name: str
    scaling: float
    rank: int
    # block index (absolute) -> {leaf_name: (A [in, r], B [r, out])}
    per_block: Dict[int, Dict[str, Tuple[np.ndarray, np.ndarray]]]


def load_adapter(
    adapter_path: str,
    family_name: str,
    *,
    block_range: range,
    name: Optional[str] = None,
) -> LoadedAdapter:
    """Read a PEFT checkpoint directory, keeping only tensors for our blocks
    (reference peft.py:31-69 filters per-block the same way)."""
    with open(os.path.join(adapter_path, "adapter_config.json")) as f:
        cfg = json.load(f)
    rank = cfg["r"]
    scaling = cfg.get("lora_alpha", rank) / rank

    from safetensors import safe_open

    from petals_tpu.models.registry import get_family

    weights_file = os.path.join(adapter_path, "adapter_model.safetensors")
    # HF projection name -> our leaf, as the family declares it
    target_map = get_family(family_name).lora_targets
    per_block: Dict[int, Dict[str, list]] = {}
    n_targeted = 0

    with safe_open(weights_file, framework="pt") as f:
        for key in f.keys():
            parsed = _parse_adapter_key(key, target_map)
            if parsed is None:
                continue
            n_targeted += 1
            block_idx, leaf, which = parsed
            if block_idx not in block_range:
                continue
            tensor = f.get_tensor(key).float().numpy()
            entry = per_block.setdefault(block_idx, {}).setdefault(leaf, [None, None])
            if which == "A":
                entry[0] = np.ascontiguousarray(tensor.T)  # [in, r]
            else:
                entry[1] = np.ascontiguousarray(tensor.T)  # [r, out]

    if not n_targeted:
        # serving the base model under the adapter's name would be a silent no-op
        raise ValueError(
            f"Adapter at {adapter_path!r} holds no LoRA tensor for a projection that family "
            f"{family_name!r} declares (lora_targets: {sorted(target_map)})"
        )
    blocks = {
        idx: {leaf: (a, b) for leaf, (a, b) in leaves.items() if a is not None and b is not None}
        for idx, leaves in per_block.items()
    }
    adapter_name = name or os.path.basename(os.path.normpath(adapter_path))
    total = sum(len(v) for v in blocks.values())
    logger.info(f"Loaded adapter {adapter_name!r}: rank {rank}, {total} wrapped linears")
    return LoadedAdapter(adapter_name, scaling, rank, blocks)


def _parse_adapter_key(key: str, target_map: dict):
    """'...layers.{i}.<module-path>.<proj>.lora_{A,B}.weight' -> (i, leaf, A|B)."""
    parts = key.split(".")
    if "lora_A" in parts:
        which = "A"
    elif "lora_B" in parts:
        which = "B"
    else:
        return None
    try:
        layer_kw = "layers" if "layers" in parts else "h"
        idx = parts[parts.index(layer_kw) + 1]
        block_idx = int(idx)
    except (ValueError, IndexError):
        return None
    proj = parts[parts.index(f"lora_{which}") - 1]
    leaf = target_map.get(proj)
    if leaf is None:
        return None
    return block_idx, leaf, which


def stack_adapter(adapter: LoadedAdapter, first_block: int, n_blocks: int, dtype) -> Dict[str, Tuple]:
    """Per-leaf stacked (A, B) across the span; blocks the adapter doesn't
    touch get zero deltas so the scan stays uniform."""
    leaves = set()
    for blocks in adapter.per_block.values():
        leaves.update(blocks.keys())
    stacked: Dict[str, Tuple] = {}
    for leaf in leaves:
        a_list, b_list = [], []
        ref = next(
            adapter.per_block[i][leaf] for i in adapter.per_block if leaf in adapter.per_block[i]
        )
        a_shape, b_shape = ref[0].shape, ref[1].shape
        for i in range(first_block, first_block + n_blocks):
            entry = adapter.per_block.get(i, {}).get(leaf)
            if entry is None:
                a_list.append(np.zeros(a_shape, np.float32))
                b_list.append(np.zeros(b_shape, np.float32))
            else:
                a_list.append(entry[0])
                b_list.append(entry[1])
        stacked[leaf] = (
            jnp.asarray(np.stack(a_list), dtype),
            jnp.asarray(np.stack(b_list), dtype),
        )
    return stacked


def apply_adapter(stacked_params: dict, stacked_adapter: Dict[str, Tuple], scaling: float) -> dict:
    """Wrap affected leaves with LoraLinear (same structure for all same-rank
    adapters => swapping adapters reuses the compiled step)."""
    out = dict(stacked_params)
    for leaf, (a, b) in stacked_adapter.items():
        if leaf not in out:
            logger.warning(f"Adapter targets unknown leaf {leaf!r}; skipping")
            continue
        out[leaf] = LoraLinear(out[leaf], a, b, scaling)
    return out
