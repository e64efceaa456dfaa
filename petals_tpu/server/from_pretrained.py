"""Load exactly one transformer block's weights from an HF checkpoint
(counterpart of reference src/petals/server/from_pretrained.py:35-224).

Reads local checkpoint directories (safetensors preferred, torch .bin
fallback) and selects only the tensors belonging to the requested block — the
same "load one block, not the model" capability. Non-directory names resolve
through the streaming Hub fetcher (utils/hub.py): config + shard index first,
then ONLY the shards containing the requested prefixes, with retry + flock'd
LRU disk cache (reference from_pretrained.py:81-128,162-213)."""

from __future__ import annotations

import json
import os
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from petals_tpu.models.registry import ModelFamily, get_family, known_families
from petals_tpu.utils.logging import get_logger

logger = get_logger(__name__)

from petals_tpu.constants import BIN_INDEX, BIN_SINGLE, SAFE_INDEX, SAFE_SINGLE  # noqa: F401 (re-exported)


def resolve_model_path(
    model_name_or_path: str,
    *,
    prefixes: Optional[tuple] = None,
    cache_dir=None,
    max_disk_space: Optional[int] = None,
    revision: str = "main",
) -> str:
    """Local directory, or a repo id resolved through the streaming Hub cache.

    With ``prefixes`` the weight shards containing those tensor prefixes are
    fetched too; without it only config.json is ensured (enough for
    AutoConfig / get_block_config)."""
    if os.path.isdir(model_name_or_path):
        return model_name_or_path
    from petals_tpu.utils import hub

    if prefixes is not None:
        return str(
            hub.ensure_weight_files(
                model_name_or_path, prefixes,
                cache_dir=cache_dir, max_disk_space=max_disk_space, revision=revision,
            )
        )
    return str(
        hub.ensure_config(
            model_name_or_path, cache_dir=cache_dir, max_disk_space=max_disk_space,
            revision=revision,
        )
    )


def load_hf_config(model_name_or_path: str, *, revision: str = "main", cache_dir=None):
    from transformers import AutoConfig, PretrainedConfig
    from transformers.models.auto.configuration_auto import CONFIG_MAPPING

    path = resolve_model_path(model_name_or_path, revision=revision, cache_dir=cache_dir)
    config_dict, _ = PretrainedConfig.get_config_dict(path)
    model_type = config_dict.get("model_type")
    if model_type not in CONFIG_MAPPING and model_type in known_families():
        # a family this build serves and the installed transformers has no
        # class for: the published keys as attributes, as its config_from_hf reads them
        config = PretrainedConfig.from_dict(config_dict)
        config.model_type = model_type
        return config
    return AutoConfig.from_pretrained(path)


def get_block_config(
    model_name_or_path: str, *, revision: str = "main", cache_dir=None
) -> Tuple[ModelFamily, object]:
    hf_config = load_hf_config(model_name_or_path, revision=revision, cache_dir=cache_dir)
    family = get_family(hf_config.model_type)
    return family, family.config_from_hf(hf_config)


def _index_weight_files(path: str) -> Dict[str, str]:
    """Return {tensor_name: filename} for the checkpoint at ``path``."""
    index_file = os.path.join(path, SAFE_INDEX)
    if os.path.exists(index_file):
        with open(index_file) as f:
            return json.load(f)["weight_map"]
    index_file = os.path.join(path, BIN_INDEX)
    if os.path.exists(index_file):
        with open(index_file) as f:
            return json.load(f)["weight_map"]
    for single in (SAFE_SINGLE, BIN_SINGLE):
        fpath = os.path.join(path, single)
        if os.path.exists(fpath):
            return {"*": single}
    raise FileNotFoundError(f"No weight files found in {path}")


def _load_tensors_with_prefixes(
    path: str, prefixes: tuple, *, keep_full_names: bool = False
) -> Dict[str, np.ndarray]:
    """Read only tensors whose name starts with one of ``prefixes`` (names
    returned relative to the matching prefix, or absolute with
    ``keep_full_names`` — use that when prefixes could collide). All candidate
    prefixes are checked in a single pass so each weight file is opened at most
    once (safetensors lazily; .bin state dicts deserialized exactly once —
    reference from_pretrained.py:81-128 semantics)."""
    weight_map = _index_weight_files(path)

    def match(name: str) -> Optional[str]:
        for prefix in prefixes:
            if name.startswith(prefix):
                return name if keep_full_names else name[len(prefix):]
        return None

    if "*" in weight_map:
        files = {weight_map["*"]}
    else:
        files = {fname for name, fname in weight_map.items() if match(name) is not None}

    out: Dict[str, np.ndarray] = {}
    for fname in sorted(files):
        fpath = os.path.join(path, fname)
        if fname.endswith(".safetensors"):
            from safetensors import safe_open

            with safe_open(fpath, framework="pt") as f:
                for name in f.keys():
                    rel = match(name)
                    if rel is not None:
                        out[rel] = _torch_to_numpy(f.get_tensor(name))
        else:
            import torch

            state = torch.load(fpath, map_location="cpu", weights_only=True)
            for name, tensor in state.items():
                rel = match(name)
                if rel is not None:
                    out[rel] = _torch_to_numpy(tensor)
    return out


def _torch_to_numpy(tensor) -> np.ndarray:
    """torch -> numpy, keeping bf16 bit-exact via ml_dtypes (numpy itself has
    no bfloat16; a float32 round-trip would be lossless but 2x the memory)."""
    import torch

    if tensor.dtype == torch.bfloat16:
        import ml_dtypes

        return tensor.view(torch.uint16).numpy().view(ml_dtypes.bfloat16)
    return tensor.numpy()


def load_block_params(
    model_name_or_path: str,
    block_index: int,
    *,
    dtype=jnp.bfloat16,
    device: Optional[jax.Device] = None,
    family: Optional[ModelFamily] = None,
    cfg=None,
    revision: str = "main",
    cache_dir=None,
) -> dict:
    """Load block ``block_index`` and return our parameter pytree on device."""
    if family is None or cfg is None:
        # same revision/cache as the weights, or the architecture could differ
        family, cfg = get_block_config(
            model_name_or_path, revision=revision, cache_dir=cache_dir
        )

    prefixes = tuple(tpl.format(i=block_index) for tpl in family.hf_block_prefixes)
    # for repo ids this streams in exactly the shards holding this block
    path = resolve_model_path(
        model_name_or_path, prefixes=prefixes, revision=revision, cache_dir=cache_dir
    )
    tensors = _load_tensors_with_prefixes(path, prefixes)
    if not tensors:
        raise KeyError(
            f"Block {block_index} not found in {path} under prefixes "
            f"{[p.format(i=block_index) for p in family.hf_block_prefixes]}"
        )

    import inspect

    kind = family.kind_of(cfg, block_index)  # a family whose blocks are not all alike maps each by its kind
    if "block_index" in inspect.signature(family.hf_to_block_params).parameters:
        # per-layer-heterogeneous architectures (gemma2's alternating
        # windows) need to know WHICH block they are mapping
        params = family.block_params_for(tensors, cfg, kind, block_index=block_index)
    else:
        params = family.block_params_for(tensors, cfg, kind)
    cast = lambda x: jnp.asarray(x, dtype) if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating) else jnp.asarray(x)
    params = {
        name: (jnp.asarray(leaf) if name in family.cast_exempt
               else jax.tree_util.tree_map(cast, leaf))
        for name, leaf in params.items()
    }
    if device is not None:
        params = jax.device_put(params, device)
    return params
