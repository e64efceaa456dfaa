"""Block sizing + auto num_blocks choice
(counterpart of reference src/petals/server/block_utils.py:12-65 +
server.py:275-326 `_choose_num_blocks`)."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from petals_tpu.ops.quant import BITS_PER_PARAM
from petals_tpu.utils.logging import get_logger

logger = get_logger(__name__)

AUTOGRAD_RESERVE_FRACTION = 0.15  # headroom for activations/backward buffers


def block_params_count(family, cfg, block_index: int = 0) -> int:
    """Parameters of the model's block ``block_index`` (any block, for a
    family whose blocks are all alike)."""
    shapes = family.param_shapes_for(cfg, family.kind_of(cfg, block_index), jnp.bfloat16)
    return int(sum(np.prod(s.shape) for s in shapes.values()))


def estimated_block_size_bytes(family, cfg, quant_type: str = "none", block_index: int = 0) -> int:
    """Bytes of one served block at the given quantization
    (reference block_utils.py:22-53; NF4 = 4.25 bits/param)."""
    return int(block_params_count(family, cfg, block_index) * BITS_PER_PARAM[quant_type] / 8)


# HBM per chip by jax ``device_kind``, for a TPU runtime whose memory_stats()
# carries no bytes_limit. An unknown kind is an error, never a guess.
HBM_BYTES_BY_DEVICE_KIND = {
    "TPU v5 lite": 16 * 2**30,  # v5e (Google Cloud "TPU v5e": 16 GB HBM2e per chip)
    "TPU v5e": 16 * 2**30,
}


def device_memory_bytes() -> Optional[int]:
    """Total memory of the first local accelerator: what the runtime reports,
    else the table above. None on the CPU backend (no device budget to size
    against — callers fall back to serving every block)."""
    device = jax.local_devices()[0]
    stats = device.memory_stats()
    if stats and "bytes_limit" in stats:
        return int(stats["bytes_limit"])
    if device.platform == "cpu":
        return None
    if device.device_kind not in HBM_BYTES_BY_DEVICE_KIND:
        raise RuntimeError(
            f"{device.platform} device {device.device_kind!r} reports no memory_stats() "
            f"bytes_limit and is not in HBM_BYTES_BY_DEVICE_KIND; pass --num_blocks and "
            f"--attn_cache_tokens explicitly or add the device"
        )
    return HBM_BYTES_BY_DEVICE_KIND[device.device_kind]


def choose_num_blocks(
    family,
    cfg,
    *,
    quant_type: str = "none",
    attn_cache_bytes: int = 0,
    memory_limit_bytes: Optional[int] = None,
) -> int:
    """How many blocks fit this chip alongside the KV budget + autograd reserve
    (reference server.py:275-326)."""
    memory = memory_limit_bytes or device_memory_bytes()
    if memory is None:
        logger.warning("Unknown device memory; defaulting to serving all blocks")
        return cfg.num_hidden_layers
    usable = memory * (1 - AUTOGRAD_RESERVE_FRACTION) - attn_cache_bytes
    # summed block by block (a family's blocks need not be all alike), and the span may start anywhere
    # (placement comes later): the most consecutive blocks that fit wherever they start
    by_kind = {}
    sizes = [
        by_kind.setdefault(kind, estimated_block_size_bytes(family, cfg, quant_type, i))
        for i, kind in enumerate(family.span_kinds(cfg, 0, cfg.num_hidden_layers))
    ]
    n = max(
        (n for n in range(1, len(sizes) + 1)
         if max(sum(sizes[i : i + n]) for i in range(len(sizes) - n + 1)) <= usable),
        default=1,
    )
    each = f"{min(sizes) / 2**20:.0f}" + (f"-{max(sizes) / 2**20:.0f}" if len(by_kind) > 1 else "")
    logger.info(
        f"Auto-selected {n} blocks ({each} MiB each, "
        f"{memory / 2**30:.1f} GiB device memory, quant={quant_type})"
    )
    return n
