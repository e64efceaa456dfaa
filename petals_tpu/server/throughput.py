"""Server throughput self-measurement
(counterpart of reference src/petals/server/throughput.py:37-237).

Measures, per block:
- inference_rps: 1-token decode steps/sec through a real jitted block
- forward_rps:   1024-token forward tokens/sec
- network_rps:   how many requests/sec the wire could carry, from a loopback
  serialization+framing probe (the reference shells out to speedtest-cli; a
  private TPU swarm measures its own stack instead — pass --network_mbps to
  override with a known WAN budget)

Results are cached in a fcntl-locked JSON file keyed by (model shape, dtype,
quant, version) — reference throughput.py:53-94.
"""

from __future__ import annotations

import fcntl
import json
import os
import time
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

import petals_tpu
from petals_tpu.utils.logging import get_logger

logger = get_logger(__name__)

DEFAULT_CACHE_PATH = Path(os.environ.get("PETALS_TPU_CACHE", Path.home() / ".cache" / "petals_tpu"))
THROUGHPUT_FILE = "throughput_v2.json"  # v2: compute-only entries (network always fresh)
RELAY_PENALTY = 0.2  # reference throughput.py:47


def get_server_throughput(
    family,
    cfg,
    *,
    compute_dtype=jnp.bfloat16,
    n_steps_inference: int = 50,
    n_steps_forward: int = 5,
    network_mbps: Optional[float] = None,
    num_blocks: int = 1,
    using_relay: bool = False,
    quant_type: str = "none",
    num_devices: int = 1,
    cache_dir: Optional[Path] = None,
    force_eval: bool = False,
) -> dict:
    """Returns {"throughput", "inference_rps", "forward_rps", "network_rps"}."""
    cache_dir = Path(cache_dir or DEFAULT_CACHE_PATH)
    cache_dir.mkdir(parents=True, exist_ok=True)
    cache_path = cache_dir / THROUGHPUT_FILE

    # every field that changes the measured speed must be in the key — a
    # server restarted with a different quant/shape/TP setting advertising a
    # stale number would mis-drive routing and block selection swarm-wide
    cache_key = json.dumps(
        {
            "family": family.name,
            "hidden": family.stream_for(cfg)[0],
            "intermediate": getattr(cfg, "intermediate_size", None),
            "kv_heads": getattr(cfg, "num_key_value_heads", None),
            "head_dim": getattr(cfg, "head_dim", None),
            "layers_probed": 1,
            "dtype": str(jnp.dtype(compute_dtype).name),
            "quant": str(quant_type),
            "num_devices": int(num_devices),
            "version": petals_tpu.__version__,
            "backend": jax.default_backend(),
        },
        sort_keys=True,
    )

    cache = _read_cache(cache_path)
    if not force_eval and cache_key in cache:
        info = dict(cache[cache_key])
        logger.info(f"Using cached compute throughput: {info}")
    else:
        info = measure_compute_rps(
            family, cfg, compute_dtype=compute_dtype, quant_type=quant_type,
            num_devices=num_devices,
            n_steps_inference=n_steps_inference, n_steps_forward=n_steps_forward,
        )
        if not info.pop("degraded", False):
            cache[cache_key] = info
            _write_cache(cache_path, cache)
        else:
            # degraded single-device estimate of a TP config: never persist it
            # under the TP key, or it would outlive the broken environment
            logger.warning("Not caching single-device estimate for a TP config")
    # the network figure is NEVER cached: the caller's swarm probe (or a
    # --network_mbps override) must always win — a cached compute entry
    # otherwise silently pins the network number from a past environment
    info["network_rps"] = measure_network_rps(family.stream_for(cfg)[0], network_mbps=network_mbps)

    # blended throughput (reference throughput.py:96-106): compute spread over
    # the hosted blocks vs what the network can carry
    compute_rps = info["forward_rps"] / max(num_blocks, 1)
    network_rps = info["network_rps"] * (RELAY_PENALTY if using_relay else 1.0)
    return {
        "throughput": min(compute_rps, network_rps),
        "inference_rps": info["inference_rps"],
        "forward_rps": info["forward_rps"],
        "network_rps": network_rps,
    }


def measure_compute_rps(
    family, cfg, *, compute_dtype=jnp.bfloat16, quant_type: str = "none",
    num_devices: int = 1, n_steps_inference: int = 50, n_steps_forward: int = 5,
) -> dict:
    """Benchmark one block through the REAL serving backend — same
    quantization, and the same TP mesh when the devices exist (reference
    throughput.py:190-237 measures the converted block for the same reason:
    the advertised number must describe the path that will serve)."""
    from petals_tpu.server.backend import TransformerBackend
    from petals_tpu.server.memory_cache import MemoryCache

    shapes = family.param_shapes_for(cfg, family.kind_of(cfg, 0), compute_dtype)  # the model's block 0
    key = jax.random.PRNGKey(0)
    params = {}
    for name, sds in sorted(shapes.items()):
        key, sub = jax.random.split(key)
        if jnp.issubdtype(sds.dtype, jnp.floating):
            params[name] = jax.random.normal(sub, sds.shape, sds.dtype) * 0.02
        else:
            # integer leaves (gemma2's per-block attn_window) must keep their
            # declared dtype — float noise would cast to a wrong config
            params[name] = jnp.zeros(sds.shape, sds.dtype)
    if "attn_window" in params and getattr(cfg, "layer_types", None):
        # probe block 0's REAL attention pattern: the advertised rps must
        # describe the path that serves (sliding layers cost less than full)
        window = (
            cfg.sliding_window
            if cfg.layer_types[0] == "sliding_attention" else 0
        )
        params["attn_window"] = jnp.asarray(window or 0, jnp.int32)
    if str(quant_type) != "none":
        from petals_tpu.utils.convert_block import convert_block_params

        # mirror the serving config: fused leaves single-chip, unfused under TP
        params = convert_block_params(
            params, family.name, quant_type, fuse=num_devices <= 1
        )
    stacked = jax.tree_util.tree_map(lambda x: x[None] if hasattr(x, "ndim") else x, params)

    mesh = None
    degraded = False
    if num_devices > 1:
        if len(jax.devices()) >= num_devices:
            from petals_tpu.parallel.mesh import tp_mesh

            mesh = tp_mesh(num_devices)
        else:
            degraded = True  # callers must not cache this as the TP number
            logger.warning(
                f"Measuring throughput for num_devices={num_devices} on "
                f"{len(jax.devices())} device(s): figure is a single-device estimate"
            )
    backend = TransformerBackend(
        family, cfg, stacked, first_block=0, n_blocks=1,
        memory_cache=MemoryCache(None), compute_dtype=compute_dtype, mesh=mesh,
    )

    token = np.zeros((1, 1, backend.hidden_size), np.float32)
    if backend.cache.paged_only:
        # a block with a recurrent state, or one whose positions cache an index row or a latent row, has no
        # private cache: the path that serves it is the paged lane pool's step, here over one lane of four pages
        # beside its slot of the state pool (or its pages of the index pool)
        kv = tuple(d.make_zeros() for d in backend.cache.pool_descriptors(4, 64, 1, 0, 1))
        tables = np.arange(4, dtype=np.int32)[None]
        step = lambda kv, position: backend.paged_decode_step(token, kv, np.full(1, position, np.int32), tables)
    else:
        kd, vd = backend.cache_descriptors(1, 256, 0, 1)
        kv = (kd.make_zeros(), vd.make_zeros())
        step = lambda kv, position: backend.inference_step(token, kv, position)

    out, kv = step(kv, 0)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for i in range(n_steps_inference):
        out, kv = step(kv, i + 1)
    jax.block_until_ready(out)
    inference_rps = n_steps_inference / (time.perf_counter() - t0)

    batch = np.zeros((1, 1024, backend.hidden_size), np.float32)
    jax.block_until_ready(backend.forward(batch))
    t0 = time.perf_counter()
    for _ in range(n_steps_forward):
        out = backend.forward(batch)
    jax.block_until_ready(out)
    forward_rps = n_steps_forward * 1024 / (time.perf_counter() - t0)

    logger.info(
        f"Measured compute: inference {inference_rps:.1f} steps/s, "
        f"forward {forward_rps:.0f} tok/s per block"
        + (f" (tp={num_devices})" if mesh is not None else "")
    )
    return {"inference_rps": inference_rps, "forward_rps": forward_rps, "degraded": degraded}


def measure_network_rps(hidden_size: int, *, network_mbps: Optional[float] = None) -> float:
    """Tokens/sec the wire can carry at 16 bits/activation element
    (reference throughput.py:147-175; default 100 Mbit/s on probe failure)."""
    if network_mbps is None:
        network_mbps = _loopback_serialization_mbps(hidden_size)
    bits_per_token = hidden_size * 16
    return network_mbps * 1e6 / bits_per_token


def _loopback_serialization_mbps(hidden_size: int) -> float:
    """Measure our own serialize->frame->deserialize path as the bandwidth
    ceiling; fall back to 100 Mbit/s (the reference's default) on failure."""
    try:
        from petals_tpu.rpc.protocol import encode_frame
        from petals_tpu.rpc.serialization import deserialize_array, serialize_array

        arr = np.random.randn(1, 1024, hidden_size).astype(np.float16)
        t0 = time.perf_counter()
        n = 5
        for _ in range(n):
            frame = encode_frame({"tensors": {"hidden": serialize_array(arr)}})
            _ = deserialize_array(
                {"shape": arr.shape, "dtype": "float16", "wire_dtype": "float16",
                 "compression": "none", "data": arr.tobytes()}
            )
        elapsed = time.perf_counter() - t0
        mbps = (n * len(frame) * 8) / elapsed / 1e6
        return min(mbps, 10_000.0)  # cap at 10 Gbit/s sanity bound
    except Exception as e:
        logger.warning(f"Network probe failed ({e}); assuming 100 Mbit/s")
        return 100.0


def _read_cache(path: Path) -> dict:
    try:
        with open(path) as f:
            fcntl.flock(f, fcntl.LOCK_SH)
            try:
                return json.load(f)
            finally:
                fcntl.flock(f, fcntl.LOCK_UN)
    except (FileNotFoundError, json.JSONDecodeError):
        return {}


def _write_cache(path: Path, cache: dict) -> None:
    with open(path, "w") as f:
        fcntl.flock(f, fcntl.LOCK_EX)
        try:
            json.dump(cache, f)
        finally:
            fcntl.flock(f, fcntl.LOCK_UN)
