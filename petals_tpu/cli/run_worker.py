"""Multi-host serving worker: the non-leader half of a span server whose
tensor parallelism spans several hosts (parallel/multihost.py).

Start ONE leader (``run_server`` with --coordinator_address/--num_hosts) and
``num_hosts - 1`` workers, each with the SAME model/span/quant/dtype flags:

    # host 0 (leader: DHT + RPC + scheduler)
    python -m petals_tpu.cli.run_server MODEL --first_block 0 --num_blocks 8 \
        --coordinator_address host0:8476 --num_hosts 2 --throughput 100

    # host 1 (worker: lockstep compute replica)
    python -m petals_tpu.cli.run_worker MODEL --first_block 0 --num_blocks 8 \
        --coordinator_address host0:8476 --num_hosts 2 --host_index 1

The worker builds the identical backend from the identical checkpoint, joins
the jax.distributed group, and executes the leader's broadcast ops until the
leader shuts down. There is no reference analogue: reference tensor
parallelism is bounded by one machine's GPUs (convert_block.py:118-135).
"""

from __future__ import annotations

import argparse


def main() -> None:
    from petals_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("model", help="model path or repo id (must match the leader's)")
    parser.add_argument("--coordinator_address", required=True)
    parser.add_argument("--num_hosts", type=int, required=True)
    parser.add_argument("--host_index", type=int, required=True,
                        help="this worker's process id (1..num_hosts-1)")
    parser.add_argument("--first_block", type=int, required=True)
    parser.add_argument("--num_blocks", type=int, required=True)
    parser.add_argument("--num_tp_devices", type=int, default=None,
                        help="global tp width (default: every device in the group / sp)")
    parser.add_argument("--num_sp_devices", type=int, default=None,
                        help="sequence-parallel width — MUST match the leader's flag")
    parser.add_argument("--quant_type", default="none",
                        choices=["none", "int8", "nf4", "nf4a", "int4", "nf4a+o", "int4+o"])
    from petals_tpu.constants import DTYPE_MAP

    parser.add_argument("--torch_dtype", "--dtype", dest="dtype", default="bfloat16",
                        choices=[k for k in DTYPE_MAP if k != "auto"])
    parser.add_argument("--max_chunk_size_bytes", type=int, default=256 * 1024 * 1024)
    parser.add_argument("--adapters", nargs="*", default=(),
                        help="PEFT checkpoint dirs — MUST match the leader's --adapters")
    parser.add_argument("--revision", default="main")
    parser.add_argument("--cache_dir", default=None)
    parser.add_argument("--no_quant_weight_cache", action="store_true")
    args = parser.parse_args()
    if not 1 <= args.host_index:
        raise SystemExit("--host_index must be >= 1 (process 0 is the run_server leader)")

    # join the group BEFORE anything initializes the XLA backend
    from petals_tpu.parallel.multihost import (
        LockstepWorker,
        init_multihost,
        multihost_mesh,
    )

    init_multihost(args.coordinator_address, args.num_hosts, args.host_index)

    import jax
    import jax.numpy as jnp

    from petals_tpu.server.backend import TransformerBackend
    from petals_tpu.server.from_pretrained import get_block_config, load_block_params
    from petals_tpu.server.memory_cache import MemoryCache
    from petals_tpu.utils.convert_block import QuantType, convert_block_params
    from petals_tpu.utils.logging import get_logger

    from petals_tpu.constants import DTYPE_MAP

    logger = get_logger("petals_tpu.cli.run_worker")
    dtype = DTYPE_MAP[args.dtype]
    family, cfg = get_block_config(args.model, revision=args.revision, cache_dir=args.cache_dir)

    # the span params must BIT-MATCH the leader's: same checkpoint, same
    # conversion pipeline, same quant disk-cache format (utils/quant_cache.py)
    def load_block(i):
        params = load_block_params(
            args.model, i, dtype=dtype, family=family, cfg=cfg,
            revision=args.revision, cache_dir=args.cache_dir,
        )
        return convert_block_params(params, family.name, args.quant_type, fuse=False)

    mesh = multihost_mesh(args.num_tp_devices, args.num_sp_devices or 1)

    def build_backend(first_block: int) -> TransformerBackend:
        """Initial build AND the live-span-move rebuild (OP_RELOAD_SPAN):
        adapters re-slice for the new span like the leader's reload does."""
        per_block = [
            load_block(i) for i in range(first_block, first_block + args.num_blocks)
        ]
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_block)
        backend = TransformerBackend(
            family, cfg, stacked,
            first_block=first_block,
            n_blocks=args.num_blocks,
            memory_cache=MemoryCache(None),
            compute_dtype=dtype,
            max_chunk_size_bytes=args.max_chunk_size_bytes,
            mesh=mesh,
        )
        if args.adapters:
            from petals_tpu.utils.peft import load_adapter, stack_adapter

            block_range = range(first_block, first_block + args.num_blocks)
            for path in args.adapters:
                adapter = load_adapter(path, family.name, block_range=block_range)
                stacked_a = stack_adapter(adapter, first_block, args.num_blocks, dtype)
                backend.adapters[adapter.name] = (stacked_a, adapter.scaling)
            logger.info(f"worker hosting adapters: {sorted(backend.adapters)}")
        return backend

    backend = build_backend(args.first_block)

    logger.info(
        f"worker {args.host_index}/{args.num_hosts}: span "
        f"[{args.first_block}, {args.first_block + args.num_blocks}) over "
        f"tp={mesh.shape['tp']}"
        + (f" x sp={mesh.shape['sp']}" if "sp" in mesh.shape else "")
    )
    LockstepWorker(backend, rebuild_fn=build_backend).run()


if __name__ == "__main__":
    main()
