"""Run a petals_tpu server: ``python -m petals_tpu.cli.run_server <model_path> [...]``
(counterpart of reference src/petals/cli/run_server.py:19-235).
"""

from __future__ import annotations

import argparse
import asyncio
import signal

import jax.numpy as jnp

from petals_tpu.constants import DTYPE_MAP
from petals_tpu.server.server import Server
from petals_tpu.utils.logging import get_logger

logger = get_logger(__name__)


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(description="Host a span of transformer blocks on this TPU host")
    parser.add_argument("model", help="Local path of the HF checkpoint to serve")
    parser.add_argument("--host", default="0.0.0.0", help="Listen address")
    parser.add_argument("--port", type=int, default=0, help="Listen port (0 = ephemeral)")
    parser.add_argument("--initial_peers", nargs="*", default=[],
                        help="Bootstrap peers as host:port/peer_id strings")
    parser.add_argument("--identity_seed", default=None,
                        help="Seed string for a deterministic peer id (test swarms)")
    parser.add_argument("--dht_prefix", default=None, help="Swarm namespace (default: derived from model name)")
    parser.add_argument("--first_block", type=int, default=None,
                        help="First block to serve (default: auto-placement from swarm state)")
    parser.add_argument("--num_blocks", type=int, default=None,
                        help="How many blocks to serve (default: auto-size to device memory)")
    parser.add_argument("--block_indices", default=None,
                        help="Alternative to first/num: a range like 0:16")
    parser.add_argument("--torch_dtype", "--dtype", dest="dtype", default="bfloat16",
                        choices=[k for k in DTYPE_MAP if k != "auto"], help="Compute dtype")
    parser.add_argument("--quant_type", default="none", choices=["none", "int8", "nf4", "nf4a", "int4", "nf4a+o", "int4+o"],
                        help="Weight quantization (ops/quant.py)")
    parser.add_argument("--coordinator_address", default=None,
                        help="multi-host serving: jax.distributed coordinator (host:port); "
                             "start num_hosts-1 run_worker processes with the same flags")
    parser.add_argument("--num_hosts", type=int, default=1,
                        help="multi-host serving: total processes incl. this leader")
    parser.add_argument("--no_quant_weight_cache", action="store_true",
                        help="Re-quantize at every start instead of persisting packed "
                             "quantized blocks in the disk cache (utils/quant_cache.py)")
    parser.add_argument("--attn_cache_tokens", type=int, default=8192,
                        help="KV-cache budget in tokens (converted to bytes for the allocator)")
    parser.add_argument("--max_chunk_size_bytes", type=int, default=256 * 1024 * 1024,
                        help="Prefill chunking bound (attention logits bytes)")
    parser.add_argument("--throughput", default="auto",
                        help='"auto" to self-measure, or a number')
    parser.add_argument("--update_period", type=float, default=30.0, help="DHT announce period, seconds")
    parser.add_argument("--mean_balance_check_period", type=float, default=0.0,
                        help=">0: periodically consider moving to under-served blocks")
    parser.add_argument("--num_tp_devices", type=int, default=None,
                        help="Tensor-parallel over this many local chips")
    parser.add_argument("--adapters", nargs="*", default=[],
                        help="PEFT adapter checkpoint dirs to host (multi-tenant LoRA)")
    parser.add_argument("--public_name", default=None, help="Display name announced to the swarm")
    parser.add_argument("--max_alloc_timeout", type=float, default=600.0)
    parser.add_argument("--num_sp_devices", type=int, default=None,
                        help=">1: ring-attention sequence parallelism for long-context "
                             "forward/backward (stateless path)")
    parser.add_argument("--compression", default="none",
                        choices=["none", "float16", "bfloat16", "qint8"],
                        help="Default reply compression (clients may override per request)")
    parser.add_argument("--max_disk_space", default=None,
                        help="Hub/checkpoint cache budget, e.g. 300GB (LRU-evicted)")
    parser.add_argument("--token", default=None,
                        help="HF Hub access token for gated/private repos (or set HF_TOKEN)")
    parser.add_argument("--network_mbps", type=float, default=None,
                        help="Known network budget in Mbit/s (default: probe swarm peers, "
                             "utils/bandwidth.py; loopback stack probe when alone)")
    parser.add_argument("--relay_via", default=None,
                        help="host:port of a relay peer (run_dht prints one): serve from behind "
                             "NAT/firewall with no inbound listener (rpc/relay.py)")
    parser.add_argument("--trace_dir", default=None,
                        help="Capture a bounded jax device trace here at startup "
                             "(or set PETALS_TPU_TRACE_DIR)")
    parser.add_argument("--drain_seconds", type=float, default=0.0,
                        help="On SIGTERM/SIGINT, park live sessions' KV and keep serving "
                             "ptu.session_export for this long before exiting, so clients "
                             "migrate caches to replacements instead of recomputing prefills")
    parser.add_argument("--inference_max_length", type=int, default=None,
                        help="Reject sessions longer than this (default: 8192 for GQA/MQA "
                             "models, 2048 otherwise — reference server.py:194-198)")
    parser.add_argument("--request_timeout", type=float, default=3 * 60,
                        help="Timeout for forward/backward requests, seconds")
    parser.add_argument("--session_timeout", type=float, default=30 * 60,
                        help="Max lifetime of an idle inference session, seconds")
    parser.add_argument("--step_timeout", type=float, default=5 * 60,
                        help="Timeout for one inference step, seconds")
    parser.add_argument("--balance_quality", type=float, default=0.75,
                        help="Rebalance only when swarm quality falls below this fraction "
                             "of the post-move optimum (reference --balance_quality)")
    parser.add_argument("--revision", default="main",
                        help="Hub revision (branch/tag/commit) for weight streaming")
    parser.add_argument("--cache_dir", default=None,
                        help="Hub download cache directory (default: PETALS_TPU_CACHE)")
    parser.add_argument("--no_batching", action="store_true",
                        help="Disable continuous batching of concurrent decode sessions")
    parser.add_argument("--batch_lanes", type=int, default=None,
                        help="Continuous-batching lane count (default: auto-size to the cache budget, <=8)")
    parser.add_argument("--batch_max_length", type=int, default=None,
                        help="Lane length in tokens (default: min(inference_max_length, 1024))")
    parser.add_argument("--page_size", type=int, default=64,
                        help="Paged KV cache: tokens per page (sessions grow page-by-page, so "
                             "admission costs one page instead of batch_max_length tokens); "
                             "0 reverts to the dense per-lane pool")
    parser.add_argument("--n_pages", type=int, default=None,
                        help="Paged KV pool size in pages (default: batch_lanes * pages-per-lane, "
                             "i.e. no oversubscription; raise to admit more sessions than lanes "
                             "could hold at full length)")
    parser.add_argument("--kv_quant_type", choices=["none", "int8", "nf4a"], default="none",
                        help="Quantize the paged KV pool in place: int8 (per-row absmax) or "
                             "packed nf4a halves decode HBM traffic ~2-4x and fits ~2-4x more "
                             "pages in the same cache budget; pages are dequantized inside the "
                             "fused attention kernel. Requires --page_size > 0")
    parser.add_argument("--prefill_token_budget", type=int, default=512,
                        help="Max prefill-chunk tokens folded into each mixed batched step "
                             "(paged lanes only: prefills share the step with decode lanes "
                             "instead of stalling them; halved under decode pressure)")
    parser.add_argument("--swap_host_bytes", type=int, default=0,
                        help="Host-RAM KV swap tier for session preemption (paged lanes only): "
                             "on pool exhaustion an idle victim session's pages are copied to "
                             "host RAM and freed, then transparently swapped back in on its "
                             "next step; 0 disables (full pool keeps the fail-at-timeout "
                             "backpressure behavior)")
    parser.add_argument("--preemption_policy", choices=["lru", "largest", "off"], default="lru",
                        help="Victim choice on pool exhaustion: 'lru' = lowest priority class "
                             "then least-recently-stepped; 'largest' = lowest class then most "
                             "pages held; 'off' disables preemption")
    parser.add_argument("--prefix_cache_bytes", type=int, default=256 * 2**20,
                        help="Host-RAM prompt-prefix cache budget; 0 disables")
    parser.add_argument("--no_server_side_generation", action="store_true",
                        help="disable the device-side greedy generation loop on full-span servers")
    parser.add_argument("--draft_model", default=None,
                        help="Local path of a SMALL checkpoint for speculative decoding: "
                             "it drafts --spec_k tokens per lane per tick and the span "
                             "verifies them in one paged step (full-span single-host "
                             "servers with server-side generation and a paged pool; "
                             "output stays bit-identical to plain decode)")
    parser.add_argument("--spec_k", type=int, default=4,
                        help="Draft tokens verified per lane per tick (with --draft_model)")
    parser.add_argument("--draft_window", type=int, default=None,
                        help="Draft context window in tokens (default 64): the draft "
                             "re-prefills the last N tokens each tick")
    parser.add_argument("--draft_quant_type", default="nf4a",
                        choices=["none", "int8", "nf4", "nf4a", "int4"],
                        help="Quantization for the draft model's blocks")
    parser.add_argument("--prefix_device_bytes", type=int, default=256 * 2**20,
                        help="HBM tier of the prefix cache (device-resident hit seeding); 0 disables")
    parser.add_argument("--metrics_port", type=int, default=None,
                        help="Serve Prometheus-text /metrics (plus the /journal scheduler "
                             "event log) on this local HTTP port; 0 = ephemeral, "
                             "omit to disable")
    parser.add_argument("--prefix_cache_policy", choices=["radix", "lru"], default="radix",
                        help="'radix' keys prefix-cache entries into a token-segment radix "
                             "tree with three-tier residency (HBM / host / swap) and "
                             "tenant-fair eviction; 'lru' is the flat insertion-order "
                             "baseline (A/B comparisons)")
    parser.add_argument("--phase_tier", choices=["generalist", "prefill", "decode"],
                        default="generalist",
                        help="Disaggregated serving tier announced to the swarm: 'prefill' "
                             "replicas soak heavy prompt processing and hand the finished KV "
                             "to a 'decode' replica over the server-to-server page-push path; "
                             "'generalist' (default) serves both phases")
    parser.add_argument("--prefix_share_scope", choices=["swarm", "peer"], default="swarm",
                        help="'swarm' shares cached prefixes across all clients (fastest; a client "
                             "can time-probe whether a prompt prefix was recently served); 'peer' "
                             "salts entries per authenticated client identity, closing that "
                             "side channel at the cost of cross-client sharing")
    return parser


def parse_block_range(args) -> tuple:
    if args.block_indices:
        first, last = args.block_indices.split(":")
        return int(first), int(last) - int(first)
    return args.first_block, args.num_blocks


def main(argv=None) -> None:
    import os

    from petals_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    args = build_parser().parse_args(argv)
    first_block, num_blocks = parse_block_range(args)

    # env-carried knobs: the hub/tracing modules read these at use time
    if args.max_disk_space:
        from petals_tpu.utils.hub import parse_size

        try:
            parse_size(args.max_disk_space)  # fail fast with the flag named
        except ValueError:
            build_parser().error(
                f"--max_disk_space: cannot parse {args.max_disk_space!r} "
                f"(expected e.g. 300GB, 512MB, or bytes)"
            )
        os.environ["PETALS_TPU_MAX_DISK_SPACE"] = args.max_disk_space
    if args.token:
        os.environ["HF_TOKEN"] = args.token
    if args.trace_dir:
        os.environ["PETALS_TPU_TRACE_DIR"] = args.trace_dir

    try:
        throughput = float(args.throughput)
    except ValueError:
        throughput = args.throughput

    # token budget -> bytes happens inside Server once the config is known
    from petals_tpu.server.from_pretrained import get_block_config

    family, cfg = get_block_config(
        args.model, revision=args.revision, cache_dir=args.cache_dir
    )
    hkv = getattr(cfg, "num_key_value_heads", cfg.num_attention_heads)
    dtype = DTYPE_MAP[args.dtype]
    attn_cache_bytes = (
        2 * args.attn_cache_tokens * hkv * cfg.head_dim * jnp.dtype(dtype).itemsize
        * (num_blocks or cfg.num_hidden_layers)
    )

    server = Server(
        args.model,
        first_block=first_block,
        num_blocks=num_blocks,
        dht_prefix=args.dht_prefix,
        host=args.host,
        port=args.port,
        initial_peers=args.initial_peers,
        identity_seed=args.identity_seed.encode() if args.identity_seed else None,
        compute_dtype=dtype,
        attn_cache_bytes=attn_cache_bytes,
        max_chunk_size_bytes=args.max_chunk_size_bytes,
        throughput=throughput,
        public_name=args.public_name,
        update_period=args.update_period,
        mean_balance_check_period=args.mean_balance_check_period,
        max_alloc_timeout=args.max_alloc_timeout,
        num_tp_devices=args.num_tp_devices,
        num_sp_devices=args.num_sp_devices,
        quant_type=args.quant_type,
        adapters=args.adapters,
        compression=args.compression,
        relay_via=args.relay_via,
        network_mbps=args.network_mbps,
        inference_max_length=args.inference_max_length,
        request_timeout=args.request_timeout,
        session_timeout=args.session_timeout,
        step_timeout=args.step_timeout,
        balance_quality=args.balance_quality,
        revision=args.revision,
        cache_dir=args.cache_dir,
        quant_weight_cache=not args.no_quant_weight_cache,
        coordinator_address=args.coordinator_address,
        num_hosts=args.num_hosts,
        batching=not args.no_batching,
        batch_lanes=args.batch_lanes,
        batch_max_length=args.batch_max_length,
        page_size=args.page_size,
        n_pages=args.n_pages,
        kv_quant_type=args.kv_quant_type,
        prefill_token_budget=args.prefill_token_budget,
        swap_host_bytes=args.swap_host_bytes,
        preemption_policy=args.preemption_policy,
        prefix_cache_bytes=args.prefix_cache_bytes,
        prefix_share_scope=args.prefix_share_scope,
        prefix_device_bytes=args.prefix_device_bytes,
        prefix_cache_policy=args.prefix_cache_policy,
        server_side_generation=not args.no_server_side_generation,
        draft_model=args.draft_model,
        spec_k=args.spec_k,
        draft_window=args.draft_window,
        draft_quant_type=args.draft_quant_type,
        metrics_port=args.metrics_port,
        phase_tier=args.phase_tier,
    )

    async def run():
        await server.start()
        logger.info(f"Serving; announce address: {server.contact_addr.to_string()}")
        stop = asyncio.Event()
        force = asyncio.Event()

        def on_signal():
            # second SIGINT/SIGTERM skips the remaining drain window: an
            # operator must always be able to force immediate shutdown
            if stop.is_set():
                force.set()
            else:
                stop.set()

        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            loop.add_signal_handler(sig, on_signal)
        await stop.wait()
        if args.drain_seconds > 0:
            parked = await server.drain(park_ttl=args.drain_seconds + 30)
            if parked:
                logger.info(
                    f"Drain window: serving KV exports for {parked} session(s) "
                    f"for {args.drain_seconds:.0f}s (signal again to skip)"
                )
                try:
                    await asyncio.wait_for(force.wait(), args.drain_seconds)
                    logger.info("Second signal: skipping the rest of the drain window")
                except asyncio.TimeoutError:
                    pass
        logger.info("Shutting down")
        await server.shutdown()

    asyncio.run(run())


if __name__ == "__main__":
    main()
