"""Server orchestrator: load a span of blocks, serve it, announce it
(counterpart of reference src/petals/server/server.py:46-775 — Server +
ModuleContainer + ModuleAnnouncerThread, collapsed into one asyncio process
since a JAX server has no per-connection forked handlers or separate runtime
process).
"""

from __future__ import annotations

import asyncio
import contextlib
import dataclasses
import math
import re
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

import petals_tpu
from petals_tpu import chaos
from petals_tpu.data_structures import ServerInfo, ServerState, make_uid, PeerID
from petals_tpu.dht.node import DHTNode, dht_time
from petals_tpu.rpc.server import RpcServer
from petals_tpu.server.backend import TransformerBackend
from petals_tpu.server.from_pretrained import get_block_config, load_block_params
from petals_tpu.server.handler import TransformerHandler
from petals_tpu.server.memory_cache import MemoryCache
from petals_tpu.utils.convert_block import QuantType, block_size_bytes, convert_block_params
from petals_tpu.utils.asyncio_utils import install_turn_clock, log_exception_callback
from petals_tpu.utils.dht_utils import declare_active_modules
from petals_tpu.utils.logging import get_logger

logger = get_logger(__name__)

DEFAULT_UPDATE_PERIOD = 30.0

# disaggregated serving tiers: generalists serve both phases; prefill-tier
# replicas soak FLOPs-bound prompt processing and hand the finished KV to a
# decode-tier replica over the page-push path (handler.rpc_session_handoff)
PHASE_TIERS = ("generalist", "prefill", "decode")


def held_chip_files() -> list:
    """The accelerator device files this process holds open (``/dev/vfio/<n>``
    or ``/dev/accel<n>``, one per chip): the only name for the PHYSICAL chip —
    a process pinned with TPU_VISIBLE_CHIPS numbers its one JAX device 0 at
    coords (0,0,0) whichever chip it was given. Empty off-TPU."""
    import os

    held = set()
    for fd in os.listdir("/proc/self/fd"):
        try:
            target = os.readlink(f"/proc/self/fd/{fd}")
        except OSError:  # the fd of this very listdir, already closed
            continue
        if target.startswith(("/dev/vfio/", "/dev/accel")) and target != "/dev/vfio/vfio":
            held.add(target)
    return sorted(held)


def default_dht_prefix(model_name: str) -> str:
    """Derive the swarm namespace from the model name (reference
    models/*/config.py dht_prefix logic: name minus org, '-hf' suffix)."""
    name = model_name.rstrip("/").split("/")[-1]
    name = re.sub(r"[^\w.-]", "-", name)
    return f"{name}-hf"


class Server:
    """Hosts blocks [first_block, first_block + num_blocks) of one model."""

    def __init__(
        self,
        model_path: str,
        *,
        first_block: Optional[int] = None,  # None: auto-place from swarm state
        num_blocks: Optional[int] = None,  # None: auto-size to device memory
        dht_prefix: Optional[str] = None,
        host: str = "127.0.0.1",
        port: int = 0,
        initial_peers: Sequence = (),
        identity_seed: Optional[bytes] = None,
        compute_dtype=jnp.bfloat16,
        attn_cache_bytes: Optional[int] = None,
        max_chunk_size_bytes: int = 256 * 1024 * 1024,
        throughput="auto",  # float, or "auto" to self-measure (server/throughput.py)
        public_name: Optional[str] = None,
        update_period: float = DEFAULT_UPDATE_PERIOD,
        mean_balance_check_period: float = 0.0,  # >0: periodically rebalance span placement
        use_flash: Optional[bool] = None,
        max_alloc_timeout: float = 600.0,
        num_tp_devices: Optional[int] = None,  # >1: shard the span over this host's chips
        num_sp_devices: Optional[int] = None,  # >1: ring-attention seq parallelism (fwd/bwd path)
        quant_type: str = "none",  # "none" | "int8" | "nf4" | "nf4a" | "int4" (ops/quant.py)
        adapters: Sequence[str] = (),  # PEFT checkpoint dirs to host (utils/peft.py)
        compression: str = "none",  # default reply codec (clients may override per request)
        relay_via: Optional[str] = None,  # "host:port" of a relay peer: serve from behind NAT
        network_mbps: Optional[float] = None,  # known WAN budget; None = probe swarm peers
        inference_max_length: Optional[int] = None,  # None: 8192 for GQA/MQA, 2048 otherwise
        request_timeout: float = 3 * 60,
        session_timeout: float = 30 * 60,
        step_timeout: float = 5 * 60,
        balance_quality: float = 0.75,  # rebalance iff swarm quality < this (block_selection.py)
        revision: str = "main",  # Hub revision for weight streaming (utils/hub.py)
        cache_dir=None,  # Hub download cache (default PETALS_TPU_CACHE)
        quant_weight_cache: bool = True,  # persist quantized blocks across restarts
        coordinator_address: Optional[str] = None,  # multi-host: jax.distributed coordinator
        num_hosts: int = 1,  # multi-host: total processes (this leader + run_worker peers)
        batching: bool = True,  # continuous batching of concurrent decode sessions
        batch_lanes: Optional[int] = None,  # None: auto-size to the cache budget (<=8)
        batch_max_length: Optional[int] = None,  # pool lane length; None: min(inference_max_length, 1024)
        page_size: int = 64,  # paged KV: tokens per page; 0 = dense lane pool
        n_pages: Optional[int] = None,  # paged KV pool size; None = lanes * pages-per-lane
        kv_quant_type: str = "none",  # paged KV pool storage: "none" | "int8" | "nf4a"
        prefill_token_budget: int = 512,  # prefill tokens folded into each mixed batched step
        swap_host_bytes: int = 0,  # host-RAM KV swap tier (session preemption); 0 disables
        preemption_policy: str = "lru",  # victim choice on pool exhaustion: lru | largest | off
        prefix_cache_bytes: int = 256 * 2**20,  # host-RAM prompt-prefix cache; 0 disables
        prefix_share_scope: str = "swarm",  # "peer" isolates the prefix cache per client identity
        prefix_device_bytes: int = 256 * 2**20,  # HBM tier of the prefix cache; 0 disables
        prefix_cache_policy: str = "radix",  # "radix" tree with tiering | "lru" flat baseline
        server_side_generation: bool = True,  # device-side greedy loop on full-span servers
        draft_model: Optional[str] = None,  # small checkpoint for speculative decoding
        spec_k: int = 4,  # drafts verified per lane per tick when draft_model is set
        draft_window: Optional[int] = None,  # draft context window (tokens); None = default
        draft_quant_type: str = "nf4a",  # draft block quantization (4-bit serving default)
        metrics_port: Optional[int] = None,  # Prometheus /metrics HTTP port; None disables, 0 = ephemeral
        phase_tier: str = "generalist",  # disaggregated serving: "generalist" | "prefill" | "decode"
    ):
        self.num_hosts = num_hosts or 1
        self.coordinator_address = coordinator_address
        if self.num_hosts > 1:
            # MUST run before anything touches jax (even jax.devices());
            # everything below may initialize the XLA backend
            from petals_tpu.parallel.multihost import init_multihost

            if not coordinator_address:
                raise ValueError("num_hosts > 1 requires coordinator_address")
            init_multihost(coordinator_address, self.num_hosts, 0)
            if first_block is None or num_blocks is None:
                raise ValueError(
                    "multi-host serving needs an explicit --first_block/--num_blocks "
                    "(workers load the identical span; auto-placement would desync them)"
                )
        self.model_path = model_path
        self.revision = revision
        self.cache_dir = cache_dir
        # config must come from the SAME revision/cache the weights stream
        # from, or block splitting and shapes follow a different architecture
        self.family, self.cfg = get_block_config(
            model_path, revision=revision, cache_dir=cache_dir
        )
        total = self.cfg.num_hidden_layers
        self.auto_placement = first_block is None
        # PETALS_TPU_RADIX_DEVICE_FRAC retunes the radix cache's HBM/host
        # split as a fraction of prefix_cache_bytes without code edits
        # (revival step 10/10 silicon crossover)
        from petals_tpu.server.prefix_cache import resolve_device_bytes

        prefix_device_bytes = resolve_device_bytes(
            prefix_cache_bytes, prefix_device_bytes
        )
        if attn_cache_bytes is None:
            from petals_tpu.server.block_utils import device_memory_bytes

            memory = device_memory_bytes()
            # default KV budget: 15% of device memory (reference reserves an
            # attn-cache fraction before packing blocks, server.py:275-326)
            attn_cache_bytes = int(memory * 0.15) if memory else 2 << 30
            # the prefix cache's HBM tier lives OUTSIDE MemoryCache's budget
            # (pinned device slices, prefix_cache.py): carve it out of the
            # auto-sized KV budget or the default-on device tier tips an
            # auto-sized server into on-chip OOM; floored so a huge
            # prefix_device_bytes cannot starve serving entirely
            if prefix_device_bytes > 0:
                attn_cache_bytes = max(
                    attn_cache_bytes - prefix_device_bytes, attn_cache_bytes // 4
                )
        if num_blocks is None:
            if first_block is not None:
                num_blocks = total - first_block
            else:
                from petals_tpu.server.block_utils import choose_num_blocks

                num_blocks = choose_num_blocks(
                    self.family, self.cfg, quant_type=quant_type,
                    attn_cache_bytes=attn_cache_bytes or 0,
                )
        self.first_block = first_block if first_block is not None else 0
        self.num_blocks = num_blocks
        assert 0 <= self.first_block < self.first_block + self.num_blocks <= total
        self.dht_prefix = dht_prefix or default_dht_prefix(model_path)
        self.host, self.port = host, port
        self.initial_peers = list(initial_peers)
        self.identity_seed = identity_seed
        self.compute_dtype = compute_dtype
        self.attn_cache_bytes = attn_cache_bytes
        self.max_chunk_size_bytes = max_chunk_size_bytes
        if not isinstance(throughput, (int, float)) and throughput != "auto":
            raise ValueError(f'throughput must be a number or "auto", got {throughput!r}')
        self._throughput_spec = throughput
        self.throughput = throughput if isinstance(throughput, (int, float)) else 1.0
        self.public_name = public_name
        self.update_period = update_period
        self.mean_balance_check_period = mean_balance_check_period
        self.use_flash = use_flash
        self.max_alloc_timeout = max_alloc_timeout
        self.num_tp_devices = num_tp_devices
        self.num_sp_devices = num_sp_devices
        if (num_sp_devices or 1) > 1 and not self.family.supports_ring_attention:
            raise ValueError(
                f"num_sp_devices>1 needs ring attention, which {self.family.name} "
                f"does not support (plain causal only) — the sp devices would "
                f"sit idle holding replicated parameters"
            )
        self.quant_type = quant_type
        self.quant_weight_cache = quant_weight_cache
        self.adapter_paths = list(adapters)
        from petals_tpu.rpc.serialization import CompressionType

        self.compression = CompressionType(compression)
        if inference_max_length is None:
            # reference server.py:194-198: longer contexts for MQA/GQA models
            # (their KV is cheap), conservative cap otherwise
            heads = getattr(self.cfg, "num_attention_heads", 1)
            kv_heads = getattr(self.cfg, "num_key_value_heads", heads) or heads
            inference_max_length = 8192 if kv_heads < heads else 2048
        self.inference_max_length = inference_max_length
        self.batching = batching
        self.batch_lanes = batch_lanes
        self.batch_max_length = batch_max_length
        self.page_size = page_size
        self.n_pages = n_pages
        from petals_tpu.ops.paged_attention import KV_QUANT_KINDS

        if kv_quant_type not in KV_QUANT_KINDS:
            raise ValueError(
                f"kv_quant_type must be one of {KV_QUANT_KINDS}, got {kv_quant_type!r}"
            )
        if kv_quant_type != "none" and not page_size:
            raise ValueError(
                "kv_quant_type requires the paged KV pool (--page_size > 0): the "
                "dense lane pool has no quantized storage path"
            )
        self.kv_quant_type = kv_quant_type
        self.prefill_token_budget = prefill_token_budget
        self.swap_host_bytes = swap_host_bytes
        self.preemption_policy = preemption_policy
        self.prefix_cache_bytes = prefix_cache_bytes
        self.prefix_share_scope = prefix_share_scope
        self.prefix_device_bytes = prefix_device_bytes
        self.prefix_cache_policy = prefix_cache_policy
        self.server_side_generation = server_side_generation
        self.draft_model_path = draft_model
        self.spec_k = int(spec_k)
        self.draft_window = draft_window
        self.draft_quant_type = draft_quant_type
        self._draft_model = None  # loaded lazily by _make_handler
        self._turn_clock = None  # the running loop's, from start() on (utils/asyncio_utils.py)
        self.request_timeout = request_timeout
        self.session_timeout = session_timeout
        self.step_timeout = step_timeout
        self.balance_quality = balance_quality
        self.module_uids = [
            make_uid(self.dht_prefix, i)
            for i in range(self.first_block, self.first_block + self.num_blocks)
        ]
        self._local_devices_only = False  # set by partial re-formation
        self.rpc_server: Optional[RpcServer] = None
        self.dht: Optional[DHTNode] = None
        self.handler: Optional[TransformerHandler] = None
        self.backend: Optional[TransformerBackend] = None
        self.memory_cache: Optional[MemoryCache] = None
        self._announcer_task: Optional[asyncio.Task] = None
        self._balancer_task: Optional[asyncio.Task] = None
        self._state = ServerState.JOINING  # what the announce loop broadcasts
        self._ready = asyncio.Event()
        # successor-server RTTs published with every announce so clients can
        # cost server->server hops (reference server.py:717-751)
        self._next_pings: dict = {}
        self._ping_aggregator = None
        self._trace_flush_task: Optional[asyncio.Task] = None
        self.relay_via = relay_via
        self.network_mbps = network_mbps
        self._relay_registrar = None
        self._contact_addr = None  # non-default announce addr (relay circuit)
        self.metrics_port = metrics_port
        self._metrics_server = None  # telemetry.exposition.MetricsServer when enabled
        if phase_tier not in PHASE_TIERS:
            raise ValueError(
                f"phase_tier must be one of {PHASE_TIERS}, got {phase_tier!r}"
            )
        self.phase_tier = phase_tier

    # ------------------------------------------------------------------ lifecycle

    async def start(self) -> None:
        from petals_tpu.utils.compile_cache import enable_compilation_cache

        self._turn_clock = install_turn_clock(asyncio.get_running_loop())
        cache_dir = enable_compilation_cache()
        devices = jax.local_devices()
        logger.info(
            f"JAX backend {jax.default_backend()}: {len(devices)} x {devices[0].device_kind}, "
            f"chips {held_chip_files()}; compile cache: {cache_dir or 'off'}"
        )
        from petals_tpu.dht.identity import Identity

        identity = (
            Identity.from_seed(self.identity_seed) if self.identity_seed else Identity.generate()
        )
        self._identity = identity  # re-used by partial re-formation
        peer_id = identity.peer_id
        self.rpc_server = RpcServer(identity=identity, host=self.host, port=self.port)
        if self.relay_via is not None:
            # NAT'd / firewalled server: no listener at all. The rpc surface is
            # served on REVERSE connections dialed out through the relay
            # (rpc/relay.py), the DHT runs query-only (reference client-mode
            # DHT, server.py:137-150), and the announced contact address is the
            # relay circuit.
            from petals_tpu.dht.routing import PeerAddr
            from petals_tpu.rpc.relay import RelayRegistrar

            relay_host, relay_port = self.relay_via.rsplit(":", 1)
            self.dht = await DHTNode.create(
                identity=identity,
                client_mode=True,
                initial_peers=self.initial_peers,
            )
            self._relay_registrar = RelayRegistrar(
                relay_host, int(relay_port), identity, self.rpc_server
            )
            await self._relay_registrar.start()
            await self._relay_registrar.wait_registered()
            self._contact_addr = PeerAddr(relay_host, int(relay_port), peer_id, relayed=True)
            # the client-mode DHT registers nothing on our serving RpcServer,
            # but peers still probe relayed servers (RTT for next_pings /
            # routing, bandwidth, health dial-backs) — serve those here
            from petals_tpu.utils.bandwidth import BandwidthProtocol

            async def _ping(_payload, _ctx):
                return {"peer_id": peer_id.to_string()}

            self.rpc_server.add_unary_handler("dht.ping", _ping)
            BandwidthProtocol().register(self.rpc_server)
            logger.info(f"Serving behind relay {self.relay_via} (no inbound listener)")
        else:
            # Start listening BEFORE the DHT bootstraps: the node advertises its
            # own (host, port) to peers during bootstrap.
            await self.rpc_server.start()
            self.dht = await DHTNode.create(
                identity=identity,
                rpc_server=self.rpc_server,
                initial_peers=self.initial_peers,
            )

        from petals_tpu.server.reachability import ReachabilityProtocol

        ReachabilityProtocol().register(self.rpc_server)

        # max_alloc_timeout caps client-requested allocation waits so one
        # unsatisfiable session can't park at the head of the FIFO forever
        self.memory_cache = MemoryCache(self.attn_cache_bytes, max_alloc_timeout=self.max_alloc_timeout)
        if self.num_hosts > 1:
            from petals_tpu.parallel.multihost import LockstepMemoryCache

            # reservation/free broadcast ALLOC/FREE so workers mirror the
            # session KV buffers by handle
            self.memory_cache = LockstepMemoryCache(self.memory_cache)

        if self._throughput_spec == "auto" and self.num_hosts == 1:
            from petals_tpu.server.throughput import get_server_throughput

            network_mbps = await self._resolve_network_mbps()
            info = await asyncio.get_running_loop().run_in_executor(
                None,
                lambda: get_server_throughput(
                    self.family, self.cfg, compute_dtype=self.compute_dtype,
                    num_blocks=self.num_blocks, quant_type=QuantType(self.quant_type).value,
                    num_devices=self.num_tp_devices or 1,
                    network_mbps=network_mbps,
                    using_relay=self.relay_via is not None,
                ),
            )
            self.throughput = info["throughput"]
            self._rps_info = info
        else:
            # multi-host "auto" probes the REAL lockstep backend after it is
            # built (workers mirror every op) — see below
            self._rps_info = None

        if self.auto_placement:
            self.first_block = await self._choose_start_block()
            self.module_uids = [
                make_uid(self.dht_prefix, i)
                for i in range(self.first_block, self.first_block + self.num_blocks)
            ]
            logger.info(f"Auto placement: serving blocks [{self.first_block}, {self.first_block + self.num_blocks})")

        # announce JOINING while blocks load (reference server.py:468-481)
        await self._announce(ServerState.JOINING)

        logger.info(
            f"Loading blocks [{self.first_block}, {self.first_block + self.num_blocks}) "
            f"of {self.model_path}"
        )
        t0 = time.perf_counter()
        # load off the event loop: the DHT node is already answering peers and
        # must not go dark for the (potentially minutes-long) weight load
        stacked = await asyncio.get_running_loop().run_in_executor(
            None, self._load_span_params, self.first_block, self.num_blocks
        )
        span_bytes = sum(block_size_bytes(run) for run in (stacked if isinstance(stacked, tuple) else (stacked,)))
        logger.info(
            f"Blocks loaded in {time.perf_counter() - t0:.1f}s "
            f"({span_bytes / 2**20:.0f} MiB for {self.num_blocks} blocks, quant={self.quant_type})"
        )

        self.backend = self._make_backend(stacked, self.first_block)
        self._install_adapters(self.backend)
        if self._throughput_spec == "auto" and self.num_hosts > 1:
            await self._measure_multihost_throughput()
        self.handler = self._make_handler()
        self.handler.register(self.rpc_server)

        from petals_tpu.utils.ping import PingAggregator

        # ride the DHT node's existing connection pool (same peer identity);
        # the first announce goes out WITHOUT next_pings — readiness must not
        # block on pinging possibly-dead successors, the announce loop fills
        # them in within one update_period
        self._ping_aggregator = PingAggregator(self.dht.pool)

        from petals_tpu.utils.tracing import (
            start_jax_trace,
            stop_jax_trace,
            trace_window_seconds,
        )

        if start_jax_trace() is not None:  # active only with PETALS_TPU_TRACE_DIR
            # bounded window: the profiler buffers until stop, so an open-ended
            # capture on a long-running server would grow host memory forever
            async def _flush_trace():
                await asyncio.sleep(trace_window_seconds())
                stop_jax_trace()

            # keep a strong ref: asyncio holds tasks weakly, and a collected
            # flush task would mean the capture never stops
            self._trace_flush_task = asyncio.create_task(_flush_trace())
            self._trace_flush_task.add_done_callback(
                log_exception_callback(logger, "trace flush")
            )

        if self.metrics_port is not None:
            from petals_tpu.telemetry.exposition import MetricsServer

            try:
                self._metrics_server = MetricsServer(port=self.metrics_port)
                logger.info(f"Prometheus /metrics on port {self._metrics_server.port}")
            except OSError as e:  # port taken: serve without scrape endpoint
                logger.warning(f"Could not bind metrics port {self.metrics_port}: {e}")
                self._metrics_server = None

        self._state = ServerState.ONLINE
        await self._announce(ServerState.ONLINE)
        self._announcer_task = asyncio.create_task(self._announce_loop())
        self._announcer_task.add_done_callback(
            log_exception_callback(logger, "announce loop")
        )
        if self.mean_balance_check_period > 0:
            self._balancer_task = asyncio.create_task(self._balance_loop())
            self._balancer_task.add_done_callback(
                log_exception_callback(logger, "balance loop")
            )
        self._ready.set()
        logger.info(f"Server ready: {self.contact_addr.to_string()} serving {self.module_uids}")

    @property
    def contact_addr(self):
        """The address this server announces: its relay circuit when hidden,
        otherwise the DHT node's own listen address."""
        return self._contact_addr or (self.dht.own_addr if self.dht is not None else None)

    async def wait_ready(self) -> None:
        await self._ready.wait()

    async def drain(self, park_ttl: float = 60.0, migrate: bool = True) -> int:
        """Graceful-shutdown prelude: stop accepting sessions, announce OFFLINE,
        and park every live session's KV in host RAM so clients can migrate
        their caches to replacement servers (``ptu.session_export``) instead of
        recomputing prefills. With ``migrate=True`` (drain-to-migrate) the
        parked KV is then proactively PUSHED to live replicas covering each
        session's span — the client's repair becomes a redirect + server-side
        ``kv_adopt``, moving zero KV bytes over the client's own link. The RPC
        server stays up — call :meth:`shutdown` after the drain window.
        Returns the number of parked sessions."""
        # a rebalance firing mid-drain would reload blocks and re-announce
        # ONLINE, overriding the OFFLINE below — stop considering moves first
        if self._balancer_task is not None:
            self._balancer_task.cancel()
            try:
                await self._balancer_task
            except asyncio.CancelledError:
                pass
            self._balancer_task = None
        parked = 0
        if self.handler is not None:
            # park BEFORE refusing steps: flipping `draining` first lets an
            # in-flight step raise and unregister its session while the park
            # snapshot awaits — the export would then find nothing. A step
            # that lands between the snapshot and the flip only makes the
            # parked copy stale, which clients top up by replaying the tail.
            parked = await self.handler.park_sessions(ttl=park_ttl)
            self.handler.draining = True
        self._state = ServerState.OFFLINE
        try:
            await self._announce(ServerState.OFFLINE, expiration=dht_time() + 60)
        except Exception as e:
            # best-effort: the DHT entry expires on its own if we cannot reach it
            logger.debug("OFFLINE announce during drain failed: %r", e)
        if parked:
            logger.info(f"Draining: parked {parked} session(s) for migration")
        if parked and migrate:
            pushed = await self._migrate_parked_sessions()
            if pushed:
                logger.info(f"Drain-to-migrate: pushed {pushed} session(s) to replicas")
        return parked

    async def _migrate_parked_sessions(self, deadline_s: float = 30.0) -> int:
        """Push every parked session's KV to a live replica covering its span
        (drain-to-migrate / rebalance path). Best-effort per session: a
        session with no covering replica, or whose push fails, simply stays
        parked — the client falls back to export-over-its-own-link or replay."""
        handler = self.handler
        if handler is None or not handler._parked or self.dht is None:
            return 0
        from petals_tpu.utils.dht_utils import get_remote_module_infos

        all_uids = [
            make_uid(self.dht_prefix, i) for i in range(self.cfg.num_hidden_layers)
        ]
        try:
            infos, addr_book = await get_remote_module_infos(self.dht, all_uids)
        except Exception as e:
            logger.warning(f"Drain-to-migrate skipped: swarm lookup failed ({e!r})")
            return 0
        migrated = 0
        for session_id, snap in list(handler._parked.items()):
            dest = self._pick_migration_target(
                infos, addr_book, snap["start"], snap["end"]
            )
            if dest is None:
                logger.info(
                    f"No live replica covers blocks [{snap['start']}, {snap['end']}): "
                    f"session {session_id!r} stays parked for client-side export"
                )
                continue
            peer_id, addr = dest
            if await handler.migrate_parked_to(
                session_id, snap, peer_id.to_string(), addr.to_string(),
                deadline_s=deadline_s,
            ):
                migrated += 1
        return migrated

    def _pick_migration_target(self, infos, addr_book, start: int, end: int):
        """Highest-throughput ONLINE peer (not us) serving every block of
        [start, end) with a known contact address, or None. Decode-tier
        replicas win ties-by-class: a migrated session is mid-generation, so
        its KV belongs on the tier shaped for token-by-token decoding."""
        candidates = None
        for i in range(start, end):
            info = infos[i] if i < len(infos) else None
            if info is None:
                return None
            here = {
                pid for pid, si in info.servers.items()
                if si.state == ServerState.ONLINE and pid in addr_book
                and pid != self.dht.peer_id
            }
            candidates = here if candidates is None else (candidates & here)
            if not candidates:
                return None
        best, best_key = None, (-1, -1.0)
        for pid in candidates:
            si = infos[start].servers[pid]
            tier = getattr(si, "phase_tier", None)
            key = (1 if tier == "decode" else 0, si.throughput or 0.0)
            if key > best_key:
                best, best_key = pid, key
        return (best, addr_book[best]) if best is not None else None

    async def shutdown(self) -> None:
        # a drain-to-migrate push racing shutdown must not hang teardown on a
        # slow (or chaos-delayed) destination peer — tell it to abort now;
        # aborted sessions stay parked and clients repair via export/replay
        if self.handler is not None:
            self.handler.abort_migrations()
        if self._balancer_task is not None:
            self._balancer_task.cancel()
            try:
                await self._balancer_task
            except asyncio.CancelledError:
                pass
        if self._announcer_task is not None:
            self._announcer_task.cancel()
            try:
                await self._announcer_task
            except asyncio.CancelledError:
                pass
        try:
            await self._announce(ServerState.OFFLINE, expiration=dht_time() + 60)
        except Exception as e:
            logger.debug("OFFLINE announce during shutdown failed: %r", e)
        from petals_tpu.utils.tracing import stop_jax_trace

        if self._trace_flush_task is not None:
            self._trace_flush_task.cancel()
        stop_jax_trace()
        if self._metrics_server is not None:
            self._metrics_server.close()
            self._metrics_server = None
        if self.num_hosts > 1 and self.backend is not None:
            # release the lockstep workers before the handler dies — they sit
            # in a blocking broadcast wait otherwise
            try:
                self.backend.shutdown_workers()
            except Exception as e:
                logger.warning(f"multihost worker shutdown broadcast failed: {e!r}")
        if self.handler is not None:
            self._time_loop_turns(self.handler, False)
            self.handler.shutdown()
        # flush + close the journal's JSONL write-through sink AFTER the
        # handler stops emitting: the last scheduler decisions of this run
        # must reach disk even if the process dies right after shutdown.
        # The in-memory ring stays usable (close only detaches the sink).
        from petals_tpu.telemetry import get_journal

        get_journal().close()
        if self._relay_registrar is not None:
            await self._relay_registrar.stop()
        if self.dht is not None:
            await self.dht.shutdown()
        if self.rpc_server is not None:
            await self.rpc_server.stop()

    # ------------------------------------------------------------------ announcing

    def _server_info(self, state: ServerState) -> ServerInfo:
        cache_tokens_left = None
        if self.memory_cache is not None and self.backend is not None:
            per_token = self.backend.cache.cache_bytes_per_token()
            if getattr(self, "kv_quant_type", "none") != "none":
                # quantized paged pool: a cached token costs wire bytes, so
                # the same budget advertises ~4x the remaining capacity
                per_token = self.backend.cache.kv_bytes_per_token()
            cache_tokens_left = int(self.memory_cache.bytes_left // max(per_token, 1))
        rps = getattr(self, "_rps_info", None) or {}
        return ServerInfo(
            state=state,
            throughput=self.throughput,
            inference_rps=rps.get("inference_rps"),
            forward_rps=rps.get("forward_rps"),
            network_rps=rps.get("network_rps"),
            start_block=self.first_block,
            end_block=self.first_block + self.num_blocks,
            public_name=self.public_name,
            version=petals_tpu.__version__,
            compute_dtype=str(jnp.dtype(self.compute_dtype).name),
            quant_type=self.quant_type,
            adapters=tuple(
                sorted(self.backend.adapters) if self.backend is not None else ()
            ),
            cache_tokens_left=cache_tokens_left,
            next_pings=dict(self._next_pings) or None,
            server_gen=(
                self.handler.server_gen_params is not None
                if getattr(self, "handler", None) is not None else None
            ),
            # sampling rides the same device-gen machinery: any server that
            # can gen greedily can warp + sample on device too
            server_gen_sampling=(
                self.handler.server_gen_params is not None
                if getattr(self, "handler", None) is not None else None
            ),
            # speculative decoding capability: k drafts verified per tick
            # (informational — spec output is bit-identical to plain decode)
            spec_k=(
                self.spec_k
                if getattr(self, "handler", None) is not None
                and self.handler.draft_model is not None else None
            ),
            # lane-pool / scheduler occupancy for load-aware routing and the
            # health monitor; None on servers without continuous batching
            pool=(
                self.handler.batcher.occupancy_info()
                if getattr(self, "handler", None) is not None
                and self.handler.batcher is not None else None
            ),
            # per-server telemetry digest: the announce loop's cadence makes
            # the tok/s figure an update_period-window average
            telemetry=self._telemetry_digest(),
            compile_stats=self._compile_stats(),
            # integrity observatory: self-probe digest_hex + quarantine flag
            # (refreshed by the announce loop; None until the first refresh)
            integrity=getattr(self, "_integrity_info", None),
            # where /metrics and /journal live, so a breaching client can
            # fetch this server's journal excerpt for its trace_id
            metrics_port=(
                self._metrics_server.port
                if getattr(self, "_metrics_server", None) is not None else None
            ),
            # disaggregated serving tier; generalists announce it too so
            # run_health's tier column distinguishes "old server" from
            # "explicit generalist"
            phase_tier=self.phase_tier,
        )

    def _telemetry_digest(self) -> Optional[dict]:
        from petals_tpu.telemetry.exposition import telemetry_digest
        from petals_tpu.telemetry.integrity import cap_announce_payload

        try:
            # size-capped: the digest rides every widely-replicated DHT
            # announce, and the ledger sub-dict can grow with tenant count
            return cap_announce_payload(telemetry_digest())
        except Exception as e:  # an announce must never fail over metrics
            logger.debug("telemetry digest failed: %r", e)
            return None

    async def _refresh_integrity(self) -> None:
        """Refresh the announce-visible integrity digest: the span's
        self-probe fingerprint (the SAME ``ptu.probe`` path external canary
        probers hit, so an injected ``integrity.corrupt`` is visible in the
        announce too) plus this server's quarantine flag from the
        process-local registry. Announce-must-never-fail discipline: any
        error leaves the previous digest in place."""
        if getattr(self, "handler", None) is None or self.backend is None:
            return
        try:
            import numpy as np

            from petals_tpu.ops import fingerprint as fp_ops
            from petals_tpu.telemetry.integrity import (
                cap_announce_payload,
                get_quarantine,
            )

            reply = await self.handler.rpc_probe({"tokens": 4}, None)
            peer_str = ""
            if self._identity is not None:
                peer_str = self._identity.peer_id.to_string()
            self._integrity_info = cap_announce_payload({
                "self_digest": fp_ops.digest_hex(
                    np.asarray(reply["fp"], dtype=np.float32)
                ),
                "fp_seed": int(reply["fp_seed"]),
                "span": f"{reply['first_block']}:{reply['first_block'] + reply['n_blocks']}",
                "quarantined": bool(
                    peer_str and get_quarantine().is_quarantined(peer_str)
                ),
            })
        except Exception as e:
            logger.debug("integrity digest refresh failed: %r", e)

    def _compile_stats(self) -> Optional[dict]:
        from petals_tpu.telemetry.observatory import compile_stats_digest

        try:
            return compile_stats_digest()
        except Exception as e:  # an announce must never fail over metrics
            logger.debug("compile stats digest failed: %r", e)
            return None

    async def _announce(self, state: ServerState, expiration: Optional[float] = None) -> None:
        if chaos.ENABLED and chaos.fire(chaos.SITE_ANNOUNCE) is not None:
            # injected announce loss: the DHT record silently ages out, as if
            # the store never reached the network
            logger.warning("chaos: dropping DHT announce (%s)", state)
            return
        expiration = expiration or (dht_time() + max(2 * self.update_period, 60.0))
        if state != ServerState.OFFLINE:
            # refresh the announce-visible self-probe digest first, so the
            # ServerInfo built below carries this period's integrity view
            await self._refresh_integrity()
        await declare_active_modules(
            self.dht, self.module_uids, self._server_info(state), expiration,
            contact_addr=self._contact_addr,
        )
        if state != ServerState.OFFLINE:
            from petals_tpu.utils.dht_utils import declare_model

            await declare_model(
                self.dht, self.dht_prefix,
                num_blocks=self.cfg.num_hidden_layers,
                expiration_time=expiration,
                public_name=self.public_name,
                model_type=self.family.name,
            )

    def _load_span_params(self, first_block: int, num_blocks: int):
        # fused qkv/gate-up halves the Pallas call count at decode; off under
        # TP (per-leaf PartitionSpecs), with adapters (unfused leaf names),
        # and multi-host (mesh always present; workers load fuse=False)
        fuse = (
            (self.num_tp_devices or 1) <= 1
            and not self.adapter_paths
            and self.num_hosts == 1
        )
        from petals_tpu.models.registry import span_runs

        def stack_run(start: int, length: int) -> dict:
            blocks = [self._load_block_converted(first_block + i, fuse=fuse) for i in range(start, start + length)]
            # leaf by leaf, each block's array let go as soon as its stack exists: the load peaks at
            # the span plus one stacked leaf, not at twice the span (ROADMAP D13 (1))
            stacked = {}
            for name in list(blocks[0]):
                leaves = [block.pop(name) for block in blocks]
                stacked[name] = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *leaves)
                del leaves
            return stacked

        # a span of more than one kind of block (ModelFamily.block_kind): one stacked tree per run of
        # consecutive blocks of one kind, in order (TransformerBackend reads the kinds and where each
        # run starts from the family again); any other span: the one stacked tree
        runs = span_runs(self.family.span_kinds(self.cfg, first_block, num_blocks))
        stacked = tuple(stack_run(start, length) for _, start, length in runs)
        return stacked[0] if len(stacked) == 1 else stacked

    def _load_block_converted(self, block_index: int, *, fuse: bool) -> dict:
        """One block, quantized per --quant_type. Quantized conversions are
        persisted in the disk cache (utils/quant_cache.py): the encode is a
        pure function of (checkpoint, kind, fuse), so restarts stream packed
        bytes instead of re-encoding (reference re-quantizes every start,
        convert_block.py:76-115 — acceptable on CUDA, minutes at 405B here)."""
        use_cache = self.quant_weight_cache and QuantType(self.quant_type) != QuantType.NONE
        if use_cache:
            from petals_tpu.utils import quant_cache

            path = quant_cache.cache_path(
                self.model_path, block_index, QuantType(self.quant_type).value,
                fuse=fuse, revision=self.revision, cache_dir=self.cache_dir,
                dtype_tag=jnp.dtype(self.compute_dtype).name,
            )
            cached = quant_cache.load_quantized_block(path)
            if cached is not None:
                return cached
        params = convert_block_params(
            load_block_params(
                self.model_path, block_index, dtype=self.compute_dtype,
                family=self.family, cfg=self.cfg, revision=self.revision,
                cache_dir=self.cache_dir,
            ),
            self.family.name,
            self.quant_type,
            fuse=fuse,
        )
        if use_cache:
            try:
                quant_cache.save_quantized_block(path, params)
            except OSError as e:
                logger.warning(f"Could not cache quantized block {block_index}: {e!r}")
        return params

    def _install_adapters(self, backend: TransformerBackend) -> None:
        if not self.adapter_paths:
            return
        from petals_tpu.utils.peft import load_adapter, stack_adapter

        block_range = range(self.first_block, self.first_block + self.num_blocks)
        for path in self.adapter_paths:
            adapter = load_adapter(path, self.family.name, block_range=block_range)
            stacked = stack_adapter(adapter, self.first_block, self.num_blocks, self.compute_dtype)
            backend.adapters[adapter.name] = (stacked, adapter.scaling)
        logger.info(f"Hosting adapters: {sorted(backend.adapters)}")

    def _make_handler(self) -> TransformerHandler:
        """Handler construction shared by start() and partial re-formation.
        Continuous-batching pool sizing: lanes cost HBM for their full lane
        length, so cap the pool at half the cache budget (private sessions
        and training still need room) and disable if fewer than 2 lanes fit."""
        batch_max_length = self.batch_max_length or min(self.inference_max_length, 1024)
        batch_lanes = self.batch_lanes
        if batch_lanes is None:
            lane_bytes = self.backend.cache.lane_bytes(batch_max_length)
            affordable = int(self.memory_cache.max_size_bytes // 2 // max(lane_bytes, 1))
            batch_lanes = max(min(8, affordable), 0)
        handler = TransformerHandler(
            self.backend,
            dht_prefix=self.dht_prefix,
            memory_cache=self.memory_cache,
            server_info_fn=lambda: dataclasses.asdict(self._server_info(ServerState.ONLINE)),
            identity=self._identity,
            compression=self.compression,
            inference_max_length=self.inference_max_length,
            request_timeout=self.request_timeout,
            session_timeout=self.session_timeout,
            step_timeout=self.step_timeout,
            batching=self.batching and batch_lanes >= 2,
            batch_lanes=batch_lanes,
            batch_max_length=batch_max_length,
            page_size=self.page_size or None,
            n_pages=self.n_pages,
            prefill_token_budget=self.prefill_token_budget,
            swap_host_bytes=self.swap_host_bytes,
            preemption_policy=self.preemption_policy,
            prefix_cache_bytes=self.prefix_cache_bytes,
            prefix_share_scope=self.prefix_share_scope,
            prefix_device_bytes=self.prefix_device_bytes,
            prefix_cache_policy=self.prefix_cache_policy,
            server_gen_params=self._load_server_gen_params(),
            draft_model=self._load_draft_model(),
            spec_k=self.spec_k if self.draft_model_path else None,
        )
        self._time_loop_turns(handler, True)
        return handler

    def _time_loop_turns(self, handler: TransformerHandler, serving: bool) -> None:
        """While a handler serves, the loop's turn clock adds to its
        ``batcher.stats["loop_busy_s" | "loop_busy_sq" | "loop_turns"]``."""
        batcher = getattr(handler, "batcher", None)
        if self._turn_clock is not None and batcher is not None:
            (self._turn_clock.attach if serving else self._turn_clock.detach)(batcher.stats)

    def _load_draft_model(self):
        """Speculative-decoding draft (server/spec_decode.py): a small full
        model loaded alongside the span. Same eligibility as server-side
        generation — the verify step embeds/samples with the client leaves —
        plus a paged pool (verification rides the chunk-scatter machinery).
        Any load failure degrades to plain decode, never a dead server."""
        if not self.draft_model_path or self.spec_k < 1:
            return None
        from petals_tpu.server.backend import SPEC_CUTS_BACK

        self.backend.cache.refuse("speculative decoding (--draft_model)", SPEC_CUTS_BACK)
        if (
            not self.server_side_generation
            or self.num_blocks != self.cfg.num_hidden_layers
            or self.first_block != 0
            or self.num_hosts > 1
            or not self.page_size
        ):
            logger.warning(
                "Speculative decoding disabled: --draft_model needs a "
                "full-span single-host server with server-side generation "
                "and a paged lane pool"
            )
            return None
        if self._draft_model is not None:
            return self._draft_model
        try:
            from petals_tpu.server.spec_decode import DEFAULT_WINDOW, DraftModel

            self._draft_model = DraftModel.from_pretrained(
                self.draft_model_path,
                spec_k=self.spec_k,
                window=int(self.draft_window or DEFAULT_WINDOW),
                quant_type=self.draft_quant_type,
                revision=self.revision,
                cache_dir=self.cache_dir,
            )
        except Exception as e:
            logger.warning(f"Speculative decoding disabled (draft load failed): {e}")
            self._draft_model = None
        return self._draft_model

    def _load_server_gen_params(self):
        """Client leaves (embed/norm/head) for the device-side greedy
        generation loop — full-span servers, single-host (TP meshes
        included: the loop reuses the span step fn, GSPMD partitions the
        whole scan, and the replicated head/embed ride along; lockstep
        groups stay excluded — the loop would need broadcast ops). Loaded
        in f32 so logits match the client's own lm_logits bit-for-bit."""
        if not self.server_side_generation:
            return None
        if (
            self.num_blocks != self.cfg.num_hidden_layers
            or self.first_block != 0
            or self.num_hosts > 1
        ):
            return None
        try:
            from petals_tpu.client.from_pretrained import load_client_params

            params = load_client_params(
                self.model_path, dtype=jnp.float32,
                family=self.family, cfg=self.cfg,
            )
            logger.info("Server-side generation enabled (client leaves loaded)")
            return params
        except Exception as e:
            logger.warning(f"Server-side generation disabled: {e}")
            return None

    def _make_raw_backend(self, stacked, first_block: int) -> TransformerBackend:
        """Backend construction WITHOUT the lockstep wrap (the live span move
        rebuilds raw backends under the broadcast lock and re-wraps itself)."""
        mesh = None
        tp = self.num_tp_devices or 1
        sp = self.num_sp_devices or 1
        # after partial re-formation, jax.devices() STILL lists the dead
        # members' chips (jax.distributed stays initialized); meshes must be
        # built from this host's devices only
        devices = jax.local_devices() if self._local_devices_only else None
        if self.num_hosts > 1:
            from petals_tpu.parallel.multihost import multihost_mesh

            # tp (x sp) over the GLOBAL device set (all hosts' chips);
            # num_tp_devices None means every device in the group divided by sp
            mesh = multihost_mesh(self.num_tp_devices, sp)
        elif sp > 1:
            from petals_tpu.parallel.mesh import serving_mesh

            mesh = serving_mesh(tp, sp, devices=devices)
        elif tp > 1:
            from petals_tpu.parallel.mesh import tp_mesh

            mesh = tp_mesh(tp, devices=devices)
        return TransformerBackend(
            self.family,
            self.cfg,
            stacked,
            first_block=first_block,
            n_blocks=self.num_blocks,
            memory_cache=self.memory_cache,
            compute_dtype=self.compute_dtype,
            max_chunk_size_bytes=self.max_chunk_size_bytes,
            use_flash=self.use_flash,
            mesh=mesh,
            kv_quant_type=self.kv_quant_type,
        )

    def _make_backend(self, stacked, first_block: int) -> TransformerBackend:
        backend = self._make_raw_backend(stacked, first_block)
        if self.num_hosts > 1:
            from petals_tpu.parallel.multihost import LockstepBackend

            backend = LockstepBackend(backend)
        return backend

    async def _choose_start_block(self, throughputs=None) -> int:
        """Pick the span covering the swarm's weakest blocks (reference
        server.py:403-418 via block_selection)."""
        import numpy as np

        from petals_tpu.data_structures import make_uid as _mk
        from petals_tpu.server.block_selection import choose_best_start, compute_throughputs
        from petals_tpu.utils.dht_utils import get_remote_module_infos

        if throughputs is None:
            all_uids = [_mk(self.dht_prefix, i) for i in range(self.cfg.num_hidden_layers)]
            infos, _ = await get_remote_module_infos(self.dht, all_uids)
            throughputs = compute_throughputs(infos, exclude_peer=self.dht.peer_id)
        return choose_best_start(np.asarray(throughputs), self.num_blocks)

    async def _balance_loop(self) -> None:
        """Periodically re-evaluate placement and move if the swarm would gain
        (reference server.py:369-384 rebalance loop)."""
        import random as _random

        from petals_tpu.data_structures import make_uid as _mk
        from petals_tpu.server.block_selection import should_choose_other_blocks
        from petals_tpu.utils.dht_utils import get_remote_module_infos

        while True:
            await asyncio.sleep(self.mean_balance_check_period * (0.5 + _random.random()))
            try:
                all_uids = [_mk(self.dht_prefix, i) for i in range(self.cfg.num_hidden_layers)]
                infos, _ = await get_remote_module_infos(self.dht, all_uids)
                if should_choose_other_blocks(
                    self.dht.peer_id, infos, self.num_blocks,
                    balance_quality=self.balance_quality,
                ):
                    from petals_tpu.server.block_selection import compute_throughputs

                    throughputs = compute_throughputs(infos, exclude_peer=self.dht.peer_id)
                    new_start = await self._choose_start_block(throughputs)
                    if new_start != self.first_block:
                        logger.info(f"Rebalancing: moving span to start at block {new_start}")
                        await self._reload_span(new_start)
            except Exception as e:
                logger.warning(f"Balance check failed: {e}")

    async def resize(self, new_first_block: int) -> bool:
        """Autoscaler actuator: move this server's span to start at
        ``new_first_block`` (same span length), migrating live sessions to
        replicas first. A no-op (returns False) when already there; raises
        ValueError on an out-of-range target so a bad policy decision fails
        loudly instead of announcing blocks that do not exist."""
        if not 0 <= new_first_block <= self.cfg.num_hidden_layers - self.num_blocks:
            raise ValueError(
                f"resize target {new_first_block} outside "
                f"[0, {self.cfg.num_hidden_layers - self.num_blocks}]"
            )
        if new_first_block == self.first_block:
            return False
        logger.info(f"Resize: moving span to start at block {new_first_block}")
        await self._reload_span(new_first_block)
        return True

    async def _reload_span(self, new_first_block: int) -> None:
        """Move to a new span: announce OFFLINE on the old blocks, reload, and
        re-register (reference ModuleContainer restart, server.py:369-384)."""
        old_uids = self.module_uids
        try:
            await declare_active_modules(
                self.dht, old_uids, self._server_info(ServerState.OFFLINE), dht_time() + 60
            )
        except Exception as e:
            # best-effort: stale entries expire; the reload must not abort here
            logger.debug("OFFLINE announce before span reload failed: %r", e)
        self.first_block = new_first_block
        self.module_uids = [
            make_uid(self.dht_prefix, i)
            for i in range(self.first_block, self.first_block + self.num_blocks)
        ]
        self._state = ServerState.JOINING  # the announce loop must NOT say ONLINE yet
        await self._announce(ServerState.JOINING)

        if self.num_hosts > 1:
            # LIVE SPAN MOVE for a lockstep group (round 5; previously moves
            # required restarting every member). Quiesce first: park live
            # sessions (their owners migrate via ptu.session_export — the
            # parked copies are host RAM, they survive the move), refuse new
            # compute, and barrier the priority queue so every in-flight op's
            # broadcasts are done. Then one OP_RELOAD_SPAN broadcast rebuilds
            # leader + workers from the checkpoint SIMULTANEOUSLY — the
            # sharded-param device_puts are collectives that pair exactly
            # like at startup, and the broadcast lock (held around the whole
            # rebuild) keeps any other collective from interleaving.
            from petals_tpu.server.task_queue import PRIORITY_BARRIER

            if self.handler is None:
                raise RuntimeError("live span move before the server started serving")
            try:
                await self.handler.park_sessions(ttl=60.0)
                # rebalance-migrate: the new span can't serve the old span's
                # KV, so hand it to replicas that can (best-effort; failures
                # leave the parked copy for client-side export)
                await self._migrate_parked_sessions()
                self.handler.draining = True
                await self.handler.queue.submit(
                    lambda: None, priority=PRIORITY_BARRIER, size=0
                )

                def build_raw():
                    stacked = self._load_span_params(self.first_block, self.num_blocks)
                    return self._make_raw_backend(stacked, self.first_block)

                old_backend = self.backend
                self.backend = await asyncio.get_running_loop().run_in_executor(
                    None, lambda: old_backend.reload_span(self.first_block, build_raw)
                )
                self._install_adapters(self.backend)
                await self.handler.swap_backend(self.backend)
            finally:
                # NEVER leave the server permanently refusing sessions: if the
                # move failed post-broadcast the group is degraded and ops
                # fail through _check_group with a clear error anyway
                self.handler.draining = False
        else:
            if self.handler is not None:
                # park + migrate BEFORE the batcher rebuild kills pooled
                # sessions: rebalance used to be a session-killer (clients
                # replayed their whole prefix); now their KV moves to a
                # replica and the repair is a redirect + kv_adopt
                try:
                    if await self.handler.park_sessions(ttl=60.0):
                        await self._migrate_parked_sessions()
                except Exception as e:
                    logger.warning(f"Rebalance-migrate failed (sessions will replay): {e!r}")
            stacked = await asyncio.get_running_loop().run_in_executor(
                None, self._load_span_params, self.first_block, self.num_blocks
            )
            # Build a FRESH backend: open PRIVATE sessions keep their reference
            # to the old one (consistent old-span compute until they close);
            # pooled sessions are invalidated by the batcher rebuild inside
            # swap_backend (the shared lane pool cannot serve two spans). The
            # constructor also re-applies TP sharding for mesh servers.
            self.backend = self._make_backend(stacked, self.first_block)
            self._install_adapters(self.backend)
            await self.handler.swap_backend(self.backend)
        # stale by construction: measured for the OLD span's successor block;
        # the announce loop re-measures for the new span within one period
        self._next_pings = {}
        self._state = ServerState.ONLINE
        await self._announce(ServerState.ONLINE)

    async def _announce_loop(self) -> None:
        while True:
            await asyncio.sleep(self.update_period)
            try:
                if self.num_hosts > 1 and await self._check_group_health():
                    return  # degraded: final OFFLINE announce already sent
                await self._measure_next_pings()
                await self._announce(self._state)
            except Exception as e:
                logger.warning(f"Announce failed: {e}")

    async def _check_group_health(self) -> bool:
        """Multi-host worker-death detection: when a lockstep op has degraded
        the group (a member died mid-collective), stop accepting sessions and
        go OFFLINE so clients fail over NOW — in-flight sessions already got
        clean MultihostDegraded errors from their steps. Then PARTIALLY
        RE-FORM (round 5): the surviving leader falls back to single-host
        serving — possibly a shorter span — with no process restarted; only
        the dead worker needs a replacement (which joins a future group).
        Returns True once degraded (the announce loop then stops; a
        successful re-formation starts a fresh one)."""
        from petals_tpu.parallel.multihost import group_degraded

        err = group_degraded()
        if err is None:
            return False
        logger.error(
            f"multihost group degraded ({err!r}): draining, going OFFLINE, "
            f"then re-forming single-host from the checkpoint"
        )
        if self.handler is not None:
            self.handler.draining = True
        self._state = ServerState.OFFLINE
        await self._announce(ServerState.OFFLINE)
        try:
            await self._reform_single_host()
        except Exception as e:
            logger.exception(
                f"single-host re-formation failed ({e!r}); staying OFFLINE — "
                f"restart the leader and workers to re-form the group"
            )
            # the reform may have died after its JOINING announce: the
            # swarm's final view of this peer must be OFFLINE, not 'coming
            # online soon'
            self._state = ServerState.OFFLINE
            with contextlib.suppress(Exception):
                await self._announce(ServerState.OFFLINE, expiration=dht_time() + 60)
            return True  # the announce loop stops; operator intervention needed
        # re-formed: num_hosts is now 1, so this health check disarms itself
        # and the announce loop keeps running for the single-host server
        return False

    async def _reform_single_host(self) -> None:
        """Partial re-formation after losing a lockstep group member
        (VERDICT r4 #4, elasticity spirit of reference server.py:369-384,
        which restarts only the module container — not the swarm's other
        members). XLA bakes the group mesh into every compiled program and
        shards params across member processes, so the OLD backend is
        unrecoverable by construction; what survives is this process, its
        DHT identity, its listening address, and the swarm's view of it.
        The leader therefore rebuilds a LOCAL backend from the checkpoint
        (shrinking the span if this host alone cannot hold it), swaps in a
        fresh memory cache + handler on the SAME RpcServer, and re-announces.
        Clients of the old group failover through the normal banned-peer
        path and find the re-formed server at the same address."""
        # the dead member can never join jax's exit-time shutdown barrier;
        # without this the interpreter-exit hook aborts the process (FATAL)
        import atexit

        try:
            import jax as _jax

            atexit.unregister(_jax.distributed.shutdown)
        except Exception:  # swarmlint: disable=no-silent-except — probing a version-dependent private hook: absence means there is nothing to unregister
            pass

        # local compute shape: the sp axis spanned the group, so locally it
        # re-forms as plain tp over this host's chips (a future replacement
        # group re-enables sp); tp=1 retry below if the local width doesn't
        # divide the model (kv-head divisibility was only checked for the
        # group width)
        n_local = len(jax.local_devices())
        group_devices = max(jax.device_count(), 1)
        local_tp = n_local if n_local > 1 else 1
        self.num_sp_devices = None
        self.num_tp_devices = local_tp if local_tp > 1 else None

        # shrink the span if one host cannot hold what the group held;
        # choose_num_blocks sizes ONE chip, and local tp shards params over
        # local_tp chips, so capacity scales with the width actually used
        from petals_tpu.server.block_utils import choose_num_blocks

        old_num = self.num_blocks
        try:
            max_local = choose_num_blocks(
                self.family, self.cfg, quant_type=self.quant_type,
                attn_cache_bytes=self.attn_cache_bytes or 0,
            ) * local_tp
        except Exception as e:
            logger.warning("Local capacity estimate failed, keeping span size: %r", e)
            max_local = old_num
        self.num_blocks = max(1, min(old_num, max_local))
        self.module_uids = [
            make_uid(self.dht_prefix, i)
            for i in range(self.first_block, self.first_block + self.num_blocks)
        ]
        if self.num_blocks != old_num:
            logger.warning(
                f"re-formation shrinks the span to [{self.first_block}, "
                f"{self.first_block + self.num_blocks}) — one host cannot "
                f"hold the group's {old_num} blocks"
            )
        self._state = ServerState.JOINING
        await self._announce(ServerState.JOINING)

        self.num_hosts = 1  # _make_backend now builds a local (non-lockstep) backend
        self._local_devices_only = True  # jax.devices() still lists dead members
        stacked = await asyncio.get_running_loop().run_in_executor(
            None, self._load_span_params, self.first_block, self.num_blocks
        )
        try:
            self.backend = self._make_backend(stacked, self.first_block)
        except Exception as e:
            if (self.num_tp_devices or 1) > 1:
                logger.warning(f"local tp={self.num_tp_devices} mesh failed ({e!r}); re-forming tp=1")
                self.num_tp_devices = None
                self.backend = self._make_backend(stacked, self.first_block)
            else:
                raise
        self._install_adapters(self.backend)
        # fresh budget: the old (Lockstep-wrapped) cache's mirrors died with
        # the workers; old sessions already got their clean errors
        old_handler = self.handler
        self.memory_cache = MemoryCache(
            self.attn_cache_bytes, max_alloc_timeout=self.max_alloc_timeout
        )
        self.handler = self._make_handler()
        self.handler.register(self.rpc_server)  # replaces the old registrations
        if old_handler is not None:
            self._time_loop_turns(old_handler, False)
            with contextlib.suppress(Exception):
                old_handler.shutdown()
        self._next_pings = {}
        # the announced throughput was measured for the GROUP's devices;
        # rescale conservatively by the width this host keeps so routing
        # doesn't over-prefer the degraded server (a fresh probe would be
        # more precise — the rescale is honest enough until the operator's
        # replacement group re-measures)
        used = min(local_tp, n_local)
        if group_devices > used:
            self.throughput = self.throughput * used / group_devices
            logger.info(
                f"throughput rescaled {group_devices}->{used} devices: "
                f"{self.throughput:.2f}"
            )
        self._state = ServerState.ONLINE
        # everything destructive already succeeded: a transient announce
        # failure must NOT mark the healthy re-formed server failed — the
        # announce loop retries every update_period
        try:
            await self._announce(ServerState.ONLINE)
        except Exception as e:
            logger.warning(f"post-reform ONLINE announce failed ({e!r}); the announce loop will retry")
        logger.info(
            f"re-formed single-host: serving {self.module_uids} at "
            f"{self.contact_addr.to_string()}"
        )

    async def _resolve_network_mbps(self):
        network_mbps = self.network_mbps
        if network_mbps is None and self.initial_peers:
            # measure the real path to swarm peers (utils/bandwidth.py) —
            # the speedtest-cli role; falls back to the loopback stack probe
            from petals_tpu.dht.routing import PeerAddr
            from petals_tpu.utils.bandwidth import probe_swarm_bandwidth_mbps

            peer_addrs = [
                p if isinstance(p, PeerAddr) else PeerAddr.from_string(p)
                for p in self.initial_peers
            ]
            network_mbps = await probe_swarm_bandwidth_mbps(self.dht.pool, peer_addrs)
        return network_mbps

    async def _measure_multihost_throughput(self) -> None:
        """Auto-throughput for multi-host spans (v2): probe the REAL lockstep
        backend — every op broadcasts, so the workers mirror the probe exactly
        like serving traffic. Measures the whole span (already 'per num_blocks'),
        never disk-cached (the number belongs to this group composition)."""
        import time as _time

        from petals_tpu.server.throughput import RELAY_PENALTY, measure_network_rps

        rng = np.random.RandomState(0)
        width = self.backend.hidden_size  # what crosses the wire: a stream's rows where the family has one
        step_h = rng.randn(1, 1, width).astype(np.float32) * 0.01
        # 1024-token forwards: the SAME basis as the single-host probe
        # (throughput.py measure_compute_rps) — announced numbers must be
        # comparable across servers or routing deprioritizes multi-host spans
        fwd_h = rng.randn(1, 1024, width).astype(np.float32) * 0.01

        descriptors = self.backend.cache_descriptors(1, 64, 0, self.num_blocks)
        async with self.memory_cache.allocate_cache(*descriptors) as handles:
            kv = tuple(self.memory_cache.get_buffers(*handles))

            def probe():
                nonlocal kv
                out, kv2 = self.backend.inference_step(step_h, kv, 0, handles=handles)
                np.asarray(out)
                pos, n = 1, 20
                t0 = _time.perf_counter()
                for _ in range(n):
                    out, kv2 = self.backend.inference_step(step_h, kv2, pos, handles=handles)
                    pos += 1
                np.asarray(out)
                inference_rps = n / (_time.perf_counter() - t0)
                np.asarray(self.backend.forward(fwd_h))  # compile
                t0 = _time.perf_counter()
                for _ in range(3):
                    np.asarray(self.backend.forward(fwd_h))
                forward_rps = 3 * fwd_h.shape[1] / (_time.perf_counter() - t0)
                return inference_rps, forward_rps

            # lockstep ops block on collectives: keep the event loop free
            inference_rps, forward_rps = await asyncio.get_running_loop().run_in_executor(
                None, probe
            )
        network_mbps = await self._resolve_network_mbps()
        network_rps = measure_network_rps(width, network_mbps=network_mbps)
        if self.relay_via is not None:
            network_rps *= RELAY_PENALTY
        # the span probe already spreads compute over num_blocks blocks
        self.throughput = min(forward_rps, network_rps)
        self._rps_info = {
            "throughput": self.throughput,
            "inference_rps": inference_rps,
            "forward_rps": forward_rps,
            "network_rps": network_rps,
        }
        logger.info(f"multihost auto-throughput: {self._rps_info}")

    async def _measure_next_pings(self) -> None:
        """Ping the servers that could follow us in an inference chain — those
        serving our end block — and stage their RTTs for the next announce
        (reference server.py:717-751: min-latency routing is half-blind to
        multi-hop chains without these inter-server edges)."""
        if self._ping_aggregator is None:
            return
        next_block = self.first_block + self.num_blocks
        if next_block >= self.cfg.num_hidden_layers:
            self._next_pings = {}
            return
        try:
            from petals_tpu.utils.dht_utils import get_remote_module_infos
            from petals_tpu.utils.random_utils import sample_up_to

            infos, addr_book = await get_remote_module_infos(
                self.dht, [make_uid(self.dht_prefix, next_block)]
            )
            if not infos or infos[0] is None:
                self._next_pings = {}
                return
            own = self.dht.peer_id
            candidates = [
                addr_book[pid]
                for pid, si in infos[0].servers.items()
                # OFFLINE/JOINING announcements linger until expiry; pinging
                # them would crowd live successors out of the sample
                if pid != own and pid in addr_book and si.state == ServerState.ONLINE
            ]
            candidates = sample_up_to(candidates, 10)
            if candidates:
                await asyncio.wait_for(self._ping_aggregator.ping(candidates), 10.0)
            candidate_ids = {addr.peer_id for addr in candidates}
            self._next_pings = {
                pid.to_string(): rtt
                for pid, rtt in self._ping_aggregator.to_dict().items()
                if pid in candidate_ids and math.isfinite(rtt)
            }
        except Exception as e:
            logger.debug(f"next_pings round failed: {e}")
