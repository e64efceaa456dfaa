"""The server's RPC surface (counterpart of reference
src/petals/server/handler.py:55-592 — rpc_inference / rpc_forward /
rpc_backward / rpc_info; streaming variants are subsumed by the framed
transport, which chunks large frames at the protocol level).

One handler instance serves one span of blocks. Sessions (multi-step inference
with server-held KV) are plain dicts in this process — the reference's
cross-process session registry (handler.py:197-245) is unnecessary in a
single-process JAX server.

Wire payloads (msgpack):
- inference open:  {uids, max_length, batch_size, active_adapter?, session_id?}
- inference step:  {tensors: {hidden, prompts?, hypo_ids?}, start_from_position?, step_id?}
- inference reply: {tensors: {hidden}, position}
- kv import step:  {kv_import: {position}, tensors: {k, v}} (first step only)
- kv adopt step:   {kv_adopt: {session_id, position}} (first step only; seeds
                   from KV this server already holds — migrated in or parked)
- session export:  {session_id, start, end, compression?} -> {position, tensors: {k, v}, ...}
                   (or {migrated_to: {peer_id, addr, position}} redirect)
- session migrate: {session_id, start, end, position, batch_size, max_length,
                   trace_id?, tensors: {k, v}} -> {ok, position} (server->server)
- forward:         {uids, tensors: {hidden, prompts?}, active_adapter?}
- backward:        {uids, tensors: {hidden, grad_out, prompts?}, active_adapter?}
- info:            {} -> ServerInfo dict + cache stats
"""

from __future__ import annotations

import asyncio
import contextlib
import time
from typing import Dict, Optional, Tuple

import numpy as np

from petals_tpu import chaos
from petals_tpu.data_structures import CHAIN_DELIMITER, ModuleUID, parse_uid
from petals_tpu.rpc.protocol import validate_gen_sampling
from petals_tpu.rpc.serialization import deserialize_array, serialize_array, CompressionType
from petals_tpu.rpc.server import RpcContext, RpcServer, StreamRequests
from petals_tpu.server.backend import TransformerBackend
from petals_tpu.server.memory_cache import MemoryCache
from petals_tpu.server.task_queue import (
    PRIORITY_INFERENCE,
    PRIORITY_TRAINING,
    PriorityTaskQueue,
)
from petals_tpu.telemetry import (
    new_trace_id,
    normalize_trace_id,
    reset_trace_id,
    set_trace_id,
)
from petals_tpu.telemetry import instruments as tm
from petals_tpu.telemetry.exposition import telemetry_digest
from petals_tpu.telemetry.observatory import compile_stats_digest
from petals_tpu.utils.asyncio_utils import log_exception_callback
from petals_tpu.utils.logging import get_logger
from petals_tpu.utils.misc import is_dummy
from petals_tpu.utils.tracing import device_annotation, get_tracer

logger = get_logger(__name__)


class _TakenStep:
    """A plain decode step that the connection's reader handed to the batcher
    in the turn that read its frame, while its session's loop was parked
    (``rpc/server.py StreamRequests.sink``): what the loop holds at its own
    ``batcher.step`` for a step that came through the queue, and, once the
    batcher has replied, the step's ``out``."""

    __slots__ = ("step", "read_at", "held_at", "hidden", "t_tok", "span", "batcher", "lane", "out")

    def __init__(self, step, read_at, held_at, hidden, t_tok, span, batcher, lane):
        self.step, self.read_at, self.held_at, self.hidden, self.t_tok = step, read_at, held_at, hidden, t_tok
        self.span, self.batcher, self.lane = span, batcher, lane
        self.out = None

    def close(self) -> None:
        """The step's future is done, or given up: what leaving ``with span: await batcher.step(...)`` does."""
        self.span.__exit__(None, None, None)
        self.batcher.end_step(self.lane)


_PLAIN_STEP_KEYS, _PLAIN_STEP_TENSORS = frozenset(("tensors", "step_id")), frozenset(("hidden",))


def _is_plain_decode_step(item, batch_size: int) -> bool:
    """One new token's hidden state a sequence and, at most, a step id: no
    prompts, no hypo_ids, no rollback, no push_to, no kv to install, nothing
    to generate. Read off the wire form, before anything is unpacked."""
    if not isinstance(item, dict) or not item.keys() <= _PLAIN_STEP_KEYS:
        return False
    tensors = item.get("tensors")
    if not isinstance(tensors, dict) or tensors.keys() != _PLAIN_STEP_TENSORS or not isinstance(tensors["hidden"], dict):
        return False
    shape = tensors["hidden"].get("shape")
    return isinstance(shape, list) and len(shape) == 3 and shape[:2] == [batch_size, 1]


def _expire(fut: asyncio.Future, *why: str) -> None:
    if not fut.done():
        fut.set_exception(asyncio.TimeoutError(*why))


class _StepSource:
    """Where a session's loop gets its next step: the client's stream or the
    push queue, whichever has one first. Pending getters persist across calls
    (no per-step task churn, no cancelled-task noise at teardown). Pulls
    straight from the request iterator — no intermediate buffer, so the
    transport's bounded inbound queue is the *only* buffer and its
    backpressure actually engages for flooding peers.

    The loop waits on ONE future, ``parked``. A getter that finishes resolves
    it, the session's timeout fails it, and a sink that took a decode step in
    the reader's turn gives it to the batcher as that step's own future
    (``take``): the batcher's reply then wakes the loop exactly as it wakes a
    caller of ``batcher.step``, and ``next()`` hands back the ``_TakenStep``."""

    def __init__(self, requests, push_queue, timeout: float):
        self._requests, self._push_queue, self._timeout = requests, push_queue, timeout
        self._pending: Dict[str, asyncio.Task] = {}
        self.parked: Optional[asyncio.Future] = None  # the loop waits, and no step of its is with the batcher
        self._taken: Optional[_TakenStep] = None
        self._timer: Optional[asyncio.TimerHandle] = None

    async def _next_client(self):
        try:
            item = await anext(self._requests)
        except StopAsyncIteration:
            return None, None  # client half-closed
        except Exception as e:
            logger.debug("Client stream error (treating as half-close): %r", e)
            return None, None
        # when the RPC server read this item's frame (its per-stream
        # object; any other iterator keeps no such time)
        return item, getattr(self._requests, "read_at", None)

    def _wake(self, _getter) -> None:
        if self.parked is not None and not self.parked.done():
            self.parked.set_result(None)

    def _get(self, name: str, coro) -> None:
        if name not in self._pending:
            task = self._pending[name] = asyncio.create_task(coro())
            task.add_done_callback(self._wake)

    def take(self, taken: _TakenStep, timeout: float) -> None:
        """``parked`` went to the batcher with ``taken``: it is that step's future now, under a step's timeout."""
        fut, self.parked, self._taken = self.parked, None, taken
        self._timer.cancel()
        self._timer = fut.get_loop().call_later(timeout, _expire, fut)

    async def next(self):
        """(step, when the RPC server read its frame), or (a ``_TakenStep`` with its ``out``, the same)."""
        self._get("client", self._next_client)
        if self._push_queue is not None:
            self._get("push", self._push_queue.get)
        # (while, not if: a getter's ``_wake`` runs a turn after the getter ended, and if the loop took that getter's
        # item meanwhile and came round again without a suspension, that wake resolves THIS future with nothing done)
        while not any(task.done() for task in self._pending.values()):
            loop = asyncio.get_running_loop()
            fut = self.parked = loop.create_future()
            self._timer = loop.call_later(self._timeout, _expire, fut, "No inference step within session_timeout")
            try:
                out = await fut
            finally:
                self.parked = None
                self._timer.cancel()
                taken, self._taken = self._taken, None
                if taken is not None:
                    taken.close()
            if taken is not None:
                taken.out = out
                return taken, taken.read_at
        name = next(name for name, task in self._pending.items() if task.done())
        result = self._pending.pop(name).result()
        # a pushed step came over no stream of this call's, so nobody here read its frame
        return result if name == "client" else (result, None)

    async def cleanup(self) -> None:
        for task in self._pending.values():
            task.cancel()
            with contextlib.suppress(asyncio.CancelledError, Exception):
                await task
        self._pending.clear()


class TransformerHandler:
    def __init__(
        self,
        backend: TransformerBackend,
        *,
        dht_prefix: str,
        memory_cache: MemoryCache,
        server_info_fn=None,
        request_timeout: float = 3 * 60,
        session_timeout: float = 30 * 60,
        step_timeout: float = 5 * 60,
        compression: CompressionType = CompressionType.NONE,
        identity=None,  # authenticates the server->server push plane
        inference_max_length: Optional[int] = None,  # cap on per-session max_length
        batching: bool = True,  # continuous batching across decode sessions
        batch_lanes: int = 8,
        batch_max_length: Optional[int] = None,  # pool lane length (tokens)
        page_size: Optional[int] = None,  # paged KV: tokens per page; None/0 = dense pool
        n_pages: Optional[int] = None,  # paged KV pool size; None = lanes * max_pages
        prefill_token_budget: int = 512,  # prefill tokens per mixed batched step
        swap_host_bytes: int = 0,  # host-RAM KV swap tier for preemption; 0 disables
        preemption_policy: str = "lru",  # victim choice: lru | largest | off
        prefix_cache_bytes: int = 256 * 2**20,  # 0 disables prefix caching
        prefix_share_scope: str = "swarm",  # "swarm" shares across clients; "peer" salts per client
        prefix_device_bytes: int = 256 * 2**20,  # HBM tier of the prefix cache; 0 disables
        prefix_cache_policy: str = "radix",  # "radix" tree + tiers | "lru" flat baseline
        server_gen_params=None,  # client leaves (embed/norm/head) for device-side generation
        draft_model=None,  # server.spec_decode.DraftModel: speculative decoding
        spec_k: Optional[int] = None,  # drafts per lane per tick; None -> draft's k
    ):
        self.backend = backend
        self.dht_prefix = dht_prefix
        self.memory_cache = memory_cache
        self.server_info_fn = server_info_fn
        self.request_timeout = request_timeout
        self.session_timeout = session_timeout
        self.step_timeout = step_timeout
        self.compression = compression
        self.inference_max_length = inference_max_length
        self.queue = PriorityTaskQueue()
        self.queue.start()
        self._sub_backends: Dict[Tuple[int, int], TransformerBackend] = {}
        # own peer id string, for integrity chaos targeting (a single-process
        # test swarm shares ONE chaos plane: rules single out a replica by
        # matching the detail string, which therefore must carry the peer)
        self._peer_str = ""
        try:
            if identity is not None:
                self._peer_str = identity.peer_id.to_string()
        except Exception as e:
            logger.debug(f"Peer id unavailable for chaos targeting: {e}")
        import zlib

        self._corrupt_seed = zlib.crc32(self._peer_str.encode("utf-8"))
        # server-to-server activation push (reference handler.py:310-350):
        # session_id -> queue of pushed step payloads
        self._push_queues: Dict[str, asyncio.Queue] = {}
        # KV migration (beyond reference): live-session registry for
        # ptu.session_export, and host-RAM parking of session KV so a
        # draining server can hand caches to replacements instead of making
        # clients recompute the prefill (client/inference_session.py repair).
        self._session_registry: Dict[str, dict] = {}
        self._parked: Dict[str, dict] = {}
        self.park_ttl = 60.0
        self.draining = False
        # Peer-to-peer migration (ptu.session_migrate): KV pushed here by a
        # draining/rebalancing peer, held until the client re-opens and adopts
        # it (kv_adopt step) or the TTL lapses. Byte-budgeted: a swarm of
        # draining peers must not be able to OOM this host.
        self._migrated: Dict[str, dict] = {}
        # sessions we pushed away: session_id -> forwarding address, served
        # as a redirect from rpc_session_export so the client finds the KV
        self._migrated_away: Dict[str, dict] = {}
        self._migrated_bytes = 0
        self.migrate_in_budget_bytes = 512 * 2**20
        self.migrate_ttl = 120.0
        from petals_tpu.rpc.pool import ConnectionPool

        self._push_pool = ConnectionPool(identity=identity)
        self._push_tasks: set = set()
        # set by abort_migrations() (Server.shutdown): in-flight migration
        # pushes stop waiting on their peer and abort immediately, so a
        # slow/chaos-delayed destination can never hang teardown
        self._migrate_abort = asyncio.Event()

        # Continuous batching (server/batching.py): concurrent single-stream
        # decode sessions on the full span coalesce into one device step.
        # Composes with TP meshes (the batched program shards like the
        # single-session one) and with multi-host lockstep (pool + lane ops
        # broadcast — parallel/multihost.py v3).
        self.batcher = None
        if batching:
            from petals_tpu.server.batching import DecodeBatcher

            self.batcher = DecodeBatcher(
                backend,
                memory_cache,
                self.queue,
                n_lanes=batch_lanes,
                max_length=batch_max_length or inference_max_length or 1024,
                gen_params=server_gen_params,
                page_size=page_size,
                n_pages=n_pages,
                prefill_token_budget=prefill_token_budget,
                swap_host_bytes=swap_host_bytes,
                preemption_policy=preemption_policy,
                draft_model=draft_model,
                spec_k=spec_k,
            )

        # Content-addressed prefix cache (server/prefix_cache.py): sessions
        # sharing a prompt prefix skip its prefill compute. Under lockstep
        # the staging rides the v2 broadcast ops (import_kv / export_kv).
        self.prefix_cache = None
        if prefix_share_scope not in ("swarm", "peer"):
            raise ValueError(f"prefix_share_scope must be 'swarm' or 'peer', got {prefix_share_scope!r}")
        # "peer" folds the requester's peer id into the hash salt: no
        # cross-client sharing, which closes the cache-hit timing side
        # channel an open swarm otherwise accepts (server/prefix_cache.py
        # module docstring spells out the tradeoff)
        self.prefix_share_scope = prefix_share_scope
        self.server_gen_params = server_gen_params
        self.draft_model = draft_model
        self.spec_k = spec_k
        paged = self.batcher is not None and self.batcher.page_size is not None
        refusal = backend.cache.prefix_cache_refusal(paged=paged) if prefix_cache_bytes > 0 else None
        if refusal is not None:
            # off for a span that holds more than keys and values, by what its family declares (server/span_cache.py)
            logger.info(refusal)
            prefix_cache_bytes = 0
        if prefix_cache_bytes > 0:
            from petals_tpu.server.prefix_cache import PrefixCache
            from petals_tpu.telemetry.ledger import get_ledger

            ledger = get_ledger()
            self.prefix_cache = PrefixCache(
                prefix_cache_bytes, device_max_bytes=prefix_device_bytes,
                policy=prefix_cache_policy,
                # the radix swap tier rides the batcher's HostSwapPool (one
                # budget with session preemption); a private-session-only
                # server has no pool, so demotion degrades to eviction
                swap_pool=(
                    self.batcher.swap_pool if self.batcher is not None else None
                ),
                # eviction consults the DRF rank: the dominant tenant's cold
                # nodes go first, and residency bills to the owning tenant
                usage_fn=ledger.peer_dominant_share,
                ledger=ledger,
            )
        if (
            self.prefix_cache is not None
            and self.batcher is not None
            and self.batcher.page_size is not None
        ):
            from petals_tpu.server.prefix_cache import SEGMENT_TOKENS

            # page-granular prefix sharing slices pinned page runs at segment
            # boundaries, so segments must tile exactly into pages
            if SEGMENT_TOKENS % self.batcher.page_size != 0:
                raise ValueError(
                    f"page_size={self.batcher.page_size} must divide the prefix-cache "
                    f"segment size ({SEGMENT_TOKENS} tokens)"
                )

    async def swap_backend(self, new_backend) -> None:
        """Retarget the handler at a freshly built backend (span reload /
        rebalance). Private sessions opened on the old span keep computing
        against the old backend object (captured at session open) until they
        close; POOLED sessions cannot — the lane pool is shared — so the old
        batcher is closed (its tenants' next step fails loudly and clients
        failover, the same recovery path as a pool reset) and a fresh pool
        opens lazily for the new span. Without this swap the old batcher
        kept serving the NEW span's pooled decode steps with the OLD span's
        weights — silently wrong outputs after every rebalance."""
        self.backend = new_backend
        self._sub_backends = {}
        if self.batcher is not None:
            from petals_tpu.server.batching import DecodeBatcher

            old = self.batcher
            self.batcher = DecodeBatcher(
                new_backend,
                self.memory_cache,
                self.queue,
                n_lanes=old.n_lanes,
                max_length=old.max_length,
                gen_params=self.server_gen_params,
                page_size=old.page_size,
                n_pages=old.n_pages or None,
                prefill_token_budget=old.prefill_token_budget,
                swap_host_bytes=old.swap_pool.max_size_bytes,
                preemption_policy=old._scheduler.policy,
                draft_model=self.draft_model,
                spec_k=self.spec_k,
            )
            await old.close()

    def register(self, server: RpcServer) -> None:
        server.add_unary_handler("ptu.forward", self.rpc_forward)
        server.add_unary_handler("ptu.backward", self.rpc_backward)
        server.add_unary_handler("ptu.info", self.rpc_info)
        server.add_unary_handler("ptu.push", self.rpc_push)
        server.add_unary_handler("ptu.session_export", self.rpc_session_export)
        server.add_unary_handler("ptu.session_migrate", self.rpc_session_migrate)
        server.add_unary_handler("ptu.session_handoff", self.rpc_session_handoff)
        server.add_unary_handler("ptu.probe", self.rpc_probe)
        server.add_stream_handler("ptu.inference", self.rpc_inference)

    async def rpc_push(self, payload, ctx: RpcContext):
        """Accept hidden states pushed by the previous server in a chain
        (reference handler.py:310-318)."""
        session_id = payload.get("session_id")
        queue = self._push_queues.get(session_id)
        if queue is None:
            raise KeyError(f"No active inference session {session_id!r} on this server")
        try:
            queue.put_nowait(payload)
        except asyncio.QueueFull:
            # Push is best-effort (the client relay is authoritative); refusing
            # beats buffering an unbounded backlog from a runaway upstream peer.
            raise RuntimeError(f"Push queue full for session {session_id!r}")
        return {"ok": True}

    async def rpc_session_export(self, payload, ctx: RpcContext):
        """Hand a session's KV cache (sliced to its position) to the caller so a
        replacement server can be seeded without recomputing the prefill.
        Serves live sessions and sessions parked by a draining server."""
        session_id = payload.get("session_id")
        want_start = int(payload["start"])
        want_end = int(payload["end"])
        comp = CompressionType(payload.get("compression", "none"))
        self._prune_parked()

        # migrated-away first, even while the drained stream is still open:
        # the copy at the destination is the authoritative one now, and an
        # adopt there (plus a replayed tail if a step raced the park) moves
        # zero KV bytes over the client's link
        fwd = self._migrated_away.get(session_id)
        if fwd is not None:
            return {"migrated_to": dict(fwd)}

        # live first: a parked snapshot goes stale if steps kept flowing
        # between drain and shutdown
        live = self._session_registry.get(session_id)
        if live is not None:
            if not (live["start"] <= want_start < want_end <= live["end"]):
                raise ValueError(
                    f"Requested blocks [{want_start}, {want_end}) outside session span "
                    f"[{live['start']}, {live['end']})"
                )
            # slice the requested block range ON DEVICE: a route upgrade may
            # ask for a narrow range of a long-context span, and the full-span
            # host copy would be 100s of wasted MB per request
            src = await self._snapshot_session(
                live, want_start - live["start"], want_end - live["start"]
            )
            b0, b1 = 0, want_end - want_start
        else:
            self._prune_migrated()
            # parked (we are draining) or migrated-in (a peer drained onto us
            # but the client's new chain doesn't end here): both are host
            # snapshots with the same layout
            src = self._parked.get(session_id) or self._migrated.get(session_id)
            if src is None:
                raise KeyError(f"No live or parked session {session_id!r}")
            if not (src["start"] <= want_start < want_end <= src["end"]):
                raise ValueError(
                    f"Requested blocks [{want_start}, {want_end}) outside session span "
                    f"[{src['start']}, {src['end']})"
                )
            b0, b1 = want_start - src["start"], want_end - src["start"]
        position = src["position"]
        if position <= 0:
            raise ValueError(f"Session {session_id!r} has no cached tokens yet")
        # migrated-in entries may hold PACKED codes + scales (quantized wire);
        # the client-facing export protocol stays dense, so decode the slice
        kv_quant = src.get("kv_quant") or "none"

        def _dense(name: str):
            arr = src[name][b0:b1]
            if kv_quant != "none":
                from petals_tpu.ops.paged_attention import dequantize_kv_np

                arr = dequantize_kv_np(arr, src[name + "_scales"][b0:b1], kv_quant)
            return serialize_array(arr, comp)

        return {
            "position": position,
            "start": want_start,
            "end": want_end,
            "batch_size": src["batch_size"],
            "tensors": {"k": _dense("k"), "v": _dense("v")},
        }

    async def rpc_session_migrate(self, payload, ctx: RpcContext):
        """Accept a session's KV pushed by a draining/rebalancing peer
        (server->server, no client in the loop). The entry is held in host
        RAM under a byte budget until the client re-opens here and adopts it
        with a ``kv_adopt`` step, exports it onward, or the TTL lapses."""
        from petals_tpu.telemetry import get_journal

        session_id = payload["session_id"]
        src_start = int(payload["start"])
        src_end = int(payload["end"])
        position = int(payload["position"])
        batch_size = int(payload["batch_size"])
        max_length = int(payload["max_length"])
        trace_id = normalize_trace_id(payload.get("trace_id"))
        if self.draining:
            raise RuntimeError("Server is draining: not accepting migrated sessions")
        first = self.backend.first_block
        if not (first <= src_start < src_end <= first + self.backend.n_blocks):
            raise ValueError(
                f"Migrated span [{src_start}, {src_end}) outside this server's "
                f"blocks [{first}, {first + self.backend.n_blocks})"
            )
        if position <= 0:
            raise ValueError("Refusing to migrate a session with no cached tokens")
        tensors = payload.get("tensors") or {}
        if "k" not in tensors or "v" not in tensors:
            raise ValueError("session_migrate needs k and v tensors")
        from petals_tpu.ops.paged_attention import KV_QUANT_KINDS

        kv_quant = str(payload.get("kv_quant") or "none")
        if kv_quant not in KV_QUANT_KINDS:
            raise ValueError(f"Unknown kv_quant {kv_quant!r} in session_migrate")

        def parse(wire):
            arr = deserialize_array(wire)
            want = (src_end - src_start, batch_size, position)
            if tuple(arr.shape[:3]) != want:
                raise ValueError(
                    f"migrated KV shape {arr.shape} != (blocks, batch, position) {want}"
                )
            return arr

        k_arr = await asyncio.to_thread(parse, tensors["k"])
        v_arr = await asyncio.to_thread(parse, tensors["v"])
        k_scales = v_scales = None
        if kv_quant != "none":
            # packed wire entry: codes ride in k/v, per-row scales alongside.
            # Stored as-is (wire bytes against the budget); kv_adopt / export
            # dequantize on the way out.
            if "k_scales" not in tensors or "v_scales" not in tensors:
                raise ValueError(
                    "quantized session_migrate needs k_scales and v_scales tensors"
                )
            k_scales = await asyncio.to_thread(parse, tensors["k_scales"])
            v_scales = await asyncio.to_thread(parse, tensors["v_scales"])
        nbytes = k_arr.nbytes + v_arr.nbytes + (
            k_scales.nbytes + v_scales.nbytes if k_scales is not None else 0
        )
        self._prune_migrated()
        if self._migrated_bytes + nbytes > self.migrate_in_budget_bytes:
            tm.MIGRATIONS.labels(direction="in", outcome="refused").inc()
            get_journal().event(
                "migrate_refused", trace_id=trace_id, session_id=session_id,
                nbytes=nbytes, in_use=self._migrated_bytes,
                budget=self.migrate_in_budget_bytes,
            )
            raise RuntimeError(
                f"Migration budget exhausted ({self._migrated_bytes + nbytes} "
                f"> {self.migrate_in_budget_bytes} bytes)"
            )
        old = self._migrated.pop(session_id, None)
        if old is not None:  # re-push after a failed adopt: replace, re-account
            self._migrated_bytes -= old["nbytes"]
        self._migrated[session_id] = {
            "k": k_arr, "v": v_arr, "position": position,
            "k_scales": k_scales, "v_scales": v_scales, "kv_quant": kv_quant,
            "start": src_start, "end": src_end,
            "batch_size": batch_size, "max_length": max_length,
            "trace_id": trace_id, "nbytes": nbytes,
            "expires": time.monotonic() + self.migrate_ttl,
        }
        self._migrated_bytes += nbytes
        tm.MIGRATIONS.labels(direction="in", outcome="ok").inc()
        tm.MIGRATION_BYTES.labels(direction="in").inc(nbytes)
        get_journal().event(
            "migrate_in", trace_id=trace_id,
            occupancy=self.batcher.occupancy_info() if self.batcher is not None else None,
            session_id=session_id, position=position, nbytes=nbytes,
            start=src_start, end=src_end,
        )
        return {"ok": True, "position": position}

    async def rpc_session_handoff(self, payload, ctx: RpcContext):
        """Disaggregated prefill->decode boundary: the client (between steps,
        so the cut lands exactly on a step boundary) asks this prefill-tier
        server to push one LIVE session's finished KV to a decode-tier
        replica over the page-push path, then adopts it there with
        ``kv_adopt`` — zero KV bytes ever cross the client link. Unlike
        drain-to-migrate the session stays live here: no redirect is
        installed and nothing is torn down, so a failed push (or a failed
        adopt at the destination) degrades to colocated decode on this
        replica with no session loss."""
        session_id = payload["session_id"]
        peer_id = str(payload["peer_id"])
        addr = str(payload["addr"])
        deadline_s = min(max(float(payload.get("deadline_s") or 30.0), 0.1), 120.0)
        reg = self._session_registry.get(session_id)
        if reg is None:
            raise KeyError(f"No live session {session_id!r} to hand off")
        if reg["position"] <= 0:
            raise ValueError(f"Session {session_id!r} has no cached tokens yet")
        snap = await self._snapshot_session(reg)
        snap["trace_id"] = reg.get("trace_id")
        snap["peer"] = reg.get("peer")  # ledger attribution of the push bytes
        ok = await self.migrate_parked_to(
            session_id, snap, peer_id, addr, deadline_s=deadline_s, kind="handoff",
        )
        return {"ok": bool(ok), "position": int(snap["position"])}

    async def migrate_parked_to(
        self, session_id: str, snap: dict, peer_id: str, addr: str,
        *, deadline_s: float = 30.0, budget_bytes: Optional[int] = None,
        kind: str = "migrate",
    ) -> bool:
        """Push one session snapshot's KV to a live replica over the
        server-to-server page-push path. Two callers share the transport:

        - ``kind="migrate"`` (drain-to-migrate / rebalance): on success the
          local parked copy becomes a redirect (``_migrated_away``) so
          exports forward the client to the new home.
        - ``kind="handoff"`` (disaggregated prefill->decode boundary): the
          source session stays LIVE and no redirect is installed — the
          client adopts at the destination, and if that fails it simply
          keeps decoding here (colocated fallback, no session loss).

        Returns False — with flight-recorder evidence — when the push fails;
        the parked/live entry stays, and the client falls back to
        export/replay (migrate) or colocated decode (handoff)."""
        from petals_tpu.dht.routing import PeerAddr
        from petals_tpu.telemetry import get_journal

        assert kind in ("migrate", "handoff"), kind
        handoff = kind == "handoff"

        def note_outcome(outcome: str, nbytes: int = 0) -> None:
            if handoff:
                tm.HANDOFFS.labels(outcome=outcome).inc()
                if outcome == "ok":
                    tm.HANDOFF_BYTES.inc(nbytes)
            else:
                tm.MIGRATIONS.labels(direction="out", outcome=outcome).inc()
                if outcome == "ok":
                    tm.MIGRATION_BYTES.labels(direction="out").inc(nbytes)

        trace_id = snap.get("trace_id")
        kv_quant = getattr(self.backend, "kv_quant_type", "none")
        if kv_quant != "none":
            # pack the dense snapshot to per-row codes + scales before it hits
            # the wire: the push moves ~4x fewer bytes and the receiver banks
            # the packed entry verbatim against its migration budget
            from petals_tpu.ops.paged_attention import quantize_kv_rows_np

            def _pack():
                kc, ks = quantize_kv_rows_np(np.asarray(snap["k"], np.float32), kv_quant)
                vc, vs = quantize_kv_rows_np(np.asarray(snap["v"], np.float32), kv_quant)
                return kc, ks, vc, vs

            k_codes, k_scales, v_codes, v_scales = await asyncio.to_thread(_pack)
            nbytes = int(
                k_codes.nbytes + k_scales.nbytes + v_codes.nbytes + v_scales.nbytes
            )
        else:
            k_codes = k_scales = v_codes = v_scales = None
            nbytes = int(snap["k"].nbytes + snap["v"].nbytes)
        t0 = time.perf_counter()

        async def _push() -> None:
            if budget_bytes is not None and nbytes > budget_bytes:
                raise RuntimeError(
                    f"session KV ({nbytes}B) exceeds the migration budget ({budget_bytes}B)"
                )
            if chaos.ENABLED:
                await chaos.inject(
                    chaos.SITE_HANDOFF_PUSH if handoff else chaos.SITE_MIGRATE_PUSH,
                    detail=session_id,
                )
            if kv_quant != "none":
                # codes are integer (lossy float codecs pass them through
                # verbatim); scales go uncompressed so the packed entry
                # round-trips the wire byte-exactly
                tensors = await asyncio.to_thread(
                    lambda: {
                        "k": serialize_array(k_codes, self.compression),
                        "v": serialize_array(v_codes, self.compression),
                        "k_scales": serialize_array(k_scales, CompressionType.NONE),
                        "v_scales": serialize_array(v_scales, CompressionType.NONE),
                    }
                )
            else:
                tensors = await asyncio.to_thread(
                    lambda: {
                        "k": serialize_array(snap["k"], self.compression),
                        "v": serialize_array(snap["v"], self.compression),
                    }
                )
            payload = {
                "session_id": session_id,
                "start": snap["start"], "end": snap["end"],
                "position": snap["position"], "batch_size": snap["batch_size"],
                "max_length": snap["max_length"], "trace_id": trace_id,
                "kv_quant": kv_quant, "tensors": tensors,
            }
            client = await self._push_pool.get_addr(PeerAddr.from_string(addr))
            await client.call("ptu.session_migrate", payload)

        # Race the push against shutdown's abort signal, with the deadline
        # covering the WHOLE push (chaos delays and serialization included —
        # previously only the RPC call was deadlined, so a chaos-delayed
        # serialize phase could hang drain past the deadline).
        push_task = asyncio.create_task(_push())
        abort_task = asyncio.create_task(self._migrate_abort.wait())
        try:
            await asyncio.wait(
                {push_task, abort_task},
                timeout=deadline_s,
                return_when=asyncio.FIRST_COMPLETED,
            )
        finally:
            abort_task.cancel()
            with contextlib.suppress(asyncio.CancelledError):
                await abort_task
        if not push_task.done():
            reason = "shutdown" if self._migrate_abort.is_set() else "deadline"
            push_task.cancel()
            with contextlib.suppress(asyncio.CancelledError):
                await push_task
            note_outcome("aborted")
            get_journal().event(
                "handoff_aborted" if handoff else "migrate_aborted",
                trace_id=trace_id, session_id=session_id,
                dest=peer_id, nbytes=nbytes, reason=reason,
                elapsed_s=time.perf_counter() - t0,
            )
            logger.warning(
                f"{kind.capitalize()} of {session_id!r} to {peer_id} aborted ({reason})"
            )
            return False
        try:
            push_task.result()
        except Exception as e:
            note_outcome("failed")
            get_journal().event(
                "handoff_failed" if handoff else "migrate_failed",
                trace_id=trace_id, session_id=session_id,
                dest=peer_id, nbytes=nbytes, error=repr(e),
            )
            from petals_tpu.telemetry.flight import flight_from_env

            flight_from_env().record(
                "handoff_failed" if handoff else "migrate_failed",
                trace_id=trace_id,
                journal=lambda: get_journal().events(trace_id=trace_id)[-50:],
                session_id=session_id, dest_peer=peer_id, dest_addr=addr,
                nbytes=nbytes, error=repr(e),
                elapsed_s=time.perf_counter() - t0,
            )
            logger.warning(f"{kind.capitalize()} of {session_id!r} to {peer_id} failed: {e}")
            return False
        if not handoff:
            # a handoff source stays live (the client may fall back to
            # colocated decode here); only a drained migration redirects
            self._migrated_away[session_id] = {
                "peer_id": peer_id, "addr": addr, "position": snap["position"],
            }
            self._parked.pop(session_id, None)
        note_outcome("ok", nbytes)
        # the parked session's lane — and ledger session — already closed
        # (and a handoff source's live session keeps its own bill), so the
        # push bills straight to the owning peer's rollup as migration bytes
        from petals_tpu.telemetry.ledger import get_ledger

        get_ledger().note_migrated(None, nbytes, peer_id=snap.get("peer"))
        get_journal().event(
            "handoff_out" if handoff else "migrate_out",
            trace_id=trace_id,
            occupancy=self.batcher.occupancy_info() if self.batcher is not None else None,
            session_id=session_id, dest=peer_id, nbytes=nbytes,
            position=snap["position"], elapsed_s=time.perf_counter() - t0,
        )
        return True

    def _prune_migrated(self) -> None:
        now = time.monotonic()
        for sid in [s for s, m in self._migrated.items() if m["expires"] < now]:
            self._migrated_bytes -= self._migrated[sid]["nbytes"]
            del self._migrated[sid]

    def _consume_migrated(self, session_id: str) -> None:
        entry = self._migrated.pop(session_id, None)
        if entry is not None:
            self._migrated_bytes -= entry["nbytes"]

    async def _install_kv_import(
        self, step, kv, handles, position, *, batch_size: int, n_blocks: int, max_length: int
    ) -> int:
        """Seed this session's KV buffers from another server's exported cache
        (must arrive before any compute so the caches never mix histories).
        Under multi-host lockstep the prefix is broadcast once and every
        process materializes its own shards (multihost.py import_kv)."""
        if position != 0:
            raise ValueError("kv_import must be the first step of a session")
        new_position = int(step["kv_import"]["position"])
        if not 0 < new_position <= max_length:
            raise ValueError(f"kv_import position {new_position} outside (0, {max_length}]")
        tensors = step.get("tensors") or {}
        if "k" not in tensors or "v" not in tensors:
            raise ValueError("kv_import needs k and v tensors")
        k_buf, v_buf = kv
        want_shape = (n_blocks, batch_size, new_position, *k_buf.shape[3:])

        def parse(name, wire):
            arr = deserialize_array(wire)
            if tuple(arr.shape) != want_shape:
                raise ValueError(f"kv_import {name} shape {arr.shape} != {want_shape}")
            return arr

        arr_k = await asyncio.to_thread(parse, "k", tensors["k"])
        arr_v = await asyncio.to_thread(parse, "v", tensors["v"])
        if getattr(self.backend, "is_lockstep", False):
            new_k, new_v = await asyncio.to_thread(
                self.backend.import_kv, handles, arr_k, arr_v,
                new_position, batch_size, max_length, n_blocks,
            )
            self.memory_cache.update_cache(handles[0], new_k)
            self.memory_cache.update_cache(handles[1], new_v)
        else:
            # staging shared with the prefix-cache hit path
            await self._seed_session_kv(
                None, kv, handles, arr_k, arr_v, new_position,
                batch_size=batch_size, n_blocks=n_blocks,
            )
        return new_position

    @contextlib.asynccontextmanager
    async def _lane_ctx(self, lane: int, batcher):
        """Session-lifetime scope of a borrowed pool lane (yields None in the
        position of the private path's cache handles). ``batcher`` is the
        pool the lane was acquired from, captured at session open — after a
        live span move self.batcher is a NEW pool whose lane indices alias
        other tenants, so releasing (or stepping) through it would corrupt
        them."""
        try:
            yield None
        finally:
            batcher.release_lane(lane)

    async def _install_kv_import_pooled(
        self, step, lane: int, position, *, batch_size: int, n_blocks: int, max_length: int,
        batcher,
    ) -> int:
        """Seed a pooled session's lane from another server's exported cache
        (validation here; the staging is shared with the prefix-cache hit
        path in _seed_session_kv)."""
        backend = batcher.backend
        if position != 0:
            raise ValueError("kv_import must be the first step of a session")
        new_position = int(step["kv_import"]["position"])
        if not 0 < new_position <= max_length:
            raise ValueError(f"kv_import position {new_position} outside (0, {max_length}]")
        tensors = step.get("tensors") or {}
        if "k" not in tensors or "v" not in tensors:
            raise ValueError("kv_import needs k and v tensors")
        want_shape = (
            n_blocks, batch_size, new_position, backend.num_kv_heads, backend.head_dim,
        )

        def parse(name, wire):
            arr = deserialize_array(wire)
            if tuple(arr.shape) != want_shape:
                raise ValueError(f"kv_import {name} shape {arr.shape} != {want_shape}")
            return arr

        arr_k = await asyncio.to_thread(parse, "k", tensors["k"])
        arr_v = await asyncio.to_thread(parse, "v", tensors["v"])
        await self._seed_session_kv(
            lane, None, None, arr_k, arr_v, new_position,
            batch_size=batch_size, n_blocks=n_blocks, batcher=batcher,
        )
        return new_position

    async def _install_kv_adopt(
        self, step, lane, kv, handles, position, *,
        abs_start: int, batch_size: int, n_blocks: int, max_length: int, batcher,
    ) -> int:
        """Seed a fresh session's cache from KV already ON THIS SERVER — a
        migrated-in entry (peer drain/rebalance pushed it here) or our own
        parked snapshot. The client sends only ``{session_id, position}``:
        the bytes never cross the client link, which is the whole point of
        peer-to-peer migration vs export/import."""
        if position != 0:
            raise ValueError("kv_adopt must be the first step of a session")
        spec = step["kv_adopt"]
        src_sid = spec["session_id"]
        cut = int(spec["position"])
        self._prune_migrated()
        self._prune_parked()
        entry = self._migrated.get(src_sid) or self._parked.get(src_sid)
        if entry is None:
            raise KeyError(f"No migrated or parked KV for session {src_sid!r}")
        if not 0 < cut <= entry["position"]:
            raise ValueError(
                f"kv_adopt position {cut} outside (0, {entry['position']}]"
            )
        if cut > max_length:
            raise ValueError(f"kv_adopt position {cut} exceeds max_length {max_length}")
        if batch_size != entry["batch_size"]:
            raise ValueError(
                f"kv_adopt batch_size {batch_size} != source {entry['batch_size']}"
            )
        if not (entry["start"] <= abs_start and abs_start + n_blocks <= entry["end"]):
            raise ValueError(
                f"Session blocks [{abs_start}, {abs_start + n_blocks}) outside "
                f"migrated span [{entry['start']}, {entry['end']})"
            )
        b0 = abs_start - entry["start"]
        kv_quant = entry.get("kv_quant") or "none"
        if kv_quant != "none":
            # packed wire entry (row-granular codes + scales, position-
            # sliceable): dequantize the adopted cut to the dense prefix the
            # seed path expects — the pool write requantizes on insert
            from petals_tpu.ops.paged_attention import dequantize_kv_np

            k_codes = np.ascontiguousarray(entry["k"][b0:b0 + n_blocks, :, :cut])
            v_codes = np.ascontiguousarray(entry["v"][b0:b0 + n_blocks, :, :cut])
            k_sc = np.ascontiguousarray(entry["k_scales"][b0:b0 + n_blocks, :, :cut])
            v_sc = np.ascontiguousarray(entry["v_scales"][b0:b0 + n_blocks, :, :cut])
            wire_nbytes = int(
                k_codes.nbytes + v_codes.nbytes + k_sc.nbytes + v_sc.nbytes
            )
            k_arr = await asyncio.to_thread(dequantize_kv_np, k_codes, k_sc, kv_quant)
            v_arr = await asyncio.to_thread(dequantize_kv_np, v_codes, v_sc, kv_quant)
        else:
            k_arr = np.ascontiguousarray(entry["k"][b0:b0 + n_blocks, :, :cut])
            v_arr = np.ascontiguousarray(entry["v"][b0:b0 + n_blocks, :, :cut])
            wire_nbytes = int(k_arr.nbytes + v_arr.nbytes)
        await self._seed_session_kv(
            lane, kv, handles, k_arr, v_arr, cut,
            batch_size=batch_size, n_blocks=n_blocks, batcher=batcher,
        )
        # consume only after the seed landed — a failed adopt leaves the
        # entry for a retry or an export until its TTL says otherwise
        self._consume_migrated(src_sid)
        self._parked.pop(src_sid, None)
        if lane is not None and batcher is not None:
            # migrated-in KV becomes this tenant's working set: bill the
            # adopted bytes to the lane's live ledger session at WIRE size
            key = batcher._ledger_keys.get(lane)
            if key is not None:
                batcher._ledger.note_migrated(key, wire_nbytes)
        from petals_tpu.telemetry import get_journal

        get_journal().event(
            "migrate_adopt", trace_id=entry.get("trace_id"),
            occupancy=self.batcher.occupancy_info() if self.batcher is not None else None,
            session_id=src_sid, position=cut, nbytes=wire_nbytes,
        )
        return cut

    async def _seed_session_kv(
        self, lane, kv, handles, k_arr, v_arr, new_position: int,
        *, batch_size: int, n_blocks: int, batcher=None,
    ):
        """Install k/v prefix rows [0, new_position) into a FRESH session's
        cache (pooled lane or private buffers) — the prefix-cache hit path.
        Returns the updated kv pair for the private path."""
        import jax
        import jax.numpy as jnp

        if lane is not None:
            backend0 = batcher.backend
            if getattr(backend0, "is_lockstep", False):
                # multihost pooled session: broadcast the prefix and let every
                # process shard its own lane-shaped mirror (v2 import op on
                # the synthetic lane handle), then check it into the pool
                def replace_lockstep(kv_lane, lane_handles):
                    return None, backend0.import_kv(
                        lane_handles, k_arr, v_arr, new_position,
                        batch_size, batcher.max_length, n_blocks,
                    )

                # extract=False: the import REPLACES the lane wholesale, so
                # checking the old content out first would waste a full-lane
                # device copy on every process
                await batcher.run_exclusive(lane, replace_lockstep, extract=False)
                return kv
            lane_shape = (
                n_blocks, batch_size, batcher.max_length,
                backend0.num_kv_heads, backend0.head_dim,
            )
            cache_dtype = jnp.dtype(backend0.cache_dtype)

            def build(arr):
                full = np.zeros(lane_shape, cache_dtype)
                full[:, :, :new_position] = arr.astype(cache_dtype)
                return full

            new_k = await asyncio.to_thread(build, k_arr)
            new_v = await asyncio.to_thread(build, v_arr)

            def replace(kv_lane, lane_handles):
                return None, (jnp.asarray(new_k), jnp.asarray(new_v))

            # paged lanes must own pages for the seeded rows before check-in
            await batcher.run_exclusive(
                lane, replace, extract=False, write_range=(0, new_position)
            )
            return kv

        k_buf, v_buf = kv
        if getattr(self.backend, "is_lockstep", False):
            # multihost: every process shards its own mirror (v2 import op)
            new_k, new_v = await asyncio.to_thread(
                self.backend.import_kv, handles, k_arr, v_arr,
                new_position, batch_size, k_buf.shape[2], n_blocks,
            )
            self.memory_cache.update_cache(handles[0], new_k)
            self.memory_cache.update_cache(handles[1], new_v)
            return (new_k, new_v)

        def stage(arr, buf):
            full = np.zeros(buf.shape, jnp.dtype(buf.dtype))
            full[:, :, :new_position] = arr.astype(full.dtype)
            return (
                jax.device_put(full, buf.sharding)
                if getattr(buf, "sharding", None) is not None
                else jnp.asarray(full)
            )

        new_k = await asyncio.to_thread(stage, k_arr, k_buf)
        new_v = await asyncio.to_thread(stage, v_arr, v_buf)
        self.memory_cache.update_cache(handles[0], new_k)
        self.memory_cache.update_cache(handles[1], new_v)
        return (new_k, new_v)

    @staticmethod
    def _build_device_seed(parts, shape, dtype, new_position: int):
        """Fresh zeroed buffer of ``shape`` with the HBM-resident prefix
        slices concatenated into rows [0, new_position) — the single seed
        construction every device-tier path shares."""
        import jax.numpy as jnp

        pref = jnp.concatenate(parts, axis=2).astype(dtype)
        return jnp.zeros(shape, dtype).at[:, :, :new_position].set(pref)

    async def _seed_lane_kv_device(
        self, batcher, lane, kd_list, vd_list, new_position: int,
        batch_size: int, n_blocks: int,
    ):
        """Pooled-lane twin of _seed_session_kv_device: build the lane-shaped
        buffer on device from the HBM-resident prefix slices and check it in
        wholesale — the host route builds a max_length-sized zeros array and
        uploads all of it."""
        import jax.numpy as jnp

        backend0 = batcher.backend
        lane_shape = (
            n_blocks, batch_size, batcher.max_length,
            backend0.num_kv_heads, backend0.head_dim,
        )
        cache_dtype = jnp.dtype(backend0.cache_dtype)
        new_k = self._build_device_seed(kd_list, lane_shape, cache_dtype, new_position)
        new_v = self._build_device_seed(vd_list, lane_shape, cache_dtype, new_position)

        def replace(kv_lane, lane_handles):
            return None, (new_k, new_v)

        await batcher.run_exclusive(
            lane, replace, extract=False, write_range=(0, new_position)
        )

    def _seed_session_kv_device(self, kv, handles, kd_list, vd_list, new_position: int):
        """Prefix-hit seeding entirely on device: concatenate the HBM-resident
        segment slices and write them into fresh zeroed buffers. No
        host->device transfer — the host staging route uploads the whole
        max_length-shaped buffer, which on slow links costs as much as the
        skipped prefill."""
        k_buf, v_buf = kv
        new_k = self._build_device_seed(kd_list, k_buf.shape, k_buf.dtype, new_position)
        new_v = self._build_device_seed(vd_list, v_buf.shape, v_buf.dtype, new_position)
        self.memory_cache.update_cache(handles[0], new_k)
        self.memory_cache.update_cache(handles[1], new_v)
        return (new_k, new_v)

    async def _store_prefix_async(
        self, keys, n_hit: int, boundary: int, lane, handles, out_full, n_blocks: int,
        batcher=None, tenant: Optional[str] = None,
    ) -> None:
        """Snapshot KV rows [0, boundary) and store the freshly computed
        segments. Runs as a task after the prefill reply; the session loop
        awaits it before executing any LATER step of the same session, so the
        stored rows always match the content hash (content-addressed: a
        rollback later cannot poison the mapping)."""
        from petals_tpu.server.prefix_cache import SEGMENT_TOKENS

        L = n_hit * SEGMENT_TOKENS
        lane_k_dev = lane_v_dev = None
        lane_pages = None
        lane_pages_epoch = 0
        try:
            if lane is not None:
                # guard on the BATCHER's backend: the session captured its
                # batcher at open, and swap_backend can retarget self.backend
                # while this snapshot still reads the old pool
                lane_backend = batcher.backend
                if batcher.page_size is not None:
                    # page tier: pin the freshly computed segments' pages so a
                    # later hit adopts them in place of any KV re-upload; only
                    # whole stored segments pin (both bounds page-aligned
                    # because page_size divides SEGMENT_TOKENS)
                    seg_end = (boundary // SEGMENT_TOKENS) * SEGMENT_TOKENS
                    if seg_end > L:
                        lane_pages_epoch = batcher.page_epoch
                        lane_pages = batcher.pin_lane_pages(lane, L, seg_end)
                if (
                    self.prefix_cache.device_max_bytes > 0
                    and batcher.page_size is None
                    and getattr(lane_backend, "mesh", None) is None
                    and not getattr(lane_backend, "is_lockstep", False)
                ):
                    k, v, lane_k_dev, lane_v_dev = await batcher.snapshot_lane(
                        lane, boundary, 0, n_blocks, return_device=True
                    )
                else:
                    k, v = await batcher.snapshot_lane(lane, boundary, 0, n_blocks)
            elif getattr(self.backend, "is_lockstep", False):
                # multihost: per-shard all_gather (v2 export op), bounded to
                # the 128-bucketed boundary inside export_kv
                k, v = await asyncio.to_thread(
                    self.backend.export_kv, handles,
                    lambda: self.memory_cache.get_buffers(*handles),
                    0, n_blocks, boundary,
                )
            else:
                for attempt in range(20):
                    try:
                        k_buf, v_buf = self.memory_cache.get_buffers(*handles)
                        k, v = await asyncio.to_thread(
                            lambda: (
                                np.asarray(k_buf[:, :, :boundary]),
                                np.asarray(v_buf[:, :, :boundary]),
                            )
                        )
                        break
                    except Exception as e:
                        if attempt == 19:
                            logger.warning(
                                "KV snapshot read kept failing after retries "
                                "(skipping prefix store): %r", e,
                            )
                            return
                        await asyncio.sleep(0.05)
        except BaseException as e:
            # release the pins on EVERY abnormal exit, cancellation included:
            # this coroutine awaits between the pin and the cache commit, and
            # an `except Exception` here would skip the unpin when the
            # session task is cancelled mid-snapshot — the pinned pages'
            # refcounts would leak until pool reset
            if lane_pages:
                batcher.unpin_pages(lane_pages, lane_pages_epoch)
            if not isinstance(e, Exception):
                raise
            # storing is best-effort; the session must never notice
            logger.debug("Prefix store skipped: %r", e)
            return
        # device tier: single-device private sessions only — lane snapshots
        # are host-side, lockstep mirrors are per-process shards, and sliced
        # TP-sharded buffers would pin sharded HBM references of unclear
        # placement. The slices are lazy device copies of the session's
        # buffers, so they stay valid after the session's cache is freed.
        k_dev = v_dev = None
        if lane is not None:
            if lane_k_dev is not None:
                k_dev = lane_k_dev[:, :, L:]
                v_dev = lane_v_dev[:, :, L:]
        elif (
            not getattr(self.backend, "is_lockstep", False)
            and getattr(self.backend, "mesh", None) is None
            and self.prefix_cache.device_max_bytes > 0
        ):
            try:
                k_buf, v_buf = self.memory_cache.get_buffers(*handles)
                k_dev = k_buf[:, :, L:boundary]
                v_dev = v_buf[:, :, L:boundary]
            except Exception:  # swarmlint: disable=no-silent-except — device-tier pin is opportunistic: a racing free only downgrades this entry to the host tier
                k_dev = v_dev = None
        self.prefix_cache.put(
            keys, n_hit, k[:, :, L:], v[:, :, L:], out_full[:, L:boundary],
            k_dev=k_dev, v_dev=v_dev,
            pages=lane_pages, pages_pool=batcher if lane_pages else None,
            pages_epoch=lane_pages_epoch,
            tenant=tenant,  # residency bills to the storing peer (ledger)
        )

    async def _snapshot_session(
        self, reg: dict, b0: Optional[int] = None, b1: Optional[int] = None
    ) -> dict:
        """Host copy of a live session's KV (optionally just blocks [b0, b1)
        relative to the span), sliced to its position. The step loop donates
        buffers into XLA, so a fetch can race a step in flight (the grabbed
        buffer gets invalidated) — retry on the fresh buffer. The device->host
        copy is 100s of MB for long contexts, so it runs off the event loop:
        other sessions' steps must not stall behind it."""
        if reg.get("lane") is not None:
            # pooled session (lockstep included — snapshot_lane routes through
            # the temp-mirror export there): the lane copy runs on the compute
            # thread, so it serializes with batched steps — no donation race
            # to retry. MUST be checked before is_lockstep: pooled sessions
            # register handles=None, so the private export below would crash.
            n = reg["end"] - reg["start"]
            position = reg["position"]
            batcher = reg.get("batcher") or self.batcher
            # suspended lanes: read the swap entry's host copy directly —
            # snapshot_lane would swap the lane back IN just to re-export it
            pair = await batcher.snapshot_from_swap(
                reg["lane"], position, b0 if b0 is not None else 0,
                b1 if b1 is not None else n,
            )
            if pair is None:
                pair = await batcher.snapshot_lane(
                    reg["lane"], position, b0 if b0 is not None else 0,
                    b1 if b1 is not None else n,
                )
            k, v = pair
            return {
                "k": k, "v": v, "position": position,
                "start": reg["start"], "end": reg["end"],
                "batch_size": reg["batch_size"], "max_length": reg["max_length"],
            }
        if getattr(self.backend, "is_lockstep", False):
            # multi-host: every process all_gathers its shards in-program
            # (multihost.py export_kv); buffer fetch + donation retry happen
            # inside, under the broadcast lock
            n = reg["end"] - reg["start"]
            position = reg["position"]
            handles = reg["handles"]
            k, v = await asyncio.to_thread(
                self.backend.export_kv, handles,
                lambda: self.memory_cache.get_buffers(*handles),
                b0 if b0 is not None else 0,
                b1 if b1 is not None else n,
                position,
            )
            return {
                "k": k, "v": v, "position": position,
                "start": reg["start"], "end": reg["end"],
                "batch_size": reg["batch_size"], "max_length": reg["max_length"],
            }
        bs = slice(b0, b1)
        for attempt in range(20):
            position = reg["position"]
            try:
                k_buf, v_buf = self.memory_cache.get_buffers(*reg["handles"])
                k, v = await asyncio.to_thread(
                    lambda: (
                        np.asarray(k_buf[bs, :, :position]),
                        np.asarray(v_buf[bs, :, :position]),
                    )
                )
                break
            except Exception:
                if attempt == 19:
                    raise
                await asyncio.sleep(0.05)
        return {
            "k": k, "v": v, "position": position,
            "start": reg["start"], "end": reg["end"],
            "batch_size": reg["batch_size"], "max_length": reg["max_length"],
        }

    async def park_sessions(self, ttl: Optional[float] = None) -> int:
        """Snapshot every live session's KV into host RAM (drain path: streams
        are about to die with the server, but exports must keep working)."""
        import time

        ttl = self.park_ttl if ttl is None else ttl
        parked = 0
        for session_id, reg in list(self._session_registry.items()):
            if reg["position"] <= 0:
                continue
            try:
                snap = await self._snapshot_session(reg)
            except Exception as e:
                logger.warning(f"Could not park session {session_id!r}: {e}")
                continue
            snap["expires"] = time.monotonic() + ttl
            snap["trace_id"] = reg.get("trace_id")
            snap["peer"] = reg.get("peer")  # ledger attribution of a later push
            self._parked[session_id] = snap
            parked += 1
        return parked

    def _prune_parked(self) -> None:
        import time

        now = time.monotonic()
        for sid in [s for s, p in self._parked.items() if p.get("expires", 0) < now]:
            del self._parked[sid]

    def abort_migrations(self) -> None:
        """Tell in-flight migration pushes to give up immediately (shutdown
        path): the parked entries stay, clients fall back to export/replay."""
        self._migrate_abort.set()

    def shutdown(self) -> None:
        self.abort_migrations()
        self.queue.shutdown()
        with contextlib.suppress(Exception):
            loop = asyncio.get_event_loop()
            if loop.is_running():
                # strong refs: the loop holds tasks weakly, and an unreferenced
                # close could be GC'd before it finishes tearing down
                closers = [loop.create_task(self._push_pool.close())]
                if self.batcher is not None:
                    closers.append(loop.create_task(self.batcher.close()))
                self._shutdown_tasks = closers
                for t in closers:
                    t.add_done_callback(log_exception_callback(logger, "shutdown close"))

    # ------------------------------------------------------------------ helpers

    def _parse_chain(self, uids: str) -> Tuple[int, int]:
        """Validate a chain of UIDs against our span; return (start, end) relative
        to the backend's first block."""
        parts = uids.split(CHAIN_DELIMITER) if isinstance(uids, str) else list(uids)
        if not parts:
            raise ValueError("Empty uid chain")
        indices = []
        for uid in parts:
            prefix, idx = parse_uid(uid)
            if prefix != self.dht_prefix:
                raise ValueError(f"UID {uid!r} does not match served prefix {self.dht_prefix!r}")
            indices.append(idx)
        lo, hi = indices[0], indices[-1] + 1
        if indices != list(range(lo, hi)):
            raise ValueError(f"UID chain must be contiguous, got {indices}")
        first, last = self.backend.first_block, self.backend.first_block + self.backend.n_blocks
        if lo < first or hi > last:
            raise ValueError(
                f"Requested blocks [{lo}, {hi}) outside served span [{first}, {last})"
            )
        return lo - first, hi - first

    def _validate_step_tensors(self, hidden, prompts, hypo_ids, batch_size: int, n_blocks: int) -> None:
        """Reject malformed step tensors with a clean error instead of an opaque
        XLA/scan failure — and keep clients from forcing fresh compilations with
        novel batch sizes on the serving hot path."""
        hsz = self.backend.hidden_size
        if hidden is not None and (
            hidden.ndim != 3 or hidden.shape[0] != batch_size or hidden.shape[2] != hsz
        ):
            raise ValueError(
                f"step hidden must be [batch={batch_size}, seq, hidden={hsz}], "
                f"got {tuple(hidden.shape)}"
            )
        if hypo_ids is not None and tuple(hypo_ids.shape) != (batch_size,):
            raise ValueError(
                f"hypo_ids must be [{batch_size}], got {tuple(hypo_ids.shape)}"
            )
        if prompts is not None and (
            prompts.ndim != 4
            or prompts.shape[0] != n_blocks
            or prompts.shape[1] != batch_size
            or prompts.shape[3] != hsz
        ):
            raise ValueError(
                f"prompts must be [{n_blocks} blocks, batch={batch_size}, pre_seq, "
                f"hidden={hsz}], got {tuple(prompts.shape)}"
            )

    def _get_tensor(self, payload: dict, name: str) -> Optional[np.ndarray]:
        wire = (payload.get("tensors") or {}).get(name)
        if wire is None:
            return None
        arr = deserialize_array(wire)
        return None if is_dummy(arr) else arr

    def _reply_compression(self, payload: dict) -> CompressionType:
        """Per-request output compression negotiation (reference
        handler.py:411-432): the client's requested codec wins over the
        server-wide default."""
        requested = payload.get("compression")
        if requested is None:
            return self.compression
        try:
            return CompressionType(requested)
        except ValueError:
            raise ValueError(f"Unknown compression {requested!r}")

    # ------------------------------------------------------------------ rpc methods

    async def rpc_forward(self, payload, ctx: RpcContext):
        start, end = self._parse_chain(payload["uids"])
        reply_comp = self._reply_compression(payload)  # reject bad codecs up front
        hidden = self._get_tensor(payload, "hidden")
        prompts = self._get_tensor(payload, "prompts")
        if hidden is None or hidden.ndim != 3 or hidden.shape[2] != self.backend.hidden_size:
            raise ValueError(
                f"rpc_forward expects a [batch, seq, hidden={self.backend.hidden_size}] "
                f"tensor, got {None if hidden is None else tuple(hidden.shape)}"
            )
        backend = self._sub_backend(start, end)
        adapter = payload.get("active_adapter")
        def run_forward():
            with device_annotation("rpc_forward"):  # on the compute thread
                return np.asarray(backend.forward(hidden, prompts=prompts, active_adapter=adapter))

        with get_tracer().span(
            "rpc_forward", annotate=False, blocks=end - start,
            tokens=hidden.shape[0] * hidden.shape[1],
        ):
            out = await asyncio.wait_for(
                self.queue.submit(
                    run_forward,
                    priority=PRIORITY_TRAINING,
                    size=hidden.shape[0] * hidden.shape[1],
                ),
                self.request_timeout,
            )
        return {"tensors": {"hidden": serialize_array(out, reply_comp)}}

    async def rpc_backward(self, payload, ctx: RpcContext):
        start, end = self._parse_chain(payload["uids"])
        reply_comp = self._reply_compression(payload)  # reject bad codecs up front
        hidden = self._get_tensor(payload, "hidden")
        grad_out = self._get_tensor(payload, "grad_out")
        prompts = self._get_tensor(payload, "prompts")
        if hidden is None or grad_out is None:
            raise ValueError("rpc_backward expects hidden and grad_out tensors")
        if hidden.ndim != 3 or hidden.shape[2] != self.backend.hidden_size:
            raise ValueError(
                f"rpc_backward expects a [batch, seq, hidden={self.backend.hidden_size}] "
                f"tensor, got {tuple(hidden.shape)}"
            )
        if grad_out.shape != hidden.shape:
            raise ValueError(
                f"grad_out shape {tuple(grad_out.shape)} != hidden shape {tuple(hidden.shape)}"
            )
        backend = self._sub_backend(start, end)
        adapter = payload.get("active_adapter")

        def run():
            with device_annotation("rpc_backward"):
                grad_hidden, grad_prompts = backend.backward(
                    hidden, grad_out, prompts=prompts, active_adapter=adapter
                )
            return np.asarray(grad_hidden), (
                np.asarray(grad_prompts) if grad_prompts is not None else None
            )

        with get_tracer().span(
            "rpc_backward", annotate=False, blocks=end - start,
            tokens=hidden.shape[0] * hidden.shape[1],
        ):
            grad_hidden, grad_prompts = await asyncio.wait_for(
                self.queue.submit(
                    run, priority=PRIORITY_TRAINING, size=hidden.shape[0] * hidden.shape[1]
                ),
                self.request_timeout,
            )
        tensors = {"grad_hidden": serialize_array(grad_hidden, reply_comp)}
        if grad_prompts is not None:
            tensors["grad_prompts"] = serialize_array(grad_prompts, reply_comp)
        return {"tensors": tensors}

    async def rpc_info(self, payload, ctx: RpcContext):
        info = dict(self.server_info_fn()) if self.server_info_fn else {}
        info.update(
            cache_tokens_available=max(
                self.memory_cache.bytes_left // max(self.backend.cache.cache_bytes_per_token(), 1), 0
            ),
            first_block=self.backend.first_block,
            n_blocks=self.backend.n_blocks,
            dht_prefix=self.dht_prefix,
            tracing=get_tracer().summary(),
            # compact metrics digest (tok/s, TTFT/step percentiles, swap
            # pressure) — same blob that rides ServerInfo on the DHT
            telemetry=telemetry_digest(),
            # compiled-program observatory digest (programs, compile seconds,
            # anomalies) — same blob as ServerInfo.compile_stats
            compile_stats=compile_stats_digest(),
        )
        if self.batcher is not None:
            info["continuous_batching"] = {
                "lanes": self.batcher.n_lanes,
                "max_length": self.batcher.max_length,
                "prefill_token_budget": self.batcher.prefill_token_budget,
                **self.batcher.stats,
            }
            paged = self.batcher.paged_summary()
            if paged is not None:
                info["continuous_batching"]["paged"] = paged
            # scheduler occupancy (busy lanes, free pages, suspended sessions,
            # swap bytes, preemptions): lets clients route around loaded
            # servers — the same dict rides ServerInfo.pool on the DHT
            info["pool"] = self.batcher.occupancy_info()
        if self.prefix_cache is not None:
            info["prefix_cache"] = self.prefix_cache.summary()
        return info

    async def rpc_probe(self, payload, ctx: RpcContext):
        """Integrity canary probe: run a CALLER-seeded golden input through
        this span's forward pass and return its activation fingerprint
        (ops/fingerprint.py). The caller picks the seed, so a replica
        cannot pre-compute or replay an honest digest; the canary prober
        (telemetry/integrity.py) compares digests across every replica of
        a span by quorum and quarantines outliers. The probe output runs
        through the same ``integrity.corrupt`` chaos site as session
        replies, so an injected corruption is probe-visible."""
        from petals_tpu.ops import fingerprint as fp_ops

        seed = int(payload.get("seed", fp_ops.fp_seed()))
        n_tokens = max(1, min(int(payload.get("tokens", 4)), 16))
        hsz = self.backend.hidden_size
        rng = np.random.RandomState(seed & 0x7FFFFFFF)
        # activation-scale golden input: magnitudes typical of embedding
        # outputs, so the forward pass exercises realistic numerics
        hidden = (rng.standard_normal((1, n_tokens, hsz)) * 0.02).astype(np.float32)
        backend = self.backend

        def run_probe():
            with device_annotation("rpc_probe"):
                return np.asarray(backend.forward(hidden))

        out = await asyncio.wait_for(
            self.queue.submit(run_probe, priority=PRIORITY_TRAINING, size=n_tokens),
            self.request_timeout,
        )
        if chaos.ENABLED and chaos.fire(
            chaos.SITE_INTEGRITY_CORRUPT, detail=f"{self._peer_str}:probe"
        ) == "corrupt":
            out = chaos.corrupt_array(
                out, site_seed=self._corrupt_seed, position=n_tokens
            )
        fp = fp_ops.fingerprint_output(out, hsz)
        return {
            "fp": fp_ops.fp_list(fp),
            "seed": seed,
            "tokens": n_tokens,
            "fp_seed": fp_ops.fp_seed(),
            "first_block": backend.first_block,
            "n_blocks": backend.n_blocks,
        }

    async def rpc_inference(self, requests, ctx: RpcContext):
        """Bidirectional inference stream: open -> step* (reference
        handler.py:132-195 + block_functions.iterate_rpc_inference)."""
        open_msg = await asyncio.wait_for(anext(requests), self.step_timeout)
        if self.draining:
            raise RuntimeError("Server is draining: not accepting new sessions")
        client_version = open_msg.get("client_version")
        if client_version is not None:
            from petals_tpu.utils.version import incompatibility_error, is_compatible

            if not is_compatible(client_version):
                raise ValueError(incompatibility_error(client_version, peer="client"))
        start, end = self._parse_chain(open_msg["uids"])
        max_length = int(open_msg["max_length"])
        if self.inference_max_length is not None and max_length > self.inference_max_length:
            raise ValueError(
                f"max_length {max_length} exceeds this server's inference_max_length "
                f"{self.inference_max_length}"
            )
        batch_size = int(open_msg.get("batch_size", 1))
        reply_comp = self._reply_compression(open_msg)  # for every step reply
        active_adapter = open_msg.get("active_adapter")
        session_id = open_msg.get("session_id")
        # Request-scoped trace identity: the client mints it at session open
        # and sends it in the open message; a missing or malformed id gets a
        # server-minted one so the causal timeline exists for old clients
        # too. It tags every span below, rides the scheduler slot, and keys
        # the admission/preemption journal events.
        trace_id = normalize_trace_id(open_msg.get("trace_id")) or new_trace_id()
        _trace_token = set_trace_id(trace_id)
        t_open = time.perf_counter()
        ttft_observed = False
        # where to push our outputs: {"addr": "host:port/peer", "session_id": ...}
        push_to = open_msg.get("push_to")
        backend = self._sub_backend(start, end)
        backend.params_for(active_adapter)  # validate the adapter exists up front

        # Continuous batching: single-stream full-span sessions borrow a lane
        # of the shared pool and decode coalesced with their neighbors; every
        # other shape gets the classic private cache. The batcher is captured
        # ONCE (like ``backend``): a live span move swaps self.batcher for a
        # new pool whose lane indices alias other tenants — this session must
        # keep stepping/releasing through the pool it acquired from (whose
        # close() fails it loudly into the failover path).
        lane: Optional[int] = None
        open_wait_s = 0.0  # lane-admission wait, reported in the open ack
        batcher = self.batcher
        # the peer this session bills to (fair-share admission + the resource
        # ledger). A PROVEN identity (rpc identity handshake) always wins;
        # without one, an UNAUTHENTICATED self-declared "peer_hint" from the
        # open message partitions the accounting view — a liar can only make
        # itself LOOK like several peers, exactly what an anonymous transport
        # already allows — and absent both, the session bills anonymously.
        peer = getattr(ctx, "remote_peer_id", None)
        if peer is not None:
            peer_str: Optional[str] = peer.to_string()
        else:
            hint = open_msg.get("peer_hint")
            peer_str = str(hint)[:64] if hint else None
        if (
            batcher is not None
            and batch_size == 1
            and active_adapter is None
            and start == 0
            and end == self.backend.n_blocks
            and max_length <= batcher.max_length
        ):
            from petals_tpu.data_structures import parse_session_priority
            from petals_tpu.server.memory_cache import AllocationFailed

            alloc_timeout = open_msg.get("alloc_timeout")
            # optional client priority hint ("high"/"normal"/"low" or an int
            # class); absent -> normal, i.e. exactly the pre-hint behavior.
            # The peer id feeds per-peer fair-share admission and the ledger.
            priority = parse_session_priority(open_msg.get("priority"))
            t_open_wait = time.perf_counter()
            try:
                lane = await batcher.acquire_lane(
                    timeout=30.0 if alloc_timeout is None else alloc_timeout,
                    priority=priority,
                    peer_id=peer_str,
                    trace_id=trace_id,
                )
            except AllocationFailed as e:
                logger.debug(f"No decode lane ({e}); serving with a private cache")
            # reported to the client in the open ack: for short sessions
            # (a handful of steps) this admission wait is the ONLY queue
            # signal they ever see, and without it a backlogged server
            # looks identical to an idle one at route-build time
            open_wait_s = time.perf_counter() - t_open_wait

        push_queue: Optional[asyncio.Queue] = None
        if lane is not None:
            cache_ctx = self._lane_ctx(lane, batcher)
        else:
            descriptors = backend.cache_descriptors(batch_size, max_length, 0, end - start)
            cache_ctx = self.memory_cache.allocate_cache(
                *descriptors, timeout=open_msg.get("alloc_timeout")
            )
        async with cache_ctx as handles:
            if lane is None:
                k_buf, v_buf = self.memory_cache.get_buffers(*handles)
                kv = (k_buf, v_buf)
            else:
                kv = None  # lives in the batcher's pool, keyed by lane
            position = 0
            reg = None
            if session_id:
                # registered only once allocation succeeded (no leak on failure)
                push_queue = asyncio.Queue(maxsize=64)
                self._push_queues[session_id] = push_queue
                reg = {
                    "handles": handles, "lane": lane, "batcher": batcher, "position": 0,
                    "start": self.backend.first_block + start,
                    "end": self.backend.first_block + end,
                    "batch_size": batch_size, "max_length": max_length,
                    "trace_id": trace_id,  # rides into parked/migrated snapshots
                    "peer": peer_str,  # ledger attribution for migrate-out pushes
                }
                self._session_registry[session_id] = reg
            # echo the trace id so the client learns a server-minted one
            yield {
                "session_open": True, "position": 0, "max_length": max_length,
                "trace_id": trace_id,
                "open_wait_s": round(open_wait_s, 6),
            }

            steps = _StepSource(requests, push_queue, self.session_timeout)
            seen_steps = set()  # dedup: the same step may arrive via client AND push
            pending_store = None  # in-flight prefix-cache store task
            reply_build = None  # the annotation around a decode reply in the making

            def take_decode_step(item, read_at: float) -> bool:
                """This stream's sink (``StreamRequests.sink``): the connection's
                reader offers an item in the turn that read its frame. A plain
                decode step that finds this loop parked is parsed and handed to
                the batcher here, as the loop does below up to ``batcher.step``,
                and the loop wakes with the step's output; anything the loop
                would have to wait for, re-order or refuse is left to the queue
                and to the loop (False), with nothing changed."""
                held_at = time.perf_counter()
                fut = steps.parked
                if (
                    fut is None or fut.done() or pending_store is not None
                    or self.draining or chaos.ENABLED
                    or not _is_plain_decode_step(item, batch_size)
                    or item.get("step_id") in seen_steps or position >= max_length
                ):
                    return False
                hidden = self._get_tensor(item, "hidden")
                if hidden is None:
                    return False
                self._validate_step_tensors(hidden, None, None, batch_size, end - start)
                t_tok = time.perf_counter()
                if not batcher.begin_step(lane, hidden, position, fut, arrived=(read_at, held_at)):
                    return False
                span = get_tracer().span(
                    "inference_step", annotate=False, trace_id=trace_id,
                    blocks=end - start, batch=batch_size, seq=1,
                )
                span.__enter__()
                steps.take(_TakenStep(item, read_at, held_at, hidden, t_tok, span, batcher, lane), self.step_timeout)
                return True

            if lane is not None and isinstance(requests, StreamRequests):
                requests.sink = take_decode_step
            try:
              while True:
                step, t_read = await steps.next()
                # a decode step the reader handed to the batcher while this
                # loop was parked: its output is in hand, and what follows
                # finds in it nothing to wait for, to install or to parse
                taken = step if isinstance(step, _TakenStep) else None
                if taken is not None:
                    step = taken.step
                # serving clock for this step's step_meta: receipt -> reply
                # ready (everything the client's wall covers except network)
                t_step_recv = time.perf_counter() if taken is None else taken.held_at
                # a later step may mutate the rows being stored (rollback,
                # overwrite): finish the store first so content stays honest
                if pending_store is not None:
                    if not pending_store.done():
                        with contextlib.suppress(Exception):
                            await pending_store
                    pending_store = None
                if step is None:
                    break
                if chaos.ENABLED:
                    # mid-step fault: a raise here kills the stream exactly at
                    # the step boundary, the worst point for a session's KV
                    await chaos.inject(chaos.SITE_HANDLER_STEP, detail=session_id)
                if self.draining:
                    # fail fast so the client repairs its chain NOW, while the
                    # parked KV export is still being served (drain window)
                    raise RuntimeError(
                        "Server is draining: migrate this session via ptu.session_export"
                    )
                if "push_to" in step:  # chain repair moved our downstream peer
                    push_to = step["push_to"] or None
                step_id = step.get("step_id")
                if step_id is not None:
                    if step_id in seen_steps:
                        continue
                    seen_steps.add(step_id)

                start_from = step.get("start_from_position")
                if start_from is not None:
                    if start_from > position:
                        raise ValueError(
                            f"start_from_position {start_from} is ahead of cache ({position})"
                        )
                    if 0 < start_from < position:
                        # a paged lane of a span with page groups: served where every windowed layer still holds what a
                        # row at ``start_from`` reaches, refused behind that (the pages went back as the window moved)
                        gone = lane is not None and batcher.grouped and not batcher.window_reach_held(lane, int(start_from))
                        backend.cache.refuse(
                            f"start_from_position {start_from} behind the cache's position {position}",
                            "a state cannot be cut back to an earlier position (0 starts the session over)", paged=gone,
                        )
                    position = int(start_from)  # rollback (speculative decoding)
                    if reg is not None:
                        reg["position"] = position

                if "kv_adopt" in step or "kv_import" in step:
                    backend.cache.refuse(
                        "kv_adopt / kv_import", "they seed keys and values cut to a position; the state is not shipped yet",
                        paged=lane is not None and batcher.grouped,
                    )
                if "kv_adopt" in step:
                    # seed from KV already on this server (migrated or parked)
                    position = await self._install_kv_adopt(
                        step, lane, kv, handles, position,
                        abs_start=self.backend.first_block + start,
                        batch_size=batch_size, n_blocks=end - start,
                        max_length=max_length, batcher=batcher,
                    )
                    if lane is None:
                        kv = tuple(self.memory_cache.get_buffers(*handles))
                    if reg is not None:
                        reg["position"] = position
                    yield {"position": position, "kv_adopt": True}
                    continue

                if "kv_import" in step:
                    if lane is not None:
                        position = await self._install_kv_import_pooled(
                            step, lane, position,
                            batch_size=batch_size, n_blocks=end - start,
                            max_length=max_length, batcher=batcher,
                        )
                    else:
                        position = await self._install_kv_import(
                            step, kv, handles, position,
                            batch_size=batch_size, n_blocks=end - start, max_length=max_length,
                        )
                        kv = tuple(self.memory_cache.get_buffers(*handles))
                    if reg is not None:
                        reg["position"] = position
                    yield {"position": position, "kv_import": True}
                    continue

                if taken is not None:
                    hidden, prompts, hypo_ids = taken.hidden, None, None  # parsed and held to the shapes by the sink
                else:
                    hidden = self._get_tensor(step, "hidden")
                    prompts = self._get_tensor(step, "prompts")
                    hypo_ids = self._get_tensor(step, "hypo_ids")
                    self._validate_step_tensors(hidden, prompts, hypo_ids, batch_size, end - start)
                seq = 0 if hidden is None else hidden.shape[1]
                if hidden is not None and position + seq > max_length:
                    raise ValueError(
                        f"Step of {seq} tokens at position {position} exceeds max_length {max_length}"
                    )

                if hidden is None or seq == 0:
                    # cache probe step (reference block_functions.py:209-211)
                    yield {"tensors": {}, "position": position}
                    continue

                pos = position

                # content-addressed prefix cache: a fresh session's prefill
                # probes for its longest cached prefix, seeds KV from host
                # RAM, and computes only the tail (server/prefix_cache.py)
                exec_hidden, prefix_out, pc_keys, pc_hits = hidden, None, None, 0
                if (
                    self.prefix_cache is not None
                    and position == 0
                    and batch_size == 1
                    and prompts is None and hypo_ids is None
                    and active_adapter is None
                    # "peer" scope isolates clients BY their authenticated
                    # identity: an unauthenticated connection has none, and
                    # salting with a shared 'None' would silently merge every
                    # such client back into one timing-observable pool — the
                    # exact channel the mode exists to close. No identity, no
                    # caching.
                    and (
                        self.prefix_share_scope == "swarm"
                        or getattr(ctx, "remote_peer_id", None) is not None
                    )
                ):
                    from petals_tpu.server.prefix_cache import SEGMENT_TOKENS, segment_keys

                    if seq >= SEGMENT_TOKENS:
                        salt = (
                            f"{self.dht_prefix}:{self.backend.first_block + start}:"
                            f"{self.backend.first_block + end}"
                        )
                        if self.prefix_share_scope == "peer":
                            # full id, not repr (repr truncates to 12 hex
                            # chars — 48 bits an attacker could grind a
                            # colliding keypair for); non-None: gated above
                            salt += f":{ctx.remote_peer_id.to_string()}"
                        # hashing is multi-MB work: off the event loop, like
                        # every other bulk host op in this file
                        pc_keys = await asyncio.to_thread(segment_keys, hidden, salt)
                        # probe + entry resolution stay synchronous on the
                        # loop: no await separates them, so a concurrent
                        # put()'s LRU eviction cannot invalidate a probed key
                        # before its entry reference is held (the heavy
                        # concatenation then runs off-loop on the references)
                        pc_hits = self.prefix_cache.probe(pc_keys)
                        if pc_hits:
                            hit_len = pc_hits * SEGMENT_TOKENS
                            pc_entries = self.prefix_cache.get_entries(pc_keys, pc_hits)
                            # device-tier refs resolve HERE, on the loop, for
                            # the same reason the entries do: a concurrent
                            # eviction pops dict fields, and a held array
                            # reference survives that where a later lookup
                            # would not
                            kd_list = [e.get("kd") for e in pc_entries]
                            vd_list = [e.get("vd") for e in pc_entries]
                            seed_backend = (
                                batcher.backend if lane is not None else self.backend
                            )
                            # page tier first: a pooled lane whose WHOLE hit
                            # prefix is still page-resident in THIS batcher's
                            # pool (same epoch — pins die on reset) adopts the
                            # pages by table reference: zero bytes copied,
                            # copy-on-write protects the shared rows
                            paged_adopted = False
                            if lane is not None and batcher.page_size is not None:
                                spp = SEGMENT_TOKENS // batcher.page_size
                                if all(
                                    e.get("pages") is not None
                                    and e.get("pages_pool") is batcher
                                    and e.get("pages_epoch") == batcher.page_epoch
                                    and len(e["pages"]) == spp
                                    for e in pc_entries
                                ):
                                    # swarmlint: disable=paired-refcount — ownership transfer: adopted refs belong to the lane's table row; release_lane / copy-on-write decref them
                                    batcher.adopt_pages(
                                        lane,
                                        [p for e in pc_entries for p in e["pages"]],
                                    )
                                    self.prefix_cache.stats["page_hits"] = (
                                        self.prefix_cache.stats.get("page_hits", 0) + 1
                                    )
                                    prefix_out = await asyncio.to_thread(
                                        lambda: np.concatenate(
                                            [e["out"] for e in pc_entries], axis=1
                                        )
                                    )
                                    paged_adopted = True
                            use_device = (
                                not paged_adopted
                                and not getattr(seed_backend, "is_lockstep", False)
                                # mesh guard mirrors the store path: after a
                                # swap_backend onto a TP mesh, surviving
                                # device entries must not seed unsharded
                                # buffers into a sharded session
                                and getattr(seed_backend, "mesh", None) is None
                                and all(x is not None for x in kd_list)
                            )
                            if paged_adopted:
                                pass  # the block table IS the seed
                            elif use_device:
                                # whole prefix HBM-resident: zero host->device
                                # traffic; only `out` rides from host RAM
                                self.prefix_cache.stats["device_hits"] = (
                                    self.prefix_cache.stats.get("device_hits", 0) + 1
                                )
                                prefix_out = await asyncio.to_thread(
                                    lambda: np.concatenate(
                                        [e["out"] for e in pc_entries], axis=1
                                    )
                                )
                                if lane is not None:
                                    await self._seed_lane_kv_device(
                                        batcher, lane, kd_list, vd_list, hit_len,
                                        batch_size, end - start,
                                    )
                                else:
                                    kv = self._seed_session_kv_device(
                                        kv, handles, kd_list, vd_list, hit_len
                                    )
                            else:
                                k_pre, v_pre, prefix_out = await asyncio.to_thread(
                                    self.prefix_cache.concat_entries, pc_entries
                                )
                                kv = await self._seed_session_kv(
                                    lane, kv, handles, k_pre, v_pre, hit_len,
                                    batch_size=batch_size, n_blocks=end - start,
                                    batcher=batcher,
                                )
                                # a host-staged hit is the radix promotion
                                # signal: hot path nodes move up to the HBM
                                # tier OFF the reply path (multi-MB uploads),
                                # so the NEXT session with this prefix seeds
                                # device-resident
                                if (
                                    not getattr(seed_backend, "is_lockstep", False)
                                    and getattr(seed_backend, "mesh", None) is None
                                    and self.prefix_cache.device_max_bytes > 0
                                ):
                                    promo = asyncio.create_task(
                                        asyncio.to_thread(
                                            self.prefix_cache.maybe_promote_device,
                                            pc_keys, pc_hits,
                                        )
                                    )
                                    promo.add_done_callback(
                                        log_exception_callback(
                                            logger, "prefix device promotion"
                                        )
                                    )
                            exec_hidden = hidden[:, hit_len:]
                            pos = hit_len

                # queue/compute attribution for the step_meta piggyback: the
                # pooled paths get the batcher's per-lane split; the rest
                # fall back to the execution-block wall (queue folded in)
                t_exec = time.perf_counter()
                step_timing = None
                step_fp = None  # fused activation fingerprint (integrity)
                step_variant = "cached"
                with contextlib.nullcontext() if taken is not None else get_tracer().span(
                    "inference_step", annotate=False, trace_id=trace_id,
                    blocks=end - start, batch=batch_size, seq=seq,
                ):  # a taken step's span ran from the sink to the batcher's reply
                    if exec_hidden.shape[1] == 0:
                        # the whole prefill was cached: no device work at all
                        out = prefix_out
                        prefix_out = None
                    elif lane is not None and seq == 1 and prompts is None and hypo_ids is None:
                        # the continuous-batching hot path: one token, coalesced
                        # with whatever other sessions are stepping right now
                        if taken is not None:
                            t_tok, out = taken.t_tok, taken.out
                        else:
                            t_tok = time.perf_counter()
                            out = await asyncio.wait_for(
                                batcher.step(lane, hidden, pos, arrived=(t_read, t_step_recv)),
                                self.step_timeout,
                            )
                        t_resumed = time.perf_counter()
                        if not step.get("gen_tokens"):
                            # a plain decode reply: nothing awaits from here
                            # to its yield, so one annotation covers the
                            # stretch (closed there, or where a raise ends up)
                            reply_build = device_annotation("ptu.reply.build", lane=lane)
                            reply_build.__enter__()
                        tm.TOKEN_LATENCY.observe(t_resumed - t_tok)
                        step_variant = "decode"
                        step_timing = batcher.pop_step_timing(lane)
                        step_fp = batcher.pop_step_fp(lane)
                    elif (
                        lane is not None and prompts is None and hypo_ids is None
                        and batcher.page_size is not None
                    ):
                        # paged-lane prefill: admitted into the MIXED step —
                        # each tick advances every decoding lane AND one
                        # bucketed chunk of this prefill in ONE jitted
                        # program over the page pool (no lane extract/insert,
                        # no stop-the-world chunks)
                        out = await asyncio.wait_for(
                            batcher.prefill_lane(lane, exec_hidden, pos),
                            self.step_timeout,
                        )
                        step_variant = "prefill"
                        step_timing = batcher.pop_step_timing(lane)
                        step_fp = batcher.pop_step_fp(lane)
                    elif lane is not None and prompts is None and hypo_ids is None:
                        # pooled long prefill on the DENSE pool (and the
                        # TP/lockstep spans, which gate paged mode off): each
                        # chunk is its OWN queue task, so other sessions'
                        # batched decode steps interleave between chunks
                        # instead of stalling for the whole prefill
                        # (Sarathi-style)
                        step_variant = "dense_prefill"
                        chunk_fns = []
                        off = 0
                        # the full prompt length is known here: every chunk
                        # declares it so LongRoPE (phi3) selects short/long
                        # factors from the FINAL sequence length instead of
                        # flipping factors between chunks (HF parity)
                        prefill_n_total = pos + exec_hidden.shape[1]
                        for clen in backend.chunk_plan(
                            batch_size, exec_hidden.shape[1], kv_buf_len=batcher.max_length
                        ):
                            chunk = exec_hidden[:, off : off + clen]
                            chunk_pos = pos + off

                            def run_chunk(kv_lane, lane_handles, chunk=chunk, chunk_pos=chunk_pos):
                                with device_annotation("inference_step"):
                                    out, new_kv = backend.inference_step(
                                        chunk, kv_lane, chunk_pos,
                                        active_adapter=active_adapter,
                                        handles=lane_handles,
                                        n_total=prefill_n_total,
                                    )
                                return np.asarray(out), new_kv

                            chunk_fns.append(run_chunk)
                            off += clen
                        outs = await asyncio.wait_for(
                            batcher.run_exclusive_chunks(
                                lane, chunk_fns, size=batch_size * exec_hidden.shape[1],
                                write_range=(pos, pos + exec_hidden.shape[1]),
                            ),
                            self.step_timeout,
                        )
                        out = outs[0] if len(outs) == 1 else np.concatenate(outs, axis=1)
                    elif lane is not None:
                        # pooled session with deep prompts or explicit
                        # hypo_ids: one atomic exclusive pass on the lane
                        step_variant = "exclusive"

                        def run_lane(kv_lane, lane_handles, hidden=hidden, prompts=prompts, hypo_ids=hypo_ids):
                            with device_annotation("inference_step"):
                                out, new_kv = backend.inference_step(
                                    hidden, kv_lane, pos, prompts=prompts,
                                    hypo_ids=hypo_ids, active_adapter=active_adapter,
                                    handles=lane_handles,
                                )
                            return np.asarray(out), new_kv

                        out = await asyncio.wait_for(
                            batcher.run_exclusive(
                                lane, run_lane, size=batch_size * seq,
                                write_range=(pos, pos + seq),
                            ),
                            self.step_timeout,
                        )
                    else:
                        step_variant = "private"

                        def run_step(exec_hidden=exec_hidden, kv=kv):
                            with device_annotation("inference_step"):
                                out, new_kv = backend.inference_step(
                                    exec_hidden, kv, pos, prompts=prompts, hypo_ids=hypo_ids,
                                    active_adapter=active_adapter, handles=handles,
                                )
                            return np.asarray(out), new_kv

                        out, kv = await asyncio.wait_for(
                            self.queue.submit(
                                run_step, priority=PRIORITY_INFERENCE,
                                size=batch_size * exec_hidden.shape[1],
                            ),
                            self.step_timeout,
                        )
                        # keep the allocator's view coherent (old buffers donated)
                        self.memory_cache.update_cache(handles[0], kv[0])
                        self.memory_cache.update_cache(handles[1], kv[1])
                fallback_compute_s = time.perf_counter() - t_exec
                if prefix_out is not None:
                    # cached prefix outputs + the freshly computed tail
                    out = await asyncio.to_thread(
                        lambda out=out: np.concatenate(
                            [prefix_out.astype(out.dtype), out], axis=1
                        )
                    )
                if pc_keys is not None and len(pc_keys) > pc_hits:
                    from petals_tpu.server.prefix_cache import SEGMENT_TOKENS

                    # skip the device->host snapshot entirely when nothing
                    # would be stored (all keys already present — e.g. a
                    # racing session won — or one segment exceeds the budget)
                    import jax.numpy as jnp

                    backend0 = self.backend
                    seg_bytes = (
                        2 * (end - start) * SEGMENT_TOKENS
                        * backend0.num_kv_heads * backend0.head_dim
                        * jnp.dtype(backend0.cache_dtype).itemsize
                        # the stored "out" segment is np.asarray(out) — its
                        # ACTUAL host dtype, not compute_dtype: on bf16
                        # servers the wire/concat path yields float32, and
                        # estimating with bf16's itemsize undercounts 2x
                        # (approving snapshots put() then has to discard)
                        + SEGMENT_TOKENS * backend0.hidden_size
                        * np.asarray(out).dtype.itemsize
                    )
                    # mirrors the store path's tier eligibility: a re-store
                    # of fully-known keys is still worth it when it would
                    # grant HBM residency (device refs for a host-only hot
                    # entry, or fresh page pins after a pool reset)
                    store_backend = batcher.backend if lane is not None else self.backend
                    device_capable = (
                        self.prefix_cache.device_max_bytes > 0
                        and getattr(store_backend, "mesh", None) is None
                        and not getattr(store_backend, "is_lockstep", False)
                        and (lane is None or batcher.page_size is None)
                    )
                    store_pages_pool = (
                        batcher
                        if lane is not None and batcher.page_size is not None
                        else None
                    )
                    if self.prefix_cache.worth_storing(
                        pc_keys, pc_hits, seg_bytes,
                        device_capable=device_capable,
                        pages_pool=store_pages_pool,
                    ):
                        # store off the reply path; the loop awaits this
                        # before any LATER step of this session
                        pending_store = asyncio.create_task(
                            self._store_prefix_async(
                                pc_keys, pc_hits, len(pc_keys) * SEGMENT_TOKENS,
                                lane, handles, np.asarray(out), end - start,
                                batcher=batcher, tenant=peer_str,
                            )
                        )
                        pending_store.add_done_callback(
                            log_exception_callback(logger, "prefix store")
                        )
                position += seq
                gen_token_list = None
                gen_n = step.get("gen_tokens")
                if gen_n:
                    # clamp to a power of two <= 32: each distinct length is
                    # its own compiled program, and arbitrary client-chosen
                    # lengths would be a compile-cache DoS; clients loop on
                    # the returned count
                    gen_n = max(1, min(int(gen_n), 32))
                    gen_n = 1 << (gen_n.bit_length() - 1)
                    # on-device sampling params (None -> greedy); malformed
                    # dicts become protocol errors before touching the device
                    gen_sampling = validate_gen_sampling(step.get("gen_sampling"))
                    # device-side generation loop (backend.generate_tokens /
                    # batching.generate_lane): single-HOST sessions (plain or
                    # TP/SP mesh — GSPMD partitions the whole scan) on a
                    # full-span server holding the client leaves; clients
                    # gate on the server_gen / server_gen_sampling info
                    # flags, so a violation here is a protocol error, not a
                    # fallback path
                    if not (
                        self.server_gen_params is not None
                        # the SESSION must cover the whole model: a sub-span
                        # session would apply the LM head to mid-stack hidden
                        # states and feed embeddings into the middle of the
                        # stack — syntactically valid, semantically garbage
                        and start == 0
                        and end == self.backend.n_blocks
                        and not getattr(backend, "is_lockstep", False)
                        and batch_size == 1
                        and prompts is None
                        and hypo_ids is None
                    ):
                        raise ValueError(
                            "server-side generation is not available for this "
                            "session (requires a whole-model session on a "
                            "full-span single-host server with client "
                            "leaves loaded; check the server_gen info flag)"
                        )
                    # the SESSION's negotiated budget caps generation just
                    # like a regular step: the lane/cache buffer may be
                    # larger than what this session negotiated at open
                    if position + gen_n - 1 > max_length:
                        raise ValueError(
                            f"Generating {gen_n} tokens at position {position} "
                            f"exceeds max_length {max_length}"
                        )

                    gen_timing = None
                    if lane is not None:
                        # pooled session: the gen loop runs INSIDE the flush
                        # loop — each of the <=32 decode steps batches this
                        # lane with every other generating lane and ordinary
                        # decode traffic into one compiled program (no more
                        # exclusive-checkout monopoly)
                        gen_arr = await asyncio.wait_for(
                            batcher.generate_lane(
                                # slice BEFORE np.asarray: out may be a
                                # device array holding the whole prefill
                                lane, np.asarray(out[:, -1:]), position,
                                gen_n, sampling=gen_sampling,
                            ),
                            self.step_timeout,
                        )
                        gen_timing = batcher.pop_step_timing(lane)
                        # token replies carry no hidden state for the client
                        # to re-digest: drop the gen loop's stale fingerprint
                        # so it cannot ride a LATER step's meta
                        batcher.pop_step_fp(lane)
                        step_fp = None
                    else:
                        def run_gen(kv=kv, out=out, gen_n=gen_n,
                                    gen_sampling=gen_sampling):
                            with device_annotation("server_gen"):
                                tokens, new_kv = backend.generate_tokens(
                                    self.server_gen_params, np.asarray(out[:, -1:]),
                                    kv, position, gen_n,
                                    active_adapter=active_adapter,
                                    sampling=gen_sampling,
                                )
                            return np.asarray(tokens), new_kv

                        t_gen = time.perf_counter()
                        gen_arr, kv = await asyncio.wait_for(
                            self.queue.submit(
                                run_gen, priority=PRIORITY_INFERENCE, size=gen_n
                            ),
                            self.step_timeout,
                        )
                        fallback_compute_s += time.perf_counter() - t_gen
                        self.memory_cache.update_cache(handles[0], kv[0])
                        self.memory_cache.update_cache(handles[1], kv[1])
                    if gen_timing is not None:
                        # a content op preceded the gen loop on this lane:
                        # the two device phases sum into one step attribution
                        if step_timing is None:
                            step_timing = gen_timing
                        else:
                            merged = {
                                "queue_s": step_timing["queue_s"] + gen_timing["queue_s"],
                                "compute_s": step_timing["compute_s"] + gen_timing["compute_s"],
                                "variant": step_timing["variant"] + "+gen",
                            }
                            # speculative evidence survives the merge
                            for k in ("spec_proposed", "spec_accepted", "acceptance_rate"):
                                if k in gen_timing:
                                    merged[k] = gen_timing[k]
                            step_timing = merged
                    position += gen_n - 1  # the last token is never fed
                    gen_token_list = [int(t) for t in gen_arr[0]]
                if reg is not None:
                    reg["position"] = position
                if not ttft_observed:
                    # first content-bearing reply of the session: open ->
                    # first token out, queue wait and prefill included
                    ttft_observed = True
                    tm.TTFT.observe(time.perf_counter() - t_open)
                # per-hop span piggyback: a compact attribution dict rides
                # every content reply, keyed by the session's trace id on the
                # client side (telemetry/spans.py). Dict-protocol replies, so
                # old clients simply ignore the unknown key.
                if step_timing is not None:
                    meta_q = step_timing["queue_s"]
                    meta_c = step_timing["compute_s"]
                    step_variant = step_timing.get("variant", step_variant)
                else:
                    meta_q, meta_c = 0.0, fallback_compute_s
                step_meta = {
                    "queue_s": round(meta_q, 6),
                    "compute_s": round(meta_c, 6),
                    "variant": step_variant,
                }
                if step_timing is not None:
                    # speculative-decoding evidence for streams that ever
                    # speculated: lifetime draft counts + acceptance rate
                    for k in ("spec_proposed", "spec_accepted", "acceptance_rate"):
                        if k in step_timing:
                            step_meta[k] = step_timing[k]
                if step_fp is not None:
                    # fused activation fingerprint of the reply's last token
                    # row (ops/fingerprint.py): the client re-derives it from
                    # the hidden state it receives and cross-checks — unknown
                    # key, so old clients ignore it
                    step_meta["fp"] = step_fp
                if lane is not None:
                    step_meta.update(batcher.occupancy_hint())
                    # the tenant's own bill since the last reply (resource
                    # ledger delta: page-seconds, compute split, tokens, swap
                    # bytes) — InferenceSession.usage_report() sums these
                    usage = batcher.pop_usage_delta(lane)
                    if usage:
                        step_meta["usage"] = usage
                if gen_token_list is not None:
                    # the client computes everything it needs from the token
                    # ids; skipping the hidden reply saves the prefill-sized
                    # upload on the wire
                    step_meta["serialize_s"] = 0.0
                    step_meta["total_s"] = round(time.perf_counter() - t_step_recv, 6)
                    yield {
                        "tokens": gen_token_list, "position": position,
                        "step_meta": step_meta,
                    }
                    continue
                if chaos.ENABLED and chaos.fire(
                    chaos.SITE_INTEGRITY_CORRUPT,
                    detail=f"{self._peer_str}:{session_id or 'anon'}",
                ) == "corrupt":
                    # seeded activation corruption AT the reply boundary: the
                    # wire output now diverges from the fused fingerprint in
                    # its own step_meta — the exact plausible-but-wrong
                    # failure the client cross-check exists to catch
                    out = chaos.corrupt_array(
                        out, site_seed=self._corrupt_seed, position=position
                    )
                t_ser = time.perf_counter()
                wire_out = serialize_array(out, reply_comp)
                ser_s = time.perf_counter() - t_ser
                tm.REPLY_SERIALIZE.observe(ser_s)
                step_meta["serialize_s"] = round(ser_s, 6)
                if push_to is not None and prompts is None:
                    # can_push = no deep prompts (reference block_functions.py:233).
                    # Fire-and-forget: the client's relay of this output remains
                    # authoritative (dedup by step_id), so a slow/dead next peer
                    # must never delay our own reply.
                    wire_hypo = (step.get("tensors") or {}).get("hypo_ids")
                    task = asyncio.create_task(
                        self._push_outputs(push_to, wire_out, step_id, start_from, wire_hypo)
                    )
                    self._push_tasks.add(task)
                    task.add_done_callback(self._push_tasks.discard)
                    task.add_done_callback(
                        log_exception_callback(logger, "output push")
                    )
                t_built = time.perf_counter()
                step_meta["total_s"] = round(t_built - t_step_recv, 6)
                decode_reply = reply_build is not None and step_timing is not None and "replied" in step_timing
                if reply_build is not None:
                    reply_build.__exit__(None, None, None)
                    reply_build = None
                yield {
                    "tensors": {"hidden": wire_out}, "position": position,
                    "step_meta": step_meta,
                }
                if decode_reply:
                    # the generator runs on from its yield once the RPC server
                    # has sent the reply; what the sending took is on the
                    # stream's own object (another caller's iterator keeps none)
                    batcher.count_decode_reply(
                        t_resumed - step_timing["replied"], t_built - t_resumed,
                        getattr(requests, "sent_s", None) or 0.0,
                    )
            finally:
                if reply_build is not None:  # a raise between the step and its reply
                    reply_build.__exit__(None, None, None)
                if pending_store is not None and not pending_store.done():
                    import sys as _sys

                    if _sys.exc_info()[1] is not None:
                        # error/cancellation teardown: drop the store NOW —
                        # holding the lane 30s on an abrupt disconnect would
                        # stall new-session admission
                        pending_store.cancel()
                    else:
                        # graceful stream end: finish the store BEFORE the
                        # lane/buffers are released (a session that ends right
                        # after its prefill — every hop of a chain does — must
                        # still populate the cache); bounded, and a
                        # re-tenanted lane is never snapshotted
                        try:
                            await asyncio.wait_for(asyncio.shield(pending_store), 30.0)
                        except asyncio.CancelledError:
                            pending_store.cancel()
                            raise
                        except Exception as e:
                            # incl. TimeoutError and store-internal failures:
                            # storing is best-effort — an otherwise-successful
                            # stream must not error over a cache hiccup
                            logger.debug("Prefix store abandoned at stream end: %r", e)
                            pending_store.cancel()
                        except BaseException:
                            # GeneratorExit (transport aclose), KeyboardInterrupt:
                            # never leak the store task holding the lane
                            pending_store.cancel()
                            raise
                if isinstance(requests, StreamRequests):
                    requests.sink = None  # and with it the stream's hold on this frame
                await steps.cleanup()
                if session_id:
                    self._push_queues.pop(session_id, None)
                    self._session_registry.pop(session_id, None)
                # drop the ambient trace id (reset_trace_id tolerates the
                # generator resuming under a different Context at teardown)
                reset_trace_id(_trace_token)

    async def _push_outputs(self, push_to: dict, wire_out, step_id, start_from, wire_hypo=None) -> None:
        """Forward our outputs straight to the next server in the chain
        (reference handler.py:320-350); push failures are non-fatal — the
        client's copy is authoritative. A rollback marker on the original step
        propagates so speculative rewinds stay coherent whichever copy wins."""
        try:
            from petals_tpu.dht.routing import PeerAddr

            payload = {
                "session_id": push_to["session_id"],
                "step_id": step_id,
                "tensors": {"hidden": wire_out},
            }
            if wire_hypo is not None:  # beam reorder must survive the push path
                payload["tensors"]["hypo_ids"] = wire_hypo
            if start_from is not None:
                payload["start_from_position"] = int(start_from)
            addr = PeerAddr.from_string(push_to["addr"])
            client = await self._push_pool.get_addr(addr)
            await asyncio.wait_for(client.call("ptu.push", payload), 10.0)
        except Exception as e:
            logger.debug(f"Push to next server failed (client copy still flows): {e}")

    def _sub_backend(self, start: int, end: int) -> TransformerBackend:
        if start == 0 and end == self.backend.n_blocks:
            return self.backend
        # Partial chains get their own backend over a sliced param stack —
        # cached so each (start, end) compiles its programs exactly once.
        key = (start, end)
        if key not in self._sub_backends:
            sliced = self.backend._slice_params(start, end)
            sub = TransformerBackend(
                self.backend.family,
                self.backend.cfg,
                sliced,
                first_block=self.backend.first_block + start,
                n_blocks=end - start,
                memory_cache=self.memory_cache,
                compute_dtype=self.backend.compute_dtype,
                cache_dtype=self.backend.cache_dtype,
                max_chunk_size_bytes=self.backend.max_chunk_size_bytes,
                use_flash=self.backend.use_flash,
                mesh=self.backend.mesh,
            )
            import jax

            sub.adapters = {
                name: (jax.tree_util.tree_map(lambda x: x[start:end], stacked), scaling)
                for name, (stacked, scaling) in self.backend.adapters.items()
            }
            if getattr(self.backend, "is_lockstep", False):
                # multi-host serving: the sliced chain must broadcast its span
                # so workers execute the same sub-backend in lockstep
                sub = self.backend.sub_view(sub, start, end)
            self._sub_backends[key] = sub
        return self._sub_backends[key]

