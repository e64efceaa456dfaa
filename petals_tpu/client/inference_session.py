"""Client-side autoregressive inference over a chain of servers
(counterpart of reference src/petals/client/inference_session.py:26-414).

- ``_ServerInferenceSession`` drives one server's bidirectional inference
  stream: open with (uids, max_length), then step (hidden, prompts, hypo_ids,
  start_from_position). It records the ``history`` of inputs it has sent so a
  replacement server's KV cache can be rebuilt after a failure.
- ``InferenceSession`` chains per-span sessions across the whole model. On a
  step failure it bans the peer, rebuilds the chain suffix starting at the
  failed span's START block, and replays the recorded history through the new
  suffix so every replacement server re-prefills its KV cache — generation
  continues without the caller noticing (reference :284-391).
"""

from __future__ import annotations

import asyncio
import contextlib
import random
import time
import uuid
from typing import List, Optional, Sequence

import numpy as np

from petals_tpu import chaos
from petals_tpu.client.routing.sequence_manager import RemoteSequenceManager
from petals_tpu.data_structures import CHAIN_DELIMITER, RemoteSpanInfo
from petals_tpu.rpc.client import THREAD_FRAME_BYTES, RpcClient, StreamCall
from petals_tpu.rpc.serialization import CompressionType, deserialize_array, serialize_array
from petals_tpu.telemetry.spans import MAX_RETIRED_HOPS, ClientTrip, HopTrace, build_trace_report
from petals_tpu.utils.asyncio_utils import turn_clock_of
from petals_tpu.utils.logging import get_logger

logger = get_logger(__name__)

# Minimum server-reported lane-admission wait (seconds) before a session open
# files congestion blame on its own. Sub-second waits are normal scheduling
# jitter and stay visible only in the hop waterfall; multi-second waits mean
# the pool is genuinely oversubscribed and the NEXT route build should know.
OPEN_WAIT_BLAME_S = 0.5
# Floor below which the reported wait is not folded into the hop waterfall at
# all: an UNCONTENDED acquire still measures a few microseconds, and recording
# it would add a phantom zero-token step to every hop's trace.
OPEN_WAIT_FOLD_MIN_S = 0.05


class _ServerInferenceSession:
    def __init__(
        self,
        span: RemoteSpanInfo,
        uids: Sequence[str],
        stream: StreamCall,
        *,
        max_length: int,
        step_timeout: float,
    ):
        self.span = span
        self.uids = list(uids)
        self.stream = stream
        self.max_length = max_length
        self.step_timeout = step_timeout
        self.compression = CompressionType.NONE  # create() sets the negotiated codec
        self.position = 0
        # inputs sent so far, as (hidden, hypo_ids) steps — replay must repeat
        # beam-lane reorders exactly (failover during beam search)
        self.history: List[tuple] = []
        self.closed = False
        self.session_id: Optional[str] = None
        # set after chain repair: dict = retarget pushes, False = disable them
        self.pending_push_to = None
        # per-hop critical-path accumulator: every step folds its client wall
        # + the server's step_meta piggyback into this (telemetry/spans.py)
        self.hop = HopTrace(span.peer_id.to_string(), span.start, span.end)
        # trace id the server echoed in its session_open ack (may be
        # server-normalized/minted; InferenceSession adopts it)
        self.echoed_trace_id: Optional[str] = None
        # integrity cross-check (telemetry/integrity.py), attached by the
        # owning InferenceSession: every reply carrying a fused fingerprint
        # is verified against the hidden state actually received
        self.monitor = None
        # the last step's readings of time.perf_counter, for the owning
        # session's ClientTrip (telemetry/spans.py): stream.send returned
        # (K2), the reply's frame read whole (K3), stream.recv returned (K4)
        self.stations: Optional[tuple] = None
        # whether the last step_from_thread's frame was written to the socket by the caller's thread (else by the loop)
        self.wrote = False

    @classmethod
    async def create(
        cls,
        seq_manager: RemoteSequenceManager,
        span: RemoteSpanInfo,
        uids: Sequence[str],
        *,
        max_length: int,
        batch_size: int = 1,
        step_timeout: float = 5 * 60,
        session_id: Optional[str] = None,
        push_to: Optional[dict] = None,
        trace_id: Optional[str] = None,
    ) -> "_ServerInferenceSession":
        stub: RpcClient = await seq_manager.get_stub(span.peer_id)
        stream = await stub.open_stream("ptu.inference")
        compression = CompressionType(seq_manager.config.compression)
        import petals_tpu

        open_msg = {
            "uids": CHAIN_DELIMITER.join(uids),
            "max_length": max_length,
            "batch_size": batch_size,
            "active_adapter": seq_manager.config.active_adapter,
            # reply compression for all steps; "none" must OVERRIDE a lossy
            # server default, so it is always sent
            "compression": compression.value,
            # handshake version gate: the server rejects incompatible clients
            # with an actionable error instead of a wire mismatch mid-step
            "client_version": petals_tpu.__version__,
        }
        if session_id:
            open_msg["session_id"] = session_id
        if push_to:
            open_msg["push_to"] = push_to
        if trace_id:
            # request-scoped trace id minted by InferenceSession: every server
            # span of this session tags its telemetry (spans, journal events,
            # metrics) with it, so one client request reconstructs as a single
            # causal timeline across the swarm. Unknown to old servers, which
            # ignore unrecognized open-message keys.
            open_msg["trace_id"] = trace_id
        # optional scheduling-priority hint; absent -> the server's default
        # ("normal"), so old servers and default configs behave identically
        priority = getattr(seq_manager.config, "session_priority", None)
        if priority is not None:
            open_msg["priority"] = priority
        # bound head-of-line blocking in the server's lane queue: absent, the
        # server parks the open for its own default (30 s) before falling back
        # to a private cache — a client that would rather re-route or degrade
        # sooner declares its own budget
        alloc_timeout = getattr(seq_manager.config, "alloc_timeout", None)
        if alloc_timeout is not None:
            open_msg["alloc_timeout"] = float(alloc_timeout)
        t_open = time.perf_counter()
        await stream.send(open_msg)
        ack = await stream.recv(timeout=step_timeout)
        open_wall_s = time.perf_counter() - t_open
        assert ack.get("session_open"), f"Unexpected open reply: {ack}"
        self = cls(span, uids, stream, max_length=max_length, step_timeout=step_timeout)
        self.session_id = session_id
        self.compression = compression
        # the server echoes the trace id it actually registered (normalized,
        # or freshly minted when the client sent none): adopt the server's
        # view so client- and server-side telemetry key identically
        echoed = ack.get("trace_id")
        if isinstance(echoed, str) and echoed:
            self.echoed_trace_id = echoed
        # fold the server's lane-admission wait (open ack piggyback) into the
        # hop waterfall as queue time, and blame it IMMEDIATELY when it
        # dominates the open handshake: short sessions — a few steps, i.e.
        # most interactive traffic — never reach the periodic step-cadence
        # blame check in _maybe_blame_hop, so without this a backlogged
        # server keeps winning route builds and a freshly scaled-out replica
        # never receives the load it was spawned to absorb
        try:
            open_wait_s = float(ack.get("open_wait_s") or 0.0)
        except (TypeError, ValueError):
            open_wait_s = 0.0
        if open_wait_s >= OPEN_WAIT_FOLD_MIN_S:
            self.hop.record(
                open_wall_s, {"queue_s": open_wait_s, "total_s": open_wait_s}, tokens=0
            )
            share = self.hop.queue_share()
            if open_wait_s >= OPEN_WAIT_BLAME_S and share > 0.5:
                report = getattr(seq_manager, "report_congestion", None)
                if report is not None:
                    report(span.peer_id, share)
                # a backlogged open is also evidence the cached swarm view is
                # stale — kick a (rate-limited) directory refresh so capacity
                # announced since the last periodic update becomes routable
                # now, not up to update_period seconds later
                refresh = getattr(seq_manager, "request_refresh", None)
                if refresh is not None:
                    refresh()
        return self

    async def import_kv(self, k: np.ndarray, v: np.ndarray, position: int) -> None:
        """Seed this (fresh) session's server-side KV from an exported cache —
        must run before any step; the server validates shapes and position."""
        assert self.position == 0 and not self.history, "import_kv only on a fresh session"
        await self.stream.send({
            "kv_import": {"position": int(position)},
            "tensors": {"k": serialize_array(k), "v": serialize_array(v)},
        })
        reply = await self.stream.recv(timeout=self.step_timeout)
        if not reply.get("kv_import") or reply.get("position") != position:
            raise RuntimeError(f"kv_import rejected: {reply}")
        self.position = position

    async def adopt_kv(self, source_session_id: str, position: int) -> None:
        """Seed this (fresh) session from KV the SERVER already holds — a
        migrated-in entry pushed by a draining peer, or its own parked
        snapshot. Only ids cross the client link; the tensor bytes moved
        server-to-server, which is the point of p2p migration vs import_kv."""
        assert self.position == 0 and not self.history, "adopt_kv only on a fresh session"
        await self.stream.send({
            "kv_adopt": {"session_id": source_session_id, "position": int(position)},
        })
        reply = await self.stream.recv(timeout=self.step_timeout)
        if not reply.get("kv_adopt") or reply.get("position") != position:
            raise RuntimeError(f"kv_adopt rejected: {reply}")
        self.position = position

    def build_step(
        self,
        hidden: np.ndarray,
        *,
        prompts: Optional[np.ndarray] = None,
        hypo_ids: Optional[np.ndarray] = None,
        start_from_position: Optional[int] = None,
        step_id: Optional[str] = None,
    ) -> dict:
        """The first half of what a step means, whichever way it is exchanged:
        the request that rides the stream."""
        if start_from_position is not None:
            self._rollback_history(start_from_position)

        comp = self.compression
        msg = {"tensors": {"hidden": serialize_array(hidden, comp)}}
        if step_id is not None:
            msg["step_id"] = step_id
        if self.pending_push_to is not None:
            msg["push_to"] = self.pending_push_to if self.pending_push_to else None
            self.pending_push_to = None
        if prompts is not None:
            msg["tensors"]["prompts"] = serialize_array(prompts, comp)
        if hypo_ids is not None:
            msg["tensors"]["hypo_ids"] = serialize_array(np.asarray(hypo_ids, np.int64))
        if start_from_position is not None:
            msg["start_from_position"] = int(start_from_position)
        return msg

    def accept_reply(
        self, hidden: np.ndarray, hypo_ids: Optional[np.ndarray], reply: dict,
        t_rpc: float, sent_at: float, held_at: float,
    ) -> np.ndarray:
        """The second half: the reply into the hop's trace, the position, the
        integrity check and the history. ``t_rpc``, ``sent_at`` and ``held_at``
        are the stepper's readings before the send, after it (K2) and with the
        reply in hand (K4)."""
        read_at = self.stream.read_at or held_at
        # a thread that hands its frame over may lose the GIL before it reads K2, and the reply
        # can be read meanwhile: K2 is then K3, so that no stretch is negative and they still tile
        self.stations = (min(sent_at, read_at), read_at, held_at)
        self.hop.record(held_at - t_rpc, reply.get("step_meta"), tokens=int(hidden.shape[1]))
        out = deserialize_array(reply["tensors"]["hidden"])
        self.position = reply["position"]
        meta = reply.get("step_meta") or {}
        if self.monitor is not None and meta.get("fp") is not None:
            # cross-check the reply against the server's FUSED fingerprint:
            # a mismatch means the activation was corrupted after the
            # compiled step (wire, serialization, or a lying replica)
            self.monitor.verify_step(
                self.span.peer_id,
                meta["fp"],
                out,
                start=self.span.start,
                end=self.span.end,
                position=int(reply["position"]),
                lossy_wire=self.compression != CompressionType.NONE,
                quant=getattr(self.span.server_info, "quant_type", None) or "none",
            )
        self.history.append((np.asarray(hidden), None if hypo_ids is None else np.asarray(hypo_ids)))
        return out

    async def step(
        self,
        hidden: np.ndarray,
        *,
        prompts: Optional[np.ndarray] = None,
        hypo_ids: Optional[np.ndarray] = None,
        start_from_position: Optional[int] = None,
        step_id: Optional[str] = None,
    ) -> np.ndarray:
        """One step exchanged by a coroutine on the loop."""
        msg = self.build_step(
            hidden, prompts=prompts, hypo_ids=hypo_ids,
            start_from_position=start_from_position, step_id=step_id,
        )
        t_rpc = time.perf_counter()
        await self.stream.send(msg)
        sent_at = time.perf_counter()
        reply = await self.stream.recv(timeout=self.step_timeout)
        return self.accept_reply(hidden, hypo_ids, reply, t_rpc, sent_at, time.perf_counter())

    def step_from_thread(self, hidden: np.ndarray, step_id: Optional[str]) -> np.ndarray:
        """The same step exchanged by the caller's own thread, which is not the
        loop's: a plain step (no prompts, lane reorder or rollback rides it)
        whose frame the stream takes whole (``rpc/client.py``) and this thread
        writes to the socket itself where the connection can take it right now
        (``wrote``; else the loop writes it, behind what it has to write)."""
        msg = self.build_step(hidden, step_id=step_id)
        t_rpc = time.perf_counter()
        self.wrote = self.stream.send_from_thread(msg)
        sent_at = time.perf_counter()
        reply = self.stream.recv_in_thread(self.step_timeout)
        return self.accept_reply(hidden, None, reply, t_rpc, sent_at, time.perf_counter())

    async def step_generate(
        self, hidden: np.ndarray, n_tokens: int, embed_fn,
        *, start_from_position: Optional[int] = None, step_id: Optional[str] = None,
        sampling: Optional[dict] = None,
    ) -> np.ndarray:
        """Feed ``hidden`` and let the server generate ``n_tokens`` tokens
        device-side (full-span servers with the server_gen capability; see
        server/backend.py generate_tokens) — greedy, or sampled when a
        ``sampling`` dict (rpc/protocol.py gen_sampling schema) is given.
        Returns the token ids [batch, n_tokens]. ``embed_fn(tokens)``
        reproduces the embeds the server fed itself — recorded into the
        replay history so failover onto a server WITHOUT the capability
        still rebuilds the exact KV."""
        if start_from_position is not None:
            self._rollback_history(start_from_position)
        msg = {
            "tensors": {"hidden": serialize_array(hidden, self.compression)},
            "gen_tokens": int(n_tokens),
        }
        if sampling is not None:
            msg["gen_sampling"] = sampling
        if step_id is not None:
            msg["step_id"] = step_id
        if start_from_position is not None:
            msg["start_from_position"] = int(start_from_position)
        t_rpc = time.perf_counter()
        await self.stream.send(msg)
        reply = await self.stream.recv(timeout=self.step_timeout)
        tokens = np.asarray(reply["tokens"], np.int64)[None]  # [1, n]
        self.hop.record(
            time.perf_counter() - t_rpc, reply.get("step_meta"),
            tokens=int(tokens.shape[1]),
        )
        self.position = reply["position"]
        self.history.append((np.asarray(hidden), None))
        if tokens.shape[1] > 1:  # the returned count governs — servers clamp
            # the server fed tokens[:-1] (the last token is never fed)
            self.history.append((np.asarray(embed_fn(tokens[:, :-1])), None))
        return tokens

    def _rollback_history(self, new_position: int) -> None:
        self.position = new_position
        kept, total = [], 0
        for h, hypo in self.history:
            if total >= new_position:
                break
            take = min(h.shape[1], new_position - total)
            kept.append((h[:, :take] if take < h.shape[1] else h, hypo))
            total += take
        self.history = kept

    def history_steps(self) -> List[tuple]:
        """The (hidden, hypo_ids) steps fed so far, for failover replay."""
        return list(self.history)

    async def close(self) -> None:
        if not self.closed:
            self.closed = True
            try:
                await self.stream.end()
            except Exception:
                pass
            await self.stream.cancel()


# what a step's message holds beside ``hidden``'s bytes (a step id, a push
# target, msgpack's framing): room left under THREAD_FRAME_BYTES for it
_FRAME_SLACK_BYTES = 4096


class _Walk:
    """One step's way through the chain: where it stands and what it carries."""

    __slots__ = ("inputs", "step_id", "t_step0", "prompts", "hypo_ids", "block_idx", "attempt")

    def __init__(self, inputs, step_id, t_step0, prompts=None, hypo_ids=None):
        self.inputs, self.step_id, self.t_step0 = inputs, step_id, t_step0  # a hop's outputs have its inputs' shape
        self.prompts, self.hypo_ids = prompts, hypo_ids
        self.block_idx = self.attempt = 0

    @property
    def n_input_tokens(self) -> int:
        return self.inputs.shape[1]


class InferenceSession:
    """Whole-model autoregressive session with mid-generation failover."""

    def __init__(self, seq_manager: RemoteSequenceManager, max_length: int, batch_size: int = 1):
        self.seq_manager = seq_manager
        self.max_length = max_length
        self.batch_size = batch_size
        self._sessions: List[_ServerInferenceSession] = []
        self._position = 0
        self._closed = False
        self._max_retries = seq_manager.config.max_retries
        self._last_prompts: Optional[np.ndarray] = None
        self._last_route_check = time.monotonic()
        # the loop this session's coroutines run on, known from its first step
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        # prompt-prefix routing affinity: same prompt -> same replicas ->
        # server-side prefix-cache hits (sequence_manager._edge_cost)
        self._affinity_seed: Optional[int] = None
        # disaggregated serving: the phase this session routed as ("prefill"
        # when the first step carries >= config.prefill_tier_tokens tokens,
        # else "decode"; None until a route exists) plus the handoff tally
        # the bench gate asserts on (happy path: adopts only, zero fallbacks)
        self._phase: Optional[str] = None
        self._handoff_stats = {"adopted": 0, "fallback": 0, "replayed": 0}
        # one trace id for the whole session, minted at the client: every
        # server span (including repaired replacements) opens with it, so the
        # session's full life is one causal timeline in swarm telemetry
        from petals_tpu.telemetry import new_trace_id
        from petals_tpu.telemetry.flight import flight_from_env

        self.trace_id: str = new_trace_id()
        # critical-path profiler state: whole-session wall/steps/tokens plus
        # the hop traces of failed-over or migrated-away sessions (bounded),
        # so trace_report() accounts for time spent on dead servers too
        self._wall_s = 0.0
        self._steps = 0
        self._tokens = 0
        self._retired_hops: List[HopTrace] = []
        # the client's own stations of a step (telemetry/spans.py): sums for
        # trace_report()["client"], one row a step in the process's ring
        self.trip = ClientTrip(self.trace_id)
        # SLO flight recorder (None unless PETALS_TPU_SLO_*_MS is set; tests
        # and embedders may assign a FlightRecorder directly)
        self.flight = flight_from_env()
        # fingerprint cross-check: verifies every reply's fused digest and
        # keeps digest continuity across repairs/migrations; divergence is
        # journaled/flight-recorded and reported to routing as a hard penalty
        from petals_tpu.telemetry.integrity import IntegrityMonitor

        self.integrity = IntegrityMonitor(
            trace_id=self.trace_id,
            on_divergence=self._on_integrity_divergence,
            flight=self.flight,
        )

    @property
    def position(self) -> int:
        return self._position

    @position.setter
    def position(self, new_position: int) -> None:
        """Roll every server's cache back (speculative-decoding support;
        reference inference_session.py:242-247)."""
        assert new_position <= self._position, "can only roll back"
        self._position = new_position
        # servers are told via start_from_position on the next step (step()
        # notices session.position > self._position)

    @property
    def num_blocks(self) -> int:
        return len(self.seq_manager.block_uids)

    async def __aenter__(self):
        return self

    async def __aexit__(self, *exc):
        await self.close()

    async def step(
        self,
        hidden: np.ndarray,
        *,
        prompts: Optional[np.ndarray] = None,  # [num_blocks, batch, pre_seq, hidden_size]
        hypo_ids: Optional[np.ndarray] = None,
    ) -> np.ndarray:
        """Run ``hidden`` through all remote blocks, updating every server's cache."""
        self.trip.on_loop(time.perf_counter())
        self._admit(hidden)
        if prompts is not None:
            self._last_prompts = prompts
        t_step0 = time.perf_counter()  # route building counts toward TTFT
        await self._ensure_route(hidden)
        # the step id dedups client relay vs server push downstream
        return await self._walk(_Walk(np.asarray(hidden), uuid.uuid4().hex, t_step0, prompts, hypo_ids))

    def _admit(self, hidden: np.ndarray) -> None:
        assert not self._closed
        n_input_tokens = hidden.shape[1]
        if self._position + n_input_tokens > self.max_length:
            raise ValueError(
                f"Maximum length exceeded: prefix {self._position} + current {n_input_tokens}"
                f" exceeds pre-allocated maximum {self.max_length}"
            )

    async def _walk(self, walk: "_Walk", failed: Optional[tuple] = None) -> np.ndarray:
        """A step's hops from ``walk.block_idx`` on, each exchanged by a
        coroutine, with the one retry loop a step has: ``step`` enters at block
        0, ``step_from_thread`` where its own exchange raised, handing
        ``failed`` (the exception, the hop's session or None) so that the
        failure is counted, waited out and repaired here as any other."""
        if self._loop is None:  # where a caller's thread posts what is the loop's to touch
            self._loop = asyncio.get_running_loop()
        prompts, hypo_ids = walk.prompts, walk.hypo_ids
        if failed is not None:
            await self._hop_failed(walk, *failed)
        while walk.block_idx < self.num_blocks:
            session = None
            try:
                session = self._session_at(walk.block_idx)
                span = session.span
                server_prompts = prompts[span.start : span.end] if prompts is not None else None
                rollback = self._position if session.position > self._position else None

                outputs = await session.step(
                    walk.inputs,
                    prompts=server_prompts,
                    hypo_ids=hypo_ids,
                    start_from_position=rollback,
                    step_id=walk.step_id,
                )
                self._hop_done(walk, session, outputs)
            except Exception as e:
                await self._hop_failed(walk, e, session)

        self._step_done(walk)
        if self._steps == 1 and self._phase == "prefill":
            # prefill done, decode begins: hand the finished KV to a
            # decode-tier replica over the page-push path (step boundary —
            # the cut equals the position, so the adopt never replays)
            await self._maybe_phase_handoff()
        await self._maybe_check_route_upgrade()
        trip = self.trip
        if trip.steps == 0:  # where a reader of the ring finds this loop's turn clock
            trip.ring.loop_clock = turn_clock_of(self._loop)
        trip.finished(time.perf_counter(), walk.n_input_tokens)
        return walk.inputs

    def _session_at(self, block_idx: int) -> _ServerInferenceSession:
        server_idx = self._find_session_index(block_idx)
        if server_idx is None:
            raise RuntimeError(f"No active session covers block {block_idx}")
        return self._sessions[server_idx]

    def _hop_done(self, walk: "_Walk", session: _ServerInferenceSession, outputs: np.ndarray) -> None:
        """A hop answered: on to the next one with its outputs."""
        assert outputs.shape == walk.inputs.shape, f"{outputs.shape} != {walk.inputs.shape}"
        self.trip.hop(*session.stations)
        walk.inputs = outputs
        walk.block_idx = session.span.end
        peer = session.span.peer_id
        if self.seq_manager.has_failure_record(peer):
            self._tell_router(self.seq_manager.on_request_success, peer)
        self._maybe_blame_hop(session)

    async def _hop_failed(self, walk: "_Walk", error: Exception, session: Optional[_ServerInferenceSession]) -> None:
        """A hop raised: count it, ban the peer, wait, repair the chain at
        that hop; the walk resumes there with the inputs it had."""
        if self._closed:  # closed under a step that was out: no peer's fault, and no chain to repair
            raise error
        walk.attempt += 1
        peer = session.span.peer_id if session is not None else None
        self.seq_manager.on_request_failure(peer)
        if self._max_retries is not None and walk.attempt > self._max_retries:
            raise error
        delay = min(
            self.seq_manager.config.min_backoff * (2 ** (walk.attempt - 1)),
            self.seq_manager.config.max_backoff,
        )
        logger.warning(
            f"Caught exception from block {walk.block_idx} "
            f"(peer {peer.to_string()[:8] if peer else '?'}), retrying in {delay:.1f}s: {error}"
        )
        await asyncio.sleep(delay)
        walk.block_idx = await self._repair_chain(walk.block_idx)

    def _step_done(self, walk: "_Walk") -> None:
        self._position += walk.n_input_tokens
        self._account_step(time.perf_counter() - walk.t_step0, walk.n_input_tokens)

    # --------------------------------------------- a step on the caller's thread

    def can_step_from_thread(self, hidden: np.ndarray) -> bool:
        """Whether this step may take the direct way, read off the call and
        the session as they stand: the caller is on no loop; a route is open
        and this is not its first step; no hop has a rollback to be told; the
        frame fits ``THREAD_FRAME_BYTES`` (a codec only shrinks ``hidden``);
        fault injection is off (``rpc.stream_recv`` keeps its one home, the
        coroutine's ``recv``); no route-upgrade check is due (the phase
        hand-off is due after a first step only, which is a coroutine's).
        The facade asks only for a step that carries neither ``prompts`` nor
        ``hypo_ids``."""
        if self._loop is None or self._steps == 0 or self._closed or not self._sessions or chaos.ENABLED:
            return False
        if hidden.nbytes + _FRAME_SLACK_BYTES > THREAD_FRAME_BYTES or asyncio._get_running_loop() is not None:
            return False
        period = self.seq_manager.config.route_upgrade_period
        if period and time.monotonic() - self._last_route_check >= period:
            return False
        position = self._position
        return all(s.position <= position for s in self._sessions)

    def step_from_thread(self, hidden: np.ndarray, run) -> np.ndarray:
        """``step`` for a caller on a thread of its own, where
        ``can_step_from_thread`` allows it: the same hops in the same order,
        each exchanged by this thread (``_ServerInferenceSession.step_from_thread``),
        so the step crosses to the loop once a hop, on the reply's way in (and
        on the request's way out only where the connection cannot take the
        frame at once: ``ClientTrip.deferred``), and starts no
        coroutine. The first exchange that raises hands the rest of the step,
        from that hop on, to ``_walk`` on the loop through ``run`` (the
        runtime's): there is one retry loop and one repair.

        What this touches off the loop, and why it may: the hop's ``HopTrace``,
        ``history``, ``position`` and ``stations``, the integrity monitor, the
        trip, ``_account_step``'s sums and ``_position`` are this session's and
        one step at a time runs on a session (the loop touches them only inside
        a step, which this thread then waits for); the ring takes an atomic
        ``append``; the flight recorder and the journal have their locks; the
        stream's inbox is thread-safe; the socket's write side is shared with
        the loop under the connection's one short lock (``rpc/client.py
        _Outlet``): frames are whole and in the order they were handed over, a
        frame that cannot go out whole at once is the loop's to write, and the
        reader stays on the loop; the router's tables (``seq_manager``) are
        read here and written on the loop alone (``_tell_router``)."""
        trip = self.trip
        trip.on_loop(time.perf_counter())  # K1 is the build's beginning: nothing is crossed before it
        self._admit(hidden)
        # the step id as uuid4().hex is, without its system call: a place where this thread would give the GIL away mid-build
        walk = _Walk(hidden, "%032x" % random.getrandbits(128), time.perf_counter())
        wrote = 0
        while walk.block_idx < self.num_blocks:
            session = None
            try:
                session = self._session_at(walk.block_idx)
                outputs = session.step_from_thread(walk.inputs, walk.step_id)
                self._hop_done(walk, session, outputs)
                wrote += session.wrote
            except Exception as e:
                return run(self._walk(walk, failed=(e, session)))
        self._step_done(walk)
        trip.finished(time.perf_counter(), walk.n_input_tokens, direct=True, wrote=wrote)
        return walk.inputs

    def _tell_router(self, fn, *args) -> None:
        """The router's tables are the loop's: a caller's thread posts its
        word there and does not wait for it."""
        loop = self._loop
        if loop is None or asyncio._get_running_loop() is loop:
            fn(*args)
            return
        try:
            loop.call_soon_threadsafe(fn, *args)
        except RuntimeError:
            pass  # the loop is closed: there is no router left to tell

    # ------------------------------------------------- critical-path profiler

    def _account_step(self, wall_s: float, n_tokens: int) -> None:
        """Fold one whole-chain step into the session totals and check it
        against the flight recorder's SLOs (the first step is the TTFT)."""
        self._wall_s += wall_s
        self._steps += 1
        self._tokens += max(int(n_tokens), 0)
        if self.flight is None:
            return
        self.flight.observe(
            "ttft" if self._steps == 1 else "token",
            wall_s,
            trace_id=self.trace_id,
            # both resolved lazily, only when the observation breaches
            waterfall=self.trace_report,
            journal=self._victim_journal_fetcher(),
        )

    def _maybe_blame_hop(self, session: "_ServerInferenceSession") -> None:
        """Hop-level routing blame: a server whose queue-wait dominates its
        own wall gets a soft (decaying) routing penalty, so the next route
        build steers load away without the hard hammer of a ban."""
        hop = session.hop
        if not hop.meta_steps or hop.steps % 16 != 0:
            return
        share = hop.queue_share()
        if share <= 0.5:
            return
        report = getattr(self.seq_manager, "report_congestion", None)
        if report is not None:
            self._tell_router(report, session.span.peer_id, share)

    def _on_integrity_divergence(self, peer_id) -> None:
        """A hop's reply diverged from its fused fingerprint: hand routing
        the hard (decaying) integrity penalty so the next route build — and
        any repair this session performs — steers off the replica."""
        report = getattr(self.seq_manager, "report_integrity", None)
        if report is not None:
            self._tell_router(report, peer_id)

    def trace_report(self) -> dict:
        """The session's per-hop latency waterfall so far: wall-clock
        attributed to network / queue / compute / serialize / other, per hop
        and in total, with the dominating (hop, component) critical path."""
        hops = list(self._retired_hops) + [
            s.hop for s in self._sessions if not s.closed
        ]
        return build_trace_report(
            self.trace_id,
            [h for h in hops if h.steps > 0],
            wall_s=self._wall_s,
            steps=self._steps,
            tokens=self._tokens,
            retired_hops=len(self._retired_hops),
            client=self.trip.report(),
        )

    def usage_report(self) -> dict:
        """The session's resource bill so far, as metered by the servers'
        per-tenant ledgers: each hop's ``step_meta["usage"]`` deltas
        (page-seconds, compute-seconds, prefill/decode tokens, swap and
        migration bytes) summed per peer and in total. Covers retired hops,
        so a bill after a repair still includes the dead server's charges."""
        hops = list(self._retired_hops) + [
            s.hop for s in self._sessions if not s.closed
        ]
        per_peer: dict = {}
        total: dict = {}
        for hop in hops:
            if not hop.usage:
                continue
            peer = per_peer.setdefault(str(hop.peer), {})
            for field, amount in hop.usage.items():
                peer[field] = round(peer.get(field, 0.0) + amount, 6)
                total[field] = round(total.get(field, 0.0) + amount, 6)
        # speculative efficiency re-derives from the summed counters (rates
        # must not be summed across steps or peers)
        from petals_tpu.telemetry.ledger import derive_efficiency

        for usage in (*per_peer.values(), total):
            if usage.get("spec_proposed"):
                usage.update(derive_efficiency(usage))
        return {
            "trace_id": self.trace_id,
            "tokens": self._tokens,
            # of the session's steps, those the caller's thread exchanged itself (step_from_thread)
            "direct_steps": self.trip.direct,
            # of those steps' frames (one a hop), those that thread wrote to the socket itself, and those it left to the loop
            "direct_frames_wrote": self.trip.wrote,
            "direct_frames_deferred": self.trip.deferred,
            "total": total,
            "peers": per_peer,
        }

    def _retire_hops(self, sessions) -> None:
        """Keep closing sessions' hop traces (bounded) so reports after a
        repair/migration still account for time spent on the old servers."""
        for s in sessions:
            if s.hop.steps > 0:
                self._retired_hops.append(s.hop)
        if len(self._retired_hops) > MAX_RETIRED_HOPS:
            del self._retired_hops[: len(self._retired_hops) - MAX_RETIRED_HOPS]

    def _victim_journal_fetcher(self):
        """Zero-arg callable for the flight recorder: at breach time, pick
        the critical-path hop as the victim and fetch its server's journal
        excerpt for this trace from the announced /metrics endpoint."""

        def fetch():
            from petals_tpu.telemetry.flight import http_journal_fetcher

            crit = self.trace_report().get("critical_path")
            peer_str = crit["peer"] if crit else None
            victim = next(
                (
                    s for s in self._sessions
                    if not s.closed and s.hop.peer == peer_str
                ),
                None,
            )
            if victim is None:
                return {"error": "victim hop has no live session", "peer": peer_str}
            port = getattr(victim.span.server_info, "metrics_port", None)
            if not port:
                return {"error": "victim server announces no metrics_port", "peer": peer_str}
            addr = self.seq_manager.addr_of(victim.span.peer_id)
            host = addr.host if addr is not None else "127.0.0.1"
            url = f"http://{host}:{port}"
            events = http_journal_fetcher(url)(self.trace_id)
            return {"peer": peer_str, "url": url, "events": events}

        return fetch

    async def _maybe_check_route_upgrade(self) -> None:
        """Periodic better-chain check, shared by the per-token and
        server-side-generation paths (a session served entirely by gen RPCs
        must still migrate onto a faster server that joins mid-stream)."""
        period = self.seq_manager.config.route_upgrade_period
        if period and time.monotonic() - self._last_route_check >= period:
            self._last_route_check = time.monotonic()
            try:
                await self._maybe_upgrade_route()
            except Exception as e:
                logger.warning(f"Route upgrade check failed (continuing as-is): {e}")

    async def _ensure_route(self, hidden: np.ndarray) -> None:
        if self._sessions:
            return
        from petals_tpu.server.prefix_cache import SEGMENT_TOKENS

        if (
            self._affinity_seed is None
            and self._position == 0
            and hidden.shape[1] >= SEGMENT_TOKENS
        ):
            # hash the first prefill segment (the unit the server-side
            # prefix cache stores) so identical prompts route identically
            import hashlib

            seg = np.ascontiguousarray(np.asarray(hidden)[:, :SEGMENT_TOKENS])
            self._affinity_seed = int.from_bytes(
                hashlib.blake2b(seg.tobytes(), digest_size=8).digest(), "big"
            )
        # opening the first chain must be as churn-tolerant as stepping on an
        # established one: a refused/dropped session open bans the hop (see
        # _enter_server_sessions) and we re-route with the same backoff
        # discipline as step()'s retry loop
        # phase-tier routing: a heavy first step is a prefill — prefer
        # prefill-tier replicas; light first steps route decode-ward. In a
        # swarm with no tiered servers the phase kwarg scores nothing.
        if self._phase is None:
            heavy = hidden.shape[1] >= self.seq_manager.config.prefill_tier_tokens
            self._phase = "prefill" if heavy else "decode"
        attempt = 0
        while True:
            chain = await self.seq_manager.make_sequence(
                0, self.num_blocks, mode="min_latency",
                cache_tokens_needed=self.batch_size * self.max_length,
                affinity_seed=self._affinity_seed,
                phase=self._phase,
            )
            try:
                self._sessions = await self._enter_server_sessions(chain)
                return
            except Exception as e:
                attempt += 1
                if self._max_retries is not None and attempt > self._max_retries:
                    raise
                delay = min(
                    self.seq_manager.config.min_backoff * (2 ** (attempt - 1)),
                    self.seq_manager.config.max_backoff,
                )
                logger.warning(
                    f"Failed to open sessions on the chosen chain, "
                    f"retrying in {delay:.1f}s: {e}"
                )
                await asyncio.sleep(delay)

    async def _maybe_phase_handoff(self) -> None:
        """Disaggregated prefill->decode handoff: the session just finished
        its prefill on (at least one) prefill-tier replica — re-route the
        decode phase onto decode-tier replicas and move the finished KV
        server-to-server over the page-push path (``ptu.session_handoff`` on
        the source, ``kv_adopt`` at the destination). The cut lands exactly
        on the step boundary, so the adopt never replays and zero KV bytes
        cross the client link. Any failure degrades to colocated decode on
        the prefill replica — the current chain keeps serving — with the
        fallback journaled (kind ``handoff_fallback``)."""
        cfg = self.seq_manager.config
        self._phase = "decode"  # subsequent routing (repairs) scores decode-ward
        if not getattr(cfg, "disagg_handoff", True) or self._position == 0:
            return
        current = [s for s in self._sessions if not s.closed]
        if not current or not any(
            getattr(s.span.server_info, "phase_tier", None) == "prefill"
            for s in current
        ):
            return  # nothing prefill-tiered to hand off from
        from petals_tpu.telemetry import get_journal

        def fallback(reason: str) -> None:
            self._handoff_stats["fallback"] += 1
            get_journal().event(
                "handoff_fallback", trace_id=self.trace_id, reason=reason,
            )
            logger.info(f"Phase handoff skipped, decoding colocated: {reason}")

        try:
            candidate = await self.seq_manager.make_sequence(
                0, self.num_blocks, mode="min_latency",
                cache_tokens_needed=self.batch_size * self.max_length,
                affinity_seed=self._affinity_seed, phase="decode",
            )
        except Exception as e:
            fallback(f"decode routing failed: {e!r}")
            return
        # the handoff moves whole spans: the decode chain must cut at the
        # same block boundaries as the prefill chain (otherwise the KV on
        # the source does not map 1:1 onto a destination session)
        if [(c.start, c.end) for c in candidate] != [
            (s.span.start, s.span.end) for s in current
        ]:
            fallback("decode chain spans misaligned with prefill chain")
            return
        moves = [
            (old, span)
            for old, span in zip(current, candidate)
            if span.peer_id != old.span.peer_id
        ]
        if not moves:
            fallback("no better decode-tier replica than the prefill chain")
            return
        if not all(
            getattr(span.server_info, "phase_tier", None) == "decode"
            for _old, span in moves
        ):
            # moving KV to another generalist/prefill replica buys nothing
            fallback("best decode chain is not decode-tiered")
            return
        replaced: List[_ServerInferenceSession] = []
        created: List[_ServerInferenceSession] = []
        try:
            for old, span in moves:
                addr = self.seq_manager.addr_of(span.peer_id)
                if addr is None:
                    raise RuntimeError(
                        f"no address for decode replica {span.peer_id.to_string()[:8]}"
                    )
                # 1) source pushes the parked-at-step-boundary KV to the
                #    decode replica (server-to-server, billed as migration
                #    bytes, chaos site handoff.push)
                stub = await self.seq_manager.get_stub(old.span.peer_id)
                reply = await asyncio.wait_for(
                    stub.call(
                        "ptu.session_handoff",
                        {
                            "session_id": old.session_id,
                            "peer_id": span.peer_id.to_string(),
                            "addr": addr.to_string(),
                            "deadline_s": cfg.handoff_timeout,
                        },
                    ),
                    timeout=cfg.handoff_timeout + 5.0,
                )
                if not reply.get("ok"):
                    raise RuntimeError(f"source refused handoff: {reply}")
                # 2) fresh session on the decode replica adopts the pushed
                #    KV in place (kv_adopt first step, zero client-link KV)
                uids = self.seq_manager.block_uids[span.start : span.end]
                session = await _ServerInferenceSession.create(
                    self.seq_manager, span, uids,
                    max_length=self.max_length, batch_size=self.batch_size,
                    session_id=uuid.uuid4().hex, trace_id=self.trace_id,
                )
                session.monitor = self.integrity
                created.append(session)
                export_pos = int(reply["position"])
                if export_pos < self._position:
                    # the cut missed the step boundary; the adopt will replay
                    self._handoff_stats["replayed"] += 1
                if not await self._seed_by_adopt(
                    session, old.session_id, export_pos, old.history_steps()
                ):
                    raise RuntimeError("pushed KV too stale to adopt")
                replaced.append(old)
        except Exception as e:
            for session in created:
                try:
                    await session.close()
                except Exception:
                    pass  # best-effort teardown of half-opened handoff sessions; the prefill chain is still live
            fallback(repr(e))
            return
        # all moves landed: splice the decode replicas in, retire the
        # prefill hops, re-link the server->server push chain
        by_old = dict(zip(replaced, created))
        self._sessions = sorted(
            [by_old.get(s, s) for s in current], key=lambda s: s.span.start
        )
        self._retire_hops(replaced)
        for old in replaced:
            try:
                await old.close()
            except Exception:
                pass  # the source may already be tearing the lane down post-handoff
        self._wire_push_chain(self._sessions)
        self._handoff_stats["adopted"] += len(replaced)
        get_journal().event(
            "handoff_complete", trace_id=self.trace_id,
            moved=len(replaced), position=self._position,
        )

    def _spans_support_server_gen(self, spans, sampling: bool = False) -> bool:
        """One span covering every block, announcing the server_gen (or, for
        ``sampling``, server_gen_sampling) capability — the shape the
        device-side generation loop needs."""
        if len(spans) != 1:
            return False
        span = spans[0]
        flag = "server_gen_sampling" if sampling else "server_gen"
        return (
            span.start == 0
            and span.end == self.num_blocks
            and bool(getattr(span.server_info, flag, False))
        )

    def server_gen_available(self, sampling: bool = False) -> bool:
        """Whether the CURRENT route supports the device-side generation
        loop. Only meaningful after a route exists."""
        if len(self._sessions) != 1 or self._sessions[0].closed:
            return False
        return self._spans_support_server_gen(
            [s.span for s in self._sessions], sampling=sampling
        )

    async def generate_remote(
        self, hidden: np.ndarray, n_tokens: int, embed_fn,
        sampling: Optional[dict] = None,
    ) -> Optional[np.ndarray]:
        """Feed ``hidden`` and have the full-span server generate ``n_tokens``
        tokens device-side — greedy, or via the server's on-device sampling
        pipeline when a ``sampling`` dict (rpc/protocol.py gen_sampling
        schema) is given. Returns token ids [batch, n_tokens], or None when
        the current route cannot do it (caller falls back to the per-token
        loop). On a mid-generate failure the server sessions are torn down —
        the server's cache may have advanced past the client's view, and the
        standard rebuild-and-replay failover (which the recorded embed
        history feeds) is the one guaranteed-consistent recovery — and None
        is returned so the caller continues client-side."""
        assert not self._closed
        self.trip.interrupt()  # no station follows a server-side generation
        n_input = hidden.shape[1]
        if self._position + n_input + n_tokens - 1 > self.max_length:
            return None
        t_step0 = time.perf_counter()
        await self._ensure_route(hidden)
        if not self.server_gen_available(sampling=sampling is not None):
            return None
        session = self._sessions[0]
        rollback = self._position if session.position > self._position else None
        try:
            tokens = await session.step_generate(
                np.asarray(hidden), n_tokens, embed_fn,
                start_from_position=rollback, step_id=uuid.uuid4().hex,
                sampling=sampling,
            )
        except Exception as e:
            logger.warning(
                f"Server-side generation failed (falling back to the "
                f"per-token path): {e}"
            )
            self.seq_manager.on_request_failure(session.span.peer_id)
            # the server's cache may have advanced past the client's view:
            # the standard repair (KV export or history replay onto a fresh
            # chain) is the one guaranteed-consistent recovery — history was
            # only appended on successful replies, so it matches _position
            try:
                await self._repair_chain(0)
            except Exception as repair_err:
                # closing the sessions here would discard the only copy of
                # the replay history while _position > 0 — a later step on a
                # fresh chain would then run against EMPTY server caches and
                # silently generate garbage. Fail loudly instead.
                raise RuntimeError(
                    "server-side generation failed and the chain could not "
                    "be repaired; the session cannot continue consistently"
                ) from repair_err
            return None
        self.seq_manager.on_request_success(session.span.peer_id)
        self._maybe_blame_hop(session)
        # advance by what the server ACTUALLY generated — it clamps chunk
        # lengths to bound its compile cache, and fed got-1 tokens
        got = tokens.shape[1]
        self._position += n_input + got - 1
        self._account_step(time.perf_counter() - t_step0, n_input + got - 1)
        await self._maybe_check_route_upgrade()
        return tokens

    def _find_session_index(self, block_idx: int) -> Optional[int]:
        for i, session in enumerate(self._sessions):
            if session.span.start == block_idx and not session.closed:
                return i
        return None

    async def _enter_server_sessions(
        self, chain: List[RemoteSpanInfo], wire_push: bool = True
    ) -> List[_ServerInferenceSession]:
        """Open one session per span; with use_server_to_server, each server is
        told where to push its outputs (the next span's session) so downstream
        compute starts before the client relays — reference
        _collect_next_servers, inference_session.py:174-182. Repair passes
        ``wire_push=False`` so history replay / KV import into the fresh
        sessions never leaks pushed steps into the surviving downstream chain
        (pushes are wired afterwards via ``pending_push_to``)."""
        use_push = wire_push and self.seq_manager.config.use_server_to_server and len(chain) > 1
        session_ids = [uuid.uuid4().hex for _ in chain]
        sessions = []
        try:
            for i, span in enumerate(chain):
                uids = self.seq_manager.block_uids[span.start : span.end]
                push_to = None
                if use_push and i + 1 < len(chain):
                    next_addr = self.seq_manager.addr_of(chain[i + 1].peer_id)
                    if next_addr is not None:
                        push_to = {"addr": next_addr.to_string(), "session_id": session_ids[i + 1]}
                try:
                    session = await _ServerInferenceSession.create(
                        self.seq_manager,
                        span,
                        uids,
                        max_length=self.max_length,
                        batch_size=self.batch_size,
                        session_id=session_ids[i],
                        push_to=push_to,
                        trace_id=self.trace_id,
                    )
                except Exception:
                    # attribute the open failure to the hop that refused it so
                    # routing bans/penalizes that peer on the retry
                    self.seq_manager.on_request_failure(span.peer_id)
                    raise
                session.monitor = self.integrity
                # adopt the server-echoed trace id (normalized or server-
                # minted) from the FIRST hop, so the spans the rest of the
                # chain opens with — and all client telemetry — key on the
                # id the servers actually registered
                if session.echoed_trace_id and session.echoed_trace_id != self.trace_id:
                    logger.debug(
                        f"Adopting server-echoed trace id {session.echoed_trace_id} "
                        f"(was {self.trace_id})"
                    )
                    self.trace_id = session.echoed_trace_id
                    self.integrity.trace_id = self.trace_id
                sessions.append(session)
            return sessions
        except Exception:
            for session in sessions:
                await session.close()
            raise

    async def _repair_chain(self, failed_block: int) -> int:
        """Repair ONLY the failed span's range [resume, dead_end), keeping the
        healthy downstream sessions — and their KV caches — alive (reference
        _update_sequence repairs the same narrow range, inference_session.py
        :364-391). The replacement is seeded by KV migration when the failed
        server is still reachable (a draining/rebalancing peer serving
        ``ptu.session_export`` — beyond reference), falling back to replaying
        the recorded input history. A drain-to-migrate server instead answers
        with a redirect to the replica now holding the KV: routing is biased
        there (``prefer_peers``) and the replacement seeds by server-side
        ``kv_adopt`` — no KV bytes on the client link at all. Returns the
        block index to resume from."""
        dead: Optional[_ServerInferenceSession] = None
        for session in self._sessions:
            if session.span.start <= failed_block < session.span.end:
                dead = session
        if dead is not None:
            resume, dead_end = dead.span.start, dead.span.end
            replay_steps = dead.history_steps()
        else:  # inconsistent chain (shouldn't happen): rebuild the whole suffix
            resume, dead_end = failed_block, self.num_blocks
            replay_steps = []

        keep_up = [s for s in self._sessions if s.span.end <= resume and not s.closed]
        keep_down = [
            s for s in self._sessions if s.span.start >= dead_end and not s.closed and s is not dead
        ]
        drop = [s for s in self._sessions if s not in keep_up and s not in keep_down]

        # try to export the hole's KV from the dying server BEFORE closing
        # anything (a drained server serves exports after its streams died).
        # A drain-to-migrate server answers with a REDIRECT instead: its KV
        # already lives on a replica, and the cheapest repair is to land the
        # new chain there and adopt it server-side (zero client-link bytes).
        exported = None
        redirect = None
        if dead is not None and dead.session_id and self._position > 0:
            got = await self._try_export(
                dead.span.peer_id, dead.session_id, resume, dead_end
            )
            if isinstance(got, dict):
                redirect = got["migrated_to"]
            else:
                exported = got

        self._retire_hops(drop)
        for session in drop:
            await session.close()

        prefer_peers = None
        if redirect is not None and redirect.get("peer_id"):
            try:
                from petals_tpu.data_structures import PeerID

                prefer_peers = (PeerID.from_string(redirect["peer_id"]),)
            except (ValueError, TypeError):
                prefer_peers = None

        # Build-and-seed is itself a chain of RPCs, each as exposed to the
        # fault that triggered the repair as the step that failed: a transient
        # drop mid-repair must NOT abandon the session. Retry the whole
        # attempt with the step loop's backoff discipline — `replay_steps`,
        # `exported`, and `redirect` were captured ONCE above, so every
        # attempt reseeds from the full original history; a half-replayed
        # replacement session is simply closed and rebuilt.
        attempt = 0
        while True:
            new_sessions = []
            try:
                await self.seq_manager.update()
                new_chain = await self.seq_manager.make_sequence(
                    resume, dead_end, mode="min_latency",
                    cache_tokens_needed=self.batch_size * self.max_length,
                    affinity_seed=self._affinity_seed,
                    prefer_peers=prefer_peers,
                )
                new_sessions = await self._enter_server_sessions(new_chain, wire_push=False)
                self._sessions = sorted(
                    keep_up + new_sessions + keep_down, key=lambda s: s.span.start
                )

                # Seed the replacement (single-span holes only — a split hole
                # would leave later spans without input history for future
                # failovers):
                # 1. server-side adopt when the chain landed on the migrated
                #    KV's new home (the p2p path: bytes already moved
                #    server-to-server);
                # 2. KV import over the client link (export in hand, or
                #    fetched from the redirect target when routing went
                #    elsewhere);
                # 3. history replay.
                seeded = False
                if (
                    redirect is not None
                    and prefer_peers
                    and len(new_sessions) == 1
                    and new_sessions[0].span.peer_id == prefer_peers[0]
                    and dead is not None
                ):
                    try:
                        seeded = await self._seed_by_adopt(
                            new_sessions[0], dead.session_id,
                            int(redirect["position"]), replay_steps,
                        )
                    except Exception as e:
                        logger.warning(f"KV adopt failed, falling back: {e}")
                        self._journal_export_fallback(str(redirect.get("peer_id")), repr(e))
                        # the session's stream state is unknown after a failed adopt
                        await new_sessions[0].close()
                        new_sessions = await self._enter_server_sessions(new_chain, wire_push=False)
                        self._sessions = sorted(
                            keep_up + new_sessions + keep_down, key=lambda s: s.span.start
                        )
                if not seeded and redirect is not None and exported is None and dead is not None:
                    exported = await self._fetch_migrated(
                        redirect, dead.session_id, resume, dead_end
                    )
                if not seeded and exported is not None and len(new_sessions) == 1:
                    try:
                        seeded = await self._seed_by_import(new_sessions[0], exported, replay_steps)
                    except Exception as e:
                        logger.warning(f"KV import failed, replaying history instead: {e}")
                        # the session's stream state is unknown after a failed import
                        await new_sessions[0].close()
                        new_sessions = await self._enter_server_sessions(new_chain, wire_push=False)
                        self._sessions = sorted(
                            keep_up + new_sessions + keep_down, key=lambda s: s.span.start
                        )
                if not seeded and replay_steps:
                    # re-prefill the hole, repeating each recorded step — including its
                    # beam-lane reorder (hypo_ids) — in original order
                    for hidden_step, hypo_step in replay_steps:
                        chunk = hidden_step
                        step_id = uuid.uuid4().hex
                        for session in new_sessions:
                            chunk = await self._replay_step(session, chunk, hypo_step, step_id)
                break
            except Exception as e:
                attempt += 1
                for session in new_sessions:
                    failed_peer = session.span.peer_id
                    try:
                        await session.close()
                    except Exception:
                        pass
                    self.seq_manager.on_request_failure(failed_peer)
                self._sessions = sorted(keep_up + keep_down, key=lambda s: s.span.start)
                if self._max_retries is not None and attempt > self._max_retries:
                    raise
                delay = min(
                    self.seq_manager.config.min_backoff * (2 ** (attempt - 1)),
                    self.seq_manager.config.max_backoff,
                )
                logger.warning(
                    f"Chain repair for blocks [{resume}, {dead_end}) failed "
                    f"(attempt {attempt}), retrying in {delay:.1f}s: {e}"
                )
                await asyncio.sleep(delay)

        self._wire_repair_pushes(keep_up, new_sessions, keep_down, dead_end)
        return resume

    async def _replay_step(self, session, chunk, hypo_step, step_id):
        span = session.span
        server_prompts = (
            self._last_prompts[span.start : span.end] if self._last_prompts is not None else None
        )
        return await session.step(
            chunk, prompts=server_prompts, hypo_ids=hypo_step, step_id=step_id
        )

    def _export_compression(self) -> str:
        # Ride the session's negotiated wire codec, except qint8: blockwise
        # quantization of KV would degrade every subsequent token, while the
        # replay fallback is exact — bfloat16 is lossless for bf16 caches and
        # half the bytes of an f32 one.
        comp = self.seq_manager.config.compression
        if comp == CompressionType.QINT8.value:
            comp = CompressionType.BFLOAT16.value
        return comp

    def _journal_export_fallback(self, peer: str, reason: str) -> None:
        """The repair is about to cost a replay (or a second fetch) instead of
        a KV transfer — journal why, so churn postmortems can separate dead
        exporters from deadline misses from budget refusals."""
        from petals_tpu.telemetry import get_journal

        get_journal().event(
            "export_fallback", trace_id=self.trace_id, peer=peer, reason=reason,
        )

    async def _try_export(self, peer_id, session_id: str, start: int, end: int):
        """Fetch the failed span's KV from its (possibly draining) server.
        Returns ``(k, v, position)``, a ``{"migrated_to": ...}`` redirect dict
        when the server already pushed this session's KV to a peer
        (drain-to-migrate), or None — the caller falls back to replay. The
        transfer deadline is ``ClientConfig.kv_export_timeout``; long-context
        caches are 100s of MB, so the default is generous."""
        try:
            stub = await asyncio.wait_for(self.seq_manager.get_stub(peer_id), timeout=5)
            # quick liveness probe first: this peer may be the one that just
            # failed, and a zombie must cost seconds — not the generous
            # transfer budget below — before the replay fallback kicks in
            await asyncio.wait_for(stub.call("ptu.info", {}), timeout=3)
            reply = await asyncio.wait_for(
                stub.call(
                    "ptu.session_export",
                    {
                        "session_id": session_id, "start": start, "end": end,
                        "compression": self._export_compression(),
                    },
                ),
                timeout=self.seq_manager.config.kv_export_timeout,
            )
            fwd = reply.get("migrated_to")
            if isinstance(fwd, dict) and fwd.get("addr"):
                logger.info(
                    f"Session KV migrated away from {peer_id.to_string()[:8]} "
                    f"to {str(fwd.get('peer_id'))[:8]}: retargeting"
                )
                return {"migrated_to": fwd}
            if int(reply.get("batch_size", -1)) != self.batch_size:
                return None
            k = deserialize_array(reply["tensors"]["k"])
            v = deserialize_array(reply["tensors"]["v"])
            return k, v, int(reply["position"])
        except Exception as e:
            logger.info(f"KV export unavailable from {peer_id.to_string()[:8]}: {e}")
            self._journal_export_fallback(peer_id.to_string(), repr(e))
            return None

    async def _fetch_migrated(self, fwd: dict, session_id: str, start: int, end: int):
        """The dead server pushed this session's KV to a peer, but the new
        chain did not land there (or the adopt failed): pull the migrated
        copy from its new home over the client link instead."""
        from petals_tpu.dht.routing import PeerAddr

        try:
            stub = await asyncio.wait_for(
                self.seq_manager.pool.get_addr(PeerAddr.from_string(fwd["addr"])),
                timeout=5,
            )
            reply = await asyncio.wait_for(
                stub.call(
                    "ptu.session_export",
                    {
                        "session_id": session_id, "start": start, "end": end,
                        "compression": self._export_compression(),
                    },
                ),
                timeout=self.seq_manager.config.kv_export_timeout,
            )
            if "migrated_to" in reply:
                return None  # no redirect chains: one forwarding hop only
            if int(reply.get("batch_size", -1)) != self.batch_size:
                return None
            k = deserialize_array(reply["tensors"]["k"])
            v = deserialize_array(reply["tensors"]["v"])
            return k, v, int(reply["position"])
        except Exception as e:
            logger.info(f"Migrated KV unavailable from {fwd.get('addr')}: {e}")
            self._journal_export_fallback(str(fwd.get("peer_id")), repr(e))
            return None

    async def _seed_by_import(self, session, exported, replay_steps) -> bool:
        """Import exported KV up to a history step boundary, then replay any
        remaining recorded steps (a parked export can be a little stale)."""
        k, v, export_pos = exported
        if export_pos > self._position:
            # the server is AHEAD of the client: it processed a step whose
            # reply was lost. If that step carried a hypo_ids reorder, the
            # WHOLE exported cache is lane-permuted while the client's history
            # (and the step it will re-send) assume pre-reorder lanes —
            # importing would double-apply the permutation. Replay is exact.
            return False
        cap = min(export_pos, self._position)
        # largest prefix of history steps whose total length fits the cap:
        # imports must cut at step boundaries so each step's hypo_ids reorder
        # stays atomic
        cut = 0
        n_prefix = 0
        for hidden_step, _ in replay_steps:
            take = hidden_step.shape[1]
            if cut + take > cap:
                break
            cut += take
            n_prefix += 1
        if cut <= 0:
            return False
        await session.import_kv(k[:, :, :cut], v[:, :, :cut], cut)
        session.history = [tuple(step) for step in replay_steps[:n_prefix]]
        chunk = None
        for hidden_step, hypo_step in replay_steps[n_prefix:]:
            chunk = await self._replay_step(session, hidden_step, hypo_step, uuid.uuid4().hex)
        logger.info(
            f"Migrated {cut} cached tokens into {session.span.peer_id.to_string()[:8]} "
            f"(+{len(replay_steps) - n_prefix} replayed steps)"
        )
        return True

    async def _seed_by_adopt(
        self, session, source_session_id: str, export_pos: int, replay_steps
    ) -> bool:
        """Adopt migrated KV already resident on the replacement server, up to
        a history step boundary, then replay any remaining recorded steps.
        Same cut discipline as ``_seed_by_import`` — only the tensors never
        touch the client link."""
        if export_pos > self._position:
            # the migrated snapshot is AHEAD of the client (a step's reply was
            # lost): a hypo_ids reorder in that step would leave the cache
            # lane-permuted vs our history — replay is exact (see
            # _seed_by_import for the full hazard)
            return False
        cap = min(export_pos, self._position)
        cut = 0
        n_prefix = 0
        for hidden_step, _ in replay_steps:
            take = hidden_step.shape[1]
            if cut + take > cap:
                break
            cut += take
            n_prefix += 1
        if cut <= 0:
            return False
        await session.adopt_kv(source_session_id, cut)
        session.history = [tuple(step) for step in replay_steps[:n_prefix]]
        for hidden_step, hypo_step in replay_steps[n_prefix:]:
            await self._replay_step(session, hidden_step, hypo_step, uuid.uuid4().hex)
        logger.info(
            f"Adopted {cut} migrated tokens on {session.span.peer_id.to_string()[:8]} "
            f"(zero client-link KV bytes, +{len(replay_steps) - n_prefix} replayed steps)"
        )
        return True

    async def _maybe_upgrade_route(self) -> bool:
        """Live route upgrading (beyond reference): when a clearly better chain
        exists — a fast server joined, congestion cleared — migrate the
        session's KV onto it via live ``ptu.session_export`` instead of staying
        on the route chosen at session open. Safe-by-construction: the current
        chain keeps serving until every replacement is seeded, and any failure
        just abandons the attempt."""
        current = [s for s in self._sessions if not s.closed]
        if not current or self._position == 0:
            return False
        await self.seq_manager.update()
        candidate = await self.seq_manager.make_sequence(
            0, self.num_blocks, mode="min_latency",
            cache_tokens_needed=self.batch_size * self.max_length,
            affinity_seed=self._affinity_seed,
        )
        cur_key = [(s.span.peer_id, s.span.start, s.span.end) for s in current]
        cand_key = [(c.peer_id, c.start, c.end) for c in candidate]
        if cand_key == cur_key:
            return False
        tokens_needed = self.batch_size * self.max_length
        cur_cost = self.seq_manager.estimate_chain_latency(
            [s.span for s in current], cache_tokens_needed=tokens_needed
        )
        new_cost = self.seq_manager.estimate_chain_latency(
            candidate, cache_tokens_needed=tokens_needed
        )
        if new_cost > self.seq_manager.config.route_upgrade_threshold * cur_cost:
            return False
        # capability guard: the latency model scores per-token RPC cost and
        # is blind to server-side generation, which amortizes the round trip
        # over whole chunks — migrating a gen-capable session onto a chain
        # WITHOUT the capability would demote it to the per-token path (a
        # large net slowdown) after paying a full KV export
        if self.server_gen_available() and not self._spans_support_server_gen(candidate):
            return False
        # history-transfer guard: each candidate span's input history must
        # exist client-side, i.e. its start must be a current session start
        # (otherwise a LATER failover of that span could not replay)
        starts = {s.span.start for s in current}
        if any(c.start not in starts for c in candidate):
            return False
        logger.info(
            f"Upgrading route (estimated {cur_cost * 1e3:.0f} -> {new_cost * 1e3:.0f} ms/token)"
        )
        return await self._migrate_to(candidate, current)

    async def _migrate_to(self, chain, current) -> bool:
        """Open sessions for ``chain``, seeding each NEW span by exporting KV
        from the live current sessions (block-sliced, concatenated across
        session boundaries); reuse current sessions that match exactly."""
        by_start = {s.span.start: s for s in current}
        new_sessions: List[_ServerInferenceSession] = []
        created: List[_ServerInferenceSession] = []
        try:
            for span in chain:
                existing = by_start.get(span.start)
                if (
                    existing is not None
                    and existing.span.peer_id == span.peer_id
                    and existing.span.end == span.end
                ):
                    new_sessions.append(existing)
                    continue
                # open the (cheap) replacement session BEFORE the (expensive,
                # 100s-of-MB) exports: a candidate that refuses the open —
                # draining, cache full — must not cost a full KV transfer
                uids = self.seq_manager.block_uids[span.start : span.end]
                session = await _ServerInferenceSession.create(
                    self.seq_manager, span, uids,
                    max_length=self.max_length, batch_size=self.batch_size,
                    session_id=uuid.uuid4().hex,
                    trace_id=self.trace_id,
                )
                session.monitor = self.integrity
                created.append(session)
                # gather [span.start, span.end) KV from the covering sessions
                pieces = []
                export_pos = self._position
                for cur in sorted(current, key=lambda s: s.span.start):
                    lo, hi = max(cur.span.start, span.start), min(cur.span.end, span.end)
                    if lo >= hi:
                        continue
                    got = await self._try_export(cur.span.peer_id, cur.session_id, lo, hi)
                    if got is None or isinstance(got, dict):
                        # a redirect here means the live session moved under
                        # us mid-upgrade — abandon, the repair path handles it
                        raise RuntimeError(f"export of blocks [{lo}, {hi}) unavailable")
                    k, v, pos = got
                    pieces.append((lo, k, v))
                    export_pos = min(export_pos, pos)
                covered = sorted(pieces, key=lambda p: p[0])
                k_all = np.concatenate([p[1][:, :, :export_pos] for p in covered], axis=0)
                v_all = np.concatenate([p[2][:, :, :export_pos] for p in covered], axis=0)
                if k_all.shape[0] != span.end - span.start:
                    raise RuntimeError(
                        f"exported {k_all.shape[0]} blocks for span [{span.start}, {span.end})"
                    )
                replay_steps = by_start[span.start].history_steps()
                if not await self._seed_by_import(session, (k_all, v_all, export_pos), replay_steps):
                    raise RuntimeError("exported cache too stale (or ahead of us) to seed from")
                new_sessions.append(session)
        except Exception as e:
            logger.warning(f"Route upgrade abandoned (staying on current chain): {e}")
            for session in created:
                await session.close()
            # back off: without this, the identical doomed attempt (and its
            # KV transfers) would repeat on every period tick
            period = self.seq_manager.config.route_upgrade_period
            self._last_route_check = time.monotonic() + 4 * period
            return False

        replaced = [s for s in current if s not in new_sessions]
        self._retire_hops(replaced)
        for session in replaced:
            await session.close()
        self._sessions = new_sessions
        self._wire_push_chain(new_sessions)
        return True

    def _wire_push_chain(self, sessions: List[_ServerInferenceSession]) -> None:
        if not self.seq_manager.config.use_server_to_server:
            return
        for i, session in enumerate(sessions):
            nxt = sessions[i + 1] if i + 1 < len(sessions) else None
            target = None
            if nxt is not None and nxt.session_id:
                addr = self.seq_manager.addr_of(nxt.span.peer_id)
                if addr is not None:
                    target = {"addr": addr.to_string(), "session_id": nxt.session_id}
            session.pending_push_to = target if target is not None else False

    def _wire_repair_pushes(self, keep_up, new_sessions, keep_down, dead_end: int) -> None:
        """Re-link the server->server push chain around the repaired hole (the
        surviving upstream server still pushes to a dead session id)."""
        if not self.seq_manager.config.use_server_to_server:
            return

        def target_for(session) -> Optional[dict]:
            if session is None or not session.session_id:
                return None
            addr = self.seq_manager.addr_of(session.span.peer_id)
            if addr is None:
                return None
            return {"addr": addr.to_string(), "session_id": session.session_id}

        downstream = keep_down[0] if keep_down and keep_down[0].span.start == dead_end else None
        chain = list(new_sessions) + ([downstream] if downstream else [None])
        for i, session in enumerate(new_sessions):
            session.pending_push_to = target_for(chain[i + 1]) or False
        if keep_up:
            keep_up[-1].pending_push_to = target_for(new_sessions[0] if new_sessions else None) or False

    async def close(self) -> None:
        if not self._closed:
            self._closed = True
            # retire the hops first so trace_report() still works post-close
            self._retire_hops(self._sessions)
            for session in self._sessions:
                await session.close()
            self._sessions = []
