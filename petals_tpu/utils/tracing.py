"""Tracing & profiling hooks (SURVEY §5.1 — the reference sets a low bar
here: env-var log levels only, src/petals/utils/logging.py. This build adds
per-RPC duration spans with aggregates, plus jax profiler integration so a
device timeline can be captured on demand).

Three layers:
- host spans: ``tracer.span("rpc_forward", tokens=...)`` records wall time +
  metadata into a bounded ring; ``tracer.summary()`` gives per-name
  count/p50/p95/total for rpc_info and logs. Each span also emits a
  ``jax.profiler.TraceAnnotation`` so the host block shows up aligned with
  device ops when a jax trace is being captured.
- step phases: ``step_phases(stats, variant=...)`` walks one batched step
  through ``assemble`` / ``dispatch`` / ``wait`` / ``post`` on the compute
  thread. Each phase is a ``ptu.step.<name>`` TraceAnnotation inside one
  ``ptu.step`` annotation, and its wall time is added to
  ``stats["<name>_s"]``, one reading of the clock closing a phase and
  opening the next. Always on; nothing goes into the span ring (a few
  hundred steps a second would push the RPC-level spans out of it).
- device timeline: ``start_jax_trace(logdir)`` / ``stop_jax_trace()`` wrap
  ``jax.profiler`` (served via ``PETALS_TPU_TRACE_DIR`` at server startup;
  view in TensorBoard/XProf).

Which serving paths a captured trace names, all on the compute thread
(``ptu-compute`` to Python; the profiler names every Python thread's line
after the process, so look for the line that holds these events): every
batched step of the lane pool (``DecodeBatcher._run_batch``,
``_run_batch_mixed``, ``_run_batch_gen``, ``_run_batch_spec``: ``ptu.step``
and its four phases, with ``variant``, ``lanes`` and ``prefill_tokens`` as
arguments; a plain decode step of the paged pool is two ``ptu.step``s,
``_launch_batch``'s with ``assemble`` and ``dispatch`` and
``_finish_batch``'s with ``post``, and the wait for its rows is
``ptu.readback`` on the readback thread's line), the private, exclusive and dense-prefill inference paths
(``inference_step``), ``server_gen``, ``rpc_forward``, ``rpc_backward`` and
``rpc_probe``. The RPC-level ``inference_step`` span around ``batcher.step``
and ``batcher.prefill_lane`` lives on the event loop and is not annotated
(concurrent spans interleave there).

On the event loop's thread (another line of the same plane) a decode token's
way out and back is annotated where a stretch holds no ``await``, so that
each closes before its coroutine yields: ``ptu.flush.resolve`` (the flush
loop sets a step's futures; ``lanes``), ``ptu.reply.build`` (the handler,
from ``batcher.step``'s return to the reply's yield; ``lane``),
``ptu.rpc.send`` (msgpack and ``writer.write`` of a stream's item),
``ptu.rpc.recv`` (``unpackb`` of any frame). One spans an ``await``:
``ptu.gather`` around the gather's wait (``lanes`` waited for), because at
most one flush task is alive and whatever else that thread runs meanwhile
opens and closes inside it. The handler's stretch from a request's receipt
to ``batcher.step`` holds conditional awaits and has a counter only
(``request_handle_s``). Their counters are in ``batcher.stats``
(``server/batching.py``).

The other half of that trip is timed where it runs (PR 54), always on and
with counters only, every reading ``time.perf_counter()``: the client's seven
stations of a step, K3 (``rpc/client.py _read_loop``: the reply's frame read
whole, handed on with the item as ``StreamCall.read_at``) to K2
(``stream.send`` returned for the session's next request), are
``telemetry/spans.py ClientTrip``: sums a session in
``trace_report()["client"]`` (``away_s``, ``recv_s``, ``finish_s``,
``wake_s``, ``user_s``, ``submit_s``, ``build_s``, ``relay_s``) and one row a
step in the process's bounded ``STEP_RING``. And an event loop's turns, the
stretches between one ``select()``'s return and the next one's call, are
``utils/asyncio_utils.install_turn_clock``: ``loop_busy_s``,
``loop_busy_sq`` and ``loop_turns``, in ``batcher.stats`` for the loop
``Server.start()`` runs on, in ``SwarmRuntime.turn_clock`` (sampled about
every 0.1 s) for a client's. No stamp crosses the wire.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import threading
import time
from collections import defaultdict, deque
from typing import Dict, Optional

from petals_tpu.utils.logging import get_logger

logger = get_logger(__name__)

try:  # resolved once: the batched step opens five of these a step
    from jax.profiler import TraceAnnotation as _TraceAnnotation
except Exception:  # profiler unavailable: spans and counters still record wall time
    _TraceAnnotation = None

TRACE_DIR_ENV = "PETALS_TPU_TRACE_DIR"
TRACE_SECONDS_ENV = "PETALS_TPU_TRACE_SECONDS"
DEFAULT_TRACE_SECONDS = 60.0  # jax.profiler buffers until stop: bound the window
_MAX_SPANS = 2048  # ring bound: tracing must never grow server memory
_MAX_DURATIONS_PER_NAME = 4096
# span metadata bounds: a hot path passing a growing dict (or a huge repr)
# must not bloat the span ring; clipped/dropped entries are counted in the
# telemetry_meta_truncated_total metric
_MAX_META_ENTRIES = 16
_MAX_META_VALUE_LEN = 256


def _bound_meta(meta: dict) -> dict:
    """Cap entry count and value sizes; count every clip/drop."""
    truncated = 0
    out = {}
    for i, (key, value) in enumerate(meta.items()):
        if i >= _MAX_META_ENTRIES:
            truncated += len(meta) - _MAX_META_ENTRIES
            break
        if isinstance(value, (int, float, bool, type(None))):
            out[key] = value
            continue
        text = value if isinstance(value, str) else repr(value)
        if len(text) > _MAX_META_VALUE_LEN:
            text = text[:_MAX_META_VALUE_LEN]
            truncated += 1
        out[key] = text
    if truncated:
        from petals_tpu.telemetry.instruments import META_TRUNCATED

        META_TRUNCATED.inc(truncated)
    return out


@dataclasses.dataclass
class Span:
    name: str
    start: float  # time.time()
    duration: float  # seconds
    meta: dict


class Tracer:
    """Thread-safe span recorder with bounded memory."""

    def __init__(self, max_spans: int = _MAX_SPANS):
        self._spans: deque = deque(maxlen=max_spans)
        self._durations: Dict[str, deque] = defaultdict(
            lambda: deque(maxlen=_MAX_DURATIONS_PER_NAME)
        )
        self._counts: Dict[str, int] = defaultdict(int)
        self._totals: Dict[str, float] = defaultdict(float)
        self._lock = threading.Lock()

    @contextlib.contextmanager
    def span(self, name: str, annotate: bool = True, **meta):
        """Record one timed span; with ``annotate`` it also marks the jax
        profiler timeline. Pass ``annotate=False`` when the span wraps an
        ``await`` on the event loop (concurrent spans would interleave
        non-LIFO there) and put ``device_annotation(name)`` around the actual
        compute on its worker thread instead."""
        annotation = device_annotation(name) if annotate else contextlib.nullcontext()
        # every span carries the ambient request trace id (telemetry.trace
        # contextvar) so one session's spans line up into a single timeline
        if "trace_id" not in meta:
            from petals_tpu.telemetry.trace import current_trace_id

            tid = current_trace_id()
            if tid is not None:
                # first position: the entry cap trims from the END, and the
                # trace id is the one key the timeline cannot lose
                meta = {"trace_id": tid, **meta}
        meta = _bound_meta(meta)
        t_wall = time.time()
        t0 = time.perf_counter()
        try:
            with annotation:
                yield
        finally:
            duration = time.perf_counter() - t0
            with self._lock:
                self._spans.append(Span(name, t_wall, duration, meta))
                self._durations[name].append(duration)
                self._counts[name] += 1
                self._totals[name] += duration

    def recent(self, limit: int = 100) -> list:
        with self._lock:
            return list(self._spans)[-limit:]

    def summary(self) -> Dict[str, dict]:
        """Per-span-name aggregates (msgpack-safe, for rpc_info / logs)."""
        out = {}
        with self._lock:
            for name, durations in self._durations.items():
                if not durations:
                    continue
                ordered = sorted(durations)
                out[name] = {
                    "count": self._counts[name],
                    "p50_ms": round(ordered[len(ordered) // 2] * 1e3, 3),
                    "p95_ms": round(ordered[int(len(ordered) * 0.95)] * 1e3, 3),
                    "total_s": round(self._totals[name], 3),
                }
        return out

    def reset(self) -> None:
        with self._lock:
            self._spans.clear()
            self._durations.clear()
            self._counts.clear()
            self._totals.clear()


def device_annotation(name: str, **args):
    """A jax profiler TraceAnnotation (no-op when the profiler is absent) —
    place it around the compute itself, on the thread that runs it. It
    records only while a profiler trace is running; ``args`` become the
    event's arguments in the trace."""
    if _TraceAnnotation is None:
        return contextlib.nullcontext()
    return _TraceAnnotation(name, **args)


STEP_PHASES = ("assemble", "dispatch", "wait", "post")


class step_phases:
    """One batched step as the four ``STEP_PHASES`` in order and without
    gaps, on the thread that runs it, inside one ``ptu.step`` annotation that
    carries ``args``: each phase is a ``ptu.step.<name>`` annotation around
    a ``perf_counter`` interval that is added to ``stats[name + "_s"]``, also
    when the body raises. Entering opens ``assemble``; the body calls
    ``enter(name)`` at each boundary, where one reading of the clock closes a
    phase and opens the next; the exit closes whichever phase is running, so
    none stays open on a raise. ``started`` and ``ended`` are the readings
    that opened the first phase and closed the last, for a caller that tiles
    the time around the step with the same clock. A step whose body is split
    where it blocks (``DecodeBatcher._launch_batch`` / ``_finish_batch``) walks
    them in two runs on the same thread, ``assemble`` and ``dispatch`` in one
    and, ``first="post"``, the last phase in another; what lies between is the
    caller's to count."""

    __slots__ = ("_stats", "_step", "_first", "_index", "_annotation", "_t0", "started", "ended")

    def __init__(self, stats: dict, first: str = STEP_PHASES[0], **args):
        self._stats = stats
        self._first = STEP_PHASES.index(first)
        self._step = device_annotation("ptu.step", **args)

    def _open(self, index: int, now: float) -> None:
        self._index = index
        self._annotation = device_annotation("ptu.step." + STEP_PHASES[index])
        self._annotation.__enter__()
        self._t0 = now

    def _close(self, exc_info=(None, None, None)) -> float:
        self._annotation.__exit__(*exc_info)
        now = time.perf_counter()
        self._stats[STEP_PHASES[self._index] + "_s"] += now - self._t0
        return now

    def __enter__(self):
        self._step.__enter__()
        self.started = time.perf_counter()
        self._open(self._first, self.started)
        return self

    def enter(self, name: str) -> None:
        index = self._index + 1
        if index >= len(STEP_PHASES) or STEP_PHASES[index] != name:
            raise RuntimeError(f"step phase {name!r} cannot follow {STEP_PHASES[self._index]!r}")
        self._open(index, self._close())

    def __exit__(self, *exc_info):
        self.ended = self._close(exc_info)
        self._step.__exit__(*exc_info)
        return False


_global_tracer: Optional[Tracer] = None
_tracing_active = False
# guards the check-then-set on _tracing_active: two concurrent starts (e.g.
# server startup racing an operator trigger) would otherwise double-call
# jax.profiler.start_trace, which raises and can corrupt the capture
_trace_lock = threading.Lock()


def get_tracer() -> Tracer:
    global _global_tracer
    if _global_tracer is None:
        _global_tracer = Tracer()
    return _global_tracer


def start_jax_trace(logdir: Optional[str] = None) -> Optional[str]:
    """Begin capturing a jax device/host trace (TensorBoard/XProf format).
    Uses ``PETALS_TPU_TRACE_DIR`` when ``logdir`` is not given; no-op (None)
    when neither is set or a capture is already running."""
    global _tracing_active
    logdir = logdir or os.environ.get(TRACE_DIR_ENV)
    if not logdir:
        return None
    import jax

    with _trace_lock:
        if _tracing_active:
            return None
        jax.profiler.start_trace(logdir)
        _tracing_active = True
    logger.info(f"jax trace capturing to {logdir}")
    return logdir


def stop_jax_trace() -> None:
    """Idempotent under races: concurrent stops (timed flush racing
    shutdown) resolve to one profiler stop_trace call."""
    global _tracing_active
    import jax

    with _trace_lock:
        if not _tracing_active:
            return
        try:
            jax.profiler.stop_trace()
        finally:
            # even if the profiler stop raises, the module must not believe a
            # capture is still running — a retry would double-stop instead
            _tracing_active = False
    logger.info("jax trace stopped")


def trace_window_seconds() -> float:
    """How long a server-startup capture should run before being flushed:
    jax.profiler buffers events until stop_trace, so an unbounded capture on
    a long-running server grows host memory without limit."""
    value = os.environ.get(TRACE_SECONDS_ENV, "").strip()
    return float(value) if value else DEFAULT_TRACE_SECONDS
