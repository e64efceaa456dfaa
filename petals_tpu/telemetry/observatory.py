"""Compiled-program observatory: the compute-side twin of the request plane.

The serving core rests on an invariant the code asserts but never observed:
"one shape -> ONE compiled program, no recompiles" (server/backend.py's
bucketed decode/mixed/gen steps). A silent recompile storm — a bucketing
bug, a drifting static argument, a shape that escapes the lane-pool
padding — shows up only as mysterious latency. This module makes the XLA
executable population a first-class observable:

- :func:`tracked_jit` wraps ``jax.jit`` (same signature, plus ``name`` and
  ``steady``). Every compilation is DETECTED (jit calls the wrapped Python
  function exactly once per new cache entry — the trace IS the compile
  signal), timed, counted in metrics, and journaled with the abstract
  shapes/static args that triggered it.
- Functions tagged ``steady=True`` (the decode/mixed/gen step programs)
  carry a warmup budget: after ``warmup_calls`` successful calls, the
  executable set is considered FROZEN and any new compilation is an
  anomaly — counter bump, ``compile_anomaly`` journal event carrying the
  offending avals, and an SLO-flight-recorder entry (the PR 7 evidence
  machinery), so a recompile storm leaves the same post-mortem trail as a
  latency breach.
- Each compiled program's XLA ``cost_analysis()`` (flops, bytes accessed)
  is extracted lazily — re-lowering from the recorded avals, never
  touching live buffers — into a per-program cost table served by the
  MetricsServer's ``/compile`` view and summarized on ``/metrics``.
  ``memory_analysis()`` (peak temp bytes) is opt-in per request: it costs
  a fresh backend compile per program.

Layering: telemetry imports nothing from the rest of ``petals_tpu``; jax
itself is imported lazily inside :func:`tracked_jit` so merely importing
the telemetry package stays dependency-free.
"""

from __future__ import annotations

import collections
import functools
import hashlib
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from petals_tpu.telemetry.journal import get_journal

DEFAULT_WARMUP_CALLS = 8
MAX_PROGRAM_RECORDS = 512
_AVALS_CAP = 24  # journal events carry at most this many per-leaf avals


def _leaf_aval_str(leaf: Any) -> str:
    aval = getattr(leaf, "aval", None)
    if aval is not None and hasattr(aval, "shape"):
        return f"{getattr(aval, 'dtype', '?')}[{','.join(map(str, aval.shape))}]"
    if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
        return f"{leaf.dtype}[{','.join(map(str, getattr(leaf, 'shape', ())))}]"
    return repr(leaf)


def _leaf_struct(leaf: Any) -> Any:
    """A buffer-free stand-in for one traced leaf (jax.ShapeDtypeStruct for
    arrays/tracers, the verbatim value for static python leaves) — enough to
    re-lower the program later without holding any donated device buffer."""
    import jax

    aval = getattr(leaf, "aval", None)
    if aval is not None and hasattr(aval, "shape"):
        return jax.ShapeDtypeStruct(aval.shape, aval.dtype)
    if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)
    return leaf


def _leaf_nbytes(leaf: Any) -> int:
    aval = getattr(leaf, "aval", leaf)
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    try:
        import numpy as np

        return n * int(np.dtype(dtype).itemsize)
    except Exception:
        return 0


class ProgramRecord:
    """One compiled executable of one tracked function."""

    __slots__ = (
        "fn", "steady", "key", "avals", "n_leaves", "arg_bytes",
        "compile_s", "t", "anomaly", "cost", "memory", "_structs", "_lower",
    )

    def __init__(self, fn, steady, key, avals, n_leaves, arg_bytes,
                 compile_s, anomaly, structs, lower):
        self.fn = fn
        self.steady = steady
        self.key = key
        self.avals = avals
        self.n_leaves = n_leaves
        self.arg_bytes = arg_bytes
        self.compile_s = compile_s
        self.t = time.time()  # wall timestamp for operators, not a span
        self.anomaly = anomaly
        self.cost: Optional[dict] = None
        self.memory: Optional[dict] = None
        self._structs = structs  # (args, kwargs) pytree of ShapeDtypeStructs
        self._lower = lower  # callable: (args, kwargs) -> jax.stages.Lowered

    def as_dict(self) -> dict:
        out = {
            "fn": self.fn,
            "steady": self.steady,
            "key": self.key,
            "avals": self.avals,
            "n_leaves": self.n_leaves,
            "arg_bytes": self.arg_bytes,
            "compile_s": round(self.compile_s, 4),
            "t": self.t,
            "anomaly": self.anomaly,
        }
        if self.cost is not None:
            out["cost"] = self.cost
        if self.memory is not None:
            out["memory"] = self.memory
        return out


class _FnAggregate:
    """Per-name totals across every wrapper instance sharing that name
    (several TransformerBackend instances in one process all register
    e.g. ``batched_decode``)."""

    __slots__ = ("name", "steady", "calls", "compiles", "compile_s", "anomalies")

    def __init__(self, name: str, steady: bool):
        self.name = name
        self.steady = steady
        self.calls = 0
        self.compiles = 0
        self.compile_s = 0.0
        self.anomalies = 0

    def as_dict(self) -> dict:
        return {
            "fn": self.name,
            "steady": self.steady,
            "calls": self.calls,
            "compiles": self.compiles,
            "compile_s": round(self.compile_s, 4),
            "anomalies": self.anomalies,
        }


class Observatory:
    """Registry of tracked jitted functions and their compiled programs."""

    def __init__(
        self,
        *,
        warmup_calls: Optional[int] = None,
        max_programs: int = MAX_PROGRAM_RECORDS,
    ):
        if warmup_calls is None:
            try:
                warmup_calls = int(
                    os.environ.get("PETALS_TPU_COMPILE_WARMUP", DEFAULT_WARMUP_CALLS)
                )
            except ValueError:
                warmup_calls = DEFAULT_WARMUP_CALLS
        self.warmup_calls = max(int(warmup_calls), 1)
        self.max_programs = int(max_programs)
        self._lock = threading.Lock()
        self._functions: Dict[str, _FnAggregate] = {}
        self._programs: "collections.OrderedDict[int, ProgramRecord]" = (
            collections.OrderedDict()
        )
        self._program_seq = 0
        self.dropped_programs = 0
        self._tls = threading.local()
        self._flight = None  # FlightRecorder, created lazily on first anomaly

    # ------------------------------------------------------------- registry

    def _register(self, name: str, steady: bool) -> _FnAggregate:
        with self._lock:
            agg = self._functions.get(name)
            if agg is None:
                agg = self._functions[name] = _FnAggregate(name, steady)
            agg.steady = agg.steady or steady
            return agg

    def _add_program(self, record: ProgramRecord) -> None:
        with self._lock:
            self._program_seq += 1
            self._programs[self._program_seq] = record
            while len(self._programs) > self.max_programs:
                self._programs.popitem(last=False)
                self.dropped_programs += 1

    def functions(self) -> List[dict]:
        with self._lock:
            return [agg.as_dict() for agg in self._functions.values()]

    def programs(self) -> List[ProgramRecord]:
        with self._lock:
            return list(self._programs.values())

    # ------------------------------------------------------------- recording

    def _in_trace_or_introspection(self) -> bool:
        tls = self._tls
        return bool(getattr(tls, "depth", 0)) or bool(getattr(tls, "introspect", 0))

    def _record_compile(
        self, agg: _FnAggregate, steady: bool, past_warmup: bool,
        pending: dict, compile_s: float,
    ) -> None:
        from petals_tpu.telemetry import instruments as tm

        anomaly = steady and past_warmup
        with self._lock:
            agg.compiles += 1
            agg.compile_s += compile_s
            if anomaly:
                agg.anomalies += 1
            compiles_total = agg.compiles
        tm.COMPILES.labels(fn=agg.name).inc()
        tm.COMPILE_SECONDS.labels(fn=agg.name).inc(compile_s)
        avals = pending["avals"]
        capped = (
            avals
            if len(avals) <= _AVALS_CAP
            else avals[:_AVALS_CAP] + [f"... +{len(avals) - _AVALS_CAP} more"]
        )
        record = ProgramRecord(
            fn=agg.name, steady=steady, key=pending["key"], avals=capped,
            n_leaves=len(avals), arg_bytes=pending["arg_bytes"],
            compile_s=compile_s, anomaly=anomaly,
            structs=pending["structs"], lower=pending["lower"],
        )
        self._add_program(record)
        journal = get_journal()
        journal.event(
            "compile", fn=agg.name, key=record.key, avals=capped,
            compile_s=round(compile_s, 4), compiles=compiles_total,
            steady=steady,
        )
        if anomaly:
            tm.COMPILE_ANOMALIES.labels(fn=agg.name).inc()
            journal.event(
                "compile_anomaly", fn=agg.name, key=record.key, avals=capped,
                compile_s=round(compile_s, 4), warmup_calls=self.warmup_calls,
            )
            self.flight_recorder().record(
                "recompile",
                fn=agg.name,
                avals=capped,
                compile_s=round(compile_s, 4),
                # lazy evidence (PR 7 machinery): the journal tail for this
                # function's compile history, resolved only when recording
                journal=lambda: get_journal().events(kind="compile")[-8:],
            )

    # ---------------------------------------------------------- flight hookup

    def attach_flight(self, recorder) -> None:
        self._flight = recorder

    def flight_recorder(self):
        if self._flight is None:
            from petals_tpu.telemetry.flight import FlightRecorder

            with self._lock:
                if self._flight is None:
                    self._flight = FlightRecorder(
                        path=os.environ.get("PETALS_TPU_FLIGHT") or None
                    )
        return self._flight

    # ------------------------------------------------------------- analysis

    def analyze(self, record: ProgramRecord, *, memory: bool = False) -> ProgramRecord:
        """Fill ``record.cost`` (and optionally ``record.memory``) from XLA.

        Cost analysis re-lowers from the recorded avals — a re-trace, no
        backend compile. Memory analysis needs a compiled executable, which
        AOT-compiles the program again (the JIT call cache is not shared
        with the AOT path) — expensive, so opt-in per request."""
        tls = self._tls
        tls.introspect = getattr(tls, "introspect", 0) + 1
        try:
            if record.cost is None:
                try:
                    args, kwargs = record._structs
                    lowered = record._lower(args, kwargs)
                    ca = lowered.cost_analysis()
                    if isinstance(ca, (list, tuple)):
                        ca = ca[0] if ca else {}
                    record.cost = {
                        "flops": float(ca.get("flops", 0.0) or 0.0),
                        "bytes_accessed": float(ca.get("bytes accessed", 0.0) or 0.0),
                        "transcendentals": float(ca.get("transcendentals", 0.0) or 0.0),
                    }
                    from petals_tpu.telemetry import instruments as tm

                    tm.COMPILED_FLOPS.labels(fn=record.fn).set(record.cost["flops"])
                    tm.COMPILED_BYTES.labels(fn=record.fn).set(
                        record.cost["bytes_accessed"]
                    )
                except Exception as e:
                    record.cost = {"error": repr(e)}
            if memory and record.memory is None:
                try:
                    args, kwargs = record._structs
                    compiled = record._lower(args, kwargs).compile()
                    ma = compiled.memory_analysis()
                    record.memory = {
                        "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0) or 0),
                        "argument_bytes": int(
                            getattr(ma, "argument_size_in_bytes", 0) or 0
                        ),
                        "output_bytes": int(
                            getattr(ma, "output_size_in_bytes", 0) or 0
                        ),
                        "code_bytes": int(
                            getattr(ma, "generated_code_size_in_bytes", 0) or 0
                        ),
                    }
                except Exception as e:
                    record.memory = {"error": repr(e)}
        finally:
            tls.introspect -= 1
        return record

    def lowered_text(self, record: ProgramRecord) -> str:
        """StableHLO text of one recorded program, re-lowered from its avals
        like the cost analysis above: says whether the program that was
        traced contains a Mosaic custom call (``tpu_custom_call``)."""
        tls = self._tls
        tls.introspect = getattr(tls, "introspect", 0) + 1
        try:
            args, kwargs = record._structs
            return record._lower(args, kwargs).as_text()
        finally:
            tls.introspect -= 1

    def cost_table(
        self, *, memory: bool = False, fn: Optional[str] = None
    ) -> List[dict]:
        """Per-program cost table (the ``/compile`` view): recorded programs
        with their lazily-computed cost analysis attached. ``fn`` narrows to
        one function — each uncached analysis is a re-lower, so scraping a
        long-lived server's full table cold can take seconds; a scoped query
        pays only for what it asks about."""
        records = self.programs()
        if fn is not None:
            records = [r for r in records if r.fn == fn]
        return [self.analyze(r, memory=memory).as_dict() for r in records]

    def compile_stats(self) -> dict:
        """Compact digest for the announce path / rpc_info: program count,
        total compile seconds, anomalies. Flat and tiny — it rides every
        ServerInfo record next to the telemetry digest."""
        with self._lock:
            return {
                "functions": len(self._functions),
                "programs": sum(a.compiles for a in self._functions.values()),
                "compile_s": round(
                    sum(a.compile_s for a in self._functions.values()), 3
                ),
                "anomalies": sum(a.anomalies for a in self._functions.values()),
            }

    # ------------------------------------------------------------- roofline

    @staticmethod
    def peak_flops() -> Optional[float]:
        """Peak FLOP/s for utilization math, from ``PETALS_TPU_PEAK_TFLOPS``
        (None when unset: on CPU there is no honest peak to divide by —
        achieved FLOP/s is still reported, utilization stays null)."""
        raw = os.environ.get("PETALS_TPU_PEAK_TFLOPS")
        if not raw:
            return None
        try:
            return float(raw) * 1e12
        except ValueError:
            return None

    def roofline(self, fn: str, step_seconds: float) -> Optional[dict]:
        """Achieved-vs-roofline utilization for one steady function: the
        largest analyzed program's flops over the measured mean step time."""
        if step_seconds <= 0:
            return None
        candidates = [r for r in self.programs() if r.fn == fn]
        if not candidates:
            return None
        for r in candidates:
            self.analyze(r)
        flops = max(
            (r.cost or {}).get("flops", 0.0) or 0.0 for r in candidates
        )
        if flops <= 0:
            return None
        achieved = flops / step_seconds
        peak = self.peak_flops()
        return {
            "fn": fn,
            "flops_per_step": flops,
            "step_mean_ms": round(step_seconds * 1e3, 3),
            "achieved_gflops": round(achieved / 1e9, 3),
            "utilization": (round(achieved / peak, 4) if peak else None),
        }

    def reset(self) -> None:
        """Drop every record and aggregate (tests)."""
        with self._lock:
            self._functions.clear()
            self._programs.clear()
            self._program_seq = 0
            self.dropped_programs = 0


_global_observatory: Optional[Observatory] = None
_observatory_lock = threading.Lock()


def get_observatory() -> Observatory:
    global _global_observatory
    if _global_observatory is None:
        with _observatory_lock:
            if _global_observatory is None:
                _global_observatory = Observatory()
    return _global_observatory


def compile_stats_digest() -> dict:
    return get_observatory().compile_stats()


def tracked_jit(
    fun: Optional[Callable] = None,
    *,
    name: Optional[str] = None,
    steady: bool = False,
    observatory: Optional[Observatory] = None,
    **jit_kwargs,
):
    """``jax.jit`` with its compilations observed (drop-in replacement).

    Usable bare or parameterized::

        @tracked_jit(name="batched_decode", steady=True, donate_argnums=(1, 2))
        def step(params, k, v, hidden, positions): ...

    Contract:

    - The returned wrapper calls the real jitted function; ``__wrapped__``
      is the undecorated Python callable (``backend._backward_fn`` relies
      on it to re-trace the raw closure for vjp), matching ``jax.jit``.
    - Every new compilation (detected by jit tracing the wrapped function)
      records metrics, a ``compile`` journal event with the abstract
      shapes, and a :class:`ProgramRecord` for the cost table.
    - With ``steady=True``, once THIS wrapper has run ``warmup_calls``
      times, any further compilation is an anomaly: counter + journal
      ``compile_anomaly`` event + flight-recorder entry.
    - Calls made while another tracked function is tracing (nested jit) or
      while the observatory is re-lowering for analysis are transparent.
    """
    if fun is None:
        return functools.partial(
            tracked_jit, name=name, steady=steady, observatory=observatory,
            **jit_kwargs,
        )
    import jax

    obs = observatory if observatory is not None else get_observatory()
    fname = name or getattr(fun, "__qualname__", getattr(fun, "__name__", "jit"))
    agg = obs._register(fname, steady)
    # wrapper-local state: warmup and anomaly detection are per INSTANCE
    # (each TransformerBackend compiles its own programs; a fresh backend
    # must not inherit another instance's frozen executable set)
    local = {"calls": 0}
    tls = obs._tls

    @functools.wraps(fun)
    def traced(*args, **kwargs):
        # jit invokes this exactly once per new cache entry — the trace is
        # the compile signal. Nested traces (this function inlined into an
        # outer tracked program) and analysis re-lowers are not counted.
        pending = getattr(tls, "pending", None)
        depth = getattr(tls, "depth", 0)
        if pending is not None and depth == 0 and not getattr(tls, "introspect", 0):
            leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
            avals = [_leaf_aval_str(leaf) for leaf in leaves]
            key_src = "|".join(avals) + "#" + str(treedef)
            pending["avals"] = avals
            pending["key"] = hashlib.md5(key_src.encode()).hexdigest()[:12]
            pending["arg_bytes"] = sum(_leaf_nbytes(leaf) for leaf in leaves)
            structs = treedef.unflatten([_leaf_struct(leaf) for leaf in leaves])
            pending["structs"] = structs
        tls.depth = depth + 1
        try:
            return fun(*args, **kwargs)
        finally:
            tls.depth = depth

    jitted = jax.jit(traced, **jit_kwargs)

    def _lower(largs, lkwargs):
        return jitted.lower(*largs, **lkwargs)

    @functools.wraps(fun)
    def wrapper(*args, **kwargs):
        if getattr(tls, "depth", 0) or getattr(tls, "introspect", 0):
            return jitted(*args, **kwargs)  # inlined into an outer trace
        past_warmup = local["calls"] >= obs.warmup_calls
        pending: dict = {}
        tls.pending = pending
        t0 = time.perf_counter()
        try:
            out = jitted(*args, **kwargs)
        finally:
            tls.pending = None
            if "key" in pending:
                pending["lower"] = _lower
                obs._record_compile(
                    agg, steady, past_warmup, pending,
                    time.perf_counter() - t0,
                )
        local["calls"] += 1
        with obs._lock:
            agg.calls += 1
        return out

    wrapper.__wrapped__ = fun
    return wrapper


__all__ = [
    "DEFAULT_WARMUP_CALLS",
    "Observatory",
    "ProgramRecord",
    "compile_stats_digest",
    "get_observatory",
    "tracked_jit",
]
