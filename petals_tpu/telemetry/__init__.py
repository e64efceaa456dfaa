"""Swarm telemetry plane: metrics registry, request-scoped trace context,
scheduler event journal, and Prometheus-text exposition.

Dependency-free by design (stdlib only), mirroring the zero-dep posture of
``utils/health.py``: servers in a public swarm cannot assume a Prometheus
client library is installed, and the decode tick path cannot afford one.

Layering contract: this package imports NOTHING from the rest of
``petals_tpu`` (``utils/tracing.py`` and the server stack import *us*), so
any module — client, RPC, batcher, compute thread — can record without
creating an import cycle.

The pieces:

- :mod:`.registry` — Counter/Gauge/Histogram with bounded label
  cardinality; exceeding the cap is surfaced AS a metric
  (``telemetry_label_overflow_total``), never silent growth.
- :mod:`.trace` — ``trace_id`` minting + contextvar propagation: the
  client mints one per session, carries it in the RPC open message, and
  every span/journal event downstream tags it so one session's life
  reconstructs as a single causal timeline.
- :mod:`.journal` — bounded structured event log of scheduler decisions
  (admission, victim selection, swap in/out) WITH the occupancy snapshot
  that justified each one; replayable as JSONL, assertable in tests.
- :mod:`.exposition` — Prometheus text rendering + a stdlib
  ``http.server`` ``/metrics`` endpoint, and the compact digest published
  in ServerInfo via the DHT announce path.
- :mod:`.instruments` — the shared named instruments (TTFT, step
  duration, swap bytes, ...) pre-registered on the global registry.
- :mod:`.spans` — the client-side critical-path profiler: per-hop
  waterfalls built from the ``step_meta`` dicts servers piggyback on
  inference replies (network / queue / compute / serialize / other).
- :mod:`.flight` — the SLO flight recorder: on a TTFT or token-latency
  breach, dump the span waterfall plus the victim server's journal
  excerpt to a bounded JSONL ring.
- :mod:`.ledger` — the per-tenant resource ledger: page-seconds (COW
  pages attributed fractionally by refcount), compute-seconds, tokens,
  swap/migrated bytes per session and per peer, with a DRF-style
  noisy-neighbor detector and the ``/ledger`` top-k view.
- :mod:`.observatory` — the compiled-program observatory:
  ``tracked_jit`` wraps ``jax.jit`` so every compilation is detected,
  timed, journaled with its avals, and cost-analyzed into the ``/compile``
  view; steady-state-tagged functions get a post-warmup recompile
  sentinel that files anomalies with the flight recorder. (jax is
  imported lazily — the package stays stdlib-only at import time.)
"""

from petals_tpu.telemetry.journal import TelemetryJournal, get_journal
from petals_tpu.telemetry.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
)
from petals_tpu.telemetry.trace import (
    current_trace_id,
    new_trace_id,
    normalize_trace_id,
    reset_trace_id,
    set_trace_id,
    trace_context,
)
from petals_tpu.telemetry.exposition import (
    MetricsServer,
    render_prometheus,
    telemetry_digest,
)
from petals_tpu.telemetry.flight import (
    FlightRecorder,
    flight_from_env,
    http_journal_fetcher,
)
from petals_tpu.telemetry.spans import (
    HopTrace,
    build_trace_report,
    format_waterfall,
)
from petals_tpu.telemetry.observatory import (
    Observatory,
    compile_stats_digest,
    get_observatory,
    tracked_jit,
)
from petals_tpu.telemetry.ledger import (
    ResourceLedger,
    get_ledger,
)

__all__ = [
    "FlightRecorder",
    "HopTrace",
    "Observatory",
    "compile_stats_digest",
    "get_observatory",
    "tracked_jit",
    "build_trace_report",
    "flight_from_env",
    "format_waterfall",
    "http_journal_fetcher",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "MetricsServer",
    "ResourceLedger",
    "TelemetryJournal",
    "get_ledger",
    "current_trace_id",
    "get_journal",
    "get_registry",
    "new_trace_id",
    "normalize_trace_id",
    "render_prometheus",
    "reset_trace_id",
    "set_trace_id",
    "telemetry_digest",
    "trace_context",
]
