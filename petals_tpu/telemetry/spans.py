"""Client-side critical-path profiler: per-hop latency waterfalls.

A Petals request's latency has no single owner — it is spread across every
server of the chain plus the network between them. Servers piggyback a
compact ``step_meta`` dict (queue-wait / compute / serialize seconds, step
variant, occupancy hint) on each inference reply; the client accumulates
those into one :class:`HopTrace` per server span and
:func:`build_trace_report` turns them into a waterfall that attributes the
session's wall-clock to named components:

- ``network``  — client-observed step wall minus the server's reported
  residency (wire + framing + event-loop handoff on both ends). Since PR 54
  the report's ``client`` key splits the client's end of it: ``recv_s`` and
  ``build_s`` are this process's framing and handoff inside a hop's wall,
  ``away_s`` is the whole wall seen from the frame's last byte read (so
  ``away_s`` less the hops' ``server_s`` is the wire and both loops'
  lateness alone)
- ``queue``    — time the step waited for a lane / page / compute slot
- ``compute``  — time inside the compiled device step
- ``serialize``— server-side reply serialization
- ``other``    — everything else (client-side work, server-side host ops,
  steps from old servers that sent no ``step_meta``)

The five components are exhaustive by construction, so the report's
``attributed_fraction`` is ~1.0 whenever clocks behave; the per-hop,
per-component shares are the routing/blame signal.

The client's own stations of a step (PR 54, :class:`ClientTrip`): seven
readings of ``time.perf_counter`` tile a session's time from one request
written to the next, one reading closing a stretch and opening the next.

====  ==========================================================  ============
K3    ``RpcClient._read_loop``: the reply's frame read whole       ``away_s``
K4    ``_ServerInferenceSession``: the reply is in the stepper's   ``recv_s``
      hands (``stream.recv`` / ``recv_in_thread`` returned)
K5    ``InferenceSession``: the step accepted, about to return     ``finish_s``
K6    ``SyncInferenceSession.step`` holds the result               ``wake_s``
K0    ``SyncInferenceSession.step`` entered again                  ``user_s``
K1    ``InferenceSession``: the step begins where it will be built ``submit_s``
K2    ``_ServerInferenceSession``: the request handed to the       ``build_s``
      connection (``stream.send`` / ``send_from_thread`` returned)
====  ==========================================================  ============

A step crosses the client's process one of two ways (PR 55). **The
coroutine's**: ``SyncInferenceSession.step`` submits ``InferenceSession.step``
to the loop, so K1 is the coroutine running there (``submit_s`` is a thread
crossing), K2 ``stream.send`` returned (the frame written and drained), K4
``stream.recv`` returned on the loop, K6 the caller's thread awake again
(``wake_s`` is the second crossing). **The direct way**
(``InferenceSession.step_from_thread``, a sync caller's steady-state step):
the caller's thread does it all, so K1 follows K0 at once (``submit_s`` ~0:
no crossing before the build), K2 is the packed frame handed to the socket or
to the loop (``build_s``). Since PR 58 the thread writes the frame to the
connection's socket itself when the connection can take it whole right now (a
plain TCP socket, nothing buffered, nothing queued: ``rpc/client.py _Outlet``)
and K2 is then the ``send`` returned; otherwise (a prompt's frame draining, a
short ``send``, no plain socket) the frame is the loop's to write, behind what
it holds, and the loop's delay until it is written lies in ``away_s``.
``ClientTrip.wrote`` / ``deferred`` count a direct step's frames each way, a
row's last column (``wrote``) those its thread wrote. A reply
read before the thread had read K2 makes K2 = K3. K4 is
the caller's thread holding the item (``recv_s`` is now the reader's unpacking
and the one loop-to-thread crossing), and K6 follows K5 on the same thread
(``wake_s`` ~0). ``ClientTrip.direct`` counts those steps and a row's
``direct`` column marks one.

``away_s`` runs from K2 to K3 (the wire, the server, the wire, this loop's
lateness on the ready socket); a caller of the async session has K6 = K5 and
K1 = K0; in a chain, K2 is the first hop's, K3/K4 the last hop's, and what
lies between one hop's reply read and the next hop's request written goes to
``relay_s``. The sums are a session's (``trace_report()["client"]``), and
every step leaves one row in :data:`STEP_RING`, bounded and process-wide, for
a reader that needs a slice of time (``perf/client_trip.py``). The loops' own
turn clocks are ``utils/asyncio_utils.install_turn_clock``.

All durations are perf_counter/monotonic deltas — never wall clock
(swarmlint ``no-naive-wallclock-in-span``).
"""

from __future__ import annotations

from collections import deque
from typing import List, Optional

COMPONENTS = ("network", "queue", "compute", "serialize", "other")

# a step's stretches in the order a row holds them, after ROW_HEAD
CLIENT_STRETCHES = ("away_s", "recv_s", "finish_s", "wake_s", "user_s", "submit_s", "build_s", "relay_s")
ROW_HEAD = ("read_at", "trace_id", "step", "hops", "tokens")  # K3, the session, its step's number, hops, tokens in
# direct: 1 for a step taken the direct way, 0 for a coroutine's; wrote: of its hops' frames, those the caller's thread wrote to the socket
ROW = (*ROW_HEAD, *CLIENT_STRETCHES, "direct", "wrote")
_WAKE, _USER, _SUBMIT, _BUILD = (ROW.index(name) for name in ("wake_s", "user_s", "submit_s", "build_s"))
# eight lanes' steps of a benchmark window (51 s of 6 ms gaps); a row is ~0.4 KB
STEP_RING_ROWS = 65536

# retired (failed-over / migrated-away) hop traces kept per session, so a
# report after a repair still accounts for time spent on the dead server
MAX_RETIRED_HOPS = 32


class HopTrace:
    """Accumulates one server span's per-step timing on the client side."""

    __slots__ = (
        "peer", "start_block", "end_block", "steps", "tokens",
        "wall_s", "server_s", "queue_s", "compute_s", "serialize_s",
        "meta_steps", "last_variant", "last_occupancy", "usage",
    )

    def __init__(self, peer: str, start_block: int, end_block: int):
        self.peer = peer
        self.start_block = start_block
        self.end_block = end_block
        self.steps = 0
        self.tokens = 0
        self.wall_s = 0.0  # client-observed send -> reply wall
        self.server_s = 0.0  # server-reported request residency (total_s)
        self.queue_s = 0.0
        self.compute_s = 0.0
        self.serialize_s = 0.0
        self.meta_steps = 0  # steps that carried step_meta
        self.last_variant: Optional[str] = None
        self.last_occupancy: Optional[dict] = None
        # server-billed resource usage (ledger deltas riding step_meta):
        # page_seconds / compute_seconds / tokens / swap bytes, summed
        self.usage: dict = {}

    def record(self, wall_s: float, meta: Optional[dict], tokens: int = 1) -> None:
        """Fold one step's client wall time and its (optional) server-side
        ``step_meta`` into the hop accumulators."""
        self.steps += 1
        self.tokens += max(int(tokens), 0)
        self.wall_s += max(float(wall_s), 0.0)
        if not meta:
            return
        self.meta_steps += 1
        q = float(meta.get("queue_s") or 0.0)
        c = float(meta.get("compute_s") or 0.0)
        z = float(meta.get("serialize_s") or 0.0)
        self.queue_s += q
        self.compute_s += c
        self.serialize_s += z
        # a server that reports components but no total still attributes them
        self.server_s += float(meta.get("total_s") or (q + c + z))
        if meta.get("variant"):
            self.last_variant = str(meta["variant"])
        usage = meta.get("usage")
        if isinstance(usage, dict):
            for field, amount in usage.items():
                if field in ("acceptance_rate", "tokens_per_compute_second"):
                    continue  # rates don't sum; re-derived from the counters
                try:
                    self.usage[field] = self.usage.get(field, 0) + float(amount)
                except (TypeError, ValueError):
                    continue  # a malformed server delta must not kill the step
        busy, wait = meta.get("busy_lanes"), meta.get("lane_waiters")
        if busy is not None or wait is not None:
            self.last_occupancy = {"busy_lanes": busy, "lane_waiters": wait}

    def components(self) -> dict:
        """Split this hop's client-observed wall into the five components.

        ``network`` is the residual between the client wall and the server's
        reported residency; server-side host work not covered by the three
        reported components lands in ``other``. Both are clamped at zero so
        scheduling jitter can't produce negative bars."""
        server = min(self.server_s, self.wall_s)
        network = max(self.wall_s - server, 0.0)
        known = self.queue_s + self.compute_s + self.serialize_s
        other = max(self.wall_s - network - known, 0.0)
        return {
            "network": network,
            "queue": self.queue_s,
            "compute": self.compute_s,
            "serialize": self.serialize_s,
            "other": other,
        }

    def queue_share(self) -> float:
        """Fraction of this hop's wall spent queue-waiting (routing blame)."""
        return self.queue_s / self.wall_s if self.wall_s > 0 else 0.0

    def to_dict(self) -> dict:
        comps = self.components()
        wall = self.wall_s or 1e-12
        return {
            "peer": self.peer,
            "blocks": [self.start_block, self.end_block],
            "steps": self.steps,
            "meta_steps": self.meta_steps,
            "tokens": self.tokens,
            "wall_s": round(self.wall_s, 6),
            "variant": self.last_variant,
            "occupancy": self.last_occupancy,
            "components": {k: round(v, 6) for k, v in comps.items()},
            "shares": {k: round(v / wall, 4) for k, v in comps.items()},
            "usage": self._usage_dict(),
        }

    def _usage_dict(self) -> dict:
        usage = {k: round(v, 6) for k, v in self.usage.items()}
        if usage.get("spec_proposed"):
            # speculative efficiency over the hop's whole stream, derived
            # from the summed counters (rates riding individual step_meta
            # deltas would not average correctly)
            from petals_tpu.telemetry.ledger import derive_efficiency

            usage.update(derive_efficiency(self.usage))
        return usage


class StepRing:
    """The newest ``STEP_RING_ROWS`` steps of every session of this process,
    oldest overwritten first: ``rows`` holds one list a step, laid out as
    ``ROW``, appended when the step's reply is in the caller's hands and
    filled in place as the later readings come (``wake_s``, then ``user_s`` /
    ``submit_s`` / ``build_s`` when the session's next request is written; a
    session's last step keeps None there). ``loop_clock`` is the turn clock of
    the loop that ran the newest session's steps, if it has one."""

    def __init__(self, rows: int = STEP_RING_ROWS):
        self.rows: deque = deque(maxlen=rows)
        self.loop_clock = None


STEP_RING = StepRing()


class ClientTrip:
    """One session's stations of a step (the module docstring's table).
    ``_t`` is the last reading: every call closes the stretch since it, so
    the stretches tile the session's time whatever happened in between (a
    retry's back-off lands in the stretch that was open). One step at a time
    touches a trip, on the caller's thread (K0, K6; every station of a direct
    step) or the loop's."""

    __slots__ = ("trace_id", "ring", "steps", "turns", "direct", "wrote", "deferred", "sums", "_t", "_row", "_entered", "_user", "_submit",
                 "_hops", "_away", "_relay", "_held_at")

    def __init__(self, trace_id: Optional[str], ring: Optional[StepRing] = None):
        self.trace_id = trace_id
        self.ring = STEP_RING if ring is None else ring
        self.steps = 0  # replies handed to the caller
        self.turns = 0  # of them, those a next request followed: what user_s, submit_s and build_s were summed over
        self.direct = 0  # of them, those the caller's thread exchanged itself (InferenceSession.step_from_thread)
        # of the direct steps' frames (one a hop): written to the socket by that thread; left to the loop to write
        self.wrote = self.deferred = 0
        self.sums = dict.fromkeys(CLIENT_STRETCHES, 0.0)
        self._t: Optional[float] = None
        self._row: Optional[list] = None  # the last step's row, until the next request is written
        self._entered = False
        self._user = self._submit = 0.0
        self._hops, self._away, self._relay, self._held_at = 0, 0.0, 0.0, 0.0

    def entered(self, now: float) -> None:
        """K0, on the caller's thread."""
        if self._row is not None:
            self._user, self._t = now - self._t, now
        self._entered = True

    def on_loop(self, now: float) -> None:
        """K1: on the loop, or on the caller's thread where a direct step's build begins.
        A caller that never passed K0 is on the loop already: K6 = K5, K0 = K1."""
        row = self._row
        if row is not None:
            if self._entered:
                self._submit = now - self._t
            else:
                row[_WAKE], self._user, self._submit = 0.0, now - self._t, 0.0
        self._t, self._entered = now, False
        self._hops, self._away, self._relay = 0, 0.0, 0.0

    def hop(self, sent_at: float, read_at: float, held_at: float) -> None:
        """One hop's K2, K3 and K4, as its ``_ServerInferenceSession`` read them."""
        if self._hops:
            self._relay += sent_at - self._t
        else:
            row = self._row
            if row is not None:  # K2 closes the turn after the step before
                row[_USER], row[_SUBMIT], row[_BUILD] = self._user, self._submit, sent_at - self._t
                self.sums["user_s"] += row[_USER]
                self.sums["submit_s"] += row[_SUBMIT]
                self.sums["build_s"] += row[_BUILD]
                self.turns += 1
                self._row = None
        self._away += read_at - sent_at
        self._t, self._held_at = read_at, held_at
        self._hops += 1

    def finished(self, now: float, tokens: int, direct: bool = False, wrote: int = 0) -> None:
        """K5: the step's row goes into the ring. ``wrote``: of a direct step's
        frames, those its thread wrote to the socket itself."""
        if not self._hops:
            return
        recv, finish = self._held_at - self._t, now - self._held_at
        row = [self._t, self.trace_id, self.steps, self._hops, tokens,
               self._away, recv, finish, None, None, None, None, self._relay, int(direct), wrote]
        sums = self.sums
        sums["away_s"] += self._away
        sums["recv_s"] += recv
        sums["finish_s"] += finish
        sums["relay_s"] += self._relay
        self.steps += 1
        if direct:
            self.direct += 1
            self.wrote += wrote
            self.deferred += self._hops - wrote
        self.ring.rows.append(row)
        self._row, self._t = row, now

    def woke(self, now: float) -> None:
        """K6, on the caller's thread."""
        row = self._row
        if row is not None:
            row[_WAKE] = now - self._t
            self.sums["wake_s"] += row[_WAKE]
            self._t = now

    def interrupt(self) -> None:
        """The session did something the stations do not follow (a server-side
        generation): the open turn is dropped and counted nowhere."""
        self._row, self._entered = None, False

    def report(self) -> dict:
        return {**{k: round(v, 6) for k, v in self.sums.items()}, "steps": self.steps, "turns": self.turns,
                "direct": self.direct, "wrote": self.wrote, "deferred": self.deferred}


def build_trace_report(
    trace_id: Optional[str],
    hops: List[HopTrace],
    *,
    wall_s: float,
    steps: int,
    tokens: int,
    retired_hops: int = 0,
    client: Optional[dict] = None,
) -> dict:
    """Assemble the per-request waterfall: per-hop component splits, swarm
    totals (client-side overhead folded into ``other``), and the single
    (hop, component) pair that dominates — the critical path."""
    hop_dicts = [h.to_dict() for h in hops]
    totals = {k: 0.0 for k in COMPONENTS}
    for h in hops:
        for k, v in h.components().items():
            totals[k] += v
    hops_wall = sum(h.wall_s for h in hops)
    # time the session spent outside any hop RPC: client-side compute
    # (sampling, embedding), inter-hop scheduling, retry backoff
    client_s = max(wall_s - hops_wall, 0.0)
    totals["other"] += client_s

    critical = None
    best = -1.0
    denom = wall_s if wall_s > 0 else 1e-12
    for h in hops:
        for comp, v in h.components().items():
            if v > best:
                best = v
                critical = {
                    "peer": h.peer,
                    "blocks": [h.start_block, h.end_block],
                    "component": comp,
                    "seconds": round(v, 6),
                    "share": round(v / denom, 4),
                }

    attributed = sum(totals.values())
    report = {
        "trace_id": trace_id,
        "steps": steps,
        "tokens": tokens,
        "wall_s": round(wall_s, 6),
        "client_s": round(client_s, 6),
        "retired_hops": retired_hops,
        "hops": hop_dicts,
        "totals": {k: round(v, 6) for k, v in totals.items()},
        "critical_path": critical,
        "attributed_fraction": round(attributed / denom, 4) if wall_s > 0 else 0.0,
    }
    if client is not None:
        report["client"] = client  # ClientTrip.report(): the client's own stations, summed
    return report


_BAR_CHARS = {"network": "~", "queue": ".", "compute": "#", "serialize": "=", "other": " "}


def format_waterfall(report: dict, width: int = 48) -> str:
    """Render a trace report as a fixed-width ASCII waterfall (one bar per
    hop, scaled to the session wall) — the ``run_health --waterfall`` view."""
    wall = float(report.get("wall_s") or 0.0) or 1e-12
    lines = [
        f"trace {report.get('trace_id') or '?'} · {report.get('steps', 0)} steps "
        f"· {report.get('tokens', 0)} tokens · {wall:.3f} s wall"
    ]
    for hop in report.get("hops", ()):
        comps = hop.get("components", {})
        hop_wall = float(hop.get("wall_s") or 0.0)
        bar = []
        for comp in COMPONENTS:
            n = int(round(width * float(comps.get(comp, 0.0)) / wall))
            bar.append(_BAR_CHARS[comp] * n)
        blocks = hop.get("blocks") or ["?", "?"]
        shares = hop.get("shares", {})
        detail = " ".join(
            f"{comp[:3]} {100.0 * float(shares.get(comp, 0.0)):.0f}%"
            for comp in COMPONENTS
            if float(comps.get(comp, 0.0)) > 0
        )
        lines.append(
            f"  blocks [{blocks[0]},{blocks[1]}) {str(hop.get('peer', '?'))[:12]:<12} "
            f"|{''.join(bar):<{width}}| {hop_wall:.3f}s  {detail}"
        )
    crit = report.get("critical_path")
    if crit:
        lines.append(
            f"  critical path: {crit['component']} on {str(crit['peer'])[:12]} "
            f"blocks [{crit['blocks'][0]},{crit['blocks'][1]}) — "
            f"{crit['seconds']:.3f}s ({100.0 * crit['share']:.0f}% of wall)"
        )
    totals = report.get("totals")
    if totals:
        lines.append(
            "  totals: "
            + "  ".join(f"{k} {float(totals.get(k, 0.0)):.3f}s" for k in COMPONENTS)
            + f"  (attributed {100.0 * float(report.get('attributed_fraction', 0.0)):.0f}%)"
        )
    client = report.get("client")
    if client and client.get("steps"):
        lines.append(
            "  client: "
            + "  ".join(f"{k[:-2]} {float(client.get(k, 0.0)):.3f}s" for k in CLIENT_STRETCHES)
            + f"  ({client['steps']} steps, {client.get('turns', 0)} followed, {client.get('direct', 0)} direct)"
        )
    legend = "  legend: " + "  ".join(f"{c}={k}" for k, c in _BAR_CHARS.items() if k != "other")
    lines.append(legend)
    return "\n".join(lines)


__all__ = [
    "CLIENT_STRETCHES",
    "COMPONENTS",
    "MAX_RETIRED_HOPS",
    "ROW",
    "STEP_RING",
    "ClientTrip",
    "HopTrace",
    "StepRing",
    "build_trace_report",
    "format_waterfall",
]
