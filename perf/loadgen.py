"""Replays a schedule through the normal client and records what a user of
the swarm would see. One process, one thread a session in flight; every time
is ``time.perf_counter()`` (one clock for the parent and the server children).

A session is timed from when it was DUE, not from when its thread got to run,
and how late it was sent is recorded beside it (``late_s``): a starved
generator must not read as a fast server.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import List, Optional

import numpy as np

from perf import traffic


HOP_PARTS = ("network", "queue", "compute", "serialize", "other")  # telemetry/spans.py's split of a hop's wall


@dataclasses.dataclass
class SessionRecord:
    plan: traffic.Plan
    due: float  # absolute perf_counter
    counted: bool  # due inside the window
    sent: Optional[float] = None
    first_reply: Optional[float] = None  # reply to the prompt
    replies: list = dataclasses.field(default_factory=list)  # (t_reply, position) of every decode step
    hops: list = dataclasses.field(default_factory=list)  # traced: (kind, n_hops, *HOP_PARTS in seconds) per step
    done: Optional[float] = None
    error: Optional[str] = None

    @property
    def late_s(self) -> Optional[float]:
        return None if self.sent is None else self.sent - self.due


class Replay:
    """``remote`` is the client's ``RemoteSequential``; ``pool`` the input pool."""

    def __init__(self, remote, pool: np.ndarray, *, traced: bool, hidden: int):
        self.remote, self.pool, self.traced, self.hidden = remote, pool, traced, hidden
        self.records: List[SessionRecord] = []
        self._lock = threading.Lock()
        self._threads: List[threading.Thread] = []
        self.stop_opening = threading.Event()

    # ------------------------------------------------------------ one session

    def _hop_totals(self, session) -> tuple:
        report = session._session.trace_report()  # PERF.md section 7: wants a public accessor
        comps = [hop["components"] for hop in report["hops"]]
        return (len(comps),) + tuple(sum(c[k] for c in comps) for k in HOP_PARTS)

    def _note_hops(self, rec: SessionRecord, session, kind: str, before: tuple) -> tuple:
        now = self._hop_totals(session)
        rec.hops.append((kind, now[0]) + tuple(b - a for a, b in zip(before[1:], now[1:])))
        return now

    def _check(self, out: np.ndarray, n: int) -> None:
        if out.shape != (1, n, self.hidden):
            raise RuntimeError(f"reply of shape {out.shape}, wanted {(1, n, self.hidden)}")
        if not np.isfinite(out).all():
            raise RuntimeError("reply holds a non-finite value")

    def run_session(self, rec: SessionRecord) -> None:
        plan = rec.plan
        try:
            rec.sent = time.perf_counter()
            with self.remote.inference_session(max_length=plan.max_length) as session:
                totals = (0,) + (0.0,) * len(HOP_PARTS)
                chunk = traffic.prompt_rows(self.pool, plan)
                out = np.asarray(session.step(chunk))
                rec.first_reply = time.perf_counter()
                self._check(out, chunk.shape[1])
                position = chunk.shape[1]
                if self.traced:
                    totals = self._note_hops(rec, session, "prefill", totals)
                for t in range(plan.output):
                    step_in = traffic.rows(self.pool, plan.decode_offset + t, 1)[None]
                    out = np.asarray(session.step(step_in))
                    now = time.perf_counter()
                    self._check(out, 1)
                    position += 1
                    rec.replies.append((now, position))
                    if self.traced:
                        totals = self._note_hops(rec, session, "decode", totals)
            rec.done = time.perf_counter()
        except Exception as e:  # counted against the session, never lost
            rec.error = repr(e)

    # ------------------------------------------------------------ the schedule

    def _spawn(self, target, *args) -> None:
        thread = threading.Thread(target=target, args=args, daemon=True)
        thread.start()
        self._threads.append(thread)

    def _record(self, plan: traffic.Plan, due: float, counted: bool) -> SessionRecord:
        rec = SessionRecord(plan=plan, due=due, counted=counted)
        with self._lock:
            self.records.append(rec)
        return rec

    def _sleep_until(self, t: float) -> None:
        delay = t - time.perf_counter()
        if delay > 0:
            time.sleep(delay)

    def _client(self, plans, start: float, t0: float, t_end: float) -> None:
        self._sleep_until(start)
        due = start
        for plan in plans:
            if self.stop_opening.is_set() or due >= t_end:
                return
            rec = self._record(plan, due, counted=due >= t0)
            self.run_session(rec)
            if rec.error is not None:
                time.sleep(0.2)  # a failing server must not spin the client
            due = time.perf_counter()  # the next session is due when the last ended

    def run(self, sched: traffic.Schedule, t0: float, seconds: float, drain_s: float) -> None:
        """Blocks until the window is over and the sessions have drained (or
        ``drain_s`` more seconds have passed)."""
        t_end = t0 + seconds
        if sched.kind == "closed":
            for plans, start in zip(sched.client_plans, sched.client_starts):
                self._spawn(self._client, plans, t0 + start, t0, t_end)
        else:
            for plan in sched.open_plans:
                due = t0 + plan.due
                self._sleep_until(due)
                rec = self._record(plan, due, counted=plan.due >= 0)
                self._spawn(self.run_session, rec)
        self._sleep_until(t_end)
        self.stop_opening.set()
        deadline = t_end + drain_s
        for thread in self._threads:
            thread.join(max(0.0, deadline - time.perf_counter()))
