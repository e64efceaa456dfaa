"""What the thirteen readers of the round trip's other half share (``layer_metrics/client_*_ms.py``,
``wire_and_loops_ms.py``, ``*_loop_busy_share.py``, ``*_loop_late_ms.py``).

Since PR 54 the client times its own stations of a step (``petals_tpu/telemetry/spans.py``: seven
readings of ``time.perf_counter`` tile a session's time from one request written to the next) and
leaves one row a step in a bounded ring of its process, which is this process: the load generator.
A row holds K3 (the reply's frame read whole), the session's trace id, the step's number, hops and
tokens in, then ``away_s`` (request written to reply read: the wire, the server, the wire, the
loop's lateness) and, after K3, ``recv_s``, ``finish_s``, ``wake_s``, ``user_s``, ``submit_s`` and
``build_s`` up to the session's next request written, and ``relay_s`` (between two hops; 0 on one).
A session's last step has None in the last three.

Both event loops time their turns (``petals_tpu/utils/asyncio_utils.py``): ``loop_busy_s``,
``loop_busy_sq`` and ``loop_turns``, on the server keys of ``batcher.stats`` (so in every mark of
the child's dump), on the client sampled about every 0.1 s beside the ring.

Every reader takes the decode steps of one hop and one token whose K3 lies between the children's
marks ``trace_start`` and ``trace_stop`` (``perf_counter`` is one clock for the parent and the
children), the slice ``off_server_ms`` and ``device_idle_share`` describe, and splits the gap the
load generator times for each, from the caller holding the reply before (K6) to the caller holding
this one: ``user_s``, ``submit_s`` and ``build_s`` of the row of the session's step BEFORE it (a
prompt's reply before a first decode step too), then the reply's own ``away_s``, ``recv_s``,
``finish_s`` and ``wake_s``. So the seven means tile the slice's mean decode gap, and a prompt's
reply, which takes long to unpack, is in no decode gap. A program without the ring or the counters,
or a run without the marks, gives None.
"""

from __future__ import annotations

from typing import Optional

from perf import round_trip
from perf.step_phases import END, START

AFTER = ("recv_s", "finish_s", "wake_s")  # a reply's frame read to the caller holding it, K3 to K6
BEFORE = ("user_s", "submit_s", "build_s")  # the caller holding the reply before to this one's request written, K6 to K2
TURN = (*AFTER, *BEFORE)  # reply read to next request written
LOOP = ("loop_busy_s", "loop_busy_sq", "loop_turns")


def _spans():
    from petals_tpu.telemetry import spans

    return spans


def slice_of(record) -> Optional[tuple]:
    """(start, stop) on ``perf_counter``: the stretch every child's two marks span."""
    if not record.children:
        return None
    marks = [child.get("marks") or {} for child in record.children]
    if any(START not in m or END not in m for m in marks):
        return None
    lo, hi = max(m[START]["mono"] for m in marks), min(m[END]["mono"] for m in marks)
    return (lo, hi) if hi > lo else None


def _rows(record) -> Optional[tuple]:
    """(the slice's decode rows, the rows of the steps before them by (trace id, step)), as dicts by ``spans.ROW``."""
    spans, span = _spans(), slice_of(record)
    ring = getattr(spans, "STEP_RING", None)
    if ring is None or span is None:
        return None
    lo, hi = span
    at, tid, step, hops, tokens = (spans.ROW.index(name) for name in ("read_at", "trace_id", "step", "hops", "tokens"))
    held = list(ring.rows)
    taken = [row for row in held if row[hops] == 1 and row[tokens] == 1 and lo <= row[at] <= hi]
    wanted = {(row[tid], row[step] - 1) for row in taken}
    before = {(row[tid], row[step]): dict(zip(spans.ROW, row)) for row in held if (row[tid], row[step]) in wanted}
    return [dict(zip(spans.ROW, row)) for row in taken], before


def steps(record) -> Optional[list]:
    """The slice's rows of one hop and one token in: its decode replies."""
    rows = _rows(record)
    return None if rows is None else rows[0]


def _stretch_rows(rows: tuple, stretch: str) -> list:
    """The rows that hold ``stretch``'s part of the slice's decode gaps: the reply's own for K3 to K6, the
    row of the session's step before it for K6 to K2 (a prompt's reply before a first decode step too;
    where the ring still holds it and it was one hop's)."""
    taken, before = rows
    if stretch in BEFORE:
        taken = (before.get((r["trace_id"], r["step"] - 1)) for r in taken)
        taken = [r for r in taken if r is not None and r["hops"] == 1]
    return [r for r in taken if r[stretch] is not None]


def _mean_ms(rows: Optional[list], column: str) -> Optional[float]:
    return 1e3 * sum(r[column] for r in rows) / len(rows) if rows else None


def stretch_ms(record, stretch: str) -> Optional[float]:
    """One of the six stretches' mean a decode gap of the slice."""
    rows = _rows(record)
    return None if rows is None else _mean_ms(_stretch_rows(rows, stretch), stretch)


def turn_ms(record) -> Optional[float]:
    """The six means summed, as the six readers give them."""
    rows = _rows(record)
    parts = [None] if rows is None else [_mean_ms(_stretch_rows(rows, stretch), stretch) for stretch in TURN]
    return None if None in parts else sum(parts)


def away_ms(record) -> Optional[float]:
    return _mean_ms(steps(record), "away_s")


def wire_and_loops_ms(record) -> Optional[float]:
    off, turn = round_trip.off_server_ms(record), turn_ms(record)
    return None if off is None or turn is None else off - turn


def server_loop(record) -> Optional[dict]:
    """The three sums' differences and ``elapsed_s`` between the two marks, summed over the children."""
    if slice_of(record) is None:
        return None
    out = dict.fromkeys((*LOOP, "elapsed_s"), 0.0)
    for child in record.children:
        lo, hi = child["marks"][START], child["marks"][END]
        if any(key not in lo["stats"] or key not in hi["stats"] for key in LOOP):
            return None
        for key in LOOP:
            out[key] += hi["stats"][key] - lo["stats"][key]
        out["elapsed_s"] += hi["mono"] - lo["mono"]
    return out if out["loop_turns"] > 0 else None  # a loop without a clock counts no turn


def client_loop(record) -> Optional[dict]:
    """The same from the two samples nearest the marks of the clock of the loop that ran the steps."""
    span = slice_of(record)
    clock = getattr(getattr(_spans(), "STEP_RING", None), "loop_clock", None)
    samples = list(clock.samples or ()) if clock is not None else []
    if span is None or not samples or samples[0][0] > span[0]:  # no clock, or its samples no longer reach back
        return None
    lo, hi = (min(samples, key=lambda s: abs(s[0] - mark)) for mark in span)
    out = {key: b - a for key, a, b in zip(LOOP, lo[1:], hi[1:])}
    out["elapsed_s"] = hi[0] - lo[0]
    return out if out["elapsed_s"] > 0 and out["loop_turns"] > 0 else None


def busy_share(loop: Optional[dict]) -> Optional[float]:
    return 100.0 * loop["loop_busy_s"] / loop["elapsed_s"] if loop else None


def late_ms(loop: Optional[dict]) -> Optional[float]:
    """What a socket that became ready at a moment unrelated to the loop's phase waited, on average."""
    return 1e3 * loop["loop_busy_sq"] / (2.0 * loop["elapsed_s"]) if loop else None
