"""What the seven step-phase readers share (``layer_metrics/step_*_ms.py``,
``host_serial_share.py``, ``no_work_share.py``).

``DecodeBatcher`` keeps five cumulative clocks in ``batcher.stats``, all taken
on the compute thread: ``assemble_s``, ``dispatch_s``, ``wait_s`` and
``post_s`` split every batched step's body from its entry to its return (the
same boundaries are ``ptu.step.*`` annotations in a profiler trace), and
``turnaround_s`` is the time between two bodies where the flush task stayed
alive in between, the hand-off with work pending. The server child copies
``batcher.stats`` under every mark, so a reader takes each clock's difference
between ``trace_start`` and ``trace_stop``, summed over the children, over
the steps (``batched_steps``) or the window (the marks' ``mono``) between the
same two marks: the slice that ``device_idle_share`` describes. A program
without the clocks, or a run without the marks, gives None.
"""

from __future__ import annotations

from typing import Optional

PHASES = ("assemble_s", "dispatch_s", "wait_s", "post_s")
CLOCKS = (*PHASES, "turnaround_s")
HOST = ("assemble_s", "dispatch_s", "post_s", "turnaround_s")  # a step to run, and not waiting on the device
START, END = "trace_start", "trace_stop"


def totals(record) -> Optional[dict]:
    """Each clock's seconds, ``batched_steps`` and ``window_s`` between the
    two marks, summed over the children."""
    if not record.children:
        return None
    counters = (*CLOCKS, "batched_steps")
    out = dict.fromkeys((*counters, "window_s"), 0.0)
    for child in record.children:
        marks = child.get("marks") or {}
        if START not in marks or END not in marks:
            return None
        lo, hi = marks[START], marks[END]
        for counter in counters:
            if counter not in lo["stats"] or counter not in hi["stats"]:
                return None
            out[counter] += hi["stats"][counter] - lo["stats"][counter]
        out["window_s"] += hi["mono"] - lo["mono"]
    return out


def per_step_ms(record, clock: str) -> Optional[float]:
    t = totals(record)
    return 1e3 * t[clock] / t["batched_steps"] if t and t["batched_steps"] > 0 else None


def share_of_window(record, clocks) -> Optional[float]:
    """Percent of the window that the named clocks ran."""
    t = totals(record)
    return 100.0 * sum(t[c] for c in clocks) / t["window_s"] if t and t["window_s"] > 0 else None
