"""What the twelve round-trip readers share (``layer_metrics/lane_return_ms.py``,
``reply_*_ms.py``, ``rpc_*_ms.py``, ``request_handle_ms.py``, ``off_server_ms.py`` and
the four idle shares ``lanes_out_share.py``, ``gather_wait_share.py``,
``handoff_share.py``, ``no_demand_share.py``).

Since PR 37 the server times its own share of a decode token's way out and back, every
reading ``time.perf_counter`` in the server process, summed in ``batcher.stats``:

- a step that replied to a decode lane (``reply_steps``): ``reply_wake_s``, the step
  body's return on the compute thread to the flush loop's resolving of the futures;
- a decode reply (``decode_replies``): ``reply_resume_s`` from there to the lane's
  handler running again, ``reply_build_s`` to the reply yielded to the RPC server,
  ``rpc_send_s`` to its frame packed, written and drained;
- a lane that came back (``lane_returns``): ``rpc_recv_s`` from its request's frame read
  whole to the handler holding the item, ``request_handle_s`` to ``batcher.step``
  entered, and ``lane_return_s``, the whole trip from the resolving to that entry.

The five stretches between the resolving and the entry are the server's; what
``lane_return_s`` holds beyond them is the wire and the client (``off_server_ms``).
They are one lane's latency, averaged, and eight lanes' stretches overlap in time.

Apart from that, four counters say what the compute thread, and so the chip, waited for
between two step bodies: ``lanes_out_s`` (nothing to run, a decode reply out),
``no_demand_s`` (nothing to run, none out), ``gather_wait_s`` (the gather's choice) and
``handoff_s`` (work there, the host in the way). With the four phase clocks of
``perf/step_phases.py`` they tile that thread's wall.

Like the step-phase readers, each takes a counter's difference between the marks
``trace_start`` and ``trace_stop``, summed over the children; a program without the
counters, or a run without the marks, gives None.
"""

from __future__ import annotations

from typing import Optional

from perf.step_phases import END, PHASES, START

SERVER = ("reply_resume_s", "reply_build_s", "rpc_send_s", "rpc_recv_s", "request_handle_s")  # the resolving to step()'s entry, less the wire and the client
IDLE = ("lanes_out_s", "no_demand_s", "gather_wait_s", "handoff_s")
TILES = (*PHASES, *IDLE)  # the compute thread's wall
COUNTED_BY = {  # seconds: the events they were summed over
    "reply_wake_s": "reply_steps",
    "reply_resume_s": "decode_replies", "reply_build_s": "decode_replies", "rpc_send_s": "decode_replies",
    "rpc_recv_s": "lane_returns", "request_handle_s": "lane_returns", "lane_return_s": "lane_returns",
}


def totals(record) -> Optional[dict]:
    """Each counter's difference and ``window_s`` between the two marks, summed over the children."""
    if not record.children:
        return None
    counters = (*TILES, *COUNTED_BY, *sorted(set(COUNTED_BY.values())))
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


def _mean_ms(t: Optional[dict], seconds: str) -> Optional[float]:
    events = t[COUNTED_BY[seconds]] if t else 0
    return 1e3 * t[seconds] / events if events > 0 else None


def mean_ms(record, seconds: str) -> Optional[float]:
    """Milliseconds of ``seconds`` an event it was summed over."""
    return _mean_ms(totals(record), seconds)


def off_server_ms(record) -> Optional[float]:
    t = totals(record)
    parts = [_mean_ms(t, seconds) for seconds in ("lane_return_s", *SERVER)]
    return None if None in parts else parts[0] - sum(parts[1:])


def share_of_window(record, clock: str) -> Optional[float]:
    t = totals(record)
    return 100.0 * t[clock] / t["window_s"] if t and t["window_s"] > 0 else None
