"""From when a session was due to the reply to its prompt: session open,
routing, lane wait and prefill; 90th percentile over the sessions due inside
the window. A session that failed or had no reply by the end of the drain
counts with the time it had waited by then. Recorded without a bound: a
window at 0.8 of a knee near one session a second holds ~41 sessions, four of
them beyond this percentile, and it spread by 70-210% between runs (PERF.md
section 2)."""
from perf.record import percentile

UNIT, LAYER, MOVES = "ms", "service (due time to reply, perf/loadgen.py)", "gap_p50_ms"


def ttft_percentile(record, q: float):
    waits = [((s.first_reply if s.first_reply is not None else record.t_drained) - s.due) * 1e3
             for s in record.counted()]
    return percentile(waits, q)


def read(record):
    return ttft_percentile(record, 90)
