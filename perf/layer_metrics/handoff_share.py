"""Share of the traced window in which work was there and the host was in the way
(``handoff_s``): futures, the flush task's spawn, ``queue.submit``, the compute thread's
wake-up, whichever flush task carried it (what ``turnaround_s`` counted until PR 25)."""
from perf import round_trip

UNIT, LAYER, MOVES = "%", "batcher (server/batching.py)", "gap_p50_ms"


def read(record):
    return round_trip.share_of_window(record, "handoff_s")
