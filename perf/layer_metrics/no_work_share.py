"""Share of the traced window in which the batcher had nothing to run: 100 less the
share of all five clocks. A step that straddles a mark is counted where it ends, so
a full server can read a fraction of a percent under 0."""
from perf import step_phases

UNIT, LAYER, MOVES = "%", "batcher (server/batching.py)", "gap_p50_ms"


def read(record):
    busy = step_phases.share_of_window(record, step_phases.CLOCKS)
    return None if busy is None else 100.0 - busy
