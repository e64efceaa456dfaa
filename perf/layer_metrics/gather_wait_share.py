"""Share of the traced window that the gather in front of a step chose to wait for lanes
on their way back (``gather_wait_s``)."""
from perf import round_trip

UNIT, LAYER, MOVES = "%", "batcher (server/batching.py)", "gap_p50_ms"


def read(record):
    return round_trip.share_of_window(record, "gather_wait_s")
