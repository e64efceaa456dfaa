"""``request_handle_s`` a lane that came back: the handler holding the item to
``batcher.step`` entered: checks, ``deserialize_array``, validation (no annotation: the
stretch holds conditional awaits)."""
from perf import round_trip

UNIT, LAYER, MOVES = "ms", "handler (server/handler.py)", "gap_p50_ms"


def read(record):
    return round_trip.mean_ms(record, "request_handle_s")
