"""``reply_wake_s`` a step that replied to a decode lane (``reply_steps``): the step body's
return on the compute thread to the flush loop, on the event loop, resolving the lanes'
futures (annotation ``ptu.flush.resolve`` marks the resolving itself)."""
from perf import round_trip

UNIT, LAYER, MOVES = "ms", "batcher (server/batching.py)", "gap_p50_ms"


def read(record):
    return round_trip.mean_ms(record, "reply_wake_s")
