"""``step_meta.queue_s`` of a decode step, per hop (mean): from the batcher's
enqueue to the start of the step that carried it."""
from perf.record import percentile

UNIT, LAYER, MOVES = "ms", "batcher (server/batching.py)", "gap_p50_ms"


def read(record):
    values = record.hop_part_ms("decode", 2)
    return float(values.mean()) if len(values) else None
