"""What is left of a decode step's wall per hop (mean) after network, queue,
compute and serialize: the handler's and the event loop's own work."""
from perf.record import percentile

UNIT, LAYER, MOVES = "ms", "handler (server/handler.py)", "gap_p50_ms"


def read(record):
    values = record.hop_part_ms("decode", 5)
    return float(values.mean()) if len(values) else None
