"""The handler's reply serialization of a decode step, per hop (mean)."""
from perf.record import percentile

UNIT, LAYER, MOVES = "ms", "handler (server/handler.py)", "gap_p50_ms"


def read(record):
    values = record.hop_part_ms("decode", 4)
    return float(values.mean()) if len(values) else None
