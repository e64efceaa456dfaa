"""Admission to first compute of a prompt (``step_meta.queue_s``), p90."""
from perf.record import percentile

UNIT, LAYER, MOVES = "ms", "batcher (server/batching.py)", "gap_p50_ms"


def read(record):
    values = record.hop_part_ms("prefill", 2)
    return percentile(values, 90)
