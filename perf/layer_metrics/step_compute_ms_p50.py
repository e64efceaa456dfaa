"""``step_meta.compute_s`` of a decode step, per hop: the batched step's wall
on the server's compute thread, which ends in a device sync."""
from perf.record import percentile

UNIT, LAYER, MOVES = "ms", "step programs (server/backend.py)", "gap_p50_ms"


def read(record):
    values = record.hop_part_ms("decode", 3)
    return percentile(values, 50)
