"""How late the load generator sent a session, against its due time."""
from perf.record import percentile

UNIT, LAYER, MOVES = "ms", "load generator (perf/)", "gap_p50_ms"


def read(record):
    return percentile([s.late_s * 1e3 for s in record.counted() if s.late_s is not None], 95)
