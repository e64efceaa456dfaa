"""Client wall minus server residency of a decode step, per hop (mean)."""
from perf.record import percentile

UNIT, LAYER, MOVES = "ms", "client + RPC (client/, rpc/)", "gap_p50_ms"


def read(record):
    values = record.hop_part_ms("decode", 1)
    return float(values.mean()) if len(values) else None
