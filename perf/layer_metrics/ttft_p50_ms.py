"""As ``ttft_p90_ms``, the median: the steadier statistic beside the tail."""
from perf.layer_metrics.ttft_p90_ms import ttft_percentile

UNIT, LAYER, MOVES = "ms", "service (due time to reply, perf/loadgen.py)", "gap_p50_ms"


def read(record):
    return ttft_percentile(record, 50)
