"""The stall a decode step suffers when a prompt chunk rides its step or a
lane waits: 95th percentile of the gaps ``gap_p50_ms`` takes the median of.
Without a bound: it spread by 5-8% between runs, so no bound up to 10% holds."""
from perf.record import percentile

UNIT, LAYER, MOVES = "ms", "service (due time to reply, perf/loadgen.py)", "gap_p50_ms"


def read(record):
    return percentile(record.gaps_ms(), 95)
