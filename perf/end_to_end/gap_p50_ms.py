"""Median reply-to-reply time of consecutive decode steps of one session."""
from perf.record import percentile

UNIT = "ms"


def read(record):
    return percentile(record.gaps_ms(), 50)
