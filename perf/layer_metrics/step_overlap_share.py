"""Share of the batched steps of the traced window that were launched while the step
before them was still in flight (``overlapped_steps`` of ``batched_steps``,
``batcher.stats``; ``DecodeBatcher._start_behind``): how often the host's part of a
step was paid beside another step's time on the device and not after it. A program
from before the counter gives None."""
UNIT, LAYER, MOVES = "%", "batcher (server/batching.py)", "gap_p50_ms"


def read(record):
    try:
        share = record.ratio_over_children("overlapped_steps", "batched_steps", start="trace_start", end="trace_stop")
    except KeyError:  # a program from before the counter
        return None
    return None if share is None else 100.0 * share
