"""``turnaround_s`` per batched step of the traced window (the space between two
``ptu.step`` annotations of one flush task): result to the event loop, futures set,
the next ``queue.submit``, the compute thread's wake-up, with work pending."""
from perf import step_phases

UNIT, LAYER, MOVES = "ms", "batcher (server/batching.py)", "gap_p50_ms"


def read(record):
    return step_phases.per_step_ms(record, "turnaround_s")
