"""Share of the traced window in which the compute thread had a step to run and was
not waiting on the device: ``assemble_s + dispatch_s + post_s + turnaround_s`` over the
window. With ``no_work_share`` and the share of ``wait_s`` it makes 100."""
from perf import step_phases

UNIT, LAYER, MOVES = "%", "batcher (server/batching.py)", "gap_p50_ms"


def read(record):
    return step_phases.share_of_window(record, step_phases.HOST)
