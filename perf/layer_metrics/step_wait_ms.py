"""``wait_s`` per batched step of the traced window (annotation ``ptu.step.wait``): the
compute thread blocked in ``np.asarray(out)`` until the step has run and its output is
on the host."""
from perf import step_phases

UNIT, LAYER, MOVES = "ms", "device", "gap_p50_ms"


def read(record):
    return step_phases.per_step_ms(record, "wait_s")
