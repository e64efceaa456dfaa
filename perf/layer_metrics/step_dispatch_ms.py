"""``dispatch_s`` per batched step of the traced window (annotation ``ptu.step.dispatch``):
the backend call until it returns: kernel-path choice, host-to-device copies, the
jit dispatch. Asynchronous: the device starts inside it."""
from perf import step_phases

UNIT, LAYER, MOVES = "ms", "step programs (server/backend.py)", "gap_p50_ms"


def read(record):
    return step_phases.per_step_ms(record, "dispatch_s")
