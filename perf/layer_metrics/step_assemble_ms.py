"""``assemble_s`` per batched step of the traced window (annotation ``ptu.step.assemble``):
a step body's entry to the backend call: the generation guard, ``np.zeros``, the
per-lane copies into hidden and positions, the table snapshot, the chunk slice."""
from perf import step_phases

UNIT, LAYER, MOVES = "ms", "batcher (server/batching.py)", "gap_p50_ms"


def read(record):
    return step_phases.per_step_ms(record, "assemble_s")
