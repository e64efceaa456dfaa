"""``post_s`` per batched step of the traced window (annotation ``ptu.step.post``): from
the output's arrival to the body's return: reset lock and pool swap, stats, the
``tm.*`` instruments, step timing, fingerprints, the ledger."""
from perf import step_phases

UNIT, LAYER, MOVES = "ms", "batcher (server/batching.py)", "gap_p50_ms"


def read(record):
    return step_phases.per_step_ms(record, "post_s")
