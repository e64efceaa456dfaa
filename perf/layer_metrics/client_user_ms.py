"""``user_s`` a step: ``SyncInferenceSession.step`` returned (K6) to entered again (K0): the application's own time
between two steps, here ``perf/loadgen.py``'s ``_check``, ``trace_report()`` when traced and the row lookup. Mean over the turns that led to the traced slice's
one-hop decode steps: the row of the session's step before each (``perf/client_trip.py``)."""
from perf import client_trip

UNIT, LAYER, MOVES = "ms", "load generator (perf/)", "gap_p50_ms"


def read(record):
    return client_trip.stretch_ms(record, "user_s")
