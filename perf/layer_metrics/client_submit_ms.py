"""``submit_s`` a step: ``SyncInferenceSession.step`` entered (K0) to ``InferenceSession.step`` running on the loop
(K1): ``run_coroutine_threadsafe``, the loop's wake-up. Mean over the turns that led to the traced slice's
one-hop decode steps: the row of the session's step before each (``perf/client_trip.py``)."""
from perf import client_trip

UNIT, LAYER, MOVES = "ms", "client + RPC (client/, rpc/)", "gap_p50_ms"


def read(record):
    return client_trip.stretch_ms(record, "submit_s")
