"""``wake_s`` a step: ``InferenceSession.step`` about to return on the loop (K5) to ``SyncInferenceSession.step``
holding the result on the caller's thread (K6): the future's result, the thread's wake-up. Mean over the replies of the traced slice's
one-hop decode steps (``perf/client_trip.py``)."""
from perf import client_trip

UNIT, LAYER, MOVES = "ms", "client + RPC (client/, rpc/)", "gap_p50_ms"


def read(record):
    return client_trip.stretch_ms(record, "wake_s")
