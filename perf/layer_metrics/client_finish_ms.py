"""``finish_s`` a step: ``stream.recv`` returned (K4) to ``InferenceSession.step`` about to return (K5):
``HopTrace.record``, ``deserialize_array``, ``verify_step``, the history, ``_account_step``, the route-upgrade check. Mean over the replies of the traced slice's
one-hop decode steps (``perf/client_trip.py``)."""
from perf import client_trip

UNIT, LAYER, MOVES = "ms", "client + RPC (client/, rpc/)", "gap_p50_ms"


def read(record):
    return client_trip.stretch_ms(record, "finish_s")
