"""``build_s`` a step: ``InferenceSession.step`` running (K1) to ``stream.send`` returned in
``_ServerInferenceSession.step`` (K2): ``_ensure_route``, ``uuid4``, ``serialize_array``, the message, pack,
``writer.write``, drain. Mean over the turns that led to the traced slice's
one-hop decode steps: the row of the session's step before each (``perf/client_trip.py``)."""
from perf import client_trip

UNIT, LAYER, MOVES = "ms", "client + RPC (client/, rpc/)", "gap_p50_ms"


def read(record):
    return client_trip.stretch_ms(record, "build_s")
