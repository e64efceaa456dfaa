"""``recv_s`` a step: the reply's frame read whole (K3, ``RpcClient._read_loop``) to ``stream.recv`` returned in
``_ServerInferenceSession.step`` (K4): ``unpackb``, the stream's queue, the ``wait_for``'s wake. Mean over the replies of the traced slice's
one-hop decode steps (``perf/client_trip.py``)."""
from perf import client_trip

UNIT, LAYER, MOVES = "ms", "client + RPC (client/, rpc/)", "gap_p50_ms"


def read(record):
    return client_trip.stretch_ms(record, "recv_s")
