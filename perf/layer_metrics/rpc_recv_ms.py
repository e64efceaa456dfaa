"""``rpc_recv_s`` a lane that came back: its request's frame read whole to the handler's
``next_step()`` returning the item: ``unpackb`` (annotation ``ptu.rpc.recv``), the stream's
queue, the handler's task woken."""
from perf import round_trip

UNIT, LAYER, MOVES = "ms", "client + RPC (client/, rpc/)", "gap_p50_ms"


def read(record):
    return round_trip.mean_ms(record, "rpc_recv_s")
