"""``rpc_send_s`` a decode reply: the reply yielded to ``write_frame`` returned: the wait for
the connection's write lock, msgpack and ``writer.write`` (annotation ``ptu.rpc.send``), and
the drain of the transport's buffer, which is counted too."""
from perf import round_trip

UNIT, LAYER, MOVES = "ms", "client + RPC (client/, rpc/)", "gap_p50_ms"


def read(record):
    return round_trip.mean_ms(record, "rpc_send_s")
