"""``lane_return_ms`` less the five stretches the server times between the resolving and
``batcher.step``'s entry: the wire, the client, the wire, and nothing of the server's but
its event loop's lateness in reading a socket that is ready."""
from perf import round_trip

UNIT, LAYER, MOVES = "ms", "client + RPC (client/, rpc/)", "gap_p50_ms"


def read(record):
    return round_trip.off_server_ms(record)
