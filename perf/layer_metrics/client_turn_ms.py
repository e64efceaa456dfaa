"""The six stretches from a reply's frame read whole (K3) to the session's next request written (K2) summed:
``client_recv_ms`` + ``client_finish_ms`` + ``client_wake_ms`` + ``client_user_ms`` + ``client_submit_ms`` +
``client_build_ms``, the client process's own share of ``off_server_ms``."""
from perf import client_trip

UNIT, LAYER, MOVES = "ms", "client + RPC (client/, rpc/)", "gap_p50_ms"


def read(record):
    return client_trip.turn_ms(record)
