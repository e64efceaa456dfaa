"""1e3 x ``loop_busy_sq`` / (2 x slice) of the server's loop: what a request that reached its socket at a moment
unrelated to the loop's phase waited on average before the loop looked at the socket."""
from perf import client_trip

UNIT, LAYER, MOVES = "ms", "handler (server/handler.py)", "gap_p50_ms"


def read(record):
    return client_trip.late_ms(client_trip.server_loop(record))
