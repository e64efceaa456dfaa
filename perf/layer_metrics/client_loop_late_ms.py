"""1e3 x ``loop_busy_sq`` / (2 x elapsed) of the client's loop between the two samples nearest the marks: what a
reply that reached its socket at a moment unrelated to the loop's phase waited on average to be read."""
from perf import client_trip

UNIT, LAYER, MOVES = "ms", "client + RPC (client/, rpc/)", "gap_p50_ms"


def read(record):
    return client_trip.late_ms(client_trip.client_loop(record))
