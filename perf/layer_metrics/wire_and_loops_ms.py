"""``off_server_ms`` less ``client_turn_ms``: what is left of a lane's trip once the server's stations and the
client's are both taken out: two crossings of the wire and both event loops' lateness on a ready socket."""
from perf import client_trip

UNIT, LAYER, MOVES = "ms", "client + RPC (client/, rpc/)", "gap_p50_ms"


def read(record):
    return client_trip.wire_and_loops_ms(record)
