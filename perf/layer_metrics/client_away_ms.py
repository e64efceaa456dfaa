"""``away_s`` a step of the traced slice: the request written (K2) to its reply's frame read whole (K3): the wire,
the server, the wire, the client loop's lateness. With ``client_turn_ms`` it is the slice's mean decode gap."""
from perf import client_trip

UNIT, LAYER, MOVES = "ms", "client + RPC (client/, rpc/)", "gap_p50_ms"


def read(record):
    return client_trip.away_ms(record)
