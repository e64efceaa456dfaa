"""Share of the traced slice the server's event loop spent between a ``select()``'s return and the next one's call
(``batcher.stats["loop_busy_s"]``): how full the one thread of handlers, replies and the flush loop is."""
from perf import client_trip

UNIT, LAYER, MOVES = "%", "handler (server/handler.py)", "gap_p50_ms"


def read(record):
    return client_trip.busy_share(client_trip.server_loop(record))
