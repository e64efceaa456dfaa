"""Share of the traced slice the client's event loop (``SwarmRuntime``'s thread: every session's sends, reads
and step bookkeeping) spent between a ``select()``'s return and the next one's call, from the two samples of
its turn clock nearest the marks."""
from perf import client_trip

UNIT, LAYER, MOVES = "%", "client + RPC (client/, rpc/)", "gap_p50_ms"


def read(record):
    return client_trip.busy_share(client_trip.client_loop(record))
