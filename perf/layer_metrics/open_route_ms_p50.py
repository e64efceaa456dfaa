"""Of the wait for the first reply, the part outside the prompt's own RPC:
session open, routing (a ``ptu.info`` refresh of the candidates) and the
stream's set-up. Median over counted sessions, from the client's clock."""
from perf.record import percentile

UNIT, LAYER, MOVES = "ms", "client + RPC (client/, rpc/)", "gap_p50_ms"


def read(record):
    waits = []
    for s in record.counted():
        first = next((h for h in s.hops if h[0] == "prefill"), None)
        if first is not None and s.first_reply is not None:
            waits.append((s.first_reply - s.sent - sum(first[2:])) * 1e3)
    return percentile(waits, 50)
