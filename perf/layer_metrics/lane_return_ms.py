"""``lane_return_s`` a lane that came back (``lane_returns``), in the traced window: the flush
loop's resolving of a decode reply's future to the same lane's next ``batcher.step``
entered: handler, RPC, wire, client, wire, RPC, handler. What the gather predicts."""
from perf import round_trip

UNIT, LAYER, MOVES = "ms", "batcher (server/batching.py)", "gap_p50_ms"


def read(record):
    return round_trip.mean_ms(record, "lane_return_s")
