"""Share of the traced window in which the compute thread had nothing to run and no
decode reply was out (``no_demand_s``): no session decoding. With the three other idle
shares and the four phases' shares it makes 100."""
from perf import round_trip

UNIT, LAYER, MOVES = "%", "batcher (server/batching.py)", "gap_p50_ms"


def read(record):
    return round_trip.share_of_window(record, "no_demand_s")
