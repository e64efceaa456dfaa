"""Share of the traced window in which the compute thread had nothing to run and a decode
reply was out (``lanes_out_s``): every live lane's token on its way."""
from perf import round_trip

UNIT, LAYER, MOVES = "%", "batcher (server/batching.py)", "gap_p50_ms"


def read(record):
    return round_trip.share_of_window(record, "lanes_out_s")
