"""``reply_build_s`` a decode reply: the handler running again to the reply yielded to the
RPC server (annotation ``ptu.reply.build``): instruments, ``step_meta``, occupancy, usage,
``serialize_array``."""
from perf import round_trip

UNIT, LAYER, MOVES = "ms", "handler (server/handler.py)", "gap_p50_ms"


def read(record):
    return round_trip.mean_ms(record, "reply_build_s")
