"""``reply_resume_s`` a decode reply (``decode_replies``): the future resolved to the lane's
handler coroutine running again after ``await batcher.step``: the k-th lane waits here
behind the k-1 replies built and sent before it."""
from perf import round_trip

UNIT, LAYER, MOVES = "ms", "handler (server/handler.py)", "gap_p50_ms"


def read(record):
    return round_trip.mean_ms(record, "reply_resume_s")
