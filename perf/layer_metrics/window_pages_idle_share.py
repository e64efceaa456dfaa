"""Of the pages the decoding lanes held in windowed layers over the steps of
the traced window (``window_pages_held``, summed step by step), the share no
layer's window could reach any more (100 less ``window_pages_in_reach`` over
it): both counted by the batcher on the host from each step's tables and
positions. Those pages stay allocated until the session ends; freeing them is
ROADMAP B3, and this is what it would give back. A family without a windowed
layer, or a program from before the counters, gives None."""
UNIT, LAYER, MOVES = "%", "batcher (server/batching.py)", "gap_p50_ms"


def read(record):
    try:
        share = record.ratio_over_children("window_pages_in_reach", "window_pages_held", start="trace_start", end="trace_stop")
    except KeyError:  # a family that declares no window, or a program from before the counters
        return None
    return None if share is None else 100.0 * (1.0 - share)
