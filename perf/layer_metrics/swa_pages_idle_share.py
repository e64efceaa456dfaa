"""Of the pages the lanes that fed rows held in windowed layers over the steps of the traced window
(``window_pages_held``, summed step by step), the share no row's window reached (100 less ``window_pages_in_reach`` over
it): ``window_pages_idle_share``'s counters, counted by the batcher on the host, in a cell whose windowed layers give
pages back as the window moves. Held is what is really held when a step starts, so a pool that frees nothing reads the
idle share of its contexts (over half at contexts of three windows) and one that gives pages back reads near nothing. A
family without a windowed layer, or a program from before the counters, gives None."""
UNIT, LAYER, MOVES = "%", "batcher (server/batching.py)", "gap_p50_ms"


def read(record):
    try:
        share = record.ratio_over_children("window_pages_in_reach", "window_pages_held", start="trace_start", end="trace_stop")
    except KeyError:  # a family that declares no window, or a program from before the counters
        return None
    return None if share is None else 100.0 * (1.0 - share)
