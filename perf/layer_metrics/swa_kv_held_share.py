"""Of the bytes of pages the lanes that fed rows would hold with every page kept (``kv_bytes_unfreed``: the pages up to
each lane's last row in every layer, summed step by step over the traced window), the share they really held
(``kv_bytes_held``): both counted by the batcher on the host from the pages a lane holds a page group. A pool of one group
under one table a lane reads 100 or over (a prompt's pages are taken ahead of its chunks); a pool that keeps pages by
kind of layer and gives a windowed layer's back reads what the full layers and the windows hold of the contexts. A
program without page groups gives None."""
UNIT, LAYER, MOVES = "%", "batcher (server/batching.py)", "gap_p50_ms"


def read(record):
    try:
        share = record.ratio_over_children("kv_bytes_held", "kv_bytes_unfreed", start="trace_start", end="trace_stop")
    except KeyError:  # a span of one page group, or a program from before the counters
        return None
    return None if share is None else 100.0 * share
