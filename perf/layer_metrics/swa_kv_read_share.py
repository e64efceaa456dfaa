"""Of the table slots the paged step programs were handed in the traced window (lanes x table width x layers,
``attn_pages_tabled``: what a step's rows would read were every layer full and every table whole), the share their walks
and gathers read (``attn_pages_gathered``): ``attn_window_read_share``'s counters, counted by the batcher on the host from
the shapes each step was started with, in a cell of windowed and full layers in turns at contexts of several windows. A
family without a windowed layer, or a program from before the counters, gives None."""
UNIT, LAYER, MOVES = "%", "attention dispatch (ops/paged_attention.py)", "gap_p50_ms"


def read(record):
    if not (record.config.get("config") or {}).get("sliding_window_layout"):
        return None
    try:
        share = record.ratio_over_children("attn_pages_gathered", "attn_pages_tabled", start="trace_start", end="trace_stop")
    except KeyError:
        return None
    return None if share is None else 100.0 * share
