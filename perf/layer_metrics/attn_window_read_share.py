"""Of the table slots the paged step programs were handed in the traced window
(lanes x table width x layers, ``attn_pages_tabled``), the share they gathered
(``attn_pages_gathered``): both counted by the batcher on the host, from the
shapes each step was started with. A layer with a static window gathers the
pages its window can reach (3 of a lane's 16 at a window of 128 and pages of
64), a full layer its whole table row, so a span of four windowed layers and
one full one reads (4 x 3 + 16) / (5 x 16) = 35%; a program that masks a
whole-table gather reads 100. A family without a windowed layer, or a program
from before the counters, gives None."""
UNIT, LAYER, MOVES = "%", "attention dispatch (ops/paged_attention.py)", "gap_p50_ms"


def read(record):
    try:
        share = record.ratio_over_children("attn_pages_gathered", "attn_pages_tabled", start="trace_start", end="trace_stop")
    except KeyError:  # a family that declares no window, or a program from before the counters
        return None
    return None if share is None else 100.0 * share
