"""Of the rows that went through a layer with a learned sparse attention in
the traced window, the share whose context was over ``topk``, so that the
selection left positions out (``sparse_rows_selected`` over that and
``sparse_rows_dense``, counted by the batcher on the host from the positions
each step was started with, times the span's layers). 100 says the cell's
traffic reaches the mechanism; a cell whose contexts stay under ``topk`` reads
0 and measures dense attention. A family that declares no index row, or a
program from before the counters, gives None."""
UNIT, LAYER, MOVES = "%", "sparse attention (ops/sparse_attention.py)", "gap_p50_ms"
KEYS = ("sparse_rows_selected", "sparse_rows_dense")


def read(record):
    try:
        deltas = [[record.stat_delta(child, key, start="trace_start", end="trace_stop") for key in KEYS] for child in record.children]
    except KeyError:  # a family that declares no index row, or a program from before the counters
        return None
    if not deltas or any(None in d for d in deltas):
        return None
    selected, dense = (sum(column) for column in zip(*deltas))
    return 100.0 * selected / (selected + dense) if selected + dense > 0 else None
