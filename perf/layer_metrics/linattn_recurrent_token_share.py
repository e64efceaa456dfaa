"""Of the rows that went through a linear-attention layer in the traced
window, the share that took the one-step form of the gated delta rule
(``linattn_recurrent_tokens``: a decode row, once a state layer) and not the
chunked form (``linattn_chunk_tokens``: a mixed step's prompt chunk, once a
state layer): both counted by the batcher on the host, from the shapes each
step was started with. The one-step form moves a lane's whole state twice a
layer for one row; the chunked form moves it once a sub-chunk of 64. A family
that declares no state, or a program from before the counters, gives None."""
UNIT, LAYER, MOVES = "%", "linear attention (ops/linear_attention.py)", "gap_p50_ms"
KEYS = ("linattn_recurrent_tokens", "linattn_chunk_tokens")


def read(record):
    try:
        deltas = [[record.stat_delta(child, key, start="trace_start", end="trace_stop") for key in KEYS] for child in record.children]
    except KeyError:  # a family without a state, or a program from before the counters
        return None
    if not deltas or any(None in d for d in deltas):
        return None
    recurrent, chunk = (sum(column) for column in zip(*deltas))
    return 100.0 * recurrent / (recurrent + chunk) if recurrent + chunk > 0 else None
