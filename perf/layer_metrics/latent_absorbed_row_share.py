"""Of the rows that went through a layer with latent attention in the traced
window, the share that took the ABSORBED form (``latent_rows_absorbed``: a
decode row, one a lane, met with the cached latent rows as they lie) over
those and the rows that took the EXPANDED one (``latent_rows_expanded``: a
prompt chunk's rows, against keys and values made of a block of positions at
a time), counted by the batcher on the host from the shapes each step was
started with, times the span's layers. The form follows from a call's shape:
a slice of decode-only steps reads 100, a slice of mixed steps of 2,048 rows
beside seven decoding lanes 0.3. A family that declares no latent row, or a
program from before the counters, gives None."""
UNIT, LAYER, MOVES = "%", "latent attention (ops/latent_attention.py)", "gap_p50_ms"
KEYS = ("latent_rows_absorbed", "latent_rows_expanded")


def read(record):
    try:
        deltas = [[record.stat_delta(child, key, start="trace_start", end="trace_stop") for key in KEYS] for child in record.children]
    except KeyError:  # a family that declares no latent row, or a program from before the counters
        return None
    if not deltas or any(None in d for d in deltas):
        return None
    absorbed, expanded = (sum(column) for column in zip(*deltas))
    return 100.0 * absorbed / (absorbed + expanded) if absorbed + expanded > 0 else None
