"""Of the tokens that went through an expert layer in the traced window, the
share that took the all-experts einsum (``moe_dense_tokens``) and not one of
the two dispatches that read the experts reached (``moe_grouped_tokens``:
``ragged_dot``'s tokens and, since PR 32, the hit kernel's, which
``moe_hit_tokens`` counts alone): all counted by the batcher on the host, from
the shapes each step was started with (decode lanes, and a mixed step's chunk
at its bucket). The einsum reads every held expert and computes
experts / top_k times what a token needs. A program or a family without the
counters gives None."""
UNIT, LAYER, MOVES = "%", "expert dispatch (models/moe.py)", "gap_p50_ms"
KEYS = ("moe_dense_tokens", "moe_grouped_tokens")


def read(record):
    try:
        deltas = [[record.stat_delta(child, key, start="trace_start", end="trace_stop") for key in KEYS] for child in record.children]
    except KeyError:  # a family without experts, or a program from before the counters
        return None
    if not deltas or any(None in d for d in deltas):
        return None
    dense, grouped = (sum(column) for column in zip(*deltas))
    return 100.0 * dense / (dense + grouped) if dense + grouped > 0 else None
