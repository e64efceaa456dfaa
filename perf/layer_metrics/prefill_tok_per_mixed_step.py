"""Prompt tokens that rode each mixed step inside the window."""
UNIT, LAYER, MOVES = "tokens/step", "batcher (server/batching.py)", "gap_p50_ms"


def read(record):
    return record.ratio_over_children("prefill_tokens", "mixed_steps")
