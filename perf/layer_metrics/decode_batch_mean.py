"""Decode tokens per batched step inside the window (``batcher.stats``)."""
UNIT, LAYER, MOVES = "tokens/step", "batcher (server/batching.py)", "gap_p50_ms"


def read(record):
    return record.ratio_over_children("batched_tokens", "batched_steps")
