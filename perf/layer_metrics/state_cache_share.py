"""Of the cache bytes the lanes that fed rows held over the steps of the
traced window, the share that was recurrent state (``state_bytes_held`` over
``state_bytes_held + kv_bytes_held``, each summed step by step by the batcher
on the host: a lane's slots in the state pool, and its pages in the blocks
that keep keys and values). A lane's states are of fixed size and its pages
grow with its context, so the share falls as contexts grow; 100 for a span
with no block that keeps keys and values. A family that declares no state, or
a program from before the counters, gives None."""
UNIT, LAYER, MOVES = "%", "batcher (server/batching.py)", "gap_p50_ms"
KEYS = ("state_bytes_held", "kv_bytes_held")


def read(record):
    try:
        deltas = [[record.stat_delta(child, key, start="trace_start", end="trace_stop") for key in KEYS] for child in record.children]
    except KeyError:  # a family without a state, or a program from before the counters
        return None
    if not deltas or any(None in d for d in deltas):
        return None
    state, kv = (sum(column) for column in zip(*deltas))
    return 100.0 * state / (state + kv) if state + kv > 0 else None
