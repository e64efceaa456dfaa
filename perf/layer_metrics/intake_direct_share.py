"""Of the decode steps whose requests reached the batcher in the traced window,
the share that the connection's reader handed over in the turn that read the
frame (``rpc_intake_direct``: ``rpc/server.py StreamRequests.sink``, the
handler's sink, ``DecodeBatcher.begin_step``) and not through the stream's
queue and a turn of the handler's own task (``rpc_intake_queued``:
``DecodeBatcher.step``). Both are counted by the batcher where a step enters
it, the slice ``rpc_recv_ms`` describes. A step goes the queue's way when its
session's handler was not parked (a pushed step in hand, a prefix store still
running), when something of its stream was queued in front of it, or when it
is more than one new token's hidden state (a rollback, prompts, hypo_ids, a
generation request); a prompt's chunk is in neither count. A program from
before the counters gives None."""
UNIT, LAYER, MOVES = "%", "client + RPC (client/, rpc/)", "gap_p50_ms"
KEYS = ("rpc_intake_direct", "rpc_intake_queued")


def read(record):
    try:
        deltas = [[record.stat_delta(child, key, start="trace_start", end="trace_stop") for key in KEYS] for child in record.children]
    except KeyError:  # a program from before the counters
        return None
    if not deltas or any(None in d for d in deltas):
        return None
    direct, queued = (sum(column) for column in zip(*deltas))
    return 100.0 * direct / (direct + queued) if direct + queued > 0 else None
