"""The KiB of hidden state a row of the traced window brought into a step and
took out of it, a way: ``stream_bytes_in + stream_bytes_out`` (the batcher's
count of what its steps' rows were, float32 as the wire carries them) over
twice the rows stepped (``batched_tokens``, a row a decoding lane, and
``prefill_tokens``, a prompt chunk's). For a family whose blocks take and
hand on a residual stream of several rows that is ``hc_mult x hidden_size x
4 / 1024`` (xing4-29b-a4b-span8: 56.0; every other configuration's would be
its hidden size's: Falcon-40B's 32.0 is the widest), so a server that
collapsed the stream at its span's edge, or a buffer still sized by the
model's width, reads low here. Reported for a configuration with such a
stream only; a program from before the counters gives None."""
from perf import hc

UNIT, LAYER, MOVES = "KiB", "batcher (server/batching.py)", "gap_p50_ms"
KEYS = ("stream_bytes_in", "stream_bytes_out", "batched_tokens", "prefill_tokens")


def read(record):
    if not record.children or hc.dims(record.config.get("config", {})) is None:
        return None
    try:
        deltas = [[record.stat_delta(child, key, start="trace_start", end="trace_stop") for key in KEYS] for child in record.children]
    except KeyError:  # a program from before the counters
        return None
    if any(None in d for d in deltas):
        return None
    bytes_in, bytes_out, decoded, prefilled = (sum(column) for column in zip(*deltas))
    rows = decoded + prefilled
    return (bytes_in + bytes_out) / (2 * rows) / 1024 if rows > 0 else None
