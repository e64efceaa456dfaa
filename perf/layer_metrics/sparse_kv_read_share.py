"""Of the positions of keys and values that the lanes feeding rows held over
the steps of the traced window (``sparse_kv_rows_held``), the share the step
programs fetched (``sparse_kv_rows_read``): both counted by the batcher on the
host, times the span's layers, from the shapes each step was started with. A
decode row fetches the ``topk`` positions its indexer chose, so at a context of
16k-30k the share is 2048 / context, 7-12; a prompt chunk walks its lane's
pages under the selection's mask and reads them all; a program that fetched
the table and masked it would read 100. A family that declares no index row,
or a program from before the counters, gives None."""
UNIT, LAYER, MOVES = "%", "sparse attention (ops/sparse_attention.py)", "gap_p50_ms"


def read(record):
    try:
        share = record.ratio_over_children("sparse_kv_rows_read", "sparse_kv_rows_held", start="trace_start", end="trace_stop")
    except KeyError:  # a family that declares no index row, or a program from before the counters
        return None
    return None if share is None else 100.0 * share
