"""Of the rows that went through a state-space layer in the traced window,
the share that took the selective scan's one-step form
(``linattn_recurrent_tokens``: a decode row, once a state layer) and not the
chunked form (``linattn_chunk_tokens``: a mixed step's prompt chunk, once a
state layer): the batcher's two counters for any family that keeps a
recurrent state, read as ``linattn_recurrent_token_share.py`` reads them. The
one-step form moves a lane's whole state twice a layer for one row; the
chunked form once a chunk. A configuration without a state-space layer, a
program from before the counters, or a window without a row gives None."""
from perf import ssm
from perf.layer_metrics import linattn_recurrent_token_share as counters

UNIT, LAYER, MOVES = "%", "selective scan (ops/selective_scan.py)", "gap_p50_ms"


def read(record):
    return None if ssm.state_bytes(record.config.get("config", {})) is None else counters.read(record)
