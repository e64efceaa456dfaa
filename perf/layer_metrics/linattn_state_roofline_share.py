"""The least seconds the chip needs to move what the gated delta rule's
one-step form had to move in the traced window, over the device seconds of
the operations that carry its names in the trace.

The need, from the batcher's counter between the trace's marks and the
configuration's sizes (perf/linattn.py): every live decode row's state matrix,
a linear layer, read once and written once (``linattn_recurrent_tokens`` counts
rows x state layers), at the chip's bandwidth (perf/peaks.json). The rule
multiplies almost nothing, so bandwidth is its only bound.

The time: the operations run under one of the named scopes ``NAMES``, as long
as any of them was running: ``ptu.linattn.recurrent`` (ops/linear_attention.py
``gated_delta_step``: the passes that read the state) and ``ptu.state.write``
(server/backend.py: the pass that writes a layer's states back into the state
pool, into which the compiler fuses the rule's update; compiled for the v5e its
root, and so its name, is the pool's ``dynamic-update-slice``). A chunk's rows
run under ``ptu.linattn.chunk`` and are not in it, but a mixed step's one write
pass also lands the chunk's lane, which only lowers the share. Read out of the
capture the child left by ``sparse_attn_roofline_share.py``'s reader of the
wire format, as ``latent_attn_roofline_share.py`` does. A family without a
state, a program from before the counters or the scopes, or a run that left no
capture of a device gives None."""
from perf import linattn
from perf.layer_metrics import sparse_attn_roofline_share as sparse

UNIT, LAYER, MOVES = "%", "linear attention (ops/linear_attention.py)", "gap_p50_ms"
NAMES = ("ptu.linattn.recurrent", "ptu.state.write")


def need(record, child: dict):
    """Bytes the window's decode rows asked the one-step rule to move at the least, or None."""
    try:
        row_layers = record.stat_delta(child, "linattn_recurrent_tokens", start="trace_start", end="trace_stop")
    except KeyError:
        return None
    return None if row_layers is None else linattn.one_step_bytes(record.config["config"], row_layers)


def named_seconds(path):
    """Device seconds in which an operation under one of ``NAMES`` ran:
    ``sparse_attn_roofline_share.named_seconds``, which reads the names out
    of its own module, shown this file's for the call."""
    theirs, sparse.NAMES = sparse.NAMES, NAMES
    try:
        return sparse.named_seconds(path)
    finally:
        sparse.NAMES = theirs


def read(record):
    if record.peaks is None or not record.children:
        return None
    least = seconds = 0.0
    for index, child in enumerate(record.children):
        asked = need(record, child)
        path = sparse.capture(index) if asked is not None and (child.get("trace") or {}).get("busy_s") else None
        named = named_seconds(path) if path is not None else None
        if not named:
            return None
        least += asked / record.peaks["hbm_bytes_per_s"]
        seconds += named
    return 100.0 * least / seconds
