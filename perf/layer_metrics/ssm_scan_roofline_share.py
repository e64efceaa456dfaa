"""The least seconds the chip needs to move what the selective scan's one-step
form had to move in the traced window, over the device seconds of the
operations that carry its names in the trace.

The need, from the batcher's counter between the trace's marks and the
configuration's sizes (perf/ssm.py): every live decode row's state, a
state-space layer, read once and written once (``linattn_recurrent_tokens``
counts rows x state layers, whatever the state is), at the chip's bandwidth
(perf/peaks.json).

The time: the operations run under one of the named scopes ``NAMES``, as long
as any of them was running: ``ptu.ssm.step`` (ops/selective_scan.py
``selective_scan_step``: the passes that read the state) and
``ptu.state.write`` (ops/linear_attention.py ``StatePool.write``: the pass
that writes a layer's states back into the state pool, into which the compiler
fuses the scan's update). A chunk's rows run under ``ptu.ssm.chunk`` and are
not in it, but a mixed step's write pass also lands the chunk's lane and every
step's the conv's tail, which only lowers the share. Read out of the capture
the child left as ``linattn_state_roofline_share.py`` reads its own scopes. A
family without such a state, a program from before the counter or the scopes,
or a run that left no capture of a device gives None."""
from perf import ssm
from perf.layer_metrics import sparse_attn_roofline_share as sparse

UNIT, LAYER, MOVES = "%", "selective scan (ops/selective_scan.py)", "gap_p50_ms"
NAMES = ("ptu.ssm.step", "ptu.state.write")


def need(record, child: dict):
    """Bytes the window's decode rows asked the one-step form to move at the least, or None."""
    try:
        row_layers = record.stat_delta(child, "linattn_recurrent_tokens", start="trace_start", end="trace_stop")
    except KeyError:
        return None
    return None if row_layers is None else ssm.one_step_bytes(record.config.get("config", {}), row_layers)


def named_seconds(path, names=NAMES):
    """Device seconds in which an operation under one of ``names`` ran:
    ``sparse_attn_roofline_share.named_seconds``, which reads the names out
    of its own module, shown these for the call."""
    theirs, sparse.NAMES = sparse.NAMES, names
    try:
        return sparse.named_seconds(path)
    finally:
        sparse.NAMES = theirs


def named_by_child(record, names=NAMES):
    """Device seconds under ``names`` in the capture each child left, or None where one left none."""
    out = []
    for index, child in enumerate(record.children):
        path = sparse.capture(index) if (child.get("trace") or {}).get("busy_s") else None
        seconds = named_seconds(path, names) if path is not None else None
        if seconds is None:
            return None
        out.append(seconds)
    return out


def read(record):
    if record.peaks is None or not record.children:
        return None
    asked = [need(record, child) for child in record.children]
    named = None if None in asked else named_by_child(record)
    if not named or not all(named):
        return None
    return 100.0 * sum(asked) / record.peaks["hbm_bytes_per_s"] / sum(named)
