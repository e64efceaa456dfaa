"""Of the device's busy seconds in the traced window, the share in which an
operation of a hyper-connection ran (``ptu.hc.coef``, ``ptu.hc.sinkhorn``,
``ptu.hc.mix``, models/xing4_0/block.py ``stream_wrap``: the float32 norm over
the stream, the three thin products, sigmoid and ``exp``, Sinkhorn's rounds
and the two mixes, twice a block, in the decode rows' half and the chunk's
half of every step): what the residual stream of several rows costs a step
beside the attention and the expert read it wraps. Every step mixes, so a
window that ran a step reads above 0; None says the scopes are missing. Read
out of the capture the child left, as ``scmoe_branch_busy_share.py`` reads its
scope (the union of the named operations' intervals); the busy seconds are
the child's own reduction of the same capture (perf/xplane.py). A
configuration without such a stream, or a run that left no capture of a
device, gives None."""
from perf import hc
from perf.layer_metrics.ssm_scan_roofline_share import named_by_child

UNIT, LAYER, MOVES = "%", "residual stream (models/xing4_0/block.py)", "gap_p50_ms"
NAMES = ("ptu.hc.",)


def read(record):
    if not record.children or hc.dims(record.config.get("config", {})) is None:
        return None
    mixed = named_by_child(record, NAMES)
    if not mixed or not all(mixed):
        return None
    return 100.0 * sum(mixed) / sum(child["trace"]["busy_s"] for child in record.children)
