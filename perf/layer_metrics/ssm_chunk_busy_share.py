"""Of the device's busy seconds in the traced window, the share in which an
operation of the selective scan's chunked form ran (``ptu.ssm.chunk``,
ops/selective_scan.py ``selective_scan_chunked``: a prompt chunk's loop over
its positions, a state-space layer): what a fused kernel for that form could
win back of a window's device time. 0.0, not None, for a window in which no
chunk ran. Read out of the capture the child left, as
``ssm_scan_roofline_share.py`` reads its scopes; the busy seconds are the
child's own reduction of the same capture (perf/xplane.py). A configuration
without a state-space layer, or a run that left no capture of a device, gives
None."""
from perf import ssm
from perf.layer_metrics.ssm_scan_roofline_share import named_by_child

UNIT, LAYER, MOVES = "%", "selective scan (ops/selective_scan.py)", "gap_p50_ms"
NAMES = ("ptu.ssm.chunk",)


def read(record):
    if not record.children or ssm.state_bytes(record.config.get("config", {})) is None:
        return None
    chunk = named_by_child(record, NAMES)
    if chunk is None:
        return None
    return 100.0 * sum(chunk) / sum(child["trace"]["busy_s"] for child in record.children)
