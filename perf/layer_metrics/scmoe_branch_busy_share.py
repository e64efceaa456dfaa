"""Of the device's busy seconds in the traced window, the share in which an
operation of the shortcut-connected expert branch ran (``ptu.scmoe.shortcut``,
models/longcat_flash/block.py ``shortcut_experts``: the router, the held
experts' dispatch and the identities' weighted add, in the decode rows' half
and the chunk's half of every step): what the branch costs a step on one
chip, where it is a bandwidth-bound expert read beside two bandwidth-bound
dense reads, and where the compiler put it relative to them. Every step has a
router, so a window that ran a step reads above 0; None says the scope is
missing. Read out of the capture the child left, as
``ssm_scan_roofline_share.py`` reads its scopes (the union of the named
operations' intervals); the busy seconds are the child's own reduction of the
same capture (perf/xplane.py). A configuration without such a branch, or a
run that left no capture of a device, gives None."""
from perf.layer_metrics.ssm_scan_roofline_share import named_by_child

UNIT, LAYER, MOVES = "%", "expert dispatch (models/moe.py)", "gap_p50_ms"
NAMES = ("ptu.scmoe.shortcut",)


def read(record):
    if not record.children or not record.config.get("config", {}).get("zero_expert_num"):
        return None
    branch = named_by_child(record, NAMES)
    if not branch or not all(branch):
        return None
    return 100.0 * sum(branch) / sum(child["trace"]["busy_s"] for child in record.children)
