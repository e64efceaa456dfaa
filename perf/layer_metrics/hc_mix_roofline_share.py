"""The least seconds the chip needs for what the hyper-connections of the
traced window had to move and compute, over the device seconds of the
operations that carry their names in the trace.

The need, from the batcher's counters between the trace's marks and the
configuration's sizes (perf/hc.py): ``hc_rows`` (rows x wraps the steps
mixed) times a wrap's read and write of the stream and its hand-over to the
sub-layer, the three ``phi`` once a wrap a step (``batched_steps`` x the
span's blocks x the wraps of a block, which is ``hc_rows`` over the rows of a
step), and their flops, against the larger of the two floors of
perf/peaks.json (the bytes': bandwidth is the bound).

The time: the operations run under a scope that starts ``ptu.hc.`` (coef,
sinkhorn, mix), as long as any of them was running, read out of the capture
the child left as ``ssm_scan_roofline_share.py`` reads its scopes. If a
kernel is ever written for the wrap, under these scopes, this is its share.
A configuration without such a stream, a program from before the counter or
the scopes, or a run that left no capture of a device gives None."""
from perf import hc
from perf.layer_metrics.hc_mix_busy_share import NAMES
from perf.layer_metrics.ssm_scan_roofline_share import named_by_child

UNIT, LAYER, MOVES = "%", "residual stream (models/xing4_0/block.py)", "gap_p50_ms"
WRAPS_A_BLOCK = 2  # attention, feed-forward


def need(record, child: dict, span: dict):
    """``(bytes, flops)`` the window's rows asked of the wraps at the least, or None."""
    try:
        rows = record.stat_delta(child, "hc_rows", start="trace_start", end="trace_stop")
        steps = record.stat_delta(child, "batched_steps", start="trace_start", end="trace_stop")
    except KeyError:
        return None
    if rows is None or steps is None:
        return None
    return hc.least(record.config.get("config", {}), rows, steps * span["num_blocks"] * WRAPS_A_BLOCK)


def read(record):
    if record.peaks is None or not record.children:
        return None
    asked = [need(record, child, span) for child, span in zip(record.children, record.config["servers"])]
    named = None if None in asked else named_by_child(record, NAMES)
    if not named or not all(named):
        return None
    least = sum(max(nbytes / record.peaks["hbm_bytes_per_s"], flops / record.peaks["bf16_flops_per_s"]) for nbytes, flops in asked)
    return 100.0 * least / sum(named)
