"""The least seconds the chip needs for what the attention over cached keys and values had to do in the traced window,
over the device seconds of the operations that carry its names in the trace.

The need (perf/swa.py ``least``), from the batcher's counters between the trace's marks and the configuration's sizes:
the pages in reach of the decode rows and the chunks, read once a layer, at perf/peaks.json's bandwidth, and the (row,
position) pairs scored at the bf16 peak, the larger of the two. The time: the operations run under one of the named
scopes ``NAMES`` (the decode walk's kernel, a windowed layer's attention, a full layer's), as long as any of them was
running (the union of their intervals), read out of the capture the child left by ``sparse_attn_roofline_share.py``'s
reader of the wire format. A configuration without windowed layers, a program from before the counters or the scopes, or
a run that left no capture of a device gives None."""
from perf import swa
from perf.layer_metrics import sparse_attn_roofline_share as sparse

UNIT, LAYER, MOVES = "%", "kernels (ops/)", "gap_p50_ms"
NAMES = ("ptu.attn.paged_decode", "ptu.attn.window", "ptu.attn.full")
PAGE_SIZE = 64  # ``Server``'s default, where the configuration's ``server_args`` name none


def need(record, child: dict):
    """(bytes, flops) the window's steps asked of the attention at the least, or None."""
    hf = record.config["config"]
    if swa.layers(hf) is None:
        return None
    kw = dict(start="trace_start", end="trace_stop")
    try:
        reach, unfreed, pairs = (record.stat_delta(child, key, **kw) for key in ("window_pages_in_reach", "kv_bytes_unfreed", "attn_score_pairs"))
    except KeyError:
        return None
    if None in (reach, unfreed, pairs):
        return None
    page_size = (record.config.get("server_args") or {}).get("page_size") or PAGE_SIZE
    return swa.least(hf, int(page_size), reach, unfreed, pairs)


def named_seconds(path):
    """Device seconds in which an operation under one of ``NAMES`` ran (``sparse_attn_roofline_share.named_seconds``,
    which reads the names out of its own module, shown this file's for the call)."""
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
        least += max(asked[0] / record.peaks["hbm_bytes_per_s"], asked[1] / record.peaks["bf16_flops_per_s"])
        seconds += named
    return 100.0 * least / seconds
