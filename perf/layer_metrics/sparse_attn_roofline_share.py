"""The least seconds the chip needs for what the sparse attention's kernels
had to do in the traced window, over the device seconds of the operations
that carry their names in the trace.

The need, from the batcher's counters between the trace's marks and the
configuration's sizes: the index keys scored (``sparse_index_rows_scored`` x
``indexer_head_dim`` x 2 B) and the rows of keys and values fetched
(``sparse_kv_rows_read`` x 2 x kv heads x head_dim x 2 B) over the chip's
bandwidth, against the scoring's flops (``sparse_score_pairs`` x 2 x
``indexer_num_heads`` x ``indexer_head_dim``) and the attention's over the
fetched rows (4 x q heads x head_dim a row) over its bf16 peak: the larger of
the two (perf/peaks.json). The selection itself (a sort, or counting passes)
has no need of its own: every byte and flop it spends counts against the
share.

The time: the operations run under one of the named scopes ``NAMES``, as
long as any of them was running (the union of their intervals: a loop's
body lies inside its own events). The reduced trace of a child's dump
cannot say which those are: an operation's name there is its HLO text
(``%sort.51 = ...``), and a step's layer loop is one ``while``. The scope is
in the capture itself, as the ``tf_op`` of an operation's metadata
(``jit(step)/while/body/.../ptu.attn.select/top_k:``), which
``jax.profiler.ProfileData`` does not hand out, so this file reads the
``.xplane.pb`` the child left under the run's directory with a reader of the
wire format of its own: planes, the two metadata tables, and the line "XLA
Ops" of a device plane. A ``while`` or ``conditional`` has no ``tf_op``: what
its body runs has, so the loop's own bookkeeping between two bodies is left
out. A family that declares no index row, a program from before the
counters or the scopes, or a run that left no capture of a device gives
None."""
from pathlib import Path

from perf import xplane

UNIT, LAYER, MOVES = "%", "sparse attention (ops/sparse_attention.py)", "gap_p50_ms"
NAMES = ("ptu.attn.index_score", "ptu.attn.select", "ptu.attn.sparse_attend")
BYTES = 2
RUNS_DIR = Path(__file__).resolve().parents[1] / ".work" / "runs"  # perf/run.py: <runs>/<cell>/trace/child<i>/


def need(record, child: dict):
    """(bytes, flops) the window's steps asked of the sparse attention's kernels, or None."""
    hf = record.config["config"]
    sa = hf.get("sa_config")
    if not sa:
        return None
    kw = dict(start="trace_start", end="trace_stop")
    try:
        scored, fetched = (record.stat_delta(child, key, **kw) for key in ("sparse_index_rows_scored", "sparse_kv_rows_read"))
        pairs = record.stat_delta(child, "sparse_score_pairs", **kw)
    except KeyError:
        return None
    if None in (scored, fetched, pairs):
        return None
    row = 2 * hf["num_key_value_heads"] * hf["head_dim"] * BYTES
    nbytes = scored * sa["indexer_head_dim"] * BYTES + fetched * row
    flops = pairs * 2 * sa["indexer_num_heads"] * sa["indexer_head_dim"] + fetched * 4 * hf["num_attention_heads"] * hf["head_dim"]
    return nbytes, flops


def fields(buf):
    """(field number, value) for each field of one protobuf message: an int
    for a varint, a view of the bytes for a length-delimited field, None for
    a fixed-width one."""
    at, end = 0, len(buf)
    while at < end:
        key = shift = 0
        while True:
            byte = buf[at]
            at += 1
            key |= (byte & 0x7F) << shift
            shift += 7
            if byte < 0x80:
                break
        wire, value = key & 7, None
        if wire == 0 or wire == 2:
            value = shift = 0
            while True:
                byte = buf[at]
                at += 1
                value |= (byte & 0x7F) << shift
                shift += 7
                if byte < 0x80:
                    break
            if wire == 2:
                value, at = buf[at : at + value], at + value
        elif wire == 1 or wire == 5:
            at += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} in a capture")
        yield key >> 3, value


def text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def table(entries) -> dict:
    """A protobuf map of metadata, as its entries' views: id -> the message."""
    out = {}
    for entry in entries:
        pair = dict(fields(entry))
        out[pair.get(1, 0)] = pair.get(2, b"")
    return out


def named_seconds(path: Path):
    """Device seconds in which an operation under one of ``NAMES`` ran, a
    mean over the capture's device planes; None where no device plane holds
    an operation."""
    per_device = []
    for no, plane in fields(memoryview(path.read_bytes())):
        if no != 1:  # XSpace.planes
            continue
        name, lines, event_meta, stat_meta = "", [], [], []
        for no, value in fields(plane):  # XPlane: name 2, lines 3, event_metadata 4, stat_metadata 5
            if no == 2:
                name = text(value)
            elif no in (3, 4, 5):
                (lines, event_meta, stat_meta)[no - 3].append(value)
        if not name.startswith(xplane.DEVICE_PLANE_PREFIX):
            continue
        stat_names = {key: text(dict(fields(meta)).get(2, b"")) for key, meta in table(stat_meta).items()}  # XStatMetadata.name
        named = set()
        for key, meta in table(event_meta).items():
            for no, stat in fields(meta):  # XEventMetadata.stats 5; XStat: metadata_id 1, str_value 5
                stat = dict(fields(stat)) if no == 5 else {}
                if stat_names.get(stat.get(1)) == "tf_op" and any(part in text(stat.get(5, b"")) for part in NAMES):
                    named.add(key)
        ran, intervals = False, []
        for line in lines:
            line_name, events = "", []
            for no, value in fields(line):  # XLine: name 2, events 4
                if no == 2:
                    line_name = text(value)
                elif no == 4:
                    events.append(value)
            if line_name != xplane.OPS_LINE:
                continue
            for event in events:  # XEvent: metadata_id 1, offset_ps 2, duration_ps 3
                ran = True
                event = dict(fields(event))
                if event.get(1) in named:
                    start = event.get(2, 0)
                    intervals.append((start, start + event.get(3, 0)))
        if ran:
            per_device.append(xplane.union_seconds(intervals) * 1e-12)
    return sum(per_device) / len(per_device) if per_device else None


def capture(index: int):
    """The newest capture a server child ``index`` left under the runs' directory, or None."""
    found = list(RUNS_DIR.glob(f"*/trace/child{index}/**/*.xplane.pb"))
    return max(found, key=lambda p: p.stat().st_mtime) if found else None


def read(record):
    if record.peaks is None or not record.children:
        return None
    least = seconds = 0.0
    for index, child in enumerate(record.children):
        asked = need(record, child)
        path = capture(index) if asked is not None and (child.get("trace") or {}).get("busy_s") else None
        named = named_seconds(path) if path is not None else None
        if not named:
            return None
        least += max(asked[0] / record.peaks["hbm_bytes_per_s"], asked[1] / record.peaks["bf16_flops_per_s"])
        seconds += named
    return 100.0 * least / seconds
