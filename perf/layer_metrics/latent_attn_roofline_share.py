"""The least seconds the chip needs for what the latent attention had to do in
the traced window, over the device seconds of the operations that carry its
names in the trace.

The need, from the batcher's counters between the trace's marks and the
configuration's sizes. Bytes: every latent row a row had to meet, read ONCE a
layer, at ``(kv_lora_rank + qk_rope_head_dim) x 2 B`` (1,152 B): the rows the
decode rows' lanes held (``latent_rows_held``) and the positions a chunk's
lane held up to the chunk's end (``latent_positions_held``), whatever the
walks read over that. Flops: the (row, position) pairs scored
(``latent_score_pairs``) at the CHEAPER form's count a pair, the expanded
one's ``2 x heads x (qk_nope_head_dim + qk_rope_head_dim + v_head_dim)``
(20,480; the absorbed form computes ``2 x heads x (2 x kv_lora_rank +
qk_rope_head_dim)``, 69,632), with nothing for the absorption, the expansion
or the softmax: every byte and flop those spend counts against the share. The
larger of bytes over the chip's bandwidth and flops over its bf16 peak
(perf/peaks.json).

The time: the operations run under one of the named scopes ``NAMES``, as long
as any of them was running (the union of their intervals), read out of the
capture the child left by ``sparse_attn_roofline_share.py``'s reader of the
wire format (its ``capture`` and ``named_seconds``; the latter reads the
names out of its own module, so this file shows it its own for the call). A
family that declares no latent row, a program from before the counters or the
scopes, or a run that left no capture of a device gives None."""
from perf.layer_metrics import sparse_attn_roofline_share as sparse

UNIT, LAYER, MOVES = "%", "latent attention (ops/latent_attention.py)", "gap_p50_ms"
NAMES = ("ptu.attn.latent_absorb", "ptu.attn.latent_decode", "ptu.attn.latent_chunk", "ptu.attn.latent_expand")
BYTES = 2


def need(record, child: dict):
    """(bytes, flops) the window's steps asked of the latent attention at the least, or None."""
    hf = record.config["config"]
    if not hf.get("kv_lora_rank"):
        return None
    kw = dict(start="trace_start", end="trace_stop")
    try:
        held, chunk_held, pairs = (record.stat_delta(child, key, **kw) for key in ("latent_rows_held", "latent_positions_held", "latent_score_pairs"))
    except KeyError:
        return None
    if None in (held, chunk_held, pairs):
        return None
    row = (hf["kv_lora_rank"] + hf["qk_rope_head_dim"]) * BYTES
    pair = 2 * hf["num_attention_heads"] * (hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"] + hf["v_head_dim"])
    return (held + chunk_held) * row, pairs * pair


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
        least += max(asked[0] / record.peaks["hbm_bytes_per_s"], asked[1] / record.peaks["bf16_flops_per_s"])
        seconds += named
    return 100.0 * least / seconds
