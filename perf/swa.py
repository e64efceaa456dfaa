"""What attention over cached keys and values has to read and compute in a span whose layers are windowed and full in
turns, from the batcher's counters and the configuration's sizes alone. Kept with the benchmark, as perf/costs.py,
perf/hc.py and perf/linattn.py are, so that no PR that claims a gain can change the yardstick
(perf/layer_metrics/swa_attn_roofline_share.py reads it).

Bytes: every page in reach of a step's rows, read ONCE a layer: a windowed layer's pages in reach are counted by the
batcher a layer (``window_pages_in_reach``); a full layer reaches every page up to a lane's last row, which the batcher
counts in bytes over ALL the span's layers as what a lane would hold with every page kept (``kv_bytes_unfreed``), so the
full layers' part of it is their share of the layers. Flops: the (row, cached position) pairs the rows score
(``attn_score_pairs``, a layer), at ``2 x 2 x query heads x head_dim`` a pair (scores and the weighted sum), with nothing
for the softmax: what that spends counts against the share."""

BYTES = 2  # bf16 pages


def layers(hf: dict):
    """``(windowed layers, full layers)`` of the configuration's span, or None where no layer is windowed."""
    layout = hf.get("sliding_window_layout")
    if not layout or not hf.get("sliding_window_size"):
        return None
    depth = hf.get("num_hidden_layers", len(layout))
    windowed = sum(1 for flag in layout[:depth] if flag)
    return (windowed, depth - windowed) if windowed else None


def page_bytes(hf: dict, page_size: int) -> int:
    """Bytes of one page of keys and values of one layer."""
    return 2 * page_size * hf["num_key_value_heads"] * hf["head_dim"] * BYTES


def least(hf: dict, page_size: int, window_pages_in_reach: float, kv_bytes_unfreed: float, score_pairs: float):
    """``(bytes, flops)`` the steps' attention needs at the least, or None for a configuration without windowed layers."""
    split = layers(hf)
    if split is None:
        return None
    windowed, full = split
    nbytes = window_pages_in_reach * page_bytes(hf, page_size) + kv_bytes_unfreed * full / (windowed + full)
    return nbytes, score_pairs * 4 * hf["num_attention_heads"] * hf["head_dim"]
