"""``memory_stats()["bytes_in_use"]`` of the fullest chip at the start of
the window: the weights, the paged pool and the programs' buffers, which is
what the deployment holds while it serves."""
UNIT, LAYER, MOVES = "GiB", "device", "gap_p50_ms"


def read(record):
    held = [c.get("marks", {}).get("window", {}).get("bytes_in_use") for c in record.children]
    held = [h for h in held if h]
    return max(held) / 2**30 if held else None
