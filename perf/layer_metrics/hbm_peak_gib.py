"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip: the most the
process held at once, from the making of the weights through warm-up, the
window and the drain. What serving holds steadily is ``hbm_steady_gib``."""
UNIT, LAYER, MOVES = "GiB", "launch (Server.start)", "setup_s"


def read(record):
    peaks = [c.get("memory", {}).get("peak_bytes_in_use") for c in record.children]
    peaks = [p for p in peaks if p]
    return max(peaks) / 2**30 if peaks else None
