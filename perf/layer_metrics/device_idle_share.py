"""1 - (union of device-op intervals) / (traced window), mean over the chips."""
UNIT, LAYER, MOVES = "%", "device", "gap_p50_ms"


def read(record):
    shares = [(c.get("trace") or {}).get("idle_share") for c in record.children]
    if not shares or any(s is None for s in shares):
        return None
    return 100.0 * sum(shares) / len(shares)
