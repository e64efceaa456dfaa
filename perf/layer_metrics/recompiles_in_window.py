"""Programs the observatory saw compiled between the window's marks."""
UNIT, LAYER, MOVES = "count", "step programs (server/backend.py)", "gap_p50_ms"


def programs(record):
    """(child index, function name) of each program compiled inside the window."""
    out = []
    for i, child in enumerate(record.children):
        marks = child.get("marks", {})
        if "window" not in marks or "window_end" not in marks:
            return None
        lo, hi = marks["window"]["wall"], marks["window_end"]["wall"]
        out += [(i, p["fn"]) for p in child.get("programs", ()) if lo <= p["wall"] <= hi]
    return out


def read(record):
    found = programs(record)
    return None if found is None else float(len(found))
