"""Decode tokens whose reply came inside the window, over the window: what an
operator's chip earns with every lane full. Without a bound: run to run the
lanes settle into groups of four (205.9-209.0 tokens/s, 13 runs of 17) or of
fewer (186.7, 195.0, 199.4; once 214.2), so a driver's two sets of six spread
either under an eighth of any bound that covers the low runs or over half of
any bound that does not (PERF.md section 2). A benchmark PR returns it to the
end-to-end metrics when the batcher's grouping no longer decides it."""
UNIT, LAYER, MOVES = "tokens/s", "service (due time to reply, perf/loadgen.py)", "gap_p50_ms"


def read(record):
    n = sum(1 for s in record.sessions for t, _ in s.replies if record.in_window(t))
    return n / record.seconds if n else None
