"""Whether the median gap stands on an edge: 100 x (p60 - p40) / p50 of the
gaps ``gap_p50_ms`` takes the median of. Where every step carries about as
many lanes the gaps make one heap and the middle fifth of them is a few
percent wide. Where steps of n and of n + 1 lanes make two heaps and the
median falls between them, it is as wide as the heaps are apart, and a few
sessions overlapping or not carry ``gap_p50_ms`` from one heap to the other
(PERF.md section 6, PR 34: ``mixtral8x7b-chat`` read 25-30 here and spread
14%). An untraced run logs it too (``detail.gaps``); over 6 there, look at
``perf/gaps.py``'s histogram and sweep the cell's knee again. A traced run's
client reads its hops after every step, which at eight lanes lengthens the
gaps by 6-12% and widens their middle (1.4-2.5 untraced, 3.7-8.2 traced in
the saturated cells): hold a result line's value against the cell's own
earlier lines, not against 6."""
import numpy as np

UNIT, LAYER, MOVES = "%", "service (due time to reply, perf/loadgen.py)", "gap_p50_ms"


def mid_width_pct(gaps):
    gaps = np.asarray(gaps, float)
    if not gaps.size:
        return None
    p40, p50, p60 = np.percentile(gaps, (40, 50, 60))
    return float(100.0 * (p60 - p40) / p50)


def read(record):
    return mid_width_pct(record.gaps_ms())
