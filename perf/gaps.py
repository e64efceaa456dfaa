#!/usr/bin/env python3
"""Where a cell's median gap stands among its gaps:

    python3 perf/gaps.py --workload <cell> --seeds 11,12,13 [--seconds S] [--trace 1]
    python3 perf/gaps.py --read chiprun_out/<run>.stderr.txt [more ...]

The first form runs ``BENCHMARK.json``'s command once a seed (``prove.py``'s
runs: one process after the other, lines and logs kept under
``chiprun_out/``); the second reads runs that were made already. Either way it
prints what ``summary`` below found in the run's window: the gaps' p40, p45,
p50, p55 and p60, the width of that middle fifth over the median
(``gap_mid_width_pct``), the samples by how many sessions were decoding when
the reply came, each group's median beside its share, and the histogram in
bins of 0.25 ms. Every run logs that summary in its ``detail`` line on
standard error, so nothing has to be run again to look. A median between two
heaps (a middle fifth over 6% wide) follows the order of the arrivals and the
host's timing, not the program: PERF.md section 6, PR 34, and perf/README.md
say what to do then. This process never imports JAX."""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from perf.layer_metrics.gap_mid_width_pct import mid_width_pct  # noqa: E402

BIN_MS = 0.25
BINS_UP_TO = 4.0  # times the median: what lies beyond (a prompt chunk's step, a stall) is counted in one number
PERCENTILES = (40, 45, 50, 55, 60)


def decoding_at(record, times: np.ndarray) -> np.ndarray:
    """How many sessions were between their first reply and their last when
    each of ``times`` came, by the load generator's clock: the lanes that had
    a token on the server or on its way. The child's dump has no count of
    steps by their lanes (``batcher.stats`` keeps sums)."""
    spans = [(s.first_reply, s.replies[-1][0]) for s in record.sessions if s.first_reply is not None and s.replies]
    starts, ends = (np.sort(column) for column in zip(*spans)) if spans else (np.empty(0), np.empty(0))
    return np.searchsorted(starts, times, side="right") - np.searchsorted(ends, times, side="left")


def summary(record) -> dict:
    """JSON-ready; ``{"n": 0}`` for a window without a decode reply."""
    samples = record.gap_samples()
    gaps = samples[:, 1]
    if not gaps.size:
        return {"n": 0}
    out = {"n": int(gaps.size), **{f"p{q}": float(v) for q, v in zip(PERCENTILES, np.percentile(gaps, PERCENTILES))},
           "mid_width_pct": mid_width_pct(gaps)}
    lanes = decoding_at(record, samples[:, 0])
    out["by_lanes"] = {str(int(k)): [float(np.mean(lanes == k)), float(np.median(gaps[lanes == k]))] for k in np.unique(lanes)}
    shown = gaps[gaps < BINS_UP_TO * out["p50"]]
    bins, counts = np.unique(np.floor(shown / BIN_MS).astype(int), return_counts=True)
    out["bin_ms"], out["bins"], out["beyond"] = BIN_MS, [[float(b * BIN_MS), int(c)] for b, c in zip(bins, counts)], int(gaps.size - shown.size)
    return out


def show(title: str, s: dict, out=sys.stdout) -> None:
    if not s.get("n"):
        print(f"{title}: no decode reply inside the window", file=out)
        return
    marks = " ".join(f"p{q} {s[f'p{q}']:.3f}" for q in PERCENTILES)
    print(f"{title}: {s['n']} gaps, {marks} ms, middle fifth {s['mid_width_pct']:.1f}% of the median", file=out)
    print("  decoding  share  median ms", file=out)
    for k, (share, median) in sorted(s["by_lanes"].items(), key=lambda kv: int(kv[0])):
        print(f"  {k:>8}  {100 * share:4.1f}%  {median:.3f}", file=out)
    top = max(c for _, c in s["bins"])
    for lo, count in s["bins"]:
        edge = "<" if lo <= s["p50"] < lo + s["bin_ms"] else " "
        print(f"  {lo:6.2f} {edge} {'#' * max(1, round(60 * count / top)):<60} {count}", file=out)
    print(f"  beyond {BINS_UP_TO:g} medians: {s['beyond']}", file=out, flush=True)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload")
    parser.add_argument("--seeds", help="comma-separated")
    parser.add_argument("--seconds", default=None, help="default: BENCHMARK.json's run_seconds")
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--read", nargs="+", default=[], help="standard error of runs already made")
    args = parser.parse_args(argv)
    from perf import prove  # the runs and their standard error are its; a run itself (perf/run.py) needs only ``summary``

    for path in args.read:
        detail = prove.detail_of(Path(path).read_text(errors="replace"))
        if detail is None or "gaps" not in detail:
            print(f"{path}: no detail line with gaps in it")
            return 1
        show(path, detail["gaps"])
    if args.read:
        return 0
    if not (args.workload and args.seeds):
        parser.error("--workload and --seeds, or --read")
    benchmark = json.loads((ROOT / "BENCHMARK.json").read_text())
    prove.OUT.mkdir(exist_ok=True)
    with open(prove.OUT / f"gaps_{args.workload}.jsonl", "a") as out:
        for seed in (int(s) for s in args.seeds.split(",")):
            r = prove.one_run(benchmark["command"], args.workload, seed, args.seconds or benchmark["run_seconds"], args.trace,
                              f"{args.workload}.gaps.seed{seed}")
            out.write(json.dumps(r) + "\n")
            out.flush()
            if r["rc"] != 0:
                return 1
            shown = {n: round(m["value"], 3) for n, m in r["metrics"].items()} if not args.trace else {}
            show(f"{args.workload} seed {seed} correct={r['correct']} failed={r['failed']}/{r['attempted']} {shown}", r["detail"]["gaps"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
