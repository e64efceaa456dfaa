#!/usr/bin/env python3
"""Several runs of one cell in one call, as the contract's proof wants them:

    python3 perf/prove.py --workload <cell> --seeds 11,12,13 --sets 2 [--seconds S] [--trace-last]

Runs ``BENCHMARK.json``'s command once per seed and set, one process after
the other (a chip belongs to one process at a time), keeps every result line
in ``chiprun_out/prove_<cell>.jsonl`` with the run's stderr and the server
logs beside it, and prints each metric's median and spread per set (distance
between the quartiles of ``statistics.quantiles(n=4)`` over the median, and
the same without the set's farthest run, which is what the driver holds to
half the bound). Each run's line also shows what its ``detail`` says of the
window: ``gap_mid_width_pct``, the mean decode batch, how late the generator
ran, the programs that compiled.
This process never imports JAX."""

from __future__ import annotations

import argparse
import json
import shutil
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
OUT = ROOT / "chiprun_out"


def spread(values: list) -> float:
    if len(values) < 2:
        return float("nan")
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def trimmed_spread(values: list) -> float:
    """The spread without the run farthest from the median, where that is the
    narrower: what the driver holds a set to (at most half the bound)."""
    if len(values) < 3:
        return spread(values)
    median = statistics.median(values)
    rest = sorted(values, key=lambda v: abs(v - median))[:-1]
    return min(spread(values), spread(rest))


def detail_of(stderr: str):
    """The ``detail`` a run logged on standard error (perf/run.py main), or None."""
    for line in reversed(stderr.splitlines()):
        _, found, rest = line.partition("] detail: ")
        if found:
            return json.loads(rest)
    return None


def one_run(command: list, workload: str, seed: int, seconds, trace: int, tag: str) -> dict:
    cmd = [*command, "--workload", workload, "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)]
    t0 = time.perf_counter()
    proc = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    wall = time.perf_counter() - t0
    (OUT / f"{tag}.stderr.txt").write_text(proc.stderr[-200_000:])
    logs = ROOT / "perf" / ".work" / "runs" / workload
    for log in logs.glob("child*.log"):
        shutil.copy(log, OUT / f"{tag}.{log.name}")
    lines = proc.stdout.strip().splitlines()
    if proc.returncode != 0 or not lines:
        print(f"{tag}: exit {proc.returncode} after {wall:.0f}s\n{proc.stderr[-3000:]}", flush=True)
        return {"rc": proc.returncode, "wall_s": wall}
    result = json.loads(lines[-1])
    result.update(rc=0, wall_s=wall, seed=seed, trace=trace, detail=detail_of(proc.stderr))
    return result


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True, help="comma-separated; the same in every set")
    parser.add_argument("--sets", type=int, default=2)
    parser.add_argument("--seconds", default=None, help="default: BENCHMARK.json's run_seconds")
    parser.add_argument("--trace-last", action="store_true", help="one more run, traced, after the sets")
    args = parser.parse_args(argv)
    benchmark = json.loads((ROOT / "BENCHMARK.json").read_text())
    seconds = args.seconds or benchmark["run_seconds"]
    seeds = [int(s) for s in args.seeds.split(",")]
    OUT.mkdir(exist_ok=True)
    out_path = OUT / f"prove_{args.workload}.jsonl"
    sets = []
    with open(out_path, "a") as out:
        for k in range(args.sets):
            results = []
            for seed in seeds:
                r = one_run(benchmark["command"], args.workload, seed, seconds, 0, f"{args.workload}.set{k}.seed{seed}")
                r["set"] = k
                out.write(json.dumps(r) + "\n")
                out.flush()
                if r["rc"] != 0:
                    return 1
                shown = {n: round(m["value"], 3) for n, m in r["metrics"].items()}
                d = r["detail"] or {}
                print(f"set {k} seed {seed}: correct={r['correct']} failed={r['failed']}/{r['attempted']} "
                      f"wall={r['wall_s']:.0f}s {shown} middle fifth {d.get('gaps', {}).get('mid_width_pct')}% "
                      f"batch {d.get('decode_batch_mean')} late p95 {d.get('gen_late_ms_p95')} ms "
                      f"recompiled {d.get('recompiled')}", flush=True)
                results.append(r)
            sets.append(results)
        if args.trace_last:
            r = one_run(benchmark["command"], args.workload, seeds[0], seconds, 1, f"{args.workload}.traced")
            r["set"] = "traced"
            out.write(json.dumps(r) + "\n")
            print(f"traced: {json.dumps(r)}", flush=True)
    for name in (sets[0][0]["metrics"] if sets and sets[0] else ()):
        row = []
        for k, results in enumerate(sets):
            # the first run of the first set compiles: its set-up is not held to the bound
            values = [r["metrics"][name]["value"] for i, r in enumerate(results) if not (name == "setup_s" and k == 0 and i == 0)]
            if not values:
                continue
            row.append(f"set {k}: median {statistics.median(values):.4f} spread {100 * spread(values):.2f}% "
                       f"(without its farthest run {100 * trimmed_spread(values):.2f}%)")
        print(f"{name}: " + "; ".join(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
