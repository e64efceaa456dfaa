#!/usr/bin/env python3
"""Find a chat cell's knee, once, on the chip:

    python3 perf/sweep.py --workload falcon40b-chat --rates 1.5,2,2.5,3,3.5 [--seconds S] [--seed N]

Each rate is one whole run of the cell (a process of its own) with only the
mix's ``rate_rps`` replaced. The knee is the highest rate at which no session
failed, the sessions drained promptly and the second half of the window
waited no longer for its first token than the first half did (no backlog
grows). The cell's traffic file then gets 0.8 of it, by hand, and the sweep's
table goes into PERF.md. Results: ``chiprun_out/sweep_<cell>.jsonl``."""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def one(args) -> int:
    from perf import run, traffic

    benchmark = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell, _ = run.find_cell(benchmark, args.workload)
    mix = traffic.load_mix(cell["traffic"])
    mix["arrival"] = {**mix["arrival"], "rate_rps": args.rate}
    sweep_dir = run.WORK_DIR / "sweep" / "traffic"
    sweep_dir.mkdir(parents=True, exist_ok=True)
    (sweep_dir / f"{cell['traffic']}.json").write_text(json.dumps(mix))
    benchmark["end_to_end"] = [dict(m, workloads=[args.workload]) for m in benchmark["end_to_end"]]  # report them all
    result = run.run_cell(benchmark, args.workload, args.seed, args.seconds, False, traffic_dir=sweep_dir)
    print(json.dumps(result), flush=True)
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--rates", default=None)
    parser.add_argument("--rate", type=float, default=None, help=argparse.SUPPRESS)  # one run: the sweep's own child
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--seed", type=int, default=20260927)
    args = parser.parse_args(argv)
    if args.seconds is None:
        args.seconds = json.loads((ROOT / "BENCHMARK.json").read_text())["run_seconds"]
    if args.rate is not None:
        return one(args)
    out_dir = ROOT / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    with open(out_dir / f"sweep_{args.workload}.jsonl", "a") as out:
        for rate in [float(r) for r in args.rates.split(",")]:
            proc = subprocess.run(
                [sys.executable, __file__, "--workload", args.workload, "--rate", str(rate),
                 "--seconds", str(args.seconds), "--seed", str(args.seed)],
                cwd=ROOT, capture_output=True, text=True,
            )
            lines = proc.stdout.strip().splitlines()
            if proc.returncode != 0 or not lines:
                print(f"rate {rate}: exit {proc.returncode}\n{proc.stderr[-3000:]}", flush=True)
                return 1
            r = json.loads(lines[-1])
            out.write(json.dumps({"rate": rate, **r}) + "\n")
            out.flush()
            shown = {n: round(m["value"], 2) for n, m in r["metrics"].items()}
            d = r["detail"]
            print(f"rate {rate}: failed {r['failed']}/{r['attempted']} {shown} drain {d['drain_s']:.1f}s "
                  f"ttft p50 halves {d['ttft_p50_ms_first_half']}/{d['ttft_p50_ms_second_half']} ms "
                  f"batch {d['decode_batch_mean']} middle fifth {d['gaps'].get('mid_width_pct')}% "
                  f"cache {d['cache_events']}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
