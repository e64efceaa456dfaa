#!/usr/bin/env python3
"""The correctness check of one cell over many seeds, on one start of its servers:

    python3 perf/prove_correct.py --workload <cell> --seeds 2147483659,2147483693,...

How a family's limits (perf/reference/<family>.py: row and median bound, tie
margin, positions allowed outside) are set and re-proved: every compared row's error and decision margin, per
seed, goes to ``chiprun_out/correct_<cell>.jsonl``; the last lines printed say
how many seeds passed and how close the worst compared row came to the bound.
No window is measured and no metric is printed."""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True, help="comma-separated")
    args = parser.parse_args(argv)
    from perf import correct, costs, reference, run
    from perf.config import load as load_config

    benchmark = json.loads((ROOT / "BENCHMARK.json").read_text())
    _, config_entry = run.find_cell(benchmark, args.workload)
    config = load_config(ROOT / config_entry["file"], config_entry["name"])
    hidden = costs.layer_params(config["family"], config["config"])["hidden"]
    seeds = [int(s) for s in args.seeds.split(",")]

    def meanwhile() -> dict:
        out = {}
        for seed in seeds:
            t = time.perf_counter()
            x = correct.inputs(seed, hidden)
            out[seed] = (x, *reference.run(config, x))
            run.log(f"reference for seed {seed}: {time.perf_counter() - t:.1f}s")
        return out

    out_dir = ROOT / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    passed, worst = 0, 0.0
    run_dir = run.WORK_DIR / "runs" / f"correct-{args.workload}"
    with run.serving(config, ROOT / config_entry["file"], run_dir, meanwhile=meanwhile) as up, \
            open(out_dir / f"correct_{args.workload}.jsonl", "a") as out:
        for seed in seeds:
            x, want, margin, weight_checks = up["meanwhile"][seed]
            run.same_weights(up["ready"], weight_checks)
            verdict = run.check(up["remote"], up["children"], config, x, want, margin)
            out.write(json.dumps({"seed": seed, **verdict}) + "\n")
            out.flush()
            passed += verdict["ok"]
            near = max(verdict[k]["max"] or 0.0 for k in ("prefill", "decode")) / verdict["row_bound"]
            worst = max(worst, near)
            shown = {k: {f: verdict[k][f] for f in ("compared", "median", "max", "positions_outside", "left_out_max")} for k in ("prefill", "decode")}
            print(f"seed {seed}: ok={verdict['ok']} identical={verdict['repeat_identical']} {json.dumps(shown)}", flush=True)
        run.stop_and_dump(up["children"])
    print(f"{passed} of {len(seeds)} seeds correct; the worst compared row reached {100 * worst:.0f}% of the row bound", flush=True)
    return 0 if passed == len(seeds) else 1


if __name__ == "__main__":
    sys.exit(main())
