#!/usr/bin/env python3
"""Six controls of a cell whose configuration is a block of two latent
attentions around a shortcut-connected expert layer with identity experts,
reference against reference, judged by the cell's own limits (perf/correct.py
``judge``):

    python3 benchmarks/prove_scmoe_matters.py --workload longcatflash-ctx2k --seeds 2147483659,2147483693

Each has to come out NOT correct, or the limits could hide what they are
there to show (perf/reference/longcat_flash.py ``CONTROLS``):

- *bf16_router*: the router's product in bfloat16 where the published router
  runs in float32 (what a served block would give that left the router in the
  activations' dtype);
- *no_q_scale*, *no_kv_scale*: ``sqrt(hidden / q_lora_rank)`` on the query, or
  ``sqrt(hidden / kv_lora_rank)`` on the normed latent, left out;
- *renormalised*: the kept weights divided by their sum, as the sigmoid rule
  does and this one does not;
- *no_identities*: the identity experts' weighted sum left out;
- *float8*: one precision lower, the reference itself with every weight and
  every block's input rounded to float8 (e4m3), the nearest precision below the
  bfloat16 the configuration is served in (benchmarks/prove_scan_matters.py
  ``reference_float8``).

With ``--served`` each control is also made against the SERVED rows: the
cell's servers are started (a TPU) once a control and perf/prove_correct.py
runs its check against the altered reference; some seed then has to fail. No
window is measured and no metric is printed. The references run wherever JAX
runs: layer by layer, 144 positions, some 10 GB at the published widths."""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmarks.prove_scan_matters import reference_float8, rows_of  # noqa: E402


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True, help="comma-separated")
    parser.add_argument("--controls", default=None, help="comma-separated; default: all five")
    parser.add_argument("--served", action="store_true", help="also hold the served rows to each altered reference (a TPU)")
    args = parser.parse_args(argv)
    from perf import run  # first: it holds this process to the CPU before anything imports JAX
    from perf import correct, costs, prove_correct, reference
    from perf.config import load as load_config

    benchmark = json.loads((ROOT / "BENCHMARK.json").read_text())
    _, config_entry = run.find_cell(benchmark, args.workload)
    config = load_config(ROOT / config_entry["file"], config_entry["name"])
    family = reference.family_of(config["family"])
    if not hasattr(family, "CONTROLS"):
        raise SystemExit(f"{config['family']}: the reference has no such controls")
    controls = args.controls.split(",") if args.controls else list(family.CONTROLS)
    hidden = costs.layer_params(config["family"], config["config"])["hidden"]
    limits = reference.limits(config)
    seeds = [int(s) for s in args.seeds.split(",")]
    seen = {name: 0 for name in [*controls, "float8"]}
    for seed in seeds:
        x = correct.inputs(seed, hidden)
        want, margin, _ = reference.run(config, x)
        for name in seen:
            family.CONTROL = name if name != "float8" else None
            try:
                got = reference_float8(config, x) if name == "float8" else reference.run(config, x)[0]
            finally:
                family.CONTROL = None
            verdict = correct.judge(rows_of(got), want, margin, limits)
            shown = {k: {f: verdict[k][f] for f in ("median", "max")} | {"outside": len(verdict[k]["positions_outside"]), "rows": verdict[k]["rows"]}
                     for k in ("prefill", "decode")}
            print(f"seed {seed}: {name}: ok={verdict['ok']} against median {limits['median_bound']:.4g}, row {limits['row_bound']:.4g}: {json.dumps(shown)}", flush=True)
            seen[name] += not verdict["ok"]
    print(f"controls judged not correct, of {len(seeds)} seeds each: {json.dumps(seen)}", flush=True)
    served_ok = True
    if args.served:
        for name in controls:
            family.CONTROL = name
            try:
                failed = prove_correct.main(["--workload", args.workload, "--seeds", args.seeds]) == 1
            finally:
                family.CONTROL = None
            print(f"the served rows against the reference with {name}: {'some seed not correct, as it must be' if failed else 'every seed CORRECT'}", flush=True)
            served_ok = served_ok and failed
    return 0 if all(n == len(seeds) for n in seen.values()) and served_ok else 1


if __name__ == "__main__":
    sys.exit(main())
