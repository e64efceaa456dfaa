#!/usr/bin/env python3
"""Two controls of a cell whose configuration has state-space layers, reference
against reference, judged by the cell's own limits (perf/correct.py ``judge``):

    python3 benchmarks/prove_scan_matters.py --workload jamba2-3b-ctx2k --seeds 2147483659,2147483693

Both have to come out NOT correct, or the limits could hide what they are
there to show:

- *the scan left out*: the reference with ``y = D u`` in its state-space
  layers (perf/reference/jamba.py ``DROP_STATE_TERM``), which is what a served
  block would give whose scan did nothing;
- *one precision lower*: the reference itself with every weight and every
  layer's input rounded to float8 (e4m3), the nearest precision below the
  bfloat16 the configuration is served in.

With ``--served`` the first control is also made against the SERVED rows: the
cell's servers are started (a TPU) and perf/prove_correct.py runs its check
against the altered reference; every seed then has to fail. No window is
measured and no metric is printed. The references run wherever JAX runs:
layer by layer, 144 positions, a few hundred MB."""
import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def rows_of(x: np.ndarray) -> list:
    """Every position of ``x`` as the rows ``judge`` takes: the last quarter as decode rows."""
    cut = 3 * len(x) // 4
    return [("prefill" if p < cut else "decode", p, x[p]) for p in range(len(x))]


def reference_float8(config: dict, hidden: np.ndarray) -> np.ndarray:
    """``perf.reference.run`` with each layer's tensors and input rounded to float8 (e4m3) on their way in."""
    import jax
    import jax.numpy as jnp

    from perf import reference, weights

    family, maker = reference.family_of(config["family"]), weights.family_of(config["family"])
    hf = config["config"]
    n_layers = sum(span["num_blocks"] for span in config["servers"])
    kinds = reference.kinds_of(config["family"], hf) or [()] * n_layers
    low = lambda t: t.astype(jnp.float8_e4m3fn).astype(jnp.float32)

    def program(kind: tuple):
        def layer(index, x):
            w = maker.layer_tensors(hf, index, weights.Draws(config["weights_seed"]), *kind)
            return family.block(hf, {k: low(v.astype(jnp.float32)) for k, v in w.items()}, low(x), *kind)[0]

        return jax.jit(layer)

    with jax.default_matmul_precision("highest"):
        programs = {kind: program(kind) for kind in dict.fromkeys(kinds[:n_layers])}
        x = jnp.asarray(hidden, jnp.float32)
        for index in range(n_layers):
            x = programs[kinds[index]](jnp.uint32(index), x)
        return np.asarray(x, np.float32)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True, help="comma-separated")
    parser.add_argument("--served", action="store_true", help="also hold the served rows to the reference without the scan (a TPU)")
    args = parser.parse_args(argv)
    from perf import run  # first: it holds this process to the CPU before anything imports JAX
    from perf import correct, costs, prove_correct, reference
    from perf.config import load as load_config

    benchmark = json.loads((ROOT / "BENCHMARK.json").read_text())
    _, config_entry = run.find_cell(benchmark, args.workload)
    config = load_config(ROOT / config_entry["file"], config_entry["name"])
    family = reference.family_of(config["family"])
    if not hasattr(family, "DROP_STATE_TERM"):
        raise SystemExit(f"{config['family']}: the reference has no state-space layer to leave the scan out of")
    hidden = costs.layer_params(config["family"], config["config"])["hidden"]
    limits = reference.limits(config)
    failed = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        x = correct.inputs(seed, hidden)
        want, margin, _ = reference.run(config, x)
        family.DROP_STATE_TERM = True
        try:
            without = reference.run(config, x)[0]
        finally:
            family.DROP_STATE_TERM = False
        for name, got in (("scan left out", without), ("float8", reference_float8(config, x))):
            verdict = correct.judge(rows_of(got), want, margin, limits)
            shown = {k: {f: verdict[k][f] for f in ("median", "max")} | {"outside": len(verdict[k]["positions_outside"]), "rows": verdict[k]["rows"]}
                     for k in ("prefill", "decode")}
            print(f"seed {seed}: {name}: ok={verdict['ok']} against median {limits['median_bound']:.4g}, row {limits['row_bound']:.4g}: {json.dumps(shown)}", flush=True)
            failed += not verdict["ok"]
    n = 2 * len(args.seeds.split(","))
    print(f"{failed} of {n} controls not correct", flush=True)
    served_ok = True
    if args.served:
        family.DROP_STATE_TERM = True
        try:
            served_ok = prove_correct.main(["--workload", args.workload, "--seeds", args.seeds]) == 1
        finally:
            family.DROP_STATE_TERM = False
        print(f"the served rows against the reference without the scan: {'some seed not correct, as it must be' if served_ok else 'every seed CORRECT'}", flush=True)
    return 0 if failed == n and served_ok else 1


if __name__ == "__main__":
    sys.exit(main())
