#!/usr/bin/env python3
"""Latent attention held to the reference at a long context, through the
served path:

    chiprun --timeout 3000 -- python3 benchmarks/prove_latent_long.py --workload kanana2-ctx32k --seed 2147483659 [--rows 4096]

perf/correct.py's sessions end at 144 positions: one block of either walk
(ops/latent_attention.py), and far inside the range where bf16's rounding of
16k weights of a softmax adds up. This runs one session of ROWS fresh rows
(16,384) through the cell's configuration at its published widths: the prompt
rides mixed steps of the servers' budget (the expanded form, 128 blocks of
positions a chunk at the end), then STEPS decode steps (the absorbed form, 5
blocks), while two short sessions decode in other lanes. The last TAIL prompt
rows and every decode row are held to the reference's row of their position by
perf/correct.py's ``judge`` under the family's limits at this depth.

perf/prove_long.py does this for a family that selects: it calls the
reference's ``block(..., choose=)`` and its ``index_parts``, and makes a
layer's tensors without its kind, so it does not run a family of two kinds of
layer and no selection unedited. This script borrows its sessions and its
inputs (``served_rows``, ``inputs``) and computes the reference itself: every
layer over the whole sequence in float32 at highest matmul precision, the
attention in blocks of rows (perf/reference/<family>.py ``block(...,
rows=)``), on the chip, by a child of this script, BEFORE the servers start.

One control, which must come out not correct: the reference with float8
(e4m3) weights and layer inputs, one precision below the one the configuration
states. Rows go to ``chiprun_out/latent_long_<cell>.jsonl``. No window is
measured and no metric is printed."""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

BLOCK = 512  # rows of the reference's attention at once
VARIANTS = ("reference", "float8")


def reference_rows(config: dict, x: np.ndarray, compared: np.ndarray, block: int = BLOCK) -> dict:
    """``x`` [seq, hidden] through the configuration's layers in float32, and
    again with float8 weights and layer inputs; the rows ``compared`` of each,
    and per compared position the smallest decision margin over the layers."""
    import jax
    import jax.numpy as jnp

    from perf import reference, weights

    family, maker = reference.family_of(config["family"]), weights.family_of(config["family"])
    hf = config["config"]
    n_layers = sum(span["num_blocks"] for span in config["servers"])
    kinds = reference.kinds_of(config["family"], hf) or [()] * n_layers
    seq = len(x)
    padded = -(-seq // block) * block  # rows of zeros after the sequence: no row before them sees them
    x = jnp.pad(jnp.asarray(x, jnp.float32), ((0, padded - seq), (0, 0)))
    f8 = lambda t: jax.lax.reduce_precision(t, exponent_bits=4, mantissa_bits=3)  # a convert to float8 and back the compiler removes
    casts = dict(zip(VARIANTS, (lambda t: t, f8)))

    def program(kind: tuple, cast):
        def layer(index, h):
            w = maker.layer_tensors(hf, index, weights.Draws(config["weights_seed"]), *kind)
            return family.block(hf, {k: cast(v.astype(jnp.float32)) for k, v in w.items()}, cast(h), *kind, rows=block)

        return jax.jit(layer)

    out = {}
    with jax.default_matmul_precision("highest"):
        for name, cast in casts.items():
            programs = {kind: program(kind, cast) for kind in dict.fromkeys(kinds[:n_layers])}
            h, margin = x, jnp.full(padded, jnp.inf)
            for index in range(n_layers):
                h, layer_margin = programs[kinds[index]](jnp.uint32(index), h)
                margin = jnp.minimum(margin, layer_margin)
            out[name] = np.asarray(h[compared], np.float32)
            if name == "reference":
                out["margin"] = np.asarray(margin[compared], np.float32)
    return out


def reference_on_the_chip(workload: str, seed: int, rows: int, path: Path) -> dict:
    """``reference_rows`` by a child of this script that takes the chip, and leaves it before the servers start."""
    env = {k: v for k, v in os.environ.items() if k not in ("JAX_PLATFORMS", "BENCH_RUN")}
    cmd = [sys.executable, str(Path(__file__).resolve()), "--workload", workload, "--seed", str(seed), "--rows", str(rows),
           "--reference-to", str(path)]
    subprocess.run(cmd, env=env, check=True, timeout=3000)
    with np.load(path) as saved:
        return {k: saved[k] for k in saved.files}


def prove(benchmark: dict, workload: str, seed: int, rows: int, *, root: Path = ROOT, work_dir: Path = None, allow_cpu: bool = False) -> dict:
    from perf import correct, costs, prove_long, reference, run
    from perf.config import load as load_config

    work_dir = work_dir or run.WORK_DIR
    _, config_entry = run.find_cell(benchmark, workload)
    config = load_config(root / config_entry["file"], config_entry["name"])
    hidden = costs.layer_params(config["family"], config["config"])["hidden"]
    limits = reference.limits(config)
    x = prove_long.inputs(seed, rows, hidden)
    compared = np.arange(rows - prove_long.TAIL, rows + prove_long.STEPS)
    t = time.perf_counter()
    if allow_cpu:
        wanted = reference_rows(config, x, compared, block=min(BLOCK, 64))
    else:
        work_dir.mkdir(parents=True, exist_ok=True)
        wanted = reference_on_the_chip(workload, seed, rows, work_dir / f"latent-long-{workload}-{seed}.npz")
    run.log(f"reference and its float8 control over {len(x)} positions: {time.perf_counter() - t:.1f}s")

    def full(rows_of: np.ndarray, fill: float = 0.0) -> np.ndarray:  # ``judge`` reads a reference row by its position
        out = np.full((len(x), *rows_of.shape[1:]), fill, np.float32)
        out[compared] = rows_of
        return out

    def nearest(verdict: dict) -> float:  # the largest number a verdict holds, in units of its limit
        return max(max(verdict[k]["max"] / limits["row_bound"], verdict[k]["median"] / limits["median_bound"]) for k in ("prefill", "decode"))

    out_dir = root / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    margin = full(wanted["margin"], np.inf)
    with run.serving(config, root / config_entry["file"], work_dir / "runs" / f"latent-long-{workload}", root=root, work_dir=work_dir,
                     allow_cpu=allow_cpu) as up:
        budget = min(r["prefill_token_budget"] for r in up["ready"])
        run.tell_all(up["children"], "mark long")
        t = time.perf_counter()
        got = prove_long.served_rows(up["remote"], x, rows)
        run.log(f"the long session: {time.perf_counter() - t:.1f}s")
        run.tell_all(up["children"], "mark long_end")
        dumps = run.stop_and_dump(up["children"])
    verdicts = {name: correct.judge(got, full(wanted[name]), margin, limits) for name in VARIANTS}
    for kind, position, error, row_margin in verdicts["reference"]["rows"]:
        print(f"{kind} {position}: error {error:.5f} (row bound {limits['row_bound']:.4f}), margin {row_margin:.4f}", flush=True)
    with open(out_dir / f"latent_long_{workload}.jsonl", "a") as out:
        out.write(json.dumps({"seed": seed, "rows": rows, **verdicts}) + "\n")
    for d in dumps:  # the prompt rode mixed steps of the budget in the expanded form, the decode rows took the absorbed one
        stats = {k: d["marks"]["long_end"]["stats"].get(k, 0) - d["marks"]["long"]["stats"].get(k, 0)
                 for k in ("mixed_steps", "latent_rows_expanded", "latent_rows_absorbed")}
        if stats["mixed_steps"] < rows // budget or stats["latent_rows_expanded"] < rows or stats["latent_rows_absorbed"] < prove_long.STEPS:
            raise SystemExit(f"the prompt rode {stats['mixed_steps']} mixed steps of {budget}, {stats['latent_rows_expanded']} rows expanded and "
                             f"{stats['latent_rows_absorbed']} absorbed: nothing was proved")
    summary = {"correct": verdicts["reference"]["ok"], "nearest": nearest(verdicts["reference"]),
               "float8_not_correct": not verdicts["float8"]["ok"], "float8_nearest": nearest(verdicts["float8"])}
    shown = {name: {k: {f: v[k][f] for f in ("compared", "median", "max", "positions_outside")} for k in ("prefill", "decode")} for name, v in verdicts.items()}
    print(f"seed {seed}, {rows} + {prove_long.STEPS} positions: {json.dumps(shown)}", flush=True)
    return summary


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--rows", type=int, default=16384)
    parser.add_argument("--reference-to", help=argparse.SUPPRESS)  # this script's own child: the reference, on the device it finds
    args = parser.parse_args(argv)
    benchmark = json.loads((ROOT / "BENCHMARK.json").read_text())
    if args.reference_to:  # perf/run.py is not imported here: it holds the process that imports it to the CPU
        from perf import costs, prove_long
        from perf.config import load as load_config

        cell = next(w for w in benchmark["workloads"] if w["name"] == args.workload)
        config_entry = next(c for c in benchmark["configs"] if c["name"] == cell["config"])
        config = load_config(ROOT / config_entry["file"], config_entry["name"])
        hidden = costs.layer_params(config["family"], config["config"])["hidden"]
        x = prove_long.inputs(args.seed, args.rows, hidden)
        np.savez(args.reference_to, **reference_rows(config, x, np.arange(args.rows - prove_long.TAIL, args.rows + prove_long.STEPS)))
        return 0
    s = prove(benchmark, args.workload, args.seed, args.rows)
    print(f"correct={s['correct']}, the nearest number at {100 * s['nearest']:.0f}% of its limit; "
          f"float8 weights and inputs: not correct={s['float8_not_correct']} ({s['float8_nearest']:.1f} times a limit)", flush=True)
    return 0 if s["correct"] and s["float8_not_correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
