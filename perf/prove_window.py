#!/usr/bin/env python3
"""A window whose pages go back, held to the reference at a context of several windows:

    python3 perf/prove_window.py --workload smallthinker21b-ctx16k --seed 2147483659 [--rows 4096]

perf/correct.py's sessions end at 144 positions, far inside a window of 4,096: they prove the block, the pages and the
experts in every run, and nothing of the window or of the release of its pages. This does: through the served path of the
cell's configuration, at its published widths, one session prefills ROWS fresh rows (12,288, three windows), which ride
mixed steps of the servers' budget, and decodes STEPS more, while two short sessions decode in other lanes. The last TAIL
prompt rows and every decode row are held to the reference's row of their position by perf/correct.py's ``judge`` under
the family's limits at this depth, and the batcher's counters over the session must show pages given back while it ran
(``window_pages_released``) and held equal to in reach (``window_pages_held`` = ``window_pages_in_reach``).

Two controls: a reference whose windowed layers attend to the WHOLE context (a server that ignored the window), and one
whose windowed layers see the last HALF window only (a ring half as long). With random weights a row of 4k-12k positions
attends nearly evenly, so a control moves a row by a few percent and may stay inside limits that were sized for bf16: so
for each control the script prints the served rows' median error against the control over their median error against
the reference, and fails where either ratio is under MIN_RATIO (a server that did what a control does reads under 1).
A slip of ONE page of 64 in 4,096 moves a row by less than bf16 does at these widths: the chip cannot see it, and the CPU
tests hold the window to the position at float32 and a toy window instead (tests/test_smallthinker.py,
tests/test_span_cache.py).

The reference runs every layer over the whole sequence in float32 at highest matmul precision, its attention in blocks
of rows so that it fits (perf/reference/<family>.py ``block(..., rows=)``). It is computed on the chip, by a child of
this script, BEFORE the servers start (a chip belongs to one process at a time), and on the CPU in the tests. Rows go
to ``chiprun_out/window_<cell>.jsonl``. No window of time is measured and no metric is printed."""

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

from perf.prove_long import served_rows  # noqa: E402  (the long session beside two short ones: the same traffic)

ROWS, STEPS, TAIL = 12288, 32, 32
BLOCK = 512  # rows of the reference's attention at once
VARIANTS = ("reference", "whole_context", "half_window")
MIN_RATIO = 1.5
WINDOW_KEY = "sliding_window_size"


def variants(hf: dict, positions: int) -> dict:
    """The configuration's keys for the reference and the two controls: the window as published, one no position
    reaches the end of, and half of it."""
    window = hf[WINDOW_KEY]
    return dict(zip(VARIANTS, (hf, {**hf, WINDOW_KEY: positions + 1}, {**hf, WINDOW_KEY: window // 2})))


def reference_rows(config: dict, x: np.ndarray, compared: np.ndarray, block: int = BLOCK) -> dict:
    """``x`` [seq, hidden] through the configuration's layers under each of VARIANTS' windows (float32, weights made layer
    by layer as ``perf.reference.run`` makes them), the rows ``compared`` of each; and ``margin``, the reference's
    smallest decision margin over the layers, a compared row."""
    import jax
    import jax.numpy as jnp

    from perf import reference, weights

    family, maker = reference.family_of(config["family"]), weights.family_of(config["family"])
    hf = config["config"]
    n_layers = sum(span["num_blocks"] for span in config["servers"])
    kinds = reference.kinds_of(config["family"], hf)
    seq = len(x)
    padded = -(-seq // block) * block  # rows of zeros after the sequence: no row before them sees them
    x = jnp.pad(jnp.asarray(x, jnp.float32), ((0, padded - seq), (0, 0)))
    rows = jnp.asarray(compared)

    def program(keys: dict, kind: tuple):
        def layer(index, h):
            w = maker.layer_tensors(hf, index, weights.Draws(config["weights_seed"]), *kind)
            return family.block(keys, {k: v.astype(jnp.float32) for k, v in w.items()}, h, *kind, rows=block)

        return jax.jit(layer)

    out = {}
    with jax.default_matmul_precision("highest"):
        for name, keys in variants(hf, padded).items():
            programs = {kind: program(keys, kind) for kind in dict.fromkeys(kinds[:n_layers])}
            h, margin = x, jnp.full(padded, jnp.inf)
            for index in range(n_layers):
                h, layer_margin = programs[kinds[index]](jnp.uint32(index), h)
                margin = jnp.minimum(margin, layer_margin)
            out[name] = np.asarray(h[rows], np.float32)
            if name == "reference":
                out["margin"] = np.asarray(margin[rows], np.float32)
    return out


def inputs(seed: int, rows: int, hidden: int) -> np.ndarray:
    return np.random.default_rng([int(seed), 9]).standard_normal((rows + STEPS, hidden), dtype=np.float32)


def reference_on_the_chip(workload: str, seed: int, rows: int, path: Path) -> dict:
    """``reference_rows`` by a child of this script that takes the chip, and leaves it before the servers start."""
    env = {k: v for k, v in os.environ.items() if k not in ("JAX_PLATFORMS", "BENCH_RUN")}
    cmd = [sys.executable, str(Path(__file__).resolve()), "--workload", workload, "--seed", str(seed), "--rows", str(rows),
           "--reference-to", str(path)]
    subprocess.run(cmd, env=env, check=True, timeout=3000)
    with np.load(path) as saved:
        return {k: saved[k] for k in saved.files}


def prove(benchmark: dict, workload: str, seed: int, rows: int = ROWS, *, root: Path = ROOT, work_dir: Path = None,
          allow_cpu: bool = False) -> dict:
    """Everything but the command line (``allow_cpu`` as perf/run.py's: for the CPU tests of the harness, where the
    reference is computed in this process). Returns what the last line says."""
    from perf import correct, costs, reference, run
    from perf.config import load as load_config

    work_dir = work_dir or run.WORK_DIR
    _, config_entry = run.find_cell(benchmark, workload)
    config = load_config(root / config_entry["file"], config_entry["name"])
    hf = config["config"]
    window = variants(hf, rows + STEPS)["reference"].get(WINDOW_KEY)
    if not window or rows < 2 * window:
        raise SystemExit(f"{rows} rows are under two windows of {window}: too few pages would go back and little would be proved")
    hidden = costs.layer_params(config["family"], hf)["hidden"]
    limits = reference.limits(config)
    x = inputs(seed, rows, hidden)
    compared = np.arange(rows - TAIL, rows + STEPS)
    t = time.perf_counter()
    if allow_cpu:
        wanted = reference_rows(config, x, compared, block=min(BLOCK, 64))
    else:
        work_dir.mkdir(parents=True, exist_ok=True)
        wanted = reference_on_the_chip(workload, seed, rows, work_dir / f"window-{workload}-{seed}.npz")
    run.log(f"reference and two controls over {len(x)} positions: {time.perf_counter() - t:.1f}s")

    def full(rows_of: np.ndarray, fill=0.0) -> np.ndarray:  # ``judge`` reads a reference row by its position
        out = np.full((len(x), *rows_of.shape[1:]), fill, np.float32)
        out[compared] = rows_of
        return out

    out_dir = root / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    margin = full(wanted["margin"], np.inf)
    with run.serving(config, root / config_entry["file"], work_dir / "runs" / f"window-{workload}", root=root, work_dir=work_dir,
                     allow_cpu=allow_cpu) as up:
        budget = min(r["prefill_token_budget"] for r in up["ready"])
        run.tell_all(up["children"], "mark long")
        t = time.perf_counter()
        got = served_rows(up["remote"], x, rows)
        run.log(f"the long session: {time.perf_counter() - t:.1f}s")
        run.tell_all(up["children"], "mark long_end")
        dumps = run.stop_and_dump(up["children"])
    verdicts = {name: correct.judge(got, full(wanted[name]), margin, limits) for name in VARIANTS}
    medians = {name: float(np.median([e for _, _, e, _ in v["rows"]])) for name, v in verdicts.items()}
    for kind, position, error, row_margin in verdicts["reference"]["rows"]:
        print(f"{kind} {position}: error {error:.5f} (row bound {limits['row_bound']:.4f}), margin {row_margin:.4f}", flush=True)
    with open(out_dir / f"window_{workload}.jsonl", "a") as out:
        out.write(json.dumps({"seed": seed, "rows": rows, "medians": medians, **verdicts}) + "\n")
    counted = {}
    for d in dumps:  # the prompt rode mixed steps of the budget, pages went back while it ran, and held was in reach
        stats = {k: d["marks"]["long_end"]["stats"].get(k, 0) - d["marks"]["long"]["stats"].get(k, 0)
                 for k in ("mixed_steps", "window_pages_released", "window_pages_held", "window_pages_in_reach")}
        if stats["mixed_steps"] < rows // budget:
            raise SystemExit(f"the prompt rode {stats['mixed_steps']} mixed steps of {budget}: nothing was proved")
        for k, n in stats.items():
            counted[k] = counted.get(k, 0) + n
    summary = {"correct": verdicts["reference"]["ok"], "medians": medians, **counted,
               "released": counted["window_pages_released"] > 0, "held_is_in_reach": counted["window_pages_held"] == counted["window_pages_in_reach"] > 0}
    for name in VARIANTS[1:]:
        summary[f"{name}_ratio"] = medians[name] / medians["reference"] if medians["reference"] else float("inf")
    summary["passed"] = bool(summary["correct"] and summary["released"] and summary["held_is_in_reach"]
                             and all(summary[f"{name}_ratio"] >= MIN_RATIO for name in VARIANTS[1:]))
    shown = {name: {k: {f: v[k][f] for f in ("median", "max", "compared")} for k in ("prefill", "decode")} for name, v in verdicts.items()}
    print(f"seed {seed}, {rows} + {STEPS} positions: {json.dumps(shown)}", flush=True)
    return summary


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--rows", type=int, default=ROWS)
    parser.add_argument("--reference-to", help=argparse.SUPPRESS)  # this script's own child: the reference, on the device it finds
    args = parser.parse_args(argv)
    benchmark = json.loads((ROOT / "BENCHMARK.json").read_text())
    if args.reference_to:  # perf/run.py is not imported here: it holds the process that imports it to the CPU
        from perf import costs
        from perf.config import load as load_config

        cell = next(w for w in benchmark["workloads"] if w["name"] == args.workload)
        config_entry = next(c for c in benchmark["configs"] if c["name"] == cell["config"])
        config = load_config(ROOT / config_entry["file"], config_entry["name"])
        hidden = costs.layer_params(config["family"], config["config"])["hidden"]
        x = inputs(args.seed, args.rows, hidden)
        np.savez(args.reference_to, **reference_rows(config, x, np.arange(args.rows - TAIL, args.rows + STEPS)))
        return 0
    s = prove(benchmark, args.workload, args.seed, args.rows)
    print(f"passed={s['passed']}: correct={s['correct']} (median error {s['medians']['reference']:.5f}); "
          f"{s['window_pages_released']} pages given back while the session ran, held {s['window_pages_held']} = in reach "
          f"{s['window_pages_in_reach']}: {s['held_is_in_reach']}; against a server that ignored the window "
          f"{s['whole_context_ratio']:.2f} times the reference's error, against a ring half as long {s['half_window_ratio']:.2f} "
          f"(each at least {MIN_RATIO})", flush=True)
    return 0 if s["passed"] else 1


if __name__ == "__main__":
    sys.exit(main())
