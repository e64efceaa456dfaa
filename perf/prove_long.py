#!/usr/bin/env python3
"""The selection of a learned sparse attention, held to the reference at a
context where it leaves positions out:

    python3 perf/prove_long.py --workload keyevl2-ctx32k --seed 2147483659 [--rows 4096]

perf/correct.py's sessions end at 144 positions, where a selection of 2,048
keeps everything: they prove the block, the pages and the experts, not the
selection. This does: through the served path of the cell's configuration, at
its published widths, one session prefills ROWS fresh rows (16,384; ``--rows
4096`` for a quick look), which ride mixed steps of the servers' budget, and
decodes STEPS more, while two short sessions decode in other lanes. The last
TAIL prompt rows and every decode row are held to the reference's row of
their position by perf/correct.py's ``judge`` under the family's limits at
this depth. Each row's error is printed, with the share of the reference's
chosen set (first layer, float32 scores) that the same scores computed from
bf16 operands choose too: what the served path, which scores in bf16 summed
in float32, can be expected to share with the reference. (The program cannot
export its index keys, so the served set itself is not seen: the error says
what it cost.)

The reference runs every layer over the whole sequence in float32 at highest
matmul precision, its attention in blocks of rows so that it fits
(perf/reference/<family>.py ``block(..., rows=)``: the same sums, the score
matrix never whole). At the published widths that is 25 TFLOP a layer: it is
computed on the chip, by a child of this script, BEFORE the servers start (a
chip belongs to one process at a time), and on the CPU in the tests.

Two controls: the same served rows against a reference whose selection rounds
its scores to float8 (e4m3) first, and against one that keeps the top half of
``topk``. Each must come out not correct. Rows go to
``chiprun_out/long_<cell>.jsonl``. No window is measured and no metric is
printed."""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

ROWS, STEPS, TAIL = 16384, 32, 32
BLOCK = 512  # rows of the reference's attention at once
BESIDE = ((96, 64), (80, 64))  # (prompt, decode steps) of the sessions that decode beside the long one
VARIANTS = ("reference", "float8_scores", "top_half")


def choosers(family) -> dict:
    """``choose`` of the reference's ``block`` for the reference and the two controls."""
    import jax

    def float8(scores, topk, first):
        # e4m3's 4 exponent and 3 mantissa bits by ``reduce_precision``: a convert to float8 and back is a pair
        # the chip's compiler removes (it allows excess precision), and the control then chose the reference's set
        return family.selection(jax.lax.reduce_precision(scores, exponent_bits=4, mantissa_bits=3), topk, first)

    def half(scores, topk, first):
        return family.selection(scores, topk // 2, first)

    return dict(zip(VARIANTS, (family.selection, float8, half)))


def reference_rows(config: dict, x: np.ndarray, compared: np.ndarray, block: int = BLOCK) -> dict:
    """``x`` [seq, hidden] through the configuration's layers under each of
    VARIANTS' selections (float32, weights made layer by layer as
    ``perf.reference.run`` makes them), the rows ``compared`` of each; and
    ``overlap``, a compared row: of the first layer's chosen set, the share
    that bf16 operands choose too."""
    import jax
    import jax.numpy as jnp

    from perf import reference, weights

    family, maker = reference.family_of(config["family"]), weights.family_of(config["family"])
    hf = config["config"]
    n_layers = sum(span["num_blocks"] for span in config["servers"])
    seq = len(x)
    padded = -(-seq // block) * block  # rows of zeros after the sequence: no row before them sees them
    x = jnp.pad(jnp.asarray(x, jnp.float32), ((0, padded - seq), (0, 0)))
    rows = jnp.asarray(compared)

    def tensors(index):
        w = maker.layer_tensors(hf, index, weights.Draws(config["weights_seed"]))
        return {k: v.astype(jnp.float32) for k, v in w.items()}

    def program(choose):
        return jax.jit(lambda index, h: family.block(hf, tensors(index), h, choose=choose, rows=block)[0])

    def overlap(h):  # the first layer's sets of the compared rows (consecutive), from float32 and from bf16 operands
        w = tensors(jnp.uint32(0))
        a = h / jnp.sqrt((h * h).mean(-1, keepdims=True) + hf["rms_norm_eps"]) * w["input_layernorm.weight"]
        q_idx, k_idx, w_idx = family.index_parts(hf, w, a)
        topk, bf16 = hf["sa_config"]["topk"], lambda t: jax.lax.reduce_precision(t, exponent_bits=8, mantissa_bits=7)
        want = family.selection(family.index_scores(q_idx[rows], k_idx, w_idx[rows]), topk, rows[0])
        got = family.selection(family.index_scores(bf16(q_idx[rows]), bf16(k_idx), w_idx[rows]), topk, rows[0])
        return (want & got).sum(-1) / want.sum(-1)

    out = {}
    with jax.default_matmul_precision("highest"):
        out["overlap"] = np.asarray(jax.jit(overlap)(x), np.float32)
        for name, choose in choosers(family).items():
            step, h = program(choose), x
            for index in range(n_layers):
                h = step(jnp.uint32(index), h)
            out[name] = np.asarray(h[rows], np.float32)
    return out


def served_rows(remote, x: np.ndarray, rows: int) -> list:
    """[(kind, position, row)] of the long session, two short sessions
    decoding in other lanes while its prompt's chunks and its decode steps run."""
    stop, errors = threading.Event(), []

    def short(prompt: int, steps: int) -> None:
        try:
            with remote.inference_session(max_length=prompt + steps) as session:
                session.step(x[None, :prompt])
                for t in range(steps):
                    if stop.is_set():
                        break
                    session.step(x[None, prompt + t : prompt + t + 1])
        except Exception as e:
            errors.append(repr(e))

    threads = [threading.Thread(target=short, args=s, daemon=True) for s in BESIDE]
    for t in threads:
        t.start()
    time.sleep(1.0)  # the short prompts are in and their sessions decode
    try:
        with remote.inference_session(max_length=rows + STEPS) as session:
            pre = np.asarray(session.step(x[None, :rows]))
            got = [("prefill", p, pre[0, p]) for p in range(rows - TAIL, rows)]
            for t in range(STEPS):
                out = np.asarray(session.step(x[None, rows + t : rows + t + 1]))
                got.append(("decode", rows + t, out[0, 0]))
    finally:
        stop.set()
        for t in threads:
            t.join(120)
    if errors:
        raise RuntimeError(f"sessions beside the long one failed: {errors}")
    return got


def inputs(seed: int, rows: int, hidden: int) -> np.ndarray:
    return np.random.default_rng([int(seed), 6]).standard_normal((rows + STEPS, hidden), dtype=np.float32)


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
    """Everything but the command line (``allow_cpu`` as perf/run.py's: for
    the CPU tests of the harness, where the reference is computed in this
    process). Returns what the last line says."""
    from perf import correct, costs, reference, run
    from perf.config import load as load_config

    work_dir = work_dir or run.WORK_DIR
    _, config_entry = run.find_cell(benchmark, workload)
    config = load_config(root / config_entry["file"], config_entry["name"])
    hf = config["config"]
    if rows <= hf.get("sa_config", {}).get("topk", rows):
        raise SystemExit(f"{rows} rows are within the selection's size: every row would keep every position and nothing would be proved")
    hidden = costs.layer_params(config["family"], hf)["hidden"]
    limits = reference.limits(config)
    x = inputs(seed, rows, hidden)
    compared = np.arange(rows - TAIL, rows + STEPS)
    t = time.perf_counter()
    if allow_cpu:
        wanted = reference_rows(config, x, compared, block=min(BLOCK, 64))
    else:
        work_dir.mkdir(parents=True, exist_ok=True)
        wanted = reference_on_the_chip(workload, seed, rows, work_dir / f"long-{workload}-{seed}.npz")
    run.log(f"reference and two controls over {len(x)} positions: {time.perf_counter() - t:.1f}s")

    def full(rows_of: np.ndarray) -> np.ndarray:  # ``judge`` reads a reference row by its position
        out = np.zeros((len(x), hidden), np.float32)
        out[compared] = rows_of
        return out

    def nearest(verdict: dict) -> float:  # the largest number a verdict holds, in units of its limit
        return max(max(verdict[k]["max"] / limits["row_bound"], verdict[k]["median"] / limits["median_bound"]) for k in ("prefill", "decode"))

    out_dir = root / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    margin = np.full(len(x), np.inf, np.float32)
    with run.serving(config, root / config_entry["file"], work_dir / "runs" / f"long-{workload}", root=root, work_dir=work_dir,
                     allow_cpu=allow_cpu) as up:
        budget = min(r["prefill_token_budget"] for r in up["ready"])
        run.tell_all(up["children"], "mark long")
        t = time.perf_counter()
        got = served_rows(up["remote"], x, rows)
        run.log(f"the long session: {time.perf_counter() - t:.1f}s")
        run.tell_all(up["children"], "mark long_end")
        dumps = run.stop_and_dump(up["children"])
    verdicts = {name: correct.judge(got, full(wanted[name]), margin, limits) for name in VARIANTS}
    errors = {int(p): e for _, p, e, _ in verdicts["reference"]["rows"]}
    for (kind, position, _), share in zip(got, wanted["overlap"]):
        print(f"{kind} {position}: error {errors[position]:.5f} (row bound {limits['row_bound']:.4f}); "
              f"bf16 operands choose {100 * share:.2f}% of the reference's set", flush=True)
    with open(out_dir / f"long_{workload}.jsonl", "a") as out:
        out.write(json.dumps({"seed": seed, "rows": rows, "overlap": [float(s) for s in wanted["overlap"]], **verdicts}) + "\n")
    for d in dumps:  # the prompt rode mixed steps of the budget, and its rows selected
        stats = {k: d["marks"]["long_end"]["stats"].get(k, 0) - d["marks"]["long"]["stats"].get(k, 0) for k in ("mixed_steps", "sparse_rows_selected")}
        if stats["mixed_steps"] < rows // budget or stats["sparse_rows_selected"] <= 0:
            raise SystemExit(f"the prompt rode {stats['mixed_steps']} mixed steps of {budget} and {stats['sparse_rows_selected']} rows selected: nothing was proved")
    summary = {"correct": verdicts["reference"]["ok"], "nearest": nearest(verdicts["reference"]),
               "overlap_min": float(wanted["overlap"].min()), "overlap_mean": float(wanted["overlap"].mean())}
    for name in VARIANTS[1:]:
        summary[f"{name}_not_correct"] = not verdicts[name]["ok"]
        summary[f"{name}_nearest"] = nearest(verdicts[name])
    shown = {name: {k: {f: v[k][f] for f in ("median", "max")} for k in ("prefill", "decode")} for name, v in verdicts.items()}
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
    print(f"correct={s['correct']}, the nearest number at {100 * s['nearest']:.0f}% of its limit; bf16 operands choose "
          f"{100 * s['overlap_mean']:.2f}% of the reference's set (least {100 * s['overlap_min']:.2f}%); "
          f"scores rounded to float8: not correct={s['float8_scores_not_correct']} ({s['float8_scores_nearest']:.1f} times a limit); "
          f"the top half kept: not correct={s['top_half_not_correct']} ({s['top_half_nearest']:.1f} times a limit)", flush=True)
    return 0 if s["correct"] and s["float8_scores_not_correct"] and s["top_half_not_correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
