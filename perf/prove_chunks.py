#!/usr/bin/env python3
"""A state handed from one mixed step to the next, held to the reference:

    python3 perf/prove_chunks.py --workload <cell> --seeds 2147483659,2147483693

perf/correct.py's sequence is 144 positions and its prompts at most 128, under
one chunk's budget (512), so no run of the check hands a recurrent state from
one mixed step to the next. This does: through the served path a session
prefills PROMPT positions, which ride three mixed steps, and decodes STEPS
more, once ALONE and once with three other sessions decoding BESIDE it. Each
decode row, and each of the TAIL prompt rows that follow a chunk's boundary or
end the prompt, is held to the reference's row of its position, by
perf/correct.py's ``judge`` and the family's limits.

The control: the same served rows against a reference whose linear-attention
layers START OVER at the first chunk's boundary (zero state, zero conv tail),
which is what a server would give that dropped the state between two mixed
steps. It must come out not correct; the last lines say by what factor of
the limits. Rows go to ``chiprun_out/chunks_<cell>.jsonl``. No window is
measured and no metric is printed. For a family none of whose layers keeps a
state the control equals the reference and the script says so and fails."""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

PROMPT, STEPS, TAIL = 1536, 32, 32
CHUNK = 512  # the batcher's default budget of prompt positions a mixed step: the prompt's chunks end at 512 and 1024
BESIDE = ((96, 64), (80, 64), (64, 64))  # (prompt, decode steps) of the sessions that decode beside the long one


def reference_rows(config: dict, hidden: np.ndarray, start_over_at: int = 0) -> np.ndarray:
    """``hidden`` [seq, hidden] through the configuration's layers, as
    ``perf.reference.run`` (float32, weights made layer by layer on the CPU).
    With ``start_over_at``, a layer whose kind keeps no keys and values
    (``layer_params``: no kv heads) runs the rows before and from that
    position as two sequences of their own."""
    import jax
    import jax.numpy as jnp

    from perf import reference, weights

    family, maker = reference.family_of(config["family"]), weights.family_of(config["family"])
    hf = config["config"]
    n_layers = sum(span["num_blocks"] for span in config["servers"])
    kinds = reference.kinds_of(config["family"], hf) or [()] * n_layers

    def program(kind: tuple, cut: int):
        def layer(index, x):
            w = maker.layer_tensors(hf, index, weights.Draws(config["weights_seed"]), *kind)
            w = {k: v.astype(jnp.float32) for k, v in w.items()}
            if cut:
                return jnp.concatenate([family.block(hf, w, part, *kind)[0] for part in (x[:cut], x[cut:])])
            return family.block(hf, w, x, *kind)[0]

        return jax.jit(layer)

    with jax.default_matmul_precision("highest"):
        programs = {}
        x = jnp.asarray(hidden, jnp.float32)
        for index in range(n_layers):
            kind = kinds[index]
            stateful = family.layer_params(hf, *kind)["kv_heads"] == 0
            key = (kind, start_over_at if stateful else 0)
            if key not in programs:
                programs[key] = program(*key)
            x = programs[key](jnp.uint32(index), x)
        return np.asarray(x, np.float32)


def served_rows(remote, x: np.ndarray, beside: bool) -> list:
    """[(kind, position, row)] of the long session; with ``beside``, three
    short sessions decode while its prompt's chunks and its decode steps run."""
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

    threads = [threading.Thread(target=short, args=s, daemon=True) for s in (BESIDE if beside else ())]
    for t in threads:
        t.start()
    if beside:
        time.sleep(1.0)  # the short prompts are in and their sessions decode
    try:
        with remote.inference_session(max_length=PROMPT + STEPS) as session:
            pre = np.asarray(session.step(x[None, :PROMPT]))
            compared = [*range(CHUNK, CHUNK + TAIL), *range(2 * CHUNK, 2 * CHUNK + TAIL), *range(PROMPT - TAIL, PROMPT)]
            rows = [("prefill", p, pre[0, p]) for p in compared]
            for t in range(STEPS):
                out = np.asarray(session.step(x[None, PROMPT + t : PROMPT + t + 1]))
                rows.append(("decode", PROMPT + t, out[0, 0]))
    finally:
        stop.set()
        for t in threads:
            t.join(120)
    if errors:
        raise RuntimeError(f"sessions beside the long one failed: {errors}")
    return rows


def prove(benchmark: dict, workload: str, seeds: list, *, root: Path = ROOT, work_dir: Path = None, allow_cpu: bool = False) -> dict:
    """Everything but the command line (``allow_cpu`` as perf/run.py's: for
    the CPU tests of the harness). Returns what the last line says."""
    from perf import correct, costs, reference, run
    from perf.config import load as load_config

    work_dir = work_dir or run.WORK_DIR
    _, config_entry = run.find_cell(benchmark, workload)
    config = load_config(root / config_entry["file"], config_entry["name"])
    hidden = costs.layer_params(config["family"], config["config"])["hidden"]
    limits = reference.limits(config)

    def meanwhile() -> dict:
        out = {}
        for seed in seeds:
            t = time.perf_counter()
            x = np.random.default_rng([seed, 5]).standard_normal((PROMPT + STEPS, hidden), dtype=np.float32)
            out[seed] = (x, reference_rows(config, x), reference_rows(config, x, start_over_at=CHUNK))
            run.log(f"reference and control for seed {seed}: {time.perf_counter() - t:.1f}s")
        return out

    def nearest(verdict: dict) -> float:  # the largest number a verdict holds, in units of its limit
        return max(max(verdict[k]["max"] / limits["row_bound"], verdict[k]["median"] / limits["median_bound"]) for k in ("prefill", "decode"))

    out_dir = root / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    margin = np.full(PROMPT + STEPS, np.inf, np.float32)
    summary = {"sessions": 2 * len(seeds), "correct": 0, "nearest": 0.0, "control_not_correct": 0, "control_nearest": float("inf")}
    with run.serving(config, root / config_entry["file"], work_dir / "runs" / f"chunks-{workload}", root=root, work_dir=work_dir,
                     allow_cpu=allow_cpu, meanwhile=meanwhile) as up, open(out_dir / f"chunks_{workload}.jsonl", "a") as out:
        budget = min(r["prefill_token_budget"] for r in up["ready"])
        if budget != CHUNK:
            raise SystemExit(f"the servers' prefill budget is {budget}, not {CHUNK}: the chunks' boundaries are elsewhere")
        for seed in seeds:
            x, want, control = up["meanwhile"][seed]
            if np.array_equal(want, control):
                raise SystemExit("no layer of this configuration keeps a state: there is nothing to hand from chunk to chunk")
            for label, beside in (("alone", False), ("beside", True)):
                run.tell_all(up["children"], f"mark chunks_{seed}_{label}")
                rows = served_rows(up["remote"], x, beside)
                run.tell_all(up["children"], f"mark chunks_{seed}_{label}_end")
                verdict, dropped = correct.judge(rows, want, margin, limits), correct.judge(rows, control, margin, limits)
                out.write(json.dumps({"seed": seed, "session": label, "verdict": verdict, "control": dropped}) + "\n")
                out.flush()
                summary["correct"] += verdict["ok"]
                summary["control_not_correct"] += not dropped["ok"]
                summary["nearest"] = max(summary["nearest"], nearest(verdict))
                summary["control_nearest"] = min(summary["control_nearest"], nearest(dropped))
                shown = [{k: {f: v[k][f] for f in ("median", "max")} for k in ("prefill", "decode")} for v in (verdict, dropped)]
                print(f"seed {seed} {label}: ok={verdict['ok']} {json.dumps(shown[0])}; state dropped at {CHUNK}: "
                      f"ok={dropped['ok']} {json.dumps(shown[1])}", flush=True)
        dumps = run.stop_and_dump(up["children"])
    # every long session's prompt rode three mixed steps on every server: the children's counters between its marks
    for seed in seeds:
        for label in ("alone", "beside"):
            mixed = [d["marks"][f"chunks_{seed}_{label}_end"]["stats"]["mixed_steps"] - d["marks"][f"chunks_{seed}_{label}"]["stats"]["mixed_steps"]
                     for d in dumps]
            if min(mixed) < 3:
                raise SystemExit(f"seed {seed} {label}: the prompt rode {mixed} mixed steps a server, not three: no state was handed on twice")
    return summary


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True, help="comma-separated")
    args = parser.parse_args(argv)
    summary = prove(json.loads((ROOT / "BENCHMARK.json").read_text()), args.workload, [int(s) for s in args.seeds.split(",")])
    print(f"{summary['correct']} of {summary['sessions']} sessions correct, the nearest at {100 * summary['nearest']:.0f}% of a limit; "
          f"with the state dropped at the first chunk's boundary {summary['control_not_correct']} of {summary['sessions']} not correct, "
          f"the nearest at {summary['control_nearest']:.1f} times a limit", flush=True)
    return 0 if summary["correct"] == summary["control_not_correct"] == summary["sessions"] else 1


if __name__ == "__main__":
    sys.exit(main())
