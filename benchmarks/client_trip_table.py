#!/usr/bin/env python3
"""One run of a benchmark cell with the client's stations read over the WHOLE window as well:

    python3 benchmarks/client_trip_table.py --workload <cell> --seed <n> [--seconds 51] [--trace 0|1]

``perf/run.py``'s run, in this process (the load generator is the client, so the ring of
``petals_tpu/telemetry/spans.py`` and its loop's turn clock are this process's and end with it).
A traced run's line carries the thirteen metrics of the traced slice; this script adds, for a
traced and an untraced run alike, the same readers over the measured window (the children's marks
``window`` / ``window_end`` shown to them as the slice's), the server's stations beside them, and
``client_direct_step_share`` (PR 55: of the span's decode steps, the share the caller's thread exchanged itself; and of its
other steps, prompts, ``client_direct_other_share``), ``client_direct_write_share`` (PR 58: of those direct decode steps' frames, the share
the caller's thread wrote to the socket itself; the rest it left to the loop, ``client_direct_frames_deferred``), and the tiling check: the mean of the load generator's own decode gaps (``SessionRecord.replies``,
read on the caller's thread after ``step()`` returned) whose reply came inside the span, against
``client_turn_ms + client_away_ms`` of the same span. One JSON line on standard output, appended
to ``chiprun_out/client_trip_table.jsonl``. ``--cpu 1`` adds ``client_cpu_share`` and ``client_cpu_ms_per_step``: this process's
CPU time (every thread's, under one GIL) over the span, sampled by one more thread. It measures nothing the benchmark judges; PERF.md
section 5's table of PR 54 was made with it."""
import argparse
import json
import sys
import threading
import time
import timeit
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

TRIP = ("client_recv_ms", "client_finish_ms", "client_wake_ms", "client_user_ms", "client_submit_ms", "client_build_ms",
        "client_turn_ms", "client_away_ms", "wire_and_loops_ms", "server_loop_busy_share", "client_loop_busy_share",
        "server_loop_late_ms", "client_loop_late_ms")
SERVER = ("lane_return_ms", "reply_wake_ms", "reply_resume_ms", "reply_build_ms", "rpc_send_ms", "rpc_recv_ms",
          "request_handle_ms", "off_server_ms", "step_assemble_ms", "step_dispatch_ms", "step_wait_ms", "step_post_ms",
          "lanes_out_share", "gather_wait_share", "handoff_share", "decode_batch_mean")


def direct_shares(decode_rows, lo: float, hi: float) -> dict:
    """Of the span's decode steps (the readers' rows: one hop, one token in) and of its other steps (prompts, a chain's),
    the share the caller's thread exchanged itself (PR 55: the last column of a ring row; None on a program without it)."""
    from petals_tpu.telemetry import spans

    row, ring = getattr(spans, "ROW", ()), getattr(spans, "STEP_RING", None)
    if "direct" not in row or ring is None:
        return {"client_direct_step_share": None, "client_direct_other_share": None, "client_direct_write_share": None}
    at, hops, tokens, direct = (row.index(name) for name in ("read_at", "hops", "tokens", "direct"))
    others = [r[direct] for r in list(ring.rows) if lo <= r[at] <= hi and not (r[hops] == 1 and r[tokens] == 1)]
    mine = [r["direct"] for r in decode_rows]
    out = {"client_direct_step_share": 100.0 * sum(mine) / len(mine) if mine else None,
           "client_direct_other_share": 100.0 * sum(others) / len(others) if others else None, "other_steps_in_span": len(others)}
    # PR 58: a row's "wrote" counts its hops' frames that the caller's thread wrote to the socket (None on a program without the column)
    frames = sum(r["hops"] for r in decode_rows if r["direct"]) if "wrote" in row else 0
    wrote = sum(r["wrote"] for r in decode_rows if r["direct"]) if frames else 0
    out["client_direct_write_share"] = 100.0 * wrote / frames if frames else None
    out["client_direct_frames_deferred"] = frames - wrote if frames else None
    return out


def cpu_sampler(samples: list, every: float = 0.05) -> threading.Event:
    """``--cpu 1``: a thread that notes ``(perf_counter, process_time)`` of this process, the load generator's, twenty times
    a second, until the event it gives is set: what the client's one process (one GIL) burns, which no station shows."""
    stop = threading.Event()

    def run():
        while not stop.wait(every):
            samples.append((time.perf_counter(), time.process_time()))

    threading.Thread(target=run, name="cpu-sampler", daemon=True).start()
    return stop


def cpu_in_span(samples: list, lo: float, hi: float, steps: int) -> dict:
    """The process's CPU seconds between the samples nearest the span's ends, as a share of that time and a step of the span."""
    if not samples or samples[0][0] > lo or samples[-1][0] < hi:
        return {}
    a, b = (min(samples, key=lambda sample: abs(sample[0] - mark)) for mark in (lo, hi))
    cpu, wall = b[1] - a[1], b[0] - a[0]
    return {"client_cpu_share": 100.0 * cpu / wall, "client_cpu_ms_per_step": 1e3 * cpu / steps if steps else None}


def span_readings(dump: dict, labels: tuple, replay, cpu_samples=()) -> dict:
    """Every reader of ``TRIP`` and ``SERVER`` over the span between the two marks, and the tiling."""
    from perf import client_trip
    from perf.record import load_reader

    marks = dump["marks"]
    if any(label not in marks for label in labels):
        return {}
    shown = SimpleNamespace(children=[{"marks": {"trace_start": marks[labels[0]], "trace_stop": marks[labels[1]],
                                                 "window": marks[labels[0]], "window_end": marks[labels[1]]}}])
    shown.stat_delta = lambda child, key, start="window", end="window_end": child["marks"][end]["stats"][key] - child["marks"][start]["stats"][key]
    shown.ratio_over_children = lambda num, den, **kw: (lambda n, d: n / d if d > 0 else None)(
        shown.stat_delta(shown.children[0], num), shown.stat_delta(shown.children[0], den))
    out = {}
    for name in (*TRIP, *SERVER):
        try:
            out[name] = load_reader("layer_metrics", name).read(shown)
        except (AttributeError, KeyError, TypeError):  # a reader that wants more of a Record than a span has
            out[name] = None
    lo, hi = marks[labels[0]]["mono"], marks[labels[1]]["mono"]
    gaps = [t - before for s in replay.records if s.first_reply is not None
            for before, t in zip([s.first_reply] + [t for t, _ in s.replies], [t for t, _ in s.replies]) if lo <= t <= hi]
    out["span_s"], out["gaps_in_span"] = hi - lo, len(gaps)
    for side, loop in (("server", client_trip.server_loop(shown)), ("client", client_trip.client_loop(shown))):
        out[f"{side}_loop_turns_per_s"] = loop["loop_turns"] / loop["elapsed_s"] if loop else None
    rows = client_trip.steps(shown)
    out["steps_per_s"] = len(rows) / (hi - lo) if rows else None
    out.update(direct_shares(rows or (), lo, hi))
    from petals_tpu.telemetry import spans

    all_steps = sum(lo <= row[0] <= hi for row in list(spans.STEP_RING.rows))  # a row's first column is its K3
    out.update(cpu_in_span(list(cpu_samples), lo, hi, all_steps))
    out["gap_mean_ms"] = 1e3 * sum(gaps) / len(gaps) if gaps else None
    if gaps and out.get("client_turn_ms") is not None and out.get("client_away_ms") is not None:
        out["turn_plus_away_ms"] = out["client_turn_ms"] + out["client_away_ms"]
        out["tiling_off_pct"] = 100.0 * (out["turn_plus_away_ms"] / out["gap_mean_ms"] - 1.0)
    return out


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=51.0)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=1)
    parser.add_argument("--cpu", type=int, choices=(0, 1), default=0, help="sample this process's CPU time too (one more thread)")
    args = parser.parse_args()
    from perf import loadgen, run

    replays = []

    class Kept(loadgen.Replay):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            replays.append(self)

    run.loadgen.Replay = Kept
    benchmark = json.loads((ROOT / "BENCHMARK.json").read_text())
    cpu_samples: list = []
    stop_sampling = cpu_sampler(cpu_samples) if args.cpu else threading.Event()
    result = run.run_cell(benchmark, args.workload, args.seed, args.seconds, bool(args.trace))
    dump = json.loads((run.WORK_DIR / "runs" / args.workload / "child0.json").read_text())
    stop_sampling.set()
    detail = result.pop("detail")
    line = {
        "workload": args.workload, "seed": args.seed, "trace": args.trace, "correct": result["correct"],
        "failed": result["failed"], "gap_p50_ms": detail["gaps"].get("p50"), "gaps_n": detail["gaps"].get("n"),
        "metrics": {k: v["value"] for k, v in result["metrics"].items()},
        "window": span_readings(dump, ("window", "window_end"), replays[0], cpu_samples),
        "slice": span_readings(dump, ("trace_start", "trace_stop"), replays[0], cpu_samples),
    }
    line["perf_counter_ns"] = 1e9 * timeit.timeit(time.perf_counter, number=200000) / 200000  # what a reading costs on this host
    text = json.dumps(line)
    out = ROOT / "chiprun_out"
    out.mkdir(exist_ok=True)
    with open(out / "client_trip_table.jsonl", "a") as f:
        f.write(text + "\n")
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
