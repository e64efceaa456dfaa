#!/usr/bin/env python3
"""One run of a benchmark cell with the client's stations read over the WHOLE window as well:

    python3 benchmarks/client_trip_table.py --workload <cell> --seed <n> [--seconds 51] [--trace 0|1]

``perf/run.py``'s run, in this process (the load generator is the client, so the ring of
``petals_tpu/telemetry/spans.py`` and its loop's turn clock are this process's and end with it).
A traced run's line carries the thirteen metrics of the traced slice; this script adds, for a
traced and an untraced run alike, the same readers over the measured window (the children's marks
``window`` / ``window_end`` shown to them as the slice's), the server's stations beside them, and
the tiling check: the mean of the load generator's own decode gaps (``SessionRecord.replies``,
read on the caller's thread after ``step()`` returned) whose reply came inside the span, against
``client_turn_ms + client_away_ms`` of the same span. One JSON line on standard output, appended
to ``chiprun_out/client_trip_table.jsonl``. It measures nothing the benchmark judges; PERF.md
section 5's table of PR 54 was made with it."""
import argparse
import json
import sys
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


def span_readings(dump: dict, labels: tuple, replay) -> dict:
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
    args = parser.parse_args()
    from perf import loadgen, run

    replays = []

    class Kept(loadgen.Replay):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            replays.append(self)

    run.loadgen.Replay = Kept
    benchmark = json.loads((ROOT / "BENCHMARK.json").read_text())
    result = run.run_cell(benchmark, args.workload, args.seed, args.seconds, bool(args.trace))
    dump = json.loads((run.WORK_DIR / "runs" / args.workload / "child0.json").read_text())
    detail = result.pop("detail")
    line = {
        "workload": args.workload, "seed": args.seed, "trace": args.trace, "correct": result["correct"],
        "failed": result["failed"], "gap_p50_ms": detail["gaps"].get("p50"), "gaps_n": detail["gaps"].get("n"),
        "metrics": {k: v["value"] for k, v in result["metrics"].items()},
        "window": span_readings(dump, ("window", "window_end"), replays[0]),
        "slice": span_readings(dump, ("trace_start", "trace_stop"), replays[0]),
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
