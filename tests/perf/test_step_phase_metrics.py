"""CPU tests of the seven step-phase readers (PR 24): hand-made marks with
known counters give known values, the three shares make 100, a program or a
run without the counters or the marks gives None, and ``BENCHMARK.json``'s
entries for them agree with their files."""

import json
from pathlib import Path

import pytest

from perf import step_phases
from perf.record import Record, load_reader

ROOT = Path(__file__).resolve().parents[2]
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
BATCHER, PROGRAMS, DEVICE = "batcher (server/batching.py)", "step programs (server/backend.py)", "device"
READERS = {  # name: (unit, better, layer)
    "step_assemble_ms": ("ms", "lower", BATCHER), "step_dispatch_ms": ("ms", "lower", PROGRAMS),
    "step_wait_ms": ("ms", "lower", DEVICE), "step_post_ms": ("ms", "lower", BATCHER),
    "step_turnaround_ms": ("ms", "lower", BATCHER), "host_serial_share": ("%", "lower", BATCHER),
    "no_work_share": ("%", "higher", BATCHER),
}


def mark(mono, steps, assemble, dispatch, wait, post, turnaround, **more):
    stats = {"batched_steps": steps, "batched_tokens": 4 * steps, "assemble_s": assemble, "dispatch_s": dispatch,
             "wait_s": wait, "post_s": post, "turnaround_s": turnaround, **more}
    return {"wall": 1e9 + mono, "mono": mono, "stats": stats, "bytes_in_use": 0}


def record_of(*children):
    return Record(config={}, t_process=0.0, t0=1.0, seconds=10.0, t_drained=12.0, sessions=[], children=list(children))


def child(start, stop):
    marks = {"window": mark(1.0, 0, 0, 0, 0, 0, 0), "window_end": mark(11.0, 9999, 9, 9, 9, 9, 9)}
    if start is not None:
        marks["trace_start"] = start
    if stop is not None:
        marks["trace_stop"] = stop
    return {"marks": marks}


# 100 steps in a 3 s slice: 0.2 ms assemble, 2 ms dispatch, 14 ms wait, 1.5 ms post, 0.8 ms turnaround a step
ONE = child(mark(5.0, 1000, 10.0, 20.0, 300.0, 5.0, 2.0), mark(8.0, 1100, 10.02, 20.2, 301.4, 5.15, 2.08))
WANT = {"step_assemble_ms": 0.2, "step_dispatch_ms": 2.0, "step_wait_ms": 14.0, "step_post_ms": 1.5,
        "step_turnaround_ms": 0.8, "host_serial_share": 15.0, "no_work_share": 100.0 - 185.0 / 3.0}


def read(name, record):
    return load_reader("layer_metrics", name).read(record)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_gives_the_hand_worked_value(name):
    assert read(name, record_of(ONE)) == pytest.approx(WANT[name], rel=1e-9)


def test_the_three_shares_make_100():
    record = record_of(ONE)
    wait_share = step_phases.share_of_window(record, ("wait_s",))
    assert wait_share == pytest.approx(100.0 * 1.4 / 3.0)
    assert read("host_serial_share", record) + read("no_work_share", record) + wait_share == pytest.approx(100.0)


def test_children_are_summed_counters_and_windows_alike():
    """A second server that ran half as many steps, each twice as long, in a
    slice of its own length: per-step values weigh by steps, shares by window."""
    two = child(mark(5.5, 0, 0.0, 0.0, 0.0, 0.0, 0.0), mark(8.5, 50, 0.02, 0.2, 1.4, 0.15, 0.08))
    record = record_of(ONE, two)
    assert read("step_wait_ms", record) == pytest.approx(1e3 * 2.8 / 150)
    assert read("host_serial_share", record) == pytest.approx(15.0)
    assert step_phases.totals(record)["window_s"] == pytest.approx(6.0)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_gives_none_where_there_is_nothing_to_read(name):
    start, stop = ONE["marks"]["trace_start"], ONE["marks"]["trace_stop"]
    assert read(name, record_of()) is None  # no child
    assert read(name, record_of(child(None, stop))) is None  # an untraced run has no trace_start
    assert read(name, record_of(child(start, None))) is None
    assert read(name, record_of(ONE, child(None, None))) is None  # one child of two without the marks
    old = {k: v for k, v in stop["stats"].items() if not k.endswith("_s")}  # a program from before the counters
    assert read(name, record_of(child({**start, "stats": old}, {**stop, "stats": old}))) is None
    idle = child(start, {**start, "mono": 8.0})  # no step in the slice: shares can be read, per-step times cannot
    got = read(name, record_of(idle))
    assert got == {"host_serial_share": 0.0, "no_work_share": 100.0}.get(name)


def the_seven() -> list:
    """``BENCHMARK.json``'s entries for the seven, found by name: later PRs append to ``per_layer``."""
    entries = {m["name"]: m for m in BENCHMARK["per_layer"]}
    return [entries[name] for name in READERS]


def test_benchmark_json_names_the_seven_after_what_it_had():
    assert len(the_seven()) == len(READERS) == 7
    layers = {m["layer"] for m in BENCHMARK["per_layer"] if m["name"] not in READERS}  # the other entries' own spellings
    for m in the_seven():
        unit, better, layer = READERS[m["name"]]
        reader = load_reader("layer_metrics", m["name"])
        assert m == {"name": m["name"], "unit": unit, "better": better, "source": "program_counter",
                     "layer": layer, "moves": "gap_p50_ms"}  # no "workloads": every cell reports them
        assert (reader.UNIT, reader.LAYER, reader.MOVES) == (unit, layer, "gap_p50_ms") and layer in layers
        assert (ROOT / "perf" / "layer_metrics" / f"{m['name']}.py").is_file()


def test_a_traced_tiny_cell_prints_all_seven(tmp_path):
    """The whole command at a toy size on the CPU, traced, with the seven
    entries beside the toy benchmark's own: the server child's marks carry
    the counters, every reader finds them, and the identities hold on a real
    run. The numbers mean nothing and go nowhere.

    This was the one test that failed in the driver's run at PR 29 (six
    workers) and it passes alone; the failure did not come again here under
    ten busy loops, six copies at once or a whole run, so which assertion
    gave way is inferred, not seen. Two can, on a machine whose other workers
    take the cores: a 5 s window's traced slice is 1.7 s, and one stalled step
    (a chunk shape compiling inside ``dispatch``) leaves it without a finished
    step, so the per-step readers find nothing; and a toy server at 3 sessions
    a second is then busy all the time, where a step that straddles a mark
    makes ``no_work_share`` read under 0. So the window is 9 s (the slice the
    3 s a cell's is) and the mix is ``tiny-open`` at half its rate; every
    assertion is as it was."""
    from perf import run

    data = Path(__file__).resolve().parent / "data"
    bench = json.loads((data / "benchmark-tiny.json").read_text())
    bench["per_layer"] += the_seven()
    bench["workloads"].append({**bench["workloads"][0], "name": "tiny-open-light", "traffic": "tiny-open-light"})
    result = run.run_cell(bench, "tiny-open-light", 2**31 + 5, 9.0, True, traffic_dir=data / "traffic",
                          work_dir=tmp_path, allow_cpu=True)
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert result["correct"] is True and set(READERS) <= set(got), sorted(got)
    assert all(result["metrics"][k]["unit"] == READERS[k][0] for k in READERS)
    per_step = [got[k] for k in ("step_assemble_ms", "step_dispatch_ms", "step_wait_ms", "step_post_ms")]
    assert all(v > 0 for v in per_step) and got["step_turnaround_ms"] >= 0
    assert 0 < got["host_serial_share"] < 100 and 0 < got["no_work_share"] < 100
