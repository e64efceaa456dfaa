"""CPU tests of ``step_overlap_share`` (PR 66): hand-made marks with known
counters give the known share, children are summed, a program from before
the counter or a run without the trace's marks gives None and does not raise,
and ``BENCHMARK.json``'s entry agrees with the reader's file."""

import json
from pathlib import Path

import pytest

from perf.record import Record, load_reader

ROOT = Path(__file__).resolve().parents[2]
NAME, BATCHER = "step_overlap_share", "batcher (server/batching.py)"


def mark(mono, steps, overlapped=None):
    stats = {"batched_steps": steps, "batched_tokens": 4 * steps}
    if overlapped is not None:
        stats["overlapped_steps"] = overlapped
    return {"wall": 1e9 + mono, "mono": mono, "stats": stats, "bytes_in_use": 0}


def child(start, stop):
    marks = {"window": mark(1.0, 0, 0), "window_end": mark(11.0, 9999, 9999)}  # the whole window's: not what is read
    marks.update({name: m for name, m in (("trace_start", start), ("trace_stop", stop)) if m is not None})
    return {"marks": marks}


def read(*children):
    record = Record(config={}, t_process=0.0, t0=1.0, seconds=10.0, t_drained=12.0, sessions=[], children=list(children))
    return load_reader("layer_metrics", NAME).read(record)


@pytest.mark.parametrize(
    "children, want",
    [
        ([child(mark(5.0, 1000, 400), mark(8.0, 1400, 640))], 60.0),  # 240 of 400 steps
        ([child(mark(5.0, 1000, 400), mark(8.0, 1400, 400))], 0.0),  # lanes that ride one step: nothing is ever pending
        ([child(mark(5.0, 1000, 400), mark(8.0, 1400, 640)), child(mark(5.5, 0, 0), mark(8.5, 200, 60))], 50.0),  # 300 of 600
        ([], None),  # no child
        ([child(None, mark(8.0, 1400, 640))], None),  # an untraced run has no trace_start
        ([child(mark(5.0, 1000, 400), mark(5.0, 1000, 400))], None),  # no step in the slice
        ([child(mark(5.0, 1000), mark(8.0, 1400))], None),  # a program from before the counter: nothing, and no raise
        ([child(mark(5.0, 1000, 400), mark(8.0, 1400, 640)), child(mark(5.0, 1000), mark(8.0, 1400))], None),
    ],
)
def test_reader(children, want):
    got = read(*children)
    assert got is None if want is None else got == pytest.approx(want)


def test_benchmark_json_names_it_for_every_cell():
    entry = {m["name"]: m for m in json.loads((ROOT / "BENCHMARK.json").read_text())["per_layer"]}[NAME]  # no "workloads"
    reader = load_reader("layer_metrics", NAME)
    assert entry == {"name": NAME, "unit": "%", "better": "higher", "source": "program_counter", "layer": BATCHER, "moves": "gap_p50_ms"}
    assert (reader.UNIT, reader.LAYER, reader.MOVES) == ("%", BATCHER, "gap_p50_ms")
