"""CPU tests of ``intake_direct_share`` (PR 60): hand-made marks with both of the
batcher's intake counters give the hand-worked share, a dump from before the counters
(the parent's) or a run without the traced slice's marks gives None, and
``BENCHMARK.json``'s entry agrees with the reader's file. The whole command at a toy
size carries the metric in its line: tests/test_intake.py's servers are the program's
side, and tests/perf/test_round_trip_metrics.py's toy cell runs the reader's whole way."""

import json
from pathlib import Path

import pytest

from perf.record import Record, load_reader

ROOT = Path(__file__).resolve().parents[2]
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = "intake_direct_share"


def mark(mono, **stats):
    return {"wall": 1e9 + mono, "mono": mono, "stats": {"batched_steps": 0, "lane_returns": 0, "rpc_recv_s": 0.0, **stats}, "bytes_in_use": 0}


def child(start, stop):
    marks = {"window": mark(1.0), "window_end": mark(11.0)}
    marks.update({label: m for label, m in (("trace_start", start), ("trace_stop", stop)) if m is not None})
    return {"marks": marks}


def read(*children):
    record = Record(config={}, t_process=0.0, t0=1.0, seconds=10.0, t_drained=12.0, sessions=[], children=list(children))
    return load_reader("layer_metrics", NAME).read(record)


# a 3 s slice: 1,600 decode requests, 1,560 of them handed over in the reader's turn
ONE = child(mark(5.0, rpc_intake_direct=8000, rpc_intake_queued=300), mark(8.0, rpc_intake_direct=9560, rpc_intake_queued=340))


@pytest.mark.parametrize("children, want", [
    ((ONE,), 97.5),
    ((ONE, child(mark(5.5, rpc_intake_direct=0, rpc_intake_queued=0), mark(8.5, rpc_intake_direct=40, rpc_intake_queued=360))), 80.0),
    ((child(mark(5.0, rpc_intake_direct=7, rpc_intake_queued=0), mark(8.0, rpc_intake_direct=7, rpc_intake_queued=25)),), 0.0),
], ids=["one_server", "a_chain_s_servers_are_summed", "all_through_the_queue"])
def test_the_share_of_decode_requests_taken_in_the_reader_s_turn(children, want):
    assert read(*children) == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("children", [
    (),
    (child(mark(5.0), mark(8.0)),),  # the parent's dump: marks, and a batcher from before the counters
    (ONE, child(mark(5.0), mark(8.0))),
    (child(None, ONE["marks"]["trace_stop"]),),  # an untraced run has no trace_start
    (child(ONE["marks"]["trace_start"], None),),
    (child(ONE["marks"]["trace_start"], {**ONE["marks"]["trace_start"], "mono": 8.0}),),  # no decode request in the slice
], ids=["no_child", "before_the_counters", "one_child_of_two_before_them", "no_trace_start", "no_trace_stop", "nothing_counted"])
def test_nothing_to_read_gives_none_and_never_raises(children):
    assert read(*children) is None


def test_benchmark_json_s_entry_is_the_reader_s_file():
    """Found by name: later PRs append to ``per_layer``."""
    reader = load_reader("layer_metrics", NAME)
    (entry,) = [m for m in BENCHMARK["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "%", "better": "higher", "source": "program_counter",
                     "layer": "client + RPC (client/, rpc/)", "moves": "gap_p50_ms"}  # no "workloads": every cell reports it
    assert (reader.UNIT, reader.LAYER, reader.MOVES) == (entry["unit"], entry["layer"], entry["moves"])
    assert reader.LAYER == load_reader("layer_metrics", "rpc_recv_ms").LAYER  # the layer's accepted spelling
