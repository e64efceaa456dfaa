"""CPU tests of the twelve round-trip readers (PR 37): hand-made marks with
known counters give known values, the eight tiling clocks make 100,
``off_server_ms`` is what the stations leave of ``lane_return_ms``, a program
or a run without the counters or the marks gives None, ``BENCHMARK.json``'s
entries for them agree with their files, and the whole command at a toy size
carries all twelve in its line."""

import json
from pathlib import Path

import pytest

from perf import round_trip, step_phases
from perf.record import Record, load_reader

ROOT = Path(__file__).resolve().parents[2]
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
BATCHER, HANDLER, RPC = "batcher (server/batching.py)", "handler (server/handler.py)", "client + RPC (client/, rpc/)"
READERS = {  # name: (unit, layer)
    "lane_return_ms": ("ms", BATCHER), "reply_wake_ms": ("ms", BATCHER), "reply_resume_ms": ("ms", HANDLER),
    "reply_build_ms": ("ms", HANDLER), "rpc_send_ms": ("ms", RPC), "rpc_recv_ms": ("ms", RPC),
    "request_handle_ms": ("ms", HANDLER), "off_server_ms": ("ms", RPC), "lanes_out_share": ("%", BATCHER),
    "gather_wait_share": ("%", BATCHER), "handoff_share": ("%", BATCHER), "no_demand_share": ("%", BATCHER),
}
STATIONS = ("reply_resume_ms", "reply_build_ms", "rpc_send_ms", "off_server_ms", "rpc_recv_ms", "request_handle_ms")
SHARES = ("lanes_out_share", "gather_wait_share", "handoff_share", "no_demand_share")
ZERO = dict.fromkeys((*round_trip.TILES, *round_trip.COUNTED_BY, "reply_steps", "decode_replies", "lane_returns"), 0.0)


def mark(mono, **stats):
    return {"wall": 1e9 + mono, "mono": mono, "stats": {"batched_steps": 0, "turnaround_s": 0.0, **ZERO, **stats}, "bytes_in_use": 0}


def record_of(*children):
    return Record(config={}, t_process=0.0, t0=1.0, seconds=10.0, t_drained=12.0, sessions=[], children=list(children))


def child(start, stop):
    marks = {"window": mark(1.0), "window_end": mark(11.0, **{key: 9.0 for key in ZERO})}
    if start is not None:
        marks["trace_start"] = start
    if stop is not None:
        marks["trace_stop"] = stop
    return {"marks": marks}


# a 3 s slice: 200 steps that replied, eight lanes a step of which ten did not come back; a reply waits 1 ms for its
# handler, is built in 0.2 and sent in 0.1; a request is unpacked and handed over in 0.1 and handled in 0.2; a trip
# takes 3.5 ms. The thread: 1.77 s in the four phases, 0.9 s lanes out, 0.03 no demand, 0.24 gathering, 0.06 hand-off
BEFORE = dict(reply_steps=1000, decode_replies=8000, lane_returns=7990, reply_wake_s=1.0, reply_resume_s=10.0, reply_build_s=3.0,
              rpc_send_s=2.0, rpc_recv_s=1.0, request_handle_s=2.0, lane_return_s=30.0, assemble_s=10.0, dispatch_s=20.0,
              wait_s=300.0, post_s=5.0, lanes_out_s=100.0, no_demand_s=50.0, gather_wait_s=7.0, handoff_s=3.0)
DELTA = dict(reply_steps=200, decode_replies=1600, lane_returns=1590, reply_wake_s=0.04, reply_resume_s=1.6, reply_build_s=0.32,
             rpc_send_s=0.16, rpc_recv_s=0.159, request_handle_s=0.318, lane_return_s=5.565, assemble_s=0.02, dispatch_s=0.2,
             wait_s=1.4, post_s=0.15, lanes_out_s=0.9, no_demand_s=0.03, gather_wait_s=0.24, handoff_s=0.06)
ONE = child(mark(5.0, **BEFORE), mark(8.0, **{key: BEFORE[key] + DELTA[key] for key in BEFORE}))
WANT = {"lane_return_ms": 3.5, "reply_wake_ms": 0.2, "reply_resume_ms": 1.0, "reply_build_ms": 0.2, "rpc_send_ms": 0.1,
        "rpc_recv_ms": 0.1, "request_handle_ms": 0.2, "off_server_ms": 1.9, "lanes_out_share": 30.0,
        "gather_wait_share": 8.0, "handoff_share": 2.0, "no_demand_share": 1.0}


def read(name, record):
    return load_reader("layer_metrics", name).read(record)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_gives_the_hand_worked_value(name):
    assert read(name, record_of(ONE)) == pytest.approx(WANT[name], rel=1e-9)


def test_the_eight_clocks_make_100_and_the_stations_make_the_trip():
    record = record_of(ONE)
    phases = sum(step_phases.share_of_window(record, (clock,)) for clock in step_phases.PHASES)
    assert phases + sum(read(name, record) for name in SHARES) == pytest.approx(100.0)
    assert sum(read(name, record) for name in STATIONS) == pytest.approx(read("lane_return_ms", record))
    assert read("off_server_ms", record) == pytest.approx(3.5 - (1.0 + 0.2 + 0.1 + 0.1 + 0.2))


def test_children_are_summed_counters_and_windows_alike():
    """A second server whose 100 lanes took twice as long to come back, in a
    slice of its own length: means weigh by events, shares by window."""
    two = child(mark(5.5), mark(8.5, lane_returns=100, lane_return_s=0.7, decode_replies=100, reply_steps=50, lanes_out_s=0.9))
    record = record_of(ONE, two)
    assert read("lane_return_ms", record) == pytest.approx(1e3 * (5.565 + 0.7) / 1690)
    assert read("lanes_out_share", record) == pytest.approx(30.0)
    assert round_trip.totals(record)["window_s"] == pytest.approx(6.0)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_gives_none_where_there_is_nothing_to_read(name):
    start, stop = ONE["marks"]["trace_start"], ONE["marks"]["trace_stop"]
    assert read(name, record_of()) is None  # no child
    assert read(name, record_of(child(None, stop))) is None  # an untraced run has no trace_start
    assert read(name, record_of(child(start, None))) is None
    assert read(name, record_of(ONE, child(None, None))) is None  # one child of two without the marks
    old = {k: v for k, v in stop["stats"].items() if k not in DELTA or k in step_phases.PHASES or k == "gather_wait_s"}
    assert read(name, record_of(child({**start, "stats": old}, {**stop, "stats": old}))) is None  # a program from before PR 37
    idle = child(start, {**start, "mono": 8.0})  # no reply in the slice: shares can be read, means cannot
    assert read(name, record_of(idle)) == (0.0 if name in SHARES else None)


def the_twelve() -> list:
    """``BENCHMARK.json``'s entries for the twelve, found by name: later PRs append to ``per_layer``."""
    entries = {m["name"]: m for m in BENCHMARK["per_layer"]}
    return [entries[name] for name in READERS]


def test_benchmark_json_names_the_twelve_after_what_it_had():
    assert len(the_twelve()) == len(READERS) == 12
    assert [m["name"] for m in BENCHMARK["per_layer"]][-12:] == list(WANT)  # appended, in the issue's order
    layers = {m["layer"] for m in BENCHMARK["per_layer"] if m["name"] not in READERS}  # the other entries' own spellings
    for m in the_twelve():
        unit, layer = READERS[m["name"]]
        reader = load_reader("layer_metrics", m["name"])
        assert m == {"name": m["name"], "unit": unit, "better": "lower", "source": "program_counter",
                     "layer": layer, "moves": "gap_p50_ms"}  # no "workloads": every cell reports them
        assert (reader.UNIT, reader.LAYER, reader.MOVES) == (unit, layer, "gap_p50_ms") and layer in layers
        assert (ROOT / "perf" / "layer_metrics" / f"{m['name']}.py").is_file()


def test_a_traced_tiny_cell_prints_all_twelve(tmp_path):
    """The whole command at a toy size on the CPU, traced, with the twelve
    entries beside the toy benchmark's own: three clients in a closed loop, 24
    tokens out each session, so that lanes come back some hundred times in
    the traced slice. The server child's marks carry the counters, every
    reader finds them, and the line's identities hold on a real run. The
    numbers mean nothing and go nowhere."""
    from perf import run

    data = Path(__file__).resolve().parent / "data"
    bench = json.loads((data / "benchmark-tiny.json").read_text())
    bench["per_layer"] += the_twelve()
    bench["workloads"].append({**bench["workloads"][0], "name": "tiny-closed-decode", "traffic": "tiny-closed-decode"})
    result = run.run_cell(bench, "tiny-closed-decode", 2**31 + 37, 9.0, True, traffic_dir=data / "traffic",
                          work_dir=tmp_path, allow_cpu=True)
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert result["correct"] is True and set(READERS) <= set(got), sorted(got)
    assert all(result["metrics"][k]["unit"] == READERS[k][0] for k in READERS)
    assert all(got[k] > 0 for k in READERS if k.endswith("_ms")), got
    assert sum(got[k] for k in STATIONS) == pytest.approx(got["lane_return_ms"])
    assert all(0 <= got[k] < 100 for k in SHARES) and got["lanes_out_share"] > 0
    # the tiling, on the dump's own marks: a stretch that straddles a mark is counted where it ends, and on a toy
    # server such a stretch can be a large part of a second, so this holds to some points here and to one on the chip
    record = record_of(json.loads((tmp_path / "runs" / "tiny-closed-decode" / "child0.json").read_text()))
    phases = sum(step_phases.share_of_window(record, (clock,)) for clock in step_phases.PHASES)
    assert phases + sum(got[k] for k in SHARES) == pytest.approx(100.0, abs=10.0)
