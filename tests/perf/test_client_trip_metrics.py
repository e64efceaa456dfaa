"""CPU tests of the thirteen readers of the round trip's other half (PR 54):
a hand-made ring, marks and loop samples give known values, the six stretches
make ``client_turn_ms``, a program without the ring or the counters and a run
without the marks give None, ``BENCHMARK.json``'s entries are found by name
and agree with their files, and the whole command at a toy size, traced,
carries all thirteen in its line."""

import json
from collections import deque
from pathlib import Path
from types import SimpleNamespace

import pytest

from perf import client_trip
from perf.record import Record, load_reader
from petals_tpu.telemetry import spans

ROOT = Path(__file__).resolve().parents[2]
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
RPC, GENERATOR, HANDLER = "client + RPC (client/, rpc/)", "load generator (perf/)", "handler (server/handler.py)"
READERS = {  # name: (unit, layer)
    "client_recv_ms": ("ms", RPC), "client_finish_ms": ("ms", RPC), "client_wake_ms": ("ms", RPC), "client_user_ms": ("ms", GENERATOR),
    "client_submit_ms": ("ms", RPC), "client_build_ms": ("ms", RPC), "client_turn_ms": ("ms", RPC), "client_away_ms": ("ms", RPC),
    "wire_and_loops_ms": ("ms", RPC), "server_loop_busy_share": ("%", HANDLER), "client_loop_busy_share": ("%", RPC),
    "server_loop_late_ms": ("ms", HANDLER), "client_loop_late_ms": ("ms", RPC),
}
SIX = ("client_recv_ms", "client_finish_ms", "client_wake_ms", "client_user_ms", "client_submit_ms", "client_build_ms")
SERVER_LOOP, CLIENT_LOOP = ("server_loop_busy_share", "server_loop_late_ms"), ("client_loop_busy_share", "client_loop_late_ms")

# the server's side of a 3 s slice, [5, 8] on the shared clock: 100 lanes came back after 5 ms, 2 ms of it at the server's
# own five stations; its loop ran 1000 turns, 0.9 s of them busy, their squares summing to 0.0018
ZERO = dict(lane_returns=0, decode_replies=0, reply_steps=0, lane_return_s=0.0, reply_wake_s=0.0, reply_resume_s=0.0, reply_build_s=0.0,
            rpc_send_s=0.0, rpc_recv_s=0.0, request_handle_s=0.0, assemble_s=0.0, dispatch_s=0.0, wait_s=0.0, post_s=0.0,
            lanes_out_s=0.0, no_demand_s=0.0, gather_wait_s=0.0, handoff_s=0.0, loop_busy_s=0.0, loop_busy_sq=0.0, loop_turns=0)
BEFORE = {**ZERO, "lane_returns": 50, "lane_return_s": 1.0, "loop_busy_s": 40.0, "loop_busy_sq": 0.5, "loop_turns": 90000}
DELTA = dict(lane_returns=100, decode_replies=100, reply_steps=30, lane_return_s=0.5, reply_resume_s=0.1, reply_build_s=0.02, rpc_send_s=0.02,
             rpc_recv_s=0.04, request_handle_s=0.02, loop_busy_s=0.9, loop_busy_sq=0.0018, loop_turns=1000)


def mark(mono, stats):
    return {"wall": 1e9 + mono, "mono": mono, "stats": dict(stats), "bytes_in_use": 0}


def child(start=mark(5.0, BEFORE), stop=mark(8.0, {**BEFORE, **{k: BEFORE[k] + v for k, v in DELTA.items()}})):
    marks = {"window": mark(1.0, ZERO), "window_end": mark(11.0, ZERO)}
    marks.update({label: m for label, m in (("trace_start", start), ("trace_stop", stop)) if m is not None})
    return {"marks": marks}


def record_of(*children):
    return Record(config={}, t_process=0.0, t0=1.0, seconds=10.0, t_drained=12.0, sessions=[], children=list(children))


def row(read_at, before=1.0, after=None, *, hops=1, tokens=1, away=0.005, followed=True, trace_id="t-0", step=1):
    """A step whose reply took ``after`` x (0.1, 0.3, 0.2) ms from its frame's reading to the caller (recv, finish, wake)
    and whose turn to the next request took ``before`` x (0.4, 0.15, 0.25) ms more (user, submit, build)."""
    recv, finish, wake = ((before if after is None else after) * ms / 1e3 for ms in (0.1, 0.3, 0.2))
    turn = tuple(before * ms / 1e3 for ms in (0.4, 0.15, 0.25)) if followed else (None, None, None)
    return [read_at, trace_id, step, hops, tokens, away, recv, finish, wake, *turn, 0.0 if hops == 1 else 0.001]


# five decode replies of the slice (5.5, 6.2, 7.0, 7.5, 7.9), 5 ms away each but a session's last (10 ms, no turn after it); their
# own recv, finish and wake are 1, 2, 2, 1.5 and 1 times the row's: 1.5 on average; the turn that LED to each is the row's of the
# session's step before it, one before the slice (4.9) and one a prompt's reply (6.0, which took 50 times as long to unpack: no
# decode gap holds that): 1, 1, 2, 1.5 and 2 times: 1.5 on average. No reader takes a prompt's away, a chain's step, a step outside
ROWS = [row(4.5, 7.0, tokens=64, step=0), row(4.9, 1.0, 7.0, step=1), row(5.5, step=2), row(6.0, 1.5, 50.0, tokens=64, trace_id="t-1", step=0),
        row(6.2, 2.0, step=3), row(6.4, 5.0, hops=2, trace_id="t-2", step=7), row(7.0, 2.0, step=4), row(7.5, 9.0, 1.5, trace_id="t-1", step=1),
        row(7.9, away=0.010, followed=False, step=5), row(8.1, trace_id="t-3", step=1)]
# the client's loop: sampled every ~0.1 s; 5.02 and 7.97 lie nearest the marks: 2.95 s, 2.36 of them busy, squares 0.00295
SAMPLES = [(4.0, 10.0, 0.1, 5000), (4.95, 10.7, 0.1009, 5400), (5.02, 10.76, 0.101, 5430), (6.5, 11.9, 0.1025, 6000),
           (7.97, 13.12, 0.10395, 6900), (8.06, 13.2, 0.104, 6950), (9.0, 13.9, 0.105, 7400)]
WANT = {"client_recv_ms": 0.15, "client_finish_ms": 0.45, "client_wake_ms": 0.3, "client_user_ms": 0.6, "client_submit_ms": 0.225,
        "client_build_ms": 0.375, "client_turn_ms": 2.1, "client_away_ms": 6.0, "wire_and_loops_ms": 3.0 - 2.1,
        "server_loop_busy_share": 30.0, "server_loop_late_ms": 0.3, "client_loop_busy_share": 80.0, "client_loop_late_ms": 0.5}


@pytest.fixture
def ring(monkeypatch):
    """The process's ring, swapped for a hand-made one."""
    made = spans.StepRing(rows=64)
    made.rows.extend(ROWS)
    made.loop_clock = SimpleNamespace(samples=deque(SAMPLES, maxlen=64))
    monkeypatch.setattr(spans, "STEP_RING", made)
    return made


def read(name, record):
    return load_reader("layer_metrics", name).read(record)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_gives_the_hand_worked_value(name, ring):
    assert read(name, record_of(child())) == pytest.approx(WANT[name], rel=1e-9)


def test_the_six_stretches_make_the_turn_and_the_turn_and_the_trip_the_gap(ring):
    record = record_of(child())
    # a decode reply's gap, caller to caller, is the turn that led to it, its away and its own way to the caller
    assert sum(read(name, record) for name in SIX) == pytest.approx(read("client_turn_ms", record), rel=1e-12)
    assert read("wire_and_loops_ms", record) + read("client_turn_ms", record) == pytest.approx(read("off_server_ms", record))
    assert [(r["read_at"], r["trace_id"]) for r in client_trip.steps(record)] == [(5.5, "t-0"), (6.2, "t-0"), (7.0, "t-0"), (7.5, "t-1"), (7.9, "t-0")]
    rows = client_trip._rows(record)
    assert [(r["read_at"], r["tokens"]) for r in client_trip._stretch_rows(rows, "build_s")] == [(4.9, 1), (5.5, 1), (6.2, 1), (6.0, 64), (7.0, 1)]  # the step before each
    assert [r["read_at"] for r in client_trip._stretch_rows(rows, "wake_s")] == [5.5, 6.2, 7.0, 7.5, 7.9]  # the reply's own
    del ring.rows[1]  # the ring no longer holds the step before the slice's first: its turn is in no mean
    assert len(client_trip._stretch_rows(client_trip._rows(record), "user_s")) == 4 and len(client_trip.steps(record)) == 5
    ring.rows.insert(1, row(4.9, hops=2, step=1))  # nor is a chain's turn one
    assert len(client_trip._stretch_rows(client_trip._rows(record), "user_s")) == 4
    # two children: the slice both describe, the loops' seconds and windows summed
    two = record_of(child(), child(mark(5.4, BEFORE), mark(8.4, {**BEFORE, "loop_busy_s": 40.3, "loop_busy_sq": 0.5006, "loop_turns": 90100})))
    assert client_trip.slice_of(two) == (5.4, 8.0) and [r["read_at"] for r in client_trip.steps(two)][0] == 5.5
    assert read("server_loop_busy_share", two) == pytest.approx(100 * 1.2 / 6.0) and read("server_loop_late_ms", two) == pytest.approx(1e3 * 0.0024 / 12.0)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_gives_none_where_there_is_nothing_to_read(name, ring, monkeypatch):
    start, stop = child()["marks"]["trace_start"], child()["marks"]["trace_stop"]
    assert read(name, record_of()) is None  # no child
    assert read(name, record_of(child(None, stop))) is None  # an untraced run has no trace_start
    assert read(name, record_of(child(start, None))) is None
    assert read(name, record_of(child(), child(None, None))) is None  # one child of two without the marks
    old = [{k: v for k, v in m["stats"].items() if not k.startswith("loop_")} for m in (start, stop)]  # a server from before PR 54
    assert (read(name, record_of(child(mark(5.0, old[0]), mark(8.0, old[1])))) is None) == (name in SERVER_LOOP)
    still = child(start, mark(8.0, {**stop["stats"], "loop_turns": BEFORE["loop_turns"]}))  # a loop that took no clock counts no turn
    assert (read(name, record_of(still)) is None) == (name in SERVER_LOOP)
    early = child(mark(2.0, BEFORE), mark(3.0, stop["stats"]))  # a slice with no step in it, from before the samples reach
    assert (read(name, record_of(early)) is None) == (name not in SERVER_LOOP)
    ring.loop_clock = None  # the steps ran on a loop without a clock
    assert (read(name, record_of(child())) is None) == (name in CLIENT_LOOP)
    ring.rows.clear()
    assert (read(name, record_of(child())) is None) == (name not in SERVER_LOOP)
    monkeypatch.delattr(spans, "STEP_RING")  # a client from before PR 54
    assert (read(name, record_of(child())) is None) == (name not in SERVER_LOOP)


def the_thirteen() -> list:
    """``BENCHMARK.json``'s entries for the thirteen, found by name: later PRs append to ``per_layer``."""
    entries = {m["name"]: m for m in BENCHMARK["per_layer"]}
    return [entries[name] for name in READERS]


@pytest.mark.parametrize("name", sorted(READERS))
def test_benchmark_json_names_the_reader_with_its_file_unit_and_layer(name):
    (entry,) = [m for m in BENCHMARK["per_layer"] if m["name"] == name]
    unit, layer = READERS[name]
    reader = load_reader("layer_metrics", name)
    assert entry == {"name": name, "unit": unit, "better": "lower", "source": "program_counter", "layer": layer,
                     "moves": "gap_p50_ms"}  # no "workloads": every cell reports it
    assert (reader.UNIT, reader.LAYER, reader.MOVES) == (unit, layer, "gap_p50_ms")
    assert layer in {m["layer"] for m in BENCHMARK["per_layer"] if m["name"] not in READERS}  # a layer the benchmark named before
    assert (ROOT / "perf" / "layer_metrics" / f"{name}.py").is_file() and reader.__doc__


def test_a_traced_tiny_cell_prints_all_thirteen(tmp_path, monkeypatch):
    """The whole command at a toy size on the CPU, traced, with the thirteen
    entries beside the toy benchmark's own: three clients in a closed loop, 24
    tokens out a session. The load generator's own process is the client, so
    the ring and its loop's clock are this process's; the server child's marks
    carry its loop's sums. The line's identities hold on a real run, and the
    client's stretches tile the load generator's own gaps of the same slice.
    The numbers mean nothing and go nowhere."""
    from perf import loadgen, run

    replays = []

    class Kept(loadgen.Replay):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            replays.append(self)

    monkeypatch.setattr(run.loadgen, "Replay", Kept)
    monkeypatch.setattr(spans, "STEP_RING", spans.StepRing())  # this run's steps alone
    data = Path(__file__).resolve().parent / "data"
    bench = json.loads((data / "benchmark-tiny.json").read_text())
    bench["per_layer"] += the_thirteen() + [m for m in BENCHMARK["per_layer"] if m["name"] == "off_server_ms"]
    bench["workloads"].append({**bench["workloads"][0], "name": "tiny-closed-decode", "traffic": "tiny-closed-decode"})
    result = run.run_cell(bench, "tiny-closed-decode", 2**31 + 54, 9.0, True, traffic_dir=data / "traffic", work_dir=tmp_path, allow_cpu=True)
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert result["correct"] is True and set(READERS) <= set(got), sorted(got)
    assert all(result["metrics"][k]["unit"] == READERS[k][0] for k in READERS)
    assert all(got[k] > 0 for k in READERS if k != "wire_and_loops_ms"), got
    assert sum(got[k] for k in SIX) == pytest.approx(got["client_turn_ms"], rel=1e-9)
    assert got["wire_and_loops_ms"] == pytest.approx(got["off_server_ms"] - got["client_turn_ms"])
    assert all(0 < got[k] < 100 for k in ("server_loop_busy_share", "client_loop_busy_share"))
    # the tiling against the load generator's own clock: its gaps whose reply came inside the server's two marks
    dump = json.loads((tmp_path / "runs" / "tiny-closed-decode" / "child0.json").read_text())
    lo, hi = (dump["marks"][label]["mono"] for label in ("trace_start", "trace_stop"))
    gaps = [t - before for s in replays[0].records if s.first_reply is not None
            for before, t in zip([s.first_reply] + [t for t, _ in s.replies], [t for t, _ in s.replies]) if lo <= t <= hi]
    assert len(gaps) > 50 and got["client_turn_ms"] + got["client_away_ms"] == pytest.approx(1e3 * sum(gaps) / len(gaps), rel=0.1)
