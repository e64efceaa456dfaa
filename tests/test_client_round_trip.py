"""The round trip's other half on record (PR 54): the client times its own
stations of a step (``telemetry/spans.py ClientTrip``, read at
``rpc/client.py``, ``client/inference_session.py`` and
``client/remote_sequential.py``), every step leaves a row in a bounded ring,
and both event loops time their turns (``utils/asyncio_utils.py``).

The first half drives the normal client against two tiny llama servers on
loopback (blocks [0, 2) and [2, 4): a chain over the first two blocks is one
hop, over all four two); the second drives the trip and the turn clock alone."""

import asyncio
import selectors
import time

import numpy as np
import pytest

from petals_tpu.client.config import ClientConfig
from petals_tpu.client.inference_session import InferenceSession
from petals_tpu.client.remote_sequential import RemoteSequential
from petals_tpu.client.runtime import SwarmRuntime
from petals_tpu.data_structures import make_uid
from petals_tpu.rpc import RpcClient, RpcServer
from petals_tpu.telemetry import spans
from petals_tpu.telemetry.spans import CLIENT_STRETCHES, ROW, STEP_RING, ClientTrip, StepRing, format_waterfall
from petals_tpu.utils.asyncio_utils import TURN_SUMS, install_turn_clock, turn_clock_of
from tests.test_full_model import SwarmHarness
from tests.utils import make_tiny_llama

pytestmark = pytest.mark.telemetry

TURN = ("recv_s", "finish_s", "wake_s", "user_s", "submit_s", "build_s")  # K3 to the next K2
LANES = dict(batching=True, batch_lanes=2, batch_max_length=32, page_size=8)


@pytest.fixture(scope="module")
def swarm(tmp_path_factory):
    path = make_tiny_llama(str(tmp_path_factory.mktemp("models")))
    harness = SwarmHarness(path, [dict(first_block=0, num_blocks=2, **LANES), dict(first_block=2, num_blocks=2, **LANES)]).start()
    yield harness
    harness.stop()


def _uids(harness, n_blocks):
    return [make_uid(harness.servers[0].dht_prefix, i) for i in range(n_blocks)]


def _rows_of(trace_id):
    return [dict(zip(ROW, row)) for row in list(STEP_RING.rows) if row[ROW.index("trace_id")] == trace_id]


def _hidden(harness, n, seed=0):
    return np.random.RandomState(seed).randn(1, n, harness.servers[0].cfg.hidden_size).astype(np.float32) * 0.1


def _assert_tiles(rows):
    """Seven stretches (and what a chain relays) sum to the time from one reply read to the next."""
    for row, after in zip(rows, rows[1:]):
        turn = sum(row[k] for k in TURN)
        assert turn + after["away_s"] + after["relay_s"] == pytest.approx(after["read_at"] - row["read_at"], abs=1e-6)


def test_a_sync_session_s_stretches_tile_its_time_reply_to_reply(swarm):
    n, think = 6, 0.003
    remote = RemoteSequential(ClientConfig(initial_peers=swarm.initial_peers), _uids(swarm, 2))
    try:
        assert remote.runtime.turn_clock is turn_clock_of(remote.runtime.loop) is not None
        with remote.inference_session(max_length=16) as session:
            session.step(_hidden(swarm, 4))
            for t in range(n):
                time.sleep(think)  # the application's own time between two steps
                session.step(_hidden(swarm, 1, seed=t + 1))
            report = session.trace_report()  # the public accessor
            assert report == session._session.trace_report()
            rows = _rows_of(report["trace_id"])
        assert STEP_RING.loop_clock is remote.runtime.turn_clock  # where a reader of the ring finds the loop's clock
    finally:
        remote.close()
    assert [(r["step"], r["hops"], r["tokens"]) for r in rows] == [(0, 1, 4)] + [(t, 1, 1) for t in range(1, n + 1)]
    _assert_tiles(rows)
    last = rows[-1]  # no request followed it: a wake, and none of the three stretches up to a K2
    assert last["wake_s"] > 0 and [last[k] for k in ("user_s", "submit_s", "build_s")] == [None] * 3
    assert all(r[k] > 0 for r in rows[:-1] for k in TURN) and all(r["relay_s"] == 0.0 for r in rows)
    # away_s runs from K2 to K3, and a reply read before the thread that wrote its own frame had read K2 makes K2 = K3
    # (telemetry/spans.py): exactly 0.0 then, which a busy host brings about, and nowhere else
    assert all(r["away_s"] > 0 or (r["away_s"] == 0.0 and r["wrote"] == 1) for r in rows[:-1]) and rows[0]["away_s"] > 0
    assert all(r["user_s"] >= think for r in rows[:-1])
    # the session's sums are the rows' columns, each stretch counted for the steps that have it
    client = report["client"]
    assert set(client) == {*CLIENT_STRETCHES, "steps", "turns", "direct", "wrote", "deferred"} and (client["steps"], client["turns"]) == (n + 1, n)
    assert client["direct"] == n and [r["direct"] for r in rows] == [0] + [1] * n  # a first step is a coroutine's (PR 55)
    # and of the direct steps' frames (one hop each), each is written by the caller's thread or left to the loop (PR 58)
    assert client["wrote"] + client["deferred"] == n and [r["wrote"] for r in rows][0] == 0 and sum(r["wrote"] for r in rows) == client["wrote"]
    for k in CLIENT_STRETCHES:
        assert client[k] == pytest.approx(sum(r[k] for r in rows if r[k] is not None), abs=1e-5)
    # seen from outside: a hop's wall (the send included) holds what was away, and what the client held the reply for
    hop = report["hops"][0]
    assert client["away_s"] + client["recv_s"] <= hop["wall_s"] + 1e-5
    # the waterfall: the lines it had, and one more
    lines = format_waterfall(report).splitlines()
    assert [line for line in lines if line.startswith("  client: ")] == [lines[-2]] and f"({n + 1} steps, {n} followed, {n} direct)" in lines[-2]
    assert format_waterfall({k: v for k, v in report.items() if k != "client"}).splitlines() == lines[:-2] + lines[-1:]


def test_an_async_caller_reads_no_wake_or_submit_and_a_chain_relays(swarm):
    async def drive(n_blocks):
        from petals_tpu.client.routing.sequence_manager import RemoteSequenceManager

        manager = await RemoteSequenceManager.create(ClientConfig(initial_peers=swarm.initial_peers), _uids(swarm, n_blocks))
        try:
            session = InferenceSession(manager, max_length=16)
            await session.step(_hidden(swarm, 3))
            for t in range(4):
                await asyncio.sleep(0.002)
                await session.step(_hidden(swarm, 1, seed=t + 1))
            report = session.trace_report()
            await session.close()
            return report
        finally:
            await manager.shutdown()

    runtime = SwarmRuntime()
    try:
        one, two = runtime.run(drive(2), timeout=300), runtime.run(drive(4), timeout=300)
    finally:
        runtime.shutdown()
    for report, hops in ((one, 1), (two, 2)):
        rows, client = _rows_of(report["trace_id"]), report["client"]
        assert [r["hops"] for r in rows] == [hops] * 5 and (client["steps"], client["turns"]) == (5, 4)
        _assert_tiles(rows)
        assert client["wake_s"] == client["submit_s"] == 0.0  # K6 = K5 and K1 = K0
        assert all(r["wake_s"] == r["submit_s"] == 0.0 and r["user_s"] >= 0.002 for r in rows[:-1])
        assert rows[-1]["wake_s"] is None  # nothing said where the last reply went
    assert one["client"]["relay_s"] == 0.0
    assert two["client"]["relay_s"] > 0 and all(r["relay_s"] > 0 for r in _rows_of(two["trace_id"]))


def test_the_server_s_loop_times_its_turns_into_batcher_stats(swarm):
    server = swarm.servers[0]
    stats = server.handler.batcher.stats
    assert set(TURN_SUMS) <= set(stats) and isinstance(stats["loop_turns"], int)
    before = {key: stats[key] for key in TURN_SUMS}
    remote = RemoteSequential(ClientConfig(initial_peers=swarm.initial_peers), _uids(swarm, 2))
    try:
        with remote.inference_session(max_length=8) as session:
            for t in range(3):
                session.step(_hidden(swarm, 1, seed=t))
    finally:
        remote.close()
    assert all(stats[key] > before[key] for key in TURN_SUMS), (before, {key: stats[key] for key in TURN_SUMS})
    assert stats["loop_busy_sq"] <= stats["loop_busy_s"] ** 2  # a sum of squares under the square of the sum
    info = swarm.run(server.handler.rpc_info({}, None))
    assert set(TURN_SUMS) <= set(info["continuous_batching"])
    # one loop, one clock, however many servers it runs: each adds to its own batcher's dict
    clock = turn_clock_of(swarm.loop)
    assert clock is install_turn_clock(swarm.loop) and swarm.servers[1].handler.batcher.stats["loop_turns"] > 0


# ------------------------------------------------- the trip and the ring alone


def _step(trip, t, *, sync=True, hops=1, tokens=1):
    """One step whose stretches are 1, 2, 4, ... ms from ``t`` on; returns the time of its last reading."""
    if sync:
        trip.entered(t)  # K0
        t += 0.001
    trip.on_loop(t)  # K1
    for h in range(hops):
        sent = t + 0.002 * (h + 1)  # K2: 2 ms to build, 4 ms to relay
        trip.hop(sent, sent + 0.004, sent + 0.004 + 0.008)  # away 4 ms, recv 8 ms
        t = sent + 0.004
    t += 0.008 + 0.016
    trip.finished(t, tokens)  # K5: finish 16 ms
    if sync:
        t += 0.032
        trip.woke(t)  # K6: wake 32 ms
    return t


def test_the_trip_s_stretches_on_hand_worked_readings():
    ring = StepRing(rows=8)
    trip = ClientTrip("t-1", ring)
    t = _step(trip, 10.0, tokens=5)
    t = _step(trip, t + 0.064)  # user 64 ms
    t = _step(trip, t + 0.064, hops=2)
    first, second, third = (dict(zip(ROW, row)) for row in ring.rows)
    want = dict(away_s=0.004, recv_s=0.008, finish_s=0.016, wake_s=0.032, user_s=0.064, submit_s=0.001, build_s=0.002, relay_s=0.0)
    assert {k: first[k] for k in CLIENT_STRETCHES} == pytest.approx(want)
    assert {k: second[k] for k in CLIENT_STRETCHES} == pytest.approx(want)
    assert (first["read_at"], first["trace_id"], first["step"], first["hops"], first["tokens"]) == (pytest.approx(10.007), "t-1", 0, 1, 5)
    assert {k: third[k] for k in CLIENT_STRETCHES} == {**{k: pytest.approx(v) for k, v in want.items()}, "away_s": pytest.approx(0.008),
                                                       "relay_s": pytest.approx(0.004), "user_s": None, "submit_s": None, "build_s": None}
    assert third["hops"] == 2 and third["read_at"] == pytest.approx(t - 0.056)  # the LAST hop's K3
    assert trip.report() == {"away_s": 0.016, "recv_s": 0.024, "finish_s": 0.048, "wake_s": 0.096, "user_s": 0.128, "submit_s": 0.002,
                             "build_s": 0.004, "relay_s": 0.004, "steps": 3, "turns": 2, "direct": 0, "wrote": 0, "deferred": 0}
    trip.interrupt()  # a server-side generation: the open turn is dropped, the next step closes none
    _step(trip, t + 5.0)
    assert trip.turns == 2 and trip.sums["user_s"] == pytest.approx(0.128) and list(ring.rows)[2][ROW.index("user_s")] is None


def test_the_ring_overwrites_oldest_first_and_never_grows():
    ring = StepRing(rows=4)
    trips = [ClientTrip(f"t-{i}", ring) for i in range(2)]  # two sessions, one ring
    t = 1.0
    for n in range(7):
        t = _step(trips[n % 2], t + 0.001, sync=False)
        assert len(ring.rows) == min(n + 1, 4)
    assert [(row[1], row[2]) for row in ring.rows] == [("t-1", 1), ("t-0", 2), ("t-1", 2), ("t-0", 3)]
    assert ring.rows.maxlen == 4 and STEP_RING.rows.maxlen == spans.STEP_RING_ROWS >= 8 * 51 * 150  # eight lanes, a window, 150 steps a second


# ------------------------------------------------- the turn clock alone


def test_a_blocking_callback_shows_in_the_loop_s_busy_seconds_and_lateness():
    runtime = SwarmRuntime()
    try:
        clock = runtime.turn_clock
        assert clock is not None and clock.samples is not None and set(clock.sums) == set(TURN_SUMS)
        runtime.run(asyncio.sleep(0.01))
        before, t0 = dict(clock.sums), time.perf_counter()

        async def block():
            time.sleep(0.05)  # what no coroutine should do: no socket is looked at meanwhile

        runtime.run(block())
        runtime.run(asyncio.sleep(0.12))  # the loop sleeps in select(): nothing is added; a sample is due after it
        runtime.run(asyncio.sleep(0))
        elapsed = time.perf_counter() - t0
        busy, busy_sq, turns = (clock.sums[key] - before[key] for key in TURN_SUMS)
        assert 0.05 <= busy < 0.05 + 0.05 and busy_sq >= 0.05**2 and 3 <= turns < 100
        # a socket ready at a random moment of those ~0.18 s met the 50 ms turn with probability 0.05 / elapsed, and then waited 25 ms
        assert busy_sq / (2 * elapsed) >= 0.05 * 0.025 / elapsed  # the lateness estimate: the squares over twice the elapsed time
        assert len(clock.samples) >= 2 and clock.samples.maxlen is not None
        t, *sums = clock.samples[-1]
        assert t0 < t <= time.perf_counter() and all(a <= b for a, b in zip(sums, (clock.sums[key] for key in TURN_SUMS)))
        gaps = np.diff([s[0] for s in clock.samples])
        assert (gaps >= 0.1).all()  # a sample about every 0.1 s, at a select()'s call: none while the loop sleeps
        sink = dict.fromkeys(TURN_SUMS, 0)
        clock.attach(sink)
        clock.attach(sink)  # once
        runtime.run(asyncio.sleep(0.01))
        clock.detach(sink)
        held = dict(sink)
        runtime.run(asyncio.sleep(0.01))
        assert held["loop_turns"] >= 1 and sink == held
    finally:
        runtime.shutdown()


class _SlottedSelector:
    """A selector that takes no attribute of its own: ``select`` cannot be wrapped."""

    __slots__ = ("_inner",)

    def __init__(self):
        self._inner = selectors.DefaultSelector()

    def __getattr__(self, name):
        return getattr(self._inner, name)


def test_a_loop_without_a_python_selector_gets_no_clock_and_serves_as_before(monkeypatch):
    class NoSelector:
        pass

    assert install_turn_clock(NoSelector()) is None and turn_clock_of(NoSelector()) is None
    monkeypatch.setattr(asyncio, "new_event_loop", lambda: asyncio.SelectorEventLoop(_SlottedSelector()))
    runtime = SwarmRuntime()
    monkeypatch.undo()
    try:
        assert runtime.turn_clock is None and turn_clock_of(runtime.loop) is None

        async def echo_over_loopback():
            server = RpcServer()

            async def echo(requests, ctx):
                async for item in requests:
                    yield item

            server.add_stream_handler("echo", echo)
            await server.start()
            client = await RpcClient.connect(server.host, server.port)
            try:
                stream = await client.open_stream("echo")
                sent = time.perf_counter()
                await stream.send({"n": 1})
                reply = await stream.recv(timeout=30)
                assert sent <= stream.read_at <= time.perf_counter()  # the frame's reading is handed on with the item
                return reply
            finally:
                await client.close()
                await server.stop()

        assert runtime.run(echo_over_loopback(), timeout=60) == {"n": 1}
    finally:
        runtime.shutdown()
