"""A sync caller's decode step exchanged by the caller's own thread (PR 55):
``SyncInferenceSession.step`` -> ``InferenceSession.step_from_thread`` ->
``_ServerInferenceSession.step_from_thread`` -> ``StreamCall.send_from_thread``
/ ``recv_in_thread``, beside the coroutine every other step still is.

The first half drives the normal client against tiny llama servers on loopback
(blocks [0, 2) once and [2, 4) twice, a preferred server and an understudy: a
chain over two blocks is one hop, over four two, and the second hop can be
repaired); the second drives a stream of ``rpc/`` alone against
``tests/test_rpc.py``'s kind of server."""

import asyncio
import contextlib
import socket
import threading
import time

import numpy as np
import pytest

from petals_tpu import chaos
from petals_tpu.client import inference_session as inference_session_module
from petals_tpu.client.config import ClientConfig
from petals_tpu.client.inference_session import InferenceSession
from petals_tpu.client.remote_sequential import RemoteSequential
from petals_tpu.client.runtime import SwarmRuntime
from petals_tpu.data_structures import make_uid
from petals_tpu.rpc import RpcClient, RpcServer
from petals_tpu.rpc.client import THREAD_FRAME_BYTES
from petals_tpu.rpc.protocol import decode_frame
from petals_tpu.rpc.server import RpcError
from petals_tpu.telemetry.spans import CLIENT_STRETCHES, ROW, STEP_RING
from tests.test_full_model import SwarmHarness
from tests.utils import make_tiny_llama

pytestmark = pytest.mark.telemetry

TURN = ("recv_s", "finish_s", "wake_s", "user_s", "submit_s", "build_s")  # K3 to the next K2
LANES = dict(batching=True, batch_lanes=4, batch_max_length=32, page_size=8)


@pytest.fixture(scope="module")
def swarm(tmp_path_factory):
    path = make_tiny_llama(str(tmp_path_factory.mktemp("models")))
    harness = SwarmHarness(path, [dict(first_block=0, num_blocks=2, **LANES), dict(first_block=2, num_blocks=2, throughput=1000.0, **LANES),
                                  dict(first_block=2, num_blocks=2, throughput=1.0, **LANES)]).start()
    yield harness
    harness.stop()


@pytest.fixture
def remote_of(swarm):
    """``remote_of(n_blocks, **config)``: a client over the first ``n_blocks`` blocks, closed after the test."""
    made = []

    def make(n_blocks, **config):
        uids = [make_uid(swarm.servers[0].dht_prefix, i) for i in range(n_blocks)]
        made.append(RemoteSequential(ClientConfig(initial_peers=swarm.initial_peers, min_backoff=0.05, **config), uids))
        return made[-1]

    yield make
    for remote in made:
        remote.close()


@pytest.fixture
def coroutine_only():
    """``with coroutine_only():`` every step within is a coroutine's, as before PR 55."""

    @contextlib.contextmanager
    def forced():
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(InferenceSession, "can_step_from_thread", lambda self, hidden: False)
            yield

    return forced


def _hidden(harness, n, seed=0):
    return np.random.RandomState(seed).randn(1, n, harness.servers[0].cfg.hidden_size).astype(np.float32) * 0.1


def _rows_of(trace_id):
    return [dict(zip(ROW, row)) for row in list(STEP_RING.rows) if row[ROW.index("trace_id")] == trace_id]


def _drive(remote, swarm, n=5, prompt=4):
    """A prompt and ``n`` decode steps; everything a caller or an operator can see of them."""
    with remote.inference_session(max_length=16) as session:
        outs = [session.step(_hidden(swarm, prompt))] + [session.step(_hidden(swarm, 1, seed=t + 1)) for t in range(n)]
        inner = session._session
        seen = dict(
            outs=outs, position=session.position, hop_positions=[s.position for s in inner._sessions],
            history=[[(h.copy(), hypo) for h, hypo in s.history] for s in inner._sessions], report=session.trace_report(),
            usage=inner.usage_report(),
        )
    seen["rows"] = _rows_of(seen["report"]["trace_id"])
    return seen


# ------------------------------------------------- one algorithm, two exchanges


@pytest.mark.parametrize("n_blocks, hops", [(2, 1), (4, 2)])
def test_the_direct_way_and_the_coroutine_give_the_same_step(swarm, remote_of, coroutine_only, n_blocks, hops):
    n, remote = 5, remote_of(n_blocks)
    direct = _drive(remote, swarm, n)
    with coroutine_only():
        coroutine = _drive(remote, swarm, n)
    assert direct["report"]["client"]["direct"] == n and coroutine["report"]["client"]["direct"] == 0
    assert [r["direct"] for r in direct["rows"]] == [0] + [1] * n and [r["direct"] for r in coroutine["rows"]] == [0] * (n + 1)
    assert direct["usage"]["direct_steps"] == n and coroutine["usage"]["direct_steps"] == 0
    for a, b in zip(direct["outs"], coroutine["outs"]):
        assert a.dtype == b.dtype and np.array_equal(a, b)  # bit for bit
    assert direct["position"] == coroutine["position"] == 4 + n
    assert direct["hop_positions"] == coroutine["hop_positions"] == [4 + n] * hops
    for mine, theirs in zip(direct["history"], coroutine["history"]):
        assert len(mine) == len(theirs) == n + 1
        assert all(np.array_equal(a, b) and ha is hb is None for (a, ha), (b, hb) in zip(mine, theirs))
    for key in ("steps", "tokens", "retired_hops"):
        assert direct["report"][key] == coroutine["report"][key]
    assert [(h["blocks"], h["steps"], h["meta_steps"], h["tokens"]) for h in direct["report"]["hops"]] == [
        (h["blocks"], h["steps"], h["meta_steps"], h["tokens"]) for h in coroutine["report"]["hops"]]
    assert len(direct["report"]["hops"]) == hops
    # ring rows of the same shape: the same columns hold numbers, the same hold None
    for mine, theirs in zip(direct["rows"], coroutine["rows"]):
        assert [(k, mine[k] is None) for k in ROW] == [(k, theirs[k] is None) for k in ROW]
        assert [mine[k] for k in ("step", "hops", "tokens")] == [theirs[k] for k in ("step", "hops", "tokens")]


def _tap(stream, on_frame):
    """Every frame the connection of ``stream`` is handed from now on goes through ``on_frame(message) -> bool`` (whether it
    goes on to the wire), whoever writes it: the loop (``writer.write``, an instance attribute over the class's method) or a
    caller's thread (the outlet's socket). Gives the undo."""
    client = stream._client
    writer, outlet = client._writer, client._outlet
    real_write, real_sock = writer.write, outlet._sock

    def kept(data) -> bool:
        data, keep = bytes(data), True
        while data:  # whole frames, one after another
            size = 4 + int.from_bytes(data[:4], "big")
            keep, data = on_frame(decode_frame(data[4:size])) and keep, data[size:]
        return keep

    class Socket:
        def send(self, data):
            return real_sock.send(data) if kept(data) else len(data)

        def close(self):
            real_sock.close()

    writer.write = lambda data: real_write(data) if kept(data) else None
    outlet._sock = Socket()

    def undo():
        writer.__dict__.pop("write", None)
        if outlet._sock is not None:  # not closed meanwhile
            outlet._sock = real_sock

    return undo


def _step_frames(sync_session, hop=0):
    """Record what the connection of ``hop``'s stream is handed for that stream from now on; gives the list the frames go to."""
    stream, frames = sync_session._session._sessions[hop].stream, []

    def on_frame(message):
        if message.get("t") == "sitem" and message.get("id") == stream._call_id:
            frames.append(message)
        return True

    return frames, _tap(stream, on_frame)


def test_a_step_s_frame_on_the_wire_is_the_coroutine_s_but_for_its_random_id(swarm, remote_of, coroutine_only):
    remote, taken = remote_of(2), {}
    for way in ("direct", "coroutine"):
        with remote.inference_session(max_length=16) as session:
            session.step(_hidden(swarm, 3))
            frames, undo = _step_frames(session)
            try:
                if way == "coroutine":
                    with coroutine_only():
                        session.step(_hidden(swarm, 1, seed=7))
                else:
                    session.step(_hidden(swarm, 1, seed=7))
            finally:
                undo()
            assert session._session.trip.direct == (1 if way == "direct" else 0)
            (taken[way],) = frames
            assert taken[way]["id"] == session._session._sessions[0].stream._call_id
    mine, theirs = taken["direct"], taken["coroutine"]
    assert set(mine) == set(theirs) == {"t", "id", "payload"} and mine["t"] == theirs["t"] == "sitem"
    assert set(mine["payload"]) == set(theirs["payload"]) == {"tensors", "step_id"}
    assert len(mine["payload"]["step_id"]) == len(theirs["payload"]["step_id"]) == 32 and mine["payload"]["step_id"] != theirs["payload"]["step_id"]
    assert mine["payload"]["tensors"] == theirs["payload"]["tensors"]  # shape, dtype, codec and every byte
    assert mine["payload"]["tensors"]["hidden"]["data"] == _hidden(swarm, 1, seed=7).tobytes()


# ------------------------------------------------- what takes the coroutine


def _first_step(session, swarm, monkeypatch):
    return session.step(_hidden(swarm, 1))


def _prompts(session, swarm, monkeypatch):
    hidden = swarm.servers[0].cfg.hidden_size
    return session.step(_hidden(swarm, 1, seed=2), prompts=np.zeros((2, 1, 1, hidden), np.float32))


def _hypo_ids(session, swarm, monkeypatch):
    return session.step(_hidden(swarm, 1, seed=2), hypo_ids=np.zeros(1, np.int64))


def _rollback(session, swarm, monkeypatch):
    session.position = session.position - 1
    return session.step(_hidden(swarm, 1, seed=2))


def _over_the_bound(session, swarm, monkeypatch):
    monkeypatch.setattr(inference_session_module, "THREAD_FRAME_BYTES", _hidden(swarm, 1).nbytes)  # no room for the rest of a message
    return session.step(_hidden(swarm, 1, seed=2))


def _chaos_armed(session, swarm, monkeypatch):
    chaos.configure(rules=[])
    try:
        return session.step(_hidden(swarm, 1, seed=2))
    finally:
        chaos.disable()


def _route_check_due(session, swarm, monkeypatch):
    session._session._last_route_check = time.monotonic() - 1e6
    return session.step(_hidden(swarm, 1, seed=2))


@pytest.mark.parametrize("condition", [_first_step, _prompts, _hypo_ids, _rollback, _over_the_bound, _chaos_armed, _route_check_due],
                         ids=lambda f: f.__name__.strip("_"))
def test_every_other_step_takes_the_coroutine_and_counts_no_direct(swarm, remote_of, monkeypatch, condition):
    remote = remote_of(2, route_upgrade_period=300.0)
    with remote.inference_session(max_length=16) as session:
        trip = session._session.trip
        if condition is not _first_step:
            session.step(_hidden(swarm, 3))
            session.step(_hidden(swarm, 1, seed=1))
            assert (trip.steps, trip.direct) == (2, 1)
        before = (trip.steps, trip.direct)
        out = condition(session, swarm, monkeypatch)
        assert out.shape == (1, 1, swarm.servers[0].cfg.hidden_size) and np.isfinite(out).all()
        assert (trip.steps, trip.direct) == (before[0] + 1, before[1])
        monkeypatch.undo()
        session.step(_hidden(swarm, 1, seed=3))  # and the step after it is direct again
        assert (trip.steps, trip.direct) == (before[0] + 2, before[1] + 1)
        assert [r["direct"] for r in _rows_of(session.trace_report()["trace_id"])][-2:] == [0, 1]


def test_a_caller_on_the_loop_takes_the_coroutine(swarm, remote_of):
    remote = remote_of(2)
    with remote.inference_session(max_length=16) as session:
        session.step(_hidden(swarm, 3))
        inner = session._session
        assert inner.can_step_from_thread(_hidden(swarm, 1))  # from here, yes

        async def on_the_loop():
            allowed = inner.can_step_from_thread(_hidden(swarm, 1))
            return allowed, await inner.step(_hidden(swarm, 1, seed=1))

        allowed, out = remote.runtime.run(on_the_loop())
        assert allowed is False and out.shape == (1, 1, swarm.servers[0].cfg.hidden_size)
        assert (inner.trip.steps, inner.trip.direct) == (2, 0)


# ------------------------------------------------- the stations of a direct step


def test_a_direct_step_s_stretches_are_numbers_tile_and_cross_nothing_before_the_build(swarm, remote_of, coroutine_only):
    """Four sessions step at once on the server's four lanes, each from a thread of its own, as a chat backend's do (and
    the benchmark's eight): the coroutine's two crossings then queue behind the other lanes' callbacks on the loop."""
    lanes, n, think, remote = 4, 12, 0.002, remote_of(2)

    def one_session(box, k):
        with remote.inference_session(max_length=32) as session:
            session.step(_hidden(swarm, 4, seed=k))
            for t in range(n):
                time.sleep(think)
                session.step(_hidden(swarm, 1, seed=10 * k + t + 1))
            report = session.trace_report()
        box[k] = (_rows_of(report["trace_id"]), report["client"])

    def drive():
        box = {}
        threads = [threading.Thread(target=one_session, args=(box, k), daemon=True) for k in range(lanes)]
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join(timeout=300)
        assert sorted(box) == list(range(lanes))
        return [box[k] for k in range(lanes)]

    direct = drive()
    with coroutine_only():
        coroutine = drive()
    for rows, client in direct:
        assert client["direct"] == n and len(rows) == n + 1
        # every stretch of a direct step a number (the last step's three have no request to close them, as ever), none negative
        assert all(isinstance(r[k], float) and r[k] >= 0.0 for r in rows[:-1] for k in CLIENT_STRETCHES)
        assert all(r[k] > 0.0 for r in rows[:-1] for k in ("recv_s", "finish_s", "user_s", "build_s"))
        assert [rows[-1][k] for k in ("user_s", "submit_s", "build_s")] == [None] * 3 and rows[-1]["wake_s"] >= 0.0
        for row, after in zip(rows, rows[1:]):  # reply to reply
            assert sum(row[k] for k in TURN) + after["away_s"] + after["relay_s"] == pytest.approx(after["read_at"] - row["read_at"], abs=1e-6)
        assert all(r["user_s"] >= think for r in rows[:-1])
        for k in CLIENT_STRETCHES:
            assert client[k] == pytest.approx(sum(r[k] for r in rows if r[k] is not None), abs=1e-5)
    assert all(client["direct"] == 0 for _, client in coroutine)
    # K1 follows K0 and K6 follows K5 on one thread: a clock reading apart, where the coroutine's are a thread crossing each.
    # wake_s is the reply's own; submit_s, in the row before, is of the turn that led to the next request (rows[0]'s led to a decode step)
    median = lambda runs, column, rows_of: float(np.median([r[column] for rows, _ in runs for r in rows_of(rows)]))
    wake, old_wake = (median(runs, "wake_s", lambda rows: rows[1:]) for runs in (direct, coroutine))
    submit, old_submit = (median(runs, "submit_s", lambda rows: rows[:-1]) for runs in (direct, coroutine))
    assert wake < old_wake / 10 and submit < old_submit / 10, (wake, old_wake, submit, old_submit)


# ------------------------------------------------- failure keeps its one path


def _swallow_next_step(sync_session, hop):
    """The next request of ``hop``'s stream never reaches the wire: whoever sent it stays parked. Gives the event set at the swallow."""
    stream, swallowed = sync_session._session._sessions[hop].stream, threading.Event()

    def on_frame(message):
        if not swallowed.is_set() and message.get("t") == "sitem" and message.get("id") == stream._call_id:
            swallowed.set()
            return False
        return True

    _tap(stream, on_frame)
    return swallowed


def _in_thread(fn):
    box = {}

    def target():
        try:
            box["out"] = fn()
        except BaseException as e:  # handed to the test's thread
            box["error"] = e

    thread = threading.Thread(target=target, daemon=True)
    thread.start()
    return thread, box


def _count_calls(monkeypatch, owner, name):
    calls, real = [], getattr(owner, name)

    def counted(*args, **kwargs):
        calls.append(args)
        return real(*args, **kwargs)

    monkeypatch.setattr(owner, name, counted)
    return calls


def test_a_connection_lost_under_a_parked_thread_is_repaired_at_that_hop_alone(swarm, remote_of, monkeypatch):
    """Two hops; the second hop's connection is lost while the caller's thread is parked on its reply. The thread
    raises, the rest of the step runs on the loop: one failure told, one repair at block 2 with that hop's
    inputs, and the first hop, which had answered, is not asked again. (No server pushes its outputs to the next here:
    a pushed copy of the step would be answered whether or not the caller's own request reached the wire.)"""
    remote = remote_of(4, use_server_to_server=False)
    with remote.inference_session(max_length=16) as reference:
        want = [reference.step(_hidden(swarm, 4))] + [reference.step(_hidden(swarm, 1, seed=t + 1)) for t in range(3)]
    with remote.inference_session(max_length=16) as session:
        inner = session._session
        got = [session.step(_hidden(swarm, 4)), session.step(_hidden(swarm, 1, seed=1))]
        first, second = inner._sessions
        assert second.span.peer_id == swarm.servers[1].dht.peer_id, "test setup: the preferred server holds the second hop"
        failures = _count_calls(monkeypatch, remote.sequence_manager, "on_request_failure")
        repairs = _count_calls(monkeypatch, inner, "_repair_chain")
        swallowed = _swallow_next_step(session, hop=1)
        thread, box = _in_thread(lambda: session.step(_hidden(swarm, 1, seed=2)))
        assert swallowed.wait(timeout=60) and thread.is_alive(), box  # parked: its request went nowhere
        client = second.stream._client
        client._loop.call_soon_threadsafe(client._writer.transport.abort)  # the connection lost
        thread.join(timeout=120)
        assert not thread.is_alive() and "error" not in box, box.get("error")
        got.append(box["out"])
        assert failures == [(second.span.peer_id,)] and repairs == [(2,)]
        assert inner._sessions[0] is first and first.hop.steps == 3 and len(first.history) == 3 and first.position == 6
        replacement = inner._sessions[1]
        assert replacement is not second and replacement.span.peer_id == swarm.servers[2].dht.peer_id and replacement.position == 6
        assert (inner.trip.steps, inner.trip.direct) == (3, 1)  # the repaired step was finished by the coroutine
        got.append(session.step(_hidden(swarm, 1, seed=3)))  # and the session goes on, directly
        assert (inner.trip.steps, inner.trip.direct) == (4, 2) and session.position == 7
        report = session.trace_report()
        assert report["retired_hops"] == 1 and report["steps"] == 4
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=0)


def test_step_timeout_expires_a_parked_thread(swarm, remote_of):
    remote = remote_of(2, max_retries=0)
    with remote.inference_session(max_length=16) as session:
        session.step(_hidden(swarm, 3))
        session.step(_hidden(swarm, 1, seed=1))
        session._session._sessions[0].step_timeout = 0.3
        swallowed, t0 = _swallow_next_step(session, hop=0), time.monotonic()
        with pytest.raises(asyncio.TimeoutError):
            session.step(_hidden(swarm, 1, seed=2))
        assert swallowed.is_set() and 0.3 <= time.monotonic() - t0 < 30
        assert session._session.trip.direct == 1 and session.position == 4


def test_close_from_another_thread_wakes_a_parked_thread(swarm, remote_of, monkeypatch):
    remote = remote_of(2)
    session = remote.inference_session(max_length=16)
    session.step(_hidden(swarm, 3))
    session.step(_hidden(swarm, 1, seed=1))
    failures = _count_calls(monkeypatch, remote.sequence_manager, "on_request_failure")
    swallowed = _swallow_next_step(session, hop=0)
    thread, box = _in_thread(lambda: session.step(_hidden(swarm, 1, seed=2)))
    assert swallowed.wait(timeout=60) and thread.is_alive()
    t0 = time.monotonic()
    session.close()
    thread.join(timeout=60)
    assert not thread.is_alive() and time.monotonic() - t0 < 30  # at once, not at step_timeout's five minutes
    assert isinstance(box.get("error"), RpcError) and "closed" in str(box["error"])
    assert failures == []  # no peer's fault, nothing to repair


# ------------------------------------------------- the stream alone


class _Echo:
    """``tests/test_rpc.py``'s kind of server: a stream that answers each item with its number and what it held."""

    def __init__(self):
        self.server, self.seen = RpcServer(), {}

        async def echo(requests, ctx):
            async for item in requests:
                if item.get("explode"):
                    raise RuntimeError("stream exploded")
                if item.get("silent"):
                    continue
                self.seen.setdefault(item["stream"], []).append(item["n"])
                blob = item["blob"]
                yield {"stream": item["stream"], "n": item["n"], "len": len(blob), "sum": sum(blob[:: max(len(blob) // 257, 1)])}

        self.server.add_stream_handler("echo", echo)


@pytest.fixture
def wired():
    """(the runtime whose loop owns the connection, the client, the server's record)."""
    runtime, echo = SwarmRuntime(), _Echo()

    async def connect():
        await echo.server.start()
        return await RpcClient.connect("127.0.0.1", echo.server.port)

    client = runtime.run(connect(), timeout=60)
    yield runtime, client, echo

    async def teardown():
        await client.close()
        await echo.server.stop()

    runtime.run(teardown(), timeout=60)
    runtime.shutdown()


def _item(stream, n, size=16 << 10):
    return {"stream": stream, "n": n, "blob": bytes([(stream * 31 + n) % 251]) * size}


def _checks(reply, stream, n, size=16 << 10):
    blob = _item(stream, n, size)["blob"]
    return reply == {"stream": stream, "n": n, "len": size, "sum": sum(blob[:: max(size // 257, 1)])}


def test_a_stream_changes_hands_between_a_coroutine_and_a_thread(wired):
    runtime, client, echo = wired
    stream = runtime.run(client.open_stream("echo"))

    async def exchange(n):
        await stream.send(_item(0, n))
        return await stream.recv(timeout=30)

    assert _checks(runtime.run(exchange(0)), 0, 0)
    for n in (1, 2):
        sent = time.perf_counter()
        stream.send_from_thread(_item(0, n))
        assert _checks(stream.recv_in_thread(30), 0, n) and sent <= stream.read_at <= time.perf_counter()
    assert _checks(runtime.run(exchange(3)), 0, 3)
    stream.send_from_thread(_item(0, 4))
    assert _checks(stream.recv_in_thread(30), 0, 4) and echo.seen[0] == [0, 1, 2, 3, 4]
    with pytest.raises(ValueError, match="takes send"):
        stream.send_from_thread(_item(0, 5, size=THREAD_FRAME_BYTES))  # over the bound with its framing: the coroutine's to send
    runtime.run(stream.cancel())
    with pytest.raises(RpcError, match="closed"):
        stream.send_from_thread(_item(0, 5))


def test_what_wakes_a_parked_coroutine_wakes_a_parked_thread(wired):
    runtime, client, echo = wired
    # nothing comes: the timeout, and the stream is still good after it
    stream = runtime.run(client.open_stream("echo"))
    stream.send_from_thread({"silent": True})
    t0 = time.monotonic()
    with pytest.raises(asyncio.TimeoutError):
        stream.recv_in_thread(0.2)
    assert 0.2 <= time.monotonic() - t0 < 10
    stream.send_from_thread(_item(1, 0))
    assert _checks(stream.recv_in_thread(30), 1, 0)
    # the handler raises: the server's ``resp`` abort
    stream.send_from_thread({"explode": True})
    with pytest.raises(RpcError, match="stream exploded"):
        stream.recv_in_thread(30)
    # the stream ends
    ended = runtime.run(client.open_stream("echo"))
    runtime.run(ended.end())
    with pytest.raises(StopAsyncIteration):
        ended.recv_in_thread(30)
    # cancel() under a parked thread, and the connection lost under another
    for wake, error in ((lambda s: runtime.run(s.cancel()), "closed"), (lambda s: runtime.run(echo.server.stop()), "Connection")):
        parked = runtime.run(client.open_stream("echo"))
        parked.send_from_thread({"silent": True})
        thread, box = _in_thread(lambda: parked.recv_in_thread(60))
        time.sleep(0.1)
        assert thread.is_alive()
        wake(parked)
        thread.join(timeout=30)
        assert not thread.is_alive() and isinstance(box.get("error"), RpcError) and error in str(box["error"]), box
    with pytest.raises(RpcError, match="closed"):
        parked.send_from_thread(_item(1, 1))  # a connection that is gone takes no frame


def test_eight_threads_frames_stay_whole_beside_a_coroutine_s_32_mb_frame(wired):
    """Eight threads step eight streams of one connection while a coroutine writes a prompt's frame of 32 MB on a
    ninth and waits for its drain: a decode frame handed over meanwhile is appended whole behind it, so every frame
    unpacks at the server, in each stream's order, with what it was sent holding."""
    runtime, client, echo = wired
    lanes, rounds, big = 8, 40, 32 << 20
    streams = [runtime.run(client.open_stream("echo")) for _ in range(lanes + 1)]
    draining, sends, span = threading.Event(), [[] for _ in range(lanes)], {}

    async def prompt():
        draining.set()
        span["t0"] = time.perf_counter()
        await streams[lanes].send(_item(lanes, 0, size=big))  # write_frame: its lock, its drain
        span["t1"] = time.perf_counter()
        return await streams[lanes].recv(timeout=120)

    def lane(k):
        stream = streams[k]
        for n in range(rounds):
            if n == rounds // 2:
                assert draining.wait(timeout=60)
            stream.send_from_thread(_item(k, n))
            sends[k].append(time.perf_counter())
            assert _checks(stream.recv_in_thread(120), k, n), (k, n)
        return True

    threads = [_in_thread(lambda k=k: lane(k)) for k in range(lanes)]
    time.sleep(0.05)
    reply = runtime.run(prompt(), timeout=300)
    for thread, box in threads:
        thread.join(timeout=300)
        assert not thread.is_alive() and box.get("out") is True, box
    assert _checks(reply, lanes, 0, size=big)
    assert all(echo.seen[k] == list(range(rounds)) for k in range(lanes)) and echo.seen[lanes] == [0]
    during = sum(span["t0"] < t < span["t1"] for lane_sends in sends for t in lane_sends)
    assert during >= 1, (span, "no decode frame was handed over while the prompt's drain was pending")


# ------------------------------------------------- who writes a thread's frame (PR 58)


def test_a_frame_goes_to_the_loop_while_the_transport_holds_bytes(wired):
    """A coroutine's 32 MB frame is draining: a thread's frame handed over meanwhile is not written beside it (``False``) but
    appended behind it by the loop; with the buffer empty again the thread writes its own (``True``)."""
    runtime, client, echo = wired
    big, transport = 32 << 20, client._writer.transport
    prompt_stream, stream = runtime.run(client.open_stream("echo")), runtime.run(client.open_stream("echo"))
    assert stream.send_from_thread(_item(0, 0)) is True and _checks(stream.recv_in_thread(30), 0, 0)

    async def prompt():
        await prompt_stream.send(_item(1, 0, size=big))
        return await prompt_stream.recv(timeout=120)

    future = asyncio.run_coroutine_threadsafe(prompt(), runtime.loop)
    while transport.get_write_buffer_size() == 0 and not future.done():
        time.sleep(0.0002)
    assert not future.done(), "test setup: the prompt's frame was gone before a thread could meet it"
    under_the_drain = stream.send_from_thread(_item(0, 1))
    assert _checks(stream.recv_in_thread(120), 0, 1)  # behind the 32 MB, whole
    assert under_the_drain is False
    assert _checks(future.result(300), 1, 0, size=big) and echo.seen[0] == [0, 1] and echo.seen[1] == [0]
    assert stream.send_from_thread(_item(0, 2)) is True and _checks(stream.recv_in_thread(30), 0, 2)  # and direct again after it


class _CutSocket:
    """The outlet's socket, whose first ``send`` takes ``keep`` bytes only."""

    def __init__(self, real, keep):
        self.real, self.keep, self.calls = real, keep, []

    def send(self, data):
        sent = self.real.send(data[: self.keep] if not self.calls else data)
        self.calls.append((len(data), sent))
        return sent

    def close(self):
        self.real.close()


@pytest.mark.parametrize("how", ["cut-by-hand", "small-sndbuf"])
def test_a_partial_send_s_remainder_goes_out_in_front_of_every_later_frame(wired, how):
    """Frames handed over back to back from a thread, none waited for: whatever a ``send`` leaves is the loop's to write, and
    every later frame (the same thread's, which finds the queue not empty) goes behind it: whole frames, in order."""
    runtime, client, echo = wired
    outlet, n, size = client._outlet, 24, 96 << 10
    stream = runtime.run(client.open_stream("echo"))
    first = 0
    if how == "cut-by-hand":
        outlet._sock = cut = _CutSocket(outlet._sock, keep=1000)
        held, release = threading.Event(), threading.Event()
        runtime.loop.call_soon_threadsafe(lambda: (held.set(), release.wait(60)))  # the loop cannot write what it is left
        assert held.wait(60)
        try:
            wrote = [stream.send_from_thread(_item(3, k, size=size)) for k in range(2)]
        finally:
            release.set()
        assert [sent for _, sent in cut.calls] == [1000] and wrote[0] is False  # one send, cut short: the remainder is the loop's
        assert wrote[1] is False  # and the frame right behind it waits its turn, whatever the socket could take
        first = 2
    else:
        client._writer.get_extra_info("socket").setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 4096)
    for k in range(first, n):
        stream.send_from_thread(_item(3, k, size=size))
    replies = [stream.recv_in_thread(120) for _ in range(n)]
    assert all(_checks(reply, 3, k, size=size) for k, reply in enumerate(replies)) and echo.seen[3] == list(range(n))
    assert stream.send_from_thread(_item(3, n, size=size)) in (True, False) and _checks(stream.recv_in_thread(120), 3, n, size=size)


def test_a_transport_without_a_plain_socket_defers_every_frame(wired):
    from petals_tpu.rpc.client import _Outlet

    class NoSocket:
        def __init__(self, **info):
            self.info = info

        def get_extra_info(self, name, default=None):
            return self.info.get(name, default)

        def get_write_buffer_size(self):
            return 0

    class Datagram:
        type = 2  # socket.SOCK_DGRAM

    assert _Outlet._plain_socket(NoSocket()) is None  # an in-memory pair
    assert _Outlet._plain_socket(NoSocket(socket=object(), sslcontext=object())) is None  # TLS over it
    assert _Outlet._plain_socket(NoSocket(socket=Datagram())) is None
    runtime, client, echo = wired
    assert client._outlet._sock is not None  # loopback TCP: a plain socket
    client._outlet.close()  # the same connection with none to write beside the transport
    stream = runtime.run(client.open_stream("echo"))
    for n in range(4):
        assert stream.send_from_thread(_item(5, n)) is False
        assert _checks(stream.recv_in_thread(30), 5, n)
    assert echo.seen[5] == [0, 1, 2, 3]


def test_the_frame_a_thread_writes_is_byte_for_byte_the_loop_s(wired):
    runtime, client, echo = wired
    outlet, stream = client._outlet, runtime.run(client.open_stream("echo"))
    by_thread, by_loop = [], []
    real_sock, real_write = outlet._sock, client._writer.write

    class Recorded:
        def send(self, data):
            by_thread.append(bytes(data))
            return real_sock.send(data)

        def close(self):
            real_sock.close()

    client._writer.write = lambda data: (by_loop.append(bytes(data)), real_write(data))[1]
    try:
        outlet._sock = Recorded()
        assert stream.send_from_thread(_item(6, 0)) is True and _checks(stream.recv_in_thread(30), 6, 0)
        outlet._sock = None
        assert stream.send_from_thread(_item(6, 0)) is False and _checks(stream.recv_in_thread(30), 6, 0)
    finally:
        client._writer.__dict__.pop("write", None)
        outlet._sock = real_sock
    assert len(by_thread) == len(by_loop) == 1 and by_thread[0] == by_loop[0]
    assert decode_frame(by_thread[0][4:]) == {"t": "sitem", "id": stream._call_id, "payload": _item(6, 0)}


def test_a_deferred_frame_is_counted_deferred_and_a_written_one_wrote(swarm, remote_of, monkeypatch):
    from petals_tpu.rpc.client import _Outlet

    remote = remote_of(4)
    with remote.inference_session(max_length=16) as session:
        inner = session._session
        session.step(_hidden(swarm, 3))
        session.step(_hidden(swarm, 1, seed=1))
        assert (inner.trip.direct, inner.trip.wrote, inner.trip.deferred) == (1, 2, 0)  # two hops, two frames, both this thread's
        with monkeypatch.context() as patch:
            patch.setattr(_Outlet, "_transport_idle", lambda self: False)  # as if every transport held bytes
            session.step(_hidden(swarm, 1, seed=2))
        assert (inner.trip.direct, inner.trip.wrote, inner.trip.deferred) == (2, 2, 2)
        session.step(_hidden(swarm, 1, seed=3))
        trace, usage = session.trace_report(), inner.usage_report()
        report = trace["client"]
        assert (report["direct"], report["wrote"], report["deferred"]) == (3, 4, 2)
        assert (usage["direct_steps"], usage["direct_frames_wrote"], usage["direct_frames_deferred"]) == (3, 4, 2)
        assert [(r["direct"], r["wrote"]) for r in _rows_of(trace["trace_id"])] == [(0, 0), (1, 2), (1, 0), (1, 2)]


def test_a_send_that_raises_fails_the_hop_once_and_bans_as_the_coroutine_s_failure_does(swarm, remote_of, monkeypatch):
    """The second hop's socket refuses the caller's thread's ``send``: ``RpcError`` out of ``send_from_thread``, the rest
    of the step on the loop, one failure told of that peer, one repair at that hop; the first hop is not asked again."""
    remote = remote_of(4, use_server_to_server=False)
    with remote.inference_session(max_length=16) as reference:
        want = [reference.step(_hidden(swarm, 4))] + [reference.step(_hidden(swarm, 1, seed=t + 1)) for t in range(3)]
    with remote.inference_session(max_length=16) as session:
        inner = session._session
        got = [session.step(_hidden(swarm, 4)), session.step(_hidden(swarm, 1, seed=1))]
        first, second = inner._sessions
        failures = _count_calls(monkeypatch, remote.sequence_manager, "on_request_failure")
        hop_failures = _count_calls(monkeypatch, inner, "_hop_failed")
        repairs = _count_calls(monkeypatch, inner, "_repair_chain")
        outlet = second.stream._client._outlet
        real_sock = outlet._sock

        class Broken:
            def send(self, data):
                raise BrokenPipeError(32, "Broken pipe")

            def close(self):
                real_sock.close()

        outlet._sock = Broken()
        try:
            got.append(session.step(_hidden(swarm, 1, seed=2)))
        finally:
            if outlet._sock is not None:
                outlet._sock = real_sock
        assert len(hop_failures) == 1 and isinstance(hop_failures[0][1], RpcError) and "BrokenPipeError" in str(hop_failures[0][1])
        assert failures == [(second.span.peer_id,)] and repairs == [(2,)]
        assert inner._sessions[0] is first and first.hop.steps == 3 and inner._sessions[1] is not second
        assert (inner.trip.steps, inner.trip.direct) == (3, 1)  # the failed step was finished by the coroutine
        got.append(session.step(_hidden(swarm, 1, seed=3)))
        assert (inner.trip.steps, inner.trip.direct) == (4, 2)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=0)
