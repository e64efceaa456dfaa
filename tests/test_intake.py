"""The server's intake of a stream's items (PR 60): a stream's handler may register a
sink (``rpc/server.py StreamRequests.sink``), and the connection's reader then hands
it an ``sitem`` in the turn that read the frame, with no queue hop and no task woken,
as long as the stream's consumer is parked on an empty queue. The inference handler's
sink takes plain decode steps (``server/handler.py take_decode_step`` ->
``DecodeBatcher.begin_step``); everything else keeps the queue.

Nothing here reads a clock. A connection is a ``_Wire``: the server's own
``_on_connection`` on a ``StreamReader`` the test feeds by hand, so that "frames that
came in one recv" is one ``feed`` exactly, and a list of the frames the server wrote.
"One pass" is read off the loop's own order: every hand-over leaves a ``call_soon``
behind, and a pass that gave the loop a turn between two hand-overs would run the
first one's before the second hand-over."""

import asyncio
import struct

import jax.numpy as jnp
import numpy as np
import pytest

from petals_tpu.data_structures import CHAIN_DELIMITER, make_uid
from petals_tpu.rpc import RpcServer
from petals_tpu.rpc import server as rpc_server
from petals_tpu.rpc.protocol import decode_frame, encode_frame
from petals_tpu.rpc.serialization import deserialize_array, serialize_array
from petals_tpu.server.handler import _is_plain_decode_step
from petals_tpu.server.server import Server, default_dht_prefix
from tests.test_gather import _rig
from tests.test_mixed_batching import _hidden, _tiny_backend
from tests.utils import make_tiny_llama

pytestmark = pytest.mark.mixed

N_LAYERS = 4  # make_tiny_llama's
OVERFLOW = {"t": "resp", "ok": False, "error": "RpcError: inbound queue overflow, call cancelled"}


def run(coro):
    return asyncio.run(asyncio.wait_for(coro, 300))


class _Wire:
    """One connection to ``server`` without a socket: its reader's bytes are fed by
    hand, and what it writes is taken apart into ``frames`` (the hello is dropped)."""

    def __init__(self, server: RpcServer):
        self.reader, self.frames, self._buffer = asyncio.StreamReader(), [], b""
        self.closed = False
        self.task = asyncio.create_task(server._on_connection(self.reader, self))

    # the writer's side, as rpc/protocol.py write_frame and _on_connection use it
    def write(self, data: bytes) -> None:
        self._buffer += data
        while len(self._buffer) >= 4 and len(self._buffer) >= 4 + struct.unpack(">I", self._buffer[:4])[0]:
            size = 4 + struct.unpack(">I", self._buffer[:4])[0]
            frame, self._buffer = decode_frame(self._buffer[4:size]), self._buffer[size:]
            if frame["t"] != "hello":
                self.frames.append(frame)

    async def drain(self) -> None:
        pass

    def close(self) -> None:
        self.closed = True

    def get_extra_info(self, _name):
        return ("wire", 0)

    # the test's side
    def feed(self, *messages) -> None:
        """All of ``messages`` in one ``recv``."""
        self.reader.feed_data(b"".join(encode_frame(m) for m in messages))

    def of(self, call_id: int) -> list:
        return [frame for frame in self.frames if frame["id"] == call_id]

    async def until(self, done) -> None:
        while not done():
            assert not self.task.done(), "the connection's loop ended"
            await asyncio.sleep(0.001)

    async def hang_up(self) -> None:
        self.reader.feed_eof()
        await self.task
        assert self.closed


def sitem(call_id, payload):
    return {"t": "sitem", "id": call_id, "payload": payload}


# ----------------------------------------------------------- the RPC server alone


class _Echo:
    """A stream handler that yields what it is given, by either way, and keeps the
    order of everything: ("sink", item) a hand-over, ("turn", item) the loop's turn
    after it, ("queue", item) an item its iterator gave it. ``takes(item)`` is the
    sink's answer; a sink that takes resolves the future the handler parks on."""

    def __init__(self, takes=lambda item: True, register=True):
        self.takes, self.register, self.order, self.busy = takes, register, [], None

    async def __call__(self, requests, ctx):
        loop = asyncio.get_running_loop()
        taken = asyncio.Queue()

        def sink(item, read_at):
            assert isinstance(read_at, float)
            if not self.takes(item):  # may raise
                return False
            self.order.append(("sink", item))
            loop.call_soon(self.order.append, ("turn", item))
            taken.put_nowait(item)
            return True

        if self.register:
            requests.sink = sink
        queued, direct = asyncio.ensure_future(anext(requests, None)), asyncio.ensure_future(taken.get())
        try:
            while True:
                await asyncio.wait({queued, direct}, return_when=asyncio.FIRST_COMPLETED)
                if direct.done():
                    item, direct = direct.result(), asyncio.ensure_future(taken.get())
                else:
                    item = queued.result()
                    if item is None:
                        return
                    self.order.append(("queue", item))
                    if self.busy is not None:
                        await self.busy.wait()  # not parked: no __anext__ is waiting
                    queued = asyncio.ensure_future(anext(requests, None))
                yield item
        finally:
            queued.cancel(), direct.cancel()


async def _echo_server(opened=True, **kwargs):
    server, echo = RpcServer(), _Echo(**kwargs)
    server.add_stream_handler("echo", echo)

    async def ping(payload, ctx):
        return payload

    server.add_unary_handler("ping", ping)
    wire = _Wire(server)
    if opened:
        wire.feed({"t": "sopen", "id": 1, "method": "echo"})
        await asyncio.sleep(0.01)  # the handler has run up to its first wait: its consumer is parked
    return wire, echo


async def _still_serves(wire, call_id=99) -> None:
    wire.feed({"t": "req", "id": call_id, "method": "ping", "payload": "alive"})
    await wire.until(lambda: wire.of(call_id))
    assert wire.of(call_id) == [{"t": "resp", "id": call_id, "ok": True, "payload": "alive"}]


def test_eight_frames_of_one_recv_reach_the_sink_in_order_in_one_pass():
    async def main():
        wire, echo = await _echo_server()
        wire.feed(*(sitem(1, i) for i in range(8)))
        await wire.until(lambda: len(wire.of(1)) == 8)
        assert echo.order == [("sink", i) for i in range(8)] + [("turn", i) for i in range(8)]
        assert [frame["payload"] for frame in wire.of(1)] == list(range(8))
        await wire.hang_up()

    run(main())


def test_a_frame_behind_a_queued_item_is_queued_too():
    """The sink leaves item 0 to the queue and would take every other: 1 and 2 came
    behind 0 in one recv and follow it through the queue; 3 comes once the consumer
    is parked on an empty queue again, and is handed over."""

    async def main():
        wire, echo = await _echo_server(takes=lambda item: item != 0)
        wire.feed(sitem(1, 0), sitem(1, 1), sitem(1, 2))
        await wire.until(lambda: len(wire.of(1)) == 3)
        assert echo.order == [("queue", 0), ("queue", 1), ("queue", 2)]
        wire.feed(sitem(1, 3))
        await wire.until(lambda: len(wire.of(1)) == 4)
        assert echo.order[3:] == [("sink", 3), ("turn", 3)]
        assert [frame["payload"] for frame in wire.of(1)] == [0, 1, 2, 3]
        await wire.hang_up()

    run(main())


@pytest.mark.parametrize("case", ["send", "cancel", "not_parked", "no_sink", "with_its_sopen"])
def test_what_keeps_the_queue(case):
    async def main():
        if case == "with_its_sopen":
            # the item comes in the recv that opened the stream: its handler has not run yet
            wire, echo = await _echo_server(opened=False)
            wire.feed({"t": "sopen", "id": 1, "method": "echo"}, sitem(1, "first"))
            await wire.until(lambda: wire.of(1))
            assert echo.order == [("queue", "first")]
        elif case == "no_sink":
            wire, echo = await _echo_server(register=False)
            wire.feed(sitem(1, "a"), sitem(1, "b"))
            await wire.until(lambda: len(wire.of(1)) == 2)
            assert echo.order == [("queue", "a"), ("queue", "b")]
        elif case == "not_parked":
            # the handler holds an item and awaits something else: nobody waits in __anext__
            wire, echo = await _echo_server(takes=lambda item: item != "held")
            echo.busy = asyncio.Event()
            wire.feed(sitem(1, "held"))
            await wire.until(lambda: echo.order == [("queue", "held")])
            wire.feed(sitem(1, "meanwhile"))
            await asyncio.sleep(0.01)
            assert echo.order == [("queue", "held")] and not wire.of(1)
            echo.busy.set()
            await wire.until(lambda: len(wire.of(1)) == 2)
            assert echo.order == [("queue", "held"), ("queue", "meanwhile")]
        elif case == "send":
            # the half-close never meets the sink, and ends the stream behind what was handed over
            wire, echo = await _echo_server()
            wire.feed(sitem(1, "last"), {"t": "send", "id": 1})
            await wire.until(lambda: len(wire.of(1)) == 2)
            assert echo.order == [("sink", "last"), ("turn", "last")]
            assert wire.of(1) == [sitem(1, "last"), {"t": "send", "id": 1}]
        else:
            wire, echo = await _echo_server()
            wire.feed({"t": "cancel", "id": 1}, sitem(1, "late"))
            await asyncio.sleep(0.01)
            wire.feed(sitem(1, "later"))  # the call is gone: nobody to hand it to, nothing answered
            await asyncio.sleep(0.01)
            assert [kind for kind, _ in echo.order if kind == "queue"] == [] and not wire.of(1)
        await _still_serves(wire)
        await wire.hang_up()

    run(main())


@pytest.mark.parametrize("case", ["declines", "raises"])
def test_a_sink_that_declines_or_raises_costs_that_call_alone(case):
    async def main():
        def takes(item):
            if item == "bad" and case == "raises":
                raise ValueError("no such step")
            return item != "bad"

        wire, echo = await _echo_server(takes=takes)
        wire.feed({"t": "sopen", "id": 2, "method": "echo"})
        await asyncio.sleep(0.01)
        wire.feed(sitem(2, "before"), sitem(1, "bad"), sitem(2, "after"))
        await wire.until(lambda: len(wire.of(2)) == 2 and wire.of(1))
        assert [frame["payload"] for frame in wire.of(2)] == ["before", "after"]
        if case == "declines":
            assert wire.of(1) == [sitem(1, "bad")] and ("queue", "bad") in echo.order
            wire.feed(sitem(1, "good"))
            await wire.until(lambda: len(wire.of(1)) == 2)
        else:  # what a raise in the handler itself is answered with
            assert wire.of(1) == [{"t": "resp", "id": 1, "ok": False, "error": "ValueError: no such step"}]
            wire.feed(sitem(1, "good"))  # for a call that is no more
        wire.feed(sitem(2, "again"))
        await wire.until(lambda: len(wire.of(2)) == 3)
        assert len(wire.of(1)) == (2 if case == "declines" else 1)
        await _still_serves(wire)
        await wire.hang_up()

    run(main())


@pytest.mark.parametrize("sink", ["none", "declining"])
def test_the_overflow_answer_is_what_it_was(sink):
    """A handler that never takes an item: the frame past the bound cancels the call and
    is answered at once, and the connection goes on."""

    async def main():
        server = RpcServer()
        started = asyncio.Event()

        async def stuck(requests, ctx):
            if sink == "declining":
                requests.sink = lambda item, read_at: False
            started.set()
            await asyncio.Event().wait()
            yield

        async def ping(payload, ctx):
            return payload

        server.add_stream_handler("stuck", stuck)
        server.add_unary_handler("ping", ping)
        wire = _Wire(server)
        wire.feed({"t": "sopen", "id": 1, "method": "stuck"})
        await started.wait()
        wire.feed(*(sitem(1, i) for i in range(rpc_server.MAX_INBOUND_QUEUE)))
        await asyncio.sleep(0.01)
        assert not wire.frames
        wire.feed(sitem(1, "one too many"), sitem(1, "and another"))
        await wire.until(lambda: wire.of(1))
        assert wire.of(1) == [{**OVERFLOW, "id": 1}]
        await _still_serves(wire)
        await wire.hang_up()

    run(main())


# ------------------------------------------------------------ the batcher's begin_step


@pytest.fixture(scope="module")
def path(tmp_path_factory):
    return make_tiny_llama(str(tmp_path_factory.mktemp("models")))


@pytest.fixture(scope="module")
def tiny(path):
    return _tiny_backend(path)


INTAKE = ("rpc_intake_direct", "rpc_intake_queued")


def test_begin_step_is_step_without_the_wait(tiny):
    """The same token by both ways gives the same row, each way counts itself, and
    the lane is held from ``begin_step`` to ``end_step`` as it is inside ``step``."""

    async def main():
        async with _rig(tiny, 2, 0.0) as rig:
            batcher, loop = rig.batcher, asyncio.get_running_loop()
            a, b = await batcher.acquire_lane(), await batcher.acquire_lane()
            for lane in (a, b):
                await batcher.prefill_lane(lane, _hidden(rig.cfg, 1, 5), 0)
            was = {key: batcher.stats[key] for key in INTAKE}
            fut = loop.create_future()
            assert batcher.begin_step(a, _hidden(rig.cfg, 7), 5, fut, arrived=(None, 0.0)) is True
            assert batcher._inflight[a] == 1 and not batcher._lane_idle(a)
            queued = await batcher.step(b, _hidden(rig.cfg, 7), 5)
            direct = await fut
            batcher.end_step(a)
            assert batcher._inflight[a] == 0
            np.testing.assert_array_equal(np.asarray(direct), np.asarray(queued))
            assert [batcher.stats[key] - was[key] for key in INTAKE] == [1, 1]

    run(main())


@pytest.mark.parametrize("why", ["no_free_page", "shared_page", "at_max_length", "pool_reset", "swapped_out"])
def test_begin_step_declines_where_step_would_wait_or_raise(tiny, why):
    """False, and nothing of the lane, the pool or the counters changed."""

    async def main():
        async with _rig(tiny, 2, 0.0) as rig:
            batcher, loop = rig.batcher, asyncio.get_running_loop()
            lane = await batcher.acquire_lane()
            await batcher.prefill_lane(lane, _hidden(rig.cfg, 1, 16), 0)  # one page of 16, full
            position = 16  # the next token needs a second page
            if why == "no_free_page":
                held = [batcher._pages.try_alloc() for _ in range(batcher.n_pages)]
                assert None in held
            elif why == "shared_page":
                position = 15
                (page,) = batcher.pin_lane_pages(lane, 0, 16)  # as the prefix cache holds it
            elif why == "at_max_length":
                position = batcher.max_length
            elif why == "pool_reset":
                batcher._generation += 1
            else:
                slot = batcher._scheduler.lanes[lane]
                slot.suspending = True
            was = (dict(batcher.stats), batcher._tables.copy(), list(batcher._pending), dict(batcher._inflight))
            fut = loop.create_future()
            assert batcher.begin_step(lane, _hidden(rig.cfg, 3), position, fut, arrived=(None, 0.0)) is False
            now = (dict(batcher.stats), batcher._tables.copy(), list(batcher._pending), dict(batcher._inflight))
            assert was[0] == now[0] and np.array_equal(was[1], now[1]) and was[2:] == now[2:] and not fut.done()
            if why == "pool_reset":
                batcher._generation -= 1
            elif why == "swapped_out":
                slot.suspending = False

    run(main())


def _wire_hidden(shape):
    return serialize_array(np.zeros(shape, np.float32))


@pytest.mark.parametrize("item, plain", [
    ({"tensors": {"hidden": _wire_hidden((1, 1, 8))}}, True),
    ({"tensors": {"hidden": _wire_hidden((1, 1, 8))}, "step_id": "ab"}, True),
    ({"tensors": {"hidden": _wire_hidden((1, 5, 8))}}, False),  # a prompt's chunk
    ({"tensors": {"hidden": _wire_hidden((1, 0, 8))}}, False),  # a probe of the cache
    ({"tensors": {"hidden": _wire_hidden((2, 1, 8))}}, False),  # another batch than the session's
    ({"tensors": {"hidden": _wire_hidden((1, 1, 8)), "hypo_ids": serialize_array(np.zeros(1, np.int64))}}, False),
    ({"tensors": {"hidden": _wire_hidden((1, 1, 8)), "prompts": _wire_hidden((4, 1, 2, 8))}}, False),
    ({"tensors": {"hidden": _wire_hidden((1, 1, 8))}, "start_from_position": 3}, False),
    ({"tensors": {"hidden": _wire_hidden((1, 1, 8))}, "push_to": None}, False),
    ({"tensors": {"hidden": _wire_hidden((1, 1, 8))}, "gen_tokens": 4}, False),
    ({"tensors": {"hidden": _wire_hidden((1, 1, 8))}, "kv_adopt": {"session_id": "s", "position": 0}}, False),
    ({"kv_import": {"position": 4}, "tensors": {"k": _wire_hidden((1, 1, 8)), "v": _wire_hidden((1, 1, 8))}}, False),
    ({"tensors": {"hidden": {"shape": "1,1,8"}}}, False),
    ({"tensors": {}}, False),
    ({"tensors": None}, False),
    ({}, False),
    (None, False),
    ([1, 2], False),
], ids=["plain", "with_step_id", "prompt", "probe", "other_batch", "hypo_ids", "prompts", "rollback", "push_to",
        "gen_tokens", "kv_adopt", "kv_import", "malformed_shape", "no_hidden", "no_tensors", "empty", "none", "a_list"])
def test_what_the_sink_calls_a_plain_decode_step(item, plain):
    assert _is_plain_decode_step(item, 1) is plain


# ------------------------------------------------- through the server, handler and batcher


async def _serve(path, lanes):
    server = Server(path, compute_dtype=jnp.float32, use_flash=False, batch_lanes=lanes, batch_max_length=64, page_size=16)
    await server.start()
    return server, _Wire(server.rpc_server)


async def _open(wire, path, ids) -> None:
    uids = CHAIN_DELIMITER.join(make_uid(default_dht_prefix(path), i) for i in range(N_LAYERS))
    for call_id in ids:  # one after the other: lanes are handed out in this order
        wire.feed({"t": "sopen", "id": call_id, "method": "ptu.inference"},
                  sitem(call_id, {"uids": uids, "max_length": 64, "batch_size": 1}))
        await wire.until(lambda: wire.of(call_id))
        assert wire.of(call_id)[0]["payload"]["session_open"] is True


def _step_of(call_id, rows, **more):
    return sitem(call_id, {"tensors": {"hidden": serialize_array(rows)}, **more})


def _replies(wire, call_id) -> list:
    return [deserialize_array(frame["payload"]["tensors"]["hidden"]) for frame in wire.of(call_id)[1:]]


def _tokens(hidden, seed, n=40):
    return np.random.RandomState(seed).randn(1, n, hidden).astype(np.float32) * 0.5


def test_a_burst_of_eight_decode_requests_enters_the_batcher_in_one_pass(path):
    """Eight sessions on one connection, parked after their prompts; their eight decode
    requests come in one recv, twice, the second time in the opposite order. Each burst
    reaches ``begin_step`` in the frames' order before the loop has another turn, counts
    eight ``rpc_intake_direct`` and no ``rpc_intake_queued``, comes back with every lane's
    reply, and leaves every lane free of the step again."""

    async def main():
        server, wire = await _serve(path, 8)
        try:
            batcher, loop = server.handler.batcher, asyncio.get_running_loop()
            ids, order = list(range(1, 9)), []
            data = {i: _tokens(batcher.backend.hidden_size, i) for i in ids}
            await _open(wire, path, ids)
            begin = batcher.begin_step

            def recorded(lane, *args, **kwargs):
                took = begin(lane, *args, **kwargs)
                order.append(("begin", lane, asyncio.current_task()))
                loop.call_soon(order.append, ("turn", lane, None))
                return took

            batcher.begin_step = recorded
            wire.feed(*(_step_of(i, data[i][:, :5]) for i in ids))  # the prompts, a chunk each: not the sink's
            await wire.until(lambda: all(len(wire.of(i)) == 2 for i in ids))
            assert not order and batcher.stats["rpc_intake_direct"] == batcher.stats["rpc_intake_queued"] == 0
            wire.feed(*(_step_of(i, data[i][:, 5:6]) for i in ids))
            await wire.until(lambda: all(len(wire.of(i)) == 3 for i in ids))
            first, order[:] = list(order), []
            wire.feed(*(_step_of(i, data[i][:, 6:7]) for i in reversed(ids)))
            await wire.until(lambda: all(len(wire.of(i)) == 4 for i in ids))
            for burst in (first, order):
                assert [kind for kind, _, _ in burst] == ["begin"] * 8 + ["turn"] * 8
                assert {task for kind, _, task in burst if kind == "begin"} == {wire.task}  # the reader's own task
            lanes = [lane for kind, lane, _ in first if kind == "begin"]
            assert sorted(lanes) == list(range(8))
            assert [lane for kind, lane, _ in order if kind == "begin"] == lanes[::-1]
            assert batcher.stats["rpc_intake_direct"] == 16 and batcher.stats["rpc_intake_queued"] == 0
            assert batcher.stats["lane_returns"] == 8 and batcher.stats["rpc_recv_s"] > 0 and batcher.stats["request_handle_s"] > 0
            assert all(frame["payload"]["position"] == 7 for i in ids for frame in wire.of(i)[-1:])
            assert not any(batcher._inflight.values()) and not batcher._pending
            await wire.hang_up()
        finally:
            await server.shutdown()

    run(main())


def test_both_ways_in_give_the_same_replies_and_each_counts_itself(path):
    """Two sessions with the same tokens: one sends plain decode steps, the other the same
    steps with a rollback to where it stands, which the handler has to look at itself.
    The first goes through the sink, the second through the queue, prompts through
    neither count, and the replies agree."""

    async def main():
        server, wire = await _serve(path, 2)
        try:
            batcher = server.handler.batcher
            data = _tokens(batcher.backend.hidden_size, 3)
            await _open(wire, path, (1, 2))
            for call_id in (1, 2):
                wire.feed(_step_of(call_id, data[:, :5]))
                await wire.until(lambda: len(wire.of(call_id)) == 2)
            for pos in range(5, 13):
                wire.feed(_step_of(1, data[:, pos : pos + 1], step_id=f"{pos:x}"),
                          _step_of(2, data[:, pos : pos + 1], start_from_position=pos))
                await wire.until(lambda: len(wire.of(1)) == len(wire.of(2)) == pos - 2)
            assert batcher.stats["rpc_intake_direct"] == 8 and batcher.stats["rpc_intake_queued"] == 8
            assert batcher.stats["lane_returns"] == 14 and batcher.stats["decode_replies"] == 16
            for plain, rolled in zip(_replies(wire, 1), _replies(wire, 2)):
                np.testing.assert_allclose(plain, rolled, rtol=0, atol=1e-6)
            variants = [{frame["payload"]["step_meta"]["variant"] for frame in wire.of(i)[2:]} for i in (1, 2)]
            assert variants[0] == variants[1] and len(variants[0]) == 1  # one kind of step, whichever way it came
            wire.feed(_step_of(1, data[:, 13:14], step_id="5"))  # a step this session has had: dropped, by the loop
            wire.feed(_step_of(1, data[:, 13:14], step_id="new"))
            await wire.until(lambda: len(wire.of(1)) == 11)
            assert wire.of(1)[-1]["payload"]["position"] == 14
            await wire.hang_up()
        finally:
            await server.shutdown()

    run(main())


def test_a_step_the_sink_refuses_fails_its_session_as_the_loop_would_and_no_other(path):
    """A decode step of the wrong width: the sink's own check raises in the reader's turn.
    The session is answered with the handler's words and gives its lane back; the other
    session of the connection goes on, through the sink."""

    async def main():
        server, wire = await _serve(path, 2)
        try:
            batcher = server.handler.batcher
            width = batcher.backend.hidden_size
            data = _tokens(width, 4)
            await _open(wire, path, (1, 2))
            for call_id in (1, 2):
                wire.feed(_step_of(call_id, data[:, :5]))
                await wire.until(lambda: len(wire.of(call_id)) == 2)
            wire.feed(_step_of(2, data[:, 5:6]), _step_of(1, np.zeros((1, 1, width + 1), np.float32)))
            await wire.until(lambda: len(wire.of(1)) == 3 and len(wire.of(2)) == 3)
            failed = wire.of(1)[-1]
            assert failed["t"] == "resp" and failed["ok"] is False
            assert failed["error"].startswith(f"ValueError: step hidden must be [batch=1, seq, hidden={width}]")
            await wire.until(lambda: len(batcher._free_lanes) == 1)
            wire.feed(_step_of(2, data[:, 6:7]))
            await wire.until(lambda: len(wire.of(2)) == 4)
            assert wire.of(2)[-1]["payload"]["position"] == 7
            assert batcher.stats["rpc_intake_direct"] == 2 and batcher.stats["rpc_intake_queued"] == 0
            await wire.hang_up()
        finally:
            await server.shutdown()

    run(main())


def test_a_getter_s_late_wake_parks_the_loop_again():
    """A getter's ``_wake`` runs a turn after the getter ended. A loop that took
    the getter's item in the turn it ended and came round again without a
    suspension (a step it had seen already, over the push plane and the stream)
    is parked on a new future when that wake comes: it parks again, and the
    next item is handed over when it is there."""
    from petals_tpu.server.handler import _StepSource

    async def main():
        items = asyncio.Queue()

        class Requests:
            def __aiter__(self):
                return self

            async def __anext__(self):
                return await items.get()

        source = _StepSource(Requests(), None, 30.0)
        items.put_nowait("one")
        source._get("client", source._next_client)
        await asyncio.sleep(0)  # the getter ends in this turn, ahead of this task; its wake is left for the next
        assert await source.next() == ("one", None)
        asyncio.get_running_loop().call_later(0.01, items.put_nowait, "two")
        assert await source.next() == ("two", None)  # parked before the stale wake ran
        await source.cleanup()

    run(main())

