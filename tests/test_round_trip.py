"""The tokens' round trip on record (PR 37): the server times its own share of
a decode token's way out and back, station by station, in ``batcher.stats``
(``server/batching.py``, ``server/handler.py``, ``rpc/server.py``), and says
what the compute thread waited for between two step bodies.

The first half drives the real ``RpcServer``, handler and batcher of a tiny
llama span on the CPU with two coroutine clients on one connection; the second
a ``DecodeBatcher`` alone on the slowed backend of tests/test_gather.py."""

import asyncio
import time

import jax.numpy as jnp
import numpy as np
import pytest

from petals_tpu.data_structures import CHAIN_DELIMITER, make_uid
from petals_tpu.rpc import RpcClient, RpcServer
from petals_tpu.rpc.serialization import deserialize_array, serialize_array
from petals_tpu.server.batching import DecodeBatcher
from petals_tpu.server.server import Server, default_dht_prefix
from petals_tpu.server.task_queue import PriorityTaskQueue
from petals_tpu.utils.tracing import STEP_PHASES
from tests.test_gather import _client, _rig
from tests.test_mixed_batching import _hidden, _tiny_backend
from tests.utils import make_tiny_llama, steps_booked

pytestmark = pytest.mark.mixed

OUT = ("reply_resume_s", "reply_build_s", "rpc_send_s")  # a decode reply: resolved, handler again, yielded, sent
BACK = ("rpc_recv_s", "request_handle_s")  # a lane that came back: frame read, item held, step() entered
TRIP = ("reply_wake_s", *OUT, *BACK, "lane_return_s")
COUNTS = ("reply_steps", "decode_replies", "lane_returns")
IDLE = ("lanes_out_s", "no_demand_s", "gather_wait_s", "handoff_s")
TILES = (*(name + "_s" for name in STEP_PHASES), *IDLE)  # the compute thread's wall
N_LAYERS = 4  # make_tiny_llama's


@pytest.fixture(scope="module")
def path(tmp_path_factory):
    return make_tiny_llama(str(tmp_path_factory.mktemp("models")))


@pytest.fixture(scope="module")
def tiny(path):
    return _tiny_backend(path)


def _tiled(batcher, before, since):
    """(the eight clocks' seconds, the compute thread's wall) from the body's
    return at which ``before`` was copied to the last body's return."""
    return sum(batcher.stats[key] - before[key] for key in TILES), batcher._last_step_end[0] - since


def test_the_counters_are_in_stats_from_construction(tiny):
    backend, _cfg = tiny
    batcher = DecodeBatcher(backend, backend.memory_cache, PriorityTaskQueue(), n_lanes=2)
    assert {key: batcher.stats[key] for key in (*TRIP, *COUNTS, *IDLE)} == {
        **dict.fromkeys((*TRIP, *IDLE), 0.0), **dict.fromkeys(COUNTS, 0),
    }
    assert all(isinstance(batcher.stats[key], int) for key in COUNTS)


# ------------------------------------------------- through the RPC server and the handler


async def _open(client, path):
    uids = CHAIN_DELIMITER.join(make_uid(default_dht_prefix(path), i) for i in range(N_LAYERS))
    stream = await client.open_stream("ptu.inference")
    await stream.send({"uids": uids, "max_length": 64, "batch_size": 1})
    await stream.recv(timeout=60)
    return stream


async def _step(stream, hidden) -> dict:
    await stream.send({"tensors": {"hidden": serialize_array(hidden)}})
    return await stream.recv(timeout=300)


def test_a_decode_token_s_round_trip_station_by_station(path):
    """Two sessions on one connection: a prompt each, then ``n`` decode steps
    each with 4 ms at the client between a reply and the next request."""
    n, think = 10, 0.004

    async def main():
        server = Server(path, compute_dtype=jnp.float32, use_flash=False, batch_lanes=2, batch_max_length=64, page_size=16)
        await server.start()
        client = await RpcClient.connect(server.rpc_server.host, server.rpc_server.port)
        try:
            batcher = server.handler.batcher
            hidden = batcher.backend.hidden_size
            rows = [np.random.RandomState(seed).randn(1, 40, hidden).astype(np.float32) * 0.5 for seed in (1, 2)]
            streams = [await _open(client, path), await _open(client, path)]
            for stream, data in zip(streams, rows):
                reply = await _step(stream, data[:, :5])
                assert deserialize_array(reply["tensors"]["hidden"]).shape == (1, 5, hidden)
            info = await client.call("ptu.info", {})
            # a prompt chunk, its reply and a unary call add to none of the trip's counters
            assert [batcher.stats[key] for key in (*TRIP, *COUNTS)] == [0.0] * len(TRIP) + [0] * len(COUNTS)
            assert set(batcher.stats) <= set(info["continuous_batching"])
            before, since = dict(batcher.stats), batcher._last_step_end[0]
            metas = []

            async def decode(stream, data):
                for pos in range(5, 5 + n):
                    reply = await _step(stream, data[:, pos : pos + 1])
                    assert set(reply) == {"tensors", "position", "step_meta"}  # the wire as it was
                    metas.append(reply["step_meta"])
                    await asyncio.sleep(think)

            t0 = time.perf_counter()
            await asyncio.gather(*(decode(stream, data) for stream, data in zip(streams, rows)))
            wall = time.perf_counter() - t0
            await steps_booked(batcher)
            stats = dict(batcher.stats)
            # every decode reply is counted, and a lane's return after each but a session's last
            assert stats["decode_replies"] == 2 * n and stats["lane_returns"] == 2 * n - 2
            assert n <= stats["reply_steps"] <= 2 * n
            assert all(stats[key] > 0 for key in TRIP), {key: stats[key] for key in TRIP}
            # the stations lie inside the trips (the two last replies' way out is in no trip:
            # the clients' 4 ms a trip are far more), and a trip holds the client's pause
            assert sum(stats[key] for key in (*OUT, *BACK)) <= stats["lane_return_s"]
            assert stats["lane_return_s"] >= (2 * n - 2) * think
            assert stats["lane_return_s"] <= 2 * wall  # two lanes, each out for less than the whole
            # no stamp crossed the wire, and step_meta kept its keys
            assert all({"queue_s", "compute_s", "variant", "serialize_s", "total_s"} <= set(meta) for meta in metas)
            assert not any({"replied", "read_at", "sent_s", "arrived"} & set(meta) for meta in metas)
            # the eight clocks tile the compute thread's time from the prompts' last body to the last
            tiled, thread_wall = _tiled(batcher, before, since)
            assert thread_wall > n * think and tiled == pytest.approx(thread_wall, rel=0.005)
            # with the clients away for 4 ms a round, the thread mostly waited for lanes that were out
            assert stats["lanes_out_s"] - before["lanes_out_s"] > 0.5 * n * think
            assert stats["handoff_s"] > before["handoff_s"]
            for stream in streams:
                await stream.end()
        finally:
            await client.close()
            await server.shutdown()

    asyncio.run(main())


def test_two_streams_of_one_connection_keep_their_own_times():
    """``StreamRequests`` is a stream's own: what the server reads and sends
    for one stream moves nothing on the other's object, the payload comes
    back as it went, and a handler that never looks at it is served as ever."""
    objects, seen = {}, []

    async def echo(requests, ctx):
        async for item in requests:
            objects[item["who"]] = requests
            seen.append((item["who"], requests.read_at, requests.sent_s))
            yield item

    async def blind(requests, ctx):
        async for item in requests:
            yield {"twice": item["x"] * 2}

    async def main():
        server = RpcServer()
        server.add_stream_handler("echo", echo)
        server.add_stream_handler("blind", blind)
        await server.start()
        client = await RpcClient.connect(server.host, server.port)
        try:
            a, b = await client.open_stream("echo"), await client.open_stream("echo")
            t0 = time.perf_counter()
            await a.send({"who": "a", "i": 0})
            assert await a.recv(timeout=10) == {"who": "a", "i": 0}
            await asyncio.sleep(0.01)  # the server's stream task has run on from its write
            a_read, a_sent = objects["a"].read_at, objects["a"].sent_s
            assert t0 < a_read < time.perf_counter() and 0 < a_sent < 1
            for i in range(3):
                await b.send({"who": "b", "i": i})
                assert await b.recv(timeout=10) == {"who": "b", "i": i}
            await asyncio.sleep(0.01)
            assert objects["a"] is not objects["b"]
            assert (objects["a"].read_at, objects["a"].sent_s) == (a_read, a_sent)  # b's traffic moved nothing of a's
            assert objects["b"].read_at > a_read
            await a.send({"who": "a", "i": 1})
            await a.recv(timeout=10)
            reads = [read_at for who, read_at, _ in seen if who == "b"]
            assert reads == sorted(reads) and len(set(reads)) == 3  # each item its own reading
            assert seen[0][2] is None and seen[-1] == ("a", objects["a"].read_at, a_sent)  # sent_s: the item before
            c = await client.open_stream("blind")
            await c.send({"x": 21})
            assert await c.recv(timeout=10) == {"twice": 42}
            for stream in (a, b, c):
                await stream.end()
        finally:
            await client.close()
            await server.stop()

    asyncio.run(main())


# ------------------------------------------------------------------- the batcher alone


def test_what_the_compute_thread_waited_for(tiny):
    """One client that is away 8 ms a round: the thread waits for a lane that
    is out. Then nobody for 60 ms and a new session's prompt: no demand. The
    hand-off is what is left, and the eight clocks tile the thread's wall."""

    async def main():
        async with _rig(tiny, 2, 0.004) as rig:
            batcher = rig.batcher
            before, since = dict(batcher.stats), batcher._last_step_end[0]
            lane = await batcher.acquire_lane()
            await batcher.prefill_lane(lane, _hidden(rig.cfg, 1, 5), 0)
            first = dict(batcher.stats)
            # the rig's warm-up ended a while ago and no reply was out since: no demand, and no trip
            assert first["no_demand_s"] > before["no_demand_s"] and first["lanes_out_s"] == before["lanes_out_s"]
            rounds, away = 8, 0.008
            await _client(rig, lane, rounds, away, pos0=5)
            await steps_booked(batcher)
            after = dict(batcher.stats)
            out = after["lanes_out_s"] - first["lanes_out_s"]
            assert (rounds - 1) * away <= out <= rounds * (away + 0.004)  # every return but the first follows a reply
            assert after["no_demand_s"] - first["no_demand_s"] < away + 0.004  # the first decode step's wait at most
            assert after["lane_returns"] - first["lane_returns"] == rounds - 1
            assert after["lane_return_s"] - first["lane_return_s"] >= (rounds - 1) * away
            # a step() called here adds nothing to the handler's and the RPC server's stretches
            assert all(after[key] == 0.0 for key in (*OUT, *BACK)) and after["decode_replies"] == 0
            assert after["reply_steps"] - first["reply_steps"] == rounds and after["reply_wake_s"] > first["reply_wake_s"]
            batcher.release_lane(lane)
            await asyncio.sleep(0.06)
            lane = await batcher.acquire_lane()
            await batcher.prefill_lane(lane, _hidden(rig.cfg, 3, 5), 0)
            last = dict(batcher.stats)  # (a prompt's chunk rides a whole body: booked when it returns)
            assert last["no_demand_s"] - after["no_demand_s"] >= 0.055  # the released lane's reply is out no more
            assert last["lanes_out_s"] - after["lanes_out_s"] < 0.02  # the last reply's 8 ms at most
            assert last["handoff_s"] > before["handoff_s"]
            tiled, thread_wall = _tiled(batcher, before, since)
            assert tiled == pytest.approx(thread_wall, rel=0.005)
            batcher.release_lane(lane)

    asyncio.run(main())


def test_a_request_s_way_in_is_counted_for_a_lane_that_comes_back_only(tiny):
    """``step(arrived=...)``: the caller's two readings are taken for a lane
    that is back from a decode reply, not for a session's first decode step,
    and a pushed step (no frame read here) still counts the handler's part."""

    async def main():
        async with _rig(tiny, 2, 0.002) as rig:
            batcher = rig.batcher
            lane = await batcher.acquire_lane()
            await batcher.prefill_lane(lane, _hidden(rig.cfg, 1, 5), 0)

            async def step(pos, read_before, held_before):
                now = time.perf_counter()
                arrived = (None if read_before is None else now - read_before, now - held_before)
                await batcher.step(lane, _hidden(rig.cfg, pos), pos, arrived=arrived)

            await step(5, 0.003, 0.001)
            assert batcher.stats["rpc_recv_s"] == 0.0 and batcher.stats["request_handle_s"] == 0.0
            await step(6, 0.003, 0.001)
            assert batcher.stats["rpc_recv_s"] == pytest.approx(0.002, abs=1e-6)
            assert 0.001 <= batcher.stats["request_handle_s"] < 0.002
            await step(7, None, 0.001)
            assert batcher.stats["rpc_recv_s"] == pytest.approx(0.002, abs=1e-6)
            assert 0.002 <= batcher.stats["request_handle_s"] < 0.004
            assert batcher.stats["lane_returns"] == 2
            batcher.count_decode_reply(0.5, 0.25, 0.125)
            assert [batcher.stats[key] for key in (*OUT, "decode_replies")] == [0.5, 0.25, 0.125, 1]
            batcher.release_lane(lane)

    asyncio.run(main())
