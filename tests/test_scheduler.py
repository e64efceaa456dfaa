"""Session scheduler (server/scheduler.py + server/batching.py preemption):
priority + fair-share admission must order lane waiters correctly, victim
selection must never evict a more important or non-idle session, swap-out /
swap-in must round-trip KV bit-exactly (including relocation onto different
physical pages), and an oversubscribed pool with the swap tier enabled must
complete every session token-identically with zero AllocationFailed."""

import asyncio

import jax.numpy as jnp
import numpy as np
import pytest

from petals_tpu.data_structures import (
    CHAIN_DELIMITER,
    SESSION_PRIORITY_HIGH,
    SESSION_PRIORITY_LOW,
    SESSION_PRIORITY_NORMAL,
    make_uid,
    parse_session_priority,
)
from petals_tpu.rpc import RpcClient
from petals_tpu.rpc.serialization import deserialize_array, serialize_array
from petals_tpu.server.memory_cache import AllocationFailed, HostSwapPool
from petals_tpu.server.scheduler import SessionScheduler
from petals_tpu.server.server import Server, default_dht_prefix
from tests.utils import make_tiny_llama

pytestmark = pytest.mark.sched


@pytest.fixture(scope="module")
def model_path(tmp_path_factory):
    return make_tiny_llama(str(tmp_path_factory.mktemp("models")))


def run(coro):
    return asyncio.run(coro)


async def _start_server(model_path, **kwargs):
    server = Server(model_path, compute_dtype=jnp.float32, use_flash=False, **kwargs)
    await server.start()
    client = await RpcClient.connect(server.rpc_server.host, server.rpc_server.port)
    return server, client


# ----------------------------------------------------------- policy units


def test_parse_session_priority_unit():
    assert parse_session_priority(None) == SESSION_PRIORITY_NORMAL
    assert parse_session_priority(None, default=SESSION_PRIORITY_LOW) == SESSION_PRIORITY_LOW
    assert parse_session_priority("high") == SESSION_PRIORITY_HIGH
    assert parse_session_priority("NORMAL") == SESSION_PRIORITY_NORMAL
    assert parse_session_priority("low") == SESSION_PRIORITY_LOW
    assert parse_session_priority(0) == SESSION_PRIORITY_HIGH
    assert parse_session_priority(7) == SESSION_PRIORITY_LOW  # clamped
    for bad in ("urgent", True, 1.5, []):
        with pytest.raises(ValueError):
            parse_session_priority(bad)


def test_victim_selection_unit():
    """Lowest priority class is evicted first; within a class, LRU by step
    clock (or most pages under "largest"); suspended/suspending lanes and
    lanes MORE important than the requester are never victims."""
    pages = {0: 3, 1: 1, 2: 4, 3: 2}
    sched = SessionScheduler(HostSwapPool(1 << 20), policy="lru", pages_fn=pages.get)
    sched.register(0, "peer-a", SESSION_PRIORITY_HIGH)
    sched.register(1, "peer-a", SESSION_PRIORITY_LOW)
    sched.register(2, "peer-b", SESSION_PRIORITY_LOW)
    sched.register(3, "peer-b", SESSION_PRIORITY_NORMAL)
    # make lane 1 the least recently stepped of the LOW pair
    sched.touch(2)

    # lowest class first, then LRU: lane 1 beats lane 2 (older), both beat 3/0
    assert sched.pick_victim([0, 1, 2, 3]) == 1
    assert sched.pick_victim([0, 2, 3]) == 2
    assert sched.pick_victim([0, 3]) == 3
    # a NORMAL requester must not evict the HIGH session
    assert sched.pick_victim([0], max_priority=SESSION_PRIORITY_NORMAL) is None
    # ...but an equal-or-lower class is fair game
    assert sched.pick_victim([0, 3], max_priority=SESSION_PRIORITY_NORMAL) == 3
    # suspended and in-flight-suspend lanes are skipped
    sched.lanes[1].swap = object()
    sched.lanes[2].suspending = True
    assert sched.pick_victim([1, 2, 3]) == 3

    # "largest" prefers the biggest page holder within a class
    sched2 = SessionScheduler(HostSwapPool(1 << 20), policy="largest", pages_fn=pages.get)
    for lane in (1, 2, 3):
        sched2.register(lane, None, SESSION_PRIORITY_LOW)
    sched2.touch(2)  # recency must NOT override size here
    assert sched2.pick_victim([1, 2, 3]) == 2  # 4 pages

    # "off" never yields a victim
    sched3 = SessionScheduler(HostSwapPool(1 << 20), policy="off", pages_fn=pages.get)
    sched3.register(1, None, SESSION_PRIORITY_LOW)
    assert sched3.pick_victim([1]) is None

    with pytest.raises(ValueError, match="preemption_policy"):
        SessionScheduler(HostSwapPool(0), policy="random")


def test_fair_share_admission_unit():
    """pick_waiter: priority class first, then the peer holding the fewest
    lanes, then FIFO — which at uniform priority/peers is exactly FIFO."""
    from petals_tpu.server.batching import _LaneWaiter

    async def main():
        loop = asyncio.get_running_loop()

        def waiter(priority, peer, seq):
            return _LaneWaiter(
                fut=loop.create_future(), priority=priority, peer_id=peer, seq=seq
            )

        sched = SessionScheduler(HostSwapPool(0))
        sched.register(0, "greedy", SESSION_PRIORITY_NORMAL)
        sched.register(1, "greedy", SESSION_PRIORITY_NORMAL)
        assert sched.peer_lanes_held("greedy") == 2
        assert sched.peer_lanes_held("modest") == 0

        w_greedy = waiter(SESSION_PRIORITY_NORMAL, "greedy", 0)
        w_modest = waiter(SESSION_PRIORITY_NORMAL, "modest", 1)
        w_low = waiter(SESSION_PRIORITY_LOW, "modest", 2)
        w_high = waiter(SESSION_PRIORITY_HIGH, "greedy", 3)

        # priority beats both fair share and arrival order
        assert sched.pick_waiter([w_greedy, w_modest, w_low, w_high]) is w_high
        # equal priority: the peer with fewer lanes held wins despite later seq
        assert sched.pick_waiter([w_greedy, w_modest, w_low]) is w_modest
        # same priority + same holdings -> FIFO by seq
        w_modest2 = waiter(SESSION_PRIORITY_NORMAL, "modest", 9)
        assert sched.pick_waiter([w_modest2, w_modest]) is w_modest
        # resolved futures are skipped; all-dead -> None
        w_modest.fut.set_result(0)
        assert sched.pick_waiter([w_modest, w_modest2]) is w_modest2
        w_modest2.fut.set_result(1)
        assert sched.pick_waiter([w_modest, w_modest2]) is None

    run(main())


def test_host_swap_pool_unit():
    pool = HostSwapPool(100)
    assert pool.try_reserve(60) and pool.bytes_in_use == 60
    assert not pool.try_reserve(50)  # all-or-nothing
    assert pool.stats["rejected"] == 1 and pool.bytes_in_use == 60
    assert pool.try_reserve(40) and pool.bytes_left == 0
    pool.free(60)
    assert pool.bytes_in_use == 40 and pool.stats["peak_bytes"] == 100
    # zero-budget pool (the default) admits nothing
    assert not HostSwapPool(0).try_reserve(1)


# ------------------------------------------------- swap parity (direct backend)


def _tiny_backend(model_path):
    import jax

    from petals_tpu.server.backend import TransformerBackend
    from petals_tpu.server.from_pretrained import get_block_config, load_block_params
    from petals_tpu.server.memory_cache import MemoryCache

    family, cfg = get_block_config(model_path)
    per_block = [
        load_block_params(model_path, i, dtype=jnp.float32, family=family, cfg=cfg)
        for i in range(2)
    ]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_block)
    return TransformerBackend(
        family, cfg, stacked, first_block=0, n_blocks=2,
        memory_cache=MemoryCache(None), compute_dtype=jnp.float32, use_flash=False,
    ), cfg


def test_swap_gather_scatter_parity_direct(model_path):
    """The device twins round-trip page content exactly: gather pages out of
    one pool, scatter them back into another at DIFFERENT physical pages
    (relocation), both onto the identity layout and a permuted one."""
    backend, _ = _tiny_backend(model_path)
    rng = np.random.RandomState(3)
    n_blocks, n_pages, ps, hkv, d = 2, 12, 8, 2, 4
    k_src = jnp.asarray(rng.randn(n_blocks, n_pages, ps, hkv, d).astype(np.float32))
    v_src = jnp.asarray(rng.randn(n_blocks, n_pages, ps, hkv, d).astype(np.float32))

    for src_pages, dst_pages in [
        (np.array([2, 3, 4], np.int32), np.array([2, 3, 4], np.int32)),  # identity
        (np.array([7, 1, 10], np.int32), np.array([0, 11, 5], np.int32)),  # permuted
    ]:
        k_host, v_host = backend._swap_out_pages_fn(k_src, v_src, src_pages)
        k_host, v_host = np.asarray(k_host), np.asarray(v_host)
        np.testing.assert_array_equal(k_host, np.asarray(k_src)[:, src_pages])
        np.testing.assert_array_equal(v_host, np.asarray(v_src)[:, src_pages])

        k_dst = jnp.zeros_like(k_src)
        v_dst = jnp.zeros_like(v_src)
        k_dst, v_dst = backend._swap_in_pages_fn(k_dst, v_dst, k_host, v_host, dst_pages)
        k_dst, v_dst = np.asarray(k_dst), np.asarray(v_dst)
        np.testing.assert_array_equal(k_dst[:, dst_pages], np.asarray(k_src)[:, src_pages])
        np.testing.assert_array_equal(v_dst[:, dst_pages], np.asarray(v_src)[:, src_pages])
        # untouched pages stayed zero
        rest = np.setdiff1d(np.arange(n_pages), dst_pages)
        assert np.abs(k_dst[:, rest]).sum() == 0 and np.abs(v_dst[:, rest]).sum() == 0


# --------------------------------------------- batcher suspend/resume roundtrip


def test_batcher_swap_roundtrip_relocates_pages(model_path):
    """Swap a lane out (pages free, bytes land in the host tier), let another
    lane steal its physical pages, then read the lane again: the batcher must
    transparently swap it back in onto DIFFERENT pages with identical KV."""

    async def main():
        server, client = await _start_server(
            model_path, batching=True, batch_lanes=2, batch_max_length=32,
            page_size=8, n_pages=4,  # 2 lanes x 4 slots = 8 > 4: tight pool
            swap_host_bytes=1 << 22,
        )
        try:
            batcher = server.handler.batcher
            sched = batcher._scheduler
            n_blocks = batcher.backend.n_blocks
            a = await batcher.acquire_lane(timeout=5)
            await batcher.prepare_write(a, 0, 16)  # two pages resident
            old_pages = [int(p) for p in batcher._tables[a] if p >= 0]
            assert len(old_pages) == 2

            # stamp recognizable content, snapshot it for the parity check
            k_pool, v_pool = batcher._buffers()
            for i, page in enumerate(old_pages):
                k_pool = k_pool.at[:, page].set(1.0 + i)
                v_pool = v_pool.at[:, page].set(-1.0 - i)
            batcher._update(k_pool, v_pool)
            a_before = await batcher.snapshot_lane(a, 16, 0, n_blocks)

            free_before = batcher._pages.n_free
            assert await batcher._swap_out_lane(a)
            assert sched.lanes[a].suspended and sched.suspended_count == 1
            assert batcher._pages.n_free == free_before + 2
            assert np.all(batcher._tables[a] == -1)
            assert batcher.swap_pool.bytes_in_use == 2 * batcher._pool.page_bytes
            assert sched.stats["preemptions"] == 1 and sched.stats["swap_outs"] == 1
            # an idle-but-suspended lane is not a victim candidate anymore
            assert not batcher._lane_idle(a)

            # lane b takes 3 of the 4 pages, including one of a's old physical
            # pages — a's swap-in must RELOCATE, and must itself preempt b to
            # find two simultaneously free pages
            b = await batcher.acquire_lane(timeout=5)
            await batcher.prepare_write(b, 0, 24)
            b_pages = {int(p) for p in batcher._tables[b] if p >= 0}
            assert len(b_pages) == 3
            assert set(old_pages) & b_pages, "freed pages were not reused (FIFO)"
            b_before = await batcher.snapshot_lane(b, 24, 0, n_blocks)

            # snapshot_lane goes through _lane_busy -> transparent swap-in
            a_after = await batcher.snapshot_lane(a, 16, 0, n_blocks)
            new_pages = [int(p) for p in batcher._tables[a] if p >= 0]
            assert len(new_pages) == 2 and set(new_pages) != set(old_pages)
            assert not sched.lanes[a].suspended
            assert sched.lanes[b].suspended, "swap-in had to evict b for room"
            assert sched.stats["swap_ins"] == 1
            assert batcher.swap_pool.bytes_in_use == 3 * batcher._pool.page_bytes
            np.testing.assert_array_equal(a_after[0], a_before[0])
            np.testing.assert_array_equal(a_after[1], a_before[1])

            # reading b swings the pendulum back: b resumes (onto relocated
            # pages), evicting a again — content still exact on both sides
            b_after = await batcher.snapshot_lane(b, 24, 0, n_blocks)
            assert not sched.lanes[b].suspended and sched.lanes[a].suspended
            assert sched.stats["swap_ins"] == 2
            np.testing.assert_array_equal(b_after[0], b_before[0])
            np.testing.assert_array_equal(b_after[1], b_before[1])

            batcher.release_lane(a)  # drops a's swap entry with the slot
            batcher.release_lane(b)
            assert batcher.swap_pool.bytes_in_use == 0
            assert batcher._pages.n_free == batcher.n_pages
        finally:
            await client.close()
            await server.shutdown()

    run(main())


def test_preemption_on_exhaustion_and_priority_admission(model_path):
    """prepare_write on an exhausted pool preempts an IDLE victim instead of
    raising; parked acquire_lane callers are admitted by priority class."""

    async def main():
        server, client = await _start_server(
            model_path, batching=True, batch_lanes=2, batch_max_length=32,
            page_size=8, n_pages=5, swap_host_bytes=1 << 22,
        )
        try:
            batcher = server.handler.batcher
            a = await batcher.acquire_lane(timeout=5, peer_id="victim")
            b = await batcher.acquire_lane(timeout=5, peer_id="requester")
            await batcher.prepare_write(a, 0, 32)  # lane a: all 4 slots
            assert batcher._pages.n_free == 0

            # the same call that raised AllocationFailed without the swap tier
            # (test_page_exhaustion_backpressure_and_wakeup) now preempts a
            await batcher.prepare_write(b, 8, 9, timeout=5)
            assert batcher._scheduler.lanes[a].suspended
            assert batcher._scheduler.stats["preemptions"] == 1
            assert int(batcher._tables[b, 1]) >= 0

            # both lanes busy: a LOW and a HIGH waiter park; on release the
            # HIGH one is admitted first despite arriving later
            low = asyncio.create_task(
                batcher.acquire_lane(timeout=10, priority=SESSION_PRIORITY_LOW)
            )
            await asyncio.sleep(0.05)
            high = asyncio.create_task(
                batcher.acquire_lane(timeout=10, priority=SESSION_PRIORITY_HIGH)
            )
            await asyncio.sleep(0.05)
            assert not low.done() and not high.done()
            batcher.release_lane(b)
            lane_high = await asyncio.wait_for(high, timeout=5)
            assert batcher._scheduler.lanes[lane_high].priority == SESSION_PRIORITY_HIGH
            assert not low.done()
            batcher.release_lane(lane_high)
            lane_low = await asyncio.wait_for(low, timeout=5)

            batcher.release_lane(lane_low)
            batcher.release_lane(a)  # drops the swap entry with the slot
            assert batcher.swap_pool.bytes_in_use == 0
        finally:
            await client.close()
            await server.shutdown()

    run(main())


def test_allocation_failed_reports_occupancy(model_path):
    """Rejections explain WHY: AllocationFailed messages carry lane/page
    occupancy and per-lane holdings, and rpc_info exposes the same numbers
    machine-readably (satellites: error context + pool observability)."""

    async def main():
        server, client = await _start_server(
            model_path, batching=True, batch_lanes=2, batch_max_length=32,
            page_size=8, n_pages=5,  # swap disabled: exhaustion still fails
        )
        try:
            batcher = server.handler.batcher
            a = await batcher.acquire_lane(timeout=5)
            await batcher.prepare_write(a, 0, 32)
            b = await batcher.acquire_lane(timeout=5)
            with pytest.raises(AllocationFailed) as exc:
                await batcher.prepare_write(b, 8, 9, timeout=0.2)
            msg = str(exc.value)
            assert "pages free" in msg and "lanes busy" in msg
            assert f"lane {a}: 4" in msg  # per-lane holdings

            # lane exhaustion names the occupancy too
            with pytest.raises(AllocationFailed, match="lanes busy"):
                await batcher.acquire_lane(timeout=0.1)

            info = await client.call("ptu.info", {}, timeout=10)
            pool = info["pool"]
            assert pool["lanes"] == 2 and pool["busy_lanes"] == 2
            assert pool["n_pages"] == 5 and pool["pages_free"] == 0
            assert pool["policy"] == "lru" and pool["suspended"] == 0
            assert pool["swap_bytes_total"] == 0 and pool["preemptions"] == 0

            batcher.release_lane(a)
            batcher.release_lane(b)
        finally:
            await client.close()
            await server.shutdown()

    run(main())


def test_session_priority_hint_via_open_message(model_path):
    """The session-open "priority" hint lands in the scheduler slot; omitting
    it keeps the default (normal) — the backward-compatible path."""

    async def main():
        server, client = await _start_server(
            model_path, batching=True, batch_lanes=4, batch_max_length=32,
            page_size=8,
        )
        try:
            cfg = server.cfg
            prefix = default_dht_prefix(model_path)
            uids = CHAIN_DELIMITER.join(
                make_uid(prefix, i) for i in range(cfg.num_hidden_layers)
            )
            sched = server.handler.batcher._scheduler

            stream = await client.open_stream("ptu.inference")
            await stream.send(
                {"uids": uids, "max_length": 16, "batch_size": 1, "priority": "high"}
            )
            await stream.recv(timeout=60)
            stream2 = await client.open_stream("ptu.inference")
            await stream2.send({"uids": uids, "max_length": 16, "batch_size": 1})
            await stream2.recv(timeout=60)

            priorities = sorted(s.priority for s in sched.lanes.values())
            assert priorities == [SESSION_PRIORITY_HIGH, SESSION_PRIORITY_NORMAL]
            await stream.end()
            await stream2.end()
        finally:
            await client.close()
            await server.shutdown()

    run(main())


# --------------------------------------------------- e2e oversubscription


def test_e2e_oversubscription_preemption(model_path):
    """Four concurrent sessions on a pool that can hold roughly HALF their
    peak pages, with the swap tier enabled: every session must complete
    token-identically to unbatched serving with ZERO AllocationFailed —
    sessions stall through preemption instead of dying."""

    async def main():
        server, client = await _start_server(
            model_path, batching=True, batch_lanes=4, batch_max_length=64,
            page_size=16, n_pages=4,  # peak demand ~6-8 pages across sessions
            swap_host_bytes=1 << 26,
        )
        try:
            cfg = server.cfg
            prefix = default_dht_prefix(model_path)
            uids = CHAIN_DELIMITER.join(
                make_uid(prefix, i) for i in range(cfg.num_hidden_layers)
            )
            rng = np.random.RandomState(17)
            sessions = []
            for i in range(4):
                prefill = rng.randn(1, 3 + 5 * i, cfg.hidden_size).astype(np.float32) * 0.1
                steps = [
                    rng.randn(1, 1, cfg.hidden_size).astype(np.float32) * 0.1
                    for _ in range(6)
                ]
                sessions.append((prefill, steps))

            # per-step cyclic barrier (asyncio.Barrier is 3.11+): every driver
            # re-syncs before submitting step k, so the four step requests hit
            # the batcher together and a flush sees >= 2 pending lanes. The
            # fixed-sleep pacing alone is flaky: once the jit cache is warm a
            # device step finishes before the next client's request arrives and
            # the lanes drift into lockstep-of-one (max_batch == 1). Waiting at
            # the barrier keeps each lane IDLE while holding its pages — the
            # same pool pressure the sleep was creating.
            n_drivers = len(sessions)
            step_waits = [0] * len(sessions[0][1])
            step_gates = [asyncio.Event() for _ in sessions[0][1]]

            async def step_sync(k):
                step_waits[k] += 1
                if step_waits[k] == n_drivers:
                    step_gates[k].set()
                await step_gates[k].wait()

            async def drive(prefill, steps, barrier):
                stream = await client.open_stream("ptu.inference")
                await stream.send({"uids": uids, "max_length": 40, "batch_size": 1})
                await stream.recv(timeout=60)
                await barrier.wait()
                outs = []
                await stream.send({"tensors": {"hidden": serialize_array(prefill)}})
                reply = await stream.recv(timeout=120)
                outs.append(deserialize_array(reply["tensors"]["hidden"]))
                for k, h in enumerate(steps):
                    # pace the stream like a real client (sampling between
                    # steps): lanes sit IDLE holding pages, so pool pressure
                    # must be resolved by preemption, not by a session
                    # finishing fast and releasing its pages first
                    await asyncio.sleep(0.05)
                    await step_sync(k)
                    await stream.send({"tensors": {"hidden": serialize_array(h)}})
                    reply = await stream.recv(timeout=120)
                    outs.append(deserialize_array(reply["tensors"]["hidden"]))
                await stream.end()
                return outs

            barrier = asyncio.Event()
            tasks = [
                asyncio.create_task(drive(p, s, barrier)) for p, s in sessions
            ]
            await asyncio.sleep(0.1)
            barrier.set()
            results = await asyncio.gather(*tasks)

            batcher = server.handler.batcher
            sched = batcher._scheduler
            # the pool CANNOT fit all sessions: preemption must actually have
            # swapped lanes out and transparently back in, with no fallback
            assert sched.stats["preemptions"] >= 1, sched.summary()
            assert sched.stats["swap_ins"] >= 1, sched.summary()
            assert batcher.stats["max_batch"] >= 2, dict(batcher.stats)
            # everything drained: no KV left in the swap tier, no leaked pages
            # (stream.end() returns before the server processes the release,
            # so give the lane teardown a moment to land)
            for _ in range(100):
                if batcher._pages.n_free == batcher.n_pages:
                    break
                await asyncio.sleep(0.05)
            assert batcher._pages.n_free == batcher.n_pages
            assert batcher.swap_pool.bytes_in_use == 0

            backend = server.backend
            for s, ((prefill, steps), got) in enumerate(zip(sessions, results)):
                kd, vd = backend.cache_descriptors(1, 64, 0, backend.n_blocks)
                kv = (kd.make_zeros(), vd.make_zeros())
                want, kv = backend.inference_step(prefill, kv, 0)
                np.testing.assert_allclose(
                    got[0], np.asarray(want), atol=2e-5, rtol=0,
                    err_msg=f"session {s} prefill",
                )
                pos = prefill.shape[1]
                for i, h in enumerate(steps):
                    want, kv = backend.inference_step(h, kv, pos)
                    pos += 1
                    np.testing.assert_allclose(
                        got[1 + i], np.asarray(want), atol=2e-5, rtol=0,
                        err_msg=f"session {s} step {i}",
                    )
        finally:
            await client.close()
            await server.shutdown()

    run(main())
