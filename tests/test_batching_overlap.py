"""A decode step launched while the step before it is still in flight, and its
rows handed back before its bookkeeping (server/batching.py ``_start_behind``,
``_launch_batch``, ``_readback_loop``, ``_step_home``, ``_finish_batch``).

No case reads a clock against a threshold. A step is held in flight by handing
the batcher rows whose copy to the host blocks on a ``threading.Event``
(``_Held``: the readback thread sits in it as it would in a device's), the
bookkeeping is held by an event in front of ``_finish_batch``, and order is
read off the events the wrapped calls append. The rule's cases hand
``_gather_until`` times they choose, as tests/test_gather.py's do."""

import asyncio
import collections
import contextlib
import threading
import types

import numpy as np
import pytest

from petals_tpu.server.batching import DecodeBatcher, _LaneReturn, _StepInFlight
from petals_tpu.server.memory_cache import AllocationFailed
from petals_tpu.server.task_queue import PriorityTaskQueue
from tests.test_mixed_batching import _hidden, _tiny_backend
from tests.test_round_trip import TILES
from tests.utils import make_tiny_llama, steps_booked

pytestmark = pytest.mark.mixed

WAIT = 60  # seconds a wait of a test may take before it is a failure, on however busy a host


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return _tiny_backend(make_tiny_llama(str(tmp_path_factory.mktemp("models"))))


def run(coro):
    return asyncio.run(coro)


class _Held:
    """A decode step's rows as the backend hands them over, whose copy to the
    host waits for ``release``: the step stays in flight until the test lets
    it come home."""

    def __init__(self, rows, release: threading.Event, rig):
        self._rows, self._release, self._rig = rows, release, rig

    def copy_to_host_async(self):
        pass

    def __array__(self, dtype=None, copy=None):
        assert self._release.wait(WAIT), "a held step was never released"
        if self._rig.fail_next:
            self._rig.fail_next = False
            raise RuntimeError("simulated device failure behind the launch")
        return np.asarray(self._rows)


@contextlib.asynccontextmanager
async def _rig(tiny, n_lanes, hold=True):
    """A paged batcher on the tiny backend with both step programs compiled.
    ``rig.events`` gets ("launch", lanes) when a decode step's program is
    called and ("finish", lanes) when its bookkeeping has run; with ``hold``
    every launched step stays in flight until ``rig.release`` is set (a new
    event a round: ``rig.next_round()``)."""
    backend, cfg = tiny
    queue = PriorityTaskQueue()
    queue.start()
    batcher = DecodeBatcher(backend, backend.memory_cache, queue, n_lanes=n_lanes, max_length=64, page_size=16)
    rig = types.SimpleNamespace(batcher=batcher, cfg=cfg, events=[], release=threading.Event(), hold=hold, fail_next=False)
    rig.next_round = lambda: setattr(rig, "release", threading.Event())
    fast, finish = backend.paged_decode_step, batcher._finish_batch

    def launch(hidden, kv, positions, *args, **kwargs):
        lanes = tuple(int(lane) for lane in np.flatnonzero(np.asarray(positions) < batcher.max_length))
        rig.events.append(("launch", lanes))
        out, pools = fast(hidden, kv, positions, *args, **kwargs)
        return (_Held(out, rig.release, rig) if rig.hold else out), pools

    def finished(flight):
        finish(flight)
        rig.events.append(("finish", tuple(flight.lanes)))

    try:
        warm = await batcher.acquire_lane()
        await batcher.prefill_lane(warm, _hidden(cfg, 1, 5), 0)
        await batcher.step(warm, _hidden(cfg, 2), 5)
        batcher.release_lane(warm)
        await steps_booked(batcher)
        backend.paged_decode_step, batcher._finish_batch = launch, finished
        yield rig
    finally:
        rig.release.set()
        backend.__dict__.pop("paged_decode_step", None)
        await batcher.close()
        queue.shutdown()


async def _until(condition):
    async with asyncio.timeout(WAIT):
        while not condition():
            await asyncio.sleep(0)


def _steps(rig, lanes, seed, position):
    """One decode step of each of ``lanes``, started in one turn so that they ride one batched step."""
    return asyncio.gather(*(rig.batcher.step(lane, _hidden(rig.cfg, seed + lane), position) for lane in lanes))


# ------------------------------------------------------------ (a) the order, and the bits


def test_the_second_group_is_launched_before_the_first_has_come_home(tiny):
    async def main():
        async with _rig(tiny, 5) as rig:
            batcher = rig.batcher
            lanes = [await batcher.acquire_lane() for _ in range(5)]
            one, two, late = tuple(lanes[:2]), tuple(lanes[2:4]), tuple(lanes[4:])
            before = dict(batcher.stats)
            first = _steps(rig, one, 10, 0)
            await _until(lambda: batcher._flights and batcher._flights[0].out is not None)
            second = _steps(rig, two, 10, 0)
            await _until(lambda: len(batcher._flights) == 2 and batcher._flights[1].out is not None)
            # both programs are launched, neither step's rows are home, nothing is booked
            assert rig.events == [("launch", one), ("launch", two)]
            assert not first.done() and not second.done()
            assert batcher.stats["batched_steps"] == before["batched_steps"]
            third = _steps(rig, late, 10, 0)  # a third step could only queue behind the second: it waits for a step to come home
            for _ in range(20):
                await asyncio.sleep(0)
            assert len(batcher._flights) == 2 and len(batcher._pending) == 1 and len(rig.events) == 2
            rig.release.set()
            await asyncio.gather(first, second, third)
            await steps_booked(batcher)
            assert [lanes_in for kind, lanes_in in rig.events if kind == "launch"] == [one, two, late]
            assert [lanes_in for kind, lanes_in in rig.events if kind == "finish"] == [one, two, late]
            assert rig.events.index(("finish", one)) > rig.events.index(("launch", two))
            delta = {key: batcher.stats[key] - before[key] for key in ("batched_steps", "batched_tokens", "overlapped_steps")}
            assert delta["batched_steps"] == 3 and delta["batched_tokens"] == 5
            assert delta["overlapped_steps"] in (1, 2)  # the third is behind the second if that is not home yet
            assert batcher.occupancy_info()["overlapped_steps"] == batcher.stats["overlapped_steps"]
            assert not batcher._aloft and not batcher._flights

    run(main())


async def _two_groups(tiny, overlap: bool, rounds: int = 32):
    """Two groups of two lanes, ``rounds`` decode steps each behind a prompt,
    and a fifth lane's prompt admitted half way. ``overlap``: each round the
    second group's step is launched behind the first's, which is held in
    flight until then; else each step is awaited before the next starts. Every
    row every lane got back, in order."""
    async with _rig(tiny, 5, hold=overlap) as rig:
        batcher = rig.batcher
        lanes = [await batcher.acquire_lane() for _ in range(5)]
        one, two, late = lanes[:2], lanes[2:4], lanes[4]
        rows = collections.defaultdict(list)
        for lane in one + two:
            rows[lane].append(await batcher.prefill_lane(lane, _hidden(rig.cfg, 50 + lane, 3), 0))
        overlapped = batcher.stats["overlapped_steps"]
        for r in range(rounds):
            rig.next_round()
            launched = len(rig.events)
            first = _steps(rig, one, 100 * r, 3 + r)
            if overlap:
                await _until(lambda: len(rig.events) > launched and batcher._flights and batcher._flights[0].out is not None)
            else:
                await first
            second = _steps(rig, two, 100 * r, 3 + r)
            if overlap:
                await _until(lambda: len(batcher._flights) == 2 and batcher._flights[1].out is not None)
                assert not first.done() and not second.done()
            else:
                await second
            prompt = None
            if r == rounds // 2:  # a prompt arrives with both steps in flight: its chunk rides a tick of its own once none is
                prompt = asyncio.ensure_future(batcher.prefill_lane(late, _hidden(rig.cfg, 77, 6), 0))
                await _until(lambda: batcher._prefill_queue)
            rig.release.set()
            for lane, row in zip(one + two, [*await first, *await second]):
                rows[lane].append(row)
            if prompt is not None:
                rows[late].append(await prompt)
        await steps_booked(batcher)
        assert batcher.stats["overlapped_steps"] - overlapped == (rounds if overlap else 0)
        return {lane: np.concatenate([row.reshape(-1) for row in got]) for lane, got in rows.items()}


def test_two_overlapping_groups_get_the_serial_batcher_s_rows_bit_for_bit(tiny):
    """The device's program order carries the pools from a step to the one
    launched behind it: 32 rounds of two groups whose steps overlap, with a
    prompt admitted in between, give every lane the rows that the same steps
    give one at a time, each awaited before the next."""
    serial = run(_two_groups(tiny, overlap=False))
    overlapped = run(_two_groups(tiny, overlap=True))
    assert sorted(serial) == sorted(overlapped) and len(serial) == 5
    for lane in serial:
        assert serial[lane].shape == overlapped[lane].shape
        assert np.array_equal(serial[lane], overlapped[lane]), lane


def test_a_launch_fills_the_buffer_the_step_in_flight_does_not_read(tiny):
    async def main():
        async with _rig(tiny, 2) as rig:
            batcher = rig.batcher
            a, b = await batcher.acquire_lane(), await batcher.acquire_lane()
            first = _steps(rig, (a,), 1, 0)
            await _until(lambda: batcher._flights and batcher._flights[0].out is not None)
            fed = batcher._lanes_in[batcher._lanes_turn].copy()
            second = _steps(rig, (b,), 2, 0)
            await _until(lambda: len(batcher._flights) == 2 and batcher._flights[1].out is not None)
            assert np.array_equal(batcher._lanes_in[batcher._lanes_turn ^ 1], fed)  # the first step's rows are as it was fed
            assert not np.array_equal(batcher._lanes_in[batcher._lanes_turn], fed)
            rig.release.set()
            await asyncio.gather(first, second)
            batcher.release_lane(a)
            assert not batcher._lanes_rows[:, a].any()  # a released lane's row is zeroed in both

    run(main())


# ------------------------------------------------------------ (b) the rule with a step in flight


def _flight(batcher, lanes, end_eta, loop=None):
    flight = _StepInFlight([(lane, None, 0, None, 0) for lane in lanes], 0, loop, False, end_eta)
    flight.out = object()  # its launch has returned
    return flight


def _by_hand(tiny, pending, flying_ms, out_ms=(), *, step_ms=20.0, now=1000.0, ends_in_ms=1.0):
    """A batcher whose state is set by hand (tests/test_gather.py
    ``test_gather_rule``): ``pending`` lanes ready, a step in flight that ends
    in ``ends_in_ms`` and carries a lane for each of ``flying_ms`` (its usual
    return, None: no history), and a lane out for each of ``out_ms`` (due in
    as many ms)."""
    backend, _cfg = tiny
    batcher = DecodeBatcher(backend, backend.memory_cache, PriorityTaskQueue(), n_lanes=32)
    batcher._step_s = step_ms / 1e3
    batcher._pending = [(100 + i, None, 0, None, 0) for i in range(pending)]
    flying = list(range(len(flying_ms)))
    for lane, usual in zip(flying, flying_ms):
        returns = [] if usual is None else [ms / 1e3 for ms in (usual if isinstance(usual, list) else [usual])]
        batcher._returns[lane] = _LaneReturn(returns=collections.deque(returns, maxlen=5))
    for lane, due in enumerate(out_ms, len(flying)):
        back = batcher._returns[lane] = _LaneReturn(returns=collections.deque([(due + 1) / 1e3], maxlen=5))
        back.reply_sent(now - 1e-3)
    return batcher, _flight(batcher, flying, now + ends_in_ms / 1e3), now


RULE_CASES = [
    # (pending, in flight: usual returns ms, out: due in ms, waits for, pays ms)
    # a straggler behind seven lanes in flight that come back 3 ms after a reply: S x 7/8 pays, as at the step's end
    (1, [3] * 7, [], 7, 17.5),
    # two groups of four whose clients are as far away as a step is long: nobody to wait for, the groups take turns
    (4, [15] * 4, [], 0, None),
    # two groups of four whose clients are near: the group in flight is worth waiting for, and the groups merge
    (4, [3] * 4, [], 4, 10.0),
    # lanes in flight that have never come back are not predicted
    (1, [None] * 7, [], 0, None),
    # nor those whose usual return is a step or more, or was once of late
    (1, [25] * 7, [], 0, None),
    (1, [[3, 3, 25, 3, 3]] * 7, [], 0, None),
    # a lane that is out and due counts beside those in flight: 1 + 3 lanes, S x 4/6
    (2, [3] * 3, [2], 4, 20 * 4 / 6),
    # the near one is worth the wait, the three in flight behind slow clients are not
    (1, [19] * 3, [2], 1, 10.0),
    # nothing pending: nothing to hold back
    (0, [3] * 7, [], 0, None),
]


@pytest.mark.parametrize("pending, flying_ms, out_ms, waits_for, pays_ms", RULE_CASES)
def test_the_rule_counts_the_lanes_in_flight_as_expected(tiny, pending, flying_ms, out_ms, waits_for, pays_ms):
    batcher, flight, now = _by_hand(tiny, pending, flying_ms, out_ms)
    until, lanes = batcher._gather_until(now, flight)
    assert len(lanes) == waits_for
    assert until == (None if pays_ms is None else pytest.approx(now + pays_ms / 1e3))
    # without the step in flight only the lanes that are out are weighed: what _gather asks once it is home
    assert len(batcher._gather_until(now)[1]) == (min(len(out_ms), waits_for) if pays_ms else 0)


@pytest.mark.parametrize(
    "lead_ms, chunk, generating, paged, pays_ms",
    [
        (0.0, False, False, True, 10.0),  # no launch measured yet: a step's wall, as ever
        (5.0, False, False, True, 7.5),  # a plain step: the late lanes' launch is hidden, they sit out 20 - 5 on the device
        (15.0, False, False, True, 7.5),  # ... and never less than a lead: the compute thread launches one step at a time
        (5.0, True, False, True, 10.0),  # a tick with a prompt chunk starts with nothing in flight: a whole wall
        (5.0, False, True, True, 10.0),  # and so does one with a generating lane
        (5.0, False, False, False, 10.0),  # the dense pool's step is one body
    ],
)
def test_what_a_late_lane_sits_out_is_the_step_s_time_on_the_device(tiny, lead_ms, chunk, generating, paged, pays_ms):
    """N ready, M expected, S = 20 ms: waiting pays while w < sat out x M / (N + M), and what a lane that misses a plain
    decode step of the paged pool sits out is the step's wall less a launch's lead, because its own step is launched
    behind. Four ready and four out due in 3 ms, with enough ready work of the other kinds to keep N + M at 8."""
    backend, _cfg = tiny
    batcher = DecodeBatcher(backend, backend.memory_cache, PriorityTaskQueue(), n_lanes=16, page_size=16 if paged else None)
    batcher._step_s, batcher._lead_s, now = 0.020, lead_ms / 1e3, 1000.0
    batcher._pending = [(100 + i, None, 0, None, 0) for i in range(4 - chunk - generating)]
    batcher._prefill_queue = [object()] if chunk else []
    batcher._gen_states = {200: object()} if generating else {}
    for lane in range(4):
        back = batcher._returns[lane] = _LaneReturn(returns=collections.deque([0.004], maxlen=5))
        back.reply_sent(now - 0.001)
    until, lanes = batcher._gather_until(now)
    assert sorted(lanes) == [0, 1, 2, 3] and until == pytest.approx(now + pays_ms / 1e3)


def test_a_step_that_is_overdue_ends_now_for_the_rule(tiny):
    batcher, flight, now = _by_hand(tiny, 1, [3] * 7, ends_in_ms=-5.0)
    until, lanes = batcher._gather_until(now, flight)
    assert len(lanes) == 7 and until == pytest.approx(now + 17.5e-3)  # due 3 ms from now, not 2 ms ago


@pytest.mark.parametrize("case", ["straggler", "two groups", "a prompt is admitted", "a lane generates", "two in flight", "launch not back"])
def test_start_behind_asks_the_rule_and_starts_only_plain_steps(tiny, case):
    """``_start_behind`` with a step that is due (its end less the lead has
    passed): a straggler whose seven are in flight is held back as it would
    be at the step's end, and goes on waiting when woken; four lanes behind
    four far clients start at once; and nothing starts behind a step while a
    prompt chunk or a generating lane waits, behind two steps, or behind a
    step whose launch has not returned."""

    async def main():
        import time

        starts = case not in ("straggler",)
        batcher, flight, _now = _by_hand(tiny, *((1, [3] * 7) if case == "straggler" else (4, [15] * 4)), now=time.perf_counter())
        flight.end_eta = time.perf_counter()  # due: whatever the lead
        batcher._flights = [flight]
        if case == "a prompt is admitted":
            batcher._prefill_queue, starts = [object()], False
        elif case == "a lane generates":
            batcher._gen_states, starts = {9: object()}, False
        elif case == "two in flight":
            batcher._flights, starts = [flight, flight], False
        elif case == "launch not back":
            flight.out, starts = None, False
        asked = asyncio.ensure_future(batcher._start_behind())
        for _ in range(10):
            await asyncio.sleep(0)
        if starts:
            assert asked.done() and asked.result() is True
            return
        assert not asked.done()  # waiting: for the lanes in flight, or for a step to come home
        batcher._gather_wake.set()  # an arrival, a step home
        async with asyncio.timeout(WAIT):
            assert await asked is False  # look again

    run(main())


def test_a_straggler_is_not_launched_alone_behind_its_seven(tiny):
    """Live: eight lanes whose clients come back at once ride one step; when
    seven are in flight and the eighth comes late, its step is not started
    behind theirs (the rule at that step's end would wait for the seven), and
    all eight ride the next step together."""

    async def main():
        async with _rig(tiny, 8) as rig:
            batcher = rig.batcher
            lanes = [await batcher.acquire_lane() for _ in range(8)]
            rig.release.set()  # rounds that come home at once, so that every lane has returns on record
            for r in range(3):
                await _steps(rig, lanes, 10 * r, r)
            await steps_booked(batcher)
            batcher._step_s = 10.0  # a step far longer than any return here, however busy the host ...
            batcher._note_step_wall = lambda duration: None  # ... and it stays so
            rig.next_round()
            seven = _steps(rig, lanes[:7], 40, 3)
            await _until(lambda: batcher._flights and batcher._flights[0].out is not None)
            batcher._flights[0].end_eta = 0.0  # due
            straggler = _steps(rig, lanes[7:], 40, 3)
            for _ in range(20):
                await asyncio.sleep(0)
            launched = len(rig.events)
            assert len(batcher._flights) == 1 and len(batcher._pending) == 1 and rig.events[-1] == ("launch", tuple(sorted(lanes[:7])))
            rig.release.set()
            await seven  # home: the gather now holds the straggler for the seven, who come back at once
            back = _steps(rig, lanes[:7], 50, 4)
            await straggler
            await back
            assert [event for event in rig.events[launched:] if event[0] == "launch"] == [("launch", tuple(sorted(lanes)))]
            await steps_booked(batcher)
            assert batcher.stats["overlapped_steps"] == 0 and batcher.stats["gather_joined"] >= 7

    run(main())


# ------------------------------------------------------------ (c) a reset with two steps in flight


def test_a_pool_reset_with_two_steps_in_flight_fails_both_and_leaves_zeros(tiny):
    async def main():
        async with _rig(tiny, 4) as rig:
            batcher = rig.batcher
            lanes = [await batcher.acquire_lane() for _ in range(4)]
            for lane in lanes:
                await batcher.prefill_lane(lane, _hidden(rig.cfg, lane, 4), 0)
            assert any(np.asarray(buffer).any() for buffer in batcher._buffers())
            before = dict(batcher.stats)
            first = _steps(rig, lanes[:2], 10, 4)
            await _until(lambda: batcher._flights and batcher._flights[0].out is not None)
            second = _steps(rig, lanes[2:], 10, 4)
            await _until(lambda: len(batcher._flights) == 2 and batcher._flights[1].out is not None)
            for buffer in batcher._buffers():  # a device failure that consumed the donated pool
                buffer.delete()
            batcher._maybe_reset_pool()
            rig.release.set()
            for group in (first, second):
                with pytest.raises(AllocationFailed, match="reset while this batched step ran"):
                    await group
            assert all(isinstance(fut.exception(), AllocationFailed) for group in (first, second) for fut in group._children)
            await steps_booked(batcher)
            assert not batcher._flights and not batcher._aloft and not batcher._returns
            assert all(not np.asarray(buffer).any() for buffer in batcher._buffers())  # the zeroed pool stayed in
            assert batcher.stats["batched_steps"] == before["batched_steps"]  # neither step is counted
            assert batcher.stats["post_s"] > before["post_s"]  # their time is
            with pytest.raises(AllocationFailed, match="pool was reset"):
                await batcher.step(lanes[0], _hidden(rig.cfg, 1), 5)

    run(main())


def test_a_step_that_fails_on_its_way_home_resets_the_pool_it_wrote(tiny):
    """A launched step's results are the pool from its launch on: when its
    rows then fail to come, the pool holds what no lane wrote, and the lanes
    of every step in flight fail."""

    async def main():
        async with _rig(tiny, 2) as rig:
            batcher = rig.batcher
            a, b = await batcher.acquire_lane(), await batcher.acquire_lane()
            generation = batcher._generation
            first = _steps(rig, (a,), 1, 0)
            await _until(lambda: batcher._flights and batcher._flights[0].out is not None)
            second = _steps(rig, (b,), 2, 0)
            await _until(lambda: len(batcher._flights) == 2 and batcher._flights[1].out is not None)
            rig.fail_next = True  # the first step's rows, which the readback waits for first
            rig.release.set()
            with pytest.raises(RuntimeError, match="simulated device failure"):
                await first
            with pytest.raises(AllocationFailed, match="reset while this batched step ran"):
                await second
            assert batcher._generation == generation + 1
            await steps_booked(batcher)
            assert not batcher._aloft and all(not np.asarray(buffer).any() for buffer in batcher._buffers())

    run(main())


# ------------------------------------------------------------ (d) the clocks


def test_the_eight_clocks_tile_the_compute_thread_s_wall_with_steps_overlapping(tiny):
    async def main():
        async with _rig(tiny, 4) as rig:
            batcher = rig.batcher
            lanes = [await batcher.acquire_lane() for _ in range(4)]
            one, two = lanes[:2], lanes[2:]
            for lane in lanes:
                await batcher.prefill_lane(lane, _hidden(rig.cfg, lane, 3), 0)
            before, since = dict(batcher.stats), batcher._last_step_end[0]
            for r in range(12):
                rig.next_round()
                first = _steps(rig, one, 10 * r, 3 + r)
                await _until(lambda: batcher._flights and batcher._flights[0].out is not None)
                if r % 3 == 2:  # one round in three the second group comes once the first is home: nothing overlaps
                    rig.release.set()
                    await first
                second = _steps(rig, two, 10 * r, 3 + r)
                if r % 3 != 2:
                    await _until(lambda: len(batcher._flights) == 2 and batcher._flights[1].out is not None)
                    await asyncio.sleep(0.002)  # both in flight and nothing to run: one stretch of wait_s, not two
                    rig.release.set()
                await asyncio.gather(first, second)
                await asyncio.sleep(0.001)  # the lanes are out
            await steps_booked(batcher)
            delta = {key: batcher.stats[key] - before[key] for key in before if isinstance(before[key], (int, float))}
            assert delta["batched_steps"] == 24 and delta["overlapped_steps"] == 8
            tiled = sum(delta[key] for key in TILES)
            wall = batcher._last_step_end[0] - since
            assert tiled == pytest.approx(wall, rel=0.005), (tiled, wall, {key: delta[key] for key in TILES})
            assert all(delta[key] > 0 for key in ("assemble_s", "dispatch_s", "wait_s", "post_s", "lanes_out_s"))
            assert delta["wait_s"] >= 8 * 0.002  # the stretches with two in flight, counted once
            assert delta["wait_s"] < wall

    run(main())


# ------------------------------------------------------------ (e) rows before bookkeeping


def test_a_reply_leaves_before_the_step_is_booked_and_nothing_of_the_booking_is_lost(tiny):
    async def main():
        async with _rig(tiny, 2, hold=False) as rig:
            batcher = rig.batcher
            a, b = await batcher.acquire_lane(), await batcher.acquire_lane()
            for lane in (a, b):
                await batcher.prefill_lane(lane, _hidden(rig.cfg, lane, 3), 0)
            await steps_booked(batcher)
            book, booked = threading.Event(), batcher._finish_batch

            def held_back(flight):
                assert book.wait(WAIT)
                booked(flight)

            batcher._finish_batch = held_back
            before = dict(batcher.stats)
            rows = await _steps(rig, (a, b), 5, 3)
            # the lanes have their rows and what a reply carries of the step ...
            assert all(row.shape == (1, 1, rig.cfg.hidden_size) for row in rows)
            timing = batcher.pop_step_timing(a)
            assert timing["variant"] == "paged" and timing["compute_s"] > 0 and timing["queue_s"] >= 0 and "replied" in timing
            assert batcher._returns[a].replied is not None
            # ... the event loop's own counters of the reply have moved, and not one of the compute thread's
            assert batcher.stats["reply_steps"] == before["reply_steps"] + 1
            moved_later = ("batched_steps", "batched_tokens", "post_s", "stream_bytes_in", "stream_bytes_out", "attn_pages_gathered", "attn_pages_tabled")
            assert all(batcher.stats[key] == before[key] for key in moved_later)
            walls = len(batcher._step_walls), batcher._step_s
            book.set()
            await steps_booked(batcher)
            # one finish later they are all there
            assert batcher.stats["batched_steps"] == before["batched_steps"] + 1
            assert batcher.stats["batched_tokens"] == before["batched_tokens"] + 2
            assert all(batcher.stats[key] > before[key] for key in moved_later)
            assert (len(batcher._step_walls), batcher._step_s) != walls  # and the rule's S took the step's wall
            assert batcher.stats["overlapped_steps"] == before["overlapped_steps"]
            assert rig.events[-2:] == [("launch", tuple(sorted((a, b)))), ("finish", (a, b))]

    run(main())


# ------------------------------------------------------------ close with a step on its way


@pytest.mark.parametrize("launch", ["queued", "in flight"])
def test_a_step_started_before_close_still_brings_its_rows_home(tiny, launch):
    """``close`` with a step in flight: the readback thread stays until that
    step is home, its lanes get their rows, and then the thread is let go.
    With the launch still queued on the compute queue, the pool is gone when
    it runs: its lanes are failed loudly, and none waits for ever."""

    async def main():
        async with _rig(tiny, 2) as rig:
            batcher = rig.batcher
            a, b = await batcher.acquire_lane(), await batcher.acquire_lane()
            busy, go = threading.Event(), threading.Event()
            if launch == "queued":  # the compute thread is held, so the launch sits in its queue

                def hold_the_thread():
                    busy.set()
                    assert go.wait(WAIT)

                batcher.queue.put(hold_the_thread, priority=0.0)
                await asyncio.get_running_loop().run_in_executor(None, busy.wait)
            rows = _steps(rig, (a, b), 20, 0)
            if launch == "queued":
                await _until(lambda: batcher._flights)
                assert rig.events == []
            else:
                await _until(lambda: batcher._flights and batcher._flights[0].out is not None)
            thread = batcher._readback_thread
            await batcher.close()
            assert thread.is_alive() and batcher._readback_thread is thread  # a step is on its way: not yet
            go.set()
            rig.release.set()
            if launch == "queued":
                with pytest.raises(AllocationFailed, match="shutting down"):
                    await rows
                assert rig.events == []
            else:
                out = await rows
                assert all(row.shape == (1, 1, rig.cfg.hidden_size) and np.isfinite(row).all() for row in out)
            assert not batcher._flights and batcher._readback_thread is None
            await asyncio.get_running_loop().run_in_executor(None, thread.join, WAIT)
            assert not thread.is_alive()

    run(main())

