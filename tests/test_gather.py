"""The gather in front of a batched step (server/batching.py ``_gather``):
before the flush loop starts a step it waits for the decode lanes that are
predictably on their way back, as long as that wait costs the ready lanes
less than the step the returning lanes would otherwise sit out
(w < S x M / (N + M), every term measured). Without it lanes whose clients
answer within a few milliseconds settle into groups that take turns, and a
step of eight lanes' width carries four tokens.

The cases that drive a live batcher run a real ``DecodeBatcher`` on the tiny
two-block backend whose step programs are wrapped to take a set time (the
``slow`` pattern of tests/test_mixed_batching.py); clients are coroutines that
come back after a set delay. The cases that test the RULE (``_gather_until``,
which takes the time) hand it times they choose, on a clock they step
(``_loop_on_a_stepped_clock``): no sleep, nothing a busy host can stretch."""

import asyncio
import collections
import contextlib
import random
import time
import types

import numpy as np
import pytest

from petals_tpu.server.batching import DecodeBatcher, _LaneReturn
from petals_tpu.server.memory_cache import AllocationFailed
from petals_tpu.server.task_queue import PriorityTaskQueue
from tests.test_mixed_batching import _hidden, _tiny_backend
from tests.utils import make_tiny_llama, steps_booked

pytestmark = pytest.mark.mixed

GATHER_KEYS = ("gather_waits", "gather_wait_s", "gather_joined", "gather_missed")


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return _tiny_backend(make_tiny_llama(str(tmp_path_factory.mktemp("models"))))


def run(coro):
    return asyncio.run(coro)


@contextlib.asynccontextmanager
async def _rig(tiny, n_lanes, step_s):
    """A batcher whose decode and mixed steps take ``step_s`` seconds, with
    both programs compiled (on a lane that is released again, so that no lane
    starts with a history) and every ride on record: (kind, start, lanes)."""
    backend, cfg = tiny
    queue = PriorityTaskQueue()
    queue.start()
    batcher = DecodeBatcher(
        backend, backend.memory_cache, queue, n_lanes=n_lanes, max_length=64, page_size=16
    )
    rig = types.SimpleNamespace(batcher=batcher, cfg=cfg, rides=[], step_s=step_s)

    def slowed(kind, fast):
        def slow(hidden, kv, positions, *args, **kwargs):
            start = time.perf_counter()
            lanes = np.flatnonzero(np.asarray(positions) < batcher.max_length)
            rig.rides.append((kind, start, tuple(int(lane) for lane in lanes)))
            out = fast(hidden, kv, positions, *args, **kwargs)
            time.sleep(max(rig.step_s - (time.perf_counter() - start), 0.0))
            return out

        return slow

    try:
        warm = await batcher.acquire_lane()
        await batcher.prefill_lane(warm, _hidden(cfg, 1, 5), 0)
        await batcher.step(warm, _hidden(cfg, 2), 5)
        batcher.release_lane(warm)
        await steps_booked(batcher)  # the warm-up's counters are all in before a test copies them
        backend.paged_decode_step = slowed("decode", backend.paged_decode_step)
        backend.paged_mixed_step = slowed("mixed", backend.paged_mixed_step)
        yield rig
    finally:
        backend.__dict__.pop("paged_decode_step", None)
        backend.__dict__.pop("paged_mixed_step", None)
        await batcher.close()
        queue.shutdown()


async def _client(rig, lane, rounds, back_after, *, pos0=0, start_after=0.0, on_reply=None):
    """One session's decode loop: a step, then ``back_after`` seconds on the
    way to the client and back. ``on_reply(i)`` runs as reply i arrives."""
    await asyncio.sleep(start_after)
    for i in range(rounds):
        await rig.batcher.step(lane, _hidden(rig.cfg, 97 * lane + i), pos0 + i)
        if on_reply is not None:
            on_reply(i)
        await asyncio.sleep(back_after)


def _gather_stats(batcher):
    return {key: batcher.stats[key] for key in GATHER_KEYS}


def _delta(batcher, before):
    return {key: batcher.stats[key] - before[key] for key in before}


# --------------------------------------------------------------------- the rule


def test_gather_counters_are_in_stats_from_construction(tiny):
    backend, _cfg = tiny
    batcher = DecodeBatcher(backend, backend.memory_cache, PriorityTaskQueue(), n_lanes=2)
    assert _gather_stats(batcher) == {
        "gather_waits": 0, "gather_wait_s": 0.0, "gather_joined": 0, "gather_missed": 0,
    }
    # and the three clocks that tile the time between two bodies with gather_wait_s (PR 37)
    assert [batcher.stats[key] for key in ("lanes_out_s", "no_demand_s", "handoff_s")] == [0.0, 0.0, 0.0]


@pytest.mark.parametrize(
    "pending, generating, chunk, etas_ms, waits_for, pays_ms",
    [
        # two groups of four: the four that are back wait up to S/2 for the other four
        (4, 0, False, [2, 2.5, 3, 3.5], 4, 10),
        # one straggler behind seven ready lanes: at most S/8
        (7, 0, False, [2], 1, 2.5),
        (7, 0, False, [3], 0, None),
        # generating lanes plus one stepped lane: bounded by S/(N+1)
        (0, 3, False, [4], 1, 5),
        (0, 3, False, [6], 0, None),
        (0, 1, False, [9], 1, 10),
        # a prompt chunk alone with four lanes 2 ms away waits for them
        (0, 0, True, [2, 2, 2, 2], 4, 16),
        # the near ones are worth the wait, the far one is not
        (1, 0, False, [2, 3, 16], 2, 20 * 2 / 3),
        # a lane that usually returns in 3 ms and is 1 ms late is still expected ...
        (1, 0, False, [(3, -1)], 1, 10),
        # ... late by more than those 3 ms it is not: it has stopped, or thinks
        (1, 0, False, [(3, -4)], 0, None),
        # a lane whose usual return is a step or more (a hop of a chain, a slow
        # client) is never expected: not far off, not due this instant, not overdue
        (1, 0, False, [(25, 24)], 0, None),
        (1, 0, False, [(120, 2)], 0, None),
        (1, 0, False, [(120, -30)], 0, None),
        (3, 0, False, [(22, 1), (120, -1)], 0, None),
        # nor one that took a step or more once of late, whatever the mean says
        (1, 0, False, [([3, 3, 25, 3, 3], 2)], 0, None),
        (1, 0, False, [([3, 3, 15, 3, 3], 2)], 1, 10),
        # just under a step it is, where its arrival is near enough to pay
        (1, 0, False, [(19, 5)], 1, 10),
        (1, 0, False, [(19, 12)], 0, None),
        # nothing ready: nothing to hold back, whoever is expected
        (0, 0, False, [1, 2], 0, None),
    ],
)
def test_gather_rule(tiny, pending, generating, chunk, etas_ms, waits_for, pays_ms):
    """``_gather_until`` on a batcher whose state is set by hand, S = 20 ms:
    waiting pays while w < S x M / (N + M), and the bound itself is the time
    until which the expected lanes' coming still pays. Expected is a lane
    whose last returns were each under a step and which is not late by more
    than its usual return."""
    backend, _cfg = tiny
    batcher = DecodeBatcher(backend, backend.memory_cache, PriorityTaskQueue(), n_lanes=16)
    batcher._step_s = 0.020
    now = 1000.0
    batcher._pending = [(100 + i, None, 0, None, 0) for i in range(pending)]
    batcher._gen_states = {200 + i: object() for i in range(generating)}
    batcher._prefill_queue = [object()] if chunk else []
    for lane, eta in enumerate(etas_ms):  # (last returns, time to go) or the latter, 1 ms after the reply
        returns, eta = eta if isinstance(eta, tuple) else (eta + 1, eta)
        returns = [ms / 1e3 for ms in (returns if isinstance(returns, list) else [returns])]
        back = batcher._returns[lane] = _LaneReturn(returns=collections.deque(returns, maxlen=5))
        back.reply_sent(now + eta / 1e3 - sum(returns) / len(returns))
    batcher._returns[15] = _LaneReturn(replied=now - 0.001)  # out, never came back: no prediction
    until, lanes = batcher._gather_until(now)
    assert sorted(lanes) == list(range(waits_for))
    if pays_ms is None:
        assert until is None
    else:
        assert until == pytest.approx(now + pays_ms / 1e3)
    batcher._step_s = 0.0  # no step measured yet: no rule
    assert batcher._gather_until(now) == (None, [])


def test_one_slow_return_takes_a_lane_out_of_reach_for_five_rounds():
    back, now = _LaneReturn(), 0.0
    back.reply_sent(now)
    assert back.eta is None  # never came back: not predicted
    for sample in (0.003, 0.003, 0.5):
        back.came_back(now + sample)
        now += 1.0
        back.reply_sent(now)
    assert back.eta - now > 0.15  # the mean: far beyond any step
    for _ in range(4):
        back.came_back(now + 0.003)
        now += 1.0
        back.reply_sent(now)
        assert back.eta - now > 0.1 and not back.expected(now, 0.2)  # 0.5 s is in the window
    back.came_back(now + 0.003)
    back.reply_sent(now + 1.0)
    assert back.eta - (now + 1.0) == pytest.approx(0.003)  # the slow one has left the window
    assert back.expected(now + 1.0, 0.2) and back.expected(now + 1.0, 0.004)
    assert not back.expected(now + 1.0, 0.003)  # a step no longer than its return
    assert back.expected(now + 1.005, 0.2) and not back.expected(now + 1.007, 0.2)  # overdue by its own return


# ------------------------------------------------------------ (a) one step a round


@pytest.mark.parametrize("k", [2, 4, 8])
def test_lanes_that_return_fast_settle_into_one_step_a_round(tiny, k):
    """K clients that start out of phase and come back 2-6 ms after a reply,
    on a step of 80 ms: at the parent they settle into groups that take
    turns; with the gather every round is one step that carries all K."""

    async def main():
        async with _rig(tiny, k, 0.08) as rig:
            batcher = rig.batcher
            lanes = [await batcher.acquire_lane() for _ in range(k)]
            rounds = 16
            await asyncio.gather(*(
                _client(rig, lane, rounds, 0.002 + 0.0005 * i, start_after=0.03 * i)
                for i, lane in enumerate(lanes)
            ))
            await steps_booked(batcher)
            stats = batcher.stats
            assert stats["batched_tokens"] == k * rounds + 1  # + the warm-up's token
            # from the first step that carried all K to the one in which the
            # first client finished: every round one full step, no two groups
            riders = [ride[2] for ride in rig.rides]
            first_full = next(i for i, lanes_in in enumerate(riders) if len(lanes_in) == k)
            rode = collections.Counter()
            for last, lanes_in in enumerate(riders):
                rode.update(lanes_in)
                if max(rode.values()) == rounds:
                    break
            settled = riders[first_full : last + 1]
            assert len(settled) >= 6, riders
            # (a loaded test machine may hold a client up for longer than the
            # rule allows a straggler; at the parent no step at all is full)
            assert sum(len(lanes_in) == k for lanes_in in settled) >= 0.6 * len(settled), riders
            assert stats["batched_tokens"] / stats["batched_steps"] >= 0.6 * k, stats
            assert stats["gather_joined"] >= k - 1 and stats["gather_waits"] >= 1, stats
            assert stats["gather_wait_s"] < stats["gather_waits"] * rig.step_s

    run(main())


# ---------------------------------------------------- (b) slow returns: as today


def _loop_on_a_stepped_clock(batcher, step_s, clients):
    """The flush loop under the rule on a clock the test steps, no thread and no sleep: ``clients`` are ``(lane, its first
    request's time, the delays between a reply and the lane's next request)``; a request is booked as ``step()`` books it
    (``_count_return``, ``_enqueue``), ``_gather_until(now)`` is asked before every step with the time the clock shows, a
    step of ``step_s`` starts when it says start now (or when the time it gave runs out) and books its replies as the
    flush loop does. Returns ``(what the rule answered, the lanes each step carried)``."""
    batcher._step_s = step_s
    arrivals = sorted((first, lane, iter(delays)) for lane, first, delays in clients)
    asked, rides, now = [], [], 0.0
    while arrivals or batcher._pending:
        while arrivals and arrivals[0][0] <= now:
            at, lane, delays = arrivals.pop(0)
            back = batcher._returns.setdefault(lane, _LaneReturn())
            if back.replied is not None:
                back.came_back(at)
            batcher._pending.append((lane, delays, 0, None, 0))
        if not batcher._pending:
            now = arrivals[0][0]
            continue
        asked.append(batcher._gather_until(now))
        until = asked[-1][0]
        if until is not None and arrivals and arrivals[0][0] < until:
            now = arrivals[0][0]  # an arrival wakes the gather, which asks again
            continue
        now = (now if until is None else until) + step_s
        rides.append(tuple(lane for lane, *_ in batcher._pending))
        for lane, delays, *_ in batcher._pending:
            batcher._returns[lane].reply_sent(now)
            delay = next(delays, None)
            if delay is not None:
                arrivals.append((now + delay, lane, delays))
        arrivals.sort(key=lambda arrival: arrival[:2])
        batcher._pending = []
    return asked, rides


def test_a_lane_that_returns_slower_than_a_step_is_never_waited_for(tiny):
    """Two lanes of a server that is one hop of many: each comes back three
    steps after its reply. The rule finds nobody to wait for at any step's
    start, and the steps, their order and what rides them are what the loop
    without a gather (the parent's) gives. On a clock the test steps: the
    rule is what is tested, and a busy host cannot stretch a return."""
    backend, _cfg = tiny
    batcher = DecodeBatcher(backend, backend.memory_cache, PriorityTaskQueue(), n_lanes=2)
    a, b = 0, 1
    asked, rides = _loop_on_a_stepped_clock(batcher, 0.03, [(a, 0.0, [0.09] * 5), (b, 0.06, [0.09] * 5)])
    assert rides == [(a,), (b,)] * 6
    assert asked == [(None, [])] * 12
    assert all(len(batcher._returns[lane].returns) == 5 and min(batcher._returns[lane].returns) >= 0.09 - 1e-9 for lane in (a, b))


def test_slow_returns_with_jitter_are_never_waited_for(tiny):
    """Four lanes of a hop of an internet chain: each comes back about four
    steps after its reply, give or take one (gauss(120 ms, 30 ms) on a step
    of 30 ms), so at any start some lane is due or overdue. The rule finds
    nobody to wait for at any of them: the gather never suspends, which makes
    the loop the parent's, step for step. On a clock the test steps, with the
    returns the seeds give: the rule is what is tested."""
    backend, _cfg = tiny
    batcher = DecodeBatcher(backend, backend.memory_cache, PriorityTaskQueue(), n_lanes=4)
    clients = []
    for lane in range(4):
        rng = random.Random(lane)
        first = rng.uniform(0, 0.12)
        clients.append((lane, first, [max(rng.gauss(0.12, 0.03), 0.04) for _ in range(15)]))
    asked, rides = _loop_on_a_stepped_clock(batcher, 0.03, clients)
    assert all(len(batcher._returns[lane].returns) == 5 for lane in range(4))  # on record all the same
    assert len(asked) == len(rides) and sum(map(len, rides)) == 4 * 16  # asked before every step, and once
    assert all(answer == (None, []) for answer in asked), [answer for answer in asked if answer[1]]
    assert len(rides) > 16  # the lanes never settled into one step a round: each start found some of them out
    assert [batcher.stats[key] for key in GATHER_KEYS] == [0, 0.0, 0, 0]


# ------------------------------------------------ (c) a lane that stops coming back


def test_a_lane_that_stops_coming_back_costs_one_wait_under_one_step(tiny):
    async def main():
        async with _rig(tiny, 2, 0.08) as rig:
            batcher = rig.batcher
            a, b = await batcher.acquire_lane(), await batcher.acquire_lane()
            marks = {}

            def b_reply(i):
                if i == 7:  # b's last reply: it will not come back
                    marks["gone"] = _gather_stats(batcher)

            def a_reply(_i):
                if "gone" in marks:
                    marks["replies"] = marks.get("replies", 0) + 1
                    if marks["replies"] == 3:
                        marks["later"] = _gather_stats(batcher)

            await asyncio.gather(
                _client(rig, a, 16, 0.004, on_reply=a_reply),
                _client(rig, b, 8, 0.005, start_after=0.03, on_reply=b_reply),
            )
            cost = _delta(batcher, marks["gone"])
            assert cost["gather_missed"] == 1 and cost["gather_waits"] == 1, (cost, rig.rides)
            assert 0 < cost["gather_wait_s"] < rig.step_s, cost
            after = _delta(batcher, marks["later"])
            assert after["gather_waits"] == 0 and after["gather_missed"] == 0, after

    run(main())


# ------------------------------------- (d, e) release, close, reset during a gather


async def _a_waits_for_b(rig):
    """Lanes a and b in one step a round, b coming back 80 ms after a reply
    and a 4 ms, on a step of 200 ms; then b stays away. Returns once a's next
    step is pending and the gather is waiting for b (for up to 100 ms)."""
    batcher = rig.batcher
    a, b = await batcher.acquire_lane(), await batcher.acquire_lane()
    await asyncio.gather(_client(rig, a, 6, 0.004), _client(rig, b, 6, 0.08))
    # both clients are back (a's 4 ms and b's 80 ms have passed); a goes on
    assert batcher._returns[b].eta is not None
    rides = len(rig.rides)
    # b's reply went out ~80 ms ago: move it to now, as if the round had just ended
    batcher._returns[b].reply_sent(time.perf_counter())
    step = asyncio.create_task(batcher.step(a, _hidden(rig.cfg, 7), 6))
    await asyncio.sleep(0.005)
    assert len(rig.rides) == rides and not step.done()  # held back: the gather waits for b
    return a, b, step, rides


def test_release_during_a_gather_ends_the_wait_at_once(tiny):
    async def main():
        async with _rig(tiny, 2, 0.2) as rig:
            batcher = rig.batcher
            a, b, step, rides = await _a_waits_for_b(rig)
            before = _gather_stats(batcher)
            would_wait_until = batcher._returns[b].eta
            released = time.perf_counter()
            batcher.release_lane(b)
            await step
            started = rig.rides[rides][1]
            assert rig.rides[rides][2] == (a,)
            # not when b was due (75 ms later), let alone when the wait would have run out (100 ms)
            assert started < would_wait_until, (started - released, would_wait_until - released)
            cost = _delta(batcher, before)
            assert cost["gather_waits"] == 1 and cost["gather_missed"] == 0, cost

    run(main())


def test_close_during_a_gather_does_not_hang(tiny):
    async def main():
        async with _rig(tiny, 2, 0.2) as rig:
            _a, _b, step, rides = await _a_waits_for_b(rig)
            closed = time.perf_counter()
            await rig.batcher.close()
            with pytest.raises(Exception) as failure:  # the pool is gone: the step fails, as at the parent
                await asyncio.wait_for(step, 5)
            assert not isinstance(failure.value, asyncio.TimeoutError)
            assert time.perf_counter() - closed < 0.08  # and not after the 95 ms the gather had left
            assert len(rig.rides) == rides  # nothing ran against the closed pool

    run(main())


def test_pool_reset_during_a_gather_fails_the_stale_entries(tiny):
    async def main():
        async with _rig(tiny, 2, 0.2) as rig:
            batcher = rig.batcher
            _a, _b, step, rides = await _a_waits_for_b(rig)
            reset = time.perf_counter()
            for buffer in batcher._buffers():  # a device failure that consumed the donated pool
                buffer.delete()
            batcher._maybe_reset_pool()
            with pytest.raises(AllocationFailed, match="reset while this step was pending"):
                await asyncio.wait_for(step, 5)
            assert time.perf_counter() - reset < 0.08  # the gather had 95 ms left
            assert len(rig.rides) == rides and not batcher._returns  # no stale step ran; nobody is expected

    run(main())


def test_a_lane_released_while_its_step_runs_leaves_no_history(tiny):
    """The reply of a step in flight does not put a released lane back on
    record: the next tenant's first return would be reckoned from the
    departed tenant's reply, and keep the lane out of five rounds' gathers."""

    async def main():
        async with _rig(tiny, 2, 0.05) as rig:
            batcher = rig.batcher
            a = await batcher.acquire_lane()
            await batcher.step(a, _hidden(rig.cfg, 1), 0)
            assert batcher._returns[a].replied is not None
            step = asyncio.create_task(batcher.step(a, _hidden(rig.cfg, 2), 1))
            await asyncio.sleep(0.02)
            assert len(rig.rides) == 2 and not step.done()  # in flight
            batcher.release_lane(a)
            await step
            assert a not in batcher._returns
            assert a in [await batcher.acquire_lane() for _ in range(2)]  # the next tenant: no history
            await batcher.step(a, _hidden(rig.cfg, 3), 0)
            assert not batcher._returns[a].returns

    run(main())


# ----------------------------------------------------------------- (f) a lone lane


def test_a_lone_lane_never_waits(tiny):
    async def main():
        async with _rig(tiny, 2, 0.03) as rig:
            batcher = rig.batcher
            a, b = await batcher.acquire_lane(), await batcher.acquire_lane()
            await batcher.step(b, _hidden(rig.cfg, 3), 0)  # b: one reply, never back: no prediction
            await _client(rig, a, 8, 0.003)
            assert _gather_stats(batcher) == {
                "gather_waits": 0, "gather_wait_s": 0.0, "gather_joined": 0, "gather_missed": 0,
            }
            assert [ride[2] for ride in rig.rides] == [(b,)] + [(a,)] * 8
            back = list(batcher._returns[a].returns)
            assert len(back) == 5 and all(w > 0.002 for w in back), back  # its way back is on record all the same

    run(main())


# ------------------------------------------- (g) a prompt chunk and lanes expected


def test_a_prompt_chunk_rides_one_mixed_step_with_the_lanes_expected(tiny):
    """Two lanes decode in one step a round and a prompt is admitted just as
    their replies leave: at the parent its chunk would start alone at once
    and both lanes would sit that step out; the gather holds the chunk for
    the 5 ms they take to come back, and all three ride one mixed step."""

    async def main():
        async with _rig(tiny, 3, 0.08) as rig:
            batcher = rig.batcher
            a, b, c = [await batcher.acquire_lane() for _ in range(3)]
            prompt = []

            def on_reply(i):
                if i == 7:
                    prompt.append(asyncio.create_task(
                        batcher.prefill_lane(c, _hidden(rig.cfg, 5, 5), 0)
                    ))

            await asyncio.gather(
                _client(rig, a, 10, 0.005, on_reply=on_reply),
                _client(rig, b, 10, 0.005, start_after=0.03),
            )
            out = await prompt[0]
            assert out.shape == (1, 5, rig.cfg.hidden_size)
            mixed = [ride for ride in rig.rides if ride[0] == "mixed"]
            assert [ride[2] for ride in mixed] == [tuple(sorted((a, b)))], rig.rides
            assert batcher.stats["mixed_steps"] == 2  # the warm-up's and this one

    run(main())
