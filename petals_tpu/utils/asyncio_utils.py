"""Asyncio helpers (counterpart of reference src/petals/utils/asyncio.py).

``install_turn_clock`` times an event loop's turns (PR 54): the stretch between
one ``select()``'s return and the next one's call, in which the loop's one
thread runs callbacks and looks at no socket. A turn adds to three sums,
``loop_busy_s``, ``loop_busy_sq`` (the stretch squared) and ``loop_turns``, in
every dict attached to the clock: a server's ``batcher.stats``, the clock's own
``sums`` on a client. ``loop_busy_s`` over an elapsed time is how full the
thread is; a socket that becomes ready at a moment unrelated to the loop's
phase waits ``loop_busy_sq / (2 x elapsed)`` on average before it is looked at.
Every reading is ``time.perf_counter()``.
"""

from __future__ import annotations

import asyncio
import time
from collections import deque
from typing import AsyncIterator, Awaitable, Callable, Optional, TypeVar

T = TypeVar("T")

TURN_SUMS = ("loop_busy_s", "loop_busy_sq", "loop_turns")
TURN_SAMPLE_S = 0.1  # a sampling clock keeps its sums about this often
TURN_SAMPLES = 8192  # and this many of them: a quarter of an hour of a busy loop


class TurnClock:
    """One loop's turn clock: what ``install_turn_clock`` put around the
    selector's ``select``. A clock asked to sample keeps ``sums``, a dict of
    its own with the three sums, and ``samples``, a bounded deque of ``(time,
    loop_busy_s, loop_busy_sq, loop_turns)`` taken about every
    ``TURN_SAMPLE_S`` seconds at a ``select()``'s call, for a reader in a
    process that takes no marks of its own (a sleeping loop takes no sample
    and its sums stand still); any other has neither and adds to the dicts
    attached to it alone. The wrapper is a closure over locals: it runs
    every turn of a serial chain (~0.6 microseconds a turn and a dict)."""

    def __init__(self, selector, sample: bool):
        self.sums: Optional[dict] = {"loop_busy_s": 0.0, "loop_busy_sq": 0.0, "loop_turns": 0} if sample else None
        self.samples: Optional[deque] = deque(maxlen=TURN_SAMPLES) if sample else None
        self._sinks = sinks = [self.sums] if sample else []
        select, clock, own, samples = selector.select, time.perf_counter, self.sums, self.samples
        returned, sample_due = clock(), 0.0

        def timed_select(timeout=None):
            nonlocal returned, sample_due
            called = clock()
            busy = called - returned
            for sums in sinks:
                sums["loop_busy_s"] += busy
                sums["loop_busy_sq"] += busy * busy
                sums["loop_turns"] += 1
            if samples is not None and called >= sample_due:
                sample_due = called + TURN_SAMPLE_S
                samples.append((called, own["loop_busy_s"], own["loop_busy_sq"], own["loop_turns"]))
            events = select(timeout)
            returned = clock()
            return events

        selector.select = timed_select  # an instance attribute over the class's method

    def attach(self, sums: dict) -> None:
        """Add the turns from now on to ``sums`` too (its three keys are there already)."""
        if not any(sums is held for held in self._sinks):
            self._sinks.append(sums)

    def detach(self, sums: dict) -> None:
        self._sinks[:] = [held for held in self._sinks if held is not sums]


def install_turn_clock(loop: asyncio.AbstractEventLoop, *, sample: bool = False) -> Optional[TurnClock]:
    """The loop's turn clock, installed at the first call: two clock readings
    and three additions a turn, no timer and no wake-up of its own. None for a
    loop without a Python selector (another policy, another platform): such a
    loop runs as it did and its readers find nothing."""
    clock = getattr(loop, "_ptu_turn_clock", None)
    if clock is not None:
        return clock
    selector = getattr(loop, "_selector", None)
    if selector is None or not callable(getattr(selector, "select", None)):
        return None
    try:
        clock = TurnClock(selector, sample)
    except AttributeError:  # a selector that takes no attribute of its own
        return None
    loop._ptu_turn_clock = clock
    return clock


def turn_clock_of(loop: asyncio.AbstractEventLoop) -> Optional[TurnClock]:
    return getattr(loop, "_ptu_turn_clock", None)


def log_exception_callback(logger, what: str) -> Callable[["asyncio.Task"], None]:
    """Done-callback for fire-and-forget tasks: surface the exception that
    asyncio would otherwise only mention at GC time (if ever). Attach with
    ``task.add_done_callback(log_exception_callback(logger, "flush loop"))``
    and keep a strong reference to the task — the loop holds tasks weakly."""

    def _callback(task: "asyncio.Task") -> None:
        if task.cancelled():
            return
        exc = task.exception()  # also marks the exception as retrieved
        if exc is not None:
            logger.warning("background task %s failed: %r", what, exc)

    return _callback


async def shield_and_wait(task: Awaitable[T]) -> T:
    """Run ``task`` to completion even if the caller is cancelled; re-raise the
    cancellation afterwards (reference asyncio.py:73-90). Prevents half-applied
    state transitions (e.g. a cache allocation that would leak its lock)."""
    inner = asyncio.ensure_future(task)
    cancel_exc: Optional[asyncio.CancelledError] = None
    while True:
        try:
            result = await asyncio.shield(inner)
            break
        except asyncio.CancelledError as e:
            if inner.cancelled():
                raise
            cancel_exc = e  # remember cancellation, let the inner task finish
    if cancel_exc is not None:
        raise cancel_exc
    return result


async def aiter_with_timeout(iterator: AsyncIterator[T], timeout: Optional[float]) -> AsyncIterator[T]:
    """Yield items from an async iterator, raising TimeoutError if the next item
    takes longer than ``timeout`` seconds."""
    while True:
        try:
            item = await asyncio.wait_for(iterator.__anext__(), timeout=timeout)
        except StopAsyncIteration:
            break
        yield item


async def as_aiter(*items: T) -> AsyncIterator[T]:
    for item in items:
        yield item


async def iter_as_aiter(iterable) -> AsyncIterator:
    for item in iterable:
        yield item


def anext_compat(ait):
    return ait.__anext__()
