"""Priority scheduling of compute onto the device
(counterpart of reference src/petals/server/task_pool.py:29-177 +
task_prioritizer.py:6-20).

The reference moves tasks between 8 forked handler processes and one Runtime
process via mp.SimpleQueue + MPFuture + shared memory. A JAX server is a single
process whose device work is dispatched asynchronously by XLA, so the same
guarantees (inference preempts training, FIFO within a class, oversized-task
rejection) reduce to a heap consumed by one worker thread. The worker calls the
jitted step and blocks until the result is ready, keeping exactly one program
in flight — same single-compute-stream model as hivemind's Runtime, with the
asyncio loop staying free for network I/O.
"""

from __future__ import annotations

import asyncio
import heapq
import itertools
import threading
from typing import Any, Callable, Optional

from petals_tpu.utils.logging import get_logger

logger = get_logger(__name__)

PRIORITY_INFERENCE = 1.0
PRIORITY_TRAINING = 2.0  # forward/backward (reference task_prioritizer.py:6-20)
PRIORITY_BARRIER = 10.0  # quiesce sentinel: runs after everything pending


class TaskRejected(Exception):
    pass


class PriorityTaskQueue:
    """Submit callables with (priority, fifo) ordering; one worker thread runs them."""

    def __init__(self, max_task_size: Optional[int] = None, name: str = "compute"):
        self.max_task_size = max_task_size
        self.name = name
        self._heap: list = []
        self._counter = itertools.count()
        self._cv = threading.Condition()
        self._thread: Optional[threading.Thread] = None
        self._shutdown = False

    def start(self) -> None:
        assert self._thread is None, "already started"
        self._thread = threading.Thread(target=self._worker, name=f"ptu-{self.name}", daemon=True)
        self._thread.start()

    def put(self, run: Callable[[], Any], *, priority: float = PRIORITY_TRAINING, size: int = 0) -> None:
        """Queue ``run()`` for the compute thread and return (any thread): nobody waits for it, so ``run`` delivers its own
        result and failure. ``submit`` is this plus a future of the caller's loop."""
        if self.max_task_size is not None and size > self.max_task_size:
            raise TaskRejected(
                f"Task of size {size} exceeds queue limit {self.max_task_size}"
            )
        with self._cv:
            if self._shutdown:
                raise TaskRejected("Task queue is shut down")
            heapq.heappush(self._heap, (priority, next(self._counter), run))
            self._cv.notify()

    async def submit(
        self, fn: Callable[..., Any], *args, priority: float = PRIORITY_TRAINING, size: int = 0, **kwargs
    ) -> Any:
        """Run ``fn(*args, **kwargs)`` on the compute thread; lowest priority first."""
        loop = asyncio.get_running_loop()
        future: asyncio.Future = loop.create_future()

        def run():
            try:
                result = fn(*args, **kwargs)
            except BaseException as e:  # noqa: BLE001 — must cross the thread boundary
                loop.call_soon_threadsafe(_set_exc, future, e)
            else:
                loop.call_soon_threadsafe(_set_result, future, result)

        self.put(run, priority=priority, size=size)
        return await future

    def _worker(self) -> None:
        while True:
            with self._cv:
                while not self._heap and not self._shutdown:
                    self._cv.wait()
                if self._shutdown and not self._heap:
                    return
                _, _, run = heapq.heappop(self._heap)
            try:
                run()
            except Exception:  # a task that failed to deliver its own failure must not take the compute thread with it
                logger.exception("A task of the %s queue raised", self.name)

    def shutdown(self) -> None:
        with self._cv:
            self._shutdown = True
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=10)


def _set_result(future: asyncio.Future, result: Any) -> None:
    if not future.done():
        future.set_result(result)


def _set_exc(future: asyncio.Future, exc: BaseException) -> None:
    if not future.done():
        future.set_exception(exc)
