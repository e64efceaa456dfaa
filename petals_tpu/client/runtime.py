"""Background event loop for the synchronous client API.

The swarm stack (DHT, RPC, sessions) is asyncio; user-facing model classes are
synchronous like the reference's torch API. One daemon thread runs the loop;
sync methods submit coroutines to it."""

from __future__ import annotations

import asyncio
import threading
from typing import Awaitable, TypeVar

from petals_tpu.utils.asyncio_utils import install_turn_clock

T = TypeVar("T")


class SwarmRuntime:
    def __init__(self):
        self.loop = asyncio.new_event_loop()
        # how full this one thread is and how long a ready socket waits for it
        # (None on a loop without a Python selector); sampled, because a
        # client's process takes no marks
        self.turn_clock = install_turn_clock(self.loop, sample=True)
        self._thread = threading.Thread(target=self._run, name="ptu-client-loop", daemon=True)
        self._thread.start()

    def _run(self) -> None:
        asyncio.set_event_loop(self.loop)
        self.loop.run_forever()

    def run(self, coro: Awaitable[T], timeout: float = None) -> T:
        future = asyncio.run_coroutine_threadsafe(coro, self.loop)
        return future.result(timeout)

    def shutdown(self) -> None:
        self.loop.call_soon_threadsafe(self.loop.stop)
        self._thread.join(timeout=10)
