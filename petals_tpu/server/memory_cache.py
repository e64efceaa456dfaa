"""Server-wide KV-cache budget allocator, HBM edition
(counterpart of reference src/petals/server/memory_cache.py:26-225).

The reference spreads this across processes (shared-memory counters, mp.Pipe
handler->runtime protocol) because torch servers fork one process per
connection handler. A JAX/TPU server is one process that owns the device, so
the same contract collapses to asyncio:

- ``allocate_cache(*descriptors, timeout=...)`` — async context manager that
  reserves budget and yields integer handles; oversubscribed requests QUEUE
  (FIFO) until space frees or the timeout elapses (AllocationFailed).
- ``get_buffers(*handles)`` — compute-side access to the device buffers;
  buffers are created lazily (zeros in HBM) on first use and replaced
  functionally after each step via ``update_cache`` (XLA donation makes this
  in-place at the buffer level).

Handles survive across RPC calls so an inference session touches its KV by
integer id only — exactly the reference's cross-process contract, minus the
processes.
"""

from __future__ import annotations

import asyncio
import collections
import contextlib
import dataclasses
import time
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from petals_tpu import chaos
from petals_tpu.analysis.sanitizer import make_async_lock
from petals_tpu.data_structures import Handle
from petals_tpu.utils.logging import get_logger

logger = get_logger(__name__)


class AllocationFailed(Exception):
    pass


@dataclasses.dataclass(frozen=True)
class TensorDescriptor:
    shape: Tuple[int, ...]
    dtype: jnp.dtype
    sharding: object = None  # optional jax.sharding.Sharding (TP: heads split)

    @property
    def nbytes(self) -> int:
        return int(np.prod(self.shape)) * jnp.dtype(self.dtype).itemsize

    def make_zeros(self, device: Optional[jax.Device] = None) -> jax.Array:
        arr = jnp.zeros(self.shape, self.dtype)
        if self.sharding is not None:
            return jax.device_put(arr, self.sharding)
        return jax.device_put(arr, device) if device is not None else arr


class PageAllocator:
    """Page-grain free list + refcounts over ONE preallocated page pool.

    The paged KV cache (server/batching.py) budgets its whole page pool
    through MemoryCache ONCE at open; this allocator then hands out page
    INDICES on demand — admission costs one page, not max_length tokens, and
    lanes grow page-by-page. Refcounts make pages shareable: a block-table
    reference and a prefix-cache pin each count one, and a page with
    ``refs > 1`` must be forked (copy-on-write) before any write.

    Synchronous core, asyncio signalling: every mutation happens on the
    event loop (the batcher's table/refcount bookkeeping is loop-side, like
    its lane lists), and ``freed_event`` wakes allocation waiters when a
    page returns — the MemoryCache backpressure contract, at page grain.
    """

    def __init__(self, n_pages: int):
        assert n_pages > 0
        self.n_pages = int(n_pages)
        self._free = collections.deque(range(self.n_pages))
        self._free_set = set(range(self.n_pages))
        self.refs = np.zeros((self.n_pages,), np.int32)
        self.freed_event = asyncio.Event()
        self.stats = {"allocated": 0, "forked": 0, "freed": 0}

    @property
    def n_free(self) -> int:
        return len(self._free)

    def try_alloc(self, preferred: Optional[int] = None) -> Optional[int]:
        """Take a free page (refs=1) or None when the pool is exhausted.
        ``preferred`` is taken when free — the batcher asks for the identity
        page so pages read in sequential HBM order and the tables_contiguous
        debug flag stays meaningful (paged attention serves identity and
        permuted tables through the same program)."""
        if not self._free:
            return None
        if preferred is not None and preferred in self._free_set:
            self._free.remove(preferred)
            page = preferred
        else:
            page = self._free.popleft()
        self._free_set.discard(page)
        self.refs[page] = 1
        self.stats["allocated"] += 1
        return page

    def free_runs(self) -> list:
        """Lengths of the contiguous free-page runs, ascending page order.
        Contiguity matters because the batcher prefers identity pages: a
        shattered free list means new lanes land on scattered pages and the
        dense-table fast path degrades to gathers."""
        runs = []
        current = 0
        prev = -2
        for page in sorted(self._free_set):
            if page == prev + 1:
                current += 1
            else:
                if current:
                    runs.append(current)
                current = 1
            prev = page
        if current:
            runs.append(current)
        return runs

    def fragmentation_info(self) -> dict:
        """Free-space economics snapshot: run-length histogram (static
        buckets — these become metric labels), largest run, and a scalar
        fragmentation ratio (1 - largest_run/free; 0 = one hole)."""
        runs = self.free_runs()
        free = len(self._free_set)
        largest = max(runs) if runs else 0
        hist = {"1": 0, "2_3": 0, "4_7": 0, "8_15": 0, "16_plus": 0}
        for r in runs:
            if r == 1:
                hist["1"] += 1
            elif r <= 3:
                hist["2_3"] += 1
            elif r <= 7:
                hist["4_7"] += 1
            elif r <= 15:
                hist["8_15"] += 1
            else:
                hist["16_plus"] += 1
        return {
            "free": free,
            "runs": len(runs),
            "largest_run": largest,
            "frag": round(1.0 - largest / free, 4) if free else 0.0,
            "run_hist": hist,
        }

    def fractional_shares(self, tables: np.ndarray) -> np.ndarray:
        """Fractional page ownership per block-table row: a page with
        refcount R contributes 1/R to each row referencing it, so summing a
        row's shares (plus the prefix cache's pin remainder) reconstructs
        exactly the allocated page count — the resource ledger's COW
        attribution rule (telemetry.ledger page-seconds conservation).
        ``tables`` is [n_rows, max_pages] int32 with -1 for empty slots."""
        mask = tables >= 0
        pages = np.where(mask, tables, 0)
        inv = np.where(mask, 1.0 / np.maximum(self.refs[pages], 1), 0.0)
        return inv.sum(axis=1)

    def incref(self, page: int) -> None:
        assert self.refs[page] > 0, f"incref of free page {page}"
        self.refs[page] += 1

    def decref(self, page: int) -> None:
        """Drop one reference; a page at zero returns to the free list (FIFO)
        and wakes allocation waiters."""
        assert self.refs[page] > 0, f"decref of free page {page}"
        self.refs[page] -= 1
        if self.refs[page] == 0 and page not in self._free_set:
            self._free.append(page)
            self._free_set.add(page)
            self.stats["freed"] += 1
            self.freed_event.set()


class HostSwapPool:
    """Byte-budgeted accounting for the host-RAM KV swap tier.

    When the page pool is exhausted, the session scheduler
    (server/scheduler.py) preempts a victim lane: its resident pages are
    gathered on device and copied to host RAM, its pool pages freed, and the
    content scattered back onto (possibly different) pages when the session
    next steps. This class only accounts the bytes — the arrays themselves
    ride inside the scheduler's swap entries, so the budget bounds how much
    host RAM preemption may pin. ``try_reserve`` is all-or-nothing: a victim
    whose KV does not fit is simply not preemptable, and the caller falls
    back to ordinary waiter backpressure.

    The radix prefix cache's swap tier shares THIS budget: demoted cache
    nodes reserve with ``kind="cache"``, tracked separately
    (``cache_bytes_in_use``) so the scheduler summary can show how the one
    budget splits between preempted sessions and demoted cache nodes. The
    cache self-limits to a fraction of the budget (prefix_cache.py
    CACHE_SWAP_FRAC) so session preemption always finds room.

    The copies land in ordinary (pageable) numpy memory; on TPU runtimes the
    device->host transfer is staged through the runtime's pinned buffers, and
    a future upgrade can place the pool in the ``pinned_host`` memory space
    once the jax version floor allows it.
    """

    def __init__(self, max_size_bytes: int):
        assert max_size_bytes >= 0
        self.max_size_bytes = int(max_size_bytes)
        self._bytes_in_use = 0
        self._cache_bytes_in_use = 0  # of which: demoted prefix-cache nodes
        self.stats = {
            "reserved": 0, "rejected": 0, "peak_bytes": 0,
            "cache_reserved": 0, "cache_rejected": 0,
        }

    @property
    def bytes_in_use(self) -> int:
        return self._bytes_in_use

    @property
    def cache_bytes_in_use(self) -> int:
        return self._cache_bytes_in_use

    @property
    def bytes_left(self) -> int:
        return self.max_size_bytes - self._bytes_in_use

    def try_reserve(self, nbytes: int, kind: str = "session") -> bool:
        """Reserve ``nbytes`` for one swap entry, or False when it would
        overflow the budget (the entry's victim stays resident).
        ``kind="cache"`` tags a prefix-cache node demotion — same budget,
        separate accounting."""
        nbytes = int(nbytes)
        assert nbytes >= 0
        if chaos.ENABLED and chaos.fire(chaos.SITE_SWAP_RESERVE) is not None:
            # injected pressure spike: behave exactly like a full budget
            self.stats["rejected" if kind == "session" else "cache_rejected"] += 1
            return False
        if nbytes > self.bytes_left:
            self.stats["rejected" if kind == "session" else "cache_rejected"] += 1
            return False
        self._bytes_in_use += nbytes
        if kind == "cache":
            self._cache_bytes_in_use += nbytes
            self.stats["cache_reserved"] += 1
        else:
            self.stats["reserved"] += 1
        self.stats["peak_bytes"] = max(self.stats["peak_bytes"], self._bytes_in_use)
        return True

    def free(self, nbytes: int, kind: str = "session") -> None:
        self._bytes_in_use -= int(nbytes)
        if kind == "cache":
            self._cache_bytes_in_use -= int(nbytes)
            assert self._cache_bytes_in_use >= 0, (
                "cache swap accounting went negative"
            )
        assert self._bytes_in_use >= 0, "swap-pool accounting went negative"


class MemoryCache:
    """Budgeted handle-based allocator for session KV buffers in HBM."""

    def __init__(self, max_size_bytes: Optional[int], max_alloc_timeout: Optional[float] = None):
        self.max_size_bytes = max_size_bytes if max_size_bytes is not None else 2**64
        self.max_alloc_timeout = max_alloc_timeout
        self._current_size_bytes = 0
        self._handle_counter = 0
        self._allocated: Dict[Handle, TensorDescriptor] = {}
        self._buffers: Dict[Handle, Optional[jax.Array]] = {}
        self._lock = make_async_lock("memory_cache._lock")
        self._freed_event = asyncio.Event()
        self._waiter_queue: list = []  # FIFO fairness for oversubscribed allocs

    @property
    def current_size_bytes(self) -> int:
        return self._current_size_bytes

    @property
    def bytes_left(self) -> int:
        return self.max_size_bytes - self._current_size_bytes

    @property
    def num_allocated(self) -> int:
        return len(self._allocated)

    @contextlib.asynccontextmanager
    async def allocate_cache(self, *descriptors: TensorDescriptor, timeout: Optional[float] = None):
        """Reserve budget for ``descriptors``; yield one handle per descriptor."""
        if self.max_alloc_timeout is not None:
            timeout = self.max_alloc_timeout if timeout is None else min(timeout, self.max_alloc_timeout)
        alloc_size = sum(d.nbytes for d in descriptors)
        if alloc_size > self.max_size_bytes:
            raise AllocationFailed(
                f"Cannot allocate {alloc_size} bytes: exceeds total cache size "
                f"{self.max_size_bytes} bytes"
            )

        alloc_task = asyncio.create_task(self._wait_and_reserve(descriptors, alloc_size, timeout))
        try:
            handles = await alloc_task
            yield handles
        finally:
            # Cancellation while *waiting* aborts cleanly (nothing reserved yet);
            # if the reservation raced to completion anyway, free it here.
            if not alloc_task.done():
                alloc_task.cancel()
                with contextlib.suppress(asyncio.CancelledError, AllocationFailed):
                    await alloc_task
            if alloc_task.done() and not alloc_task.cancelled() and alloc_task.exception() is None:
                self._free(alloc_task.result())

    async def _wait_and_reserve(
        self, descriptors: Sequence[TensorDescriptor], alloc_size: int, timeout: Optional[float]
    ) -> Tuple[Handle, ...]:
        start = time.monotonic()
        my_turn = asyncio.Event()
        self._waiter_queue.append(my_turn)
        if len(self._waiter_queue) == 1:
            my_turn.set()
        try:
            while True:
                if self._waiter_queue and self._waiter_queue[0] is my_turn:
                    my_turn.set()
                if my_turn.is_set():
                    async with self._lock:
                        # re-check under the lock: acquiring it may have yielded
                        if alloc_size <= self.bytes_left:
                            return self._reserve(descriptors, alloc_size)
                remaining = None if timeout is None else timeout - (time.monotonic() - start)
                if remaining is not None and remaining <= 0:
                    raise AllocationFailed(
                        f"Could not allocate {alloc_size} bytes within {timeout} s "
                        f"({self.bytes_left} of {self.max_size_bytes} bytes free, "
                        f"{len(self._waiter_queue) - 1} waiters ahead)"
                    )
                self._freed_event.clear()
                try:
                    await asyncio.wait_for(self._freed_event.wait(), timeout=remaining)
                except asyncio.TimeoutError:
                    pass  # loop once more to produce the AllocationFailed message
        finally:
            self._waiter_queue.remove(my_turn)
            self._freed_event.set()  # let the next waiter re-check its turn

    def _reserve(self, descriptors: Sequence[TensorDescriptor], alloc_size: int) -> Tuple[Handle, ...]:
        handles = []
        for descr in descriptors:
            handle = self._handle_counter
            self._handle_counter += 1
            self._allocated[handle] = descr
            self._buffers[handle] = None  # lazily materialized by use_cache
            handles.append(handle)
        self._current_size_bytes += alloc_size
        logger.debug(f"Allocated {alloc_size} bytes, handles={handles}; left={self.bytes_left}")
        return tuple(handles)

    def _free(self, handles: Sequence[Handle]) -> None:
        freed = 0
        for handle in handles:
            descr = self._allocated.pop(handle, None)
            if descr is not None:
                freed += descr.nbytes
            self._buffers.pop(handle, None)  # drops the HBM buffer reference
        self._current_size_bytes -= freed
        self._freed_event.set()
        logger.debug(f"Freed {freed} bytes, handles={list(handles)}; left={self.bytes_left}")

    @contextlib.contextmanager
    def use_cache(self, *handles: Handle, device: Optional[jax.Device] = None):
        """Deprecated contextmanager shim; use :meth:`get_buffers` (the
        single-process design never needed scoped access)."""
        yield self.get_buffers(*handles, device=device)

    def get_buffers(self, *handles: Handle, device: Optional[jax.Device] = None) -> list:
        """Compute-side access: the device buffers for ``handles``,
        materializing zeros on first touch."""
        buffers = []
        for handle in handles:
            if handle not in self._allocated:
                raise KeyError(f"Handle {handle} was not allocated (or already freed)")
            if self._buffers[handle] is None:
                self._buffers[handle] = self._allocated[handle].make_zeros(device)
            buffers.append(self._buffers[handle])
        return buffers

    def reset_buffer(self, handle: Handle) -> None:
        """Drop a handle's buffer so the next get_buffers rematerializes
        zeros (recovery path: a failed donating step consumed the buffer)."""
        if handle not in self._allocated:
            raise KeyError(f"Handle {handle} was not allocated (or already freed)")
        self._buffers[handle] = None

    def update_cache(self, handle: Handle, new_buffer: jax.Array) -> None:
        """Store the post-step buffer for ``handle`` (functional update; pair with
        XLA donation so the HBM allocation is reused)."""
        if handle not in self._allocated:
            raise KeyError(f"Handle {handle} was not allocated (or already freed)")
        descr = self._allocated[handle]
        assert tuple(new_buffer.shape) == tuple(descr.shape), (new_buffer.shape, descr.shape)
        self._buffers[handle] = new_buffer
