"""Continuous batching: coalesce concurrent decode sessions into one step.

The reference explicitly never batches across requests — its task pools note
"there is no batching" (reference src/petals/server/task_pool.py:35-36), so a
server's aggregate decode throughput equals single-stream throughput. On TPU
that wastes the hardware: decode is weight-bandwidth-bound, so stepping 8
sessions in one program costs barely more than stepping one (the measured
batch-8 step is ~1.4x the batch-1 step for 8x the tokens).

TPU-first design — a LANE pool, not a page table:

- One shared KV pool [n_blocks, n_lanes, max_len, kv_heads, head_dim] x2,
  budgeted through MemoryCache like any session cache. Each session borrows a
  LANE for its lifetime; sessions at different decode depths coexist via a
  per-lane position vector (models/common.py absolute_positions).
- Every batched step runs the SAME compiled program over the whole pool —
  static shapes, so sessions joining/leaving NEVER recompile (XLA's one-trace
  model makes vLLM-style dynamic page tables recompile-hostile; decode reads
  the whole masked buffer either way, so lane-granularity loses no bandwidth,
  it only rounds memory up to max_len per active session).
- Idle lanes ride along with position = max_len (the out-of-range sentinel):
  their KV writes are dropped by the scatter, their outputs ignored.
- Non-batchable work on a pooled session (chunked prefill, kv import/export)
  extracts the lane into session-shaped buffers, runs the normal path, and
  inserts it back — all under the server's priority queue, so it serializes
  with batched steps.

Scheduling: coalescing, with a gather in front of every step. Step requests
accumulate while the current device step runs; before the flush loop drains
them into the next step it waits for the lanes that are predictably on their
way back, for as long as that wait costs the ready lanes less than the step
the returning lanes would otherwise sit out (DecodeBatcher._gather: both
sides of that sum are measured, per lane and per server, and nothing is
configured). Without it lanes whose clients answer within a few milliseconds
settle into groups that take turns, and every token's gap is two steps.
Single-stream latency is untouched (a lone request flushes immediately).
Where lanes do ride as groups that take turns (clients further away than a
step), a group's step is launched while the other's is still on the chip
(DecodeBatcher._start_behind, by the same rule) and a step's rows leave before
its bookkeeping, so a lane's gap holds its own step's host part and not the
other group's as well.
"""

from __future__ import annotations

import asyncio
import contextlib
import dataclasses
import itertools
import os
import statistics
import threading
import time
from collections import deque
from queue import SimpleQueue
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from petals_tpu.analysis.sanitizer import (
    lock_try_acquire_nowait,
    make_async_lock,
    make_thread_lock,
)
from petals_tpu.utils.locks import AsyncTryLock
from petals_tpu.data_structures import SESSION_PRIORITY_NORMAL
from petals_tpu.ops.sampling import sampling_vectors
from petals_tpu.server.backend import bucket_length
from petals_tpu.server.memory_cache import (
    AllocationFailed,
    HostSwapPool,
    MemoryCache,
    PageAllocator,
)
from petals_tpu.server.scheduler import SessionScheduler, SwapEntry
from petals_tpu.server.spec_decode import min_accept_floor
from petals_tpu.server.task_queue import PRIORITY_INFERENCE, PriorityTaskQueue, TaskRejected
from petals_tpu.telemetry import get_journal
from petals_tpu.telemetry import instruments as tm
from petals_tpu.utils.asyncio_utils import log_exception_callback
from petals_tpu.utils.logging import get_logger
from petals_tpu.utils.tracing import device_annotation, step_phases

logger = get_logger(__name__)


@dataclasses.dataclass
class _LaneGenState:
    """Host-side bookkeeping for one lane mid server-side generation: the
    flush loop advances every registered lane by one token per batched step
    (feeding ``token`` at ``position``) until ``remaining`` hits zero, then
    resolves ``future`` with the collected stream."""

    future: asyncio.Future
    generation: int
    token: int  # last sampled token — fed on the next step
    position: int  # cache write position for that next step
    remaining: int  # decode steps left (n_tokens - 1 at start)
    collected: List[int]
    do_sample: bool = False
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    repetition_penalty: float = 1.0
    seed: int = 0
    draw_idx: int = 0
    seen: Optional[np.ndarray] = None  # [vocab] bool; only when penalty active
    # per-hop latency attribution (handler step_meta): admission time, first
    # queue wait, and cumulative compiled-step time across the stream
    enqueued: float = 0.0  # time.perf_counter() at registration
    started: bool = False  # first batched step already recorded the wait
    queue_s: float = 0.0
    compute_s: float = 0.0
    # speculative decoding (server/spec_decode.py): prompt context for the
    # draft's window, the per-lane acceptance-rate EMA driving auto-disable,
    # the cooldown (plain-decode ticks left after a disable), and lifetime
    # proposed/accepted counts for the stream's step_meta
    context: Optional[List[int]] = None
    spec_ema: float = 1.0  # optimistic start: new lanes get to speculate
    spec_cooldown: int = 0
    spec_proposed: int = 0
    spec_accepted: int = 0


@dataclasses.dataclass
class _LanePrefillState:
    """Host-side bookkeeping for one lane's admitted prefill: the flush loop
    feeds one bucketed chunk per mixed step (round-robin across admitted
    prefills, bounded by the per-tick token budget) until ``offset`` reaches
    the full length, then resolves ``future`` with the concatenated span
    outputs. Pages for the WHOLE range were prepared at admission, so the
    flush loop never blocks on allocation mid-prefill (a grouped pool's
    windowed groups: a chunk at a time, ``_window_pages_for_tick``)."""

    future: asyncio.Future
    generation: int
    lane: int
    hidden: np.ndarray  # [1, total, hidden] host-side
    position: int  # absolute position of the next unfed token
    offset: int  # tokens already fed
    cap: int  # per-step chunk cap (chunk_plan byte sizing)
    n_total: int  # final sequence length (longrope factor selection)
    outs: List[np.ndarray]
    enqueued: float = 0.0  # time.perf_counter() at admission (queue-wait metric)
    wait_observed: bool = False  # first chunk already recorded the queue wait
    queue_s: float = 0.0  # admission -> first chunk (handler step_meta)
    compute_s: float = 0.0  # cumulative mixed-step wall across chunks
    starved: float = 0.0  # time.monotonic() since which its next chunk has waited for a windowed layer's page (0: not waiting)


@dataclasses.dataclass
class _LaneReturn:
    """One lane's way back to the batcher, as the gather sees it: when its
    last decode reply was resolved, and how long after such a reply its next
    ``step()`` has been coming lately (handler, wire, client, wire, handler).
    The prediction is the mean of the last few returns, so one slow return
    takes the lane out of every gather's reach until it has left the window."""

    replied: Optional[float] = None  # None: not out (pending, in a step, back)
    eta: Optional[float] = None  # predicted arrival; None: not expected
    returns: deque = dataclasses.field(default_factory=lambda: deque(maxlen=5))

    def usual(self) -> Optional[float]:
        """The return to reckon with; None for a lane that has never come back, which is not predicted."""
        return sum(self.returns) / len(self.returns) if self.returns else None

    def reply_sent(self, now: float) -> None:
        self.replied = now
        if self.returns:
            self.eta = now + self.usual()

    def came_back(self, now: float) -> None:
        self.returns.append(now - self.replied)
        self.replied = self.eta = None

    def predictable(self, step_s: float) -> bool:
        """It has come back before, each of its last returns in less than a step."""
        return bool(self.returns) and max(self.returns) < step_s

    def expected(self, now: float, step_s: float) -> bool:
        """Is this lane on its way back so that a step could wait for it: out
        with a prediction, each of its last returns shorter than a step (a
        hop of a chain or a slow client returns steps later and with jitter,
        and no prediction of that is good to a fraction of one step), and not
        yet late by more than its usual return (then it has stopped, or
        thinks)."""
        if self.eta is None:
            return False
        return self.predictable(step_s) and now - self.eta < self.eta - self.replied


@dataclasses.dataclass(eq=False)
class _StepInFlight:
    """One plain decode step of the paged pool from the flush loop's decision
    to start it to its bookkeeping, handed from thread to thread and written
    by one at a time: the event loop makes it (``DecodeBatcher._launch``), the
    compute thread launches it (``_launch_batch``), the readback thread waits
    for its rows (``_readback_loop``), the event loop hands them to the lanes
    (``_step_home``) and the compute thread books it (``_finish_batch``)."""

    batch: list  # the entries of ``_pending`` it carries
    generation: int
    loop: asyncio.AbstractEventLoop
    behind: bool  # started with another step in flight: it queues behind that one on the device
    end_eta: float  # when its rows should be on the host, as the start reckoned (S and the lead as measured then)
    # the launch's (compute thread)
    t_step: float = 0.0
    out: Any = None  # [n_lanes, 1, hidden] on the device, its copy to the host queued behind the step
    fp: Any = None  # the step's fused fingerprints on the device, or None
    # every lane's position as the step was fed and the pages it held then (a group): the counters' view of the
    # step, kept because the lanes have moved on by the time it is booked
    positions: Optional[np.ndarray] = None
    held: Tuple[np.ndarray, Optional[tuple]] = (None, None)
    # the readback's
    rows: Optional[np.ndarray] = None
    fp_rows: Optional[np.ndarray] = None
    rows_at: float = 0.0  # the rows were on the host
    error: Optional[BaseException] = None

    @property
    def lanes(self) -> List[int]:
        return [entry[0] for entry in self.batch]


@dataclasses.dataclass
class _LaneWaiter:
    """One parked acquire_lane caller. Admission order is a POLICY decision
    (scheduler.pick_waiter): priority class first, then per-peer fair share,
    then ``seq`` — which alone reproduces the old FIFO at default priority."""

    fut: asyncio.Future
    priority: int
    peer_id: Optional[str]
    seq: int
    # request trace id (telemetry.trace): pre-admission, so the scheduler
    # slot doesn't exist yet — the waiter carries it for journal events
    trace_id: Optional[str] = None


# what a span with a recurrent state answers a request for a lane's cache cut to a position
_SNAPSHOT = "a snapshot of a lane's cache (session export, migration, parking, a stored prefix)"
_SNAPSHOT_WHY = "it ships keys and values cut to a position; the state is not shipped yet and cannot be cut"


class _WindowGroup:
    """One page group of a grouped pool beside the first (server/span_cache.py ``SpanCache.page_groups``): the layers of one
    static ``window``, with a pool, an allocator and lane tables of their own. The first group is the lane's own table
    (``DecodeBatcher._tables``, ``_pages``) and keeps every page; a lane here holds the pages its window can still reach
    and those of the rows being fed, and gives the rest back (``DecodeBatcher._window_pages_now``). ``tables`` is written
    on the event loop alone, as the first group's are (``_write_tables`` says how the compute thread reads them)."""

    def __init__(self, window: int, layers: int, n_pages: int, n_lanes: int, max_pages: int):
        self.window, self.layers, self.n_pages = int(window), int(layers), int(n_pages)
        self.alloc = PageAllocator(self.n_pages)
        self.tables = np.full((n_lanes, max_pages), -1, np.int32)
        self.lane_held = np.zeros(n_lanes, np.int64)
        # a lane's held slots are one run, [first, last]: two numbers a lane, so that a step's give-and-take walks no row
        self.first = [0] * n_lanes
        self.last = [-1] * n_lanes


class DecodeBatcher:
    """Shared-pool continuous batcher for one backend (one span of blocks).

    Who owns the pools. The pool's buffers live in the ``MemoryCache`` and every
    program that touches them runs from the compute thread, one run at a time:
    a run reads the buffers there (``_buffers``, ``_state``), calls its program
    with them donated, and swaps what the call returned back in before it ends
    (``_update``, under ``_reset_lock`` with the generation check). What it
    swaps in may be arrays the device has not computed yet: JAX hands them on
    as such, and **the device runs the programs of one process in the order
    they were launched**, so the next run's program (a decode step launched
    behind the one in flight, an exclusive op, a copy-on-write fork, a swap, a
    snapshot) reads pools that every launch before it has written, though no
    thread has waited for any of them. Nothing else orders two steps in
    flight, and nothing else needs to: no step's program starts against a pool
    that a step launched before it has yet to write
    (tests/test_batching_overlap.py holds the tokens of two overlapping groups
    to the serial batcher's, bit for bit). A reply is another matter: a lane's
    rows leave only once THEY are on the host (``_readback_loop``), and a pool
    reset fails the lanes of every step in flight (``_step_home``)."""

    def __init__(
        self,
        backend,
        memory_cache: MemoryCache,
        queue: PriorityTaskQueue,
        *,
        n_lanes: int = 8,
        max_length: int = 1024,
        alloc_timeout: Optional[float] = None,
        gen_params=None,  # full-model client leaves: enables pooled server-gen
        page_size: Optional[int] = None,  # None/0 -> dense lane pool (legacy)
        n_pages: Optional[int] = None,  # default: n_lanes * max_pages (no oversub)
        prefill_token_budget: int = 512,  # max prefill-chunk tokens per mixed step
        swap_host_bytes: int = 0,  # host-RAM KV swap tier; 0 -> no preemption
        preemption_policy: str = "lru",  # lru | largest | off
        ledger=None,  # telemetry.ledger.ResourceLedger; None -> process singleton
        draft_model=None,  # server.spec_decode.DraftModel; enables spec decode
        spec_k: Optional[int] = None,  # drafts per lane per tick; None -> draft's k
    ):
        self.backend = backend
        self.memory_cache = memory_cache
        self.queue = queue
        self.n_lanes = n_lanes
        self.max_length = max_length
        self.alloc_timeout = alloc_timeout
        self.gen_params = gen_params
        # paged KV mode: the pool becomes [n_blocks, n_pages, page_size, ...]
        # and lanes address it through per-lane block tables. Gated off under
        # lockstep (the paged programs are single-host) and TP meshes (the
        # page axis is unsharded); those keep the dense lane pool.
        lockstep = bool(getattr(backend, "is_lockstep", False))
        if page_size and not lockstep and getattr(backend, "mesh", None) is None:
            self.page_size: Optional[int] = int(page_size)
            # round the lane capacity UP to whole pages so tables tile exactly
            self.max_length = -(-int(max_length) // self.page_size) * self.page_size
            self.max_pages = self.max_length // self.page_size
            self.n_pages = int(n_pages) if n_pages else self.n_lanes * self.max_pages
            if self.n_pages < self.max_pages:
                raise ValueError(
                    f"n_pages={self.n_pages} cannot hold even one full lane "
                    f"({self.max_pages} pages of {self.page_size} tokens)"
                )
        else:
            self.page_size = None
            self.max_pages = 0
            self.n_pages = 0
        # what a lane holds for the span's blocks beside or in place of pages of keys and values (server/span_cache.py). A
        # recurrent state: beside its pages, a lane owns its slot in the STATE pool, taken and released with the lane; a row
        # at position 0 starts from zeros, so a new tenant needs no clearing. An index row: a third page pool under the same
        # tables, allocated, freed and reused with the pages; it rides the paged step programs where a state pool would
        # (``_state``). A latent row: the two pools of ``_buffers`` are its latents and its rotated keys, allocated, freed
        # and reused as pages of keys and values are. Only the paged pool's step programs carry any of them
        cache = backend.cache
        if self.page_size is None:
            cache.refuse(
                "the dense lane pool" + (" (which a tp mesh or a multi-host group falls back to)" if page_size else ""),
                "it has no place for the state: serve with page_size > 0",
            )
        if cache.content in ("index", "latent") and int(swap_host_bytes or 0) > 0:
            cache.refuse("the host swap tier (swap_host_bytes > 0)", "")
        # a span whose layers keep pages in groups by static window (``cache.page_groups``): on the paged pool every group
        # beside the first has a pool, an allocator and lane tables of its own (``_win``, made with the pool) and gives a
        # lane's pages back as its window moves; what ships, stores, cuts back or adopts a lane's pages is refused by name
        self._grouped = self.page_size is not None and cache.grouped
        self._win: List[_WindowGroup] = []
        self._group_pages: tuple = ()
        if self._grouped and int(swap_host_bytes or 0) > 0:
            cache.refuse("the host swap tier (swap_host_bytes > 0)", "", paged=True)
        # fixed with the backend and asked by the step bodies' counters every step: what a paged step reads, a page's and a lane's
        # state's bytes (``_pool``: server/span_cache.py ``LanePool``), and the expert dispatch a block call of a shape takes
        self._pool = cache.lane_pool(n_lanes, self.max_pages, self.page_size, grouped=self._grouped) if self.page_size else None
        self._moe_took: Dict[tuple, Optional[str]] = {}
        self._pages: Optional[PageAllocator] = None
        # [n_lanes, max_pages] int32, -1 = unallocated. What everyone READS is a view that refuses writes: an
        # entry changes value through ``_write_tables`` alone, which keeps beside the tables what the step bodies
        # would otherwise reckon from them every step: ``_lane_held`` (the slots a lane owns) and ``_tables_version``
        # (bumped after every write), by which ``_step_tables`` knows whether the copy the device holds
        # (``_tables_on_device``: the version it was made from, the array) is still what the tables say
        self._tables: Optional[np.ndarray] = None
        self._tables_rw: Optional[np.ndarray] = None
        self._lane_held: Optional[np.ndarray] = None  # [n_lanes] int64
        self._tables_version = 0
        self._tables_on_device: Tuple[int, Any] = (-1, None)
        # the lanes' rows and positions of a paged decode or mixed step, in the form its program takes
        # (backend.pack_lanes: [n_lanes, hidden + 1] int32, a float32 row bit for bit and the position last), in
        # TWO buffers made once and filled in turn (``_fill_lanes``): a step body writes its batch's rows and every
        # lane's position into one and hands it over whole, one copy to the device; the launch after it takes the
        # other, because the first's copy may still be read when a second step is launched behind it. An idle lane
        # keeps the row it last fed there (any finite filler will do for a row at the sentinel position);
        # ``release_lane`` zeroes it in both, so a new tenant's neighbours never step beside a stranger's row.
        # ``_lanes_rows`` is the float32 view of the rows
        self._lanes_in: Optional[np.ndarray] = None  # [2, n_lanes, hidden + 1]
        self._lanes_rows: Optional[np.ndarray] = None
        self._lanes_turn = 0
        # cached tables_are_contiguous result for the stats/debug surface
        # (paged_summary); None = recompute on next read. The STEP path no
        # longer consults it — paged attention serves identity and permuted
        # tables alike — so the O(n_lanes*max_pages) scan runs only when the
        # tables actually changed AND someone asks (rpc_info), not per tick.
        self._tables_contig: Optional[bool] = None
        # bumped on every pool reset: prefix-cache page pins carry the epoch
        # they were taken under so stale pins never decref a rebuilt allocator
        self._page_epoch = 0
        # lanes currently running server-side generation: advanced one token
        # per flush-loop iteration alongside (and batched WITH) ordinary
        # per-token decode traffic
        self._gen_states: Dict[int, _LaneGenState] = {}
        # paged-lane prefills admitted into the MIXED step (prefill_lane):
        # one bucketed chunk rides each flush tick, round-robin, so decode
        # lanes keep stepping while prefills stream in
        self._prefill_queue: List[_LanePrefillState] = []
        self.prefill_token_budget = max(int(prefill_token_budget), 1)
        if self._grouped:  # pages a group: the first group's as asked, a windowed group's as many lanes' worth
            self._group_pages = cache.group_pages(self.n_lanes, self.max_pages, self.page_size, self.prefill_token_budget, self.n_pages)
        # speculative decoding (server/spec_decode.py): with a draft model
        # loaded, eligible gen lanes move onto the draft-verify path — k
        # drafts verified in ONE paged step per tick, up to k+1 tokens
        # committed. Paged pool only (verification rides the chunk-scatter
        # machinery); requires gen_params (the verify program embeds/samples
        # with the client leaves). spec_k must match the draft's compiled k.
        self.draft = draft_model
        self.spec_k = int(spec_k if spec_k is not None
                          else getattr(draft_model, "spec_k", 0) or 0)
        if draft_model is not None:
            draft_k = int(getattr(draft_model, "spec_k", self.spec_k))
            if self.spec_k != draft_k:
                raise ValueError(
                    f"spec_k={self.spec_k} does not match the draft model's "
                    f"compiled k={draft_k}"
                )
            if gen_params is None:
                raise ValueError(
                    "Speculative decoding needs the client leaves loaded "
                    "(gen_params): the verify step embeds and samples on device"
                )
            from petals_tpu.server.backend import SPEC_CUTS_BACK

            cache.refuse("speculative decoding", SPEC_CUTS_BACK, paged=self._grouped)
        # the draft instance whose bucket shapes have been pre-compiled via
        # DraftModel.warmup (first spec tick, on the compute thread); keyed
        # on the object so a swapped-in draft re-warms
        self._draft_warmed = None
        # per-lane acceptance EMA auto-disable: a lane whose EMA drops below
        # the floor falls back to plain decode for a cooldown window (both
        # journaled as 'spec_disabled' with the EMA evidence)
        self._spec_min_accept = min_accept_floor()
        self._spec_ema_alpha = 0.2
        try:
            self._spec_cooldown_ticks = max(
                int(os.environ.get("PETALS_TPU_SPEC_COOLDOWN", 64)), 1
            )
        except ValueError:
            self._spec_cooldown_ticks = 64

        self._pool_stack: Optional[contextlib.AsyncExitStack] = None
        self._handles = None
        # a failed donating step can consume the pool buffers; recovery zeros
        # the pool and bumps the generation so every OUTSTANDING lane is
        # invalidated (its KV is gone — silently serving zeros would corrupt
        # every tenant token-by-token)
        self._generation = 0
        # makes the compute thread's post-step generation-check + buffer swap
        # atomic w.r.t. the event loop's reset (check-then-update alone is a
        # TOCTOU: a reset landing between them would be overwritten)
        self._reset_lock = make_thread_lock("batching._reset_lock")
        self._lane_generation: Dict[int, int] = {}
        self._free_lanes: List[int] = []
        self._lane_waiters: List[_LaneWaiter] = []
        self._waiter_seq = itertools.count()
        self._pending: List[tuple] = []  # (lane, hidden, position, future, generation)
        # per-hop latency attribution (handler step_meta): admission time of
        # the in-flight step per lane, and the finished step's queue/compute
        # split for the handler to pop after the future resolves. Plain dict
        # ops (GIL-atomic) — one step in flight per lane (_lane_busy), so the
        # event loop and compute thread never race on the same key.
        self._enq_t: Dict[int, float] = {}
        self._step_timing: Dict[int, dict] = {}
        # integrity fingerprints of the in-flight step per lane (ops/
        # fingerprint.py, fused into the batched programs) — same
        # single-writer discipline as _step_timing
        self._step_fp: Dict[int, list] = {}
        # session scheduler: priority + per-peer fair-share admission, and (in
        # paged mode with swap_host_bytes > 0) preemption of idle victim lanes
        # to the host-RAM swap tier on pool exhaustion. With the default
        # swap_host_bytes=0 no lane ever suspends and a full pool keeps the
        # exact waiter-backpressure/AllocationFailed behavior of PR 2.
        self.swap_pool = HostSwapPool(int(swap_host_bytes or 0))
        # per-tenant resource ledger (telemetry.ledger): page-seconds with
        # fractional COW attribution, compute-seconds, tokens, swap bytes —
        # settled at the same boundaries where _note_occupancy runs. Its
        # dominant-resource share feeds the scheduler's fair-share admission
        # and victim tie-breaks in place of the raw lanes-held count.
        if ledger is None:
            from petals_tpu.telemetry.ledger import get_ledger

            ledger = get_ledger()
        self._ledger = ledger
        # price the pool for /ledger readers: wire bytes per cached token
        # (quantized pools cost ~4x less) and the storage kind. Guarded by
        # hasattr because unit-test stub ledgers lack the accessor.
        if hasattr(ledger, "set_kv_cost"):
            ledger.set_kv_cost(cache.kv_quant_type, cache.kv_bytes_per_token())
        self._ledger_keys: Dict[int, str] = {}  # lane -> ledger session key
        self._scheduler = SessionScheduler(
            self.swap_pool, policy=preemption_policy, pages_fn=self._lane_pages,
            usage_fn=ledger.peer_dominant_share,
        )
        # per-lane asyncio locks serializing swap-out against swap-in, and an
        # in-flight op counter making lanes with ANY active work unpreemptable
        self._lane_locks: Dict[int, AsyncTryLock] = {}
        self._inflight: Dict[int, int] = {}
        # swap-ins serialize through this fair (FIFO-wakeup) lock: N resumers
        # racing _alloc_pages would each grab pages the others need and an
        # unlucky one could starve past its timeout; one-at-a-time, the head
        # gets every freed page and provably drains the queue
        self._swap_in_turnstile = make_async_lock("batching._swap_in_turnstile")
        self._flush_task: Optional[asyncio.Task] = None
        # flush tasks spawned so far, and (time, spawn count) at the last step
        # body's return: together they tell a hand-off with work pending from
        # a stretch in which the batcher had nothing to run (_step_phases)
        self._flush_spawns = 0
        self._last_step_end: Tuple[float, int] = (0.0, -1)
        # what _step_phases needs besides to say what the compute thread waited
        # for between two bodies: the seconds _gather waited since the last
        # body's return, and whether a lane has come back from a decode reply
        # since then (so a reply was out while there was nothing to run)
        # (from, to) of each gather's wait, for _split_idle to lay over its gap (the event loop appends, the compute
        # thread pops from the left: a deque, so neither loses the other's), and since when one is waiting now
        self._gathered: deque = deque()
        self._gathering: Optional[float] = None
        self._back_since_step = False
        # plain decode steps of the paged pool that are started and whose rows are not yet with their lanes (event
        # loop; at most two, ``_start_behind``; ``_home_wake`` is set when one comes home), those launched whose time
        # in flight the compute thread has yet to count (its own list: ``_split_idle``), and the thread that waits
        # for a launched step's rows (``_readback_loop``: started with the first launch, ended once the batcher is
        # closed and no step is in flight, ``_end_readback``)
        self._flights: List[_StepInFlight] = []
        self._home_wake = asyncio.Event()
        self._aloft: List[_StepInFlight] = []
        self._readback: SimpleQueue = SimpleQueue()
        self._readback_thread: Optional[threading.Thread] = None
        # the gather (_gather): each lane's returns after its decode replies,
        # the median wall of the last decode step bodies that carried no
        # prompt chunk (the step a late lane sits out), and the event with
        # which an arrival, a release, close() and a pool reset wake a gather
        self._returns: Dict[int, _LaneReturn] = {}
        self._step_walls: deque = deque(maxlen=9)
        self._step_s = 0.0
        # and of the last launches, the host's part before the device has the step (assemble and dispatch): how long
        # before a step in flight ends the one behind it is due (``_start_behind``)
        self._lead_walls: deque = deque(maxlen=9)
        self._lead_s = 0.0
        self._gather_wake = asyncio.Event()
        self._open_lock = make_async_lock("batching._open_lock")
        self._closed = False
        # multi-host lockstep (parallel/multihost.py): lane ops broadcast so
        # every process mirrors the pool; extracted lanes live on workers as
        # synthetic NEGATIVE-handle mirrors minted here (never colliding with
        # MemoryCache's non-negative handles)
        self._lockstep = bool(getattr(backend, "is_lockstep", False))
        self._temp_ids = itertools.count(-2, -1)
        # observability + tests: how many device steps served how many tokens.
        # EVERY key is pre-initialized — rpc_info spreads this dict into the
        # health summary, and lazily created keys made the schema depend on
        # which code paths had run
        self.stats = {
            "batched_steps": 0, "batched_tokens": 0, "max_batch": 0,
            # of the batched steps, the decode steps launched while the step before was still in flight (_start_behind)
            "overlapped_steps": 0,
            # step bodies that copied the block tables to the device (the rest reused the copy there)
            "tables_sent": 0,
            "gen_steps": 0, "gen_lane_tokens": 0, "max_gen_lanes": 0,
            "exclusive_chunks": 0, "prefill_tokens": 0, "mixed_steps": 0,
            "max_prefill_tokens_per_step": 0,
            # the bytes of hidden state the batched steps took in and handed back (_count_stream): a row of
            # ``backend.hidden_size`` float32 each way, as the wire carries it
            "stream_bytes_in": 0, "stream_bytes_out": 0,
            "spec_steps": 0, "spec_proposed": 0, "spec_accepted": 0,
            "spec_disabled": 0, "max_spec_lanes": 0,
            # where the compute thread's time went, cumulative seconds: the
            # four phases of every step body (utils/tracing.step_phases) and
            # the hand-off between two steps of one flush task (_step_phases)
            "assemble_s": 0.0, "dispatch_s": 0.0, "wait_s": 0.0, "post_s": 0.0,
            "turnaround_s": 0.0,
            # the gather in front of a step (_gather): steps that waited at
            # all, seconds waited (in none of the five counters above), lanes
            # that arrived during a wait and rode that step, expected lanes
            # given up on
            "gather_waits": 0, "gather_wait_s": 0.0,
            "gather_joined": 0, "gather_missed": 0,
            # the rest of the compute thread's time between two bodies
            # (_step_phases): no lane's work there and a decode reply out,
            # none there and none out, work there and the host in the way.
            # With the four phases and gather_wait_s they tile that thread's
            # wall from the first body's return on
            "lanes_out_s": 0.0, "no_demand_s": 0.0, "handoff_s": 0.0,
            # a decode token's way out of the server and back in, station by
            # station, every reading of time.perf_counter in this process
            # (seconds summed over replies; the layer that owns each stretch
            # adds it): the last body's return to the flush loop's resolving
            # the futures (a step that replied, counted in reply_steps), from
            # there to the lane's handler running again, to the reply yielded,
            # to its frame handed to the transport and drained
            # (count_decode_reply, decode_replies); and of a lane that comes
            # back (step(), lane_returns, the whole trip lane_return_s): its
            # request's frame read whole to the handler holding the item, to
            # step() entered. What lane_return_s holds beyond the five
            # stretches between resolve and step() is the wire and the client
            "reply_wake_s": 0.0, "reply_steps": 0,
            "reply_resume_s": 0.0, "reply_build_s": 0.0, "rpc_send_s": 0.0, "decode_replies": 0,
            "rpc_recv_s": 0.0, "request_handle_s": 0.0, "lane_return_s": 0.0, "lane_returns": 0,
            # decode steps by the way their request came in: handed over by the
            # connection's reader in the turn that read the frame (begin_step),
            # or through the stream's queue and the handler's own turn (step)
            "rpc_intake_direct": 0, "rpc_intake_queued": 0,
            # the event loop's turns, added by its turn clock once Server.start
            # attaches this dict (utils/asyncio_utils.install_turn_clock): the
            # stretches between one select()'s return and the next one's call,
            # summed, their squares summed, counted. busy_s over an elapsed time
            # is how full the loop's thread was, busy_sq / (2 x elapsed) how long
            # a socket that became ready at a random moment waited to be read
            "loop_busy_s": 0.0, "loop_busy_sq": 0.0, "loop_turns": 0,
        }
        if getattr(backend, "moe_dims", None) is not None:
            # a family with routed experts only (_count_moe): tokens by the
            # dispatch their step gave them (dense: the all-experts einsum;
            # grouped: one of the two that read the experts reached, of which
            # hit: the stacked-run kernel, the rest ragged_dot), and the times
            # a step's program walked a layer's experts
            self.stats.update(moe_dense_tokens=0, moe_grouped_tokens=0, moe_hit_tokens=0, moe_weight_passes=0)
            dims = backend.moe_dims
            if dims.share:
                # a server that holds a share of the routed experts only: the expert-rows (a position through one
                # expert) a mixed step's chunk half makes its dispatch multiply (positions x held experts under the
                # all-experts einsum, positions x top k under the grouped one, which is handed every assignment's
                # row), against those the routing sends here (positions x top k x held / routed)
                self.stats.update(moe_chunk_rows_computed=0, moe_chunk_rows_routed=0.0)
        # on the paged pool: what a step's programs read of what the lanes hold, the keys that the span's content opens
        # (server/span_cache.py ``LanePool.new_stats``; ``count_step`` adds a step's)
        if self._pool is not None:
            self.stats.update(self._pool.new_stats())
        if getattr(backend, "stream_mixes", 0):
            # a family whose hidden state is a stream of several rows only (ModelFamily.block_stream; _count_stream):
            # rows times the mixes of the stream the span's blocks make of each (a hyper-connection a sub-layer)
            self.stats["hc_rows"] = 0
        # swarm telemetry plane: every admission / victim-selection / swap
        # decision is journaled WITH the occupancy snapshot that justified it
        # (telemetry.journal), and the pool gauges/counters feed the /metrics
        # endpoint + the announce digest
        self._journal = get_journal()

    # ------------------------------------------------------------------ pool

    @property
    def is_open(self) -> bool:
        return self._handles is not None

    async def ensure_open(self, timeout: Optional[float] = None) -> None:
        """Allocate the pool on first use (budgeted through MemoryCache).
        ``timeout`` bounds the budget wait — callers on the session-open path
        must be able to fall back to a private cache promptly instead of
        hanging on a full cache."""
        async with self._open_lock:
            if self._handles is not None or self._closed:
                return
            # descriptors come from the backend so the pool carries the same
            # sharding as session caches (kv-head axis over the tp mesh) —
            # under lockstep the workers mirror the alloc with the identical
            # sharded descriptors, and materialization is a collective every
            # process must enter with the SAME specs (an unsharded leader
            # pool would deadlock the group at open)
            if self.page_size is not None:
                # 2 descriptors (k, v) unquantized; 4 (k/v codes, k/v scales)
                # when the backend stores the pool quantized; the state pool's
                # leaves ride last, as the index pool does
                descs = self.backend.cache.pool_descriptors(
                    self._group_pages if self._grouped else self.n_pages, self.page_size, self.n_lanes, 0, self.backend.n_blocks
                )
            else:
                descs = self.backend.cache_descriptors(
                    self.n_lanes, self.max_length, 0, self.backend.n_blocks
                )
            stack = contextlib.AsyncExitStack()
            try:
                handles = await stack.enter_async_context(
                    self.memory_cache.allocate_cache(
                        *descs,
                        timeout=self.alloc_timeout if timeout is None else timeout,
                    )
                )
            except BaseException:
                await stack.aclose()
                raise
            self._pool_stack = stack
            self._handles = handles
            self._free_lanes = list(range(self.n_lanes))
            if self.page_size is not None:
                self._pages = PageAllocator(self.n_pages)
                self._tables_rw = np.full((self.n_lanes, self.max_pages), -1, np.int32)
                self._tables = self._tables_rw.view()
                self._tables.flags.writeable = False
                self._lane_held = np.zeros(self.n_lanes, np.int64)
                self._win = self._new_window_groups()
                hsz = self.backend.hidden_size
                self._lanes_in = np.zeros((2, self.n_lanes, hsz + 1), np.int32)
                self._lanes_rows = self._lanes_in[:, :, :hsz].view(np.float32)
                logger.info(
                    f"Paged-batching pool open: {self.n_pages} pages x "
                    f"{self.page_size} tokens of {list(self.backend.cache.pool_row)} ({self.n_lanes} lanes x "
                    f"{self.max_pages} table slots) for blocks "
                    f"[{self.backend.first_block}, {self.backend.first_block + self.backend.n_blocks})"
                    + "".join(f"; {g.n_pages} pages for the {g.layers} layers of window {g.window}, given back as it moves" for g in self._win)
                )
            else:
                logger.info(
                    f"Continuous-batching pool open: {self.n_lanes} lanes x "
                    f"{self.max_length} tokens for blocks "
                    f"[{self.backend.first_block}, {self.backend.first_block + self.backend.n_blocks})"
                )

    async def close(self) -> None:
        self._closed = True
        self._gather_wake.set()
        self._end_readback()
        for w in self._lane_waiters:
            if not w.fut.done():
                w.fut.set_exception(AllocationFailed("Batcher is shutting down"))
        self._lane_waiters.clear()
        self._scheduler.reset()  # drop swap entries, release their host bytes
        for st in self._gen_states.values():
            if not st.future.done():
                st.future.set_exception(AllocationFailed("Batcher is shutting down"))
        self._gen_states.clear()
        for pst in self._prefill_queue:
            if not pst.future.done():
                pst.future.set_exception(AllocationFailed("Batcher is shutting down"))
        self._prefill_queue.clear()
        if self._pool_stack is not None:
            await self._pool_stack.aclose()
            self._pool_stack = None
            self._handles = None

    def _buffers(self):
        """The (k_pool, v_pool) pair every step/compute path consumes. A
        quantized pool rides as 4 MemoryCache buffers (codes x2, scales x2)
        and is re-wrapped into PagedPool pytrees HERE, so every caller —
        step bodies, swap, COW, snapshots — keeps the 2-tuple shape."""
        bufs = self.memory_cache.get_buffers(*self._handles[: len(self._handles) - self._beside])
        if len(bufs) == 4:
            from petals_tpu.ops.paged_attention import PagedPool

            return PagedPool(bufs[0], bufs[2]), PagedPool(bufs[1], bufs[3])
        return bufs

    def _state(self) -> tuple:
        """The state pool's leaves, which the paged step programs take after
        the pair of ``_buffers`` and hand back after it; none for a span
        without a recurrent state. A span that caches an index row hands its
        index pool over the same way."""
        n = self._beside
        if not n:
            return ()
        return tuple(self.memory_cache.get_buffers(*self._handles[-n:]))

    @property
    def grouped(self) -> bool:
        """The pool keeps pages by kind of layer and gives the windowed groups' back (``SpanCache.page_groups``)."""
        return self._grouped

    @property
    def _beside(self) -> int:
        """How many of the pool's buffers ride the step programs after the pair of ``_buffers``: the state's leaves or the
        index pool, or, for a grouped pool, the other groups' pairs of pools."""
        return 2 * (len(self._group_pages) - 1) if self._grouped else self.backend.cache.pools_beside_pages

    def _new_window_groups(self) -> List[_WindowGroup]:
        groups = self.backend.cache.page_groups[1:] if self._grouped else ()
        return [
            _WindowGroup(window, len(blocks), n_pages, self.n_lanes, self.max_pages)
            for (window, blocks), n_pages in zip(groups, self._group_pages[1:])
        ]

    def _held_by_group(self) -> Optional[tuple]:
        """A grouped pool's pages a lane, a group (``LanePool.count_step``'s ``group_held``); None for a pool of one group."""
        return (self._lane_held, *(g.lane_held for g in self._win)) if self._grouped else None

    def _update(self, k_pool, v_pool, *state) -> None:
        from petals_tpu.ops.paged_attention import PagedPool

        for handle, leaf in zip(self._handles[-len(state) :] if state else (), state):
            self.memory_cache.update_cache(handle, leaf)
        if isinstance(k_pool, PagedPool):
            self.memory_cache.update_cache(self._handles[0], k_pool.codes)
            self.memory_cache.update_cache(self._handles[1], v_pool.codes)
            self.memory_cache.update_cache(self._handles[2], k_pool.scales)
            self.memory_cache.update_cache(self._handles[3], v_pool.scales)
            return
        self.memory_cache.update_cache(self._handles[0], k_pool)
        self.memory_cache.update_cache(self._handles[1], v_pool)

    # ------------------------------------------------------------------ lanes

    async def acquire_lane(
        self,
        timeout: Optional[float] = None,
        *,
        priority: int = SESSION_PRIORITY_NORMAL,
        peer_id: Optional[str] = None,
        trace_id: Optional[str] = None,
    ) -> int:
        """Borrow a lane; queues when all lanes are taken — the allocation-
        pressure behavior of MemoryCache, at lane granularity. Parked callers
        are admitted by priority class, then per-peer fair share, then FIFO
        (scheduler.pick_waiter); at default priority that is exactly the old
        FIFO. ``timeout`` bounds the WHOLE acquisition including first-use
        pool allocation, so session opens can fall back to a private cache.

        Paged mode: admission additionally claims ONE page (not max_length
        tokens) — the lane grows page-by-page via prepare_write, and a full
        page pool exerts the same waiter backpressure as a full lane list
        (preempting an idle victim first when the swap tier is enabled)."""
        t_wait = time.perf_counter()
        lane = await self._acquire_lane(
            timeout=timeout, priority=priority, peer_id=peer_id, trace_id=trace_id
        )
        self._scheduler.register(lane, peer_id, int(priority), trace_id=trace_id)
        # ledger session opens at admission, before the first page claim, so
        # every page-second of this lane's residency lands on its bill
        self._ledger_keys[lane] = self._ledger.open_session(peer_id, trace_id)
        if self.page_size is not None:
            try:
                await self.prepare_write(lane, 0, 1, timeout=timeout)
            except BaseException:
                self.release_lane(lane)
                raise
        self._journal.event(
            "admission", trace_id=trace_id, lane=lane,
            occupancy=self.occupancy_info(),
            priority=int(priority),
            wait_s=round(time.perf_counter() - t_wait, 6),
        )
        self._note_occupancy()
        return lane

    async def _acquire_lane(
        self,
        timeout: Optional[float] = None,
        priority: int = SESSION_PRIORITY_NORMAL,
        peer_id: Optional[str] = None,
        trace_id: Optional[str] = None,
    ) -> int:
        await self.ensure_open(timeout=timeout)
        if self._closed:
            raise AllocationFailed("Batcher is closed")
        if self._free_lanes:
            # FIFO like the waiter queue: least-recently-released lane first,
            # so reuse is fair and page-table churn stays predictable
            lane = self._free_lanes.pop(0)
            self._lane_generation[lane] = self._generation
            return lane
        waiter = _LaneWaiter(
            fut=asyncio.get_running_loop().create_future(),
            priority=int(priority),
            peer_id=peer_id,
            seq=next(self._waiter_seq),
            trace_id=trace_id,
        )
        fut = waiter.fut
        self._lane_waiters.append(waiter)
        try:
            lane = await asyncio.wait_for(fut, timeout)
            self._lane_generation[lane] = self._generation
            return lane
        except asyncio.TimeoutError:
            if fut.done() and not fut.cancelled() and fut.exception() is None:
                lane = fut.result()  # resolved in the cancellation race window
                self._lane_generation[lane] = self._generation
                return lane
            tm.ALLOC_FAILED.inc()
            raise AllocationFailed(
                f"No free decode lane within {timeout} s ({self._occupancy()})"
            )
        except BaseException:
            # cancelled after release_lane already handed us the lane: put it
            # back, or pool capacity shrinks forever
            if fut.done() and not fut.cancelled() and fut.exception() is None:
                self.release_lane(fut.result())
            raise
        finally:
            if waiter in self._lane_waiters:
                self._lane_waiters.remove(waiter)

    def release_lane(self, lane: int) -> None:
        # drop stale latency attributions — they belong to the departing
        # tenant, not whoever acquires this lane next
        self._enq_t.pop(lane, None)
        self._step_timing.pop(lane, None)
        self._step_fp.pop(lane, None)
        # nor is the departing tenant's return time the next one's, and a
        # gather that waits for this lane has one fewer to wait for
        self._forget_returns(lane)
        # a timed-out/cancelled session may have left a step queued: purge it,
        # or its stale KV write could land in the next tenant's history
        kept = []
        for entry in self._pending:
            if entry[0] == lane:
                fut = entry[3]
                if not fut.done():
                    fut.set_exception(AllocationFailed("Lane released mid-step"))
            else:
                kept.append(entry)
        self._pending = kept
        # likewise a mid-generation release: fail the stream so the handler
        # never resolves it against a lane now owned by someone else
        st = self._gen_states.pop(lane, None)
        if st is not None and not st.future.done():
            st.future.set_exception(AllocationFailed("Lane released mid-step"))
        # ...and a mid-prefill release: the remaining chunks must never run
        # against a lane now owned by someone else
        for pst in [p for p in self._prefill_queue if p.lane == lane]:
            self._prefill_queue.remove(pst)
            if not pst.future.done():
                pst.future.set_exception(AllocationFailed("Lane released mid-step"))
        self._lane_generation.pop(lane, None)
        # drop the scheduler slot: a suspended lane's host swap bytes free
        # here, and a swap-out racing this release aborts on its post-gather
        # validation (the slot object it captured is no longer registered)
        self._scheduler.unregister(lane)
        # settle and close the tenant's bill; totals fold into the peer rollup
        key = self._ledger_keys.pop(lane, None)
        if key is not None:
            self._ledger.close_session(key)
        # paged mode: drop this lane's table references — pages whose refcount
        # hits zero (no prefix-cache pin) return to the pool and wake any
        # prepare_write waiters blocked on an exhausted pool
        if self.page_size is not None and self._tables is not None:
            row = self._tables[lane]
            for slot in range(self.max_pages):
                if row[slot] >= 0:
                    self._pages.decref(int(row[slot]))
            self._write_tables(lane, slice(None), -1)
            for group in self._win:
                self._window_release(group, lane, self.max_pages)
            self._lanes_rows[:, lane] = 0.0  # the next tenant's neighbours step beside zeros, not this tenant's last row
        # hand straight to the best-placed waiter (priority class, then
        # per-peer fair share, then FIFO), else back to the free list; the
        # new session overwrites the lane from position 0, so no zeroing
        while self._lane_waiters:
            w = self._scheduler.pick_waiter(self._lane_waiters)
            if w is None:
                self._lane_waiters.clear()  # every parked future already dead
                break
            self._lane_waiters.remove(w)
            if not w.fut.done():
                # the pick_waiter POLICY decision, with its justification:
                # who was chosen (priority / fair share) over how many others
                self._journal.event(
                    "waiter_picked", trace_id=w.trace_id, lane=lane,
                    occupancy=self.occupancy_info(),
                    priority=w.priority,
                    waiters=len(self._lane_waiters) + 1,
                )
                w.fut.set_result(lane)
                self._note_occupancy()
                return
        self._free_lanes.append(lane)
        self._note_occupancy()

    # ------------------------------------------------------------------ pages

    async def prepare_write(
        self, lane: int, t0: int, t1: int, timeout: Optional[float] = None, *, windowed: bool = True
    ) -> None:
        """Make token range [t0, t1) of ``lane`` writable: allocate missing
        pages on demand and copy-on-write-fork any page shared with the
        prefix cache (refs > 1). Blocks on an exhausted pool until a page
        frees (release_lane / prefix-cache eviction), raising
        AllocationFailed at ``timeout`` — MemoryCache's backpressure
        contract at page grain. No-op in dense mode. A grouped pool's
        windowed groups take the range's pages too and give back what lies
        behind its first row's window (``_window_pages_now``), unless
        ``windowed`` is false: a prompt admitted whole takes its pages there a
        chunk at a time, as each chunk is fed (``_flush_loop``)."""
        if self.page_size is None or t1 <= t0:
            return
        self._check_lane(lane)
        if t1 > self.max_length:
            raise ValueError(
                f"Write range [{t0}, {t1}) overflows the lane buffer "
                f"({self.max_length} tokens)"
            )
        alloc = self._pages
        deadline = None if timeout is None else time.monotonic() + timeout
        pages_changed = False
        for slot in range(t0 // self.page_size, (t1 - 1) // self.page_size + 1):
            cur = int(self._tables[lane, slot])
            if cur >= 0 and alloc.refs[cur] == 1:
                continue  # already exclusively owned
            preferred = self._identity_page(lane, slot)
            while True:
                page = alloc.try_alloc(preferred=preferred)
                if page is not None:
                    break
                # pool exhausted: before parking on freed_event, try to swap
                # an idle victim lane out to host RAM (no-op when the swap
                # tier is disabled — the PR2 backpressure path is unchanged)
                if await self._try_preempt(exclude=lane):
                    if self._pages is not alloc:
                        raise AllocationFailed(
                            "Lane pool was reset while waiting for a free page"
                        )
                    self._check_lane(lane)
                    continue
                remaining = None if deadline is None else deadline - time.monotonic()
                if remaining is not None and remaining <= 0:
                    tm.ALLOC_FAILED.inc()
                    raise AllocationFailed(
                        f"No free KV page within {timeout} s ({self._occupancy()})"
                    )
                alloc.freed_event.clear()
                wait = remaining
                if self.swap_pool.max_size_bytes > 0 and self._scheduler.policy != "off":
                    # a victim can become IDLE without any page freeing, so
                    # freed_event alone would never retry preemption: poll
                    wait = 0.05 if wait is None else min(wait, 0.05)
                try:
                    await asyncio.wait_for(alloc.freed_event.wait(), timeout=wait)
                except asyncio.TimeoutError:
                    pass  # loop once more to produce the AllocationFailed message
                if self._pages is not alloc:
                    raise AllocationFailed(
                        "Lane pool was reset while waiting for a free page"
                    )
                self._check_lane(lane)
            try:
                if cur >= 0:
                    # shared page: fork it on the compute thread (serialized
                    # with batched steps by the queue), then drop our shared ref
                    await self.queue.submit(
                        self._copy_page, cur, page,
                        priority=PRIORITY_INFERENCE, size=0,
                    )
                    alloc.stats["forked"] += 1
                    self._check_lane(lane)
                    alloc.decref(cur)
            except BaseException:
                if self._pages is alloc:
                    alloc.decref(page)  # never reached the table: hand it back
                raise
            self._write_tables(lane, slot, page)
            pages_changed = True
        if pages_changed:
            # attribution rates changed (a grow or a COW fork): settle the
            # ledger here, not on the next admission boundary — page-seconds
            # accrued under the old rates up to this instant
            self._ledger_sync()
        while windowed and self._win and not self._window_pages_now(lane, t0, t1):
            remaining = None if deadline is None else deadline - time.monotonic()
            if remaining is not None and remaining <= 0:
                tm.ALLOC_FAILED.inc()
                raise AllocationFailed(f"No free KV page of a windowed layer within {timeout} s ({self._occupancy()})")
            await asyncio.sleep(0.005 if remaining is None else min(remaining, 0.005))  # a page goes back as another lane's window moves
            if self._pages is not alloc:
                raise AllocationFailed("Lane pool was reset while waiting for a free page")
            self._check_lane(lane)

    def _window_release(self, group: _WindowGroup, lane: int, below: int, above: Optional[int] = None) -> int:
        """Give ``lane``'s pages in ``group``'s slots under ``below`` (and, where given, over ``above``: what a rollback
        left ahead of the lane) back to the group's allocator; how many went. A lane's held slots are one run,
        ``[group.first[lane], group.last[lane]]``, cut here from either end."""
        first, last = group.first[lane], group.last[lane]
        keep_to = last if above is None else min(last, above)
        row = group.tables[lane]
        gone = [slot for slot in (*range(first, min(below, last + 1)), *range(max(keep_to + 1, first, below), last + 1)) if row[slot] >= 0]
        if not gone:
            return 0
        for slot in gone:
            group.alloc.decref(int(row[slot]))
        row[gone] = -1
        group.first[lane], group.last[lane] = max(first, below), keep_to
        if group.first[lane] > group.last[lane]:  # nothing held: the next page taken starts a run of its own
            group.first[lane], group.last[lane] = 0, -1
        group.lane_held[lane] -= len(gone)
        self._tables_version += 1
        return len(gone)

    def _window_pages_now(self, lane: int, t0: int, t1: int) -> bool:
        """A grouped pool's windowed groups made ready for ``lane`` to feed rows [t0, t1), without a wait (event loop): in
        each, the pages wholly behind the first row's window (every position under ``t0 - window + 1``) go back to the
        group's allocator, counted in ``window_pages_released``, and the pages of [t0, t1) the lane lacks are taken. So a
        lane holds there, when a step starts, exactly the pages its rows' windows reach. False where a group has no free
        page yet (what was taken stays with the lane; the caller waits and asks again)."""
        ps = self.page_size
        for group in self._win:
            self.stats["window_pages_released"] += self._window_release(group, lane, max(t0 - group.window + 1, 0) // ps, (t1 - 1) // ps)
            for slot in range(max(t0 // ps, group.last[lane] + 1), (t1 - 1) // ps + 1):
                page = group.alloc.try_alloc()
                if page is None:
                    return False
                group.tables[lane, slot] = page
                if group.last[lane] < group.first[lane]:
                    group.first[lane] = slot
                group.last[lane] = slot
                group.lane_held[lane] += 1
                self._tables_version += 1
        return True

    def window_reach_held(self, lane: int, position: int) -> bool:
        """Whether ``lane`` still holds, in every windowed group, the pages a row at ``position`` reaches behind itself: a
        rollback to there can be served (a page behind a window that has moved on has gone back to the pool, with its rows)."""
        ps = self.page_size
        if position <= 0:
            return True
        return all(
            (group.tables[lane, max(position - group.window + 1, 0) // ps : (position - 1) // ps + 1] >= 0).all() for group in self._win
        )

    def _identity_page(self, lane: int, slot: int) -> Optional[int]:
        """The page to ask for first: identity preference keeps tables
        contiguous at the default pool size. Paged attention serves any
        layout, but identity tables read pages in sequential HBM order (and
        keep the tables_contiguous debug flag meaningful)."""
        if self.n_pages != self.n_lanes * self.max_pages:
            return None
        return lane * self.max_pages + slot

    def _own_page_now(self, lane: int, position: int) -> bool:
        """``prepare_write`` for one token, as far as it goes without a wait:
        True with the token's page the lane's own (already, or taken from the
        free list here). False, and nothing changed, where it would have to
        wait for a page, fork a shared one, or raise."""
        if position >= self.max_length:
            return False
        slot = position // self.page_size
        cur = int(self._tables[lane, slot])
        if cur >= 0:
            return self._pages.refs[cur] == 1 and self._window_pages_now(lane, position, position + 1)
        page = self._pages.try_alloc(preferred=self._identity_page(lane, slot))
        if page is None:
            return False
        self._write_tables(lane, slot, page)
        self._ledger_sync()  # a grow: page-seconds accrued under the old rates up to here
        return self._window_pages_now(lane, position, position + 1)

    def _copy_page(self, src: int, dst: int) -> None:
        """Compute-thread body: device copy of one page (all blocks) — the
        copy-on-write fork. Donating, so swapped under the reset lock like
        every other pool-touching op."""
        with self._reset_lock:
            k_pool, v_pool = self._buffers()
            k_pool, v_pool = self.backend._copy_page_fn(
                k_pool, v_pool, np.int32(src), np.int32(dst)
            )
            self._update(k_pool, v_pool)

    @property
    def page_epoch(self) -> int:
        return self._page_epoch

    @property
    def page_nbytes(self) -> int:
        """Wire bytes of one KV page across this span (0 for dense pools) —
        how the radix prefix cache prices its pinned page runs when billing
        HBM residency to tenants through the ledger."""
        if self.page_size is None:
            return 0
        return self._pool.page_bytes

    def pin_lane_pages(self, lane: int, t0: int, t1: int) -> Optional[List[int]]:
        """Take a reference on the pages backing token range [t0, t1) of
        ``lane`` (page-aligned) so the prefix cache can share them after the
        lane is released. Returns the page list, or None when the range is
        not fully resident (or not paged). Pair with unpin_pages."""
        if self.page_size is None or self._tables is None:
            return None
        assert t0 % self.page_size == 0 and t1 % self.page_size == 0, (t0, t1)
        row = self._tables[lane]
        pages = []
        for slot in range(t0 // self.page_size, t1 // self.page_size):
            page = int(row[slot])
            if page < 0:
                return None
            pages.append(page)
        for page in pages:
            # swarmlint: disable=paired-refcount — ownership transfer: the refs belong to the caller (prefix cache), released via unpin_pages; no code below this loop can raise
            self._pages.incref(page)
        self._ledger_sync()  # refcounts moved: the lane's fractional share shrank
        return pages

    def unpin_pages(self, pages: Sequence[int], epoch: int) -> None:
        """Drop prefix-cache references taken by pin_lane_pages. Ignores pins
        from a previous epoch: the reset rebuilt the allocator, so those
        pages no longer exist to decref."""
        if self.page_size is None or self._pages is None or epoch != self._page_epoch:
            return
        for page in pages:
            self._pages.decref(int(page))
        self._ledger_sync()  # pins released: surviving holders' shares grew

    def adopt_pages(self, lane: int, pages: Sequence[int]) -> None:
        """Point ``lane``'s first len(pages) table slots at already-resident
        (prefix-cache-pinned) pages — a cache hit that copies ZERO bytes.
        The lane holds them read-shared; its first write past the prefix
        forks via prepare_write."""
        assert self.page_size is not None and self._tables is not None
        assert len(pages) <= self.max_pages
        row = self._tables[lane]
        for slot, page in enumerate(pages):
            cur = int(row[slot])
            self._pages.incref(int(page))
            if cur >= 0:
                self._pages.decref(cur)
        if pages:
            self._write_tables(lane, slice(0, len(pages)), np.asarray(pages, np.int32))
            tm.PREFIX_ADOPT.inc()
            self._ledger_sync()  # the lane now shares the prefix pages' refcounts

    def _write_tables(self, lane, slots, pages) -> None:
        """The ONE writer of the block tables (event loop): ``pages`` into
        ``slots`` of ``lane``'s row (ints, slices or index arrays, as
        ``tables[lane, slots] = pages`` takes them: alloc, COW fork, adopt,
        release, swap out and in, a pool reset). ``_tables`` itself refuses
        writes, so nothing goes round this. Beside the write it counts the
        slots the lane now owns (``_lane_held``), drops the cached contiguity
        flag and, LAST, bumps ``_tables_version``.

        ``_step_tables`` (compute thread) reads the version FIRST and copies
        the tables after. Nothing orders the two threads but the task queue:
        a lane's pages are written (``prepare_write``, ``_swap_in``) before
        its entry is submitted to the compute thread, so the step that reads
        the lane's row unmasked sees a version at or past that write's and
        sends the tables if its copy is older. A write to ANOTHER lane that
        lands between the read and the copy is in the copy under the old
        number, and the next step sends again: an extra send, never a stale
        table. (The other order, bump then write, or copy then read, could
        record a version whose write the copy lacks.)"""
        self._tables_rw[lane, slots] = pages
        self._lane_held[lane] = (self._tables_rw[lane] >= 0).sum(axis=-1)
        self._tables_contig = None
        self._tables_version += 1

    def _step_tables(self):
        """The block tables a paged step's program reads, on the device
        (compute thread): the copy made for an earlier step if no entry has
        changed value since, else a new snapshot (counted in ``tables_sent``).
        A sent copy is a snapshot and an unsent one is unchanged by
        definition, so a step reads what ``self._tables.copy()`` gave it when
        every step copied: the event loop may grow OTHER lanes while the step
        runs, but never slots this step reads unmasked or writes
        (``prepare_write`` ran before each entry was enqueued). Order and
        races: ``_write_tables``."""
        version = self._tables_version  # before the copy
        sent, on_device = self._tables_on_device
        if on_device is None or sent != version:
            # a grouped pool: the tables a group, [groups, n_lanes, max_pages], the lane's own first
            tables = np.stack([self._tables, *(g.tables for g in self._win)]) if self._win else self._tables
            on_device = self.backend.device_tables(tables)
            self._tables_on_device = (version, on_device)
            self.stats["tables_sent"] += 1
        return on_device

    def tables_contiguous(self) -> Optional[bool]:
        """Stats/debug surface ONLY: are the block tables currently the
        identity layout? The step path no longer branches on this (one paged
        attention path serves both); the flag is kept for observability —
        identity tables mean page reads stream sequentially through HBM.
        Cached; recomputed lazily after a table mutation."""
        if self.page_size is None or self._tables is None:
            return None
        if self._tables_contig is None:
            from petals_tpu.ops.paged_attention import tables_are_contiguous

            self._tables_contig = tables_are_contiguous(self._tables, self.n_pages)
        return self._tables_contig

    def paged_summary(self) -> Optional[dict]:
        """Observability: pool occupancy + allocator counters (rpc_info)."""
        if self.page_size is None:
            return None
        alloc = self._pages
        return {
            "page_size": self.page_size,
            "n_pages": self.n_pages,
            "page_epoch": self._page_epoch,
            "pages_free": alloc.n_free if alloc is not None else self.n_pages,
            "tables_contiguous": self.tables_contiguous(),
            **({f"pages_{k}": v for k, v in alloc.stats.items()} if alloc else {}),
            **({"page_groups": [{"window": g.window, "n_pages": g.n_pages, "pages_free": g.alloc.n_free} for g in self._win]} if self._win else {}),
        }

    # -------------------------------------------------------- preemption / swap

    def _lane_pages(self, lane: int) -> int:
        """Resident page count of a lane (scheduler pages_fn: victim sizing
        and fair-share accounting)."""
        if self._tables is None:
            return 0
        return int(self._lane_held[lane])

    def _moe_dispatch(self, seq: int, chunk: bool = False) -> Optional[str]:
        """``backend.moe_grouped``, asked once a shape (``_count_moe`` asks every step)."""
        took = self._moe_took.get((seq, chunk))
        if took is None:
            took = self._moe_took[seq, chunk] = self.backend.moe_grouped(seq, chunk=chunk)
        return took

    def _lane_lock(self, lane: int) -> AsyncTryLock:
        lock = self._lane_locks.get(lane)
        if lock is None:
            # one shared sanitizer name: lane locks are an equivalence class
            # (never nested within each other except via trylock, below)
            lock = self._lane_locks[lane] = make_async_lock("batching.lane_lock")
        return lock

    @contextlib.asynccontextmanager
    async def _lane_busy(self, lane: int):
        """Guard every lane-touching op: a suspended lane transparently swaps
        back in first, then the in-flight counter marks the lane unpreemptable
        for the op's duration. No await between the resident check returning
        and the increment, so the pair is atomic on the event loop."""
        await self._ensure_resident(lane)
        self._enter_lane(lane)
        try:
            yield
        finally:
            self._leave_lane(lane)

    def _enter_lane(self, lane: int) -> None:
        self._inflight[lane] = self._inflight.get(lane, 0) + 1
        self._scheduler.touch(lane)

    def _leave_lane(self, lane: int) -> None:
        self._inflight[lane] -= 1
        # a step boundary IS the preemption opportunity: when decode is
        # compute-bound, lanes are idle only in the sliver between ops,
        # which timer polls almost always miss — wake page waiters now
        # so they re-attempt victim selection while this lane is idle
        if (
            self._inflight[lane] == 0
            and self._pages is not None
            and self.swap_pool.max_size_bytes > 0
        ):
            self._pages.freed_event.set()

    def _lane_idle(self, lane: int, *, ignore_lock: bool = False) -> bool:
        """A lane is preemptable only while NOTHING is touching it: no step
        pending or in flight, no server-gen or prefill stream, no exclusive
        op, no swap already in progress — and some pages actually resident
        to reclaim. ``ignore_lock`` is for the re-check inside
        _swap_out_lane, which holds the lane lock itself."""
        if self._lane_generation.get(lane) != self._generation:
            return False
        if self._inflight.get(lane, 0) > 0:
            return False
        if lane in self._gen_states:
            return False
        if any(p.lane == lane for p in self._prefill_queue):
            return False
        if any(e[0] == lane for e in self._pending):
            return False
        if not ignore_lock:
            lock = self._lane_locks.get(lane)
            if lock is not None and lock.locked():
                return False
        return self._lane_pages(lane) > 0

    async def _try_preempt(self, exclude: int) -> bool:
        """Pool exhausted: try to swap ONE idle victim lane out to host RAM.
        Returns True when a victim's pages were freed (the caller retries
        allocation immediately); False means no preemptable victim — fall
        back to waiting on freed_event, the old backpressure path. Victims
        must be of equal-or-lower priority than the requester."""
        sched = self._scheduler
        if (
            self.page_size is None
            or sched.policy == "off"
            or self.swap_pool.max_size_bytes <= 0
        ):
            return False
        req = sched.lanes.get(exclude)
        max_priority = req.priority if req is not None else None
        candidates = [
            l for l in list(self._lane_generation)
            if l != exclude and self._lane_idle(l)
        ]
        victim = sched.pick_victim(candidates, max_priority=max_priority)
        if victim is None:
            return False
        # journal the DECISION (outcome shows as a following swap_out event
        # or its absence): who was evicted, for whom, under what occupancy
        self._journal.event(
            "victim_selected",
            trace_id=sched.trace_id_of(victim),
            lane=victim,
            occupancy=self.occupancy_info(),
            requester_lane=exclude,
            requester_trace_id=sched.trace_id_of(exclude),
            policy=sched.policy,
            candidates=list(candidates),
        )
        return await self._swap_out_lane(victim)

    async def _swap_out_lane(self, lane: int) -> bool:
        """Suspend ``lane``: gather its resident pages on device, copy them to
        the host swap pool, then free the pages (waking allocation waiters).
        The block-table row is cleared; swap-in may later land the content on
        entirely different physical pages. Aborts harmlessly (False) if the
        lane's state moved while the gather ran — release_lane, a pool reset,
        or a racing op all invalidate the snapshot."""
        sched = self._scheduler
        slot = sched.lanes.get(lane)
        if slot is None or slot.swap is not None or slot.suspending:
            return False
        lock = self._lane_lock(lane)
        # non-blocking trylock (records no sanitizer order edge): a held lane
        # lock means the lane is busy, i.e. not preemptable — and a blocking
        # acquire would invert the lane-lock -> turnstile order, since
        # _try_preempt can run with the swap-in turnstile held (_swap_in)
        if not lock_try_acquire_nowait(lock):
            return False
        try:
            if not self._lane_idle(lane, ignore_lock=True):
                return False
            if sched.lanes.get(lane) is not slot or slot.swap is not None:
                return False
            alloc = self._pages
            gen = self._lane_generation.get(lane)
            row = self._tables[lane]
            slots = np.flatnonzero(row >= 0).astype(np.int32)
            if slots.size == 0:
                return False
            pages = row[slots].astype(np.int32).copy()
            nbytes = int(slots.size) * self._pool.page_bytes + self._pool.state_bytes
            if not self.swap_pool.try_reserve(nbytes):
                return False  # swap tier full: this victim is not preemptable
            slot.suspending = True
            try:
                k_host, v_host, state_host = await self.queue.submit(
                    self._swap_out_device, pages, lane,
                    priority=PRIORITY_INFERENCE, size=0,
                )
            except asyncio.CancelledError:
                self.swap_pool.free(nbytes)
                slot.suspending = False
                sched.stats["swap_aborted"] += 1
                raise
            except Exception as e:
                # the gather is non-donating, so the pool is intact; degrade
                # to the plain backpressure path rather than failing the
                # REQUESTER for the victim's trouble
                logger.warning("Swap-out gather for lane %d failed: %r", lane, e)
                self.swap_pool.free(nbytes)
                slot.suspending = False
                sched.stats["swap_aborted"] += 1
                return False
            # validate nothing moved while the gather ran; only now (host
            # copy landed, snapshot still true) do the pages actually free
            if (
                sched.lanes.get(lane) is not slot
                or self._pages is not alloc
                or self._lane_generation.get(lane) != gen
                or gen != self._generation
                or not np.array_equal(self._tables[lane][slots], pages)
            ):
                self.swap_pool.free(nbytes)
                slot.suspending = False
                sched.stats["swap_aborted"] += 1
                return False
            for page in pages:
                alloc.decref(int(page))
            self._write_tables(lane, slots, -1)
            slot.swap = SwapEntry(
                k=k_host, v=v_host, slots=slots, nbytes=nbytes, generation=gen,
                suspended_at=time.monotonic(), state=state_host,
            )
            slot.suspending = False
            sched.stats["preemptions"] += 1
            sched.stats["swap_outs"] += 1
            tm.PREEMPTIONS.inc()
            tm.SWAP_OUT_BYTES.inc(nbytes)
            key = self._ledger_keys.get(lane)
            if key is not None:
                self._ledger.note_swap(key, out_bytes=nbytes)
            self._journal.event(
                "swap_out", trace_id=slot.trace_id, lane=lane,
                occupancy=self.occupancy_info(),
                pages=int(slots.size), nbytes=nbytes,
            )
            self._note_occupancy()
            logger.debug(
                f"Preempted lane {lane}: {slots.size} pages -> host swap "
                f"({self.swap_pool.bytes_in_use}/{self.swap_pool.max_size_bytes} B used)"
            )
            return True
        finally:
            lock.release()

    def _swap_out_device(self, pages: np.ndarray, lane: int):
        """Compute-thread body: gather the victim's pages, and its states
        where the span keeps any, and land them in host RAM. Non-donating —
        the pool stays live; the pages only free once the event loop
        validates and commits the suspend."""
        with self._reset_lock:
            k_pool, v_pool = self._buffers()
            k, v = self.backend._swap_out_pages_fn(k_pool, v_pool, pages)
            state = self.backend._lane_state_take_fn(self._state(), np.int32(lane)) if self.backend.cache.lane_state else ()
            # per-leaf host copy: a quantized pool's SwapEntry holds a
            # PagedPool of numpy arrays — packed wire bytes, never fp pages
            # (rows of [hkv, d_store], whatever row the pool stores: a
            # reshape of the host's copy)
            to_host = lambda t: jax.tree_util.tree_map(np.asarray, t)
            to_wire = self.backend.pool_to_wire
            return to_wire(to_host(k)), to_wire(to_host(v)), to_host(tuple(state))

    def _resident_now(self, lane: int) -> bool:
        """Neither swapped out nor on its way out: an op may touch the lane without a swap-in first."""
        slot = self._scheduler.lanes.get(lane)
        return slot is None or (slot.swap is None and not slot.suspending)

    async def _ensure_resident(self, lane: int) -> None:
        """Transparent resume: if ``lane`` is suspended (or a suspend is in
        flight — the lock serializes us behind it), swap its KV back in
        before the caller's op proceeds."""
        sched = self._scheduler
        if self._resident_now(lane):
            return
        async with self._lane_lock(lane):
            slot = sched.lanes.get(lane)
            if slot is None or slot.swap is None:
                return  # suspend aborted, or lane released meanwhile
            await self._swap_in(lane, slot)

    async def _swap_in(self, lane: int, slot) -> None:
        """Resume a suspended lane (lane lock held): allocate fresh pages
        (all-or-nothing, preempting others if needed), scatter the host copy
        back into the pool, and restore the block-table row — onto possibly
        different physical pages than before."""
        sched = self._scheduler
        entry = slot.swap
        self._check_lane(lane)
        # only the ALLOCATION is serialized: once this resumer holds its
        # pages the next one can start negotiating for pages while our
        # scatter runs on the compute queue — the turnstile exists to stop
        # concurrent allocators hoarding partial page sets, not to make
        # swap-ins take turns at the device
        async with self._swap_in_turnstile:
            pages = await self._alloc_pages(lane, entry.slots)
        alloc = self._pages
        pages_arr = np.asarray(pages, np.int32)
        try:
            await self.queue.submit(
                self._swap_in_device, lane, entry, pages_arr,
                priority=PRIORITY_INFERENCE, size=0,
            )
        except BaseException:
            if self._pages is alloc:
                for page in pages:
                    alloc.decref(int(page))
            self._maybe_reset_pool()  # the scatter donates the pool buffers
            raise
        self._write_tables(lane, entry.slots, pages_arr)
        slot.swap = None
        slot.resumed_at = time.monotonic()
        self.swap_pool.free(entry.nbytes)
        sched.stats["swap_ins"] += 1
        tm.SWAP_IN_BYTES.inc(entry.nbytes)
        key = self._ledger_keys.get(lane)
        if key is not None:
            self._ledger.note_swap(key, in_bytes=entry.nbytes)
        self._journal.event(
            "swap_in", trace_id=slot.trace_id, lane=lane,
            occupancy=self.occupancy_info(),
            pages=int(entry.slots.size), nbytes=entry.nbytes,
        )
        self._note_occupancy()
        logger.debug(f"Resumed lane {lane}: {entry.slots.size} pages swapped in")

    def _swap_in_device(self, lane: int, entry, pages: np.ndarray) -> None:
        """Compute-thread body: scatter a swap entry's KV onto fresh pages.
        Donating, so the generation check rides INSIDE the reset lock — the
        same TOCTOU rule as _insert_lane."""
        with self._reset_lock:
            self._check_lane(lane)
            if entry.generation != self._generation:
                raise AllocationFailed(
                    "Lane pool was reset while this session was swapped out"
                )
            k_pool, v_pool = self._buffers()
            to_pool = self.backend.wire_to_pool  # the host's rows of [hkv, d_store] as the pool stores them
            k_pool, v_pool = self.backend._swap_in_pages_fn(
                k_pool, v_pool, to_pool(entry.k, k_pool), to_pool(entry.v, v_pool), pages
            )
            state = self.backend._lane_state_put_fn(self._state(), entry.state, np.int32(lane)) if self.backend.cache.lane_state else ()
            self._update(k_pool, v_pool, *state)

    async def _alloc_pages(self, lane: int, slots: np.ndarray) -> List[int]:
        """All-or-nothing page allocation for a swap-in: take len(slots)
        pages only once that many are simultaneously free — two resuming
        lanes each holding a partial set would deadlock — preempting other
        lanes when the pool is short. Identity slots are preferred so a
        resumed lane can regain the contiguous fast path when its old pages
        happen to be free."""
        alloc = self._pages
        n = int(len(slots))
        identity_base = (
            lane * self.max_pages
            if self.n_pages == self.n_lanes * self.max_pages else None
        )
        timeout = 30.0 if self.alloc_timeout is None else self.alloc_timeout
        deadline = time.monotonic() + timeout
        while True:
            if self._pages is not alloc:
                raise AllocationFailed("Lane pool was reset while waiting for a free page")
            self._check_lane(lane)
            if alloc.n_free >= n:
                pages = []
                for slot in slots:
                    preferred = None if identity_base is None else identity_base + int(slot)
                    page = alloc.try_alloc(preferred=preferred)
                    assert page is not None, "n_free lied: allocator invariant broken"
                    pages.append(page)
                return pages
            if await self._try_preempt(exclude=lane):
                continue
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                tm.ALLOC_FAILED.inc()
                raise AllocationFailed(
                    f"No free KV page for swap-in within {timeout} s ({self._occupancy()})"
                )
            alloc.freed_event.clear()
            try:
                # bounded wait (not remaining): see prepare_write — preemption
                # must re-attempt when a victim merely becomes idle
                await asyncio.wait_for(
                    alloc.freed_event.wait(), timeout=min(remaining, 0.05)
                )
            except asyncio.TimeoutError:
                pass  # loop once more to produce the AllocationFailed message

    # -------------------------------------------------------- observability

    def _note_occupancy(self) -> None:
        """Refresh the pool gauges. Called at admission/release/swap
        boundaries — occupancy only changes there, so the decode tick path
        pays nothing for these."""
        busy = (self.n_lanes - len(self._free_lanes)) if self.is_open else 0
        tm.LANES_BUSY.set(busy)
        if self.page_size is not None:
            tm.PAGES_TOTAL.set(self.n_pages)
            tm.PAGES_FREE.set(
                self._pages.n_free if self._pages is not None else self.n_pages
            )
            if self._pages is not None:
                # page-pool economics: free-run histogram + fragmentation.
                # O(free pages) with a sort, but only at admission/release/
                # swap boundaries — never on the decode tick.
                info = self._pages.fragmentation_info()
                tm.PAGE_FRAGMENTATION.set(info["frag"])
                tm.PAGE_LARGEST_RUN.set(info["largest_run"])
                for bucket, child in tm.PAGE_FREE_RUN_CHILDREN.items():
                    child.set(info["run_hist"][bucket])
        mc = self.memory_cache
        if mc is not None and mc.max_size_bytes < 2**60:
            # only meaningful under a real HBM budget (the default cache is
            # effectively unbounded and would read as 2**64 headroom)
            tm.HBM_HEADROOM.set(mc.bytes_left)
        tm.SWAP_RESIDENCY_OLDEST.set(self._scheduler.oldest_swap_age())
        # the same boundaries are the ledger's settlement points: push a
        # fresh attribution-rate snapshot, then give the noisy-neighbor
        # detector a look while the admission queue state is current
        self._ledger_sync()
        if self._lane_waiters:
            self._ledger_check_noisy()

    def _ledger_sync(self) -> None:
        """Settle the resource ledger and install the new piecewise-constant
        rates: each session's fractional page holding (1/refcount per
        referenced page — prefix-cache pins absorb the remainder) plus the
        pool occupancy whose integral the per-session split must sum to.
        Called wherever block tables or refcounts change; O(lanes x
        max_pages) vectorized, never on the per-token decode path."""
        weights: Dict[str, float] = {}
        occupied = 0.0
        if (
            self.page_size is not None
            and self._pages is not None
            and self._tables is not None
        ):
            occupied = float(self.n_pages - self._pages.n_free)
            if self._ledger_keys:
                lanes = list(self._ledger_keys)
                shares = self._pages.fractional_shares(self._tables[lanes])
                weights = {
                    self._ledger_keys[lane]: float(s)
                    for lane, s in zip(lanes, shares)
                }
        self._ledger.set_rates(weights, occupied)

    def _ledger_check_noisy(self) -> None:
        """Ask the DRF detector whether one peer's dominant-resource share
        is starving the admission queue; journal the evidence when it fires
        (the counter bump + flight-recorder entry happen inside the ledger)."""
        evidence = self._ledger.check_noisy(
            [w.peer_id for w in self._lane_waiters if not w.fut.done()]
        )
        if evidence is not None:
            self._journal.event(
                "noisy_neighbor", occupancy=self.occupancy_info(), **evidence
            )

    def pop_usage_delta(self, lane: int) -> Optional[dict]:
        """Per-session resource usage since the last call — the tenant's own
        bill, piggybacked on step_meta so InferenceSession.usage_report()
        can aggregate it client-side. None for unmetered (dense/private)
        lanes or an empty delta."""
        key = self._ledger_keys.get(lane)
        if key is None:
            return None
        delta = self._ledger.usage_delta(key)
        if delta and delta.get("spec_proposed"):
            # per-reply speculative efficiency rides the bill (acceptance
            # rate and tokens per compute-second over this delta window)
            from petals_tpu.telemetry.ledger import derive_efficiency

            delta.update(derive_efficiency(delta))
        return delta or None

    def _occupancy(self) -> str:
        """Human-readable pool occupancy for AllocationFailed messages: lane
        and page counts, per-lane page holdings, and swap-tier usage — so a
        rejected client (and the operator reading its logs) can see WHY."""
        busy = (self.n_lanes - len(self._free_lanes)) if self.is_open else 0
        parts = [
            f"{busy}/{self.n_lanes} lanes busy",
            f"{len(self._lane_waiters)} waiters",
        ]
        if self.page_size is not None and self._pages is not None:
            parts.append(f"{self._pages.n_free}/{self.n_pages} pages free")
            if self._tables is not None and self._lane_generation:
                held = ", ".join(
                    f"lane {l}: {self._lane_pages(l)}"
                    for l in sorted(self._lane_generation)
                )
                parts.append(f"pages held: [{held}]")
        if self.swap_pool.max_size_bytes > 0:
            parts.append(
                f"{self._scheduler.suspended_count} suspended, swap "
                f"{self.swap_pool.bytes_in_use}/{self.swap_pool.max_size_bytes} B"
            )
        return "; ".join(parts)

    def occupancy_info(self) -> dict:
        """Machine-readable pool/scheduler occupancy (ServerInfo.pool,
        rpc_info, run_health): enough for a client to route around a loaded
        server — busy lanes, free pages, suspended sessions, swap bytes,
        preemption count."""
        info = {
            "lanes": self.n_lanes,
            "busy_lanes": (self.n_lanes - len(self._free_lanes)) if self.is_open else 0,
            "lane_waiters": len(self._lane_waiters),
        }
        if self.page_size is not None:
            info["n_pages"] = self.n_pages
            info["pages_free"] = (
                self._pages.n_free if self._pages is not None else self.n_pages
            )
            if self._pages is not None:
                frag = self._pages.fragmentation_info()
                info["frag"] = frag["frag"]
                info["largest_free_run"] = frag["largest_run"]
            # honest capacity math for clients: the pool's encoding and its
            # WIRE bytes/token (what a page actually costs under kv quant)
            cache, pool = self.backend.cache, self._pool
            info["kv_quant"] = cache.kv_quant_type
            # the trailing dims the pool keeps a token row in: (hkv, d_store), or folded to one (``stored_row``: a row under 128 lanes,
            # or of up to 4 kv heads)
            info["pool_row"] = list(cache.pool_row)
            # which walk a decode row's attention takes over these pages, a distinct window of the span's layers
            info["decode_walk"] = [walk[-1] for walk in pool.walks]
            info["kv_bytes_per_token"] = int(cache.kv_bytes_per_token())
            if cache.content == "state":
                # which form of the one-step rule a decode row's state layers take; a lane's fixed part, beside what
                # its pages cost a token, and what the busy lanes hold of it
                info["state_step"] = pool.state_step
                info["state_bytes_per_lane"] = pool.state_bytes
                info["state_bytes_held"] = info["busy_lanes"] * pool.state_bytes
            elif cache.content == "index":  # of kv_bytes_per_token, the index rows' part
                info["index_bytes_per_token"] = int(cache.index_bytes_per_token())
            elif cache.content == "latent":
                # what a position caches in place of keys and values, (latent, rotated key), and what the pages in
                # use hold of it
                info["latent_row"] = list(cache.latent_row)
                info["latent_bytes_held"] = (self.n_pages - info["pages_free"]) * pool.page_bytes
            if pool.windows and self._tables is not None:
                # over the lanes that hold pages, at the last position each fed
                live = np.flatnonzero(self._lane_held)
                info["window_pages_held"], info["window_pages_in_reach"] = pool.window_pages(live, self._lane_held, self._held_by_group())
            if self._grouped:
                # pages by kind of layer: a windowed group's free pages and the most a lane holds there when a step starts
                info["page_groups"] = [
                    {"window": None, "layers": len(cache.page_groups[0][1]), "n_pages": self.n_pages, "pages_free": info["pages_free"],
                     "lane_pages": self.max_pages},
                    *({"window": g.window, "layers": g.layers, "n_pages": g.n_pages, "pages_free": g.alloc.n_free,
                       "lane_pages": cache.lane_pages(g.window, self.max_pages, self.page_size, self.prefill_token_budget)} for g in self._win),
                ]
            # of the step bodies so far, those that copied the block tables to the device (``_step_tables``)
            info["tables_sent"], info["batched_steps"] = self.stats["tables_sent"], self.stats["batched_steps"]
            # ... and those launched while the step before was still in flight (``_start_behind``)
            info["overlapped_steps"] = self.stats["overlapped_steps"]
        info.update(self._scheduler.summary())
        return info

    def occupancy_hint(self) -> dict:
        """Two-field load hint riding every step_meta reply (cheaper than the
        full occupancy_info dict, and small enough for every token)."""
        return {
            "busy_lanes": (self.n_lanes - len(self._free_lanes)) if self.is_open else 0,
            "lane_waiters": len(self._lane_waiters),
        }

    def pop_step_timing(self, lane: int) -> Optional[dict]:
        """Consume the finished step's queue/compute attribution for ``lane``
        (written by the compute thread / flush loop just before the step
        future resolved). None when no timed step completed — e.g. a
        cached-prefix fast path that never touched the device."""
        return self._step_timing.pop(lane, None)

    def count_decode_reply(self, resumed_s: float, built_s: float, sent_s: float) -> None:
        """One decode reply's way out, from the handler that made it, once it
        is sent: the flush loop's resolving of the lane's future
        (``pop_step_timing``'s ``replied``) to the handler running again, from
        there to the reply yielded to the RPC server, and what the server took
        to send it (``rpc.server.StreamRequests.sent_s``)."""
        self.stats["decode_replies"] += 1
        self.stats["reply_resume_s"] += resumed_s
        self.stats["reply_build_s"] += built_s
        self.stats["rpc_send_s"] += sent_s

    def pop_step_fp(self, lane: int) -> Optional[list]:
        """Consume the finished step's fused activation fingerprint for
        ``lane`` (FP_DIM floats; ops/fingerprint.py) — the handler
        piggybacks it on step_meta next to the timing attribution. None
        when fingerprinting is disabled or no batched step ran."""
        return self._step_fp.pop(lane, None)

    def _capture_step_fp(self, lanes, chunk_lane: Optional[int] = None) -> None:
        """Stash the backend's fused per-lane fingerprints (compute thread,
        right after the step's host sync — same discipline as
        _record_decode_timing). ``chunk_lane`` takes the mixed step's
        prefill-chunk digest: its LAST chunk's digest is what the client
        re-derives from the assembled prefill reply."""
        pop = getattr(self.backend, "pop_step_fp", None)
        if pop is None:
            return  # wrapper backend without the fingerprint plane
        fp, chunk_fp = pop()
        if fp is not None:
            host = np.asarray(fp)
            for lane in lanes:
                self._step_fp[lane] = [float(x) for x in host[lane]]
        if chunk_fp is not None and chunk_lane is not None:
            self._step_fp[chunk_lane] = [
                float(x) for x in np.asarray(chunk_fp).reshape(-1)
            ]

    # ------------------------------------------------------------------ stepping

    def _check_lane(self, lane: int) -> None:
        if self._lane_generation.get(lane) != self._generation:
            raise AllocationFailed(
                "Lane pool was reset after a failed device step: this "
                "session's KV is gone; the client must re-open the session"
            )

    async def step(
        self, lane: int, hidden: np.ndarray, position: int,
        arrived: Optional[Tuple[Optional[float], float]] = None,
    ) -> np.ndarray:
        """One decode token for ``lane`` (hidden [1, 1, hidden]). It rides the
        next batched step together with every lane that is pending when that
        step starts, and the flush loop does not start a step while lanes that
        are predictably on their way back are worth waiting for (``_gather``).
        A preempted (swapped-out) lane transparently swaps back in first.
        ``arrived`` is the caller's account of the request's way here, two
        readings of ``time.perf_counter``: when its frame was read whole (None
        if nobody took that) and when the caller held the item."""
        t_enq = time.perf_counter()  # before _lane_busy: lock + alloc waits count as queue
        self.stats["rpc_intake_queued"] += 1
        self._count_return(lane, t_enq, arrived)
        async with self._lane_busy(lane):
            self._check_lane(lane)
            if self.page_size is not None:
                # grow the lane to cover this token BEFORE the device step —
                # allocation can await a freed page; the step itself never
                # blocks. alloc_timeout bounds the wait: without it, N
                # sessions each needing one more page from an exhausted pool
                # (and none willing to release) deadlock forever
                await self.prepare_write(
                    lane, int(position), int(position) + 1,
                    timeout=self.alloc_timeout,
                )
            fut = asyncio.get_running_loop().create_future()
            self._enqueue(lane, hidden, position, fut, t_enq)
            return await fut

    def begin_step(
        self, lane: int, hidden: np.ndarray, position: int, fut: asyncio.Future,
        arrived: Optional[Tuple[Optional[float], float]] = None,
    ) -> bool:
        """``step()`` up to its await, for a caller that may not wait (the
        connection's reader, on its own turn): the token is queued for the
        next batched step, which resolves ``fut`` as it would ``step()``'s
        own, and the caller owes an ``end_step(lane)`` once ``fut`` is done.
        False, with nothing changed, where ``step()`` would have had to wait
        or to raise: a lane swapped out or on its way out, a pool that was
        reset, a page to take from an empty pool or to fork."""
        if not self._resident_now(lane) or self._lane_generation.get(lane) != self._generation:
            return False
        if self.page_size is not None and not self._own_page_now(lane, int(position)):
            return False
        t_enq = time.perf_counter()
        self.stats["rpc_intake_direct"] += 1
        self._count_return(lane, t_enq, arrived)
        self._enter_lane(lane)
        self._enqueue(lane, hidden, position, fut, t_enq)
        return True

    def end_step(self, lane: int) -> None:
        """A ``begin_step`` 's future is done (or given up): the lane is no
        longer held for it."""
        self._leave_lane(lane)

    def _count_return(self, lane: int, t_enq: float, arrived) -> None:
        back = self._returns.get(lane)
        if back is not None and back.replied is not None:
            # the lane is back from a decode reply: the trip's length, and
            # the server's own share of its way in
            self.stats["lane_returns"] += 1
            self.stats["lane_return_s"] += t_enq - back.replied
            if arrived is not None:
                read_at, held_at = arrived
                if read_at is not None:
                    self.stats["rpc_recv_s"] += held_at - read_at
                self.stats["request_handle_s"] += t_enq - held_at
            back.came_back(t_enq)
            self._back_since_step = True
            self._gather_wake.set()  # no longer expected, even if a page wait holds it up

    def _enqueue(self, lane: int, hidden: np.ndarray, position: int, fut: asyncio.Future, t_enq: float) -> None:
        self._enq_t[lane] = t_enq  # written under _lane_busy: no overwrite race
        self._pending.append((lane, hidden, int(position), fut, self._generation))
        # its way back is on record from here until release_lane: the
        # reply of a step in flight finds no entry for a lane released
        # meanwhile, so the next tenant starts without a history
        self._returns.setdefault(lane, _LaneReturn())
        self._gather_wake.set()
        self._spawn_flush_loop()

    def _spawn_flush_loop(self) -> None:
        """(Re)start the flush loop if it is not already draining. The strong
        reference in ``self._flush_task`` keeps the loop alive (asyncio holds
        tasks weakly) and the done-callback surfaces a crashed drain — a
        silently dead flush loop would hang every pending step future."""
        if self._flush_task is None or self._flush_task.done():
            self._flush_spawns += 1
            self._flush_task = asyncio.create_task(self._flush_loop())
            self._flush_task.add_done_callback(
                log_exception_callback(logger, "decode flush loop")
            )

    def _gather_until(self, now: float, flight: Optional[_StepInFlight] = None) -> Tuple[Optional[float], List[int]]:
        """The rule of the gather. N units of work are ready (pending decode
        lanes, generating and speculating lanes, one admitted prompt chunk)
        and some lanes are expected (``_LaneReturn.expected``: the decode
        reply is out, the lane has come back before, in less than a step, and
        is not long overdue). Waiting w for M of them costs the ready lanes
        N x w and spares those M lanes (S - w) each, the rest of the step of
        S seconds they would otherwise sit out, so it pays while
        w < S x M / (N + M). Over the expected lanes in the order of their
        predicted arrival, the largest M whose arrival lies inside that bound
        gives the lanes to wait for, and the bound itself the time until
        which their coming still pays; (None, []) says start now.

        S is what a lane that misses the step sits out. For a tick that
        carries a prompt chunk or a generating lane that is a step's wall, as
        ever. For a plain decode step of the paged pool it is less: the late
        lanes' own step is launched behind this one (``_start_behind``), its
        host part is paid while this one is on the chip, and what they sit out
        is this step's time on the device, its wall less a launch's lead (and
        no less than that lead: the compute thread launches one step at a
        time). The sum is the same one, N x w against M x (S - w), with the
        term measured that the overlap changed.

        Asked with a step in flight (``_start_behind``, which asks as at that
        step's end), ``flight``'s lanes are expected too: their replies leave
        when its rows are home, so each is due its usual return after that."""
        step_s = self._step_s
        ready = len(self._pending) + len(self._gen_states) + bool(self._prefill_queue)
        if not step_s or not ready:
            return None, []
        sits_out = step_s
        if self.page_size is not None and not self._gen_states and not self._prefill_queue:
            sits_out = max(step_s - self._lead_s, min(self._lead_s, step_s))
        expected = [
            (back.eta, lane) for lane, back in self._returns.items()
            if back.expected(now, step_s)
        ]
        if flight is not None:
            home = max(flight.end_eta, now)
            for lane in flight.lanes:
                back = self._returns.get(lane)
                if back is not None and back.predictable(step_s):
                    expected.append((home + back.usual(), lane))
        expected.sort()
        until, count = None, 0
        for m, (eta, _lane) in enumerate(expected, 1):
            pays_for = sits_out * m / (ready + m)
            if eta - now < pays_for:
                until, count = now + pays_for, m
        return until, [lane for _eta, lane in expected[:count]]

    async def _gather(self) -> None:
        """Before a step starts: wait, on the event loop, for the lanes that
        ``_gather_until`` finds worth waiting for. An arrival, a release,
        ``close()`` and a pool reset each wake the wait, and the rule is asked
        again from that moment (N has grown, M has shrunk; what has been
        waited is spent either way, so the last of eight lanes is weighed
        against S/8 from when the seventh came, not from when the first did).
        It ends the moment nobody is expected, when the time the rule gave
        runs out with nobody come, and one step after it began whatever the
        arrivals: that, S, bounds the whole wait. A lane that was waited for
        and did not come leaves the expected set until it is seen again, so
        it costs one wait, once. With nobody expected this returns without
        suspending, and the loop is the one it was without a gather."""
        start = now = time.perf_counter()
        stop = start + self._step_s
        waited_for: set = set()
        while not self._closed and now < stop:
            self._gather_wake.clear()
            until, lanes = self._gather_until(now)
            if until is None:
                break
            waited_for.update(lanes)
            self._gathering = start
            try:
                # the one annotation that spans an await: at most one flush
                # task is alive, and whatever else the loop's thread runs
                # during the wait opens and closes inside it
                with device_annotation("ptu.gather", lanes=len(lanes)):
                    await asyncio.wait_for(self._gather_wake.wait(), min(until, stop) - now)
            except asyncio.TimeoutError:
                break
            now = time.perf_counter()
        self._gathering = None
        if not waited_for:
            return
        waited = time.perf_counter() - start
        self.stats["gather_waits"] += 1
        pending = {entry[0] for entry in self._pending}
        for lane in waited_for:
            back = self._returns.get(lane)
            if lane in pending:
                self.stats["gather_joined"] += 1
            elif back is not None and back.replied is not None:
                self.stats["gather_missed"] += 1
                back.eta = None
        # the batcher chose to have nothing running: that is no hand-off
        # (turnaround_s, handoff_s: a step to run and the host in the way), and
        # gather_wait_s is what of it the compute thread had nothing in flight
        # and nothing to run (_split_idle lays it over that thread's gap)
        self._gathered.append((start, start + waited))

    async def _start_behind(self) -> bool:
        """In ``_gather``'s place while a step is in flight: whether to start
        the pending lanes' step behind it. The device runs the two programs in
        order, so the second gains nothing by an early start but loses the
        lanes that would still have joined it: it is due when the host's part
        of a launch (``_lead_s``: assemble and dispatch, as measured) will
        just be over as the step ahead ends (``_StepInFlight.end_eta``).
        Until then nothing that arrives changes anything and only a step
        coming home ends the wait; from then ``_gather_until`` is asked what
        it would be asked at that end, with the lanes in flight among the
        expected. So which lanes ride together is decided as it is with one
        step at a time; what moves is when the host's part of that step is
        paid. True: start it now (the rule said so, or the time it gave ran
        out). False: look again (a step came home, a lane arrived or left, the
        step became due). At most two steps are in flight, because a third
        could only queue behind the second; a tick that carries a prompt
        chunk or a generating lane waits here until none is; and nothing is
        started behind a step whose launch has not returned (the compute
        thread could not run it yet: look again when a lane arrives or that
        step comes home)."""
        ahead, now, until = self._flights[-1], time.perf_counter(), None
        one = len(self._flights) < 2 and not self._gen_states and not self._prefill_queue and not self._closed
        wake, wait = self._gather_wake, None  # nothing to start: until a lane arrives or a step comes home
        if one and self._pending and ahead.out is not None:
            due = ahead.end_eta - self._lead_s
            if now < due:
                wake, wait = self._home_wake, due - now
            else:
                until, _lanes = self._gather_until(now, ahead)
                if until is None:
                    return True
                wait = until - now
        wake.clear()
        if wait is None:
            await wake.wait()
            return False
        try:
            await asyncio.wait_for(wake.wait(), wait)
        except asyncio.TimeoutError:
            return until is not None
        return False

    def _drop_stale(self) -> None:
        """Whatever was queued before a pool reset fails loudly (event loop):
        running it against the rematerialized (zeroed) pool would be the
        silent corruption the generation machinery exists to prevent."""
        stale = [e for e in self._pending if e[4] != self._generation]
        if stale:
            self._pending = [e for e in self._pending if e[4] == self._generation]
        for *_, fut, _gen in stale:
            if not fut.done():
                fut.set_exception(AllocationFailed(
                    "Lane pool was reset while this step was pending"
                ))
        # same staleness rule for mid-generation lanes
        for lane, st in list(self._gen_states.items()):
            if st.generation != self._generation:
                del self._gen_states[lane]
                if not st.future.done():
                    st.future.set_exception(AllocationFailed(
                        "Lane pool was reset while this step was pending"
                    ))
        # ...and for admitted prefills
        for pst in [p for p in self._prefill_queue if p.generation != self._generation]:
            self._prefill_queue.remove(pst)
            if not pst.future.done():
                pst.future.set_exception(AllocationFailed(
                    "Lane pool was reset while this step was pending"
                ))

    async def _flush_loop(self) -> None:
        """One tick a turn: wait for the lanes worth waiting for, take what is
        pending, run it as one step, hand the rows back. A plain decode step
        of the paged pool is not awaited: it is started (``_launch``) and the
        loop comes round again at once, and while its rows are not home the
        pending lanes' next step may be started behind it (``_start_behind``),
        so that the host's part of a step, its launch as much as its
        bookkeeping and the hand-offs around them, is off the path of the
        lanes that did not ride it. The rows of such a step reach its lanes
        from ``_step_home``. Every other tick (a prompt chunk, generating or
        speculating lanes, the dense pool) starts with nothing in flight and
        is awaited whole, as ever."""
        while self._pending or self._gen_states or self._prefill_queue or self._flights:
            if self._flights:
                start = await self._start_behind()
            else:
                await self._gather()
                start = True
            self._drop_stale()
            if not start:
                continue
            batch, self._pending = self._pending, []
            gen_states = dict(self._gen_states)
            # speculating lanes leave the plain gen dict for this tick and
            # ride their own draft-verify step; their verify rows share the
            # prefill fairness budget (they are chunk writes, like prefill)
            spec_states = self._pick_spec_lanes(gen_states)
            pf = self._next_prefill_chunk(
                len(batch) + len(gen_states) + len(spec_states),
                spec_tokens=len(spec_states) * (self.spec_k + 1),
            )
            if self._win:
                pf = self._window_pages_for_tick(pf, gen_states)
                if pf is None and self._prefill_queue and not batch and not gen_states:
                    await asyncio.sleep(0.005)  # a chunk waits for a windowed layer's page, which goes back as another lane moves on
            if not batch and not gen_states and not spec_states and pf is None:
                continue
            try:
                out = toks = chunk_out = spec_res = None
                if spec_states:
                    spec_res = await self.queue.submit(
                        self._run_batch_spec, spec_states,
                        priority=PRIORITY_INFERENCE,
                        size=len(spec_states) * (self.spec_k + 1),
                    )
                if gen_states:
                    out, toks = await self.queue.submit(
                        self._run_batch_gen, batch, gen_states,
                        priority=PRIORITY_INFERENCE,
                        size=len(batch) + len(gen_states),
                    )
                    if pf is not None:
                        # the gen program has no prefill half: the chunk rides
                        # its own mixed step this tick (decode entries already
                        # ran above, so neither side starves the other)
                        _, chunk_out = await self.queue.submit(
                            self._run_batch_mixed, [], pf,
                            priority=PRIORITY_INFERENCE, size=pf[1],
                        )
                elif pf is not None:
                    out, chunk_out = await self.queue.submit(
                        self._run_batch_mixed, batch, pf,
                        priority=PRIORITY_INFERENCE, size=len(batch) + pf[1],
                    )
                elif batch and self.page_size is not None:
                    self._launch(batch)
                    continue
                elif batch:
                    out = await self.queue.submit(
                        self._run_batch, batch, priority=PRIORITY_INFERENCE,
                        size=len(batch),
                    )
            except BaseException as e:  # noqa: BLE001 — deliver to every waiter
                for *_, fut, _gen in batch:
                    if not fut.done():
                        fut.set_exception(e)
                for lane, st in itertools.chain(
                    gen_states.items(), spec_states.items()
                ):
                    if self._gen_states.get(lane) is st:
                        del self._gen_states[lane]
                    if not st.future.done():
                        st.future.set_exception(e)
                if pf is not None:
                    pst = pf[0]
                    if pst in self._prefill_queue:
                        self._prefill_queue.remove(pst)
                    if not pst.future.done():
                        pst.future.set_exception(e)
                self._maybe_reset_pool()
                continue
            self._reply(batch, out, self._last_step_end[0])
            if pf is not None and chunk_out is not None:
                self._advance_prefill(pf[0], pf[1], chunk_out)
            if spec_res is not None:
                self._commit_spec_results(spec_states, *spec_res)
            if toks is None:
                continue
            # per-lane post-step bookkeeping (event-loop side, no races with
            # the compute thread): collect the sampled token, advance the
            # feed/draw cursors, and resolve finished streams
            for lane, st in gen_states.items():
                if self._gen_states.get(lane) is not st:
                    continue  # released/cancelled while the step ran
                tok = int(toks[lane])
                st.collected.append(tok)
                st.token = tok
                st.position += 1
                st.draw_idx += 1
                if st.seen is not None and 0 <= tok < st.seen.shape[0]:
                    st.seen[tok] = True
                st.remaining -= 1
                if st.remaining <= 0:
                    del self._gen_states[lane]
                    self._step_timing[lane] = self._gen_step_timing(st, "gen")
                    if not st.future.done():
                        st.future.set_result(
                            np.asarray([st.collected], np.int32)
                        )

    def _reply(self, batch, out, since: float) -> None:
        """A step's rows to its lanes (event loop): each future resolved, the
        lane's way back on record from this moment, and the step's
        ``_step_timing`` stamped for the handler's ``count_decode_reply``.
        ``since`` is when the rows were there to be handed out (a whole
        body's return, the readback's for a step that was launched)."""
        replied = time.perf_counter()
        if batch:
            self.stats["reply_steps"] += 1
            self.stats["reply_wake_s"] += replied - since
        with device_annotation("ptu.flush.resolve", lanes=len(batch)):
            for lane, _, _, fut, _gen in batch:
                if not fut.done():
                    fut.set_result(out[lane : lane + 1])
                    if lane in self._returns:  # not released while the step ran
                        self._returns[lane].reply_sent(replied)
                    timing = self._step_timing.get(lane)
                    if timing is not None:
                        timing["replied"] = replied  # for the handler's count_decode_reply

    def _launch(self, batch) -> None:
        """Start ``batch``'s plain decode step (event loop): its launch goes
        to the compute thread and nobody waits for it here. ``end_eta`` is
        when its rows should be home: a step's wall from now on an idle chip;
        behind another, that one's end plus what a step takes the device."""
        now = time.perf_counter()
        ahead = self._flights[-1] if self._flights else None
        end_eta = now + self._step_s
        if ahead is not None:
            end_eta = max(end_eta, ahead.end_eta + self._step_s - self._lead_s)
        flight = _StepInFlight(batch, batch[0][4], asyncio.get_running_loop(), ahead is not None, end_eta)
        if self._readback_thread is None:
            self._readback_thread = threading.Thread(target=self._readback_loop, name="ptu-readback", daemon=True)
            self._readback_thread.start()
        self.queue.put(lambda: self._launch_batch(flight), priority=PRIORITY_INFERENCE, size=len(batch))
        self._flights.append(flight)

    def _step_home(self, flight: _StepInFlight) -> None:
        """A launched step's rows are on the host, or it failed (event loop,
        called from the readback thread or, for a launch that raised, the
        compute thread). The flush loop is woken first, then the lanes get
        their rows with what a reply carries of the step (``_step_timing``,
        ``_step_fp``); the step's bookkeeping (``_finish_batch``) is queued
        behind the turns those replies have just been given (``_book``):
        nothing a lane waits for lies behind a counter. The generation is
        checked here, on the thread that
        resets the pool: a reset that landed after the launch fails the lanes
        of every step in flight, whose results the zeroed pool no longer
        holds."""
        self._flights.remove(flight)
        self._home_wake.set()
        self._gather_wake.set()
        error = flight.error
        if error is None and flight.generation != self._generation:
            error = AllocationFailed("Lane pool was reset while this batched step ran")
        if error is None:
            self._record_decode_timing(flight.batch, flight.t_step, flight.rows_at - flight.t_step)
            if flight.fp_rows is not None:
                for lane in flight.lanes:
                    self._step_fp[lane] = [float(x) for x in flight.fp_rows[lane]]
            self._reply(flight.batch, flight.rows, flight.rows_at)
        else:
            for *_, fut, _gen in flight.batch:
                if not fut.done():
                    fut.set_exception(error)
            # a step whose results the launch swapped in and which then failed has left in the pools what no lane wrote
            self._maybe_reset_pool(broken=flight.error is not None and flight.out is not None)
        if flight.out is not None:
            flight.loop.call_soon(self._book, flight)
        self._end_readback()

    def _book(self, flight: _StepInFlight) -> None:
        """Queue a step's bookkeeping (event loop, a turn of its own that
        ``_step_home`` asked for once the lanes' futures were resolved). The
        replies of a step are a chain of turns of this loop under one
        interpreter lock, and the gap is set by the LAST lane back:
        bookkeeping started beside the chain would take the lock at the first
        reply's write and hold up every reply after it, as much as it did in
        front of them all. The loop runs its turns in the order they were
        asked for, so this one comes after each woken handler has had its
        turn, in which a decode reply is built and written."""
        try:
            self.queue.put(lambda: self._finish_batch(flight), priority=PRIORITY_INFERENCE)
        except TaskRejected:
            pass  # the queue is shut down: nobody reads the counters

    def _end_readback(self) -> None:
        """Once the batcher is closed and every step in flight is home, let
        the readback thread go (event loop). Not before: a launch that was
        running at ``close`` still puts its step there, and its lanes still
        get their rows (one that was only queued fails them loudly)."""
        if self._closed and not self._flights and self._readback_thread is not None:
            self._readback.put(None)
            self._readback_thread = None

    def _window_pages_for_tick(self, pf, gen_states):
        """A grouped pool, before a tick's step is started (event loop): the windowed groups' pages of the prompt chunk
        ``pf`` about to ride (a chunk at a time, never a prompt at a time) and of each generating lane's next row
        (``_window_pages_now``). A chunk whose pages are not all there yet sits this tick out (``pf`` comes back None) and
        its prompt fails once it has waited ``alloc_timeout``; a generating lane that cannot have its page fails at once."""
        for lane, st in list(gen_states.items()):
            if not self._window_pages_now(lane, st.position, st.position + 1):
                del gen_states[lane]
                self._gen_states.pop(lane, None)
                if not st.future.done():
                    st.future.set_exception(AllocationFailed(f"No free KV page of a windowed layer ({self._occupancy()})"))
        if pf is None:
            return None
        st, take = pf
        if self._window_pages_now(st.lane, st.position, st.position + take):
            st.starved = 0.0
            return pf
        now = time.monotonic()
        st.starved = st.starved or now
        if now - st.starved > (30.0 if self.alloc_timeout is None else self.alloc_timeout):
            self._prefill_queue.remove(st)
            if not st.future.done():
                st.future.set_exception(AllocationFailed(f"No free KV page of a windowed layer for a prompt's chunk ({self._occupancy()})"))
        elif len(self._prefill_queue) > 1:
            self._prefill_queue.append(self._prefill_queue.pop(0))  # another prompt's chunk may fit
        return None

    def _pick_spec_lanes(self, gen_states) -> Dict[int, _LaneGenState]:
        """Partition this tick's generating lanes: lanes eligible to
        speculate move into the returned dict (and OUT of ``gen_states``);
        the rest take the plain one-token path. Eligibility: a draft model
        is loaded, the pool is paged, the lane's auto-disable cooldown has
        expired, and the lane has room for the best case — the verify step
        writes spec_k + 1 KV rows at positions p..p+spec_k, which must stay
        inside generate_lane's up-front page reservation (remaining rows
        starting at the current position)."""
        if self.draft is None or self.spec_k < 1 or self.page_size is None:
            return {}
        spec: Dict[int, _LaneGenState] = {}
        for lane, st in list(gen_states.items()):
            if st.spec_cooldown > 0:
                st.spec_cooldown -= 1
                continue
            if st.remaining < self.spec_k + 1:
                continue
            spec[lane] = st
            del gen_states[lane]
        return spec

    def _gen_step_timing(self, st: _LaneGenState, variant: str) -> dict:
        """The finished stream's step_meta timing dict. Streams that ever
        speculated also report their lifetime acceptance evidence."""
        timing = {
            "queue_s": st.queue_s, "compute_s": st.compute_s, "variant": variant,
        }
        if st.spec_proposed:
            timing["spec_proposed"] = st.spec_proposed
            timing["spec_accepted"] = st.spec_accepted
            timing["acceptance_rate"] = round(
                st.spec_accepted / st.spec_proposed, 4
            )
        return timing

    def _commit_spec_results(self, spec_states, g_hat, n_emit) -> None:
        """Post-step bookkeeping for a spec tick (event-loop side): commit
        each lane's emitted prefix g_hat[lane, :n_emit[lane]] — by the
        deterministic-stream acceptance rule those are the target's OWN
        sampled tokens, bit-identical to what plain decode would have
        emitted — then advance position/draw cursors by the emitted count.
        Rollback of the rejected suffix is pure position truncation: the
        stale KV rows past the new position stay in the pages (masked out
        of every future step by kv_length) and are overwritten in place by
        the next tick. No pages move, no refcounts change.

        Also the acceptance-EMA auto-disable: a lane whose EMA falls below
        the PETALS_TPU_SPEC_MIN_ACCEPT floor stops speculating for a
        cooldown window (draft compute on a hostile stream costs more than
        it saves), journaled with the EMA evidence."""
        for lane, st in spec_states.items():
            if self._gen_states.get(lane) is not st:
                continue  # released/cancelled while the step ran
            m = int(n_emit[lane])  # in [1, spec_k + 1] <= st.remaining
            emitted = [int(t) for t in g_hat[lane, :m]]
            for tok in emitted:
                st.collected.append(tok)
                if st.seen is not None and 0 <= tok < st.seen.shape[0]:
                    st.seen[tok] = True
            st.token = emitted[-1]
            st.position += m
            st.draw_idx += m
            st.remaining -= m
            accepted = m - 1  # of spec_k proposed drafts
            st.spec_proposed += self.spec_k
            st.spec_accepted += accepted
            alpha = self._spec_ema_alpha
            st.spec_ema = (
                (1.0 - alpha) * st.spec_ema + alpha * (accepted / self.spec_k)
            )
            if st.spec_ema < self._spec_min_accept and st.remaining > 0:
                ema = st.spec_ema
                st.spec_cooldown = self._spec_cooldown_ticks
                st.spec_ema = 1.0  # optimistic restart after the cooldown
                self.stats["spec_disabled"] += 1
                tm.SPEC_DISABLED.inc()
                self._journal.event(
                    "spec_disabled", lane=lane, ema=round(ema, 4),
                    floor=self._spec_min_accept,
                    cooldown_ticks=self._spec_cooldown_ticks,
                    proposed=st.spec_proposed, accepted=st.spec_accepted,
                )
            if st.remaining <= 0:
                del self._gen_states[lane]
                self._step_timing[lane] = self._gen_step_timing(st, "spec")
                if not st.future.done():
                    st.future.set_result(np.asarray([st.collected], np.int32))

    def _prefill_budget(self, n_decode: int, spec_tokens: int = 0) -> int:
        """Per-tick fairness: the prefill token budget shrinks under decode
        pressure (more than half the lanes actively stepping), but never
        below one page — prefills always make progress, and decode lanes
        never wait on more than one bounded chunk per tick. Spec-verify rows
        spend from the same budget (they are chunk writes riding the tick,
        exactly like prefill tokens), with the same one-page floor."""
        budget = self.prefill_token_budget
        if n_decode > max(1, self.n_lanes // 2):
            budget = max(self.page_size or 1, budget // 2)
        if spec_tokens:
            budget = max(self.page_size or 1, budget - int(spec_tokens))
        return budget

    def _next_prefill_chunk(
        self, n_decode: int, spec_tokens: int = 0
    ) -> Optional[tuple]:
        """Pick the chunk riding this tick: the queue head's next ``take``
        tokens, capped by the byte-sized chunk cap and the fairness budget,
        with the chunk END aligned to an absolute page boundary unless it is
        the prefill's final chunk (whole-page scatters — satellite of
        backend.chunk_plan's page alignment). Returns (state, take) or None."""
        if not self._prefill_queue:
            return None
        st = self._prefill_queue[0]
        remaining = st.hidden.shape[1] - st.offset
        take = min(remaining, st.cap, self._prefill_budget(n_decode, spec_tokens))
        if self.page_size and take < remaining:
            end = st.position + take
            aligned = end - end % self.page_size
            if aligned > st.position:
                take = aligned - st.position
        if not st.wait_observed:
            # first chunk entering a step: the admission -> first-compute gap
            st.wait_observed = True
            if st.enqueued:
                st.queue_s = max(time.perf_counter() - st.enqueued, 0.0)
                tm.PREFILL_QUEUE_WAIT.observe(st.queue_s)
        return st, max(int(take), 1)

    def _advance_prefill(self, st: _LanePrefillState, take: int, chunk_out) -> None:
        """Post-step bookkeeping (event-loop side): collect the chunk's span
        output, advance the cursor, resolve finished prefills, and rotate the
        queue so concurrent prefills share the budget round-robin."""
        if st not in self._prefill_queue:
            return  # released/cancelled while the step ran
        st.outs.append(np.asarray(chunk_out))
        st.offset += take
        st.position += take
        if st.offset >= st.hidden.shape[1]:
            self._prefill_queue.remove(st)
            self._step_timing[st.lane] = {
                "queue_s": st.queue_s, "compute_s": st.compute_s, "variant": "mixed",
            }
            if not st.future.done():
                out = (
                    st.outs[0] if len(st.outs) == 1
                    else np.concatenate(st.outs, axis=1)
                )
                st.future.set_result(out)
        elif len(self._prefill_queue) > 1:
            self._prefill_queue.append(self._prefill_queue.pop(0))

    async def prefill_lane(
        self, lane: int, hidden: np.ndarray, position: int
    ) -> np.ndarray:
        """Admit a multi-token prefill (hidden [1, seq, hidden]) for a PAGED
        lane into the mixed-step queue: pages for the whole range are
        allocated up front (this await is the only blocking point), then the
        flush loop feeds one bucketed, page-aligned chunk per tick alongside
        every pending decode lane — one jitted program per tick, no lane
        extract/insert, no stop-the-world chunks (contrast
        run_exclusive_chunks, which remains the dense-pool fallback).
        Returns the span output for the whole range, token-identical to the
        exclusive path."""
        if self.page_size is None:
            raise RuntimeError("prefill_lane requires the paged lane pool")
        async with self._lane_busy(lane):
            self._check_lane(lane)
            total = int(hidden.shape[1])
            position = int(position)
            if position + total > self.max_length:
                raise ValueError(
                    f"Prefill of {total} tokens at position {position} overflows "
                    f"the lane buffer ({self.max_length} tokens)"
                )
            await self.prepare_write(
                lane, position, position + total, timeout=self.alloc_timeout, windowed=False
            )
            plan = self.backend.chunk_plan(
                1, total, kv_buf_len=self.max_length,
                page_size=self.page_size, start=position,
            )
            st = _LanePrefillState(
                future=asyncio.get_running_loop().create_future(),
                generation=self._lane_generation[lane],
                lane=lane,
                hidden=np.ascontiguousarray(np.asarray(hidden, np.float32)),
                position=position,
                offset=0,
                cap=int(max(plan)),
                n_total=position + total,
                outs=[],
                enqueued=time.perf_counter(),
            )
            self._prefill_queue.append(st)
            self._forget_returns(lane)  # a new prompt: not a decode token's return
            self._spawn_flush_loop()
            try:
                return await st.future
            finally:
                if st in self._prefill_queue:
                    self._prefill_queue.remove(st)

    async def generate_lane(
        self, lane: int, last_hidden: np.ndarray, position: int,
        n_tokens: int, sampling: Optional[dict] = None,
    ) -> np.ndarray:
        """Server-side generation ON the pooled lane: sample ``n_tokens``
        starting from ``last_hidden`` (the span output of the last fed
        token), feeding n_tokens - 1 of them into the lane's KV starting at
        ``position`` (the final token stays unfed — the session resume
        convention shared with backend.generate_tokens). Unlike the old
        run_exclusive monopoly, the per-token loop lives in the flush loop:
        every step batches THIS lane with every other generating lane and any
        ordinary decode traffic into one compiled program.

        ``sampling`` is a validated rpc/protocol.validate_gen_sampling dict
        (None -> greedy). Returns tokens [1, n_tokens] int32."""
        if self.gen_params is None:
            raise RuntimeError("This batcher has no client leaves loaded for server-gen")
        async with self._lane_busy(lane):
            self._check_lane(lane)
            if position + n_tokens - 1 > self.max_length:
                raise ValueError(
                    f"Generating {n_tokens} tokens at position {position} overflows "
                    f"the lane buffer ({self.max_length} tokens)"
                )
            if self.page_size is not None and n_tokens > 1:
                # reserve the whole stream's pages up front: the flush loop can't
                # await page allocation mid-generation
                # a grouped pool's windowed groups take a generating lane's pages a row at a time (``_window_pages_for_tick``)
                await self.prepare_write(lane, int(position), int(position) + int(n_tokens) - 1, windowed=False)

            # bootstrap: t0 comes from the caller's hidden, not a pool step —
            # submitted through the queue so it serializes with batched steps
            def boot():
                self._check_lane(lane)
                return self.backend.sample_from_hidden(
                    self.gen_params, last_hidden, sampling
                )

            t0 = int((await self.queue.submit(
                boot, priority=PRIORITY_INFERENCE, size=1
            ))[0])
            if n_tokens <= 1:
                return np.asarray([[t0]], np.int32)

            st = _LaneGenState(
                future=asyncio.get_running_loop().create_future(),
                generation=self._lane_generation[lane],
                token=t0, position=int(position), remaining=int(n_tokens) - 1,
                collected=[t0], enqueued=time.perf_counter(),
            )
            if sampling is not None:
                st.do_sample = bool(sampling.get("do_sample", False))
                st.temperature = float(sampling.get("temperature", 1.0))
                st.top_k = int(sampling.get("top_k", 0) or 0)
                st.top_p = float(sampling.get("top_p", 1.0) or 1.0)
                st.repetition_penalty = float(
                    sampling.get("repetition_penalty", 1.0) or 1.0
                )
                st.seed = int(sampling.get("seed", 0))
                st.draw_idx = int(sampling.get("offset", 0)) + 1
                # the draft model conditions on (context + collected); a
                # missing context only costs acceptance rate, never parity
                ctx = sampling.get("context")
                if ctx:
                    st.context = [int(t) for t in ctx]
                if st.repetition_penalty != 1.0:
                    vocab = self.backend.cfg.vocab_size
                    seen = np.zeros((vocab,), bool)
                    for t in sampling.get("context") or ():
                        if 0 <= int(t) < vocab:
                            seen[int(t)] = True
                    if 0 <= t0 < vocab:
                        seen[t0] = True
                    st.seen = seen
            self._gen_states[lane] = st
            self._forget_returns(lane)  # generates here: it will not come back
            self._spawn_flush_loop()
            try:
                return await st.future
            finally:
                if self._gen_states.get(lane) is st:
                    del self._gen_states[lane]

    def _maybe_reset_pool(self, broken: bool = False) -> None:
        """A failed batched step may have CONSUMED the donated pool buffers
        (or, ``broken``, is known to have left them unusable: a launched step
        that failed once its results were the pool). Zero the pool and
        invalidate every outstanding lane (generation bump) — their KV is
        unrecoverable, and letting tenants silently decode against zeros
        would corrupt outputs; their next step errors instead, so clients
        re-open through the normal failover path."""
        if self._handles is None:
            return
        if not broken:
            try:
                k_pool, v_pool = self._buffers()
                broken = k_pool.is_deleted() or v_pool.is_deleted()
            except Exception as e:
                logger.debug("Pool liveness probe raised (treating as consumed): %r", e)
                broken = True
        if not broken:
            return  # routine failures (cancellation, rejects) leave the pool intact
        if self._lockstep:
            # a consumed pool under lockstep means a device op died mid-
            # collective: the GROUP is degraded (multihost._degrade_on_failure)
            # and every subsequent op fails loudly through _check_group. A
            # leader-local reset would both desync the workers' mirrors and
            # hang (rematerializing a cross-process-sharded buffer is itself
            # a collective the workers aren't entering).
            with self._reset_lock:
                self._generation += 1
            self._forget_returns()
            logger.warning(
                "Pool-consuming lockstep op failed: invalidating outstanding "
                "pooled sessions (group degradation handles the rest)"
            )
            return
        logger.warning(
            "Pool-touching step failed with the donated buffers consumed: "
            "resetting the lane pool; outstanding pooled sessions are invalidated"
        )
        with self._reset_lock:
            self._generation += 1
            if self.page_size is not None:
                # every table reference died with the lanes; rebuild the
                # allocator and bump the epoch so prefix-cache pins taken
                # against the old pool become no-op unpins. Swap entries
                # target the dead generation too: drop them, freeing their
                # host bytes — suspended sessions fail loudly via _check_lane
                self._scheduler.reset()
                self._page_epoch += 1
                if self._pages is not None:
                    # wake prepare_write waiters parked on the dead allocator
                    # so they observe the swap and fail loudly
                    self._pages.freed_event.set()
                self._pages = PageAllocator(self.n_pages)
                if self._tables is not None:
                    self._win = self._new_window_groups()
                    self._write_tables(slice(None), slice(None), -1)
                    self._tables_on_device = (-1, None)  # the device's copy goes with the pool
            for handle in self._handles or ():
                try:
                    self.memory_cache.reset_buffer(handle)
                except KeyError:
                    pass  # racing close(): handles already freed
        self._forget_returns()

    def _forget_returns(self, lane: Optional[int] = None) -> None:
        """``lane`` (every lane after a pool reset, which invalidated them
        all) is not expected back as it was: drop what is known of its way
        back and let a gather in progress ask its rule again, so that work
        admitted meanwhile counts and stale entries fail at once."""
        if lane is None:
            self._returns.clear()
        else:
            self._returns.pop(lane, None)
        self._gather_wake.set()

    def _arrivals(self, batch, *states) -> List[float]:
        """When each piece of work a body is about to run was there to be run
        (``_step_phases``' ``arrived``): a decode entry's and a first prompt
        chunk's or generating lane's enqueue; 0.0 for what continues and was
        ready the moment the body before returned."""
        arrived = [self._enq_t.get(entry[0], 0.0) for entry in batch]
        for st in states:
            first = st.offset == 0 if isinstance(st, _LanePrefillState) else not st.started
            arrived.append(st.enqueued if first else 0.0)
        return arrived

    @contextlib.contextmanager
    def _step_phases(self, variant: str, lanes: int, prefill_tokens: int = 0, arrived=(), first: str = "assemble", spawn: Optional[int] = None):
        """The phase clock shared by the step bodies (compute thread):
        ``assemble_s`` from the body's entry to the backend call,
        ``dispatch_s`` the backend call (the device starts inside it),
        ``wait_s`` blocked until the outputs are on the host, ``post_s`` from
        there to the return; the same boundaries are ``ptu.step.*``
        annotations in a profiler trace. A plain decode step of the paged pool
        is two runs of this thread, its launch (``assemble``, ``dispatch``)
        and its bookkeeping (``first="post"``), and this thread does not block
        in between (``_readback_loop`` does): ``wait_s`` is then the time this
        thread had a step in flight (launched, its rows not yet on the host by
        the readback's reading) and nothing to run. With two in flight that
        time is one stretch, not two, so the clocks keep tiling the wall.

        ``turnaround_s`` is the time since this thread's last run ended with
        nothing in flight, counted only where the flush task stayed alive in
        between: results to the event loop, futures set, the next
        ``queue.put``, this thread's wake-up (and whatever else the queue ran
        meanwhile), less what the gather waited. A step started behind another
        has no turnaround: that is the point of starting it there.

        Whichever flush task carried it, that time with nothing in flight is
        split by what this thread, and so the chip, waited for. Up to the
        earliest of ``arrived`` (``_arrivals``) there was nothing to run:
        ``lanes_out_s`` where a decode reply was out meanwhile (a lane is
        still out, or one has come back since), every live lane's token on
        its way; ``no_demand_s`` where none was, no session decoding. Of the
        rest ``gather_wait_s`` is what ``_gather`` chose to wait, and
        ``handoff_s`` what is left: work there and the host in the way
        (futures, the task's spawn, ``queue.put``, this thread's wake-up).
        The reading that opens a run's first phase ends that stretch, and
        the one that closes its last begins the next (``_split_idle``, which
        runs inside the first phase), so the eight clocks tile this thread's
        wall from the first body's return on."""
        phases = step_phases(self.stats, first, variant=variant, lanes=lanes, prefill_tokens=prefill_tokens)
        try:
            with phases:
                self._split_idle(phases.started, arrived)
                yield phases
        finally:
            self._back_since_step = False
            # the flush task that carried the step, which is alive while this body runs (a launched step's bookkeeping, which
            # runs whenever, leaves the last launch's in place)
            self._last_step_end = (phases.ended, self._flush_spawns if spawn is None else spawn)

    def _split_idle(self, entered: float, arrived) -> None:
        """``_step_phases``' account of the time from this thread's last run
        to this one's first phase (the same reading opened it, so nothing
        falls between)."""
        ended, spawn = self._last_step_end
        if spawn < 0:
            return  # the first body: no return to reckon from
        if self._aloft:
            # a step was in flight: up to the reading its rows came home with (the readback's), this thread had
            # nothing to run but that step's end to wait for
            landed = [flight.rows_at for flight in self._aloft]
            self._aloft = [flight for flight, at in zip(self._aloft, landed) if not at]
            covered = entered if self._aloft else min(max(max(landed), ended), entered)
            self.stats["wait_s"] += covered - ended
            if covered == entered:
                return
            ended = covered
        # what the gather chose to wait, as far as it lies in this gap (a wait that began while the
        # step before was being booked is that run's time up to there)
        gathered = [self._gathered.popleft() for _ in range(len(self._gathered))]
        if self._gathering is not None:  # a step's bookkeeping that runs during a gather: the wait so far
            gathered.append((self._gathering, entered))
        waited = sum(max(min(to, entered) - max(since, ended), 0.0) for since, to in gathered)
        self.stats["gather_wait_s"] += waited
        idle = entered - ended - waited
        if arrived is not None and spawn == self._flush_spawns:
            self.stats["turnaround_s"] += idle
        idle = max(idle, 0.0)
        # (``arrived`` None: a step's bookkeeping, which nothing waited for: until it ran there was nothing to run)
        empty = idle if arrived is None else min(max(min(arrived, default=0.0) - ended, 0.0), idle)
        if empty:
            out = self._back_since_step or any(
                back.replied is not None for back in list(self._returns.values())
            )
            self.stats["lanes_out_s" if out else "no_demand_s"] += empty
        self.stats["handoff_s"] += idle - empty

    def _count_moe(self, tokens: int, *, seq: int = 1, chunk_tokens: int = 0) -> None:
        """The expert counters of one step (compute thread; a family with
        experts only), from the shapes the step was started with and nothing
        from the device: ``tokens`` rode block calls of ``seq`` positions a
        lane, ``chunk_tokens`` the mixed step's chunk half at its bucket; each
        half is one more walk of every layer's experts (backend.py calls
        ``block_apply`` once for the lanes and once for the chunk)."""
        if "moe_weight_passes" not in self.stats:
            return
        dispatch = self._moe_dispatch
        halves = [(dispatch(seq), tokens)]
        if chunk_tokens:
            chunk_took = dispatch(bucket_length(chunk_tokens), True)
            halves.append((chunk_took, chunk_tokens))
            if "moe_chunk_rows_computed" in self.stats:  # a span that holds a share of its routed experts
                dims = self.backend.moe_dims
                self.stats["moe_chunk_rows_computed"] += chunk_tokens * (dims.experts if chunk_took == "dense" else dims.top_k)
                self.stats["moe_chunk_rows_routed"] += chunk_tokens * dims.top_k * dims.experts / dims.routed
        for took, n in halves:
            self.stats["moe_dense_tokens" if took == "dense" else "moe_grouped_tokens"] += n
            if took == "hit":
                self.stats["moe_hit_tokens"] += n
        self.stats["moe_weight_passes"] += len(halves)

    def _count_stream(self, wire_rows: int, rows: Optional[int] = None) -> None:
        """The hidden state one batched step took in and handed back (compute
        thread), from the step's shapes: ``wire_rows`` rows of
        ``backend.hidden_size`` float32 each way (a lane that generates on
        the server takes and returns a token, no row); and, for a family whose
        hidden state is a stream of several rows, the ``rows`` the step
        computed (default: ``wire_rows``) times the mixes a block makes of
        each times the span's blocks."""
        nbytes = wire_rows * self.backend.hidden_size * 4
        self.stats["stream_bytes_in"] += nbytes
        self.stats["stream_bytes_out"] += nbytes
        if "hc_rows" in self.stats:
            self.stats["hc_rows"] += (wire_rows if rows is None else rows) * self.backend.stream_mixes * self.backend.n_blocks

    def _fill_lanes(self, batch) -> Tuple[np.ndarray, np.ndarray]:
        """``batch``'s rows and every lane's position written into the
        lanes' buffer whose turn it is (compute thread; ``_lanes_in``): ``(the
        buffer as the step's program takes it, its column of positions)``,
        both views good until the step body after the next fills them again.
        A lane out of the batch rides at the idle sentinel, ``max_length``,
        with the row it last fed there."""
        self._lanes_turn ^= 1
        lanes_in = self._lanes_in[self._lanes_turn]
        positions = lanes_in[:, -1]
        positions[:] = self.max_length
        rows = self._lanes_rows[self._lanes_turn]
        for lane, h, pos, _fut, _gen in batch:
            rows[lane] = np.asarray(h, np.float32).reshape(-1)
            positions[lane] = pos
        return lanes_in, positions

    def _launch_batch(self, flight: _StepInFlight) -> None:
        """Compute-thread body, first half: ONE jitted step for every pending
        lane of the paged pool, launched and not waited for. The pools the
        call returns are swapped in at once, as arrays the device has yet to
        compute (the class docstring says why that is safe); the rows' copy to
        the host is queued behind the step and ``_readback_loop`` waits for
        it. A launch that raises hands its lanes the failure itself."""
        batch = flight.batch
        try:
            with self._step_phases("paged", len(batch), arrived=self._arrivals(batch)) as phases:
                # generation guards on BOTH sides of the call: an exclusive
                # op's failure can reset the pool from the event loop while this
                # task is queued or mid-call, and decoding against the
                # rematerialized zeros must fail loudly, never resolve futures
                # (a reset that lands later still: ``_step_home``)
                if flight.generation != self._generation:
                    raise AllocationFailed("Lane pool was reset before this batched step ran")
                if self._closed:  # queued when ``close`` came, which has freed the pool
                    raise AllocationFailed("Batcher is shutting down")
                flight.t_step = time.perf_counter()
                k_pool, v_pool = self._buffers()
                state = self._state()
                lanes_in, positions = self._fill_lanes(batch)
                tables = self._step_tables()
                phases.enter("dispatch")
                out, (k_pool, v_pool, *state) = self.backend.paged_decode_step(
                    lanes_in, (k_pool, v_pool, *state), positions, tables,
                    handles=self._handles,
                )
                out.copy_to_host_async()  # queued behind the step: the rows are on their way when it ends
                pop_fp = getattr(self.backend, "pop_step_fp", None)  # the next launch's would take their place
                fp = pop_fp()[0] if pop_fp is not None else None
                with self._reset_lock:
                    if flight.generation != self._generation:
                        # the reset landed while the step was launched: the buffers it
                        # read were either consumed (we would have raised) or already
                        # zeroed. Checked atomically with the swap (under the reset
                        # lock) so the freshly reset pool stays zeroed — swapping in
                        # the stale stepped buffers would silently break the 'reset
                        # leaves a zeroed pool' recovery invariant.
                        raise AllocationFailed("Lane pool was reset while this batched step ran")
                    self._update(k_pool, v_pool, *state)
                by_group = self._held_by_group()
                flight.held = (self._lane_held.copy(), by_group and tuple(held.copy() for held in by_group))
                flight.out, flight.fp, flight.positions = out, fp, positions.copy()
                self._aloft.append(flight)
            self._lead_walls.append(phases.ended - phases.started)
            self._lead_s = statistics.median(self._lead_walls)
        except BaseException as e:  # noqa: BLE001 — deliver to every waiter
            flight.error = e
            flight.loop.call_soon_threadsafe(self._step_home, flight)
            return
        self._readback.put(flight)

    def _readback_loop(self) -> None:
        """The one thread that blocks until a launched step's rows are on the
        host, so that the compute thread never does while a launch is due. It
        holds no counter and no clock but the reading the rows came with, and
        hands each step to the event loop in the order of the launches (the
        device runs them in that order)."""
        while True:
            flight = self._readback.get()
            if flight is None:
                return
            try:
                with device_annotation("ptu.readback", lanes=len(flight.batch)):
                    flight.rows = np.asarray(flight.out)  # device sync: the step has fully executed
                    if flight.fp is not None:
                        flight.fp_rows = np.asarray(flight.fp)
            except BaseException as e:  # noqa: BLE001 — deliver to every waiter
                flight.error = e
            flight.rows_at = time.perf_counter()
            try:
                flight.loop.call_soon_threadsafe(self._step_home, flight)
            except RuntimeError:
                logger.debug("A step's rows came home to a closed event loop")
                return

    def _finish_batch(self, flight: _StepInFlight) -> None:
        """Compute-thread body, second half: everything a launched step is
        counted by, after its lanes have their rows (``_book`` queued
        this). A step whose lanes were failed is not counted; its time is."""
        batch = flight.batch
        with self._step_phases("paged", len(batch), arrived=None, first="post", spawn=self._last_step_end[1]):
            if flight.error is not None or flight.generation != self._generation:
                return
            duration = flight.rows_at - flight.t_step
            self._book_decode_step(batch, flight.positions, duration, *flight.held)
            if flight.behind:
                self.stats["overlapped_steps"] += 1
            else:
                self._note_step_wall(duration)

    def _book_decode_step(self, batch, positions, duration: float, lane_held=None, group_held=None) -> None:
        """The counters of one plain decode step (compute thread); of the paged pool's, with the pages each lane held
        when the step was launched."""
        paged = self.page_size is not None
        self.stats["batched_steps"] += 1
        self.stats["batched_tokens"] += len(batch)
        self.stats["max_batch"] = max(self.stats["max_batch"], len(batch))
        self._count_moe(len(batch))
        self._count_stream(len(batch))
        if paged:
            self._pool.count_step(self.stats, positions, lane_held, group_held=group_held)
            tm.STEP_PAGED.observe(duration)
            tm.STEPS_PAGED.inc()
        else:
            tm.STEP_DENSE.observe(duration)
            tm.STEPS_DENSE.inc()
        tm.DECODE_TOKENS.inc(len(batch))
        self._ledger_account_step(duration, decode_lanes=[entry[0] for entry in batch])

    def _run_batch(self, batch) -> np.ndarray:
        """Compute-thread body of the dense pool's decode step, whole: the
        launch, the wait and the bookkeeping in one run."""
        with self._step_phases("dense", len(batch), arrived=self._arrivals(batch)) as phases:
            if batch and batch[0][4] != self._generation:  # see _launch_batch
                raise AllocationFailed("Lane pool was reset before this batched step ran")
            t_step = time.perf_counter()
            k_pool, v_pool = self._buffers()
            hsz = self.backend.hidden_size
            hidden = np.zeros((self.n_lanes, 1, hsz), np.float32)
            positions = np.full((self.n_lanes,), self.max_length, np.int32)  # idle sentinel
            for lane, h, pos, _fut, _gen in batch:
                hidden[lane] = np.asarray(h, np.float32).reshape(1, hsz)
                positions[lane] = pos
            phases.enter("dispatch")
            out, (k_pool, v_pool) = self.backend.batched_decode_step(
                hidden, (k_pool, v_pool), positions, handles=self._handles
            )
            phases.enter("wait")
            host_out = np.asarray(out)  # device sync: the step has fully executed
            phases.enter("post")
            with self._reset_lock:
                if batch and batch[0][4] != self._generation:  # see _launch_batch
                    raise AllocationFailed("Lane pool was reset while this batched step ran")
                self._update(k_pool, v_pool)
            duration = time.perf_counter() - t_step
            self._book_decode_step(batch, positions, duration)
            self._note_step_wall(duration)
            self._record_decode_timing(batch, t_step, duration)
            self._capture_step_fp([entry[0] for entry in batch])
        return host_out

    def _note_step_wall(self, duration: float) -> None:
        """S of the gather's rule: the median wall of the last decode and gen
        steps, from the launch to the rows on the host (compute thread). A
        median, so that a step that compiled or stalled does not pass for the
        step a late lane would sit out; a mixed step is left out because its
        chunk makes it longer than the step the rule reckons with, which errs
        towards waiting less, and so is a step launched behind another, whose
        wall holds its wait for the device."""
        self._step_walls.append(duration)
        self._step_s = statistics.median(self._step_walls)

    def _record_decode_timing(self, batch, t_step: float, duration: float) -> None:
        """Per-lane queue/compute split for the handler's step_meta: queue is
        enqueue -> compute start, compute is the shared batched-step wall (the
        lane rode the whole program). Runs on the compute thread (a whole
        body's) or the event loop (``_step_home``), before the lanes' futures
        resolve; see _enq_t."""
        variant = "paged" if self.page_size is not None else "dense"
        for lane, _h, _pos, _fut, _gen in batch:
            enq = self._enq_t.pop(lane, None)
            self._step_timing[lane] = {
                "queue_s": max(t_step - enq, 0.0) if enq is not None else 0.0,
                "compute_s": duration,
                "variant": variant,
            }

    def _ledger_account_step(
        self, duration: float, *, decode_lanes=(), gen_lanes=(), prefill=None
    ) -> None:
        """Ledger attribution of one batched tick (compute thread): the
        step's wall time splits EQUALLY across the lanes that rode it — the
        whole-step wall that step_meta reports per lane would multiply-count
        shared compute — plus one decode token per decode/gen lane and the
        prefill chunk's token count. ``prefill`` is (lane, take)."""
        keys = []
        for lane in decode_lanes:
            key = self._ledger_keys.get(lane)
            if key is not None:
                keys.append(key)
                self._ledger.note_tokens(key, decode=1)
        for lane in gen_lanes:
            key = self._ledger_keys.get(lane)
            if key is not None:
                keys.append(key)
                self._ledger.note_tokens(key, decode=1)
        if prefill is not None:
            lane, take = prefill
            key = self._ledger_keys.get(lane)
            if key is not None:
                keys.append(key)
                self._ledger.note_tokens(key, prefill=int(take))
        self._ledger.note_compute(keys, duration)

    def _run_batch_mixed(self, batch, pf) -> Tuple[np.ndarray, np.ndarray]:
        """Compute-thread body: ONE jitted step advancing every pending
        decode lane AND one prefill chunk together (backend.paged_mixed_step).
        The prefill lane rides the decode half at the idle sentinel, so its
        decode-side write drops; its tokens ride the prefill half."""
        st, take = pf
        with self._step_phases("mixed", len(batch), take, arrived=self._arrivals(batch, st)) as phases:
            expected = batch[0][4] if batch else st.generation
            if expected != self._generation or st.generation != self._generation:
                raise AllocationFailed("Lane pool was reset before this batched step ran")
            t_step = time.perf_counter()
            lanes_in, positions = self._fill_lanes(batch)
            chunk = st.hidden[:, st.offset : st.offset + take]
            k_pool, v_pool = self._buffers()
            state = self._state()
            tables = self._step_tables()
            phases.enter("dispatch")
            out, chunk_out, (k_pool, v_pool, *state) = self.backend.paged_mixed_step(
                lanes_in, (k_pool, v_pool, *state), positions, tables,
                chunk, st.lane, st.position, n_total=st.n_total,
                handles=self._handles, trim=False,
            )
            out.copy_to_host_async()  # both queued behind the step, as _launch_batch's
            chunk_out.copy_to_host_async()
            phases.enter("wait")
            host_out = np.asarray(out)  # device sync: the step has fully executed
            host_chunk = np.asarray(chunk_out)[:, :take]  # the chunk's bucket, cut to its rows here and not on the device
            phases.enter("post")
            with self._reset_lock:
                if expected != self._generation:
                    # see _launch_batch: checked atomically with the swap so a reset
                    # landing mid-step leaves the freshly zeroed pool in place
                    raise AllocationFailed("Lane pool was reset while this batched step ran")
                self._update(k_pool, v_pool, *state)
            self.stats["batched_steps"] += 1
            self.stats["batched_tokens"] += len(batch)
            self.stats["max_batch"] = max(self.stats["max_batch"], len(batch))
            self.stats["mixed_steps"] += 1
            self.stats["prefill_tokens"] += take
            self.stats["max_prefill_tokens_per_step"] = max(
                self.stats["max_prefill_tokens_per_step"], take
            )
            self._count_moe(len(batch), chunk_tokens=take)
            self._count_stream(len(batch) + take)
            self._pool.count_step(self.stats, positions, self._lane_held, chunk=(st.lane, st.position, take), group_held=self._held_by_group())
            duration = time.perf_counter() - t_step
            tm.STEP_MIXED.observe(duration)
            tm.STEPS_MIXED.inc()
            tm.DECODE_TOKENS.inc(len(batch))
            self._record_decode_timing(batch, t_step, duration)
            self._capture_step_fp(
                [entry[0] for entry in batch], chunk_lane=st.lane
            )
            self._ledger_account_step(
                duration,
                decode_lanes=[entry[0] for entry in batch],
                prefill=(st.lane, take),
            )
            st.compute_s += duration  # whole-prefill compute accumulates per chunk
        return host_out, host_chunk

    def _run_batch_gen(self, batch, gen_states) -> Tuple[np.ndarray, np.ndarray]:
        """Compute-thread body: one jitted step advancing every pending decode
        lane AND every generating lane together (the client leaves embed the
        gen lanes' tokens and sample their next ones on device)."""
        with self._step_phases(
            "gen", len(batch) + len(gen_states), arrived=self._arrivals(batch, *gen_states.values())
        ) as phases:
            expected = (
                batch[0][4] if batch
                else next(iter(gen_states.values())).generation
            )
            if expected != self._generation or any(
                st.generation != self._generation for st in gen_states.values()
            ):
                raise AllocationFailed("Lane pool was reset before this batched step ran")
            t_step = time.perf_counter()
            hsz = self.backend.hidden_size
            hidden = np.zeros((self.n_lanes, 1, hsz), np.float32)
            positions = np.full((self.n_lanes,), self.max_length, np.int32)  # idle sentinel
            tokens = np.zeros((self.n_lanes,), np.int32)
            use_token = np.zeros((self.n_lanes,), bool)
            vecs = sampling_vectors(self.n_lanes, self.backend.cfg.vocab_size)
            for lane, h, pos, _fut, _gen in batch:
                hidden[lane] = np.asarray(h, np.float32).reshape(1, hsz)
                positions[lane] = pos
            for lane, st in gen_states.items():
                tokens[lane] = st.token
                use_token[lane] = True
                positions[lane] = st.position
                vecs["do_sample"][lane] = st.do_sample
                vecs["temperature"][lane] = st.temperature
                vecs["top_k"][lane] = st.top_k
                vecs["top_p"][lane] = st.top_p
                vecs["repetition_penalty"][lane] = st.repetition_penalty
                vecs["seeds"][lane] = st.seed
                vecs["draw_idx"][lane] = st.draw_idx
                if st.seen is not None:
                    vecs["seen_mask"][lane] = st.seen
            k_pool, v_pool = self._buffers()
            state = self._state()
            tables = self._step_tables() if self.page_size is not None else None
            phases.enter("dispatch")
            if tables is not None:
                out, toks, (k_pool, v_pool, *state) = self.backend.paged_gen_decode_step(
                    self.gen_params, hidden, tokens, use_token, (k_pool, v_pool, *state),
                    positions, tables, sampling_vecs=vecs,
                    handles=self._handles,
                )
            else:
                out, toks, (k_pool, v_pool) = self.backend.batched_gen_decode_step(
                    self.gen_params, hidden, tokens, use_token, (k_pool, v_pool),
                    positions, sampling_vecs=vecs, handles=self._handles,
                )
            phases.enter("wait")
            host_out = np.asarray(out)  # device sync: the step has fully executed
            host_toks = np.asarray(toks)
            phases.enter("post")
            with self._reset_lock:
                if expected != self._generation:
                    # see _launch_batch: checked atomically with the swap so a reset
                    # landing mid-step leaves the freshly zeroed pool in place
                    raise AllocationFailed("Lane pool was reset while this batched step ran")
                self._update(k_pool, v_pool, *state)
            self.stats["batched_steps"] += 1
            self.stats["batched_tokens"] += len(batch) + len(gen_states)
            self.stats["max_batch"] = max(
                self.stats["max_batch"], len(batch) + len(gen_states)
            )
            self.stats["gen_steps"] += 1
            self.stats["gen_lane_tokens"] += len(gen_states)
            self.stats["max_gen_lanes"] = max(
                self.stats["max_gen_lanes"], len(gen_states)
            )
            self._count_moe(len(batch) + len(gen_states))
            self._count_stream(len(batch), len(batch) + len(gen_states))
            if tables is not None:
                self._pool.count_step(self.stats, positions, self._lane_held, group_held=self._held_by_group())
            duration = time.perf_counter() - t_step
            tm.STEP_GEN.observe(duration)
            tm.STEPS_GEN.inc()
            tm.DECODE_TOKENS.inc(len(batch) + len(gen_states))
            self._note_step_wall(duration)
            self._record_decode_timing(batch, t_step, duration)
            self._capture_step_fp([entry[0] for entry in batch] + list(gen_states))
            self._ledger_account_step(
                duration,
                decode_lanes=[entry[0] for entry in batch],
                gen_lanes=list(gen_states),
            )
            for st in gen_states.values():
                if not st.started:
                    st.started = True
                    st.queue_s = max(t_step - st.enqueued, 0.0) if st.enqueued else 0.0
                st.compute_s += duration
        return host_out, host_toks

    def _run_batch_spec(self, spec_states) -> Tuple[np.ndarray, np.ndarray]:
        """Compute-thread body for one speculative tick: the draft proposes
        k tokens per speculating lane, then ONE verify step (backend.
        paged_spec_verify_step) feeds [last committed token, k drafts] at
        positions p..p+k, samples the target's own token for every row from
        the lane's seed+offset PRNG stream, and returns the emitted prefix
        per lane. Non-speculating lanes ride at the idle sentinel. Returns
        (g_hat [n_lanes, spec_k+1], n_emit [n_lanes]); the event loop
        commits g_hat[lane, :n_emit[lane]] (_commit_spec_results).

        Ledger honesty: the WHOLE tick wall (draft + verify, both on this
        thread) splits equally across the speculating lanes via the normal
        note_compute path — conservation holds unchanged — and the draft's
        share is additionally recorded per lane as the draft_seconds
        'of which' annotation, with proposed/accepted counts feeding the
        per-peer acceptance_rate (/ledger, step_meta usage)."""
        with self._step_phases(
            "spec", len(spec_states), arrived=self._arrivals((), *spec_states.values())
        ) as phases:
            expected = next(iter(spec_states.values())).generation
            if expected != self._generation or any(
                st.generation != self._generation for st in spec_states.values()
            ):
                raise AllocationFailed("Lane pool was reset before this batched step ran")
            if self._draft_warmed is not self.draft:
                # compile every propose bucket before the first measured tick so
                # later lane-count mixes never compile (spec_decode.DraftModel)
                self.draft.warmup(self.n_lanes)
                self._draft_warmed = self.draft
            t_step = time.perf_counter()
            S = self.spec_k + 1
            contexts: List[Optional[List[int]]] = [None] * self.n_lanes
            for lane, st in spec_states.items():
                contexts[lane] = (st.context or []) + st.collected
            drafts = self.draft.propose(contexts)  # [n_lanes, spec_k] greedy
            draft_s = time.perf_counter() - t_step
            tokens = np.zeros((self.n_lanes, S), np.int32)
            positions = np.full((self.n_lanes,), self.max_length, np.int32)  # idle sentinel
            vecs = sampling_vectors(self.n_lanes, self.backend.cfg.vocab_size)
            for lane, st in spec_states.items():
                tokens[lane, 0] = st.token
                tokens[lane, 1:] = drafts[lane]
                positions[lane] = st.position
                vecs["do_sample"][lane] = st.do_sample
                vecs["temperature"][lane] = st.temperature
                vecs["top_k"][lane] = st.top_k
                vecs["top_p"][lane] = st.top_p
                vecs["repetition_penalty"][lane] = st.repetition_penalty
                vecs["seeds"][lane] = st.seed
                vecs["draw_idx"][lane] = st.draw_idx
                if st.seen is not None:
                    vecs["seen_mask"][lane] = st.seen
            k_pool, v_pool = self._buffers()
            tables = self._step_tables()
            phases.enter("dispatch")
            g_hat, n_emit, (k_pool, v_pool) = self.backend.paged_spec_verify_step(
                self.gen_params, tokens, (k_pool, v_pool), positions,
                tables, sampling_vecs=vecs, handles=self._handles,
            )
            phases.enter("wait")
            host_g = np.asarray(g_hat)  # device sync: the step has fully executed
            host_m = np.asarray(n_emit)
            phases.enter("post")
            with self._reset_lock:
                if expected != self._generation:
                    # see _launch_batch: checked atomically with the swap so a reset
                    # landing mid-step leaves the freshly zeroed pool in place
                    raise AllocationFailed("Lane pool was reset while this batched step ran")
                self._update(k_pool, v_pool)
            n_spec = len(spec_states)
            emitted_total = int(sum(int(host_m[lane]) for lane in spec_states))
            accepted_total = emitted_total - n_spec  # one bonus token per lane
            proposed_total = n_spec * self.spec_k
            self.stats["batched_steps"] += 1
            self.stats["batched_tokens"] += emitted_total
            self.stats["spec_steps"] += 1
            self.stats["spec_proposed"] += proposed_total
            self.stats["spec_accepted"] += accepted_total
            self.stats["max_spec_lanes"] = max(self.stats["max_spec_lanes"], n_spec)
            self._count_moe(n_spec * S, seq=S)
            self._pool.count_step(self.stats, positions, self._lane_held, seq=S)
            duration = time.perf_counter() - t_step
            tm.STEP_SPEC.observe(duration)
            tm.STEPS_SPEC.inc()
            tm.DECODE_TOKENS.inc(emitted_total)
            tm.SPEC_PROPOSED.inc(proposed_total)
            tm.SPEC_ACCEPTED.inc(accepted_total)
            self._capture_step_fp(list(spec_states))
            keys = []
            per_lane_draft = draft_s / n_spec
            for lane, st in spec_states.items():
                key = self._ledger_keys.get(lane)
                if key is not None:
                    keys.append(key)
                    self._ledger.note_tokens(key, decode=int(host_m[lane]))
                    self._ledger.note_spec(
                        key, draft_seconds=per_lane_draft,
                        proposed=self.spec_k, accepted=int(host_m[lane]) - 1,
                    )
            self._ledger.note_compute(keys, duration)
            for st in spec_states.values():
                if not st.started:
                    st.started = True
                    st.queue_s = max(t_step - st.enqueued, 0.0) if st.enqueued else 0.0
                st.compute_s += duration
        return host_g, host_m

    # ------------------------------------------------------- non-batchable ops

    def _new_temp(self) -> Optional[tuple]:
        """Synthetic mirror handles for an extracted lane under lockstep
        (None otherwise): exclusive-op fns pass these to the backend so
        workers address their copy of the checked-out lane."""
        if not self._lockstep:
            return None
        t = next(self._temp_ids)
        return (t, t)

    def _extract_lane(self, lane: int, temp: Optional[tuple] = None):
        """Compute-thread body: lane checked OUT of the pool as session-shaped
        [n_blocks, 1, max_len, hkv, d] buffers (broadcast under lockstep so
        workers mirror the copy under ``temp``)."""
        k_pool, v_pool = self._buffers()
        if temp is not None:
            return self.backend.lane_extract(
                k_pool, v_pool, lane,
                pool_handle=self._handles[0], temp_handle=temp[0],
            )
        if self.page_size is not None:
            # gather the lane's pages into the session-shaped dense view the
            # exclusive fns (prefill, kv import) expect — same layout as the
            # dense pool's lane, so those fns are mode-oblivious
            return self.backend._paged_lane_gather_fn(
                k_pool, v_pool, self._tables[lane].copy()
            )
        return self.backend._lane_extract_fn(k_pool, v_pool, np.int32(lane))

    def _insert_lane(self, lane: int, kv_lane, temp: Optional[tuple] = None) -> None:
        """Compute-thread body: lane checked back IN. The whole read-insert-
        swap runs under the reset lock: a reset landing mid-way would
        otherwise let the insert donate the freshly zeroed pool's buffers (or
        swap stale pre-reset buffers back in), breaking the 'reset leaves a
        zeroed pool' invariant — the same TOCTOU _launch_batch guards against.
        The lane check raises BEFORE any buffer is donated, so a failed
        insert leaves the new pool untouched."""
        k2, v2 = kv_lane
        with self._reset_lock:
            self._check_lane(lane)
            k_pool, v_pool = self._buffers()
            if temp is not None:
                k_pool, v_pool = self.backend.lane_insert(
                    k_pool, v_pool, (k2, v2), lane,
                    pool_handle=self._handles[0], temp_handle=temp[0],
                )
            elif self.page_size is not None:
                # scatter the dense lane view back through the block table;
                # unallocated (-1) slots drop, so content past the session's
                # resident pages never lands anywhere
                k_pool, v_pool = self.backend._paged_lane_scatter_fn(
                    k_pool, v_pool, k2, v2, self._tables[lane].copy()
                )
            else:
                k_pool, v_pool = self.backend._lane_insert_fn(
                    k_pool, v_pool, k2, v2, np.int32(lane)
                )
            self._update(k_pool, v_pool)

    def _release_temp(self, temp: Optional[tuple]) -> None:
        """Best-effort drop of a synthetic lockstep mirror that will NOT be
        inserted back (a failed/cancelled exclusive op): without the OP_FREE
        broadcast every worker would retain a full lane-sized KV copy per
        failure — an unbounded leak under repeated client disconnects."""
        if temp is None:
            return
        try:
            self.backend.release_temp(temp[0])
        except Exception:  # swarmlint: disable=no-silent-except — best-effort by contract: a degraded lockstep group already dropped the mirrors with its workers
            pass

    async def run_exclusive(
        self, lane: int, fn, *, size: int = 0, extract: bool = True,
        write_range: Optional[Tuple[int, int]] = None,
    ):
        """Run ``fn(kv_lane, lane_handles) -> (result, kv_lane')`` with the
        lane extracted into session-shaped buffers, then insert the updated
        lane back — all in ONE atomic queue task. Used for KV import and any
        step the batched program doesn't cover. Serialized with batched steps
        by the queue. ``lane_handles`` is None single-host; under lockstep it
        is the synthetic mirror handle pair the fn must pass to the backend
        (e.g. ``backend.inference_step(..., handles=lane_handles)``).
        ``extract=False`` skips the checkout (fn receives kv_lane=None) for
        ops that wholesale REPLACE the lane (prefix seed, kv import) — under
        lockstep that saves every process a full-lane device copy.
        ``write_range=(t0, t1)`` declares the token range the fn writes:
        paged mode allocates/forks those pages up front (prepare_write) so
        the check-in scatter has somewhere to land."""
        self.backend.cache.refuse(
            "an exclusive op on a checked-out lane (deep prompts, beam search's hypo_ids, a seeded or imported cache)",
            "the lane's session-shaped view holds keys and values only", paged=self._grouped,
        )
        async with self._lane_busy(lane):
            self._check_lane(lane)
            if self.page_size is not None and write_range is not None:
                await self.prepare_write(lane, int(write_range[0]), int(write_range[1]))
            # exclusive ops run alone on the device: their whole wall bills
            # to this one tenant, and a declared write range is prompt
            # tokens landing in its cache (dense-prefill / kv-import path)
            ledger_key = self._ledger_keys.get(lane)
            if ledger_key is not None and write_range is not None:
                self._ledger.note_tokens(
                    ledger_key, prefill=int(write_range[1]) - int(write_range[0])
                )

            def run():
                self._check_lane(lane)  # re-check: a reset may have raced the queue
                temp = self._new_temp()
                t_run = time.perf_counter()
                try:
                    kv_lane = self._extract_lane(lane, temp) if extract else None
                    result, kv_lane = fn(kv_lane, temp)
                    self._insert_lane(lane, kv_lane, temp)
                except BaseException:
                    self._release_temp(temp)
                    raise
                if ledger_key is not None:
                    self._ledger.note_compute(
                        [ledger_key], time.perf_counter() - t_run
                    )
                return result

            try:
                return await self.queue.submit(run, priority=PRIORITY_INFERENCE, size=size)
            except AllocationFailed:
                raise
            except BaseException:
                # exclusive ops donate the pool buffers too (_lane_insert_fn):
                # a failure here can consume them just like a batched step
                self._maybe_reset_pool()
                raise

    async def run_exclusive_chunks(
        self, lane: int, chunk_fns, *, size: int = 0,
        write_range: Optional[Tuple[int, int]] = None,
    ):
        """Chunked-prefill interleaving (Sarathi-style): extract the lane
        once, run each ``fn(kv_lane, lane_handles) -> (result, kv_lane')`` as
        its OWN priority-queue task, insert once. Between chunks the flush
        loop's batched decode steps run freely — a long prefill no longer
        stalls every decoding session for its full length. Safe while checked
        out: batched steps never write an idle-sentinel lane, and the FIFO
        queue guarantees the final insert lands before any new tenant's first
        task even if this session is cancelled mid-chunks (stale content
        beyond a tenant's position is masked by attention anyway)."""
        async with self._lane_busy(lane):
            return await self._run_exclusive_chunks(
                lane, chunk_fns, size=size, write_range=write_range
            )

    async def _run_exclusive_chunks(
        self, lane: int, chunk_fns, *, size: int = 0,
        write_range: Optional[Tuple[int, int]] = None,
    ):
        self._check_lane(lane)
        if self.page_size is not None and write_range is not None:
            await self.prepare_write(lane, int(write_range[0]), int(write_range[1]))
        ledger_key = self._ledger_keys.get(lane)
        if ledger_key is not None and write_range is not None:
            # bill the whole declared prompt span once, up front (the chunks
            # below and the single-chunk delegation never re-declare it)
            self._ledger.note_tokens(
                ledger_key, prefill=int(write_range[1]) - int(write_range[0])
            )
        if len(chunk_fns) == 1:
            # short prefills skip the extract/insert round-trips
            return [await self.run_exclusive(lane, chunk_fns[0], size=size)]
        state = {}

        def extract():
            self._check_lane(lane)  # re-check: a reset may have raced the queue
            state["temp"] = self._new_temp()
            state["kv"] = self._extract_lane(lane, state["temp"])

        def insert():
            self._check_lane(lane)  # a stale lane's data must not be re-inserted
            self._insert_lane(lane, state["kv"], state["temp"])

        try:
            await self.queue.submit(extract, priority=PRIORITY_INFERENCE, size=0)
        except BaseException:
            # a leader-side failure AFTER the extract broadcast leaves the
            # workers holding the temp mirror: free it before propagating
            self._release_temp(state.get("temp"))
            raise
        results = []
        try:
            for fn in chunk_fns:
                def run_chunk(fn=fn):
                    self._check_lane(lane)
                    t_run = time.perf_counter()
                    res, state["kv"] = fn(state["kv"], state["temp"])
                    self.stats["exclusive_chunks"] += 1
                    if ledger_key is not None:
                        self._ledger.note_compute(
                            [ledger_key], time.perf_counter() - t_run
                        )
                    return res

                try:
                    results.append(
                        await self.queue.submit(run_chunk, priority=PRIORITY_INFERENCE, size=size)
                    )
                except AllocationFailed:
                    raise
                except BaseException:
                    self._maybe_reset_pool()
                    raise
        finally:
            # always check the lane back in (a failed chunk leaves the last
            # consistent kv; the session's host-side position was not advanced)
            inserted = False
            if "kv" in state:
                try:
                    await self.queue.submit(insert, priority=PRIORITY_INFERENCE, size=0)
                    inserted = True
                except AllocationFailed:
                    pass  # lane invalidated mid-prefill: nothing to check in
                except BaseException:
                    self._maybe_reset_pool()
                    raise
                finally:
                    if not inserted:
                        # the workers' temp mirror will never be consumed by
                        # an insert: free it or it leaks a lane-sized buffer
                        self._release_temp(state.get("temp"))
        return results

    async def snapshot_lane(
        self, lane: int, position: int, b0: int, b1: int,
        *, return_device: bool = False,
    ):
        """Host copy of blocks [b0, b1) of a lane, sliced to ``position``
        (KV export/migration for pooled sessions). Under lockstep the lane's
        shards live on every process: a read-only extract registers a temp
        mirror, the export all_gather runs through it, and the temp is
        released (never inserted back — nothing was modified).

        ``return_device=True`` returns ``(k, v, k_dev, v_dev)`` where the
        device pair are the same slices still resident in HBM (None under
        lockstep, whose shards are per-process) — the prefix cache's device
        tier pins these so a later hit can seed without re-uploading."""
        self.backend.cache.refuse(_SNAPSHOT, _SNAPSHOT_WHY, paged=self._grouped)
        self._check_lane(lane)

        def run():
            self._check_lane(lane)  # re-check: a reset may have raced the queue
            temp = self._new_temp()
            if temp is not None:
                kv_lane = self._extract_lane(lane, temp)
                try:
                    k, v = self.backend.export_kv(
                        temp, lambda: kv_lane, b0, b1, position
                    )
                    return (k, v, None, None) if return_device else (k, v)
                finally:
                    self.backend.release_temp(temp[0])
            k_pool, v_pool = self._buffers()
            if self.page_size is not None:
                k, v = self.backend._paged_lane_gather_fn(
                    k_pool, v_pool, self._tables[lane].copy()
                )
            else:
                k, v = self.backend._lane_extract_fn(k_pool, v_pool, np.int32(lane))
            kd = k[b0:b1, :, :position]
            vd = v[b0:b1, :, :position]
            host = (np.asarray(kd), np.asarray(vd))
            return (*host, kd, vd) if return_device else host

        async with self._lane_busy(lane):
            return await self.queue.submit(run, priority=PRIORITY_INFERENCE, size=0)

    async def snapshot_from_swap(self, lane: int, position: int, b0: int, b1: int):
        """Host KV snapshot of a SUSPENDED lane assembled straight from its
        SwapEntry — pure numpy, no device work. ``snapshot_lane`` would first
        swap the lane back IN (``_lane_busy`` -> ``_ensure_resident``),
        burning pool pages and two device copies just to read bytes that
        already sit in host RAM; a draining server parking its preempted
        tenants hits exactly that case. Returns ``(k, v)`` shaped like
        ``snapshot_lane``'s host pair, or None when the lane isn't suspended,
        is busy, or its swap entry doesn't cover ``[0, position)`` — the
        caller falls back to the device path."""
        self.backend.cache.refuse(_SNAPSHOT, _SNAPSHOT_WHY, paged=self._grouped)
        if self.page_size is None:
            return None
        slot = self._scheduler.lanes.get(lane)
        if slot is None:
            return None
        lock = self._lane_lock(lane)
        # trylock (no sanitizer order edge): busy means a step or resume is
        # mid-flight — the device path serializes behind it correctly
        if not lock_try_acquire_nowait(lock):
            return None
        try:
            entry = slot.swap
            if entry is None or slot.suspending:
                return None
            ps = self.page_size
            n_slots = -(-position // ps)  # ceil: table slots covering [0, position)
            index_of = {int(s): i for i, s in enumerate(entry.slots)}
            if any(s not in index_of for s in range(n_slots)):
                return None  # partial residency: only the pool knows the rest

            def assemble():
                from petals_tpu.ops.paged_attention import PagedPool, dequantize_kv_np

                quantized = isinstance(entry.k, PagedPool)
                if quantized:
                    # packed swap entry: dequantize the covered slots to the
                    # dense fp view the snapshot contract promises
                    hkv = entry.k.scales.shape[-1]
                    d = entry.k.shape[-1]  # logical (PagedPool.shape unpacks)
                    out_dtype = np.float32
                else:
                    hkv, d = entry.k.shape[-2], entry.k.shape[-1]
                    out_dtype = entry.k.dtype
                nb = b1 - b0
                k_out = np.zeros((nb, 1, position, hkv, d), out_dtype)
                v_out = np.zeros((nb, 1, position, hkv, d), out_dtype)
                for s in range(n_slots):
                    i = index_of[s]
                    t0, t1 = s * ps, min((s + 1) * ps, position)
                    if quantized:
                        kind = entry.k.kind
                        k_out[:, 0, t0:t1] = dequantize_kv_np(
                            entry.k.codes[b0:b1, i, : t1 - t0],
                            entry.k.scales[b0:b1, i, : t1 - t0], kind,
                        )
                        v_out[:, 0, t0:t1] = dequantize_kv_np(
                            entry.v.codes[b0:b1, i, : t1 - t0],
                            entry.v.scales[b0:b1, i, : t1 - t0], kind,
                        )
                    else:
                        k_out[:, 0, t0:t1] = entry.k[b0:b1, i, : t1 - t0]
                        v_out[:, 0, t0:t1] = entry.v[b0:b1, i, : t1 - t0]
                return k_out, v_out

            # the lane lock stays held across the copy so a racing resume
            # can't consume the entry mid-assembly; it's a trylock, so the
            # sanitizer's await-under-lock rule is not in play
            return await asyncio.to_thread(assemble)
        finally:
            lock.release()
