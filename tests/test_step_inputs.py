"""What a paged step body hands the device (PR 51): the lanes' rows and
positions in ONE reused host buffer (``backend.pack_lanes``' form), the block
tables as a copy that stays on the device until an entry changes value
(``DecodeBatcher._write_tables`` / ``_step_tables``), and per-layer counters
reckoned from the step's shapes and ``_lane_held`` with no pass over the tables.

Held here: the packed program against the same body handed separate operands,
bit for bit, for a plain span, a span with a state pool and a span with an
index pool; the device's tables following every writer (with ``tables_sent``
counting exactly the steps that had to send); steps between two writes
sending nothing; the counters against the per-step reductions they replaced;
and an idle lane's stale row staying out of a live lane's output."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from petals_tpu.models.registry import span_runs
from petals_tpu.ops import paged_flash_attention as pfa
from petals_tpu.server.backend import TransformerBackend, bucket_length
from petals_tpu.server.batching import DecodeBatcher
from petals_tpu.server.from_pretrained import get_block_config, load_block_params
from petals_tpu.server.memory_cache import MemoryCache
from petals_tpu.server.task_queue import PriorityTaskQueue
from tests.utils import (
    counted,
    make_tiny_deepseek_v3,
    make_tiny_exaone_moe,
    make_tiny_keye_vl2,
    make_tiny_llama,
    make_tiny_olmo_hybrid,
    steps_booked,
    make_tiny_qwen3_next,
)

TOYS = {
    "plain": make_tiny_llama, "state": make_tiny_qwen3_next, "index": make_tiny_keye_vl2,
    "window": make_tiny_exaone_moe, "latent": make_tiny_deepseek_v3, "state-dense": make_tiny_olmo_hybrid,
}
PAGE, SLOTS, LANES = 8, 4, 3  # lanes of 32 positions: over the index toy's selection of 16, so its rows choose
_BACKENDS = {}


def span_backend(sort: str, tmp_path_factory) -> TransformerBackend:
    """The whole toy of ``sort`` as one span, float32, one stacked tree a run
    of blocks of one kind (made once a module run: loading is the slow part)."""
    if sort not in _BACKENDS:
        path = TOYS[sort](str(tmp_path_factory.mktemp(f"step-inputs-{sort}")))
        family, cfg = get_block_config(path)
        depth = min(cfg.num_hidden_layers, 4) if sort in ("plain", "state-dense") else cfg.num_hidden_layers
        stacked = tuple(
            jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *(load_block_params(path, i, dtype=jnp.float32) for i in range(start, start + length)))
            for _, start, length in span_runs(family.span_kinds(cfg, 0, depth))
        )
        _BACKENDS[sort] = TransformerBackend(
            family, cfg, stacked[0] if len(stacked) == 1 else stacked, first_block=0, n_blocks=depth,
            memory_cache=MemoryCache(None), compute_dtype=jnp.float32, use_flash=False,
        )
    return _BACKENDS[sort]


def make_pools(backend, n_pages: int, seed: int) -> tuple:
    """(k, v, *state or index): pools of small noise, so that a row read off the wrong page shows."""
    descs = backend.cache.pool_descriptors(n_pages, PAGE, LANES, 0, backend.n_blocks)
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal(d.shape).astype(np.float32) * 0.05, d.dtype) for d in descs)


def copied(tree):
    return jax.tree_util.tree_map(jnp.copy, tree)  # a step donates what it is given


def rows(backend, seed: int, n: int = 1) -> np.ndarray:
    return np.random.default_rng(seed).standard_normal((n, 1, backend.hidden_size)).astype(np.float32) * 0.1


def same_bytes(got, want, what=""):
    got, want = jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
    assert len(got) == len(want)
    for i, (g, w) in enumerate(zip(got, want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), err_msg=f"{what} leaf {i}")


# ------------------------------------------------------------------ (a) the packed program against separate operands


@pytest.mark.parametrize("program", ["decode", "mixed"])
@pytest.mark.parametrize("sort", ["plain", "state", "index"])
def test_packed_step_gives_the_bytes_of_the_same_body_handed_separate_operands(tmp_path_factory, monkeypatch, sort, program):
    """The step program's body (``_scan_paged_span`` over the family's
    ``block_apply``) is run twice: as the backend runs it, its lanes' rows
    and positions unpacked from the one int32 operand, and handed ``hidden``,
    ``positions`` and ``tables`` as three operands of their own, which is
    the program every step ran before the packing. Outputs, pools and state
    agree bit for bit, whether the packed operand was made by the public
    call from separate arrays or is a reused buffer whose idle lane holds a
    stale row."""
    backend = span_backend(sort, tmp_path_factory)
    n_pages = LANES * SLOTS
    tables = np.random.default_rng(3).permutation(n_pages).astype(np.int32).reshape(LANES, SLOTS)
    max_length = PAGE * SLOTS
    hidden = rows(backend, 5, LANES)
    positions = np.array([13, max_length, 20], np.int32)  # lane 1 idle: in the mixed step it takes the chunk
    chunk = rows(backend, 7, 5).reshape(1, 5, -1)
    pools = make_pools(backend, n_pages, seed=11)

    def public(lanes_hidden, lanes_positions, given_tables):
        if program == "decode":
            return backend.paged_decode_step(lanes_hidden, copied(pools), lanes_positions, given_tables)
        return backend.paged_mixed_step(lanes_hidden, copied(pools), lanes_positions, given_tables, chunk, 1, 3)

    got = public(hidden, positions, tables)
    # the batcher's way in: one buffer in pack_lanes' form, the idle lane's row stale, the tables already on the device
    buffer = backend.pack_lanes(hidden, positions)
    assert buffer.dtype == np.int32 and buffer.shape == (LANES, backend.hidden_size + 1)
    np.testing.assert_array_equal(buffer[:, :-1].view(np.float32), hidden[:, 0])
    assert backend.pack_lanes(buffer, None) is buffer
    reused = public(buffer, buffer[:, -1], backend.device_tables(tables))
    same_bytes(reused, got, "the reused buffer and device tables")
    on_device = backend.pack_lanes(jnp.asarray(hidden), positions)  # rows already on the device are packed there
    np.testing.assert_array_equal(np.asarray(on_device), buffer)

    # the same body handed separate operands: ``lanes`` is then the pair itself
    monkeypatch.setattr(TransformerBackend, "_unpack_lanes", staticmethod(lambda lanes, dtype: (lanes[0].astype(dtype), lanes[1])))
    k_pool, v_pool, *state = copied(pools)
    separate = (jnp.asarray(hidden), jnp.asarray(positions))
    if program == "decode":
        raw = backend._paged_decode_fn.__wrapped__
        want = jax.jit(lambda *a: raw(*a, with_fp=False))(backend.params, k_pool, v_pool, separate, jnp.asarray(tables), tuple(state))
    else:
        raw = backend._paged_mixed_step_fn.__wrapped__
        padded = np.pad(chunk, ((0, 0), (0, bucket_length(5) - 5), (0, 0)))
        want = jax.jit(lambda *a: raw(*a, with_fp=False))(
            backend.params, k_pool, v_pool, separate, jnp.asarray(tables), padded, np.int32(1), np.int32(3), np.int32(5), np.int32(8), tuple(state)
        )
        got = (got[0], jnp.pad(got[1], ((0, 0), (0, bucket_length(5) - 5), (0, 0))), got[2])
        want = (want[0], want[1].at[:, 5:].set(0), *want[2:])  # the public call trims the chunk to its rows
    want_out, want_pools = want[: 1 + (program == "mixed")], want[1 + (program == "mixed"):]
    flat_pools = (*want_pools[:2], *(want_pools[2] if len(want_pools) > 2 else ()))
    same_bytes(got[:-1], want_out, "outputs")
    same_bytes(got[-1], flat_pools, "pools")


# ------------------------------------------------------------------ (b), (c), (e): through a batcher


class Rig:
    """A ``DecodeBatcher`` on a toy span with its queue, and the oracle: the
    step the batcher is about to run, computed by the public call from
    separate arrays, zeros in every idle lane's row and the batcher's HOST
    tables as they stand, on copies of its pools."""

    def __init__(self, backend, **kwargs):
        self.backend = backend
        self.queue = PriorityTaskQueue()
        self.queue.start()
        self.batcher = DecodeBatcher(backend, backend.memory_cache, self.queue, n_lanes=LANES, max_length=PAGE * SLOTS, page_size=PAGE, **kwargs)

    async def close(self):
        await self.batcher.close()
        self.queue.shutdown()

    @property
    def sent(self) -> int:
        return self.batcher.stats["tables_sent"]

    async def step(self, lane: int, hidden: np.ndarray, position: int, sends: int, what: str) -> np.ndarray:
        """One decode step of ``lane`` alone, held to the oracle; the body sent the tables ``sends`` times."""
        batcher = self.batcher
        await batcher._ensure_resident(lane)
        await batcher.prepare_write(lane, position, position + 1)  # what step() does first: the oracle needs the tables after it
        every = np.zeros((LANES, 1, self.backend.hidden_size), np.float32)
        every[lane] = hidden
        positions = np.full(LANES, batcher.max_length, np.int32)
        positions[lane] = position
        pools = copied((*batcher._buffers(), *batcher._state()))
        want, _ = self.backend.paged_decode_step(every, pools, positions, batcher._tables.copy())
        before = self.sent
        got = await batcher.step(lane, hidden, position)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want)[lane : lane + 1], err_msg=what)
        assert self.sent - before == sends, f"{what}: the tables were sent {self.sent - before} times, not {sends}"
        return got


def test_device_tables_follow_every_writer_and_rest_between_writes(tmp_path_factory):
    """Grow a lane across a page boundary, end a session, swap a lane out and
    in, fork a shared page, reset the pool: after each, the next step reads
    the new table (its output is the oracle's, computed from the host
    tables) and ``tables_sent`` rose by exactly one; every step between two
    writes sent nothing."""
    backend = span_backend("plain", tmp_path_factory)

    async def main():
        rig = Rig(backend, n_pages=LANES * SLOTS, swap_host_bytes=1 << 22)
        batcher = rig.batcher
        try:
            a = await batcher.acquire_lane()
            await batcher.prefill_lane(a, rows(backend, 1, 5).reshape(1, 5, -1), 0)
            assert rig.sent == 1 and batcher.occupancy_info()["tables_sent"] == 1  # the first body of all sends them
            for pos in (5, 6, 7):  # (c) the lane stays inside its page
                await rig.step(a, rows(backend, 10 + pos), pos, 0, f"position {pos}, no write since the last step")
            await rig.step(a, rows(backend, 18), 8, 1, "the lane grew a page")
            await rig.step(a, rows(backend, 19), 9, 0, "the step after the grow")

            b = await batcher.acquire_lane()
            await rig.step(b, rows(backend, 20), 0, 1, "another session's first page")
            await rig.step(a, rows(backend, 21), 10, 0, "two sessions, no write")
            batcher.release_lane(b)
            assert not batcher._lanes_rows[:, b].any()  # its row went with it, in both buffers
            await rig.step(a, rows(backend, 22), 11, 1, "a session ended")

            # swap out and in: lane a's pages go to the host and come back
            c = await batcher.acquire_lane()
            await rig.step(c, rows(backend, 23), 0, 1, "a third session's first page")
            assert await batcher._swap_out_lane(a) and not (batcher._tables[a] >= 0).any()
            await rig.step(c, rows(backend, 24), 1, 1, "a lane was swapped out")
            await rig.step(c, rows(backend, 25), 2, 0, "the step after the swap out")
            await rig.step(a, rows(backend, 26), 12, 1, "the lane swapped back in")
            assert (batcher._tables[a] >= 0).sum() == 2 and batcher._scheduler.stats["swap_ins"] == 1
            await rig.step(a, rows(backend, 27), 13, 0, "the step after the swap in")

            # copy on write: lane c adopts lane a's first page and writes into it
            pinned = batcher.pin_lane_pages(a, 0, PAGE)
            batcher.release_lane(c)
            d = await batcher.acquire_lane()
            batcher.adopt_pages(d, pinned)
            shared = int(batcher._tables[d, 0])
            await rig.step(d, rows(backend, 28), 3, 1, "a shared page was forked")  # release, adopt and fork: one send
            assert int(batcher._tables[d, 0]) != shared and batcher._pages.stats["forked"] == 1
            await rig.step(a, rows(backend, 29), 14, 0, "the step after the fork")
            batcher.unpin_pages(pinned, batcher.page_epoch)

            # a failed donating step: the pool is reset and the device's copy of the tables goes with it
            await steps_booked(batcher)
            steps = batcher.stats["batched_steps"]
            batcher._buffers()[0].delete()
            batcher._maybe_reset_pool()
            assert batcher._tables_on_device == (-1, None) and not (batcher._tables >= 0).any()
            for lane in (a, d):
                batcher.release_lane(lane)
            e = await batcher.acquire_lane()
            await rig.step(e, rows(backend, 30), 0, 1, "the pool was reset")
            await rig.step(e, rows(backend, 31), 1, 0, "the step after the reset")
            await steps_booked(batcher)
            info = batcher.occupancy_info()
            assert (info["tables_sent"], info["batched_steps"]) == (rig.sent, steps + 2)
        finally:
            await rig.close()

    asyncio.run(main())


def test_nothing_writes_the_tables_but_the_one_writer(tmp_path_factory):
    """``_tables`` refuses writes, a row taken out of it too: a writer that
    went round ``_write_tables`` (and so round the version and the counts a
    lane holds) fails where it stands."""
    backend = span_backend("plain", tmp_path_factory)

    async def main():
        rig = Rig(backend)
        batcher = rig.batcher
        try:
            lane = await batcher.acquire_lane()
            await batcher.prepare_write(lane, 0, 2 * PAGE)
            version = batcher._tables_version
            with pytest.raises(ValueError, match="read-only"):
                batcher._tables[lane, 0] = 5
            row = batcher._tables[lane]
            with pytest.raises(ValueError, match="read-only"):
                row[:] = -1
            assert batcher._tables_version == version and batcher._lane_pages(lane) == 2
            batcher._write_tables(lane, 1, -1)
            assert batcher._tables_version == version + 1 and batcher._lane_pages(lane) == 1 and batcher._tables[lane, 1] == -1
            assert batcher.tables_contiguous() is not None  # the cached flag was dropped and reckoned again
        finally:
            await rig.close()

    asyncio.run(main())


@pytest.mark.parametrize("sort", ["plain", "state"])
def test_an_idle_lanes_stale_row_does_not_reach_a_live_lanes_output(tmp_path_factory, sort):
    """A lane feeds a row of NaN and of 1e30 and then sits a step out: its
    row is still in the buffer (nothing zeroes it every step), at the idle
    sentinel, and the lane that does step gets the oracle's bytes, which were
    computed with zeros there. Experts and a state pool included: the hit
    dispatch and the one-step rule see live rows only."""
    backend = span_backend(sort, tmp_path_factory)

    async def main():
        rig = Rig(backend)
        batcher = rig.batcher
        try:
            a, b = await batcher.acquire_lane(), await batcher.acquire_lane()
            await rig.step(b, rows(backend, 1), 0, 1, "lane b's first step")
            poison = rows(backend, 2)
            poison[0, 0, ::2], poison[0, 0, 1::2] = np.nan, 1e30
            await batcher.step(a, poison, 0)
            assert np.isnan(batcher._lanes_rows[:, a]).any()
            for pos in (1, 2):
                got = await rig.step(b, rows(backend, 2 + pos), pos, 0, "beside a stale row of NaN")
                assert np.isfinite(got).all()
            # (the lanes' two buffers are filled in turn: the stale row rode one of the two steps, the zeros it started as the other)
            assert np.isnan(batcher._lanes_rows[:, a]).any() and (batcher._lanes_in[:, a, -1] == batcher.max_length).all()
            batcher.release_lane(a)
            assert not batcher._lanes_rows[:, a].any()
        finally:
            await rig.close()

    asyncio.run(main())


# ------------------------------------------------------------------ (d) the counters against the reductions they replaced


def old_counts(batcher, stats: dict, tables: np.ndarray, positions: np.ndarray, chunk) -> None:
    """``_count_window``, ``_count_state``, ``_count_sparse`` and
    ``_count_latent`` as they stood before PR 51, every one indexing and
    reducing the step's copy of the tables, into ``stats``."""
    self, backend, pool = batcher, batcher.backend, batcher._pool
    if "attn_pages_gathered" in stats:
        layers = backend.cache.page_layers
        last = positions[positions < self.max_length]
        by_kernel = 0
        if pool.selects or backend.cache.latent_row is not None or not last.size:
            read = 0
        else:
            read, by_kernel = pfa.pages_walked(pool.walks, last, self.page_size, self.n_lanes)
        stats["attn_pages_gathered"] += read
        stats["attn_pages_kernel"] += by_kernel
        stats["attn_pages_tabled"] += self.n_lanes * self.max_pages * layers
        if chunk is not None:
            lane, first, take = chunk
            if backend.cache.latent_row is not None:
                from petals_tpu.ops.latent_attention import chunk_reads

                stats["attn_pages_gathered"] += layers * chunk_reads(self.max_pages, self.page_size, first, take) // self.page_size
            elif not pool.selects:
                stats["attn_pages_gathered"] += pool._pages_gathered(bucket_length(take))
            stats["attn_pages_tabled"] += self.max_pages * layers
        if pool.windows:
            lanes, last = np.flatnonzero(positions < self.max_length), last.astype(np.int64)
            if chunk is not None:
                lanes, last = np.append(lanes, lane), np.append(last, first + take - 1)
            held = (tables[lanes] >= 0).sum(axis=1)
            reach = 0
            for window in pool.windows:
                pages = last // self.page_size - np.maximum(last - window + 1, 0) // self.page_size + 1
                reach += int(np.minimum(pages, held).sum())
            stats["window_pages_held"] += int(held.sum()) * len(pool.windows)
            stats["window_pages_in_reach"] += reach
    if backend.cache.lane_state:
        layers = len(backend.cache.state_layers)
        lanes = np.flatnonzero(positions < self.max_length)
        stats["linattn_recurrent_tokens"] += int(lanes.size) * layers
        if pool.state_step == "kernel":
            stats["linattn_kernel_tokens"] += int(lanes.size) * layers
        if chunk is not None:
            lanes = np.append(lanes, chunk[0])
            stats["linattn_chunk_tokens"] += int(chunk[2]) * layers
        stats["state_bytes_held"] += int(lanes.size) * pool.state_bytes
        stats["kv_bytes_held"] += int((tables[lanes] >= 0).sum()) * self._pool.page_bytes
    if backend.cache.index_row is not None:
        lanes = np.flatnonzero(positions < self.max_length)
        reads = counted(backend, self.n_lanes, self.max_pages, self.page_size, positions[lanes], chunk=None if chunk is None else chunk[1:])
        for key, n in reads.items():
            if key.startswith("sparse_"):  # the selection's own, of all that ``count_step`` adds
                stats[key] += n
        if pool.selects:
            stats["attn_pages_gathered"] += -(-reads["sparse_kv_rows_read"] // self.page_size)
        if chunk is not None:
            lanes = np.append(lanes, chunk[0])
        pages = int((tables[lanes] >= 0).sum())
        index = pages * self.page_size * int(backend.cache.index_bytes_per_token())
        stats["index_bytes_held"] += index
        stats["kv_bytes_held"] += pages * self._pool.page_bytes - index
    if backend.cache.latent_row is not None:
        lanes = np.flatnonzero(positions < self.max_length)
        reads = counted(backend, self.n_lanes, self.max_pages, self.page_size, positions[lanes], chunk=None if chunk is None else chunk[1:])
        for key, n in reads.items():
            if key.startswith("latent_"):  # the latent attention's own (no page is held there: its bytes count 0)
                stats[key] += n
        stats["attn_pages_gathered"] += reads["latent_rows_read"] // self.page_size
        if chunk is not None:
            lanes = np.append(lanes, chunk[0])
        stats["latent_bytes_held"] += int((tables[lanes] >= 0).sum()) * self._pool.page_bytes


COUNTED = {
    "plain": {"attn_pages_gathered", "attn_pages_tabled"},  # attn_pages_kernel: 0 off the chip, where the composed walk runs
    "window": {"window_pages_held", "window_pages_in_reach"},
    "state": {"linattn_recurrent_tokens", "linattn_chunk_tokens", "state_bytes_held", "kv_bytes_held"},
    "state-dense": {"linattn_recurrent_tokens", "linattn_chunk_tokens", "state_bytes_held", "kv_bytes_held"},
    "index": {"sparse_rows_selected", "sparse_kv_rows_read", "sparse_kv_rows_held", "index_bytes_held", "kv_bytes_held"},
    "latent": {"latent_rows_read", "latent_rows_held", "latent_positions_expanded", "latent_bytes_held"},
}


@pytest.mark.parametrize("sort", sorted(COUNTED))
def test_counters_equal_the_per_step_reductions_over_the_tables(tmp_path_factory, sort):
    """200 seeded steps of lanes of different lengths that start, grow page
    by page, end and start again, every eighth one with a prompt chunk on an
    idle lane: what the step bodies count now (positions, shapes and the
    pages a lane holds as ``_write_tables`` keeps them) equals, key by key,
    what the old expressions reduce from a copy of the tables every step."""
    backend = span_backend(sort, tmp_path_factory)
    rng = np.random.default_rng(51)

    async def main():
        rig = Rig(backend)
        batcher = rig.batcher
        try:
            await batcher.ensure_open()
            keys = [key for key, value in batcher.stats.items() if isinstance(value, int) and not isinstance(value, bool)]
            assert COUNTED[sort] <= set(keys), COUNTED[sort] - set(keys)
            want = {key: batcher.stats[key] for key in batcher.stats}
            length = np.zeros(LANES, np.int64)  # 0: the lane has no session

            def hold(lane, positions_held):
                """``lane`` owns pages for its first ``positions_held`` positions, and no others."""
                slots = -(-positions_held // PAGE)
                for slot in range(SLOTS):
                    owned = batcher._tables[lane, slot] >= 0
                    if slot < slots and not owned:
                        batcher._write_tables(lane, slot, batcher._pages.try_alloc())
                    elif slot >= slots and owned:
                        batcher._pages.decref(int(batcher._tables[lane, slot]))
                        batcher._write_tables(lane, slot, -1)

            for step in range(200):
                positions = np.full(LANES, batcher.max_length, np.int32)
                chunk = None
                for lane in range(LANES):
                    if length[lane] == 0 and rng.random() < 0.3:
                        length[lane] = int(rng.integers(1, PAGE * SLOTS - 8))  # a session starts at some length
                    elif length[lane] and (length[lane] >= PAGE * SLOTS - 1 or rng.random() < 0.03):
                        length[lane] = 0  # and ends
                    hold(lane, length[lane] + (length[lane] > 0))
                idle = np.flatnonzero(length == 0)
                if step % 8 == 0 and idle.size:  # a prompt chunk rides: its lane holds the prompt's pages and feeds no decode row
                    lane, first, take = int(idle[0]), int(rng.integers(0, 8)), int(rng.integers(1, 9))
                    hold(lane, first + take)
                    chunk = (lane, first, take)
                live = np.flatnonzero((length > 0) & (rng.random(LANES) < 0.8))  # a lane may sit a step out
                positions[live] = length[live]
                old_counts(batcher, want, batcher._tables.copy(), positions, chunk)
                batcher._pool.count_step(batcher.stats, positions, batcher._lane_held, chunk=chunk)
                length[live] += 1
                if chunk is not None:
                    hold(chunk[0], 0)
            moved = {key for key in keys if batcher.stats[key] != 0}
            assert COUNTED[sort] <= moved, f"the sequence never moved {COUNTED[sort] - moved}"
            assert {key: batcher.stats[key] for key in want} == want
        finally:
            await rig.close()

    asyncio.run(main())
