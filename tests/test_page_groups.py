"""Pages by kind of layer (server/span_cache.py ``SpanCache.page_groups``; server/batching.py ``_WindowGroup``): a span
whose layers are windowed and full in turns keeps, on the paged lane pool, a pool, an allocator and lane tables a group,
and a windowed group gives a lane's pages back as its window moves past them. Driven on a real ``DecodeBatcher`` over
the toy SmallThinker (two periods of a full layer and three layers of window 8, pages of 4) and the toy K-EXAONE."""

import asyncio
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from petals_tpu.server.batching import DecodeBatcher
from petals_tpu.server.memory_cache import AllocationFailed
from petals_tpu.server.span_cache import GROUPS_RIDE
from petals_tpu.server.task_queue import PriorityTaskQueue
from tests.test_smallthinker import HF, reference_hidden, whole_backend
from tests.utils import lane_pools, make_tiny_exaone_moe, make_tiny_smallthinker, published_span_cache, tiny_smallthinker_tensors

PAGE, WINDOW = 4, 8


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    path = make_tiny_smallthinker(str(tmp_path_factory.mktemp("models")))
    return whole_backend(path), tiny_smallthinker_tensors(HF)


@contextlib.asynccontextmanager
async def rig(backend, **kw):
    queue = PriorityTaskQueue()
    queue.start()
    batcher = DecodeBatcher(backend, backend.memory_cache, queue, **{"n_lanes": 2, "max_length": 48, "page_size": PAGE, "prefill_token_budget": 8, **kw})
    try:
        yield batcher
    finally:
        await batcher.close()
        queue.shutdown()


def rows(seed: int, n: int) -> np.ndarray:
    return np.random.RandomState(seed).standard_normal((1, n, 64)).astype(np.float32)


async def session(batcher, lane: int, x: np.ndarray, prompt: int, watch=None) -> np.ndarray:
    """``x`` [1, n, h] through ``lane``: a prompt of ``prompt`` rows in mixed steps, the rest a row a step; ``watch(position)``
    is called when each step has its pages and has not started yet."""
    outs = [await batcher.prefill_lane(lane, x[:, :prompt], 0)]
    for p in range(prompt, x.shape[1]):
        await batcher.prepare_write(lane, p, p + 1)
        if watch is not None:
            watch(p)
        outs.append(await batcher.step(lane, x[:, p : p + 1], p))
    return np.concatenate([np.asarray(o) for o in outs], axis=1)


def test_a_span_s_page_groups_are_its_layers_by_window_and_one_kind_is_one_group(tiny, tmp_path):
    backend, _ = tiny
    cache = backend.cache
    assert cache.grouped and cache.page_groups == ((None, (0, 4)), (WINDOW, (1, 2, 3, 5, 6, 7)))
    assert cache.group_slots == ((0, 0), (1, 0), (1, 1), (1, 2), (0, 1), (1, 3), (1, 4), (1, 5)) and cache.slots == tuple(range(8))
    # pages a group: the full group's as asked, the windowed group's as many lanes' worth of what a window and a chunk reach
    assert cache.lane_pages(None, 12, PAGE, 8) == 12 and cache.lane_pages(WINDOW, 12, PAGE, 8) == 5 and cache.lane_pages(WINDOW, 12, PAGE, 1) == 3
    assert cache.group_pages(2, 12, PAGE, 8) == (24, 10) and cache.group_pages(2, 12, PAGE, 8, n_pages=12) == (12, 5)
    pools = cache.pool_descriptors((24, 10), PAGE, 2, 0, 8)
    assert [d.shape for d in pools] == [(2, 24, PAGE, 32)] * 2 + [(6, 10, PAGE, 32)] * 2  # a row of 2 x 16 is stored folded
    assert [d.shape for d in cache.pool_descriptors(24, PAGE, 2, 0, 8)] == [(8, 24, PAGE, 32)] * 2  # a number: one pool, as before groups
    a_layer = 2 * 2 * 16 * 4
    assert cache.cache_bytes_per_token() == 8 * a_layer and cache.lane_bytes(48) == 2 * a_layer * 48 + 6 * a_layer * WINDOW
    pool = cache.lane_pool(2, 12, PAGE, grouped=True)
    assert pool.grouped and pool.group_page_bytes == (2 * a_layer * PAGE, 6 * a_layer * PAGE)
    assert {"window_pages_released", "kv_bytes_held", "kv_bytes_unfreed", "attn_score_pairs"} <= set(pool.new_stats())
    assert not {"window_pages_released", "kv_bytes_unfreed"} & set(cache.lane_pool(2, 12, PAGE).new_stats())  # a pool of one group
    # a span of one kind of layer, packed pages, a family without declared windows: one group
    for first, n in ((1, 3), (0, 1)):
        assert not whole_backend(backend_path(tmp_path), first, n).cache.grouped
    packed = type(cache)(backend.family, backend.cfg, backend.runs, cache_dtype=jnp.float32, kv_quant_type="int8")
    assert not packed.grouped and packed.page_groups == ((None, tuple(range(8))),)
    exaone = whole_exaone(tmp_path)
    assert exaone.cache.page_groups == ((None, (3,)), (8, (0, 1, 2, 4))) and whole_exaone(tmp_path, 0, 3).cache.page_groups == ((None, (0, 1, 2)),)


def backend_path(tmp_path) -> str:
    return make_tiny_smallthinker(str(tmp_path))


def whole_exaone(tmp_path, first: int = 0, n: int = 5):
    from tests.test_exaone_moe import whole_backend as exaone_backend

    return exaone_backend(make_tiny_exaone_moe(str(tmp_path)), first, n)


def test_a_lane_holds_what_its_window_reaches_and_replies_as_a_pool_that_frees_nothing(tiny):
    """A lane decoding past its window holds, when a step starts, exactly the pages its window reaches in a windowed
    layer; its replies are bit-equal to a pool of the same groups that frees nothing, and agree with the reference's whole
    forward pass and with a pool of ONE group under one table (the program before groups) to float32 rounding."""
    backend, tensors = tiny
    x = rows(3, 40)

    async def main():
        async with rig(backend) as batcher:
            lane = await batcher.acquire_lane()
            group = batcher._win[0]
            seen = []

            def watch(p):
                held = np.flatnonzero(group.tables[lane] >= 0).tolist()
                assert held == list(range(max(p - WINDOW + 1, 0) // PAGE, p // PAGE + 1)), (p, held)  # exactly the window's reach
                assert int(group.lane_held[lane]) == len(held) and int(batcher._lane_held[lane]) == p // PAGE + 1  # the full group: every page up to this row
                seen.append(len(held))

            got = await session(batcher, lane, x, 21, watch)
            assert set(seen) == {2, 3} and batcher.stats["window_pages_released"] == 10 - 2  # ten pages written, two still held
            assert batcher.stats["window_pages_held"] == batcher.stats["window_pages_in_reach"] > 0
            assert batcher.occupancy_info()["page_groups"][1] == {"window": WINDOW, "layers": 6, "n_pages": 10, "pages_free": 8, "lane_pages": 5}
            assert batcher.paged_summary()["page_groups"] == [{"window": WINDOW, "n_pages": 10, "pages_free": 8}]
            batcher.release_lane(lane)
            assert group.alloc.n_free == 10 and batcher._pages.n_free == 24
        async with rig(backend) as keeps:  # the same groups, nothing given back (its windowed pool as large as the full one's lanes)
            keeps._group_pages = (24, 24)
            keeps._window_release = lambda group, lane, below, above=None: 0
            lane = await keeps.acquire_lane()
            kept = await session(keeps, lane, x, 21)
            assert int(keeps._win[0].lane_held[lane]) == 10 and keeps.stats["window_pages_released"] == 0
        return got, kept

    got, kept = asyncio.run(main())
    np.testing.assert_array_equal(got, kept)
    np.testing.assert_allclose(got[0], reference_hidden(HF, tensors, x[0]), atol=1e-4, rtol=0)
    # the program before groups: one pool for all eight layers under one table
    (k_desc, v_desc), _ = lane_pools(backend, 12, PAGE, end=8)
    pool_kv = (k_desc.make_zeros(), v_desc.make_zeros())
    tables = np.arange(12, dtype=np.int32)[None]
    _, chunk, pool_kv = backend.paged_mixed_step(np.zeros((1, 1, 64), np.float32), pool_kv, np.asarray([48], np.int32), tables, x[:, :21], 0, 0)
    outs = [np.asarray(chunk)]
    for p in range(21, 40):
        out, pool_kv = backend.paged_decode_step(x[:, p : p + 1], pool_kv, np.asarray([p], np.int32), tables)
        outs.append(np.asarray(out))
    np.testing.assert_allclose(got, np.concatenate(outs, axis=1), atol=2e-5, rtol=0)


def test_a_pool_too_small_for_unfreed_lanes_admits_them_and_a_released_page_is_reused_without_its_rows(tiny):
    """Two lanes of 40 positions need twenty pages a windowed layer unfreed; the windowed group has ten. Both run, the
    second takes pages the first gave back, and each lane's rows are its own session's alone (a lane that read a
    stranger's rows off a reused page would differ from the same session run alone)."""
    backend, tensors = tiny
    xa, xb = rows(5, 40), rows(6, 40)

    async def main():
        async with rig(backend) as batcher:
            a, b = await batcher.acquire_lane(), await batcher.acquire_lane()
            group = batcher._win[0]
            assert group.n_pages == 10 < 2 * 10
            owned = {a: set(), b: set()}

            def watch_for(lane):
                def watch(p):
                    owned[lane].update(int(page) for page in group.tables[lane] if page >= 0)
                    assert not set(group.tables[a][group.tables[a] >= 0].tolist()) & set(group.tables[b][group.tables[b] >= 0].tolist())
                return watch

            got_a = await session(batcher, a, xa, 21, watch_for(a))
            got_b, more_a = await asyncio.gather(session(batcher, b, xb, 21, watch_for(b)), batcher.step(a, xa[:, :1], 40))
            assert owned[a] & owned[b], "the second lane took none of the pages the first gave back"
            assert batcher.stats["window_pages_released"] >= 14 and group.alloc.n_free >= 10 - 2 * 3
            return got_a, got_b

    got_a, got_b = asyncio.run(main())
    np.testing.assert_allclose(got_a[0], reference_hidden(HF, tensors, xa[0]), atol=1e-4, rtol=0)
    np.testing.assert_allclose(got_b[0], reference_hidden(HF, tensors, xb[0]), atol=1e-4, rtol=0)


def test_a_windowed_group_with_no_free_page_makes_a_lane_wait_and_then_fail_by_name(tiny):
    """A pool of one lane's worth of pages (12 in the full group, so 5 in the windowed one): lane a's prompt of 12 rows
    holds three of the five, lane b's first row one; b decodes on until its window wants a page that is not there, waits
    its timeout and fails by the layer's kind; when a's window has moved on, the page is there."""
    backend, _ = tiny

    async def main():
        async with rig(backend, n_pages=12, alloc_timeout=0.2) as batcher:
            a, b = await batcher.acquire_lane(), await batcher.acquire_lane()
            group = batcher._win[0]
            assert batcher._group_pages == (12, 5)
            await batcher.prefill_lane(a, rows(1, 12), 0)
            assert int(group.lane_held[a]) == 3 and int(group.lane_held[b]) == 1 and group.alloc.n_free == 1
            for p in range(1, 8):
                await batcher.prepare_write(b, p, p + 1, timeout=0.2)  # b's second page is the last free one
            assert group.alloc.n_free == 0
            with pytest.raises(AllocationFailed, match="No free KV page of a windowed layer within 0.2 s"):
                await batcher.prepare_write(b, 8, 9, timeout=0.2)
            await batcher.prepare_write(a, 16, 17, timeout=0.2)  # a's window moves past its first two pages
            await batcher.prepare_write(b, 8, 9, timeout=0.2)
            assert int(group.lane_held[b]) == 3 and batcher.stats["window_pages_released"] == 2

    asyncio.run(main())


REFUSED = {
    "swap": (lambda backend: DecodeBatcher(backend, backend.memory_cache, PriorityTaskQueue(), n_lanes=2, max_length=48, page_size=PAGE, swap_host_bytes=1 << 20),
             r"the host swap tier \(swap_host_bytes > 0\)"),
    "speculative-decoding": (lambda backend: backend.cache.refuse("speculative decoding", "", paged=True), "speculative decoding"),
}


@pytest.mark.parametrize("what", ["swap", "speculative-decoding", "snapshot", "snapshot-from-swap", "exclusive-op", "prefix-cache", "rollback-behind-the-window",
                                  "kv-adopt"])
def test_what_ships_stores_cuts_back_or_adopts_a_lane_s_pages_is_refused_by_name(tiny, what):
    """Every path of a paged lane pool that needs a lane's whole cache refuses a span with page groups through
    ``SpanCache.refuse(..., paged=True)``: the family by name, what was asked, the groups, the reason. The same span's
    private cache and dense pool keep every position and are served (tests/test_smallthinker.py)."""
    backend, _ = tiny
    sentence = r"^smallthinker: {} is not served for a span whose layers keep pages in groups by window \(2 full, 6 of window 8\): "
    if what in REFUSED:
        make, asked = REFUSED[what]
        with pytest.raises(NotImplementedError, match=sentence.format(asked) + "only the paged lane pool's decode"):
            make(backend)
        return
    if what == "prefix-cache":
        assert "a stored prefix of a windowed layer is gone once the window passed it" in backend.cache.prefix_cache_refusal(paged=True)
        assert backend.cache.prefix_cache_refusal() is None  # a server without a paged pool keeps whole private caches
        return

    async def main():
        async with rig(backend) as batcher:
            lane = await batcher.acquire_lane()
            await session(batcher, lane, rows(2, 30), 21)
            if what == "snapshot":
                with pytest.raises(NotImplementedError, match=sentence.format("a snapshot of a lane's cache.*")):
                    await batcher.snapshot_lane(lane, 30, 0, 8)
            elif what == "snapshot-from-swap":
                with pytest.raises(NotImplementedError, match=sentence.format("a snapshot of a lane's cache.*")):
                    await batcher.snapshot_from_swap(lane, 30, 0, 8)
            elif what == "exclusive-op":
                with pytest.raises(NotImplementedError, match=sentence.format("an exclusive op on a checked-out lane.*")):
                    await batcher.run_exclusive(lane, lambda kv, handles: (None, kv))
            elif what == "kv-adopt":
                with pytest.raises(NotImplementedError, match=sentence.format("kv_adopt / kv_import")):
                    backend.cache.refuse("kv_adopt / kv_import", "", paged=batcher.grouped)
            else:
                # the lane fed 30 rows: its windowed layers hold slots 5-7 (positions 20-29 in reach of a row at 29)
                assert batcher.window_reach_held(lane, 28) and batcher.window_reach_held(lane, 27) and not batcher.window_reach_held(lane, 24)
                with pytest.raises(NotImplementedError, match=sentence.format("start_from_position 24 behind the cache's position 30")):
                    backend.cache.refuse("start_from_position 24 behind the cache's position 30", "", paged=not batcher.window_reach_held(lane, 24))
                backend.cache.refuse("start_from_position 28 behind the cache's position 30", "", paged=not batcher.window_reach_held(lane, 28))  # served

    asyncio.run(main())
    assert GROUPS_RIDE.startswith("only the paged lane pool's decode")


def test_a_rollback_inside_the_window_s_reach_is_served_and_answers_as_the_first_pass_did(tiny):
    """Fed 30 rows, cut back to position 27 and fed rows 27-29 again: the same replies, to the bit; the pages ahead of the
    cut go back and are taken again."""
    backend, _ = tiny
    x = rows(9, 30)

    async def main():
        async with rig(backend) as batcher:
            lane = await batcher.acquire_lane()
            first = await session(batcher, lane, x, 21)
            assert batcher.window_reach_held(lane, 27)
            again = [np.asarray(await batcher.step(lane, x[:, p : p + 1], p)) for p in range(27, 30)]
            return first, np.concatenate(again, axis=1)

    first, again = asyncio.run(main())
    np.testing.assert_array_equal(first[:, 27:], again)


@pytest.mark.parametrize("chunk", [0, 9], ids=["decode", "mixed"])
def test_exaone_moe_s_step_over_two_groups_is_its_step_over_one_pool(tmp_path, chunk):
    """K-EXAONE at a toy size, one full layer among four windowed: the step program handed a pair of pools a group and
    tables a group gives the bits the program before groups gives, handed one pool under one table (which is the program
    it was: its lowering is untouched by the groups)."""
    backend = whole_exaone(tmp_path)
    cache = backend.cache
    lanes, slots = 2, 6
    x = rows(11, 24)
    tables = np.stack([np.arange(slots), slots + np.arange(slots)]).astype(np.int32)
    one = tuple(d.make_zeros() for d in cache.pool_descriptors(lanes * slots, PAGE, lanes, 0, 5))
    two = tuple(d.make_zeros() for d in cache.pool_descriptors((lanes * slots, lanes * slots), PAGE, lanes, 0, 5))
    assert [p.shape[0] for p in one] == [5, 5] and [p.shape[0] for p in two] == [1, 1, 4, 4]
    grouped_tables = np.stack([tables, tables])
    idle = np.asarray([24, 24], np.int32)
    hidden = np.zeros((lanes, 1, 64), np.float32)
    _, a, one = backend.paged_mixed_step(hidden, one, idle, tables, x[:, :14], 1, 0)
    _, b, two = backend.paged_mixed_step(hidden, two, idle, grouped_tables, x[:, :14], 1, 0)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for p in range(14, 20):
        hidden[1] = x[0, p]
        at = np.asarray([24, p], np.int32)
        if chunk:
            a, ca, one = backend.paged_mixed_step(hidden, one, at, tables, x[:, :chunk], 0, 0)
            b, cb, two = backend.paged_mixed_step(hidden, two, at, grouped_tables, x[:, :chunk], 0, 0)
            np.testing.assert_array_equal(np.asarray(ca), np.asarray(cb))
        else:
            a, one = backend.paged_decode_step(hidden, one, at, tables)
            b, two = backend.paged_decode_step(hidden, two, at, grouped_tables)
        np.testing.assert_array_equal(np.asarray(a)[1], np.asarray(b)[1])
    np.testing.assert_array_equal(np.asarray(one[0])[3], np.asarray(two[0])[0])  # the full layer's pages, the same rows
    np.testing.assert_array_equal(np.asarray(one[1])[[0, 1, 2, 4]], np.asarray(two[3]))  # the windowed layers' values


@pytest.mark.parametrize("on_tpu", [True, False], ids=["tpu", "off-the-chip"])
def test_a_span_of_4_kv_heads_of_128_walks_both_its_groups_in_the_kernel_and_the_counters_say_so(tmp_path, monkeypatch, on_tpu):
    """The published SmallThinker span on shapes alone, as its cell runs it (16 lanes, tables of 256 slots, pages of 64): its
    pools keep a row of 4 kv heads of 128 folded (``stored_row``), so on a TPU ``LanePool.walks`` says ``kernel`` for the
    three full layers and for the nine of window 4,096 (blocks of 8 pages: half a megabyte of a folded row of 512), and a
    decode step's ``attn_pages_kernel`` is all of its ``attn_pages_gathered``: each live lane read to its OWN last block,
    in a windowed layer from its window's first slot. Off the chip both are the composed walk's and the kernel counts nothing."""
    from petals_tpu.ops import paged_flash_attention as pfa

    cache, _ = published_span_cache("smallthinker-21b-a3b-span12", tmp_path)
    assert cache.pool_row == (512,) and [(w, len(blocks)) for w, blocks in cache.page_groups] == [(None, 3), (4096, 9)]
    monkeypatch.setattr(pfa, "_on_tpu", lambda: on_tpu)
    lanes, slots, page = 16, 256, 64
    pool = cache.lane_pool(lanes, slots, page, grouped=True)
    path = "kernel" if on_tpu else "composed"
    # (window, layers, block, cut to the window's reach, path); off the chip the composed walk's blocks: ``WALK_MAX_TRIPS``
    assert sorted(pool.walks, key=str) == sorted([(None, 3, 8 if on_tpu else 4, False, path), (4096, 9, 8 if on_tpu else 2, True, path)], key=str)
    positions = np.full(lanes, slots * page, np.int32)  # two idle lanes
    positions[:14] = [2500, 3300, 4100, 4900, 5700, 6500, 7300, 8100, 8900, 9700, 10500, 11300, 12100, 14500]
    live = positions[positions < slots * page].astype(np.int64)
    full_held = np.where(positions < slots * page, positions // page + 1, 0).astype(np.int64)
    window_held = np.where(positions < slots * page, positions // page - np.maximum(positions - 4095, 0) // page + 1, 0).astype(np.int64)
    stats = pool.new_stats()
    pool.count_step(stats, positions, full_held, group_held=[full_held, window_held])
    own_full = 3 * 8 * int((live // (8 * page) + 1).sum())  # whole blocks of 8 slots up to each lane's own last row
    own_window = 9 * int((-(-(live // page - np.maximum(live - 4095, 0) // page + 1) // 8) * 8).sum())  # from the window's first slot
    if on_tpu:
        assert stats["attn_pages_kernel"] == stats["attn_pages_gathered"] == own_full + own_window
    else:  # every lane of the pool's to the longest live lane's last block, in blocks of 4 slots and of 2
        assert stats["attn_pages_kernel"] == 0
        assert stats["attn_pages_gathered"] == 3 * lanes * 228 + 9 * lanes * 66
    assert stats["attn_pages_tabled"] == 12 * lanes * slots
    before = dict(stats)  # a mixed step: the decode rows' walks are the kernel's, the chunk's rows gather their reach
    pool.count_step(stats, positions, full_held, chunk=(15, 0, 300), group_held=[full_held, window_held])
    kernel, gathered = (stats[key] - before[key] for key in ("attn_pages_kernel", "attn_pages_gathered"))
    assert kernel == (own_full + own_window if on_tpu else 0) and gathered > before["attn_pages_gathered"]


def test_two_lanes_of_4_kv_heads_of_128_decode_through_the_kernel_in_both_groups_and_answer_as_the_reference(tmp_path, monkeypatch):
    """The toy SmallThinker at the published row (4 kv heads of 128 under 8 query heads, four layers: a full one and three
    of window 16, pages of 8) on a backend that says it is a TPU: the grouped pools are folded rows of 512, every decode
    row's walk, a full layer's and a windowed layer's cut to its reach, is the walk's kernel (interpreted here) inside the
    grouped decode and mixed steps, two lanes of other lengths side by side, one of them past its window so that pages
    went back; every reply against the reference's whole forward pass, and all a decode step read counted as the kernel's."""
    from petals_tpu.ops import paged_flash_attention as pfa

    hf = {**HF, "head_dim": 128, "num_key_value_heads": 4, "num_attention_heads": 8, "num_hidden_layers": 4, "sliding_window_size": 16,
          "rope_layout": [0, 1, 1, 1], "sliding_window_layout": [0, 1, 1, 1]}
    overrides = {key: value for key, value in hf.items() if HF[key] != value}
    backend, tensors = whole_backend(make_tiny_smallthinker(str(tmp_path), **overrides), n_blocks=4), tiny_smallthinker_tensors(hf)
    assert backend.cache.pool_row == (512,) and [(w, len(blocks)) for w, blocks in backend.cache.page_groups] == [(None, 1), (16, 3)]
    monkeypatch.setattr(pfa, "_on_tpu", lambda: True)  # ``_interpret`` still sees the CPU
    a_rows, b_rows = rows(5, 44), rows(6, 20)

    async def main():
        async with rig(backend, page_size=8, max_length=48) as batcher:
            a, b = await batcher.acquire_lane(), await batcher.acquire_lane()
            info = batcher.occupancy_info()
            assert info["decode_walk"] == ["kernel", "kernel"] and info["pool_row"] == [512]
            got_a, got_b = [await batcher.prefill_lane(a, a_rows[:, :30], 0)], [await batcher.prefill_lane(b, b_rows[:, :6], 0)]
            before = dict(batcher.stats)
            for i in range(14):  # A at 30..43, its window two to three pages behind it; B at 6..19, its whole context in sight
                await asyncio.gather(batcher.prepare_write(a, 30 + i, 31 + i), batcher.prepare_write(b, 6 + i, 7 + i))
                outs = await asyncio.gather(batcher.step(a, a_rows[:, 30 + i : 31 + i], 30 + i), batcher.step(b, b_rows[:, 6 + i : 7 + i], 6 + i))
                got_a.append(outs[0]), got_b.append(outs[1])
            walked = batcher.stats["attn_pages_gathered"] - before["attn_pages_gathered"]
            assert walked == batcher.stats["attn_pages_kernel"] - before["attn_pages_kernel"] > 0
            assert batcher.stats["window_pages_released"] > 0
            return np.concatenate([np.asarray(o) for o in got_a], axis=1), np.concatenate([np.asarray(o) for o in got_b], axis=1)

    got_a, got_b = asyncio.run(main())
    for got, data in ((got_a, a_rows), (got_b, b_rows)):
        np.testing.assert_allclose(got[0], reference_hidden(hf, tensors, data[0], last=4), atol=1e-4, rtol=0)
