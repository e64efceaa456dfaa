"""The page pool's storage rule (ops/paged_attention.py ``stored_row``): a
values or codes leaf whose row is under the chip's 128 lanes is stored with the
kv heads folded into it, ``[.., n_pages, page_size, hkv * d_store]``; the same
bytes in the same order, so the step programs give the outputs they gave over
rows of ``[hkv, d_store]`` bit for bit, and everything that leaves the device
(swap entries, snapshots and imports, adopted prefix pages read back) keeps
rows of ``[hkv, d]``. On a toy Falcon of head_dim 64, the published head's."""

import asyncio

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from petals_tpu.ops.paged_attention import (
    KV_QUANT_KINDS, PagedKV, PagedPool, fold_rows, gather_pages, pool_geometry, stored_row, unfold_rows,
)
from petals_tpu.rpc import RpcClient
from petals_tpu.server.backend import TransformerBackend
from petals_tpu.server.from_pretrained import get_block_config, load_block_params
from petals_tpu.server.memory_cache import MemoryCache
from petals_tpu.server.server import Server
from tests.utils import lane_pools, make_tiny_falcon, published_span_cache

LANES, MAX_PAGES, PAGE_SIZE, N_PAGES = 3, 2, 8, 6
SENTINEL = MAX_PAGES * PAGE_SIZE


@pytest.fixture(scope="module")
def model_path(tmp_path_factory):
    return make_tiny_falcon(str(tmp_path_factory.mktemp("falcon-d64")), n_layers=2, head_dim=64)


def _backend(path, kind):
    family, cfg = get_block_config(path)
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *(load_block_params(path, i, dtype=jnp.float32) for i in range(2)))
    backend = TransformerBackend(family, cfg, stacked, first_block=0, n_blocks=2, memory_cache=MemoryCache(None),
                                 compute_dtype=jnp.float32, use_flash=False, kv_quant_type=kind)
    assert (backend.num_kv_heads, backend.head_dim) == (2, 64)
    return backend, cfg


def _d_store(kind, d):
    return d // 2 if kind == "nf4a" else d


def _pools(backend, folded):
    """The pair of zeroed pools, as the rule stores them or with the rows of
    ``[hkv, d_store]`` every pool had before it."""
    descs = lane_pools(backend, N_PAGES, PAGE_SIZE, end=2)[0]
    leaves = [jnp.zeros(d.shape if folded or i >= 2 else (*d.shape[:3], backend.num_kv_heads, d.shape[3] // backend.num_kv_heads), d.dtype)
              for i, d in enumerate(descs)]
    return (leaves[0], leaves[1]) if len(leaves) == 2 else (PagedPool(leaves[0], leaves[2]), PagedPool(leaves[1], leaves[3]))


def _bytes(pool):
    """A pool's leaves as the bytes they hold, row-major: the form is a view."""
    return [np.asarray(leaf).reshape(-1) for leaf in jax.tree_util.tree_leaves(pool)]


def test_the_rule_folds_a_row_under_128_lanes_or_of_up_to_4_heads_and_no_other():
    assert stored_row(8, 64) == (512,)  # Falcon-40B, bf16: the pool PR 38 was written for
    assert stored_row(8, 128) == (8, 128) and stored_row(16, 128) == (16, 128) and stored_row(32, 128) == (32, 128)  # the other five cells
    assert stored_row(8, 256) == (8, 256) and stored_row(30, 128) == (30, 128) and stored_row(5, 128) == (5, 128)
    assert stored_row(2, 16) == (32,) and stored_row(8, 96) == (768,)
    assert stored_row(2, 256) == (512,) and stored_row(1, 128) == (128,) and stored_row(3, 128) == (384,)  # up to 4 heads: folded whatever the width
    assert stored_row(4, 128) == (512,) and stored_row(4, 256) == (1024,)  # a page of 4 x 128 is one matrix of whole tiles where pages are walked
    # a span whose decode rows fetch single rows (one with an index row) stops the rule under 4: a row of [4, 128] is a tile of its own
    assert stored_row(4, 128, row_fetch=True) == (4, 128) and stored_row(4, 256, row_fetch=True) == (4, 256)
    for hkv, d in ((8, 64), (2, 16), (8, 96), (2, 256), (1, 128), (3, 128), (8, 128), (16, 128), (30, 128)):  # and changes nothing else
        assert stored_row(hkv, d, row_fetch=True) == stored_row(hkv, d)
    rows = np.arange(2 * 3 * 4 * 5).reshape(2, 3, 4, 5)
    assert fold_rows(rows, (20,)).shape == (2, 3, 20) and fold_rows(rows, (4, 5)).shape == rows.shape
    np.testing.assert_array_equal(unfold_rows(fold_rows(rows, (20,)), 4), rows)
    pool = jnp.zeros((6, 8, 128))
    assert pool_geometry(pool, 64) == (6, 8, 2, 64) and pool_geometry(pool.reshape(6, 8, 2, 64), 64) == (6, 8, 2, 64)
    assert pool_geometry(jnp.zeros((6, 8, 512)), 128) == (6, 8, 4, 128)
    quantized = PagedPool(jnp.zeros((6, 8, 64), jnp.uint8), jnp.zeros((6, 8, 2)))
    assert pool_geometry(quantized, 64) == (6, 8, 2, 32) and quantized.shape == (6, 8, 2, 64) and quantized.ndim == 4
    assert PagedPool(jnp.zeros((6, 8, 2, 32), jnp.uint8), jnp.zeros((6, 8, 2))).shape == (6, 8, 2, 64)
    with pytest.raises(ValueError, match="does not say its kv heads"):
        gather_pages(pool, jnp.zeros((1, 2), jnp.int32))
    assert gather_pages(pool, jnp.zeros((1, 2), jnp.int32), 2).shape == (1, 16, 2, 64)
    assert PagedKV(pool, jnp.zeros((3, 2), jnp.int32)).shape == (3, 16, 128) and PagedKV(pool, jnp.zeros((3, 2), jnp.int32)).page_size == 8


@pytest.mark.parametrize("hkv", [4, 8])
@pytest.mark.parametrize("kind", KV_QUANT_KINDS)
def test_a_pool_of_head_dim_128_is_declared_by_its_heads(tmp_path, kind, hkv):
    """The descriptors of a span whose rows fill the lanes. Of 8 kv heads they are the parent's: ``[.., hkv, d]`` values
    or int8 codes. Of 4 (up to ``FOLDED_ROW_HEADS``) the row is folded, values and int8 codes alike, ``[.., 512]``: a
    page is then one matrix of whole tiles for the decode walk's kernel. (nf4a packs two dims a byte, so its codes of a
    head_dim of 128 are 64 wide and fold, as any row of 64 does.) Scales keep ``[.., hkv]`` in every case."""
    from transformers import LlamaConfig

    LlamaConfig(vocab_size=64, hidden_size=128 * hkv, intermediate_size=128, num_hidden_layers=2, num_attention_heads=hkv,
                num_key_value_heads=hkv).save_pretrained(str(tmp_path))
    family, cfg = get_block_config(str(tmp_path))
    params = {name: jax.ShapeDtypeStruct((2, *leaf.shape), leaf.dtype) for name, leaf in family.block_param_shapes(cfg, jnp.bfloat16).items()}
    backend = TransformerBackend(family, cfg, params, first_block=0, n_blocks=2, memory_cache=None, kv_quant_type=kind)
    assert (backend.num_kv_heads, backend.head_dim) == (hkv, 128) and backend.cache.index_row is None
    shapes = [(d.shape, jnp.dtype(d.dtype)) for d in lane_pools(backend, 6, 8, end=2)[0]]
    d_store = _d_store(kind, 128)
    row = (hkv, d_store) if hkv == 8 and d_store == 128 else (hkv * d_store,)
    codes = {"none": jnp.dtype(backend.cache_dtype), "int8": jnp.dtype(jnp.int8), "nf4a": jnp.dtype(jnp.uint8)}[kind]
    assert backend.cache.pool_row == row
    assert shapes == [((2, 6, 8, *row), codes)] * 2 + ([] if kind == "none" else [((2, 6, 8, hkv), jnp.dtype(jnp.float32))] * 2)
    # which walk a decode row of such a pool takes on a TPU: the kernel over the plain folded row, the composed one over codes
    from petals_tpu.ops import paged_flash_attention as pfa

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(pfa, "_on_tpu", lambda: True)
        walks = backend.cache.lane_pool(3, 4, 16).walks
    assert [walk[-1] for walk in walks] == ["kernel" if (hkv, kind) == (4, "none") else "composed"]


@pytest.mark.parametrize("name,row,index", [
    ("keye-vl2-30b-a3b-span5", (4, 128), True), ("smallthinker-21b-a3b-span12", (512,), False), ("qwen3-next-80b-a3b-span8-ep4", (512,), False),
    ("jamba2-3b-span28", (128,), False), ("mixtral-8x7b-span2", (8, 128), False), ("falcon-40b-span5", (512,), False),
])
def test_a_published_span_s_row_follows_what_its_decode_rows_do_with_the_pool(tmp_path, name, row, index):
    """``SpanCache.pool_row`` tells the rule what the span's decode rows do: 4 kv heads of 128 are folded where pages are
    walked (SmallThinker's two page groups) and stay rows of ``[4, 128]`` in a span WITH an index row, whose sparse
    attention fetches the rows it chose one by one (Keye-VL-2.0's; its compiled programs are the parent's). The others
    as they were: on the configurations' shapes alone."""
    cache, blocks = published_span_cache(name, tmp_path)
    assert (cache.index_row is not None) == index and cache.pool_row == row
    pages = cache.pool_descriptors(cache.group_pages(2, 4, 64, 64) if cache.grouped else 8, 64, 2, 0, blocks)
    assert all(tuple(d.shape[3:]) == row for d in pages[: 2 * max(len(cache.page_groups), 1)])


def _one_step_apart(got, want, codes_dtype) -> int:
    """How many stored codes differ between two pools' bytes, none by more
    than one step (a packed nf4a byte: neither nibble)."""
    got, want = got.astype(np.int32), want.astype(np.int32)
    if codes_dtype == np.uint8:
        steps = np.maximum(np.abs((got & 15) - (want & 15)), np.abs((got >> 4) - (want >> 4)))
    else:
        steps = np.abs(got - want)
    assert steps.max() <= 1
    return int((steps > 0).sum())


@pytest.mark.parametrize("kind", KV_QUANT_KINDS)
def test_rows_land_and_come_back_as_the_same_bytes_in_either_form(kind):
    """The moves themselves, where nothing is computed between the two forms:
    a decode row a lane, a verify's rows a lane and a prompt's chunk written
    by ``paged_update_kv`` leave the same bytes in a folded pool and in one of
    rows of ``[hkv, d_store]`` (quantized the same way: the fold comes after
    the codes are made), ``gather_pages`` takes the same view out of both,
    holes zero, and the composed attention over it (a decode row's walk, a
    chunk's dense view) answers the same to float32 rounding."""
    from petals_tpu.ops.paged_attention import paged_update_kv, quantize_kv_rows
    from petals_tpu.ops.paged_flash_attention import composed_paged_attend

    hkv, d, group = 2, 64, 2
    d_store = _d_store(kind, d)
    rng = np.random.default_rng(3)
    rows = lambda *shape: jnp.asarray(rng.standard_normal(shape).astype(np.float32) * 0.5)
    tables = jnp.asarray(np.array([[4, 1], [0, -1], [3, 5]], np.int32))

    def empty(folded):
        if kind == "none":
            return jnp.zeros((N_PAGES, PAGE_SIZE, *((hkv * d,) if folded else (hkv, d))), jnp.float32)
        codes, scales = quantize_kv_rows(jnp.zeros((N_PAGES, PAGE_SIZE, hkv, d), jnp.float32), kind)
        return PagedPool(fold_rows(codes, (hkv * d_store,)) if folded else codes, scales)

    writes = [  # (new rows, position, n_valid): a chunk into lane 0, decode rows, a verify's three rows a lane
        (rows(1, 11, hkv, d), jnp.int32(0), jnp.int32(9)),
        (rows(LANES, 1, hkv, d), jnp.asarray([9, SENTINEL, 0], jnp.int32), None),
        (rows(LANES, 3, hkv, d), jnp.asarray([10, SENTINEL, 1], jnp.int32), None),
    ]
    q_dec, q_chunk = rows(LANES, 1, hkv * group, d), rows(1, 5, hkv * group, d)
    pos = jnp.asarray([12, SENTINEL, 3], jnp.int32)

    @jax.jit
    def run(k_pool, v_pool):
        for new, position, n_valid in writes:
            lane_tables = tables[:1] if position.ndim == 0 else tables
            k_kv, v_kv, _ = paged_update_kv(PagedKV(k_pool, lane_tables), PagedKV(v_pool, lane_tables), new, -new, position, n_valid)
            k_pool, v_pool = k_kv.pool, v_kv.pool
        view = gather_pages(k_pool, tables, hkv)
        walked = composed_paged_attend(q_dec, k_pool, v_pool, tables, q_offset=pos, kv_length=pos + 1)
        dense = composed_paged_attend(q_chunk, k_pool, v_pool, tables[:1], q_offset=jnp.int32(8), kv_length=jnp.int32(13))
        return k_pool, v_pool, view, walked, dense

    folded, parent = run(empty(True), empty(True)), run(empty(False), empty(False))
    assert jax.tree_util.tree_leaves(folded[0])[0].shape == (N_PAGES, PAGE_SIZE, hkv * d_store)
    assert jax.tree_util.tree_leaves(parent[0])[0].shape == (N_PAGES, PAGE_SIZE, hkv, d_store)
    for pool, parent_pool in zip(folded[:2], parent[:2]):
        for got, want in zip(_bytes(pool), _bytes(parent_pool)):
            assert want.any()
            np.testing.assert_array_equal(got, want)
    assert folded[2].shape == (LANES, SENTINEL, hkv, d) and not np.asarray(folded[2])[1, PAGE_SIZE:].any()  # lane 1's hole
    np.testing.assert_array_equal(np.asarray(folded[2]), np.asarray(parent[2]))
    for got, want in zip(folded[3:], parent[3:]):
        assert np.abs(np.asarray(want)).max() > 0.01
        np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=0, atol=1e-6)


@pytest.mark.parametrize("kind", KV_QUANT_KINDS)
def test_a_folded_pool_runs_the_program_of_rows_of_hkv_d(model_path, kind):
    """A prompt through the mixed step's chunk half with every lane idle
    (prefill), a mixed step with a lane decoding beside a second prompt, and
    decode steps, all through ``_scan_paged_span``: over the pool as the rule
    stores it (``[2, 6, 8, 128]``) and over the parent's rows of ``[hkv,
    d_store]``. Every consumer reads the form off the leaf it is handed, so
    the second arm IS the parent's program. The two are two XLA programs, and
    XLA:CPU contracts the rope's multiply-adds one way in one and another way
    in the other: keys come out one float32 rounding apart (values, which no
    arithmetic follows, the same bits), so the outputs are held to float32
    rounding and a quantized pool's codes to one step, as
    tests/test_paged_pool_carry.py holds the carry's programs; the bytes
    themselves: ``test_rows_land_and_come_back_as_the_same_bytes_in_either_form``."""
    backend, cfg = _backend(model_path, kind)
    hkv, d_store = backend.num_kv_heads, _d_store(kind, backend.head_dim)
    assert backend.cache.pool_row == (hkv * d_store,)
    descs = lane_pools(backend, N_PAGES, PAGE_SIZE, end=2)[0]
    assert descs[0].shape == descs[1].shape == (2, N_PAGES, PAGE_SIZE, hkv * d_store)
    if kind != "none":
        assert descs[2].shape == descs[3].shape == (2, N_PAGES, PAGE_SIZE, hkv)
    rng = np.random.default_rng(38)
    tables = np.array([[4, 1], [0, -1], [3, 5]], np.int32)  # permuted, one hole
    idle = np.full(LANES, SENTINEL, np.int32)
    rows = lambda *shape: (rng.standard_normal(shape) * 0.3).astype(np.float32)
    prompt, second, lanes_in = rows(1, 11, cfg.hidden_size), rows(1, 5, cfg.hidden_size), rows(3, LANES, 1, cfg.hidden_size)

    def run(pools):
        outs = []
        dec, chunk, pools = backend.paged_mixed_step(lanes_in[0], pools, idle, tables, prompt, 0, 0)  # prefill: no lane decodes
        outs.append(chunk)
        positions = np.array([11, SENTINEL, SENTINEL], np.int32)
        dec, chunk, pools = backend.paged_mixed_step(lanes_in[1], pools, positions, tables, second, 2, 0)  # lane 0 decodes beside lane 2's prompt
        outs += [dec[:1], chunk]
        for step, positions in enumerate(([12, SENTINEL, 5], [13, SENTINEL, 6])):  # into lane 0's second page
            dec, pools = backend.paged_decode_step(lanes_in[2] * (1 + step), pools, np.asarray(positions, np.int32), tables)
            outs.append(np.asarray(dec)[[0, 2]])
        return [np.asarray(o) for o in outs], pools

    folded_out, folded = run(_pools(backend, folded=True))
    parent_out, parent = run(_pools(backend, folded=False))
    assert jax.tree_util.tree_leaves(folded)[0].shape == (2, N_PAGES, PAGE_SIZE, hkv * d_store)
    assert jax.tree_util.tree_leaves(parent)[0].shape == (2, N_PAGES, PAGE_SIZE, hkv, d_store)
    flipped = 0
    for pool, parent_pool in zip(folded, parent):
        leaves, parent_leaves = jax.tree_util.tree_leaves(pool), jax.tree_util.tree_leaves(parent_pool)
        assert np.asarray(parent_leaves[0]).any()
        if kind == "none":
            np.testing.assert_allclose(_bytes(pool)[0], _bytes(parent_pool)[0], rtol=0, atol=1e-6)
        else:
            flipped += _one_step_apart(_bytes(pool)[0], _bytes(parent_pool)[0], np.asarray(leaves[0]).dtype)
            np.testing.assert_allclose(np.asarray(leaves[1]), np.asarray(parent_leaves[1]), rtol=1e-5)
    assert flipped <= 4  # of 12,288 or 6,144 codes a side: a key within one rounding of a step's edge
    for got, want in zip(folded_out, parent_out):
        assert np.isfinite(want).all() and np.abs(want).max() > 0.1
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-6 if not flipped else 0.05)


@pytest.mark.parametrize("kind", KV_QUANT_KINDS)
def test_what_leaves_the_device_keeps_rows_of_hkv_d(model_path, kind):
    """kv import and export (``snapshot_lane``), swap out (the host's entry,
    and a snapshot assembled from it) and back in onto other pages, and a
    prefix page adopted by a second lane, forked on its first write and read
    back: the pool is folded, the wire is rows of ``[hkv, d]`` as it was."""

    async def main():
        server = Server(model_path, compute_dtype=jnp.float32, use_flash=False, batching=True, batch_lanes=2, batch_max_length=32,
                        page_size=8, n_pages=4, swap_host_bytes=1 << 22, kv_quant_type=kind)
        await server.start()
        client = await RpcClient.connect(server.rpc_server.host, server.rpc_server.port)
        try:
            batcher, backend = server.handler.batcher, server.handler.backend
            await batcher.ensure_open()
            n_blocks, hkv, d = backend.n_blocks, backend.num_kv_heads, backend.head_dim
            d_store = _d_store(kind, d)
            assert batcher.occupancy_info()["pool_row"] == [hkv * d_store] == list(backend.cache.pool_row)
            for pool in batcher._buffers():
                leaves = jax.tree_util.tree_leaves(pool)
                assert leaves[0].shape == (n_blocks, 4, 8, hkv * d_store)
                assert kind == "none" or leaves[1].shape == (n_blocks, 4, 8, hkv)

            # kv import, as the handler does it: a session-shaped lane of rows of [hkv, d]
            a = await batcher.acquire_lane(timeout=5)
            rng = np.random.default_rng(7)
            sent = [(rng.standard_normal((n_blocks, 1, 16, hkv, d)) * 0.5).astype(np.float32) for _ in range(2)]
            full = [np.zeros((n_blocks, 1, batcher.max_length, hkv, d), np.float32) for _ in range(2)]
            for whole, part in zip(full, sent):
                whole[:, :, :16] = part
            await batcher.run_exclusive(a, lambda kv_lane, handles: (None, (jnp.asarray(full[0]), jnp.asarray(full[1]))), extract=False, write_range=(0, 16))
            before = await batcher.snapshot_lane(a, 16, 0, n_blocks)  # kv export
            for got, want in zip(before, sent):
                assert got.shape == (n_blocks, 1, 16, hkv, d)
                if kind == "none":
                    np.testing.assert_array_equal(got, want)
                else:  # what the codes hold: within a quantization step of what was sent
                    assert np.abs(got - want).max() < (0.02 if kind == "int8" else 0.4) * np.abs(want).max()

            # swap out: the host's entry holds rows of [hkv, d_store]; a snapshot from it is pure numpy
            old_pages = [int(p) for p in batcher._tables[a] if p >= 0]
            assert await batcher._swap_out_lane(a)
            entry = batcher._scheduler.lanes[a].swap
            for side in (entry.k, entry.v):
                if kind == "none":
                    assert isinstance(side, np.ndarray) and side.shape == (n_blocks, 2, 8, hkv, d)
                else:
                    assert side.codes.shape == (n_blocks, 2, 8, hkv, d_store) and side.scales.shape == (n_blocks, 2, 8, hkv)
            from_swap = await batcher.snapshot_from_swap(a, 16, 0, n_blocks)
            for got, want in zip(from_swap, before):  # numpy dequantizes to float32, the device to bf16
                np.testing.assert_allclose(got, np.asarray(want, np.float32), rtol=0 if kind == "none" else 2.0 ** -7, atol=0)
            # another lane takes three of the four pages; reading a swaps it back in onto others (and b out), byte for byte
            b = await batcher.acquire_lane(timeout=5)
            await batcher.prepare_write(b, 0, 24)
            assert set(old_pages) & {int(p) for p in batcher._tables[b] if p >= 0}
            after = await batcher.snapshot_lane(a, 16, 0, n_blocks)
            assert batcher._scheduler.stats["swap_ins"] == 1 and {int(p) for p in batcher._tables[a] if p >= 0} != set(old_pages)
            for got, want in zip(after, before):
                np.testing.assert_array_equal(got, want)
            batcher.release_lane(b)

            # a prefix page adopted by a second lane: zero bytes copied, the same rows read back; forked on its first write
            epoch = batcher.page_epoch
            pinned = batcher.pin_lane_pages(a, 0, 8)
            b = await batcher.acquire_lane(timeout=5)
            batcher.adopt_pages(b, pinned)
            adopted = await batcher.snapshot_lane(b, 8, 0, n_blocks)
            await batcher.prepare_write(b, 0, 4)
            assert int(batcher._tables[b, 0]) != pinned[0] and batcher._pages.stats["forked"] == 1
            forked = await batcher.snapshot_lane(b, 8, 0, n_blocks)
            for got, again, want in zip(adopted, forked, before):
                assert got.shape == (n_blocks, 1, 8, hkv, d)
                np.testing.assert_array_equal(got, want[:, :, :8])
                np.testing.assert_array_equal(again, want[:, :, :8])
            batcher.unpin_pages(pinned, epoch)
            batcher.release_lane(a)
            batcher.release_lane(b)
            assert batcher._pages.n_free == batcher.n_pages and batcher.swap_pool.bytes_in_use == 0
        finally:
            await client.close()
            await server.shutdown()

    asyncio.run(main())
