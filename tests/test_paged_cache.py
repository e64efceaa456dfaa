"""Paged KV cache (ops/paged_attention.py + server/batching.py paged mode):
page-pool layout with per-lane block tables must be token-identical to the
dense lane pool, admission must cost one page (with pool-exhaustion
backpressure and release->waiter wakeup), and prefix sharing must be
copy-on-write at page granularity."""

import asyncio

import jax.numpy as jnp
import numpy as np
import pytest

from petals_tpu.data_structures import CHAIN_DELIMITER, make_uid
from petals_tpu.rpc import RpcClient
from petals_tpu.rpc.serialization import deserialize_array, serialize_array
from petals_tpu.server.memory_cache import AllocationFailed, PageAllocator
from petals_tpu.server.server import Server, default_dht_prefix
from tests.utils import make_tiny_llama

pytestmark = pytest.mark.pages


@pytest.fixture(scope="module")
def model_path(tmp_path_factory):
    return make_tiny_llama(str(tmp_path_factory.mktemp("models")))


def run(coro):
    return asyncio.run(coro)


async def _start_server(model_path, **kwargs):
    server = Server(model_path, compute_dtype=jnp.float32, use_flash=False, **kwargs)
    await server.start()
    client = await RpcClient.connect(server.rpc_server.host, server.rpc_server.port)
    return server, client


# --------------------------------------------------------------- allocator unit


def test_page_allocator_unit():
    async def main():
        alloc = PageAllocator(3)
        a, b, c = alloc.try_alloc(), alloc.try_alloc(), alloc.try_alloc()
        assert {a, b, c} == {0, 1, 2} and alloc.n_free == 0
        assert alloc.try_alloc() is None  # exhausted
        alloc.incref(b)
        alloc.decref(b)
        assert alloc.n_free == 0  # still referenced once
        alloc.decref(b)
        assert alloc.n_free == 1 and alloc.freed_event.is_set()
        # FIFO reuse of freed pages
        alloc.decref(a)
        assert alloc.try_alloc() == b and alloc.try_alloc() == a
        # preferred page wins when free
        alloc.decref(a)
        alloc.decref(b)
        assert alloc.try_alloc(preferred=a) == a
        assert alloc.stats["allocated"] >= 6 and alloc.stats["freed"] >= 3

    run(main())


# ------------------------------------------------------- decode parity (direct)


def _tiny_backend(model_path):
    import jax

    from petals_tpu.server.backend import TransformerBackend
    from petals_tpu.server.from_pretrained import get_block_config, load_block_params
    from petals_tpu.server.memory_cache import MemoryCache

    family, cfg = get_block_config(model_path)
    per_block = [
        load_block_params(model_path, i, dtype=jnp.float32, family=family, cfg=cfg)
        for i in range(2)
    ]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_block)
    return TransformerBackend(
        family, cfg, stacked, first_block=0, n_blocks=2,
        memory_cache=MemoryCache(None), compute_dtype=jnp.float32, use_flash=False,
    ), cfg


def test_paged_decode_parity_direct(model_path):
    """Direct backend check of both compiled variants on a fixed seed:
    identity tables (the contiguous layout) must agree with the dense batched
    program to float32 rounding (a decode row walks its pages block by block
    with a running softmax: the dense program's sums in another order), and a permuted/oversubscribed table layout (the
    real gather/scatter path) must match per-lane scalar decode."""
    from petals_tpu.ops.paged_attention import identity_tables

    backend, cfg = _tiny_backend(model_path)
    rng = np.random.RandomState(0)
    L, PS, MAX_PAGES = 3, 8, 4
    MAXLEN = PS * MAX_PAGES
    positions = np.array([5, 0, 17], np.int32)
    hidden = rng.randn(L, 1, cfg.hidden_size).astype(np.float32) * 0.1

    # per-lane ground truth + each lane's dense cache content
    kd, vd = backend.cache_descriptors(1, MAXLEN, 0, 2)
    want, lanes_kv = [], []
    for l in range(L):
        kv = (kd.make_zeros(), vd.make_zeros())
        if positions[l]:
            pre = rng.randn(1, positions[l], cfg.hidden_size).astype(np.float32) * 0.1
            _, kv = backend.inference_step(pre, kv, 0)
        lanes_kv.append((np.asarray(kv[0]), np.asarray(kv[1])))
        out, _ = backend.inference_step(hidden[l : l + 1], kv, int(positions[l]))
        want.append(np.asarray(out))

    k_dense = np.concatenate([kv[0] for kv in lanes_kv], axis=1)
    v_dense = np.concatenate([kv[1] for kv in lanes_kv], axis=1)

    def page_pool(tables, n_pages):
        """Scatter the dense per-lane caches into a page pool per ``tables``."""
        n_blocks, _, _, hkv, hd = k_dense.shape
        kp = np.zeros((n_blocks, n_pages, PS, hkv, hd), np.float32)
        vp = np.zeros_like(kp)
        for l in range(L):
            for s in range(MAX_PAGES):
                page = tables[l, s]
                if page < 0:
                    continue
                kp[:, page] = k_dense[:, l, s * PS : (s + 1) * PS]
                vp[:, page] = v_dense[:, l, s * PS : (s + 1) * PS]
        return jnp.asarray(kp), jnp.asarray(vp)

    # (a) identity layout == the dense program, to float32 rounding
    ident = identity_tables(L, MAX_PAGES)
    kp, vp = page_pool(ident, L * MAX_PAGES)
    out_paged, _ = backend.paged_decode_step(hidden, (kp, vp), positions, ident)
    out_dense, _ = backend.batched_decode_step(
        hidden, (jnp.asarray(k_dense), jnp.asarray(v_dense)), positions
    )
    np.testing.assert_allclose(np.asarray(out_paged), np.asarray(out_dense), atol=1e-5, rtol=0)

    # (b) permuted, oversubscribed-pool layout (gather/scatter path): lanes
    # hold only the pages they need, scattered across a bigger pool
    n_pages = 20
    perm_tables = np.full((L, MAX_PAGES), -1, np.int32)
    free = list(rng.permutation(n_pages))
    for l in range(L):
        n_slots = max(1, -(-int(positions[l] + 1) // PS))
        for s in range(n_slots):
            perm_tables[l, s] = free.pop()
    kp, vp = page_pool(perm_tables, n_pages)
    out_perm, (kp2, vp2) = backend.paged_decode_step(
        hidden, (kp, vp), positions, perm_tables
    )
    for l in range(L):
        np.testing.assert_allclose(
            np.asarray(out_perm)[l : l + 1], want[l], atol=1e-5, rtol=0,
            err_msg=f"lane {l} (permuted tables)",
        )
    # the written token rows landed in the right pages
    kp2 = np.asarray(kp2)
    for l in range(L):
        pos = int(positions[l])
        page = perm_tables[l, pos // PS]
        row = kp2[:, page, pos % PS]
        assert np.abs(row).sum() > 0, f"lane {l} decode row never written"


def test_paged_gen_decode_parity_direct(model_path):
    """Server-gen paged twin: greedy AND sampled token streams from the paged
    gen program (permuted tables) must equal the dense gen program's."""
    from petals_tpu.client.from_pretrained import load_client_params
    from petals_tpu.ops.sampling import sampling_vectors

    backend, cfg = _tiny_backend(model_path)
    # a 2-block "full model" for the client leaves: fine for parity purposes
    backend.n_blocks = 2
    client_params = load_client_params(model_path, dtype=jnp.float32)
    rng = np.random.RandomState(1)
    L, PS, MAX_PAGES = 2, 8, 3
    positions = np.array([4, 9], np.int32)
    hidden = rng.randn(L, 1, cfg.hidden_size).astype(np.float32) * 0.1
    tokens = np.array([7, 11], np.int32)
    use_token = np.array([True, True])

    kd, vd = backend.cache_descriptors(1, PS * MAX_PAGES, 0, 2)
    lanes_kv = []
    for l in range(L):
        kv = (kd.make_zeros(), vd.make_zeros())
        pre = rng.randn(1, positions[l], cfg.hidden_size).astype(np.float32) * 0.1
        _, kv = backend.inference_step(pre, kv, 0)
        lanes_kv.append((np.asarray(kv[0]), np.asarray(kv[1])))
    k_dense = np.concatenate([kv[0] for kv in lanes_kv], axis=1)
    v_dense = np.concatenate([kv[1] for kv in lanes_kv], axis=1)

    for sampled in (False, True):
        vecs = sampling_vectors(L, cfg.vocab_size)
        if sampled:
            vecs["do_sample"][:] = True
            vecs["temperature"][:] = 0.8
            vecs["top_k"][:] = 10
            vecs["seeds"][:] = np.array([42, 43])
            vecs["draw_idx"][:] = 1
        out_d, toks_d, _ = backend.batched_gen_decode_step(
            client_params, hidden, tokens, use_token,
            (jnp.asarray(k_dense), jnp.asarray(v_dense)), positions,
            sampling_vecs=vecs,
        )
        n_pages = 11
        tables = np.full((L, MAX_PAGES), -1, np.int32)
        free = list(np.random.RandomState(2).permutation(n_pages))
        n_blocks, _, _, hkv, hd = k_dense.shape
        kp = np.zeros((n_blocks, n_pages, PS, hkv, hd), np.float32)
        vp = np.zeros_like(kp)
        for l in range(L):
            for s in range(-(-int(positions[l] + 1) // PS)):
                page = free.pop()
                tables[l, s] = page
                kp[:, page] = k_dense[:, l, s * PS : (s + 1) * PS]
                vp[:, page] = v_dense[:, l, s * PS : (s + 1) * PS]
        vecs2 = sampling_vectors(L, cfg.vocab_size)
        if sampled:
            vecs2["do_sample"][:] = True
            vecs2["temperature"][:] = 0.8
            vecs2["top_k"][:] = 10
            vecs2["seeds"][:] = np.array([42, 43])
            vecs2["draw_idx"][:] = 1
        out_p, toks_p, _ = backend.paged_gen_decode_step(
            client_params, hidden, tokens, use_token,
            (jnp.asarray(kp), jnp.asarray(vp)), positions, tables,
            sampling_vecs=vecs2,
        )
        np.testing.assert_array_equal(
            np.asarray(toks_p), np.asarray(toks_d),
            err_msg=f"sampled={sampled}: paged gen tokens diverge from dense",
        )
        np.testing.assert_allclose(
            np.asarray(out_p), np.asarray(out_d), atol=1e-5, rtol=0
        )


# ------------------------------------------- admission, backpressure, wakeup


def test_page_exhaustion_backpressure_and_wakeup(model_path):
    """Admission costs ONE page; an exhausted pool blocks prepare_write with
    the lane-waiter backpressure contract (timeout -> AllocationFailed), and
    a release wakes the waiter."""

    async def main():
        server, client = await _start_server(
            model_path, batching=True, batch_lanes=2, batch_max_length=32,
            page_size=8, n_pages=5,  # oversubscribed: 2 lanes x 4 slots > 5 pages
        )
        try:
            batcher = server.handler.batcher
            assert batcher.page_size == 8 and batcher.n_pages == 5
            a = await batcher.acquire_lane(timeout=5)  # 1 page
            b = await batcher.acquire_lane(timeout=5)  # 1 page
            await batcher.prepare_write(a, 0, 32)  # lane a now holds 4 pages
            assert batcher._pages.n_free == 0

            # backpressure: no page frees within the timeout
            with pytest.raises(AllocationFailed, match="page"):
                await batcher.prepare_write(b, 8, 9, timeout=0.2)

            # wakeup: a release returns pages and unblocks the waiter
            waiter = asyncio.create_task(batcher.prepare_write(b, 8, 9, timeout=10))
            await asyncio.sleep(0.05)
            assert not waiter.done()
            batcher.release_lane(a)
            await asyncio.wait_for(waiter, timeout=5)
            assert int(batcher._tables[b, 1]) >= 0
            batcher.release_lane(b)
            assert batcher._pages.n_free == batcher.n_pages  # nothing leaked
        finally:
            await client.close()
            await server.shutdown()

    run(main())


def test_cow_fork_on_shared_pages(model_path):
    """A page shared with a prefix-cache pin must be FORKED before a lane
    writes into it: the lane gets a content-identical private copy, the
    pinned original stays untouched."""

    async def main():
        server, client = await _start_server(
            model_path, batching=True, batch_lanes=2, batch_max_length=32,
            page_size=8, n_pages=8,
        )
        try:
            batcher = server.handler.batcher
            a = await batcher.acquire_lane(timeout=5)
            await batcher.prepare_write(a, 0, 16)  # two pages resident
            page0 = int(batcher._tables[a, 0])

            # stamp recognizable content into lane a's first page
            k_pool, v_pool = batcher._buffers()
            k_pool = k_pool.at[:, page0].set(1.25)
            batcher._update(k_pool, v_pool)

            # prefix-cache-style pin, then adopt into a second lane
            epoch = batcher.page_epoch
            pinned = batcher.pin_lane_pages(a, 0, 8)
            assert pinned == [page0]
            assert int(batcher._pages.refs[page0]) == 2
            b = await batcher.acquire_lane(timeout=5)
            batcher.adopt_pages(b, pinned)
            assert int(batcher._pages.refs[page0]) == 3

            # lane b writes into the shared page -> copy-on-write fork
            await batcher.prepare_write(b, 0, 4)
            forked = int(batcher._tables[b, 0])
            assert forked != page0
            assert batcher._pages.stats["forked"] == 1
            assert int(batcher._pages.refs[page0]) == 2  # b dropped its share
            k_pool, _ = batcher._buffers()
            np.testing.assert_array_equal(
                np.asarray(k_pool[:, forked]), np.asarray(k_pool[:, page0])
            )
            assert float(np.asarray(k_pool[:, forked]).max()) == 1.25

            # unpin + release: every page returns to the pool
            batcher.unpin_pages(pinned, epoch)
            batcher.release_lane(a)
            batcher.release_lane(b)
            assert batcher._pages.n_free == batcher.n_pages
        finally:
            await client.close()
            await server.shutdown()

    run(main())


def test_dead_lane_release_keeps_shared_pages(model_path):
    """Failover hygiene: a dying session's lane release (the server-side
    teardown a kill/drain triggers) must only drop ITS OWN share of
    COW-shared prefix pages — survivors adopted onto the same pages keep
    their content, and the page is not handed back to the pool while any
    survivor references it."""

    async def main():
        server, client = await _start_server(
            model_path, batching=True, batch_lanes=3, batch_max_length=32,
            page_size=8, n_pages=8,
        )
        try:
            batcher = server.handler.batcher
            dying = await batcher.acquire_lane(timeout=5)
            await batcher.prepare_write(dying, 0, 8)
            page0 = int(batcher._tables[dying, 0])
            k_pool, v_pool = batcher._buffers()
            k_pool = k_pool.at[:, page0].set(2.5)  # the shared prefix content
            batcher._update(k_pool, v_pool)

            # two survivors share the dying session's prefix page (the
            # prefix-cache pin holds one ref, each adoption one more)
            epoch = batcher.page_epoch
            pinned = batcher.pin_lane_pages(dying, 0, 8)
            assert pinned == [page0]
            survivors = []
            for _ in range(2):
                lane = await batcher.acquire_lane(timeout=5)
                batcher.adopt_pages(lane, pinned)
                survivors.append(lane)
            assert int(batcher._pages.refs[page0]) == 4

            # the session dies: its lane is torn down (failover path)
            batcher.release_lane(dying)
            assert int(batcher._pages.refs[page0]) == 3, (
                "a dead lane must only drop its own share of a COW page"
            )

            # the page must NOT be allocatable out from under the survivors:
            # exhaust the pool and verify page0 was never handed out
            grabbed = []
            while (p := batcher._pages.try_alloc()) is not None:
                grabbed.append(p)
            assert page0 not in grabbed
            for p in grabbed:
                batcher._pages.decref(p)

            # survivors still read the shared prefix content intact
            k_pool, _ = batcher._buffers()
            for lane in survivors:
                assert int(batcher._tables[lane, 0]) == page0
            assert float(np.asarray(k_pool[:, page0]).min()) == 2.5

            # full teardown returns every page: nothing leaked, nothing
            # double-freed by the dead lane
            for lane in survivors:
                batcher.release_lane(lane)
            batcher.unpin_pages(pinned, epoch)
            assert batcher._pages.n_free == batcher.n_pages
        finally:
            await client.close()
            await server.shutdown()

    run(main())


# ------------------------------------------------- end-to-end paged sessions


def test_paged_sessions_token_identical_oversubscribed(model_path):
    """Concurrent sessions on an OVERSUBSCRIBED paged pool (more lanes than
    full-length sessions would fit; non-identity tables, so the real
    gather/scatter program runs) stay token-identical to unbatched serving."""

    async def main():
        server, client = await _start_server(
            model_path, batching=True, batch_lanes=4, batch_max_length=64,
            page_size=16, n_pages=10,  # 4 lanes x 4 slots = 16 > 10 pages
        )
        try:
            cfg = server.cfg
            prefix = default_dht_prefix(model_path)
            uids = CHAIN_DELIMITER.join(
                make_uid(prefix, i) for i in range(cfg.num_hidden_layers)
            )
            rng = np.random.RandomState(11)
            sessions = []
            for i in range(4):
                prefill = rng.randn(1, 3 + 5 * i, cfg.hidden_size).astype(np.float32) * 0.1
                steps = [
                    rng.randn(1, 1, cfg.hidden_size).astype(np.float32) * 0.1
                    for _ in range(6)
                ]
                sessions.append((prefill, steps))

            async def drive(prefill, steps, barrier):
                stream = await client.open_stream("ptu.inference")
                await stream.send({"uids": uids, "max_length": 40, "batch_size": 1})
                await stream.recv(timeout=60)
                await barrier.wait()
                outs = []
                await stream.send({"tensors": {"hidden": serialize_array(prefill)}})
                reply = await stream.recv(timeout=120)
                outs.append(deserialize_array(reply["tensors"]["hidden"]))
                for h in steps:
                    await stream.send({"tensors": {"hidden": serialize_array(h)}})
                    reply = await stream.recv(timeout=120)
                    outs.append(deserialize_array(reply["tensors"]["hidden"]))
                await stream.end()
                return outs

            barrier = asyncio.Event()
            tasks = [
                asyncio.create_task(drive(p, s, barrier)) for p, s in sessions
            ]
            await asyncio.sleep(0.1)
            barrier.set()
            results = await asyncio.gather(*tasks)
            stats = dict(server.handler.batcher.stats)
            assert stats["max_batch"] >= 2, f"never coalesced: {stats}"
            paged = server.handler.batcher.paged_summary()
            assert paged is not None and paged["pages_allocated"] > 0, paged

            backend = server.backend
            for s, ((prefill, steps), got) in enumerate(zip(sessions, results)):
                kd, vd = backend.cache_descriptors(1, 64, 0, backend.n_blocks)
                kv = (kd.make_zeros(), vd.make_zeros())
                want, kv = backend.inference_step(prefill, kv, 0)
                np.testing.assert_allclose(
                    got[0], np.asarray(want), atol=2e-5, rtol=0,
                    err_msg=f"session {s} prefill",
                )
                pos = prefill.shape[1]
                for i, h in enumerate(steps):
                    want, kv = backend.inference_step(h, kv, pos)
                    pos += 1
                    np.testing.assert_allclose(
                        got[1 + i], np.asarray(want), atol=2e-5, rtol=0,
                        err_msg=f"session {s} step {i}",
                    )
        finally:
            await client.close()
            await server.shutdown()

    run(main())
