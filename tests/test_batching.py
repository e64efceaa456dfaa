"""Continuous batching (server/batching.py): concurrent decode sessions
coalesce into one device step over a shared lane pool, token-identical to
unbatched serving, with join/leave mid-flight and lane-pressure fallback.

Beats the reference, whose task pools never batch across requests
(reference src/petals/server/task_pool.py:35-36)."""

import asyncio

import jax.numpy as jnp
import numpy as np
import pytest

from petals_tpu.data_structures import CHAIN_DELIMITER, make_uid
from petals_tpu.rpc import RpcClient
from petals_tpu.rpc.serialization import deserialize_array, serialize_array
from petals_tpu.server.server import Server, default_dht_prefix
from tests.utils import make_tiny_llama, steps_booked


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


def _session_plan(cfg, idx, n_steps, prefill_len):
    """Deterministic per-session inputs: a prefill chunk + n_steps decode steps."""
    rng = np.random.RandomState(100 + idx)
    prefill = rng.randn(1, prefill_len, cfg.hidden_size).astype(np.float32) * 0.1
    steps = [
        rng.randn(1, 1, cfg.hidden_size).astype(np.float32) * 0.1
        for _ in range(n_steps)
    ]
    return prefill, steps


async def _drive_session(client, uids, prefill, steps, *, start_barrier=None, delay=0.0):
    """Open an inference stream, run prefill + decode steps, return outputs."""
    stream = await client.open_stream("ptu.inference")
    await stream.send({"uids": uids, "max_length": 64, "batch_size": 1})
    await stream.recv(timeout=60)
    outputs = []
    if start_barrier is not None:
        await start_barrier.wait()
    if delay:
        await asyncio.sleep(delay)
    await stream.send({"tensors": {"hidden": serialize_array(prefill)}})
    reply = await stream.recv(timeout=120)
    outputs.append(deserialize_array(reply["tensors"]["hidden"]))
    for h in steps:
        await stream.send({"tensors": {"hidden": serialize_array(h)}})
        reply = await stream.recv(timeout=120)
        outputs.append(deserialize_array(reply["tensors"]["hidden"]))
    await stream.end()
    return outputs


def test_batched_sessions_token_identical(model_path):
    """N concurrent sessions with batching ON produce the same per-session
    outputs as the same sessions run against an unbatched server — and the
    batcher really coalesced (max_batch > 1)."""

    async def collect(batching, concurrent):
        server, client = await _start_server(model_path, batching=batching)
        try:
            cfg = server.cfg
            prefix = default_dht_prefix(model_path)
            uids = CHAIN_DELIMITER.join(
                make_uid(prefix, i) for i in range(cfg.num_hidden_layers)
            )
            plans = [_session_plan(cfg, i, n_steps=6, prefill_len=3 + i) for i in range(4)]
            barrier = asyncio.Event() if concurrent else None
            tasks = [
                asyncio.create_task(
                    _drive_session(client, uids, p, s, start_barrier=barrier)
                )
                for p, s in plans
            ]
            if concurrent:
                await asyncio.sleep(0.1)
                barrier.set()
            results = await asyncio.gather(*tasks)
            if server.handler.batcher:
                await steps_booked(server.handler.batcher)
            stats = dict(server.handler.batcher.stats) if server.handler.batcher else {}
            return results, stats
        finally:
            await client.close()
            await server.shutdown()

    batched, stats = run(collect(batching=True, concurrent=True))
    unbatched, _ = run(collect(batching=False, concurrent=False))

    assert stats["batched_tokens"] >= 4 * 6  # every decode step went through the pool
    assert stats["max_batch"] >= 2, f"never coalesced: {stats}"
    for s, (got, want) in enumerate(zip(batched, unbatched)):
        for i, (g, w) in enumerate(zip(got, want)):
            np.testing.assert_allclose(
                g, w, atol=2e-5, rtol=0, err_msg=f"session {s} output {i}"
            )


def test_join_leave_mid_batch(model_path):
    """Sessions of different lengths, joining at different times: each one's
    outputs must be independent of its neighbors' lifecycles."""

    async def main():
        server, client = await _start_server(model_path, batching=True)
        try:
            cfg = server.cfg
            prefix = default_dht_prefix(model_path)
            uids = CHAIN_DELIMITER.join(
                make_uid(prefix, i) for i in range(cfg.num_hidden_layers)
            )
            # A: long-lived; B: starts immediately, leaves early; C: joins late
            plan_a = _session_plan(cfg, 0, n_steps=12, prefill_len=4)
            plan_b = _session_plan(cfg, 1, n_steps=3, prefill_len=2)
            plan_c = _session_plan(cfg, 2, n_steps=5, prefill_len=6)
            out_a, out_b, out_c = await asyncio.gather(
                _drive_session(client, uids, *plan_a),
                _drive_session(client, uids, *plan_b),
                _drive_session(client, uids, *plan_c, delay=0.3),
            )
            # ground truth from the backend directly (private cache, no pool)
            backend = server.backend
            for plan, got in ((plan_a, out_a), (plan_b, out_b), (plan_c, out_c)):
                prefill, steps = plan
                kd, vd = backend.cache_descriptors(1, 64, 0, backend.n_blocks)
                kv = (kd.make_zeros(), vd.make_zeros())
                want, kv = backend.inference_step(prefill, kv, 0)
                np.testing.assert_allclose(got[0], np.asarray(want), atol=2e-5, rtol=0)
                pos = prefill.shape[1]
                for i, h in enumerate(steps):
                    want, kv = backend.inference_step(h, kv, pos)
                    pos += 1
                    np.testing.assert_allclose(
                        got[1 + i], np.asarray(want), atol=2e-5, rtol=0,
                        err_msg=f"step {i}",
                    )
        finally:
            await client.close()
            await server.shutdown()

    run(main())


def test_lane_pressure_fallback(model_path):
    """More concurrent sessions than lanes: the extra sessions are still
    served (private-cache fallback or lane hand-off), all token-correct."""

    async def main():
        server, client = await _start_server(
            model_path, batching=True, batch_lanes=2
        )
        try:
            cfg = server.cfg
            prefix = default_dht_prefix(model_path)
            uids = CHAIN_DELIMITER.join(
                make_uid(prefix, i) for i in range(cfg.num_hidden_layers)
            )
            plans = [_session_plan(cfg, i, n_steps=4, prefill_len=2 + i) for i in range(5)]
            barrier = asyncio.Event()
            tasks = [
                asyncio.create_task(
                    _drive_session(client, uids, p, s, start_barrier=barrier)
                )
                for p, s in plans
            ]
            await asyncio.sleep(0.1)
            barrier.set()
            results = await asyncio.gather(*tasks)

            backend = server.backend
            for (prefill, steps), got in zip(plans, results):
                kd, vd = backend.cache_descriptors(1, 64, 0, backend.n_blocks)
                kv = (kd.make_zeros(), vd.make_zeros())
                want, kv = backend.inference_step(prefill, kv, 0)
                np.testing.assert_allclose(got[0], np.asarray(want), atol=2e-5, rtol=0)
                pos = prefill.shape[1]
                for i, h in enumerate(steps):
                    want, kv = backend.inference_step(h, kv, pos)
                    pos += 1
                    np.testing.assert_allclose(got[1 + i], np.asarray(want), atol=2e-5, rtol=0)
            assert server.handler.batcher.stats["batched_tokens"] > 0
        finally:
            await client.close()
            await server.shutdown()

    run(main())


def test_prefill_interleaves_with_decode(model_path):
    """Sarathi-style chunked-prefill interleaving: a long prefill runs as one
    queue task per chunk, so a concurrent session's decode steps complete
    BETWEEN chunks instead of stalling for the whole prefill. Pinned to the
    dense lane pool (page_size=0): paged lanes route prefills through the
    mixed batched step instead (tests/test_mixed_batching.py covers it),
    and this exclusive-chunk path is their dense/TP/lockstep fallback."""

    async def main():
        server, client = await _start_server(
            model_path, batching=True, max_chunk_size_bytes=4096, page_size=0,
        )
        try:
            cfg = server.cfg
            prefix = default_dht_prefix(model_path)
            uids = CHAIN_DELIMITER.join(
                make_uid(prefix, i) for i in range(cfg.num_hidden_layers)
            )
            rng = np.random.RandomState(3)
            long_prefill = rng.randn(1, 96, cfg.hidden_size).astype(np.float32) * 0.1
            b_prefill = rng.randn(1, 2, cfg.hidden_size).astype(np.float32) * 0.1
            b_steps = [
                rng.randn(1, 1, cfg.hidden_size).astype(np.float32) * 0.1
                for _ in range(3)
            ]

            # session B first: prefilled and ready to decode
            stream_b = await client.open_stream("ptu.inference")
            await stream_b.send({"uids": uids, "max_length": 128, "batch_size": 1})
            await stream_b.recv(timeout=60)
            await stream_b.send({"tensors": {"hidden": serialize_array(b_prefill)}})
            await stream_b.recv(timeout=120)

            # session A: the long, many-chunk prefill
            stream_a = await client.open_stream("ptu.inference")
            await stream_a.send({"uids": uids, "max_length": 128, "batch_size": 1})
            await stream_a.recv(timeout=60)

            times = {}

            async def run_a():
                await stream_a.send({"tensors": {"hidden": serialize_array(long_prefill)}})
                reply = await stream_a.recv(timeout=300)
                times["a_done"] = asyncio.get_running_loop().time()
                return deserialize_array(reply["tensors"]["hidden"])

            async def run_b():
                await asyncio.sleep(0.05)  # let A's prefill get going
                outs = []
                for h in b_steps:
                    await stream_b.send({"tensors": {"hidden": serialize_array(h)}})
                    reply = await stream_b.recv(timeout=300)
                    outs.append(deserialize_array(reply["tensors"]["hidden"]))
                times["b_done"] = asyncio.get_running_loop().time()
                return outs

            out_a, outs_b = await asyncio.gather(run_a(), run_b())
            await stream_a.end()
            await stream_b.end()

            stats = server.handler.batcher.stats
            assert stats.get("exclusive_chunks", 0) >= 4, stats
            assert times["b_done"] < times["a_done"], (
                f"decode stalled behind the whole prefill: {times}, {stats}"
            )

            # both sessions token-correct
            backend = server.backend
            kd, vd = backend.cache_descriptors(1, 128, 0, backend.n_blocks)
            kv = (kd.make_zeros(), vd.make_zeros())
            want_a, kv = backend.inference_step(long_prefill, kv, 0)
            np.testing.assert_allclose(out_a, np.asarray(want_a), atol=2e-5, rtol=0)
            kv = (kd.make_zeros(), vd.make_zeros())
            want, kv = backend.inference_step(b_prefill, kv, 0)
            pos = 2
            for i, h in enumerate(b_steps):
                want, kv = backend.inference_step(h, kv, pos)
                pos += 1
                np.testing.assert_allclose(outs_b[i], np.asarray(want), atol=2e-5, rtol=0)
        finally:
            await client.close()
            await server.shutdown()

    run(main())


def test_batched_decode_bloom_alibi(tmp_path_factory):
    """Vector-position batched decode on the ALiBi family (no RoPE): bloom's
    bias depends only on absolute kv positions, but the per-lane causal mask
    must still isolate each lane's history."""
    from tests.utils import make_tiny_bloom

    path = make_tiny_bloom(str(tmp_path_factory.mktemp("models_bloom")))

    async def main():
        server, client = await _start_server(path, batching=True)
        try:
            cfg = server.cfg
            prefix = default_dht_prefix(path)
            uids = CHAIN_DELIMITER.join(
                make_uid(prefix, i) for i in range(cfg.num_hidden_layers)
            )
            plans = [_session_plan(cfg, i, n_steps=5, prefill_len=2 + 2 * i) for i in range(3)]
            barrier = asyncio.Event()
            tasks = [
                asyncio.create_task(
                    _drive_session(client, uids, p, s, start_barrier=barrier)
                )
                for p, s in plans
            ]
            await asyncio.sleep(0.1)
            barrier.set()
            results = await asyncio.gather(*tasks)
            assert server.handler.batcher.stats["max_batch"] >= 2

            backend = server.backend
            for (prefill, steps), got in zip(plans, results):
                kd, vd = backend.cache_descriptors(1, 64, 0, backend.n_blocks)
                kv = (kd.make_zeros(), vd.make_zeros())
                want, kv = backend.inference_step(prefill, kv, 0)
                np.testing.assert_allclose(got[0], np.asarray(want), atol=2e-5, rtol=0)
                pos = prefill.shape[1]
                for i, h in enumerate(steps):
                    want, kv = backend.inference_step(h, kv, pos)
                    pos += 1
                    np.testing.assert_allclose(
                        got[1 + i], np.asarray(want), atol=2e-5, rtol=0
                    )
        finally:
            await client.close()
            await server.shutdown()

    run(main())


@pytest.mark.parametrize("quant", [pytest.param("int8", marks=pytest.mark.slow), "int4"])
def test_batched_decode_quantized(model_path, quant):
    """The batched program's quant-consts path (StackedQuantLinear views over
    scan consts) must match per-session scalar decode bit-for-bit."""
    import jax
    import jax.numpy as jnp

    from petals_tpu.server.backend import TransformerBackend
    from petals_tpu.server.from_pretrained import get_block_config, load_block_params
    from petals_tpu.server.memory_cache import MemoryCache
    from petals_tpu.utils.convert_block import convert_block_params

    family, cfg = get_block_config(model_path)
    per_block = [
        convert_block_params(
            load_block_params(model_path, i, dtype=jnp.float32, family=family, cfg=cfg),
            family.name, quant, fuse=False,
        )
        for i in range(2)
    ]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_block)
    backend = TransformerBackend(
        family, cfg, stacked, first_block=0, n_blocks=2,
        memory_cache=MemoryCache(None), compute_dtype=jnp.float32, use_flash=False,
    )
    rng = np.random.RandomState(0)
    L, MAXLEN = 3, 32
    positions = np.array([4, 0, 9], np.int32)
    hidden = rng.randn(L, 1, cfg.hidden_size).astype(np.float32) * 0.1

    # per-lane ground truth with the same quantized weights
    kd, vd = backend.cache_descriptors(1, MAXLEN, 0, 2)
    want = []
    lanes_kv = []
    for l in range(L):
        kv = (kd.make_zeros(), vd.make_zeros())
        if positions[l]:
            pre = rng.randn(1, positions[l], cfg.hidden_size).astype(np.float32) * 0.1
            _, kv = backend.inference_step(pre, kv, 0)
        # host copies BEFORE the decode step donates the buffers
        lanes_kv.append((np.asarray(kv[0]), np.asarray(kv[1])))
        out, _ = backend.inference_step(hidden[l : l + 1], kv, int(positions[l]))
        want.append(np.asarray(out))

    # pool assembled from the same per-lane caches
    k_pool = jnp.asarray(np.concatenate([kv[0] for kv in lanes_kv], axis=1))
    v_pool = jnp.asarray(np.concatenate([kv[1] for kv in lanes_kv], axis=1))
    out, _ = backend.batched_decode_step(hidden, (k_pool, v_pool), positions)
    for l in range(L):
        np.testing.assert_allclose(
            np.asarray(out)[l : l + 1], want[l], atol=1e-5, rtol=0,
            err_msg=f"lane {l} ({quant})",
        )


def test_lane_lifecycle_races(model_path):
    """Two allocator races: (a) a waiter cancelled right after release_lane
    handed it a lane must put the lane back (no capacity leak); (b) releasing
    a lane purges its queued-but-unflushed step so the next tenant's cache
    can't be corrupted by a stale write."""

    async def main():
        server, client = await _start_server(model_path, batching=True, batch_lanes=2)
        try:
            batcher = server.handler.batcher
            await batcher.ensure_open()
            lanes = [await batcher.acquire_lane() for _ in range(2)]

            # (a) waiter resolved then cancelled before resuming. On py>=3.12
            # wait_for propagates the cancel and acquire_lane must put the
            # lane back itself; py<3.12 wait_for swallows a cancel that lands
            # after the future resolved and hands the lane over — then WE hold
            # it and must release. Either way the pool must not shrink.
            waiter = asyncio.create_task(batcher.acquire_lane(timeout=5))
            await asyncio.sleep(0)  # waiter is now parked in _lane_waiters
            batcher.release_lane(lanes[0])  # resolves the waiter's future
            waiter.cancel()
            try:
                handed_over = await waiter
            except asyncio.CancelledError:
                handed_over = None
            if handed_over is not None:
                batcher.release_lane(handed_over)
            assert len(batcher._free_lanes) == 1, "lane leaked on cancel race"

            # (b) stale pending step purged on release
            lane = lanes[1]
            fut = asyncio.get_running_loop().create_future()
            batcher._pending.append((lane, np.zeros((1, 1, 4)), 3, fut))
            batcher.release_lane(lane)
            assert fut.done() and fut.exception() is not None
            assert all(e[0] != lane for e in batcher._pending)
        finally:
            await client.close()
            await server.shutdown()

    run(main())


def test_pool_reset_after_consumed_buffers(model_path):
    """A batched step that fails AFTER consuming the donated pool buffers
    must reset the pool and invalidate every outstanding lane — tenants get
    loud errors (client failover re-opens), never silent zeroed-KV decode."""

    async def main():
        server, client = await _start_server(model_path, batching=True)
        try:
            batcher = server.handler.batcher
            await batcher.ensure_open()
            lane = await batcher.acquire_lane()
            cfg = server.cfg

            # simulate a device failure that consumed the donated buffers
            def exploding_step(*args, **kwargs):
                k_pool, v_pool = batcher._buffers()
                k_pool.delete()
                v_pool.delete()
                raise RuntimeError("simulated device failure mid-donation")

            batcher.backend.paged_decode_step = batcher.backend.batched_decode_step = exploding_step
            h = np.zeros((1, 1, cfg.hidden_size), np.float32)
            with pytest.raises(RuntimeError, match="simulated device failure"):
                await batcher.step(lane, h, 0)
            del batcher.backend.paged_decode_step, batcher.backend.batched_decode_step

            # the outstanding lane is invalidated...
            from petals_tpu.server.memory_cache import AllocationFailed

            with pytest.raises(AllocationFailed, match="pool was reset"):
                await batcher.step(lane, h, 1)
            # ...including entries that were already PENDING when the reset
            # landed (they must never run against the rematerialized pool)
            fut = asyncio.get_running_loop().create_future()
            batcher._pending.append((lane, h, 1, fut, batcher._generation - 1))
            await batcher._flush_loop()
            assert isinstance(fut.exception(), AllocationFailed)
            batcher.release_lane(lane)

            # ...but a NEW session works on the fresh pool, token-correct
            prefix = default_dht_prefix(model_path)
            uids = CHAIN_DELIMITER.join(
                make_uid(prefix, i) for i in range(cfg.num_hidden_layers)
            )
            prefill, steps = _session_plan(cfg, 0, n_steps=3, prefill_len=4)
            got = await _drive_session(client, uids, prefill, steps)
            backend = server.backend
            kd, vd = backend.cache_descriptors(1, 64, 0, backend.n_blocks)
            kv = (kd.make_zeros(), vd.make_zeros())
            want, kv = backend.inference_step(prefill, kv, 0)
            np.testing.assert_allclose(got[0], np.asarray(want), atol=2e-5, rtol=0)
            pos = 4
            for i, hstep in enumerate(steps):
                want, kv = backend.inference_step(hstep, kv, pos)
                pos += 1
                np.testing.assert_allclose(got[1 + i], np.asarray(want), atol=2e-5, rtol=0)
        finally:
            await client.close()
            await server.shutdown()

    run(main())


def test_concurrent_server_gen_lanes(model_path):
    """>=3 concurrent server-gen sessions advance through the SHARED lane
    pool — each token is one compiled program over every generating lane
    (plus any ordinary decode traffic) with a per-lane position vector —
    token-identical to HF, with per-lane stop/length bookkeeping (each
    session asks for a different token count and leaves the pool alone)."""
    import jax.numpy as jnp

    from petals_tpu.client.from_pretrained import load_client_params
    from petals_tpu.server.from_pretrained import get_block_config
    from tests.test_full_model import _hf_greedy

    family, cfg = get_block_config(model_path)
    client_params = load_client_params(model_path, dtype=jnp.float32)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, 100, (1, 3 + 3 * i)).astype(np.int64) for i in range(3)]
    gen_lens = [8, 16, 32]  # different depths AND different stop steps
    expected = [_hf_greedy(model_path, p, n) for p, n in zip(prompts, gen_lens)]

    async def main():
        server, client = await _start_server(model_path, batching=True)
        try:
            prefix = default_dht_prefix(model_path)
            uids = CHAIN_DELIMITER.join(
                make_uid(prefix, i) for i in range(cfg.num_hidden_layers)
            )
            barrier = asyncio.Event()

            async def drive(prompt, n):
                emb = np.asarray(
                    family.client_embed(client_params, jnp.asarray(prompt), cfg),
                    np.float32,
                )
                stream = await client.open_stream("ptu.inference")
                await stream.send({"uids": uids, "max_length": 64, "batch_size": 1})
                await stream.recv(timeout=60)
                await barrier.wait()
                await stream.send({
                    "tensors": {"hidden": serialize_array(emb)}, "gen_tokens": n,
                })
                reply = await stream.recv(timeout=300)
                await stream.end()
                return reply["tokens"]

            tasks = [
                asyncio.create_task(drive(p, n))
                for p, n in zip(prompts, gen_lens)
            ]
            await asyncio.sleep(0.1)
            barrier.set()
            results = await asyncio.gather(*tasks)
            stats = dict(server.handler.batcher.stats)
            return results, stats
        finally:
            await client.close()
            await server.shutdown()

    results, stats = run(main())
    for toks, p, n, want in zip(results, prompts, gen_lens, expected):
        np.testing.assert_array_equal(
            np.asarray(toks), want[0, p.shape[1]:],
            err_msg=f"lane with prefill {p.shape[1]}, gen {n}",
        )
    assert stats["gen_steps"] > 0, stats
    assert stats["max_gen_lanes"] >= 3, f"gen lanes never coalesced: {stats}"
    # n_tokens - 1 pooled steps per lane (t0 comes from the bootstrap sample)
    assert stats["gen_lane_tokens"] >= sum(n - 1 for n in gen_lens), stats


def test_pooled_server_gen_sampling_matches_private_path(model_path):
    """A SAMPLING server-gen session on the pooled lanes — running alongside
    an ordinary decode session, so the combined gen+decode program is what
    actually executes — must produce the same tokens as the private-path
    compiled scan (backend.generate_tokens) under the same seed, and the
    decode neighbor must be unaffected."""
    import jax.numpy as jnp

    from petals_tpu.client.from_pretrained import load_client_params
    from petals_tpu.rpc.protocol import validate_gen_sampling
    from petals_tpu.server.from_pretrained import get_block_config

    family, cfg = get_block_config(model_path)
    client_params = load_client_params(model_path, dtype=jnp.float32)
    rng = np.random.RandomState(5)
    prompt = rng.randint(0, 100, (1, 6)).astype(np.int64)
    gen_n = 16
    sampling = {
        "do_sample": True, "temperature": 0.8, "top_k": 10, "top_p": 0.9,
        "repetition_penalty": 1.3, "seed": 42, "offset": 0,
        "context": [int(t) for t in prompt[0]],
    }

    async def main():
        server, client = await _start_server(model_path, batching=True)
        try:
            prefix = default_dht_prefix(model_path)
            uids = CHAIN_DELIMITER.join(
                make_uid(prefix, i) for i in range(cfg.num_hidden_layers)
            )
            emb = np.asarray(
                family.client_embed(client_params, jnp.asarray(prompt), cfg),
                np.float32,
            )
            barrier = asyncio.Event()

            async def drive_gen():
                stream = await client.open_stream("ptu.inference")
                await stream.send({"uids": uids, "max_length": 64, "batch_size": 1})
                await stream.recv(timeout=60)
                await barrier.wait()
                await stream.send({
                    "tensors": {"hidden": serialize_array(emb)},
                    "gen_tokens": gen_n, "gen_sampling": sampling,
                })
                reply = await stream.recv(timeout=300)
                await stream.end()
                return reply["tokens"]

            decode_plan = _session_plan(cfg, 1, n_steps=8, prefill_len=3)
            gen_task = asyncio.create_task(drive_gen())
            dec_task = asyncio.create_task(
                _drive_session(client, uids, *decode_plan, start_barrier=barrier)
            )
            await asyncio.sleep(0.1)
            barrier.set()
            toks, decode_out = await asyncio.gather(gen_task, dec_task)
            stats = dict(server.handler.batcher.stats)

            # ground truth AFTER the pooled traffic drained: the private-path
            # scan from the same prefill and the same validated sampling dict
            backend = server.backend
            kd, vd = backend.cache_descriptors(1, 64, 0, backend.n_blocks)
            kv = (kd.make_zeros(), vd.make_zeros())
            out, kv = backend.inference_step(emb, kv, 0)
            want_toks, _ = backend.generate_tokens(
                server.handler.server_gen_params, np.asarray(out[:, -1:]), kv,
                prompt.shape[1], gen_n, sampling=validate_gen_sampling(sampling),
            )
            want_decode = []
            kv = (kd.make_zeros(), vd.make_zeros())
            prefill, steps = decode_plan
            want, kv = backend.inference_step(prefill, kv, 0)
            want_decode.append(np.asarray(want))
            pos = prefill.shape[1]
            for h in steps:
                want, kv = backend.inference_step(h, kv, pos)
                pos += 1
                want_decode.append(np.asarray(want))
            return toks, decode_out, np.asarray(want_toks), want_decode, stats
        finally:
            await client.close()
            await server.shutdown()

    toks, decode_out, want_toks, want_decode, stats = run(main())
    np.testing.assert_array_equal(np.asarray(toks), want_toks[0])
    for i, (got, want) in enumerate(zip(decode_out, want_decode)):
        np.testing.assert_allclose(
            got, want, atol=2e-5, rtol=0, err_msg=f"decode neighbor output {i}"
        )
    assert stats["gen_steps"] > 0, stats


def test_pooled_session_rollback(model_path):
    """start_from_position (speculative-decoding rollback) on a pooled
    session: later tokens must be recomputed from the rewound cache."""

    async def main():
        server, client = await _start_server(model_path, batching=True)
        try:
            cfg = server.cfg
            prefix = default_dht_prefix(model_path)
            uids = CHAIN_DELIMITER.join(
                make_uid(prefix, i) for i in range(cfg.num_hidden_layers)
            )
            rng = np.random.RandomState(7)
            prefill = rng.randn(1, 4, cfg.hidden_size).astype(np.float32) * 0.1
            h5 = rng.randn(1, 1, cfg.hidden_size).astype(np.float32) * 0.1
            h5_alt = rng.randn(1, 1, cfg.hidden_size).astype(np.float32) * 0.1

            stream = await client.open_stream("ptu.inference")
            await stream.send({"uids": uids, "max_length": 32, "batch_size": 1})
            await stream.recv(timeout=60)
            await stream.send({"tensors": {"hidden": serialize_array(prefill)}})
            await stream.recv(timeout=120)
            # a step at position 4, then roll back and redo with different input
            await stream.send({"tensors": {"hidden": serialize_array(h5)}})
            await stream.recv(timeout=120)
            await stream.send({
                "tensors": {"hidden": serialize_array(h5_alt)},
                "start_from_position": 4,
            })
            reply = await stream.recv(timeout=120)
            got = deserialize_array(reply["tensors"]["hidden"])
            assert reply["position"] == 5
            await stream.end()

            backend = server.backend
            kd, vd = backend.cache_descriptors(1, 32, 0, backend.n_blocks)
            kv = (kd.make_zeros(), vd.make_zeros())
            _, kv = backend.inference_step(prefill, kv, 0)
            want, kv = backend.inference_step(h5_alt, kv, 4)
            np.testing.assert_allclose(got, np.asarray(want), atol=2e-5, rtol=0)
        finally:
            await client.close()
            await server.shutdown()

    run(main())
