"""Unified continuous batching (server/batching.py mixed step): a paged
lane's prefill chunks ride the SAME compiled program as the decode lanes'
tokens — one jitted mixed prefill+decode step over the page pool, token-
identical to the exclusive-chunk path and to a single full-length prefill,
with decode traffic never stalling behind a long prefill.

Beats the reference, whose server runs every prefill as its own exclusive
task pool step (reference src/petals/server/task_pool.py:35-36)."""

import asyncio
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from petals_tpu.data_structures import CHAIN_DELIMITER, make_uid
from petals_tpu.rpc import RpcClient
from petals_tpu.rpc.serialization import deserialize_array, serialize_array
from petals_tpu.server.batching import DecodeBatcher
from petals_tpu.server.memory_cache import AllocationFailed, MemoryCache
from petals_tpu.server.server import Server, default_dht_prefix
from petals_tpu.server.task_queue import PriorityTaskQueue
from tests.utils import make_tiny_llama, steps_booked

pytestmark = pytest.mark.mixed


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


def _tiny_backend(model_path):
    from petals_tpu.server.backend import TransformerBackend
    from petals_tpu.server.from_pretrained import get_block_config, load_block_params

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


# ------------------------------------------------------ mixed-step parity (direct)


def test_paged_mixed_step_parity_direct(model_path):
    """Direct backend check of the mixed prefill+decode program on a fixed
    seed: decode lanes must match per-lane scalar decode, the prefill chunk's
    output must match a standalone prefill, and the chunk's KV must land in
    the right pages — on BOTH the identity (contiguous fast path) and a
    permuted/oversubscribed table layout, including a continuation chunk at
    a non-zero position."""
    from petals_tpu.ops.paged_attention import identity_tables

    backend, cfg = _tiny_backend(model_path)
    rng = np.random.RandomState(0)
    L, PS, MAX_PAGES = 3, 8, 6
    MAXLEN = PS * MAX_PAGES
    positions = np.array([5, MAXLEN, 17], np.int32)  # lane 1 idle: it prefills
    hidden = rng.randn(L, 1, cfg.hidden_size).astype(np.float32) * 0.1
    chunk_lane = 1
    full_prefill = rng.randn(1, 20, cfg.hidden_size).astype(np.float32) * 0.1
    split = 13  # chunk 1: [0, 13), chunk 2: [13, 20) — a continuation

    # per-lane ground truth + each decode lane's dense cache content
    kd, vd = backend.cache_descriptors(1, MAXLEN, 0, 2)
    want, lanes_kv = {}, {}
    for l in (0, 2):
        kv = (kd.make_zeros(), vd.make_zeros())
        pre = rng.randn(1, positions[l], cfg.hidden_size).astype(np.float32) * 0.1
        _, kv = backend.inference_step(pre, kv, 0)
        lanes_kv[l] = (np.asarray(kv[0]), np.asarray(kv[1]))
        out, _ = backend.inference_step(hidden[l : l + 1], kv, int(positions[l]))
        want[l] = np.asarray(out)
    kv = (kd.make_zeros(), vd.make_zeros())
    want_chunk, kv = backend.inference_step(full_prefill, kv, 0)
    want_chunk = np.asarray(want_chunk)
    chunk_kv = (np.asarray(kv[0]), np.asarray(kv[1]))

    def page_pool(tables, n_pages):
        """Scatter the decode lanes' dense caches into a pool per ``tables``
        (the prefill lane starts empty — the mixed step writes it)."""
        n_blocks, _, _, hkv, hd = lanes_kv[0][0].shape
        kp = np.zeros((n_blocks, n_pages, PS, hkv, hd), np.float32)
        vp = np.zeros_like(kp)
        for l, (kl, vl) in lanes_kv.items():
            for s in range(MAX_PAGES):
                page = tables[l, s]
                if page < 0:
                    continue
                kp[:, page] = kl[:, 0, s * PS : (s + 1) * PS]
                vp[:, page] = vl[:, 0, s * PS : (s + 1) * PS]
        return jnp.asarray(kp), jnp.asarray(vp)

    def check(tables, n_pages, layout):
        kp, vp = page_pool(tables, n_pages)
        out1, c1, (kp, vp) = backend.paged_mixed_step(
            hidden, (kp, vp), positions, tables,
            full_prefill[:, :split], chunk_lane, 0,
        )
        # decode lanes rode the mixed step untouched by the prefill half
        for l in (0, 2):
            np.testing.assert_allclose(
                np.asarray(out1)[l : l + 1], want[l], atol=2e-5, rtol=0,
                err_msg=f"decode lane {l} ({layout})",
            )
        # continuation chunk: scalar position 13, attends to chunk 1's pages
        idle = np.full((L, 1, cfg.hidden_size), 0, np.float32)
        sentinel = np.array([MAXLEN, MAXLEN, MAXLEN], np.int32)
        _, c2, (kp, vp) = backend.paged_mixed_step(
            idle, (kp, vp), sentinel, tables,
            full_prefill[:, split:], chunk_lane, split,
        )
        got_chunk = np.concatenate([np.asarray(c1), np.asarray(c2)], axis=1)
        np.testing.assert_allclose(
            got_chunk, want_chunk, atol=2e-5, rtol=0,
            err_msg=f"prefill chunk output ({layout})",
        )
        # the chunk's KV landed in the prefill lane's pages, byte-correct
        kp, vp = np.asarray(kp), np.asarray(vp)
        for t in range(20):
            page = tables[chunk_lane, t // PS]
            np.testing.assert_allclose(
                kp[:, page, t % PS], chunk_kv[0][:, 0, t], atol=1e-5, rtol=0,
                err_msg=f"k row {t} ({layout})",
            )
            np.testing.assert_allclose(
                vp[:, page, t % PS], chunk_kv[1][:, 0, t], atol=1e-5, rtol=0,
                err_msg=f"v row {t} ({layout})",
            )

    # (a) identity layout: the contiguous fast path handles the decode half
    check(np.asarray(identity_tables(L, MAX_PAGES)), L * MAX_PAGES, "identity")

    # (b) permuted, oversubscribed pool: the real gather/scatter path
    n_pages = 20
    perm = np.full((L, MAX_PAGES), -1, np.int32)
    free = list(rng.permutation(n_pages))
    need = {0: positions[0] + 1, 1: 20, 2: positions[2] + 1}
    for l in range(L):
        for s in range(-(-int(need[l]) // PS)):
            perm[l, s] = free.pop()
    check(perm, n_pages, "permuted")


def test_prefill_lane_matches_exclusive_and_full(model_path):
    """The SAME prefill run three ways — through the mixed step
    (prefill_lane), through the exclusive-chunk path, and as one full-length
    inference_step — must agree, and decode steps from the resulting caches
    must agree too."""
    backend, cfg = _tiny_backend(model_path)
    backend.max_chunk_size_bytes = 4096  # force several exclusive chunks

    async def main():
        queue = PriorityTaskQueue()
        queue.start()
        batcher = DecodeBatcher(
            backend, backend.memory_cache, queue, n_lanes=2, max_length=128,
            page_size=16, prefill_token_budget=32,
        )
        rng = np.random.RandomState(7)
        total = 50  # not page-aligned: exercises the partial-tail chunk
        prefill = rng.randn(1, total, cfg.hidden_size).astype(np.float32) * 0.1
        steps = [
            rng.randn(1, 1, cfg.hidden_size).astype(np.float32) * 0.1
            for _ in range(3)
        ]
        try:
            lane_a = await batcher.acquire_lane()
            lane_b = await batcher.acquire_lane()

            # (1) mixed-step path
            out_mixed = await batcher.prefill_lane(lane_a, prefill, 0)

            # (2) exclusive-chunk path, chunked exactly as the handler does
            plan = backend.chunk_plan(
                1, total, kv_buf_len=128, page_size=batcher.page_size
            )
            assert len(plan) > 1, plan  # the comparison needs a real chunk split
            chunk_fns, off = [], 0
            for clen in plan:
                def run_chunk(kv, temp, chunk=prefill[:, off : off + clen], pos=off):
                    out, kv2 = backend.inference_step(chunk, kv, pos, handles=temp)
                    return np.asarray(out), kv2
                chunk_fns.append(run_chunk)
                off += clen
            outs = await batcher.run_exclusive_chunks(
                lane_b, chunk_fns, write_range=(0, total)
            )
            out_excl = np.concatenate(outs, axis=1)

            # (3) one full-length dense prefill
            kd, vd = backend.cache_descriptors(1, 128, 0, 2)
            kv = (kd.make_zeros(), vd.make_zeros())
            want, kv = backend.inference_step(prefill, kv, 0)
            want = np.asarray(want)

            np.testing.assert_allclose(np.asarray(out_mixed), want, atol=2e-5, rtol=0)
            np.testing.assert_allclose(out_excl, want, atol=2e-5, rtol=0)

            # decode from all three caches stays in agreement
            pos = total
            for i, h in enumerate(steps):
                got_a = await batcher.step(lane_a, h, pos)
                got_b = await batcher.step(lane_b, h, pos)
                want_s, kv = backend.inference_step(h, kv, pos)
                pos += 1
                np.testing.assert_allclose(
                    got_a, np.asarray(want_s), atol=2e-5, rtol=0,
                    err_msg=f"mixed-path decode step {i}",
                )
                np.testing.assert_allclose(
                    got_b, np.asarray(want_s), atol=2e-5, rtol=0,
                    err_msg=f"exclusive-path decode step {i}",
                )

            stats = dict(batcher.stats)
            assert stats["mixed_steps"] >= 2, stats
            assert stats["prefill_tokens"] == total, stats
            assert stats["max_prefill_tokens_per_step"] <= 32, stats
            assert stats["exclusive_chunks"] == len(plan), stats
        finally:
            await batcher.close()
            queue.shutdown()

    run(main())


# ------------------------------------------- exclusive-chunk failure path (direct)


def test_exclusive_chunks_failed_checkin_no_leak_no_deadlock(model_path):
    """A lane invalidated mid-prefill (pool reset racing the chunk queue)
    must abort the remaining chunks with AllocationFailed, release the temp
    buffer instead of leaking it, and leave the lane pool serviceable — a
    blocked lane waiter is handed the lane and can run a fresh prefill."""
    backend, cfg = _tiny_backend(model_path)

    async def main():
        queue = PriorityTaskQueue()
        queue.start()
        batcher = DecodeBatcher(
            backend, backend.memory_cache, queue, n_lanes=1, max_length=64,
            page_size=16,
        )
        try:
            lane = await batcher.acquire_lane()
            released, ran = [], []
            orig_release = batcher._release_temp
            batcher._release_temp = lambda t: (released.append(t), orig_release(t))

            def chunk_then_invalidate(kv, temp):
                ran.append("c1")
                # simulate a pool reset landing between chunks: this lane's
                # generation is no longer current
                batcher._lane_generation.pop(lane, None)
                return np.zeros((1, 2, cfg.hidden_size), np.float32), kv

            def never_runs(kv, temp):
                ran.append("c2")
                return np.zeros((1, 2, cfg.hidden_size), np.float32), kv

            # a second session queued on the single lane: must NOT deadlock
            waiter = asyncio.create_task(batcher.acquire_lane(timeout=30))
            await asyncio.sleep(0)

            with pytest.raises(AllocationFailed):
                await batcher.run_exclusive_chunks(
                    lane, [chunk_then_invalidate, never_runs, never_runs],
                    write_range=(0, 4),
                )

            assert ran == ["c1"], ran  # later chunks never ran on a stale lane
            # the failed check-in released the temp buffer exactly once
            assert released == [None], released  # single-host temp is None

            batcher.release_lane(lane)
            lane2 = await asyncio.wait_for(waiter, 10)

            # the pool is fully serviceable for the next tenant
            rng = np.random.RandomState(11)
            prefill = rng.randn(1, 5, cfg.hidden_size).astype(np.float32) * 0.1
            out = await batcher.prefill_lane(lane2, prefill, 0)
            kd, vd = backend.cache_descriptors(1, 64, 0, 2)
            kv = (kd.make_zeros(), vd.make_zeros())
            want, _ = backend.inference_step(prefill, kv, 0)
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(want), atol=2e-5, rtol=0
            )
        finally:
            await batcher.close()
            queue.shutdown()

    run(main())


# ----------------------------------------------------------------- end to end


def test_mixed_prefill_interleaves_with_decode(model_path):
    """A long prefill on a paged lane rides the mixed step: a concurrent
    session's decode steps complete BETWEEN mixed ticks (never stalling for
    the whole prefill), the prefill never falls back to exclusive chunks,
    and both sessions stay token-identical to unbatched serving."""

    async def main():
        server, client = await _start_server(
            model_path, batching=True, batch_lanes=2, batch_max_length=128, page_size=16, n_pages=16,
            prefill_token_budget=16,
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
                for _ in range(40)
            ]

            # session B first: prefilled and ready to decode
            stream_b = await client.open_stream("ptu.inference")
            await stream_b.send({"uids": uids, "max_length": 128, "batch_size": 1})
            await stream_b.recv(timeout=60)
            await stream_b.send({"tensors": {"hidden": serialize_array(b_prefill)}})
            await stream_b.recv(timeout=120)

            # session A: the long prefill — 96 tokens / 16-token budget = 6 ticks
            stream_a = await client.open_stream("ptu.inference")
            await stream_a.send({"uids": uids, "max_length": 128, "batch_size": 1})
            await stream_a.recv(timeout=60)

            times = {}

            async def run_a():
                await stream_a.send(
                    {"tensors": {"hidden": serialize_array(long_prefill)}}
                )
                reply = await stream_a.recv(timeout=300)
                times["a_done"] = asyncio.get_running_loop().time()
                return deserialize_array(reply["tensors"]["hidden"])

            async def run_b():
                # decode continuously while A's prefill is in flight: steps
                # completing DURING the prefill window prove decode rides the
                # mixed ticks instead of stalling behind the whole prefill
                await asyncio.sleep(0.05)  # let A's prefill get going
                outs, step_times = [], []
                loop = asyncio.get_running_loop()
                while "a_done" not in times and len(outs) < len(b_steps):
                    h = b_steps[len(outs)]
                    await stream_b.send({"tensors": {"hidden": serialize_array(h)}})
                    reply = await stream_b.recv(timeout=300)
                    outs.append(deserialize_array(reply["tensors"]["hidden"]))
                    step_times.append(loop.time())
                return outs, step_times

            out_a, (outs_b, step_times) = await asyncio.gather(run_a(), run_b())
            await stream_a.end()
            await stream_b.end()

            stats = dict(server.handler.batcher.stats)
            assert stats["mixed_steps"] >= 6, stats
            assert stats["prefill_tokens"] >= 96 + 2, stats
            assert stats["max_prefill_tokens_per_step"] <= 16, stats
            # routed through the batcher, NOT the exclusive fallback
            assert stats["exclusive_chunks"] == 0, stats
            during = sum(1 for t in step_times if t < times["a_done"])
            assert during >= 1, (
                f"decode stalled behind the whole prefill: "
                f"{during}/{len(step_times)} steps during prefill, {stats}"
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
            for i, got in enumerate(outs_b):
                want, kv = backend.inference_step(b_steps[i], kv, pos)
                pos += 1
                np.testing.assert_allclose(got, np.asarray(want), atol=2e-5, rtol=0)
        finally:
            await client.close()
            await server.shutdown()

    run(main())


def test_server_gen_after_mixed_prefill_greedy_and_sampling(model_path):
    """Server-side generation whose PROMPT rode the mixed prefill step:
    a greedy session must be token-identical to HF, and a sampling session
    (fixed seed) must match the private-path compiled scan — proving the
    mixed step's KV is byte-equivalent for both decode flavors."""
    from petals_tpu.client.from_pretrained import load_client_params
    from petals_tpu.rpc.protocol import validate_gen_sampling
    from petals_tpu.server.from_pretrained import get_block_config
    from tests.test_full_model import _hf_greedy

    family, cfg = get_block_config(model_path)
    client_params = load_client_params(model_path, dtype=jnp.float32)
    rng = np.random.RandomState(5)
    greedy_prompt = rng.randint(0, 100, (1, 24)).astype(np.int64)
    greedy_n = 8
    want_greedy = _hf_greedy(model_path, greedy_prompt, greedy_n)
    samp_prompt = rng.randint(0, 100, (1, 20)).astype(np.int64)
    samp_n = 8
    sampling = {
        "do_sample": True, "temperature": 0.8, "top_k": 10, "top_p": 0.9,
        "repetition_penalty": 1.3, "seed": 42, "offset": 0,
        "context": [int(t) for t in samp_prompt[0]],
    }

    async def main():
        server, client = await _start_server(
            model_path, batching=True, batch_lanes=2, batch_max_length=64, page_size=8, n_pages=16,
            prefill_token_budget=8,
        )
        try:
            prefix = default_dht_prefix(model_path)
            uids = CHAIN_DELIMITER.join(
                make_uid(prefix, i) for i in range(cfg.num_hidden_layers)
            )
            barrier = asyncio.Event()

            async def drive(prompt, n, samp):
                emb = np.asarray(
                    family.client_embed(client_params, jnp.asarray(prompt), cfg),
                    np.float32,
                )
                stream = await client.open_stream("ptu.inference")
                await stream.send({"uids": uids, "max_length": 64, "batch_size": 1})
                await stream.recv(timeout=60)
                await barrier.wait()
                msg = {"tensors": {"hidden": serialize_array(emb)}, "gen_tokens": n}
                if samp is not None:
                    msg["gen_sampling"] = samp
                await stream.send(msg)
                reply = await stream.recv(timeout=300)
                await stream.end()
                return reply["tokens"]

            g_task = asyncio.create_task(drive(greedy_prompt, greedy_n, None))
            s_task = asyncio.create_task(drive(samp_prompt, samp_n, sampling))
            await asyncio.sleep(0.1)
            barrier.set()
            g_toks, s_toks = await asyncio.gather(g_task, s_task)
            stats = dict(server.handler.batcher.stats)

            # sampling ground truth: private-path scan from the same prefill
            backend = server.backend
            kd, vd = backend.cache_descriptors(1, 64, 0, backend.n_blocks)
            kv = (kd.make_zeros(), vd.make_zeros())
            emb = np.asarray(
                family.client_embed(client_params, jnp.asarray(samp_prompt), cfg),
                np.float32,
            )
            out, kv = backend.inference_step(emb, kv, 0)
            want_samp, _ = backend.generate_tokens(
                server.handler.server_gen_params, np.asarray(out[:, -1:]), kv,
                samp_prompt.shape[1], samp_n,
                sampling=validate_gen_sampling(sampling),
            )
            return g_toks, s_toks, np.asarray(want_samp), stats
        finally:
            await client.close()
            await server.shutdown()

    g_toks, s_toks, want_samp, stats = run(main())
    np.testing.assert_array_equal(
        np.asarray(g_toks), want_greedy[0, greedy_prompt.shape[1]:]
    )
    np.testing.assert_array_equal(np.asarray(s_toks), want_samp[0])
    # both prompts rode the mixed step (24 and 20 tokens / 8-token budget)
    assert stats["mixed_steps"] >= 5, stats
    assert stats["prefill_tokens"] >= 44, stats
    assert stats["exclusive_chunks"] == 0, stats
    assert stats["gen_steps"] > 0, stats


# ------------------------------------------------ host phases of a step body

PHASE_KEYS = ("assemble_s", "dispatch_s", "wait_s", "post_s")
PHASE_NAMES = ["ptu.step.assemble", "ptu.step.dispatch", "ptu.step.wait", "ptu.step.post"]


def _phase_batcher(backend, queue, **kwargs):
    return DecodeBatcher(backend, backend.memory_cache, queue, n_lanes=2, max_length=64, **kwargs)


def _hidden(cfg, seed, n=1):
    return np.random.RandomState(seed).randn(1, n, cfg.hidden_size).astype(np.float32) * 0.1


def test_phase_counters_sum_to_the_step_walls(model_path):
    """After a handful of decode steps and one mixed step the four phase
    counters are positive and together make up the walls the batcher already
    reports as ``step_meta.compute_s`` (which start after the generation
    guard and end before the telemetry, so they are the smaller by a little)."""
    backend, cfg = _tiny_backend(model_path)

    async def main():
        queue = PriorityTaskQueue()
        queue.start()
        batcher = _phase_batcher(backend, queue, page_size=16)
        try:
            a, b = await batcher.acquire_lane(), await batcher.acquire_lane()
            await batcher.prefill_lane(a, _hidden(cfg, 1, 5), 0)  # compiles the mixed step
            await batcher.step(a, _hidden(cfg, 2), 5)  # ...and the decode step
            await steps_booked(batcher)
            before = dict(batcher.stats)
            walls = 0.0
            for i in range(6):
                await batcher.step(a, _hidden(cfg, 10 + i), 6 + i)
                walls += batcher.pop_step_timing(a)["compute_s"]
                await steps_booked(batcher)  # or the next launch could come before this step's post
            behind_the_walls = batcher.stats["post_s"] - before["post_s"]
            await batcher.prefill_lane(b, _hidden(cfg, 3, 5), 0)
            walls += batcher.pop_step_timing(b)["compute_s"]
            delta = {k: batcher.stats[k] - before[k] for k in batcher.stats}
            assert delta["batched_steps"] == 7 and delta["mixed_steps"] == 1
            assert all(delta[k] > 0 for k in PHASE_KEYS), delta
            # a launched step's wall ends with its rows on the host, where its wait ends too (the readback's one
            # reading): the six decode steps' post is booked behind their walls, the mixed step's is inside its own
            phases = sum(delta[k] for k in PHASE_KEYS) - behind_the_walls
            # measured here: 12.60 ms of phases on 12.52 ms of walls
            assert 0.98 * walls <= phases <= 1.1 * walls + 2e-3, (phases, walls, delta)
        finally:
            await batcher.close()
            queue.shutdown()

    run(main())


def test_turnaround_counts_only_inside_one_flush_task(model_path):
    """``turnaround_s`` is the hand-off with work pending: it stays put when
    every step starts a fresh flush task (the batcher had nothing to run in
    between), it grows when one lane's step follows another's under one task,
    and the time the batcher spends gathering between two such steps (waiting
    for a lane on its way back, ``_gather``) goes to ``gather_wait_s`` and
    not to it."""
    backend, cfg = _tiny_backend(model_path)

    async def main():
        queue = PriorityTaskQueue()
        queue.start()
        batcher = _phase_batcher(backend, queue, page_size=16)
        try:
            a, b = await batcher.acquire_lane(), await batcher.acquire_lane()
            await batcher.step(a, _hidden(cfg, 1), 0)
            await batcher.step(b, _hidden(cfg, 2), 0)
            spawns, before = batcher._flush_spawns, batcher.stats["turnaround_s"]
            for i in range(4):  # one lane alone: its reply ends the flush task every time
                await batcher.step(a, _hidden(cfg, 10 + i), 1 + i)
            assert batcher._flush_spawns == spawns + 4
            assert batcher.stats["turnaround_s"] == before

            fast, in_step, queued_behind, step_s = backend.paged_decode_step, threading.Event(), threading.Event(), 0.0

            def slow(*args, **kwargs):  # held until the other lane's step has arrived, however busy the host
                in_step.set()
                time.sleep(step_s)
                assert queued_behind.wait(60)
                return fast(*args, **kwargs)

            backend.paged_decode_step = slow

            async def step_behind_the_one_in_flight(ahead_step, lane_behind, hidden, pos_behind):
                """Start ``ahead_step``, and once the device holds it, lane_behind's: the step in flight is let go
                only when that request is in ``_pending``, so the flush task that runs it finds it there."""
                in_step.clear()
                queued_behind.clear()
                ahead = asyncio.create_task(ahead_step)
                await asyncio.get_running_loop().run_in_executor(None, in_step.wait)
                behind = asyncio.create_task(batcher.step(lane_behind, hidden, pos_behind))
                async with asyncio.timeout(60):
                    while not any(entry[0] == lane_behind for entry in batcher._pending):
                        await asyncio.sleep(0)
                queued_behind.set()
                return ahead, behind

            # two fresh tenants, so neither lane has a return on record and the
            # gather waits for nobody: lane b's step falls in behind lane a's
            # step in flight and follows it under the same flush task
            batcher.release_lane(a)
            batcher.release_lane(b)
            a, b = await batcher.acquire_lane(), await batcher.acquire_lane()
            await steps_booked(batcher)
            spawns, waits, behind = batcher._flush_spawns, batcher.stats["gather_waits"], batcher.stats["overlapped_steps"]
            await asyncio.gather(*await step_behind_the_one_in_flight(batcher.step(a, _hidden(cfg, 20), 0), b, _hidden(cfg, 30), 0))
            await steps_booked(batcher)
            assert batcher._flush_spawns == spawns + 1  # the second step followed the first under one task
            # ... after a hand-off and not behind it: a's step was held in its launch, and nothing is started behind a
            # step whose launch has not returned
            handed_off = batcher.stats["turnaround_s"] - before
            assert handed_off > 0 and batcher.stats["overlapped_steps"] == behind and batcher.stats["gather_waits"] == waits

            # now lane a comes back 50 ms after its replies, on a step of 200 ms:
            # lane b falls in behind a's step again, and this time the gather
            # holds b's step for the 50 ms until a is back
            step_s = 0.2
            for i in range(5):  # S, the rule's step, is the median of the last nine walls
                await batcher.step(a, _hidden(cfg, 40 + i), 1 + i)
                await asyncio.sleep(0.05)
            await steps_booked(batcher)
            spawns, before = batcher._flush_spawns, dict(batcher.stats)
            ahead, behind = await step_behind_the_one_in_flight(batcher.step(a, _hidden(cfg, 50), 6), b, _hidden(cfg, 51), 1)
            await ahead
            await asyncio.sleep(0.05)
            await asyncio.gather(behind, batcher.step(a, _hidden(cfg, 52), 7))
            del backend.paged_decode_step
            await steps_booked(batcher)
            assert batcher._flush_spawns == spawns + 1
            delta = {k: batcher.stats[k] - before[k] for k in before}
            assert delta["batched_steps"] == 2 and delta["batched_tokens"] == 3, delta
            assert delta["gather_waits"] == 1 and delta["gather_joined"] == 1, delta
            # had the 50 ms gathered counted as hand-off, turnaround_s would exceed them
            assert delta["gather_wait_s"] > 0.04 > delta["turnaround_s"], delta
        finally:
            await batcher.close()
            queue.shutdown()

    run(main())


@pytest.mark.parametrize("variant", ["paged", "dense", "mixed"])
def test_step_emits_its_phases_in_order(model_path, monkeypatch, variant):
    """With TraceAnnotation replaced by a recorder, a step is one ``ptu.step``
    (carrying variant, lanes and prefill tokens) around assemble, dispatch,
    wait and post, each opened once and closed before the next; a plain
    decode step of the paged pool is two, its launch and its bookkeeping, and
    the wait between them is no run of the compute thread's."""
    from tests.utils import record_step_annotations, recorded_steps

    backend, cfg = _tiny_backend(model_path)
    events = record_step_annotations(monkeypatch)

    async def main():
        queue = PriorityTaskQueue()
        queue.start()
        batcher = _phase_batcher(backend, queue, page_size=None if variant == "dense" else 16)
        try:
            lane = await batcher.acquire_lane()
            if variant == "mixed":
                await batcher.prefill_lane(lane, _hidden(cfg, 1, 5), 0)
            else:
                await batcher.step(lane, _hidden(cfg, 1), 0)
            await steps_booked(batcher)
        finally:
            await batcher.close()
            queue.shutdown()

    run(main())
    steps = recorded_steps(events)
    want = {"variant": variant, "lanes": 0 if variant == "mixed" else 1, "prefill_tokens": 5 if variant == "mixed" else 0}
    runs = [PHASE_NAMES[:2], PHASE_NAMES[3:]] if variant == "paged" else [PHASE_NAMES]
    assert steps == [(want, names) for names in runs], steps


def test_phases_close_when_the_body_raises(model_path, monkeypatch):
    """A pool reset that lands between the launch's two generation guards
    makes it raise in ``dispatch``: both phases were opened once, neither
    stays open, the time is counted and the step is not, and no bookkeeping
    follows a step that was never in flight."""
    from tests.utils import record_step_annotations, recorded_steps

    backend, cfg = _tiny_backend(model_path)
    events = record_step_annotations(monkeypatch)

    async def main():
        queue = PriorityTaskQueue()
        queue.start()
        batcher = _phase_batcher(backend, queue, page_size=16)
        try:
            lane = await batcher.acquire_lane()
            step = backend.paged_decode_step

            def reset_lands_meanwhile(*args, **kwargs):
                batcher._generation += 1
                return step(*args, **kwargs)

            backend.paged_decode_step = reset_lands_meanwhile
            with pytest.raises(AllocationFailed, match="reset while this batched step ran"):
                await batcher.step(lane, _hidden(cfg, 1), 0)
            del backend.paged_decode_step
            await steps_booked(batcher)
            assert batcher.stats["batched_steps"] == 0 and not batcher._aloft and not batcher._flights
            assert all(batcher.stats[k] > 0 for k in PHASE_KEYS[:2]), batcher.stats
            assert all(batcher.stats[k] == 0 for k in PHASE_KEYS[2:]), batcher.stats
        finally:
            await batcher.close()
            queue.shutdown()

    run(main())
    assert [names for _args, names in recorded_steps(events)] == [PHASE_NAMES[:2]]


def test_phase_order_is_enforced():
    """The shared walker refuses a phase out of its order or entered twice."""
    from petals_tpu.utils.tracing import STEP_PHASES, step_phases

    stats = {f"{name}_s": 0.0 for name in STEP_PHASES}
    with step_phases(stats, variant="paged") as phases:
        phases.enter("dispatch")
        with pytest.raises(RuntimeError, match="cannot follow"):
            phases.enter("dispatch")
        with pytest.raises(RuntimeError, match="cannot follow"):
            phases.enter("post")
        phases.enter("wait")
        phases.enter("post")
        with pytest.raises(RuntimeError, match="cannot follow"):
            phases.enter("assemble")
    assert all(v > 0 for v in stats.values())
