"""Olmo-Hybrid (``olmo_hybrid``) on the normal path, at a toy size on the CPU:
a span of two kinds of block, of which one keeps no keys and values but a
recurrent state a lane. The block from a checkpoint against the in-repo
reference (perf/reference/olmo_hybrid.py); prefill in chunks and decode beside
other lanes through ``Server`` and the paged lane pool against the reference's
whole forward pass; the lane pool's state pool (depths, a reused lane, an idle
lane, swap out and in, the counters, the sizing); what the family refuses,
each with its reason; and a Falcon span left as it was."""

import asyncio
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.reference import olmo_hybrid as reference
from petals_tpu.client.model import AutoDistributedModelForCausalLM
from petals_tpu.data_structures import CHAIN_DELIMITER, make_uid
from petals_tpu.models.registry import span_runs
from petals_tpu.ops import linear_attention
from petals_tpu.rpc import RpcClient
from petals_tpu.rpc.serialization import deserialize_array, serialize_array
from petals_tpu.server.backend import TransformerBackend
from petals_tpu.server.batching import DecodeBatcher
from petals_tpu.server.from_pretrained import get_block_config, load_block_params
from petals_tpu.server.memory_cache import MemoryCache
from petals_tpu.server.server import Server, default_dht_prefix
from petals_tpu.server.task_queue import PriorityTaskQueue
from tests.test_full_model import SwarmHarness
from tests.utils import lane_pools, make_tiny_falcon, make_tiny_olmo_hybrid, steps_booked, tiny_olmo_hybrid_tensors, TINY_OLMO_HYBRID

HF = dict(TINY_OLMO_HYBRID)
LINEAR, FULL = "linear_attention", "full_attention"
KINDS = [LINEAR, LINEAR, LINEAR, FULL] * 2
STATE_KEYS = {"linattn_recurrent_tokens", "linattn_kernel_tokens", "linattn_chunk_tokens", "state_bytes_held", "kv_bytes_held"}
# float32 on the CPU, the served path against the reference, as a share of the largest output: they differ in
# the order of float32 sums and in the chunked form's triangular solve (measured 2e-6..2e-5)
CLOSE = 2e-4


def run(coro):
    return asyncio.run(coro)


def layer_tensors(tensors: dict, layer: int) -> dict:
    prefix = f"model.layers.{layer}."
    return {k[len(prefix):]: jnp.asarray(v) for k, v in tensors.items() if k.startswith(prefix)}


def reference_hidden(tensors: dict, hidden, first: int = 0, last: int = 8) -> np.ndarray:
    """``hidden`` [seq, h] through layers [first, last) of the reference."""
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(hidden, jnp.float32)
        for i in range(first, last):
            x, _ = reference.block(HF, layer_tensors(tensors, i), x, KINDS[i])
    return np.asarray(x)


def reference_logits(tensors: dict, ids) -> np.ndarray:
    x = reference_hidden(tensors, tensors["model.embed_tokens.weight"][np.asarray(ids)])
    x = x / np.sqrt((x * x).mean(-1, keepdims=True) + HF["rms_norm_eps"]) * tensors["model.norm.weight"]
    return x @ tensors["lm_head.weight"].T


def off(got, want) -> float:
    return float(np.abs(np.asarray(got) - want).max() / np.abs(want).max())


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return make_tiny_olmo_hybrid(str(tmp_path_factory.mktemp("models"))), tiny_olmo_hybrid_tensors(HF)


def whole_backend(path: str, first_block: int = 0, n_blocks: int = 8, **kw) -> TransformerBackend:
    family, cfg = get_block_config(path)
    runs = span_runs(family.span_kinds(cfg, first_block, n_blocks))
    stacked = tuple(
        jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *(load_block_params(path, first_block + i, dtype=jnp.float32)
                                                           for i in range(start, start + length)))
        for _, start, length in runs
    )
    return TransformerBackend(family, cfg, stacked[0] if len(stacked) == 1 else stacked, first_block=first_block,
                              n_blocks=n_blocks, memory_cache=MemoryCache(None), compute_dtype=jnp.float32, use_flash=False, **kw)


async def start_server(path, **kwargs):
    server = Server(path, compute_dtype=jnp.float32, use_flash=False, **kwargs)
    await server.start()
    client = await RpcClient.connect(server.rpc_server.host, server.rpc_server.port)
    return server, client


async def open_session(client, path, max_length: int):
    uids = CHAIN_DELIMITER.join(make_uid(default_dht_prefix(path), i) for i in range(HF["num_hidden_layers"]))
    stream = await client.open_stream("ptu.inference")
    await stream.send({"uids": uids, "max_length": max_length, "batch_size": 1})
    await stream.recv(timeout=60)
    return stream


async def step(stream, hidden, **extra) -> np.ndarray:
    await stream.send({"tensors": {"hidden": serialize_array(hidden)}, **extra})
    return deserialize_array((await stream.recv(timeout=300))["tensors"]["hidden"])


def rows(seed: int, n: int) -> np.ndarray:
    return (np.random.RandomState(seed).randn(1, n, HF["hidden_size"]) * 0.5).astype(np.float32)


# ---------------------------------------------------------------------------------
# the block, from a checkpoint
# ---------------------------------------------------------------------------------


@pytest.mark.parametrize("layer", [1, 3])
def test_a_checkpoint_s_block_of_each_kind_matches_the_reference(tiny, layer):
    """``hf_to_block_params`` under the assumed tensor names, and the block
    over 70 positions (two sub-chunks of the chunked form) and then one
    position at a time from the cache that left, against the reference."""
    path, tensors = tiny
    family, cfg = get_block_config(path)
    kind = KINDS[layer]
    assert family.name == "olmo_hybrid" and family.kind_of(cfg, layer) == kind
    params = load_block_params(path, layer, dtype=jnp.float32)
    assert set(params) == set(family.block_param_shapes(cfg, kind)) and ("conv" in params) == (kind == LINEAR)
    x = rows(layer, 80)
    want = reference_hidden(tensors, x[0], layer, layer + 1)
    with jax.default_matmul_precision("highest"):
        out, _ = family.block_apply(params, jnp.asarray(x), None, 0, cfg, kind=kind)
        assert off(out[0], want) < CLOSE
        state = family.state_for(cfg, kind)
        cache = (tuple(jnp.zeros((1, *shape), dtype or jnp.float32) for shape, dtype in state) if state
                 else tuple(jnp.zeros((1, 80, cfg.cache_kv_heads, cfg.head_dim), jnp.float32) for _ in range(2)))  # 8 heads kept for the model's 4
        out, cache = family.block_apply(params, jnp.asarray(x[:, :70]), cache, 0, cfg, kind=kind)
        got = [np.asarray(out[0])]
        for pos in range(70, 80):
            out, cache = family.block_apply(params, jnp.asarray(x[:, pos : pos + 1]), cache, pos, cfg, kind=kind)
            got.append(np.asarray(out[0]))
        assert off(np.concatenate(got), want) < CLOSE


def test_forward_and_backward_run_the_chunked_form_from_a_zero_state(tiny):
    """``rpc_forward`` / ``rpc_backward``'s programs need no cache."""
    path, tensors = tiny
    backend = whole_backend(path)
    x = rows(5, 70)
    with jax.default_matmul_precision("highest"):
        assert off(backend.forward(x)[0], reference_hidden(tensors, x[0])) < CLOSE
    grad, _ = backend.backward(x, np.ones_like(x))
    assert grad.shape == x.shape and np.isfinite(np.asarray(grad)).all() and float(np.abs(np.asarray(grad)).max()) > 0


# ---------------------------------------------------------------------------------
# the lane pool: pages in the blocks that keep keys and values, a state pool beside them
# ---------------------------------------------------------------------------------


def test_the_page_pool_is_as_deep_as_the_full_layers_and_the_state_pool_as_the_linear_ones(tiny):
    path, _ = tiny
    backend = whole_backend(path)
    assert backend.cache.kv_layers == (3, 7) and backend.cache.state_layers == (0, 1, 2, 4, 5, 6) and backend.cache.slots == (0, 1, 2, 0, 3, 4, 5, 1)
    k, v = lane_pools(backend, 12, 16, end=8)[0]
    # the cache keeps a tile's 8 kv heads for the model's 4, the spare ones zeros (cfg.cache_kv_heads: the pool's layout on the device)
    assert backend.cfg.num_key_value_heads == 4 and backend.num_kv_heads == backend.cfg.cache_kv_heads == 8
    assert k.shape == v.shape == (2, 12, 16, 8 * 16)  # rows of 8 kv heads of 16, under 128 lanes: stored folded
    matrix, tail = lane_pools(backend, 1, 1, 3)[1]
    assert matrix.shape == (6, 3, 4, 8, 16) and jnp.dtype(matrix.dtype) == jnp.float32  # float32 whatever the cache's dtype
    assert tail.shape == (6, 3, 3, 4 * (8 + 8 + 16))
    assert backend.cache.state_bytes_per_lane() == 6 * (4 * 8 * 16 + 3 * 128) * 4
    assert backend.cache.cache_bytes_per_token() == backend.cache.kv_bytes_per_token() == 2 * 2 * 8 * 16 * 4  # two layers of pages, not eight
    linear_only = whole_backend(path, 0, 3)  # a span with no full layer: no pages at all
    assert linear_only.cache.kv_layers == () and lane_pools(linear_only, 12, 16, end=3)[0][0].shape[0] == 0
    assert linear_only.cache.kv_bytes_per_token() == 0 and len(linear_only.runs) == 1


def test_the_published_span_s_pools_and_the_lanes_the_default_budget_affords():
    """olmo-hybrid-7b-span16 on shapes alone: pages 4 layers deep, states 12,
    a lane's states 27.4 MB and a lane of 2,560 positions 195.1 MB (its pages
    keep 32 kv heads for the model's 30), of which the default budget (half of
    15% of the chip) affords 6; counted as 16 layers of pages a lane would be
    671 MB and the budget would afford 1."""
    from pathlib import Path

    from perf.config import load as load_config

    root = Path(__file__).resolve().parents[1]
    hf = load_config(root / "perf/configs/olmo-hybrid-7b-span16.json", "olmo-hybrid-7b-span16")["config"]
    import tempfile

    with tempfile.TemporaryDirectory() as tmp:
        (Path(tmp) / "config.json").write_text(json.dumps(hf))
        family, cfg = get_block_config(tmp)
    S = jax.ShapeDtypeStruct
    runs = tuple({name: S((length, *leaf.shape), leaf.dtype) for name, leaf in family.param_shapes_for(cfg, kind, jnp.bfloat16).items()}
                 for kind, _, length in span_runs(family.span_kinds(cfg, 0, 16)))
    backend = TransformerBackend(family, cfg, runs, first_block=0, n_blocks=16, memory_cache=None)
    assert len(backend.cache.kv_layers) == 4 and len(backend.cache.state_layers) == 12
    assert cfg.num_key_value_heads == 30 and cfg.cache_kv_heads == 32
    assert lane_pools(backend, 320, 64, end=16)[0][0].shape == (4, 320, 64, 32, 128)
    matrix, tail = lane_pools(backend, 1, 1, 8)[1]
    assert (matrix.shape, tail.shape) == ((12, 8, 30, 96, 192), (12, 8, 3, 11520)) and jnp.dtype(tail.dtype) == jnp.bfloat16
    assert backend.cache.state_bytes_per_lane() == 12 * (2_211_840 + 69_120) == 27_371_520
    lane = backend.cache.cache_bytes_per_token() * 2560 + backend.cache.state_bytes_per_lane()
    assert backend.cache.cache_bytes_per_token() == 4 * 16384 and lane == 195_143_680
    budget = int(15.75 * 2**30 * 0.15)  # Server's default cache budget on a v5e
    assert budget // 2 // lane == 6 and budget // 2 // (16 * 16384 * 2560) == 1


def test_lane_auto_sizing_counts_the_state(tiny):
    """``Server`` with no ``batch_lanes``: a lane costs its pages in two
    layers and its six states, and the budget is halved."""
    path, _ = tiny

    async def main():
        backend = whole_backend(path)
        lane = backend.cache.cache_bytes_per_token() * 32 + backend.cache.state_bytes_per_lane()
        assert (2 * 5 * lane + 100) // 2 // (backend.cache.cache_bytes_per_token() * 32) == 6  # what the pages alone would afford
        server = Server(path, compute_dtype=jnp.float32, use_flash=False, batch_max_length=32, page_size=16,
                        attn_cache_bytes=2 * 5 * lane + 100, prefix_cache_bytes=0)
        await server.start()
        try:
            batcher = server.handler.batcher
            assert batcher.n_lanes == 5
            await batcher.ensure_open()
            info = batcher.occupancy_info()
            assert info["state_bytes_per_lane"] == backend.cache.state_bytes_per_lane() and info["state_bytes_held"] == 0
            assert info["kv_bytes_per_token"] == backend.cache.kv_bytes_per_token()
            a = await batcher.acquire_lane(timeout=5)
            assert batcher.occupancy_info()["state_bytes_held"] == backend.cache.state_bytes_per_lane()
            batcher.release_lane(a)
        finally:
            await server.shutdown()

    run(main())


@pytest.mark.parametrize("state_step", ["plain", "kernel"])
def test_prompt_in_three_mixed_steps_beside_two_decoding_lanes_then_decode_matches_the_reference(tiny, monkeypatch, state_step):
    """Sessions B and C decode while A's prompt of 40 rides three mixed steps
    (a budget of 16: chunks of 16, 16 and 8, the state and the conv's tail
    handed chunk to chunk), then all three decode at once: every row of every
    session against the reference's whole forward pass. The counters say
    which form each row took. ``kernel``: the decode rows' one-step rule named
    as on a TPU (ops/linear_attention.py ``_step_kernel``, interpreted here),
    on the state pool where it lies, in the decode steps and in the mixed
    steps beside A's chunks: the same replies within the same tolerance."""
    path, tensors = tiny

    if state_step == "kernel":
        monkeypatch.setattr(linear_attention, "_on_tpu", lambda: True)  # ``_interpret`` still sees the CPU

    async def main():
        server, client = await start_server(path, batch_lanes=3, batch_max_length=64, page_size=16, prefill_token_budget=16)
        try:
            batcher = server.handler.batcher
            assert batcher.occupancy_info()["state_step"] == state_step
            assert batcher.page_size == 16 and server.handler.prefix_cache is None and STATE_KEYS <= set(batcher.stats)
            a_rows, b_rows, c_rows = rows(1, 52), rows(2, 40), rows(3, 40)
            b, c = await open_session(client, path, 64), await open_session(client, path, 64)
            got_b, got_c = [await step(b, b_rows[:, :5])], [await step(c, c_rows[:, :3])]
            before = dict(batcher.stats)
            a = await open_session(client, path, 64)

            async def decode(stream, data, got, start, until):
                pos = start
                while not until.is_set() and pos < data.shape[1] - 12:
                    got.append(await step(stream, data[:, pos : pos + 1]))
                    pos += 1
                return pos

            done = asyncio.Event()

            async def prompt():
                out = await step(a, a_rows[:, :40])
                done.set()
                return out

            got_a, pos_b, pos_c = await asyncio.gather(prompt(), decode(b, b_rows, got_b, 5, done), decode(c, c_rows, got_c, 3, done))
            got_a = [got_a]
            mixed = batcher.stats["mixed_steps"] - before["mixed_steps"]
            assert mixed == 3 and batcher.stats["prefill_tokens"] - before["prefill_tokens"] == 40
            assert batcher.stats["linattn_chunk_tokens"] - before["linattn_chunk_tokens"] == 40 * 6
            for i in range(12):  # all three decode at once
                outs = await asyncio.gather(step(a, a_rows[:, 40 + i : 41 + i]), step(b, b_rows[:, pos_b + i : pos_b + i + 1]),
                                            step(c, c_rows[:, pos_c + i : pos_c + i + 1]))
                for got, out in zip((got_a, got_b, got_c), outs):
                    got.append(out)
            decoded = (len(got_b) - 1) + (len(got_c) - 1) + 12  # B's and C's replies but their prompts', and A's 12
            await steps_booked(batcher)
            assert batcher.stats["linattn_recurrent_tokens"] - before["linattn_recurrent_tokens"] == decoded * 6
            # of them, those whose state the kernel moved where it lies: all on a TPU, none on the default CPU path
            assert batcher.stats["linattn_kernel_tokens"] == (batcher.stats["linattn_recurrent_tokens"] if state_step == "kernel" else 0)
            assert batcher.stats["state_bytes_held"] > before["state_bytes_held"] and batcher.stats["kv_bytes_held"] > before["kv_bytes_held"]
            info = await client.call("ptu.info", {})
            assert STATE_KEYS <= set(info["continuous_batching"])
            for got, data in ((got_a, a_rows), (got_b, b_rows), (got_c, c_rows)):
                got = np.concatenate(got, axis=1)[0]
                assert off(got, reference_hidden(tensors, data[0, : got.shape[0]])) < CLOSE
            for stream in (a, b, c):
                await stream.end()
        finally:
            await client.close()
            await server.shutdown()

    run(main())


def test_the_one_step_rule_s_path_follows_from_the_pool_and_the_call_and_gives_its_reason(tiny, monkeypatch):
    """What the backend tells the batcher its lanes' steps take, and why the
    span's other calls keep the plain form: a chunk, a call whose state is not
    the pool's, a head the kernel refuses."""
    backend = whole_backend(tiny[0])
    leaves = tuple(jax.ShapeDtypeStruct(d.shape, d.dtype) for d in lane_pools(backend, 1, 1, 3)[1])
    pool = linear_attention.StatePool(leaves, 0)
    assert leaves[0].shape == (6, 3, 4, 8, 16) and backend.cache.lane_pool(3, 4, 16).state_step == "plain"  # off the chip
    monkeypatch.setattr(linear_attention, "_on_tpu", lambda: True)
    assert backend.cache.lane_pool(3, 4, 16).state_step == "kernel" and linear_attention.step_kernel_unsupported(pool, 1) is None
    assert "16 rows a lane" in linear_attention.step_kernel_unsupported(pool, 16)
    a_lane = tuple(jnp.zeros((1, *leaf.shape[2:]), leaf.dtype) for leaf in leaves)  # what a chunk's lane is handed
    assert "no pooled state" in linear_attention.step_kernel_unsupported(a_lane, 1)
    assert "no pooled state" in linear_attention.step_kernel_unsupported(None, 40)
    odd = linear_attention.StatePool((jax.ShapeDtypeStruct((6, 3, 4, 12, 16), jnp.float32), leaves[1]), 0)
    assert "12 is no multiple of the 8 sublanes" in linear_attention.step_kernel_unsupported(odd, 1)
    assert linear_attention.gated_delta_step_path(odd, 1) == linear_attention.gated_delta_step_path(pool, 16) == "plain"


def test_a_reused_lane_starts_from_zero_and_an_idle_lane_s_state_keeps_its_bytes(tiny):
    path, tensors = tiny

    async def main():
        server, client = await start_server(path, batch_lanes=2, batch_max_length=64, page_size=16)
        try:
            batcher = server.handler.batcher
            first = await open_session(client, path, 64)
            await step(first, rows(7, 20))
            assert [l for l in range(2) if l not in batcher._free_lanes] == [0]
            dirty = [np.asarray(leaf[:, 0]) for leaf in batcher._state()]
            assert all(np.abs(leaf).max() > 0 for leaf in dirty)
            # the other lane's session steps: lane 0 is idle in those steps and keeps its state, byte for byte
            other = await open_session(client, path, 64)
            data = rows(8, 12)
            got = [await step(other, data[:, :1])] + [await step(other, data[:, p : p + 1]) for p in range(1, 12)]
            assert off(np.concatenate(got, axis=1)[0], reference_hidden(tensors, data[0])) < CLOSE
            for was, leaf in zip(dirty, batcher._state()):
                assert np.asarray(leaf[:, 0]).tobytes() == was.tobytes()
            await first.end()
            await other.end()
            await asyncio.sleep(0.2)
            assert sorted(batcher._free_lanes) == [0, 1]
            # lanes are handed out least recently released first: the next session takes lane 0, stale state and all
            again, data = await open_session(client, path, 64), rows(9, 24)
            assert batcher._free_lanes == [1] and np.abs(np.asarray(batcher._state()[0][:, 0])).max() > 0
            got = [await step(again, data[:, :9])] + [await step(again, data[:, p : p + 1]) for p in range(9, 24)]
            assert off(np.concatenate(got, axis=1)[0], reference_hidden(tensors, data[0])) < CLOSE
            await again.end()
        finally:
            await client.close()
            await server.shutdown()

    run(main())


def test_swapped_out_and_in_a_lane_gives_the_reply_of_an_undisturbed_one(tiny):
    """The lane's pages and its slot of the state pool leave for the host
    together and come back together, onto other pages; the next replies are
    the bytes an undisturbed session of the same rows gives."""
    path, tensors = tiny

    async def main():
        server, client = await start_server(path, batch_lanes=2, batch_max_length=64, page_size=16, swap_host_bytes=1 << 24)
        try:
            batcher = server.handler.batcher
            data = rows(11, 30)
            replies = []
            for disturbed in (False, True):
                stream = await open_session(client, path, 64)
                got = [await step(stream, data[:, :20])]
                if disturbed:
                    lane = next(l for l in range(2) if l not in batcher._free_lanes)
                    held = int((batcher._tables[lane] >= 0).sum())
                    assert await batcher._swap_out_lane(lane)
                    entry = batcher._scheduler.lanes[lane].swap
                    assert len(entry.state) == 2 and entry.state[0].shape == (6, 4, 8, 16)
                    assert entry.nbytes == held * batcher._pool.page_bytes + batcher.backend.cache.state_bytes_per_lane()
                    # the slot is overwritten while the lane is away: what comes back is the host's copy
                    batcher._update(*batcher._buffers(), *(jnp.full_like(leaf, 3.0) for leaf in batcher._state()))
                got += [await step(stream, data[:, p : p + 1]) for p in range(20, 30)]
                replies.append(np.concatenate(got, axis=1))
                await stream.end()
                await asyncio.sleep(0.1)
            assert batcher._scheduler.stats["swap_ins"] == 1
            assert replies[0].tobytes() == replies[1].tobytes()
            assert off(replies[1][0], reference_hidden(tensors, data[0])) < CLOSE
        finally:
            await client.close()
            await server.shutdown()

    run(main())


def test_server_side_generation_s_pooled_step_carries_the_state_as_the_decode_step_does(tiny):
    """``paged_gen_decode_step`` takes the span's layer loop from the same
    helper: fed hidden states it is the decode step bit for bit, pages,
    states and all, an idle lane's state untouched in both."""
    from petals_tpu.client.from_pretrained import load_client_params
    from petals_tpu.ops.sampling import sampling_vectors

    path, _ = tiny
    backend = whole_backend(path)
    cfg, lanes, ps, max_pages = backend.cfg, 3, 4, 6
    client_params = load_client_params(path, dtype=jnp.float32)
    rng = np.random.default_rng(11)
    made = lambda descs: tuple(jnp.asarray(rng.standard_normal(d.shape).astype(np.float32) * 0.1).astype(d.dtype) for d in descs)
    pool = (*made(lane_pools(backend, lanes * max_pages, ps, end=8)[0]), *made(lane_pools(backend, 1, 1, lanes)[1]))
    assert len(pool) == 4 and pool[0].shape[0] == 2 and pool[2].shape[:2] == (6, lanes)
    tables = rng.permutation(lanes * max_pages).astype(np.int32).reshape(lanes, max_pages)
    positions = np.array([13, ps * max_pages, 6], np.int32)  # lane 1 idle
    tokens = rng.integers(1, cfg.vocab_size, lanes).astype(np.int32)
    hidden = np.asarray(backend.family.client_embed(client_params, jnp.asarray(tokens[:, None]), cfg), np.float32)
    copy = jax.tree_util.tree_map(jnp.copy, pool)
    idle_before = [np.asarray(leaf[:, 1]).tobytes() for leaf in pool[2:]]
    live_before = [np.asarray(leaf[:, 0]).tobytes() for leaf in pool[2:]]
    out, after = backend.paged_decode_step(hidden, pool, positions, tables)
    gen_out, _, gen_after = backend.paged_gen_decode_step(
        client_params, hidden, tokens, np.zeros(lanes, bool), copy, positions, tables, sampling_vecs=sampling_vectors(lanes, cfg.vocab_size))
    np.testing.assert_array_equal(np.asarray(gen_out), np.asarray(out))
    assert len(after) == len(gen_after) == 4
    for a, b in zip(after, gen_after):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert [np.asarray(leaf[:, 1]).tobytes() for leaf in after[2:]] == idle_before
    assert all(np.asarray(leaf[:, 0]).tobytes() != was for leaf, was in zip(after[2:], live_before))


@pytest.fixture(scope="module")
def swarm(tiny):
    """A chain of two spans on the default server: blocks [0, 5) end in a
    linear layer and [5, 8) start with two."""
    path, tensors = tiny
    specs = [dict(first_block=0, num_blocks=5, page_size=8, batch_max_length=64, prefill_token_budget=16),
             dict(first_block=5, num_blocks=3, page_size=8, batch_max_length=64, prefill_token_budget=16)]
    harness = SwarmHarness(path, specs).start()
    model = AutoDistributedModelForCausalLM.from_pretrained(path, initial_peers=harness.initial_peers)
    yield path, tensors, harness, model
    model.close()
    harness.stop()


def test_remote_sequential_session_prefill_in_chunks_then_decode_matches_the_reference(swarm):
    """Through ``Server`` with no flag and ``RemoteSequential`` over a chain of
    two spans: a prompt of 37 in three mixed steps a server, then decode; the
    LOGITS of every position against the reference's whole forward pass."""
    path, tensors, harness, model = swarm
    batchers = [server.handler.batcher for server in harness.servers]
    assert all(b is not None and b.page_size == 8 and len(b.backend.cache.lane_state) == 2 for b in batchers)
    assert [len(b.backend.cache.state_layers) for b in batchers] == [4, 2] and [len(b.backend.cache.kv_layers) for b in batchers] == [1, 1]
    before = [dict(b.stats) for b in batchers]
    ids = np.random.RandomState(3).randint(0, 128, (1, 50)).astype(np.int64)
    hidden = np.asarray(model.embed(ids))
    with model.remote.inference_session(max_length=50) as session:
        outs = [np.asarray(session.step(hidden[:, :37]))]
        outs += [np.asarray(session.step(hidden[:, p : p + 1])) for p in range(37, 50)]
    logits = np.asarray(model.lm_logits(np.concatenate(outs, axis=1)))[0]
    np.testing.assert_allclose(logits, reference_logits(tensors, ids[0]), atol=3e-4, rtol=0)
    for batcher, was in zip(batchers, before):
        layers = len(batcher.backend.cache.state_layers)
        assert batcher.stats["mixed_steps"] - was["mixed_steps"] == 3
        assert batcher.stats["linattn_chunk_tokens"] - was["linattn_chunk_tokens"] == 37 * layers
        assert batcher.stats["linattn_recurrent_tokens"] - was["linattn_recurrent_tokens"] == 13 * layers


def test_generate_token_identical_over_a_chain_of_two_spans(swarm):
    path, tensors, _, model = swarm
    ids = np.random.RandomState(6).randint(0, 128, (1, 5)).astype(np.int64)
    got = np.asarray(model.generate(ids, max_new_tokens=10))
    want = list(ids[0])
    for _ in range(10):
        want.append(int(np.argmax(reference_logits(tensors, want)[-1])))
    np.testing.assert_array_equal(got[0], want)


# ---------------------------------------------------------------------------------
# what is refused, and why
# ---------------------------------------------------------------------------------


@pytest.mark.parametrize("key,value,named", [
    ("rope_parameters", {"rope_theta": 500000.0}, "rope_theta"), ("rope_theta", 10000.0, "rope_theta"),
    ("linear_num_key_heads", 2, "linear_num_key_heads"), ("hidden_act", "gelu", "hidden_act"), ("attention_bias", True, "attention_bias"),
])
def test_what_the_block_does_not_compute_is_refused_at_load(tmp_path, key, value, named):
    (tmp_path / "config.json").write_text(json.dumps({**HF, key: value}))
    with pytest.raises(NotImplementedError, match=f"olmo_hybrid: {named}"):
        get_block_config(str(tmp_path))


REFUSED_BY_THE_BACKEND = {
    "a private cache": lambda b: b.cache_descriptors(1, 32, 0, 8),
    "a step on a private cache": lambda b: b.inference_step(rows(0, 4), (None, None), 0),
    "speculative verify": lambda b: b.paged_spec_verify_step(None, np.zeros((2, 3), np.int32), (None, None), np.zeros(2, np.int32),
                                                             np.zeros((2, 2), np.int32), sampling_vecs={}),
    "server-side generation on a private cache": lambda b: b.generate_tokens({}, rows(0, 1), (None, None), 4, 2),
    "the dense lane pool": lambda b: DecodeBatcher(b, b.memory_cache, PriorityTaskQueue(), n_lanes=2, max_length=32, page_size=None),
    "the dense lane pool's step": lambda b: b._batched_decode_fn,
}


@pytest.mark.parametrize("what", sorted(REFUSED_BY_THE_BACKEND))
def test_cache_paths_that_do_not_carry_a_state_refuse_it_with_the_reason(tiny, what):
    backend = whole_backend(tiny[0])
    with pytest.raises(NotImplementedError, match="olmo_hybrid: .* recurrent state .*6 of its 8 blocks"):
        REFUSED_BY_THE_BACKEND[what](backend)
    full_only = whole_backend(tiny[0], 3, 1)  # a span of this family without a state layer is served like any other
    assert not full_only.cache.state_layers and full_only.cache.lane_state == () and len(full_only.cache_descriptors(1, 32, 0, 1)) == 2


def test_options_the_family_cannot_take_yet_are_refused(tiny, tmp_path):
    """A tp mesh, quantized weights, quantized pages, a LoRA adapter and a
    draft model: refused with the family's name, as K-EXAONE's are."""
    from petals_tpu.parallel.mesh import tp_mesh
    from petals_tpu.utils.convert_block import QuantType, convert_block_params
    from petals_tpu.utils.peft import load_adapter
    from safetensors.numpy import save_file

    path, _ = tiny
    family, cfg = get_block_config(path)
    assert family.tp_pspecs is None and not family.quantizable_leaves and not family.lora_targets
    with pytest.raises(NotImplementedError, match="olmo_hybrid.*tp mesh"):
        whole_backend(path, mesh=tp_mesh(2))
    with pytest.raises(KeyError, match="No TP spec for family 'olmo_hybrid'"):
        whole_backend(path, 0, 3, mesh=tp_mesh(2))
    with pytest.raises(NotImplementedError, match="olmo_hybrid: kv_quant_type 'int8'.*recurrent state"):
        whole_backend(path, 0, 3, kv_quant_type="int8")
    with pytest.raises(ValueError, match="olmo_hybrid"):
        convert_block_params(dict(load_block_params(path, 1, dtype=jnp.float32)), "olmo_hybrid", QuantType.NF4)
    (tmp_path / "adapter_config.json").write_text(json.dumps({"r": 2, "lora_alpha": 4, "target_modules": ["q_proj"], "peft_type": "LORA"}))
    save_file({"base_model.model.model.layers.3.self_attn.q_proj.lora_A.weight": np.zeros((2, 64), np.float32),
               "base_model.model.model.layers.3.self_attn.q_proj.lora_B.weight": np.zeros((64, 2), np.float32)},
              str(tmp_path / "adapter_model.safetensors"))
    with pytest.raises(ValueError, match="olmo_hybrid"):
        load_adapter(str(tmp_path), "olmo_hybrid", block_range=range(0, 8))
    backend = whole_backend(path)

    class Draft:
        spec_k = 2

    with pytest.raises(NotImplementedError, match="olmo_hybrid: speculative decoding .* cannot be cut back"):
        DecodeBatcher(backend, backend.memory_cache, PriorityTaskQueue(), n_lanes=2, max_length=32, page_size=8,
                      gen_params={}, draft_model=Draft())


def test_what_cuts_a_cache_back_is_refused_over_the_wire_and_the_prefix_cache_is_off(tiny):
    """``start_from_position`` behind the state's position (0 starts over and
    is served), ``kv_adopt``, a session export, and a session that would take
    a private cache: each error names the reason. The server's default prefix
    cache is switched off for the span."""
    path, tensors = tiny

    async def main():
        server, client = await start_server(path, batch_lanes=2, batch_max_length=32, page_size=8)  # prefix_cache_bytes: the default
        try:
            assert server.handler.prefix_cache is None and len(server.handler.batcher.backend.cache.lane_state) == 2
            data = rows(21, 12)
            stream = await open_session(client, path, 32)
            await step(stream, data[:, :8])
            with pytest.raises(Exception, match="start_from_position 5 behind the cache's position 8.*cannot be cut back"):
                await step(stream, data[:, 5:6], start_from_position=5)
            stream = await open_session(client, path, 32)
            await step(stream, data[:, :8])
            again = await step(stream, data[:, :12], start_from_position=0)  # from the start: a zero state again
            assert off(again[0], reference_hidden(tensors, data[0])) < CLOSE
            with pytest.raises(Exception, match="kv_adopt / kv_import.*state is not shipped"):
                await stream.send({"kv_adopt": {"session_id": "x", "position": 4}})
                await stream.recv(timeout=60)
            uids = CHAIN_DELIMITER.join(make_uid(default_dht_prefix(path), i) for i in range(8))
            live = await client.open_stream("ptu.inference")
            await live.send({"uids": uids, "max_length": 32, "batch_size": 1, "session_id": "live-one"})
            await live.recv(timeout=60)
            await step(live, data[:, :8])
            with pytest.raises(Exception, match="a snapshot of a lane's cache.*state is not shipped"):
                await client.call("ptu.session_export", {"session_id": "live-one", "start": 0, "end": 8})
            await live.end()
            wide = await client.open_stream("ptu.inference")  # two sequences a session take no lane
            await wide.send({"uids": uids, "max_length": 32, "batch_size": 2})
            with pytest.raises(Exception, match="a private cache.*only the paged lane pool carries the state"):
                await wide.recv(timeout=60)
        finally:
            await client.close()
            await server.shutdown()

    run(main())


# ---------------------------------------------------------------------------------
# a family without a state is served as it was
# ---------------------------------------------------------------------------------

STATS_BEFORE = {
    "batched_steps", "batched_tokens", "max_batch", "gen_steps", "gen_lane_tokens", "max_gen_lanes", "exclusive_chunks",
    "prefill_tokens", "mixed_steps", "max_prefill_tokens_per_step", "spec_steps", "spec_proposed", "spec_accepted",
    "spec_disabled", "max_spec_lanes", "assemble_s", "dispatch_s", "wait_s", "post_s", "turnaround_s", "gather_waits",
    "gather_wait_s", "gather_joined", "gather_missed",
    # PR 37: what the compute thread waited for between two bodies, and a decode token's round trip
    "lanes_out_s", "no_demand_s", "handoff_s", "reply_wake_s", "reply_steps", "reply_resume_s", "reply_build_s",
    "rpc_send_s", "decode_replies", "rpc_recv_s", "request_handle_s", "lane_return_s", "lane_returns",
}


def test_a_falcon_span_s_pools_programs_and_stats_are_what_they_were(tmp_path):
    path = make_tiny_falcon(str(tmp_path))
    family, cfg = get_block_config(path)
    assert family.block_state is None and family.state_for(cfg, None) is None
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *(load_block_params(path, i, dtype=jnp.float32) for i in range(2)))
    backend = TransformerBackend(family, cfg, stacked, first_block=0, n_blocks=2, memory_cache=MemoryCache(None),
                                 compute_dtype=jnp.float32, use_flash=False)
    assert backend.cache.state_layers == () and backend.cache.kv_layers == (0, 1) and backend.cache.lane_state == () and lane_pools(backend, 1, 1, 4)[1] == ()
    assert backend.cache.state_bytes_per_lane() == 0
    per_token = 2 * 2 * backend.num_kv_heads * backend.head_dim * 4
    assert backend.cache.cache_bytes_per_token() == backend.cache.kv_bytes_per_token() == per_token
    assert [d.shape for d in lane_pools(backend, 6, 8, end=2)[0]] == [(2, 6, 8, backend.num_kv_heads * backend.head_dim)] * 2  # folded: the toy's head_dim is under 128 lanes, as Falcon's 64 is
    assert [d.shape for d in backend.cache_descriptors(3, 24, 0, 2)] == [(2, 3, 24, backend.num_kv_heads, backend.head_dim)] * 2
    batcher = DecodeBatcher(backend, backend.memory_cache, PriorityTaskQueue(), n_lanes=3, max_length=24, page_size=8)
    # since PR 36 every family on the paged pool counts the table slots its steps read (_count_window)
    # ... and since PR 51 every batcher the step bodies that sent the block tables to the device (_step_tables)
    # ... and since PR 54 the event loop's turns (utils/asyncio_utils.install_turn_clock)
    # ... and since PR 59 every batcher the bytes of hidden state its steps took in and handed back (_count_stream)
    # ... and since PR 60 the decode steps by the way their request came in (begin_step / step)
    # ... and since PR 66 the decode steps launched while the step before was in flight (_start_behind)
    assert set(batcher.stats) == STATS_BEFORE | {"attn_pages_gathered", "attn_pages_tabled", "attn_pages_kernel", "tables_sent",
                                                 "loop_busy_s", "loop_busy_sq", "loop_turns", "stream_bytes_in",
                                                 "stream_bytes_out", "rpc_intake_direct",
                                                 "rpc_intake_queued", "overlapped_steps"} and len(batcher.backend.cache.lane_state) == 0 and batcher._state() == ()
    assert not {"state_bytes_per_lane", "state_bytes_held"} & set(batcher.occupancy_info())
    # the step programs take the pair of pools and give the pair back, and carry what they carried
    k, v = (jnp.zeros(d.shape, d.dtype) for d in lane_pools(backend, 6, 8, end=2)[0])
    tables = np.arange(6, dtype=np.int32).reshape(3, 2)
    tables = np.concatenate([tables, np.full((3, 1), -1, np.int32)], axis=1)
    hidden, positions = np.zeros((3, 1, cfg.hidden_size), np.float32), np.array([0, 24, 3], np.int32)
    out, pools = backend.paged_decode_step(hidden, (k, v), positions, tables)
    assert len(pools) == 2 and out.shape == (3, 1, cfg.hidden_size)
    out, chunk, pools = backend.paged_mixed_step(hidden, pools, positions, tables, np.zeros((1, 5, cfg.hidden_size), np.float32), 1, 0)
    assert len(pools) == 2 and chunk.shape == (1, 5, cfg.hidden_size)
    jaxpr = jax.make_jaxpr(lambda *a: backend._paged_decode_fn.__wrapped__(*a, with_fp=False))(
        backend.params, k, v, backend.pack_lanes(hidden, positions), tables)
    assert len(jaxpr.out_avals) == 3  # hidden and the two pools: no state rides a span without one
