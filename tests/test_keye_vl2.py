"""Keye-VL-2.0's language model (``KeyeVL2``) on the normal path, at a toy size
on the CPU: a block whose rows attend to the ``topk`` (here 16) cached
positions an indexer scores highest, the index keys in pages of their own
beside keys and values. The block from a checkpoint against the in-repo
reference (perf/reference/keye_vl2.py); prefill in chunks and decode beside
other lanes through ``Server`` and the paged lane pool against the reference's
whole forward pass, at contexts of 40-150 so that the selection bites; the
stateless forward and backward passes; the index pool (its stored form, the
sizing, the counters); what the family refuses, each with its reason."""

import asyncio
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.reference import keye_vl2 as reference
from petals_tpu.client.model import AutoDistributedModelForCausalLM
from petals_tpu.data_structures import CHAIN_DELIMITER, make_uid
from petals_tpu.ops import sparse_attention as sparse
from petals_tpu.ops.paged_attention import PagedKV
from petals_tpu.rpc import RpcClient
from petals_tpu.rpc.serialization import deserialize_array, serialize_array
from petals_tpu.server.backend import TransformerBackend
from petals_tpu.server.batching import DecodeBatcher
from petals_tpu.server.from_pretrained import get_block_config, load_block_params
from petals_tpu.server.memory_cache import MemoryCache
from petals_tpu.server.server import Server, default_dht_prefix
from petals_tpu.server.task_queue import PriorityTaskQueue
from tests.test_full_model import SwarmHarness
from tests.utils import lane_pools, make_tiny_keye_vl2, steps_booked, tiny_keye_vl2_tensors, TINY_KEYE_VL2

HF = dict(TINY_KEYE_VL2)
LAYERS, TOPK = HF["num_hidden_layers"], HF["sa_config"]["topk"]
SPARSE_KEYS = {"sparse_rows_selected", "sparse_rows_dense", "sparse_index_rows_scored", "sparse_score_pairs", "sparse_kv_rows_read",
               "sparse_kv_rows_held", "index_bytes_held", "kv_bytes_held"}
# float32 on the CPU, the served path against the reference, as a share of the largest output: they differ in
# the order of float32 sums (measured 4e-7..2e-6); a row that chose one other position lands near 1e-2
CLOSE = 5e-5


def run(coro):
    return asyncio.run(coro)


def layer_tensors(tensors: dict, layer: int) -> dict:
    prefix = f"model.layers.{layer}."
    return {k[len(prefix):]: jnp.asarray(v) for k, v in tensors.items() if k.startswith(prefix)}


def reference_hidden(tensors: dict, hidden, first: int = 0, last: int = LAYERS, **kw) -> np.ndarray:
    """``hidden`` [seq, h] through layers [first, last) of the reference."""
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(hidden, jnp.float32)
        for i in range(first, last):
            x, _ = reference.block(HF, layer_tensors(tensors, i), x, **kw)
    return np.asarray(x)


def reference_logits(tensors: dict, ids) -> np.ndarray:
    x = reference_hidden(tensors, tensors["model.embed_tokens.weight"][np.asarray(ids)])
    x = x / np.sqrt((x * x).mean(-1, keepdims=True) + HF["rms_norm_eps"]) * tensors["model.norm.weight"]
    return x @ tensors["lm_head.weight"].T


def off(got, want) -> float:
    return float(np.abs(np.asarray(got) - want).max() / np.abs(want).max())


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return make_tiny_keye_vl2(str(tmp_path_factory.mktemp("models"))), tiny_keye_vl2_tensors(HF)


def whole_backend(path: str, first_block: int = 0, n_blocks: int = LAYERS, **kw) -> TransformerBackend:
    family, cfg = get_block_config(path)
    stacked = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *(load_block_params(path, first_block + i, dtype=jnp.float32) for i in range(n_blocks))
    )
    return TransformerBackend(family, cfg, stacked, first_block=first_block, n_blocks=n_blocks, memory_cache=MemoryCache(None),
                              compute_dtype=jnp.float32, use_flash=False, **kw)


async def start_server(path, **kwargs):
    server = Server(path, compute_dtype=jnp.float32, use_flash=False, **kwargs)
    await server.start()
    client = await RpcClient.connect(server.rpc_server.host, server.rpc_server.port)
    return server, client


async def open_session(client, path, max_length: int, **extra):
    uids = CHAIN_DELIMITER.join(make_uid(default_dht_prefix(path), i) for i in range(LAYERS))
    stream = await client.open_stream("ptu.inference")
    await stream.send({"uids": uids, "max_length": max_length, "batch_size": 1, **extra})
    await stream.recv(timeout=60)
    return stream


async def step(stream, hidden, **extra) -> np.ndarray:
    await stream.send({"tensors": {"hidden": serialize_array(hidden)}, **extra})
    return deserialize_array((await stream.recv(timeout=300))["tensors"]["hidden"])


def rows(seed: int, n: int) -> np.ndarray:
    return (np.random.RandomState(seed).randn(1, n, HF["hidden_size"]) * 0.5).astype(np.float32)


# ---------------------------------------------------------------------------------
# the selection, and the block from a checkpoint
# ---------------------------------------------------------------------------------


@pytest.mark.parametrize("scores", ["generic", "ties", "zeros"])
def test_the_selection_is_the_top_k_by_score_with_ties_to_the_lower_position(scores):
    """``select_mask`` (a bisection on the scores' bits, no sort) against a
    sort by (score descending, position ascending): generic scores, scores
    rounded so that the k-th has many equals, and rows mostly of exact zeros
    (what the relu gives); rows that see fewer than k positions keep them all,
    rows that see none keep none."""
    rng = np.random.default_rng(5)
    sc = rng.standard_normal((3, 7, 200)).astype(np.float32)
    if scores == "ties":
        sc = np.round(sc * 2) / 2
    if scores == "zeros":
        sc = np.maximum(sc, 0) * np.where(rng.random(sc.shape) < 0.5, -1.0, 1.0).astype(np.float32)  # +0.0 and -0.0 too
    valid = rng.random(sc.shape) < 0.7
    valid[0, 0, :] = False
    valid[0, 1, :5], valid[0, 1, 5:] = True, False
    got = np.asarray(sparse.select_mask(jnp.asarray(sc), jnp.asarray(valid), TOPK))
    want = np.zeros_like(valid)
    for i, j in np.ndindex(3, 7):
        idx = np.flatnonzero(valid[i, j])
        want[i, j, idx[np.lexsort((idx, -sc[i, j, idx]))][:TOPK]] = True
    assert (got == want).all() and got[0, 1].sum() == 5 and not got[0, 0].any()
    assert int(got.sum(-1).max()) == TOPK


@pytest.mark.parametrize("scores", ["generic", "ties", "zeros"])
def test_a_decode_row_attends_to_its_top_k_by_score_with_ties_to_the_lower_position(scores):
    """``sparse_decode_attend`` itself against plain float32 NumPy: score every
    position the lane sees, sort by (score descending, position ascending),
    softmax over the first ``topk``. Permuted tables with holes past what a
    lane holds; lanes of 50, 10 (fewer than ``topk``: it keeps all), 0 (idle,
    the sentinel position: zeros) and 64 positions (a full table). Pages no
    lane owns hold NaN, and a hole reads row 0 of the pool, which lane 3 owns.
    The index keys have two live coordinates, each met by one index head, so
    a score is ``w0 relu(x) + w1 relu(y)`` to the bit in any order of sums:
    generic values; values rounded so that the k-th score has many equals;
    and rows mostly of exact zeros, ``+0.0`` at the bottom of a lane whose
    weights are positive and ``-0.0`` at the top of one whose weights are
    negative, so that in both the set is closed among equals by position."""
    rng = np.random.default_rng(11)
    lanes, max_pages, ps, hkv, group, d, d_idx = 4, 8, 8, 2, 2, 16, 16
    n_pages, max_length, hq = 30, max_pages * ps, hkv * group
    lengths = np.array([50, 10, 0, 64])
    xy = rng.standard_normal((lanes, max_length, 2)).astype(np.float32)
    w = np.array([[1.0, -0.5], [0.5, 1.0], [1.0, 1.0], [-1.0, 2.0]], np.float32)
    if scores == "ties":
        xy = np.round(xy * 2) / 2
    if scores == "zeros":
        xy = np.where(rng.random(xy.shape) < 0.2, np.maximum(xy, 0), -1.0).astype(np.float32)
        w = np.array([[1.0, 1.0], [1.0, 0.5], [1.0, 1.0], [-1.0, -1.0]], np.float32)
    k_idx = np.zeros((lanes, max_length, d_idx), np.float32)
    k_idx[..., 0], k_idx[..., 5] = xy[..., 0], xy[..., 1]
    q_idx = np.zeros((lanes, 1, 2, d_idx), np.float32)
    q_idx[:, 0, 0, 0] = q_idx[:, 0, 1, 5] = 1.0
    q, k, v = (rng.standard_normal(shape).astype(np.float32) for shape in ((lanes, 1, hq, d), (lanes, max_length, hkv, d), (lanes, max_length, hkv, d)))

    free = rng.permutation(np.arange(1, n_pages))
    tables = np.full((lanes, max_pages), -1, np.int32)
    for lane, length in enumerate(lengths):
        held = -(-length // ps)
        tables[lane, :held], free = free[:held], free[held:]
    tables[3, 2] = 0  # page 0 is somebody's: what a hole reads is real, finite and masked
    rows_a_page, row_width = sparse.index_pool_row(ps, d_idx)
    fold = ps // rows_a_page
    k_pool, v_pool = (np.full((n_pages, ps, hkv, d), np.nan, np.float32) for _ in range(2))
    i_pool = np.full((n_pages, rows_a_page, row_width), np.nan, np.float32)
    for lane in range(lanes):
        for slot, page in enumerate(tables[lane]):
            if page >= 0:
                at = slice(slot * ps, (slot + 1) * ps)
                k_pool[page], v_pool[page] = k[lane, at], v[lane, at]
                i_pool[page] = k_idx[lane, at].reshape(rows_a_page, fold * d_idx)
    positions = np.where(lengths > 0, lengths - 1, max_length).astype(np.int32)
    kv = [PagedKV(jnp.asarray(pool), jnp.asarray(tables)) for pool in (k_pool, v_pool, i_pool)]
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(lambda *a: sparse.sparse_decode_attend(*a, topk=TOPK))(
            jnp.asarray(q), jnp.asarray(q_idx), jnp.asarray(w[:, None]), *kv, jnp.asarray(positions)))

    want, sets = np.zeros((lanes, 1, hq, d), np.float32), []
    for lane, length in enumerate(lengths):
        dots = np.maximum(k_idx[lane, :length] @ q_idx[lane, 0].T, 0)  # [length, 2]
        score = w[lane, 0] * dots[:, 0] + w[lane, 1] * dots[:, 1]
        chosen = np.lexsort((np.arange(length), -score))[:TOPK]
        sets.append((score, chosen))
        for head in range(hq):
            logits = k[lane, chosen, head // group] @ q[lane, 0, head] * d**-0.5
            p = np.exp(logits - logits.max(initial=-np.inf))
            want[lane, 0, head] = (p / max(p.sum(), 1e-30)) @ v[lane, chosen, head // group] if length else 0.0
    assert got.shape == want.shape and np.isfinite(got).all() and not got[2].any()
    assert np.abs(got - want).max() < 1e-5, np.abs(got - want).reshape(lanes, -1).max(-1)
    # the cases are what they claim: the set is closed among equals, and in the zeros' rows at a zero of either sign
    score, chosen = sets[0]
    kth, dropped = score[chosen[-1]], np.setdiff1d(np.arange(lengths[0]), chosen)
    if scores != "generic":
        assert (score[dropped] == kth).any() and dropped[score[dropped] == kth].min() > chosen[score[chosen] == kth].max()
    if scores == "zeros":
        assert kth == 0 and not np.signbit(kth) and np.signbit(sets[3][0][sets[3][1]]).all() and (sets[3][0] < 0).any()


def test_a_chunk_in_runs_of_rows_is_the_chunk_at_once(monkeypatch):
    """``sparse_chunk_attend`` sends a chunk of more than ``CHUNK_ROWS`` rows
    through in runs (at the published widths 512 of a budget's 2,048): the
    same rows, each run's scores and walk cut at its own last row."""
    rng = np.random.default_rng(9)
    n_pages, ps, hkv, d, heads, d_idx = 10, 8, 2, 16, 4, 8
    pools = [jnp.asarray(rng.standard_normal((n_pages, ps, *row)), jnp.float32) for row in ((hkv, d), (hkv, d), (d_idx,))]
    tables = jnp.asarray(rng.permutation(n_pages).astype(np.int32)[None])
    k_kv, v_kv, i_kv = (PagedKV(pool, tables) for pool in pools)
    q, q_idx = jnp.asarray(rng.standard_normal((1, 32, 4, d)), jnp.float32), jnp.asarray(rng.standard_normal((1, 32, heads, d_idx)), jnp.float32)
    w_idx = jnp.asarray(rng.standard_normal((1, 32, heads)), jnp.float32)
    args = (q, q_idx, w_idx, k_kv, v_kv, i_kv, jnp.int32(40), jnp.int32(29))
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(sparse.sparse_chunk_attend(*args, topk=TOPK))
        monkeypatch.setattr(sparse, "CHUNK_ROWS", 8)
        runs = np.asarray(sparse.sparse_chunk_attend(*args, topk=TOPK))
    assert np.abs(runs[0, :29] - whole[0, :29]).max() < 1e-5 and np.isfinite(runs).all()
    assert sparse.chunk_reads(10, 8, TOPK, 40, 29, 32) == (4 * 80, 4 * 8 * 80, 4 * 80)  # a table of 80 positions is one block
    monkeypatch.setattr(sparse, "CHUNK_ROWS", 512)
    assert sparse.chunk_reads(10, 8, TOPK, 40, 29, 32) == (80, 32 * 80, 80) and sparse.chunk_reads(10, 8, TOPK, 0, 16, 16) == (0, 0, 80)


def test_a_checkpoint_s_block_matches_the_reference_and_under_topk_positions_it_is_dense_attention(tiny):
    """``hf_to_block_params`` under the assumed tensor names, and the block
    over 100 positions with no cache (the stateless pass's form) against the
    reference. The selection bites: the reference with every position kept is
    0.2-0.5 away past row 16, and equal to the bit up to it, where the served
    block equals it too."""
    path, tensors = tiny
    family, cfg = get_block_config(path)
    assert family.name == "KeyeVL2" and family.index_for(cfg, None) == (8, None, 16) and family.block_state is None
    assert (cfg.index_heads, cfg.index_dim, cfg.index_topk) == (4, 8, 16)
    params = load_block_params(path, 1, dtype=jnp.float32)
    assert set(params) == set(family.block_param_shapes(cfg))
    x = rows(1, 100)
    want = reference_hidden(tensors, x[0], 1, 2)
    everything = reference_hidden(tensors, x[0], 1, 2, choose=lambda s, k, first: jnp.tril(jnp.ones(s.shape, bool)))
    with jax.default_matmul_precision("highest"):
        out = np.asarray(family.block_apply(params, jnp.asarray(x), None, 0, cfg)[0][0])
    assert off(out, want) < CLOSE
    assert off(everything[TOPK:], want[TOPK:]) > 0.1 and np.array_equal(everything[:TOPK], want[:TOPK])
    assert off(out[:TOPK], everything[:TOPK]) < CLOSE


def test_forward_and_backward_run_a_whole_sequence_under_the_selection_s_mask(tiny):
    """The stateless passes (``rpc_forward`` / ``rpc_backward``: what
    fine-tuning through the swarm calls): the span's output over 60 positions
    against the reference, and the gradient against the reference's, whose
    mask is a constant (a choice of positions has no gradient)."""
    path, tensors = tiny
    backend = whole_backend(path)
    x = rows(4, 60)
    want = reference_hidden(tensors, x[0])
    grad_out = rows(5, 60)
    with jax.default_matmul_precision("highest"):
        assert off(np.asarray(backend.forward(x))[0], want) < CLOSE
        grad = np.asarray(backend.backward(x, grad_out)[0])[0]
        _, vjp = jax.vjp(lambda h: jnp.asarray(_reference_traced(tensors, h)), jnp.asarray(x[0]))
        want_grad = np.asarray(vjp(jnp.asarray(grad_out[0]))[0])
    assert off(grad, want_grad) < 10 * CLOSE


def _reference_traced(tensors, x):
    for i in range(LAYERS):
        x, _ = reference.block(HF, layer_tensors(tensors, i), x)
    return x


# ---------------------------------------------------------------------------------
# the index pool
# ---------------------------------------------------------------------------------


def test_the_index_pool_lies_beside_the_pages_and_a_narrow_row_is_stored_several_positions_to_a_row(tiny):
    path, _ = tiny
    backend = whole_backend(path)
    assert backend.cache.index_row == (8, jnp.dtype(jnp.float32)) and backend.cache.state_layers == () and backend.cache.kv_layers == (0, 1, 2, 3)
    k, v = lane_pools(backend, 12, 16, end=4)[0]
    (index,) = lane_pools(backend, 12, 16)[1]
    assert k.shape == v.shape == (4, 12, 16, 2 * 16)  # rows of 2 kv heads of 16, under 128 lanes: folded over the heads
    assert index.shape == (4, 12, 1, 128) and sparse.index_pool_row(16, 8) == (1, 128)  # 16 positions of 8 to a row of 128
    assert sparse.index_pool_row(8, 8) == (8, 8) and sparse.index_fold(64, 64) == 2 and sparse.index_pool_row(64, 64) == (32, 128)
    assert sparse.index_pool_row(64, 128) == (64, 128) and sparse.index_pool_row(64, 96) == (64, 96)
    assert backend.cache.index_bytes_per_token() == 4 * 8 * 4
    assert backend.cache.cache_bytes_per_token() == backend.cache.kv_bytes_per_token() == 4 * (2 * 2 * 16 + 8) * 4
    # rows written through permuted tables land where the fold says, and nowhere else
    pool = jnp.zeros((6, 2, 64), jnp.float32)  # 6 pages of 16 positions of width 8: fold 8, two rows a page
    tables = jnp.asarray([[4, 1, -1], [0, 5, 2]], jnp.int32)
    new = jnp.arange(2 * 8, dtype=jnp.float32).reshape(2, 1, 8) + 1
    got = np.asarray(sparse.scatter_index_rows(PagedKV(pool, tables), new, jnp.asarray([21, 48], jnp.int32), None, 16).pool)
    assert np.array_equal(got[1, 0, 40:48], np.asarray(new[0, 0])) and np.count_nonzero(got) == 8  # lane 1 rides the idle sentinel
    chunk = jnp.arange(5 * 8, dtype=jnp.float32).reshape(1, 5, 8) + 1
    got = np.asarray(sparse.scatter_index_rows(PagedKV(pool, tables[1:]), chunk, 30, 3, 16).pool)  # positions 30, 31, 32; two padded
    assert np.array_equal(got[5, 1, 48:], np.asarray(chunk[0, :2]).reshape(-1)) and np.array_equal(got[2, 0, :8], np.asarray(chunk[0, 2]))
    assert np.count_nonzero(got) == 3 * 8


def test_the_published_span_s_cache_is_2176_bytes_a_position_a_layer_and_its_lanes_fit_the_stated_budget():
    """keye-vl2-30b-a3b-span5 on shapes alone: a position caches 2,048 B of
    keys and values and 128 B of index key a layer, 10,880 B over the five;
    the configuration's 8 lanes of 32,768 fit the budget it states, and
    ``Server``'s auto-sizing (half the budget) would afford 4; counted
    without the index rows a lane would read 5.9% short."""
    from pathlib import Path

    from perf.config import load as load_config

    root = Path(__file__).resolve().parents[1]
    config = load_config(root / "perf/configs/keye-vl2-30b-a3b-span5.json", "keye-vl2-30b-a3b-span5")
    import tempfile

    with tempfile.TemporaryDirectory() as tmp:
        (Path(tmp) / "config.json").write_text(json.dumps(config["config"]))
        family, cfg = get_block_config(tmp)
    S = jax.ShapeDtypeStruct
    params = {name: S((5, *leaf.shape), leaf.dtype) for name, leaf in family.block_param_shapes(cfg, jnp.bfloat16).items()}
    assert sum(int(np.prod(leaf.shape[1:])) for name, leaf in params.items() if name in ("iq", "ik", "iw")) == 2_260_992
    assert sum(int(np.prod(leaf.shape[1:])) for leaf in params.values()) == 625_377_280 + 4 * 2048 // 2 * 0 + 2 * 2048 + 2 * 128 + 2 * 64
    backend = TransformerBackend(family, cfg, params, first_block=0, n_blocks=5, memory_cache=None)
    args = config["server_args"]
    assert backend.cache.index_row == (64, jnp.dtype(jnp.bfloat16))
    assert backend.cache.kv_bytes_per_token() == backend.cache.cache_bytes_per_token() == 5 * 2176 == 10_880 and backend.cache.index_bytes_per_token() == 5 * 128
    k, v = lane_pools(backend, 8 * 512, 64, end=5)[0]
    (index,) = lane_pools(backend, 8 * 512, 64)[1]
    assert k.shape == (5, 4096, 64, 4, 128) and index.shape == (5, 4096, 32, 128)
    pool = sum(int(np.prod(d.shape)) * 2 for d in (k, v, index))
    lane = backend.cache.cache_bytes_per_token() * args["batch_max_length"]
    assert pool == args["batch_lanes"] * lane == 2_852_126_720 <= args["attn_cache_bytes"]
    assert args["attn_cache_bytes"] // 2 // lane == 4  # what Server._make_handler would size with no batch_lanes given
    assert args["attn_cache_bytes"] // 2 // (5 * 2048 * args["batch_max_length"]) == 4 and (2176 - 2048) / 2176 == pytest.approx(0.0588, abs=1e-4)


def test_lane_auto_sizing_and_the_occupancy_count_the_index_rows(tiny):
    """``Server`` with no ``batch_lanes``: a lane costs its pages of keys and
    values and of index rows, and the budget is halved."""
    path, _ = tiny

    async def main():
        backend = whole_backend(path)
        per_token = backend.cache.cache_bytes_per_token()
        assert per_token == 1152 and (2 * 5 * per_token * 32 + 100) // 2 // ((per_token - 128) * 32) == 5
        server = Server(path, compute_dtype=jnp.float32, use_flash=False, batch_max_length=32, page_size=16,
                        attn_cache_bytes=2 * 4 * per_token * 32 + 9 * per_token, prefix_cache_bytes=0)
        await server.start()
        try:
            batcher = server.handler.batcher
            assert batcher.n_lanes == 4 and batcher.backend.cache.index_row is not None and len(batcher.backend.cache.lane_state) == 0
            await batcher.ensure_open()
            info = batcher.occupancy_info()
            assert info["kv_bytes_per_token"] == per_token and info["index_bytes_per_token"] == 128
            assert len(batcher._state()) == 1 and batcher._state()[0].shape == (4, 8, 1, 128) and len(batcher._buffers()) == 2
        finally:
            await server.shutdown()

    run(main())


# ---------------------------------------------------------------------------------
# through Server and the paged lane pool
# ---------------------------------------------------------------------------------


def test_prompt_in_mixed_steps_beside_two_decoding_lanes_of_other_lengths_then_decode_matches_the_reference(tiny):
    """Sessions B (a context of 70 and more) and C (under ``topk`` at first)
    decode while A's prompt of 100 rides seven mixed steps of 16, each row of
    a chunk choosing its own 16 of the positions its lane holds and the
    chunk's own; then all three decode at once at contexts of different
    lengths over permuted pages. Every row of every session against the
    reference's whole forward pass; the counters say what the rows did."""
    path, tensors = tiny

    async def main():
        server, client = await start_server(path, batch_lanes=3, batch_max_length=160, page_size=16, n_pages=26, prefill_token_budget=16)
        try:
            batcher = server.handler.batcher
            assert batcher.page_size == 16 and server.handler.prefix_cache is None and SPARSE_KEYS <= set(batcher.stats)
            a_rows, b_rows, c_rows = rows(1, 130), rows(2, 140), rows(3, 60)
            b, c = await open_session(client, path, 160), await open_session(client, path, 160)
            got_b, got_c = [await step(b, b_rows[:, :70])], [await step(c, c_rows[:, :3])]
            before = dict(batcher.stats)
            a = await open_session(client, path, 160)

            async def decode(stream, data, got, start, until):
                pos = start
                while not until.is_set() and pos < data.shape[1] - 14:
                    got.append(await step(stream, data[:, pos : pos + 1]))
                    pos += 1
                return pos

            done = asyncio.Event()

            async def prompt():
                out = await step(a, a_rows[:, :100])
                done.set()
                return out

            got_a, pos_b, pos_c = await asyncio.gather(prompt(), decode(b, b_rows, got_b, 70, done), decode(c, c_rows, got_c, 3, done))
            got_a = [got_a]
            assert batcher.stats["mixed_steps"] - before["mixed_steps"] == 7 and batcher.stats["prefill_tokens"] - before["prefill_tokens"] == 100
            assert not batcher.paged_summary()["tables_contiguous"]
            for i in range(12):  # all three decode at once
                outs = await asyncio.gather(step(a, a_rows[:, 100 + i : 101 + i]), step(b, b_rows[:, pos_b + i : pos_b + i + 1]),
                                            step(c, c_rows[:, pos_c + i : pos_c + i + 1]))
                for got, out in zip((got_a, got_b, got_c), outs):
                    got.append(out)
            await steps_booked(batcher)
            now = batcher.stats
            fed = 100 + (pos_b - 70) + (pos_c - 3) + 3 * 12  # rows through the span since ``before``
            assert (now["sparse_rows_selected"] - before["sparse_rows_selected"]) + (now["sparse_rows_dense"] - before["sparse_rows_dense"]) == fed * LAYERS
            assert now["sparse_rows_dense"] - before["sparse_rows_dense"] >= (TOPK + min(pos_c - 3, TOPK - 3)) * LAYERS  # A's first 16 rows, C's first 13
            assert now["sparse_rows_selected"] - before["sparse_rows_selected"] >= (84 + 12 * 2) * LAYERS
            assert 0 < now["sparse_kv_rows_read"] - before["sparse_kv_rows_read"] < now["sparse_kv_rows_held"] - before["sparse_kv_rows_held"]
            assert now["sparse_index_rows_scored"] > before["sparse_index_rows_scored"]
            # the pages' counters count what was fetched in pages' worth, not a walk of the tables that no program made
            paged = (now["attn_pages_gathered"] - before["attn_pages_gathered"]) * batcher.page_size
            assert 0 <= paged - (now["sparse_kv_rows_read"] - before["sparse_kv_rows_read"]) < (7 + 12 + pos_b - 70 + pos_c - 3) * batcher.page_size
            assert (now["index_bytes_held"] - before["index_bytes_held"]) * (1152 - 128) == (now["kv_bytes_held"] - before["kv_bytes_held"]) * 128
            info = await client.call("ptu.info", {})
            assert SPARSE_KEYS <= set(info["continuous_batching"]) and info["pool"]["index_bytes_per_token"] == 128
            for got, data in ((got_a, a_rows), (got_b, b_rows), (got_c, c_rows)):
                got = np.concatenate(got, axis=1)[0]
                assert off(got, reference_hidden(tensors, data[0, : got.shape[0]])) < CLOSE
            # a lane given back and taken again: the pages' old index rows lie past the new tenant's length
            await a.end()
            again = await open_session(client, path, 160)
            d_rows = rows(7, 40)
            out = [await step(again, d_rows[:, :30])] + [await step(again, d_rows[:, p : p + 1]) for p in range(30, 40)]
            assert off(np.concatenate(out, axis=1)[0], reference_hidden(tensors, d_rows[0])) < CLOSE
            for stream in (again, b, c):
                await stream.end()
        finally:
            await client.close()
            await server.shutdown()

    run(main())


@pytest.fixture(scope="module")
def swarm(tiny):
    """A chain of two spans on the default server."""
    path, tensors = tiny
    specs = [dict(first_block=0, num_blocks=3, page_size=8, batch_max_length=96, prefill_token_budget=32),
             dict(first_block=3, num_blocks=1, page_size=16, batch_max_length=96, prefill_token_budget=32)]
    harness = SwarmHarness(path, specs).start()
    model = AutoDistributedModelForCausalLM.from_pretrained(path, initial_peers=harness.initial_peers)
    yield path, tensors, harness, model
    model.close()
    harness.stop()


def test_remote_sequential_session_prefill_in_chunks_then_decode_matches_the_reference_s_logits(swarm):
    """Through ``Server`` with no flag and ``RemoteSequential`` over a chain of
    two spans (index rows a position to a row on one, 16 to a row on the
    other): a prompt of 70 in three mixed steps a server, then decode; the
    LOGITS of every position against the reference's whole forward pass."""
    path, tensors, harness, model = swarm
    batchers = [server.handler.batcher for server in harness.servers]
    assert all(b is not None and b.backend.cache.index_row is not None for b in batchers) and [b.page_size for b in batchers] == [8, 16]
    before = [dict(b.stats) for b in batchers]
    ids = np.random.RandomState(3).randint(0, 128, (1, 85)).astype(np.int64)
    hidden = np.asarray(model.embed(ids))
    with model.remote.inference_session(max_length=85) as session:
        outs = [np.asarray(session.step(hidden[:, :70]))]
        outs += [np.asarray(session.step(hidden[:, p : p + 1])) for p in range(70, 85)]
    logits = np.asarray(model.lm_logits(np.concatenate(outs, axis=1)))[0]
    np.testing.assert_allclose(logits, reference_logits(tensors, ids[0]), atol=3e-4, rtol=0)
    for batcher, was in zip(batchers, before):
        layers = len(batcher.backend.cache.kv_layers)
        assert batcher.stats["mixed_steps"] - was["mixed_steps"] == 3
        assert batcher.stats["sparse_rows_selected"] - was["sparse_rows_selected"] == (85 - TOPK) * layers
        assert batcher.stats["sparse_rows_dense"] - was["sparse_rows_dense"] == TOPK * layers


def test_generate_token_identical_and_forward_through_a_chain_of_two_spans(swarm):
    path, tensors, _, model = swarm
    ids = np.random.RandomState(6).randint(0, 128, (1, 30)).astype(np.int64)
    got = np.asarray(model.generate(ids, max_new_tokens=6))
    want = list(ids[0])
    for _ in range(6):
        want.append(int(np.argmax(reference_logits(tensors, want)[-1])))
    np.testing.assert_array_equal(got[0], want)
    hidden = np.asarray(model.embed(ids))
    out = np.asarray(model.remote.forward(hidden))  # rpc_forward: the whole sequence under the selection's mask
    assert off(out[0], reference_hidden(tensors, hidden[0])) < CLOSE


# ---------------------------------------------------------------------------------
# what is refused, and why
# ---------------------------------------------------------------------------------


@pytest.mark.parametrize("key,value,named", [
    ("hidden_act", "gelu", "hidden_act"), ("attention_bias", True, "attention_bias"), ("use_sliding_window", True, "use_sliding_window"),
    ("decoder_sparse_step", 2, "a layer without experts"), ("rope_scaling", {"rope_type": "yarn", "factor": 4.0}, "rope_scaling"),
    ("sa_config", {**HF["sa_config"], "indexer_num_kv_heads": 2}, "indexer_num_kv_heads"),
])
def test_what_the_block_does_not_compute_is_refused_at_load(tmp_path, key, value, named):
    (tmp_path / "config.json").write_text(json.dumps({**HF, key: value}))
    with pytest.raises(NotImplementedError, match=f"KeyeVL2: {named}"):
        get_block_config(str(tmp_path))


REFUSED_BY_THE_BACKEND = {
    "a private cache": lambda b: b.cache_descriptors(1, 32, 0, LAYERS),
    "a step on a private cache": lambda b: b.inference_step(rows(0, 4), (None, None), 0),
    "speculative verify": lambda b: b.paged_spec_verify_step(None, np.zeros((2, 3), np.int32), (None, None), np.zeros(2, np.int32),
                                                             np.zeros((2, 2), np.int32), sampling_vecs={}),
    "server-side generation on a private cache": lambda b: b.generate_tokens({}, rows(0, 1), (None, None), 4, 2),
    "the dense lane pool": lambda b: DecodeBatcher(b, b.memory_cache, PriorityTaskQueue(), n_lanes=2, max_length=32, page_size=None),
    "the dense lane pool's step": lambda b: b._batched_decode_fn,
    "the host swap tier": lambda b: DecodeBatcher(b, b.memory_cache, PriorityTaskQueue(), n_lanes=2, max_length=32, page_size=8,
                                                  swap_host_bytes=1 << 20),
}


@pytest.mark.parametrize("what", sorted(REFUSED_BY_THE_BACKEND))
def test_cache_paths_that_do_not_carry_the_index_rows_refuse_them_with_the_reason(tiny, what):
    backend = whole_backend(tiny[0])
    with pytest.raises(NotImplementedError, match="KeyeVL2: .* index row beside .*only the paged lane pool's decode, generation and mixed steps"):
        REFUSED_BY_THE_BACKEND[what](backend)


def test_options_the_family_cannot_take_yet_are_refused(tiny, tmp_path):
    """A tp mesh, quantized weights, quantized pages, a LoRA adapter and a
    draft model: refused with the family's name."""
    from petals_tpu.parallel.mesh import tp_mesh
    from petals_tpu.utils.convert_block import QuantType, convert_block_params
    from petals_tpu.utils.peft import load_adapter
    from safetensors.numpy import save_file

    path, _ = tiny
    family, cfg = get_block_config(path)
    assert family.tp_pspecs is None and not family.quantizable_leaves and not family.lora_targets
    with pytest.raises(KeyError, match="No TP spec for family 'KeyeVL2'"):  # and were it to declare them: backend._check_index
        whole_backend(path, mesh=tp_mesh(2))
    for kind in ("int8", "nf4a"):
        with pytest.raises(NotImplementedError, match=f"KeyeVL2: kv_quant_type '{kind}'.*index row"):
            whole_backend(path, kv_quant_type=kind)
    with pytest.raises(ValueError, match="KeyeVL2"):
        convert_block_params(dict(load_block_params(path, 1, dtype=jnp.float32)), "KeyeVL2", QuantType.NF4)
    (tmp_path / "adapter_config.json").write_text(json.dumps({"r": 2, "lora_alpha": 4, "target_modules": ["q_proj"], "peft_type": "LORA"}))
    save_file({"base_model.model.model.layers.1.self_attn.q_proj.lora_A.weight": np.zeros((2, 64), np.float32),
               "base_model.model.model.layers.1.self_attn.q_proj.lora_B.weight": np.zeros((64, 2), np.float32)},
              str(tmp_path / "adapter_model.safetensors"))
    with pytest.raises(ValueError, match="KeyeVL2"):
        load_adapter(str(tmp_path), "KeyeVL2", block_range=range(0, LAYERS))
    backend = whole_backend(path)

    class Draft:
        spec_k = 2

    with pytest.raises(NotImplementedError, match="KeyeVL2: speculative decoding .* index row"):
        DecodeBatcher(backend, backend.memory_cache, PriorityTaskQueue(), n_lanes=2, max_length=32, page_size=8,
                      gen_params={}, draft_model=Draft())


def test_what_ships_or_cuts_a_cache_is_refused_over_the_wire_and_the_prefix_cache_is_off(tiny):
    """``kv_adopt``, a session export (what migration and parking ship), a
    rollback behind the position and a session that would take a private
    cache: each error names the reason. The server's default prefix cache is
    switched off for the span."""
    path, tensors = tiny

    async def main():
        server, client = await start_server(path, batch_lanes=2, batch_max_length=64, page_size=8)  # prefix_cache_bytes: the default
        try:
            assert server.handler.prefix_cache is None and server.handler.batcher.backend.cache.index_row is not None
            data = rows(21, 40)
            stream = await open_session(client, path, 64)
            await step(stream, data[:, :30])
            with pytest.raises(Exception, match="start_from_position 5 behind the cache's position 30.*index row"):
                await step(stream, data[:, 5:6], start_from_position=5)
            stream = await open_session(client, path, 64)
            await step(stream, data[:, :8])
            again = await step(stream, data[:, :40], start_from_position=0)  # from the start: served
            assert off(again[0], reference_hidden(tensors, data[0])) < CLOSE
            with pytest.raises(Exception, match="kv_adopt / kv_import.*index row"):
                await stream.send({"kv_adopt": {"session_id": "x", "position": 4}})
                await stream.recv(timeout=60)
            live = await open_session(client, path, 64, session_id="live-one")
            await step(live, data[:, :8])
            with pytest.raises(Exception, match="a snapshot of a lane's cache.*index row"):
                await client.call("ptu.session_export", {"session_id": "live-one", "start": 0, "end": LAYERS})
            await live.end()
            uids = CHAIN_DELIMITER.join(make_uid(default_dht_prefix(path), i) for i in range(LAYERS))
            wide = await client.open_stream("ptu.inference")  # two sequences a session take no lane
            await wide.send({"uids": uids, "max_length": 32, "batch_size": 2})
            with pytest.raises(Exception, match="a private cache.*index row"):
                await wide.recv(timeout=60)
        finally:
            await client.close()
            await server.shutdown()

    run(main())


def test_a_family_without_an_index_row_opens_the_pools_and_programs_it_had(tmp_path):
    from tests.utils import make_tiny_falcon

    path = make_tiny_falcon(str(tmp_path))
    family, cfg = get_block_config(path)
    assert family.block_index is None and family.index_for(cfg, None) is None
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *(load_block_params(path, i, dtype=jnp.float32) for i in range(2)))
    backend = TransformerBackend(family, cfg, stacked, first_block=0, n_blocks=2, memory_cache=MemoryCache(None),
                                 compute_dtype=jnp.float32, use_flash=False)
    assert backend.cache.index_row is None and lane_pools(backend, 6, 8)[1] == () and backend.cache.index_bytes_per_token() == 0
    batcher = DecodeBatcher(backend, backend.memory_cache, PriorityTaskQueue(), n_lanes=3, max_length=24, page_size=8)
    assert batcher.backend.cache.index_row is None and not SPARSE_KEYS & set(batcher.stats) and "index_bytes_per_token" not in batcher.occupancy_info()
