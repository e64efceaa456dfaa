"""``deepseek_v3`` (Kanana-2-30B-A3B's model_type) on the normal path, at a toy
size on the CPU: a block whose positions cache ONE latent row for all heads in
place of keys and values. The block from a checkpoint against the in-repo
reference (perf/reference/deepseek_v3.py) and against transformers' own
``DeepseekV3DecoderLayer`` (the interleaved rotary, the router's bias, the
shared experts); the three forms of the attention against each other; prefill
in chunks and decode beside other lanes through ``Server`` and the paged lane
pool against the reference's whole forward pass; the stateless forward and
backward passes; the pools (their stored form, the sizing, the counters); what
the family refuses, each with its reason."""

import asyncio
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.reference import deepseek_v3 as reference
from petals_tpu.client.model import AutoDistributedModelForCausalLM
from petals_tpu.data_structures import CHAIN_DELIMITER, make_uid
from petals_tpu.ops import latent_attention as latent
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
from tests.utils import counted, lane_pools, make_tiny_deepseek_v3, steps_booked, tiny_deepseek_v3_tensors, TINY_DEEPSEEK_V3

HF = dict(TINY_DEEPSEEK_V3)
LAYERS, KINDS = HF["num_hidden_layers"], reference.layer_kinds(HF)
ROW = (HF["kv_lora_rank"] + HF["qk_rope_head_dim"]) * 4  # bytes a position a layer in float32
LATENT_KEYS = {"latent_rows_read", "latent_rows_held", "latent_rows_absorbed", "latent_rows_expanded", "latent_positions_expanded",
               "latent_positions_held", "latent_score_pairs", "latent_bytes_held"}
# float32 on the CPU, the served path against the reference, as a share of the largest output: they differ in
# the order of float32 sums (measured 4e-7..5e-6); a row that read another lane's page lands near 1
CLOSE = 5e-5


def run(coro):
    return asyncio.run(coro)


def layer_tensors(tensors: dict, layer: int) -> dict:
    prefix = f"model.layers.{layer}."
    return {k[len(prefix):]: jnp.asarray(v) for k, v in tensors.items() if k.startswith(prefix)}


def reference_hidden(tensors: dict, hidden, first: int = 0, last: int = LAYERS, hf: dict = HF, **kw) -> np.ndarray:
    """``hidden`` [seq, h] through layers [first, last) of the reference."""
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(hidden, jnp.float32)
        for i in range(first, last):
            x, _ = reference.block(hf, layer_tensors(tensors, i), x, KINDS[i], **kw)
    return np.asarray(x)


def reference_logits(tensors: dict, ids) -> np.ndarray:
    x = reference_hidden(tensors, tensors["model.embed_tokens.weight"][np.asarray(ids)])
    x = x / np.sqrt((x * x).mean(-1, keepdims=True) + HF["rms_norm_eps"]) * tensors["model.norm.weight"]
    return x @ tensors["lm_head.weight"].T


def off(got, want) -> float:
    return float(np.abs(np.asarray(got) - want).max() / np.abs(want).max())


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return make_tiny_deepseek_v3(str(tmp_path_factory.mktemp("models"))), tiny_deepseek_v3_tensors(HF)


def whole_backend(path: str, **kw) -> TransformerBackend:
    family, cfg = get_block_config(path)
    runs = []
    for kind, start, length in ((KINDS[0], 0, 1), (KINDS[1], 1, LAYERS - 1)):
        runs.append(jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *(load_block_params(path, start + i, dtype=jnp.float32) for i in range(length))))
    return TransformerBackend(family, cfg, tuple(runs), first_block=0, n_blocks=LAYERS, memory_cache=MemoryCache(None),
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
# the block from a checkpoint: the reference, and transformers' own layer
# ---------------------------------------------------------------------------------


@pytest.mark.parametrize("layer", [0, 1], ids=["dense", "sparse"])
def test_a_checkpoint_s_block_matches_the_reference(tiny, layer):
    """``hf_to_block_params`` under transformers' names (the rope columns
    de-interleaved, ``kv_b_proj`` cut into ``wuk`` and ``wuv``), and the block
    over 40 positions with no cache (the stateless pass's form, expanded)
    against the reference, which rotates in the published, interleaved form."""
    path, tensors = tiny
    family, cfg = get_block_config(path)
    kind = family.kind_of(cfg, layer)
    assert family.name == "deepseek_v3" and kind == KINDS[layer] and family.latent_for(cfg, kind) == (32, 8)
    assert family.block_state is None and family.block_index is None and (family.moe_dims_for(cfg, kind) is None) == (layer == 0)
    params = load_block_params(path, layer, dtype=jnp.float32)
    shapes = family.param_shapes_for(cfg, kind)
    assert set(params) == set(shapes) and all(params[name].shape == shapes[name].shape for name in shapes)
    assert params["wuk"].shape == (4, 16, 32) and params["wuv"].shape == (4, 32, 16) and ("ws1" in params) == (layer == 1)
    x = rows(1, 40)
    with jax.default_matmul_precision("highest"):
        out = np.asarray(family.apply_for(kind)(params, jnp.asarray(x), None, 0, cfg)[0][0])
    assert off(out, reference_hidden(tensors, x[0], layer, layer + 1)) < CLOSE


def test_the_reference_is_transformers_layer_interleaved_rotary_biased_choice_unbiased_weights_and_shared_experts(tiny):
    """The reference (and so the served block) against transformers'
    ``DeepseekV3ForCausalLM`` on the same tensors, layer by layer. What each
    piece is worth, on the reference itself: the rotary taken as halves where
    the checkpoint pairs its columns, the router choosing without its bias,
    the bias left in the weights, and the shared experts dropped are each far
    outside what separates the two implementations."""
    import torch
    from transformers import DeepseekV3Config, DeepseekV3ForCausalLM

    _, tensors = tiny
    model = DeepseekV3ForCausalLM(DeepseekV3Config(**{k: v for k, v in HF.items() if k != "model_type"})).eval()
    loaded = model.load_state_dict({k: torch.tensor(v) for k, v in tensors.items()}, strict=False)
    assert not loaded.missing_keys and not loaded.unexpected_keys
    x = rows(2, 48)
    with torch.no_grad():
        theirs = model.model(inputs_embeds=torch.tensor(x), output_hidden_states=True).hidden_states
    for layer in range(LAYERS - 1):  # the last of ``hidden_states`` is under the final norm
        assert off(reference_hidden(tensors, x[0], 0, layer + 1), theirs[layer + 1][0].numpy()) < CLOSE
    want = reference_hidden(tensors, x[0], 1, 2)
    assert off(reference_hidden(tensors, x[0], 1, 2, hf={**HF, "rope_interleave": False}), want) > 1e-2
    no_bias = {**tensors, "model.layers.1.mlp.gate.e_score_correction_bias": np.zeros(HF["n_routed_experts"], np.float32)}
    assert off(reference_hidden(no_bias, x[0], 1, 2), want) > 1e-2  # the bias chooses
    no_shared = {k: (np.zeros_like(v) if "layers.1.mlp.shared_experts.down_proj" in k else v) for k, v in tensors.items()}
    assert off(reference_hidden(no_shared, x[0], 1, 2), want) > 1e-2
    # the bias does not weigh: a bias that moves no choice (the same on every expert) changes nothing
    shifted = {**tensors, "model.layers.1.mlp.gate.e_score_correction_bias": tensors["model.layers.1.mlp.gate.e_score_correction_bias"] + 0.25}
    assert off(reference_hidden(shifted, x[0], 1, 2), want) < 1e-6


def test_the_rope_columns_folded_to_halves_rotate_as_transformers_interleaved_form():
    """``apply_rotary_pos_emb_interleave`` on pairs ``(2j, 2j + 1)`` against
    rotate-half on the columns in the order ``rope_halves`` gives them (what
    ``hf_to_block_params`` folds into ``wq`` and ``wkva``): the same vectors,
    so every ``q_pe . k_pe`` is the same."""
    import torch
    from transformers.models.deepseek_v3.modeling_deepseek_v3 import apply_rotary_pos_emb_interleave

    from petals_tpu.models.deepseek_v3.block import rope_halves
    from petals_tpu.ops.rotary import apply_rotary, rotary_tables

    rng = np.random.default_rng(3)
    q, k = rng.standard_normal((1, 4, 9, 8)).astype(np.float32), rng.standard_normal((1, 1, 9, 8)).astype(np.float32)  # [b, h, s, d]
    positions = np.arange(9, dtype=np.int32)[None]
    cos, sin = rotary_tables(jnp.asarray(positions), 8, theta=1e6)
    theirs_q, theirs_k = apply_rotary_pos_emb_interleave(torch.tensor(q), torch.tensor(k), torch.tensor(np.asarray(cos)), torch.tensor(np.asarray(sin)))
    order = rope_halves(8)
    assert list(order) == [0, 2, 4, 6, 1, 3, 5, 7]
    mine_q = np.asarray(apply_rotary(jnp.asarray(q[..., order]).transpose(0, 2, 1, 3), cos, sin)).transpose(0, 2, 1, 3)
    mine_k = np.asarray(apply_rotary(jnp.asarray(k[..., order]).transpose(0, 2, 1, 3), cos, sin)).transpose(0, 2, 1, 3)
    assert np.abs(mine_q - theirs_q.numpy()).max() < 1e-6 and np.abs(mine_k - theirs_k.numpy()).max() < 1e-6


def test_forward_and_backward_run_a_whole_sequence_in_the_expanded_form(tiny):
    """The stateless passes (``rpc_forward`` / ``rpc_backward``: what
    fine-tuning through the swarm calls): the span's output over 60 positions
    against the reference, and the gradient against the reference's."""
    path, tensors = tiny
    backend = whole_backend(path)
    x, grad_out = rows(4, 60), rows(5, 60)

    def traced(h):
        for i in range(LAYERS):
            h, _ = reference.block(HF, layer_tensors(tensors, i), h, KINDS[i])
        return h

    with jax.default_matmul_precision("highest"):
        assert off(np.asarray(backend.forward(x))[0], reference_hidden(tensors, x[0])) < CLOSE
        grad = np.asarray(backend.backward(x, grad_out)[0])[0]
        _, vjp = jax.vjp(traced, jnp.asarray(x[0]))
        want_grad = np.asarray(vjp(jnp.asarray(grad_out[0]))[0])
    assert off(grad, want_grad) < 10 * CLOSE


# ---------------------------------------------------------------------------------
# the three forms of the attention, and the pools
# ---------------------------------------------------------------------------------


ABSORBED_CASES = [  # path, the lanes' lengths, table slots a grid step of the kernel (pages of 8 positions)
    pytest.param("composed", (50, 10, 0, 64), None, id="composed"),
    pytest.param("kernel", (50, 10, 0, 64), 2, id="kernel"),
    pytest.param("kernel", (5, 15, 0, 9), 2, id="kernel-lanes-shorter-than-a-block"),
    pytest.param("kernel", (16, 32, 8, 48), 2, id="kernel-lanes-that-end-on-a-block-s-and-on-a-page-s-last-position"),
    pytest.param("kernel", (51, 33, 1, 7), 2, id="kernel-odd-lengths-and-a-lane-of-one-position"),
    pytest.param("kernel", (0, 0, 0, 0), 2, id="kernel-no-live-lane"),
    pytest.param("kernel", (50, 10, 0, 64), 3, id="kernel-blocks-that-do-not-divide-the-table"),
    pytest.param("kernel", (50, 10, 0, 64), 8, id="kernel-a-block-of-the-whole-table"),
]


@pytest.mark.parametrize("path,lengths,pages", ABSORBED_CASES)
def test_absorbed_equals_expanded_over_ragged_lanes_permuted_pages_an_idle_lane_and_a_full_table(monkeypatch, path, lengths, pages):
    """``latent_decode_attend`` (absorbed, the rows met as the pools store
    them, the rotated keys two positions to a row) against
    ``latent_attend_dense`` (expanded, no cache) and plain float32 NumPy, a
    lane: lanes of 50, 10, 0 (idle, the sentinel position: zeros) and 64
    positions (a full table) over permuted tables with holes past what a lane
    holds; pages no lane owns hold NaN, and a hole reads page 0, which lane 3
    owns (or nobody, finite). And ``latent_chunk_attend`` (expanded inside a
    walk): a chunk of 24 rows, 19 of them real, from position 30 of lane 0, in
    blocks of one page. As cases, the composed walk and the kernel
    (interpreted), and the kernel's own edges: lanes shorter than a block of
    ``pages`` table slots, lanes that end on a block's and on a page's last
    position, odd lengths (the last pool row of rotated keys half filled) and
    a lane of one position, no live lane, blocks that do not divide the table
    and one of the whole table."""
    if pages is not None:
        monkeypatch.setattr(latent, "DECODE_KERNEL_PAGES", pages)
    rng = np.random.default_rng(11)
    lanes, max_pages, ps, heads, dn, dr, dv, C = 4, 8, 8, 4, 16, 64, 16, 32
    n_pages, max_length = 30, max_pages * ps
    lengths = np.array(lengths)
    c, k_pe = rng.standard_normal((lanes, max_length, C)).astype(np.float32), rng.standard_normal((lanes, max_length, dr)).astype(np.float32)
    q_nope, q_pe = rng.standard_normal((lanes, 1, heads, dn)).astype(np.float32), rng.standard_normal((lanes, 1, heads, dr)).astype(np.float32)
    w_uk, w_uv = rng.standard_normal((heads, dn, C)).astype(np.float32) * 0.3, rng.standard_normal((heads, C, dv)).astype(np.float32) * 0.3
    scale = (dn + dr) ** -0.5
    free = rng.permutation(np.arange(1, n_pages))
    tables = np.full((lanes, max_pages), -1, np.int32)
    for lane, length in enumerate(lengths):
        held = -(-length // ps)
        tables[lane, :held], free = free[:held], free[held:]
    if lengths[3] > 2 * ps:
        tables[3, 2] = 0  # page 0 is somebody's: what a hole reads is real, finite and masked
    (c_rows, c_width), (pe_rows, pe_width) = latent.latent_pool_rows(ps, C, dr)
    assert (c_rows, c_width, pe_rows, pe_width) == (8, 32, 4, 128)  # a rotated key of 64: two positions to a row of 128
    c_pool, pe_pool = np.full((n_pages, c_rows, c_width), np.nan, np.float32), np.full((n_pages, pe_rows, pe_width), np.nan, np.float32)
    for lane in range(lanes):
        for slot, page in enumerate(tables[lane]):
            if page >= 0:
                at = slice(slot * ps, (slot + 1) * ps)
                c_pool[page], pe_pool[page] = c[lane, at], k_pe[lane, at].reshape(pe_rows, pe_width)
    if np.isnan(c_pool[0]).any():  # nobody's: finite all the same
        c_pool[0], pe_pool[0] = 3.0, -3.0
    positions = np.where(lengths > 0, lengths - 1, max_length).astype(np.int32)
    c_kv, pe_kv = PagedKV(jnp.asarray(c_pool), jnp.asarray(tables)), PagedKV(jnp.asarray(pe_pool), jnp.asarray(tables))
    with jax.default_matmul_precision("highest"):
        u = latent.latent_decode_attend(latent.absorb_queries(jnp.asarray(q_nope), jnp.asarray(w_uk)), jnp.asarray(q_pe), c_kv, pe_kv,
                                        jnp.asarray(positions), scale=scale, path=path)
        got = np.asarray(latent.expand_outputs(u, jnp.asarray(w_uv)))
    want = np.zeros((lanes, 1, heads, dv), np.float32)
    for lane, length in enumerate(lengths):
        k_nope, v = np.einsum("sc,hdc->shd", c[lane, :length], w_uk), np.einsum("sc,hcv->shv", c[lane, :length], w_uv)
        for head in range(heads):
            logits = (k_nope[:, head] @ q_nope[lane, 0, head] + k_pe[lane, :length] @ q_pe[lane, 0, head]) * scale
            p = np.exp(logits - logits.max(initial=-np.inf))
            want[lane, 0, head] = (p / max(p.sum(), 1e-30)) @ v[:, head] if length else 0.0
    assert got.shape == want.shape and np.isfinite(got).all() and not got[lengths == 0].any()
    assert np.abs(got - want).max() < 1e-5, np.abs(got - want).reshape(lanes, -1).max(-1)
    if lengths[0] != 50:  # the other two forms are held to lane 0's 50 rows
        return
    with jax.default_matmul_precision("highest"):  # the last row of the dense form over a lane's whole sequence: the same row
        q_all = np.zeros((1, 50, heads, dn), np.float32), np.zeros((1, 50, heads, dr), np.float32)
        q_all[0][0, -1], q_all[1][0, -1] = q_nope[0, 0], q_pe[0, 0]
        dense = latent.latent_attend_dense(jnp.asarray(q_all[0]), jnp.asarray(q_all[1]), jnp.asarray(c[:1, :50]), jnp.asarray(k_pe[:1, :50]),
                                           jnp.asarray(w_uk), jnp.asarray(w_uv), scale=scale)
    assert np.abs(np.asarray(dense)[0, -1] - want[0, 0]).max() < 1e-5
    # a chunk over lane 0's table: rows 30..48 real, five padded, each row against the positions up to its own
    chunk_nope, chunk_pe = rng.standard_normal((1, 24, heads, dn)).astype(np.float32), rng.standard_normal((1, 24, heads, dr)).astype(np.float32)
    lane0 = (PagedKV(c_kv.pool, c_kv.tables[:1]), PagedKV(pe_kv.pool, pe_kv.tables[:1]))
    with jax.default_matmul_precision("highest"):
        chunk = np.asarray(latent.latent_chunk_attend(jnp.asarray(chunk_nope), jnp.asarray(chunk_pe), jnp.asarray(w_uk), jnp.asarray(w_uv), *lane0,
                                                      jnp.int32(30), jnp.int32(19), scale=scale))
    k_nope, v = np.einsum("sc,hdc->shd", c[0], w_uk), np.einsum("sc,hcv->shv", c[0], w_uv)
    for t in range(19):
        for head in range(heads):
            logits = (k_nope[: 31 + t, head] @ chunk_nope[0, t, head] + k_pe[0, : 31 + t] @ chunk_pe[0, t, head]) * scale
            p = np.exp(logits - logits.max())
            assert np.abs(chunk[0, t, head] - (p / p.sum()) @ v[: 31 + t, head]).max() < 1e-5
    assert np.isfinite(chunk[0, :19]).all()
    assert latent.decode_reads(4, 8, 8, [50, 10], kernel=False) == 4 * 64 and latent.chunk_reads(8, 8, 30, 19) == 64  # a table of 64 positions is one block


def test_the_pools_hold_one_row_a_position_once_and_rows_land_where_the_tables_say(tiny):
    path, _ = tiny
    backend = whole_backend(path)
    assert backend.cache.latent_row == (32, 8) and backend.cache.index_row is None and backend.cache.state_layers == () and backend.cache.kv_layers == (0, 1, 2, 3)
    assert [run[0] for run in backend.runs] == ["dense", "sparse"]
    c, pe = lane_pools(backend, 12, 16, end=4)[0]
    assert c.shape == (4, 12, 16, 32) and pe.shape == (4, 12, 1, 128)  # 16 rotated keys of 8 to a row of 128; no pool of keys, none of values
    assert lane_pools(backend, 12, 16)[1] == () and lane_pools(backend, 1, 1, 3)[1] == ()
    assert backend.cache.cache_bytes_per_token() == backend.cache.kv_bytes_per_token() == 4 * ROW == 640 and backend.cache.pool_row == (40,)
    assert sum(int(np.prod(d.shape)) * 4 for d in (c, pe)) == 12 * 16 * backend.cache.kv_bytes_per_token()  # stored once, nothing padded
    c_kv = PagedKV(jnp.zeros((6, 16, 32), jnp.float32), jnp.asarray([[4, 1, -1], [0, 5, 2]], jnp.int32))
    pe_kv = PagedKV(jnp.zeros((6, 1, 128), jnp.float32), c_kv.tables)
    new_c, new_pe = jnp.arange(2 * 32, dtype=jnp.float32).reshape(2, 1, 32) + 1, jnp.arange(2 * 8, dtype=jnp.float32).reshape(2, 1, 8) + 1
    got_c, got_pe = latent.scatter_latent_rows(c_kv, pe_kv, new_c, new_pe, jnp.asarray([21, 48], jnp.int32), None)
    got_c, got_pe = np.asarray(got_c.pool), np.asarray(got_pe.pool)
    assert np.array_equal(got_c[1, 5], np.asarray(new_c[0, 0])) and np.count_nonzero(got_c) == 32  # lane 1 rides the idle sentinel
    assert np.array_equal(got_pe[1, 0, 40:48], np.asarray(new_pe[0, 0])) and np.count_nonzero(got_pe) == 8
    chunk_c, chunk_pe = jnp.ones((1, 5, 32), jnp.float32), jnp.ones((1, 5, 8), jnp.float32)
    one = (PagedKV(c_kv.pool, c_kv.tables[1:]), PagedKV(pe_kv.pool, pe_kv.tables[1:]))
    got_c, got_pe = latent.scatter_latent_rows(*one, chunk_c, chunk_pe, 30, 3)  # positions 30, 31, 32; two padded
    assert np.count_nonzero(np.asarray(got_c.pool)) == 3 * 32 and np.count_nonzero(np.asarray(got_pe.pool)) == 3 * 8
    assert np.asarray(got_c.pool)[5, 14:].all() and np.asarray(got_c.pool)[2, 0].all()


@pytest.mark.parametrize("ps,width,pos,seq,n", [(64, 64, 0, 256, 256), (64, 64, 499, 64, 33), (64, 64, 129, 16, 16), (16, 8, 30, 5, 3),
                                                 (16, 8, 0, 64, 64), (8, 8, 17, 24, 0), (64, 64, 448, 128, 128)])
def test_a_chunk_s_rotated_keys_written_a_pool_row_at_a_time_are_the_rows_written_a_position_at_a_time(ps, width, pos, seq, n):
    """``_scatter_folded_chunk`` (one gather and one scatter of whole pool
    rows) against ``scatter_index_rows`` (a position at a time at a column
    offset: what the chip runs as a loop of as many trips as the chunk has
    rows), byte for byte over a pool that already holds rows: chunks that
    start and end inside a pool row, padded rows, no real row, a table with a
    hole, and a chunk that runs past the table's end."""
    from petals_tpu.ops.sparse_attention import index_pool_row, scatter_index_rows

    rng = np.random.default_rng(pos + seq)
    n_pages, max_pages = 40, 8 if ps == 64 else 6
    rows_a_page, row_width = index_pool_row(ps, width)
    pool = jnp.asarray(rng.standard_normal((n_pages, rows_a_page, row_width)), jnp.float32)
    table = rng.permutation(n_pages)[:max_pages].astype(np.int32)
    table[-2] = -1
    kv = PagedKV(pool, jnp.asarray(table[None]))
    new = jnp.asarray(rng.standard_normal((1, seq, width)), jnp.float32)
    want = np.asarray(scatter_index_rows(kv, new, jnp.int32(pos), jnp.int32(n), ps).pool)
    got = np.asarray(jax.jit(lambda kv, new, pos, n: latent._scatter_folded_chunk(kv, new[0], pos, n, ps))(kv, new, jnp.int32(pos), jnp.int32(n)).pool)
    assert np.array_equal(got, want) and (n > 0 or np.array_equal(got, np.asarray(pool)))


def published_span() -> tuple:
    """``(backend, runs, configuration)`` of kanana2-30b-a3b-span6 on shapes alone."""
    import tempfile
    from pathlib import Path

    from perf.config import load as load_config

    root = Path(__file__).resolve().parents[1]
    config = load_config(root / "perf/configs/kanana2-30b-a3b-span6.json", "kanana2-30b-a3b-span6")
    with tempfile.TemporaryDirectory() as tmp:
        (Path(tmp) / "config.json").write_text(json.dumps(config["config"]))
        family, cfg = get_block_config(tmp)
    S = jax.ShapeDtypeStruct
    runs = tuple({name: S((n, *leaf.shape), leaf.dtype) for name, leaf in family.param_shapes_for(cfg, kind, jnp.bfloat16).items()}
                 for kind, n in (("dense", 1), ("sparse", 5)))
    return TransformerBackend(family, cfg, runs, first_block=0, n_blocks=6, memory_cache=None), runs, config


def test_the_published_span_s_cache_is_1152_bytes_a_position_a_layer_and_its_lanes_fit_the_default_budget():
    """kanana2-30b-a3b-span6 on shapes alone: ISSUE 42's count of the
    parameters, a position's 1,152 B a layer (6,912 B over the six, where
    ``num_key_value_heads`` 32 x ``head_dim`` 64 read as keys and values
    would be 8,192 B a layer), and the configuration's 8 lanes of 32,768 in
    1.81 GB, inside ``Server``'s default budget of 15% of a 16 GiB chip."""
    backend, runs, config = published_span()
    matrices = lambda run: sum(int(np.prod(leaf.shape[1:])) for name, leaf in run.items() if leaf.ndim > 2)
    assert matrices(runs[0]) == 64_094_208 and matrices(runs[1]) == 640_024_576
    assert matrices(runs[0]) + 5 * matrices(runs[1]) == 3_264_217_088  # 6.53 GB in bf16, 6.08 GiB
    args = config["server_args"]
    assert backend.cache.latent_row == (512, 64) and backend.cache.kv_bytes_per_token() == backend.cache.cache_bytes_per_token() == 6 * 1152 == 6912
    assert 2 * 6 * backend.num_kv_heads * backend.head_dim * 2 == 6 * 8192  # what the published keys would size, read as keys and values
    c, pe = lane_pools(backend, 8 * 512, 64, end=6)[0]
    assert c.shape == (6, 4096, 64, 512) and pe.shape == (6, 4096, 32, 128)
    pool = sum(int(np.prod(d.shape)) * 2 for d in (c, pe))
    assert pool == args["batch_lanes"] * 6912 * args["batch_max_length"] == 1_811_939_328 and "attn_cache_bytes" not in args
    assert pool <= 0.15 * 16 * 2**30
    assert backend.cache.lane_pool(8, 512, 64).walks == ()  # the latent walk counts itself: ``latent_reads``


@pytest.mark.parametrize("on_tpu", [False, True], ids=["composed", "kernel"])
def test_the_decode_counters_count_what_the_path_that_runs_reads(monkeypatch, on_tpu):
    """``decode_path`` follows from the backend and the pools' rows alone (the
    published rows tile for the kernel, the toy's do not), and
    ``backend.latent_reads`` counts that path's walk at the cell's lengths:
    the composed walk reads all eight lanes in whole blocks of
    ``DECODE_BLOCK_ROWS`` up to the longest live lane's; the kernel each live
    lane's own blocks of ``DECODE_KERNEL_PAGES`` pages, the blocks its grid
    does not skip (``_live``), within 6% of what the lanes hold. What the
    roofline's need is made of does not depend on the path."""
    monkeypatch.setattr(latent, "_on_tpu", lambda: on_tpu)
    backend, _, _ = published_span()
    rows = latent.latent_pool_rows(64, 512, 64)
    assert latent.decode_path(*rows, jnp.bfloat16) == ("kernel" if on_tpu else "composed")
    assert latent.decode_path(*latent.latent_pool_rows(16, 32, 8), jnp.float32) == "composed"  # the toy's rows: 32 wide, eight keys to a row
    assert latent.decode_path(*rows, jnp.float16) == "composed" and "float16" in latent.decode_kernel_unsupported(*rows, jnp.float16)
    contexts = np.array([16_400, 24_576, 30_720, 1, 32_768])  # three lanes idle
    reads = counted(backend, 8, 512, 64, contexts - 1)
    held = int(contexts.sum())
    assert reads["latent_rows_held"] == 6 * held and reads["latent_score_pairs"] == 6 * held and reads["latent_rows_absorbed"] == 6 * 5
    if on_tpu:
        block = latent.DECODE_KERNEL_PAGES * 64
        fetched = sum(block for ctx in contexts for i in range(-(-512 * 64 // block)) if latent._live(i, block, ctx))
        assert reads["latent_rows_read"] == 6 * fetched
        cell = counted(backend, 8, 512, 64, np.array([16_400, 18_000, 20_480, 23_000, 24_576, 27_000, 29_500, 30_720]) - 1)
        assert 1.0 <= cell["latent_rows_read"] / cell["latent_rows_held"] < 1.06
    else:
        assert reads["latent_rows_read"] == 6 * 8 * 32_768
    assert counted(backend, 8, 512, 64, np.array([], np.int64))["latent_rows_read"] == 0


def test_lane_auto_sizing_and_the_occupancy_count_the_latent_row(tiny):
    """``Server`` with no ``batch_lanes``: a lane costs its pages of latent
    rows (640 B a position over the four toy layers), and the budget is halved."""
    path, _ = tiny

    async def main():
        per_token = 4 * ROW
        server = Server(path, compute_dtype=jnp.float32, use_flash=False, batch_max_length=32, page_size=16,
                        attn_cache_bytes=2 * 4 * per_token * 32 + 9 * per_token, prefix_cache_bytes=0)
        await server.start()
        try:
            batcher = server.handler.batcher
            assert batcher.n_lanes == 4 and batcher.backend.cache.latent_row is not None and batcher.backend.cache.index_row is None and len(batcher.backend.cache.lane_state) == 0
            await batcher.ensure_open()
            info = batcher.occupancy_info()
            assert info["kv_bytes_per_token"] == per_token and info["latent_row"] == [32, 8] and info["latent_bytes_held"] == 0
            assert batcher._state() == () and [b.shape for b in batcher._buffers()] == [(4, 8, 16, 32), (4, 8, 1, 128)]
        finally:
            await server.shutdown()

    run(main())


# ---------------------------------------------------------------------------------
# through Server and the paged lane pool
# ---------------------------------------------------------------------------------


def test_prompt_in_mixed_steps_beside_two_decoding_lanes_of_other_lengths_then_decode_matches_the_reference(tiny):
    """Sessions B (a context of 70 and more) and C (3 and more) decode while
    A's prompt of 100 rides seven mixed steps of 16 (the expanded form over
    what the lane holds and the chunk's own rows); then all three decode at
    once (the absorbed form) at contexts of different lengths over permuted
    pages, beside an idle lane. Every row of every session against the
    reference's whole forward pass; the counters say what the rows did. Then a
    lane given back and taken again."""
    path, tensors = tiny

    async def main():
        server, client = await start_server(path, batch_lanes=4, batch_max_length=160, page_size=16, n_pages=30, prefill_token_budget=16)
        try:
            batcher = server.handler.batcher
            assert batcher.page_size == 16 and server.handler.prefix_cache is None and LATENT_KEYS <= set(batcher.stats)
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
            for i in range(12):  # all three decode at once, the fourth lane idle
                outs = await asyncio.gather(step(a, a_rows[:, 100 + i : 101 + i]), step(b, b_rows[:, pos_b + i : pos_b + i + 1]),
                                            step(c, c_rows[:, pos_c + i : pos_c + i + 1]))
                for got, out in zip((got_a, got_b, got_c), outs):
                    got.append(out)
            await steps_booked(batcher)
            now = batcher.stats
            delta = {key: now[key] - before[key] for key in LATENT_KEYS}
            decoded = (pos_b - 70) + (pos_c - 3) + 3 * 12
            assert delta["latent_rows_absorbed"] == decoded * LAYERS and delta["latent_rows_expanded"] == 100 * LAYERS  # by shape, not by a switch
            assert delta["latent_positions_held"] == sum(16 * (i + 1) for i in range(6)) * LAYERS + 100 * LAYERS
            assert delta["latent_positions_expanded"] >= delta["latent_positions_held"]  # whole blocks (a toy table is one)
            assert 0 < delta["latent_rows_held"] <= delta["latent_rows_read"] and delta["latent_score_pairs"] > delta["latent_rows_held"]
            assert delta["latent_bytes_held"] > 0 and delta["latent_bytes_held"] % (16 * 4 * ROW) == 0  # whole pages of 640 B a position
            info = await client.call("ptu.info", {})
            assert LATENT_KEYS <= set(info["continuous_batching"]) and info["pool"]["latent_row"] == [32, 8]
            assert info["pool"]["latent_bytes_held"] == (30 - info["pool"]["pages_free"]) * 16 * 4 * ROW > 0
            for got, data in ((got_a, a_rows), (got_b, b_rows), (got_c, c_rows)):
                got = np.concatenate(got, axis=1)[0]
                assert off(got, reference_hidden(tensors, data[0, : got.shape[0]])) < CLOSE
            # a lane given back and taken again: the pages' old rows lie past the new tenant's length
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
    """A chain of two spans on the default server: the dense layer with two
    expert layers, and the last expert layer alone."""
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
    two spans (a rotated key a position to a row on one, 16 to a row on the
    other): a prompt of 70 in three mixed steps a server, then decode; the
    LOGITS of every position against the reference's whole forward pass."""
    path, tensors, harness, model = swarm
    batchers = [server.handler.batcher for server in harness.servers]
    assert all(b is not None and b.backend.cache.latent_row is not None for b in batchers) and [b.page_size for b in batchers] == [8, 16]
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
        assert batcher.stats["latent_rows_expanded"] - was["latent_rows_expanded"] == 70 * layers
        assert batcher.stats["latent_rows_absorbed"] - was["latent_rows_absorbed"] == 15 * layers


def test_generate_token_identical_and_forward_through_a_chain_of_two_spans(swarm):
    path, tensors, _, model = swarm
    ids = np.random.RandomState(6).randint(0, 128, (1, 30)).astype(np.int64)
    got = np.asarray(model.generate(ids, max_new_tokens=6))
    want = list(ids[0])
    for _ in range(6):
        want.append(int(np.argmax(reference_logits(tensors, want)[-1])))
    np.testing.assert_array_equal(got[0], want)
    hidden = np.asarray(model.embed(ids))
    out = np.asarray(model.remote.forward(hidden))  # rpc_forward: the whole sequence, expanded, no cache
    assert off(out[0], reference_hidden(tensors, hidden[0])) < CLOSE


# ---------------------------------------------------------------------------------
# what is refused, and why
# ---------------------------------------------------------------------------------


@pytest.mark.parametrize("key,value,named", [
    ("n_group", 2, "n_group"), ("topk_group", 2, "topk_group"), ("scoring_func", "softmax", "scoring_func"),
    ("topk_method", "greedy", "topk_method"), ("hidden_act", "gelu", "hidden_act"), ("attention_bias", True, "attention_bias"),
    ("rope_scaling", {"type": "yarn", "factor": 4.0, "mscale": 1.0}, "rope_scaling"), ("moe_layer_freq", 2, "moe_layer_freq"),
    ("q_lora_rank", 24, "q_lora_rank"),
])
def test_what_the_block_does_not_compute_is_refused_at_load(tmp_path, key, value, named):
    """A non-null ``q_lora_rank`` (two q matrices with a norm between them) is
    refused, not served: the configuration this PR brings publishes null."""
    (tmp_path / "config.json").write_text(json.dumps({**HF, key: value}))
    with pytest.raises(NotImplementedError, match=f"deepseek_v3: {named}"):
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
def test_cache_paths_that_do_not_carry_the_latent_rows_refuse_them_with_the_reason(tiny, what):
    backend = whole_backend(tiny[0])
    with pytest.raises(NotImplementedError, match="deepseek_v3: .* latent row in place of .*only the paged lane pool's decode, generation and mixed steps"):
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
    with pytest.raises(NotImplementedError, match="deepseek_v3: a span of more than one kind of block is not served over a tp mesh"):
        whole_backend(path, mesh=tp_mesh(2))
    for kind in ("int8", "nf4a"):
        with pytest.raises(NotImplementedError, match=f"deepseek_v3: kv_quant_type '{kind}'.*latent row"):
            whole_backend(path, kv_quant_type=kind)
    with pytest.raises(ValueError, match="deepseek_v3"):
        convert_block_params(dict(load_block_params(path, 1, dtype=jnp.float32)), "deepseek_v3", QuantType.NF4)
    (tmp_path / "adapter_config.json").write_text(json.dumps({"r": 2, "lora_alpha": 4, "target_modules": ["q_proj"], "peft_type": "LORA"}))
    save_file({"base_model.model.model.layers.1.self_attn.q_proj.lora_A.weight": np.zeros((2, 64), np.float32),
               "base_model.model.model.layers.1.self_attn.q_proj.lora_B.weight": np.zeros((96, 2), np.float32)},
              str(tmp_path / "adapter_model.safetensors"))
    with pytest.raises(ValueError, match="deepseek_v3"):
        load_adapter(str(tmp_path), "deepseek_v3", block_range=range(0, LAYERS))
    backend = whole_backend(path)

    class Draft:
        spec_k = 2

    with pytest.raises(NotImplementedError, match="deepseek_v3: speculative decoding .* latent row"):
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
            assert server.handler.prefix_cache is None and server.handler.batcher.backend.cache.latent_row is not None
            data = rows(21, 40)
            stream = await open_session(client, path, 64)
            await step(stream, data[:, :30])
            with pytest.raises(Exception, match="start_from_position 5 behind the cache's position 30.*latent row"):
                await step(stream, data[:, 5:6], start_from_position=5)
            stream = await open_session(client, path, 64)
            await step(stream, data[:, :8])
            again = await step(stream, data[:, :40], start_from_position=0)  # from the start: served
            assert off(again[0], reference_hidden(tensors, data[0])) < CLOSE
            with pytest.raises(Exception, match="kv_adopt / kv_import.*latent row"):
                await stream.send({"kv_adopt": {"session_id": "x", "position": 4}})
                await stream.recv(timeout=60)
            live = await open_session(client, path, 64, session_id="live-one")
            await step(live, data[:, :8])
            with pytest.raises(Exception, match="a snapshot of a lane's cache.*latent row"):
                await client.call("ptu.session_export", {"session_id": "live-one", "start": 0, "end": LAYERS})
            await live.end()
            uids = CHAIN_DELIMITER.join(make_uid(default_dht_prefix(path), i) for i in range(LAYERS))
            wide = await client.open_stream("ptu.inference")  # two sequences a session take no lane
            await wide.send({"uids": uids, "max_length": 32, "batch_size": 2})
            with pytest.raises(Exception, match="a private cache.*latent row"):
                await wide.recv(timeout=60)
        finally:
            await client.close()
            await server.shutdown()

    run(main())


def test_a_family_without_a_latent_row_opens_the_pools_and_programs_it_had(tmp_path):
    from tests.utils import make_tiny_falcon

    path = make_tiny_falcon(str(tmp_path))
    family, cfg = get_block_config(path)
    assert family.block_latent is None and family.latent_for(cfg, None) is None
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *(load_block_params(path, i, dtype=jnp.float32) for i in range(2)))
    backend = TransformerBackend(family, cfg, stacked, first_block=0, n_blocks=2, memory_cache=MemoryCache(None),
                                 compute_dtype=jnp.float32, use_flash=False)
    k, v = lane_pools(backend, 6, 8, end=2)[0]
    assert backend.cache.latent_row is None and k.shape == v.shape and backend.cache.kv_bytes_per_token() == 2 * 2 * backend.num_kv_heads * backend.head_dim * 4
    batcher = DecodeBatcher(backend, backend.memory_cache, PriorityTaskQueue(), n_lanes=3, max_length=24, page_size=8)
    assert batcher.backend.cache.latent_row is None and not LATENT_KEYS & set(batcher.stats) and "latent_row" not in batcher.occupancy_info()
