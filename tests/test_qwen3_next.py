"""Qwen3-Next (``qwen3_next``) on the normal path, at a toy size on the CPU: a
span of two kinds of block, both with the expert layer, of which one keeps no
keys and values but a recurrent state a lane. Both kinds of block, and the
in-repo reference (perf/reference/qwen3_next.py), against transformers' own
``Qwen3NextDecoderLayer``; the fused projections taken apart at load; the
shares of the routed experts adding up to the uncut layer; prefill in chunks
and decode beside other lanes through ``Server`` and the paged lane pool (a
state pool AND an expert stack in one span) against the reference's whole
forward pass; what the family refuses, each with its reason."""

import asyncio
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.reference import qwen3_next as reference
from petals_tpu.client.model import AutoDistributedModelForCausalLM
from petals_tpu.data_structures import CHAIN_DELIMITER, make_uid
from petals_tpu.models.gated_delta import MixerDims
from petals_tpu.models.qwen3_next.block import split_ba, split_qkvz
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
from tests.utils import lane_pools, make_tiny_qwen3_next, qwen3_next_layer_types, steps_booked, tiny_qwen3_next_tensors, TINY_QWEN3_NEXT

HF = dict(TINY_QWEN3_NEXT)
LINEAR, FULL = "linear_attention", "full_attention"
KINDS = qwen3_next_layer_types(HF)
STATE_KEYS = {"linattn_recurrent_tokens", "linattn_kernel_tokens", "linattn_chunk_tokens", "state_bytes_held", "kv_bytes_held"}
MOE_KEYS = {"moe_dense_tokens", "moe_grouped_tokens", "moe_hit_tokens", "moe_weight_passes"}
SHARE_KEYS = {"moe_chunk_rows_computed", "moe_chunk_rows_routed"}
# float32 on the CPU, the served path against the reference, as a share of the largest output: they differ in
# the order of float32 sums and in the chunked form's triangular solve
CLOSE = 2e-4


def run(coro):
    return asyncio.run(coro)


def layer_tensors(tensors: dict, layer: int) -> dict:
    prefix = f"model.layers.{layer}."
    return {k[len(prefix):]: jnp.asarray(v) for k, v in tensors.items() if k.startswith(prefix)}


def reference_hidden(tensors: dict, hidden, first: int = 0, last: int = 8, hf: dict = HF) -> np.ndarray:
    """``hidden`` [seq, h] through layers [first, last) of the reference."""
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(hidden, jnp.float32)
        for i in range(first, last):
            x, _ = reference.block(hf, layer_tensors(tensors, i), x, KINDS[i])
    return np.asarray(x)


def reference_logits(tensors: dict, ids) -> np.ndarray:
    x = reference_hidden(tensors, tensors["model.embed_tokens.weight"][np.asarray(ids)])
    x = x / np.sqrt((x * x).mean(-1, keepdims=True) + HF["rms_norm_eps"]) * (1.0 + tensors["model.norm.weight"])  # zero-centred
    return x @ tensors["lm_head.weight"].T


def off(got, want) -> float:
    return float(np.abs(np.asarray(got) - want).max() / np.abs(want).max())


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return make_tiny_qwen3_next(str(tmp_path_factory.mktemp("models"))), tiny_qwen3_next_tensors(HF)


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
# the block, from a checkpoint, against transformers' own layer
# ---------------------------------------------------------------------------------


@pytest.fixture(scope="module")
def hf_layers(tiny):
    """transformers' ``Qwen3NextDecoderLayer`` of a linear and of a full layer, in float32, with the toy's tensors."""
    import torch
    from transformers.models.qwen3_next.configuration_qwen3_next import Qwen3NextConfig
    from transformers.models.qwen3_next.modeling_qwen3_next import Qwen3NextDecoderLayer, Qwen3NextRotaryEmbedding

    config = Qwen3NextConfig(**HF)
    config._attn_implementation = "eager"
    assert list(config.layer_types) == KINDS
    rotary = Qwen3NextRotaryEmbedding(config)

    def layer(index: int):
        module = Qwen3NextDecoderLayer(config, index).eval().float()
        module.load_state_dict({k: torch.tensor(np.asarray(v)) for k, v in layer_tensors(tiny[1], index).items()}, strict=True)

        def forward(x: np.ndarray) -> np.ndarray:  # [seq, h], from position 0
            seq = x.shape[0]
            positions = torch.arange(seq)[None]
            mask = torch.full((seq, seq), float("-inf")).triu(1)[None, None] if KINDS[index] == FULL else None
            with torch.no_grad():
                hidden = torch.tensor(x)[None]
                out = module(hidden, position_embeddings=rotary(hidden, positions), attention_mask=mask, position_ids=positions)
            return (out[0] if isinstance(out, tuple) else out)[0].numpy()

        return forward

    return {index: layer(index) for index in (1, 3)}


@pytest.mark.parametrize("layer", [1, 3])
def test_a_checkpoint_s_block_of_each_kind_matches_transformers_layer(tiny, hf_layers, layer):
    """``hf_to_block_params`` and the block over a whole sequence of 80, then
    a chunk of 70 padded to 96 and one position at a time from the cache that
    left, against ``Qwen3NextDecoderLayer`` in float32."""
    path, _ = tiny
    family, cfg = get_block_config(path)
    kind = KINDS[layer]
    assert family.name == "qwen3_next" and family.kind_of(cfg, layer) == kind and cfg.rotary_dim == 4
    params = load_block_params(path, layer, dtype=jnp.float32)
    assert set(params) == set(family.block_param_shapes(cfg, kind)) and ("conv" in params) == (kind == LINEAR)
    assert {name: leaf.shape for name, leaf in params.items()} == {name: s.shape for name, s in family.block_param_shapes(cfg, kind).items()}
    x = rows(layer, 80)
    want = hf_layers[layer](x[0])
    with jax.default_matmul_precision("highest"):
        out, _ = family.block_apply(params, jnp.asarray(x), None, 0, cfg, kind=kind)
        assert off(out[0], want) < CLOSE
        state = family.state_for(cfg, kind)
        cache = (tuple(jnp.zeros((1, *shape), dtype or jnp.float32) for shape, dtype in state) if state
                 else tuple(jnp.zeros((1, 96, cfg.num_key_value_heads, cfg.head_dim), jnp.float32) for _ in range(2)))
        padded = jnp.pad(jnp.asarray(x[:, :70]), ((0, 0), (0, 26), (0, 0)), constant_values=7.0)  # the padding is not zeros
        out, cache = family.block_apply(params, padded, cache, 0, cfg, kind=kind, n_valid=70)
        got = [np.asarray(out[0, :70])]
        for pos in range(70, 80):
            out, cache = family.block_apply(params, jnp.asarray(x[:, pos : pos + 1]), cache, pos, cfg, kind=kind)
            got.append(np.asarray(out[0]))
        assert off(np.concatenate(got), want) < CLOSE


@pytest.mark.parametrize("layer", [1, 3])
def test_the_reference_matches_transformers_layer(tiny, hf_layers, layer):
    x = rows(10 + layer, 48)[0]
    assert off(reference_hidden(tiny[1], x, layer, layer + 1), hf_layers[layer](x)) < 2e-5


def test_the_fused_projections_are_taken_apart_as_transformers_orders_them():
    """``split_qkvz`` / ``split_ba`` against ``fix_query_key_value_ordering``
    on a projection whose every output row names itself."""
    import torch
    from transformers.models.qwen3_next.configuration_qwen3_next import Qwen3NextConfig
    from transformers.models.qwen3_next.modeling_qwen3_next import Qwen3NextGatedDeltaNet

    net = Qwen3NextGatedDeltaNet(Qwen3NextConfig(**HF), 0)
    dims = MixerDims(2, 4, 8, 16, 4)
    n_qkvz, n_ba = 2 * 2 * 8 + 2 * 4 * 16, 2 * 4
    # a "hidden" of one-hots: the product is the projection's rows' own numbers
    q, k, v, z, b, a = net.fix_query_key_value_ordering(torch.arange(n_qkvz, dtype=torch.float32)[None, None],
                                                        torch.arange(n_ba, dtype=torch.float32)[None, None])
    w_qkvz, w_ba = np.arange(n_qkvz, dtype=np.float32)[:, None], np.arange(n_ba, dtype=np.float32)[:, None]
    ours = {**split_qkvz(w_qkvz, dims), **split_ba(w_ba, dims)}
    for name, theirs in (("wq", q), ("wk", k), ("wv", v), ("wz", z), ("wb", b), ("wa", a)):
        np.testing.assert_array_equal(ours[name][0], theirs.reshape(-1).numpy())
    assert ours["wq"].shape == (1, 16) and ours["wv"].shape == (1, 64) and ours["wb"].shape == (1, 4)


def test_the_four_shares_routed_parts_and_the_shared_expert_once_add_up_to_the_uncut_layer(tmp_path, tiny):
    """Four chips of four experts each: what every chip computes alike (the
    mixer, the residual, the shared expert with its gate) counted once, the
    four routed parts add up to what a server of all sixteen gives, in the
    served block and in the reference alike, for both kinds of layer."""
    _, tensors = tiny
    whole_path = tiny[0]
    family, cfg = get_block_config(whole_path)
    for layer in (0, 3):
        x = rows(30 + layer, 24)
        kind = KINDS[layer]
        with jax.default_matmul_precision("highest"):
            whole, _ = family.block_apply(load_block_params(whole_path, layer, dtype=jnp.float32), jnp.asarray(x), None, 0, cfg, kind=kind)
            parts, ref_parts = [], []
            for first in (0, 4, 8, 12):
                path = make_tiny_qwen3_next(str(tmp_path), held=4, first=first)
                _, share_cfg = get_block_config(path)
                assert (share_cfg.num_experts, share_cfg.num_experts_routed, share_cfg.first_expert) == (4, 16, first)
                params = load_block_params(path, layer, dtype=jnp.float32)
                assert params["w1"].shape[0] == 4 and params["gate"].shape[-1] == 16
                parts.append(np.asarray(family.block_apply(params, jnp.asarray(x), None, 0, share_cfg, kind=kind)[0]))
                hf = {**HF, "num_experts": 4, "expert_share": {"routed": 16, "first": first}}
                ref_parts.append(reference_hidden(tensors, x[0], layer, layer + 1, hf=hf))
                assert off(parts[-1][0], ref_parts[-1]) < CLOSE
            # every part holds the common terms (residual, mixer, gated shared expert): of the four, three are taken off
            no_experts = dict(load_block_params(whole_path, layer, dtype=jnp.float32))
            no_experts.update({name: jnp.zeros_like(no_experts[name]) for name in ("w1", "w2", "w3")})
            common = np.asarray(family.block_apply(no_experts, jnp.asarray(x), None, 0, cfg, kind=kind)[0])
        assert off(sum(parts) - 3 * common, np.asarray(whole)) < CLOSE
        assert off((sum(ref_parts) - 3 * common[0]), reference_hidden(tensors, x[0], layer, layer + 1)) < CLOSE


def test_a_shared_expert_without_a_gate_is_added_whole_as_before():
    """models/moe.py ``_add_shared``: ``wsg`` scales, and a family without it
    (K-EXAONE, Kanana-2) gets the sum it got."""
    from petals_tpu.models.moe import _add_shared

    rng = np.random.RandomState(0)
    x, routed = jnp.asarray(rng.randn(2, 3, 8), jnp.float32), jnp.asarray(rng.randn(2, 3, 8), jnp.float32)
    params = {"ws1": jnp.asarray(rng.randn(8, 4), jnp.float32), "ws3": jnp.asarray(rng.randn(8, 4), jnp.float32),
              "ws2": jnp.asarray(rng.randn(4, 8), jnp.float32)}
    shared = (jax.nn.silu(x @ params["ws1"]) * (x @ params["ws3"])) @ params["ws2"]
    np.testing.assert_allclose(_add_shared(params, x, routed), routed + shared, rtol=1e-6)
    wsg = jnp.asarray(rng.randn(8, 1), jnp.float32)
    np.testing.assert_allclose(_add_shared({**params, "wsg": wsg}, x, routed), routed + jax.nn.sigmoid(x @ wsg) * shared, rtol=1e-5, atol=1e-6)
    assert _add_shared({}, x, routed) is routed
    plain = str(jax.make_jaxpr(lambda x, r: _add_shared(params, x, r))(x, routed))
    assert "logistic" in str(jax.make_jaxpr(lambda x, r: _add_shared({**params, "wsg": wsg}, x, r))(x, routed))
    assert plain.count("logistic") == 1  # silu's own: no gate without the leaf


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
# the lane pool: pages in the full layers, a state pool beside them, an expert stack in every run
# ---------------------------------------------------------------------------------


def test_the_page_pool_is_as_deep_as_the_full_layers_and_the_state_pool_as_the_linear_ones(tiny):
    path, _ = tiny
    backend = whole_backend(path)
    assert backend.cache.kv_layers == (3, 7) and backend.cache.state_layers == (0, 1, 2, 4, 5, 6) and backend.cache.slots == (0, 1, 2, 0, 3, 4, 5, 1)
    assert [kind for kind, _, _ in backend.runs] == [LINEAR, FULL, LINEAR, FULL]
    assert backend.moe_dims == (16, 4, 64, 32, 16, 0, 0) and backend.moe_grouped(1) == "hit"  # every run's experts ride the stack
    k, v = lane_pools(backend, 12, 16, end=8)[0]
    assert backend.num_kv_heads == 2 and k.shape == v.shape == (2, 12, 16, 2 * 16)  # rows of 2 kv heads of 16, under 128 lanes: folded
    matrix, tail = lane_pools(backend, 1, 1, 3)[1]
    assert matrix.shape == (6, 3, 4, 8, 16) and jnp.dtype(matrix.dtype) == jnp.float32  # a state a VALUE head, float32 whatever the cache's dtype
    assert tail.shape == (6, 3, 3, 2 * 2 * 8 + 4 * 16)
    assert backend.cache.state_bytes_per_lane() == 6 * (4 * 8 * 16 + 3 * 96) * 4
    assert backend.cache.cache_bytes_per_token() == backend.cache.kv_bytes_per_token() == 2 * 2 * 2 * 16 * 4  # two layers of pages, not eight


def test_the_published_span_s_pools_and_what_a_lane_costs():
    """qwen3-next-80b-a3b-span8-ep4 on shapes alone: pages 2 layers deep (2 kv
    heads of 256: 2 KB a position a layer), states 6 (2.1 MB of matrix and 48
    KB of conv tail a lane a layer), 128 of 512 experts a layer, 3.51 B
    parameters."""
    import tempfile
    from pathlib import Path

    from perf.config import load as load_config

    root = Path(__file__).resolve().parents[1]
    hf = load_config(root / "perf/configs/qwen3-next-80b-a3b-span8-ep4.json", "qwen3-next-80b-a3b-span8-ep4")["config"]
    with tempfile.TemporaryDirectory() as tmp:
        (Path(tmp) / "config.json").write_text(json.dumps(hf))
        family, cfg = get_block_config(tmp)
    assert list(cfg.layer_types) == [LINEAR, LINEAR, LINEAR, FULL] * 2 and (cfg.num_experts, cfg.num_experts_routed) == (128, 512)
    S = jax.ShapeDtypeStruct
    runs = tuple({name: S((length, *leaf.shape), leaf.dtype) for name, leaf in family.param_shapes_for(cfg, kind, jnp.bfloat16).items()}
                 for kind, _, length in span_runs(family.span_kinds(cfg, 0, 8)))
    n_params = sum(int(np.prod(leaf.shape)) for run in runs for leaf in run.values())
    assert 3.51e9 < n_params < 3.52e9
    backend = TransformerBackend(family, cfg, runs, first_block=0, n_blocks=8, memory_cache=None)
    assert len(backend.cache.kv_layers) == 2 and len(backend.cache.state_layers) == 6
    assert lane_pools(backend, 320, 64, end=8)[0][0].shape == (2, 320, 64, 512)  # two kv heads: a folded row (stored_row)
    matrix, tail = lane_pools(backend, 1, 1, 8)[1]
    assert (matrix.shape, tail.shape) == ((6, 8, 32, 128, 128), (6, 8, 3, 8192)) and jnp.dtype(tail.dtype) == jnp.bfloat16
    assert backend.cache.kv_bytes_per_token() == 2 * 2048 and backend.cache.state_bytes_per_lane() == 6 * (2_097_152 + 49_152)
    assert backend.moe_grouped(1) == "hit" and backend.moe_grouped(512, chunk=True) == "dense"


@pytest.mark.parametrize("state_step", ["plain", "kernel"])
def test_prompt_in_three_mixed_steps_beside_two_decoding_lanes_then_decode_matches_the_reference(tmp_path, tiny, monkeypatch, state_step):
    """A server that holds experts 4-11 of 16: sessions B and C decode while
    A's prompt of 40 rides three mixed steps (a budget of 16: the state and
    the conv's tail handed chunk to chunk), then all three decode at once:
    every row of every session against the reference's whole forward pass.
    The counters say which form each row took and what the chunks' dispatch
    multiplied. ``kernel``: the decode rows' one-step rule named as on a TPU
    (ops/linear_attention.py ``_step_kernel``, interpreted here), on the
    state pool where it lies, in the decode steps and in the mixed steps
    beside A's chunks: the same replies within the same tolerance."""
    _, tensors = tiny
    path = make_tiny_qwen3_next(str(tmp_path), held=8, first=4)
    hf = {**HF, "num_experts": 8, "expert_share": {"routed": 16, "first": 4}}

    if state_step == "kernel":
        monkeypatch.setattr(linear_attention, "_on_tpu", lambda: True)  # ``_interpret`` still sees the CPU

    async def main():
        server, client = await start_server(path, batch_lanes=3, batch_max_length=64, page_size=16, prefill_token_budget=16)
        try:
            batcher = server.handler.batcher
            assert batcher.occupancy_info()["state_step"] == state_step
            assert batcher.page_size == 16 and server.handler.prefix_cache is None
            assert STATE_KEYS | MOE_KEYS | SHARE_KEYS <= set(batcher.stats)
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
            assert batcher.stats["mixed_steps"] - before["mixed_steps"] == 3 and batcher.stats["prefill_tokens"] - before["prefill_tokens"] == 40
            assert batcher.stats["linattn_chunk_tokens"] - before["linattn_chunk_tokens"] == 40 * 6
            # the chunks take the all-experts einsum: 8 held experts a position, where the routing sends 4 x 8 / 16 here
            assert batcher.stats["moe_chunk_rows_computed"] - before["moe_chunk_rows_computed"] == 40 * 8
            assert batcher.stats["moe_chunk_rows_routed"] - before["moe_chunk_rows_routed"] == 40 * 4 * 8 / 16
            assert batcher.stats["moe_dense_tokens"] - before["moe_dense_tokens"] == 40
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
            assert batcher.stats["moe_hit_tokens"] - before["moe_hit_tokens"] == decoded
            assert batcher.stats["state_bytes_held"] > before["state_bytes_held"] and batcher.stats["kv_bytes_held"] > before["kv_bytes_held"]
            info = await client.call("ptu.info", {})
            assert STATE_KEYS | MOE_KEYS | SHARE_KEYS <= set(info["continuous_batching"])
            for got, data in ((got_a, a_rows), (got_b, b_rows), (got_c, c_rows)):
                got = np.concatenate(got, axis=1)[0]
                assert off(got, reference_hidden(tensors, data[0, : got.shape[0]], hf=hf)) < CLOSE
            for stream in (a, b, c):
                await stream.end()
        finally:
            await client.close()
            await server.shutdown()

    run(main())


def test_the_full_layers_decode_rows_walk_their_pages_in_the_kernel_and_the_counters_say_so(tmp_path, monkeypatch):
    """Two kv heads of whole lanes (128 here, 256 published) are stored as one
    folded row, and on a backend that says it is a TPU a decode row's attention
    over them is the walk's kernel (ops/paged_flash_attention.py
    ``_walk_kernel``, interpreted here) inside the decode steps and the mixed
    steps: two sessions of other lengths decode side by side after their
    prompts, every reply against the reference's whole forward pass, and every
    table slot a decode step's two full layers read is counted as the
    kernel's (``attn_pages_kernel`` of ``attn_pages_gathered``)."""
    from petals_tpu.ops import paged_flash_attention as pfa

    hf = {**HF, "head_dim": 128}
    path, tensors = make_tiny_qwen3_next(str(tmp_path), head_dim=128), tiny_qwen3_next_tensors(hf)
    monkeypatch.setattr(pfa, "_on_tpu", lambda: True)  # ``_interpret`` still sees the CPU

    async def main():
        server, client = await start_server(path, batch_lanes=3, batch_max_length=64, page_size=16)
        try:
            batcher = server.handler.batcher
            assert server.backend.cache.pool_row == (2 * 128,) and batcher.occupancy_info()["decode_walk"] == ["kernel"]
            b_rows, c_rows = rows(2, 45), rows(3, 45)
            b, c = await open_session(client, path, 64), await open_session(client, path, 64)
            got_b, got_c = [await step(b, b_rows[:, :30])], [await step(c, c_rows[:, :3])]  # 2 pages and 1
            before = dict(batcher.stats)
            for i in range(15):  # B crosses into its third page, C stays in its first two
                outs = await asyncio.gather(step(b, b_rows[:, 30 + i : 31 + i]), step(c, c_rows[:, 3 + i : 4 + i]))
                got_b.append(outs[0]), got_c.append(outs[1])
            walked = batcher.stats["attn_pages_gathered"] - before["attn_pages_gathered"]
            assert walked == batcher.stats["attn_pages_kernel"] - before["attn_pages_kernel"] > 0
            for got, data in ((got_b, b_rows), (got_c, c_rows)):
                got = np.concatenate(got, axis=1)[0]
                assert off(got, reference_hidden(tensors, data[0, : got.shape[0]], hf=hf)) < CLOSE
            for stream in (b, c):
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


def test_a_server_of_every_expert_counts_no_share(tiny):
    """The two chunk counters are a span's that holds a share only."""
    backend = whole_backend(tiny[0])
    batcher = DecodeBatcher(backend, backend.memory_cache, PriorityTaskQueue(), n_lanes=2, max_length=32, page_size=8)
    assert MOE_KEYS | STATE_KEYS <= set(batcher.stats) and not SHARE_KEYS & set(batcher.stats)


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
            # the next session takes lane 0, stale state and all
            again, data = await open_session(client, path, 64), rows(9, 24)
            assert batcher._free_lanes == [1] and np.abs(np.asarray(batcher._state()[0][:, 0])).max() > 0
            got = [await step(again, data[:, :9])] + [await step(again, data[:, p : p + 1]) for p in range(9, 24)]
            assert off(np.concatenate(got, axis=1)[0], reference_hidden(tensors, data[0])) < CLOSE
            await again.end()
        finally:
            await client.close()
            await server.shutdown()

    run(main())


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
    LOGITS of every position (the client's zero-centred final norm and head)
    against the reference's whole forward pass."""
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
        assert batcher.stats["moe_hit_tokens"] - was["moe_hit_tokens"] == 13


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
    ("mlp_only_layers", [1], "mlp_only_layers"), ("decoder_sparse_step", 2, "decoder_sparse_step"),
    ("rope_scaling", {"rope_type": "linear", "factor": 2.0}, "rope_scaling"), ("attention_bias", True, "attention_bias"),
    ("hidden_act", "gelu", "hidden_act"), ("linear_num_value_heads", 3, "linear_num_value_heads"),
])
def test_what_the_block_does_not_compute_is_refused_at_load(tmp_path, key, value, named):
    (tmp_path / "config.json").write_text(json.dumps({**HF, key: value}))
    with pytest.raises(NotImplementedError, match=f"qwen3_next: {named}"):
        get_block_config(str(tmp_path))


def test_a_share_outside_the_routed_experts_is_refused_at_load(tmp_path):
    (tmp_path / "config.json").write_text(json.dumps({**HF, "num_experts": 8, "expert_share": {"routed": 16, "first": 12}}))
    with pytest.raises(ValueError, match=r"qwen3_next: experts \[12, 20\) are not among the 16"):
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
    with pytest.raises(NotImplementedError, match="qwen3_next: .* recurrent state .*6 of its 8 blocks"):
        REFUSED_BY_THE_BACKEND[what](backend)
    full_only = whole_backend(tiny[0], 3, 1)  # a span of this family without a state layer is served like any other
    assert not full_only.cache.state_layers and full_only.cache.lane_state == () and len(full_only.cache_descriptors(1, 32, 0, 1)) == 2


def test_options_the_family_cannot_take_yet_are_refused(tiny, tmp_path):
    """A tp mesh, quantized weights, quantized pages and a LoRA adapter:
    refused with the family's name, as Olmo-Hybrid's are."""
    from petals_tpu.parallel.mesh import tp_mesh
    from petals_tpu.utils.convert_block import QuantType, convert_block_params
    from petals_tpu.utils.peft import load_adapter
    from safetensors.numpy import save_file

    path, _ = tiny
    family, _ = get_block_config(path)
    assert family.tp_pspecs is None and not family.quantizable_leaves and not family.lora_targets
    with pytest.raises(NotImplementedError, match="qwen3_next.*tp mesh"):
        whole_backend(path, mesh=tp_mesh(2))
    with pytest.raises(NotImplementedError, match="qwen3_next: kv_quant_type 'int8'.*recurrent state"):
        whole_backend(path, 0, 3, kv_quant_type="int8")
    with pytest.raises(ValueError, match="qwen3_next"):
        convert_block_params(dict(load_block_params(path, 1, dtype=jnp.float32)), "qwen3_next", QuantType.NF4)
    (tmp_path / "adapter_config.json").write_text(json.dumps({"r": 2, "lora_alpha": 4, "target_modules": ["q_proj"], "peft_type": "LORA"}))
    save_file({"base_model.model.model.layers.3.self_attn.q_proj.lora_A.weight": np.zeros((2, 64), np.float32),
               "base_model.model.model.layers.3.self_attn.q_proj.lora_B.weight": np.zeros((128, 2), np.float32)},
              str(tmp_path / "adapter_model.safetensors"))
    with pytest.raises(ValueError, match="qwen3_next"):
        load_adapter(str(tmp_path), "qwen3_next", block_range=range(0, 8))


def test_what_cuts_a_cache_back_is_refused_over_the_wire_and_the_prefix_cache_is_off(tiny):
    """``start_from_position`` behind the state's position (0 starts over and
    is served), ``kv_adopt``, a session export, and a session that would take
    a private cache: each error names the reason."""
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
            wide = await client.open_stream("ptu.inference")  # two sequences a session take no lane
            await wide.send({"uids": uids, "max_length": 32, "batch_size": 2})
            with pytest.raises(Exception, match="qwen3_next: a private cache.*only the paged lane pool carries the state"):
                await wide.recv(timeout=60)
        finally:
            await client.close()
            await server.shutdown()

    run(main())
