"""Jamba (``jamba``) on the normal path, at a toy size on the CPU: a span of
two kinds of block, of which one keeps no keys and values but a state-space
model's state a lane, the other one kv head for all its query heads and no
position signal. Both kinds of block, and the in-repo reference
(perf/reference/jamba.py), against transformers' own ``JambaMambaDecoderLayer``
and ``JambaAttentionDecoderLayer``; the selective scan's two forms against a
plain loop; prefill in chunks and decode beside other lanes through ``Server``
and the paged lane pool against the reference's whole forward pass; what the
family refuses, each with its reason."""

import asyncio
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.reference import jamba as reference
from petals_tpu.client.model import AutoDistributedModelForCausalLM
from petals_tpu.data_structures import CHAIN_DELIMITER, make_uid
from petals_tpu.models.registry import span_runs
from petals_tpu.ops import linear_attention, selective_scan
from petals_tpu.rpc import RpcClient
from petals_tpu.rpc.serialization import deserialize_array, serialize_array
from petals_tpu.server.backend import TransformerBackend
from petals_tpu.server.batching import DecodeBatcher
from petals_tpu.server.from_pretrained import get_block_config, load_block_params
from petals_tpu.server.memory_cache import MemoryCache
from petals_tpu.server.server import Server, default_dht_prefix
from petals_tpu.server.task_queue import PriorityTaskQueue
from tests.test_full_model import SwarmHarness
from tests.utils import jamba_layer_types, lane_pools, make_tiny_jamba, steps_booked, tiny_jamba_tensors, TINY_JAMBA

HF = dict(TINY_JAMBA)
MAMBA, ATTENTION = "mamba", "attention"
KINDS = jamba_layer_types(HF)
N = HF["num_hidden_layers"]
STATE_KEYS = {"linattn_recurrent_tokens", "linattn_kernel_tokens", "linattn_chunk_tokens", "state_bytes_held", "kv_bytes_held"}
# float32 on the CPU, the served path against the reference, as a share of the largest output: they differ in
# the order of float32 sums
CLOSE = 2e-4


def run(coro):
    return asyncio.run(coro)


def layer_tensors(tensors: dict, layer: int) -> dict:
    prefix = f"model.layers.{layer}."
    return {k[len(prefix):]: jnp.asarray(v) for k, v in tensors.items() if k.startswith(prefix)}


def reference_hidden(tensors: dict, hidden, first: int = 0, last: int = N) -> np.ndarray:
    """``hidden`` [seq, h] through layers [first, last) of the reference."""
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(hidden, jnp.float32)
        for i in range(first, last):
            x, _ = reference.block(HF, layer_tensors(tensors, i), x, KINDS[i])
    return np.asarray(x)


def reference_logits(tensors: dict, ids) -> np.ndarray:
    x = reference_hidden(tensors, tensors["model.embed_tokens.weight"][np.asarray(ids)])
    x = x / np.sqrt((x * x).mean(-1, keepdims=True) + HF["rms_norm_eps"]) * tensors["model.final_layernorm.weight"]
    return x @ tensors["model.embed_tokens.weight"].T  # the head is tied


def off(got, want) -> float:
    return float(np.abs(np.asarray(got) - want).max() / np.abs(want).max())


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return make_tiny_jamba(str(tmp_path_factory.mktemp("models"))), tiny_jamba_tensors(HF)


def whole_backend(path: str, first_block: int = 0, n_blocks: int = N, **kw) -> TransformerBackend:
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
    uids = CHAIN_DELIMITER.join(make_uid(default_dht_prefix(path), i) for i in range(N))
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
# the block, from a checkpoint, against transformers' own layers
# ---------------------------------------------------------------------------------


@pytest.fixture(scope="module")
def hf_layers(tiny):
    """transformers' decoder layer of a mamba and of an attention layer, in float32, with the toy's tensors
    (``use_mamba_kernels`` false: ``JambaMambaMixer.slow_forward``)."""
    import torch
    from transformers.models.jamba.configuration_jamba import JambaConfig
    from transformers.models.jamba.modeling_jamba import JambaAttentionDecoderLayer, JambaMambaDecoderLayer

    config = JambaConfig(**HF)
    config._attn_implementation = "eager"
    assert list(config.layers_block_type) == KINDS and set(config.layers_num_experts) == {1}

    def layer(index: int):
        module = (JambaMambaDecoderLayer if KINDS[index] == MAMBA else JambaAttentionDecoderLayer)(config, index).eval().float()
        module.load_state_dict({k: torch.tensor(np.asarray(v)) for k, v in layer_tensors(tiny[1], index).items()}, strict=True)

        def forward(x: np.ndarray) -> np.ndarray:  # [seq, h], from position 0
            seq = x.shape[0]
            mask = torch.full((seq, seq), float("-inf")).triu(1)[None, None] if KINDS[index] == ATTENTION else None
            with torch.no_grad():
                return module(torch.tensor(x)[None], attention_mask=mask)[0][0].numpy()

        return forward

    return {index: layer(index) for index in (0, 1)}


@pytest.mark.parametrize("layer", [0, 1])
def test_a_checkpoint_s_block_of_each_kind_matches_transformers_layer(tiny, hf_layers, layer):
    """``load_block_params`` (``hf_to_block_params`` under transformers'
    tensor names: ``A_log`` turned to the state's layout, the conv's taps and
    bias, ``dt_proj`` with its bias, three inner norms) and ``block_apply``,
    a whole sequence from a zero state: the chunked form of the scan."""
    path, _ = tiny
    family, cfg = get_block_config(path)
    assert family.name == "jamba" and family.span_kinds(cfg, 0, N) == KINDS and cfg.head_dim == 16 and cfg.mamba_d_inner == 128
    params = load_block_params(path, layer, dtype=jnp.float32)
    assert set(params) == set(family.param_shapes_for(cfg, KINDS[layer]))
    assert all(params[k].shape == s.shape for k, s in family.param_shapes_for(cfg, KINDS[layer]).items())
    if KINDS[layer] == MAMBA:
        assert params["a_log"].shape == (16, 128) and params["conv"].shape == (4, 128) and params["w_in"].shape == (64, 256)
    x = rows(11, 37)
    with jax.default_matmul_precision("highest"):
        got, _ = family.apply_for(KINDS[layer])(params, jnp.asarray(x), None, 0, cfg)
    assert off(got[0], hf_layers[layer](x[0])) < 2e-5


def test_the_leaves_that_keep_their_dtype_under_a_bf16_load(tiny):
    """``A_log``, ``D`` and ``dt_proj.bias`` stay float32 under a bfloat16 load (``cast_exempt``)."""
    params = load_block_params(tiny[0], 0, dtype=jnp.bfloat16)
    assert {k for k, v in params.items() if v.dtype == jnp.float32} == {"a_log", "d", "dt_b"}


@pytest.mark.parametrize("layer", [0, 1])
def test_the_reference_matches_transformers_layer(tiny, hf_layers, layer):
    x = rows(12, 37)
    assert off(reference_hidden(tiny[1], x[0], layer, layer + 1), hf_layers[layer](x[0])) < 2e-5


# ---------------------------------------------------------------------------------
# the selective scan: its two forms against a plain loop
# ---------------------------------------------------------------------------------


def _scan_inputs(seed: int, batch: int, seq: int, n: int = 16, channels: int = 128):
    rng = np.random.RandomState(seed)
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape).astype(np.float32))
    dt = jax.nn.softplus(f(batch, seq, channels) - 2.0)
    a = -jnp.exp(jnp.asarray(np.log(rng.uniform(1, 16, (n, channels))).astype(np.float32)))
    return f(batch, seq, channels), dt, a, f(batch, seq, n), f(batch, seq, n), f(channels)


def _plain_loop(state, u, dt, a, b, c, d, n_valid):
    """HF's loop (``slow_forward`` 3.c) on the state as this repo lays it out, in numpy float64."""
    state, (u, dt, a, b, c, d) = np.asarray(state, np.float64), (np.asarray(t, np.float64) for t in (u, dt, a, b, c, d))
    ys = []
    for t in range(n_valid):
        state = np.exp(dt[:, t, None, :] * a) * state + (dt[:, t] * u[:, t])[:, None, :] * b[:, t, :, None]
        ys.append((state * c[:, t, :, None]).sum(1) + d * u[:, t])
    return state, np.stack(ys, axis=1)


@pytest.mark.parametrize("start", ["zero", "held"])
@pytest.mark.parametrize("seq,n_valid", [(24, None), (24, 17), (5, 5), (1, None)])
def test_the_chunked_form_is_the_one_step_form_is_a_plain_loop(start, seq, n_valid):
    """From a zero and from a non-zero state, with padded rows: the state
    handed back is the state after the last valid row, and the valid rows'
    outputs are the loop's."""
    u, dt, a, b, c, d = _scan_inputs(5, 2, seq)
    state = jnp.zeros((2, 16, 128)) if start == "zero" else jnp.asarray(np.random.RandomState(6).randn(2, 16, 128).astype(np.float32))
    valid = seq if n_valid is None else n_valid
    want_state, want_y = _plain_loop(state, u, dt, a, b, c, d, valid)
    got_state, got_y = jax.jit(selective_scan.selective_scan_chunked)(state, u, dt, a, b, c, d, n_valid)
    assert got_state.dtype == got_y.dtype == jnp.float32 and got_y.shape == (2, seq, 128)
    np.testing.assert_allclose(got_state, want_state, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(got_y[:, :valid], want_y, atol=2e-5, rtol=1e-5)
    stepped, ys = state, []
    for t in range(valid):
        stepped, y = selective_scan.selective_scan_step(stepped, u[:, t], dt[:, t], a, b[:, t], c[:, t], d)
        ys.append(y)
    np.testing.assert_allclose(stepped, want_state, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(jnp.stack(ys, axis=1), want_y, atol=2e-5, rtol=1e-5)
    if seq == 1:  # the dispatch: one row a lane is the one-step form
        one_state, one_y = selective_scan.selective_scan(state, u, dt, a, b, c, d)
        assert np.array_equal(one_state, stepped) and np.array_equal(one_y[:, 0], ys[0])


def test_the_pooled_step_starts_a_fresh_lane_from_zero_and_leaves_an_idle_one_alone():
    """``selective_scan_pooled`` on a pool of 3 layers x 3 lanes at slot 1:
    lane 0 live from its held state, lane 1 fresh (its slot's bytes are
    stale), lane 2 idle; the other layers' bytes are not touched."""
    u, dt, a, b, c, d = _scan_inputs(7, 3, 1)
    held = jnp.asarray(np.random.RandomState(8).randn(3, 3, 16, 128).astype(np.float32))
    tails = jnp.zeros((3, 3, 3, 128))
    pool = linear_attention.StatePool((held, tails), jnp.int32(1))
    live, fresh = jnp.asarray([True, True, False]), jnp.asarray([False, True, False])
    new, y = selective_scan.selective_scan_pooled(pool, u[:, 0], dt[:, 0], a, b[:, 0], c[:, 0], d, live=live, fresh=fresh)
    start = jnp.where(fresh[:, None, None], 0.0, held[1])
    want_state, want_y = selective_scan.selective_scan_step(start, u[:, 0], dt[:, 0], a, b[:, 0], c[:, 0], d)
    assert np.array_equal(new.leaves[0][1, :2], want_state[:2]) and np.array_equal(y[:2], want_y[:2])
    assert np.array_equal(new.leaves[0][1, 2], held[1, 2]) and np.array_equal(np.asarray(new.leaves[0])[[0, 2]], np.asarray(held)[[0, 2]])
    assert new.leaves[1] is tails


def test_neither_form_unrolls_over_positions_at_trace_time():
    """A chunk of 512 traces to a loop whose body holds ``UNROLL`` positions,
    and no array of it is ``[positions, d_state, channels]``."""
    u, dt, a, b, c, d = (jax.ShapeDtypeStruct(s, jnp.float32) for s in ((1, 512, 128), (1, 512, 128), (16, 128), (1, 512, 16), (1, 512, 16), (128,)))
    jaxpr = jax.make_jaxpr(selective_scan.selective_scan_chunked)(jax.ShapeDtypeStruct((1, 16, 128), jnp.float32), u, dt, a, b, c, d, 300)
    text = str(jaxpr)
    assert text.count("scan[") == 1 and text.count(" exp ") + text.count("= exp") <= selective_scan.UNROLL
    assert "512,16,128" not in text.replace(" ", "") and "512,1,16,128" not in text.replace(" ", "")


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
# the lane pool: pages in the attention layers, a state pool beside them
# ---------------------------------------------------------------------------------


def test_the_page_pool_is_as_deep_as_the_attention_layers_and_the_state_pool_as_the_mamba_ones(tiny):
    path, _ = tiny
    backend = whole_backend(path)
    assert backend.cache.kv_layers == (1, 3) and backend.cache.state_layers == (0, 2) and backend.cache.slots == (0, 0, 1, 1)
    assert [kind for kind, _, _ in backend.runs] == [MAMBA, ATTENTION, MAMBA, ATTENTION] and backend.moe_dims is None
    k, v = lane_pools(backend, 12, 16, end=N)[0]
    assert backend.num_kv_heads == 1 and k.shape == v.shape == (2, 12, 16, 16)  # one kv head of 16: a folded row
    state, tail = lane_pools(backend, 1, 1, 3)[1]
    assert state.shape == (2, 3, 16, 128) and jnp.dtype(state.dtype) == jnp.float32  # [d_state, channels], float32 whatever the cache's dtype
    assert tail.shape == (2, 3, 3, 128)
    assert backend.cache.state_bytes_per_lane() == 2 * (16 * 128 + 3 * 128) * 4
    assert backend.cache.cache_bytes_per_token() == backend.cache.kv_bytes_per_token() == 2 * 2 * 1 * 16 * 4  # two layers of pages, not four
    # the one-step form has no kernel of its own yet: the gated delta rule's says why it is not that state's
    assert backend.cache.lane_pool(3, 4, 16).state_step == "plain"
    leaves = tuple(jax.ShapeDtypeStruct(d.shape, d.dtype) for d in lane_pools(backend, 1, 1, 3)[1])
    assert "not [layers, lanes, heads, d_k, d_v]" in linear_attention.step_kernel_unsupported(linear_attention.StatePool(leaves, 0), 1)


def test_the_published_span_s_pools_and_what_a_lane_costs():
    """jamba2-3b-span28 on shapes alone: the whole model in five runs of
    kinds, pages 2 layers deep (one kv head of 128: 512 B a position), states
    26 (328 KB of state and 30 KB of conv tail a lane a layer), 2.86 B
    parameters; the decode walk's kernel takes the one kv head's folded row
    (on a TPU backend: off the chip the composed walk runs)."""
    import tempfile
    from pathlib import Path

    from perf.config import load as load_config
    from petals_tpu.ops import paged_flash_attention as pfa

    root = Path(__file__).resolve().parents[1]
    hf = load_config(root / "perf/configs/jamba2-3b-span28.json", "jamba2-3b-span28")["config"]
    with tempfile.TemporaryDirectory() as tmp:
        (Path(tmp) / "config.json").write_text(json.dumps(hf))
        family, cfg = get_block_config(tmp)
    kinds = family.span_kinds(cfg, 0, 28)
    assert [i for i, k in enumerate(kinds) if k == ATTENTION] == [7, 21] and cfg.head_dim == 128 and cfg.mamba_d_inner == 5120
    assert [(kind, length) for kind, _, length in span_runs(kinds)] == [(MAMBA, 7), (ATTENTION, 1), (MAMBA, 13), (ATTENTION, 1), (MAMBA, 6)]
    S = jax.ShapeDtypeStruct
    runs = tuple({name: S((length, *leaf.shape), leaf.dtype) for name, leaf in family.param_shapes_for(cfg, kind, jnp.bfloat16).items()}
                 for kind, _, length in span_runs(kinds))
    n_params = sum(int(np.prod(leaf.shape)) for run in runs for leaf in run.values())
    assert 2.86e9 < n_params < 2.87e9
    backend = TransformerBackend(family, cfg, runs, first_block=0, n_blocks=28, memory_cache=None)
    assert len(backend.cache.kv_layers) == 2 and len(backend.cache.state_layers) == 26
    k_pool = lane_pools(backend, 320, 64, end=28)[0][0]
    assert k_pool.shape == (2, 320, 64, 128)  # one kv head: a folded row (stored_row)
    state, tail = lane_pools(backend, 1, 1, 8)[1]
    assert (state.shape, tail.shape) == ((26, 8, 16, 5120), (26, 8, 3, 5120)) and jnp.dtype(tail.dtype) == jnp.bfloat16
    assert backend.cache.kv_bytes_per_token() == 2 * 512 and backend.cache.state_bytes_per_lane() == 26 * (327_680 + 30_720)
    assert pfa.walk_kernel_unsupported(S(k_pool.shape[1:], k_pool.dtype), (8, 1, 20, 128), (8, 40)) is None
    assert [(layers, path) for _, layers, _, _, path in backend.cache.lane_pool(8, 40, 64).walks] == [(2, "composed")]  # this backend is no TPU
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(pfa, "_on_tpu", lambda: True)
        assert [(layers, block, path) for _, layers, block, _, path in backend.cache.lane_pool(8, 40, 64).walks] == [(2, 32, "kernel")]


def test_prompt_in_three_mixed_steps_beside_two_decoding_lanes_then_decode_matches_the_reference(tiny):
    """Sessions B and C decode while A's prompt of 40 rides three mixed steps
    (a budget of 16: the state and the conv's tail handed chunk to chunk),
    then all three decode at once: every row of every session against the
    reference's whole forward pass. The counters say which form each row
    took."""
    path, tensors = tiny

    async def main():
        server, client = await start_server(path, batch_lanes=3, batch_max_length=64, page_size=16, prefill_token_budget=16)
        try:
            batcher = server.handler.batcher
            assert batcher.occupancy_info()["state_step"] == "plain"
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
            assert batcher.stats["mixed_steps"] - before["mixed_steps"] == 3 and batcher.stats["prefill_tokens"] - before["prefill_tokens"] == 40
            assert batcher.stats["linattn_chunk_tokens"] - before["linattn_chunk_tokens"] == 40 * 2
            for i in range(12):  # all three decode at once
                outs = await asyncio.gather(step(a, a_rows[:, 40 + i : 41 + i]), step(b, b_rows[:, pos_b + i : pos_b + i + 1]),
                                            step(c, c_rows[:, pos_c + i : pos_c + i + 1]))
                for got, out in zip((got_a, got_b, got_c), outs):
                    got.append(out)
            decoded = (len(got_b) - 1) + (len(got_c) - 1) + 12  # B's and C's replies but their prompts', and A's 12
            await steps_booked(batcher)
            assert batcher.stats["linattn_recurrent_tokens"] - before["linattn_recurrent_tokens"] == decoded * 2
            assert batcher.stats["linattn_kernel_tokens"] == 0  # the plain form: no kernel takes this state
            assert batcher.stats["state_bytes_held"] > before["state_bytes_held"] and batcher.stats["kv_bytes_held"] > before["kv_bytes_held"]
            assert batcher.stats["attn_pages_gathered"] > 0 and batcher.stats["attn_pages_kernel"] == 0  # the composed walk
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
    """A chain of two spans on the default server, a mamba and an attention layer each."""
    path, tensors = tiny
    specs = [dict(first_block=0, num_blocks=2, page_size=8, batch_max_length=64, prefill_token_budget=16),
             dict(first_block=2, num_blocks=2, page_size=8, batch_max_length=64, prefill_token_budget=16)]
    harness = SwarmHarness(path, specs).start()
    model = AutoDistributedModelForCausalLM.from_pretrained(path, initial_peers=harness.initial_peers)
    yield path, tensors, harness, model
    model.close()
    harness.stop()


def test_remote_sequential_session_prefill_in_chunks_then_decode_matches_the_reference(swarm):
    """Through ``Server`` with no flag and ``RemoteSequential`` over a chain of
    two spans: a prompt of 37 in three mixed steps a server, then decode; the
    LOGITS of every position (the client's final norm and tied head) against
    the reference's whole forward pass."""
    path, tensors, harness, model = swarm
    batchers = [server.handler.batcher for server in harness.servers]
    assert all(b is not None and b.page_size == 8 and len(b.backend.cache.lane_state) == 2 for b in batchers)
    assert [len(b.backend.cache.state_layers) for b in batchers] == [1, 1] and [len(b.backend.cache.kv_layers) for b in batchers] == [1, 1]
    before = [dict(b.stats) for b in batchers]
    ids = np.random.RandomState(3).randint(0, 128, (1, 50)).astype(np.int64)
    hidden = np.asarray(model.embed(ids))
    with model.remote.inference_session(max_length=50) as session:
        outs = [np.asarray(session.step(hidden[:, :37]))]
        outs += [np.asarray(session.step(hidden[:, p : p + 1])) for p in range(37, 50)]
    logits = np.asarray(model.lm_logits(np.concatenate(outs, axis=1)))[0]
    np.testing.assert_allclose(logits, reference_logits(tensors, ids[0]), atol=3e-4, rtol=0)
    for batcher, was in zip(batchers, before):
        assert batcher.stats["mixed_steps"] - was["mixed_steps"] == 3
        assert batcher.stats["linattn_chunk_tokens"] - was["linattn_chunk_tokens"] == 37
        assert batcher.stats["linattn_recurrent_tokens"] - was["linattn_recurrent_tokens"] == 13


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
    ("num_experts", 4, "num_experts 4"), ("sliding_window", 8, "sliding_window 8"), ("hidden_act", "gelu", "hidden_act"),
    ("mamba_proj_bias", True, "mamba_proj_bias"), ("mamba_conv_bias", False, "mamba_conv_bias"),
])
def test_what_the_block_does_not_compute_is_refused_at_load(tmp_path, key, value, named):
    (tmp_path / "config.json").write_text(json.dumps({**HF, key: value}))
    with pytest.raises(NotImplementedError, match=f"jamba: {named}"):
        get_block_config(str(tmp_path))


REFUSED_BY_THE_BACKEND = {
    "a private cache": lambda b: b.cache_descriptors(1, 32, 0, N),
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
    with pytest.raises(NotImplementedError, match="jamba: .* recurrent state .*2 of its 4 blocks"):
        REFUSED_BY_THE_BACKEND[what](backend)
    attention_only = whole_backend(tiny[0], 1, 1)  # a span of this family without a state layer is served like any other
    assert not attention_only.cache.state_layers and attention_only.cache.lane_state == () and len(attention_only.cache_descriptors(1, 32, 0, 1)) == 2


def test_options_the_family_cannot_take_yet_are_refused(tiny, tmp_path):
    """A tp mesh, quantized weights, quantized pages and a LoRA adapter:
    refused with the family's name, as the other state families' are."""
    from petals_tpu.parallel.mesh import tp_mesh
    from petals_tpu.utils.convert_block import QuantType, convert_block_params
    from petals_tpu.utils.peft import load_adapter
    from safetensors.numpy import save_file

    path, _ = tiny
    family, _ = get_block_config(path)
    assert family.tp_pspecs is None and not family.quantizable_leaves and not family.lora_targets
    with pytest.raises(NotImplementedError, match="jamba.*tp mesh"):
        whole_backend(path, mesh=tp_mesh(2))
    with pytest.raises(NotImplementedError, match="jamba: kv_quant_type 'int8'.*recurrent state"):
        whole_backend(path, 0, 3, kv_quant_type="int8")
    with pytest.raises(ValueError, match="jamba"):
        convert_block_params(dict(load_block_params(path, 1, dtype=jnp.float32)), "jamba", QuantType.NF4)
    (tmp_path / "adapter_config.json").write_text(json.dumps({"r": 2, "lora_alpha": 4, "target_modules": ["q_proj"], "peft_type": "LORA"}))
    save_file({"base_model.model.model.layers.1.self_attn.q_proj.lora_A.weight": np.zeros((2, 64), np.float32),
               "base_model.model.model.layers.1.self_attn.q_proj.lora_B.weight": np.zeros((64, 2), np.float32)},
              str(tmp_path / "adapter_model.safetensors"))
    with pytest.raises(ValueError, match="jamba"):
        load_adapter(str(tmp_path), "jamba", block_range=range(0, N))


def test_what_cuts_a_cache_back_is_refused_over_the_wire_and_the_prefix_cache_is_off(tiny):
    """``start_from_position`` behind the state's position (0 starts over and
    is served), ``kv_adopt``, and a session that would take a private cache:
    each error names the reason."""
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
            uids = CHAIN_DELIMITER.join(make_uid(default_dht_prefix(path), i) for i in range(N))
            wide = await client.open_stream("ptu.inference")  # two sequences a session take no lane
            await wide.send({"uids": uids, "max_length": 32, "batch_size": 2})
            with pytest.raises(Exception, match="jamba: a private cache.*only the paged lane pool carries the state"):
                await wide.recv(timeout=60)
        finally:
            await client.close()
            await server.shutdown()

    run(main())
