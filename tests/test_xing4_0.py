"""``xing4_0`` (Xing4.0-29B-A4B's model_type) on the normal path, at a toy size
on the CPU: ``deepseek_v3``'s sub-layers (latent attention with a low-rank
query under yarn, dense and expert feed-forwards) inside a residual STREAM of
``hc_mult`` rows, mixed around every sub-layer by manifold-constrained
hyper-connections, so that what crosses the wire between two blocks is
``hc_mult x hidden_size`` wide. The block from a checkpoint against the
in-repo reference (perf/reference/xing4_0.py); the reference's sub-layers
against transformers' own ``DeepseekV3Attention`` / ``DeepseekV3MLP`` /
``DeepseekV3MoE``; yarn against ``_compute_yarn_parameters``; the wrap alone
against a ten-line numpy Sinkhorn; prefill in chunks and decode beside other
lanes through ``Server`` and the paged lane pool against the reference's whole
forward pass; client embed -> two servers -> client norm against the whole
model's reference; the stream's width in every buffer and frame; what the
family refuses, each with its reason."""

import asyncio
import json
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.reference import xing4_0 as reference
from petals_tpu.client.model import AutoDistributedModelForCausalLM
from petals_tpu.data_structures import CHAIN_DELIMITER, make_uid
from petals_tpu.rpc import RpcClient
from petals_tpu.rpc.serialization import deserialize_array, serialize_array
from petals_tpu.server.backend import TransformerBackend
from petals_tpu.server.from_pretrained import get_block_config, load_block_params
from petals_tpu.server.memory_cache import MemoryCache
from petals_tpu.server.server import Server, default_dht_prefix
from tests.test_full_model import SwarmHarness
from tests.utils import TINY_XING4_0, make_tiny_deepseek_v3, make_tiny_xing4_0, steps_booked, tiny_xing4_0_tensors

HF = dict(TINY_XING4_0)
LAYERS, KINDS = HF["num_hidden_layers"], reference.layer_kinds(HF)
N, C = HF["hc_mult"], HF["hidden_size"]
WIDTH = N * C  # what crosses the wire
# float32 on the CPU, the served path against the reference, as a share of the largest output: they differ in the
# order of float32 sums (measured 2e-7..2e-6); a row that read another lane's page or another row of the stream lands near 1
CLOSE = 5e-5


def run(coro):
    return asyncio.run(coro)


def layer_tensors(tensors: dict, layer: int) -> dict:
    prefix = f"model.layers.{layer}."
    return {k[len(prefix):]: jnp.asarray(v) for k, v in tensors.items() if k.startswith(prefix)}


def reference_stream(tensors: dict, stream, first: int = 0, last: int = LAYERS, hf: dict = HF) -> np.ndarray:
    """``stream`` [seq, n*C] through layers [first, last) of the reference."""
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(stream, jnp.float32)
        for i in range(first, last):
            x, _ = reference.block(hf, layer_tensors(tensors, i), x, KINDS[i])
    return np.asarray(x)


def reference_logits(tensors: dict, ids) -> np.ndarray:
    """The whole model: the embedding repeated ``n`` times, every layer, the sum of the rows, the final norm, the head."""
    x = reference_stream(tensors, np.tile(tensors["model.embed_tokens.weight"][np.asarray(ids)], N))
    x = x.reshape(len(ids), N, C).sum(1)
    x = x / np.sqrt((x * x).mean(-1, keepdims=True) + HF["rms_norm_eps"]) * tensors["model.norm.weight"]
    return x @ tensors["lm_head.weight"].T


def off(got, want) -> float:
    return float(np.abs(np.asarray(got) - want).max() / np.abs(want).max())


def rows(seed: int, n: int, width: int = WIDTH) -> np.ndarray:
    return (np.random.RandomState(seed).randn(1, n, width) * 0.5).astype(np.float32)


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return make_tiny_xing4_0(str(tmp_path_factory.mktemp("models"))), tiny_xing4_0_tensors(HF)


def whole_backend(path: str, **kw) -> TransformerBackend:
    family, cfg = get_block_config(path)
    runs = []
    for start, length in ((0, 2), (2, LAYERS - 2)):
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


# ---------------------------------------------------------------------------------
# the block from a checkpoint: the reference, and transformers' own sub-layers
# ---------------------------------------------------------------------------------


@pytest.mark.parametrize("layer", [1, 2], ids=["dense", "sparse"])
def test_a_checkpoint_s_block_matches_the_reference(tiny, layer):
    """``hf_to_block_params`` (the rope columns of ``q_b_proj`` and
    ``kv_a_proj_with_mqa`` de-interleaved, ``kv_b_proj`` cut into ``wuk`` and
    ``wuv``, the three ``phi`` of a wrap as ONE matrix with ``alpha`` and
    ``b`` beside it in float32), and the block over 40 positions of a stream
    256 wide with no cache against the reference, which rotates in the
    published, interleaved form and mixes with einsums over ``[seq, n, C]``."""
    path, tensors = tiny
    family, cfg = get_block_config(path)
    kind = family.kind_of(cfg, layer)
    assert family.name == "xing4_0" and kind == KINDS[layer] and family.latent_for(cfg, kind) == (32, 8)
    assert family.stream_for(cfg) == (WIDTH, 2) and cfg.stream_width == WIDTH and cfg.hidden_size == C
    assert cfg.rope_scaling is not None and cfg.softmax_mscale == pytest.approx((0.1 * np.log(64) + 1) ** 2)
    assert (family.moe_dims_for(cfg, kind) is None) == (layer == 1)
    params = load_block_params(path, layer, dtype=jnp.float32)
    shapes = family.param_shapes_for(cfg, kind)
    assert set(params) == set(shapes) and all(params[name].shape == shapes[name].shape for name in shapes)
    assert params["hc_phi_attn"].shape == (WIDTH, 2 * N + N * N) and params["hc_alpha_mlp"].shape == (3,) and params["hc_bias_attn"].shape == (24,)
    assert params["wqa"].shape == (C, 24) and params["wqb"].shape == (24, 4 * 24) and ("ws1" in params) == (layer == 2)
    # under a bf16 load the wraps' scalars and biases and the router's bias stay float32
    low = load_block_params(path, layer, dtype=jnp.bfloat16)
    assert low["hc_phi_attn"].dtype == jnp.bfloat16 and all(low[f"hc_{leaf}_{wrap}"].dtype == jnp.float32 for leaf in ("alpha", "bias") for wrap in ("attn", "mlp"))
    x = rows(1, 40)
    with jax.default_matmul_precision("highest"):
        out = np.asarray(family.apply_for(kind)(params, jnp.asarray(x), None, 0, cfg)[0][0])
    assert out.shape == (40, WIDTH) and off(out, reference_stream(tensors, x[0], layer, layer + 1)) < CLOSE
    with pytest.raises(ValueError, match=f"xing4_0: a block takes the residual stream flat.*{WIDTH}"):
        family.apply_for(kind)(params, jnp.asarray(x[..., :C]), None, 0, cfg)


@pytest.fixture(scope="module")
def theirs():
    """transformers' ``deepseek_v3`` configuration of the toy's sub-layers (the class knows no ``hc_*`` key and needs none)."""
    from transformers import DeepseekV3Config

    keys = {k: v for k, v in HF.items() if k not in ("model_type", "hc_mult", "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
                                                    "mhc_h_res_clamp_max", "num_nextn_predict_layers", "ep_size")}
    config = DeepseekV3Config(**keys, rope_interleave=True)
    config._attn_implementation = "eager"
    return config


def _load(module, tensors: dict, prefix: str):
    import torch

    state = {k[len(prefix):]: torch.tensor(v) for k, v in tensors.items() if k.startswith(prefix)}
    loaded = module.load_state_dict(state, strict=False)
    assert not loaded.missing_keys and not loaded.unexpected_keys, loaded
    return module.eval()


def test_the_inner_attention_is_transformers_deepseek_v3_attention_with_a_low_rank_query_under_yarn(tiny, theirs):
    """The reference's ``attention`` (and so the served block's) against
    ``DeepseekV3Attention`` with ``q_lora_rank`` 24 and the toy's yarn, on the
    same tensors, under a causal mask: the low-rank query's norm at the class
    default 1e-6 (``rms_norm_eps`` is 1e-5 here), the ramp inside the four
    frequencies, ``mscale^2`` = 2.0047 on the softmax. Each piece shows:
    without yarn, and without the softmax's ``mscale^2``, the reference is
    far outside what separates the two implementations."""
    import torch
    from transformers.models.deepseek_v3.modeling_deepseek_v3 import DeepseekV3Attention, DeepseekV3RotaryEmbedding

    _, tensors = tiny
    seq, layer = 48, 1
    attn = _load(DeepseekV3Attention(theirs, layer), tensors, f"model.layers.{layer}.self_attn.")
    assert attn.q_lora_rank == 24 and attn.scaling == pytest.approx(24**-0.5 * (0.1 * np.log(64) + 1) ** 2)
    a = rows(2, seq, C)
    with torch.no_grad():
        cos, sin = DeepseekV3RotaryEmbedding(theirs)(torch.tensor(a), torch.arange(seq)[None])
        mask = torch.full((seq, seq), float("-inf")).triu(1)[None, None]
        want = attn(torch.tensor(a), (cos, sin), mask)[0][0].numpy()
    w = layer_tensors(tensors, layer)
    with jax.default_matmul_precision("highest"):
        assert off(reference.attention(HF, w, jnp.asarray(a[0])), want) < CLOSE
        plain = {**HF, "rope_scaling": None}
        assert off(reference.attention(plain, w, jnp.asarray(a[0])), want) > 1e-2
        no_mscale = {**HF, "rope_scaling": {**HF["rope_scaling"], "mscale_all_dim": 0}}  # the tables' factor becomes mscale(64) too
        assert off(reference.attention(no_mscale, w, jnp.asarray(a[0])), want) > 1e-2


def test_the_inner_feed_forwards_are_transformers_mlp_and_moe(tiny, theirs):
    """The reference's ``feed_forward`` of both kinds against
    ``DeepseekV3MLP`` and ``DeepseekV3MoE`` (sigmoid scores, the bias chooses
    and does not weigh, the kept weights renormalised and doubled, one shared
    expert) on the same tensors."""
    import torch
    from transformers.models.deepseek_v3.modeling_deepseek_v3 import DeepseekV3MLP, DeepseekV3MoE

    _, tensors = tiny
    r = rows(3, 48, C)
    with jax.default_matmul_precision("highest"):
        for layer, module in ((0, DeepseekV3MLP(theirs)), (2, DeepseekV3MoE(theirs))):
            module = _load(module, tensors, f"model.layers.{layer}.mlp.")
            with torch.no_grad():
                want = module(torch.tensor(r))[0].numpy()
            got, margin = reference.feed_forward(HF, layer_tensors(tensors, layer), jnp.asarray(r[0]), KINDS[layer])
            assert off(got, want) < CLOSE and bool(np.isinf(margin).all()) == (layer == 0)


YARNS = [
    pytest.param(64, {"factor": 64, "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
                      "type": "yarn"}, id="the-published-dict"),
    pytest.param(8, HF["rope_scaling"], id="the-toy-s"),
    pytest.param(64, {"factor": 40, "mscale": 1.0, "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096, "rope_type": "yarn"},
                 id="mscale-and-mscale_all_dim-differ"),
    pytest.param(128, {"factor": 4.0, "original_max_position_embeddings": 8192, "rope_type": "yarn", "truncate": False}, id="no-mscale-not-truncated"),
    pytest.param(64, {"factor": 16, "attention_factor": 1.25, "original_max_position_embeddings": 2048, "rope_type": "yarn"}, id="attention-factor-given"),
]


@pytest.mark.parametrize("dim,scaling", YARNS)
def test_rotary_tables_yarn_is_transformers_compute_yarn_parameters(dim, scaling):
    """``rotary_tables(rope_scaling={type: yarn, ...})`` against transformers'
    ``_compute_yarn_parameters``: the blended frequencies and the factor on
    cos and sin; and the reference's own ``yarn`` against both."""
    from transformers import PretrainedConfig
    from transformers.modeling_rope_utils import _compute_yarn_parameters

    from petals_tpu.ops.rotary import rotary_tables

    config = PretrainedConfig(rope_theta=10000.0, head_dim=dim, hidden_size=dim * 4, num_attention_heads=4, max_position_embeddings=262144,
                              rope_scaling=dict(scaling))
    inv_freq, factor = _compute_yarn_parameters(config, "cpu")
    positions = np.array([[0, 1, 17, 4095, 4096, 100_000]], np.int32)
    angles = positions[0][:, None].astype(np.float64) * inv_freq.numpy().astype(np.float64)[None]
    cos, sin = rotary_tables(jnp.asarray(positions), dim, theta=10000.0, rope_scaling=dict(scaling))
    assert cos.shape == (1, 6, dim)
    # float32 angles of up to 1e5 radians: a rounding of the product is 4e-3 of a turn at the far end
    near = positions[0] < 5000
    np.testing.assert_allclose(np.asarray(cos)[0, near, : dim // 2], np.cos(angles[near]) * factor, atol=2e-3)
    np.testing.assert_allclose(np.asarray(sin)[0, near, dim // 2 :], np.sin(angles[near]) * factor, atol=2e-3)
    np.testing.assert_allclose(np.asarray(cos)[0, :3, : dim // 2], np.cos(angles[:3]) * factor, atol=1e-6)
    mine, on_tables, _ = reference.yarn({"qk_rope_head_dim": dim, "rope_theta": 10000.0, "rope_scaling": dict(scaling), "max_position_embeddings": 262144})
    np.testing.assert_allclose(np.asarray(mine), inv_freq.numpy(), rtol=1e-6)
    assert on_tables == pytest.approx(factor)
    assert not np.allclose(inv_freq.numpy(), 1.0 / 10000.0 ** (np.arange(0, dim, 2) / dim))  # the blend moved something


@pytest.mark.parametrize("n", [4, 2], ids=["hc_mult-4", "hc_mult-2"])
def test_the_wrap_alone_is_a_ten_line_numpy_sinkhorn(n):
    """``stream_wrap`` around a sub-layer that doubles what it reads, against
    numpy written from the paper's lines, for a stream of four rows and of
    two: after 20 rounds at the toy's weights the rows and columns of ``M``
    sum to 1 within 1e-3; the clamp binds before ``exp``; ``hc_eps`` guards
    Sinkhorn's denominators and ``rms_norm_eps`` the norm's."""
    from petals_tpu.models.xing4_0.block import stream_coefficients, stream_wrap
    from petals_tpu.models.xing4_0.config import Xing40BlockConfig

    path_cfg = {**HF, "hc_mult": n}
    cfg = Xing40BlockConfig.from_hf_config(type("C", (), path_cfg))
    rng = np.random.RandomState(5 + n)
    cols = 2 * n + n * n
    phi, alpha, bias = rng.randn(n * C, cols).astype(np.float32) * 0.1, rng.uniform(0.3, 0.9, 3).astype(np.float32), rng.randn(cols).astype(np.float32) * 0.1
    X = rng.randn(2, 7, n, C).astype(np.float32)

    def numpy_wrap(X, clamp=(-30.0, 30.0)):
        flat = X.reshape(*X.shape[:2], n * C).astype(np.float64)
        x = flat / np.sqrt((flat * flat).mean(-1, keepdims=True) + HF["rms_norm_eps"])
        z = x @ phi.astype(np.float64)
        Hp = 1 / (1 + np.exp(-(alpha[0] * z[..., :n] + bias[:n])))
        Hq = 2 / (1 + np.exp(-(alpha[1] * z[..., n : 2 * n] + bias[n : 2 * n])))
        M = np.exp(np.clip(alpha[2] * z[..., 2 * n :] + bias[2 * n :], *clamp)).reshape(*X.shape[:2], n, n)
        for _ in range(HF["hc_sinkhorn_iters"]):
            M = M / (M.sum(-1, keepdims=True) + HF["hc_eps"])
            M = M / (M.sum(-2, keepdims=True) + HF["hc_eps"])
        u = np.einsum("bsn,bsnc->bsc", Hp, X)
        return np.einsum("bsmn,bsnc->bsmc", M, X) + Hq[..., None] * (2 * u)[:, :, None, :], M

    want, M = numpy_wrap(X)
    assert np.abs(M.sum(-1) - 1).max() < 1e-3 and np.abs(M.sum(-2) - 1).max() < 1e-3 and M.min() > 0
    assert np.abs(M - 1 / n).max() > 0.05  # Sinkhorn had work to do: the mix is not the uniform one
    params = {"hc_phi_attn": jnp.asarray(phi), "hc_alpha_attn": jnp.asarray(alpha), "hc_bias_attn": jnp.asarray(bias)}
    flat = jnp.asarray(X.reshape(2, 7, n * C))
    with jax.default_matmul_precision("highest"):
        got, extra = stream_wrap(params, "attn", flat, lambda u: (2 * u, "beside"), cfg)
        pre, post, mix = stream_coefficients(jnp.asarray(phi), jnp.asarray(alpha), jnp.asarray(bias), flat, cfg)
    assert extra == "beside" and got.shape == (2, 7, n * C) and off(np.asarray(got).reshape(X.shape), want) < CLOSE
    assert pre.shape == post.shape == (n, 2, 7) and mix.shape == (n, n, 2, 7)
    np.testing.assert_allclose(np.moveaxis(np.asarray(mix), (0, 1), (2, 3)), M, atol=1e-5)  # M[m, k]: row k of X in row m of X'
    # the clamp sits on the logits: a tight one changes M, and so X'
    import dataclasses

    tight = dataclasses.replace(cfg, hc_res_clamp=(-0.05, 0.05))
    with jax.default_matmul_precision("highest"):
        clamped, _ = stream_wrap(params, "attn", flat, lambda u: (2 * u, None), tight)
    assert off(np.asarray(clamped).reshape(X.shape), numpy_wrap(X, (-0.05, 0.05))[0]) < CLOSE and off(np.asarray(clamped), np.asarray(got)) > 1e-3


def test_forward_and_backward_run_a_whole_sequence_over_the_dense_sparse_boundary(tiny):
    """The stateless passes (``rpc_forward`` / ``rpc_backward``) of a span of
    both kinds (two runs, two programs): the span's output over 60 positions
    of stream against the reference, and the gradient against the
    reference's; the backend sizes itself by the stream."""
    path, tensors = tiny
    backend = whole_backend(path)
    assert backend.hidden_size == WIDTH and backend.stream_mixes == 2 and [kind for kind, _, _ in backend.runs] == ["dense", "sparse"]
    assert backend.cache.latent_row == (32, 8) and backend.pack_lanes(np.zeros((3, 1, WIDTH), np.float32), np.arange(3)).shape == (3, WIDTH + 1)
    x, grad_out = rows(4, 60), rows(5, 60)

    def traced(h):
        for i in range(LAYERS):
            h, _ = reference.block(HF, layer_tensors(tensors, i), h, KINDS[i])
        return h

    with jax.default_matmul_precision("highest"):
        assert off(np.asarray(backend.forward(x))[0], reference_stream(tensors, x[0])) < CLOSE
        grad = np.asarray(backend.backward(x, grad_out)[0])[0]
        _, vjp = jax.vjp(traced, jnp.asarray(x[0]))
        want_grad = np.asarray(vjp(jnp.asarray(grad_out[0]))[0])
    assert off(grad, want_grad) < 10 * CLOSE


def test_the_published_span_is_4_726_259_712_parameters_and_a_position_caches_1152_bytes_a_block(tmp_path):
    """``block_param_shapes`` at the published widths: the counts the
    configuration's ``deployment`` states, 8.80 GiB in bf16; the wire's row
    is 57,344 B; the cache 1,152 B a position a block."""
    import math
    from pathlib import Path

    from perf.config import load as load_config

    config = load_config(Path(__file__).resolve().parents[1] / "perf/configs/xing4-29b-a4b-span8.json", "xing4-29b-a4b-span8")
    (tmp_path / "config.json").write_text(json.dumps(config["config"]))
    family, cfg = get_block_config(str(tmp_path))
    count = lambda kind: sum(math.prod(leaf.shape) for name, leaf in family.param_shapes_for(cfg, kind).items()
                             if leaf.ndim > 1 and not name.startswith("hc_"))
    wraps = sum(math.prod(leaf.shape) for name, leaf in family.param_shapes_for(cfg, "dense").items() if name.startswith("hc_phi"))
    assert (count("dense") + wraps, count("sparse") + wraps, wraps) == (128_188_416, 744_980_480, 688_128)
    total = 2 * 128_188_416 + 6 * 744_980_480
    assert total == 4_726_259_712 and str(total // 1000 * 1000)[:4] in config["deployment"].replace(",", "") and 8.80 < total * 2 / 2**30 < 8.81
    assert family.stream_for(cfg) == (14336, 2) and 14336 * 4 == 57_344
    params = tuple({name: jax.ShapeDtypeStruct((length, *leaf.shape), leaf.dtype) for name, leaf in family.param_shapes_for(cfg, kind).items()}
                   for kind, length in (("dense", 2), ("sparse", 6)))
    backend = TransformerBackend(family, cfg, params, first_block=0, n_blocks=8, memory_cache=None)
    assert backend.cache.kv_bytes_per_token() == 8 * 1152 and backend.hidden_size == 14336
    assert cfg.softmax_mscale == pytest.approx(2.0047, abs=1e-4) and dict(cfg.rope_scaling)["original_max_position_embeddings"] == 4096


# ---------------------------------------------------------------------------------
# through Server and the paged lane pool
# ---------------------------------------------------------------------------------


def test_prompt_in_mixed_steps_beside_two_decoding_lanes_then_decode_matches_the_reference_and_the_counters_count_the_stream(tiny):
    """Sessions B (a context of 70 and more) and C (3 and more) decode while
    A's prompt of 100 rides seven mixed steps of 16; then all three decode at
    once over permuted pages, beside an idle lane. Every row of every
    session, 256 wide, against the reference's whole forward pass; ``hc_rows``
    counts rows x 2 wraps x 4 blocks, ``stream_bytes_in`` / ``_out`` 1,024 B a
    row each way. A frame of the model's width is refused with the stream's."""
    path, tensors = tiny

    async def main():
        server, client = await start_server(path, batch_lanes=4, batch_max_length=160, page_size=16, n_pages=30, prefill_token_budget=16)
        try:
            batcher = server.handler.batcher
            assert batcher.page_size == 16 and batcher.backend.cache.latent_row is not None and {"hc_rows", "stream_bytes_in", "stream_bytes_out"} <= set(batcher.stats)
            assert batcher._lanes_in.shape == (4, WIDTH + 1) if batcher._lanes_in is not None else True
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
            for i in range(12):  # all three decode at once, the fourth lane idle
                outs = await asyncio.gather(step(a, a_rows[:, 100 + i : 101 + i]), step(b, b_rows[:, pos_b + i : pos_b + i + 1]),
                                            step(c, c_rows[:, pos_c + i : pos_c + i + 1]))
                for got, out in zip((got_a, got_b, got_c), outs):
                    got.append(out)
            stepped = (pos_b - 70) + (pos_c - 3) + 3 * 12 + 100  # decode rows and the prompt's
            await steps_booked(batcher)
            delta = {key: batcher.stats[key] - before[key] for key in ("hc_rows", "stream_bytes_in", "stream_bytes_out", "batched_tokens", "prefill_tokens")}
            assert delta["batched_tokens"] + delta["prefill_tokens"] == stepped
            assert delta["hc_rows"] == stepped * 2 * LAYERS and delta["stream_bytes_in"] == delta["stream_bytes_out"] == stepped * WIDTH * 4
            info = await client.call("ptu.info", {})
            assert {"hc_rows", "stream_bytes_in"} <= set(info["continuous_batching"])
            for got, data in ((got_a, a_rows), (got_b, b_rows), (got_c, c_rows)):
                got = np.concatenate(got, axis=1)[0]
                assert got.shape[1] == WIDTH and off(got, reference_stream(tensors, data[0, : got.shape[0]])) < CLOSE
            # a frame as wide as the model, not the stream: refused with the width, on every way in
            narrow = await open_session(client, path, 160)
            with pytest.raises(Exception, match=f"step hidden must be .*hidden={WIDTH}.*got \\(1, 5, {C}\\)"):
                await step(narrow, rows(9, 5, C))
            uids = CHAIN_DELIMITER.join(make_uid(default_dht_prefix(path), i) for i in range(LAYERS))
            for method, extra in (("ptu.forward", {}), ("ptu.backward", {"grad_out": serialize_array(rows(9, 5, C))})):
                with pytest.raises(Exception, match=f"expects a \\[batch, seq, hidden={WIDTH}\\]"):
                    await client.call(method, {"uids": uids, "tensors": {"hidden": serialize_array(rows(9, 5, C)), **extra}})
            out = await client.call("ptu.forward", {"uids": uids, "tensors": {"hidden": serialize_array(a_rows[:, :20])}})
            assert off(deserialize_array(out["tensors"]["hidden"])[0], reference_stream(tensors, a_rows[0, :20])) < CLOSE
            probe = await client.call("ptu.probe", {"seed": 7, "tokens": 4})  # the golden input and its fingerprint are the stream's width
            assert probe.get("fingerprint") is not None or probe
            for stream in (a, b, c):
                await stream.end()
        finally:
            await client.close()
            await server.shutdown()

    run(main())


@pytest.fixture(scope="module")
def swarm(tiny):
    """A chain of two spans on the default server: both dense layers with the
    first expert layer (two kinds of block on one server), and the last expert
    layer alone. Between them the client carries the stream."""
    path, tensors = tiny
    specs = [dict(first_block=0, num_blocks=3, page_size=8, batch_max_length=96, prefill_token_budget=32),
             dict(first_block=3, num_blocks=1, page_size=16, batch_max_length=96, prefill_token_budget=32)]
    harness = SwarmHarness(path, specs).start()
    model = AutoDistributedModelForCausalLM.from_pretrained(path, initial_peers=harness.initial_peers)
    yield path, tensors, harness, model
    model.close()
    harness.stop()


def test_client_embed_two_servers_in_a_chain_client_norm_is_the_whole_model_s_reference(swarm):
    """``client_embed`` repeats the embedding four times (256 wide), the
    session carries ``[1, s, 256]`` through ``Server`` with no flag on both
    spans (a prompt of 70 in three mixed steps a server, then decode), and
    ``client_norm`` / the head sum the rows first: the last hidden state and
    the LOGITS of every position against the whole model's reference."""
    path, tensors, harness, model = swarm
    batchers = [server.handler.batcher for server in harness.servers]
    assert all(b is not None and b.backend.cache.latent_row is not None and b.backend.hidden_size == WIDTH for b in batchers)
    before = [dict(b.stats) for b in batchers]
    ids = np.random.RandomState(3).randint(0, 128, (1, 85)).astype(np.int64)
    hidden = np.asarray(model.embed(ids))
    assert hidden.shape == (1, 85, WIDTH) and all(np.array_equal(hidden[..., :C], hidden[..., k * C : (k + 1) * C]) for k in range(N))
    with model.remote.inference_session(max_length=85) as session:
        outs = [np.asarray(session.step(hidden[:, :70]))]
        outs += [np.asarray(session.step(hidden[:, p : p + 1])) for p in range(70, 85)]
    out = np.concatenate(outs, axis=1)
    assert out.shape == (1, 85, WIDTH)
    np.testing.assert_allclose(np.asarray(model.lm_logits(out))[0], reference_logits(tensors, ids[0]), atol=3e-4, rtol=0)
    family, cfg = get_block_config(path)
    normed = np.asarray(family.client_norm(model.client_params, out, cfg))[0]
    x = reference_stream(tensors, hidden[0]).reshape(85, N, C).sum(1)
    want = x / np.sqrt((x * x).mean(-1, keepdims=True) + HF["rms_norm_eps"]) * tensors["model.norm.weight"]
    assert normed.shape == (85, C) and off(normed, want) < CLOSE
    for batcher, was in zip(batchers, before):
        blocks = batcher.backend.n_blocks
        assert batcher.stats["mixed_steps"] - was["mixed_steps"] == 3
        assert batcher.stats["hc_rows"] - was["hc_rows"] == 85 * 2 * blocks
        assert batcher.stats["latent_rows_absorbed"] - was["latent_rows_absorbed"] == 15 * blocks


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
    assert off(out[0], reference_stream(tensors, hidden[0])) < CLOSE


# ---------------------------------------------------------------------------------
# what is refused, and why
# ---------------------------------------------------------------------------------


@pytest.mark.parametrize("key,value,named", [
    ("hc_mult", 1, "hc_mult 1"), ("hc_mult", None, "hc_mult None"), ("n_group", 2, "n_group"), ("topk_group", 2, "topk_group"),
    ("scoring_func", "softmax", "scoring_func"), ("topk_method", "greedy", "topk_method"), ("hidden_act", "gelu", "hidden_act"),
    ("attention_bias", True, "attention_bias"), ("moe_layer_freq", 2, "moe_layer_freq"), ("q_lora_rank", None, "q_lora_rank null"),
    ("rope_scaling", {"type": "linear", "factor": 4.0}, "rope_scaling of type 'linear'"),
])
def test_what_the_block_does_not_compute_is_refused_at_load(tmp_path, key, value, named):
    (tmp_path / "config.json").write_text(json.dumps({**HF, key: value}))
    with pytest.raises(NotImplementedError, match=f"xing4_0: {named}"):
        get_block_config(str(tmp_path))


def test_deepseek_v3_still_refuses_a_low_rank_query_yarn_and_a_stream_and_the_prediction_layer_is_said_once(tmp_path, caplog):
    """``deepseek_v3`` knows no ``hc_mult`` and refuses one by name, as it
    refuses ``q_lora_rank`` and ``rope_scaling``; ``xing4_0`` accepts
    ``num_nextn_predict_layers`` 1 and logs once, by its own name, that no
    server holds the layer; ``rope_scaling`` null is served (the plain rotary)."""
    from petals_tpu.models.deepseek_v3 import config as ds_config
    from tests.utils import TINY_DEEPSEEK_V3

    for key, value in (("hc_mult", 4), ("q_lora_rank", 24), ("rope_scaling", HF["rope_scaling"])):
        (tmp_path / "config.json").write_text(json.dumps({**TINY_DEEPSEEK_V3, key: value}))
        with pytest.raises(NotImplementedError, match=f"deepseek_v3: {key}"):
            get_block_config(str(tmp_path))
    (tmp_path / "config.json").write_text(json.dumps({**TINY_DEEPSEEK_V3, "hc_mult": 1}))
    assert get_block_config(str(tmp_path))[0].name == "deepseek_v3"
    ds_config._say_unserved.cache_clear()
    (tmp_path / "config.json").write_text(json.dumps(HF))
    package = logging.getLogger("petals_tpu")  # it does not propagate to the root logger caplog listens on
    package.addHandler(caplog.handler)
    try:
        for _ in range(3):
            family, cfg = get_block_config(str(tmp_path))
    finally:
        package.removeHandler(caplog.handler)
    said = [r for r in caplog.records if "num_nextn_predict_layers 1 is not served" in r.getMessage()]
    assert len(said) == 1 and said[0].getMessage().startswith("xing4_0:") and family.name == "xing4_0"
    (tmp_path / "config.json").write_text(json.dumps({**HF, "rope_scaling": None}))
    _, plain = get_block_config(str(tmp_path))
    assert plain.rope_scaling is None and plain.softmax_mscale == 1.0


def test_options_the_family_cannot_take_yet_are_refused_by_name_with_the_reason(tiny, tmp_path):
    """Deep prompts (on the server and in the client's prompt tuning), the
    torch surface, a tp mesh, quantized weights, quantized pages and a LoRA
    adapter: each refused with the family's name."""
    from petals_tpu.client.ptune import PTuneConfig, PTuneMixin
    from petals_tpu.parallel.mesh import tp_mesh
    from petals_tpu.parallel.tp import span_param_pspecs
    from petals_tpu.utils.convert_block import QuantType, convert_block_params
    from petals_tpu.utils.peft import load_adapter
    from safetensors.numpy import save_file

    path, _ = tiny
    family, cfg = get_block_config(path)
    assert family.tp_pspecs is None and not family.quantizable_leaves and not family.lora_targets and family.block_stream is not None
    backend = whole_backend(path)
    prompts = np.zeros((LAYERS, 1, 2, WIDTH), np.float32)
    for call in (lambda: backend.forward(rows(0, 4), prompts=prompts), lambda: backend.backward(rows(0, 4), rows(1, 4), prompts=prompts),
                 lambda: backend.inference_step(rows(0, 4), (None, None), 0, prompts=prompts)):
        with pytest.raises(NotImplementedError, match=f"xing4_0: deep prompts are not served .* stream of {N} rows \\({WIDTH} wide against the model's {C}\\)"):
            call()

    class Tuned(PTuneMixin):
        pass

    tuned = Tuned()
    tuned.family, tuned.cfg = family, cfg
    for mode in ("ptune", "deep_ptune"):
        with pytest.raises(NotImplementedError, match=f"xing4_0: prompt tuning \\({mode}\\) is not served .*{WIDTH} against {C}"):
            tuned.init_ptune(PTuneConfig(pre_seq_len=4, tuning_mode=mode))
    tuned.init_ptune(None)  # no prompt: nothing to refuse
    from petals_tpu.compat.torch_model import TorchDistributedModelForCausalLM

    with pytest.raises(NotImplementedError, match="xing4_0: the torch surface is not served"):
        TorchDistributedModelForCausalLM(tuned)
    with pytest.raises(NotImplementedError, match="xing4_0: a span of more than one kind of block is not served over a tp mesh"):
        whole_backend(path, mesh=tp_mesh(2))
    with pytest.raises(KeyError, match="xing4_0.*tp_pspecs"):
        span_param_pspecs("xing4_0", cfg)
    for kind in ("int8", "nf4a"):
        with pytest.raises(NotImplementedError, match=f"xing4_0: kv_quant_type '{kind}'.*latent row"):
            whole_backend(path, kv_quant_type=kind)
    with pytest.raises(ValueError, match="xing4_0"):
        convert_block_params(dict(load_block_params(path, 2, dtype=jnp.float32)), "xing4_0", QuantType.NF4)
    (tmp_path / "adapter_config.json").write_text(json.dumps({"r": 2, "lora_alpha": 4, "target_modules": ["q_b_proj"], "peft_type": "LORA"}))
    save_file({"base_model.model.model.layers.1.self_attn.q_b_proj.lora_A.weight": np.zeros((2, 24), np.float32),
               "base_model.model.model.layers.1.self_attn.q_b_proj.lora_B.weight": np.zeros((96, 2), np.float32)},
              str(tmp_path / "adapter_model.safetensors"))
    with pytest.raises(ValueError, match="xing4_0"):
        load_adapter(str(tmp_path), "xing4_0", block_range=range(0, LAYERS))


def test_a_family_without_a_stream_keeps_the_width_and_the_counters_it_had(tmp_path):
    from petals_tpu.server.batching import DecodeBatcher
    from petals_tpu.server.task_queue import PriorityTaskQueue

    path = make_tiny_deepseek_v3(str(tmp_path))
    family, cfg = get_block_config(path)
    assert family.block_stream is None and family.stream_for(cfg) == (cfg.hidden_size, 0)
    params = jax.tree_util.tree_map(lambda x: jnp.asarray(x)[None], load_block_params(path, 1, dtype=jnp.float32))
    backend = TransformerBackend(family, cfg, params, first_block=1, n_blocks=1, memory_cache=MemoryCache(None), compute_dtype=jnp.float32, use_flash=False)
    assert backend.hidden_size == cfg.hidden_size and backend.stream_mixes == 0
    backend.refuse_deep_prompts(np.zeros((1, 1, 2, cfg.hidden_size), np.float32))  # not refused here (the latent row's own refusal is elsewhere)
    batcher = DecodeBatcher(backend, backend.memory_cache, PriorityTaskQueue(), n_lanes=2, max_length=32, page_size=8)
    assert "hc_rows" not in batcher.stats and batcher.stats["stream_bytes_in"] == 0 == batcher.stats["stream_bytes_out"]
