"""``longcat_flash`` (LongCat-Flash-Chat's model_type) on the normal path, at a
toy size on the CPU: a block that is a DOUBLE layer (two latent attentions,
two dense feed-forwards, one shortcut-connected expert layer whose router is
wider than the experts that exist), so a position caches TWO latent rows a
block. The block and its expert branch from a checkpoint against
transformers' own ``LongcatFlashDecoderLayer`` / ``LongcatFlashMoE`` and
against the in-repo reference (perf/reference/longcat_flash.py); the three
routing rules of models/moe.py each against its published router; the shares
of the experts adding up with the identities counted once; prefill in mixed
steps and decode beside other lanes through ``Server`` and both sub-layers'
pages against the reference's whole forward pass; the stateless passes; what
the family refuses, each with its reason."""

import asyncio
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.reference import longcat_flash as reference
from petals_tpu.client.model import AutoDistributedModelForCausalLM
from petals_tpu.data_structures import CHAIN_DELIMITER, make_uid
from petals_tpu.models import moe
from petals_tpu.models.longcat_flash.block import shortcut_experts
from petals_tpu.rpc import RpcClient
from petals_tpu.rpc.serialization import deserialize_array, serialize_array
from petals_tpu.server.backend import TransformerBackend
from petals_tpu.server.batching import DecodeBatcher
from petals_tpu.server.from_pretrained import get_block_config, load_block_params
from petals_tpu.server.memory_cache import MemoryCache
from petals_tpu.server.server import Server, default_dht_prefix
from petals_tpu.server.task_queue import PriorityTaskQueue
from tests.test_full_model import SwarmHarness
from tests.utils import counted, lane_pools, make_tiny_longcat_flash, steps_booked, tiny_longcat_flash_tensors, TINY_LONGCAT_FLASH

HF = dict(TINY_LONGCAT_FLASH)
BLOCKS, SUBLAYERS = HF["num_layers"], 2
ROW = (HF["kv_lora_rank"] + HF["qk_rope_head_dim"]) * 4  # bytes a position an ATTENTION in float32
LATENT_KEYS = {"latent_rows_read", "latent_rows_held", "latent_rows_absorbed", "latent_rows_expanded", "latent_positions_expanded",
               "latent_positions_held", "latent_score_pairs", "latent_bytes_held"}
# float32 on the CPU, the served path against the reference, as a share of the largest output: they differ in
# the order of float32 sums (measured 2e-7..5e-6); a row that read another lane's or another sub-layer's page lands near 1
CLOSE = 5e-5


def run(coro):
    return asyncio.run(coro)


def layer_tensors(tensors: dict, layer: int) -> dict:
    prefix = f"model.layers.{layer}."
    return {k[len(prefix):]: jnp.asarray(v) for k, v in tensors.items() if k.startswith(prefix)}


def reference_hidden(tensors: dict, hidden, first: int = 0, last: int = BLOCKS, hf: dict = HF) -> np.ndarray:
    """``hidden`` [seq, h] through blocks [first, last) of the reference."""
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(hidden, jnp.float32)
        for i in range(first, last):
            x, _ = reference.block(hf, layer_tensors(tensors, i), x)
    return np.asarray(x)


def reference_logits(tensors: dict, ids) -> np.ndarray:
    x = reference_hidden(tensors, tensors["model.embed_tokens.weight"][np.asarray(ids)])
    x = x / np.sqrt((x * x).mean(-1, keepdims=True) + HF["rms_norm_eps"]) * tensors["model.norm.weight"]
    return x @ tensors["lm_head.weight"].T


def off(got, want) -> float:
    return float(np.abs(np.asarray(got) - want).max() / np.abs(want).max())


def rows(seed: int, n: int) -> np.ndarray:
    return (np.random.RandomState(seed).randn(1, n, HF["hidden_size"]) * 0.5).astype(np.float32)


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return make_tiny_longcat_flash(str(tmp_path_factory.mktemp("models"))), tiny_longcat_flash_tensors(HF)


@pytest.fixture(scope="module")
def theirs():
    """transformers' own double layers, the toy checkpoint's tensors loaded: ``(layers, run)``, ``run(layer, x [1, seq, h])``."""
    import torch
    from transformers import LongcatFlashConfig
    from transformers.models.longcat_flash import modeling_longcat_flash as modeling

    config = LongcatFlashConfig(**{k: v for k, v in HF.items() if k != "model_type"}, attn_implementation="eager")
    tensors = tiny_longcat_flash_tensors(HF)
    layers = []
    for i in range(BLOCKS):
        layer = modeling.LongcatFlashDecoderLayer(config, i).eval()
        prefix = f"model.layers.{i}."
        layer.load_state_dict({k[len(prefix):]: torch.tensor(v) for k, v in tensors.items() if k.startswith(prefix)}, strict=True)
        layers.append(layer)
    rotary = modeling.LongcatFlashRotaryEmbedding(config)

    def run_layer(i: int, x: np.ndarray) -> np.ndarray:
        seq = x.shape[1]
        xt, positions = torch.tensor(x), torch.arange(seq)[None]
        mask = torch.full((seq, seq), float("-inf")).triu(1)[None, None]  # eager attention takes its causal mask from the caller
        with torch.no_grad():
            return layers[i](xt, attention_mask=mask, position_ids=positions, position_embeddings=rotary(xt, positions)).numpy()

    return layers, run_layer


def whole_backend(path: str, **kw) -> TransformerBackend:
    family, cfg = get_block_config(path)
    params = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *(load_block_params(path, i, dtype=jnp.float32) for i in range(BLOCKS)))
    return TransformerBackend(family, cfg, params, first_block=0, n_blocks=BLOCKS, memory_cache=MemoryCache(None),
                              compute_dtype=jnp.float32, use_flash=False, **kw)


async def start_server(path, **kwargs):
    server = Server(path, compute_dtype=jnp.float32, use_flash=False, **kwargs)
    await server.start()
    client = await RpcClient.connect(server.rpc_server.host, server.rpc_server.port)
    return server, client


async def open_session(client, path, max_length: int, **extra):
    uids = CHAIN_DELIMITER.join(make_uid(default_dht_prefix(path), i) for i in range(BLOCKS))
    stream = await client.open_stream("ptu.inference")
    await stream.send({"uids": uids, "max_length": max_length, "batch_size": 1, **extra})
    await stream.recv(timeout=60)
    return stream


async def step(stream, hidden, **extra) -> np.ndarray:
    await stream.send({"tensors": {"hidden": serialize_array(hidden)}, **extra})
    return deserialize_array((await stream.recv(timeout=300))["tensors"]["hidden"])


# ---------------------------------------------------------------------------------
# the block and its expert branch from a checkpoint: transformers' own layer, and the reference
# ---------------------------------------------------------------------------------


@pytest.mark.parametrize("layer", [0, 1])
def test_a_checkpoint_s_block_is_transformers_double_layer_and_the_reference(tiny, theirs, layer):
    """``hf_to_block_params`` under transformers' names (both sub-layers'
    leaves, the rope columns of ``q_b_proj`` and ``kv_a_proj_with_mqa``
    de-interleaved, ``kv_b_proj`` cut into ``wuk`` and ``wuv``), and the block
    over 40 positions with no cache against ``LongcatFlashDecoderLayer`` and
    against the reference, which rotates in the published, interleaved form.
    The framework's number of blocks is ``num_layers``, not the class's
    ``num_hidden_layers``."""
    path, tensors = tiny
    family, cfg = get_block_config(path)
    assert family.name == "longcat_flash" and family.kind_of(cfg, layer) is None and cfg.num_hidden_layers == BLOCKS == HF["num_hidden_layers"] // 2
    assert family.latent_for(cfg, None) == (16, 8) and family.sublayers_for(cfg, None) == 2 and family.block_state is None
    dims = family.moe_dims_for(cfg, None)
    assert (dims.experts, dims.top_k, dims.routed, dims.first, dims.identities) == (8, 3, 12, 0, 4)
    assert (cfg.q_scale, cfg.kv_scale, cfg.latent_norm_eps, cfg.rms_norm_eps) == (2**0.5, 2.0, 1e-6, 1e-5)
    params = load_block_params(path, layer, dtype=jnp.float32)
    shapes = family.param_shapes_for(cfg, None)
    assert set(params) == set(shapes) and all(params[name].shape == shapes[name].shape for name in shapes)
    assert params["wuk_1"].shape == (4, 16, 16) and params["wqb_0"].shape == (32, 96) and params["gate"].shape == (64, 12) and params["w1"].shape == (8, 64, 32)
    x = rows(1 + layer, 40)
    with jax.default_matmul_precision("highest"):
        out = np.asarray(family.block_apply(params, jnp.asarray(x), None, 0, cfg)[0][0])
    want = theirs[1](layer, x)[0]
    assert off(out, want) < CLOSE and off(reference_hidden(tensors, x[0], layer, layer + 1), want) < CLOSE


def test_the_expert_branch_alone_is_transformers_moe_and_each_piece_of_it_shows(tiny, theirs):
    """At random weights the branch is small beside the residual, so the
    block's comparison alone would not see it: ``shortcut_experts`` and the
    reference's ``experts`` against ``LongcatFlashMoE`` on the same rows. What
    each piece is worth, on the reference itself: the identities left out, a
    renormalised weight, a missing scale, the bias left out of the choice, and
    the bias left in the weights are each far outside what separates the
    implementations."""
    import torch

    path, tensors = tiny
    family, cfg = get_block_config(path)
    params = load_block_params(path, 1, dtype=jnp.float32)
    n = rows(5, 48)
    with torch.no_grad():
        want = theirs[0][1].mlp(torch.tensor(n)).numpy()[0]
    w = layer_tensors(tensors, 1)
    with jax.default_matmul_precision("highest"):
        assert off(shortcut_experts(params, jnp.asarray(n), cfg)[0], want) < CLOSE
        mine, margin = reference.experts(HF, w, jnp.asarray(n[0]))
        assert off(mine, want) < CLOSE and np.isfinite(np.asarray(margin)).all()  # every expert held: every boundary counts
        assert off(reference.experts({**HF, "zero_expert_num": 0}, {**w, "mlp.router.classifier.weight": w["mlp.router.classifier.weight"][:8],
                                     "mlp.router.e_score_correction_bias": w["mlp.router.e_score_correction_bias"][:8]}, jnp.asarray(n[0]))[0], want) > 1e-2
        assert off(reference.experts({**HF, "routed_scaling_factor": 1.0}, w, jnp.asarray(n[0]))[0], want) > 0.5
        no_bias = {**w, "mlp.router.e_score_correction_bias": jnp.zeros(12)}
        assert off(reference.experts(HF, no_bias, jnp.asarray(n[0]))[0], want) > 1e-2  # the bias chooses
        shifted = {**w, "mlp.router.e_score_correction_bias": w["mlp.router.e_score_correction_bias"] + 0.25}
        assert off(reference.experts(HF, shifted, jnp.asarray(n[0]))[0], want) < 1e-6  # and does not weigh
        # a renormalised weight is another function: the served rule with ``renormalize`` on
        renormalised = moe.moe_apply(params, jnp.asarray(n), top_k=3, renormalize=True, dispatch="dense", scoring="softmax_bias", scale=6.0, identities=4)
        assert off(renormalised[0], want) > 1e-2


ROUTERS = ["softmax", "sigmoid", "softmax_bias"]


@pytest.mark.parametrize("rule", ROUTERS)
def test_each_routing_rule_is_its_published_router(rule):
    """``moe.route`` under each ``Routing.scoring`` against the router it was
    written from: ``OlmoeSparseMoeBlock``'s lines (softmax, top k, the kept
    weights as they are or renormalised), ``DeepseekV3TopkRouter`` (sigmoid,
    the bias chooses, renormalised and scaled) and ``LongcatFlashTopkRouter``
    (softmax, the bias chooses, scaled, not renormalised)."""
    import torch

    rng = np.random.default_rng(ROUTERS.index(rule))
    x = rng.standard_normal((2, 9, 64)).astype(np.float32)
    gate, bias = rng.standard_normal((64, 12)).astype(np.float32) * 0.3, rng.standard_normal(12).astype(np.float32) * 0.05
    params = {"gate": jnp.asarray(gate), "gate_bias": jnp.asarray(bias)}
    xt = torch.tensor(x).reshape(-1, 64)
    if rule == "softmax":
        probs = torch.nn.functional.softmax(xt @ torch.tensor(gate), dim=-1, dtype=torch.float)
        want_w, want_i = torch.topk(probs, 3, dim=-1)
        routing = moe.Routing(3)
        with jax.default_matmul_precision("highest"):
            renorm = moe.route(params, jnp.asarray(x), moe.Routing(3, renormalize=True))[1]
        np.testing.assert_allclose(np.asarray(renorm).reshape(-1, 3), (want_w / want_w.sum(-1, keepdim=True)).numpy(), atol=1e-6)
    elif rule == "sigmoid":
        from transformers import DeepseekV3Config
        from transformers.models.deepseek_v3.modeling_deepseek_v3 import DeepseekV3TopkRouter

        router = DeepseekV3TopkRouter(DeepseekV3Config(hidden_size=64, n_routed_experts=12, num_experts_per_tok=3, n_group=1, topk_group=1,
                                                       norm_topk_prob=True, routed_scaling_factor=2.5))
        router.weight.data, router.e_score_correction_bias = torch.tensor(gate.T.copy()), torch.tensor(bias)
        with torch.no_grad():
            want_i, want_w = router(xt)
        routing = moe.Routing(3, "sigmoid", True, 2.5)
    else:
        from transformers import LongcatFlashConfig
        from transformers.models.longcat_flash.modeling_longcat_flash import LongcatFlashTopkRouter

        router = LongcatFlashTopkRouter(LongcatFlashConfig(hidden_size=64, n_routed_experts=8, zero_expert_num=4, moe_topk=3, routed_scaling_factor=6.0))
        router.classifier.weight.data, router.e_score_correction_bias = torch.tensor(gate.T.copy()), torch.tensor(bias)
        with torch.no_grad():
            want_i, want_w = router(xt)
        routing = moe.Routing(3, "softmax_bias", False, 6.0)
    with jax.default_matmul_precision("highest"):
        idx, weights = moe.route(params, jnp.asarray(x), routing)
    got = {(t, int(e)): float(w) for t, (es, ws) in enumerate(zip(np.asarray(idx).reshape(-1, 3), np.asarray(weights).reshape(-1, 3))) for e, w in zip(es, ws)}
    want = {(t, int(e)): float(w) for t, (es, ws) in enumerate(zip(want_i.numpy(), want_w.numpy())) for e, w in zip(es, ws)}
    assert got.keys() == want.keys()  # the same picks, in whatever order
    assert max(abs(got[k] - want[k]) for k in want) < 1e-5
    with pytest.raises(ValueError, match="unknown routing rule"):
        moe.route(params, jnp.asarray(x), moe.Routing(3, "tanh"))


@pytest.mark.parametrize("dispatch", ["dense", "grouped", "hit"])
def test_the_shares_add_up_with_the_identities_counted_once(tmp_path_factory, theirs, dispatch):
    """Four servers of 2 of the 8 FFN experts each, every one under the whole
    router of 12 outputs: the held parts, plus the identities' part counted
    ONCE (every chip computes it alike), equal the uncut ``LongcatFlashMoE``,
    in each dispatch. A share's own part alone is not the layer."""
    import torch

    tensors = tiny_longcat_flash_tensors(HF)
    n = rows(9, 6 if dispatch == "hit" else 40)
    if dispatch == "hit":
        n = n.transpose(1, 0, 2)  # decode-shaped: six lanes of one row
    with torch.no_grad():
        want = theirs[0][0].mlp(torch.tensor(n)).numpy()
    root = str(tmp_path_factory.mktemp("shares"))
    parts, zero = [], None
    for first in (0, 2, 4, 6):
        path = make_tiny_longcat_flash(root, held=2, first=first)
        family, cfg = get_block_config(path)
        dims = family.moe_dims_for(cfg, None)
        assert (dims.experts, dims.routed, dims.first, dims.identities) == (2, 12, first, 4) and cfg.num_experts_exist == 8
        params = dict(load_block_params(path, 0, dtype=jnp.float32))
        assert params["w1"].shape == (2, 64, 32) and params["gate"].shape == (64, 12)
        if dispatch == "hit":
            params["experts"] = moe.ExpertStack(params.pop("w1")[None], params.pop("w3")[None], params.pop("w2")[None], jnp.int32(0))
        kw = dict(top_k=3, renormalize=False, scoring="softmax_bias", scale=6.0, identities=4)
        with jax.default_matmul_precision("highest"):
            parts.append(np.asarray(moe.moe_apply(params, jnp.asarray(n), dispatch=dispatch, first=first, **kw)))
            if zero is None:  # the identities' part: the layer with no expert's pick kept
                nothing = {**params, "gate_bias": params["gate_bias"].at[:8].set(-1.0)}
                if dispatch != "hit":
                    nothing["w2"] = jnp.full_like(params["w2"], jnp.nan)  # no pick of an expert: no expert's row is weighed
                idx, w = moe.route(params, jnp.asarray(n), moe.Routing(3, "softmax_bias", False, 6.0))
                zero = np.asarray((jnp.where(idx >= 8, w, 0.0).sum(-1)[..., None] * jnp.asarray(n)))
    assert off(sum(parts) - 3 * zero, want) < CLOSE and off(parts[0], want) > 1e-2 and np.abs(zero).max() > 1e-2


def test_an_identity_pick_takes_no_hit_slot_and_no_grouped_row():
    """Of six picks two are identities (indices past every expert) and one an
    absent expert's: ``hit_slots`` fills slots with the held experts that were
    picked and weighs nothing else; the grouped dispatch's groups hold the
    held picks' rows and no other; the rule that chooses a dispatch sees the
    FFN picks only."""
    top_idx = jnp.asarray([[0, 9, 2], [11, 2, 5]], jnp.int32)  # 4 experts held of 8; 8..11 identities; 5 absent
    top_w = jnp.asarray([[0.5, 0.25, 0.125], [0.75, 0.0625, 0.3]], jnp.float32)
    slot_expert, n_hit, combine = moe.hit_slots(top_idx, top_w, None, 4)
    assert int(n_hit) == 2 and list(np.asarray(slot_expert)) == [0, 2, 2, 2]
    assert np.array_equal(np.asarray(combine), [[0.5, 0.0], [0.125, 0.0625], [0.0, 0.0], [0.0, 0.0]])
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((1, 2, 16)), jnp.float32)
    w1, w3, w2 = (jnp.asarray(rng.standard_normal(s), jnp.float32) * 0.3 for s in ((4, 16, 8), (4, 16, 8), (4, 8, 16)))
    got = moe._experts_grouped(x, w1, w2, w3, top_idx[None], top_w[None], share=True)
    expert = lambda e, row: (jax.nn.silu(row @ w1[e]) * (row @ w3[e])) @ w2[e]
    want = jnp.stack([0.5 * expert(0, x[0, 0]) + 0.125 * expert(2, x[0, 0]), 0.0625 * expert(2, x[0, 1])])
    assert np.abs(np.asarray(got[0]) - np.asarray(want)).max() < 1e-5
    wide = moe.MoeDims(16, 12, 6144, 2048, routed=768, first=0, identities=256)
    assert moe.grouped_dispatch(wide, 1, stacked=True) == "hit" and moe.grouped_dispatch(wide, 512, stacked=True) == "dense"
    every = moe.MoeDims(8, 3, 64, 32, routed=12, first=0, identities=4)  # all that exist are held: no share, whatever the router's width
    assert moe.grouped_dispatch(every, 64) == "grouped" and moe.grouped_dispatch(every._replace(experts=4), 64) == "dense"


def test_forward_and_backward_run_a_whole_sequence_through_both_attentions(tiny):
    """The stateless passes (``rpc_forward`` / ``rpc_backward``): the span's
    output over 60 positions against the reference, and the gradient against
    the reference's."""
    path, tensors = tiny
    backend = whole_backend(path)
    x, grad_out = rows(4, 60), rows(5, 60)

    def traced(h):
        for i in range(BLOCKS):
            h, _ = reference.block(HF, layer_tensors(tensors, i), h)
        return h

    with jax.default_matmul_precision("highest"):
        assert off(np.asarray(backend.forward(x))[0], reference_hidden(tensors, x[0])) < CLOSE
        grad = np.asarray(backend.backward(x, grad_out)[0])[0]
        _, vjp = jax.vjp(traced, jnp.asarray(x[0]))
        want_grad = np.asarray(vjp(jnp.asarray(grad_out[0]))[0])
    assert off(grad, want_grad) < 10 * CLOSE


# ---------------------------------------------------------------------------------
# two cache rows a position a block: the pools, the counters, the published span
# ---------------------------------------------------------------------------------


def test_the_pools_hold_two_layers_of_pages_a_block_and_each_attention_writes_its_own(tiny):
    path, _ = tiny
    backend = whole_backend(path)
    assert backend.cache.latent_row == (16, 8) and backend.cache.block_rows == 2 and backend.cache.page_layers == 4 and backend.cache.kv_layers == (0, 1)
    c, pe = lane_pools(backend, 6, 16, end=BLOCKS)[0]
    assert c.shape == (4, 6, 16, 16) and pe.shape == (4, 6, 1, 128)  # [2 x blocks, ...]: a block's two layers one after the other
    assert lane_pools(backend, 6, 16, start=1, end=2)[0][0].shape[0] == 2
    assert backend.cache.cache_bytes_per_token() == backend.cache.kv_bytes_per_token() == 4 * ROW == 384 and backend.cache.lane_pool(3, 2, 16).walks == ()
    reads = counted(backend, 3, 2, 16, np.array([4, 20]), chunk=(0, 10))
    assert reads["latent_rows_held"] == 4 * (5 + 21) and reads["latent_rows_absorbed"] == 4 * 2 and reads["latent_rows_expanded"] == 4 * 10
    assert reads["latent_positions_held"] == 4 * 10 and reads["latent_score_pairs"] == 4 * (5 + 21 + 55)
    # one decode step of two lanes (the third idle) over zeroed pools: each of the four layers of pages holds exactly
    # the two live lanes' rows, at the pages the tables name, and nothing of one sub-layer lies in another's pages
    pools = tuple(jnp.zeros(d.shape, jnp.float32) for d in (c, pe))
    tables = np.array([[4, 1], [0, 5], [2, 3]], np.int32)
    positions = np.array([17, 3, 32], np.int32)  # lane 2 rides the idle sentinel
    with jax.default_matmul_precision("highest"):
        out, (c_pool, pe_pool) = backend.paged_decode_step(rows(3, 3).transpose(1, 0, 2), pools, positions, tables)
    c_pool, pe_pool = np.asarray(c_pool), np.asarray(pe_pool)
    assert np.isfinite(np.asarray(out)).all()
    written = [tuple(map(tuple, np.argwhere(np.abs(c_pool[layer]).sum(-1) > 0))) for layer in range(4)]
    assert all(w == ((0, 3), (1, 1)) for w in written), written  # page 1 row 1 (position 17), page 0 row 3
    assert len({c_pool[layer, 1, 1].tobytes() for layer in range(4)}) == 4  # four attentions, four different rows
    assert all(np.count_nonzero(pe_pool[layer]) == 2 * 8 for layer in range(4))
    with pytest.raises(NotImplementedError, match="longcat_flash: a cache without one pair of latent rows' pages a sub-layer"):
        backend.family.block_apply(jax.tree_util.tree_map(lambda l: l[0], backend.params), jnp.zeros((1, 1, 64)), (None,), 0, backend.cfg)


def test_a_family_of_one_cache_row_a_block_keeps_its_pools_and_more_rows_without_a_latent_row_are_refused(tmp_path):
    import dataclasses

    from tests.utils import make_tiny_falcon

    path = make_tiny_falcon(str(tmp_path))
    family, cfg = get_block_config(path)
    assert family.block_sublayers is None and family.sublayers_for(cfg, None) == 1
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *(load_block_params(path, i, dtype=jnp.float32) for i in range(2)))
    make = lambda fam: TransformerBackend(fam, cfg, stacked, first_block=0, n_blocks=2, memory_cache=MemoryCache(None),
                                          compute_dtype=jnp.float32, use_flash=False)
    backend = make(family)
    assert backend.cache.block_rows == 1 and backend.cache.page_layers == 2 and lane_pools(backend, 6, 8, end=2)[0][0].shape[0] == 2
    with pytest.raises(NotImplementedError, match="falcon: more than one cache row a position a block .* latent rows"):
        make(dataclasses.replace(family, block_sublayers=lambda cfg, kind: 2))


def published_span() -> tuple:
    """``(backend, stacked shapes, configuration)`` of longcat-flash-span4-ep32 on shapes alone."""
    import tempfile
    from pathlib import Path

    from perf.config import load as load_config

    root = Path(__file__).resolve().parents[1]
    config = load_config(root / "perf/configs/longcat-flash-span4-ep32.json", "longcat-flash-span4-ep32")
    with tempfile.TemporaryDirectory() as tmp:
        (Path(tmp) / "config.json").write_text(json.dumps(config["config"]))
        family, cfg = get_block_config(tmp)
    stacked = {name: jax.ShapeDtypeStruct((4, *leaf.shape), leaf.dtype) for name, leaf in family.param_shapes_for(cfg, None, jnp.bfloat16).items()}
    return TransformerBackend(family, cfg, stacked, first_block=0, n_blocks=4, memory_cache=None), stacked, config


def test_the_published_span_is_62_percent_of_a_chip_and_a_position_caches_2304_bytes_a_block():
    """longcat-flash-span4-ep32 on shapes alone: ISSUE 56's count of the
    parameters, a position's 2 x 1,152 B a block, the 8 lanes in 189 MB, the
    router's 768 outputs over 16 held experts, and the dispatch each call
    shape takes."""
    backend, stacked, config = published_span()
    cfg, args = backend.cfg, config["server_args"]
    matrices = sum(int(np.prod(leaf.shape[1:])) for leaf in stacked.values() if leaf.ndim > 2)
    assert matrices == 1_242_824_704 and 0.62 < 4 * matrices * 2 / 16e9 < 0.63
    assert cfg.num_hidden_layers == 4 and cfg.router_width == 768 and (cfg.num_experts, cfg.num_experts_exist, cfg.first_expert) == (16, 512, 0)
    assert (cfg.q_scale, round(cfg.kv_scale, 3)) == (2.0, 3.464)
    assert backend.cache.latent_row == (512, 64) and backend.cache.page_layers == 8 and backend.cache.kv_bytes_per_token() == 4 * 2 * 1152 == 9216
    c, pe = lane_pools(backend, 8 * 40, 64, end=4)[0]
    assert c.shape == (8, 320, 64, 512) and pe.shape == (8, 320, 32, 128)
    pool = sum(int(np.prod(d.shape)) * 2 for d in (c, pe))
    assert pool == args["batch_lanes"] * 9216 * args["batch_max_length"] == 188_743_680 and pool <= 0.15 * 16 * 2**30
    dims = backend.moe_dims
    assert (dims.experts, dims.top_k, dims.routed, dims.identities) == (16, 12, 768, 256)
    assert backend.moe_grouped(1) == "hit" and backend.moe_grouped(512, chunk=True) == "dense"
    reads = counted(backend, 8, 40, 64, np.full(8, 1799))
    assert reads["latent_rows_held"] == 8 * 8 * 1800 and reads["latent_rows_absorbed"] == 8 * 8


# ---------------------------------------------------------------------------------
# through Server and the paged lane pool
# ---------------------------------------------------------------------------------


def test_prompt_in_three_mixed_steps_beside_two_decoding_lanes_then_decode_and_a_reused_lane_match_the_reference(tmp_path_factory):
    """On a server that holds FFN experts 4-7 of 8 (under the whole router,
    identities included): sessions B (a context of 70 and more) and C (3 and
    more) decode while A's prompt of 100 rides three mixed steps (32, 32 and 36
    rows: a budget of 40 cut to its bucket; the expanded form over what the lane holds and the chunk's own rows, in
    both attentions of both blocks); then all three decode at once (the
    absorbed form) over permuted pages beside an idle lane. Every ROW of every
    session against the reference's whole forward pass at the same share; the
    counters count four sub-layers. Then a lane given back and taken again:
    the new tenant reads nothing of its predecessor in either sub-layer's
    pages."""
    path = make_tiny_longcat_flash(str(tmp_path_factory.mktemp("share")), held=4, first=4)
    hf, tensors = {**HF, "n_routed_experts": 4, "expert_share": {"routed": 8, "first": 4}}, tiny_longcat_flash_tensors(HF)

    async def main():
        server, client = await start_server(path, batch_lanes=4, batch_max_length=160, page_size=16, n_pages=30, prefill_token_budget=40)
        try:
            batcher = server.handler.batcher
            assert batcher.page_size == 16 and server.handler.prefix_cache is None and LATENT_KEYS <= set(batcher.stats)
            assert batcher._pool.page_bytes == 16 * 4 * ROW
            assert {"moe_chunk_rows_computed", "moe_chunk_rows_routed"} <= set(batcher.stats)  # a share of the experts that exist
            a_rows, b_rows, c_rows = rows(1, 130), rows(2, 140), rows(3, 60)
            b, c = await open_session(client, path, 160), await open_session(client, path, 160)
            got_b, got_c = [await step(b, b_rows[:, :70])], [await step(c, c_rows[:, :3])]
            assert [buf.shape for buf in batcher._buffers()] == [(4, 30, 16, 16), (4, 30, 1, 128)]  # two blocks: four layers of pages
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
            assert batcher.stats["mixed_steps"] - before["mixed_steps"] == 3 and batcher.stats["prefill_tokens"] - before["prefill_tokens"] == 100
            assert not batcher.paged_summary()["tables_contiguous"]
            for i in range(12):  # all three decode at once, the fourth lane idle
                outs = await asyncio.gather(step(a, a_rows[:, 100 + i : 101 + i]), step(b, b_rows[:, pos_b + i : pos_b + i + 1]),
                                            step(c, c_rows[:, pos_c + i : pos_c + i + 1]))
                for got, out in zip((got_a, got_b, got_c), outs):
                    got.append(out)
            await steps_booked(batcher)
            now = batcher.stats
            delta = {key: now[key] - before[key] for key in LATENT_KEYS}
            decoded, layers = (pos_b - 70) + (pos_c - 3) + 3 * 12, BLOCKS * SUBLAYERS
            assert delta["latent_rows_absorbed"] == decoded * layers and delta["latent_rows_expanded"] == 100 * layers
            assert delta["latent_positions_held"] == (32 + 64 + 100) * layers and delta["latent_positions_expanded"] >= delta["latent_positions_held"]
            assert 0 < delta["latent_rows_held"] <= delta["latent_rows_read"] and delta["latent_score_pairs"] > delta["latent_rows_held"]
            assert delta["latent_bytes_held"] > 0 and delta["latent_bytes_held"] % (16 * 4 * ROW) == 0  # whole pages of four rows a position
            assert now["moe_chunk_rows_computed"] - before["moe_chunk_rows_computed"] == 100 * 4  # the einsum over the 4 held
            assert now["moe_chunk_rows_routed"] - before["moe_chunk_rows_routed"] == pytest.approx(100 * 3 * 4 / 12)
            info = await client.call("ptu.info", {})
            assert info["pool"]["latent_row"] == [16, 8] and info["pool"]["latent_bytes_held"] == (30 - info["pool"]["pages_free"]) * 16 * 4 * ROW > 0
            for got, data in ((got_a, a_rows), (got_b, b_rows), (got_c, c_rows)):
                got = np.concatenate(got, axis=1)[0]
                assert off(got, reference_hidden(tensors, data[0, : got.shape[0]], hf=hf)) < CLOSE
            assert off(np.concatenate(got_a, axis=1)[0], reference_hidden(tensors, a_rows[0, :112])) > 1e-3  # the uncut layer is another function
            await a.end()
            again = await open_session(client, path, 160)
            d_rows = rows(7, 40)
            out = [await step(again, d_rows[:, :30])] + [await step(again, d_rows[:, p : p + 1]) for p in range(30, 40)]
            assert off(np.concatenate(out, axis=1)[0], reference_hidden(tensors, d_rows[0], hf=hf)) < CLOSE
            for stream in (again, b, c):
                await stream.end()
        finally:
            await client.close()
            await server.shutdown()

    run(main())


def test_remote_sequential_prefill_in_chunks_then_decode_matches_the_reference_s_logits(tiny):
    """Through ``Server`` with no flag and ``RemoteSequential`` over a chain of
    two spans of one double layer each (DHT announce by ``num_layers`` uids): a
    prompt of 70 in three mixed steps a server, then decode; the LOGITS of
    every position against the reference's whole forward pass; then
    ``rpc_forward`` over the chain."""
    path, tensors = tiny
    specs = [dict(first_block=0, num_blocks=1, page_size=8, batch_max_length=96, prefill_token_budget=32),
             dict(first_block=1, num_blocks=1, page_size=16, batch_max_length=96, prefill_token_budget=32)]
    harness = SwarmHarness(path, specs).start()
    model = AutoDistributedModelForCausalLM.from_pretrained(path, initial_peers=harness.initial_peers)
    try:
        batchers = [server.handler.batcher for server in harness.servers]
        assert all(b is not None and b.backend.cache.latent_row is not None and b.backend.cache.page_layers == 2 for b in batchers) and [b.page_size for b in batchers] == [8, 16]
        before = [dict(b.stats) for b in batchers]
        ids = np.random.RandomState(3).randint(0, 128, (1, 85)).astype(np.int64)
        hidden = np.asarray(model.embed(ids))
        with model.remote.inference_session(max_length=85) as session:
            outs = [np.asarray(session.step(hidden[:, :70]))]
            outs += [np.asarray(session.step(hidden[:, p : p + 1])) for p in range(70, 85)]
        logits = np.asarray(model.lm_logits(np.concatenate(outs, axis=1)))[0]
        np.testing.assert_allclose(logits, reference_logits(tensors, ids[0]), atol=3e-4, rtol=0)
        for batcher, was in zip(batchers, before):
            assert batcher.stats["mixed_steps"] - was["mixed_steps"] == 3
            assert batcher.stats["latent_rows_expanded"] - was["latent_rows_expanded"] == 70 * 2
            assert batcher.stats["latent_rows_absorbed"] - was["latent_rows_absorbed"] == 15 * 2
        out = np.asarray(model.remote.forward(hidden))  # rpc_forward: the whole sequence, expanded, no cache
        assert off(out[0], reference_hidden(tensors, hidden[0])) < CLOSE
    finally:
        model.close()
        harness.stop()


# ---------------------------------------------------------------------------------
# what is refused, and why
# ---------------------------------------------------------------------------------


@pytest.mark.parametrize("key,value,named", [
    ("rope_scaling", {"rope_type": "yarn", "factor": 4.0, "original_max_position_embeddings": 64}, "rope_scaling"),
    ("attention_bias", True, "attention_bias"), ("router_bias", True, "router_bias"), ("zero_expert_type", "copy", "zero_expert_type"),
    ("hidden_act", "gelu", "hidden_act"), ("q_lora_rank", None, "q_lora_rank"), ("mla_scale_kv_lora", False, "mla_scale_kv_lora"),
    ("mla_scale_q_lora", False, "mla_scale_q_lora"),
])
def test_what_the_block_does_not_compute_is_refused_at_load(tmp_path, key, value, named):
    (tmp_path / "config.json").write_text(json.dumps({**HF, key: value}))
    with pytest.raises(NotImplementedError, match=f"longcat_flash: {named}"):
        get_block_config(str(tmp_path))


def test_a_share_that_is_not_among_the_experts_that_exist_is_refused(tmp_path):
    (tmp_path / "config.json").write_text(json.dumps({**HF, "n_routed_experts": 4, "expert_share": {"routed": 8, "first": 6}}))
    with pytest.raises(ValueError, match=r"longcat_flash: experts \[6, 10\) are not among the 8 that exist"):
        get_block_config(str(tmp_path))


REFUSED_BY_THE_BACKEND = {
    "a private cache": lambda b: b.cache_descriptors(1, 32, 0, BLOCKS),
    "a step on a private cache": lambda b: b.inference_step(rows(0, 4), (None, None), 0),
    "speculative verify": lambda b: b.paged_spec_verify_step(None, np.zeros((2, 3), np.int32), (None, None), np.zeros(2, np.int32),
                                                             np.zeros((2, 2), np.int32), sampling_vecs={}),
    "the dense lane pool": lambda b: DecodeBatcher(b, b.memory_cache, PriorityTaskQueue(), n_lanes=2, max_length=32, page_size=None),
    "the host swap tier": lambda b: DecodeBatcher(b, b.memory_cache, PriorityTaskQueue(), n_lanes=2, max_length=32, page_size=8,
                                                  swap_host_bytes=1 << 20),
}


@pytest.mark.parametrize("what", sorted(REFUSED_BY_THE_BACKEND))
def test_cache_paths_that_do_not_carry_the_latent_rows_refuse_them_with_the_reason(tiny, what):
    backend = whole_backend(tiny[0])
    with pytest.raises(NotImplementedError, match="longcat_flash: .* latent row in place of .*only the paged lane pool's decode, generation and mixed steps"):
        REFUSED_BY_THE_BACKEND[what](backend)


def test_options_the_family_cannot_take_yet_are_refused(tiny, tmp_path):
    """A tp mesh, quantized weights, quantized pages and a LoRA adapter: refused with the family's name."""
    from petals_tpu.parallel.mesh import tp_mesh
    from petals_tpu.utils.convert_block import QuantType, convert_block_params
    from petals_tpu.utils.peft import load_adapter
    from safetensors.numpy import save_file

    path, _ = tiny
    family, cfg = get_block_config(path)
    assert family.tp_pspecs is None and not family.quantizable_leaves and not family.lora_targets
    with pytest.raises(KeyError, match="longcat_flash"):
        whole_backend(path, mesh=tp_mesh(2))
    for kind in ("int8", "nf4a"):
        with pytest.raises(NotImplementedError, match=f"longcat_flash: kv_quant_type '{kind}'.*latent row"):
            whole_backend(path, kv_quant_type=kind)
    with pytest.raises(ValueError, match="longcat_flash"):
        convert_block_params(dict(load_block_params(path, 1, dtype=jnp.float32)), "longcat_flash", QuantType.NF4)
    (tmp_path / "adapter_config.json").write_text(json.dumps({"r": 2, "lora_alpha": 4, "target_modules": ["q_b_proj"], "peft_type": "LORA"}))
    save_file({"base_model.model.model.layers.1.self_attn.0.q_b_proj.lora_A.weight": np.zeros((2, 32), np.float32),
               "base_model.model.model.layers.1.self_attn.0.q_b_proj.lora_B.weight": np.zeros((96, 2), np.float32)},
              str(tmp_path / "adapter_model.safetensors"))
    with pytest.raises(ValueError, match="longcat_flash"):
        load_adapter(str(tmp_path), "longcat_flash", block_range=range(0, BLOCKS))


def test_what_ships_or_cuts_a_cache_is_refused_over_the_wire_and_the_prefix_cache_is_off(tiny):
    """A rollback behind the position, ``kv_adopt``, a session export and a
    session that would take a private cache: each error names the reason. The
    server's default prefix cache is switched off for the span."""
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
            again = await step(stream, data[:, :40], start_from_position=0)  # from the start: served, both sub-layers' rows written over
            assert off(again[0], reference_hidden(tensors, data[0])) < CLOSE
            with pytest.raises(Exception, match="kv_adopt / kv_import.*latent row"):
                await stream.send({"kv_adopt": {"session_id": "x", "position": 4}})
                await stream.recv(timeout=60)
            live = await open_session(client, path, 64, session_id="live-one")
            await step(live, data[:, :8])
            with pytest.raises(Exception, match="a snapshot of a lane's cache.*latent row"):
                await client.call("ptu.session_export", {"session_id": "live-one", "start": 0, "end": BLOCKS})
            await live.end()
            uids = CHAIN_DELIMITER.join(make_uid(default_dht_prefix(path), i) for i in range(BLOCKS))
            wide = await client.open_stream("ptu.inference")  # two sequences a session take no lane
            await wide.send({"uids": uids, "max_length": 32, "batch_size": 2})
            with pytest.raises(Exception, match="a private cache.*latent row"):
                await wide.recv(timeout=60)
        finally:
            await client.close()
            await server.shutdown()

    run(main())
