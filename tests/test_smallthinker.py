"""SmallThinker (``smallthinker``) on the normal path, at a toy size on the CPU: a router that reads the layer's input
before attention, ReGLU experts, full layers without positions and windowed layers with rotary in turns. Each kind's block
against the in-repo reference (perf/reference/smallthinker.py); prefill in chunks and decode through the paged lanes, whose
windowed layers give pages back, against the reference's full forward pass; the split routing; ReGLU through the three
dispatches; the checkpoint mapping against the benchmark's weight maker."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.reference import smallthinker as reference
from petals_tpu.client.model import AutoDistributedModelForCausalLM
from petals_tpu.models import moe
from petals_tpu.models.moe import ExpertStack, MoeDims, moe_apply, moe_experts, moe_route
from petals_tpu.models.registry import get_family, span_runs
from petals_tpu.models.smallthinker import block as block_mod
from petals_tpu.server.backend import TransformerBackend
from petals_tpu.server.from_pretrained import get_block_config, load_block_params
from petals_tpu.server.memory_cache import MemoryCache
from tests.test_full_model import SwarmHarness
from tests.utils import TINY_SMALLTHINKER, make_tiny_smallthinker, tiny_smallthinker_tensors

HF = dict(TINY_SMALLTHINKER)
KINDS = [("nope", "full"), ("rope", "sliding"), ("rope", "sliding"), ("rope", "sliding")] * 2
SEQ = 40  # five windows of 8


def layer_tensors(tensors: dict, layer: int) -> dict:
    prefix = f"model.layers.{layer}."
    return {k[len(prefix):]: jnp.asarray(v) for k, v in tensors.items() if k.startswith(prefix)}


def reference_hidden(hf: dict, tensors: dict, hidden, first: int = 0, last: int = 8, block=reference.block):
    """``hidden`` [seq, h] through layers [first, last) of the reference."""
    kinds = reference.layer_kinds(hf)
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(hidden, jnp.float32)
        for i in range(first, last):
            x, _ = block(hf, layer_tensors(tensors, i), x, kinds[i])
    return np.asarray(x)


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return make_tiny_smallthinker(str(tmp_path_factory.mktemp("models"))), tiny_smallthinker_tensors(HF)


@pytest.fixture(scope="module")
def swarm(tiny):
    """One server of all eight blocks on the default path (continuous batching on the paged pool): pages of 4 under a
    window of 8, lanes of 48 positions, so a windowed layer's pages go back as a lane decodes."""
    path, tensors = tiny
    harness = SwarmHarness(path, [dict(first_block=0, num_blocks=8, page_size=4, batch_max_length=48, prefill_token_budget=8)]).start()
    model = AutoDistributedModelForCausalLM.from_pretrained(path, initial_peers=harness.initial_peers)
    yield path, tensors, harness, model
    model.close()
    harness.stop()


def whole_backend(path: str, first_block: int = 0, n_blocks: int = 8) -> TransformerBackend:
    family, cfg = get_block_config(path)
    runs = span_runs(family.span_kinds(cfg, first_block, n_blocks))
    stacked = tuple(
        jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *(load_block_params(path, first_block + i, dtype=jnp.float32)
                                                           for i in range(start, start + length)))
        for _, start, length in runs
    )
    return TransformerBackend(family, cfg, stacked[0] if len(stacked) == 1 else stacked, first_block=first_block,
                              n_blocks=n_blocks, memory_cache=MemoryCache(None), compute_dtype=jnp.float32, use_flash=False)


# ---------------------------------------------------------------------------------
# the block, by kind
# ---------------------------------------------------------------------------------


@pytest.mark.parametrize("layer", [0, 1])
def test_each_kind_of_block_matches_the_reference(tiny, layer):
    path, tensors = tiny
    family, cfg = get_block_config(path)
    assert family.name == "smallthinker" and [family.kind_of(cfg, i) for i in range(8)] == KINDS == reference.layer_kinds(HF)
    assert [family.block_window(cfg, kind) for kind in KINDS[:2]] == [None, 8]
    x = np.random.RandomState(layer).standard_normal((1, 20, 64)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        want, _ = reference.block(HF, layer_tensors(tensors, layer), jnp.asarray(x[0]), KINDS[layer])
        got, _ = family.apply_for(KINDS[layer])(load_block_params(path, layer, dtype=jnp.float32), jnp.asarray(x), None, 0, cfg)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), atol=2e-5, rtol=0)


def test_the_router_reads_the_layer_s_input_and_the_experts_the_normed_state(tiny, monkeypatch):
    """The router is fed ``h``, the experts ``m``: a block that routes on ``m`` (every other family's order) is another
    function, and the reference tells them apart."""
    path, tensors = tiny
    family, cfg = get_block_config(path)
    params = load_block_params(path, 1, dtype=jnp.float32)
    x = np.random.RandomState(5).standard_normal((1, 20, 64)).astype(np.float32)
    seen = {}
    route, experts = block_mod.moe_route, block_mod.moe_experts

    def spy_route(p, routed_on, **kw):
        seen["routed_on"] = routed_on
        return route(p, routed_on, **kw)

    def spy_experts(p, fed, *picks, **kw):
        seen["fed"], seen["activation"] = fed, kw.get("activation")
        return experts(p, fed, *picks, **kw)

    monkeypatch.setattr(block_mod, "moe_route", spy_route)
    monkeypatch.setattr(block_mod, "moe_experts", spy_experts)
    with jax.default_matmul_precision("highest"):
        want, _ = reference.block(HF, layer_tensors(tensors, 1), jnp.asarray(x[0]), KINDS[1])
        got, _ = family.apply_for(KINDS[1])(params, jnp.asarray(x), None, 0, cfg)
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), atol=2e-5, rtol=0)
        np.testing.assert_array_equal(np.asarray(seen["routed_on"]), x)  # the layer's input as it came
        assert seen["activation"] == "relu" and np.abs(np.asarray(seen["fed"]) - x).max() > 0.1
        # the other order: route on what the experts are fed
        monkeypatch.setattr(block_mod, "moe_route", lambda p, routed_on, **kw: ())
        monkeypatch.setattr(block_mod, "moe_experts", lambda p, fed, *_, **kw: experts(p, fed, *route(p, fed, top_k=3, renormalize=True), **kw))
        wrong, _ = family.apply_for(KINDS[1])(params, jnp.asarray(x), None, 0, cfg)
    assert np.abs(np.asarray(wrong[0]) - np.asarray(want)).max() > 1e-2


@pytest.mark.parametrize("activation", ["relu", "silu"])
@pytest.mark.parametrize("dispatch", ["dense", "grouped", "hit"])
def test_the_gate_s_activation_is_data_in_all_three_dispatches(dispatch, activation):
    """ReGLU (and SwiGLU, as it was) through the einsum, the grouped ``ragged_dot`` and the hit kernel against the plain
    sum over the chosen experts; routed on one tensor, fed another."""
    rng = np.random.RandomState(7)
    E, k, h, m, rows = 8, 3, 64, 32, 5
    params = {"gate": jnp.asarray(rng.standard_normal((h, E)), jnp.float32), **{
        name: jnp.asarray(rng.standard_normal(shape) * 0.1, jnp.float32) for name, shape in (("w1", (E, h, m)), ("w3", (E, h, m)), ("w2", (E, m, h)))}}
    routed_on = jnp.asarray(rng.standard_normal((rows, 1, h)), jnp.float32)
    fed = jnp.asarray(rng.standard_normal((rows, 1, h)), jnp.float32)
    act = {"relu": jax.nn.relu, "silu": jax.nn.silu}[activation]
    with jax.default_matmul_precision("highest"):
        idx, weights = moe_route(params, routed_on, top_k=k, renormalize=True)
        np.testing.assert_allclose(np.asarray(weights.sum(-1)), 1.0, atol=1e-6)
        want = np.zeros((rows, 1, h), np.float32)
        for r in range(rows):
            for e, w in zip(np.asarray(idx[r, 0]), np.asarray(weights[r, 0])):
                want[r, 0] += w * np.asarray((act(fed[r, 0] @ params["w1"][e]) * (fed[r, 0] @ params["w3"][e])) @ params["w2"][e])
        if dispatch == "hit":
            stacked = {"gate": params["gate"], "experts": ExpertStack(params["w1"][None], params["w3"][None], params["w2"][None], jnp.int32(0))}
            got = moe_experts(stacked, fed, idx, weights, dispatch="hit", activation=activation)
        else:
            got = moe_experts(params, fed, idx, weights, dispatch=dispatch, activation=activation)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=0)
    assert np.abs(want).max() > 1e-2
    if activation == "silu":  # one tensor for both, the default activation: ``moe_apply`` as every other family calls it
        with jax.default_matmul_precision("highest"):
            whole = moe_apply(params, fed, top_k=k, renormalize=True, dispatch="dense" if dispatch == "hit" else dispatch)
            again = moe_experts(params, fed, *moe_route(params, fed, top_k=k, renormalize=True), dispatch="dense" if dispatch == "hit" else dispatch)
        np.testing.assert_array_equal(np.asarray(whole), np.asarray(again))


def test_hf_to_block_params_agrees_with_the_benchmark_s_weight_maker(tiny):
    """The family's checkpoint mapping and perf/weights/smallthinker.py ``block_params`` lay the same tensors out alike."""
    from perf.weights import smallthinker as maker

    path, tensors = tiny
    family, cfg = get_block_config(path)
    for layer in (0, 1):
        t = layer_tensors(tensors, layer)
        ours = family.block_params_for({k: np.asarray(v) for k, v in t.items()}, cfg, KINDS[layer])
        theirs = maker.block_params(HF, t, KINDS[layer])
        assert sorted(ours) == sorted(theirs) == sorted(family.param_shapes_for(cfg, KINDS[layer]))
        for name, leaf in ours.items():
            np.testing.assert_array_equal(np.asarray(leaf), np.asarray(theirs[name]), err_msg=name)
            assert leaf.shape == family.param_shapes_for(cfg, KINDS[layer])[name].shape, name
    assert family.moe_dims_for(cfg, KINDS[0]) == MoeDims(8, 3, 64, 32) and moe.ACTIVATIONS.keys() == {"silu", "relu"}


def test_what_the_block_does_not_compute_is_refused_at_load(tiny, tmp_path):
    with pytest.raises(NotImplementedError, match="rope_scaling"):
        get_block_config(make_tiny_smallthinker(str(tmp_path), rope_scaling={"type": "yarn", "factor": 4}))
    with pytest.raises(NotImplementedError, match="apply_softmax"):
        get_block_config(make_tiny_smallthinker(str(tmp_path), moe_primary_router_apply_softmax=False))
    with pytest.raises(ValueError, match="fewer than num_hidden_layers"):
        get_block_config(make_tiny_smallthinker(str(tmp_path), num_hidden_layers=9))
    assert get_family("smallthinker").block_window is not None


# ---------------------------------------------------------------------------------
# through the server
# ---------------------------------------------------------------------------------


def test_private_cache_step_and_stateless_forward_walk_the_runs(tiny):
    path, tensors = tiny
    backend = whole_backend(path)
    assert [kind for kind, _, _ in backend.runs] == [KINDS[0], KINDS[1]] * 2 and backend.cache.grouped
    assert backend.cache.page_groups == ((None, (0, 4)), (8, (1, 2, 3, 5, 6, 7)))
    x = np.random.RandomState(11).standard_normal((1, 24, 64)).astype(np.float32)
    want = reference_hidden(HF, tensors, x[0])
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(np.asarray(backend.forward(x))[0], want, atol=5e-5, rtol=0)
        kv = tuple(d.make_zeros() for d in backend.cache_descriptors(1, 32, 0, 8))  # a private cache keeps every position
        out, kv = backend.inference_step(x[:, :17], kv, 0)
        outs = [np.asarray(out)]
        for p in range(17, 24):
            out, kv = backend.inference_step(x[:, p : p + 1], kv, p)
            outs.append(np.asarray(out))
    np.testing.assert_allclose(np.concatenate(outs, axis=1)[0], want, atol=5e-5, rtol=0)


def test_paged_prefill_in_chunks_then_decode_matches_the_reference_s_full_pass(swarm):
    """Through ``Server`` and ``RemoteSequential``: a prompt of 21 positions rides the lane pool's mixed steps in chunks of
    at most 8, then 19 decode steps to position 40, five windows of 8 and ten pages of 4. Every row of the span's output
    against the reference's whole forward pass; and the pool's counters: pages went back while the session ran, and a
    lane held in the windowed layers, when a step started, exactly what its rows' windows reached."""
    path, tensors, harness, model = swarm
    batcher = harness.servers[0].handler.batcher
    assert batcher.grouped and batcher.page_size == 4 and batcher.max_pages == 12
    before = dict(batcher.stats)
    hidden = np.random.RandomState(3).standard_normal((1, SEQ, 64)).astype(np.float32)
    with model.remote.inference_session(max_length=SEQ) as session:
        outs = [np.asarray(session.step(hidden[:, :21]))]
        outs += [np.asarray(session.step(hidden[:, p : p + 1])) for p in range(21, SEQ)]
    np.testing.assert_allclose(np.concatenate(outs, axis=1)[0], reference_hidden(HF, tensors, hidden[0]), atol=1e-4, rtol=0)
    delta = {k: batcher.stats[k] - before[k] for k in before if isinstance(before[k], (int, float))}
    assert delta["prefill_tokens"] == 21 and delta["batched_tokens"] == SEQ - 21 and delta["mixed_steps"] >= 3
    assert delta["window_pages_released"] >= 6  # of the ten pages a lane of 40 positions wrote a windowed group
    assert 0 < delta["window_pages_in_reach"] == delta["window_pages_held"]
    assert 0 < delta["kv_bytes_held"] < delta["kv_bytes_unfreed"]
    info = batcher.occupancy_info()
    assert [(g.window, g.layers) for g in batcher._win] == [(8, 6)]  # the pool opened with the first session
    assert info["window_pages_held"] == 0 == info["window_pages_in_reach"]  # the session is closed
    assert [g["pages_free"] == g["n_pages"] for g in info["page_groups"]] == [True, True]


def test_generate_token_identical_through_the_grouped_pool(swarm):
    """A greedy ``generate()`` of 14 tokens past the window's edge through ``Server`` (server-side generation on the lane
    pool where the server offers it: a generating lane takes its windowed layers' pages a row at a time) against the
    reference's logits."""
    path, tensors, harness, model = swarm
    ids = np.random.RandomState(6).randint(0, 128, (1, 5)).astype(np.int64)
    got = np.asarray(model.generate(ids, max_new_tokens=14))
    want = list(ids[0])
    for _ in range(14):
        x = reference_hidden(HF, tensors, tensors["model.embed_tokens.weight"][np.asarray(want)])
        x = x / np.sqrt((x * x).mean(-1, keepdims=True) + HF["rms_norm_eps"]) * tensors["model.norm.weight"]
        want.append(int(np.argmax((x @ tensors["lm_head.weight"].T)[-1])))
    np.testing.assert_array_equal(got[0], want)
    assert harness.servers[0].handler.batcher.stats["window_pages_released"] > 0
