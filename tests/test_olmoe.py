"""OLMoE (64-expert class: many small experts, top-k weights not renormalised,
QK-norm) on the normal path, at a tiny size on the CPU: the block against HF's
layer, prefill and decode through the paged lane pool against the full forward
pass, a greedy run through the swarm, quantized conversion, the tp spec, and
the shared expert dispatch (models/moe.py) held to what Mixtral's was."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch

from petals_tpu.client.model import AutoDistributedModelForCausalLM
from petals_tpu.models.common import silu
from petals_tpu.models.moe import MoeDims, grouped_dispatch, moe_apply
from petals_tpu.server.from_pretrained import get_block_config, load_block_params
from tests.test_block_exact_match import _hf_hidden_states
from tests.test_full_model import SwarmHarness, _hf_greedy, _hf_logits
from tests.utils import make_tiny_olmoe


@pytest.fixture(scope="module")
def tiny_olmoe(tmp_path_factory):
    return make_tiny_olmoe(str(tmp_path_factory.mktemp("models")))


@pytest.fixture(scope="module")
def olmoe_swarm(tiny_olmoe):
    # the default server: continuous batching on the paged lane pool
    harness = SwarmHarness(tiny_olmoe, [dict(first_block=0, num_blocks=2, page_size=16)]).start()
    model = AutoDistributedModelForCausalLM.from_pretrained(tiny_olmoe, initial_peers=harness.initial_peers)
    yield tiny_olmoe, harness, model
    model.close()
    harness.stop()


def test_block_matches_hf_layer(tiny_olmoe):
    """One block against HF's ``OlmoeDecoderLayer`` on seeded weights in
    float32 (norm vectors drawn, not ones, so a QK-norm vector that is missing
    or applied per head shows). 18 tokens a row: the grouped dispatch; and the
    same rows one position at a time below: the all-experts einsum."""
    family, cfg = get_block_config(tiny_olmoe)
    assert family.name == "olmoe" and (cfg.num_experts, cfg.num_experts_per_tok, cfg.norm_topk_prob) == (8, 3, False)
    torch.manual_seed(1)
    input_ids = torch.randint(0, 100, (2, 18))
    hiddens = _hf_hidden_states(tiny_olmoe, input_ids)
    for i in range(cfg.num_hidden_layers):
        params = load_block_params(tiny_olmoe, i, dtype=jnp.float32)
        assert params["q_norm"].shape == (64,) and params["w1"].shape == (8, 64, 64) and params["w2"].shape == (8, 64, 64)
        ours, _ = family.block_apply(params, jnp.asarray(hiddens[i]), None, 0, cfg)
        np.testing.assert_allclose(np.asarray(ours), hiddens[i + 1], atol=1e-4, rtol=0, err_msg=f"olmoe block {i}")
    # the same block through a KV cache: 7 positions at once, then one at a time
    kv = tuple(jnp.zeros((2, 24, cfg.num_key_value_heads, cfg.head_dim), jnp.float32) for _ in range(2))
    hidden, outs, position = jnp.asarray(hiddens[1]), [], 0
    for chunk in (hidden[:, :7], *(hidden[:, p : p + 1] for p in range(7, 18))):
        out, kv = family.block_apply(params, chunk, kv, position, cfg)
        outs.append(np.asarray(out))
        position += chunk.shape[1]
    np.testing.assert_allclose(np.concatenate(outs, axis=1), hiddens[2], atol=1e-4, rtol=0)


def test_paged_prefill_then_decode_matches_the_full_forward_pass(olmoe_swarm):
    """Through ``Server`` and ``RemoteSequential`` as any family goes: a
    prompt of 21 tokens rides the lane pool's mixed step in page-aligned
    chunks, then 6 decode steps, each fed the next token of a fixed sequence.
    The LOGITS of every position are compared with HF's full forward pass over
    the whole sequence, not the tokens: 2e-4 absolute on logits of size ~0.1-1,
    the tolerance of ``test_full_model_forward_matches_hf``, because both sides
    are float32 and differ only in summation order (chunked attention over
    pages, grouped against per-expert matmuls)."""
    path, harness, model = olmoe_swarm
    batcher = harness.servers[0].handler.batcher
    assert batcher is not None and batcher.page_size == 16
    before = dict(batcher.stats)
    ids = np.random.RandomState(3).randint(0, 100, (1, 27)).astype(np.int64)
    hidden = np.asarray(model.embed(ids))
    with model.remote.inference_session(max_length=27) as session:
        outs = [np.asarray(session.step(hidden[:, :21]))]
        outs += [np.asarray(session.step(hidden[:, p : p + 1])) for p in range(21, 27)]
    logits = np.asarray(model.lm_logits(np.concatenate(outs, axis=1)))
    np.testing.assert_allclose(logits, _hf_logits(path, ids), atol=2e-4, rtol=0)
    after = batcher.stats
    assert after["prefill_tokens"] - before["prefill_tokens"] == 21  # the prompt rode mixed steps of the lane pool
    assert after["batched_tokens"] - before["batched_tokens"] == 6
    # the expert counters (host side, from the shapes each step started with)
    # (the toy's 8 experts: the prompt's chunks go to ragged_dot; the 6 decode tokens took the hit dispatch, which
    # counts among the grouped, the two that read the experts reached, and alone besides)
    assert after["moe_dense_tokens"] - before["moe_dense_tokens"] == 0
    assert after["moe_grouped_tokens"] - before["moe_grouped_tokens"] == 27
    assert after["moe_hit_tokens"] - before["moe_hit_tokens"] == 6
    steps, mixed = (after[k] - before[k] for k in ("batched_steps", "mixed_steps"))
    assert after["moe_weight_passes"] - before["moe_weight_passes"] == steps + mixed


def test_a_family_without_experts_has_no_expert_counters(tmp_path):
    """``batcher.stats`` is spread into ``rpc_info()``: a dense family's
    carries none of the three keys, an expert family's carries all."""
    from petals_tpu.server.backend import TransformerBackend
    from petals_tpu.server.batching import DecodeBatcher
    from petals_tpu.server.memory_cache import MemoryCache
    from petals_tpu.server.task_queue import PriorityTaskQueue
    from tests.utils import make_tiny_llama, make_tiny_mixtral

    keys = {"moe_dense_tokens", "moe_grouped_tokens", "moe_hit_tokens", "moe_weight_passes"}
    for maker, has in ((make_tiny_llama, False), (make_tiny_mixtral, True), (make_tiny_olmoe, True)):
        path = maker(str(tmp_path))
        family, cfg = get_block_config(path)
        stacked = jax.tree_util.tree_map(lambda leaf: leaf[None], load_block_params(path, 0, dtype=jnp.float32))
        backend = TransformerBackend(family, cfg, stacked, first_block=0, n_blocks=1, memory_cache=MemoryCache(None),
                                     compute_dtype=jnp.float32, use_flash=False)
        batcher = DecodeBatcher(backend, backend.memory_cache, PriorityTaskQueue(), n_lanes=2, max_length=64, page_size=16)
        assert (keys <= set(batcher.stats)) == has and (not keys & set(batcher.stats)) == (not has), family.name
        assert (backend.moe_grouped(1) is None) == (not has)
        if has:
            # a decode row reads the experts hit out of the stacked run; a chunk of 7 rides bucket 8 (few experts: ragged_dot)
            assert backend.moe_grouped(1) == "hit" and backend.moe_grouped(7, chunk=True) == "grouped"


def test_generate_token_identical(olmoe_swarm):
    path, _, model = olmoe_swarm
    ids = np.random.RandomState(6).randint(0, 100, (1, 5)).astype(np.int64)
    np.testing.assert_array_equal(model.generate(ids, max_new_tokens=8), _hf_greedy(path, ids, 8))
    long_ids = np.random.RandomState(7).randint(0, 100, (1, 19)).astype(np.int64)  # a grouped-dispatch prompt
    np.testing.assert_array_equal(model.generate(long_ids, max_new_tokens=4), _hf_greedy(path, long_ids, 4))


def test_nf4_conversion_finds_the_family_s_leaves(tiny_olmoe):
    from petals_tpu.ops.quant import QuantizedLinear
    from petals_tpu.utils.convert_block import QuantType, convert_block_params

    family, cfg = get_block_config(tiny_olmoe)
    params = load_block_params(tiny_olmoe, 0, dtype=jnp.float32)
    quant = convert_block_params(dict(params), "olmoe", QuantType.NF4)
    quantized = {name for name, leaf in quant.items() if isinstance(leaf, QuantizedLinear)}
    assert quantized == {"wq", "wk", "wv", "wo", "w1", "w2", "w3"}  # norms, both QK-norm vectors and the router stay dense
    hidden = jnp.asarray(np.random.RandomState(0).randn(1, 9, cfg.hidden_size), jnp.float32)
    dense, _ = family.block_apply(params, hidden, None, 0, cfg)
    got, _ = family.block_apply(quant, hidden, None, 0, cfg)
    assert np.isfinite(np.asarray(got)).all()
    assert float(np.abs(np.asarray(got) - np.asarray(dense)).max()) < 0.25 * float(np.abs(np.asarray(dense)).max())


def test_tp_mesh_takes_qk_norm_across_shards(tiny_olmoe):
    """q and k are column-sharded under a tp mesh and QK-norm's mean runs over
    all their columns: the tp backend must equal the single-device one."""
    from petals_tpu.parallel.mesh import make_mesh
    from petals_tpu.server.backend import TransformerBackend
    from petals_tpu.server.memory_cache import MemoryCache

    family, cfg = get_block_config(tiny_olmoe)
    per_block = [load_block_params(tiny_olmoe, i, dtype=jnp.float32) for i in range(cfg.num_hidden_layers)]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_block)
    common = dict(first_block=0, n_blocks=cfg.num_hidden_layers, memory_cache=MemoryCache(None),
                  compute_dtype=jnp.float32, use_flash=False)
    plain = TransformerBackend(family, cfg, stacked, **common)
    tp = TransformerBackend(family, cfg, stacked, mesh=make_mesh((2,), ("tp",)), **common)
    assert len(tp.params["q_norm"].sharding.device_set) == 2
    hidden = np.random.RandomState(0).randn(2, 6, cfg.hidden_size).astype(np.float32)
    np.testing.assert_allclose(np.asarray(tp.forward(hidden)), np.asarray(plain.forward(hidden)), atol=2e-5, rtol=0)

    def cache(backend):
        kd, vd = backend.cache_descriptors(2, 16, 0, backend.n_blocks)
        return kd.make_zeros(), vd.make_zeros()

    (out_p, kv_p), (out_t, kv_t) = plain.inference_step(hidden, cache(plain), 0), tp.inference_step(hidden, cache(tp), 0)
    np.testing.assert_allclose(np.asarray(out_t), np.asarray(out_p), atol=2e-5, rtol=0)
    nxt = np.random.RandomState(1).randn(2, 1, cfg.hidden_size).astype(np.float32)
    np.testing.assert_allclose(np.asarray(tp.inference_step(nxt, kv_t, 6)[0]), np.asarray(plain.inference_step(nxt, kv_p, 6)[0]),
                               atol=2e-5, rtol=0)


def test_config_refuses_what_the_block_does_not_compute():
    from transformers import OlmoeConfig

    from petals_tpu.models.olmoe.config import OlmoeBlockConfig

    assert OlmoeBlockConfig.from_hf_config(OlmoeConfig()).num_experts == 64
    for bad in (dict(rope_scaling={"rope_type": "linear", "factor": 2.0}), dict(attention_bias=True), dict(hidden_act="gelu")):
        with pytest.raises(NotImplementedError, match="olmoe"):
            OlmoeBlockConfig.from_hf_config(OlmoeConfig(**bad))


# ---------------------------------------------------------------------------------
# the shared dispatch, held to Mixtral's before this PR
# ---------------------------------------------------------------------------------

OLD_MIN_SEQ = 8  # models/mixtral/block.py before the dispatch moved to models/moe.py


def _old_moe_apply(params, x, n_experts, top_k, *, sparse):
    """``models/mixtral/block.py`` ``moe_apply`` / ``_moe_sparse`` as they
    stood before PR 26 (dense weights only), kept here to hold the shared
    dispatch to their bits."""
    router_logits = x @ params["gate"]
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    top_probs, top_idx = jax.lax.top_k(probs, top_k)
    top_probs = top_probs / top_probs.sum(axis=-1, keepdims=True)
    w1, w2, w3 = params["w1"], params["w2"], params["w3"]
    if sparse:
        b, s, h = x.shape
        n_assign = b * s * top_k
        xf = x.reshape(b * s, h)
        flat_experts = top_idx.reshape(n_assign)
        order = jnp.argsort(flat_experts, stable=True)
        token_of = order // top_k
        xg = jnp.take(xf, token_of, axis=0)
        group_sizes = jnp.bincount(flat_experts, length=n_experts).astype(jnp.int32)
        g1 = jax.lax.ragged_dot(xg, w1, group_sizes)
        g3 = jax.lax.ragged_dot(xg, w3, group_sizes)
        out = jax.lax.ragged_dot(silu(g1) * g3, w2, group_sizes)
        wts = jnp.take(top_probs.reshape(n_assign), order).astype(jnp.float32)
        y = jnp.zeros((b * s, h), jnp.float32)
        y = y.at[token_of].add(out.astype(jnp.float32) * wts[:, None])
        return y.astype(x.dtype).reshape(b, s, h)
    one_hot = jax.nn.one_hot(top_idx, n_experts, dtype=top_probs.dtype)
    combine = (one_hot * top_probs[..., None]).sum(axis=2).astype(x.dtype)
    gate_out = jnp.einsum("bsh,ehm->ebsm", x, w1)
    up = jnp.einsum("bsh,ehm->ebsm", x, w3)
    expert_out = jnp.einsum("ebsm,emh->ebsh", silu(gate_out) * up, w2)
    return jnp.einsum("ebsh,bse->bsh", expert_out, combine)


# (hidden, expert width, experts, top k): tests/utils.make_tiny_mixtral, and Mixtral-8x7B as published
MIXTRAL_TINY, MIXTRAL_PUBLISHED = (64, 96, 4, 2), (4096, 14336, 8, 2)
# decode rows 1-8 ([lanes, 1, h]), then every chunk bucket the cells warm ([1, bucket, h]; run.warm_lengths
# at the default budget of 512 over chat prompts to 768), and the lengths under the least bucket
MIXTRAL_CALLS = [(rows, 1) for rows in range(1, 9)] + [(1, s) for s in (2, 4, 7, 8, 16, 32, 64, 128, 256, 512, 1024)]


@pytest.mark.parametrize("batch,seq", MIXTRAL_CALLS)
def test_shared_dispatch_is_mixtral_s_at_mixtral_s_shapes(batch, seq):
    """The rule that took the old constant's place takes the path Mixtral
    took before, at its tiny and its published shape class, and the shared
    ``moe_apply`` gives the old function's bits on seeded inputs (at the tiny
    class: the published one's choice is checked, its 2.8 GB are not run)."""
    old_sparse = seq >= OLD_MIN_SEQ
    for h, m, n_experts, top_k in (MIXTRAL_TINY, MIXTRAL_PUBLISHED):
        assert grouped_dispatch(MoeDims(n_experts, top_k, h, m), seq) == ("grouped" if old_sparse else "dense")
    h, m, n_experts, top_k = MIXTRAL_TINY
    keys = jax.random.split(jax.random.PRNGKey(batch * 4096 + seq), 5)
    params = {"gate": jax.random.normal(keys[0], (h, n_experts), jnp.float32) * 0.2,
              "w1": jax.random.normal(keys[1], (n_experts, h, m), jnp.float32) * 0.05,
              "w2": jax.random.normal(keys[2], (n_experts, m, h), jnp.float32) * 0.05,
              "w3": jax.random.normal(keys[3], (n_experts, h, m), jnp.float32) * 0.05}
    for dtype in (jnp.float32, jnp.bfloat16):
        p = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
        x = (jax.random.normal(keys[4], (batch, seq, h), jnp.float32) * 0.3).astype(dtype)
        new = jax.jit(lambda p, x: moe_apply(p, x, top_k=top_k, renormalize=True, dispatch="grouped" if old_sparse else "dense"))(p, x)
        old = jax.jit(lambda p, x: _old_moe_apply(p, x, n_experts, top_k, sparse=old_sparse))(p, x)
        assert np.asarray(new).tobytes() == np.asarray(old).tobytes()


def test_routing_weights_are_kept_or_renormalised_as_the_family_says():
    """One token, hand-made logits: without renormalising, the output is the
    experts' outputs weighted by their softmax mass; with it, by their share
    of the kept mass. Dense and grouped agree on both."""
    h, m, n_experts, top_k = 8, 4, 6, 2
    rng = np.random.RandomState(0)
    params = {"gate": jnp.asarray(rng.randn(h, n_experts), jnp.float32), "w1": jnp.asarray(rng.randn(n_experts, h, m), jnp.float32),
              "w2": jnp.asarray(rng.randn(n_experts, m, h), jnp.float32), "w3": jnp.asarray(rng.randn(n_experts, h, m), jnp.float32)}
    x = jnp.asarray(rng.randn(1, 1, h), jnp.float32)
    probs = np.asarray(jax.nn.softmax(x[0, 0] @ params["gate"]))
    kept = np.argsort(-probs)[:top_k]
    expert = lambda e: np.asarray((silu(x[0, 0] @ params["w1"][e]) * (x[0, 0] @ params["w3"][e])) @ params["w2"][e])
    for renormalize in (False, True):
        weights = probs[kept] / (probs[kept].sum() if renormalize else 1.0)
        want = sum(w * expert(e) for w, e in zip(weights, kept))
        for grouped in (False, True):
            got = np.asarray(moe_apply(params, x, top_k=top_k, renormalize=renormalize, dispatch="grouped" if grouped else "dense"))[0, 0]
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
