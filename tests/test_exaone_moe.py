"""K-EXAONE (``exaone_moe``) on the normal path, at a toy size on the CPU: a
span of more than one kind of block (a dense first layer before expert layers,
windowed and full attention in turns), a share of each layer's experts beside
a shared one, a sigmoid router with a selection bias. Each kind's block against
the in-repo reference (perf/reference/exaone_moe.py) and against the pieces
the installed transformers has; prefill and decode through the private cache
and the paged pool against the reference's full forward pass; a greedy run
over a chain of two spans; what the family refuses; its counters."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch

from perf.reference import exaone_moe as reference
from petals_tpu.client.model import AutoDistributedModelForCausalLM
from petals_tpu.models.common import rms_norm, silu
from petals_tpu.models.moe import MoeDims, Routing, grouped_dispatch, moe_apply, route
from petals_tpu.models.registry import span_runs
from petals_tpu.server.backend import TransformerBackend
from petals_tpu.server.from_pretrained import get_block_config, load_block_params
from petals_tpu.server.memory_cache import MemoryCache
from tests.test_full_model import SwarmHarness
from tests.utils import TINY_EXAONE_MOE, make_tiny_exaone_moe, make_tiny_falcon, tiny_exaone_moe_tensors

HF = dict(TINY_EXAONE_MOE)
KINDS = [("dense", "sliding"), ("sparse", "sliding"), ("sparse", "sliding"), ("sparse", "full"), ("sparse", "sliding")]
SEQ = 40  # five windows of 8


def layer_tensors(tensors: dict, layer: int) -> dict:
    prefix = f"model.layers.{layer}."
    return {k[len(prefix):]: jnp.asarray(v) for k, v in tensors.items() if k.startswith(prefix)}


def reference_hidden(hf: dict, tensors: dict, hidden, first: int = 0, last: int = 5):
    """``hidden`` [seq, h] through layers [first, last) of the reference."""
    kinds = reference.layer_kinds(hf)
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(hidden, jnp.float32)
        for i in range(first, last):
            x, _ = reference.block(hf, layer_tensors(tensors, i), x, kinds[i])
    return np.asarray(x)


def reference_logits(hf: dict, tensors: dict, ids) -> np.ndarray:
    """ids [seq] -> logits [seq, vocab]: embedding, every layer of the
    reference, the final RMS norm, the head."""
    x = reference_hidden(hf, tensors, tensors["model.embed_tokens.weight"][np.asarray(ids)])
    x = x / np.sqrt((x * x).mean(-1, keepdims=True) + hf["rms_norm_eps"]) * tensors["model.norm.weight"]
    return x @ tensors["lm_head.weight"].T


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return make_tiny_exaone_moe(str(tmp_path_factory.mktemp("models"))), tiny_exaone_moe_tensors(HF)


@pytest.fixture(scope="module")
def swarm(tiny):
    """A chain of two spans on the default server (continuous batching on the
    paged pool): blocks [0, 3) are D-L, S-L, S-L and the second span starts at
    block 3, S-G, whose kinds differ from block 0's. Pages of 4 under a window
    of 8: a windowed layer's decode gathers 3 of a lane's 12 table slots."""
    path, tensors = tiny
    specs = [dict(first_block=0, num_blocks=3, page_size=4, batch_max_length=48),
             dict(first_block=3, num_blocks=2, page_size=4, batch_max_length=48)]
    harness = SwarmHarness(path, specs).start()
    model = AutoDistributedModelForCausalLM.from_pretrained(path, initial_peers=harness.initial_peers)
    yield path, tensors, harness, model
    model.close()
    harness.stop()


def whole_backend(path: str, first_block: int = 0, n_blocks: int = 5) -> TransformerBackend:
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


def test_each_kind_of_block_matches_the_reference(tiny):
    """Every layer of the toy model (four kinds) over 40 positions at once,
    then the same rows through a KV cache: 13 positions, then one at a time
    across the window's edge. The kind is read at the block's absolute index."""
    path, tensors = tiny
    family, cfg = get_block_config(path)
    assert family.name == "exaone_moe" and family.span_kinds(cfg, 0, 5) == KINDS == reference.layer_kinds(HF)
    assert family.span_kinds(cfg, 3, 2) == KINDS[3:]  # a server that starts at block 3 gets its own kinds
    assert span_runs(KINDS) == [(KINDS[0], 0, 1), (KINDS[1], 1, 2), (KINDS[3], 3, 1), (KINDS[4], 4, 1)]
    x = np.random.RandomState(0).randn(SEQ, 64).astype(np.float32)
    for i, kind in enumerate(KINDS):
        want = reference_hidden(HF, tensors, x, i, i + 1)
        params = load_block_params(path, i, dtype=jnp.float32)
        assert params["q_norm"].shape == (16,) and ("wg" in params) == (kind[0] == "dense") and ("ws1" in params) == (kind[0] == "sparse")
        apply = family.apply_for(kind)
        got, _ = apply(params, jnp.asarray(x)[None], None, 0, cfg)
        np.testing.assert_allclose(np.asarray(got)[0], want, atol=1e-4, rtol=0, err_msg=f"block {i} {kind}")
        kv = tuple(jnp.zeros((1, SEQ, cfg.num_key_value_heads, cfg.head_dim), jnp.float32) for _ in range(2))
        outs, position = [], 0
        for chunk in (x[None, :13], *(x[None, p : p + 1] for p in range(13, SEQ))):
            out, kv = apply(params, jnp.asarray(chunk), kv, position, cfg)
            outs.append(np.asarray(out))
            position += chunk.shape[1]
        np.testing.assert_allclose(np.concatenate(outs, axis=1)[0], want, atol=1e-4, rtol=0, err_msg=f"cached block {i} {kind}")


@pytest.mark.parametrize("layer,sliding", [(0, True), (3, False)])
def test_attention_matches_hf_exaone4_attention(tiny, layer, sliding):
    """The family's own attention in the installed transformers
    (``Exaone4Attention`` on a hybrid toy config: QK-norm over each head,
    rotary and the window in sliding layers only). The block's attention is
    read off a dense-MLP block whose down projection is zero."""
    from transformers import Exaone4Config
    from transformers.models.exaone4.modeling_exaone4 import Exaone4Attention, Exaone4RotaryEmbedding

    path, tensors = tiny
    family, cfg = get_block_config(path)
    hf_cfg = Exaone4Config(hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16, num_hidden_layers=5,
                           sliding_window=8, sliding_window_pattern="LLLG", layer_types=HF["layer_types"],
                           rope_theta=1e6, rms_norm_eps=1e-5, max_position_embeddings=256, attention_dropout=0.0)
    hf_cfg._attn_implementation = "eager"
    attn = Exaone4Attention(hf_cfg, layer).eval()
    w = layer_tensors(tensors, layer)
    with torch.no_grad():
        for name in ("q_proj", "k_proj", "v_proj", "o_proj", "q_norm", "k_norm"):
            getattr(attn, name).weight.copy_(torch.from_numpy(np.array(w[f"self_attn.{name}.weight"])))
    assert attn.is_sliding == sliding
    x = np.random.RandomState(1).randn(1, 24, 64).astype(np.float32)
    normed = np.asarray(rms_norm(jnp.asarray(x), w["input_layernorm.weight"], 1e-5))
    distance = np.arange(24)[:, None] - np.arange(24)[None, :]
    allowed = (distance >= 0) & ((distance < 8) if sliding else True)
    mask = torch.from_numpy(np.where(allowed, 0.0, -np.inf).astype(np.float32))[None, None]
    with torch.no_grad():
        cos_sin = Exaone4RotaryEmbedding(hf_cfg)(torch.from_numpy(normed), torch.arange(24)[None])
        want = attn(torch.from_numpy(normed), cos_sin, attention_mask=mask)[0].numpy()
    kind = ("dense", "sliding" if sliding else "full")
    params = dict(load_block_params(path, 0, dtype=jnp.float32))  # block 0's dense MLP, silenced
    params.update({k: v for k, v in load_block_params(path, layer, dtype=jnp.float32).items() if k in ("ln1", "wq", "wk", "wv", "wo", "q_norm", "k_norm")})
    params["wd"] = jnp.zeros_like(params["wd"])
    got, _ = family.block_apply(params, jnp.asarray(x), None, 0, cfg, kind=kind)
    np.testing.assert_allclose(np.asarray(got) - x, want, atol=1e-5, rtol=0)


def test_expert_layer_matches_hf_deepseek_v3_moe(tiny):
    """The router the config's keys name, in the installed transformers
    (``DeepseekV3MoE``: sigmoid scores in float32, a selection bias that
    chooses and does not weigh, kept weights renormalised and scaled, one
    shared expert): both dispatches, every expert held."""
    from transformers import DeepseekV3Config
    from transformers.models.deepseek_v3.modeling_deepseek_v3 import DeepseekV3MoE

    path, tensors = tiny
    hf_cfg = DeepseekV3Config(hidden_size=64, moe_intermediate_size=32, n_routed_experts=16, n_shared_experts=1,
                              num_experts_per_tok=4, n_group=1, topk_group=1, norm_topk_prob=True, routed_scaling_factor=2.5,
                              hidden_act="silu")
    moe = DeepseekV3MoE(hf_cfg).eval()
    w = layer_tensors(tensors, 2)
    with torch.no_grad():
        t = lambda name: torch.from_numpy(np.array(w[name]))
        moe.gate.weight.copy_(t("mlp.gate.weight"))
        moe.gate.e_score_correction_bias.copy_(t("mlp.gate.e_score_correction_bias"))
        for module, prefix in [(moe.shared_experts, "mlp.shared_experts."), *((moe.experts[e], f"mlp.experts.{e}.") for e in range(16))]:
            for proj in ("gate_proj", "up_proj", "down_proj"):
                getattr(module, proj).weight.copy_(t(prefix + proj + ".weight"))
    x = np.random.RandomState(2).randn(2, 11, 64).astype(np.float32)
    with torch.no_grad():
        want = moe(torch.from_numpy(x)).numpy()
    params = load_block_params(path, 2, dtype=jnp.float32)
    for grouped in (False, True):
        got = moe_apply(params, jnp.asarray(x), top_k=4, renormalize=True, dispatch="grouped" if grouped else "dense", scoring="sigmoid", scale=2.5)
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-5, rtol=0, err_msg=f"grouped={grouped}")


# ---------------------------------------------------------------------------------
# a share of a layer's experts (model-configs guide, section 4)
# ---------------------------------------------------------------------------------


def test_the_shares_add_up_to_the_uncut_layer_with_the_shared_expert_once(tiny):
    """Four chips hold 4 of the 16 routed experts each (``first`` 0, 4, 8,
    12). Their routed parts plus the shared expert ONCE are the uncut layer of
    the reference, within float32 summation order, in both dispatches; a token
    none of whose chosen experts a chip holds gets the shared expert's part
    alone there. The reference given the same share agrees with each chip."""
    path, tensors = tiny
    w = {**layer_tensors(tensors, 1), "self_attn.o_proj.weight": jnp.zeros((64, 64))}  # attention silenced: out = x + F(n2(x))
    x = np.random.RandomState(3).randn(SEQ, 64).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        uncut = np.asarray(reference.block(HF, w, jnp.asarray(x), KINDS[1])[0]) - x
    params = load_block_params(path, 1, dtype=jnp.float32)
    r = rms_norm(jnp.asarray(x)[None], params["ln2"], 1e-5)
    rule = dict(top_k=4, renormalize=True, scoring="sigmoid", scale=2.5)
    chosen = np.asarray(route(params, r, Routing(4, "sigmoid", True, 2.5))[0])[0]  # [seq, 4] among the 16
    for grouped in (False, True):
        parts, shared = [], None
        for first in (0, 4, 8, 12):
            mine = {**params, **{k: params[k][first : first + 4] for k in ("w1", "w2", "w3")}}
            routed_only = {k: v for k, v in mine.items() if not k.startswith("ws")}
            part = np.asarray(moe_apply(routed_only, r, dispatch="grouped" if grouped else "dense", first=first, **rule))[0]
            with_shared = np.asarray(moe_apply(mine, r, dispatch="grouped" if grouped else "dense", first=first, **rule))[0]
            shared = with_shared - part if shared is None else shared
            none_held = ~((chosen >= first) & (chosen < first + 4)).any(-1)
            assert none_held.any() and not part[none_held].any()  # nothing of the routed experts: dropped, not rerouted
            np.testing.assert_array_equal(with_shared[none_held], (with_shared - part)[none_held])
            parts.append(part)
            share_hf = {**HF, "num_experts": 4, "expert_share": {"routed": 16, "first": first}}
            with jax.default_matmul_precision("highest"):
                want = np.asarray(reference.block(share_hf, w, jnp.asarray(x), KINDS[1])[0]) - x
            np.testing.assert_allclose(with_shared, want, atol=1e-5, rtol=0)
        np.testing.assert_allclose(sum(parts) + shared, uncut, atol=1e-5, rtol=0, err_msg=f"grouped={grouped}")
    # through the loader: a model directory that holds experts [8, 12) slices them out of the published tensors
    share_path = make_tiny_exaone_moe(path + "-share", held=4, first=8)
    family, cfg = get_block_config(share_path)
    assert (cfg.num_experts, cfg.num_experts_routed, cfg.first_expert) == (4, 16, 8)
    assert family.moe_dims_for(cfg, KINDS[1]) == MoeDims(4, 4, 64, 32, routed=16, first=8) and family.moe_dims_for(cfg, KINDS[0]) is None
    loaded = load_block_params(share_path, 1, dtype=jnp.float32)
    assert loaded["w1"].shape == (4, 64, 32) and loaded["gate"].shape == (64, 16) and loaded["gate_bias"].dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(loaded["w2"]), np.asarray(params["w2"][8:12]))


def test_the_router_s_bias_chooses_and_does_not_weigh(tiny):
    path, _ = tiny
    params = load_block_params(path, 1, dtype=jnp.float32)
    r = jnp.asarray(np.random.RandomState(4).randn(1, SEQ, 64), jnp.float32)
    rule = Routing(4, "sigmoid", True, 2.5)
    scores = np.asarray(jax.nn.sigmoid(r @ params["gate"]))[0]
    with_bias, weights = (np.asarray(a)[0] for a in route(params, r, rule))
    without, _ = route({**params, "gate_bias": jnp.zeros(16)}, r, rule)
    assert (np.sort(with_bias, -1) != np.sort(np.asarray(without)[0], -1)).any()  # the bias changes which are chosen
    kept = np.take_along_axis(scores, with_bias, -1)
    np.testing.assert_allclose(weights, 2.5 * kept / kept.sum(-1, keepdims=True), rtol=1e-5)  # weighed by score alone
    pushed, weights = (np.asarray(a)[0] for a in route({**params, "gate_bias": jnp.zeros(16).at[5].set(10.0)}, r, rule))
    assert (pushed == 5).any(-1).all()  # a large bias puts expert 5 among every token's four
    kept = np.take_along_axis(scores, pushed, -1)
    np.testing.assert_allclose(weights, 2.5 * kept / kept.sum(-1, keepdims=True), rtol=1e-5)  # at its score, not score + 10


def _moe_apply_before(params, x, *, top_k, renormalize, grouped):
    """``models/moe.py`` ``moe_apply`` as it stood before this PR (dense
    weights only), kept here to hold the new one to Mixtral's and OLMoE's bits."""
    router_logits = x @ params["gate"]
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    top_probs, top_idx = jax.lax.top_k(probs, top_k)
    if renormalize:
        top_probs = top_probs / top_probs.sum(axis=-1, keepdims=True)
    w1, w2, w3 = params["w1"], params["w2"], params["w3"]
    n_experts = params["gate"].shape[-1]
    if grouped:
        b, s, h = x.shape
        n_assign = b * s * top_k
        flat_experts = top_idx.reshape(n_assign)
        order = jnp.argsort(flat_experts, stable=True)
        token_of = order // top_k
        xg = jnp.take(x.reshape(b * s, h), token_of, axis=0)
        group_sizes = jnp.bincount(flat_experts, length=n_experts).astype(jnp.int32)
        g1 = jax.lax.ragged_dot(xg, w1, group_sizes)
        g3 = jax.lax.ragged_dot(xg, w3, group_sizes)
        out = jax.lax.ragged_dot(silu(g1) * g3, w2, group_sizes)
        wts = jnp.take(top_probs.reshape(n_assign), order).astype(jnp.float32)
        y = jnp.zeros((b * s, h), jnp.float32).at[token_of].add(out.astype(jnp.float32) * wts[:, None])
        return y.astype(x.dtype).reshape(b, s, h)
    one_hot = jax.nn.one_hot(top_idx, n_experts, dtype=top_probs.dtype)
    combine = (one_hot * top_probs[..., None]).sum(axis=2).astype(x.dtype)
    gate_out = jnp.einsum("bsh,ehm->ebsm", x, w1)
    up = jnp.einsum("bsh,ehm->ebsm", x, w3)
    expert_out = jnp.einsum("ebsm,emh->ebsh", silu(gate_out) * up, w2)
    return jnp.einsum("ebsh,bse->bsh", expert_out, combine)


@pytest.mark.parametrize("family_rule", ["mixtral", "olmoe"])
@pytest.mark.parametrize("batch,seq", [(8, 1), (3, 1), (1, 8), (1, 64), (2, 19)])
def test_every_expert_held_and_a_softmax_rule_give_the_bits_they_gave(family_rule, batch, seq):
    """With every expert held, no shared expert and a softmax rule the expert
    layer is bit for bit the function before this PR, in both dispatches and
    both dtypes: Mixtral's rule (4 experts top 2, renormalised) and OLMoE's
    (8 experts top 3, the softmax mass kept as it is)."""
    h, m, n_experts, top_k, renormalize = (64, 96, 4, 2, True) if family_rule == "mixtral" else (64, 64, 8, 3, False)
    keys = jax.random.split(jax.random.PRNGKey(batch * 4096 + seq), 5)
    params = {"gate": jax.random.normal(keys[0], (h, n_experts), jnp.float32) * 0.2,
              "w1": jax.random.normal(keys[1], (n_experts, h, m), jnp.float32) * 0.05,
              "w2": jax.random.normal(keys[2], (n_experts, m, h), jnp.float32) * 0.05,
              "w3": jax.random.normal(keys[3], (n_experts, h, m), jnp.float32) * 0.05}
    for dtype in (jnp.float32, jnp.bfloat16):
        p = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
        x = (jax.random.normal(keys[4], (batch, seq, h), jnp.float32) * 0.3).astype(dtype)
        for grouped in (False, True):
            new = jax.jit(lambda p, x: moe_apply(p, x, top_k=top_k, renormalize=renormalize, dispatch="grouped" if grouped else "dense"))(p, x)
            old = jax.jit(lambda p, x: _moe_apply_before(p, x, top_k=top_k, renormalize=renormalize, grouped=grouped))(p, x)
            assert np.asarray(new).tobytes() == np.asarray(old).tobytes(), (dtype, grouped)


def test_the_dispatch_rule_knows_the_share_and_keeps_the_others_choices():
    """``grouped_dispatch`` reckons a held share by the copy ``ragged_dot``
    needs out of the stacked run, with no family's name in it: at K-EXAONE's
    published shapes every call measured keeps the all-experts einsum (alone
    the grouped dispatch wins a decode step's 8 rows, 0.40 ms a layer against
    1.65 on the v5e; in the step it lost, 15.7 ms against 12.3). Mixtral's and
    OLMoE's choices at every bucket are what they were."""
    share = MoeDims(16, 8, 6144, 2048, routed=128)
    for seq in (1, 4, 7, 8, 16, 32, 64, 128, 256, 512, 1024):
        assert grouped_dispatch(share, seq) == "dense"
    assert grouped_dispatch(MoeDims(8, 2, 4096, 14336, routed=8), 64) == "grouped"  # every routed expert held: no share
    for seq in (1, 4, 7):
        assert grouped_dispatch(MoeDims(8, 2, 4096, 14336), seq) == "dense" == grouped_dispatch(MoeDims(64, 8, 2048, 1024), seq)
    for seq in (8, 16, 32, 64, 128, 256, 512, 1024):
        assert grouped_dispatch(MoeDims(8, 2, 4096, 14336), seq) == "grouped"
        assert grouped_dispatch(MoeDims(64, 8, 2048, 1024), seq) == ("grouped" if seq >= 1024 else "dense")


# ---------------------------------------------------------------------------------
# a span of more than one kind through the programs
# ---------------------------------------------------------------------------------


def test_private_cache_step_and_stateless_forward_walk_the_runs(tiny):
    """``TransformerBackend`` over all five blocks (four runs of three kinds
    of tree): a prompt chunk then decode steps through the private cache, and
    the stateless forward with its backward, against the reference's full pass."""
    path, tensors = tiny
    backend = whole_backend(path)
    assert [(kind, start, length) for kind, start, length in backend.runs] == span_runs(KINDS)
    assert backend.moe_dims == MoeDims(16, 4, 64, 32, routed=16, first=0) and backend.cache.layer_windows == (8, 8, 8, None, 8)
    x = np.random.RandomState(5).randn(1, SEQ, 64).astype(np.float32)
    want = reference_hidden(HF, tensors, x[0])
    np.testing.assert_allclose(np.asarray(backend.forward(x))[0], want, atol=1e-4, rtol=0)
    grad_out = np.random.RandomState(6).randn(1, SEQ, 64).astype(np.float32)
    grad, _ = backend.backward(x, grad_out)

    def whole(h):
        for (kind, start, length), run in zip(backend.runs, backend.params):
            for i in range(length):
                h, _ = backend.family.apply_for(kind)(jax.tree_util.tree_map(lambda a: a[i], run), h, None, 0, backend.cfg)
        return h

    np.testing.assert_allclose(np.asarray(grad), np.asarray(jax.vjp(whole, jnp.asarray(x))[1](jnp.asarray(grad_out))[0]), atol=1e-4, rtol=0)
    descriptors = backend.cache_descriptors(1, SEQ, 0, 5)
    k, v = (jnp.zeros(d.shape, d.dtype) for d in descriptors)
    outs, position = [], 0
    for chunk in (x[:, :13], *(x[:, p : p + 1] for p in range(13, SEQ))):
        out, (k, v) = backend.inference_step(chunk, (k, v), position)
        outs.append(np.asarray(out))
        position += chunk.shape[1]
    np.testing.assert_allclose(np.concatenate(outs, axis=1)[0], want, atol=1e-4, rtol=0)
    with pytest.raises(ValueError, match="exaone_moe.*4 runs of one kind"):  # a span of more than one kind comes as its runs
        TransformerBackend(backend.family, backend.cfg, backend.params[0], first_block=0, n_blocks=5,
                           memory_cache=MemoryCache(None), compute_dtype=jnp.float32, use_flash=False)


def test_paged_prefill_then_decode_matches_the_reference_s_full_pass(swarm):
    """Through ``Server`` and ``RemoteSequential`` over the chain of two
    spans: a prompt of 21 tokens rides the lane pools' mixed steps in
    page-aligned chunks, then 19 decode steps to position 40, crossing the
    window's edge (8) and many pages' (4). The LOGITS of every position
    against the reference's full forward pass. The window counters: a windowed
    layer's decode gathers 3 of a lane's 12 table slots, a full one all 12."""
    path, tensors, harness, model = swarm
    batchers = [server.handler.batcher for server in harness.servers]
    assert all(b is not None and b.page_size == 4 and b.max_pages == 12 for b in batchers) and [b.grouped for b in batchers] == [False, True]
    before = [dict(b.stats) for b in batchers]
    ids = np.random.RandomState(3).randint(0, 128, (1, SEQ)).astype(np.int64)
    hidden = np.asarray(model.embed(ids))
    with model.remote.inference_session(max_length=SEQ) as session:
        outs = [np.asarray(session.step(hidden[:, :21]))]
        outs += [np.asarray(session.step(hidden[:, p : p + 1])) for p in range(21, SEQ)]
    logits = np.asarray(model.lm_logits(np.concatenate(outs, axis=1)))[0]
    np.testing.assert_allclose(logits, reference_logits(HF, tensors, ids[0]), atol=2e-4, rtol=0)
    for batcher, was, windows in zip(batchers, before, ([8, 8, 8], [None, 8])):
        delta = {k: batcher.stats[k] - was[k] for k in was if isinstance(was[k], (int, float))}
        assert delta["prefill_tokens"] == 21 and delta["batched_tokens"] == SEQ - 21
        assert delta["moe_dense_tokens"] + delta["moe_grouped_tokens"] == SEQ and delta["moe_weight_passes"] == delta["batched_steps"] + delta["mixed_steps"]
        # the prompt's chunks took the all-experts einsum (a share keeps it), every decode token the hit dispatch
        assert delta["moe_dense_tokens"] == 21 and delta["moe_grouped_tokens"] == delta["moe_hit_tokens"] == SEQ - 21
        lanes, pure = batcher.n_lanes, delta["batched_steps"] - delta["mixed_steps"]
        assert pure == SEQ - 21  # the prompt's chunks rode steps of their own kind
        decode_gathered = lanes * sum(3 if w else 12 for w in windows)
        assert backend_reach(batcher) == decode_gathered
        assert delta["attn_pages_tabled"] == (lanes * delta["batched_steps"] + delta["mixed_steps"]) * 12 * len(windows)
        assert pure * decode_gathered < delta["attn_pages_gathered"] < delta["attn_pages_tabled"]
        if batcher.grouped:
            # windowed and full layers in one span: pages by kind of layer, and a windowed layer's go back as its window
            # moves, so a lane holds there, when a step starts, exactly the 2 or 3 pages its window reaches
            assert 0 < delta["window_pages_in_reach"] == delta["window_pages_held"] and delta["window_pages_released"] >= 6
        else:  # one kind of layer: one pool under one table, the decoding lane held 6-10 pages a windowed layer, its window reached 2 or 3
            assert 0 < delta["window_pages_in_reach"] < delta["window_pages_held"] and "window_pages_released" not in delta
        info = batcher.occupancy_info()
        assert info["window_pages_held"] == 0 == info["window_pages_in_reach"]  # the session is closed


def backend_reach(batcher) -> int:
    return batcher.n_lanes * batcher._pool._pages_gathered(1)


@pytest.mark.parametrize("path", ["composed", "kernel"])
def test_a_family_without_declared_windows_has_no_window_counters(tmp_path, monkeypatch, path):
    """Every family on the paged pool counts the table slots its steps read
    against those they are handed; the pages a window still reaches are
    counted for a family that declares windows alone. A decode step's count
    follows the walk that runs (``LanePool.walks``): the composed walk
    reads every lane to the longest LIVE lane's block, the kernel each live
    lane to its own, and ``attn_pages_kernel`` says how many of the slots read
    the kernel fetched."""
    from petals_tpu.ops import paged_flash_attention as pfa
    from petals_tpu.server.batching import DecodeBatcher
    from petals_tpu.server.task_queue import PriorityTaskQueue

    read, held = {"attn_pages_gathered", "attn_pages_tabled", "attn_pages_kernel"}, {"window_pages_held", "window_pages_in_reach"}
    if path == "kernel":  # a pool the kernel takes, 8 kv heads of 128 at float32, on a backend that says it is a TPU
        monkeypatch.setattr(pfa, "_on_tpu", lambda: True)
        model = make_tiny_falcon(str(tmp_path), n_layers=1, head_dim=128, heads=8, kv_heads=8)
    else:
        model = make_tiny_falcon(str(tmp_path))
    family, cfg = get_block_config(model)
    stacked = jax.tree_util.tree_map(lambda leaf: leaf[None], load_block_params(model, 0, dtype=jnp.float32))
    backend = TransformerBackend(family, cfg, stacked, first_block=0, n_blocks=1, memory_cache=MemoryCache(None),
                                 compute_dtype=jnp.float32, use_flash=False)
    # one slot of the table a block, of two lanes' pages of 16 rows at float32 or of one's (the batcher asks at its start)
    a_page = 16 * backend.num_kv_heads * backend.head_dim * 4
    monkeypatch.setattr(pfa, "WALK_BLOCK_BYTES", 2 * a_page)
    monkeypatch.setattr(pfa, "WALK_KERNEL_BLOCK_BYTES", a_page)
    batcher = DecodeBatcher(backend, backend.memory_cache, PriorityTaskQueue(), n_lanes=2, max_length=64, page_size=16)
    assert read <= set(batcher.stats) and not held & set(batcher.stats) and backend.cache.layer_windows is None and len(backend.runs) == 1
    assert "moe_weight_passes" not in batcher.stats and not held & set(batcher.occupancy_info())
    assert batcher._pool.walks == ((None, 1, 1, False, path),) and batcher.occupancy_info()["decode_walk"] == [path]
    # the composed walk: both lanes to the longest live one's page; the kernel: each live lane to its own
    for positions, walked, own in (([5, 64], 1, 1), ([64, 16], 2, 2), ([47, 0], 3, 4), ([64, 64], 0, 0), ([63, 64], 4, 4), ([63, 17], 4, 6)):
        was = dict(batcher.stats)
        batcher._pool.count_step(batcher.stats, np.asarray(positions, np.int32), batcher._lane_held)  # nothing of it reads the tables (PR 51)
        want = own if path == "kernel" else 2 * walked  # an idle lane is no length
        assert batcher.stats["attn_pages_gathered"] - was["attn_pages_gathered"] == want, positions
        assert batcher.stats["attn_pages_kernel"] - was["attn_pages_kernel"] == (want if path == "kernel" else 0), positions
        assert batcher.stats["attn_pages_tabled"] - was["attn_pages_tabled"] == 2 * 4
    was = dict(batcher.stats)
    batcher._pool.count_step(batcher.stats, np.asarray([64, 3], np.int32), batcher._lane_held, chunk=(0, 16, 20))  # a chunk gathers its lane's whole row
    assert batcher.stats["attn_pages_gathered"] - was["attn_pages_gathered"] == (1 if path == "kernel" else 2 * 1) + 4
    assert batcher.stats["attn_pages_kernel"] - was["attn_pages_kernel"] == (1 if path == "kernel" else 0)  # the chunk's gather is no walk
    assert batcher.stats["attn_pages_tabled"] - was["attn_pages_tabled"] == 2 * 4 + 4
    # under a static window of 128 (pages of 64, blocks of 2 slots, lanes at 300 and 10): the table cut to its reach, or whole
    last = np.asarray([300, 10])
    for cut, want in ((True, 4 + 2), (False, (6 - 2) + 2)):  # the kernel skips the whole blocks before a lane's first position in sight
        assert pfa.pages_walked(((128, 1, 2, cut, "kernel"),), last, 64, 2) == (want, want)
    assert pfa.pages_walked(((128, 1, 2, True, "composed"),), last, 64, 2) == (2 * 4, 0)
    if path == "kernel":
        # the same pool under the RW generation's ALiBi bias: the family says what its blocks hand their attention
        # (ModelFamily.block_attention), the kernel knows no bias, and the counters say so
        model = make_tiny_falcon(str(tmp_path), variant="rw", n_layers=1, head_dim=128, heads=8, kv_heads=8)
        family, cfg = get_block_config(model)
        stacked = jax.tree_util.tree_map(lambda leaf: leaf[None], load_block_params(model, 0, dtype=jnp.float32))
        biased = TransformerBackend(family, cfg, stacked, first_block=0, n_blocks=1, memory_cache=MemoryCache(None),
                                    compute_dtype=jnp.float32, use_flash=False)
        assert biased.cache.pool_row == backend.cache.pool_row and biased.cache.lane_pool(2, 4, 16).walks == ((None, 1, 1, False, "composed"),)
    monkeypatch.undo()
    exaone = whole_backend(make_tiny_exaone_moe(str(tmp_path)))
    batcher = DecodeBatcher(exaone, exaone.memory_cache, PriorityTaskQueue(), n_lanes=2, max_length=64, page_size=16)
    assert read | held | {"moe_dense_tokens", "moe_grouped_tokens", "moe_hit_tokens", "moe_weight_passes"} <= set(batcher.stats)
    assert batcher.occupancy_info()["decode_walk"] == ["composed"] * len(batcher._pool.walks)  # off the chip
    dense_pool = DecodeBatcher(exaone, exaone.memory_cache, PriorityTaskQueue(), n_lanes=2, max_length=64, page_size=None)
    assert not (read | held) & set(dense_pool.stats)  # the counters count pages: the paged pool only


def test_generate_token_identical_over_a_chain_of_two_spans(swarm):
    path, tensors, _, model = swarm
    ids = np.random.RandomState(6).randint(0, 128, (1, 5)).astype(np.int64)
    got = np.asarray(model.generate(ids, max_new_tokens=12))  # past the window's edge
    want = list(ids[0])
    for _ in range(12):
        want.append(int(np.argmax(reference_logits(HF, tensors, want)[-1])))
    np.testing.assert_array_equal(got[0], want)


def test_windowed_decode_gathers_the_pages_in_reach_and_gives_the_whole_table_s_answer():
    """ops/paged_flash_attention.py: under a static window the composed path
    cuts a lane's table row to the slots its rows can reach, for decode rows,
    a prompt chunk and a verify's rows alike, and attends as over the whole
    row (permuted pages, holes, an idle lane at the sentinel)."""
    from petals_tpu.ops.attention import attend_reference
    from petals_tpu.ops.paged_attention import gather_pages
    from petals_tpu.ops.paged_flash_attention import composed_paged_attend, window_pages

    assert window_pages(128, 1, 64, 16) == 3 and window_pages(None, 1, 64, 16) == 16 and window_pages(8, 1, 4, 12) == 3
    assert window_pages(128, 128, 64, 16) == 5 and window_pages(4096, 1, 64, 16) == 16
    rng = np.random.RandomState(0)
    n_lanes, max_pages, ps, hkv, d, window = 3, 12, 4, 2, 16, 8
    tables = rng.permutation(n_lanes * max_pages).astype(np.int32).reshape(n_lanes, max_pages)
    pool_k, pool_v = (jnp.asarray(rng.randn(n_lanes * max_pages, ps, hkv, d), jnp.float32) for _ in range(2))
    for q_len, positions in ((1, [5, 30, max_pages * ps]), (1, [0, 47, 11]), (3, [20, 7, 44])):
        pos = jnp.asarray(positions, jnp.int32)
        live = tables.copy()
        for lane, p in enumerate(positions):
            live[lane, (min(p, max_pages * ps - 1) + q_len - 1) // ps + 1 :] = -1  # unallocated past the lane's last row
        q = jnp.asarray(rng.randn(n_lanes, q_len, 4, d), jnp.float32)
        kw = dict(q_offset=pos, kv_length=pos + q_len, sliding_window=window)
        got = composed_paged_attend(q, pool_k, pool_v, jnp.asarray(live), **kw)
        want = attend_reference(q, gather_pages(pool_k, jnp.asarray(live)), gather_pages(pool_v, jnp.asarray(live)), **kw)
        busy = np.asarray(positions) < max_pages * ps
        np.testing.assert_allclose(np.asarray(got)[busy], np.asarray(want)[busy], atol=1e-6, rtol=0)
    q = jnp.asarray(rng.randn(1, 16, 4, d), jnp.float32)  # a prompt chunk: one lane, a scalar position
    kw = dict(q_offset=jnp.int32(19), kv_length=jnp.int32(35), sliding_window=window)
    got = composed_paged_attend(q, pool_k, pool_v, jnp.asarray(tables[:1]), **kw)
    want = attend_reference(q, gather_pages(pool_k, jnp.asarray(tables[:1])), gather_pages(pool_v, jnp.asarray(tables[:1])), **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6, rtol=0)


# ---------------------------------------------------------------------------------
# what the family refuses, by name
# ---------------------------------------------------------------------------------


@pytest.mark.parametrize("key,value", [("n_group", 2), ("topk_group", 2), ("scoring_func", "softmax"), ("hidden_act", "gelu"),
                                       ("rope_scaling", {"rope_type": "yarn", "factor": 4.0}),
                                       ("rope_parameters", {"rope_theta": 1e6, "rope_type": "yarn", "factor": 4.0})])
def test_what_the_block_does_not_compute_is_refused_at_load(tiny, tmp_path, key, value):
    (tmp_path / "config.json").write_text(json.dumps({**HF, key: value}))
    with pytest.raises(NotImplementedError, match=f"exaone_moe: {'rope_scaling' if key.startswith('rope') else key}"):
        get_block_config(str(tmp_path))


def test_options_the_family_cannot_take_yet_are_refused_by_name(tiny, tmp_path):
    """A tp mesh, a quantized format and a LoRA adapter: the family declares
    no ``tp_pspecs``, ``quantizable_leaves`` or ``lora_targets``, and the
    modules that read them say so with its name."""
    from petals_tpu.parallel.mesh import tp_mesh
    from petals_tpu.utils.convert_block import QuantType, convert_block_params

    path, _ = tiny
    family, cfg = get_block_config(path)
    assert family.tp_pspecs is None and not family.quantizable_leaves and not family.lora_targets
    one_kind = jax.tree_util.tree_map(lambda leaf: leaf[None], load_block_params(path, 1, dtype=jnp.float32))
    make = lambda params, first, n, **kw: TransformerBackend(family, cfg, params, first_block=first, n_blocks=n, memory_cache=MemoryCache(None),
                                                              compute_dtype=jnp.float32, use_flash=False, **kw)
    with pytest.raises(KeyError, match="No TP spec for family 'exaone_moe'"):
        make(one_kind, 1, 1, mesh=tp_mesh(2))
    with pytest.raises(NotImplementedError, match="exaone_moe.*tp mesh"):
        make(whole_backend(path).params, 0, 5, mesh=tp_mesh(2))
    with pytest.raises(ValueError, match="exaone_moe"):
        convert_block_params(dict(load_block_params(path, 1, dtype=jnp.float32)), "exaone_moe", QuantType.NF4)
    from petals_tpu.utils.peft import load_adapter
    from safetensors.numpy import save_file

    (tmp_path / "adapter_config.json").write_text(json.dumps({"r": 2, "lora_alpha": 4, "target_modules": ["q_proj"], "peft_type": "LORA"}))
    save_file({"base_model.model.model.layers.1.self_attn.q_proj.lora_A.weight": np.zeros((2, 64), np.float32),
               "base_model.model.model.layers.1.self_attn.q_proj.lora_B.weight": np.zeros((64, 2), np.float32)},
              str(tmp_path / "adapter_model.safetensors"))
    with pytest.raises(ValueError, match="exaone_moe"):
        load_adapter(str(tmp_path), "exaone_moe", block_range=range(0, 5))


def test_server_side_generation_and_speculative_verify_walk_the_runs_too(tiny):
    """The other two paged programs get the span's runs from the same helper
    (``backend._scan_paged_span``): a server-side generation step fed hidden
    states is the decode step bit for bit, pools and all, and a verify step's
    first row picks the token the decode step's output would."""
    from petals_tpu.client.from_pretrained import load_client_params
    from petals_tpu.ops.sampling import sampling_vectors

    path, _ = tiny
    backend = whole_backend(path)
    cfg, lanes, ps, max_pages = backend.cfg, 3, 4, 6
    client_params = load_client_params(path, dtype=jnp.float32)
    rng = np.random.default_rng(11)
    shape = (5, lanes * max_pages, ps, cfg.num_key_value_heads, cfg.head_dim)
    pools = lambda: tuple(jnp.asarray(rng.standard_normal(shape).astype(np.float32)) for _ in range(2))
    tables = rng.permutation(lanes * max_pages).astype(np.int32).reshape(lanes, max_pages)
    positions = np.array([13, ps * max_pages, 6], np.int32)  # lane 1 idle
    tokens = rng.integers(1, cfg.vocab_size, (lanes, 3)).astype(np.int32)
    hidden = np.asarray(backend.family.client_embed(client_params, jnp.asarray(tokens[:, :1]), cfg), np.float32)
    vecs = sampling_vectors(lanes, cfg.vocab_size)
    k, v = pools()
    copies = [jax.tree_util.tree_map(jnp.copy, (k, v)) for _ in range(2)]
    out, (k_dec, v_dec) = backend.paged_decode_step(hidden, (k, v), positions, tables)
    gen_out, _, (k_gen, v_gen) = backend.paged_gen_decode_step(
        client_params, hidden, tokens[:, 0], np.zeros(lanes, bool), copies[0], positions, tables, sampling_vecs=vecs)
    np.testing.assert_array_equal(np.asarray(gen_out), np.asarray(out))
    np.testing.assert_array_equal(np.asarray(k_gen), np.asarray(k_dec))
    np.testing.assert_array_equal(np.asarray(v_gen), np.asarray(v_dec))
    g_hat, n_emit, (k_spec, _) = backend.paged_spec_verify_step(client_params, tokens, copies[1], positions, tables, sampling_vecs=vecs)
    want = np.argmax(np.asarray(backend.family.client_head(client_params, out, cfg))[:, -1], axis=-1)
    busy = positions < ps * max_pages
    np.testing.assert_array_equal(np.asarray(g_hat)[busy, 0], want[busy])
    assert (np.asarray(n_emit)[busy] >= 1).all() and np.asarray(k_spec).shape == shape
