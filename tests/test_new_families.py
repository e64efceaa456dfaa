"""Qwen2, Mistral and Gemma end-to-end: token-identical greedy generation
through a live swarm (the same acceptance bar as the reference's four
families). These families are BEYOND the reference inventory — llama-style
blocks with the qwen bias convention (q/k/v-only), the mistral all-layer
sliding window, and gemma's (1+w)-folded norms / tanh-GELU / scaled embeds.
"""

import os

import jax.numpy as jnp
import numpy as np
import pytest

from petals_tpu.client.model import AutoDistributedModelForCausalLM
from petals_tpu.models.registry import known_families
from tests.test_full_model import SwarmHarness, _hf_greedy
from tests.utils import (
    make_tiny_bloom,
    make_tiny_deepseek_v3,
    make_tiny_falcon,
    make_tiny_gemma,
    make_tiny_gemma2,
    make_tiny_jamba,
    make_tiny_longcat_flash,
    make_tiny_llama,
    make_tiny_mistral,
    make_tiny_mixtral,
    make_tiny_exaone_moe,
    make_tiny_keye_vl2,
    make_tiny_olmo_hybrid,
    make_tiny_olmoe,
    make_tiny_phi3,
    make_tiny_qwen2,
    make_tiny_qwen3_next,
    make_tiny_smallthinker,
    make_tiny_xing4_0,
)


# One tiny checkpoint per registered family. A family registered without a
# maker here fails every contract test below by name, not silently.
MAKERS = {
    "llama": make_tiny_llama, "bloom": make_tiny_bloom, "falcon": make_tiny_falcon,
    "mixtral": make_tiny_mixtral, "olmoe": make_tiny_olmoe, "qwen2": make_tiny_qwen2,
    "mistral": make_tiny_mistral, "gemma": make_tiny_gemma, "phi3": make_tiny_phi3,
    "gemma2": make_tiny_gemma2, "exaone_moe": make_tiny_exaone_moe, "olmo_hybrid": make_tiny_olmo_hybrid,
    "KeyeVL2": make_tiny_keye_vl2, "deepseek_v3": make_tiny_deepseek_v3, "qwen3_next": make_tiny_qwen3_next,
    "jamba": make_tiny_jamba, "longcat_flash": make_tiny_longcat_flash, "xing4_0": make_tiny_xing4_0,
    "smallthinker": make_tiny_smallthinker,
}
LLAMA_ALIASES = ("mistral", "qwen2", "phi3", "gemma")  # dataclasses.replace over llama


@pytest.fixture(scope="module")
def family_block(tmp_path_factory):
    """name -> (checkpoint path, family, cfg, block 0's params), built once."""
    from petals_tpu.server.from_pretrained import get_block_config, load_block_params

    built = {}

    def get(name):
        if name not in built:
            path = MAKERS[name](str(tmp_path_factory.mktemp(name)))
            family, cfg = get_block_config(path)
            assert family.name == name
            params = load_block_params(path, 0, dtype=jnp.float32, family=family, cfg=cfg)
            built[name] = (path, family, cfg, params)
        return built[name]

    return get


@pytest.mark.parametrize("name", known_families())
def test_quantization_applies_to_every_family(family_block, name):
    """What a family declares on its ModelFamily is what gets quantized: the
    declared leaves exist in its block, fuse-group members are among them, and
    convert_block_params quantizes exactly those (fused where declared).
    Families built over the llama block inherit its declaration (a silent
    dense fallback here once shipped as a no-op --quant_type)."""
    from petals_tpu.ops.quant import QuantizedLinear
    from petals_tpu.utils.convert_block import convert_block_params

    _, family, cfg, params = family_block(name)
    declared = family.quantizable_leaves
    if (family.block_kind is not None or family.block_index is not None or family.block_latent is not None) and not declared:
        # a family whose blocks are not all alike, or whose pages carry an index row or a latent row, may declare none yet:
        # refused by name, never a dense no-op
        with pytest.raises(ValueError, match=name):
            convert_block_params(dict(params), name, "nf4", fuse=True)
        return
    assert declared and declared <= set(params), (declared, sorted(params))
    assert declared <= set(family.block_param_shapes(cfg))
    fused_away = set()
    for fused_w, parts, _, _ in family.fuse_groups:
        assert set(parts) <= declared, (fused_w, parts)
        fused_away |= set(parts)
    kind = "int8" if name == "mixtral" else "nf4"  # tiny mixtral's experts are 96 wide: no 64-row blocks
    q = convert_block_params(dict(params), name, kind, fuse=True)
    quantized = {k for k, v in q.items() if isinstance(v, QuantizedLinear)}
    assert quantized == (declared - fused_away) | {g[0] for g in family.fuse_groups}
    if name in LLAMA_ALIASES:
        assert {"wqkv", "wgu", "wo", "wd"} <= quantized, quantized


@pytest.mark.parametrize("name", known_families())
def test_tp_specs_cover_the_block_or_are_refused(family_block, name):
    """A family either declares TP specs for exactly the leaves its checkpoint
    yields, and its stacked block shards over a tp mesh, or is refused by
    name. The llama aliases get llama's specs (they were a KeyError)."""
    from jax.sharding import PartitionSpec as P

    from petals_tpu.parallel import make_mesh
    from petals_tpu.parallel.tp import COL, shard_span_params, span_param_pspecs

    _, family, cfg, params = family_block(name)
    if family.tp_pspecs is None:
        with pytest.raises(KeyError, match=f"{name}.*tp_pspecs"):
            span_param_pspecs(name, cfg)
        return
    specs = span_param_pspecs(name, cfg)
    assert set(specs) == set(params)
    if name in LLAMA_ALIASES:
        assert specs == span_param_pspecs("llama", cfg)
    stacked = {k: jnp.asarray(v)[None] for k, v in params.items()}
    sharded = shard_span_params(stacked, make_mesh((2,), ("tp",)), name, cfg)
    for leaf, spec in specs.items():
        assert isinstance(spec, P) and len(spec) <= stacked[leaf].ndim, (leaf, spec)
        expect = list(stacked[leaf].shape)
        for dim, axis in enumerate(spec):
            if axis == COL:
                expect[dim] //= 2
        assert sharded[leaf].addressable_shards[0].data.shape == tuple(expect), leaf
    assert any(COL in spec for spec in specs.values())


@pytest.mark.parametrize("name", known_families())
def test_lora_targets_name_existing_leaves(family_block, name):
    """Every projection a family maps to a leaf maps to one its block has; the
    llama aliases load the tiny llama-style adapter to llama's leaves (they
    loaded nothing, silently); an adapter that matches nothing in the
    family's map is refused, not served as the base model."""
    from tests.test_peft import make_fake_peft_adapter

    from petals_tpu.utils.peft import load_adapter

    path, family, cfg, params = family_block(name)
    targets = {leaf for leaf in family.lora_targets.values() if leaf is not None}
    assert targets <= set(params), (targets, sorted(params))
    if name in LLAMA_ALIASES:
        assert family.lora_targets == family_block("llama")[1].lora_targets
    # the adapter of tests/test_peft.py wraps q_proj and down_proj of every layer
    expected = {family.lora_targets.get(proj) for proj in ("q_proj", "down_proj")} - {None}
    if name in LLAMA_ALIASES:
        assert expected == {"wq", "wd"}
    shapes_from = path if expected else family_block("llama")[0]  # bloom's config has no intermediate_size
    adapter_path = make_fake_peft_adapter(os.path.dirname(path), shapes_from)
    if expected:
        adapter = load_adapter(adapter_path, name, block_range=range(cfg.num_hidden_layers))
        assert set(adapter.per_block[0]) == expected
    else:
        with pytest.raises(ValueError, match=f"no LoRA tensor.*{name}"):
            load_adapter(adapter_path, name, block_range=range(2))


@pytest.mark.parametrize("name", known_families())
def test_buffers_and_frames_are_sized_by_the_family_s_stream_width(family_block, name):
    """What crosses the wire between two blocks is as wide as the family says
    (``ModelFamily.block_stream``; ``cfg.hidden_size`` where it says nothing,
    every family's but ``xing4_0``, whose stream of four rows is 256 wide at
    the toy's 64), and the backend, the batcher and the handler size by that
    one width: the lanes' packed rows, the pool's reused buffer, the frame
    check, which names the width it wants. A frame as wide as the model is
    refused where the stream is wider."""
    import asyncio
    import types

    import jax

    from petals_tpu.server.backend import TransformerBackend
    from petals_tpu.server.batching import DecodeBatcher
    from petals_tpu.server.handler import TransformerHandler
    from petals_tpu.server.memory_cache import MemoryCache
    from petals_tpu.server.task_queue import PriorityTaskQueue

    _, family, cfg, params = family_block(name)
    width, mixes = family.stream_for(cfg)
    assert (width, mixes) == ((4 * cfg.hidden_size, 2) if name == "xing4_0" else (cfg.hidden_size, 0))
    stacked = jax.tree_util.tree_map(lambda leaf: jnp.asarray(leaf)[None], params)
    backend = TransformerBackend(family, cfg, stacked, first_block=0, n_blocks=1, memory_cache=MemoryCache(None),
                                 compute_dtype=jnp.float32, use_flash=False)
    assert backend.hidden_size == width and backend.stream_mixes == mixes
    assert backend.pack_lanes(np.zeros((3, 1, width), np.float32), np.arange(3)).shape == (3, width + 1)
    handler = types.SimpleNamespace(backend=backend)
    TransformerHandler._validate_step_tensors(handler, np.zeros((1, 5, width), np.float32), None, None, 1, 1)
    refused = [width + 8] + ([cfg.hidden_size] if width != cfg.hidden_size else [])
    for wrong in refused:
        with pytest.raises(ValueError, match=f"hidden={width}\\], got \\(1, 5, {wrong}\\)"):
            TransformerHandler._validate_step_tensors(handler, np.zeros((1, 5, wrong), np.float32), None, None, 1, 1)
    batcher = DecodeBatcher(backend, backend.memory_cache, PriorityTaskQueue(), n_lanes=2, max_length=32, page_size=8)
    assert ("hc_rows" in batcher.stats) == bool(mixes) and batcher.stats["stream_bytes_in"] == 0

    async def opened():
        await batcher.ensure_open()
        try:
            return batcher._lanes_in.shape
        finally:
            await batcher.close()

    assert asyncio.run(opened()) == (2, 2, width + 1)  # two buffers, filled in turn (a launch beside a step in flight)


@pytest.mark.parametrize("maker", [make_tiny_qwen2, make_tiny_mistral])
def test_llama_alias_serves_over_tp_mesh_with_adapter(tmp_path, maker):
    """What the declarations buy a user: a server of a family built over
    llama shards over a tp mesh (it died on a KeyError) and applies a LoRA
    adapter (it served the base model without a word). qwen2 brings the
    q/k/v-only biases, mistral the sliding window."""
    from tests.test_peft import _hf_with_lora, make_fake_peft_adapter

    path = maker(str(tmp_path))
    adapter = make_fake_peft_adapter(str(tmp_path), path)
    harness = SwarmHarness(
        path, [dict(first_block=0, num_blocks=4, num_tp_devices=2, adapters=[adapter])]
    ).start()
    try:
        ids = np.random.RandomState(0).randint(0, 100, (1, 5)).astype(np.int64)
        model = AutoDistributedModelForCausalLM.from_pretrained(
            path, initial_peers=harness.initial_peers
        )
        try:
            np.testing.assert_array_equal(
                model.generate(ids, max_new_tokens=4), _hf_greedy(path, ids, 4)
            )
        finally:
            model.close()
        model = AutoDistributedModelForCausalLM.from_pretrained(
            path, initial_peers=harness.initial_peers, active_adapter=os.path.basename(adapter)
        )
        try:
            np.testing.assert_allclose(
                np.asarray(model.forward(ids)), _hf_with_lora(path, adapter, ids), atol=2e-3
            )
        finally:
            model.close()
    finally:
        harness.stop()


def test_quantization_refuses_unknown_architecture():
    from petals_tpu.utils.convert_block import convert_block_params

    with pytest.raises(ValueError, match="no quantizable"):
        convert_block_params({"w_mystery": jnp.ones((8, 8))}, "not-a-family", "nf4")


@pytest.fixture(scope="module", params=["qwen2", "mistral", "gemma", "phi3"])
def family_swarm(request, tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("models"))
    if request.param == "qwen2":
        path = make_tiny_qwen2(tmp)
    elif request.param == "gemma":
        path = make_tiny_gemma(tmp)
    elif request.param == "phi3":
        path = make_tiny_phi3(tmp)
    else:
        # window=6: generation must cross the sliding-window edge mid-stream
        path = make_tiny_mistral(tmp, window=6)
    harness = SwarmHarness(
        path, [dict(first_block=0, num_blocks=2), dict(first_block=2, num_blocks=2)]
    ).start()
    yield request.param, path, harness
    harness.stop()


def test_generate_token_identical(family_swarm):
    name, path, harness = family_swarm
    model = AutoDistributedModelForCausalLM.from_pretrained(
        path, initial_peers=harness.initial_peers
    )
    try:
        rng = np.random.RandomState(0)
        input_ids = rng.randint(0, 100, (1, 6)).astype(np.int64)
        expected = _hf_greedy(path, input_ids, 8)  # 6+8 = 14 tokens > window 6
        out = model.generate(input_ids, max_new_tokens=8)
        np.testing.assert_array_equal(out, expected, err_msg=f"{name} diverged from HF")
    finally:
        model.close()


def test_session_reuse_and_failover_ready(family_swarm):
    """Multi-call chat sessions (token-skip resume) work for the new families."""
    name, path, harness = family_swarm
    model = AutoDistributedModelForCausalLM.from_pretrained(
        path, initial_peers=harness.initial_peers
    )
    try:
        rng = np.random.RandomState(1)
        input_ids = rng.randint(0, 100, (1, 5)).astype(np.int64)
        expected = _hf_greedy(path, input_ids, 6)
        with model.remote.inference_session(max_length=24, batch_size=1) as session:
            first = model.generate(input_ids, max_new_tokens=3, session=session)
            final = model.generate(first, max_new_tokens=3, session=session)
        np.testing.assert_array_equal(final, expected, err_msg=f"{name} session diverged")
    finally:
        model.close()


def test_gemma_norm_fold_survives_bf16_loading(tmp_path):
    """Gemma's (1+w) norm fold is exact only in float32: the cast_exempt
    plumbing must keep the folded norms f32 when everything else loads bf16
    (rms_norm upcasts anyway, so serving numerics see the exact fold)."""
    from petals_tpu.client.from_pretrained import load_client_params
    from petals_tpu.server.from_pretrained import load_block_params

    path = make_tiny_gemma(str(tmp_path))
    params = load_block_params(path, 0, dtype=jnp.bfloat16)
    assert params["ln1"].dtype == jnp.float32 and params["ln2"].dtype == jnp.float32
    assert params["wq"].dtype == jnp.bfloat16
    client = load_client_params(path, dtype=jnp.bfloat16)
    assert client["norm"].dtype == jnp.float32
    assert client["embed"].dtype == jnp.bfloat16


def test_phi3_longrope_boundary_crossing(tmp_path):
    """Cached decode that CROSSES the pretrained window (original 64) must
    match HF on both sides of the switch: HF re-selects the long extension
    factors per forward from the runtime length, and the traced jnp.where in
    ops/rotary._longrope_inv_freq must agree step by step (cached K rows
    keep their short-factor rotation on both sides — the HF cache quirk this
    mirrors). Block-level and deterministic: the e2e greedy variant of this
    test tripped near-tie argmax cascades (1.4e-3 logit margins vs the bf16
    serving noise), which tests the tiny random model, not the rope."""
    import jax.numpy as jnp
    import torch
    from transformers import DynamicCache, Phi3ForCausalLM

    from petals_tpu.models.registry import get_family
    from petals_tpu.server.from_pretrained import load_block_params

    path = make_tiny_phi3(str(tmp_path))
    model = Phi3ForCausalLM.from_pretrained(path).eval()
    layer = model.model.layers[0]
    rot = model.model.rotary_emb
    fam = get_family("phi3")
    cfg = fam.config_from_hf(model.config)
    params = load_block_params(path, 0, dtype=jnp.float32)

    rng = np.random.RandomState(0)
    prefill = rng.randn(1, 62, 64).astype(np.float32) * 0.3
    steps = [rng.randn(1, 1, 64).astype(np.float32) * 0.3 for _ in range(4)]

    cache = DynamicCache()
    with torch.no_grad():
        cos, sin = rot(torch.tensor(prefill), torch.arange(62)[None])
        layer(torch.tensor(prefill), position_embeddings=(cos, sin),
              attention_mask=None, past_key_value=cache,
              cache_position=torch.arange(62))
    hf_outs = []
    for i, s in enumerate(steps):
        p = 62 + i
        with torch.no_grad():
            cos, sin = rot(torch.tensor(s), torch.tensor([[p]]))
            o = layer(torch.tensor(s), position_embeddings=(cos, sin),
                      attention_mask=None, past_key_value=cache,
                      cache_position=torch.tensor([p]))
        hf_outs.append((o[0] if isinstance(o, tuple) else o).numpy())

    kd = jnp.zeros((1, 128, cfg.num_key_value_heads, cfg.head_dim), jnp.float32)
    kv = (kd, kd)
    _, kv = fam.block_apply(params, jnp.asarray(prefill), kv, 0, cfg)
    for i, s in enumerate(steps):
        p = 62 + i  # seq 63..66 straddles the original_max=64 switch
        o, kv = fam.block_apply(params, jnp.asarray(s), kv, p, cfg)
        np.testing.assert_allclose(
            np.asarray(o), hf_outs[i], atol=1e-5,
            err_msg=f"phi3 longrope diverged at position {p} (seq {p + 1})",
        )


def test_longrope_per_row_and_padding_selection():
    """The short/long switch is per ROW and counts only REAL tokens: one
    deep lane (or the idle-lane sentinel at max_length) must not flip a
    shallow lane's factors, and a padded bucket tail must not trip the
    switch (n_valid overrides the padded maximum)."""
    import jax.numpy as jnp

    from petals_tpu.ops.rotary import rotary_tables

    scaling = {
        "rope_type": "longrope",
        "short_factor": tuple(1.0 for _ in range(4)),
        "long_factor": tuple(4.0 for _ in range(4)),
        "original_max_position_embeddings": 64,
        "factor": 4.0,
    }

    def tables(positions, n_valid=None):
        return rotary_tables(
            jnp.asarray(positions, jnp.int32), 8, rope_scaling=dict(scaling),
            n_valid=n_valid,
        )

    # batched decode: lane 0 shallow (pos 5), lane 1 deep (pos 100)
    cos, _ = tables([[5], [100]])
    cos_short, _ = tables([[5]])
    cos_long, _ = tables([[100]])
    np.testing.assert_allclose(np.asarray(cos[0]), np.asarray(cos_short[0]), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(cos[1]), np.asarray(cos_long[0]), rtol=1e-6)
    # the factors actually differ between the regimes (the test has teeth)
    assert np.abs(np.asarray(cos_short[0]) - np.asarray(cos_long[0])).max() > 1e-3

    # padded prefill chunk: 8 real tokens from position 58 (real end 66 > 64
    # -> long), padded to 16 rows whose tail reaches position 73
    padded = [list(range(58, 74))]
    cos_pad, _ = tables(padded, n_valid=8)
    cos_ref, _ = tables([[100] + list(range(59, 74))])  # all-long reference angles
    # row 0 must use LONG factors (real end 66 > 64): compare against the
    # unambiguous long-regime table at the same position
    cos_long58, _ = tables([[58, 59]])  # max+1=60 <= 64 -> short; differs
    assert np.abs(np.asarray(cos_pad[0, 0]) - np.asarray(cos_long58[0, 0])).max() > 1e-3
    # and with n_valid pushing the real end INSIDE the window, short applies
    cos_short_nv, _ = tables(padded, n_valid=2)  # real end 60 <= 64
    np.testing.assert_allclose(
        np.asarray(cos_short_nv[0, 0]), np.asarray(cos_long58[0, 0]), rtol=1e-6
    )


def test_gemma2_block_exact_and_e2e(tmp_path):
    """Gemma-2 (9th family, own block architecture): per-layer alternating
    sliding/full attention, attention-logit soft-capping, four folded
    post-norms, query_pre_attn_scalar scaling, final-logit soft-capping.
    Full-pipeline cached decode (embed -> 4 blocks -> norm+head) must match
    HF logits step by step past the window edge — driving the MODEL, not
    naked layers, because HF implements the sliding window in the
    model-level mask preparation — and swarm generation must be
    token-identical."""
    import jax.numpy as jnp
    import torch
    from transformers import Gemma2ForCausalLM

    from petals_tpu.models.registry import get_family
    from petals_tpu.client.from_pretrained import load_client_params
    from petals_tpu.server.from_pretrained import load_block_params
    from tests.utils import make_tiny_gemma2

    path = make_tiny_gemma2(str(tmp_path))
    model = Gemma2ForCausalLM.from_pretrained(path, attn_implementation="eager").eval()
    fam = get_family("gemma2")
    cfg = fam.config_from_hf(model.config)
    assert cfg.layer_types[0] == "sliding_attention"
    assert cfg.layer_types[1] == "full_attention"

    rng = np.random.RandomState(0)
    ids = rng.randint(0, 100, (1, 15)).astype(np.int64)  # 12 prefill + 3 steps

    with torch.no_grad():
        hf_logits = model(torch.from_numpy(ids)).logits.numpy()

    blocks = [load_block_params(path, i, dtype=jnp.float32) for i in range(4)]
    assert int(blocks[0]["attn_window"]) == 6 and int(blocks[1]["attn_window"]) == 0
    client = load_client_params(path, dtype=jnp.float32, family=fam, cfg=cfg)

    def ours_logits(token_ids, kvs, position):
        h = fam.client_embed(client, jnp.asarray(token_ids), cfg)
        new_kvs = []
        for p, kv in zip(blocks, kvs):
            h, kv = fam.block_apply(p, h, kv, position, cfg)
            new_kvs.append(kv)
        return np.asarray(fam.client_head(client, h, cfg)), new_kvs

    kd = jnp.zeros((1, 32, cfg.num_key_value_heads, cfg.head_dim), jnp.float32)
    kvs = [(kd, kd)] * 4
    out, kvs = ours_logits(ids[:, :12], kvs, 0)  # prefill crosses window 6
    np.testing.assert_allclose(out, hf_logits[:, :12], atol=2e-4, rtol=0,
                               err_msg="gemma2 prefill logits diverged")
    for i in range(3):  # cached decode on both layer types
        out, kvs = ours_logits(ids[:, 12 + i : 13 + i], kvs, 12 + i)
        np.testing.assert_allclose(
            out[:, 0], hf_logits[:, 12 + i], atol=2e-4, rtol=0,
            err_msg=f"gemma2 decode logits diverged at position {12 + i}",
        )

    # e2e: greedy through a live swarm, token-identical (crosses window 6)
    harness = SwarmHarness(
        path, [dict(first_block=0, num_blocks=2), dict(first_block=2, num_blocks=2)]
    ).start()
    try:
        client_model = AutoDistributedModelForCausalLM.from_pretrained(
            path, initial_peers=harness.initial_peers
        )
        try:
            input_ids = rng.randint(0, 100, (1, 5)).astype(np.int64)
            # expected from the EAGER model: the default (sdpa) attention
            # silently drops attn_logit_softcapping, so _hf_greedy would
            # validate against softcap-free math
            with torch.no_grad():
                expected = model.generate(
                    torch.from_numpy(input_ids), max_new_tokens=8, do_sample=False
                ).numpy()
            out = client_model.generate(input_ids, max_new_tokens=8)
            np.testing.assert_array_equal(out, expected, err_msg="gemma2 e2e diverged")
        finally:
            client_model.close()
    finally:
        harness.stop()
