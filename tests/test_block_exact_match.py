"""Block-level exactness vs the HF torch reference (port of reference
tests/test_block_exact_match.py:78-108 — forward and incremental inference
must match a local HF model within tight tolerances)."""

import jax.numpy as jnp
import numpy as np
import pytest
import torch

from petals_tpu.server.from_pretrained import get_block_config, load_block_params
from tests.utils import make_tiny_bloom, make_tiny_llama, make_tiny_mistral, make_tiny_qwen2

ATOL_FORWARD = 1e-4
ATOL_INFERENCE = 1e-4


@pytest.fixture(scope="module")
def tiny_llama(tmp_path_factory):
    return make_tiny_llama(str(tmp_path_factory.mktemp("models")))


@pytest.fixture(scope="module")
def tiny_bloom(tmp_path_factory):
    return make_tiny_bloom(str(tmp_path_factory.mktemp("models")))


@pytest.fixture(scope="module")
def tiny_llama_biased(tmp_path_factory):
    return make_tiny_llama(str(tmp_path_factory.mktemp("models")), n_layers=2, biased=True)


@pytest.fixture(scope="module")
def tiny_qwen2(tmp_path_factory):
    return make_tiny_qwen2(str(tmp_path_factory.mktemp("models")), n_layers=2)


@pytest.fixture(scope="module")
def tiny_mistral(tmp_path_factory):
    # window=6 < the 16-token test sequence, so the window edge is exercised
    return make_tiny_mistral(str(tmp_path_factory.mktemp("models")), n_layers=2, window=6)


def _hf_hidden_states(model_path, input_ids):
    """Run the HF model, returning the hidden states entering/leaving each block.

    Uses forward hooks on the decoder layers: HF's ``output_hidden_states``
    applies the final norm to the last entry, which would poison the last-block
    comparison."""
    from transformers import AutoModelForCausalLM

    model = AutoModelForCausalLM.from_pretrained(model_path, dtype=torch.float32).eval()
    decoder = model.model if hasattr(model, "model") else model.transformer
    layers = decoder.layers if hasattr(decoder, "layers") else decoder.h
    captured = []

    def hook(_module, _inputs, output):
        captured.append((output[0] if isinstance(output, tuple) else output).detach().numpy())

    handles = [layer.register_forward_hook(hook) for layer in layers]
    try:
        with torch.no_grad():
            out = model(input_ids, output_hidden_states=True)
    finally:
        for h in handles:
            h.remove()
    embeddings = out.hidden_states[0].numpy()
    return [embeddings] + captured


@pytest.mark.parametrize(
    "model_fixture",
    ["tiny_llama", "tiny_bloom", "tiny_llama_biased", "tiny_qwen2", "tiny_mistral"],
)
def test_block_forward_exact_match(model_fixture, request):
    model_path = request.getfixturevalue(model_fixture)
    family, cfg = get_block_config(model_path)

    torch.manual_seed(42)
    input_ids = torch.randint(0, 100, (2, 16))
    hiddens = _hf_hidden_states(model_path, input_ids)

    for block_index in range(cfg.num_hidden_layers):
        params = load_block_params(model_path, block_index, dtype=jnp.float32)
        ours, _ = family.block_apply(
            params, jnp.asarray(hiddens[block_index]), None, 0, cfg
        )
        np.testing.assert_allclose(
            np.asarray(ours),
            hiddens[block_index + 1],
            atol=ATOL_FORWARD,
            rtol=0,
            err_msg=f"{model_fixture} block {block_index} diverged from HF",
        )


@pytest.mark.parametrize(
    "model_fixture", ["tiny_llama", "tiny_bloom", "tiny_qwen2", "tiny_mistral"]
)
def test_block_inference_with_cache_matches_forward(model_fixture, request):
    """Chunked prefill + token-by-token decode through the KV cache must equal
    one full forward (reference test_block_exact_match.py inference path)."""
    model_path = request.getfixturevalue(model_fixture)
    family, cfg = get_block_config(model_path)
    params = load_block_params(model_path, 0, dtype=jnp.float32)

    rng = np.random.RandomState(0)
    batch, total = 2, 12
    hidden = jnp.asarray(rng.randn(batch, total, cfg.hidden_size), jnp.float32)

    full, _ = family.block_apply(params, hidden, None, 0, cfg)

    max_len = 16
    hkv = getattr(cfg, "num_key_value_heads", cfg.num_attention_heads)
    kv = (
        jnp.zeros((batch, max_len, hkv, cfg.head_dim), jnp.float32),
        jnp.zeros((batch, max_len, hkv, cfg.head_dim), jnp.float32),
    )

    outputs = []
    position = 0
    for chunk in (hidden[:, :5], hidden[:, 5:6], hidden[:, 6:7], hidden[:, 7:]):
        out, kv = family.block_apply(params, chunk, kv, position, cfg)
        outputs.append(np.asarray(out))
        position += chunk.shape[1]

    stitched = np.concatenate(outputs, axis=1)
    np.testing.assert_allclose(stitched, np.asarray(full), atol=ATOL_INFERENCE, rtol=0)


def test_block_loader_rejects_missing_block(tiny_llama):
    with pytest.raises(KeyError):
        load_block_params(tiny_llama, 99)


def test_bf16_load(tiny_llama):
    params = load_block_params(tiny_llama, 0, dtype=jnp.bfloat16)
    assert params["wq"].dtype == jnp.bfloat16


def test_moe_sparse_dispatch_matches_dense():
    """The prefill-time sparse (ragged_dot) MoE dispatch must equal the dense
    all-experts path: same HF-exact routing, no dropped tokens, only summation
    order differs (round-3 sparse dispatch, reference has dense-only MoE)."""
    import jax
    import jax.numpy as jnp

    from petals_tpu.models.moe import moe_apply as shared_moe_apply

    def moe_apply(params, x, *, sparse):
        return shared_moe_apply(params, x, top_k=2, renormalize=True, dispatch="grouped" if sparse else "dense")

    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 5)
    params = {
        "gate": jax.random.normal(ks[0], (64, 8), jnp.float32) * 0.2,
        "w1": jax.random.normal(ks[1], (8, 64, 128), jnp.float32) * 0.05,
        "w2": jax.random.normal(ks[2], (8, 128, 64), jnp.float32) * 0.05,
        "w3": jax.random.normal(ks[3], (8, 64, 128), jnp.float32) * 0.05,
    }
    x = jax.random.normal(ks[4], (2, 16, 64), jnp.float32) * 0.3
    dense = np.asarray(moe_apply(params, x, sparse=False))
    sparse = np.asarray(moe_apply(params, x, sparse=True))
    np.testing.assert_allclose(sparse, dense, atol=1e-5, rtol=1e-5)

    # degenerate routing (all tokens pick the same experts): group sizes are
    # maximally skewed, ragged groups of size 0 must be fine
    params_skew = dict(params)
    skew = np.zeros((64, 8), np.float32)
    skew[:, 3] = 5.0
    skew[:, 6] = 4.0
    params_skew["gate"] = jnp.asarray(skew)
    dense = np.asarray(moe_apply(params_skew, x, sparse=False))
    sparse = np.asarray(moe_apply(params_skew, x, sparse=True))
    np.testing.assert_allclose(sparse, dense, atol=1e-5, rtol=1e-5)


# head geometry of the three families the benchmark's cells run, at a tiny width:
# (hidden, query heads, kv heads, head_dim)
HEAD_GEOMETRIES = {"falcon": (64, 8, 2, 8), "mixtral": (64, 4, 2, 16), "olmoe": (32, 4, 4, 8)}


@pytest.mark.parametrize("family", sorted(HEAD_GEOMETRIES))
def test_project_heads_is_mm_bit_for_bit(family):
    """``project_heads`` pins the product's layout and nothing else: the same
    bytes as ``mm`` into the head split, for a dense weight and through a
    ``LoraLinear``, and the same gradients (the backward path runs
    ``block_apply`` too)."""
    import functools

    import jax

    from petals_tpu.models.common import mm, project_heads
    from petals_tpu.utils.peft import LoraLinear

    hidden, hq, hkv, d = HEAD_GEOMETRIES[family]
    keys = jax.random.split(jax.random.PRNGKey(27), 4)
    x = jax.random.normal(keys[0], (2, 5, hidden), jnp.float32).astype(jnp.bfloat16)
    for heads, key in ((hq, keys[1]), (hkv, keys[2])):
        dense = (0.1 * jax.random.normal(key, (hidden, heads * d), jnp.float32)).astype(jnp.bfloat16)
        ka, kb = jax.random.split(keys[3])
        lora = LoraLinear(
            dense,
            jax.random.normal(ka, (hidden, 4), jnp.float32).astype(jnp.bfloat16),
            jax.random.normal(kb, (4, heads * d), jnp.float32).astype(jnp.bfloat16),
            0.5,
        )
        for w in (dense, lora):

            def loss(project, x, w):
                out = project(x, w).reshape(2, 5, heads, d).astype(jnp.float32)
                return jnp.sum(out * out), out

            def run(project):
                grad = jax.value_and_grad(functools.partial(loss, project), (0, 1), has_aux=True)
                (_, out), grads = jax.jit(grad)(x, w)
                return [out, *jax.tree_util.tree_leaves(grads)]

            for got, want in zip(run(project_heads), run(mm), strict=True):
                np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
