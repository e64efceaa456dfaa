"""Quantization tests: INT8/NF4/INT4 formats, Pallas dequant-matmul vs XLA
reference, quantized block error bounds, quantized server e2e
(the TPU-native replacement for bitsandbytes — SURVEY.md §2.3)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from petals_tpu.ops.quant import (
    NF4_BLOCK,
    dequantize,
    nf4_matmul_pallas,
    packed4_matmul_pallas,
    quant_matmul,
    quantize_int4,
    quantize_int8,
    quantize_nf4,
    quantize_nf4a,
    quantized_bytes,
)
from petals_tpu.utils.convert_block import QuantType, convert_block_params


def test_int8_roundtrip_error():
    rng = np.random.RandomState(0)
    w = rng.randn(128, 256).astype(np.float32)
    q = quantize_int8(w)
    # rows pad to the Pallas k-tile (zero rows are exact for int8); the
    # logical size is recorded and dequantize slices back to it
    assert q.data.dtype == jnp.int8 and q.data.shape[0] >= 128 and q.in_features == 128
    deq = np.asarray(dequantize(q, jnp.float32))
    assert deq.shape == (128, 256)
    # symmetric per-channel int8: error bounded by scale/2 per channel
    scale = np.abs(w).max(axis=0) / 127
    assert (np.abs(deq - w) <= scale[None, :] * 0.5 + 1e-6).all()


def test_nf4_roundtrip_error():
    rng = np.random.RandomState(1)
    w = (rng.randn(256, 128) * 0.05).astype(np.float32)
    q = quantize_nf4(w)
    assert q.data.dtype == jnp.uint8
    stored = q.data.shape[0] * 2  # input axis padded to the Pallas k-tile
    assert stored >= 256 and q.data.shape[1] == 128
    assert q.scales.shape == (stored // NF4_BLOCK, 128)
    deq = np.asarray(dequantize(q, jnp.float32))
    # blockwise absmax: worst-case error is half the largest codebook gap * absmax
    blocks = w.reshape(-1, NF4_BLOCK, 128)
    absmax = np.abs(blocks).max(axis=1)
    max_gap = 0.18  # largest NF4 inter-code distance
    bound = np.repeat(absmax, NF4_BLOCK, axis=0) * max_gap
    assert (np.abs(deq - w) <= bound + 1e-6).all()
    # genuine 4.25-bit format over the STORED (k-tile padded) size; padding
    # overhead only matters for toy matrices like this one
    assert q.nbytes <= quantized_bytes(stored * 128, "nf4") + 1024


def test_int4_roundtrip_error():
    rng = np.random.RandomState(7)
    w = (rng.randn(256, 128) * 0.05).astype(np.float32)
    q = quantize_int4(w)
    assert q.kind == "int4" and q.data.dtype == jnp.uint8
    deq = np.asarray(dequantize(q, jnp.float32))
    # affine levels: error bounded by scale/2 = absmax/14 per block (+ the
    # bf16 rounding of the stored scale)
    blocks = w.reshape(-1, NF4_BLOCK, 128)
    absmax = np.abs(blocks).max(axis=1)
    bound = np.repeat(absmax, NF4_BLOCK, axis=0) / 14 + np.abs(w) * 2**-7 + 1e-6
    assert (np.abs(deq - w) <= bound).all()
    stored = q.data.shape[0] * 2
    assert q.nbytes <= quantized_bytes(stored * 128, "int4") + 1024


@pytest.mark.parametrize("quantizer", [quantize_nf4, quantize_nf4a, quantize_int4])
def test_packed4_pallas_matches_xla(quantizer):
    rng = np.random.RandomState(2)
    w = (rng.randn(512, 256) * 0.05).astype(np.float32)
    x = rng.randn(16, 512).astype(np.float32)
    q = quantizer(w)
    expected = x @ np.asarray(dequantize(q, jnp.float32))
    got = np.asarray(packed4_matmul_pallas(jnp.asarray(x), q))
    np.testing.assert_allclose(got, expected, atol=2e-2, rtol=1e-2)


@pytest.mark.parametrize("quantizer", [quantize_nf4, quantize_nf4a, quantize_int4])
@pytest.mark.parametrize("m", [1, 40])  # decode (M<=32) and prefill kernels
def test_packed4_pallas_stacked_matches_xla(quantizer, m):
    from petals_tpu.ops.quant import StackedQuantLinear, packed4_matmul_pallas_stacked

    rng = np.random.RandomState(3)
    x = rng.randn(m, 512).astype(np.float32)
    qs = [quantizer((rng.randn(512, 256) * 0.05).astype(np.float32)) for _ in range(3)]
    data = jnp.stack([q.data for q in qs])
    scales = jnp.stack([q.scales for q in qs])
    for idx in (0, 2):
        sq = StackedQuantLinear(qs[0].kind, data, scales, jnp.int32(idx), 512, 256)
        expected = x @ np.asarray(dequantize(qs[idx], jnp.float32))
        got = np.asarray(packed4_matmul_pallas_stacked(jnp.asarray(x), sq))
        np.testing.assert_allclose(got, expected, atol=2e-2, rtol=1e-2)


@pytest.mark.parametrize("m", [1, 40])
def test_int8_pallas_matches_xla(m):
    from petals_tpu.ops.quant import int8_matmul_pallas

    rng = np.random.RandomState(4)
    w = (rng.randn(512, 256) * 0.05).astype(np.float32)
    x = rng.randn(m, 512).astype(np.float32)
    q = quantize_int8(w)
    expected = x @ np.asarray(dequantize(q, np.float32))
    got = np.asarray(int8_matmul_pallas(jnp.asarray(x), q))
    np.testing.assert_allclose(got, expected, atol=2e-2, rtol=1e-2)


@pytest.mark.parametrize("m", [1, 40])
def test_int8_pallas_stacked_matches_xla(m):
    from petals_tpu.ops.quant import StackedQuantLinear, int8_matmul_pallas_stacked

    rng = np.random.RandomState(5)
    x = rng.randn(m, 512).astype(np.float32)
    qs = [quantize_int8((rng.randn(512, 256) * 0.05).astype(np.float32)) for _ in range(3)]
    data = jnp.stack([q.data for q in qs])
    scales = jnp.stack([q.scales for q in qs])
    for idx in (0, 2):
        sq = StackedQuantLinear("int8", data, scales, jnp.int32(idx), 512, 256)
        expected = x @ np.asarray(dequantize(qs[idx], np.float32))
        got = np.asarray(int8_matmul_pallas_stacked(jnp.asarray(x), sq))
        np.testing.assert_allclose(got, expected, atol=2e-2, rtol=1e-2)


def test_pick_tiles_rejects_unsupported_out_features():
    from petals_tpu.ops.quant import _pick_tiles

    with pytest.raises(ValueError, match="divisible"):
        _pick_tiles(1024, 384)


def test_nf4_pallas_alias():
    assert nf4_matmul_pallas is packed4_matmul_pallas  # back-compat name


def test_quant_matmul_grad_flows_to_x():
    rng = np.random.RandomState(3)
    w = (rng.randn(256, 256) * 0.05).astype(np.float32)
    q = quantize_nf4(w)
    x = jnp.asarray(rng.randn(1, 4, 256), jnp.float32)

    def loss(x):
        return quant_matmul(x, q).sum()

    g = jax.grad(loss)(x)
    expected = np.asarray(dequantize(q, jnp.float32)).sum(axis=1)
    np.testing.assert_allclose(
        np.asarray(g[0, 0], np.float32), expected, atol=0.3, rtol=0.05
    )


@pytest.mark.parametrize("quant", [QuantType.INT8, QuantType.NF4, QuantType.NF4A, QuantType.INT4, QuantType.NF4A_O])
def test_quantized_block_close_to_dense(quant, tmp_path):
    from petals_tpu.server.from_pretrained import get_block_config, load_block_params
    from tests.utils import make_tiny_llama

    path = make_tiny_llama(str(tmp_path))
    family, cfg = get_block_config(path)
    params = load_block_params(path, 0, dtype=jnp.float32)
    qparams = convert_block_params(params, "llama", quant)

    rng = np.random.RandomState(4)
    hidden = jnp.asarray(rng.randn(1, 8, cfg.hidden_size) * 0.5, jnp.float32)
    dense_out, _ = family.block_apply(params, hidden, None, 0, cfg)
    quant_out, _ = family.block_apply(qparams, hidden, None, 0, cfg)
    err = np.abs(np.asarray(quant_out) - np.asarray(dense_out)).max()
    bound = {QuantType.NF4: 0.2, QuantType.NF4A: 0.2, QuantType.INT4: 0.3, QuantType.INT8: 0.05, QuantType.NF4A_O: 0.2}[quant]
    assert err < bound, f"{quant}: err {err}"


@pytest.mark.parametrize("quant", ["nf4", "nf4a", "nf4a+o", "int4"])
def test_quantized_server_generates(quant, tmp_path):
    """4-bit servers serve a session end-to-end (reference CI quantized-server
    coverage); greedy tokens may differ from f32 HF — assert mechanics."""
    from petals_tpu.client.model import AutoDistributedModelForCausalLM
    from tests.test_full_model import SwarmHarness
    from tests.utils import make_tiny_llama

    path = make_tiny_llama(str(tmp_path))
    harness = SwarmHarness(path, [dict(first_block=0, num_blocks=4, quant_type=quant)]).start()
    try:
        model = AutoDistributedModelForCausalLM.from_pretrained(
            path, initial_peers=harness.initial_peers
        )
        try:
            rng = np.random.RandomState(5)
            ids = rng.randint(0, 100, (1, 5)).astype(np.int64)
            out = model.generate(ids, max_new_tokens=4)
            assert out.shape == (1, 9)
            assert (out >= 0).all() and (out < model.cfg.vocab_size).all()
            # training path through a quantized server too
            logits = np.asarray(model.forward(ids))
            assert np.isfinite(logits).all()
        finally:
            model.close()
    finally:
        harness.stop()


def test_nf4_decode_path_selection(monkeypatch):
    """The autotuned decode-path flag picks pallas vs XLA for small-M (decode)
    traces; prefill always takes the fused kernel (quant.py autotune)."""
    import jax.numpy as jnp

    from petals_tpu.ops import quant

    calls = []
    real_dequant = quant.dequantize

    def fake_pallas(x, w, **kwargs):
        calls.append(tuple(x.shape))
        return (x.astype(jnp.bfloat16) @ real_dequant(w, jnp.bfloat16)).astype(x.dtype)

    monkeypatch.setattr(quant, "packed4_matmul_pallas", fake_pallas)
    monkeypatch.setattr(quant.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(quant, "_NF4_DECODE_USE_PALLAS", False)

    rng = np.random.RandomState(0)
    w = quant.quantize_nf4(jnp.asarray(rng.randn(512, 256).astype(np.float32) * 0.05))
    decode_x = jnp.asarray(rng.randn(1, 512).astype(np.float32) * 0.1)
    prefill_x = jnp.asarray(rng.randn(64, 512).astype(np.float32) * 0.1)

    out = quant.quant_matmul(decode_x, w)  # decode + xla-preferred -> no kernel
    assert calls == [] and out.shape == (1, 256)
    quant.quant_matmul(prefill_x, w)  # prefill always uses the kernel
    assert calls == [(64, 512)]

    monkeypatch.setattr(quant, "_NF4_DECODE_USE_PALLAS", True)
    quant.quant_matmul(decode_x, w)  # decode + pallas-preferred -> kernel
    assert calls[-1] == (1, 512)


def test_nf4_autotune_noop_off_tpu():
    from petals_tpu.ops import quant

    # on CPU the autotune must not run (keeps the default) and must not crash
    assert quant.maybe_autotune_nf4_decode(128) == quant._NF4_DECODE_USE_PALLAS


@pytest.mark.parametrize("quant", ["nf4", "nf4a", "int4", "int8"])
def test_fused_block_matches_unfused(quant):
    """convert_block_params(fuse=True) merges qkv / gate+up into single leaves;
    scales are per-output-column, so the fused block must match the unfused one
    bit-for-bit (same codes, same scales, just concatenated columns)."""
    import jax.numpy as jnp

    from petals_tpu.models.registry import get_family
    from petals_tpu.models.llama.config import LlamaBlockConfig

    cfg = LlamaBlockConfig(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        intermediate_size=128, num_hidden_layers=1, rms_norm_eps=1e-6, vocab_size=64,
    )
    family = get_family("llama")
    rng = np.random.RandomState(0)
    shapes = family.block_param_shapes(cfg, jnp.float32)
    params = {
        name: jnp.asarray(rng.randn(*sds.shape) * 0.05, jnp.float32)
        for name, sds in shapes.items()
    }
    plain = convert_block_params(dict(params), "llama", quant)
    fused = convert_block_params(dict(params), "llama", quant, fuse=True)
    assert "wqkv" in fused and "wgu" in fused and "wq" not in fused

    hidden = jnp.asarray(rng.randn(1, 5, cfg.hidden_size) * 0.1, jnp.float32)
    out_plain, _ = family.block_apply(plain, hidden, None, 0, cfg)
    out_fused, _ = family.block_apply(fused, hidden, None, 0, cfg)
    np.testing.assert_array_equal(np.asarray(out_plain), np.asarray(out_fused))


def test_nf4a_roundtrip_error_and_levels():
    """NF4A: cubic-fitted levels track NF4's codebook to ~0.05 absolute, so
    the same blockwise-absmax error bound applies — while decode is pure
    arithmetic (no codebook gather in the kernels)."""
    from petals_tpu.ops.quant import NF4A_A, NF4A_B, NF4A_CODE, NF4_CODE

    # the levels ARE the cubic map (what the kernels compute arithmetically)
    d = np.arange(16) - 7.5
    np.testing.assert_allclose(NF4A_CODE, NF4A_A * d + NF4A_B * d**3, rtol=1e-6)
    assert np.abs(NF4A_CODE - NF4_CODE).max() < 0.06
    rng = np.random.RandomState(11)
    w = (rng.randn(256, 128) * 0.05).astype(np.float32)
    q = quantize_nf4a(w)
    assert q.kind == "nf4a" and q.data.dtype == jnp.uint8
    deq = np.asarray(dequantize(q, jnp.float32))
    blocks = w.reshape(-1, NF4_BLOCK, 128)
    absmax = np.abs(blocks).max(axis=1)
    max_gap = 0.23  # largest NF4A inter-level distance (at the tails)
    bound = np.repeat(absmax, NF4_BLOCK, axis=0) * max_gap
    assert (np.abs(deq - w) <= bound + 1e-6).all()
    stored = q.data.shape[0] * 2
    assert q.nbytes <= quantized_bytes(stored * 128, "nf4a") + 1024


def test_nf4a_matches_nf4_quality():
    """The serving-default claim: NF4A's weight-space SNR is at least NF4's
    (within measurement slack) on gaussian AND heavy-tailed weights — the
    regimes where uniform int4 loses 1-3 dB (tests/test_quant_quality.py)."""
    rng = np.random.RandomState(5)
    shape = (1024, 512)
    for w in (
        (rng.randn(*shape) * 0.02).astype(np.float32),
        (rng.standard_t(df=4, size=shape) * 0.02).astype(np.float32),
    ):
        def snr(q):
            dq = np.asarray(dequantize(q, jnp.float32))
            rel = np.square(dq - w).mean() / np.square(w).mean()
            return 10 * np.log10(1.0 / rel)

        assert snr(quantize_nf4a(w)) >= snr(quantize_nf4(w)) - 0.1


def test_outlier_quant_recovers_outlier_channels():
    """'+o': the top input channels by magnitude are exact (dense bf16) and
    the packed stream's blocks are no longer crushed by them — SNR in the
    outlier-channel regime beats the plain base kind by several dB, at
    ~4.5 bits/param."""
    from petals_tpu.ops.quant import (
        OUTLIER_DIVISOR,
        OutlierQuantLinear,
        quantize,
    )

    rng = np.random.RandomState(3)
    w = (rng.randn(512, 256) * 0.02).astype(np.float32)
    hot = rng.choice(512, size=512 // 128, replace=False)
    w[hot] *= 25.0  # outlier input channels (LLM.int8 regime)

    def snr(dq):
        rel = np.square(dq - w).mean() / np.square(w).mean()
        return 10 * np.log10(1.0 / rel)

    plain = snr(np.asarray(dequantize(quantize(jnp.asarray(w), "nf4a"), jnp.float32)))
    q = quantize(jnp.asarray(w), "nf4a+o")
    assert isinstance(q, OutlierQuantLinear) and q.kind == "nf4a+o"
    assert q.idx.shape == (512 // OUTLIER_DIVISOR,)
    with_o = snr(np.asarray(dequantize(q, jnp.float32)))
    assert with_o >= plain + 3.0, (plain, with_o)
    # every hot channel must be among the kept outliers (exact rows)
    kept = set(np.asarray(q.idx).tolist())
    assert set(hot.tolist()) <= kept
    # matmul path agrees with the dequantized reference
    x = rng.randn(4, 512).astype(np.float32) * 0.1
    got = np.asarray(quant_matmul(jnp.asarray(x), q))
    want = x @ np.asarray(dequantize(q, jnp.float32))
    np.testing.assert_allclose(got, want, atol=5e-2, rtol=1e-2)
