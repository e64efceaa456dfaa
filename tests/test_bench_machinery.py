"""The bench harness itself must not rot between driver runs (round 3 lost
its on-chip window partly to late harness failures): drive each e2e bench
coroutine at tiny shapes on CPU. Numbers are meaningless here — these tests
assert the MACHINERY (servers, streams, push chaining, lane pool, stats,
result schema) works end-to-end."""

import asyncio

import pytest

pytestmark = pytest.mark.slow  # real-process/heavyweight tier (run with -m slow)

import bench
from petals_tpu.models.llama.config import LlamaBlockConfig


@pytest.fixture()
def tiny_cfg():
    return LlamaBlockConfig(
        hidden_size=64,
        num_attention_heads=4,
        num_key_value_heads=2,
        head_dim=16,
        intermediate_size=128,
        num_hidden_layers=2,
        rms_norm_eps=1e-5,
        vocab_size=128,
    )


def test_chain_hop_bench_machinery(tiny_cfg):
    r = asyncio.run(
        bench.run_chain_hop_bench(cfg=tiny_cfg, quant=None, steps=4, prefill=4)
    )
    assert r["label"] == "chain_hop_405b_shapes"
    assert r["chain_step_ms"] > 0 and r["chain_tok_s"] > 0
    assert len(r["device_ms_per_span"]) == 2
    assert r["hop_software_ms"] >= 0
    assert r["serialize_ms"] > 0 and r["wire_bytes_per_activation"] > 0


def test_continuous_batching_bench_machinery(tiny_cfg, monkeypatch):
    monkeypatch.setattr(bench, "N_BLOCKS", 2)
    monkeypatch.setattr(bench, "MAX_LENGTH", 64)
    monkeypatch.setattr(bench, "llama7b_cfg", lambda n_blocks=2: tiny_cfg)
    r = asyncio.run(
        bench.run_continuous_batching_bench(concurrent=3, steps=4, prefill=4)
    )
    assert r["concurrent_agg_tok_s"] > 0 and r["serial_agg_tok_s"] > 0
    assert r["batcher_stats"]["max_batch"] >= 2, r  # coalescing really happened


def test_prefix_cache_bench_machinery(tiny_cfg):
    r = asyncio.run(bench.run_prefix_cache_bench(prefill=256, cfg=tiny_cfg))
    assert r["hit_tokens"] >= 256, r
    assert r["miss_prefill_ms"] > 0 and r["hit_prefill_ms"] > 0


def test_e2e_bench_machinery(tiny_cfg, monkeypatch):
    # MHA tiny (the matmul-chain tail assumes wq/wk/wv share an output dim)
    mha = LlamaBlockConfig(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
        head_dim=16, intermediate_size=128, num_hidden_layers=2,
        rms_norm_eps=1e-5, vocab_size=128,
    )
    monkeypatch.setattr(bench, "N_BLOCKS", 2)
    monkeypatch.setattr(bench, "MAX_LENGTH", 64)
    monkeypatch.setattr(bench, "PREFILL_TOKENS", 8)
    monkeypatch.setattr(bench, "WARMUP_STEPS", 1)
    monkeypatch.setattr(bench, "MEASURE_STEPS", 4)
    monkeypatch.setattr(bench, "llama7b_cfg", lambda n_blocks=2: mha)
    r = asyncio.run(bench.run_e2e_bench())
    for key in ("tok_s", "step_ms", "device_step_ms", "jit_step_ms", "matmul_chain_ms"):
        assert key in r, key
    assert r["tok_s"] > 0


def test_full_run_parent_stays_off_jax_and_fails_off_chip():
    """``python bench.py`` never imports jax in the parent, runs its rows as
    ``--row --on_chip`` children, and on a host without a TPU the first child
    refuses: non-zero exit, no metric line."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "bench.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == "", proc.stdout
    assert "not a TPU" in proc.stderr
