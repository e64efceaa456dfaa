"""Ring attention over a virtual mesh must match single-device attention
exactly (a capability the reference does not have — SURVEY.md §2.2)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from petals_tpu.ops.attention import attend_reference
from petals_tpu.ops.ring_attention import ring_attention_sharded
from petals_tpu.parallel.mesh import make_mesh


@pytest.mark.parametrize("ring,hq,hkv", [(4, 4, 4), (8, 8, 2)])
def test_ring_matches_reference(ring, hq, hkv):
    assert len(jax.devices()) >= ring
    mesh = make_mesh((ring,), ("sp",))
    rng = np.random.RandomState(0)
    b, seq, d = 2, 8 * ring, 16
    q = jnp.asarray(rng.randn(b, seq, hq, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, seq, hkv, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, seq, hkv, d), jnp.float32)

    expected = attend_reference(q, k, v, kv_length=seq)
    with mesh:
        got = ring_attention_sharded(q, k, v, mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=3e-5, rtol=1e-5)


def test_ring_alibi_matches_reference():
    """ALiBi bias rides the ring on global positions (BLOOM/Falcon can be
    sequence-parallel too)."""
    from petals_tpu.ops.alibi import build_alibi_slopes

    mesh = make_mesh((4,), ("sp",))
    rng = np.random.RandomState(2)
    b, seq, h, d = 2, 32, 8, 16
    q = jnp.asarray(rng.randn(b, seq, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, seq, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, seq, h, d), jnp.float32)
    slopes = build_alibi_slopes(h)

    expected = attend_reference(q, k, v, kv_length=seq, alibi_slopes=slopes)
    with mesh:
        got = ring_attention_sharded(q, k, v, mesh, alibi_slopes=slopes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=3e-5, rtol=1e-5)


@pytest.mark.parametrize("window", [pytest.param(8, marks=pytest.mark.slow), 17])
def test_ring_sliding_window_matches_reference(window):
    """Sliding windows apply to GLOBAL positions inside the ring (Mixtral
    long-context sequence parallelism)."""
    mesh = make_mesh((4,), ("sp",))
    rng = np.random.RandomState(3)
    b, seq, hq, hkv, d = 1, 32, 4, 2, 16
    q = jnp.asarray(rng.randn(b, seq, hq, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, seq, hkv, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, seq, hkv, d), jnp.float32)

    expected = attend_reference(q, k, v, kv_length=seq, sliding_window=window)
    with mesh:
        got = ring_attention_sharded(q, k, v, mesh, sliding_window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=3e-5, rtol=1e-5)


@pytest.mark.parametrize(
    "family_fixture",
    ["bloom", pytest.param("falcon", marks=pytest.mark.slow), pytest.param("mixtral", marks=pytest.mark.slow), "olmoe"],
)
def test_block_ring_matches_plain(family_fixture, tmp_path):
    """Every family's block must produce identical outputs with and without
    the ring (the sp training path now covers all four families)."""
    from petals_tpu.server.from_pretrained import get_block_config, load_block_params
    from tests.utils import make_tiny_bloom, make_tiny_falcon, make_tiny_mixtral, make_tiny_olmoe

    maker = {
        "bloom": make_tiny_bloom,
        "falcon": make_tiny_falcon,
        "mixtral": make_tiny_mixtral,
        "olmoe": make_tiny_olmoe,
    }[family_fixture]
    path = maker(str(tmp_path))
    family, cfg = get_block_config(path)
    assert family.supports_ring_attention
    params = load_block_params(path, 0, dtype=jnp.float32)

    mesh = make_mesh((2,), ("sp",))
    rng = np.random.RandomState(4)
    hidden = jnp.asarray(rng.randn(1, 16, cfg.hidden_size) * 0.1, jnp.float32)

    plain, _ = family.block_apply(params, hidden, None, 0, cfg)
    with mesh:
        ringed, _ = family.block_apply(params, hidden, None, 0, cfg, ring_mesh=mesh)
    np.testing.assert_allclose(
        np.asarray(ringed), np.asarray(plain), atol=5e-5, rtol=1e-4
    )


def test_ring_under_jit_with_sharded_inputs():
    """The op composes with jit + explicitly sharded activations (the
    training-path usage)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = make_mesh((4,), ("sp",))
    rng = np.random.RandomState(1)
    b, seq, h, d = 1, 32, 4, 8
    sharding = NamedSharding(mesh, P(None, "sp", None, None))
    q = jax.device_put(jnp.asarray(rng.randn(b, seq, h, d), jnp.float32), sharding)
    k = jax.device_put(jnp.asarray(rng.randn(b, seq, h, d), jnp.float32), sharding)
    v = jax.device_put(jnp.asarray(rng.randn(b, seq, h, d), jnp.float32), sharding)

    @jax.jit
    def f(q, k, v):
        return ring_attention_sharded(q, k, v, mesh)

    with mesh:
        out = f(q, k, v)
    expected = attend_reference(q, k, v, kv_length=seq)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=3e-5, rtol=1e-5)
    # output stays sequence-sharded — no all-gather of activations
    assert len(out.sharding.device_set) == 4
