"""ops/linear_attention.py: the gated delta rule's one-step form against its
chunked form, the state handed from chunk to chunk, padding left out of it,
and the causal conv with the rows it hands on. Float32 on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from petals_tpu.ops.linear_attention import CHUNK, causal_conv, gated_delta, gated_delta_chunked, gated_delta_step

# float32 tolerance of one form against the other, relative to the largest output: the chunked form
# solves a 64 x 64 system where the one-step form adds 64 times (measured 2e-6..6e-6 here)
FORMS_AGREE = 3e-5
HEADS, D_K, D_V = 3, 16, 24


def _inputs(seq: int, seed: int = 0, batch: int = 2):
    rng = np.random.default_rng(seed)

    def unit(a):
        return a / np.sqrt((a * a).sum(-1, keepdims=True) + 1e-6)

    q = unit(rng.standard_normal((batch, seq, HEADS, D_K))) / np.sqrt(D_K)
    k = unit(rng.standard_normal((batch, seq, HEADS, D_K)))
    v = rng.standard_normal((batch, seq, HEADS, D_V))
    g = np.log(rng.uniform(0.2, 0.999, (batch, seq, HEADS)))  # alpha as the configuration's weights spread it
    beta = rng.uniform(0.0, 2.0, (batch, seq, HEADS))  # doubled: linear_allow_neg_eigval
    state = rng.standard_normal((batch, HEADS, D_K, D_V)) * 0.1
    return tuple(jnp.asarray(a, jnp.float32) for a in (state, q, k, v, g, beta))


def _stepwise(state, q, k, v, g, beta):
    outs = []
    for t in range(q.shape[1]):
        state, o = gated_delta_step(state, q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t])
        outs.append(o)
    return state, jnp.stack(outs, axis=1)


def _close(a, b, tol=FORMS_AGREE):
    a, b = np.asarray(a), np.asarray(b)
    assert np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1e-6), (np.abs(a - b).max(), np.abs(b).max())


@pytest.mark.parametrize("seq", [2, CHUNK - 1, CHUNK, CHUNK + 1, 2 * CHUNK, 3 * CHUNK + 8])
def test_chunked_form_equals_the_one_step_form(seq):
    """Lengths that are and are not multiples of the sub-chunk."""
    inputs = _inputs(seq, seed=seq)
    state_1, out_1 = _stepwise(*inputs)
    state_c, out_c = jax.jit(gated_delta_chunked)(*inputs)
    assert out_c.shape == out_1.shape and out_c.dtype == jnp.float32
    _close(out_c, out_1)
    _close(state_c, state_1)


@pytest.mark.parametrize("cut", [1, CHUNK, CHUNK + 7])
def test_state_is_handed_from_chunk_to_chunk_to_a_decode_step(cut):
    seq = 2 * CHUNK + 5
    state, *rows = _inputs(seq + 1, seed=cut)
    whole_state, whole = gated_delta_chunked(state, *(a[:, :seq] for a in rows))
    mid, first = gated_delta_chunked(state, *(a[:, :cut] for a in rows))
    end, second = gated_delta(mid, *(a[:, cut:seq] for a in rows))
    _close(jnp.concatenate([first, second], axis=1), whole)
    _close(end, whole_state)
    after_whole = gated_delta(whole_state, *(a[:, seq:] for a in rows))  # one row: the one-step form
    after_parts = gated_delta(end, *(a[:, seq:] for a in rows))
    _close(after_parts[1], after_whole[1])


@pytest.mark.parametrize("n_valid", [1, 37, CHUNK, 100])
def test_padding_leaves_the_state_alone(n_valid):
    bucket = 128
    state, *rows = _inputs(bucket, seed=n_valid)
    want_state, want = gated_delta_chunked(state, *(a[:, :n_valid] for a in rows))
    got_state, got = jax.jit(gated_delta_chunked)(state, *rows, jnp.int32(n_valid))
    _close(got[:, :n_valid], want)
    _close(got_state, want_state)


@pytest.mark.parametrize("split", [(7,), (1, 1, 1, 4), (3, 4), (2, 5)])
def test_conv_hands_on_its_last_rows(split):
    rng = np.random.default_rng(sum(split))
    channels, width = 10, 4
    u = jnp.asarray(rng.standard_normal((2, 7, channels)), jnp.float32)
    taps = jnp.asarray(rng.standard_normal((width, channels)), jnp.float32)
    padded = jnp.pad(u, ((0, 0), (width - 1, 0), (0, 0)))
    pre = sum(taps[j] * padded[:, j : j + 7] for j in range(width))
    want = pre * jax.nn.sigmoid(pre)
    tail, outs, at = jnp.zeros((2, width - 1, channels), jnp.float32), [], 0
    for n in split:
        out, tail = causal_conv(u[:, at : at + n], tail, taps)
        outs.append(out)
        at += n
    np.testing.assert_allclose(np.concatenate(outs, axis=1), want, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(tail, u[:, -(width - 1) :])


def test_conv_tail_of_a_padded_chunk_is_of_its_valid_rows():
    rng = np.random.default_rng(5)
    u = jnp.asarray(rng.standard_normal((1, 8, 6)), jnp.float32)
    taps = jnp.asarray(rng.standard_normal((4, 6)), jnp.float32)
    tail0 = jnp.asarray(rng.standard_normal((1, 3, 6)), jnp.float32)
    _, tail = jax.jit(causal_conv)(u, tail0, taps, jnp.int32(5))
    np.testing.assert_array_equal(tail, u[:, 2:5])
    _, tail = causal_conv(u, tail0, taps, jnp.int32(2))  # fewer valid rows than the tail is long: the old tail's last
    np.testing.assert_array_equal(tail, jnp.concatenate([tail0[:, 2:], u[:, :2]], axis=1))
