"""ops/linear_attention.py: the gated delta rule's one-step form against its
chunked form, the state handed from chunk to chunk, padding left out of it,
the causal conv with the rows it hands on, and the one-step form's kernel over
the state pool (interpreted) against its plain form. Float32 on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from petals_tpu.ops import linear_attention as la
from petals_tpu.ops.linear_attention import CHUNK, StatePool, causal_conv, gated_delta, gated_delta_chunked, gated_delta_step

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


# ---------------------------------------------------------------- the one-step form where the states lie in the pool (PR 49)

# the kernel against the plain form, relative to the largest value: the same float32 products, the sums over d_k in
# another order (measured 2e-7..6e-7 over three steps here)
KERNEL_AGREES = 5e-6
QWEN3_NEXT, OLMO_HYBRID = (32, 16, 128, 128), (30, 30, 96, 192)  # value heads, key heads, d_k, d_v: the two configurations'
LANES, SLOTS, STEPS = 4, 3, 3


def _pool_inputs(heads, key_heads, d_k, d_v, seed):
    rng = np.random.default_rng(seed)

    def unit(a):
        return a / np.sqrt((a * a).sum(-1, keepdims=True) + 1e-6)

    q, k = (unit(rng.standard_normal((STEPS, LANES, key_heads, d_k))) for _ in range(2))
    q, k = (np.repeat(a, heads // key_heads, axis=2) for a in (q / np.sqrt(d_k), k))  # a key head serves consecutive value heads
    v = rng.standard_normal((STEPS, LANES, heads, d_v))
    g = np.log(rng.uniform(0.2, 0.999, (STEPS, LANES, heads)))
    beta = rng.uniform(0.0, 2.0, (STEPS, LANES, heads))
    pool = rng.standard_normal((SLOTS, LANES, heads, d_k, d_v)) * 0.1
    return tuple(jnp.asarray(a, jnp.float32) for a in (pool, q, k, v, g, beta))


STEP_CASES = [
    # (live, fresh) a step: lanes that decode, sit idle, or start a sequence from a slot that held another's state
    pytest.param(QWEN3_NEXT, None, [("1011", "0000"), ("1101", "0100"), ("0110", "0000")], id="qwen3next-32x128x128-mixed"),
    pytest.param(QWEN3_NEXT, 8, [("1111", "0000"), ("0000", "0000"), ("0001", "0001")], id="qwen3next-8-heads-a-step-all-none-one"),
    pytest.param(OLMO_HYBRID, None, [("1011", "0000"), ("1101", "0100"), ("0110", "0000")], id="olmohybrid-30x96x192-mixed"),
    pytest.param(OLMO_HYBRID, 6, [("1111", "0000"), ("0000", "0000"), ("1000", "1000")], id="olmohybrid-6-heads-a-step-all-none-one"),
    pytest.param(OLMO_HYBRID, 1, [("0101", "0001"), ("1010", "0000"), ("1111", "1111")], id="olmohybrid-1-head-a-step-all-fresh"),
]


@pytest.mark.parametrize("sizes,heads_a_step,steps", STEP_CASES)
def test_the_kernel_moves_the_live_lanes_states_where_they_lie_and_agrees_with_the_plain_form(sizes, heads_a_step, steps):
    """Consecutive steps on one slot of a pool of three: after each, outputs
    and the slot's live lanes within float32 reassociation of
    ``gated_delta_step`` on a copy; idle lanes and the other two slots bit for
    bit as they went in."""
    pool, *rows = _pool_inputs(*sizes, seed=len(steps[0][0]) + sizes[0])
    slot = 1
    def step(pool, live, fresh, *row):
        state, out = la.gated_delta_pooled(StatePool((pool,), jnp.int32(slot)), *row, live=live, fresh=fresh, path="kernel", heads_a_step=heads_a_step)
        return state.leaves[0], out

    step = jax.jit(step)
    for t, (live, fresh) in enumerate(steps):
        live, fresh = (np.array([c == "1" for c in flags]) for flags in (live, fresh))
        row = [a[t] for a in rows]
        want_state, want_out = gated_delta_step(jnp.where(fresh[:, None, None, None], 0.0, pool[slot]), *row)
        new, out = step(pool, live, fresh, *row)
        assert new.dtype == out.dtype == jnp.float32 and new.shape == pool.shape and out.shape == want_out.shape
        np.testing.assert_array_equal(np.asarray(new)[[0, 2]], np.asarray(pool)[[0, 2]])
        np.testing.assert_array_equal(np.asarray(new[slot])[~live], np.asarray(pool[slot])[~live])
        assert not np.asarray(out)[~live].any()  # no grid step writes an idle lane's output: zeros, not what lay there
        if live.any():
            _close(np.asarray(new[slot])[live], np.asarray(want_state)[live], KERNEL_AGREES)
            _close(np.asarray(out)[live], np.asarray(want_out)[live], KERNEL_AGREES)
        pool = new


@pytest.mark.parametrize("sizes", [QWEN3_NEXT, OLMO_HYBRID], ids=["qwen3next", "olmohybrid"])
def test_a_fresh_lane_s_result_does_not_depend_on_what_its_slot_held(sizes):
    """NaN in the slot of a lane that starts a sequence: zeros are what it starts from, by a select and not a product."""
    pool, *rows = _pool_inputs(*sizes, seed=7)
    row = [a[0] for a in rows]
    live, fresh = np.array([True, True, False, True]), np.array([False, True, False, False])
    poisoned = pool.at[1, 1].set(jnp.nan)
    results = [la.gated_delta_pooled(StatePool((p,), jnp.int32(1)), *row, live=live, fresh=fresh, path="kernel") for p in (pool, poisoned)]
    results = [(state.leaves[0], out) for state, out in results]
    np.testing.assert_array_equal(np.asarray(results[0][0][1]), np.asarray(results[1][0][1]))
    np.testing.assert_array_equal(np.asarray(results[0][1])[live], np.asarray(results[1][1])[live])
    assert np.isfinite(np.asarray(results[1][0][1])).all()


def test_the_plain_form_over_the_pool_is_the_one_step_form_on_a_layer_s_slice():
    """Off the chip ``gated_delta_pooled`` is ``gated_delta_step`` between the two selects, to the bit."""
    pool, *rows = _pool_inputs(4, 4, 16, 24, seed=3)
    row = [a[0] for a in rows]
    live, fresh = jnp.asarray([True, False, True, True]), jnp.asarray([False, False, True, False])
    assert la.gated_delta_step_path(StatePool((pool,), 2), 1) == "plain"
    state, out = la.gated_delta_pooled(StatePool((pool,), jnp.int32(2)), *row, live=live, fresh=fresh)
    new = state.leaves[0]
    want_state, want_out = gated_delta_step(jnp.where(fresh[:, None, None, None], 0.0, pool[2]), *row)
    np.testing.assert_array_equal(np.asarray(new[2]), np.asarray(jnp.where(live[:, None, None, None], want_state, pool[2])))
    np.testing.assert_array_equal(np.asarray(new[:2]), np.asarray(pool[:2]))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want_out))


def _pool_of(heads, d_k, d_v, dtype=jnp.float32):
    return StatePool((jax.ShapeDtypeStruct((6, 8, heads, d_k, d_v), dtype), jax.ShapeDtypeStruct((6, 8, 3, 64), jnp.bfloat16)), 0)


PATH_CASES = [
    pytest.param(_pool_of(32, 128, 128), 1, None, id="qwen3next"),
    pytest.param(_pool_of(30, 96, 192), 1, None, id="olmohybrid-192-is-the-full-last-dimension"),
    pytest.param(_pool_of(32, 128, 128), 64, "64 rows a lane", id="a-chunk"),
    pytest.param((jnp.zeros((1, 4, 16, 24)), jnp.zeros((1, 3, 64))), 1, "no pooled state", id="a-chunk-s-lane-one-row"),
    pytest.param(None, 7, "no pooled state", id="a-whole-sequence-without-a-cache"),
    pytest.param(_pool_of(4, 100, 128), 1, "100 is no multiple of the 8 sublanes", id="a-key-head-of-100"),
    pytest.param(_pool_of(4, 1024, 1024), 1, "over the 3 MiB a grid step holds", id="a-head-of-four-MiB"),
    pytest.param(_pool_of(32, 128, 128, jnp.bfloat16), 1, "a state of bfloat16", id="a-bfloat16-state"),
]


@pytest.mark.parametrize("state,rows,reason", PATH_CASES)
def test_the_path_follows_from_what_the_call_shows_and_gives_its_reason(monkeypatch, state, rows, reason):
    why = la.step_kernel_unsupported(state, rows)
    assert (why is None) if reason is None else (reason in why), why
    assert la.gated_delta_step_path(state, rows) == "plain"  # off the chip, whatever the call
    monkeypatch.setattr(la, "_on_tpu", lambda: True)
    assert la.gated_delta_step_path(state, rows) == ("kernel" if reason is None else "plain")


def test_heads_a_grid_step_follow_from_the_shapes():
    assert la.step_kernel_heads(32, 128, 128) == 32  # 2 MiB in and as much out: a lane's heads in one grid step
    assert la.step_kernel_heads(30, 96, 192) == 30  # 2.8 MiB as they lie in tiles of 128 lanes
    assert la.step_kernel_heads(32, 256, 256) == 8 and la.step_kernel_heads(7, 512, 512) == 1  # a divisor of the heads
    assert la.step_kernel_heads(256, 8, 128) == 128  # k and q are turned as one tile
