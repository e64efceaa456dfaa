"""Mamba-1's selective scan in plain ``jax.numpy``, in its two forms: one
position a row for decode rows, and a prompt's chunk of positions from a
lane's state on (``transformers`` ``models/jamba/modeling_jamba.py``
``JambaMambaMixer.slow_forward``, step 3; the reference has no such layer).

A channel keeps a state of ``d_state`` numbers in place of keys and values.
At position t, with the step ``dt_t`` a channel, ``B_t`` and ``C_t`` a state
index (all three made from the input: that is what "selective" means) and
``A`` < 0 a channel and a state index:

    S_t[n, c] = exp(dt_t[c] A[n, c]) S_(t-1)[n, c] + dt_t[c] B_t[n] u_t[c]
    y_t[c]    = sum_n S_t[n, c] C_t[n] + D[c] u_t[c]

The decay differs by channel AND by state index, so there is no matrix form
as the gated delta rule has: it is element-wise work on ``[d_state,
channels]``, a few flops a byte of state. Both forms keep the state and do its
arithmetic in float32, written as products and sums (a dot would round the
state to bfloat16 on the TPU). Nothing here knows a model: the caller makes
``dt`` (after its softplus), ``B``, ``C`` and ``A = -exp(A_log)``.

The state is held ``[d_state, channels]``, the channels along the chip's 128
lanes: as the checkpoint has it, ``[channels, d_state]`` with 16 minor, a
float32 tile would pad 16 to 128 and the state pool would be 8 times its
declared bytes. A lane pool's step hands a layer its lanes' states as they
lie in the state pool (ops/linear_attention.py ``StatePool``).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from petals_tpu.ops.linear_attention import StatePool

# positions of a chunk the scan's body holds at once: the state stays where the body computes for that many
# positions before the loop carries it on, and the loop's own cost is paid once for them
UNROLL = 8


def _advance(state, u, dt, a, b, c):
    """One position: ``state`` [batch, d_state, channels]; ``u``, ``dt`` [batch, channels]; ``a`` [d_state,
    channels]; ``b``, ``c`` [batch, d_state]. Returns (state, sum_n state C), without D's term."""
    state = jnp.exp(dt[:, None, :] * a) * state + (dt * u)[:, None, :] * b[:, :, None]
    return state, (state * c[:, :, None]).sum(1)


def selective_scan_step(state, u, dt, a, b, c, d) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One position a row. ``state`` [batch, d_state, channels] float32; ``u``,
    ``dt`` [batch, channels]; ``a`` [d_state, channels]; ``b``, ``c`` [batch,
    d_state]; ``d`` [channels]. Returns (state, y [batch, channels]), float32."""
    with jax.named_scope("ptu.ssm.step"):
        u, dt, a, b, c, d = (t.astype(jnp.float32) for t in (u, dt, a, b, c, d))
        state, y = _advance(state, u, dt, a, b, c)
        return state, y + d * u


def selective_scan_chunked(state, u, dt, a, b, c, d, n_valid=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """A chunk of ``seq`` positions a row, from ``state`` on. ``u``, ``dt``
    [batch, seq, channels]; ``b``, ``c`` [batch, seq, d_state]. Returns (state
    after the chunk, y [batch, seq, channels]), float32; positions from
    ``n_valid`` on are padding and leave the state as it is.

    A loop over the positions (``lax.scan``, ``UNROLL`` of them a turn): what
    it holds at once is the state and a turn's rows, never ``[seq, d_state,
    channels]`` (168 MB an array for a chunk of 512 at 16 x 5,120)."""
    with jax.named_scope("ptu.ssm.chunk"):
        seq = u.shape[1]
        u, dt, a, b, c, d = (t.astype(jnp.float32) for t in (u, dt, a, b, c, d))
        if n_valid is not None:  # padding: a step of 0 decays by exp(0) and adds nothing
            dt = jnp.where((jnp.arange(seq) < n_valid)[None, :, None], dt, 0.0)

        def position(state, row):
            u_t, dt_t, b_t, c_t = row
            return _advance(state, u_t, dt_t, a, b_t, c_t)

        rows = tuple(jnp.moveaxis(t, 1, 0) for t in (u, dt, b, c))
        state, y = jax.lax.scan(position, state, rows, unroll=min(UNROLL, seq))
        return state, jnp.moveaxis(y, 0, 1) + d * u


def selective_scan(state, u, dt, a, b, c, d, n_valid=None):
    """The form a call's shape asks for: one row a lane is a decode step,
    more is a prompt chunk."""
    if u.shape[1] == 1:
        state, y = selective_scan_step(state, u[:, 0], dt[:, 0], a, b[:, 0], c[:, 0], d)
        return state, y[:, None]
    return selective_scan_chunked(state, u, dt, a, b, c, d, n_valid)


def selective_scan_pooled(state: StatePool, u, dt, a, b, c, d, *, live, fresh) -> Tuple[StatePool, jnp.ndarray]:
    """One position a lane from the states where they lie in the state pool
    (``state.leaves[0]``, this layer's at ``state.slot``), for a lane pool's
    step: ``u``, ``dt``, ``b``, ``c`` as ``selective_scan_step``'s with the
    lanes as the batch, ``live`` / ``fresh`` bool [lanes]. A ``fresh`` lane (a
    row at position 0) starts from zeros whatever its slot held; a lane that
    is not ``live`` (idle) keeps its bytes and its output means nothing.
    Returns (the pool, y [lanes, channels] float32): this layer's slice of
    the pool is read once and written back once, whole."""
    live, fresh = jnp.asarray(live), jnp.asarray(fresh)
    held = state.read(0)
    new, y = selective_scan_step(jnp.where(fresh[:, None, None], 0.0, held), u, dt, a, b, c, d)
    return state.write(0, jnp.where(live[:, None, None], new, held)), y
