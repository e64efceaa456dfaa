"""The gated-delta mixer of a linear-attention layer, shared by the families
that have one (olmo_hybrid, qwen3_next): projections, the short causal conv,
the rule (ops/linear_attention.py), the gated output norm and the output
projection, from a lane's STATE on and back to it.

The state is what ``state_shapes`` declares: a float32 matrix of ``d_k x d_v``
a VALUE head, and the last ``taps - 1`` rows of the conv's input. The
parameters are one set of leaves for both families (a checkpoint that fuses
its projections is taken apart at load): ``wq`` / ``wk`` [h, key heads x d_k],
``wv`` / ``wz`` [h, value heads x d_v], ``wa`` / ``wb`` [h, value heads],
``conv`` [taps, channels], ``a_log`` / ``dt_bias`` [value heads], ``o_norm``
[d_v], ``wo`` [value heads x d_v, h]. Two things differ by family and are
``MixerDims``' to say: how many value heads a key head serves (``key_heads``
under ``heads``: each key head's q and k are repeated for its consecutive
value heads; with as many of one as of the other nothing is repeated) and
whether beta is doubled (``beta_scale``)."""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from petals_tpu.models.common import mm, rms_norm, silu
from petals_tpu.ops.linear_attention import StatePool, causal_conv, gated_delta, gated_delta_pooled


class MixerDims(NamedTuple):
    """The static shapes of a family's linear-attention layer."""

    key_heads: int
    heads: int  # value heads: one state each
    d_k: int
    d_v: int
    taps: int
    beta_scale: float = 1.0  # 2.0: beta in (0, 2), so a state's eigenvalues reach -1

    @property
    def channels(self) -> int:
        """q, k and v of every head side by side: what the short conv runs over."""
        return 2 * self.key_heads * self.d_k + self.heads * self.d_v


def state_shapes(dims: MixerDims) -> tuple:
    """``ModelFamily.block_state`` of a linear layer: ``((shape, dtype), ...)`` a lane, dtype None for the cache's own."""
    return (((dims.heads, dims.d_k, dims.d_v), jnp.float32), ((dims.taps - 1, dims.channels), None))


def _l2_norm(x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + eps)


def gated_delta_mixer(params: dict, x: jnp.ndarray, state, position, dims: MixerDims, eps: float, n_valid, live_rows):
    """The mixer over ``x`` [batch, seq, hidden] from ``state`` on: (its
    output, the state after it). ``state`` None: a whole sequence from its
    start, no state handed back. ``state`` a ``StatePool``: a lane pool's
    step, one row a lane; the matrices stay in the pool, where the one-step
    rule reads and writes them (``gated_delta_pooled``), only the conv's tail
    is taken out and put back, and the pool is what comes back."""
    batch, seq, _ = x.shape
    key_heads, heads, d_k, d_v = dims.key_heads, dims.heads, dims.d_k, dims.d_v
    pooled = isinstance(state, StatePool)
    u = jnp.concatenate([mm(x, params[name]) for name in ("wq", "wk", "wv")], axis=-1)
    if state is None:  # no cache: a whole sequence from its start
        matrix = jnp.zeros((batch, heads, d_k, d_v), jnp.float32)
        tail = jnp.zeros((batch, dims.taps - 1, u.shape[-1]), u.dtype)
    else:
        fresh = jnp.broadcast_to(jnp.asarray(position, jnp.int32) == 0, (batch,))
        held_tail = state.read(1) if pooled else state[1]
        tail = jnp.where(fresh[:, None, None], jnp.zeros((), held_tail.dtype), held_tail)
        if not pooled:
            matrix = jnp.where(fresh[:, None, None, None], 0.0, state[0])
    mixed, tail = causal_conv(u, tail, params["conv"], n_valid)
    q, k, v = jnp.split(mixed, (key_heads * d_k, 2 * key_heads * d_k), axis=-1)
    q = _l2_norm(q.reshape(batch, seq, key_heads, d_k)) * (1.0 / math.sqrt(d_k))
    k = _l2_norm(k.reshape(batch, seq, key_heads, d_k))
    if heads != key_heads:  # a key head serves ``heads // key_heads`` consecutive value heads
        q, k = (jnp.repeat(t, heads // key_heads, axis=2) for t in (q, k))
    v = v.reshape(batch, seq, heads, d_v)
    beta = jax.nn.sigmoid(mm(x, params["wb"]).astype(jnp.float32)) * dims.beta_scale
    decay = -jnp.exp(params["a_log"].astype(jnp.float32))
    g = decay * jax.nn.softplus(mm(x, params["wa"]).astype(jnp.float32) + params["dt_bias"].astype(jnp.float32))
    if pooled:
        assert seq == 1, "a lane pool's step hands its state pooled with one row a lane"
        live = jnp.ones((batch,), bool) if live_rows is None else live_rows
        state, out = gated_delta_pooled(state, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], live=live, fresh=fresh)
        out = out[:, None]
    else:
        matrix, out = gated_delta(matrix, q, k, v, g, beta, n_valid)
    with jax.named_scope("ptu.linattn.gate_norm"):
        gate = silu(mm(x, params["wz"]).astype(jnp.float32)).reshape(batch, seq, heads, d_v)
        out = (rms_norm(out, params["o_norm"], eps) * gate).astype(x.dtype)
    y = mm(out.reshape(batch, seq, heads * d_v), params["wo"])
    if state is None:
        return y, None
    if live_rows is not None:  # an idle lane's state stays as it was
        if not pooled:
            matrix = jnp.where(live_rows[:, None, None, None], matrix, state[0])
        tail = jnp.where(live_rows[:, None, None], tail, held_tail)
    tail = tail.astype(held_tail.dtype)
    return y, (state.write(1, tail) if pooled else (matrix, tail))
