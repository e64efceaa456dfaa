"""Linear attention by the gated delta rule, in plain ``jax.numpy``: the short
causal conv in front of it with the rows it hands on, and the rule in its two
forms, one position at a time for decode rows and chunked for a prompt chunk
(``transformers`` ``models/qwen3_next``: ``torch_recurrent_gated_delta_rule``,
``torch_chunk_gated_delta_rule``; the reference has no such layer).

A head keeps a state ``S`` of ``d_k x d_v`` in place of keys and values. At
position t, with ``alpha_t`` in (0, 1] and ``beta_t`` a scalar:

    S' = alpha_t S;  S = S' + k_t (beta_t (v_t - S'^T k_t))^T;  o_t = S^T q_t

Both forms keep the state and do its arithmetic in float32, with no matmul at
the TPU's default (bfloat16) precision between a state and what is read from
it. Nothing here knows a model: the caller normalises q and k, scales q, and
gives ``g = log(alpha)`` and ``beta``.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

CHUNK = 64  # positions a sub-chunk of the chunked form: one triangular system each
_EXACT = jax.lax.Precision.HIGHEST


def causal_conv(u: jnp.ndarray, tail: jnp.ndarray, taps: jnp.ndarray, n_valid=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Depthwise causal conv of ``K`` taps without bias, then silu.

    ``u`` [batch, seq, channels] are the new rows, ``tail`` [batch, K - 1,
    channels] the rows before them (zeros at a sequence's start), ``taps``
    [K, channels]: ``out_t = silu(sum_j taps[j] * u_(t - (K - 1) + j))``.
    Returns the float32 output [batch, seq, channels] and the next call's
    tail, the last ``K - 1`` rows of what has been fed in ``tail``'s dtype:
    of the first ``n_valid`` rows of ``u`` where the rest is padding."""
    with jax.named_scope("ptu.linattn.conv"):
        seq, width = u.shape[1], taps.shape[0]
        fed = jnp.concatenate([tail.astype(u.dtype), u], axis=1)  # [batch, K - 1 + seq, channels]
        w = taps.astype(jnp.float32)
        out = sum(w[j] * fed[:, j : j + seq].astype(jnp.float32) for j in range(width))
        n = seq if n_valid is None else n_valid
        new_tail = jax.lax.dynamic_slice_in_dim(fed, n, width - 1, axis=1).astype(tail.dtype)
        return out * jax.nn.sigmoid(out), new_tail


def gated_delta_step(state, q, k, v, g, beta):
    """One position a row. ``state`` [batch, heads, d_k, d_v] float32; ``q``,
    ``k`` [batch, heads, d_k]; ``v`` [batch, heads, d_v]; ``g`` (log alpha),
    ``beta`` [batch, heads]. Returns (state, o [batch, heads, d_v]), float32.

    It moves the state twice and multiplies almost nothing, so it is written
    as products and sums over ``d_k``: a dot would round the state to
    bfloat16 on the TPU."""
    with jax.named_scope("ptu.linattn.recurrent"):
        q, k, v, g, beta = (a.astype(jnp.float32) for a in (q, k, v, g, beta))
        state = state * jnp.exp(g)[..., None, None]
        read = (state * k[..., None]).sum(-2)
        delta = (v - read) * beta[..., None]
        state = state + k[..., None] * delta[..., None, :]
        return state, (state * q[..., None]).sum(-2)


def gated_delta_chunked(state, q, k, v, g, beta, n_valid=None):
    """A chunk of ``seq`` positions a row, from ``state`` on. ``q``, ``k``
    [batch, seq, heads, d_k]; ``v`` [batch, seq, heads, d_v]; ``g``, ``beta``
    [batch, seq, heads]. Returns (state after the chunk, o [batch, seq,
    heads, d_v]), float32; positions from ``n_valid`` on are padding and
    leave the state as it is.

    Sub-chunks of ``CHUNK``: within one the rule is a unit lower triangular
    system (solved, not unrolled), across them the state is carried."""
    with jax.named_scope("ptu.linattn.chunk"):
        batch, seq, heads, d_v = v.shape
        q, k, v, g, beta = (a.astype(jnp.float32) for a in (q, k, v, g, beta))
        if n_valid is not None:  # padding: alpha 1, beta 0
            valid = (jnp.arange(seq) < n_valid)[None, :, None]
            g, beta = jnp.where(valid, g, 0.0), jnp.where(valid, beta, 0.0)
        pad = -seq % CHUNK
        if pad:
            q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)) for a in (q, k, v, g, beta))
        n = (seq + pad) // CHUNK

        def chunks(a):  # [batch, seq, heads, ...] -> [n, batch, heads, CHUNK, ...]
            a = a.reshape(batch, n, CHUNK, *a.shape[2:])
            return jnp.moveaxis(jnp.moveaxis(a, 3, 1), 2, 0)

        q, k, v, g, beta = map(chunks, (q, k, v, g, beta))
        g = jnp.cumsum(g, axis=-1)  # log of the decay from a sub-chunk's start to each of its positions
        lower = jnp.tril(jnp.ones((CHUNK, CHUNK), bool))
        decay = jnp.exp(jnp.where(lower, g[..., :, None] - g[..., None, :], -jnp.inf))  # [.., i, j]: j to i, 0 above
        k_beta, v_beta = k * beta[..., None], v * beta[..., None]
        system = jnp.where(lower, jnp.einsum("...ik,...jk->...ij", k_beta, k, precision=_EXACT) * decay, 0.0)
        rhs = jnp.concatenate([v_beta, k_beta * jnp.exp(g)[..., None]], axis=-1)
        solved = jax.scipy.linalg.solve_triangular(system, rhs, lower=True, unit_diagonal=True)
        v_own, k_decayed = solved[..., :d_v], solved[..., d_v:]

        def sub_chunk(state, xs):
            q_i, k_i, v_i, kd_i, g_i, decay_i = xs
            within = jnp.einsum("...ik,...jk->...ij", q_i, k_i, precision=_EXACT) * decay_i
            v_new = v_i - jnp.einsum("...ik,...kv->...iv", kd_i, state, precision=_EXACT)
            out = jnp.einsum("...ik,...kv->...iv", q_i * jnp.exp(g_i)[..., None], state, precision=_EXACT)
            out = out + jnp.einsum("...ij,...jv->...iv", within, v_new, precision=_EXACT)
            to_end = jnp.exp(g_i[..., -1:] - g_i)[..., None]
            state = state * jnp.exp(g_i[..., -1])[..., None, None]
            state = state + jnp.einsum("...jk,...jv->...kv", k_i * to_end, v_new, precision=_EXACT)
            return state, out

        state, out = jax.lax.scan(sub_chunk, state, (q, k, v_own, k_decayed, g, decay))
        out = jnp.moveaxis(jnp.moveaxis(out, 0, 2), 1, 3)  # [batch, n, CHUNK, heads, d_v]
        return state, out.reshape(batch, n * CHUNK, heads, d_v)[:, :seq]


def gated_delta(state, q, k, v, g, beta, n_valid=None):
    """The form a call's shape asks for: one row a lane is a decode step,
    more is a prompt chunk."""
    if q.shape[1] == 1:
        state, out = gated_delta_step(state, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
        return state, out[:, None]
    return gated_delta_chunked(state, q, k, v, g, beta, n_valid)
