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

A lane pool's step hands a layer its lanes' states as they lie in the state
pool (``StatePool``, as ``PagedKV`` stands for pages). Its decode rows' one-step
form is then, on a TPU, ONE Pallas kernel a layer (``_step_kernel``) that reads
each live lane's matrix out of the pool once and writes it back once, in place;
``step_kernel_unsupported`` says, from what the call shows, why a call keeps
the plain form instead, which is also the kernel's reference.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 64  # positions a sub-chunk of the chunked form: one triangular system each
_EXACT = jax.lax.Precision.HIGHEST
LANES, SUBLANES = 128, 8  # the chip's tile of float32


def causal_conv(u: jnp.ndarray, tail: jnp.ndarray, taps: jnp.ndarray, n_valid=None, bias=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Depthwise causal conv of ``K`` taps, then silu; ``bias`` [channels] is added in front of the silu where a layer has one.

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
        if bias is not None:
            out = out + bias.astype(jnp.float32)
        n = seq if n_valid is None else n_valid
        new_tail = jax.lax.dynamic_slice_in_dim(fed, n, width - 1, axis=1).astype(tail.dtype)
        return out * jax.nn.sigmoid(out), new_tail


def gated_delta_step(state, q, k, v, g, beta):
    """One position a row. ``state`` [batch, heads, d_k, d_v] float32; ``q``,
    ``k`` [batch, heads, d_k]; ``v`` [batch, heads, d_v]; ``g`` (log alpha),
    ``beta`` [batch, heads]. Returns (state, o [batch, heads, d_v]), float32.

    It multiplies almost nothing and only moves the state (compiled for the
    v5e: four passes over it with the pool's write-back, PERF.md section 5,
    PR 49), so it is written as products and sums over ``d_k``: a dot would
    round the state to bfloat16 on the TPU."""
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


# ---------------------------------------------------------------------------------- a layer's states where they lie in the pool


class StatePool(NamedTuple):
    """A state layer's stand-in for its lanes' states in a lane pool's step,
    as ``PagedKV`` is for pages: the state pool's ``leaves`` whole, each
    ``[state layers, lanes, ...]`` as the layer loop carries them, and the
    layer's ``slot`` in them. What comes back from the layer is the pool."""

    leaves: tuple
    slot: jnp.ndarray  # int32 scalar, traced

    def read(self, leaf: int) -> jnp.ndarray:
        """This layer's ``[lanes, ...]`` of one leaf: a copy."""
        return jax.lax.dynamic_index_in_dim(self.leaves[leaf], self.slot, 0, keepdims=False)

    def write(self, leaf: int, new: jnp.ndarray) -> "StatePool":
        """The pool with this layer's ``[lanes, ...]`` of one leaf set."""
        with jax.named_scope("ptu.state.write"):  # the pass the compiler fuses what made ``new`` into
            held = self.leaves[leaf]
            held = jax.lax.dynamic_update_index_in_dim(held, new.astype(held.dtype), self.slot, 0)
        return self._replace(leaves=(*self.leaves[:leaf], held, *self.leaves[leaf + 1:]))

    def lane(self, lane) -> tuple:
        """One lane's states in this layer, ``[1, ...]`` a leaf: what a prompt's chunk starts from."""
        return tuple(
            jax.lax.dynamic_slice(a, (self.slot, lane) + (0,) * (a.ndim - 2), (1, 1) + a.shape[2:])[0] for a in self.leaves
        )

    def with_lane(self, lane, new: tuple) -> "StatePool":
        """The pool with one lane's states in this layer set: that lane's bytes move, no other's."""
        with jax.named_scope("ptu.state.write"):
            leaves = tuple(
                jax.lax.dynamic_update_slice(a, n.astype(a.dtype)[None], (self.slot, lane) + (0,) * (a.ndim - 2))
                for a, n in zip(self.leaves, new)
            )
        return self._replace(leaves=leaves)


# A grid step of the kernel holds at most this many bytes of one lane's heads in fast memory, coming in, and as many going
# out, each double-buffered, counted as they lie there (``d_v`` in whole tiles of 128 lanes): all 32 of Qwen3-Next's heads
# of [128, 128] (2 MiB) and all 30 of Olmo-Hybrid's of [96, 192] (2.8 MiB). On the v5e (benchmarks/ablate_gated_delta_step.py,
# PR 49, a pool too deep to be found again on the chip; PERF.md section 5) the kernel is as fast as a kernel that only
# copies its blocks in and out, 60-72% of the bandwidth floor, so a grid step's own cost and the pipeline's first fetch and
# last write-back are all there is to win: 4 live lanes of Qwen3-Next 0.515 / 0.342 / 0.251 / 0.210 / 0.199 / 0.188 ms
# over six layers at 1 / 2 / 4 / 8 / 16 / 32 heads a step (the plain form 0.624), Olmo-Hybrid's twelve 1.06 ... 0.547 (6) ...
# 0.489 (30) (plain 1.68).
STEP_KERNEL_BLOCK_BYTES = 3 << 20


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    # off the chip the kernel runs in the Pallas interpreter; a compile-only test patches this (and ``_on_tpu``)
    return jax.default_backend() != "tpu"


def _head_bytes(d_k: int, d_v: int) -> int:
    """One head's float32 matrix as it lies in fast memory: ``d_v`` in whole tiles of 128 lanes."""
    return 4 * d_k * -(-d_v // LANES) * LANES


def step_kernel_heads(heads: int, d_k: int, d_v: int) -> int:
    """Heads of one lane a grid step of the kernel takes, from the shapes
    alone: the largest divisor of ``heads`` whose matrices stay within
    ``STEP_KERNEL_BLOCK_BYTES``, at least one (and at most the 128 whose k
    and q the kernel turns as one tile)."""
    fit = min(max(1, STEP_KERNEL_BLOCK_BYTES // _head_bytes(d_k, d_v)), LANES)
    return max(n for n in range(1, heads + 1) if heads % n == 0 and n <= fit)


def step_kernel_unsupported(state, rows: int) -> Optional[str]:
    """Why the one-step rule's kernel cannot take this call, or None: a static
    predicate on what the call shows. ``state`` is what the layer was handed
    (a ``StatePool`` whose first leaf is the matrices', of arrays or of
    anything with their ``shape`` and ``dtype``; the sliced states of a chunk's
    lane; None for a whole sequence without a cache), ``rows`` the rows a lane
    feeds. The kernel's blocks are whole matrices ``[d_k, d_v]`` of the pool as
    it is stored, so ``d_v`` is always the array's full last dimension (192 of
    Olmo-Hybrid as well as 128), ``d_k`` has to be whole sublane tiles for the
    columns of k and q it is met with, and one head, in and out and
    double-buffered, has to fit a grid step's share of fast memory."""
    if not isinstance(state, StatePool):
        return "no pooled state: a chunk's lane, or a whole sequence without a cache, carries its own"
    if rows != 1:
        return f"{rows} rows a lane: only a decode row takes the one-step form"
    matrix = state.leaves[0]
    if len(matrix.shape) != 5 or jnp.dtype(matrix.dtype) != jnp.dtype(jnp.float32):
        return f"a state of {jnp.dtype(matrix.dtype).name}{list(matrix.shape)}: not [layers, lanes, heads, d_k, d_v] of float32"
    d_k, d_v = matrix.shape[3:]
    if d_k % SUBLANES:
        return f"a key head of {d_k} is no multiple of the {SUBLANES} sublanes of float32"
    if _head_bytes(d_k, d_v) > STEP_KERNEL_BLOCK_BYTES:
        return f"one head's [{d_k}, {d_v}] of float32 is over the {STEP_KERNEL_BLOCK_BYTES >> 20} MiB a grid step holds"
    return None


def gated_delta_step_path(state, rows: int) -> str:
    """``"kernel"`` on a TPU backend for a call the kernel takes
    (``step_kernel_unsupported``), ``"plain"`` everywhere else: what a lane
    pool's decode rows run in ``gated_delta_pooled`` and what the batcher's
    ``linattn_kernel_tokens`` counts (server/span_cache.py ``LanePool.state_step``)
    follow from this alone."""
    return "kernel" if _on_tpu() and step_kernel_unsupported(state, rows) is None else "plain"


def _step_kernel(slot_ref, lane_ref, live_ref, fresh_ref, alpha_ref, beta_ref, q_ref, k_ref, v_ref, s_ref, o_ref, s_out_ref):
    """Grid (place among the live lanes, group of heads). ``lane_ref`` holds
    the live lanes first; every place after the last live one names the last
    live lane's last block again, so that nothing is fetched or written back
    for an idle lane (a block revisited is neither), and the body is skipped.
    ``s_ref`` / ``s_out_ref`` are one block of the SAME pool: a lane's group of
    heads of this layer. A head's matrix is met as it lies, ``[d_k, d_v]``
    with ``d_v`` along the lanes: k and q, which come with ``d_k`` along the
    lanes, are turned once a group so that a head's is a column."""
    place, group = pl.program_id(0), pl.program_id(1)
    n_live, lane = live_ref[0], lane_ref[place]
    heads_a_step, d_k = s_ref.shape[:2]

    @pl.when((n_live == 0) & (place == 0) & (group == 0))
    def _():  # no lane is live: the one block every step names goes back as it came
        s_out_ref[...] = s_ref[...]

    @pl.when(place < n_live)
    def _():
        def columns(ref):  # [heads (padded), d_k (padded)] -> [d_k (padded), 128]: column h is head h's vector
            rows = ref[...]
            return jnp.concatenate([rows] * (LANES // rows.shape[0]), axis=0).T

        q_cols, k_cols = columns(q_ref), columns(k_ref)
        fresh = fresh_ref[lane] != 0
        first = (lane * pl.num_programs(1) + group) * heads_a_step  # this block's first head in ``alpha`` / ``beta``
        for h in range(heads_a_step):
            alpha, beta = alpha_ref[first + h], beta_ref[first + h]
            k, q = k_cols[:d_k, h : h + 1], q_cols[:d_k, h : h + 1]
            state = jnp.where(fresh, 0.0, s_ref[h]) * alpha  # a fresh lane starts from zeros, whatever its slot held
            read = jnp.sum(state * k, axis=0, keepdims=True)
            delta = (v_ref[h : h + 1, :] - read) * beta
            state = state + k * delta
            s_out_ref[h] = state
            o_ref[h : h + 1, :] = jnp.sum(state * q, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("heads_a_step", "interpret"))
def _gated_delta_step_kernel(matrix, slot, q, k, v, g, beta, live, fresh, *, heads_a_step: Optional[int], interpret: bool):
    # under a jit of its own: traced once a process and lowered once a program, not once a run of layers in each of a
    # server's ten step programs (what a kernel costs a server's start is ``setup_s``'s alone to see: PERF.md, PR 43)
    _, lanes, heads, d_k, d_v = matrix.shape
    n = heads_a_step or step_kernel_heads(heads, d_k, d_v)
    groups = heads // n
    n_live = live.sum().astype(jnp.int32)
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)  # the live lanes first
    order = order[jnp.minimum(jnp.arange(lanes), jnp.maximum(n_live - 1, 0))]  # then the last live one again

    def grouped(x, rows, width):  # [lanes, heads, w] -> [lanes, groups, rows, width], zeros around
        x = x.astype(jnp.float32).reshape(lanes, groups, n, x.shape[-1])
        return jnp.pad(x, ((0, 0), (0, 0), (0, rows - n), (0, width - x.shape[-1])))

    # k and q are turned in the kernel as one [128, d_k] tile: their heads padded to a power of two that divides 128
    rows, width = max(SUBLANES, 1 << (n - 1).bit_length()), -(-d_k // LANES) * LANES

    def at(place, group, slot_ref, lane_ref, live_ref, *_):  # (lane, group of heads) of a grid step's blocks
        return lane_ref[place], jnp.where(place < live_ref[0], group, groups - 1)

    def vectors(rows, width):
        return pl.BlockSpec((None, None, rows, width), lambda place, group, *refs: (*at(place, group, *refs), 0, 0))

    matrices = pl.BlockSpec((None, None, n, d_k, d_v), lambda place, group, *refs: (refs[0][0], *at(place, group, *refs), 0, 0))
    # o leaves as [lanes, heads, d_v] where a block of it is whole tiles (or all the heads): what reads it then meets the
    # array it expects, and not a reshape of another (with one group of 32 heads the compiled step relaid the gate's WEIGHT
    # to suit it, every layer: tests/test_kernels_lower_tpu.py)
    whole = n == heads or n % SUBLANES == 0
    o_spec = pl.BlockSpec((None, n, d_v), lambda place, group, *refs: (*at(place, group, *refs), 0)) if whole else vectors(n, d_v)
    out, matrix = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(lanes, groups),
            in_specs=[vectors(rows, width), vectors(rows, width), vectors(n, d_v), matrices],
            out_specs=[o_spec, matrices],
        ),
        out_shape=[jax.ShapeDtypeStruct((lanes, heads, d_v) if whole else (lanes, groups, n, d_v), jnp.float32), jax.ShapeDtypeStruct(matrix.shape, matrix.dtype)],
        input_output_aliases={9: 1},  # the pool, after the six prefetched scalars and q, k, v: written where it lies
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=4 * n * _head_bytes(d_k, d_v) + (8 << 20)
        ),
        interpret=interpret,
        name="gated_delta_step",
    )(
        jnp.asarray(slot, jnp.int32).reshape(1), order, n_live.reshape(1), fresh.astype(jnp.int32),
        jnp.exp(g.astype(jnp.float32)).reshape(-1), beta.astype(jnp.float32).reshape(-1),
        grouped(q, rows, width), grouped(k, rows, width), grouped(v, n, d_v), matrix,
    )
    # no grid step visits an idle lane's block of o: what lies there was never written
    return matrix, jnp.where(live[:, None, None], out.reshape(lanes, heads, d_v), 0.0)


def gated_delta_pooled(state: StatePool, q, k, v, g, beta, *, live, fresh, path: Optional[str] = None, heads_a_step: Optional[int] = None):
    """One position a lane from the matrices where they lie in the state pool
    (``state.leaves[0]``, this layer's at ``state.slot``), for a lane pool's
    step: q, k, v, g and beta as ``gated_delta_step``'s with the lanes as the
    batch, ``live`` / ``fresh`` bool [lanes]. A ``fresh`` lane (a row at
    position 0) starts from zeros whatever its slot held; a lane that is not
    ``live`` (idle) keeps its bytes and its output means nothing (zeros from
    the kernel). Returns (the pool, o [lanes, heads, d_v] float32).

    ``gated_delta_step_path`` says which way from what the call shows: the
    kernel, which moves each live lane's matrices once in and once out and no
    other byte of the pool, or the plain form on this layer's slice of the
    pool, written back whole (every lane's, idle ones among them). ``path``
    names one for a test (off the chip the kernel is interpreted)."""
    live, fresh = jnp.asarray(live), jnp.asarray(fresh)
    if path is None:
        path = gated_delta_step_path(state, 1)
    if path == "kernel":
        with jax.named_scope("ptu.linattn.recurrent"):  # the scope holds the small operands' making too; the kernel is ``gated_delta_step`` in a trace
            matrix, out = _gated_delta_step_kernel(
                state.leaves[0], state.slot, q, k, v, g, beta, live, fresh, heads_a_step=heads_a_step, interpret=_interpret()
            )
        return state._replace(leaves=(matrix, *state.leaves[1:])), out
    held = state.read(0)
    new, out = gated_delta_step(jnp.where(fresh[:, None, None, None], 0.0, held), q, k, v, g, beta)
    return state.write(0, jnp.where(live[:, None, None, None], new, held)), out
