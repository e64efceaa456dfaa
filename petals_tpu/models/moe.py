"""Mixture-of-experts feed-forward shared by the families that route
(mixtral, olmoe, exaone_moe, keye_vl2, deepseek_v3, qwen3_next, smallthinker): a router
over the published number of experts, the top k kept, gated experts (SwiGLU, or
ReGLU where the family says ``activation="relu"``) stacked
``w1``/``w3`` [E, h, m] and ``w2`` [E, m, h]. Routing (``moe_route``) and application (``moe_experts``) are two calls, so
that a block may route on one tensor and feed its experts another; ``moe_apply`` is both on one tensor. The routing rule is data (``Routing``): a softmax whose kept
weights are renormalised or not, a sigmoid whose choice a bias moves and
whose kept weights are renormalised and scaled, or a softmax whose choice a
bias moves and whose kept weights are scaled as they are.

The router may be WIDER than the experts that exist: its last ``identities``
outputs are identity ("zero-compute") experts, whose output is the token
itself. A pick of one is a weight on the token and nothing else: their
weighted sum, ``(sum of the kept weights of identity picks) * x``, is one
fused elementwise op for every token (``ptu.moe.zero``), whatever share of the
experts a server holds (every chip computes it alike, as it would a shared
expert, and it needs no exchange). An identity pick is in no dispatch: no slot
of "hit", no group of "grouped", no weight in the einsum's combine.

A server may hold a SHARE of a layer's experts: ``w1`` stacks the ``E`` it
holds, the router (``gate`` [h, routed]) is as wide as the model publishes,
and ``first`` says which of the routed the first held one is. The layer then
returns, a token, the part of the result its held experts give: an
assignment to an absent expert is dropped, in both dispatches alike (the
chips that hold the rest add theirs; that exchange is not this module's). A
shared expert (``ws1``/``ws3`` [h, m], ``ws2`` [m, h]) runs for every token
and is added whole, or scaled by a sigmoid gate of the token where the
parameters have one (``wsg`` [h, 1]). With every expert held nothing is
dropped and the bits are what they were before a share could be told.

Three dispatches share the routing:

- DENSE: every expert runs over every token (one batched einsum per
  projection) with a top-k one-hot combine: static shapes, zero scatter, and
  the only path under a tp/ep mesh or with quantized experts. It reads every
  held expert's weights, whatever the tokens asked for, and computes E / top_k
  times the FLOPs a token needs: right for a chunk of tokens that reaches
  them all anyway.
- GROUPED (round-3 "sparse" dispatch): assignments are sorted by expert and
  the three projections run as grouped matmuls via ``jax.lax.ragged_dot``
  (static total size N*k, dynamic per-expert group sizes), so FLOPs scale with
  top_k instead of E: the megablocks-style dispatch in XLA's native ragged op.
  Tokens are never dropped (no capacity factor); outputs match the dense
  path's to within accumulation precision (the grouped combine runs in f32
  where the dense combine rounds the routing weights to the compute dtype).
  ``ragged_dot`` is a custom call: inside a step program's layer loop it is
  handed a COPY of the layer's experts, all that are held.
- HIT: for the decode-shaped calls of a step program. The block is handed the
  run's expert weights whole (``ExpertStack``: ``w1`` / ``w3`` [L, E, h, m],
  ``w2`` [L, E, m, h], and which layer of the run it is) and which of its
  rows are live; one Pallas call (ops/expert_hit.py) addresses ``[layer,
  expert]`` itself and reads, tile by tile, the experts the live rows reach
  and no others. Every row rides every hit expert and a combine weight of
  zero does the selecting, so the mathematics is the einsum's, accumulated in
  f32. A dead row (an idle lane of the pool) routes like any other but
  reaches no expert and gets nothing.

``grouped_dispatch`` chooses among them from what a call can observe: its
shape, the weights' type, the mesh and whether a stack was handed over.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from petals_tpu.models.common import mm, silu
from petals_tpu.parallel.tp import COL

# The expert part of what Mixtral and OLMoE declare on their ModelFamily
# (models/registry.py). Under a tp mesh the EXPERT axis is sharded: expert
# parallelism over the mesh (goes beyond the reference, which keeps experts
# unsharded); the router stays whole.
_EXPERT_SPLIT = P(None, COL, None, None)
EXPERT_PSPECS = {"gate": P(), "w1": _EXPERT_SPLIT, "w2": _EXPERT_SPLIT, "w3": _EXPERT_SPLIT}
# the expert stacks carry >90% of Mixtral's params: quantized per expert
# (3-D leaves), where the reference leaves them dense
EXPERT_LEAVES = frozenset({"w1", "w2", "w3"})


class MoeDims(NamedTuple):
    """The static shapes of a block's expert layer (a family's ``moe_dims(cfg)``)."""

    experts: int  # held on this server
    top_k: int
    hidden: int
    expert_width: int
    routed: Optional[int] = None  # the router's width; None: every expert is held
    first: int = 0  # which of the routed experts the first held one is
    identities: int = 0  # of the router's outputs, the last that are identity experts: ``routed - identities`` experts exist

    @property
    def share(self) -> bool:
        """Whether fewer experts are held than exist (the router's identity outputs are no experts)."""
        return self.routed is not None and self.routed - self.identities > self.experts


class Routing(NamedTuple):
    """How a router's logits become the kept experts and their weights."""

    top_k: int
    scoring: str = "softmax"  # or "sigmoid" | "softmax_bias": chosen by score + ``gate_bias``, weighed by score
    renormalize: bool = False  # kept weights divided by their sum
    scale: float = 1.0  # and multiplied by this (a sigmoid router's ``routed_scaling_factor``)


class ExpertStack(NamedTuple):
    """A run's expert weights where they lie, and which of its layers a block
    is: what ``server/backend.py _scan_span`` puts in a block's parameters as
    ``experts`` in place of the layer's own ``w1`` / ``w3`` / ``w2``."""

    w1: jnp.ndarray  # [L, E, h, m]
    w3: jnp.ndarray  # [L, E, h, m]
    w2: jnp.ndarray  # [L, E, m, h]
    layer: jnp.ndarray  # int32 scalar, traced: the loop's counter

    def of_layer(self):
        """``(w1, w3, w2)`` of the block's own layer (a slice that fuses into the dot that reads it)."""
        return self.w1[self.layer], self.w3[self.layer], self.w2[self.layer]


def route(params: dict, x: jnp.ndarray, routing: Routing):
    """``(top_idx, top_weights)`` [b, s, k]: indices among the ROUTED experts."""
    if routing.scoring == "softmax":
        router_logits = x @ params["gate"]  # [b, s, routed]
        probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
        top_probs, top_idx = jax.lax.top_k(probs, routing.top_k)  # [b, s, k]
        if routing.renormalize:
            top_probs = top_probs / top_probs.sum(axis=-1, keepdims=True)
        return top_idx, top_probs
    if routing.scoring not in ("sigmoid", "softmax_bias"):
        raise ValueError(f"unknown routing rule {routing.scoring!r}")
    # the published router runs in float32 (HF DeepseekV3TopkRouter, LongcatFlashTopkRouter)
    logits = jnp.matmul(
        x.astype(jnp.float32), params["gate"].astype(jnp.float32), precision=jax.lax.Precision.HIGHEST
    )
    scores = jax.nn.sigmoid(logits) if routing.scoring == "sigmoid" else jax.nn.softmax(logits, axis=-1)
    _, top_idx = jax.lax.top_k(scores + params["gate_bias"].astype(jnp.float32), routing.top_k)
    top_scores = jnp.take_along_axis(scores, top_idx, axis=-1)  # the bias chooses, it does not weigh
    if routing.renormalize:
        top_scores = top_scores / (top_scores.sum(axis=-1, keepdims=True) + 1e-20)
    return top_idx, top_scores * routing.scale


ACTIVATIONS = {"silu": silu, "relu": jax.nn.relu}  # an expert's gate activation: SwiGLU | ReGLU, data of the family


def _experts_grouped(x, w1, w2, w3, top_idx, top_probs, share: bool = False, activation: str = "silu") -> jnp.ndarray:
    """Grouped-matmul dispatch: FLOPs proportional to N * top_k. ``top_idx``
    counts among the held experts; under a ``share`` an index outside them is
    an assignment to an absent expert: sorted behind every group, in none of
    them, and its rows weigh nothing."""
    b, s, h = x.shape
    E, k = w1.shape[0], top_idx.shape[-1]
    n_assign = b * s * k
    xf = x.reshape(b * s, h)
    flat_experts = top_idx.reshape(n_assign)
    if share:
        held = (flat_experts >= 0) & (flat_experts < E)
        flat_experts = jnp.where(held, flat_experts, E)
    order = jnp.argsort(flat_experts, stable=True)  # group assignments by expert
    token_of = order // k
    xg = jnp.take(xf, token_of, axis=0)  # [N*k, h]
    group_sizes = jnp.bincount(flat_experts, length=E).astype(jnp.int32)
    g1 = jax.lax.ragged_dot(xg, w1, group_sizes)
    g3 = jax.lax.ragged_dot(xg, w3, group_sizes)
    out = jax.lax.ragged_dot(ACTIVATIONS[activation](g1) * g3, w2, group_sizes)  # [N*k, h]
    wts = jnp.take(top_probs.reshape(n_assign), order).astype(jnp.float32)
    contribution = out.astype(jnp.float32) * wts[:, None]
    if share:  # rows past the last group are whatever ragged_dot left there
        contribution = jnp.where(jnp.take(held, order)[:, None], contribution, 0.0)
    y = jnp.zeros((b * s, h), jnp.float32)
    y = y.at[token_of].add(contribution)
    return y.astype(x.dtype).reshape(b, s, h)


def hit_slots(top_idx, top_probs, live, n_experts: int):
    """What the hit dispatch reads, from the rows' choices: ``(slot_expert [S],
    n_hit, combine [S, N])``. ``top_idx`` [N, k] counts among the held experts
    (outside them: absent), ``live`` [N] says which rows count (None: all).
    The ``n_hit`` held experts some live row chose fill the first slots in
    ascending order, the rest of the ``S = min(E, N * k)`` repeat the last of
    them; ``combine`` is the weight of (slot, row), zero where the row did not
    choose the slot's expert, is dead, or the slot is not in use."""
    n_rows, k = top_idx.shape
    n_slots = min(n_experts, n_rows * k)
    chosen = top_idx[..., None] == jnp.arange(n_experts, dtype=top_idx.dtype)  # [N, k, E]; an absent expert: nowhere
    if live is not None:
        chosen = chosen & live[:, None, None]
    weight_of = jnp.where(chosen, top_probs.astype(jnp.float32)[..., None], 0.0).sum(axis=1)  # [N, E]
    hit = chosen.any(axis=(0, 1))  # [E]
    n_hit = hit.sum().astype(jnp.int32)
    by_hit = jnp.argsort(~hit, stable=True)[:n_slots].astype(jnp.int32)  # the hit experts first, ascending
    in_use = jnp.arange(n_slots, dtype=jnp.int32) < n_hit
    slot_expert = jnp.where(in_use, by_hit, by_hit[jnp.maximum(n_hit - 1, 0)])
    combine = jnp.where(in_use[:, None], jnp.take(weight_of, slot_expert, axis=1).T, 0.0)
    return slot_expert, n_hit, combine


def _experts_hit(x, stack: ExpertStack, top_idx, top_probs, live_rows, activation: str = "silu") -> jnp.ndarray:
    """The experts the live rows reach, read out of the stacked run in place."""
    from petals_tpu.ops.expert_hit import hit_experts

    b, s, h = x.shape
    k = top_idx.shape[-1]
    live = None if live_rows is None else jnp.broadcast_to(live_rows[:, None], (b, s)).reshape(b * s)
    slot_expert, n_hit, combine = hit_slots(
        top_idx.reshape(b * s, k), top_probs.reshape(b * s, k), live, stack.w1.shape[1]
    )
    y = hit_experts(
        x.reshape(b * s, h), stack.w1, stack.w3, stack.w2, stack.layer, slot_expert, n_hit, combine, activation=activation
    )
    return y.astype(x.dtype).reshape(b, s, h)


def moe_route(params: dict, x: jnp.ndarray, *, top_k: int, renormalize: bool, scoring: str = "softmax", scale: float = 1.0):
    """The routing half of ``moe_apply``: ``(top_idx, top_weights)`` [b, s, k] from the tensor the router reads, under the
    scope ``ptu.moe.router``. A block whose router reads another tensor than its experts are fed (one that routes on its
    input before its attention) calls this there and hands the pair to ``moe_experts`` later."""
    with jax.named_scope("ptu.moe.router"):
        return route(params, x, Routing(top_k, scoring, renormalize, scale))


def moe_apply(params: dict, x: jnp.ndarray, *, top_k: int, renormalize: bool, dispatch: str = "dense",
              scoring: str = "softmax", scale: float = 1.0, first: int = 0, identities: int = 0, live_rows=None,
              activation: str = "silu") -> jnp.ndarray:
    """x: [batch, seq, hidden] -> what the held experts give each token of the
    mixture of its top-k experts (HF-exact routing), plus the shared expert
    where ``params`` has one. ``renormalize`` divides the kept weights by
    their sum (Mixtral's rule; OLMoE's ``norm_topk_prob`` false keeps the
    softmax mass as it is); ``scoring`` and ``scale`` are ``Routing``'s,
    ``first`` and ``identities`` are ``MoeDims``'. Routing (``moe_route``) and
    application (``moe_experts``) on the one tensor."""
    routed = moe_route(params, x, top_k=top_k, renormalize=renormalize, scoring=scoring, scale=scale)
    return moe_experts(params, x, *routed, dispatch=dispatch, first=first, identities=identities, live_rows=live_rows,
                       activation=activation)


def moe_experts(params: dict, x: jnp.ndarray, top_idx, top_probs, *, dispatch: str = "dense", first: int = 0,
                identities: int = 0, live_rows=None, activation: str = "silu") -> jnp.ndarray:
    """The application half of ``moe_apply``: the experts ``top_idx`` (among the ROUTED, as ``moe_route`` gives them) fed
    ``x`` and weighed by ``top_probs``. ``activation`` is the experts' gate activation (``ACTIVATIONS``: SwiGLU or
    ReGLU), static, the same in all three dispatches.

    ``dispatch`` is ``grouped_dispatch``'s answer (``choose_dispatch`` asks it
    for a block). The expert weights are ``params``' ``w1`` / ``w3`` / ``w2``
    or, from a step program's layer loop, its ``experts`` (an ``ExpertStack``),
    which "hit" needs; ``live_rows`` (bool [batch], None: every row) is read
    by "hit" alone: the other two compute dead rows like any other."""
    from petals_tpu.ops.quant import QuantizedLinear, quant_matmul

    act = ACTIVATIONS[activation]
    stack = params.get("experts")
    n_experts = stack.w1.shape[1] if stack is not None else params["w1"].shape[0]
    share = n_experts != params["gate"].shape[-1]  # a pick may fall outside the held: an absent expert's, or an identity's
    if identities:
        with jax.named_scope("ptu.moe.zero"):
            to_self = jnp.where(top_idx >= params["gate"].shape[-1] - identities, top_probs, 0.0).sum(axis=-1)
            zero = (to_self[..., None] * x.astype(jnp.float32)).astype(x.dtype)
    if share:
        top_idx = top_idx - first  # among the held; outside [0, n_experts): absent, or an identity (beyond every expert)

    def with_rest(y):
        return _add_shared(params, x, y + zero if identities else y)

    if dispatch == "hit":
        with jax.named_scope("ptu.moe.experts.hit"):
            y = _experts_hit(x, stack, top_idx, top_probs, live_rows, activation)
        return with_rest(y)

    w1, w3, w2 = stack.of_layer() if stack is not None else (params["w1"], params["w3"], params["w2"])
    if dispatch == "grouped":
        with jax.named_scope("ptu.moe.experts.grouped"):
            y = _experts_grouped(x, w1, w2, w3, top_idx, top_probs, share, activation)
        return with_rest(y)

    with jax.named_scope("ptu.moe.experts.dense"):
        # combine weights per held expert: [b, s, E] (an index outside them one-hots to nothing)
        one_hot = jax.nn.one_hot(top_idx, n_experts, dtype=top_probs.dtype)
        combine = (one_hot * top_probs[..., None]).sum(axis=2).astype(x.dtype)
        if isinstance(w1, QuantizedLinear):
            # Quantized experts: run each expert through quant_matmul (the fused
            # NF4 kernel on TPU) — dense expert weights are never materialized, so
            # the 4-bit memory budget that sized this span holds at runtime.
            def expert(e):
                def slice_q(q):
                    return QuantizedLinear(q.kind, q.data[e], q.scales[e], q.in_features, q.out_features)

                g = act(quant_matmul(x, slice_q(w1))) * quant_matmul(x, slice_q(w3))
                return quant_matmul(g, slice_q(w2))

            expert_out = jnp.stack([expert(e) for e in range(n_experts)])  # [E, b, s, h]
        else:
            # dense expert compute on stacked weights: w1/w3 [E, h, m], w2 [E, m, h]
            gate_out = jnp.einsum("bsh,ehm->ebsm", x, w1)
            up = jnp.einsum("bsh,ehm->ebsm", x, w3)
            expert_out = jnp.einsum("ebsm,emh->ebsh", act(gate_out) * up, w2)
        y = jnp.einsum("ebsh,bse->bsh", expert_out, combine)
    return with_rest(y)


def _add_shared(params: dict, x: jnp.ndarray, routed: jnp.ndarray) -> jnp.ndarray:
    """The shared expert's SwiGLU over every token, added whole, or, where
    the parameters have a gate for it (``wsg`` [h, 1]), scaled a token by
    ``sigmoid(x wsg)`` (a family without a shared expert: ``routed`` as it
    came)."""
    if "ws1" not in params:
        return routed
    with jax.named_scope("ptu.moe.shared"):
        shared = mm(silu(mm(x, params["ws1"])) * mm(x, params["ws3"]), params["ws2"])
        if "wsg" not in params:
            return routed + shared
    with jax.named_scope("ptu.moe.shared_gate"):
        gate = jax.nn.sigmoid(mm(x, params["wsg"]).astype(jnp.float32))
        return routed + (gate * shared).astype(shared.dtype)


# What the rule below reckons with, measured on one TPU v5e (PERF.md section 6,
# PRs 26 and 31; benchmarks/ablate_moe_dispatch.py, one layer's experts in bf16):
GROUPED_MIN_SEQ = 8  # a call of fewer positions a row is decode-shaped ([lanes, 1, h], a spec verify's k + 1)
FEW_EXPERTS = 8  # up to here ragged_dot's fixed cost a group is a rounding error of a layer (Mixtral: 0.2 ms of 3.7)
GROUP_COST_S = 25e-6  # that fixed cost, three projections: 64 groups of 12.6 MB take 2.6 ms at 64-256 tokens,
# where reading them takes 0.98
HBM_BYTES_PER_S = 819e9
DENSE_FLOPS_PER_S = 150e12  # the all-experts einsums past the weight read: 5.28 us a token at OLMoE's 805 MFLOP
GROUPED_FLOPS_PER_S = 48e12  # ragged_dot's slope from 512 tokens to 1024 at the same shapes: 2.08 us a token


def grouped_dispatch(dims: MoeDims, seq: int, *, stacked: bool = False, quantized: bool = False, mesh: bool = False) -> str:
    """The dispatch a block call of ``seq`` positions a row takes: "dense",
    "grouped" or "hit", from what the call can observe (one choice per
    compiled program): the static shapes, whether the block was handed the
    run's stacked experts and its layer in them (``stacked``), whether the
    expert weights are quantized, whether a tp / ring mesh is about.

    - Quantized experts, or a mesh: the all-experts einsum, the only one that
      runs per-expert ``quant_matmul`` or carries the expert shardings.
    - Under ``GROUPED_MIN_SEQ`` positions (the decode-shaped calls, bound by
      the weight read): "hit" where a stack was handed over, so that the read
      is of the experts the live rows reach (K-EXAONE's 8 lanes reach 6.4 of
      16, OLMoE's 41.6 of 64, two live lanes of Mixtral's 3.5 of 8); without a
      stack the einsum, which reads them all.
    - Few experts, all held (Mixtral's 8): grouped from there on, as before
      PR 26 (Mixtral-8x7B, a layer: 2.3-3.4 ms against the einsum's 3.7 at
      8-64 tokens, 7.2 against 9.0 at 512; worse at 128 and 256, 4.3 and 6.6
      against 3.8 and 4.5, kept as it was: PERF.md section 7).
    - Many small experts (OLMoE's 64 of 12.6 MB): ragged_dot's fixed cost is
      paid once a group whatever the chunk, so the einsum wins (1.15 ms a
      layer against 1.6-2.8 from 32 tokens to 256) until its E / top_k-fold
      FLOPs outgrow that: the two estimates below cross at ~810 tokens
      (measured: 2.70 against 3.13 ms at 512, 5.41 against 4.19 at 1024).
    - A share of the routed experts held (K-EXAONE's 16 of 128, 75.5 MB
      each, top 8: one of a token's eight assignments falls here): the
      estimates, which count what the grouped dispatch costs INSIDE a step
      program. ``ragged_dot`` cannot read a layer's experts where they lie in
      the stacked run, so the loop first copies them out (a read and a write
      of all that are held), and it is handed every assignment's row, those
      of absent experts too. Alone it wins the small chunks it is handed as
      they are (1.15 ms a layer against the einsum's 1.65 at 16 tokens); then
      the einsum, 1.65-1.74 against 2.06, 3.32 and 3.93 at 32, 64 and 128,
      2.02 against 4.38 at 256, 4.59 against 4.94 at 512, 7.48 against 7.38
      at 1024; in a step the copy eats what win there is (PERF.md section 6,
      PR 31). So a share's chunk-shaped calls keep the einsum at every shape
      measured."""
    if quantized or mesh:
        return "dense"
    if seq < GROUPED_MIN_SEQ:
        return "hit" if stacked else "dense"
    share = dims.share
    if dims.experts <= FEW_EXPERTS and not share:
        return "grouped"
    expert_params = 3 * dims.hidden * dims.expert_width
    read_s = dims.experts * 2 * expert_params / HBM_BYTES_PER_S
    dense_s = max(read_s, seq * 2 * dims.experts * expert_params / DENSE_FLOPS_PER_S)
    # of a token's picks, those that are experts': an identity pick is no row of any group
    top_k = dims.top_k * (1 - dims.identities / dims.routed) if dims.identities else dims.top_k
    grouped_s = read_s + dims.experts * GROUP_COST_S + seq * 2 * top_k * expert_params / GROUPED_FLOPS_PER_S
    if share:
        grouped_s += 2 * read_s  # the layer's held experts copied out of the stacked run
    return "grouped" if grouped_s < dense_s else "dense"


def choose_dispatch(params: dict, dims: MoeDims, seq: int, *, mesh: bool) -> str:
    """``grouped_dispatch`` for a block: what its parameters show, asked once."""
    from petals_tpu.ops.quant import QuantizedLinear

    stacked = "experts" in params
    return grouped_dispatch(
        dims, seq, stacked=stacked, quantized=not stacked and isinstance(params["w1"], QuantizedLinear), mesh=mesh
    )
