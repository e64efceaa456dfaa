"""TransformerBackend: the server's compute engine for a span of blocks
(counterpart of reference src/petals/server/backend.py:24-235).

TPU-first redesign:

- The reference wraps each block in a torch module and merges per-block task
  pools so a chain runs in one Runtime call (backend.py:201-235). Here a span's
  parameters are STACKED along a leading layer axis and the whole chain is one
  jitted ``lax.scan`` — one XLA program per step, no per-block dispatch, MXU
  stays hot. (This is also why no CUDA-graph analogue is needed.)
- KV caches are stacked too: [n_blocks, batch, max_len, kv_heads, head_dim]
  buffers live in HBM via MemoryCache handles; decode steps donate them to XLA
  so updates happen in place.
- Variable shapes are bucketed (decode=1 exact; prefill padded to powers of
  two) with the true token count passed as a dynamic scalar — each bucket
  compiles once, then every step is a cached executable
  (reference's recompile-free decode requirement, SURVEY.md §7 hard part 1).
- Beam-search cache reorder (reference backend.py:154-158) is a batch gather
  on the stacked cache, fused into the same step.
- Chunked prefill (reference backend.py:126-152): long inputs are split into
  chunks whose attention-weight footprint fits max_chunk_size_bytes.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import inspect
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from petals_tpu.models.moe import EXPERT_LEAVES, ExpertStack, grouped_dispatch
from petals_tpu.models.registry import ModelFamily, kind_label, span_runs
from petals_tpu.ops import fingerprint as fp_ops
from petals_tpu.ops.sampling import sample_tokens, sampling_vectors
from petals_tpu.server.memory_cache import MemoryCache, TensorDescriptor
from petals_tpu.server.span_cache import SpanCache, cache_kv_heads
from petals_tpu.telemetry.observatory import tracked_jit
from petals_tpu.utils.logging import get_logger

logger = get_logger(__name__)

PREFILL_BUCKETS = (8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096)
# why a span with a recurrent state takes no draft model (``SpanCache.refuse``: here, the batcher, the server)
SPEC_CUTS_BACK = "a rejected draft is rolled back by cutting the cache to the last accepted position, and a state cannot be cut back"


def bucket_length(n: int) -> int:
    for b in PREFILL_BUCKETS:
        if n <= b:
            return b
    return -(-n // PREFILL_BUCKETS[-1]) * PREFILL_BUCKETS[-1]


@dataclasses.dataclass
class SpanDtypes:
    compute: jnp.dtype = jnp.bfloat16
    cache: jnp.dtype = jnp.bfloat16


class TransformerBackend:
    """Serves blocks [first_block, first_block + n_blocks) of one model."""

    def __init__(
        self,
        family: ModelFamily,
        cfg,
        stacked_params,  # pytree with leading n_blocks axis on every leaf; a span of more than one kind
        # of block (ModelFamily.block_kind): a tuple of such trees, one per run of consecutive blocks of one kind
        *,
        first_block: int,
        n_blocks: int,
        memory_cache: MemoryCache,
        compute_dtype=jnp.bfloat16,
        cache_dtype=None,
        max_chunk_size_bytes: int = 256 * 1024 * 1024,
        use_flash: Optional[bool] = None,
        mesh=None,  # jax.sharding.Mesh with a "tp" axis: intra-server tensor parallelism
        kv_quant_type: str = "none",  # paged-pool encoding: none | int8 | nf4a
    ):
        self.family = family
        self.cfg = cfg
        self.params = stacked_params
        self.first_block = first_block
        self.n_blocks = n_blocks
        self.memory_cache = memory_cache
        self.compute_dtype = compute_dtype
        self.cache_dtype = cache_dtype or compute_dtype
        self.max_chunk_size_bytes = max_chunk_size_bytes
        from petals_tpu.ops.paged_attention import KV_QUANT_KINDS

        if kv_quant_type not in KV_QUANT_KINDS:
            raise ValueError(
                f"kv_quant_type must be one of {KV_QUANT_KINDS}, got {kv_quant_type!r}"
            )
        if kv_quant_type != "none" and mesh is not None:
            raise ValueError("kv_quant_type requires a mesh-less server (paged pool only)")
        if kv_quant_type == "nf4a" and cfg.head_dim % 2:
            raise ValueError(f"nf4a KV packing needs an even head_dim, got {cfg.head_dim}")
        self.kv_quant_type = kv_quant_type
        # the span as runs of consecutive blocks of one kind, (kind, start in the span, length): one
        # run of kind None for a family whose blocks are all alike
        self.runs = span_runs(family.span_kinds(cfg, first_block, n_blocks))
        if len(self.runs) > 1:
            self._check_runs(stacked_params, mesh)
        if use_flash is None:
            use_flash = jax.default_backend() == "tpu"
        self.mesh = mesh
        if mesh is not None:
            from petals_tpu.parallel.tp import shard_span_params

            self.params = shard_span_params(self.params, mesh, family.name, cfg)
            # flash stays ON: attend() runs the Pallas kernel per TP head-shard
            # via shard_map (ops/attention.py _attend_sharded) — GSPMD has no
            # partitioning rule for Mosaic custom calls, shard_map sidesteps it
        self.use_flash = use_flash

        self.num_kv_heads = cache_kv_heads(cfg)
        self.head_dim = cfg.head_dim
        # what crosses the wire between two blocks: ``cfg.hidden_size``, or a residual stream of several rows, flat, for
        # a family that declares one (ModelFamily.block_stream), with the times a block mixes it (0: no stream). Every
        # buffer, frame check, probe and fingerprint is sized by ``hidden_size``, none by ``cfg.hidden_size``
        self.hidden_size, self.stream_mixes = family.stream_for(cfg)

        if mesh is None and jax.default_backend() == "tpu":
            from petals_tpu.ops.quant import QuantizedLinear, maybe_autotune_nf4_decode

            has_nf4 = any(
                isinstance(leaf, QuantizedLinear) and leaf.kind == "nf4"
                for leaf in jax.tree_util.tree_leaves(
                    self.params, is_leaf=lambda x: isinstance(x, QuantizedLinear)
                )
            )
            if has_nf4:
                # pick the faster decode path ON THIS DEVICE before the first
                # trace bakes one in (quant.py maybe_autotune_nf4_decode)
                maybe_autotune_nf4_decode(cfg.hidden_size)
        # a family with routed experts (models/moe.py): the expert layer's static shapes (the same in
        # every kind of block that has one)
        dims = [family.moe_dims_for(cfg, kind) for kind, _, _ in self.runs]
        self.moe_dims = next((d for d in dims if d is not None), None)
        # what a lane holds for each block of the span, beside or in place of pages of keys and values: the layout the
        # step programs read at trace time, what is refused for it, its pools, its bytes and its counters
        # (server/span_cache.py, which alone asks the family's hooks and refuses here what is declared and not served)
        self.cache = SpanCache(family, cfg, self.runs, cache_dtype=self.cache_dtype, kv_quant_type=kv_quant_type, mesh=mesh)
        # adapter name -> (stacked {leaf: (A, B)}, scaling); see utils/peft.py
        self.adapters: Dict[str, tuple] = {}
        self._dummy_operands: Dict[tuple, jax.Array] = {}
        # integrity observatory: the last batched step's fused activation
        # fingerprints (ops/fingerprint.py), stashed here by the step
        # wrappers — the public step-method return contracts stay unchanged
        # — and popped by the batcher on its single compute thread
        self._last_step_fp = None  # [n_lanes, FP_DIM] device array or None
        self._last_chunk_fp = None  # [FP_DIM] (mixed step's prefill chunk)

    def moe_grouped(self, seq: int, *, chunk: bool = False) -> Optional[str]:
        """The expert dispatch a block call of ``seq`` tokens a row (a mixed
        step's ``chunk``: padded to its bucket) takes in the lane pool's step
        programs (which give the block no mesh, and the run's stacked experts
        where ``_scan_span`` hands them over): "dense", "grouped" or "hit", by
        the rule the block itself asks; None for a family without experts. The
        batcher counts its tokens by this."""
        if self.moe_dims is None:
            return None
        if chunk:
            seq = bucket_length(seq)
        from petals_tpu.ops.quant import QuantizedLinear

        w1 = next(run["w1"] for run in self._by_run(self.params) if "w1" in run)
        return grouped_dispatch(
            self.moe_dims, seq, stacked=self._stacks_experts(w1), quantized=isinstance(w1, QuantizedLinear),
            mesh=self.mesh is not None,
        )

    # ------------------------------------------------------------- a span as runs of one kind

    def _check_runs(self, params, mesh) -> None:
        """A span of more than one kind of block: what it cannot do yet is
        refused here, by the family's name, not served wrong."""
        from petals_tpu.ops.quant import QuantizedLinear

        name = self.family.name
        if not isinstance(params, (tuple, list)) or len(params) != len(self.runs):
            raise ValueError(
                f"{name}: blocks [{self.first_block}, {self.first_block + self.n_blocks}) are {len(self.runs)} runs of "
                f"one kind ({', '.join(kind_label(k) for k, _, _ in self.runs)}); their parameters come as one stacked tree a run"
            )
        if mesh is not None:
            raise NotImplementedError(f"{name}: a span of more than one kind of block is not served over a tp mesh yet")
        if any(isinstance(leaf, QuantizedLinear)
               for leaf in jax.tree_util.tree_leaves(params, is_leaf=lambda x: isinstance(x, QuantizedLinear))):
            raise NotImplementedError(f"{name}: a span of more than one kind of block is not served quantized yet")

    def refuse_deep_prompts(self, prompts) -> None:
        """Raise for deep prompts over a span whose hidden state is a stream
        wider than the model (ModelFamily.block_stream): a trained prompt is
        a row of ``cfg.hidden_size`` and which rows of the stream it is added
        to is the model's to say, not this server's."""
        if prompts is not None and self.stream_mixes:
            raise NotImplementedError(
                f"{self.family.name}: deep prompts are not served for a span whose hidden state is a stream of "
                f"{self.hidden_size // self.cfg.hidden_size} rows ({self.hidden_size} wide against the model's "
                f"{self.cfg.hidden_size}): a prompt is a row of hidden_size and no published rule says where it enters the stream"
            )

    def _by_run(self, params) -> tuple:
        """The span's parameters as one stacked tree per run."""
        return (params,) if len(self.runs) == 1 else tuple(params)

    def _stacks_experts(self, w1) -> bool:
        """Whether ``_scan_span`` hands a run's expert weights over whole:
        plain arrays, no mesh (models/moe.py's "hit" dispatch reads them where
        they lie; sharded or quantized experts keep the paths they had)."""
        from petals_tpu.ops.quant import QuantizedLinear

        return self.mesh is None and not isinstance(w1, QuantizedLinear)

    def _scan_span(self, params, carry, xs, layer, *, stack_experts: bool = True, pass_kind: bool = False):
        """The layer loop of every program: one ``jax.lax.scan`` a run of
        consecutive blocks of one kind over that run's stacked weights (one
        scan for a family whose blocks are all alike), the carry handed from
        run to run.

        ``layer(block_apply, carry, p_block, x, block_idx) -> (carry, y)``
        runs one block with its kind's ``block_apply``; ``x`` is the block's
        slice of ``xs`` (a pytree of arrays with the span's depth leading) and
        ``block_idx`` counts from the span's first block, across runs.
        Returns ``(carry, ys)``, ``ys`` stacked over the span's depth.

        A run's weights ride as the scan's ``xs``, a layer's slice a trip,
        but for two kinds of leaf that stay whole as scan CONSTS, the block
        getting a view of the stack and its layer's index in it: quantized
        leaves (``_use_quant_consts``), and the expert leaves of a run
        (``_stacks_experts``; ``p_block["experts"]``, a ``moe.ExpertStack``,
        in place of ``w1`` / ``w3`` / ``w2``), whose decode-shaped calls then
        read the experts their rows reach and no others. A run of one block is
        a stack of one. ``stack_experts`` false leaves the experts in ``xs``:
        for the program the backward pass differentiates. ``pass_kind`` hands
        ``layer`` the run's kind as ``kind=``."""
        split = self._use_quant_consts
        outs = []
        for (kind, start, length), run_params in zip(self.runs, self._by_run(params)):
            dense, quant, outliers = self._split_quant(run_params) if split else (run_params, None, None)
            experts = None
            if stack_experts and "w1" in dense and self._stacks_experts(dense["w1"]):
                experts = ExpertStack(dense["w1"], dense["w3"], dense["w2"], layer=None)  # the layer: the body's
                dense = {name: leaf for name, leaf in dense.items() if name not in EXPERT_LEAVES}
            block_apply = self.family.apply_for(kind)
            run_layer = functools.partial(layer, kind=kind) if pass_kind else layer

            def body(c, scanned, block_apply=block_apply, quant=quant, outliers=outliers, experts=experts, start=start,
                     layer=run_layer):
                p_block, x, block_idx = scanned
                if quant is not None:
                    p_block = self._reattach_quant(p_block, quant, outliers, block_idx - start)
                if experts is not None:
                    p_block = {**p_block, "experts": experts._replace(layer=block_idx - start)}
                return layer(block_apply, c, p_block, x, block_idx)

            run_xs = xs if len(self.runs) == 1 else jax.tree_util.tree_map(lambda a: a[start : start + length], xs)
            scope = contextlib.nullcontext() if kind is None else jax.named_scope(f"ptu.span.{kind_label(kind)}")
            with scope:
                carry, y = jax.lax.scan(
                    body, carry, (dense, run_xs, jnp.arange(start, start + length, dtype=jnp.int32))
                )
            outs.append(y)
        ys = outs[0] if len(outs) == 1 else jax.tree_util.tree_map(lambda *a: jnp.concatenate(a), *outs)
        return carry, ys

    def _live_rows(self, positions, max_length) -> dict:
        """``block_apply``'s ``live_rows`` for a lane pool's step, for a
        family whose block takes it: an idle lane rides at the sentinel
        position ``max_length`` and is no row of anyone's."""
        if "live_rows" not in inspect.signature(self.family.block_apply).parameters:
            return {}
        return {"live_rows": positions < max_length}

    # ------------------------------------------------------------- cache descriptors

    def cache_descriptors(self, batch_size: int, max_length: int, start: int, end: int):
        """(k, v) descriptors for blocks [start, end) of this span; under TP the
        kv-head axis is sharded over the mesh (reference backend.py:88-99's
        per-shard descriptors, expressed as one NamedSharding)."""
        self.cache.refuse(
            "a private cache or a dense lane pool",
            "only the paged lane pool carries the state (a session of batch size 1 over the whole span with no "
            "adapter and a max_length within the lanes' length takes a lane)",
        )
        n = end - start
        shape = (n, batch_size, max_length, self.num_kv_heads, self.head_dim)
        sharding = None
        if self.mesh is not None:
            from jax.sharding import NamedSharding

            from petals_tpu.parallel.tp import kv_cache_pspec

            sharding = NamedSharding(self.mesh, kv_cache_pspec())
        return (
            TensorDescriptor(shape, self.cache_dtype, sharding),
            TensorDescriptor(shape, self.cache_dtype, sharding),
        )

    def pool_to_wire(self, pages):
        """Pages taken out of the stacked pool (``[n_blocks, n_slots,
        page_size, *row]``: a plain array or a ``PagedPool``, jax or numpy),
        as everything outside the device holds them: rows of ``[hkv,
        d_store]``. Reads the form off the leaf; a reshape of the host's copy
        where it is done on one: free."""
        from petals_tpu.ops.paged_attention import PagedPool, unfold_rows

        if isinstance(pages, PagedPool):
            return PagedPool(self.pool_to_wire(pages.codes), pages.scales)
        return unfold_rows(pages, self.num_kv_heads) if pages.ndim == 4 else pages

    @staticmethod
    def wire_to_pool(pages, pool):
        """``pool_to_wire``'s inverse: rows of ``[hkv, d_store]`` folded to
        the row that the stacked ``pool`` stores."""
        from petals_tpu.ops.paged_attention import PagedPool, fold_rows

        if isinstance(pages, PagedPool):
            return PagedPool(fold_rows(pages.codes, pool.codes.shape[3:]), pages.scales)
        return fold_rows(pages, pool.shape[3:])

    # ------------------------------------------------------------- jitted programs

    def _quant_ctx(self):
        """Under a TP mesh, trace quantized matmuls via the XLA dequant path
        (Mosaic kernels cannot be GSPMD-partitioned). No-op otherwise."""
        if self.mesh is not None:
            from petals_tpu.ops.quant import force_xla_quant_matmul

            return force_xla_quant_matmul()
        return contextlib.nullcontext()

    def _slice_params(self, start: int, end: int):
        if start == 0 and end == self.n_blocks:
            return self.params
        if len(self.runs) > 1:
            raise NotImplementedError(f"{self.family.name}: a span of more than one kind of block is served whole")
        return jax.tree_util.tree_map(lambda x: x[start:end], self.params)

    def params_for(self, active_adapter: Optional[str]):
        """Span params with the requested LoRA adapter applied (reference
        peft.py:132-170's per-request adapter selection, as a pytree arg)."""
        if not active_adapter:
            return self.params
        if active_adapter not in self.adapters:
            raise KeyError(f"Adapter {active_adapter!r} is not loaded on this server")
        from petals_tpu.utils.peft import apply_adapter

        stacked_adapter, scaling = self.adapters[active_adapter]
        return apply_adapter(self.params, stacked_adapter, scaling)

    @functools.cached_property
    def _use_quant_consts(self):
        """Quantized leaves must NOT ride the scan xs: XLA materializes each
        iteration's slice of the packed uint8 bytes at a fraction of kernel
        DMA rate, which dominated quantized decode. Instead they stay whole
        as scan CONSTS and the body hands block_apply a StackedQuantLinear
        view (stacked bytes + the loop counter); the Pallas kernel then
        DMAs its tiles straight out of the stacked array. Off under TP —
        that path traces the XLA dequant matmul, which fuses its slices."""
        from petals_tpu.ops.quant import QuantizedLinear

        return self.mesh is None and any(
            isinstance(leaf, QuantizedLinear)
            for leaf in jax.tree_util.tree_leaves(
                self.params, is_leaf=lambda x: isinstance(x, QuantizedLinear)
            )
        )

    @staticmethod
    def _split_quant(params):
        """Partition span params into (dense-for-scan-xs, quant-for-consts,
        outlier-leaf names). Only span-stacked 2-D weights ([n_blocks, in//2,
        out]) take the consts path; mixtral's stacked EXPERT leaves are 4-D
        and their block code slices experts itself — leave them in the scan
        xs. Outlier-augmented leaves split: the packed inner rides the consts
        path (kernel DMAs from the stacked bytes), the tiny idx/w_out side
        arrays ride the scan xs and are re-attached in the body."""
        from petals_tpu.ops.quant import OutlierQuantLinear, QuantizedLinear

        is_q = lambda x: isinstance(x, QuantizedLinear) and x.data.ndim == 3
        dense, quant, outlier_names = {}, {}, set()
        for k, v in params.items():
            if isinstance(v, OutlierQuantLinear) and v.inner.data.ndim == 3:
                quant[k] = v.inner
                outlier_names.add(k)
                dense[k + "__oidx"] = v.idx  # [n_blocks, k]
                dense[k + "__ow"] = v.w_out  # [n_blocks, k, out]
            elif is_q(v):
                quant[k] = v
            else:
                dense[k] = v
        return dense, quant, outlier_names

    @staticmethod
    def _reattach_quant(p_block: dict, quant_params: dict, outlier_names, block_idx):
        """Rebuild this block's quantized leaves inside a scan body: each
        consts-path weight becomes a StackedQuantLinear view at ``block_idx``,
        with outlier side arrays (threaded through the scan xs by
        _split_quant) re-attached. Shared by the session and lane-pool step
        programs so the re-attach protocol cannot drift between them."""
        from petals_tpu.ops.quant import OutlierQuantLinear, StackedQuantLinear

        p_block = dict(p_block)
        for name, q in quant_params.items():
            sq = StackedQuantLinear(
                q.kind, q.data, q.scales, block_idx, q.in_features, q.out_features
            )
            if name in outlier_names:
                sq = OutlierQuantLinear(
                    sq, p_block.pop(name + "__oidx"), p_block.pop(name + "__ow")
                )
            p_block[name] = sq
        return p_block

    @functools.cached_property
    def _inference_step_fn(self):
        family, cfg, use_flash = self.family, self.cfg, self.use_flash
        tp_mesh = self.mesh
        scan_span = self._scan_span
        # sequence parallelism for KV-cached PREFILL (round-3, VERDICT weak
        # #5): chunks with seq > 1 divisible by sp shard queries over the "sp"
        # axis (attention against the replicated cache via ops/attention._attend_sharded);
        # decode steps (seq == 1) stay tp-only
        sp_size = self.mesh.shape.get("sp", 1) if self.mesh is not None else 1
        supports_sp = family.supports_ring_attention and sp_size > 1
        # longrope (phi3) selects rotary factors from the FINAL sequence
        # length; only families whose block accepts it get the extra operand
        takes_n_total = "n_total" in inspect.signature(family.block_apply).parameters

        @tracked_jit(
            name="inference_step",
            static_argnames=("with_prompts", "with_hypo", "padded"),
            donate_argnums=(1, 2),
        )
        def step(params, k_stack, v_stack, hidden, position, n_valid, n_total,
                 prompts, hypo_ids,
                 *, with_prompts: bool, with_hypo: bool, padded: bool):
            hidden = hidden.astype(k_stack.dtype)
            use_sp = supports_sp and hidden.shape[1] > 1 and hidden.shape[1] % sp_size == 0
            if use_sp:
                from jax.sharding import NamedSharding, PartitionSpec as P

                hidden = jax.lax.with_sharding_constraint(
                    hidden, NamedSharding(tp_mesh, P(None, "sp", None))
                )
            if with_hypo:
                # beam search: reorder per-sequence cache lanes in place
                k_stack = jnp.take(k_stack, hypo_ids, axis=1)
                v_stack = jnp.take(v_stack, hypo_ids, axis=1)

            if with_prompts:
                # deep prompts cover absolute positions [0, pre_seq): add the
                # overlap with this chunk [position, position + seq)
                pre_seq = prompts.shape[2]
                seq = hidden.shape[1]
                pos_in_chunk = position + jnp.arange(seq, dtype=jnp.int32)
                prompt_mask = (pos_in_chunk < pre_seq)[None, :, None]

            def layer(block_apply, h, p_block, x, block_idx):
                k_block, v_block, prompt = x
                if with_prompts:
                    seq = h.shape[1]
                    pre = prompt.shape[1]
                    # gather the prompt rows aligned with this chunk's positions
                    idx = jnp.clip(position + jnp.arange(seq, dtype=jnp.int32), 0, pre - 1)
                    aligned = jnp.take(prompt, idx, axis=1)
                    h = h + jnp.where(prompt_mask, aligned, 0).astype(h.dtype)
                extra = (
                    {"ring_mesh": tp_mesh if use_sp else None}
                    if family.supports_ring_attention
                    else {}
                )
                if takes_n_total:
                    extra["n_total"] = n_total
                out, (k_new, v_new) = block_apply(
                    p_block, h, (k_block, v_block), position, cfg,
                    use_flash=use_flash, n_valid=n_valid if padded else None,
                    tp_mesh=tp_mesh, **extra,
                )
                return out, (k_new, v_new)

            hidden, (k_stack, v_stack) = scan_span(params, hidden, (k_stack, v_stack, prompts), layer)
            return hidden, k_stack, v_stack

        return step

    @functools.cached_property
    def _batched_decode_fn(self):
        """One decode step for MANY independent sessions at once — the
        continuous-batching hot path (beats the reference, whose task pools
        explicitly never batch across requests: reference task_pool.py:35-36).

        The whole lane pool rides every step with a per-lane position vector:
        lanes without a request this step carry the out-of-range sentinel
        (pool length), so their KV writes drop (scatter mode="drop") and
        their outputs are ignored. One shape -> ONE compiled program, no
        recompilation as sessions join and leave mid-flight; decode is
        weight-bandwidth-bound, so the extra lanes are nearly free.

        Under a TP mesh (incl. multi-host lockstep) the batched step shards
        like the single-session step: params carry their PartitionSpecs, the
        pool's kv-head axis is sharded, and block_apply inserts the psum —
        decode steps are seq==1, so no sp handling is needed here."""
        cfg = self.cfg
        fp_proj = fp_ops.projection(self.hidden_size)  # baked constant

        cache_dtype = jnp.dtype(self.cache_dtype)
        self.cache.refuse("the dense lane pool", "it has no place for the state: serve with page_size > 0")

        @tracked_jit(
            name="batched_decode", steady=True,
            static_argnames=("with_fp",), donate_argnums=(1, 2),
        )
        def step(params, k_pool, v_pool, hidden, positions, *, with_fp: bool):
            # hidden: [n_lanes, 1, hidden]; positions: [n_lanes] int32
            hidden = hidden.astype(cache_dtype)
            hidden, (k_pool, v_pool) = self._scan_span(
                params, hidden, (k_pool, v_pool), self._dense_lanes_layer(positions)
            )
            if with_fp:
                # fused integrity fingerprint: one [n_lanes, hidden] x
                # [hidden, FP_DIM] matmul on the post-span hidden state —
                # the digest the client re-derives from its reply
                fp = fp_ops.fingerprint_rows(hidden[:, -1, :], fp_proj)
                return hidden, k_pool, v_pool, fp
            return hidden, k_pool, v_pool

        return step

    def _dense_lanes_layer(self, positions):
        """``_scan_span``'s ``layer`` for a step over the dense lane pool:
        every lane feeds one row at its own position."""
        cfg, tp_mesh = self.cfg, self.mesh

        def layer(block_apply, h, p_block, x, block_idx):
            live = self._live_rows(positions, x[0].shape[1])  # a layer's k: [n_lanes, max_len, hkv, d]
            return block_apply(p_block, h, x, positions, cfg, use_flash=False, tp_mesh=tp_mesh, **live)

        return layer

    def batched_decode_step(self, hidden, pool_kv, positions, handles=None):
        """One coalesced decode step over the whole lane pool.

        Args:
          hidden: [n_lanes, 1, hidden] (idle lanes: any finite filler).
          pool_kv: (k, v) pool buffers [n_blocks, n_lanes, max_len, hkv, d].
          positions: int32 [n_lanes]; idle lanes hold max_len (the sentinel).
          handles: ignored here; the lockstep wrapper uses the pool's mirror
            handle to address the workers' copy (parallel/multihost.py).
        """
        k_pool, v_pool = pool_kv
        if not isinstance(hidden, jax.Array):
            hidden = np.ascontiguousarray(hidden)
        with_fp = fp_ops.enabled()
        with self._quant_ctx():  # mesh: XLA dequant path (Mosaic can't GSPMD)
            res = self._batched_decode_fn(
                self.params, k_pool, v_pool, hidden,
                np.asarray(positions, np.int32), with_fp=with_fp,
            )
        if with_fp:
            out, k_pool, v_pool, self._last_step_fp = res
        else:
            out, k_pool, v_pool = res
            self._last_step_fp = None
        return out, (k_pool, v_pool)

    def _scan_paged_span(self, params, k_pool, v_pool, carry, layer, state=(), state_layer=None):
        """The layer loop of every paged step program: ``_scan_span`` over
        the span's blocks with the page pools in the loop's CARRY, updated in
        place, and only the stacked weights (and the layer index) as ``xs``.
        A span of more than one kind of block is one loop a run over the same
        carried pools, the layer index (and so a layer's first page)
        continuing across runs.

        A scan's ``ys`` is a buffer of its own: pools that ride as ``xs`` and
        come back as ``ys`` are sliced out, copied and written whole into a
        second pool layer by layer, and that pool is copied over the donated
        one after the loop, all to land a lane's one new row. So the pools
        are flattened to ``[n_blocks * n_pages, page_size, *pool_row]`` (a
        bitcast; a quantized ``PagedPool`` leaf by leaf) and a layer reaches
        its pages through block tables shifted by ``layer * n_pages`` (holes
        stay -1): ``PagedKV``'s scatter and gather work on the carried pool
        as they would on one layer's, the same rows land at the same places,
        the drop sentinel is one past the end of the flat pool, and the
        donated buffers alias the outputs.

        ``layer(block_apply, carry, p_block, spans, paged) -> (carry,
        spans)`` runs one block with its kind's ``block_apply``: ``spans``
        are the carried pools, keys' and values' (and the index rows' for a
        span that caches one), ``paged(spans, tables)`` wraps them and a set
        of block tables as that block's ``PagedKV``s (for a block of more
        than one attention sub-layer, ``cache.block_rows``: one tuple of them a
        sub-layer, each shifted to its own layer of pages, the block's
        ``block_rows`` layers lying one after the other in the pools), and
        the pools ``block_apply`` hands back go on to the next layer. Returns
        ``(carry, k_pool, v_pool, state)``, the pools in their stacked shape.

        A span with a recurrent state carries its STATE pool (``state``: one
        array a leaf, ``[state layers, n_lanes, ...]``) through the same loop
        beside the pages, donated and written in place as they are. The page
        pools are then only as deep as the blocks that keep keys and values,
        and a block's layer in its pool is its place among its own sort
        (``cache.slots``), not its index in the span. A block of a kind that
        declares a state runs ``state_layer(block_apply, carry, p_block,
        mine) -> (carry, mine)`` on the state pool where it lies, ``mine``
        the pool's leaves whole and the block's slot in them
        (ops/linear_attention.py ``StatePool``, a stand-in as ``PagedKV`` is
        for pages, and not a sliced copy: a copy out and a write back are two
        passes over every lane's state), and touches no page.

        A span whose positions cache an index row (``cache.index_row``) hands its
        INDEX pool in as ``state``'s one leaf, ``[kv layers, n_pages,
        page_size, width]``: a third page pool, flattened and carried as the
        other two and reached through the same shifted tables, which comes
        back in ``state``'s place."""
        from petals_tpu.ops.linear_attention import StatePool
        from petals_tpu.ops.paged_attention import PagedKV

        if self.cache.grouped and len(state) == 2 * (len(self.cache.page_groups) - 1):
            # handed a pair of pools a page group (the others' where a state pool would ride) and tables a group
            carry, (k_pool, v_pool, *others) = self._scan_grouped_span(params, (k_pool, v_pool, *state), carry, layer)
            return carry, k_pool, v_pool, tuple(others)
        depth, n_pages, rows = k_pool.shape[0], k_pool.shape[1], self.cache.block_rows
        by_sort = bool(self.cache.state_layers)
        indexed = self.cache.index_row is not None

        def merged(pool):  # [depth, n_pages, ...] -> [depth * n_pages, ...]
            return jax.tree_util.tree_map(
                lambda a: a.reshape(depth * n_pages, *a.shape[2:]), pool
            )

        def stacked(pool):
            return jax.tree_util.tree_map(
                lambda a: a.reshape(depth, n_pages, *a.shape[1:]), pool
            )

        def one(block_apply, scanned, p_block, slot, block_idx, kind=None):
            inner, spans, state = scanned
            if by_sort and kind in self.cache.state_kinds:
                inner, mine = state_layer(block_apply, inner, p_block, StatePool(state, slot))
                return (inner, spans, tuple(mine.leaves)), None
            first_page = (slot if by_sort else block_idx) * (rows * n_pages)

            def paged(spans, tables, sub=None):
                if sub is None and rows > 1:  # one ``kv`` a sub-layer, each over its own layer of pages
                    return tuple(paged(spans, tables, sub) for sub in range(rows))
                own = (first_page + (sub or 0) * n_pages, n_pages)
                shifted = jnp.where(tables >= 0, tables + own[0], -1)
                return tuple(PagedKV(span, shifted, own) for span in spans)

            return (*layer(block_apply, inner, p_block, spans, paged), state), None

        spans = (k_pool, v_pool, *state) if indexed else (k_pool, v_pool)
        (carry, spans, state), _ = self._scan_span(
            params, (carry, tuple(merged(pool) for pool in spans), () if indexed else tuple(state)),
            jnp.asarray(self.cache.slots, jnp.int32) if by_sort else (), one, pass_kind=by_sort,
        )
        k_pool, v_pool, *index = (stacked(span) for span in spans)
        return carry, k_pool, v_pool, tuple(index) if indexed else state

    def _scan_grouped_span(self, params, pools, carry, layer):
        """``_scan_paged_span`` for a span whose layers keep pages in GROUPS by static window (``cache.page_groups``): ``pools``
        is a pair (k, v) a group, ``[the group's layers, the group's pages, page_size, *pool_row]``, and the tables the
        ``layer`` closes over are ``[groups, n_lanes, max_pages]`` (a chunk lane's row ``[groups, 1, max_pages]``). Every
        group's pools are flattened and carried through every run's loop as the one pool is, written in place; a block
        reaches its own group's through that group's tables shifted by its layer in that pool (``cache.group_slots``), and
        the other groups' pass through its trip untouched. Returns ``(carry, pools)``, stacked again."""
        from petals_tpu.ops.paged_attention import PagedKV

        groups = self.cache.page_groups
        shapes = [pools[2 * g].shape[:2] for g in range(len(groups))]  # (layers, pages) a group
        merged = tuple(
            tuple(a.reshape(depth * n_pages, *a.shape[2:]) for a in pools[2 * g : 2 * g + 2]) for g, (depth, n_pages) in enumerate(shapes)
        )
        group_of = {kind: self.cache.group_slots[start][0] for kind, start, _ in self.runs}  # a kind has one window

        def one(block_apply, scanned, p_block, slot, block_idx, kind=None):
            inner, spans = scanned
            g = group_of[kind]
            own = (slot * shapes[g][1], shapes[g][1])

            def paged(mine, tables):
                tables = tables[g]
                return tuple(PagedKV(span, jnp.where(tables >= 0, tables + own[0], -1), own) for span in mine)

            inner, mine = layer(block_apply, inner, p_block, spans[g], paged)
            return (inner, (*spans[:g], tuple(mine), *spans[g + 1 :])), None

        slots = jnp.asarray([slot for _, slot in self.cache.group_slots], jnp.int32)
        (carry, spans), _ = self._scan_span(params, (carry, merged), slots, one, pass_kind=True)
        stacked = tuple(a.reshape(depth, n_pages, *a.shape[1:]) for pair, (depth, n_pages) in zip(spans, shapes) for a in pair)
        return carry, stacked

    def _paged_lanes_layer(self, tables, positions):
        """``_scan_paged_span``'s ``layer`` for a step in which every lane
        feeds rows at its own position (decode, server-side generation,
        speculative verify): one ``block_apply`` over the lanes' tables."""
        cfg = self.cfg

        def layer(block_apply, h, p_block, spans, paged):
            live = self._live_rows(positions, tables.shape[-1] * spans[0].shape[1])  # max_pages * page_size
            out, new_kv = block_apply(
                p_block, h, paged(spans, tables), positions, cfg,
                use_flash=False, tp_mesh=None, **live,
            )
            return out, self._pools_of(new_kv)

        return layer

    @staticmethod
    def _pools_of(new_kv: tuple) -> tuple:
        """The carried pools out of what a block returned as its ``kv``: its
        ``PagedKV``s' pools, the LAST sub-layer's for a block of more than
        one (``block_rows``: each wrote the pools the one before handed on)."""
        from petals_tpu.ops.paged_attention import PagedKV

        return tuple(kv.pool for kv in (new_kv if isinstance(new_kv[0], PagedKV) else new_kv[-1]))

    def _state_lanes_layer(self, positions, max_length):
        """``_scan_paged_span``'s ``state_layer`` for a step in which every
        lane feeds one row at its own position: one ``block_apply`` over the
        lanes' states where they lie in the pool. An idle lane (``positions``
        at ``max_length``) is no live row, and its state stays as it was."""
        cfg = self.cfg

        def layer(block_apply, h, p_block, mine):
            return block_apply(p_block, h, mine, positions, cfg, use_flash=False, tp_mesh=None, live_rows=positions < max_length)

        return layer

    @staticmethod
    def _with_state(results: tuple, state: tuple) -> tuple:
        """A step program's results, its state pool last where it has one."""
        return (*results, state) if state else results

    @staticmethod
    def _split_state(results: tuple, state: tuple) -> Tuple[tuple, tuple]:
        return (results[:-1], tuple(results[-1])) if state else (results, ())

    def pack_lanes(self, hidden, positions):
        """A lane pool step's ``hidden`` [n_lanes, 1, hidden] and ``positions``
        [n_lanes] as the ONE array its program takes, so that a step is one
        copy to the device: ``[n_lanes, hidden + 1]`` int32, a lane's float32
        row bit for bit (a view, nothing converted) and its position in the
        last column. An integer carrier because a position read as float32
        bits is a denormal, which a TPU may flush wherever arithmetic touches
        it; integers pass through copies and slices as they are. An array
        that already has that form (the batcher's reused buffer) is handed
        back as it is, ``positions`` being its last column."""
        if not isinstance(hidden, jax.Array):
            hidden = np.asarray(hidden)
        n, hsz = len(hidden), self.hidden_size
        if hidden.ndim == 2 and hidden.dtype == np.int32 and hidden.shape[1] == hsz + 1:
            return hidden
        if isinstance(hidden, jax.Array):  # rows already on the device (tests): packed there
            rows = jax.lax.bitcast_convert_type(hidden.astype(jnp.float32).reshape(n, hsz), jnp.int32)
            return jnp.concatenate([rows, jnp.asarray(positions, jnp.int32)[:, None]], axis=1)
        packed = np.empty((n, hsz + 1), np.int32)
        packed[:, :hsz].view(np.float32)[...] = np.asarray(hidden, np.float32).reshape(n, hsz)
        packed[:, hsz] = positions
        return packed

    @staticmethod
    def _unpack_lanes(lanes, dtype):
        """``pack_lanes`` undone inside a step program: ``(hidden [n_lanes, 1,
        hidden] in ``dtype``, positions [n_lanes] int32)``; the bit-cast back
        to float32 is exact."""
        hidden = jax.lax.bitcast_convert_type(lanes[:, :-1], jnp.float32)
        return hidden[:, None, :].astype(dtype), lanes[:, -1]

    @staticmethod
    def device_tables(tables: np.ndarray) -> jax.Array:
        """A snapshot of the block tables on the device, which a caller keeps
        and hands to the paged step programs for as long as no entry changes
        (the batcher's ``_step_tables``); never donated."""
        return jax.device_put(np.array(tables, np.int32))  # np.array copies: what the device reads is nobody's to write

    @staticmethod
    def _as_tables(tables):
        """The tables a paged step program is handed: a device array as it is
        (nothing pulls it back to the host), anything else as int32."""
        return tables if isinstance(tables, jax.Array) else np.asarray(tables, np.int32)

    @functools.cached_property
    def _paged_decode_fn(self):
        """Paged twin of ``_batched_decode_fn``: the pool is page-granular
        ([n_blocks, n_pages, page_size, hkv, d]) and the (pool, block-table)
        pair rides through the model family's block code as a ``PagedKV``
        stand-in for the dense buffer — ``update_kv_cache`` scatters the new
        token rows straight into the pages and ``attend`` dispatches to the
        decode walk over the lanes' pages (ops/paged_flash_attention.py
        ``paged_attend_dispatch``). ONE attention code path: dense is just the
        identity block table, with no host-side contiguity special case.

        The pool a block sees is the whole span's and its tables are shifted
        by the layer: the stacked pools are the layer loop's carry, written
        in place (``_scan_paged_span``), and come back in the donated
        buffers."""
        cfg = self.cfg
        fp_proj = fp_ops.projection(self.hidden_size)  # baked constant

        cache_dtype = jnp.dtype(self.cache_dtype)

        @tracked_jit(
            name="paged_decode", steady=True,
            static_argnames=("with_fp",), donate_argnums=(1, 2, 5),
        )
        def step(params, k_pool, v_pool, lanes, tables, state=(),
                 *, with_fp: bool):
            # lanes: [n_lanes, hidden + 1] int32, the lanes' rows and positions
            # as ``pack_lanes`` lays them out (one copy in a step);
            # tables: [n_lanes, max_pages] int32 (-1 = unallocated slot);
            # state: the state pool's leaves, none for a span without one
            hidden, positions = self._unpack_lanes(lanes, cache_dtype)
            hidden, k_pool, v_pool, state = self._scan_paged_span(
                params, k_pool, v_pool, hidden,
                self._paged_lanes_layer(tables, positions),
                state, self._state_lanes_layer(positions, tables.shape[-1] * k_pool.shape[2]),
            )
            if with_fp:
                # same projection as the dense program: path-invariance —
                # identical tokens through dense vs paged yield identical
                # digests (the PR 2/3 bit-exactness contract, observable)
                fp = fp_ops.fingerprint_rows(hidden[:, -1, :], fp_proj)
                return self._with_state((hidden, k_pool, v_pool, fp), state)
            return self._with_state((hidden, k_pool, v_pool), state)

        return step

    def paged_decode_step(self, hidden, pool_kv, positions, tables,
                          handles=None):
        """One coalesced decode step over the whole lane pool, PAGED layout.

        Args:
          hidden: [n_lanes, 1, hidden] (idle lanes: any finite filler), or
            the lanes' rows and positions already in ``pack_lanes``' form
            (the batcher's reused buffer); either way the program is handed
            that one array, so the two ways in run the same compiled step.
          pool_kv: (k, v) page pools [kv layers, n_pages, page_size, hkv, d],
            then the state pool's leaves for a span that keeps one; they
            come back in the same order.
          positions: int32 [n_lanes]; idle sentinel = max_pages * page_size.
          tables: int32 [n_lanes, max_pages] block tables (-1 unallocated),
            on the host or already on the device (``device_tables``).
        """
        k_pool, v_pool, *state = pool_kv
        with_fp = fp_ops.enabled()
        with self._quant_ctx():
            res = self._paged_decode_fn(
                self.params, k_pool, v_pool, self.pack_lanes(hidden, positions),
                self._as_tables(tables), tuple(state), with_fp=with_fp,
            )
        res, state = self._split_state(res, state)
        if with_fp:
            out, k_pool, v_pool, self._last_step_fp = res
        else:
            out, k_pool, v_pool = res
            self._last_step_fp = None
        return out, (k_pool, v_pool, *state)

    @functools.cached_property
    def _paged_gen_decode_fn(self):
        """Paged twin of ``_batched_gen_decode_fn``: the pooled server-gen
        step (client leaves in the loop) over the page-granular pool. Same
        PagedKV single attention path and the same layer loop as
        ``_paged_decode_fn``: the span's pool carried and written in place,
        each block's tables shifted by its layer (``_scan_paged_span``)."""
        family, cfg = self.family, self.cfg
        client_embed, client_head = family.client_embed, family.client_head
        fp_proj = fp_ops.projection(self.hidden_size)  # baked constant

        cache_dtype = jnp.dtype(self.cache_dtype)

        @tracked_jit(
            name="paged_gen_decode", steady=True,
            static_argnames=("with_fp",), donate_argnums=(2, 3, 17),
        )
        def step(params, client_params, k_pool, v_pool, hidden, tokens,
                 use_token, positions, do_sample, temperature, top_k, top_p,
                 rep_penalty, seeds, draw_idx, seen_mask, tables, state=(),
                 *, with_fp: bool):
            emb = client_embed(client_params, tokens[:, None], cfg)
            hidden = jnp.where(
                use_token[:, None, None],
                emb.astype(cache_dtype),
                hidden.astype(cache_dtype),
            )
            hidden, k_pool, v_pool, state = self._scan_paged_span(
                params, k_pool, v_pool, hidden,
                self._paged_lanes_layer(tables, positions),
                state, self._state_lanes_layer(positions, tables.shape[-1] * k_pool.shape[2]),
            )
            logits = client_head(client_params, hidden, cfg)[:, -1, :]
            next_tok = sample_tokens(
                logits, do_sample=do_sample, temperature=temperature,
                top_k=top_k, top_p=top_p, repetition_penalty=rep_penalty,
                seen_mask=seen_mask, seeds=seeds, draw_idx=draw_idx,
            )
            if with_fp:
                fp = fp_ops.fingerprint_rows(hidden[:, -1, :], fp_proj)
                return self._with_state((hidden, next_tok, k_pool, v_pool, fp), state)
            return self._with_state((hidden, next_tok, k_pool, v_pool), state)

        return step

    def paged_gen_decode_step(self, client_params, hidden, tokens, use_token,
                              pool_kv, positions, tables, *, sampling_vecs,
                              handles=None):
        """Paged twin of ``batched_gen_decode_step`` (same argument contract
        plus the block tables)."""
        k_pool, v_pool, *state = pool_kv
        tables = self._as_tables(tables)
        if not isinstance(hidden, jax.Array):
            hidden = np.ascontiguousarray(hidden)
        v = sampling_vecs
        with_fp = fp_ops.enabled()
        with self._quant_ctx():
            res = self._paged_gen_decode_fn(
                self.params, client_params, k_pool, v_pool, hidden,
                np.asarray(tokens, np.int32), np.asarray(use_token, bool),
                np.asarray(positions, np.int32), v["do_sample"],
                v["temperature"], v["top_k"], v["top_p"],
                v["repetition_penalty"], v["seeds"], v["draw_idx"],
                v["seen_mask"], tables, tuple(state), with_fp=with_fp,
            )
        res, state = self._split_state(res, state)
        if with_fp:
            out, toks, k_pool, v_pool, self._last_step_fp = res
        else:
            out, toks, k_pool, v_pool = res
            self._last_step_fp = None
        return out, toks, (k_pool, v_pool, *state)

    @functools.cached_property
    def _paged_spec_verify_fn(self):
        """Speculative-decode verify step: every speculating lane feeds its
        last committed token plus k draft tokens ([n_lanes, k+1] rows) through
        the span in ONE program — verification IS chunked prefill into the
        lane's pages (scatter_lane_chunk_rows writes all k+1 candidate KV rows
        per lane; attend masks per-row causally with vector q_offset).

        Acceptance is deterministic-stream: row j's logits are sampled with
        the lane's OWN seed+offset contract (draw_idx + j) to produce the
        target's token ĝ_{j+1} — exactly the token plain decode would have
        produced at that draw, conditioned on the fed prefix. A draft token
        d_j is accepted iff it equals ĝ_j AND every earlier draft matched
        (cumprod of the match vector); the lane emits m = min(a + 1, k + 1)
        tokens ĝ_1..ĝ_m, so the emitted stream is BIT-IDENTICAL to plain
        decode by construction, for greedy and sampling lanes alike — the
        distribution-preservation bar the parity tests pin down.

        Rollback is position truncation: rows past ĝ_m stay in the pages but
        are masked by kv_length (= position + 1 on every later step) and
        overwritten as the lane advances through them — no page frees, no
        refcount edits, which is what keeps the ledger conservation invariant
        trivially intact. The repetition-penalty seen-mask accumulates the
        FED token before sampling each row (idempotent for row 0's already-
        seen committed token), matching plain decode's per-token host update.
        Non-speculating lanes ride along with the idle sentinel position:
        their writes drop and their outputs are ignored.

        The layer loop is ``_paged_decode_fn``'s (``_scan_paged_span``): the
        pool a block sees is the whole span's, carried and written in place,
        and its tables are shifted by the layer."""
        family, cfg = self.family, self.cfg
        client_embed, client_head = family.client_embed, family.client_head
        fp_proj = fp_ops.projection(self.hidden_size)  # baked constant

        cache_dtype = jnp.dtype(self.cache_dtype)

        @tracked_jit(
            name="paged_spec_verify", steady=True,
            static_argnames=("with_fp",), donate_argnums=(1, 2),
        )
        def step(params, k_pool, v_pool, client_params, tokens, positions,
                 do_sample, temperature, top_k, top_p, rep_penalty, seeds,
                 draw_idx, seen_mask, tables, *, with_fp: bool):
            # tokens: [n_lanes, S] int32 (S = spec_k + 1): column 0 is the
            # lane's last committed token, columns 1..S-1 the draft proposals;
            # positions: [n_lanes] int32, idle sentinel for non-spec lanes
            S = tokens.shape[1]
            hidden = client_embed(client_params, tokens, cfg).astype(cache_dtype)
            hidden, k_pool, v_pool, _ = self._scan_paged_span(
                params, k_pool, v_pool, hidden,
                self._paged_lanes_layer(tables, positions),
            )
            logits = client_head(client_params, hidden, cfg)  # [n, S, vocab]
            vocab_ids = jnp.arange(logits.shape[-1], dtype=jnp.int32)[None, :]
            emitted = []
            seen = seen_mask
            for j in range(S):  # S is static and small (spec_k + 1)
                # plain decode adds each fed token to the penalty set before
                # the next draw; row 0's committed token is already in the
                # host mask, so the OR is idempotent there
                seen = seen | (vocab_ids == tokens[:, j][:, None])
                g_j = sample_tokens(
                    logits[:, j], do_sample=do_sample, temperature=temperature,
                    top_k=top_k, top_p=top_p, repetition_penalty=rep_penalty,
                    seen_mask=seen, seeds=seeds, draw_idx=draw_idx + j,
                )
                emitted.append(g_j)
            g_hat = jnp.stack(emitted, axis=1)  # [n, S]
            # leading-match count: draft d_j (tokens column j) verifies
            # against ĝ_j (emitted row j-1); a mismatch invalidates every
            # later row's conditioning, hence the cumprod prefix
            match = (tokens[:, 1:] == g_hat[:, :-1]).astype(jnp.int32)
            n_accept = jnp.sum(jnp.cumprod(match, axis=1), axis=1)  # [n]
            n_emit = jnp.minimum(n_accept + 1, S).astype(jnp.int32)
            if with_fp:
                # per-lane digest of the LAST EMITTED row's hidden state —
                # the spec twin of decode's hidden[:, -1, :] digest
                last = jnp.take_along_axis(
                    hidden, jnp.clip(n_emit - 1, 0, S - 1)[:, None, None], axis=1
                )[:, 0, :]
                fp = fp_ops.fingerprint_rows(last, fp_proj)
                return g_hat, n_emit, k_pool, v_pool, fp
            return g_hat, n_emit, k_pool, v_pool

        return step

    def paged_spec_verify_step(self, client_params, tokens, pool_kv,
                               positions, tables, *, sampling_vecs,
                               handles=None):
        """One batched draft–verify step over the lane pool (PAGED layout).

        Args:
          client_params: the span-holder's client leaves (embed/norm/head).
          tokens: int32 [n_lanes, spec_k + 1] — column 0 the last committed
            token per lane, columns 1.. the draft proposals (non-spec lanes:
            anything; their sentinel position drops every write).
          pool_kv: (k, v) page pools [n_blocks, n_pages, page_size, hkv, d].
          positions: int32 [n_lanes]; idle sentinel = max_pages * page_size.
          tables: int32 [n_lanes, max_pages] block tables (-1 unallocated).
          sampling_vecs: per-lane sampling parameter dict (sampling_vectors).

        Returns (g_hat [n_lanes, spec_k+1] int32, n_emit [n_lanes] int32,
        pool_kv): lane i must commit exactly g_hat[i, :n_emit[i]].
        """
        self.cache.refuse("speculative verify", SPEC_CUTS_BACK)
        k_pool, v_pool = pool_kv
        tables = self._as_tables(tables)
        v = sampling_vecs
        with_fp = fp_ops.enabled()
        with self._quant_ctx():
            res = self._paged_spec_verify_fn(
                self.params, k_pool, v_pool, client_params,
                np.asarray(tokens, np.int32), np.asarray(positions, np.int32),
                v["do_sample"], v["temperature"], v["top_k"], v["top_p"],
                v["repetition_penalty"], v["seeds"], v["draw_idx"],
                v["seen_mask"], tables, with_fp=with_fp,
            )
        if with_fp:
            g_hat, n_emit, k_pool, v_pool, self._last_step_fp = res
        else:
            g_hat, n_emit, k_pool, v_pool = res
            self._last_step_fp = None
        return g_hat, n_emit, (k_pool, v_pool)

    @functools.cached_property
    def _paged_mixed_step_fn(self):
        """Mixed prefill+decode step — the unified continuous-batching
        program ("Ragged Paged Attention" folding, PAPERS.md): every decode
        lane advances one token AND one lane runs a bucketed prefill chunk,
        in a single jitted scan that carries the page pool (the whole span's,
        written in place; each block's tables shifted by its layer:
        ``_scan_paged_span``). The decode half is ``_paged_decode_fn``'s
        layer verbatim (``_paged_lanes_layer``); the prefill half wraps the
        chunk lane's table row as a single-lane PagedKV and runs the SAME
        block compute as the exclusive path (``_inference_step_fn`` at
        batch=1: scalar position, bucket-padded chunk with n_valid
        scatter-drop, n_total for longrope) — update_kv_cache scatters only
        the chunk's freshly written KV rows straight into the pages and
        attend dispatches to the fused prefill kernel or its XLA fallback.
        No lane extract/insert round-trip, so concurrent decode never stalls
        behind a prefill; lanes' pages are disjoint (the prefill lane's
        decode position is the idle sentinel, so its decode-side write
        drops), so decode-before-prefill ordering is immaterial."""
        family, cfg = self.family, self.cfg
        takes_n_total = "n_total" in inspect.signature(family.block_apply).parameters
        fp_proj = fp_ops.projection(self.hidden_size)  # baked constant

        cache_dtype = jnp.dtype(self.cache_dtype)

        @tracked_jit(
            name="paged_mixed_step", steady=True,
            static_argnames=("with_fp",), donate_argnums=(1, 2, 10),
        )
        def step(params, k_pool, v_pool, lanes, tables,
                 chunk_hidden, chunk_lane, chunk_pos, chunk_n_valid,
                 chunk_n_total, state=(), *, with_fp: bool):
            # lanes: [n_lanes, hidden + 1] int32, the decode half's rows and
            # positions as ``pack_lanes`` lays them out (idle sentinel =
            # max_len); chunk_hidden: [1, B, hidden] (B = static
            # bucket); chunk_lane/chunk_pos/chunk_n_valid/chunk_n_total:
            # int32 scalars describing the ONE prefill chunk riding this step
            B = chunk_hidden.shape[1]
            hidden, positions = self._unpack_lanes(lanes, cache_dtype)
            chunk_hidden = chunk_hidden.astype(cache_dtype)
            table_row = jnp.expand_dims(jnp.take(tables, chunk_lane, axis=-2), -2)  # [1, max_pages] (a group: [groups, 1, max_pages])
            decode_half = self._paged_lanes_layer(tables, positions)
            extra = {"n_total": chunk_n_total} if takes_n_total else {}

            def layer(block_apply, carry, p_block, spans, paged):
                h_dec, h_pf = carry
                out_dec, spans = decode_half(block_apply, h_dec, p_block, spans, paged)
                if self.cache.latent_row is not None:
                    # the decode rows' walk reads the pools in a loop of its own, which nothing orders against the
                    # chunk's writes: left free, the compiler wrote the chunk first and kept a COPY of both pools
                    # for the walk, every layer (tests/test_kernels_lower_tpu.py). Tied to the walk's result, the
                    # pools the chunk writes are the ones the walk has read
                    out_dec, spans = jax.lax.optimization_barrier((out_dec, spans))
                # --- prefill half: the chunk lane's table row as a
                # single-lane PagedKV over the pools the decode half wrote;
                # writes land in the pages directly
                out_pf, new_kv = block_apply(
                    p_block, h_pf, paged(spans, table_row), chunk_pos, cfg,
                    use_flash=False, n_valid=chunk_n_valid, tp_mesh=None, **extra,
                )
                return (out_dec, out_pf), self._pools_of(new_kv)

            decode_state = self._state_lanes_layer(positions, tables.shape[-1] * k_pool.shape[2])

            def state_layer(block_apply, carry, p_block, mine):
                # the lanes' rows through the one-step form, then the chunk
                # through the chunked form from its lane's state on (that lane
                # is idle in the decode half, which left its state alone),
                # leaving the state and the conv's tail for the next chunk or
                # the first decode step; only that ONE lane's state leaves the pool and goes back
                h_dec, h_pf = carry
                out_dec, mine = decode_state(block_apply, h_dec, p_block, mine)
                out_pf, lane = block_apply(
                    p_block, h_pf, mine.lane(chunk_lane), chunk_pos, cfg, use_flash=False, n_valid=chunk_n_valid, tp_mesh=None,
                )
                return (out_dec, out_pf), mine.with_lane(chunk_lane, lane)

            (hidden, chunk_out), k_pool, v_pool, state = self._scan_paged_span(
                params, k_pool, v_pool, (hidden, chunk_hidden), layer, state, state_layer
            )
            if with_fp:
                fp = fp_ops.fingerprint_rows(hidden[:, -1, :], fp_proj)
                # the chunk's digest is of its LAST VALID row — the last
                # token the client receives for this prefill chunk, which
                # is what the client-side twin re-derives
                last_row = jnp.take(
                    chunk_out[0], jnp.clip(chunk_n_valid - 1, 0, B - 1), axis=0
                )
                chunk_fp = fp_ops.fingerprint_rows(last_row[None, :], fp_proj)[0]
                return self._with_state((hidden, chunk_out, k_pool, v_pool, fp, chunk_fp), state)
            return self._with_state((hidden, chunk_out, k_pool, v_pool), state)

        return step

    def paged_mixed_step(self, hidden, pool_kv, positions, tables,
                         chunk_hidden, chunk_lane, chunk_pos, *,
                         n_total=None, handles=None, trim: bool = True):
        """One coalesced mixed step: every decode lane (1 token each) plus
        ONE prefill chunk for ``chunk_lane``, in a single jitted program.

        Args:
          hidden: [n_lanes, 1, hidden] (idle lanes: any finite filler), or
            the lanes' rows and positions in ``pack_lanes``' form, as
            ``paged_decode_step`` takes them.
          pool_kv: (k, v) page pools [n_blocks, n_pages, page_size, hkv, d].
          positions: int32 [n_lanes]; idle sentinel = max_pages * page_size.
            The chunk lane must carry the sentinel here — its tokens ride the
            prefill half, not the decode half.
          tables: int32 [n_lanes, max_pages] block tables (-1 unallocated),
            on the host or already on the device.
          chunk_hidden: [1, seq, hidden], unpadded; bucket padding (and the
            matching n_valid) happens here so callers stay shape-oblivious.
          chunk_lane / chunk_pos: which lane, and the chunk's first absolute
            token position.
          n_total: final sequence length when known up front (longrope factor
            selection — same contract as inference_step); defaults to
            chunk_pos + seq.

          trim: cut ``chunk_out`` back from its bucket to ``seq`` rows here,
            on the device. That slice is an eager op, compiled once a
            (bucket, seq) pair: 0.1-0.3 s on the compute thread with every
            lane waiting, and a mix of long prompts ends nearly every prompt
            on a length of its own. A caller that fetches the rows anyway
            (the batcher) passes False and cuts them on the host.

        Returns (decode_out [n_lanes, 1, h], chunk_out [1, seq, h], pool_kv).
        """
        k_pool, v_pool, *state = pool_kv
        seq = chunk_hidden.shape[1]
        bucket = bucket_length(seq)
        if not isinstance(chunk_hidden, jax.Array):
            chunk_hidden = np.ascontiguousarray(chunk_hidden)
            if bucket != seq:
                chunk_hidden = np.pad(
                    chunk_hidden, ((0, 0), (0, bucket - seq), (0, 0))
                )
        elif bucket != seq:
            chunk_hidden = jnp.pad(
                chunk_hidden, ((0, 0), (0, bucket - seq), (0, 0))
            )
        if n_total is None:
            n_total = int(chunk_pos) + seq
        with_fp = fp_ops.enabled()
        with self._quant_ctx():
            res = self._paged_mixed_step_fn(
                self.params, k_pool, v_pool, self.pack_lanes(hidden, positions),
                self._as_tables(tables), chunk_hidden,
                np.int32(chunk_lane), np.int32(chunk_pos), np.int32(seq),
                np.int32(n_total), tuple(state), with_fp=with_fp,
            )
        res, state = self._split_state(res, state)
        if with_fp:
            out, chunk_out, k_pool, v_pool, self._last_step_fp, self._last_chunk_fp = res
        else:
            out, chunk_out, k_pool, v_pool = res
            self._last_step_fp = None
            self._last_chunk_fp = None
        if trim and chunk_out.shape[1] != seq:
            chunk_out = chunk_out[:, :seq]
        return out, chunk_out, (k_pool, v_pool, *state)

    @functools.cached_property
    def _paged_lane_gather_fn(self):
        """Assemble one lane's dense session-shaped view [n_blocks, 1,
        max_len, hkv, d] from its block-table row — the paged stand-in for
        ``_lane_extract_fn`` (exclusive ops: chunked prefill, kv export).
        Unallocated slots read as ZEROS: this view escapes attention (kv
        export crosses the wire), so it must never alias another tenant's
        page content — same contract as ops/paged_attention.py
        gather_pages."""

        from petals_tpu.ops.paged_attention import PagedPool, dequantize_kv

        @tracked_jit(name="paged_lane_gather")
        def f(k_pool, v_pool, table_row):
            n_blocks, n_pages, page_size = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
            max_pages = table_row.shape[0]
            safe = jnp.clip(table_row, 0, n_pages - 1)

            def gather_leaf(arr):
                g = jnp.take(arr, safe, axis=1)
                hole = (table_row >= 0).reshape(1, -1, *([1] * (arr.ndim - 2)))
                return jnp.where(hole, g, jnp.zeros((), arr.dtype))

            def one(pool):
                # quantized pools dequantize here: the dense lane view is the
                # fp-facing boundary (prefill compute, kv export, snapshots),
                # and rows of [hkv, d] whatever row the pool stores
                if isinstance(pool, PagedPool):
                    return dequantize_kv(
                        self.pool_to_wire(gather_leaf(pool.codes)), gather_leaf(pool.scales), pool.kind
                    )
                return gather_leaf(pool)

            k, v = one(k_pool), one(v_pool)
            shape = (n_blocks, 1, max_pages * page_size, self.num_kv_heads, self.head_dim)
            return k.reshape(shape), v.reshape(shape)

        return f

    @functools.cached_property
    def _paged_lane_scatter_fn(self):
        """Write a session-shaped lane buffer back into its pages — the paged
        stand-in for ``_lane_insert_fn`` (prefill lands its KV directly in
        the pages; unallocated slots drop). Quantized pools REQUANTIZE the
        checked-in buffer row by row — the write range was freshly computed,
        untouched rows round-trip within one quant step."""
        from petals_tpu.ops.paged_attention import PagedPool, quantize_kv_rows

        @tracked_jit(name="paged_lane_scatter", donate_argnums=(0, 1))
        def f(k_pool, v_pool, k, v, table_row):
            n_blocks, n_pages, page_size = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
            max_pages = table_row.shape[0]
            safe = jnp.where(table_row >= 0, table_row, n_pages)

            def one(pool, buf):
                pages = buf.reshape(n_blocks, max_pages, page_size, *pool.shape[3:])  # a plain pool's own row
                if isinstance(pool, PagedPool):
                    codes, scales = quantize_kv_rows(pages, pool.kind)
                    codes = self.wire_to_pool(codes, pool.codes)
                    return PagedPool(
                        pool.codes.at[:, safe].set(
                            codes.astype(pool.codes.dtype), mode="drop"
                        ),
                        pool.scales.at[:, safe].set(
                            scales.astype(pool.scales.dtype), mode="drop"
                        ),
                    )
                return pool.at[:, safe].set(pages.astype(pool.dtype), mode="drop")

            return one(k_pool, k), one(v_pool, v)

        return f

    @functools.cached_property
    def _swap_out_pages_fn(self):
        """Gather an explicit page list out of the pool as [n_blocks, n_slots,
        page_size, *pool_row] pairs, bound for the host swap tier (scheduler
        preemption; the host's copy is held as rows of [hkv, d_store]:
        ``pool_to_wire`` there, ``wire_to_pool`` on the way back).
        Non-donating: the pool stays live — the pages are only FREED once the host copy has landed (server/batching.py
        _swap_out_lane validates the lane generation first). Per-leaf, so a
        quantized pool swaps its PACKED codes + scales — the host tier holds
        (and the ledger bills) wire bytes, never re-inflated fp pages."""

        @tracked_jit(name="swap_out_pages")
        def f(k_pool, v_pool, pages):
            take = lambda a: jnp.take(a, pages, axis=1)
            return (
                jax.tree_util.tree_map(take, k_pool),
                jax.tree_util.tree_map(take, v_pool),
            )

        return f

    @functools.cached_property
    def _swap_in_pages_fn(self):
        """Scatter swapped-out page contents back into the pool on a FRESH
        page list (block tables make relocation free). The donating twin of
        ``_swap_out_pages_fn``; negative entries drop, mirroring
        ``_paged_lane_scatter_fn``. Per-leaf: packed pages land back
        byte-exact — swap round trips lose nothing on a quantized pool."""

        @tracked_jit(name="swap_in_pages", donate_argnums=(0, 1))
        def f(k_pool, v_pool, k_pages, v_pages, pages):
            n_pages = k_pool.shape[1]
            safe = jnp.where(pages >= 0, pages, n_pages)

            def put(pool, pg):
                return jax.tree_util.tree_map(
                    lambda a, b: a.at[:, safe].set(b.astype(a.dtype), mode="drop"),
                    pool, pg,
                )

            return put(k_pool, k_pages), put(v_pool, v_pages)

        return f

    @functools.cached_property
    def _lane_state_take_fn(self):
        """One lane's states out of the state pool, ``[state layers, ...]`` a
        leaf: what leaves with the lane's pages when it is swapped out.
        Non-donating, as ``_swap_out_pages_fn``."""

        @tracked_jit(name="lane_state_take")
        def f(state, lane):
            return tuple(jax.lax.dynamic_index_in_dim(a, lane, 1, keepdims=False) for a in state)

        return f

    @functools.cached_property
    def _lane_state_put_fn(self):
        """The donating twin of ``_lane_state_take_fn``: a lane's states back
        into the pool, byte for byte."""

        @tracked_jit(name="lane_state_put", donate_argnums=(0,))
        def f(state, lane_state, lane):
            return tuple(
                jax.lax.dynamic_update_index_in_dim(a, new.astype(a.dtype), lane, 1) for a, new in zip(state, lane_state)
            )

        return f

    @functools.cached_property
    def _copy_page_fn(self):
        """Duplicate one page across all blocks of the pool (the copy-on-write
        fork: a shared page must be copied before a lane writes into it).
        Per-leaf: a quantized fork copies codes + scales bytes verbatim."""

        @tracked_jit(name="copy_page", donate_argnums=(0, 1))
        def f(k_pool, v_pool, src, dst):
            def cp(a):
                page = jax.lax.dynamic_slice_in_dim(a, src, 1, axis=1)
                return jax.lax.dynamic_update_slice_in_dim(a, page, dst, axis=1)

            return (
                jax.tree_util.tree_map(cp, k_pool),
                jax.tree_util.tree_map(cp, v_pool),
            )

        return f

    @functools.cached_property
    def _lane_extract_fn(self):
        """Copy one lane out of the pool as a [n_blocks, 1, max_len, hkv, d]
        session-shaped KV pair (for non-batchable work: prefill, kv export)."""

        @tracked_jit(name="lane_extract")
        def f(k_pool, v_pool, lane):
            k = jax.lax.dynamic_slice_in_dim(k_pool, lane, 1, axis=1)
            v = jax.lax.dynamic_slice_in_dim(v_pool, lane, 1, axis=1)
            return k, v

        return f

    @functools.cached_property
    def _lane_insert_fn(self):
        # only the pool buffers are donatable (the lane tensors cannot alias
        # an output: their shapes differ from both outputs)
        @tracked_jit(name="lane_insert", donate_argnums=(0, 1))
        def f(k_pool, v_pool, k, v, lane):
            k_pool = jax.lax.dynamic_update_slice_in_dim(
                k_pool, k.astype(k_pool.dtype), lane, axis=1
            )
            v_pool = jax.lax.dynamic_update_slice_in_dim(
                v_pool, v.astype(v_pool.dtype), lane, axis=1
            )
            return k_pool, v_pool

        return f

    @functools.cached_property
    def _forward_fn(self):
        family, cfg = self.family, self.cfg
        tp_mesh = self.mesh
        # sequence parallelism on the stateless (no-KV) path: activations ride
        # the "sp" axis and attention runs as a ring over it (ops/
        # ring_attention.py) — the long-context training/forward path scales
        # past one chip's activation memory
        sp_size = self.mesh.shape.get("sp", 1) if self.mesh is not None else 1
        supports_ring = family.supports_ring_attention and sp_size > 1

        # The training path (forward + vjp-recompute backward) NEVER uses the
        # Pallas flash kernel: it has no reverse-mode AD rule, and keeping
        # forward and backward on the same (XLA) attention means the backward
        # recompute linearizes exactly what the client saw.
        @tracked_jit(name="forward", static_argnames=("with_prompts",))
        def fwd(params, hidden, prompts, *, with_prompts: bool):
            use_ring = supports_ring and hidden.shape[1] % sp_size == 0
            if use_ring:
                from jax.sharding import NamedSharding, PartitionSpec as P

                hidden = jax.lax.with_sharding_constraint(
                    hidden, NamedSharding(tp_mesh, P(None, "sp", None))
                )

            def layer(block_apply, h, p_block, prompt, block_idx):
                if with_prompts:
                    pre = prompt.shape[1]
                    h = h.at[:, :pre].add(prompt.astype(h.dtype))
                extra = (
                    {"ring_mesh": tp_mesh if use_ring else None}
                    if family.supports_ring_attention
                    else {}
                )
                out, _ = block_apply(
                    p_block, h, None, 0, cfg, use_flash=False, tp_mesh=tp_mesh, **extra
                )
                return out, None

            # no stacked experts: the backward differentiates this program, and the hit kernel has no VJP
            hidden, _ = self._scan_span(params, hidden, prompts, layer, stack_experts=False)
            return hidden

        return fwd

    @functools.cached_property
    def _backward_fn(self):
        fwd_raw = self._forward_fn.__wrapped__  # un-jitted closure for vjp

        @tracked_jit(name="backward", static_argnames=("with_prompts",))
        def bwd(params, hidden, prompts, grad_out, *, with_prompts: bool):
            def f(h, p):
                return fwd_raw(params, h, p, with_prompts=with_prompts)

            _, vjp = jax.vjp(f, hidden, prompts)
            grad_hidden, grad_prompts = vjp(grad_out.astype(hidden.dtype))
            return grad_hidden, grad_prompts

        return bwd

    @functools.cached_property
    def _server_gen_fn(self):
        """Device-resident greedy generation: sample -> embed -> span-scan ->
        sample, the whole multi-token loop as ONE jitted lax.scan. The
        per-token serving path pays a host<->device round trip per token for
        the logits (its share of a step is not measured on the current chip,
        ROADMAP S1) — a full-span server holding the client leaves can
        amortize it over n tokens. Token parity with the client path: the
        same family client_head/client_embed hooks compute logits in f32 and
        the embed rides the identical cast into the span step.

        Ordering keeps the session resume convention: the FIRST token comes
        from the caller-provided last hidden (the prefill/step output), each
        scan iteration feeds token t_i and samples t_{i+1}, and the LAST
        sampled token is never fed — exactly like the client loop, so a
        follow-up step sends it as the unseen suffix."""
        family, cfg = self.family, self.cfg
        step_fn = self._inference_step_fn
        client_embed, client_head = family.client_embed, family.client_head

        @tracked_jit(
            name="server_gen", static_argnames=("n_tokens",), donate_argnums=(2, 3)
        )
        def gen(span_params, client_params, k_stack, v_stack, last_hidden,
                position, dummy_prompts, dummy_hypo, *, n_tokens: int):
            def sample(h):
                logits = client_head(client_params, h[:, -1:], cfg)
                return jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)  # [b]

            t0 = sample(last_hidden)

            def body(carry, _):
                tok, k_stack, v_stack, pos = carry
                h_in = client_embed(client_params, tok[:, None], cfg)
                out, k_stack, v_stack = step_fn(
                    span_params, k_stack, v_stack, h_in, pos, jnp.int32(1),
                    pos + 1, dummy_prompts, dummy_hypo,
                    with_prompts=False, with_hypo=False, padded=False,
                )
                nt = sample(out)
                return (nt, k_stack, v_stack, pos + 1), nt

            (_, k_stack, v_stack, _), toks = jax.lax.scan(
                body,
                (t0, k_stack, v_stack, jnp.asarray(position, jnp.int32)),
                None,
                length=n_tokens - 1,
            )
            tokens = jnp.concatenate([t0[None], toks], axis=0)  # [n, b]
            return tokens.T, k_stack, v_stack

        return gen

    @functools.cached_property
    def _server_gen_sampled_fn(self):
        """Sampling twin of ``_server_gen_fn``: the same sample -> embed ->
        span-scan loop with the ops/sampling warp pipeline (repetition
        penalty -> temperature -> top-k -> top-p -> inverse-CDF draw)
        compiled into each iteration. The PRNG schedule is stateless —
        draw ``i`` uses fold_in(PRNGKey(seed), i) — so the client can
        replay the uniform stream for mid-stream fallback, and a fixed
        seed is bit-reproducible across runs. The greedy fn stays separate
        and untouched: greedy sessions keep their existing (already
        compiled) executable and never pay for the warp stages."""
        family, cfg = self.family, self.cfg
        step_fn = self._inference_step_fn
        client_embed, client_head = family.client_embed, family.client_head

        @tracked_jit(
            name="server_gen_sampled", static_argnames=("n_tokens",),
            donate_argnums=(2, 3),
        )
        def gen(span_params, client_params, k_stack, v_stack, last_hidden,
                position, dummy_prompts, dummy_hypo, do_sample, temperature,
                top_k, top_p, rep_penalty, seeds, draw0, seen0,
                *, n_tokens: int):
            batch = seen0.shape[0]

            def sample(h, seen, idx):
                logits = client_head(client_params, h[:, -1:], cfg)[:, -1, :]
                return sample_tokens(
                    logits, do_sample=do_sample, temperature=temperature,
                    top_k=top_k, top_p=top_p, repetition_penalty=rep_penalty,
                    seen_mask=seen, seeds=seeds, draw_idx=idx,
                )

            def mark(seen, tok):
                return seen.at[jnp.arange(batch), tok].set(True)

            t0 = sample(last_hidden, seen0, draw0)

            def body(carry, _):
                tok, k_stack, v_stack, pos, seen, idx = carry
                seen = mark(seen, tok)
                h_in = client_embed(client_params, tok[:, None], cfg)
                out, k_stack, v_stack = step_fn(
                    span_params, k_stack, v_stack, h_in, pos, jnp.int32(1),
                    pos + 1, dummy_prompts, dummy_hypo,
                    with_prompts=False, with_hypo=False, padded=False,
                )
                nt = sample(out, seen, idx)
                return (nt, k_stack, v_stack, pos + 1, seen, idx + 1), nt

            (_, k_stack, v_stack, _, _, _), toks = jax.lax.scan(
                body,
                (t0, k_stack, v_stack, jnp.asarray(position, jnp.int32),
                 seen0, draw0 + 1),
                None,
                length=n_tokens - 1,
            )
            tokens = jnp.concatenate([t0[None], toks], axis=0)  # [n, b]
            return tokens.T, k_stack, v_stack

        return gen

    def generate_tokens(
        self, client_params, last_hidden, kv, position: int, n_tokens: int,
        *, active_adapter: Optional[str] = None,
        sampling: Optional[dict] = None,
    ):
        """Generate ``n_tokens`` on device from ``last_hidden`` (the span
        output of the last fed token) — greedy by default, sampled when a
        validated ``sampling`` dict (rpc/protocol.validate_gen_sampling
        schema) is given. Feeds n_tokens - 1 tokens into the cache (the
        final token stays unfed, client-loop convention).
        Returns (tokens [batch, n_tokens] int32, (k_stack, v_stack))."""
        assert client_params is not None
        self.cache.refuse(
            "server-side generation on a private cache", "only the paged lane pool's generation step carries the state"
        )
        k_stack, v_stack = kv
        batch = k_stack.shape[1]
        if position + n_tokens - 1 > k_stack.shape[2]:
            raise ValueError(
                f"Generating {n_tokens} tokens at position {position} overflows "
                f"the allocated cache ({k_stack.shape[2]} tokens)"
            )
        span_params = self.params_for(active_adapter)
        dummy_p = self._dummy_operand(
            (self.n_blocks, batch, 0, self.hidden_size), self.compute_dtype
        )
        dummy_h = self._dummy_operand((batch,), jnp.int32)
        with self._quant_ctx():
            if sampling is None:
                tokens, k_stack, v_stack = self._server_gen_fn(
                    span_params, client_params, k_stack, v_stack,
                    jnp.asarray(last_hidden), np.int32(position), dummy_p,
                    dummy_h, n_tokens=int(n_tokens),
                )
            else:
                vec = sampling_vectors(batch, self.cfg.vocab_size, sampling)
                tokens, k_stack, v_stack = self._server_gen_sampled_fn(
                    span_params, client_params, k_stack, v_stack,
                    jnp.asarray(last_hidden), np.int32(position), dummy_p,
                    dummy_h, vec["do_sample"], vec["temperature"],
                    vec["top_k"], vec["top_p"], vec["repetition_penalty"],
                    vec["seeds"], vec["draw_idx"], vec["seen_mask"],
                    n_tokens=int(n_tokens),
                )
        return tokens, (k_stack, v_stack)

    @functools.cached_property
    def _sample_hidden_fn(self):
        """Head + sample from a last-hidden, jitted: the lane-pool gen
        bootstrap (t0 comes from the caller's prefill/step output before the
        pooled per-token loop takes over)."""
        family, cfg = self.family, self.cfg
        client_head = family.client_head

        @tracked_jit(name="sample_hidden")
        def f(client_params, last_hidden, do_sample, temperature, top_k,
              top_p, rep_penalty, seen, seeds, draw_idx):
            logits = client_head(client_params, last_hidden[:, -1:], cfg)[:, -1, :]
            return sample_tokens(
                logits, do_sample=do_sample, temperature=temperature,
                top_k=top_k, top_p=top_p, repetition_penalty=rep_penalty,
                seen_mask=seen, seeds=seeds, draw_idx=draw_idx,
            )

        return f

    def sample_from_hidden(self, client_params, last_hidden,
                           sampling: Optional[dict] = None) -> np.ndarray:
        """Pick the next token(s) [batch] int32 from a span output — greedy
        unless a validated ``sampling`` dict is given."""
        assert client_params is not None
        batch = last_hidden.shape[0]
        vec = sampling_vectors(batch, self.cfg.vocab_size, sampling)
        with self._quant_ctx():
            tok = self._sample_hidden_fn(
                client_params, jnp.asarray(last_hidden), vec["do_sample"],
                vec["temperature"], vec["top_k"], vec["top_p"],
                vec["repetition_penalty"], vec["seen_mask"], vec["seeds"],
                vec["draw_idx"],
            )
        return np.asarray(tok)

    @functools.cached_property
    def _batched_gen_decode_fn(self):
        """One decode step over the whole lane pool with the client leaves in
        the loop: gen lanes feed their previous TOKEN (embedded on device)
        while plain decode lanes feed their client-provided hidden, the pool
        scan advances every lane at its own position, and the head + sampling
        pipeline picks each gen lane's next token — N server-gen sessions at
        different depths advance in ONE compiled program per token, sharing
        the step with ordinary per-token traffic. Per-lane sampling vectors
        let greedy and sampling sessions coexist in the same step."""
        family, cfg = self.family, self.cfg
        client_embed, client_head = family.client_embed, family.client_head
        fp_proj = fp_ops.projection(self.hidden_size)  # baked constant

        cache_dtype = jnp.dtype(self.cache_dtype)

        @tracked_jit(
            name="batched_gen_decode", steady=True,
            static_argnames=("with_fp",), donate_argnums=(2, 3),
        )
        def step(params, client_params, k_pool, v_pool, hidden, tokens,
                 use_token, positions, do_sample, temperature, top_k, top_p,
                 rep_penalty, seeds, draw_idx, seen_mask, *, with_fp: bool):
            # hidden: [n_lanes, 1, hidden]; tokens/use_token/positions: [n_lanes]
            emb = client_embed(client_params, tokens[:, None], cfg)
            hidden = jnp.where(
                use_token[:, None, None],
                emb.astype(cache_dtype),
                hidden.astype(cache_dtype),
            )
            hidden, (k_pool, v_pool) = self._scan_span(
                params, hidden, (k_pool, v_pool), self._dense_lanes_layer(positions)
            )
            logits = client_head(client_params, hidden, cfg)[:, -1, :]
            next_tok = sample_tokens(
                logits, do_sample=do_sample, temperature=temperature,
                top_k=top_k, top_p=top_p, repetition_penalty=rep_penalty,
                seen_mask=seen_mask, seeds=seeds, draw_idx=draw_idx,
            )
            if with_fp:
                fp = fp_ops.fingerprint_rows(hidden[:, -1, :], fp_proj)
                return hidden, next_tok, k_pool, v_pool, fp
            return hidden, next_tok, k_pool, v_pool

        return step

    def batched_gen_decode_step(self, client_params, hidden, tokens,
                                use_token, pool_kv, positions, *,
                                sampling_vecs, handles=None):
        """One coalesced decode+generate step over the whole lane pool.

        Args:
          client_params: the full-model client leaves (embed + head).
          hidden: [n_lanes, 1, hidden] — plain decode lanes' inputs (idle and
            gen lanes: any finite filler).
          tokens: int32 [n_lanes] — gen lanes' previous token (others: 0).
          use_token: bool [n_lanes] — True where the embedded token (not
            ``hidden``) is this lane's step input.
          pool_kv / positions: as in batched_decode_step (idle sentinel =
            pool length).
          sampling_vecs: per-lane parameter dict (ops/sampling.sampling_vectors
            layout: do_sample/temperature/top_k/top_p/repetition_penalty/
            seen_mask/seeds/draw_idx).
        Returns (hidden_out, next_tokens [n_lanes] i32, (k_pool, v_pool)).
        """
        k_pool, v_pool = pool_kv
        if not isinstance(hidden, jax.Array):
            hidden = np.ascontiguousarray(hidden)
        v = sampling_vecs
        with_fp = fp_ops.enabled()
        with self._quant_ctx():
            res = self._batched_gen_decode_fn(
                self.params, client_params, k_pool, v_pool, hidden,
                np.asarray(tokens, np.int32), np.asarray(use_token, bool),
                np.asarray(positions, np.int32), v["do_sample"],
                v["temperature"], v["top_k"], v["top_p"],
                v["repetition_penalty"], v["seeds"], v["draw_idx"],
                v["seen_mask"], with_fp=with_fp,
            )
        if with_fp:
            out, toks, k_pool, v_pool, self._last_step_fp = res
        else:
            out, toks, k_pool, v_pool = res
            self._last_step_fp = None
        return out, toks, (k_pool, v_pool)

    def pop_step_fp(self):
        """Take (and clear) the last batched step's fused fingerprints:
        ``(lane_fp, chunk_fp)`` device arrays or Nones. Called by the
        batcher on its single compute thread right after the step's host
        sync, so the stash never outlives its step. getattr-tolerant so
        wrapper backends (multihost lockstep) that do not run our
        ``__init__`` report (None, None) instead of raising."""
        fp = getattr(self, "_last_step_fp", None)
        chunk = getattr(self, "_last_chunk_fp", None)
        self._last_step_fp = None
        self._last_chunk_fp = None
        return fp, chunk

    # ------------------------------------------------------------- public API

    def inference_step(
        self,
        hidden: np.ndarray,  # [batch, seq, hidden] (real tokens, unpadded)
        kv: Tuple[jax.Array, jax.Array],
        position: int,
        *,
        prompts: Optional[np.ndarray] = None,  # [n_blocks, batch, pre_seq, hidden]
        hypo_ids: Optional[np.ndarray] = None,  # [batch]
        active_adapter: Optional[str] = None,
        handles=None,  # session identity for the multi-host lockstep wrapper; unused here
        n_total: Optional[int] = None,  # final sequence length override (chunked callers)
    ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
        """One (chunked-as-needed) inference step over the whole span chain.

        ``n_total`` lets a caller that ALREADY chunked the prompt (the
        batcher's dense-prefill path submits one inference_step per chunk)
        declare the full final sequence length, so length-dependent rotary
        variants (LongRoPE short/long factor selection) see the same n_total
        in every chunk instead of flipping factors mid-prompt. Defaults to
        position + seq — exact for unchunked callers."""
        self.refuse_deep_prompts(prompts)
        self.cache.refuse(
            "a step on a private or checked-out cache (deep prompts, beam search's hypo_ids, a session that took no lane)",
            "only the paged lane pool's own step programs carry the state",
        )
        k_stack, v_stack = kv
        max_length = k_stack.shape[2]
        batch, total_seq, _ = hidden.shape
        if position + total_seq > max_length:
            raise ValueError(
                f"Step of {total_seq} tokens at position {position} overflows the "
                f"allocated cache ({max_length} tokens)"
            )
        if n_total is not None and n_total < position + total_seq:
            raise ValueError(
                f"n_total={n_total} is shorter than this step's own end "
                f"({position} + {total_seq})"
            )

        # keep hidden host-side (numpy): each chunk ships inside its step's ONE
        # jit dispatch (the jit casts to compute dtype); an eager asarray+cast
        # here cost two extra device round trips per decode token
        if not isinstance(hidden, jax.Array):
            hidden = np.ascontiguousarray(hidden)
        span_params = self.params_for(active_adapter)
        outputs = []
        offset = 0
        # The final sequence length after this step is known up front: thread
        # it through so longrope (phi3) selects rotary factors from it in
        # EVERY chunk — a chunked prefill then matches HF's single full
        # forward instead of flipping factors mid-prompt.
        if n_total is None:
            n_total = position + total_seq
        for chunk_len in self.chunk_plan(batch, total_seq, kv_buf_len=max_length):
            chunk = hidden[:, offset : offset + chunk_len]
            out, k_stack, v_stack = self._step_once(
                span_params, chunk, k_stack, v_stack, position + offset, prompts,
                hypo_ids if offset == 0 else None, n_total=n_total,
            )
            outputs.append(out)
            offset += chunk_len

        result = outputs[0] if len(outputs) == 1 else jnp.concatenate(outputs, axis=1)
        return result, (k_stack, v_stack)

    def _step_once(self, span_params, chunk, k_stack, v_stack, position, prompts,
                   hypo_ids, n_total=None):
        batch, seq, _ = chunk.shape
        n_valid = seq
        if n_total is None:
            n_total = position + seq
        if seq == 1:
            padded, is_padded = chunk, False
        else:
            bucket = bucket_length(seq)
            if bucket != seq:
                padded = jnp.pad(chunk, ((0, 0), (0, bucket - seq), (0, 0)))
                is_padded = True
            else:
                padded, is_padded = chunk, False

        with_prompts = prompts is not None
        with_hypo = hypo_ids is not None
        # dummy prompts/hypo operands: device-resident and cached per shape —
        # allocating them per step added host->device dispatches on the
        # per-token path (decode is called hundreds of times per second)
        if prompts is None:
            prompts_arr = self._dummy_operand(
                (self.n_blocks, batch, 0, self.hidden_size), self.compute_dtype
            )
        else:
            prompts_arr = jnp.asarray(prompts, self.compute_dtype)
        hypo_arr = (
            jnp.asarray(hypo_ids, jnp.int32)
            if hypo_ids is not None
            else self._dummy_operand((batch,), jnp.int32)
        )

        with self._quant_ctx():
            out, k_stack, v_stack = self._inference_step_fn(
                span_params,
                k_stack,
                v_stack,
                padded,
                np.int32(position),
                np.int32(n_valid),
                np.int32(n_total),
                prompts_arr,
                hypo_arr,
                with_prompts=with_prompts,
                with_hypo=with_hypo,
                padded=is_padded,
            )
        if out.shape[1] != seq:
            out = out[:, :seq]
        return out, k_stack, v_stack

    def _dummy_operand(self, shape, dtype) -> jax.Array:
        key = (shape, jnp.dtype(dtype).name)
        arr = self._dummy_operands.get(key)
        if arr is None:
            arr = self._dummy_operands[key] = jnp.zeros(shape, dtype)
        return arr

    def chunk_plan(self, batch: int, total_seq: int, kv_buf_len: int = None,
                   page_size: int = None, start: int = 0) -> Sequence[int]:
        """Split a long prefill so each chunk's attention footprint stays under
        max_chunk_size_bytes (reference backend.py:126-152 semantics). Public:
        the continuous batcher plans queue-task boundaries with it, so the
        chunk policy lives here in exactly one place.

        ``page_size`` (paged lanes): chunk ENDS are aligned to absolute page
        boundaries — each chunk's KV scatter is whole-page writes, with a
        partial tail page only on the final chunk. ``start`` is the absolute
        position of the first token (alignment is in absolute positions, so
        an unaligned start self-corrects after the first chunk)."""
        if total_seq <= 1:
            return [total_seq]
        # The linear sizing below is only sound when the flash kernel will
        # actually run: attend() silently falls back to the logit-materializing
        # XLA path when the kernel can't handle the shapes (cache length not a
        # multiple of 128), and then chunks must be sized by the quadratic
        # formula. Sliding windows are handled by the kernel.
        flash_will_run = self.use_flash and (kv_buf_len is None or kv_buf_len % 128 == 0)
        if flash_will_run:
            # flash never materializes the [chunk, total_seq] logits; the
            # footprint is the chunk's activations (hidden + MLP intermediate +
            # per-head rows), linear in chunk length
            itemsize = jnp.dtype(self.compute_dtype).itemsize
            per_token = batch * itemsize * (
                2 * self.hidden_size
                + getattr(self.cfg, "intermediate_size", 4 * self.hidden_size)
                + self.cfg.num_attention_heads * self.head_dim
            )
            max_chunk = max(self.max_chunk_size_bytes // max(per_token, 1), 1)
        else:
            # attention logits per chunk ≈ batch * heads * chunk * total_seq * 4 bytes
            heads = self.cfg.num_attention_heads
            denom = max(batch * heads * total_seq * 4, 1)
            max_chunk = max(self.max_chunk_size_bytes // denom, 1)
        chunks = []
        remaining = total_seq
        pos = int(start)
        while remaining > 0:
            step = min(max_chunk, remaining)
            if page_size and step < remaining:
                # align this chunk's end DOWN to an absolute page boundary
                # (whole-page scatters); keep the unaligned step when the
                # boundary is out of reach (max_chunk < one page span)
                end = pos + step
                aligned = end - end % page_size
                if aligned > pos:
                    step = aligned - pos
            chunks.append(step)
            remaining -= step
            pos += step
        return chunks

    def forward(
        self, hidden: np.ndarray, prompts: Optional[np.ndarray] = None,
        active_adapter: Optional[str] = None,
    ) -> jax.Array:
        """Training-style forward over the span (no KV cache)."""
        self.refuse_deep_prompts(prompts)
        hidden = jnp.asarray(hidden, self.compute_dtype)
        span_params = self.params_for(active_adapter)
        with_prompts = prompts is not None
        prompts_arr = (
            jnp.asarray(prompts, self.compute_dtype)
            if prompts is not None
            else jnp.zeros((self.n_blocks, hidden.shape[0], 0, self.hidden_size), self.compute_dtype)
        )
        with self._quant_ctx():
            return self._forward_fn(span_params, hidden, prompts_arr, with_prompts=with_prompts)

    def backward(
        self, hidden: np.ndarray, grad_out: np.ndarray, prompts: Optional[np.ndarray] = None,
        active_adapter: Optional[str] = None,
    ) -> Tuple[jax.Array, Optional[jax.Array]]:
        """Grads wrt inputs (and deep prompts if given) — recomputes the chain
        forward like the reference (run_rpc_backward, block_functions.py:84-141)."""
        self.refuse_deep_prompts(prompts)
        hidden = jnp.asarray(hidden, self.compute_dtype)
        grad_out = jnp.asarray(grad_out, self.compute_dtype)
        with_prompts = prompts is not None
        prompts_arr = (
            jnp.asarray(prompts, self.compute_dtype)
            if prompts is not None
            else jnp.zeros((self.n_blocks, hidden.shape[0], 0, self.hidden_size), self.compute_dtype)
        )
        with self._quant_ctx():
            grad_hidden, grad_prompts = self._backward_fn(
                self.params_for(active_adapter), hidden, prompts_arr, grad_out,
                with_prompts=with_prompts,
            )
        return grad_hidden, (grad_prompts if with_prompts else None)
