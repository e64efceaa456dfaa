"""Server-side speculative decoding: the draft model.

The span verifies k draft tokens per lane in ONE paged-attention step
(backend.py ``paged_spec_verify_step``); this module supplies the k drafts.
A ``DraftModel`` is a SMALL full model (any registered family — embeddings,
every block, head — typically NF4A-quantized) loaded alongside the span via
``--draft_model``. It is deliberately stateless across ticks:

- No persistent draft KV cache. Each propose() call re-prefills a bounded
  token WINDOW (the last ``window`` tokens of each lane's context) into a
  fresh dense buffer and then decodes k tokens greedily. That makes drafts
  a pure function of (window tokens) — no draft-side rollback, reorder, or
  page bookkeeping when the verify step rejects a suffix, no extra state to
  migrate, and one compiled program regardless of which lanes speculate.
- Static BUCKETED shapes: speculating lanes are compacted and padded to the
  next power-of-two lane count (clamped to the pool size), so a single
  speculating lane pays for a [1, window] prefill, not the whole pool's
  [n_lanes, window] — on a half-idle pool the window prefill is the draft's
  dominant cost and it scales linearly with the padded batch. One
  ``tracked_jit`` program ("draft_propose", steady=True) per
  (bucket, window, k); :meth:`warmup` compiles every bucket up front (the
  batcher calls it on the first spec tick) so zero post-warmup recompiles —
  a gate_spec_decode acceptance bar — holds across any mix of lane counts.
- Greedy argmax proposals. Draft quality only moves the ACCEPTANCE RATE,
  never correctness: the verify step samples the target's own tokens from
  the lane's seed+offset PRNG stream and accepts drafts by exact match, so
  the emitted stream is bit-identical to plain decode whatever the draft
  says (backend.py ``_paged_spec_verify_fn`` docstring).

Window positions are chunk-local (the window re-prefills at position 0), so
a draft conditioned on a truncated context sees shifted rotary phases versus
the target. That costs acceptance on long sessions and nothing else; a
cooperative draft whose window covers the whole context (the bench setup)
sees exact positions.
"""

from __future__ import annotations

import os
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from petals_tpu.telemetry.observatory import tracked_jit
from petals_tpu.utils.logging import get_logger

logger = get_logger(__name__)

DEFAULT_WINDOW = 64
# acceptance-rate EMA floor below which a lane falls back to plain decode
# (the batcher's auto-disable heuristic; see server/batching.py)
MIN_ACCEPT_ENV = "PETALS_TPU_SPEC_MIN_ACCEPT"


def min_accept_floor(default: float = 0.1) -> float:
    try:
        return float(os.environ.get(MIN_ACCEPT_ENV, default))
    except ValueError:
        return default


class DraftModel:
    """A small full model proposing k greedy tokens per lane per tick.

    ``block_params`` is a LIST of per-block parameter trees (NOT stacked):
    the propose program unrolls the block loop in Python, which sidesteps the
    quant-constant scan machinery the big span needs — draft models are small
    enough that per-block unrolling compiles in bounded time and lets NF4A
    blocks ride through ``mm``'s isinstance dispatch unchanged.
    """

    def __init__(
        self,
        family,
        cfg,
        block_params: Sequence[dict],
        client_params: dict,
        *,
        spec_k: int,
        window: int = DEFAULT_WINDOW,
        compute_dtype=jnp.float32,
    ):
        if spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        if window < 1:
            raise ValueError(f"draft window must be >= 1, got {window}")
        if family.client_embed is None or family.client_head is None:
            raise ValueError(f"{family.name} has no client embed/head mapping")
        self.family = family
        self.cfg = cfg
        self.block_params = list(block_params)
        self.client_params = client_params
        self.spec_k = int(spec_k)
        self.window = int(window)
        self.compute_dtype = compute_dtype
        self.num_kv_heads = getattr(cfg, "num_key_value_heads", cfg.num_attention_heads)
        self.head_dim = cfg.head_dim
        self._propose_fn = self._build_propose_fn()

    # ------------------------------------------------------------------ load

    @classmethod
    def from_pretrained(
        cls,
        model_name_or_path: str,
        *,
        spec_k: int,
        window: int = DEFAULT_WINDOW,
        quant_type: str = "nf4a",
        compute_dtype=jnp.float32,
        revision: str = "main",
        cache_dir=None,
    ) -> "DraftModel":
        """Load every block + the client leaves of a (small) checkpoint,
        quantizing blocks per ``quant_type`` (NF4A default — the 4-bit
        serving default, utils/convert_block.py)."""
        from petals_tpu.client.from_pretrained import load_client_params
        from petals_tpu.server.from_pretrained import get_block_config, load_block_params
        from petals_tpu.utils.convert_block import QuantType, convert_block_params

        family, cfg = get_block_config(
            model_name_or_path, revision=revision, cache_dir=cache_dir
        )
        n_blocks = cfg.num_hidden_layers
        block_params = [
            convert_block_params(
                load_block_params(
                    model_name_or_path, i, dtype=compute_dtype,
                    family=family, cfg=cfg, revision=revision, cache_dir=cache_dir,
                ),
                family.name,
                QuantType(quant_type),
            )
            for i in range(n_blocks)
        ]
        client_params = load_client_params(
            model_name_or_path, dtype=jnp.float32,
            family=family, cfg=cfg, revision=revision, cache_dir=cache_dir,
        )
        logger.info(
            f"Draft model {model_name_or_path}: {n_blocks} blocks "
            f"({quant_type}), window={window}, k={spec_k}"
        )
        return cls(
            family, cfg, block_params, client_params,
            spec_k=spec_k, window=window, compute_dtype=compute_dtype,
        )

    # --------------------------------------------------------------- program

    def _build_propose_fn(self):
        family, cfg = self.family, self.cfg
        k, W = self.spec_k, self.window
        hkv, d = self.num_kv_heads, self.head_dim
        n_blocks = len(self.block_params)
        dtype = self.compute_dtype
        client_embed, client_head = family.client_embed, family.client_head

        @tracked_jit(name="draft_propose", steady=True)
        def propose(block_params, client_params, tokens, lengths):
            # tokens: [n, W] int32 left-aligned; lengths: [n] int32 (0 =
            # lane sits this tick out; its row computes ignored garbage)
            n = tokens.shape[0]
            buf_len = W + k  # window prefill + k-1 decode writes, with slack
            caches = [
                (jnp.zeros((n, buf_len, hkv, d), dtype),
                 jnp.zeros((n, buf_len, hkv, d), dtype))
                for _ in range(n_blocks)
            ]

            def run(hidden, position):
                h = hidden.astype(dtype)
                for i, p_block in enumerate(block_params):
                    # the draft spans the whole model: block i by its kind, for a family whose blocks are not all alike
                    h, caches[i] = family.apply_for(family.kind_of(cfg, i))(
                        p_block, h, caches[i], position, cfg,
                        use_flash=False, tp_mesh=None,
                    )
                return h

            # window prefill at position 0: rows past each lane's length are
            # garbage, but causal masking keeps them out of the rows we read
            hidden = run(client_embed(client_params, tokens, cfg), 0)
            logits = client_head(client_params, hidden, cfg)  # [n, W, vocab]
            last = jnp.clip(lengths - 1, 0, W - 1)
            row = jnp.take_along_axis(logits, last[:, None, None], axis=1)[:, 0]
            tok = jnp.argmax(row, axis=-1).astype(jnp.int32)  # draft 1
            drafts = [tok]
            pos = jnp.maximum(lengths, 1)  # write the next token AT the length
            for _ in range(k - 1):
                h = run(client_embed(client_params, tok[:, None], cfg), pos)
                logits = client_head(client_params, h, cfg)[:, -1]
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                drafts.append(tok)
                pos = pos + 1
            return jnp.stack(drafts, axis=1)  # [n, k]

        return propose

    # ------------------------------------------------------------------ host

    @staticmethod
    def _buckets(max_lanes: int) -> List[int]:
        """Padded batch sizes the propose program compiles for: powers of two
        up to (and always including) ``max_lanes`` — O(log) executables."""
        out, b = [], 1
        while b < max_lanes:
            out.append(b)
            b <<= 1
        out.append(max(int(max_lanes), 1))
        return out

    def warmup(self, max_lanes: int) -> None:
        """Compile every bucket shape once, so steady state never compiles.

        The batcher calls this from the compute thread on the first spec
        tick: warmup calls land inside the observatory's per-program warmup
        budget, and afterwards any mix of speculating-lane counts hits a
        cached executable (the zero post-warmup recompile invariant)."""
        W = self.window
        for b in self._buckets(max_lanes):
            self._propose_fn(
                tuple(self.block_params), self.client_params,
                np.zeros((b, W), np.int32), np.zeros((b,), np.int32),
            )

    def propose(
        self, contexts: Sequence[Optional[Sequence[int]]]
    ) -> np.ndarray:
        """Greedy k-token proposals for a batch of lanes.

        ``contexts[i]`` is lane i's token history (prompt context, when the
        client supplied one, plus every generated token INCLUDING the last
        committed one) or None for lanes not speculating this tick. Returns
        int32 [len(contexts), k]; rows for None/empty contexts are garbage
        the caller must ignore.

        Active lanes are compacted to the front and padded to the smallest
        bucket (power of two, clamped to len(contexts)) before dispatch, so
        the compiled window-prefill cost tracks how many lanes actually
        speculate this tick rather than the pool size.
        """
        n = len(contexts)
        W = self.window
        active = [i for i, ctx in enumerate(contexts) if ctx]
        out = np.zeros((n, self.spec_k), np.int32)
        if not active:
            return out
        B = next(b for b in self._buckets(n) if b >= len(active))
        tokens = np.zeros((B, W), np.int32)
        lengths = np.zeros((B,), np.int32)
        for row, i in enumerate(active):
            tail = list(contexts[i])[-W:]
            tokens[row, : len(tail)] = tail
            lengths[row] = len(tail)
        drafts = self._propose_fn(
            tuple(self.block_params), self.client_params, tokens, lengths
        )
        drafts = np.asarray(drafts, np.int32)
        for row, i in enumerate(active):
            out[i] = drafts[row]
        return out


__all__ = ["DraftModel", "DEFAULT_WINDOW", "MIN_ACCEPT_ENV", "min_accept_floor"]
