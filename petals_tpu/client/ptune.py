"""Prompt tuning: client-held trainable prompts
(counterpart of reference src/petals/client/ptune.py:15-84).

- "ptune": `pre_seq_len` virtual tokens prepended to the input embeddings.
- "deep_ptune": additionally one trainable prompt per remote block, sent with
  every request and added server-side (the backend injects them between
  blocks).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class PTuneConfig:
    pre_seq_len: int = 0
    tuning_mode: Optional[str] = None  # None | "ptune" | "deep_ptune"


class PTuneMixin:
    """Requires self.cfg (hidden_size, num_hidden_layers) and self.remote."""

    def init_ptune(self, ptune: Optional[PTuneConfig], seed: int = 0) -> None:
        self.ptune = ptune or PTuneConfig()
        self.prompt_embeddings: Optional[jnp.ndarray] = None
        self.deep_prompt_embeddings: Optional[jnp.ndarray] = None
        if self.ptune.tuning_mode is None or self.ptune.pre_seq_len == 0:
            return
        family = getattr(self, "family", None)
        if family is not None and family.block_stream is not None:
            raise NotImplementedError(
                f"{family.name}: prompt tuning ({self.ptune.tuning_mode}) is not served for a family whose hidden state "
                f"between blocks is a stream wider than the model ({family.stream_for(self.cfg)[0]} against "
                f"{self.cfg.hidden_size}): a trained prompt is a row of hidden_size, and where it enters the stream's "
                f"rows is the model's to say"
            )
        key = jax.random.PRNGKey(seed)
        scale = 1.0 / np.sqrt(self.cfg.hidden_size)
        self.prompt_embeddings = (
            jax.random.normal(key, (self.ptune.pre_seq_len, self.cfg.hidden_size), jnp.float32) * scale
        )
        if self.ptune.tuning_mode == "deep_ptune":
            key2 = jax.random.PRNGKey(seed + 1)
            self.deep_prompt_embeddings = (
                jax.random.normal(
                    key2,
                    (self.cfg.num_hidden_layers, self.ptune.pre_seq_len, self.cfg.hidden_size),
                    jnp.float32,
                )
                * scale
            )

    def apply_shallow_prompts(self, hidden: jnp.ndarray) -> jnp.ndarray:
        """Prepend trainable prompt embeddings (only on full-sequence calls at
        position 0; generation steps never re-prepend)."""
        if self.prompt_embeddings is None or getattr(self, "_in_generation", False):
            return hidden
        batch = hidden.shape[0]
        prompts = jnp.broadcast_to(
            self.prompt_embeddings[None], (batch, *self.prompt_embeddings.shape)
        ).astype(hidden.dtype)
        return jnp.concatenate([prompts, hidden], axis=1)

    def deep_prompts_for_batch(self, batch: int) -> Optional[np.ndarray]:
        if self.deep_prompt_embeddings is None:
            return None
        deep = np.asarray(self.deep_prompt_embeddings)
        return np.broadcast_to(deep[:, None], (deep.shape[0], batch, deep.shape[1], deep.shape[2]))

    def strip_shallow_prompt_logits(self, logits: jnp.ndarray) -> jnp.ndarray:
        if self.prompt_embeddings is None:
            return logits
        return logits[:, self.ptune.pre_seq_len :]

    def trainable_params(self) -> dict:
        out = {}
        if self.prompt_embeddings is not None:
            out["prompt_embeddings"] = self.prompt_embeddings
        if self.deep_prompt_embeddings is not None:
            out["deep_prompt_embeddings"] = self.deep_prompt_embeddings
        return out

    def set_trainable_params(self, params: dict) -> None:
        if "prompt_embeddings" in params:
            self.prompt_embeddings = params["prompt_embeddings"]
        if "deep_prompt_embeddings" in params:
            self.deep_prompt_embeddings = params["deep_prompt_embeddings"]
