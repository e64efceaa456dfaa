"""Speculative decoding: a local draft model proposes tokens, the swarm
verifies them in one batched step, and the session's KV caches roll back past
rejected drafts (counterpart of reference
src/petals/models/llama/speculative_model.py:13-111 + the cache-rollback
plumbing at inference_session.py:242-247 / block_functions.py:163-168).

Greedy verification: draft tokens are accepted while they equal the target
model's argmax; output is token-identical to plain greedy decoding regardless
of draft quality — a bad draft only costs speed, never correctness.
"""

from __future__ import annotations

from typing import Callable, Optional

import numpy as np

from petals_tpu.utils.logging import get_logger

logger = get_logger(__name__)

# draft_fn(context_ids [seq], k) -> proposed next tokens [k]
DraftFn = Callable[[np.ndarray, int], np.ndarray]


def speculative_generate(
    model,
    draft_fn: DraftFn,
    input_ids: np.ndarray,  # [1, seq]
    *,
    max_new_tokens: int,
    speculative_tokens: int = 4,
    session=None,
) -> np.ndarray:
    """Greedy generation accelerated by draft-and-verify (batch 1)."""
    input_ids = np.asarray(input_ids)
    assert input_ids.shape[0] == 1, "speculative decoding is single-stream"
    k = max(int(speculative_tokens), 1)

    own_session = session is None
    if session is None:
        total = input_ids.shape[1] + max_new_tokens + k + 1
        session = model.remote.inference_session(max_length=total, batch_size=1)

    stats = {"steps": 0, "accepted": 0, "drafted": 0}
    try:
        # prefill everything except the last token (it rides with the drafts)
        generated = input_ids
        prefix, last = input_ids[:, :-1], input_ids[:, -1:]
        if prefix.shape[1] > 0:
            session.step(np.asarray(model.embed(prefix, with_prompts=False)))

        while generated.shape[1] - input_ids.shape[1] < max_new_tokens:
            budget = max_new_tokens - (generated.shape[1] - input_ids.shape[1])
            n_draft = min(k, max(budget - 1, 0))
            drafts = (
                np.asarray(draft_fn(generated[0], n_draft)).reshape(-1)[:n_draft]
                if n_draft > 0
                else np.empty(0, np.int64)
            )
            stats["drafted"] += len(drafts)

            # one verification step: [last_pending, d1 .. d_{n-1}]
            chunk = np.concatenate([generated[0, -1:], drafts[:-1]]) if len(drafts) else generated[0, -1:]
            chunk = chunk[None].astype(np.int64)
            base_position = session.position
            out_hidden = session.step(np.asarray(model.embed(chunk, with_prompts=False)))
            logits = np.asarray(model.lm_logits(out_hidden))[0]  # [len(chunk), vocab]
            targets = logits.argmax(axis=-1)  # g_1 .. g_len

            accepted = 0
            while accepted < len(drafts) and drafts[accepted] == targets[accepted]:
                accepted += 1
            if accepted < len(drafts):
                # first mismatch: keep the accepted prefix + the target's correction
                new_tokens = list(drafts[:accepted]) + [targets[accepted]]
            elif len(drafts) > 0:
                # all drafts accepted; the last draft was never FED, so there is
                # no "bonus" logit — it stays pending for the next round
                new_tokens = list(drafts)
            else:
                new_tokens = [targets[0]]  # plain greedy step (no draft budget)
            stats["accepted"] += accepted
            stats["steps"] += 1

            if accepted < len(drafts):
                # roll the swarm's KV back past the rejected suffix: keep the
                # pending token + accepted drafts only
                session.position = base_position + 1 + accepted

            new_tokens = np.asarray(new_tokens[: budget], dtype=np.int64)
            generated = np.concatenate([generated, new_tokens[None]], axis=1)

        if stats["drafted"]:
            logger.debug(
                f"Speculative: {stats['accepted']}/{stats['drafted']} drafts accepted "
                f"over {stats['steps']} verify steps"
            )
        return generated
    finally:
        if own_session:
            session.close()


def make_local_draft_fn(model_path: str, *, dtype=None) -> DraftFn:
    """Greedy draft from a small model run fully locally in JAX (the reference
    uses a small HF model on the client the same way)."""
    import jax.numpy as jnp

    from petals_tpu.client.from_pretrained import load_client_params
    from petals_tpu.server.from_pretrained import get_block_config, load_block_params

    dtype = dtype or jnp.float32
    family, cfg = get_block_config(model_path)
    client_params = load_client_params(model_path, dtype=dtype, family=family, cfg=cfg)
    blocks = [
        load_block_params(model_path, i, dtype=dtype, family=family, cfg=cfg)
        for i in range(cfg.num_hidden_layers)
    ]

    def draft(context: np.ndarray, k: int) -> np.ndarray:
        ids = np.asarray(context)[None]
        out = []
        for _ in range(k):
            hidden = family.client_embed(client_params, ids, cfg)
            for i, p in enumerate(blocks):  # each block by its kind, for a family whose blocks are not all alike
                hidden, _ = family.apply_for(family.kind_of(cfg, i))(p, hidden, None, 0, cfg)
            logits = family.client_head(client_params, hidden[:, -1:], cfg)
            nxt = int(np.asarray(logits)[0, -1].argmax())
            out.append(nxt)
            ids = np.concatenate([ids, [[nxt]]], axis=1)
        return np.asarray(out, np.int64)

    return draft
