"""Client-side training through the swarm: loss + gradients for the
client-held trainable parameters (prompt embeddings, deep prompts, LM head)
with server blocks in the middle (counterpart of the reference's training
story — sequential_autograd + ptune + examples/benchmark_training.py:50-107;
servers stay stateless and recompute activations during backward).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from petals_tpu.client.model import DistributedModelForCausalLM
from petals_tpu.utils.logging import get_logger

logger = get_logger(__name__)


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray, ignore_index: int = -100) -> jnp.ndarray:
    logprobs = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    mask = labels != ignore_index
    safe = jnp.where(mask, labels, 0)
    nll = -jnp.take_along_axis(logprobs, safe[..., None], axis=-1)[..., 0]
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1)


def _swarm_loss_and_grads(model, input_ids: np.ndarray, back_fn) -> Tuple[float, Dict[str, jnp.ndarray]]:
    """Shared fault-tolerant sequential-autograd scaffolding:
    local embed (vjp) -> [swarm forward] -> ``back_fn`` head+loss (vjp) ->
    [swarm backward] -> local embed vjp. Servers stay stateless and recompute
    activations during backward."""
    params = model.trainable_params()
    batch = input_ids.shape[0]

    # ---- local front: embeddings (+ shallow prompts), tracked by vjp
    def front(trainable):
        if "prompt_embeddings" in trainable:
            model_prompts = trainable["prompt_embeddings"]
            token_embeds = model._embed_jit(model.client_params, np.asarray(input_ids))
            prompts = jnp.broadcast_to(
                model_prompts[None], (batch, *model_prompts.shape)
            ).astype(token_embeds.dtype)
            return jnp.concatenate([prompts, token_embeds], axis=1)
        return model._embed_jit(model.client_params, np.asarray(input_ids))

    hidden0, front_vjp = jax.vjp(front, params)

    deep_prompts = None
    if "deep_prompt_embeddings" in params:
        deep = params["deep_prompt_embeddings"]
        deep_prompts = np.broadcast_to(
            np.asarray(deep)[:, None], (deep.shape[0], batch, deep.shape[1], deep.shape[2])
        )

    # ---- swarm middle (no autodiff across the network; servers recompute)
    out_hidden, histories, spans = model.remote.forward_with_state(
        np.asarray(hidden0), prompts=deep_prompts
    )

    loss, back_vjp = jax.vjp(back_fn, jnp.asarray(out_hidden))
    (grad_out_hidden,) = back_vjp(jnp.ones_like(loss))

    # ---- swarm backward
    grad_hidden0, grad_deep = model.remote.backward(
        np.asarray(grad_out_hidden), histories, spans, prompts=deep_prompts
    )

    # ---- fold back into trainable params
    (grads,) = front_vjp(jnp.asarray(grad_hidden0, hidden0.dtype))
    grads = dict(grads)
    if "deep_prompt_embeddings" in params:
        if grad_deep is not None:
            # sum over the broadcast batch axis
            grads["deep_prompt_embeddings"] = jnp.asarray(grad_deep).sum(axis=1)
        else:
            grads["deep_prompt_embeddings"] = jnp.zeros_like(params["deep_prompt_embeddings"])
    return float(loss), grads


def compute_loss_and_grads(
    model: DistributedModelForCausalLM,
    input_ids: np.ndarray,
    labels: np.ndarray,
) -> Tuple[float, Dict[str, jnp.ndarray]]:
    """Causal-LM swarm training step: (loss, grads) over
    model.trainable_params() (prompt/deep-prompt embeddings under ptune)."""
    pre_seq = model.ptune.pre_seq_len if model.ptune.tuning_mode else 0
    batch = input_ids.shape[0]

    padded_labels = labels
    if pre_seq:
        pad = np.full((batch, pre_seq), -100, dtype=labels.dtype)
        padded_labels = np.concatenate([pad, labels], axis=1)

    def back(out_hidden):
        logits = model._head_jit(model.client_params, out_hidden)
        shifted = logits[:, :-1]
        targets = jnp.asarray(padded_labels)[:, 1:]
        return cross_entropy(shifted, targets)

    return _swarm_loss_and_grads(model, input_ids, back)


def compute_cls_loss_and_grads(
    model,  # DistributedModelForSequenceClassification
    input_ids: np.ndarray,
    labels: np.ndarray,  # [batch] class ids
) -> Tuple[float, Dict[str, jnp.ndarray]]:
    """Classification swarm training step (the cls task of the reference's
    own benchmark_training script, lines 50-107): cross-entropy on the pooled
    last-non-pad-token logits, grads for the ptune prompts."""
    input_ids = np.asarray(input_ids)
    pos = model.pool_positions(input_ids)
    batch = input_ids.shape[0]

    def back(out_hidden):
        logits = model._head_jit(model.client_params, out_hidden)  # [b, seq, labels]
        pooled = logits[jnp.arange(batch), jnp.asarray(pos)]
        return cross_entropy(pooled, jnp.asarray(labels))

    return _swarm_loss_and_grads(model, input_ids, back)


def sgd_step(model: DistributedModelForCausalLM, grads: Dict[str, jnp.ndarray], lr: float) -> None:
    params = model.trainable_params()
    model.set_trainable_params(
        {name: params[name] - lr * grads[name] for name in params}
    )
