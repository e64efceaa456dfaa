"""PyTorch adapter: train through the swarm from a torch pipeline.

The reference's client IS a torch ``transformers`` model (BASELINE north star:
"the RemoteSequential client stays PyTorch"); this build's native client is
JAX. This module gives torch users the same training surface against the same
swarm, without duplicating any model math:

- ``TorchRemoteSequential``: a ``torch.nn.Module`` whose forward/backward run
  the fault-tolerant swarm pipeline (client/sequential_autograd.py) through a
  ``torch.autograd.Function`` — torch gradients flow straight through remote
  servers (which recompute activations, reference block_functions.py:84-141).
- ``TorchDistributedModelForCausalLM``: embeddings + LM head evaluated by the
  native (JAX) client hooks, exposed to torch autograd via ``jax.vjp``; soft
  prompts are a plain ``torch.nn.Parameter`` trained by any torch optimizer.
  The loss formula matches client/training.compute_loss_and_grads exactly, so
  torch-side gradients are numerically identical to the native path.

Known v1 limits: ``generate()`` delegates to the native sampler and does not
apply the torch-held soft prompts; deep (per-block) prompts stay native-only.
"""

from __future__ import annotations

from types import SimpleNamespace
from typing import Optional, Sequence

import numpy as np

import torch  # CPU torch; tensors bridge via numpy (zero-copy on CPU)

from petals_tpu.client.model import DistributedModelForCausalLM
from petals_tpu.client.remote_sequential import RemoteSequential
from petals_tpu.utils.logging import get_logger

logger = get_logger(__name__)


class _RemoteBlocksFn(torch.autograd.Function):
    """Differentiable swarm chain: forward keeps per-span activations, backward
    replays them through rpc_backward on (possibly different) servers."""

    @staticmethod
    def forward(ctx, hidden: torch.Tensor, remote: RemoteSequential):
        np_hidden = np.ascontiguousarray(hidden.detach().cpu().numpy(), dtype=np.float32)
        out, histories, spans = remote.forward_with_state(np_hidden)
        ctx.remote, ctx.histories, ctx.spans = remote, histories, spans
        return torch.from_numpy(np.ascontiguousarray(out)).to(hidden.dtype)

    @staticmethod
    def backward(ctx, grad_out: torch.Tensor):
        grad_np = np.ascontiguousarray(grad_out.detach().cpu().numpy(), dtype=np.float32)
        grad_in, _ = ctx.remote.backward(grad_np, ctx.histories, ctx.spans)
        return torch.from_numpy(np.ascontiguousarray(grad_in)).to(grad_out.dtype), None


class _JaxFn(torch.autograd.Function):
    """Torch autograd over a frozen jax function of one array (the client
    embed/head hooks): forward runs jax.vjp, backward applies it."""

    @staticmethod
    def forward(ctx, x: torch.Tensor, jax_fn):
        import jax
        import jax.numpy as jnp

        out, vjp = jax.vjp(jax_fn, jnp.asarray(x.detach().cpu().numpy()))
        ctx.vjp, ctx.in_dtype = vjp, x.dtype
        # copy: np.asarray over a jax array is a read-only XLA-buffer view, and
        # torch.from_numpy would alias it (in-place torch ops -> UB in jax)
        return torch.from_numpy(np.array(out, copy=True))

    @staticmethod
    def backward(ctx, grad_out: torch.Tensor):
        import jax.numpy as jnp

        (grad_in,) = ctx.vjp(jnp.asarray(grad_out.detach().cpu().numpy()))
        return torch.from_numpy(np.array(grad_in, np.float32, copy=True)).to(ctx.in_dtype), None


class TorchRemoteSequential(torch.nn.Module):
    """The chain of remote blocks as a differentiable torch module."""

    def __init__(self, remote: RemoteSequential):
        super().__init__()
        self.remote = remote

    def forward(self, hidden: torch.Tensor) -> torch.Tensor:
        if not torch.is_grad_enabled():
            # eval path: no per-span activation histories retained
            np_hidden = np.ascontiguousarray(hidden.detach().cpu().numpy(), dtype=np.float32)
            return torch.from_numpy(np.ascontiguousarray(self.remote.forward(np_hidden))).to(hidden.dtype)
        return _RemoteBlocksFn.apply(hidden, self.remote)

    def close(self) -> None:
        self.remote.close()


class TorchDistributedModelForCausalLM(torch.nn.Module):
    """HF-style causal LM for torch pipelines: local embed/head (native JAX
    hooks under torch autograd), remote blocks, torch-held soft prompts."""

    def __init__(self, native: DistributedModelForCausalLM, *, pre_seq_len: int = 0):
        super().__init__()
        if native.family.block_stream is not None:
            raise NotImplementedError(
                f"{native.family.name}: the torch surface is not served for a family whose hidden state between blocks is "
                f"a stream wider than the model ({native.family.stream_for(native.cfg)[0]} against "
                f"{native.cfg.hidden_size}): its soft prompts and its callers' hooks take rows of hidden_size"
            )
        self.native = native
        self.cfg = native.cfg
        self.blocks = TorchRemoteSequential(native.remote)
        self.pre_seq_len = pre_seq_len
        if pre_seq_len > 0:
            # same init scale as the native ptune prompts (client/ptune.py:
            # 1/sqrt(hidden_size))
            self.prompt_embeddings = torch.nn.Parameter(
                torch.randn(pre_seq_len, self.cfg.hidden_size)
                / float(np.sqrt(self.cfg.hidden_size))
            )
        else:
            self.prompt_embeddings = None

    @classmethod
    def from_pretrained(
        cls,
        model_name_or_path: str,
        *,
        initial_peers: Sequence[str],
        pre_seq_len: int = 0,
        **kwargs,
    ) -> "TorchDistributedModelForCausalLM":
        if "ptune" in kwargs:
            # two prompt states (random JAX prompts in generate, torch prompts
            # in training) would silently diverge — prompts live torch-side here
            raise ValueError("use pre_seq_len= (torch-held prompts), not ptune=")
        native = DistributedModelForCausalLM.from_pretrained(
            model_name_or_path, initial_peers=initial_peers, **kwargs
        )
        return cls(native, pre_seq_len=pre_seq_len)

    # ------------------------------------------------------------------ forward

    def embed_tokens(self, input_ids: torch.Tensor) -> torch.Tensor:
        """Frozen token embeddings via the native hook (no grad to weights —
        matching the reference's frozen-client-embedding training setup)."""
        hidden = self.native.embed(np.asarray(input_ids.cpu().numpy()), with_prompts=False)
        return torch.from_numpy(np.array(hidden, np.float32, copy=True))

    def forward(
        self,
        input_ids: torch.Tensor,  # [batch, seq] int64
        labels: Optional[torch.Tensor] = None,  # [batch, seq], -100 = ignored
    ) -> SimpleNamespace:
        batch, seq = input_ids.shape
        hidden = self.embed_tokens(input_ids)
        if self.prompt_embeddings is not None:
            prompts = self.prompt_embeddings.unsqueeze(0).expand(batch, -1, -1)
            hidden = torch.cat([prompts.to(hidden.dtype), hidden], dim=1)

        hidden = self.blocks(hidden)

        head_fn = lambda h: self.native._head_jit(self.native.client_params, h)  # noqa: E731
        if torch.is_grad_enabled():
            logits_full = _JaxFn.apply(hidden, head_fn)  # [batch, pre+seq, vocab] f32
        else:  # eval path: plain jitted head, no vjp residuals
            logits_full = torch.from_numpy(
                np.array(head_fn(hidden.detach().cpu().numpy()), copy=True)
            )

        loss = None
        if labels is not None:
            padded = labels
            if self.pre_seq_len:
                pad = torch.full(
                    (batch, self.pre_seq_len), -100, dtype=labels.dtype, device=labels.device
                )
                padded = torch.cat([pad, labels], dim=1)
            # identical formula to client/training.compute_loss_and_grads:
            # shift over the FULL (prompt + tokens) length, mean over real
            # targets — with the native path's max(count, 1) guard, so an
            # all-ignored batch yields 0, not 0/0 = NaN
            targets = padded[:, 1:].reshape(-1)
            ce_sum = torch.nn.functional.cross_entropy(
                logits_full[:, :-1].reshape(-1, logits_full.shape[-1]),
                targets, ignore_index=-100, reduction="sum",
            )
            loss = ce_sum / (targets != -100).sum().clamp(min=1)
        logits = logits_full[:, self.pre_seq_len :]
        return SimpleNamespace(loss=loss, logits=logits)

    # ------------------------------------------------------------------ misc

    @torch.no_grad()
    def generate(self, input_ids: torch.Tensor, **kwargs) -> torch.Tensor:
        """Delegates to the native sampler (token-identical to HF); the
        torch-held soft prompts are NOT applied (v1 limitation)."""
        out = self.native.generate(np.asarray(input_ids.cpu().numpy()), **kwargs)
        return torch.from_numpy(np.array(out, copy=True))

    def close(self) -> None:
        self.native.close()
