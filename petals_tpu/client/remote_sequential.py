"""RemoteSequential: the chain of remote blocks as one callable module
(counterpart of reference src/petals/client/remote_sequential.py:20-58)."""

from __future__ import annotations

import time
from typing import List, Optional, Sequence

import numpy as np

from petals_tpu.client.config import ClientConfig
from petals_tpu.client.inference_session import InferenceSession
from petals_tpu.client.routing.sequence_manager import RemoteSequenceManager
from petals_tpu.client.runtime import SwarmRuntime
from petals_tpu.client.sequential_autograd import (
    sequential_backward_batched,
    sequential_forward_batched,
)
from petals_tpu.data_structures import ModuleUID


class RemoteSequential:
    """Synchronous facade over the async swarm stack."""

    def __init__(
        self,
        config: ClientConfig,
        block_uids: Sequence[ModuleUID],
        *,
        runtime: Optional[SwarmRuntime] = None,
        dht=None,
    ):
        self.config = config
        self.block_uids = tuple(block_uids)
        self._owns_runtime = runtime is None
        self.runtime = runtime or SwarmRuntime()
        self.sequence_manager: RemoteSequenceManager = self.runtime.run(
            RemoteSequenceManager.create(config, self.block_uids, dht=dht)
        )

    def __len__(self) -> int:
        return len(self.block_uids)

    def __getitem__(self, index) -> "RemoteSequential":
        """A sub-chain over a contiguous block range (the reference's
        RemoteSequential slicing, used for custom pipelines). The slice shares
        this instance's runtime and DHT node but OWNS its router (background
        refresh + connections): close() it when done, or use it as a context
        manager. Closing a slice never tears down the parent."""
        if isinstance(index, int):
            if index < 0:
                index += len(self)
            if not 0 <= index < len(self):
                raise IndexError("RemoteSequential index out of range")
            index = slice(index, index + 1)
        if not isinstance(index, slice):
            raise TypeError(f"Expected int or slice, got {type(index).__name__}")
        start, stop, step = index.indices(len(self))
        if step != 1 or stop <= start:
            raise ValueError("RemoteSequential slices must be contiguous and non-empty")
        return RemoteSequential(
            self.config,
            self.block_uids[start:stop],
            runtime=self.runtime,
            dht=self.sequence_manager.dht,
        )

    def __enter__(self) -> "RemoteSequential":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def forward(self, hidden: np.ndarray, prompts: Optional[np.ndarray] = None) -> np.ndarray:
        """Training-style forward (no server-side state); fault-tolerant."""
        out, _, _ = self.runtime.run(
            sequential_forward_batched(self.sequence_manager, np.asarray(hidden), prompts)
        )
        return out

    __call__ = forward

    def forward_with_state(self, hidden: np.ndarray, prompts: Optional[np.ndarray] = None):
        """Forward returning (output, histories, spans) for a later backward."""
        return self.runtime.run(
            sequential_forward_batched(self.sequence_manager, np.asarray(hidden), prompts)
        )

    def backward(
        self,
        grad_out: np.ndarray,
        histories: List,
        spans: List,
        prompts: Optional[np.ndarray] = None,
    ):
        return self.runtime.run(
            sequential_backward_batched(self.sequence_manager, np.asarray(grad_out), histories, spans, prompts)
        )

    def inference_session(self, max_length: int, batch_size: int = 1) -> "SyncInferenceSession":
        return SyncInferenceSession(
            InferenceSession(self.sequence_manager, max_length, batch_size), self.runtime
        )

    def update_routing(self) -> None:
        self.runtime.run(self.sequence_manager.update())

    def close(self) -> None:
        self.runtime.run(self.sequence_manager.shutdown())
        if self._owns_runtime:
            self.runtime.shutdown()


class SyncInferenceSession:
    """Blocking wrapper around the async InferenceSession."""

    def __init__(self, session: InferenceSession, runtime: SwarmRuntime):
        self._session = session
        self._runtime = runtime

    def step(
        self, hidden: np.ndarray, *, prompts: Optional[np.ndarray] = None, hypo_ids: Optional[np.ndarray] = None
    ) -> np.ndarray:
        """A plain step of an open session is exchanged by this thread itself
        (``InferenceSession.step_from_thread``); any other, a session's first
        included, is a coroutine on the runtime's loop, as is what is left of
        a step whose exchange failed."""
        session, hidden = self._session, np.asarray(hidden)
        trip = session.trip  # the caller's side of a step's stations (telemetry/spans.py)
        trip.entered(time.perf_counter())
        if prompts is None and hypo_ids is None and session.can_step_from_thread(hidden):
            out = session.step_from_thread(hidden, self._runtime.run)
        else:
            out = self._runtime.run(session.step(hidden, prompts=prompts, hypo_ids=hypo_ids))
        trip.woke(time.perf_counter())
        return out

    def generate_remote(self, hidden: np.ndarray, n_tokens: int, embed_fn,
                        sampling=None):
        return self._runtime.run(
            self._session.generate_remote(
                np.asarray(hidden), n_tokens, embed_fn, sampling=sampling
            )
        )

    @property
    def position(self) -> int:
        return self._session.position

    @position.setter
    def position(self, value: int) -> None:
        self._session.position = value

    @property
    def max_length(self) -> int:
        return self._session.max_length

    @property
    def batch_size(self) -> int:
        return self._session.batch_size

    def trace_report(self) -> dict:
        """The session's latency waterfall so far (``InferenceSession.trace_report``)."""
        return self._session.trace_report()

    @property
    def integrity(self):
        """The session's fingerprint cross-check monitor (divergence counts,
        digest continuity ring) — see telemetry/integrity.py."""
        return self._session.integrity

    def close(self) -> None:
        self._runtime.run(self._session.close())

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
