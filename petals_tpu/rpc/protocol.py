"""Wire protocol: length-prefixed msgpack frames over asyncio TCP streams.

This is the swarm's inter-host data plane (the role libp2p streams play in the
reference — SURVEY.md §5.8). One TCP connection multiplexes many concurrent
calls; each call has a connection-local id. Message kinds:

  {"t": "hello", "peer_id": hex}                      — sent once by each side
  {"t": "req",  "id", "method", "payload"}            — unary request
  {"t": "resp", "id", "ok", "payload"|"error"}        — unary response / stream abort
  {"t": "sopen", "id", "method"}                      — open bidirectional stream
  {"t": "sitem", "id", "payload"}                     — stream item (either way)
  {"t": "send",  "id"}                                — half-close (either way)
  {"t": "cancel", "id"}                               — cancel in-flight call

Frames: 4-byte big-endian length + msgpack body. Payload tensors ride as
msgpack bin (see rpc/serialization.py).

Server-side generation rides the ``inference`` stream: a step item may carry
``"gen_tokens": n`` (generate n tokens on device from the step's output) and,
optionally, ``"gen_sampling"``, a dict validated by
:func:`validate_gen_sampling`:

  {"do_sample": bool, "temperature": f>0, "top_k": int>=0 (0=off),
   "top_p": f in (0,1] (1=off), "repetition_penalty": f>0 (1=off),
   "seed": int in [0, 2^31), "offset": int>=0, "context": [int token ids]?}

The PRNG contract is stateless: draw ``i`` of a stream seeded ``s`` uses
uniform(fold_in(PRNGKey(s), i)); ``offset`` is the first draw index of this
request, so a client can resume or replay the stream mid-generation.
``context`` (previously seen token ids) is only consulted when
repetition_penalty != 1.
"""

from __future__ import annotations

import asyncio
import contextlib
import struct
from typing import Any, Optional

import msgpack

MAX_FRAME_BYTES = 1 << 30  # 1 GiB hard cap; large tensors stream in chunks far below this
DEFAULT_CHUNK_BYTES = 4 << 20  # split tensors into ~4 MiB stream items
_NO_SPAN = contextlib.nullcontext()


async def read_frame_body(reader: asyncio.StreamReader) -> bytes:
    """The next frame's body, whole and still packed (a caller that times its
    own unpacking takes the two halves of ``read_frame`` apart here)."""
    header = await reader.readexactly(4)
    (length,) = struct.unpack(">I", header)
    if length > MAX_FRAME_BYTES:
        raise ValueError(f"Frame of {length} bytes exceeds the {MAX_FRAME_BYTES} byte cap")
    return await reader.readexactly(length)


def decode_frame(body: bytes) -> Any:
    return msgpack.unpackb(body, raw=False, strict_map_key=False)


async def read_frame(reader: asyncio.StreamReader) -> Any:
    return decode_frame(await read_frame_body(reader))


def encode_frame(message: Any) -> bytes:
    body = msgpack.packb(message, use_bin_type=True)
    if len(body) > MAX_FRAME_BYTES:
        raise ValueError(f"Frame of {len(body)} bytes exceeds the {MAX_FRAME_BYTES} byte cap")
    return struct.pack(">I", len(body)) + body


async def write_frame(writer: asyncio.StreamWriter, message: Any, lock: asyncio.Lock, span=None) -> None:
    """Pack ``message`` and hand it to the transport, then wait for the
    transport's buffer to drain below its mark. ``span`` is a context manager
    the caller wants around the synchronous half (pack and ``writer.write``):
    it is entered under the lock, so that it closes before anything yields."""
    async with lock:  # interleaving-safe: one frame at a time per connection
        with span or _NO_SPAN:
            writer.write(encode_frame(message))
        await writer.drain()


def validate_gen_sampling(payload: Any) -> Optional[dict]:
    """Normalize and validate a step item's ``gen_sampling`` dict (schema in
    the module docstring). Returns a clean dict with every field present, or
    None for a None payload. Raises ValueError on anything malformed — the
    handler turns that into a protocol error before touching the device."""
    if payload is None:
        return None
    if not isinstance(payload, dict):
        raise ValueError(f"gen_sampling must be a dict, got {type(payload).__name__}")
    out = {
        "do_sample": bool(payload.get("do_sample", False)),
        "temperature": float(payload.get("temperature", 1.0)),
        "top_k": int(payload.get("top_k", 0) or 0),
        "top_p": float(payload.get("top_p", 1.0) if payload.get("top_p") is not None else 1.0),
        "repetition_penalty": float(payload.get("repetition_penalty", 1.0) or 1.0),
        "seed": int(payload.get("seed", 0)),
        "offset": int(payload.get("offset", 0)),
    }
    if not out["temperature"] > 0:
        raise ValueError(f"gen_sampling.temperature must be > 0, got {out['temperature']}")
    if out["top_k"] < 0:
        raise ValueError(f"gen_sampling.top_k must be >= 0, got {out['top_k']}")
    if not 0 < out["top_p"] <= 1:
        raise ValueError(f"gen_sampling.top_p must be in (0, 1], got {out['top_p']}")
    if not out["repetition_penalty"] > 0:
        raise ValueError(
            f"gen_sampling.repetition_penalty must be > 0, got {out['repetition_penalty']}"
        )
    if not 0 <= out["seed"] < 1 << 31:
        raise ValueError(f"gen_sampling.seed must be in [0, 2^31), got {out['seed']}")
    if out["offset"] < 0:
        raise ValueError(f"gen_sampling.offset must be >= 0, got {out['offset']}")
    context = payload.get("context")
    if context is not None:
        if not isinstance(context, (list, tuple)):
            raise ValueError("gen_sampling.context must be a list of token ids")
        out["context"] = [int(t) for t in context]
    return out
