"""Asyncio RPC server: unary + bidirectional-streaming methods over the framed
msgpack protocol (the role of hivemind's ServicerBase/ConnectionHandler RPC
surface in the reference — src/petals/server/handler.py:55 serves 7 such
methods; this server hosts them all in one process).
"""

from __future__ import annotations

import asyncio
import dataclasses
import time
import traceback
from typing import Any, AsyncIterator, Awaitable, Callable, Dict, Optional

from petals_tpu.data_structures import PeerID
from petals_tpu.rpc.protocol import decode_frame, read_frame_body, write_frame
from petals_tpu.utils.logging import get_logger
from petals_tpu.utils.tracing import device_annotation

logger = get_logger(__name__)

_END = object()

# Per-call inbound buffer bound. A well-behaved streaming client (inference
# session) keeps at most a couple of steps in flight; a peer stuffing frames
# faster than the handler consumes would otherwise grow the queue — and server
# memory — without limit (frames can be up to MAX_FRAME_BYTES each).
MAX_INBOUND_QUEUE = 128


class RpcError(Exception):
    """Error raised on the caller when the remote handler failed."""


@dataclasses.dataclass
class RpcContext:
    local_peer_id: Optional[PeerID]
    remote_peer_id: Optional[PeerID]
    remote_addr: tuple


class StreamRequests:
    """The inbound side of one streaming call, as its handler sees it: an
    async iterator over the call's items that this stream alone owns
    (``RpcContext`` is shared by every stream of a connection), with two
    readings of the server's own clock (``time.perf_counter``). A handler may
    read them, and one that does not loses nothing; neither crosses the wire.

    ``read_at``: when the frame of the item last handed out lay whole in
    memory, before it was unpacked. ``sent_s``: the seconds the item last
    yielded took to send: packing, the wait for the connection's write lock,
    ``writer.write`` and the drain of the transport's buffer.

    ``sink``: a handler may set it to a ``callable(item, read_at) -> bool`` and
    take items without the queue. The connection's reader then calls it with
    an ``sitem``'s payload in the turn that read the frame, on the reader's own
    task, so frames that came in one ``recv`` are taken in one pass with no
    queue hop and no task woken an item. It must not await; True says the item
    is the sink's, False leaves it to the queue, a raise fails this call alone
    (the peer gets the ``resp`` a raise in the handler would have sent). Which
    frames may bypass the queue: an ``sitem`` only, and only while the
    iterator's consumer is parked in ``__anext__`` on an EMPTY queue. Order
    within a stream holds because of that: an item is only ever handed over
    when nothing of its stream is queued or on its way from the queue to the
    consumer (a ``put`` leaves the item in the queue until the consumer has
    run), so whatever arrives behind a queued item is queued behind it, and
    the half-close (``send``) always takes the queue."""

    __slots__ = ("_queue", "_ended", "_parked", "read_at", "sent_s", "sink")

    def __init__(self):
        # Per-call inbound buffer, bounded (MAX_INBOUND_QUEUE, above).
        self._queue: asyncio.Queue = asyncio.Queue(maxsize=MAX_INBOUND_QUEUE)
        self._ended = False
        self._parked = False  # the consumer waits in __anext__, nothing handed to it yet
        self.read_at: Optional[float] = None
        self.sent_s: Optional[float] = None
        self.sink: Optional[Callable[[Any, float], bool]] = None

    def __aiter__(self):
        return self

    async def __anext__(self):
        if not self._ended:
            self._parked = True
            try:
                item, read_at = await self._queue.get()
            finally:
                self._parked = False  # in the step of the task that took the item: no turn between
            if item is not _END:
                self.read_at = read_at
                return item
            self._ended = True
        raise StopAsyncIteration

    def deliver(self, item: Any, read_at: float) -> None:
        """The reader's side of an ``sitem``: to the sink if it may go there and
        the sink takes it, else into the queue (``asyncio.QueueFull`` past the
        bound)."""
        sink = self.sink
        if sink is not None and self._parked and self._queue.empty() and sink(item, read_at):
            return
        self._queue.put_nowait((item, read_at))

    def end(self, read_at: float) -> None:
        """The reader's side of a ``send``: the half-close, behind whatever is queued."""
        self._queue.put_nowait((_END, read_at))


UnaryHandler = Callable[[Any, RpcContext], Awaitable[Any]]
StreamHandler = Callable[[AsyncIterator[Any], RpcContext], AsyncIterator[Any]]


class RpcServer:
    def __init__(
        self,
        peer_id: Optional[PeerID] = None,
        host: str = "127.0.0.1",
        port: int = 0,
        identity=None,  # dht.identity.Identity: enables authenticated hellos
    ):
        self.identity = identity
        self.peer_id = identity.peer_id if identity is not None else peer_id
        self.host, self._requested_port = host, port
        self._unary: Dict[str, UnaryHandler] = {}
        self._stream: Dict[str, StreamHandler] = {}
        self._server: Optional[asyncio.AbstractServer] = None
        self._conn_tasks: set = set()

    def add_unary_handler(self, method: str, fn: UnaryHandler) -> None:
        self._unary[method] = fn

    def add_stream_handler(self, method: str, fn: StreamHandler) -> None:
        self._stream[method] = fn

    async def start(self) -> None:
        self._server = await asyncio.start_server(self._on_connection, self.host, self._requested_port)
        logger.debug(f"RpcServer listening on {self.listen_addr}")

    @property
    def port(self) -> int:
        assert self._server is not None, "server not started"
        return self._server.sockets[0].getsockname()[1]

    @property
    def listen_addr(self) -> str:
        return f"{self.host}:{self.port}"

    async def stop(self) -> None:
        if self._server is not None:
            self._server.close()
        # Cancel live connections BEFORE wait_closed(): since py3.12 wait_closed
        # also waits for active connection handlers to finish.
        for task in list(self._conn_tasks):
            task.cancel()
        if self._conn_tasks:
            await asyncio.gather(*self._conn_tasks, return_exceptions=True)
        if self._server is not None:
            await self._server.wait_closed()

    # ------------------------------------------------------------------ connection

    async def _on_connection(self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter):
        task = asyncio.current_task()
        self._conn_tasks.add(task)
        write_lock = asyncio.Lock()
        call_tasks: Dict[int, asyncio.Task] = {}
        inbound: Dict[int, StreamRequests] = {}
        ctx = RpcContext(
            local_peer_id=self.peer_id,
            remote_peer_id=None,
            remote_addr=writer.get_extra_info("peername") or ("?", 0),
        )
        import secrets

        our_nonce = secrets.token_bytes(16)
        client_pub: Optional[bytes] = None
        client_claimed: Optional[PeerID] = None
        try:
            hello = {"t": "hello", "peer_id": self.peer_id.to_string() if self.peer_id else None}
            if self.identity is not None:
                hello["pub"] = self.identity.public_bytes.hex()
                hello["nonce"] = our_nonce.hex()
            await write_frame(writer, hello, write_lock)
            while True:
                body = await read_frame_body(reader)
                read_at = time.perf_counter()
                with device_annotation("ptu.rpc.recv"):
                    msg = decode_frame(body)
                kind = msg.get("t")
                if kind == "hello":
                    # claims are recorded but remote_peer_id is set ONLY after
                    # a valid "auth" proof — hello alone cannot impersonate
                    client_pub = bytes.fromhex(msg["pub"]) if msg.get("pub") else None
                    client_claimed = (
                        PeerID.from_string(msg["peer_id"]) if msg.get("peer_id") else None
                    )
                    if (
                        self.identity is not None
                        and client_pub is not None
                        and msg.get("nonce")
                    ):
                        # prove OUR identity to the client: sign its nonce,
                        # with our own key bound into the message
                        from petals_tpu.dht.identity import hello_challenge_message

                        sig = self.identity.sign(
                            hello_challenge_message(
                                self.identity.public_bytes,
                                client_pub,
                                bytes.fromhex(msg["nonce"]),
                            )
                        )
                        await write_frame(writer, {"t": "auth", "sig": sig.hex()}, write_lock)
                elif kind == "auth":
                    from petals_tpu.dht import identity as ident

                    if self.identity is None or client_pub is None:
                        continue
                    try:
                        sig = bytes.fromhex(msg.get("sig") or "")
                    except ValueError:
                        sig = b""
                    message = ident.hello_challenge_message(
                        client_pub, self.identity.public_bytes, our_nonce
                    )
                    proven = ident.peer_id_of(client_pub)
                    if ident.verify(client_pub, sig, message) and (
                        client_claimed is None or proven == client_claimed
                    ):
                        ctx.remote_peer_id = proven
                    else:
                        logger.warning(
                            f"Rejecting peer {ctx.remote_addr}: invalid identity proof"
                        )
                        break  # close the connection
                elif kind == "req":
                    call_tasks[msg["id"]] = asyncio.create_task(
                        self._run_unary(msg, ctx, writer, write_lock, call_tasks)
                    )
                elif kind == "sopen":
                    requests = inbound[msg["id"]] = StreamRequests()
                    call_tasks[msg["id"]] = asyncio.create_task(
                        self._run_stream(msg, requests, ctx, writer, write_lock, call_tasks, inbound)
                    )
                elif kind in ("sitem", "send"):
                    requests = inbound.get(msg["id"])
                    if requests is not None:
                        try:
                            if kind == "send":
                                requests.end(read_at)
                            else:
                                requests.deliver(msg.get("payload"), read_at)
                        except asyncio.QueueFull:
                            # The handler is MAX_INBOUND_QUEUE frames behind this
                            # peer: abusive or wedged either way. Kill the call
                            # instead of buffering its frames unboundedly.
                            logger.warning(
                                f"Inbound queue overflow on call {msg['id']} from "
                                f"{ctx.remote_addr}; cancelling the call"
                            )
                            # tell the peer: its pending recv should fail fast,
                            # not hang until its own timeout
                            await self._abort_stream(
                                msg["id"], "RpcError: inbound queue overflow, call cancelled",
                                writer, write_lock, call_tasks, inbound,
                            )
                        except Exception as e:
                            # the stream's sink raised on this item: that call's
                            # failure, as a raise in its handler is, not this loop's
                            logger.debug(f"Stream sink of call {msg['id']} failed: {e}\n{traceback.format_exc()}")
                            await self._abort_stream(
                                msg["id"], _format_error(e), writer, write_lock, call_tasks, inbound
                            )
                elif kind == "cancel":
                    task_to_cancel = call_tasks.get(msg["id"])
                    if task_to_cancel is not None:
                        task_to_cancel.cancel()
                else:
                    logger.warning(f"Unknown frame kind {kind!r} from {ctx.remote_addr}")
        except (asyncio.IncompleteReadError, ConnectionResetError, BrokenPipeError):
            pass  # remote disconnected
        except asyncio.CancelledError:
            raise
        except Exception:
            logger.exception(f"Connection loop failed for {ctx.remote_addr}")
        finally:
            for call_task in call_tasks.values():
                call_task.cancel()
            if call_tasks:
                await asyncio.gather(*call_tasks.values(), return_exceptions=True)
            writer.close()
            self._conn_tasks.discard(task)

    @staticmethod
    async def _abort_stream(call_id, error: str, writer, write_lock, call_tasks, inbound) -> None:
        """Cancel a streaming call from the reader's side and answer it as failed."""
        stuck = call_tasks.get(call_id)
        if stuck is not None:
            stuck.cancel()
        inbound.pop(call_id, None)
        await write_frame(writer, {"t": "resp", "id": call_id, "ok": False, "error": error}, write_lock)

    async def _run_unary(self, msg, ctx, writer, write_lock, call_tasks):
        call_id = msg["id"]
        try:
            handler = self._unary.get(msg.get("method"))
            if handler is None:
                raise RpcError(f"Unknown unary method {msg.get('method')!r}")
            result = await handler(msg.get("payload"), ctx)
            await write_frame(writer, {"t": "resp", "id": call_id, "ok": True, "payload": result}, write_lock)
        except asyncio.CancelledError:
            raise
        except Exception as e:
            logger.debug(f"Unary {msg.get('method')} failed: {e}\n{traceback.format_exc()}")
            try:
                await write_frame(
                    writer, {"t": "resp", "id": call_id, "ok": False, "error": _format_error(e)}, write_lock
                )
            except (ConnectionError, RuntimeError):
                pass
        finally:
            call_tasks.pop(call_id, None)

    async def _run_stream(self, msg, requests, ctx, writer, write_lock, call_tasks, inbound):
        call_id = msg["id"]
        try:
            handler = self._stream.get(msg.get("method"))
            if handler is None:
                raise RpcError(f"Unknown stream method {msg.get('method')!r}")
            async for item in handler(requests, ctx):
                yielded = time.perf_counter()
                await write_frame(
                    writer, {"t": "sitem", "id": call_id, "payload": item}, write_lock,
                    span=device_annotation("ptu.rpc.send"),
                )
                requests.sent_s = time.perf_counter() - yielded
            await write_frame(writer, {"t": "send", "id": call_id}, write_lock)
        except asyncio.CancelledError:
            raise
        except Exception as e:
            logger.debug(f"Stream {msg.get('method')} failed: {e}\n{traceback.format_exc()}")
            try:
                await write_frame(
                    writer, {"t": "resp", "id": call_id, "ok": False, "error": _format_error(e)}, write_lock
                )
            except (ConnectionError, RuntimeError):
                pass
        finally:
            call_tasks.pop(call_id, None)
            inbound.pop(call_id, None)


def _format_error(e: Exception) -> str:
    return f"{type(e).__name__}: {e}"
