"""Asyncio RPC client with connection multiplexing (the stub side of the wire
protocol — role of hivemind's StubBase in the reference, e.g.
TransformerConnectionHandler.get_stub at src/petals/server/handler.py).

One ``RpcClient`` owns one TCP connection; concurrent unary calls and streams
share it, matched by call id. Connection failures fail all in-flight calls —
retry/ban policy belongs to the routing layer above.
"""

from __future__ import annotations

import asyncio
import itertools
import queue
import socket
import threading
import time
from typing import Any, AsyncIterator, Optional

from petals_tpu import chaos
from petals_tpu.data_structures import PeerID
from petals_tpu.rpc.protocol import decode_frame, encode_frame, read_frame_body, write_frame
from petals_tpu.rpc.server import RpcError
from petals_tpu.utils.logging import get_logger

logger = get_logger(__name__)

_END = object()

# The largest frame a thread hands to the connection itself (``send_from_thread``):
# such a frame is written whole without waiting for ``drain``, so what keeps the
# transport's buffer bounded is that a stream has one of them out at a time and
# none is larger than this (a decode step's is 16-33 KB; a prompt's takes
# ``send``, its lock and its ``drain``).
THREAD_FRAME_BYTES = 1 << 17


class _Outlet:
    """A connection's write side, which two kinds of writer share: coroutines
    on the connection's loop (``write_frame`` takes this for its writer) and
    threads that are not the loop's (``write_from_thread``).

    The ordering rule, whichever thread writes: frames reach the wire whole
    and in the order they were handed over. One short lock lies around "look
    at what is buffered, then send or append" on both sides and is held for no
    blocking call (the socket is non-blocking, ``transport.write`` never
    waits). A thread writes its frame to the socket itself when the connection
    can take it whole right now: a plain TCP socket, nothing in the
    transport's buffer, nothing queued here. Otherwise the frame is queued
    for the loop, and so is what a partial ``send`` left over, in front of
    everything later: the loop writes the queue ahead of its own next frame,
    or on the turn a thread posted for it.

    The socket a thread sends on is a duplicate of the transport's (the
    public way to one: ``TransportSocket`` offers no ``send``); ``close``
    gives it back, so that the connection's end is the transport's to say."""

    def __init__(self, loop: asyncio.AbstractEventLoop, writer: asyncio.StreamWriter):
        self._loop, self._writer, self._transport = loop, writer, writer.transport
        self._lock = threading.Lock()
        self._queue: list = []  # threads' frames that are the loop's to write, oldest first
        self._posted = False  # a turn of the loop is on its way for the queue
        self._sock = self._plain_socket(self._transport)

    @staticmethod
    def _plain_socket(transport) -> Optional[socket.socket]:
        """None where a frame cannot be written beside the transport: no
        socket under it (a test's in-memory pair), TLS over it, not a stream."""
        if transport.get_extra_info("sslcontext") is not None or not hasattr(transport, "get_write_buffer_size"):
            return None
        sock = transport.get_extra_info("socket")
        if sock is None or sock.type != socket.SOCK_STREAM:
            return None
        try:
            return sock.dup()
        except OSError:  # no descriptor to spare: the loop writes every frame, as it did
            return None

    # ------------------------------------------------------------ the loop's side

    def write(self, data: bytes) -> None:
        with self._lock:
            self._write_queue()
            self._writer.write(data)

    async def drain(self) -> None:
        await self._writer.drain()

    def _flush(self) -> None:
        with self._lock:
            self._posted = False
            self._write_queue()

    def _write_queue(self) -> None:
        if self._queue:
            self._writer.write(b"".join(self._queue))
            self._queue.clear()

    # ------------------------------------------------------------ a thread's side

    def write_from_thread(self, frame: bytes) -> bool:
        """Whether this thread wrote the whole frame to the socket itself
        (False: it is the loop's to write, whole or from where ``send``
        stopped). A ``send`` that fails raises ``RpcError``."""
        with self._lock:
            sent = 0
            if self._sock is not None and not self._queue and self._transport_idle():
                try:
                    sent = self._sock.send(frame)
                except (BlockingIOError, InterruptedError):
                    pass
                except OSError as e:
                    raise RpcError(f"Connection lost: {type(e).__name__}: {e}") from e
                if sent == len(frame):
                    return True
            self._queue.append(frame[sent:] if sent else frame)
            if not self._posted:
                try:
                    self._loop.call_soon_threadsafe(self._flush)
                except RuntimeError as e:  # the loop is closed
                    self._queue.clear()
                    raise RpcError(f"Client connection is closed: {e}") from e
                self._posted = True
        return False

    def _transport_idle(self) -> bool:
        """Under the lock: the transport holds no byte and takes none until the
        lock is free (only ``write`` above hands it any)."""
        transport = self._transport
        try:
            return not transport.is_closing() and transport.get_write_buffer_size() == 0
        except RuntimeError:  # its buffer changed under the count: it holds bytes
            return False

    def close(self) -> None:
        with self._lock:
            sock, self._sock = self._sock, None
        if sock is not None:
            sock.close()


class StreamCall:
    """A bidirectional stream: ``send``/``end`` feed the server, iterate to read.

    One consumer at a time reads it, a coroutine on the connection's loop
    (``send`` / ``recv``) or a thread that is not the loop's
    (``send_from_thread`` / ``recv_in_thread``: no coroutine, task, timer or
    future is made, and the loop is crossed once each way). Both take their
    items from one thread-safe inbox, which the connection's reader fills, so
    a stream may change hands between two exchanges and whatever wakes a
    parked coroutine (an item, an abort, the connection lost, ``cancel``)
    wakes a parked thread.

    ``read_at`` is this process's ``time.perf_counter`` when the frame of the
    item last received lay whole in memory, before it was unpacked (what
    ``rpc/server.py StreamRequests.read_at`` is to a handler); it never
    crosses the wire."""

    def __init__(self, client: "RpcClient", call_id: int, method: Optional[str] = None):
        self._client = client
        self._call_id = call_id
        self._method = method  # chaos-injection detail for rpc.stream_recv
        self._inbound: queue.SimpleQueue = queue.SimpleQueue()  # (item, read_at), put on the loop
        self._waiter: Optional[asyncio.Future] = None  # of the coroutine parked in recv(); the loop's alone
        self._closed = False
        self.read_at: Optional[float] = None

    async def send(self, payload: Any) -> None:
        if self._closed:
            raise RpcError("Stream is closed")
        await self._client._send({"t": "sitem", "id": self._call_id, "payload": payload})

    async def end(self) -> None:
        """Half-close: no more requests will be sent."""
        await self._client._send({"t": "send", "id": self._call_id})

    async def recv(self, timeout: Optional[float] = None) -> Any:
        """Next response item; raises StopAsyncIteration at end of stream."""
        if chaos.ENABLED:
            await chaos.inject(chaos.SITE_RPC_STREAM_RECV, detail=self._method)
        while True:
            try:
                entry = self._inbound.get_nowait()
                break
            except queue.Empty:
                self._waiter = waiter = asyncio.get_running_loop().create_future()
                try:
                    await asyncio.wait_for(waiter, timeout)
                finally:
                    self._waiter = None
        return self._take(entry)

    def _take(self, entry: tuple) -> Any:
        item, read_at = entry
        if item is _END:
            self._closed = True
            raise StopAsyncIteration
        if isinstance(item, Exception):
            self._closed = True
            raise item
        self.read_at = read_at
        return item

    def send_from_thread(self, payload: Any) -> bool:
        """``send`` for a caller on a thread that is not the loop's: the frame
        is packed here and written to the connection's socket by this thread
        when the connection can take it whole right now (True), else handed
        to the loop, which writes it on a turn of its own (False): a prompt's
        frame is draining, a ``send`` came up short, the transport offers no
        plain socket. Either way frames reach the wire whole and in the order
        they were handed over, a coroutine's ``write_frame`` among them
        (``_Outlet``: its one lock and its rule). It does not wait for
        ``drain``: hence ``THREAD_FRAME_BYTES``. A ``send`` that fails raises
        ``RpcError``, as a connection that is gone does."""
        client = self._client
        if self._closed:
            raise RpcError("Stream is closed")
        if client._closed:
            raise RpcError("Client connection is closed")
        frame = encode_frame({"t": "sitem", "id": self._call_id, "payload": payload})
        if len(frame) > THREAD_FRAME_BYTES:
            raise ValueError(f"A frame of {len(frame)} bytes takes send(): over {THREAD_FRAME_BYTES}")
        return client._outlet.write_from_thread(frame)

    def recv_in_thread(self, timeout: Optional[float] = None) -> Any:
        """``recv`` for the same caller: parks the thread on the inbox."""
        try:
            entry = self._inbound.get(timeout=timeout)
        except queue.Empty:
            raise asyncio.TimeoutError() from None
        return self._take(entry)

    def __aiter__(self) -> AsyncIterator[Any]:
        return self

    async def __anext__(self) -> Any:
        return await self.recv()

    async def cancel(self) -> None:
        if not self._closed:
            self._closed = True
            self._push(RpcError("Stream is closed"))  # whoever is parked on it now
            try:
                await self._client._send({"t": "cancel", "id": self._call_id})
            except (ConnectionError, RpcError):
                pass
        self._client._streams.pop(self._call_id, None)

    def _push(self, item: Any, read_at: Optional[float] = None) -> None:
        """On the loop: the reader's, an abort's or ``cancel``'s item to whoever waits."""
        self._inbound.put((item, read_at))
        waiter = self._waiter
        if waiter is not None and not waiter.done():
            waiter.set_result(None)


class RpcClient:
    def __init__(self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter,
                 peer_id: Optional[PeerID] = None, identity=None):
        import secrets

        self._reader, self._writer = reader, writer
        self._loop = asyncio.get_running_loop()
        self._outlet = _Outlet(self._loop, writer)  # every frame's way out, a coroutine's and a thread's
        self._identity = identity
        self._peer_id = identity.peer_id if identity is not None else peer_id
        self._nonce = secrets.token_bytes(16)
        self._write_lock = asyncio.Lock()
        self._call_ids = itertools.count()
        self._pending: dict = {}  # call_id -> Future (unary)
        self._streams: dict = {}  # call_id -> StreamCall
        self._closed = False
        # set ONLY once the server PROVES the id by signing our nonce with the
        # key whose hash is the id — an unauthenticated hello proves nothing
        self.remote_peer_id: Optional[PeerID] = None
        self._server_pub: Optional[bytes] = None
        self._server_nonce: Optional[bytes] = None
        self._server_claimed: Optional[PeerID] = None
        # set once the server's hello is processed (and our auth proof sent):
        # connect() waits on it so our first request never overtakes the proof
        self._handshake_done = asyncio.Event()
        # set once the server's auth frame is processed (valid or not) — TCP
        # ordering puts it right after the hello when the server will prove
        self._auth_done = asyncio.Event()
        self._loop_task = asyncio.create_task(self._read_loop())

    async def _on_server_hello(self, msg) -> None:
        self._server_pub = bytes.fromhex(msg["pub"]) if msg.get("pub") else None
        self._server_nonce = bytes.fromhex(msg["nonce"]) if msg.get("nonce") else None
        self._server_claimed = (
            PeerID.from_string(msg["peer_id"]) if msg.get("peer_id") else None
        )
        if (
            self._identity is not None
            and self._server_pub is not None
            and self._server_nonce is not None
        ):
            from petals_tpu.dht.identity import hello_challenge_message

            sig = self._identity.sign(
                hello_challenge_message(
                    self._identity.public_bytes, self._server_pub, self._server_nonce
                )
            )
            await self._send({"t": "auth", "sig": sig.hex()})
        self._handshake_done.set()

    def _on_server_auth(self, msg) -> None:
        """The server's proof: its signature over OUR public key and nonce."""
        from petals_tpu.dht import identity as ident

        try:
            if self._server_pub is None or self._identity is None:
                return
            try:
                sig = bytes.fromhex(msg.get("sig") or "")
            except ValueError:
                return
            message = ident.hello_challenge_message(
                self._server_pub, self._identity.public_bytes, self._nonce
            )
            if not ident.verify(self._server_pub, sig, message):
                return
            proven = ident.peer_id_of(self._server_pub)
            if self._server_claimed is None or proven == self._server_claimed:
                self.remote_peer_id = proven
        finally:
            self._auth_done.set()

    async def wait_authenticated(self, timeout: float = 10.0) -> Optional[PeerID]:
        """Waits for the server's identity proof (if it advertised a key) and
        returns the PROVEN peer id — None if the server never proves or the
        proof is invalid. Callers pinning a peer id (relay circuits) must
        compare against this, not the unauthenticated hello claim."""
        if self._identity is None or self._server_pub is None:
            return self.remote_peer_id
        try:
            await asyncio.wait_for(self._auth_done.wait(), timeout)
        except asyncio.TimeoutError:
            pass
        return self.remote_peer_id

    @classmethod
    async def connect(
        cls, host: str, port: int, *, peer_id: Optional[PeerID] = None,
        identity=None, timeout: float = 10.0,
    ) -> "RpcClient":
        reader, writer = await asyncio.wait_for(asyncio.open_connection(host, port), timeout)
        return await cls.from_streams(
            reader, writer, peer_id=peer_id, identity=identity, timeout=timeout
        )

    @classmethod
    async def from_streams(
        cls, reader: asyncio.StreamReader, writer: asyncio.StreamWriter, *,
        peer_id: Optional[PeerID] = None, identity=None, timeout: float = 10.0,
    ) -> "RpcClient":
        """Handshake over an already-established byte stream (direct TCP or a
        relay splice — rpc/relay.py): the hello/auth exchange is end-to-end."""
        client = cls(reader, writer, peer_id, identity)
        hello = {"t": "hello", "peer_id": client._peer_id.to_string() if client._peer_id else None}
        if identity is not None:
            hello["pub"] = identity.public_bytes.hex()
            hello["nonce"] = client._nonce.hex()
        await client._send(hello)
        try:
            await asyncio.wait_for(client._handshake_done.wait(), timeout)
        except asyncio.TimeoutError:
            await client.close()
            raise
        if client._closed:
            raise RpcError("Connection closed during handshake")
        return client

    async def _send(self, message: Any) -> None:
        if self._closed:
            raise RpcError("Client connection is closed")
        await write_frame(self._outlet, message, self._write_lock)

    async def call(self, method: str, payload: Any = None, timeout: Optional[float] = None) -> Any:
        if chaos.ENABLED:
            await chaos.inject(chaos.SITE_RPC_CALL, detail=method)
        call_id = next(self._call_ids)
        future: asyncio.Future = asyncio.get_running_loop().create_future()
        self._pending[call_id] = future
        try:
            await self._send({"t": "req", "id": call_id, "method": method, "payload": payload})
            return await asyncio.wait_for(future, timeout)
        except (asyncio.TimeoutError, asyncio.CancelledError):
            # tell the server to stop working on this call (best effort)
            if not self._closed:
                try:
                    await self._send({"t": "cancel", "id": call_id})
                except (ConnectionError, RpcError):
                    pass
            raise
        finally:
            self._pending.pop(call_id, None)

    async def open_stream(self, method: str) -> StreamCall:
        if chaos.ENABLED:
            await chaos.inject(chaos.SITE_RPC_STREAM, detail=method)
        call_id = next(self._call_ids)
        stream = StreamCall(self, call_id, method)
        self._streams[call_id] = stream
        await self._send({"t": "sopen", "id": call_id, "method": method})
        return stream

    async def _read_loop(self) -> None:
        error: Exception = RpcError("Connection closed")
        try:
            while True:
                body = await read_frame_body(self._reader)
                read_at = time.perf_counter()
                msg = decode_frame(body)
                kind = msg.get("t")
                if kind == "hello":
                    await self._on_server_hello(msg)
                elif kind == "auth":
                    self._on_server_auth(msg)
                elif kind == "resp":
                    call_id = msg["id"]
                    if msg.get("ok"):
                        future = self._pending.get(call_id)
                        if future is not None and not future.done():
                            future.set_result(msg.get("payload"))
                    else:
                        exc = RpcError(msg.get("error", "remote error"))
                        future = self._pending.get(call_id)
                        if future is not None and not future.done():
                            future.set_exception(exc)
                        stream = self._streams.pop(call_id, None)
                        if stream is not None:
                            stream._push(exc)
                elif kind == "sitem":
                    stream = self._streams.get(msg["id"])
                    if stream is not None:
                        stream._push(msg.get("payload"), read_at)
                elif kind == "send":
                    stream = self._streams.pop(msg["id"], None)
                    if stream is not None:
                        stream._push(_END)
                else:
                    logger.warning(f"Unknown frame kind {kind!r} from server")
        except (asyncio.IncompleteReadError, ConnectionResetError, BrokenPipeError) as e:
            error = RpcError(f"Connection lost: {type(e).__name__}")
        except asyncio.CancelledError:
            pass
        except Exception as e:
            logger.exception("Client read loop crashed")
            error = RpcError(f"Client read loop crashed: {e}")
        finally:
            self._closed = True
            self._outlet.close()
            # unblock connect(): a connection that died mid-handshake should
            # fail immediately (connect checks _closed), not wait out the timeout
            self._handshake_done.set()
            self._auth_done.set()
            for future in self._pending.values():
                if not future.done():
                    future.set_exception(error)
            for stream in self._streams.values():
                stream._push(error)
            self._streams.clear()

    async def close(self) -> None:
        self._closed = True
        self._loop_task.cancel()
        try:
            await self._loop_task
        except asyncio.CancelledError:
            pass
        self._outlet.close()
        self._writer.close()
        try:
            await self._writer.wait_closed()
        except (ConnectionError, BrokenPipeError):
            pass
