"""Swarm-plane authentication: keypair-derived peer ids,
challenge/response hellos, signed DHT announcements."""

import asyncio

import pytest

from petals_tpu.dht.identity import (
    Identity,
    announce_message,
    peer_id_of,
    sign_announcement,
    verify,
    verify_announcement,
)
from petals_tpu.rpc import RpcClient
from petals_tpu.rpc.server import RpcServer


def run(coro):
    return asyncio.run(coro)


def test_identity_is_keypair_derived_and_deterministic():
    a = Identity.from_seed(b"seed-1")
    b = Identity.from_seed(b"seed-1")
    c = Identity.from_seed(b"seed-2")
    assert a.peer_id == b.peer_id != c.peer_id
    assert a.peer_id == peer_id_of(a.public_bytes)
    sig = a.sign(b"message")
    assert verify(a.public_bytes, sig, b"message")
    assert not verify(a.public_bytes, sig, b"other")
    assert not verify(c.public_bytes, sig, b"message")


def test_announcement_sign_verify_and_tamper():
    ident = Identity.generate()
    record = sign_announcement(ident, "m.3", {"info": [2, 1.5]}, 12345.678)
    subkey = ident.peer_id.to_string()
    assert verify_announcement(record, subkey, 12345.678)
    # wrong subkey (someone else's id)
    other = Identity.generate().peer_id.to_string()
    assert not verify_announcement(record, other, 12345.678)
    # tampered payload / uid / expiration
    tampered = dict(record, payload={"info": [2, 999.0]})
    assert not verify_announcement(tampered, subkey, 12345.678)
    tampered = dict(record, uid="m.4")
    assert not verify_announcement(tampered, subkey, 12345.678)
    assert not verify_announcement(record, subkey, 99999.0)
    # unsigned / malformed
    assert not verify_announcement({"payload": 1}, subkey, 12345.678)
    assert not verify_announcement("not-a-dict", subkey, 12345.678)
    assert announce_message("m.3", subkey, {"a": 1}, 1.0) == announce_message(
        "m.3", subkey, {"a": 1}, 1.0
    )


def test_hello_authentication_proves_both_sides():
    server_ident = Identity.generate()
    client_ident = Identity.generate()
    seen = {}

    async def who(payload, ctx):
        seen["remote"] = ctx.remote_peer_id
        return {"ok": True}

    async def main():
        server = RpcServer(identity=server_ident)
        server.add_unary_handler("who", who)
        await server.start()
        try:
            client = await RpcClient.connect(
                "127.0.0.1", server.port, identity=client_ident
            )
            await client.call("who", {}, timeout=10)
            # server saw the PROVEN client id (not just a claim)
            assert seen["remote"] == client_ident.peer_id
            # give the auth round-trip a beat, then check the server's proof
            for _ in range(50):
                if client.remote_peer_id is not None:
                    break
                await asyncio.sleep(0.02)
            assert client.remote_peer_id == server_ident.peer_id
            await client.close()
        finally:
            await server.stop()

    run(main())


def test_unauthenticated_claim_is_not_trusted():
    """A peer id claimed in a hello WITHOUT a key proof must never become
    ctx.remote_peer_id."""
    from petals_tpu.data_structures import PeerID

    server_ident = Identity.generate()
    seen = {}

    async def who(payload, ctx):
        seen["remote"] = ctx.remote_peer_id
        return {"ok": True}

    async def main():
        server = RpcServer(identity=server_ident)
        server.add_unary_handler("who", who)
        await server.start()
        try:
            # legacy client: claims an id but has no identity/keypair
            client = await RpcClient.connect(
                "127.0.0.1", server.port, peer_id=PeerID.generate()
            )
            await client.call("who", {}, timeout=10)
            assert seen["remote"] is None, "unproven claim must not be trusted"
            await client.close()
        finally:
            await server.stop()

    run(main())


def test_invalid_proof_closes_connection():
    server_ident = Identity.generate()
    honest = Identity.generate()

    async def main():
        server = RpcServer(identity=server_ident)
        server.add_unary_handler("who", lambda p, c: _ok())
        await server.start()
        try:
            client = await RpcClient.connect("127.0.0.1", server.port, identity=honest)
            # overwrite the pending auth with a forged signature for a
            # DIFFERENT claimed id: the server must drop the connection
            client2 = await RpcClient.connect("127.0.0.1", server.port, identity=honest)
            await asyncio.sleep(0.1)
            await client2._send({"t": "auth", "sig": "00" * 64})
            with pytest.raises(Exception):
                await client2.call("who", {}, timeout=2)
            await client.close()
            try:
                await client2.close()
            except Exception:
                pass
        finally:
            await server.stop()

    run(main())


async def _ok():
    return {"ok": True}
