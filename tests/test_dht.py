"""DHT tests: routing table, storage semantics, and a real localhost swarm
(this layer replaces hivemind's DHT, so it gets direct coverage — the strategy
follows the reference's "real miniature swarm on localhost" approach,
SURVEY.md §4)."""

import asyncio
import time

import pytest

from petals_tpu.data_structures import PeerID
from petals_tpu.dht import DHTNode, PeerAddr
from petals_tpu.dht.routing import RoutingTable, bucket_index, xor_distance
from petals_tpu.dht.storage import DHTStorage


def run(coro):
    return asyncio.run(coro)


# ----------------------------------------------------------------- routing table


def test_xor_distance_and_buckets():
    a, b = PeerID.from_seed(b"a"), PeerID.from_seed(b"b")
    assert xor_distance(a, a) == 0
    assert xor_distance(a, b) == xor_distance(b, a) > 0
    assert 0 <= bucket_index(a, b) < 256


def test_routing_table_add_remove_nearest():
    own = PeerID.from_seed(b"own")
    table = RoutingTable(own, bucket_size=4)
    peers = [PeerAddr("127.0.0.1", 1000 + i, PeerID.from_seed(bytes([i]))) for i in range(32)]
    for p in peers:
        table.add(p)
    assert len(table) > 0
    target = PeerID.from_seed(b"target")
    nearest = table.nearest(target, 5)
    assert len(nearest) == 5
    dists = [xor_distance(p.peer_id, target) for p in nearest]
    assert dists == sorted(dists)
    table.remove(nearest[0].peer_id)
    assert table.get(nearest[0].peer_id) is None
    # own id is never added
    table.add(PeerAddr("127.0.0.1", 1, own))
    assert table.get(own) is None


def test_peer_addr_string_roundtrip():
    addr = PeerAddr("10.0.0.1", 31337, PeerID.generate())
    assert PeerAddr.from_string(addr.to_string()) == addr


# ----------------------------------------------------------------- storage


def test_storage_plain_and_expiry():
    storage = DHTStorage()
    now = time.time()
    assert storage.store(b"k", "v1", now + 10)
    assert storage.get(b"k")[0] == "v1"
    # stale write loses
    assert not storage.store(b"k", "v0", now + 5)
    assert storage.get(b"k")[0] == "v1"
    # fresher write wins
    assert storage.store(b"k", "v2", now + 20)
    assert storage.get(b"k")[0] == "v2"
    # expired records vanish
    assert storage.store(b"gone", "x", now + 0.05)
    time.sleep(0.1)
    assert storage.get(b"gone") is None
    # expired-at-write rejected
    assert not storage.store(b"dead", "x", now - 1)


def test_storage_subkeys():
    storage = DHTStorage()
    now = time.time()
    assert storage.store(b"k", {"block": 1}, now + 10, subkey="peerA")
    assert storage.store(b"k", {"block": 2}, now + 20, subkey="peerB")
    value, expiration = storage.get(b"k")
    assert set(value) == {"peerA", "peerB"}
    assert value["peerA"][0] == {"block": 1}
    assert expiration == pytest.approx(now + 20, abs=1)
    # per-subkey freshness
    assert not storage.store(b"k", {"block": 0}, now + 5, subkey="peerA")
    assert storage.store(b"k", {"block": 3}, now + 30, subkey="peerA")
    assert storage.get(b"k")[0]["peerA"][0] == {"block": 3}


# ----------------------------------------------------------------- live swarm


async def _make_swarm(n, **kwargs):
    bootstrap = await DHTNode.create(maintenance_period=1000, **kwargs)
    peers = [bootstrap]
    for _ in range(n - 1):
        node = await DHTNode.create(
            initial_peers=[bootstrap.own_addr], maintenance_period=1000, **kwargs
        )
        peers.append(node)
    return peers


async def _shutdown(nodes):
    await asyncio.gather(*(n.shutdown() for n in nodes))


def test_store_get_across_swarm():
    async def main():
        nodes = await _make_swarm(5)
        try:
            ok = await nodes[1].store("mykey", {"hello": "world"}, dht_expiration(10))
            assert ok
            for reader in (nodes[0], nodes[2], nodes[4]):
                record = await reader.get("mykey")
                assert record is not None, f"node {reader.peer_id} could not find the record"
                assert record[0] == {"hello": "world"}
            assert await nodes[3].get("missing-key") is None
        finally:
            await _shutdown(nodes)

    run(main())


def test_subkey_announcements_merge_across_swarm():
    """Two peers announce under the same key with different subkeys — readers
    must see both (the pattern behind declare_active_modules). Subkey records
    must be SIGNED by the subkey's keyholder to be accepted."""
    from petals_tpu.dht.identity import sign_announcement

    async def main():
        nodes = await _make_swarm(4)
        try:
            exp = dht_expiration(30)
            for node, payload in ((nodes[1], [2, 100.0]), (nodes[2], [2, 50.0])):
                await node.store(
                    "blocks.0",
                    sign_announcement(node.identity, "blocks.0", payload, exp),
                    exp,
                    subkey=node.peer_id.to_string(),
                )
            record = await nodes[3].get("blocks.0")
            assert record is not None
            subkeys = record[0]
            assert nodes[1].peer_id.to_string() in subkeys
            assert nodes[2].peer_id.to_string() in subkeys
            assert subkeys[nodes[1].peer_id.to_string()][0]["payload"] == [2, 100.0]
        finally:
            await _shutdown(nodes)

    run(main())


def test_unsigned_or_forged_subkey_records_rejected():
    """The swarm plane is authenticated: a peer cannot overwrite
    another peer's announcements — unsigned subkey stores and records signed
    by the WRONG key are rejected by honest storers."""
    from petals_tpu.dht.identity import sign_announcement

    async def main():
        nodes = await _make_swarm(3)
        try:
            exp = dht_expiration(30)
            victim = nodes[1]
            attacker = nodes[2]
            # 1) unsigned record under the victim's subkey: rejected remotely
            ok = await attacker.store(
                "blocks.0", {"fake": True}, exp, subkey=victim.peer_id.to_string()
            )
            # (local acceptance is irrelevant — attacker isn't in the lookup path
            # for readers who verify; remote stores must all have failed)
            record = await nodes[0].get("blocks.0")
            if record is not None:
                assert victim.peer_id.to_string() not in record[0]

            # 2) record SIGNED BY THE ATTACKER but claiming the victim's subkey
            forged = sign_announcement(attacker.identity, "blocks.0", {"fake": 2}, exp)
            await attacker.store(
                "blocks.0", forged, exp, subkey=victim.peer_id.to_string()
            )
            record = await nodes[0].get("blocks.0")
            if record is not None:
                assert victim.peer_id.to_string() not in record[0]

            # 3) the honest signed record still lands
            good = sign_announcement(victim.identity, "blocks.0", {"real": 1}, exp)
            assert await victim.store(
                "blocks.0", good, exp, subkey=victim.peer_id.to_string()
            )
            record = await nodes[0].get("blocks.0")
            assert record is not None and victim.peer_id.to_string() in record[0]
        finally:
            await _shutdown(nodes)

    run(main())


def test_client_mode_node_can_read_and_write():
    async def main():
        nodes = await _make_swarm(3)
        client = await DHTNode.create(
            initial_peers=[nodes[0].own_addr], client_mode=True, maintenance_period=1000
        )
        try:
            assert client.server is None and client.own_addr is None
            assert await client.store("from-client", 42, dht_expiration(10))
            record = await client.get("from-client")
            assert record is not None and record[0] == 42
            # and full nodes see it too
            record = await nodes[2].get("from-client")
            assert record is not None and record[0] == 42
        finally:
            await _shutdown(nodes + [client])

    run(main())


def test_dead_node_does_not_break_swarm():
    async def main():
        nodes = await _make_swarm(4)
        try:
            await nodes[3].store("key-before", "v", dht_expiration(30))
            await nodes[1].shutdown()
            record = await nodes[2].get("key-before")
            # the record may have been replicated to the dead node, but other
            # replicas must still serve it
            assert record is not None and record[0] == "v"
            assert await nodes[0].store("key-after", "w", dht_expiration(30))
            record = await nodes[2].get("key-after")
            assert record is not None and record[0] == "w"
        finally:
            await _shutdown([nodes[0], nodes[2], nodes[3]])

    run(main())


def test_expired_record_disappears_from_swarm():
    async def main():
        nodes = await _make_swarm(3)
        try:
            await nodes[0].store("ephemeral", "x", dht_expiration(0.3))
            record = await nodes[1].get("ephemeral")
            assert record is not None
            await asyncio.sleep(0.4)
            assert await nodes[1].get("ephemeral") is None
        finally:
            await _shutdown(nodes)

    run(main())


def test_fixed_identity_from_seed():
    from petals_tpu.dht.identity import Identity

    async def main():
        node = await DHTNode.create(identity_seed=b"bootstrap-1", maintenance_period=1000)
        try:
            # ids are KEYPAIR-derived now: hash of the seed-derived public key
            assert node.peer_id == Identity.from_seed(b"bootstrap-1").peer_id
            assert node.peer_id != PeerID.from_seed(b"bootstrap-1")
        finally:
            await node.shutdown()

    run(main())


def dht_expiration(seconds: float) -> float:
    return time.time() + seconds
