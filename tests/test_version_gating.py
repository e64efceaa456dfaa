"""Protocol version gating: incompatible servers are excluded from routing
with a named warning, and an incompatible handshake fails with an actionable
error instead of an opaque wire mismatch (reference utils/version.py:21-51 is
a PyPI update check; the swarm-compat half is this build's addition)."""

import numpy as np
import pytest

import petals_tpu
from petals_tpu.utils.version import incompatibility_error, is_compatible, parse_version


def test_compat_policy(monkeypatch):
    ours = parse_version(petals_tpu.__version__)
    assert ours is not None
    major, minor = ours
    assert is_compatible(petals_tpu.__version__)
    assert is_compatible(f"{major}.{minor}.99")
    assert not is_compatible(f"{major}.{minor + 1}.0")
    assert not is_compatible(f"{major + 1}.0.0")
    assert is_compatible(None)  # pre-gating builds
    assert is_compatible("weird-version")  # unparseable: stay reachable
    # a malformed/malicious announce (non-string) must not crash routing
    assert is_compatible(123) and parse_version(123) is None
    assert is_compatible([1, 2]) and parse_version(b"1.2") is None
    monkeypatch.setenv("PETALS_TPU_IGNORE_VERSION", "1")
    assert is_compatible(f"{major + 1}.0.0")  # escape hatch


def test_routing_excludes_incompatible_servers():
    from petals_tpu.client.routing.sequence_info import RemoteSequenceInfo
    from petals_tpu.data_structures import (
        RemoteModuleInfo,
        ServerInfo,
        ServerState,
    )

    def server(version):
        return ServerInfo(state=ServerState.ONLINE, throughput=1.0, version=version)

    infos = [
        RemoteModuleInfo(
            uid=f"m.{i}",
            servers={
                b"good-peer": server(petals_tpu.__version__),
                b"old-peer": server("999.0.0"),
            },
        )
        for i in range(2)
    ]
    seq = RemoteSequenceInfo.make_empty([f"m.{i}" for i in range(2)])
    seq.update_(infos)
    peers = {span.peer_id for span in seq.spans_by_priority}
    assert peers == {b"good-peer"}, peers
    for block_spans in seq.spans_containing_block:
        assert {s.peer_id for s in block_spans} == {b"good-peer"}

    # a non-string version in an announce is junk, not a crash: the server
    # stays reachable (pre-gating semantics) and routing completes
    infos_junk = [
        RemoteModuleInfo(uid="m.0", servers={b"junk-peer": server(12345)}),
        RemoteModuleInfo(uid="m.1", servers={b"junk-peer": server(12345)}),
    ]
    seq2 = RemoteSequenceInfo.make_empty(["m.0", "m.1"])
    seq2.update_(infos_junk)
    assert {s.peer_id for s in seq2.spans_by_priority} == {b"junk-peer"}


def test_client_routing_rejects_incompatible_swarm(tmp_path):
    """A client across the compat line from every server fails with
    MissingBlocks after the named warning — not an opaque wire error."""
    from petals_tpu.client.model import AutoDistributedModelForCausalLM
    from tests.test_full_model import SwarmHarness
    from tests.utils import make_tiny_llama

    path = make_tiny_llama(str(tmp_path))
    # no second announce inside the test: the server reads the patched version when it announces again (every
    # update_period, 30 s by default), and on a loaded machine the client was still looking when it did
    harness = SwarmHarness(path, [dict(first_block=0, num_blocks=4, update_period=600.0)]).start()
    try:
        real_version = petals_tpu.__version__
        petals_tpu.__version__ = "999.0.0"
        try:
            model = AutoDistributedModelForCausalLM.from_pretrained(
                path, initial_peers=harness.initial_peers, max_retries=0
            )
            try:
                ids = np.arange(4, dtype=np.int64).reshape(1, 4)
                with pytest.raises(Exception, match="[Nn]o servers"):
                    model.generate(ids, max_new_tokens=2)
            finally:
                model.close()
        finally:
            petals_tpu.__version__ = real_version
    finally:
        harness.stop()


def test_handshake_rejects_incompatible_client(tmp_path):
    """The server refuses a session open whose client_version is across the
    compat line, naming both versions (routing normally prevents this; the
    handshake is the backstop for clients that skipped it)."""
    from tests.test_full_model import SwarmHarness
    from tests.utils import make_tiny_llama

    path = make_tiny_llama(str(tmp_path))
    harness = SwarmHarness(path, [dict(first_block=0, num_blocks=4)]).start()
    try:
        server = harness.servers[0]
        prefix = server.dht_prefix

        async def open_with_bad_version():
            from petals_tpu.rpc.client import RpcClient

            addr = server.contact_addr
            client = await RpcClient.connect(addr.host, addr.port)
            try:
                stream = await client.open_stream("ptu.inference")
                await stream.send({
                    "uids": " ".join(f"{prefix}.{i}" for i in range(4)),
                    "max_length": 8,
                    "batch_size": 1,
                    "compression": "none",
                    "client_version": "999.0.0",
                })
                return await stream.recv(timeout=30)
            finally:
                await client.close()

        with pytest.raises(Exception, match="999.0.0|interoperate"):
            harness.run(open_with_bad_version())
    finally:
        harness.stop()
