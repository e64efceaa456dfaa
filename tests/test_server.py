"""Server runtime tests: a real Server process (in-loop) serving a tiny model,
driven through raw RPC (reference handler semantics: rpc_info / rpc_forward /
rpc_backward / rpc_inference session)."""

import asyncio

import jax.numpy as jnp
import numpy as np
import pytest

from petals_tpu.data_structures import CHAIN_DELIMITER, make_uid
from petals_tpu.rpc import RpcClient, RpcError
from petals_tpu.rpc.serialization import deserialize_array, serialize_array
from petals_tpu.server.server import Server, default_dht_prefix
from tests.utils import make_tiny_llama


@pytest.fixture(scope="module")
def model_path(tmp_path_factory):
    return make_tiny_llama(str(tmp_path_factory.mktemp("models")))


def run(coro):
    return asyncio.run(coro)


async def _start_server(model_path, **kwargs):
    server = Server(model_path, compute_dtype=jnp.float32, use_flash=False, **kwargs)
    await server.start()
    client = await RpcClient.connect(server.rpc_server.host, server.rpc_server.port)
    return server, client


def test_info_forward_backward(model_path):
    async def main():
        server, client = await _start_server(model_path)
        try:
            prefix = default_dht_prefix(model_path)
            info = await client.call("ptu.info", {}, timeout=10)
            assert info["first_block"] == 0 and info["n_blocks"] == server.cfg.num_hidden_layers
            assert info["cache_tokens_available"] > 0

            uids = CHAIN_DELIMITER.join(make_uid(prefix, i) for i in range(server.cfg.num_hidden_layers))
            rng = np.random.RandomState(0)
            hidden = rng.randn(1, 7, server.cfg.hidden_size).astype(np.float32)

            result = await client.call(
                "ptu.forward",
                {"uids": uids, "tensors": {"hidden": serialize_array(hidden)}},
                timeout=60,
            )
            out = deserialize_array(result["tensors"]["hidden"])
            expected = np.asarray(server.backend.forward(hidden))
            np.testing.assert_allclose(out, expected, atol=1e-5, rtol=0)

            grad_out = rng.randn(*hidden.shape).astype(np.float32)
            result = await client.call(
                "ptu.backward",
                {
                    "uids": uids,
                    "tensors": {
                        "hidden": serialize_array(hidden),
                        "grad_out": serialize_array(grad_out),
                    },
                },
                timeout=60,
            )
            grad = deserialize_array(result["tensors"]["grad_hidden"])
            assert grad.shape == hidden.shape and np.abs(grad).sum() > 0

            # partial chain (single mid-block) also works
            result = await client.call(
                "ptu.forward",
                {"uids": make_uid(prefix, 1), "tensors": {"hidden": serialize_array(hidden)}},
                timeout=60,
            )
            assert deserialize_array(result["tensors"]["hidden"]).shape == hidden.shape
        finally:
            await client.close()
            await server.shutdown()

    run(main())


def test_inference_session_stream(model_path):
    async def main():
        server, client = await _start_server(model_path)
        try:
            prefix = default_dht_prefix(model_path)
            n = server.cfg.num_hidden_layers
            uids = CHAIN_DELIMITER.join(make_uid(prefix, i) for i in range(n))
            rng = np.random.RandomState(1)
            total = 6
            hidden = rng.randn(1, total, server.cfg.hidden_size).astype(np.float32)
            expected = np.asarray(server.backend.forward(hidden))

            stream = await client.open_stream("ptu.inference")
            await stream.send({"uids": uids, "max_length": 16, "batch_size": 1})
            ack = await stream.recv(timeout=30)
            assert ack.get("session_open") and ack["max_length"] == 16

            # prefill 3 tokens, then decode one at a time
            await stream.send({"tensors": {"hidden": serialize_array(hidden[:, :3])}})
            out = await stream.recv(timeout=60)
            assert out["position"] == 3
            parts = [deserialize_array(out["tensors"]["hidden"])]
            for t in range(3, total):
                await stream.send({"tensors": {"hidden": serialize_array(hidden[:, t : t + 1])}})
                out = await stream.recv(timeout=60)
                parts.append(deserialize_array(out["tensors"]["hidden"]))
            stitched = np.concatenate(parts, axis=1)
            np.testing.assert_allclose(stitched, expected, atol=1e-5, rtol=0)

            # rollback (speculative decoding support): rewind to position 3 and redo
            await stream.send(
                {"tensors": {"hidden": serialize_array(hidden[:, 3:4])}, "start_from_position": 3}
            )
            out = await stream.recv(timeout=60)
            assert out["position"] == 4
            np.testing.assert_allclose(
                deserialize_array(out["tensors"]["hidden"]), expected[:, 3:4], atol=1e-5, rtol=0
            )
            await stream.end()
        finally:
            await client.close()
            await server.shutdown()

    run(main())


def test_inference_rejects_overflow_and_bad_chain(model_path):
    async def main():
        server, client = await _start_server(model_path)
        try:
            prefix = default_dht_prefix(model_path)
            uids = make_uid(prefix, 0)
            stream = await client.open_stream("ptu.inference")
            await stream.send({"uids": uids, "max_length": 4, "batch_size": 1})
            await stream.recv(timeout=30)
            big = np.zeros((1, 6, server.cfg.hidden_size), np.float32)
            await stream.send({"tensors": {"hidden": serialize_array(big)}})
            with pytest.raises(RpcError, match="exceeds max_length"):
                await stream.recv(timeout=30)

            with pytest.raises(RpcError, match="does not match served prefix"):
                await client.call(
                    "ptu.forward",
                    {"uids": "wrong.0", "tensors": {"hidden": serialize_array(big)}},
                    timeout=30,
                )
        finally:
            await client.close()
            await server.shutdown()

    run(main())


def test_inference_rejects_malformed_step_tensors(model_path):
    """Wrong batch size / hidden dim / hypo_ids shape must fail with a clean
    ValueError before reaching the jitted step (not an opaque XLA error)."""

    async def main():
        server, client = await _start_server(model_path)
        try:
            prefix = default_dht_prefix(model_path)
            uids = make_uid(prefix, 0)
            hsz = server.cfg.hidden_size

            async def open_session():
                stream = await client.open_stream("ptu.inference")
                await stream.send({"uids": uids, "max_length": 8, "batch_size": 1})
                await stream.recv(timeout=30)
                return stream

            stream = await open_session()
            wrong_batch = np.zeros((2, 1, hsz), np.float32)
            await stream.send({"tensors": {"hidden": serialize_array(wrong_batch)}})
            with pytest.raises(RpcError, match="step hidden must be"):
                await stream.recv(timeout=30)

            stream = await open_session()
            wrong_hidden = np.zeros((1, 1, hsz + 1), np.float32)
            await stream.send({"tensors": {"hidden": serialize_array(wrong_hidden)}})
            with pytest.raises(RpcError, match="step hidden must be"):
                await stream.recv(timeout=30)

            stream = await open_session()
            ok = np.zeros((1, 1, hsz), np.float32)
            bad_hypo = np.zeros((3,), np.int64)
            await stream.send(
                {"tensors": {"hidden": serialize_array(ok), "hypo_ids": serialize_array(bad_hypo)}}
            )
            with pytest.raises(RpcError, match="hypo_ids must be"):
                await stream.recv(timeout=30)

            with pytest.raises(RpcError, match="rpc_forward expects"):
                await client.call(
                    "ptu.forward",
                    {"uids": uids, "tensors": {"hidden": serialize_array(wrong_hidden)}},
                    timeout=30,
                )
        finally:
            await client.close()
            await server.shutdown()

    run(main())


def test_per_request_compression_negotiation(model_path):
    """Clients request reply compression per call/session; the server honors
    it over its own default (reference handler.py:411-432 + the override test
    tests/test_remote_sequential.py:147-167)."""

    async def main():
        server, client = await _start_server(model_path)
        try:
            prefix = default_dht_prefix(model_path)
            n = server.cfg.num_hidden_layers
            uids = CHAIN_DELIMITER.join(make_uid(prefix, i) for i in range(n))
            rng = np.random.RandomState(3)
            hidden = rng.randn(1, 4, server.cfg.hidden_size).astype(np.float32)
            dense = np.asarray(server.backend.forward(hidden))

            # unary forward: requested qint8 reply
            result = await client.call(
                "ptu.forward",
                {
                    "uids": uids,
                    "compression": "qint8",
                    "tensors": {"hidden": serialize_array(hidden)},
                },
                timeout=60,
            )
            wire = result["tensors"]["hidden"]
            assert wire["compression"] == "qint8"
            np.testing.assert_allclose(
                deserialize_array(wire), dense, atol=np.abs(dense).max() / 50, rtol=0
            )

            # no request -> server default (none)
            result = await client.call(
                "ptu.forward",
                {"uids": uids, "tensors": {"hidden": serialize_array(hidden)}},
                timeout=60,
            )
            assert result["tensors"]["hidden"]["compression"] == "none"

            # inference stream: compression fixed at session open
            stream = await client.open_stream("ptu.inference")
            await stream.send(
                {"uids": uids, "max_length": 8, "batch_size": 1, "compression": "bfloat16"}
            )
            await stream.recv(timeout=30)
            await stream.send({"tensors": {"hidden": serialize_array(hidden)}})
            reply = await stream.recv(timeout=60)
            assert reply["tensors"]["hidden"]["compression"] == "bfloat16"
            await stream.end()

            # unknown codec is rejected cleanly
            with pytest.raises(RpcError, match="Unknown compression"):
                await client.call(
                    "ptu.forward",
                    {
                        "uids": uids,
                        "compression": "zstd",
                        "tensors": {"hidden": serialize_array(hidden)},
                    },
                    timeout=30,
                )
        finally:
            await client.close()
            await server.shutdown()

    run(main())


def test_server_announces_to_dht(model_path):
    async def main():
        from petals_tpu.dht import DHTNode
        from petals_tpu.utils.dht_utils import ModuleDirectory, compute_spans

        boot = await DHTNode.create(maintenance_period=1000)
        server, client = await _start_server(model_path, initial_peers=[boot.own_addr])
        try:
            reader = await DHTNode.create(
                initial_peers=[boot.own_addr], client_mode=True, maintenance_period=1000
            )
            directory = ModuleDirectory(reader)
            infos = await directory.fetch(server.module_uids)
            assert all(info is not None for info in infos)
            spans = compute_spans(infos)
            assert server.dht.peer_id in spans
            span = spans[server.dht.peer_id]
            assert (span.start, span.end) == (0, server.cfg.num_hidden_layers)
            assert directory.addr_of(server.dht.peer_id) == server.dht.own_addr
            await reader.shutdown()
        finally:
            await client.close()
            await server.shutdown()
            await boot.shutdown()

    run(main())


def test_compilation_cache_rule(tmp_path, monkeypatch):
    """One rule (utils/compile_cache.py): JAX_COMPILATION_CACHE_DIR set ->
    the code sets no directory and JAX's own handling fills it; unset ->
    <checkout>/.jax_cache; PETALS_TPU_NO_COMPILATION_CACHE turns the default
    off. A restarted server then skips recompiling its step executables."""
    import jax

    from petals_tpu.utils import compile_cache

    configured = jax.config.jax_compilation_cache_dir  # conftest's per-run temp dir
    # the variable set from outside: nothing is configured by our code
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "elsewhere"))
    assert compile_cache.enable_compilation_cache() == str(tmp_path / "elsewhere")
    assert jax.config.jax_compilation_cache_dir == configured
    # unset and opted out: off
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    monkeypatch.setenv("PETALS_TPU_NO_COMPILATION_CACHE", "1")
    assert compile_cache.enable_compilation_cache() is None
    assert jax.config.jax_compilation_cache_dir == configured
    # unset: the checkout's own directory, resolved from the package location
    monkeypatch.delenv("PETALS_TPU_NO_COMPILATION_CACHE")
    monkeypatch.setattr(compile_cache, "CHECKOUT_CACHE_DIR", tmp_path / ".jax_cache")

    def _reset():  # de-init the once-per-process cache singleton
        from jax._src import compilation_cache as _cc

        _cc.reset_cache()

    _reset()
    counts = compile_cache.count_cache_events()
    try:
        assert compile_cache.enable_compilation_cache() == str(tmp_path / ".jax_cache")
        import jax.numpy as jnp

        @jax.jit
        def step(x):
            return (x @ x).sum()

        jax.block_until_ready(step(jnp.ones((64, 64))))
        assert list((tmp_path / ".jax_cache").iterdir()), "compilation cache must be populated"
        assert counts["requests"] >= 1 and counts["writes"] >= 1, counts
    finally:
        # restore process-wide state: later tests must not write to tmp_path
        jax.config.update("jax_compilation_cache_dir", configured)
        _reset()


def test_checkout_cache_dir_is_fixed_and_inside_the_checkout():
    import os

    from petals_tpu.utils.compile_cache import CHECKOUT_CACHE_DIR

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert str(CHECKOUT_CACHE_DIR) == os.path.join(repo, ".jax_cache")
