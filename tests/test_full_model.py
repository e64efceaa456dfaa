"""End-to-end swarm tests: full model over a local swarm must be
token-identical to the local HF model (port of reference
tests/test_full_model.py:36-155 — the project's acceptance bar)."""

import asyncio
import threading

import jax.numpy as jnp
import numpy as np
import pytest
import torch

from petals_tpu.client.model import AutoDistributedModelForCausalLM
from petals_tpu.server.server import Server
from tests.utils import make_tiny_bloom, make_tiny_llama

MAX_NEW_TOKENS = 8


class SwarmHarness:
    """Bootstrap DHT + N servers on localhost, run in a dedicated loop thread."""

    def __init__(self, model_path, server_specs):
        self.model_path = model_path
        self.server_specs = server_specs
        self.loop = asyncio.new_event_loop()
        self._thread = threading.Thread(target=self._run_loop, daemon=True)
        self._thread.start()
        self.bootstrap = None
        self.servers = []

    def _run_loop(self):
        asyncio.set_event_loop(self.loop)
        self.loop.run_forever()

    def run(self, coro, timeout=300):
        return asyncio.run_coroutine_threadsafe(coro, self.loop).result(timeout)

    def start(self):
        async def boot():
            from petals_tpu.dht import DHTNode

            self.bootstrap = await DHTNode.create(maintenance_period=1000)
            for spec in self.server_specs:
                server = Server(
                    self.model_path,
                    initial_peers=[self.bootstrap.own_addr],
                    compute_dtype=jnp.float32,
                    use_flash=False,
                    **spec,
                )
                await server.start()
                self.servers.append(server)

        self.run(boot())
        return self

    @property
    def initial_peers(self):
        return [self.bootstrap.own_addr.to_string()]

    def stop(self):
        async def teardown():
            for server in self.servers:
                await server.shutdown()
            await self.bootstrap.shutdown()

        self.run(teardown())
        self.loop.call_soon_threadsafe(self.loop.stop)
        self._thread.join(timeout=10)


@pytest.fixture(scope="module")
def llama_swarm(tmp_path_factory):
    path = make_tiny_llama(str(tmp_path_factory.mktemp("models")))
    # two servers: blocks [0, 3) and [2, 4) — overlapping, multi-hop chains
    harness = SwarmHarness(path, [dict(first_block=0, num_blocks=3), dict(first_block=2, num_blocks=2)]).start()
    yield path, harness
    harness.stop()


@pytest.fixture(scope="module")
def llama_client(llama_swarm):
    path, harness = llama_swarm
    model = AutoDistributedModelForCausalLM.from_pretrained(
        path, initial_peers=harness.initial_peers
    )
    yield path, model
    model.close()


def _hf_greedy(model_path, input_ids, max_new_tokens):
    from transformers import AutoModelForCausalLM

    model = AutoModelForCausalLM.from_pretrained(model_path, dtype=torch.float32).eval()
    with torch.no_grad():
        out = model.generate(
            torch.from_numpy(input_ids), max_new_tokens=max_new_tokens, do_sample=False
        )
    return out.numpy()


def _hf_logits(model_path, input_ids):
    from transformers import AutoModelForCausalLM

    model = AutoModelForCausalLM.from_pretrained(model_path, dtype=torch.float32).eval()
    with torch.no_grad():
        return model(torch.from_numpy(input_ids)).logits.numpy()


def test_full_model_forward_matches_hf(llama_client):
    path, model = llama_client
    rng = np.random.RandomState(0)
    input_ids = rng.randint(0, 100, (2, 10)).astype(np.int64)
    logits = np.asarray(model.forward(input_ids))
    expected = _hf_logits(path, input_ids)
    np.testing.assert_allclose(logits, expected, atol=2e-4, rtol=0)


def test_greedy_generation_token_identical(llama_client):
    path, model = llama_client
    rng = np.random.RandomState(1)
    input_ids = rng.randint(0, 100, (1, 6)).astype(np.int64)
    ours = model.generate(input_ids, max_new_tokens=MAX_NEW_TOKENS)
    expected = _hf_greedy(path, input_ids, MAX_NEW_TOKENS)
    np.testing.assert_array_equal(ours, expected)


def test_batched_generation(llama_client):
    path, model = llama_client
    rng = np.random.RandomState(2)
    input_ids = rng.randint(0, 100, (3, 5)).astype(np.int64)
    ours = model.generate(input_ids, max_new_tokens=4)
    expected = _hf_greedy(path, input_ids, 4)
    np.testing.assert_array_equal(ours, expected)


def test_sampling_reproducible_and_valid(llama_client):
    path, model = llama_client
    rng = np.random.RandomState(3)
    input_ids = rng.randint(0, 100, (1, 4)).astype(np.int64)
    a = model.generate(input_ids, max_new_tokens=4, do_sample=True, top_k=10, temperature=0.8, seed=7)
    b = model.generate(input_ids, max_new_tokens=4, do_sample=True, top_k=10, temperature=0.8, seed=7)
    np.testing.assert_array_equal(a, b)
    assert a.shape == (1, 8)


def test_multi_call_chat_session(llama_client):
    """Two generate() calls in one session == one longer generation (reference
    remote_generation multi-call pattern)."""
    path, model = llama_client
    rng = np.random.RandomState(4)
    input_ids = rng.randint(0, 100, (1, 4)).astype(np.int64)

    with model.remote.inference_session(max_length=32, batch_size=1) as session:
        first = model.generate(input_ids, max_new_tokens=3, session=session)
        second = model.generate(first, max_new_tokens=3, session=session)

    expected = _hf_greedy(path, input_ids, 6)
    np.testing.assert_array_equal(second, expected)


def test_bloom_full_model(tmp_path_factory):
    path = make_tiny_bloom(str(tmp_path_factory.mktemp("models")))
    harness = SwarmHarness(path, [dict(first_block=0, num_blocks=3)]).start()
    try:
        model = AutoDistributedModelForCausalLM.from_pretrained(
            path, initial_peers=harness.initial_peers
        )
        try:
            rng = np.random.RandomState(5)
            input_ids = rng.randint(0, 100, (1, 5)).astype(np.int64)
            ours = model.generate(input_ids, max_new_tokens=5)
            expected = _hf_greedy(path, input_ids, 5)
            np.testing.assert_array_equal(ours, expected)
        finally:
            model.close()
    finally:
        harness.stop()


def test_mixtral_full_model(tmp_path_factory):
    from tests.utils import make_tiny_mixtral

    path = make_tiny_mixtral(str(tmp_path_factory.mktemp("models")))
    harness = SwarmHarness(path, [dict(first_block=0, num_blocks=2)]).start()
    try:
        model = AutoDistributedModelForCausalLM.from_pretrained(
            path, initial_peers=harness.initial_peers
        )
        try:
            rng = np.random.RandomState(6)
            input_ids = rng.randint(0, 100, (1, 5)).astype(np.int64)
            ours = model.generate(input_ids, max_new_tokens=5)
            expected = _hf_greedy(path, input_ids, 5)
            np.testing.assert_array_equal(ours, expected)

            # a prompt of 8 tokens or more takes the grouped (ragged_dot)
            # MoE dispatch in the serving prefill; still token-identical
            long_ids = rng.randint(0, 100, (1, 12)).astype(np.int64)
            ours_long = model.generate(long_ids, max_new_tokens=4)
            np.testing.assert_array_equal(ours_long, _hf_greedy(path, long_ids, 4))
        finally:
            model.close()
    finally:
        harness.stop()


def test_falcon_full_model(tmp_path_factory):
    from tests.utils import make_tiny_falcon

    path = make_tiny_falcon(str(tmp_path_factory.mktemp("models")), variant="new")
    harness = SwarmHarness(path, [dict(first_block=0, num_blocks=3)]).start()
    try:
        model = AutoDistributedModelForCausalLM.from_pretrained(
            path, initial_peers=harness.initial_peers
        )
        try:
            rng = np.random.RandomState(7)
            input_ids = rng.randint(0, 100, (1, 5)).astype(np.int64)
            ours = model.generate(input_ids, max_new_tokens=5)
            expected = _hf_greedy(path, input_ids, 5)
            np.testing.assert_array_equal(ours, expected)
        finally:
            model.close()
    finally:
        harness.stop()


def test_bare_distributed_model_matches_hf(llama_swarm):
    """DistributedModel (the reference's bare Distributed*Model): forward is
    HF's last_hidden_state, post final norm, no head."""
    from transformers import AutoModel

    from petals_tpu.client.model import AutoDistributedModel

    path, harness = llama_swarm
    model = AutoDistributedModel.from_pretrained(path, initial_peers=harness.initial_peers)
    try:
        rng = np.random.RandomState(19)
        input_ids = rng.randint(0, 100, (2, 7)).astype(np.int64)
        ours = np.asarray(model.forward(input_ids))
        hf = AutoModel.from_pretrained(path, dtype=torch.float32).eval()
        with torch.no_grad():
            expected = hf(torch.from_numpy(input_ids)).last_hidden_state.numpy()
        np.testing.assert_allclose(ours, expected, atol=2e-4, rtol=0)
    finally:
        model.close()


def test_model_level_inference_session(llama_client):
    """with model.inference_session(...): generate() picks up the active
    session automatically (the reference's chat pattern)."""
    path, model = llama_client
    rng = np.random.RandomState(22)
    input_ids = rng.randint(0, 100, (1, 4)).astype(np.int64)

    with model.inference_session(max_length=32) as session:
        first = model.generate(input_ids, max_new_tokens=3)
        assert model._active_session is session
        second = model.generate(first, max_new_tokens=3)
    assert model._active_session is None
    np.testing.assert_array_equal(second, _hf_greedy(path, input_ids, 6))


def test_remote_sequential_slicing(llama_client):
    """remote[1:3] is a live sub-chain (reference RemoteSequential slicing):
    its forward matches the local blocks 1..2, and closing the slice leaves
    the parent connected."""
    import jax.numpy as jnp

    from petals_tpu.server.from_pretrained import get_block_config, load_block_params

    path, model = llama_client
    family, cfg = get_block_config(path)
    with pytest.raises(IndexError):
        model.remote[99]
    sub = model.remote[1:3]
    try:
        assert len(sub) == 2
        rng = np.random.RandomState(21)
        hidden = rng.randn(1, 5, cfg.hidden_size).astype(np.float32)
        out = np.asarray(sub.forward(hidden))
        h = jnp.asarray(hidden)
        for i in (1, 2):
            h, _ = family.block_apply(
                load_block_params(path, i, dtype=jnp.float32), h, None, 0, cfg
            )
        np.testing.assert_allclose(out, np.asarray(h), atol=1e-4, rtol=0)
    finally:
        sub.close()
    # parent still works after the slice is closed
    ids = np.random.RandomState(2).randint(0, 100, (1, 4)).astype(np.int64)
    assert model.generate(ids, max_new_tokens=2).shape == (1, 6)


def test_beam_search_matches_hf(llama_client):
    """Beam search with server-side KV lane reorder (hypo_ids) must match HF's
    beam search token-for-token (reference test_full_model.py beam coverage)."""
    from transformers import AutoModelForCausalLM

    path, model = llama_client
    rng = np.random.RandomState(8)
    input_ids = rng.randint(0, 100, (1, 5)).astype(np.int64)

    ours = model.generate(input_ids, max_new_tokens=6, num_beams=3)

    hf = AutoModelForCausalLM.from_pretrained(path, dtype=torch.float32).eval()
    with torch.no_grad():
        expected = hf.generate(
            torch.from_numpy(input_ids), max_new_tokens=6, num_beams=3, do_sample=False
        ).numpy()
    np.testing.assert_array_equal(ours, expected)


def test_beam_search_eos_and_length_penalty_match_hf(llama_client):
    """EOS-aware beam finalization with length penalty / early stopping must
    match HF's BeamSearchScorer token-for-token (reference
    remote_generation.py:84-164 inherits this from GenerationMixin)."""
    from transformers import AutoModelForCausalLM

    path, model = llama_client
    rng = np.random.RandomState(11)
    input_ids = rng.randint(0, 100, (1, 5)).astype(np.int64)
    hf = AutoModelForCausalLM.from_pretrained(path, dtype=torch.float32).eval()

    with torch.no_grad():
        free_run = hf.generate(
            torch.from_numpy(input_ids), max_new_tokens=8, num_beams=3, do_sample=False
        ).numpy()
    # use tokens the model actually emits as eos so finalization really fires
    eos_candidates = [int(free_run[0, 7]), int(free_run[0, 11])]

    for eos in eos_candidates:
        for length_penalty, early_stopping in [(1.0, False), (2.0, False), (0.5, True)]:
            kwargs = dict(
                max_new_tokens=8, num_beams=3, eos_token_id=eos, pad_token_id=eos,
                length_penalty=length_penalty, early_stopping=early_stopping,
            )
            with torch.no_grad():
                expected = hf.generate(
                    torch.from_numpy(input_ids), do_sample=False, **kwargs
                ).numpy()
            ours = model.generate(input_ids, **kwargs)
            np.testing.assert_array_equal(
                ours, expected,
                err_msg=f"eos={eos} lp={length_penalty} es={early_stopping}",
            )


@pytest.mark.slow
def test_beam_search_batched_matches_hf(llama_client):
    """Beam search over batch > 1 (independent hypothesis pools per row,
    KV-lane reorder across the flattened batch*beams lanes)."""
    from transformers import AutoModelForCausalLM

    path, model = llama_client
    rng = np.random.RandomState(12)
    input_ids = rng.randint(0, 100, (2, 5)).astype(np.int64)
    hf = AutoModelForCausalLM.from_pretrained(path, dtype=torch.float32).eval()

    with torch.no_grad():
        free_run = hf.generate(
            torch.from_numpy(input_ids), max_new_tokens=6, num_beams=3, do_sample=False
        ).numpy()
    eos = int(free_run[0, 8])  # fires mid-generation for at least one row

    for kwargs in (
        dict(max_new_tokens=6, num_beams=3),
        dict(max_new_tokens=6, num_beams=3, eos_token_id=eos, pad_token_id=0),
    ):
        with torch.no_grad():
            expected = hf.generate(
                torch.from_numpy(input_ids), do_sample=False, **kwargs
            ).numpy()
        ours = model.generate(input_ids, **kwargs)
        np.testing.assert_array_equal(ours, expected, err_msg=str(kwargs))


@pytest.mark.slow
def test_eos_padding_and_max_length_match_hf(llama_client):
    """Batched greedy with eos: finished rows emit pad_token_id (HF _sample
    semantics); max_length caps total length in both greedy and beam paths."""
    from transformers import AutoModelForCausalLM

    path, model = llama_client
    rng = np.random.RandomState(14)
    input_ids = rng.randint(1, 100, (2, 5)).astype(np.int64)
    hf = AutoModelForCausalLM.from_pretrained(path, dtype=torch.float32).eval()

    with torch.no_grad():
        free = hf.generate(
            torch.from_numpy(input_ids), max_new_tokens=8, do_sample=False
        ).numpy()
    eos = int(free[0, 7])  # one row finishes early, the other keeps going

    kwargs = dict(max_new_tokens=8, eos_token_id=eos, pad_token_id=0)
    with torch.no_grad():
        expected = hf.generate(torch.from_numpy(input_ids), do_sample=False, **kwargs).numpy()
    ours = model.generate(input_ids, **kwargs)
    np.testing.assert_array_equal(ours, expected)

    for beam_kwargs in (dict(max_length=8), dict(max_length=8, num_beams=3)):
        with torch.no_grad():
            expected = hf.generate(
                torch.from_numpy(input_ids), do_sample=False, **beam_kwargs
            ).numpy()
        ours = model.generate(input_ids, **beam_kwargs)
        np.testing.assert_array_equal(ours, expected, err_msg=str(beam_kwargs))


@pytest.mark.slow
def test_num_return_sequences_and_min_new_tokens_match_hf(llama_client):
    """num_return_sequences (ranked beam outputs) and min_new_tokens (EOS ban
    until the minimum) must be token-identical to HF."""
    from transformers import AutoModelForCausalLM

    path, model = llama_client
    rng = np.random.RandomState(15)
    input_ids = rng.randint(1, 100, (1, 5)).astype(np.int64)
    hf = AutoModelForCausalLM.from_pretrained(path, dtype=torch.float32).eval()

    kwargs = dict(max_new_tokens=6, num_beams=4, num_return_sequences=3)
    with torch.no_grad():
        expected = hf.generate(torch.from_numpy(input_ids), do_sample=False, **kwargs).numpy()
    ours = model.generate(input_ids, **kwargs)
    assert ours.shape[0] == 3
    np.testing.assert_array_equal(ours, expected)

    # min_new_tokens with an eos that would otherwise fire immediately
    with torch.no_grad():
        free = hf.generate(
            torch.from_numpy(input_ids), max_new_tokens=6, do_sample=False
        ).numpy()
    eos = int(free[0, 5])  # the very first generated token
    for kwargs in (
        dict(max_new_tokens=6, eos_token_id=eos, pad_token_id=0, min_new_tokens=3),
        dict(max_new_tokens=6, num_beams=3, eos_token_id=eos, pad_token_id=0,
             min_new_tokens=3),
    ):
        with torch.no_grad():
            expected = hf.generate(
                torch.from_numpy(input_ids), do_sample=False, **kwargs
            ).numpy()
        ours = model.generate(input_ids, **kwargs)
        np.testing.assert_array_equal(ours, expected, err_msg=str(kwargs))


def test_repetition_penalties_match_hf(llama_client):
    """repetition_penalty and no_repeat_ngram_size in greedy decoding must be
    token-identical to HF's logits processors."""
    from transformers import AutoModelForCausalLM

    path, model = llama_client
    rng = np.random.RandomState(13)
    input_ids = rng.randint(0, 100, (2, 6)).astype(np.int64)
    hf = AutoModelForCausalLM.from_pretrained(path, dtype=torch.float32).eval()

    for kwargs in (
        dict(max_new_tokens=8, repetition_penalty=1.8),
        dict(max_new_tokens=8, no_repeat_ngram_size=2),
        dict(max_new_tokens=8, repetition_penalty=1.5, no_repeat_ngram_size=2),
    ):
        with torch.no_grad():
            expected = hf.generate(
                torch.from_numpy(input_ids), do_sample=False, **kwargs
            ).numpy()
        ours = model.generate(input_ids, **kwargs)
        np.testing.assert_array_equal(ours, expected, err_msg=str(kwargs))


def test_generate_streamer(llama_swarm):
    """HF streamer protocol: the prompt then every sampled token, then end();
    the streamed tokens reassemble the returned sequence exactly."""
    path, harness = llama_swarm
    model = AutoDistributedModelForCausalLM.from_pretrained(
        path, initial_peers=harness.initial_peers
    )

    class Recorder:
        def __init__(self):
            self.chunks, self.ended = [], False

        def put(self, value):
            self.chunks.append(np.asarray(value))

        def end(self):
            self.ended = True

    try:
        rng = np.random.RandomState(11)
        ids = rng.randint(0, 100, (1, 5)).astype(np.int64)
        rec = Recorder()
        out = model.generate(ids, max_new_tokens=6, streamer=rec)
        assert rec.ended
        np.testing.assert_array_equal(rec.chunks[0], ids)  # prompt first
        streamed = np.concatenate([c.reshape(1, -1) for c in rec.chunks], axis=1)
        np.testing.assert_array_equal(streamed, out)

        with pytest.raises(ValueError, match="streamer"):
            model.generate(ids, max_new_tokens=2, num_beams=2, streamer=rec)
    finally:
        model.close()
