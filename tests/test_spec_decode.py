"""Speculative decoding tier (server/spec_decode.py + the backend's
paged_spec_verify_step + the batcher's spec tick): the draft–verify path
must be DISTRIBUTION-PRESERVING — the emitted stream bit-identical to plain
decode for greedy and fixed-seed sampling lanes alike, with rollback a pure
position truncation (no page frees, no refcount edits), the acceptance-EMA
fallback journaled with evidence, the ledger billing draft+verify compute
honestly, and zero post-warmup recompiles from the two new programs."""

import asyncio

import jax.numpy as jnp
import numpy as np
import pytest

from petals_tpu.client.from_pretrained import load_client_params
from petals_tpu.ops.sampling import sampling_vectors
from petals_tpu.telemetry.journal import get_journal
from tests.test_full_model import SwarmHarness, _hf_greedy
from tests.utils import make_tiny_llama

pytestmark = pytest.mark.spec

SPEC_K = 3


@pytest.fixture(scope="module")
def model_path(tmp_path_factory):
    return make_tiny_llama(str(tmp_path_factory.mktemp("models")))


@pytest.fixture(scope="module")
def spec_swarm(model_path):
    """One full-span server with a cooperative draft (the tiny model drafts
    for itself, unquantized) on a paged 3-lane pool."""
    harness = SwarmHarness(
        model_path,
        [dict(
            first_block=0, num_blocks=4, batch_lanes=3, batch_max_length=64,
            page_size=8, draft_model=model_path, spec_k=SPEC_K,
            draft_quant_type="none", draft_window=48,
        )],
    ).start()
    yield harness
    harness.stop()


def run(coro):
    return asyncio.run(coro)


# ------------------------------------------------------ direct backend parity


def _full_backend(model_path):
    import jax

    from petals_tpu.server.backend import TransformerBackend
    from petals_tpu.server.from_pretrained import get_block_config, load_block_params
    from petals_tpu.server.memory_cache import MemoryCache

    family, cfg = get_block_config(model_path)
    per_block = [
        load_block_params(model_path, i, dtype=jnp.float32, family=family, cfg=cfg)
        for i in range(2)
    ]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_block)
    # a 2-block "full model" for the client leaves: fine for parity purposes
    return TransformerBackend(
        family, cfg, stacked, first_block=0, n_blocks=2,
        memory_cache=MemoryCache(None), compute_dtype=jnp.float32, use_flash=False,
    ), cfg


def _dense_prefill(backend, cfg, positions, maxlen, rng):
    """Per-lane dense prompt caches (random hidden prompts) concatenated to
    [n_blocks, L, maxlen, hkv, d] — the template every paged layout below
    scatters from."""
    kd, vd = backend.cache_descriptors(1, maxlen, 0, 2)
    lanes = []
    for l in range(len(positions)):
        kv = (kd.make_zeros(), vd.make_zeros())
        if positions[l]:
            pre = rng.randn(1, positions[l], cfg.hidden_size).astype(np.float32) * 0.1
            _, kv = backend.inference_step(pre, kv, 0)
        lanes.append((np.asarray(kv[0]), np.asarray(kv[1])))
    k_dense = np.concatenate([kv[0] for kv in lanes], axis=1)
    v_dense = np.concatenate([kv[1] for kv in lanes], axis=1)
    return k_dense, v_dense


def _build_pool(k_dense, v_dense, positions, ps, max_pages, n_pages, rng, rows):
    """Scatter the dense caches into a permuted page pool, allocating enough
    slots per lane for ``rows`` upcoming writes past its position."""
    L = k_dense.shape[1]
    tables = np.full((L, max_pages), -1, np.int32)
    free = list(rng.permutation(n_pages))
    n_blocks, _, _, hkv, hd = k_dense.shape
    kp = np.zeros((n_blocks, n_pages, ps, hkv, hd), np.float32)
    vp = np.zeros_like(kp)
    for l in range(L):
        if positions[l] + rows == 0:
            continue
        for s in range(-(-int(positions[l] + rows) // ps)):
            page = free.pop()
            tables[l, s] = page
            kp[:, page] = k_dense[:, l, s * ps : (s + 1) * ps]
            vp[:, page] = v_dense[:, l, s * ps : (s + 1) * ps]
    return (kp, vp), tables


def _vecs(L, vocab, sampled, draw_idx):
    v = sampling_vectors(L, vocab)
    if sampled:
        v["do_sample"][:] = True
        v["temperature"][:] = 0.8
        v["top_k"][:] = 10
        v["seeds"][:] = 42 + np.arange(L)
        v["draw_idx"][:] = draw_idx
    return v


def _plain_stream(backend, cfg, client_params, pool, tables, positions,
                  use_token, t0, n_steps, sampled, draw0=1):
    """Ground truth: n_steps of the ordinary paged gen decode loop, one
    token per tick (draw_idx advancing one per emitted token)."""
    L = len(positions)
    kp, vp = jnp.asarray(pool[0].copy()), jnp.asarray(pool[1].copy())
    toks = np.asarray(t0, np.int32).copy()
    pos = np.asarray(positions, np.int32).copy()
    hidden = np.zeros((L, 1, cfg.hidden_size), np.float32)
    stream = []
    for i in range(n_steps):
        _, nxt, (kp, vp) = backend.paged_gen_decode_step(
            client_params, hidden, toks, use_token, (kp, vp), pos, tables,
            sampling_vecs=_vecs(L, cfg.vocab_size, sampled, draw0 + i),
        )
        nxt = np.asarray(nxt, np.int32)
        # idle lanes must not advance (mirrors the batcher's lane bookkeeping)
        toks = np.where(use_token, nxt, toks)
        stream.append(toks.copy())
        pos = pos + np.where(use_token, 1, 0).astype(np.int32)
    return np.stack(stream, axis=1), (np.asarray(kp), np.asarray(vp))


def _verify(backend, client_params, pool, tables, positions, t0, drafts,
            sampled, vocab, draw0=1):
    L = len(positions)
    tokens = np.concatenate(
        [np.asarray(t0, np.int32)[:, None], np.asarray(drafts, np.int32)], axis=1
    )
    g_hat, n_emit, _ = backend.paged_spec_verify_step(
        client_params, tokens,
        (jnp.asarray(pool[0].copy()), jnp.asarray(pool[1].copy())),
        positions, tables,
        sampling_vecs=_vecs(L, vocab, sampled, draw0),
    )
    return np.asarray(g_hat, np.int32), np.asarray(n_emit, np.int32)


def test_spec_verify_parity_direct(model_path):
    """Backend-level distribution preservation on permuted/holey tables with
    an idle lane riding at the sentinel: cooperative drafts accept the whole
    window and emit EXACTLY the plain stream; hostile drafts roll back to
    one token (still the plain token); a partial match truncates at the
    first divergence — for greedy AND fixed-seed sampling lanes."""
    backend, cfg = _full_backend(model_path)
    client_params = load_client_params(model_path, dtype=jnp.float32)
    rng = np.random.RandomState(3)
    L, PS, MAX_PAGES = 3, 8, 4
    S = SPEC_K + 1
    maxlen = PS * MAX_PAGES
    # lane 2 is idle: sentinel position, empty table row, ignored outputs
    positions = np.array([4, 9, maxlen], np.int32)
    use_token = np.array([True, True, False])
    active = slice(0, 2)
    t0 = np.array([7, 11, 0], np.int32)
    k_dense, v_dense = _dense_prefill(backend, cfg, [4, 9, 0], maxlen, rng)

    for sampled in (False, True):
        pool, tables = _build_pool(
            k_dense, v_dense, [4, 9, -S], PS, MAX_PAGES, 17,
            np.random.RandomState(5), rows=S,
        )
        plain, _ = _plain_stream(
            backend, cfg, client_params, pool, tables, positions, use_token,
            t0, S, sampled,
        )
        # cooperative drafts (== the plain stream): full acceptance
        g, m = _verify(backend, client_params, pool, tables, positions, t0,
                       plain[:, :SPEC_K], sampled, cfg.vocab_size)
        assert (m[active] == S).all(), f"sampled={sampled}: {m}"
        np.testing.assert_array_equal(
            g[active], plain[active],
            err_msg=f"sampled={sampled}: accepted stream diverges from plain decode",
        )
        # hostile drafts (guaranteed wrong): everything rolls back to the one
        # bonus token, which is still plain decode's first token
        bad = (plain[:, :SPEC_K] + 1) % cfg.vocab_size
        g, m = _verify(backend, client_params, pool, tables, positions, t0,
                       bad, sampled, cfg.vocab_size)
        assert (m[active] == 1).all(), f"sampled={sampled}: {m}"
        np.testing.assert_array_equal(g[active, 0], plain[active, 0])
        # partial match: first draft right, second wrong -> exactly 2 emitted
        part = bad.copy()
        part[:, 0] = plain[:, 0]
        g, m = _verify(backend, client_params, pool, tables, positions, t0,
                       part, sampled, cfg.vocab_size)
        assert (m[active] == 2).all(), f"sampled={sampled}: {m}"
        np.testing.assert_array_equal(g[active, :2], plain[active, :2])
        # the idle lane and out-of-table pages never get written
        kp2 = np.asarray(backend.paged_spec_verify_step(
            client_params,
            np.concatenate([t0[:, None], plain[:, :SPEC_K]], axis=1),
            (jnp.asarray(pool[0].copy()), jnp.asarray(pool[1].copy())),
            positions, tables,
            sampling_vecs=_vecs(L, cfg.vocab_size, sampled, 1),
        )[2][0])
        untouched = sorted(set(range(17)) - set(tables[tables >= 0].ravel().tolist()))
        assert np.abs(kp2[:, untouched]).sum() == 0, "write leaked outside the tables"


def test_spec_verify_k1_degenerates_bit_exact(model_path):
    """k=1 is the smallest speculation window: one draft, one bonus token.
    A right draft emits the two plain tokens; a wrong one emits exactly the
    first — g_hat[:, 0] equals plain decode's token regardless of drafts."""
    backend, cfg = _full_backend(model_path)
    client_params = load_client_params(model_path, dtype=jnp.float32)
    rng = np.random.RandomState(9)
    L, PS, MAX_PAGES = 2, 8, 3
    positions = np.array([6, 3], np.int32)
    use_token = np.array([True, True])
    t0 = np.array([2, 9], np.int32)
    k_dense, v_dense = _dense_prefill(backend, cfg, positions, PS * MAX_PAGES, rng)
    pool, tables = _build_pool(
        k_dense, v_dense, positions, PS, MAX_PAGES, 9,
        np.random.RandomState(10), rows=2,
    )
    for sampled in (False, True):
        plain, _ = _plain_stream(
            backend, cfg, client_params, pool, tables, positions, use_token,
            t0, 2, sampled,
        )
        g, m = _verify(backend, client_params, pool, tables, positions, t0,
                       plain[:, :1], sampled, cfg.vocab_size)
        assert (m == 2).all()
        np.testing.assert_array_equal(g, plain)
        g, m = _verify(backend, client_params, pool, tables, positions, t0,
                       (plain[:, :1] + 1) % cfg.vocab_size, sampled, cfg.vocab_size)
        assert (m == 1).all()
        np.testing.assert_array_equal(g[:, 0], plain[:, 0])


def test_spec_rollback_then_plain_decode_consistent(model_path):
    """Satellite: rollback is position truncation ONLY. After a hostile
    verify (1 of k+1 rows committed; the other k rows hold stale draft KV in
    the lane's pages), plain decode continuing from the truncated position
    must reproduce the from-scratch plain stream bit-for-bit — the stale
    rows are masked by kv_length and overwritten in place — with the block
    tables untouched."""
    backend, cfg = _full_backend(model_path)
    client_params = load_client_params(model_path, dtype=jnp.float32)
    rng = np.random.RandomState(11)
    L, PS, MAX_PAGES = 2, 8, 4
    S = SPEC_K + 1
    positions = np.array([5, 12], np.int32)
    use_token = np.array([True, True])
    t0 = np.array([4, 13], np.int32)
    k_dense, v_dense = _dense_prefill(backend, cfg, positions, PS * MAX_PAGES, rng)
    n_cont = 3  # plain steps after the rollback
    pool, tables = _build_pool(
        k_dense, v_dense, positions, PS, MAX_PAGES, 14,
        np.random.RandomState(12), rows=S + n_cont,
    )
    for sampled in (False, True):
        ref, _ = _plain_stream(
            backend, cfg, client_params, pool, tables, positions, use_token,
            t0, 1 + n_cont, sampled,
        )
        tables_before = tables.copy()
        tokens = np.concatenate(
            [t0[:, None], (ref[:, :SPEC_K] + 1) % cfg.vocab_size], axis=1
        )
        g_hat, n_emit, (kp, vp) = backend.paged_spec_verify_step(
            client_params, tokens,
            (jnp.asarray(pool[0].copy()), jnp.asarray(pool[1].copy())),
            positions, tables,
            sampling_vecs=_vecs(L, cfg.vocab_size, sampled, 1),
        )
        g_hat, n_emit = np.asarray(g_hat), np.asarray(n_emit)
        assert (n_emit == 1).all()
        np.testing.assert_array_equal(tables, tables_before)
        # commit g1, truncate to position + 1 (the batcher's rollback), then
        # keep decoding plain on the SAME pool — over the stale rows
        cont, _ = _plain_stream(
            backend, cfg, client_params, (np.asarray(kp), np.asarray(vp)),
            tables, positions + 1, use_token, g_hat[:, 0], n_cont, sampled,
            draw0=2,
        )
        np.testing.assert_array_equal(
            cont, ref[:, 1:],
            err_msg=f"sampled={sampled}: stream after rollback diverges",
        )


# ------------------------------------------------------------ pooled server


def _batcher(spec_swarm):
    return spec_swarm.servers[0].handler.batcher


def _embed(batcher, ctx):
    emb = batcher.backend.family.client_embed(
        batcher.gen_params, np.asarray([ctx], np.int32), batcher.backend.cfg
    )
    return np.asarray(emb, np.float32)


async def _pooled_generate(batcher, prompt_hidden, n_tokens, sampling=None,
                           peer_id=None):
    """Drive one session the way the handler does: admit a lane, prefill the
    prompt, then server-side generate. Returns (tokens [1, n], usage delta)."""
    lane = await batcher.acquire_lane(timeout=60, peer_id=peer_id)
    try:
        out = await batcher.prefill_lane(lane, prompt_hidden, 0)
        toks = await batcher.generate_lane(
            lane, np.asarray(out[:, -1:]), int(prompt_hidden.shape[1]),
            n_tokens, sampling,
        )
        usage = batcher.pop_usage_delta(lane)
    finally:
        batcher.release_lane(lane)
    return np.asarray(toks), usage


def test_pooled_spec_stream_identical_to_plain(spec_swarm, model_path):
    """The whole spec tick (draft propose -> one verify step -> commit /
    rollback) on the live lane pool emits the SAME stream as plain decode,
    greedy and fixed-seed sampling alike — speculation is invisible in the
    output, visible only in the stats."""
    batcher = _batcher(spec_swarm)

    async def main():
        rng = np.random.RandomState(21)
        ctx = [int(t) for t in rng.randint(0, 100, size=7)]
        hidden = _embed(batcher, ctx)
        sampled = dict(do_sample=True, temperature=0.8, top_k=10, seed=1234,
                       offset=0, context=ctx)
        def counts():
            return batcher.stats["spec_accepted"], batcher.stats["spec_proposed"]

        spec0 = batcher.stats["spec_steps"]
        accepted0, proposed0 = counts()
        spec_g, _ = await _pooled_generate(batcher, hidden, 14, {"context": ctx})
        accepted, proposed = counts()
        spec_s, _ = await _pooled_generate(batcher, hidden, 14, dict(sampled))
        assert batcher.stats["spec_steps"] > spec0, "spec path never engaged"
        assert batcher.stats["max_spec_lanes"] >= 1
        draft = batcher.draft
        batcher.draft = None  # plain-decode reference on the same server
        try:
            plain_g, _ = await _pooled_generate(batcher, hidden, 14, {"context": ctx})
            plain_s, _ = await _pooled_generate(batcher, hidden, 14, dict(sampled))
        finally:
            batcher.draft = draft
        np.testing.assert_array_equal(spec_g, plain_g)
        np.testing.assert_array_equal(spec_s, plain_s)
        # cooperative draft (same weights, unquantized): on the greedy stream
        # speculation actually pays — most proposals are accepted (9 of 9
        # here). The share is taken on that stream alone: the draft proposes
        # greedily (server/spec_decode.py), so against the sampled session's
        # own draws at temperature 0.8 it matches by chance only (0 of 30
        # here, CPU, PR 28), and a share pooled over both said 9 of 39.
        accepted, proposed = accepted - accepted0, proposed - proposed0
        assert proposed > 0 and accepted / proposed > 0.3, (accepted, proposed)

    spec_swarm.run(main())


def test_mixed_tick_spec_plain_prefill(spec_swarm):
    """Spec lanes coexist with plain decode lanes and chunked prefills in
    the same flush loop: a speculating session, a 2-token session (remaining
    < k+1, so it never speculates), and a concurrent prefill all run
    concurrently and all produce their plain-path streams."""
    batcher = _batcher(spec_swarm)

    async def main():
        rng = np.random.RandomState(23)
        ctx_a = [int(t) for t in rng.randint(0, 100, size=6)]
        ctx_b = [int(t) for t in rng.randint(0, 100, size=5)]
        hid_a, hid_b = _embed(batcher, ctx_a), _embed(batcher, ctx_b)
        pre = rng.randn(1, 20, batcher.backend.cfg.hidden_size).astype(np.float32) * 0.1

        async def prefill_only():
            lane = await batcher.acquire_lane(timeout=60)
            try:
                return await batcher.prefill_lane(lane, pre, 0)
            finally:
                batcher.release_lane(lane)

        spec0, gen0 = batcher.stats["spec_steps"], batcher.stats["gen_steps"]
        (toks_a, _), (toks_b, _), pre_out = await asyncio.gather(
            _pooled_generate(batcher, hid_a, 16, {"context": ctx_a}),
            _pooled_generate(batcher, hid_b, 2, {"context": ctx_b}),
            prefill_only(),
        )
        assert batcher.stats["spec_steps"] > spec0
        assert batcher.stats["gen_steps"] > gen0, "the 2-token lane should decode plain"
        assert pre_out.shape == (1, 20, batcher.backend.cfg.hidden_size)
        draft = batcher.draft
        batcher.draft = None
        try:
            ref_a, _ = await _pooled_generate(batcher, hid_a, 16, {"context": ctx_a})
            ref_b, _ = await _pooled_generate(batcher, hid_b, 2, {"context": ctx_b})
        finally:
            batcher.draft = draft
        np.testing.assert_array_equal(toks_a, ref_a)
        np.testing.assert_array_equal(toks_b, ref_b)

    spec_swarm.run(main())


def test_spec_ema_autodisable_journals_evidence(spec_swarm):
    """A draft whose proposals keep missing trips the per-lane acceptance
    EMA below the floor: speculation disables for a cooldown window, the
    journal records a ``spec_disabled`` event WITH the EMA evidence, and the
    output stream is still exactly the plain stream."""
    batcher = _batcher(spec_swarm)

    async def main():
        rng = np.random.RandomState(29)
        ctx = [int(t) for t in rng.randint(0, 100, size=6)]
        hidden = _embed(batcher, ctx)
        old_floor = batcher._spec_min_accept
        batcher._spec_min_accept = 0.95
        # hostile draft: constant proposals, (almost) never the next token
        batcher.draft.propose = lambda contexts: np.full(
            (len(contexts), SPEC_K), 3, np.int32
        )
        seq0 = get_journal().seq
        disabled0 = batcher.stats["spec_disabled"]
        try:
            toks, _ = await _pooled_generate(batcher, hidden, 12, {"context": ctx})
        finally:
            batcher._spec_min_accept = old_floor
            del batcher.draft.propose  # restore the class method
        assert batcher.stats["spec_disabled"] > disabled0
        events = get_journal().events(kind="spec_disabled", since_seq=seq0)
        assert events, "no spec_disabled journal event"
        ev = events[0]
        assert ev["ema"] < 0.95 and ev["floor"] == 0.95
        assert ev["cooldown_ticks"] >= 1 and ev["proposed"] > 0
        # cooldown: after the disable, the rest of the stream decodes plain
        draft = batcher.draft
        batcher.draft = None
        try:
            ref, _ = await _pooled_generate(batcher, hidden, 12, {"context": ctx})
        finally:
            batcher.draft = draft
        np.testing.assert_array_equal(toks, ref)

    spec_swarm.run(main())


def test_spec_ledger_attribution_and_conservation(spec_swarm):
    """PR 10 honesty: the whole spec tick's wall is billed through the
    normal note_compute path (conservation unchanged), the draft's share
    rides as the draft_seconds "of which" annotation, every emitted token is
    billed exactly once, and acceptance_rate / tokens_per_compute_second are
    derived per delta — then the allocator comes back clean."""
    from petals_tpu.telemetry.ledger import ResourceLedger

    batcher = _batcher(spec_swarm)

    async def main():
        rng = np.random.RandomState(31)
        ctx = [int(t) for t in rng.randint(0, 100, size=6)]
        hidden = _embed(batcher, ctx)
        old_led = batcher._ledger
        led = ResourceLedger()
        batcher._ledger = led
        try:
            _, usage = await _pooled_generate(
                batcher, hidden, 16, {"context": ctx}, peer_id="tenant-spec"
            )
        finally:
            batcher._ledger = old_led
        assert usage is not None
        assert usage["decode_tokens"] == 15, usage  # n_tokens - 1, spec + plain ticks
        assert usage["prefill_tokens"] == 6
        assert usage["spec_proposed"] > 0
        assert usage.get("spec_accepted", 0) >= 0
        assert 0.0 < usage["draft_seconds"] < usage["compute_seconds"]
        assert 0.0 <= usage["acceptance_rate"] <= 1.0
        assert usage["tokens_per_compute_second"] > 0
        # conservation over the isolated ledger: every page-second is either
        # attributed to a session or explicitly unattributed. The two sides
        # sample the wall clock at different instants, so under a loaded
        # single-core run they can drift a few tenths of a percent — the
        # tolerance bounds the *accounting* identity, not scheduler jitter.
        snap = led.snapshot()
        assert led.attributed_page_seconds() + snap["unattributed_page_seconds"] == (
            pytest.approx(snap["pool_page_seconds"], rel=1e-2, abs=1e-6)
        )
        # rollback never frees or releases pages mid-stream; after release
        # the allocator must be whole again
        assert batcher._pages.n_free == batcher.n_pages
        assert (batcher._pages.refs == 0).all()

    spec_swarm.run(main())


def test_spec_zero_postwarmup_recompiles(spec_swarm):
    """Both new programs (draft_propose, paged_spec_verify) run under
    tracked_jit with static pool shapes: after warmup, further generations
    must not compile — a single anomaly event for either fn fails."""
    from petals_tpu.telemetry.observatory import get_observatory

    batcher = _batcher(spec_swarm)

    async def main():
        rng = np.random.RandomState(37)
        for i in range(2):  # push the wrappers well past the warmup budget
            ctx = [int(t) for t in rng.randint(0, 100, size=6)]
            await _pooled_generate(batcher, _embed(batcher, ctx), 20, {"context": ctx})

    spec_swarm.run(main())
    fns = {f["fn"]: f for f in get_observatory().functions()}
    for name in ("draft_propose", "paged_spec_verify"):
        assert name in fns, f"{name} never ran under the observatory"
        assert fns[name]["anomalies"] == 0, fns[name]
    anomalies = [
        e for e in get_journal().events(kind="compile_anomaly")
        if e.get("fn") in ("draft_propose", "paged_spec_verify")
    ]
    assert anomalies == []


def test_server_announces_spec_k(spec_swarm):
    from petals_tpu.data_structures import ServerState

    info = spec_swarm.servers[0]._server_info(ServerState.ONLINE)
    assert info.spec_k == SPEC_K
    assert info.server_gen is True


# ------------------------------------------------------------------ e2e client


def test_e2e_generate_with_spec_matches_hf(spec_swarm, model_path):
    """Whole-stack check through the real client: generate() against the
    speculating server stays token-identical to HF greedy and reproducible
    under a fixed sampling seed — speculation changed the speed contract,
    never the output contract."""
    from petals_tpu.client.model import AutoDistributedModelForCausalLM

    batcher = _batcher(spec_swarm)
    model = AutoDistributedModelForCausalLM.from_pretrained(
        model_path, initial_peers=spec_swarm.initial_peers
    )
    try:
        rng = np.random.RandomState(41)
        input_ids = rng.randint(0, 100, (1, 6)).astype(np.int64)
        spec0 = batcher.stats["spec_steps"]
        acc0 = batcher.stats["spec_accepted"]
        out = model.generate(input_ids, max_new_tokens=12)
        np.testing.assert_array_equal(out, _hf_greedy(model_path, input_ids, 12))
        # the greedy fast path must ship the prompt as the draft's context:
        # a cooperative draft with the full window accepts on a repetitive
        # tiny-model stream — zero acceptance means the window went missing
        assert batcher.stats["spec_accepted"] > acc0, (
            "greedy client path got zero accepted drafts"
        )
        case = dict(do_sample=True, temperature=0.8, top_k=10, seed=77)
        out1 = model.generate(input_ids, max_new_tokens=10, **case)
        out2 = model.generate(input_ids, max_new_tokens=10, **case)
        np.testing.assert_array_equal(out1, out2)
        assert batcher.stats["spec_steps"] > spec0, "spec path never engaged e2e"
    finally:
        model.close()


@pytest.mark.parametrize("variant", ["gen", "spec"])
def test_gen_and_spec_steps_emit_their_phases_in_order(spec_swarm, monkeypatch, variant):
    """The server-side generation body and the draft-verify body go through
    the same phase walker as the decode bodies: one ``ptu.step`` a step, its
    variant in the arguments, assemble / dispatch / wait / post inside it in
    that order, and the four counters grow."""
    from tests.utils import record_step_annotations, recorded_steps

    batcher = _batcher(spec_swarm)
    events = record_step_annotations(monkeypatch)
    keys = ("assemble_s", "dispatch_s", "wait_s", "post_s")

    async def main():
        ctx = [int(t) for t in np.random.RandomState(29).randint(0, 100, size=6)]
        before = dict(batcher.stats)
        draft = batcher.draft
        if variant == "gen":
            batcher.draft = None  # the lane decodes plain: _run_batch_gen
        try:
            await _pooled_generate(batcher, _embed(batcher, ctx), 10, {"context": ctx})
        finally:
            batcher.draft = draft
        assert batcher.stats[f"{variant}_steps"] > before[f"{variant}_steps"]
        assert all(batcher.stats[k] > before[k] for k in keys)

    spec_swarm.run(main())
    steps = [s for s in recorded_steps(events) if s[0]["variant"] == variant]
    assert steps and all(args["lanes"] == 1 and args["prefill_tokens"] == 0 for args, _ in steps)
    assert all(
        names == ["ptu.step." + k[:-2] for k in keys] for _args, names in steps
    ), steps
