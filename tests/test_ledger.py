"""Per-tenant resource ledger (telemetry/ledger.py + the batcher/scheduler
wiring): page-second attribution must CONSERVE — the per-session fractional
COW split plus the unattributed remainder equals the pool occupancy integral
— stay exact under concurrent writers, bound peer cardinality like the
metrics registry, and feed both the DRF noisy-neighbor detector and the
scheduler's fair-share ranks. The e2e test forces one greedy tenant to
starve three light ones on an oversubscribed pool and expects the journal,
the /ledger endpoint, and the clients' step_meta bills to all show it."""

import asyncio
import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from petals_tpu.telemetry.ledger import (
    ANON_PEER,
    OVERFLOW_PEER,
    USAGE_FIELDS,
    ResourceLedger,
    get_ledger,
    normalize_peer,
)

pytestmark = pytest.mark.telemetry


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def make_ledger(**kw):
    clock = FakeClock()
    kw.setdefault("window_s", 10.0)
    kw.setdefault("noisy_min_interval_s", 0.0)
    kw.setdefault("noisy_cooldown_s", 0.0)
    return ResourceLedger(clock=clock, **kw), clock


# ------------------------------------------------------------ conservation


def test_fractional_cow_conservation_under_refcount_churn():
    """Adopt, fork, prefix pin, and dead-lane release all move refcounts;
    after every move the per-session page-second split (1/refcount per
    referenced page, via PageAllocator.fractional_shares) plus the
    unattributed remainder must still integrate to the pool occupancy."""
    from petals_tpu.server.memory_cache import PageAllocator

    led, clock = make_ledger()
    alloc = PageAllocator(8)
    tables = np.full((2, 4), -1, np.int64)

    def sync(keys_by_row):
        occupied = float(alloc.n_pages - alloc.n_free)
        rows = list(keys_by_row)
        shares = alloc.fractional_shares(tables[rows])
        led.set_rates(
            {keys_by_row[r]: float(s) for r, s in zip(rows, shares)}, occupied
        )

    a = led.open_session("peer-a")
    b = led.open_session("peer-b")

    # t=0: A allocates two private pages
    p0, p1 = alloc.try_alloc(), alloc.try_alloc()
    tables[0, 0], tables[0, 1] = p0, p1
    sync({0: a, 1: b})
    clock.advance(1.0)

    # t=1: B adopts p0 (COW share): both rows now hold it at refcount 2
    alloc.incref(p0)
    tables[1, 0] = p0
    sync({0: a, 1: b})
    clock.advance(1.0)

    # t=2: B forks a private page (copy-on-write write)
    p2 = alloc.try_alloc()
    tables[1, 1] = p2
    sync({0: a, 1: b})
    clock.advance(1.0)

    # t=3: the prefix cache pins p1 — that extra ref has NO live lane, so
    # half of p1's residency becomes unattributed from here on
    alloc.incref(p1)
    sync({0: a, 1: b})
    clock.advance(1.0)

    # t=4: dead-lane release — A closes; p1 survives on the prefix pin alone
    alloc.decref(p0)
    alloc.decref(p1)
    tables[0, :] = -1
    totals_a = led.close_session(a)
    sync({1: b})
    clock.advance(1.0)

    # t=5: pin released, p1 freed
    alloc.decref(p1)
    sync({1: b})
    clock.advance(1.0)

    totals_b = led.close_session(b)
    alloc.decref(p0)
    alloc.decref(p2)
    tables[1, :] = -1
    snap = led.snapshot()

    # hand-integrated expectations (piecewise-constant rates)
    assert totals_a["page_seconds"] == pytest.approx(2 + 1.5 + 1.5 + 1.0)
    assert totals_b["page_seconds"] == pytest.approx(0.5 + 1.5 + 1.5 + 2 + 2)
    assert snap["unattributed_page_seconds"] == pytest.approx(0.5 + 1.0)
    assert snap["pool_page_seconds"] == pytest.approx(2 + 2 + 3 + 3 + 3 + 2)
    # conservation: attributed + unattributed == pool integral
    assert led.attributed_page_seconds() + snap["unattributed_page_seconds"] == (
        pytest.approx(snap["pool_page_seconds"])
    )
    # nothing leaked into the allocator either
    assert alloc.n_free == alloc.n_pages


def test_lazy_reads_do_not_disturb_rates():
    """snapshot()/usage_delta() settle up to "now" but must not change the
    piecewise-constant rates — interleaving reads cannot change the bill."""
    led, clock = make_ledger()
    a = led.open_session("p")
    led.set_rates({a: 2.0}, 2.0)
    for _ in range(5):
        clock.advance(0.2)
        led.snapshot()
        led.session_usage(a)
    clock.advance(1.0)
    assert led.close_session(a)["page_seconds"] == pytest.approx(2.0 * 2.0)


def test_ledger_exact_under_concurrent_writers():
    """Eight threads hammer the additive meters (the compute thread's calls)
    against concurrent settles/reads; integer meters must come out EXACT and
    the float ones within accumulation tolerance."""
    led, _clock = make_ledger()
    keys = [led.open_session(f"peer-{i}") for i in range(8)]
    n_iters, stop = 500, threading.Event()

    def writer(key):
        for _ in range(n_iters):
            led.note_compute([key], 1e-4)
            led.note_tokens(key, prefill=2, decode=1)
            led.note_swap(key, out_bytes=3, in_bytes=2)
            led.note_migrated(key, 5)

    def reader():
        while not stop.is_set():
            led.snapshot(k=8)
            led.peer_totals()
            led.usage_delta(keys[0])

    threads = [threading.Thread(target=writer, args=(k,)) for k in keys]
    spectators = [threading.Thread(target=reader) for _ in range(2)]
    for t in threads + spectators:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    for t in spectators:
        t.join()

    totals = led.peer_totals()
    for i in range(8):
        u = totals[f"peer-{i}"]
        assert u["decode_tokens"] == n_iters
        assert u["prefill_tokens"] == 2 * n_iters
        assert u["swap_out_bytes"] == 3 * n_iters
        assert u["swap_in_bytes"] == 2 * n_iters
        assert u["migrated_bytes"] == 5 * n_iters
        assert u["compute_seconds"] == pytest.approx(n_iters * 1e-4, rel=1e-9)


# ------------------------------------------------- cardinality + lifecycle


def test_normalize_peer_and_overflow_discipline():
    assert normalize_peer(None) == ANON_PEER
    assert normalize_peer("") == ANON_PEER
    assert len(normalize_peer("x" * 200)) == 64

    led, _ = make_ledger(max_peers=2)
    led.open_session("p1")
    led.open_session("p2")
    k3 = led.open_session("p3")  # past the cap: collapses to _overflow
    k4 = led.open_session("p4")
    led.note_tokens(k3, decode=1)
    led.note_tokens(k4, decode=1)
    assert led.peer_overflows == 2
    totals = led.peer_totals()
    assert set(totals) == {"p1", "p2", OVERFLOW_PEER}
    assert totals[OVERFLOW_PEER]["decode_tokens"] == 2
    # the out-push rollup path honors the same cap
    led.note_migrated(None, 7, peer_id="p5")
    assert led.peer_totals()[OVERFLOW_PEER]["migrated_bytes"] == 7


def test_usage_delta_pops_and_close_folds_rollup():
    led, clock = make_ledger()
    a = led.open_session("peer-a", trace_id="t-1")
    led.set_rates({a: 1.0}, 1.0)
    clock.advance(1.0)
    led.note_tokens(a, decode=3)
    d1 = led.usage_delta(a)
    assert d1["decode_tokens"] == 3 and d1["page_seconds"] == pytest.approx(1.0)
    assert isinstance(d1["decode_tokens"], int)  # integral deltas stay ints
    assert led.usage_delta(a) == {}  # popped: nothing new
    clock.advance(0.5)
    assert led.usage_delta(a)["page_seconds"] == pytest.approx(0.5)
    assert led.usage_delta("nope") is None
    led.close_session(a)
    assert led.peer_totals()["peer-a"]["decode_tokens"] == 3
    assert led.session_usage(a) is None


# --------------------------------------------------------------------- DRF


def _drive_two_peers(led, clock, greedy, light):
    """greedy accrues 3 pages + most compute; light 1 page + a little."""
    led.set_rates({greedy: 3.0, light: 1.0}, 4.0)
    clock.advance(2.0)
    led.note_compute([greedy], 0.9)
    led.note_compute([light], 0.1)


def test_noisy_neighbor_detector_and_cooldown():
    led, clock = make_ledger(noisy_share=0.5, noisy_cooldown_s=5.0)
    g = led.open_session("greedy")
    l = led.open_session("light")
    _drive_two_peers(led, clock, g, l)

    # no one queued: never a neighbor problem
    assert led.check_noisy([]) is None
    # only the greedy peer's own admissions queue: not a neighbor problem
    assert led.check_noisy(["greedy"]) is None

    ev = led.check_noisy(["light", "other"])
    assert ev is not None
    assert ev["peer"] == "greedy"
    assert ev["dominant_share"] >= 0.5
    assert ev["dominant_resource"] in ("page_seconds", "compute_seconds")
    assert ev["queued_peers"] == ["light", "other"]
    assert ev["top"][0]["peer"] == "greedy"
    assert led.noisy_events == 1

    # cooldown: the same peer cannot re-fire until noisy_cooldown_s passes
    clock.advance(1.0)
    assert led.check_noisy(["light"]) is None
    clock.advance(5.0)
    assert led.check_noisy(["light"])["peer"] == "greedy"
    assert led.noisy_events == 2


def test_noisy_detector_respects_min_interval():
    led, clock = make_ledger(noisy_min_interval_s=1.0, noisy_share=0.5)
    g = led.open_session("greedy")
    l = led.open_session("light")
    _drive_two_peers(led, clock, g, l)
    assert led.check_noisy(["light"]) is not None
    clock.advance(0.5)  # within the sampling interval: throttled
    assert led.check_noisy(["light"]) is None


def test_dominant_share_ignores_uncontended_resources():
    """A peer alone on an idle resource (delta below the floor) must not
    read as dominating it at 100%."""
    led, clock = make_ledger()
    a = led.open_session("a")
    led.open_session("b")
    led.note_tokens(a, decode=0)  # nothing at all yet
    clock.advance(1.0)
    assert led.peer_dominant_share("a") == 0.0
    # sub-floor swap activity still cannot define dominance
    led.note_swap(a, out_bytes=0, in_bytes=0)
    clock.advance(1.0)
    assert led.peer_dominant_share("a") == 0.0


def test_rebase_window_forgets_history():
    led, clock = make_ledger()
    g = led.open_session("greedy")
    l = led.open_session("light")
    _drive_two_peers(led, clock, g, l)
    assert led.peer_dominant_share("greedy") >= 0.5
    led.rebase_window()
    led.set_rates({g: 0.0, l: 0.0}, 0.0)
    clock.advance(1.0)
    # post-rebase, only NEW activity counts — and there is none
    assert led.peer_dominant_share("greedy") == 0.0


def test_snapshot_digest_shapes():
    led, clock = make_ledger()
    a = led.open_session("peer-a", trace_id="tr")
    led.set_rates({a: 2.0}, 2.0)
    clock.advance(1.0)
    snap = led.snapshot(k=3)
    for key in ("window_s", "peers", "sessions", "pool_page_seconds",
                "unattributed_page_seconds", "peer_overflows", "noisy_events",
                "top", "live_sessions"):
        assert key in snap
    live = snap["live_sessions"][0]
    assert live["peer"] == "peer-a" and live["trace_id"] == "tr"
    assert all(f in live for f in USAGE_FIELDS)

    dig = led.digest(k=2)
    assert set(dig) == {
        "peers", "sessions", "page_s", "compute_s", "cache_byte_s", "noisy", "top"
    }
    assert dig["top"][0][0] == "peer-a"  # [peer16, share, page_s] triples
    json.dumps(dig)  # must be announce-serializable


def test_cache_residency_channel_is_conservation_neutral():
    """The prefix cache's set_cache_rates bills per-tenant resident bytes
    through a SEPARATE channel: byte-seconds integrate piecewise-constant
    like page-seconds, show up in snapshot/top/digest, and leave both the
    page-second conservation identity and the DRF vectors untouched."""
    led, clock = make_ledger()
    a = led.open_session("peer-a")
    led.set_rates({a: 2.0}, 2.0)
    led.set_cache_rates({"peer-a": 1000.0, "peer-b": 3000.0})
    clock.advance(2.0)
    resid = led.cache_residency()
    assert resid["peer-a"] == pytest.approx(2000.0)
    assert resid["peer-b"] == pytest.approx(6000.0)
    # rate change settles the old interval first
    led.set_cache_rates({"peer-a": 500.0})
    clock.advance(1.0)
    resid = led.cache_residency()
    assert resid["peer-a"] == pytest.approx(2500.0)
    assert resid["peer-b"] == pytest.approx(6000.0)  # rate dropped to 0

    # conservation: cache billing added NOTHING to the page-second books
    assert led.pool_page_seconds == pytest.approx(6.0)
    assert led.attributed_page_seconds() == pytest.approx(6.0)
    # ...and nothing to the DRF vector (peer-b never held a page)
    assert led.peer_dominant_share("peer-b") == 0.0

    snap = led.snapshot(k=3)
    assert snap["cache_byte_seconds"] == pytest.approx(8500.0)
    by_peer = {row["peer"]: row for row in snap["top"]}
    assert by_peer["peer-a"]["cache_byte_s"] == pytest.approx(2500.0)
    # a cache-only tenant still gets a top row (zero usage, billed bytes)
    assert by_peer["peer-b"]["cache_byte_s"] == pytest.approx(6000.0)
    json.dumps(led.digest(k=2))


def test_cache_rates_respect_peer_cardinality_bound():
    led, clock = make_ledger(max_peers=2)
    led.set_cache_rates({f"peer-{i}": 100.0 for i in range(5)})
    clock.advance(1.0)
    resid = led.cache_residency()
    # past max_peers the rest collapse into the overflow rollup
    assert OVERFLOW_PEER in resid
    assert resid[OVERFLOW_PEER] == pytest.approx(300.0)
    assert sum(resid.values()) == pytest.approx(500.0)


# --------------------------------------------------- scheduler integration


def test_scheduler_ranks_by_dominant_share():
    """pick_waiter prefers the lighter tenant and pick_victim the heavier
    one when a usage_fn is wired; without one both degrade to the exact
    pre-ledger order (covered by test_scheduler.py, re-checked here)."""
    from petals_tpu.data_structures import SESSION_PRIORITY_NORMAL
    from petals_tpu.server.batching import _LaneWaiter
    from petals_tpu.server.memory_cache import HostSwapPool
    from petals_tpu.server.scheduler import SessionScheduler

    shares = {"greedy": 0.9, "light": 0.05}

    async def main():
        loop = asyncio.get_running_loop()

        def waiter(peer, seq):
            return _LaneWaiter(
                fut=loop.create_future(), priority=SESSION_PRIORITY_NORMAL,
                peer_id=peer, seq=seq,
            )

        sched = SessionScheduler(
            HostSwapPool(0), usage_fn=lambda p: shares.get(p, 0.0)
        )
        # greedy arrived first and holds FEWER lanes — share still outranks
        sched.register(0, "light", SESSION_PRIORITY_NORMAL)
        w_greedy, w_light = waiter("greedy", 0), waiter("light", 1)
        assert sched.pick_waiter([w_greedy, w_light]) is w_light

        # victim choice: equal priority, the dominant peer is evicted first
        pages = {0: 2, 1: 2}
        sched2 = SessionScheduler(
            HostSwapPool(1 << 20), policy="lru", pages_fn=pages.get,
            usage_fn=lambda p: shares.get(p, 0.0),
        )
        sched2.register(0, "light", SESSION_PRIORITY_NORMAL)
        sched2.register(1, "greedy", SESSION_PRIORITY_NORMAL)
        sched2.touch(0)
        sched2.touch(1)  # greedy is MOST recently stepped: LRU alone spares it
        assert sched2.pick_victim([0, 1]) == 1

        # a broken usage_fn degrades to share 0.0, never blocks admission
        sched3 = SessionScheduler(
            HostSwapPool(0), usage_fn=lambda p: 1 / 0
        )
        assert sched3.peer_usage_share("anyone") == 0.0

    asyncio.run(main())


def test_fair_share_reduces_light_peer_admission_wait():
    """Deterministic replay of an admission backlog: one greedy tenant's
    four queued sessions vs three light tenants' one each. Ledger-informed
    fair share admits every light session before the greedy backlog; the
    lanes-held rank alone (all zero held — they are WAITERS) degrades to
    FIFO and makes the lights wait behind the greedy burst."""
    from petals_tpu.data_structures import SESSION_PRIORITY_NORMAL
    from petals_tpu.server.batching import _LaneWaiter
    from petals_tpu.server.memory_cache import HostSwapPool
    from petals_tpu.server.scheduler import SessionScheduler

    def admission_positions(usage_fn):
        async def main():
            loop = asyncio.get_running_loop()
            sched = SessionScheduler(HostSwapPool(0), usage_fn=usage_fn)
            waiters = [
                _LaneWaiter(
                    fut=loop.create_future(),
                    priority=SESSION_PRIORITY_NORMAL, peer_id=peer, seq=seq,
                )
                for seq, peer in enumerate(
                    ["greedy"] * 4 + ["light-1", "light-2", "light-3"]
                )
            ]
            order = {}
            pending = list(waiters)
            for position in range(len(waiters)):
                w = sched.pick_waiter(pending)
                w.fut.set_result(position)
                order[w.peer_id, w.seq] = position
                pending.remove(w)
            return [
                pos for (peer, _), pos in order.items() if peer.startswith("light")
            ]

        return asyncio.run(main())

    shares = {"greedy": 0.8}
    fair = admission_positions(lambda p: shares.get(p, 0.0))
    fifo = admission_positions(None)
    assert max(fair) < min(fifo)  # p99 light wait strictly improves
    assert sorted(fair) == [0, 1, 2]
    assert sorted(fifo) == [4, 5, 6]


# ------------------------------------------------------------- exposition


def test_ledger_endpoint_and_metrics():
    from petals_tpu.telemetry.exposition import MetricsServer, telemetry_digest

    led = get_ledger()
    key = led.open_session("endpoint-peer")
    led.note_tokens(key, decode=2)
    server = MetricsServer(port=0)
    try:
        base = f"http://127.0.0.1:{server.port}"
        # k well over what the process may hold: the ledger is the process's own, the view lists its FIRST k
        # live sessions, and test files that ran earlier on this xdist worker leave theirs open
        # (tests/test_gather.py 26, tests/test_mixed_batching.py 11): with k=4 this passed or failed by
        # which files the worker had been given before
        with urllib.request.urlopen(f"{base}/ledger?k=1000", timeout=5) as r:
            view = json.loads(r.read())
        assert view["sessions"] >= 1
        assert any(
            s["peer"] == "endpoint-peer" for s in view["live_sessions"]
        )
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(f"{base}/ledger?k=bogus", timeout=5)
        assert e.value.code == 400
        # aggregate-only metrics: the ledger series exist, peer ids do NOT
        with urllib.request.urlopen(f"{base}/metrics", timeout=5) as r:
            text = r.read().decode()
        assert "petals_ledger_page_seconds_total" in text
        assert "petals_ledger_noisy_neighbor_total" in text
        assert "endpoint-peer" not in text
    finally:
        server.close()
        led.close_session(key)
    digest = telemetry_digest()
    assert set(digest["ledger"]) == {
        "peers", "sessions", "page_s", "compute_s", "cache_byte_s", "noisy", "top"
    }


def test_hop_trace_accumulates_usage():
    from petals_tpu.telemetry.spans import HopTrace

    hop = HopTrace("peer-x", 0, 4)
    hop.record(0.1, {"usage": {"page_seconds": 0.5, "decode_tokens": 1}})
    hop.record(0.1, {"usage": {"page_seconds": 0.25, "decode_tokens": 1,
                               "swap_out_bytes": 64}})
    hop.record(0.1, {"usage": {"decode_tokens": "garbage"}})  # ignored
    hop.record(0.1, None)  # meta-less steps leave the bill alone
    assert hop.usage["page_seconds"] == pytest.approx(0.75)
    assert hop.usage["decode_tokens"] == 2
    assert hop.usage["swap_out_bytes"] == 64
    assert hop.to_dict()["usage"]["page_seconds"] == pytest.approx(0.75)


def test_health_monitor_aggregates_ledger_digests():
    from petals_tpu.cli.run_health import render_top
    from petals_tpu.utils.health import HealthMonitor

    monitor = HealthMonitor([])
    monitor._state = {
        "updated_at": 123.0,
        "models": {
            "model-a": {
                "servers": {
                    "srv-1": {"telemetry": {
                        "tok_s": 1.0,
                        "ledger": {
                            "peers": 2, "sessions": 2, "page_s": 6.0,
                            "compute_s": 1.0, "noisy": 1,
                            "top": [["tenant-a", 0.8, 5.0], ["tenant-b", 0.2, 1.0]],
                        },
                    }},
                    "srv-2": {"telemetry": {
                        "tok_s": 1.0,
                        "ledger": {
                            "peers": 1, "sessions": 1, "page_s": 2.5,
                            "compute_s": 0.5, "noisy": 0,
                            "top": [["tenant-a", 0.6, 2.5]],
                        },
                    }},
                    "srv-3": {"telemetry": None},  # pre-ledger server: skipped
                }
            }
        },
    }
    agg = monitor.metrics_summary()["models"]["model-a"]["aggregate"]
    assert agg["ledger_page_s"] == pytest.approx(8.5)
    assert agg["ledger_sessions"] == 3
    assert agg["noisy_neighbor_events"] == 1
    top = agg["top_consumers"]
    assert top[0]["peer"] == "tenant-a"
    assert top[0]["page_s"] == pytest.approx(7.5)
    assert top[0]["share_max"] == pytest.approx(0.8)
    assert top[0]["servers"] == 2

    rendered = render_top(monitor.metrics_summary())
    assert "tenant-a" in rendered and "1 noisy-neighbor events" in rendered


def test_run_health_cli_exposes_top(capsys):
    from petals_tpu.cli.run_health import main

    with pytest.raises(SystemExit):
        main(["--help"])
    assert "--top" in capsys.readouterr().out


# --------------------------------------- e2e: forced noisy-neighbor scenario


@pytest.fixture(scope="module")
def model_path(tmp_path_factory):
    from tests.utils import make_tiny_llama

    return make_tiny_llama(str(tmp_path_factory.mktemp("models")))


def test_e2e_noisy_neighbor_detected_and_billed(model_path):
    """One greedy tenant (long prefill, long decode) and three light tenants
    sharing a 2-lane paged pool, identities via the unauthenticated
    peer_hint: the lights queue behind the greedy session, the DRF detector
    fires and journals evidence, the live /ledger endpoint ranks the greedy
    peer on top, and every greedy step reply carries its usage bill."""
    import jax.numpy as jnp

    from petals_tpu.data_structures import CHAIN_DELIMITER, make_uid
    from petals_tpu.rpc import RpcClient
    from petals_tpu.rpc.serialization import serialize_array
    from petals_tpu.server.server import Server, default_dht_prefix

    async def main():
        server = Server(
            model_path, compute_dtype=jnp.float32, use_flash=False,
            batching=True, batch_lanes=2, batch_max_length=64,
            page_size=16, n_pages=8, swap_host_bytes=1 << 26,
            metrics_port=0,
        )
        await server.start()
        client = await RpcClient.connect(
            server.rpc_server.host, server.rpc_server.port
        )
        batcher = server.handler.batcher
        led = batcher._ledger  # the process singleton: restore what we tune
        saved = (led.noisy_share, led.noisy_min_interval_s, led.noisy_cooldown_s)
        led.noisy_share, led.noisy_min_interval_s, led.noisy_cooldown_s = (
            0.3, 0.0, 0.0
        )
        led.rebase_window()  # shares must reflect THIS scenario, not history
        journal = batcher._journal
        noisy_before = len(journal.events(kind="noisy_neighbor"))
        events_before = led.noisy_events
        try:
            cfg = server.cfg
            prefix = default_dht_prefix(model_path)
            uids = CHAIN_DELIMITER.join(
                make_uid(prefix, i) for i in range(cfg.num_hidden_layers)
            )
            rng = np.random.RandomState(23)
            greedy_usage = []

            async def drive(hint, max_length, prefill_len, n_steps, usage_out):
                stream = await client.open_stream("ptu.inference")
                await stream.send({
                    "uids": uids, "max_length": max_length,
                    "peer_hint": hint, "alloc_timeout": 60,
                })
                await stream.recv(timeout=60)
                h = rng.randn(1, prefill_len, cfg.hidden_size).astype(np.float32) * 0.1
                await stream.send({"tensors": {"hidden": serialize_array(h)}})
                reply = await stream.recv(timeout=120)
                for _ in range(n_steps):
                    await asyncio.sleep(0.02)
                    step = rng.randn(1, 1, cfg.hidden_size).astype(np.float32) * 0.1
                    await stream.send({"tensors": {"hidden": serialize_array(step)}})
                    reply = await stream.recv(timeout=120)
                    usage = (reply.get("step_meta") or {}).get("usage")
                    if usage_out is not None and usage:
                        usage_out.append(usage)
                await stream.end()

            greedy_task = asyncio.create_task(
                drive("greedy-hog", 60, 33, 20, greedy_usage)
            )
            await asyncio.sleep(0.15)  # let the greedy span accrue dominance
            light_tasks = [
                asyncio.create_task(drive(f"light-{i}", 16, 4, 3, None))
                for i in range(3)
            ]
            await asyncio.gather(greedy_task, *light_tasks)

            # the detector fired and journaled ledger evidence
            events = journal.events(kind="noisy_neighbor")[noisy_before:]
            assert events, "noisy neighbor never journaled"
            assert led.noisy_events > events_before
            ev = events[-1]
            assert ev["peer"] == "greedy-hog"
            assert ev["dominant_share"] >= 0.3
            assert ev["dominant_resource"] in (
                "page_seconds", "compute_seconds", "tokens", "swap_bytes"
            )
            assert ev["top"][0]["peer"] == "greedy-hog"
            assert isinstance(ev["occupancy"], dict)  # batcher attached it
            assert any(p.startswith("light-") for p in ev["queued_peers"])

            # the greedy tenant saw its own bill on step replies
            assert greedy_usage, "no usage deltas rode step_meta"
            assert sum(u.get("decode_tokens", 0) for u in greedy_usage) >= 15
            assert any(u.get("page_seconds", 0) > 0 for u in greedy_usage)

            # the LIVE /ledger endpoint ranks the greedy tenant on top
            port = server._metrics_server.port
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/ledger?k=8", timeout=5
            ) as r:
                view = json.loads(r.read())
            rows = {t["peer"]: t for t in view["top"]}
            assert "greedy-hog" in rows, view["top"]
            for peer, row in rows.items():
                if peer.startswith("light-"):
                    assert rows["greedy-hog"]["page_s"] > row["page_s"]
            assert view["noisy_events"] > 0
        finally:
            led.noisy_share, led.noisy_min_interval_s, led.noisy_cooldown_s = saved
            await client.close()
            await server.shutdown()

    asyncio.run(main())
