"""Swarm telemetry plane (petals_tpu/telemetry/ + its hooks in the handler,
batcher, and scheduler): the metrics registry must stay exact under concurrent
writers and bounded under label abuse, trace ids minted by the client must tag
every server-side span/journal event of that session, a forced preemption +
swap cycle must leave a replayable journal whose events all carry the victim's
trace id and the occupancy snapshot that justified the decision, and the
/metrics endpoint must expose non-zero TTFT/step histograms in valid
Prometheus text."""

import asyncio
import json
import re
import threading
import urllib.error
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

from petals_tpu.data_structures import CHAIN_DELIMITER, make_uid
from petals_tpu.rpc import RpcClient
from petals_tpu.rpc.serialization import deserialize_array, serialize_array
from petals_tpu.server.server import Server, default_dht_prefix
from petals_tpu.telemetry import (
    MetricsRegistry,
    TelemetryJournal,
    current_trace_id,
    get_journal,
    new_trace_id,
    normalize_trace_id,
    render_prometheus,
    set_trace_id,
    reset_trace_id,
    telemetry_digest,
    trace_context,
)
from petals_tpu.telemetry import instruments as tm
from tests.utils import make_tiny_llama

pytestmark = pytest.mark.telemetry


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


# ------------------------------------------------------------ registry units


def test_counter_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", "requests")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("busy", "busy lanes")
    g.set(4)
    g.dec()
    assert g.value == 3.0
    # re-registration with identical shape returns the same family...
    assert reg.counter("reqs_total") is c
    # ...a conflicting redeclaration is a programming error
    with pytest.raises(ValueError):
        reg.gauge("reqs_total")
    with pytest.raises(ValueError):
        reg.counter("reqs_total", labels=("mode",))


def test_label_cap_routes_to_overflow_series():
    reg = MetricsRegistry()
    c = reg.counter("per_thing", labels=("thing",), max_series=4)
    for i in range(10):
        c.labels(thing=f"t{i}").inc()
    snap = reg.snapshot()
    series = snap["per_thing"]["series"]
    # memory stays bounded: 4 real children + the shared overflow child
    assert len(series) == 5
    assert series["thing=_overflow"] == 6.0
    # ...and the drop is surfaced AS a metric, never silent
    overflow = snap["telemetry_label_overflow_total"]["series"]
    assert overflow["metric=per_thing"] == 6.0


def test_concurrent_writers_exact():
    reg = MetricsRegistry()
    c = reg.counter("n", "")
    h = reg.histogram("lat", "", buckets=(0.1, 1.0))

    def work():
        for _ in range(10_000):
            c.inc()
            h.observe(0.05)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 80_000
    snap = h.snapshot()
    assert snap["count"] == 80_000 and snap["counts"][0] == 80_000


def test_histogram_bucket_math():
    reg = MetricsRegistry()
    h = reg.histogram("d", "", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.01, 0.05, 0.5, 5.0):
        h.observe(v)
    h.observe(float("nan"))  # guarded: must not poison sum/count
    h.observe(float("inf"))
    snap = h.snapshot()
    # bisect_left: a value equal to a bound lands IN that bound's bucket
    assert snap["counts"] == [2, 1, 1, 1]
    assert snap["cumulative"] == [2, 3, 4, 5]
    assert snap["count"] == 5
    assert abs(snap["sum"] - 5.565) < 1e-9
    # quantile: linear interpolation inside the winning bucket
    assert 0.0 < h.quantile(0.5) <= 0.1
    assert h.quantile(0.99) == 1.0  # clamped to the last finite bound


# ------------------------------------------------------------- trace context


def test_trace_id_normalization():
    assert normalize_trace_id("abc123-XYZ_") == "abc123-XYZ_"
    assert normalize_trace_id("bad id!") is None  # spaces/punct rejected
    assert normalize_trace_id("x" * 65) is None  # too long
    assert normalize_trace_id(42) is None
    assert normalize_trace_id(None) is None
    tid = new_trace_id()
    assert normalize_trace_id(tid) == tid and len(tid) == 16


def test_trace_contextvar_roundtrip():
    assert current_trace_id() is None
    token = set_trace_id("t-outer")
    try:
        assert current_trace_id() == "t-outer"
        with trace_context("t-inner"):
            assert current_trace_id() == "t-inner"
        assert current_trace_id() == "t-outer"
    finally:
        reset_trace_id(token)
    assert current_trace_id() is None


# ------------------------------------------------------------------ journal


def test_journal_capture_and_bounds():
    j = TelemetryJournal(maxlen=4)
    j.event("admission", trace_id="t1", lane=0, occupancy={"pages_free": 3})
    j.event("swap_out", trace_id="t1", lane=0, pages=2)
    j.event("admission", trace_id="t2", lane=1)
    assert len(j) == 3
    assert [e["kind"] for e in j.events(trace_id="t1")] == ["admission", "swap_out"]
    assert j.events(kind="admission", trace_id="t2")[0]["lane"] == 1
    # seq is monotonic and events carry their occupancy snapshot verbatim
    seqs = [e["seq"] for e in j]
    assert seqs == sorted(seqs)
    assert j.events(kind="admission", trace_id="t1")[0]["occupancy"] == {"pages_free": 3}
    # bounded: old events fall off, the journal never grows past maxlen
    for i in range(10):
        j.event("tick", lane=i)
    assert len(j) == 4
    # every line of the JSONL export parses back
    lines = j.to_jsonl().strip().splitlines()
    assert len(lines) == 4 and all(json.loads(line) for line in lines)


def test_journal_file_sink(tmp_path):
    path = tmp_path / "journal.jsonl"
    j = TelemetryJournal(maxlen=8, path=str(path))
    j.event("admission", trace_id="t1", lane=0)
    j.close()
    rows = [json.loads(line) for line in path.read_text().strip().splitlines()]
    assert rows[0]["kind"] == "admission" and rows[0]["trace_id"] == "t1"


# ------------------------------------------- tracer meta bounding (satellite)


def test_span_meta_bounded_and_trace_tagged():
    from petals_tpu.utils.tracing import Tracer

    tracer = Tracer(max_spans=64)
    truncated_before = tm.META_TRUNCATED.value
    big_meta = {f"k{i:02d}": "v" * 1000 for i in range(40)}
    with trace_context("span-trace-1"):
        with tracer.span("unit_test_span", **big_meta):
            pass
    meta = [s for s in tracer.recent() if s.name == "unit_test_span"][-1].meta
    # entries capped, values clipped — a hostile/buggy caller cannot balloon
    # the tracer ring; the drop is counted, not silent
    assert len(meta) <= 16
    assert all(len(v) <= 256 for v in meta.values() if isinstance(v, str))
    assert tm.META_TRUNCATED.value > truncated_before
    # the trace id is the one key bounding must never trim
    assert meta["trace_id"] == "span-trace-1"


# ------------------------------------------------- e2e: trace id propagation


def test_trace_id_propagation_client_to_scheduler(model_path):
    """The open-message trace id must reach the session-open reply, the
    scheduler slot, and the admission journal event; a malformed id is
    replaced by a server-minted one instead of being trusted."""

    async def main():
        server, client = await _start_server(
            model_path, batching=True, batch_lanes=4, batch_max_length=32,
            page_size=8,
        )
        try:
            cfg = server.cfg
            prefix = default_dht_prefix(model_path)
            uids = CHAIN_DELIMITER.join(
                make_uid(prefix, i) for i in range(cfg.num_hidden_layers)
            )
            tid = "cli-trace-0001"
            stream = await client.open_stream("ptu.inference")
            await stream.send(
                {"uids": uids, "max_length": 16, "batch_size": 1, "trace_id": tid}
            )
            ack = await stream.recv(timeout=60)
            assert ack["session_open"] and ack["trace_id"] == tid

            sched = server.handler.batcher._scheduler
            assert [s.trace_id for s in sched.lanes.values()] == [tid]
            admissions = get_journal().events(kind="admission", trace_id=tid)
            assert admissions, "admission event not journaled"
            assert "occupancy" in admissions[-1] and "wait_s" in admissions[-1]

            # a step's tracer span is tagged with the same id
            h = np.random.RandomState(0).randn(1, 3, cfg.hidden_size).astype(np.float32)
            await stream.send({"tensors": {"hidden": serialize_array(h)}})
            reply = await stream.recv(timeout=120)
            assert "tensors" in reply
            from petals_tpu.utils.tracing import get_tracer

            spans = [
                s for s in get_tracer().recent(500)
                if s.name == "inference_step" and s.meta.get("trace_id") == tid
            ]
            assert spans, "inference_step span not tagged with the trace id"
            await stream.end()

            # malformed ids are NOT echoed back: the server mints its own
            stream2 = await client.open_stream("ptu.inference")
            await stream2.send(
                {"uids": uids, "max_length": 16, "batch_size": 1,
                 "trace_id": "bad id! with spaces"}
            )
            ack2 = await stream2.recv(timeout=60)
            assert ack2["trace_id"] != "bad id! with spaces"
            assert normalize_trace_id(ack2["trace_id"]) is not None
            await stream2.end()
        finally:
            await client.close()
            await server.shutdown()

    run(main())


# ---------------------------------------- e2e: journaled preemption + swap


def test_journal_records_preemption_cycle(model_path):
    """Acceptance: one forced preemption+swap cycle yields a journal whose
    events (admission -> victim selection -> swap-out -> swap-in) all carry
    the victim session's trace id and the occupancy snapshot that justified
    the decision."""

    async def main():
        server, client = await _start_server(
            model_path, batching=True, batch_lanes=2, batch_max_length=32,
            page_size=8, n_pages=5, swap_host_bytes=1 << 22,
        )
        try:
            batcher = server.handler.batcher
            victim_tid, req_tid = new_trace_id(), new_trace_id()
            a = await batcher.acquire_lane(timeout=5, peer_id="victim", trace_id=victim_tid)
            b = await batcher.acquire_lane(timeout=5, peer_id="req", trace_id=req_tid)
            await batcher.prepare_write(a, 0, 32)  # victim takes all 4 slots
            assert batcher._pages.n_free == 0
            # pool exhausted: this write must preempt a, journaling the choice
            await batcher.prepare_write(b, 8, 9, timeout=5)
            assert batcher._scheduler.lanes[a].suspended
            # touching the victim forces the transparent swap-in
            await batcher.snapshot_lane(a, 16, 0, batcher.backend.n_blocks)
            assert not batcher._scheduler.lanes[a].suspended

            journal = get_journal()
            victim_events = journal.events(trace_id=victim_tid)
            kinds = [e["kind"] for e in victim_events]
            # the victim's full life is one causal timeline under ONE id
            for expected in ("admission", "victim_selected", "swap_out", "swap_in"):
                assert expected in kinds, (expected, kinds)
            assert kinds.index("admission") < kinds.index("victim_selected")
            assert kinds.index("victim_selected") < kinds.index("swap_out")
            assert kinds.index("swap_out") < kinds.index("swap_in")
            by_kind = {e["kind"]: e for e in victim_events}
            for kind in ("admission", "victim_selected", "swap_out", "swap_in"):
                occ = by_kind[kind]["occupancy"]
                assert isinstance(occ, dict) and "pages_free" in occ, (kind, occ)
            # the eviction names who asked and why it was legal
            picked = by_kind["victim_selected"]
            assert picked["requester_trace_id"] == req_tid
            assert picked["policy"] in ("lru", "largest")
            # the snapshot that justified the preemption: pool was exhausted
            assert picked["occupancy"]["pages_free"] == 0
            # swap volume is accounted in bytes on both legs
            assert by_kind["swap_out"]["nbytes"] > 0
            assert by_kind["swap_in"]["nbytes"] == by_kind["swap_out"]["nbytes"]

            batcher.release_lane(a)
            batcher.release_lane(b)
        finally:
            await client.close()
            await server.shutdown()

    run(main())


# ------------------------------------------------- e2e: /metrics exposition

_PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? (-?[0-9.e+-]+|NaN)$"
)


def _parse_prometheus(text):
    """Minimal format check + sample extraction: every non-comment line must
    be `name{labels} value`; returns {full_series_name: float}."""
    samples = {}
    for line in text.strip().splitlines():
        if line.startswith("#"):
            assert line.startswith(("# HELP ", "# TYPE ")), line
            continue
        assert _PROM_LINE.match(line), f"malformed exposition line: {line!r}"
        name, value = line.rsplit(" ", 1)
        samples[name] = float(value)
    return samples


def test_metrics_scrape_after_inference(model_path):
    """Run a real session against a server with the metrics endpoint enabled,
    then scrape /metrics over HTTP: TTFT and step-duration histograms must be
    non-zero and the exposition text must parse."""

    async def main():
        server, client = await _start_server(
            model_path, batching=True, batch_lanes=2, batch_max_length=32,
            page_size=8, metrics_port=0,
        )
        try:
            assert server._metrics_server is not None
            cfg = server.cfg
            prefix = default_dht_prefix(model_path)
            uids = CHAIN_DELIMITER.join(
                make_uid(prefix, i) for i in range(cfg.num_hidden_layers)
            )
            ttft_before = tm.TTFT.snapshot()["count"]
            rng = np.random.RandomState(3)
            stream = await client.open_stream("ptu.inference")
            await stream.send({"uids": uids, "max_length": 16, "batch_size": 1})
            await stream.recv(timeout=60)
            h = rng.randn(1, 3, cfg.hidden_size).astype(np.float32) * 0.1
            await stream.send({"tensors": {"hidden": serialize_array(h)}})
            out = deserialize_array((await stream.recv(timeout=120))["tensors"]["hidden"])
            assert out.shape == (1, 3, cfg.hidden_size)
            for _ in range(3):
                step = rng.randn(1, 1, cfg.hidden_size).astype(np.float32) * 0.1
                await stream.send({"tensors": {"hidden": serialize_array(step)}})
                await stream.recv(timeout=120)
            await stream.end()

            port = server._metrics_server.port
            url = f"http://127.0.0.1:{port}/metrics"
            text = (
                await asyncio.to_thread(urllib.request.urlopen, url, None, 10)
            ).read().decode()
            samples = _parse_prometheus(text)
            assert samples["petals_ttft_seconds_count"] > ttft_before
            assert samples["petals_ttft_seconds_sum"] > 0.0
            # the +Inf bucket equals _count (cumulative histogram invariant)
            assert (
                samples['petals_ttft_seconds_bucket{le="+Inf"}']
                == samples["petals_ttft_seconds_count"]
            )
            step_counts = [
                v for k, v in samples.items()
                if k.startswith("petals_step_duration_seconds_count")
            ]
            assert step_counts and sum(step_counts) > 0
            assert samples["petals_decode_tokens_total"] > 0

            # the DHT-announced digest mirrors the same state, compactly
            digest = telemetry_digest()
            assert digest["tokens_total"] > 0 and digest["ttft_p99_ms"] > 0
            info = server._server_info(server._state)
            assert isinstance(info.telemetry, dict)
            assert info.telemetry["steps_total"] > 0

            # the journal rides the same endpoint for operators
            jurl = f"http://127.0.0.1:{port}/journal"
            jtext = (
                await asyncio.to_thread(urllib.request.urlopen, jurl, None, 10)
            ).read().decode()
            assert all(json.loads(line) for line in jtext.strip().splitlines())
        finally:
            await client.close()
            await server.shutdown()
        # the scrape endpoint dies with the server
        with pytest.raises(Exception):
            urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics", None, 2)

    run(main())


# -------------------------------------------------- exposition render units


def test_render_prometheus_escaping_and_types():
    reg = MetricsRegistry()
    c = reg.counter("esc_total", 'help with "quotes" and \\slash\nline2', labels=("mode",))
    c.labels(mode='we"ird\\val\nue').inc(2)
    reg.gauge("g1", "a gauge").set(1.5)
    text = render_prometheus(reg)
    # HELP escapes backslash + newline only; quotes stay literal (0.0.4 spec)
    assert '# HELP esc_total help with "quotes" and \\\\slash\\nline2' in text
    assert "# TYPE esc_total counter" in text
    assert 'esc_total{mode="we\\"ird\\\\val\\nue"} 2' in text
    assert "g1 1.5" in text


def test_health_metrics_summary_aggregation():
    """run_health's /api/v1/metrics rollup: throughputs sum, p99s take the
    worst server, occupancy spans the pool columns."""
    from petals_tpu.utils.health import HealthMonitor

    monitor = HealthMonitor([])
    monitor._state = {
        "updated_at": 123.0,
        "models": {
            "m": {
                "servers": {
                    "peer-a": {
                        "public_name": None, "blocks": [0, 2],
                        "pool": {"lanes": 4, "busy_lanes": 2},
                        "telemetry": {
                            "tok_s": 10.0, "tokens_total": 100,
                            "ttft_p99_ms": 50.0, "step_p99_ms": 4.0,
                            "swap_out_bytes": 8, "swap_in_bytes": 8,
                            "preemptions": 1, "alloc_failed": 0,
                        },
                    },
                    "peer-b": {
                        "public_name": None, "blocks": [2, 4],
                        "pool": {"lanes": 4, "busy_lanes": 4},
                        "telemetry": {
                            "tok_s": 5.0, "tokens_total": 40,
                            "ttft_p99_ms": 200.0, "step_p99_ms": 2.0,
                            "preemptions": 0, "alloc_failed": 2,
                        },
                    },
                    "peer-c": {  # old server: no digest announced
                        "public_name": None, "blocks": [4, 6], "pool": None,
                        "telemetry": None,
                    },
                },
            }
        },
    }
    agg = monitor.metrics_summary()["models"]["m"]["aggregate"]
    assert agg["tok_s"] == 15.0 and agg["tokens_total"] == 140
    assert agg["ttft_p99_ms_max"] == 200.0 and agg["step_p99_ms_max"] == 4.0
    assert agg["swap_out_bytes"] == 8 and agg["alloc_failed"] == 2
    assert agg["servers_reporting"] == 2
    assert agg["occupancy"] == 6 / 8


# ------------------------------------------------ /journal endpoint filters


def test_journal_endpoint_filters():
    """/journal serves the ring as JSONL with ?kind= / ?trace_id= /
    ?since_seq= filters (the flight recorder's evidence API); a malformed
    since_seq is a 400, not a crash."""
    from petals_tpu.telemetry.exposition import MetricsServer

    journal = get_journal()
    tid_a, tid_b = new_trace_id(), new_trace_id()
    e1 = journal.event("gate_test_admission", trace_id=tid_a)
    journal.event("gate_test_admission", trace_id=tid_b)
    journal.event("gate_test_swap", trace_id=tid_a)

    server = MetricsServer(port=0)
    try:
        def fetch(query=""):
            url = f"http://127.0.0.1:{server.port}/journal{query}"
            with urllib.request.urlopen(url, timeout=10) as resp:
                body = resp.read().decode()
            return [json.loads(line) for line in body.splitlines() if line.strip()]

        by_trace = fetch(f"?trace_id={tid_a}")
        assert {e["trace_id"] for e in by_trace} == {tid_a}
        assert {e["kind"] for e in by_trace} == {
            "gate_test_admission", "gate_test_swap"
        }
        by_kind = fetch("?kind=gate_test_swap")
        assert by_kind and all(e["kind"] == "gate_test_swap" for e in by_kind)
        combined = fetch(f"?kind=gate_test_admission&trace_id={tid_b}")
        assert len(combined) == 1 and combined[0]["trace_id"] == tid_b
        since = fetch(f"?since_seq={e1['seq']}&trace_id={tid_a}")
        assert [e["kind"] for e in since] == ["gate_test_swap"]

        with pytest.raises(urllib.error.HTTPError) as err:
            fetch("?since_seq=notanint")
        assert err.value.code == 400
    finally:
        server.close()


# ------------------------- e2e: 2-hop critical path + SLO flight recorder


def test_two_hop_chain_trace_and_flight_recorder(model_path):
    """Acceptance for the critical-path tracer: a 2-server chain yields a
    trace_report() with one waterfall entry per hop, both servers see the
    SAME client-minted trace id, and >=95% of the session's wall-clock is
    attributed to named components. A session with microscopic SLOs then
    breaches on every step and the flight recorder captures the client
    waterfall plus the victim server's journal excerpt for that trace id."""

    async def main():
        from petals_tpu.client.config import ClientConfig
        from petals_tpu.client.inference_session import InferenceSession
        from petals_tpu.client.routing.sequence_manager import RemoteSequenceManager
        from petals_tpu.dht import DHTNode
        from petals_tpu.telemetry.flight import FlightRecorder
        from petals_tpu.telemetry.spans import format_waterfall

        bootstrap = await DHTNode.create(maintenance_period=1000)
        servers = []
        for first in (0, 2):
            server = Server(
                model_path,
                initial_peers=[bootstrap.own_addr],
                first_block=first,
                num_blocks=2,
                compute_dtype=jnp.float32,
                use_flash=False,
                batching=True,
                batch_lanes=2,
                batch_max_length=32,
                page_size=8,
                metrics_port=0,
            )
            await server.start()
            servers.append(server)

        prefix = servers[0].dht_prefix
        uids = [make_uid(prefix, i) for i in range(4)]
        manager = await RemoteSequenceManager.create(
            ClientConfig(initial_peers=[bootstrap.own_addr.to_string()]), uids
        )
        try:
            rng = np.random.RandomState(7)
            hidden_size = servers[0].cfg.hidden_size
            session = InferenceSession(manager, max_length=16)
            await session.step(rng.randn(1, 4, hidden_size).astype(np.float32) * 0.1)
            for _ in range(3):
                await session.step(
                    rng.randn(1, 1, hidden_size).astype(np.float32) * 0.1
                )

            # ---- the same client-minted id reached BOTH servers' schedulers
            tid = session.trace_id
            for server in servers:
                lane_tids = [
                    s.trace_id
                    for s in server.handler.batcher._scheduler.lanes.values()
                ]
                assert tid in lane_tids, (server.first_block, lane_tids)
            # ...and both hops' admissions are journaled under it (process-
            # global journal: the excerpt is distinguished by trace_id)
            assert len(get_journal().events(kind="admission", trace_id=tid)) >= 2

            # ---- per-hop waterfall: one entry per server span, attributed
            report = session.trace_report()
            assert report["trace_id"] == tid
            assert [h["blocks"] for h in report["hops"]] == [[0, 2], [2, 4]]
            for hop in report["hops"]:
                assert hop["steps"] == 4
                assert hop["meta_steps"] == 4, hop  # every reply carried meta
                assert hop["wall_s"] > 0
                assert hop["components"]["compute"] > 0, hop
                assert hop["occupancy"] is not None
            assert report["steps"] == 4 and report["tokens"] == 7
            assert report["critical_path"] is not None
            # the components are exhaustive by construction: ~all wall-clock
            # is attributed (the acceptance threshold)
            assert report["attributed_fraction"] >= 0.95, report
            rendered = format_waterfall(report)
            assert tid in rendered and "critical path:" in rendered

            # ---- resource bill: ledger usage deltas rode step_meta from
            # BOTH hops, so the client can total its own charges
            bill = session.usage_report()
            assert bill["trace_id"] == tid
            assert bill["total"].get("decode_tokens", 0) >= 3
            assert bill["total"].get("page_seconds", 0) > 0
            assert len(bill["peers"]) == 2, bill
            await session.close()

            # ---- flight recorder: microscopic SLOs force a breach per kind
            session2 = InferenceSession(manager, max_length=16)
            session2.flight = FlightRecorder(
                ttft_slo_s=1e-9, token_slo_s=1e-9, cooldown_s=0.0
            )
            await session2.step(rng.randn(1, 2, hidden_size).astype(np.float32) * 0.1)
            await session2.step(rng.randn(1, 1, hidden_size).astype(np.float32) * 0.1)
            ttft_entries = session2.flight.entries(kind="ttft")
            token_entries = session2.flight.entries(kind="token")
            assert len(ttft_entries) == 1 and len(token_entries) == 1
            for entry in ttft_entries + token_entries:
                assert entry["trace_id"] == session2.trace_id
                assert entry["observed_s"] > entry["slo_s"]
                # evidence 1: the client waterfall at breach time
                wf = entry["waterfall"]
                assert wf["trace_id"] == session2.trace_id and wf["hops"]
                # evidence 2: the victim server's journal excerpt over HTTP,
                # already filtered to this trace
                sj = entry["server_journal"]
                assert "error" not in sj, sj
                assert sj["events"], sj
                assert all(
                    e["trace_id"] == session2.trace_id for e in sj["events"]
                )
                assert any(e["kind"] == "admission" for e in sj["events"])
            await session2.close()
        finally:
            await manager.shutdown()
            for server in servers:
                await server.shutdown()
            await bootstrap.shutdown()

    run(asyncio.wait_for(main(), 600))


# ------------------------------------------- compiled-program observatory


def test_tracked_jit_compile_detection_and_warmup_anomaly():
    """The recompile sentinel end to end, on a private Observatory: every
    new (shape, static-arg) signature is one detected compile; once a
    steady wrapper has run ``warmup_calls`` times, a further compile is an
    anomaly — journal event with the offending avals + flight entry."""
    import jax

    from petals_tpu.telemetry.flight import FlightRecorder
    from petals_tpu.telemetry.observatory import Observatory, tracked_jit

    obs = Observatory(warmup_calls=2)
    flight = FlightRecorder(cooldown_s=0.0)
    obs.attach_flight(flight)

    @tracked_jit(name="toy", steady=True, observatory=obs,
                 static_argnames=("flag",))
    def toy(x, y, flag=True):
        return x + y if flag else x - y

    seq0 = get_journal().seq
    a = jnp.ones((4, 4), jnp.float32)
    for _ in range(3):
        toy(a, a)
    stats = obs.compile_stats()
    assert stats == {
        "functions": 1, "programs": 1, "compile_s": stats["compile_s"],
        "anomalies": 0,
    }
    assert stats["compile_s"] > 0
    # the compile journal event carries the signature that was traced
    compiles = get_journal().events(kind="compile", since_seq=seq0)
    assert len(compiles) == 1 and compiles[0]["fn"] == "toy"
    assert "float32[4,4]" in compiles[0]["avals"]

    # past warmup: a novel shape is exactly one anomaly, with evidence
    b = jnp.ones((2, 2), jnp.float32)
    toy(b, b)
    anomalies = get_journal().events(kind="compile_anomaly", since_seq=seq0)
    assert len(anomalies) == 1
    assert anomalies[0]["fn"] == "toy"
    assert "float32[2,2]" in anomalies[0]["avals"]
    assert anomalies[0]["warmup_calls"] == 2
    entries = flight.entries(kind="recompile")
    assert len(entries) == 1 and entries[0]["fn"] == "toy"
    assert entries[0]["server_journal"], "flight entry carries the compile tail"
    assert all(e["kind"] == "compile" for e in entries[0]["server_journal"])

    # a drifting STATIC argument recompiles too — same sentinel
    toy(b, b, flag=False)
    assert obs.compile_stats()["anomalies"] == 2
    assert obs.compile_stats()["programs"] == 3
    # cache hit on a known signature: no new program, no new anomaly
    toy(a, a)
    assert obs.compile_stats() == {
        "functions": 1, "programs": 3,
        "compile_s": obs.compile_stats()["compile_s"], "anomalies": 2,
    }
    # the wrapper honors the jax.jit contract the backward path relies on
    assert toy.__wrapped__ is not None and not hasattr(
        toy.__wrapped__, "__wrapped__"
    )


def test_cost_table_roofline_and_memory_analysis(monkeypatch):
    """XLA cost attribution: the lazily-filled per-program cost table has
    real flops/bytes, roofline math divides by the measured step time (and
    by peak only when a peak is declared), and memory_analysis is opt-in."""
    from petals_tpu.telemetry.observatory import Observatory, tracked_jit

    obs = Observatory(warmup_calls=8)

    @tracked_jit(name="mm", steady=True, observatory=obs)
    def mm(x, y):
        return x @ y

    x = jnp.ones((8, 16), jnp.float32)
    mm(x, x.T @ x @ jnp.ones((16, 8)))  # nested device math is irrelevant
    table = obs.cost_table()
    assert len(table) == 1
    cost = table[0]["cost"]
    assert cost["flops"] > 0 and cost["bytes_accessed"] > 0
    # re-lowering for analysis never records a new program
    assert obs.compile_stats()["programs"] == 1

    r = obs.roofline("mm", 0.001)
    assert r["fn"] == "mm" and r["flops_per_step"] == cost["flops"]
    assert r["step_mean_ms"] == 1.0 and r["achieved_gflops"] >= 0
    assert r["utilization"] is None  # no declared peak on CPU
    monkeypatch.setenv("PETALS_TPU_PEAK_TFLOPS", "0.000001")
    assert obs.roofline("mm", 0.001)["utilization"] > 0

    # memory analysis costs a fresh AOT compile: only on request
    assert "memory" not in table[0]
    mem_table = obs.cost_table(memory=True)
    assert mem_table[0]["memory"]["argument_bytes"] > 0


def test_journal_sink_close_and_seq_agreement(tmp_path):
    """The JSONL write-through sink and the in-memory export agree on the
    final seq: concurrent writers never interleave file lines out of order,
    close() flushes everything and is idempotent, and the ring stays usable
    (Server.shutdown closes the sink, not the journal)."""
    path = tmp_path / "journal.jsonl"
    j = TelemetryJournal(maxlen=64, path=str(path))

    def work(i):
        for n in range(50):
            j.event("spin", worker=i, n=n)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    j.close()
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert [l["seq"] for l in lines] == list(range(1, 201))
    assert j.seq == 200  # file sink and /journal export agree
    # ring keeps recording after close; the file does not grow
    j.event("post_close")
    assert j.seq == 201 and j.events(kind="post_close")
    j.close()  # idempotent
    assert len(path.read_text().splitlines()) == 200


def test_page_pool_economics_units():
    """Free-run/fragmentation math on the page allocator across a COW
    share-and-release cycle, and prefix-cache hit/miss/evict counters."""
    from petals_tpu.server.memory_cache import PageAllocator
    from petals_tpu.server.prefix_cache import SEGMENT_TOKENS, PrefixCache

    alloc = PageAllocator(16)
    pages = [alloc.try_alloc() for _ in range(16)]
    info = alloc.fragmentation_info()
    assert info["free"] == 0 and info["frag"] == 0.0 and info["runs"] == 0
    # COW share: a prefix pin holds pages 0..3 while the lane releases them
    for p in pages[:4]:
        alloc.incref(p)
    for p in pages[:4]:
        alloc.decref(p)
    assert alloc.fragmentation_info()["free"] == 0  # shared != free
    # pin drops -> one contiguous 4-page hole: zero fragmentation
    for p in pages[:4]:
        alloc.decref(p)
    info = alloc.fragmentation_info()
    assert info["free"] == 4 and info["largest_run"] == 4
    assert info["frag"] == 0.0 and info["run_hist"]["4_7"] == 1
    # shatter the upper half into singletons: frag = 1 - 4/10
    for p in pages[5::2]:
        alloc.decref(p)
    info = alloc.fragmentation_info()
    assert info["free"] == 10 and info["largest_run"] == 4
    assert info["frag"] == round(1.0 - 4 / 10, 4)
    assert info["run_hist"] == {
        "1": 6, "2_3": 0, "4_7": 1, "8_15": 0, "16_plus": 0,
    }

    rng = np.random.RandomState(2)
    seg_kv = rng.randn(2, 1, SEGMENT_TOKENS, 2, 4).astype(np.float32)
    seg_out = rng.randn(1, SEGMENT_TOKENS, 8).astype(np.float32)
    entry_bytes = 2 * seg_kv.nbytes + seg_out.nbytes
    h0, m0 = tm.PREFIX_HIT.value, tm.PREFIX_MISS.value
    e0 = tm.PREFIX_EVICT.value
    # the flat baseline pins insertion-order eviction; the default radix
    # policy would protect the probed-hot "a" and evict "b" instead
    cache = PrefixCache(max_bytes=2 * entry_bytes + 10, policy="lru")
    cache.put(["a"], 0, seg_kv, seg_kv, seg_out)
    assert cache.probe(["a"]) == 1 and tm.PREFIX_HIT.value == h0 + 1
    assert cache.probe(["nope"]) == 0 and tm.PREFIX_MISS.value == m0 + 1
    cache.put(["b"], 0, seg_kv, seg_kv, seg_out)
    cache.put(["c"], 0, seg_kv, seg_kv, seg_out)  # over budget: "a" evicted
    assert cache.stats["evictions"] >= 1
    assert tm.PREFIX_EVICT.value == e0 + cache.stats["evictions"]
    assert cache.probe(["a"]) == 0  # ...and the miss after eviction counts
    assert tm.PREFIX_MISS.value == m0 + 2
    # the announce digest derives its hit rate from these same counters
    digest = telemetry_digest()
    assert digest["prefix_hit_rate"] is not None
    assert 0.0 <= digest["prefix_hit_rate"] <= 1.0


def test_observatory_acceptance_steady_decode_then_forced_recompile(model_path):
    """Acceptance: >=40 post-warmup decode ticks through the DecodeBatcher
    produce ZERO compile anomalies (one shape -> one program, frozen); a
    forced novel shape on the warmed steady program then produces exactly
    one anomaly event carrying its avals, plus a flight-recorder entry.
    Along the way: /metrics and /compile expose the cost table, the
    announce digest carries compile_stats, and the page-pool gauges are
    live."""

    async def main():
        from petals_tpu.telemetry.observatory import get_observatory

        server, client = await _start_server(
            model_path, batching=True, batch_lanes=2, batch_max_length=64,
            page_size=8, metrics_port=0,
        )
        obs = get_observatory()
        journal = get_journal()
        seq0 = journal.seq
        # the observatory is process-global: earlier tests in a full-suite
        # run may have left anomalies behind — assert DELTAS, not totals
        anomalies0 = obs.compile_stats()["anomalies"]
        try:
            cfg = server.cfg
            prefix = default_dht_prefix(model_path)
            uids = CHAIN_DELIMITER.join(
                make_uid(prefix, i) for i in range(cfg.num_hidden_layers)
            )
            rng = np.random.RandomState(11)
            stream = await client.open_stream("ptu.inference")
            await stream.send({"uids": uids, "max_length": 60, "batch_size": 1})
            await stream.recv(timeout=60)
            h = rng.randn(1, 3, cfg.hidden_size).astype(np.float32) * 0.1
            await stream.send({"tensors": {"hidden": serialize_array(h)}})
            await stream.recv(timeout=120)
            # 44 decode ticks: warmup (8 calls) long past, shape constant
            for _ in range(44):
                step = rng.randn(1, 1, cfg.hidden_size).astype(np.float32) * 0.1
                await stream.send({"tensors": {"hidden": serialize_array(step)}})
                await stream.recv(timeout=120)
            await stream.end()

            # ---- steady state: the decode program compiled ONCE, no anomaly
            assert journal.events(kind="compile_anomaly", since_seq=seq0) == []
            fns = {f["fn"]: f for f in obs.functions()}
            assert fns["paged_decode"]["steady"]
            assert fns["paged_decode"]["calls"] >= 44
            stats = obs.compile_stats()
            assert stats["programs"] >= 1 and stats["compile_s"] > 0

            # ---- the digest rides the announce path next to PR 6 telemetry
            info = server._server_info(server._state)
            assert info.compile_stats is not None
            assert info.compile_stats["programs"] >= 1
            assert info.compile_stats["anomalies"] == anomalies0

            # ---- /metrics and the /compile view expose the cost table
            port = server._metrics_server.port
            text = (
                await asyncio.to_thread(
                    urllib.request.urlopen,
                    f"http://127.0.0.1:{port}/metrics", None, 10,
                )
            ).read().decode()
            samples = _parse_prometheus(text)
            assert samples['petals_compiles_total{fn="paged_decode"}'] >= 1
            assert samples["petals_page_pool_fragmentation"] >= 0.0
            # ?fn= scopes the analysis: a full-table scrape re-lowers every
            # program recorded in this (shared, process-global) table
            view = json.loads(
                (
                    await asyncio.to_thread(
                        urllib.request.urlopen,
                        f"http://127.0.0.1:{port}/compile?fn=paged_decode",
                        None, 30,
                    )
                ).read().decode()
            )
            assert view["stats"]["programs"] >= 1
            assert view["warmup_calls"] == obs.warmup_calls
            progs = [p for p in view["programs"] if p["fn"] == "paged_decode"]
            # newest record = THIS server's steady compile (the program table
            # is process-global and ordered; earlier suites may precede it)
            assert progs and progs[-1]["cost"]["flops"] > 0
            assert progs[-1]["avals"] and not progs[-1]["anomaly"]

            # ---- page-pool economics gauges are wired to the live pool
            batcher = server.handler.batcher
            assert tm.PAGES_TOTAL.value == batcher.n_pages
            assert 0.0 <= tm.PAGE_FRAGMENTATION.value <= 1.0
            assert tm.PAGE_LARGEST_RUN.value >= 1
            occ = batcher.occupancy_info()
            assert "frag" in occ and "largest_free_run" in occ
            digest = telemetry_digest()
            for key in ("frag", "prefix_hit_rate", "hbm_free_bytes",
                        "swap_oldest_s"):
                assert key in digest, key
            # a prefix-cache page adoption (zero-copy COW share) is counted
            lane = await batcher.acquire_lane()
            page = batcher._pages.try_alloc()
            a0 = tm.PREFIX_ADOPT.value
            batcher.adopt_pages(lane, [page])
            assert tm.PREFIX_ADOPT.value == a0 + 1
            batcher._pages.decref(page)  # drop the alloc ref; table ref stays
            batcher.release_lane(lane)  # frees the adopted page with the lane

            # ---- force a novel shape on the FROZEN steady program: one
            # extra lane row changes every aval -> exactly one anomaly
            backend = batcher.backend
            k_pool, v_pool = batcher._buffers()
            tables = np.asarray(batcher._tables, np.int32)
            ext = np.vstack([tables, tables[:1]])
            sentinel = batcher.max_pages * batcher.page_size
            hidden = np.zeros((ext.shape[0], 1, cfg.hidden_size), np.float32)
            positions = np.full((ext.shape[0],), sentinel, np.int32)
            seq1 = journal.seq
            flight = obs.flight_recorder()
            before = len(flight.entries(kind="recompile"))
            backend.paged_decode_step(
                hidden,
                (jnp.zeros(k_pool.shape, k_pool.dtype),
                 jnp.zeros(v_pool.shape, v_pool.dtype)),
                positions, ext,
            )
            anomalies = journal.events(kind="compile_anomaly", since_seq=seq1)
            assert len(anomalies) == 1, anomalies
            assert anomalies[0]["fn"] == "paged_decode"
            assert any("float32" in a or "bfloat16" in a
                       for a in anomalies[0]["avals"])
            entries = flight.entries(kind="recompile")
            assert len(entries) == before + 1
            assert entries[-1]["fn"] == "paged_decode"
        finally:
            await client.close()
            await server.shutdown()

    run(asyncio.wait_for(main(), 600))
