"""Integrity observatory tier (beyond reference): activation fingerprints,
client cross-checks, canary quorums, divergence quarantine, and the
autoscaler's drain-and-replace response.

Covers the three planes of petals_tpu/telemetry/integrity.py plus the
sensor itself (petals_tpu/ops/fingerprint.py):

- fingerprint units: shared projection, digest helpers, tolerance regimes;
- PATH INVARIANCE: the fused digest of the same tokens through the dense,
  identity-table paged, permuted paged, and mixed batched step programs
  agrees within the calibrated regimes (the PR 2/3 bit-exactness contract,
  made observable);
- tolerance calibration against REAL int8/nf4 requantization of the same
  weights (the cross-replica comparison the canary prober performs);
- client monitor: reply cross-check, continuity across replays, evidence
  (journal + flight) with both digests;
- canary quorum attribution discipline and the quarantine registry decay;
- announce payload cap + truncation counter;
- autoscaler policy: quarantine drain -> replacement scale-out sequence,
  sole-coverage replacement-first, and the max_replicas IOU drop.

Everything here runs with fingerprinting ON (the lane's whole point); the
autouse fixture restores the process flag so other lanes keep their
compiled-variant expectations.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from petals_tpu.ops import fingerprint as fp_ops
from petals_tpu.telemetry import instruments as tm
from petals_tpu.telemetry.integrity import (
    CanaryProber,
    IntegrityMonitor,
    QuarantineRegistry,
    cap_announce_payload,
    quorum_outliers,
)
from petals_tpu.telemetry.journal import get_journal
from tests.utils import make_tiny_llama

pytestmark = pytest.mark.integrity


@pytest.fixture(autouse=True)
def _fingerprinting_on():
    prev = fp_ops.enabled()
    fp_ops.set_enabled(True)
    yield
    fp_ops.set_enabled(prev)


@pytest.fixture(scope="module")
def model_path(tmp_path_factory):
    return make_tiny_llama(str(tmp_path_factory.mktemp("models")))


def _tiny_backend(model_path, quant=None):
    from petals_tpu.server.backend import TransformerBackend
    from petals_tpu.server.from_pretrained import get_block_config, load_block_params
    from petals_tpu.server.memory_cache import MemoryCache
    from petals_tpu.utils.convert_block import convert_block_params

    family, cfg = get_block_config(model_path)
    per_block = []
    for i in range(2):
        params = load_block_params(
            model_path, i, dtype=jnp.float32, family=family, cfg=cfg
        )
        if quant:
            params = convert_block_params(params, family.name, quant, fuse=False)
        per_block.append(params)
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_block)
    return TransformerBackend(
        family, cfg, stacked, first_block=0, n_blocks=2,
        memory_cache=MemoryCache(None), compute_dtype=jnp.float32, use_flash=False,
    ), cfg


class _FlightStub:
    def __init__(self):
        self.records = []

    def record(self, kind, **fields):
        self.records.append({"kind": kind, **fields})
        return self.records[-1]


# ---------------------------------------------------------- fingerprint units


def test_projection_shared_and_deterministic():
    a = fp_ops.projection(64, seed=1)
    b = fp_ops.projection(64, seed=1)
    assert a is b  # cached: the jitted programs bake one shared constant
    assert a.shape == (64, fp_ops.FP_DIM) and a.dtype == np.float32
    assert not np.allclose(a, fp_ops.projection(64, seed=2))
    assert fp_ops.projection(128, seed=1).shape == (128, fp_ops.FP_DIM)


def test_fingerprint_output_is_last_token_row():
    rng = np.random.RandomState(0)
    hidden = rng.randn(1, 5, 32).astype(np.float32)
    fp = fp_ops.fingerprint_output(hidden, 32, seed=3)
    want = fp_ops.fingerprint_rows(
        hidden[0, -1, :].reshape(1, 32), fp_ops.projection(32, seed=3)
    )[0]
    np.testing.assert_array_equal(fp, want)
    # earlier rows must not influence the digest (it tracks the STREAM tip)
    hidden2 = hidden.copy()
    hidden2[0, 0, :] += 1.0
    np.testing.assert_array_equal(fp, fp_ops.fingerprint_output(hidden2, 32, seed=3))


def test_fp_close_relative_scale_and_shape():
    base = np.array([1.0, -2.0, 100.0], np.float64)
    assert fp_ops.fp_close(base, base, rtol=0.0)
    assert fp_ops.fp_close(base * 1.0009, base, rtol=1e-3)
    assert not fp_ops.fp_close(base * 1.1, base, rtol=1e-3)
    assert not fp_ops.fp_close(base[:2], base, rtol=1.0)  # shape mismatch
    assert fp_ops.fp_close([], [], rtol=0.0)


def test_digest_hex_and_fp_list():
    fp = np.array([0.1234567, -2.5, 3.0], np.float32)
    h = fp_ops.digest_hex(fp)
    assert h == fp_ops.digest_hex(fp) and len(h) == 16
    assert h != fp_ops.digest_hex(fp + 0.001)
    assert h == fp_ops.digest_hex(fp + 1e-9)  # rounded: wire jitter collapses
    lst = fp_ops.fp_list(fp)
    assert isinstance(lst, list) and len(lst) == 3
    assert all(isinstance(x, float) for x in lst)
    np.testing.assert_allclose(lst, fp, atol=1e-6)


def test_tolerance_regimes_ordered():
    assert fp_ops.TOL_EXACT < fp_ops.TOL_TRANSPORT < fp_ops.TOL_LOSSY_WIRE
    assert (
        fp_ops.tolerance_for("none")
        < fp_ops.tolerance_for("int8")
        < fp_ops.tolerance_for("nf4")
    )
    assert fp_ops.tolerance_for(None) == fp_ops.tolerance_for("none")
    # unknown mode: widest known tolerance, never a KeyError mid-probe
    assert fp_ops.tolerance_for("mystery") == fp_ops.tolerance_for("nf4")


# ------------------------------------------------------------- path invariance


def test_fused_fingerprint_path_invariance(model_path):
    """The SAME lanes stepped through the dense program, the identity-table
    paged program (statically the dense program), the permuted-table paged
    program, and the mixed prefill+decode program must produce fused digests
    within the calibrated regimes — and the client twin recomputed from the
    step output must match within the transport tolerance."""
    from petals_tpu.ops.paged_attention import identity_tables

    backend, cfg = _tiny_backend(model_path)
    rng = np.random.RandomState(0)
    L, PS, MAX_PAGES = 3, 8, 4
    MAXLEN = PS * MAX_PAGES
    positions = np.array([4, 0, 9], np.int32)
    hidden = rng.randn(L, 1, cfg.hidden_size).astype(np.float32) * 0.1

    # per-lane caches (ground truth prefill content, shared by every layout)
    kd, vd = backend.cache_descriptors(1, MAXLEN, 0, 2)
    lanes_kv = []
    for l in range(L):
        kv = (kd.make_zeros(), vd.make_zeros())
        if positions[l]:
            pre = rng.randn(1, positions[l], cfg.hidden_size).astype(np.float32) * 0.1
            _, kv = backend.inference_step(pre, kv, 0)
        lanes_kv.append((np.asarray(kv[0]), np.asarray(kv[1])))

    # ---- dense batched step
    k_pool = jnp.asarray(np.concatenate([kv[0] for kv in lanes_kv], axis=1))
    v_pool = jnp.asarray(np.concatenate([kv[1] for kv in lanes_kv], axis=1))
    out_dense, _ = backend.batched_decode_step(hidden, (k_pool, v_pool), positions)
    fp_dense, chunk_fp = backend.pop_step_fp()
    assert fp_dense is not None and chunk_fp is None
    fp_dense = np.asarray(fp_dense)
    assert fp_dense.shape == (L, fp_ops.FP_DIM)
    # the stash is pop-once: a second pop must not replay a stale digest
    assert backend.pop_step_fp() == (None, None)

    # client twin: recompute each lane's digest from the step output
    for l in range(L):
        twin = fp_ops.fingerprint_output(np.asarray(out_dense)[l : l + 1], cfg.hidden_size)
        assert fp_ops.fp_close(twin, fp_dense[l], rtol=fp_ops.TOL_TRANSPORT), (
            f"client twin diverged on lane {l}"
        )

    def paged_pool(tables, n_pages):
        n_blocks, _, _, hkv, hd = lanes_kv[0][0].shape
        kp = np.zeros((n_blocks, n_pages, PS, hkv, hd), np.float32)
        vp = np.zeros_like(kp)
        for l, (kl, vl) in enumerate(lanes_kv):
            for s in range(MAX_PAGES):
                page = tables[l, s]
                if page < 0:
                    continue
                kp[:, page] = kl[:, 0, s * PS : (s + 1) * PS]
                vp[:, page] = vl[:, 0, s * PS : (s + 1) * PS]
        return jnp.asarray(kp), jnp.asarray(vp)

    # ---- identity-table paged step: statically the dense program, bit-exact
    ident = np.asarray(identity_tables(L, MAX_PAGES))
    kp, vp = paged_pool(ident, L * MAX_PAGES)
    backend.paged_decode_step(hidden, (kp, vp), positions, ident)
    fp_ident, _ = backend.pop_step_fp()
    assert fp_ops.fp_close(
        np.asarray(fp_ident).reshape(-1), fp_dense.reshape(-1), rtol=fp_ops.TOL_EXACT
    ), "identity-table paged digest must be bit-exact vs dense"

    # ---- permuted-table paged step: real gather/scatter, same math
    n_pages = 16
    perm = np.full((L, MAX_PAGES), -1, np.int32)
    free = list(rng.permutation(n_pages))
    for l in range(L):
        for s in range(-(-int(positions[l] + 1) // PS)):
            perm[l, s] = free.pop()
    kp, vp = paged_pool(perm, n_pages)
    backend.paged_decode_step(hidden, (kp, vp), positions, perm)
    fp_perm, _ = backend.pop_step_fp()
    assert fp_ops.fp_close(
        np.asarray(fp_perm).reshape(-1), fp_dense.reshape(-1), rtol=fp_ops.TOL_TRANSPORT
    ), "permuted-table paged digest must match dense within transport tolerance"

    # ---- mixed prefill+decode step: lanes 0/2 decode while lane 1 prefills;
    # their digest rows must still match the dense program's
    chunk = rng.randn(1, 6, cfg.hidden_size).astype(np.float32) * 0.1
    mixed_pos = np.array([positions[0], MAXLEN, positions[2]], np.int32)
    kp, vp = paged_pool(perm, n_pages)
    tables = perm.copy()
    for s in range(MAX_PAGES):  # give the prefill lane somewhere to write
        if tables[1, s] < 0:
            tables[1, s] = free.pop()
    backend.paged_mixed_step(hidden, (kp, vp), mixed_pos, tables, chunk, 1, 0)
    fp_mixed, fp_chunk = backend.pop_step_fp()
    fp_mixed = np.asarray(fp_mixed)
    for l in (0, 2):
        assert fp_ops.fp_close(
            fp_mixed[l], fp_dense[l], rtol=fp_ops.TOL_TRANSPORT
        ), f"mixed-step digest diverged from dense on decode lane {l}"
    assert fp_chunk is not None and np.asarray(fp_chunk).shape == (fp_ops.FP_DIM,)
    assert not np.allclose(np.asarray(fp_chunk), 0.0), "chunk digest must be live"


def test_cross_quant_tolerance_calibration(model_path):
    """tolerance_for() calibrated against REAL requantization: the digests of
    the same tokens through fp32 vs int8 vs nf4 weights must agree within the
    mode's tolerance — and nf4's noise must EXCEED the fp32 cross-replica
    band, proving the per-quant regimes are load-bearing, not decorative.
    On TPU the accumulation order differs: re-calibrate on-chip before
    trusting cross-backend comparisons (not measured on the current chip)."""
    rng = np.random.RandomState(1)
    backend_f32, cfg = _tiny_backend(model_path)
    prompt = rng.randn(1, 7, cfg.hidden_size).astype(np.float32) * 0.1

    def digest(backend):
        kd, vd = backend.cache_descriptors(1, 16, 0, 2)
        out, _ = backend.inference_step(prompt, (kd.make_zeros(), vd.make_zeros()), 0)
        return fp_ops.fingerprint_output(np.asarray(out), cfg.hidden_size)

    fp_f32 = digest(backend_f32)
    for quant in ("int8", "nf4"):
        fp_q = digest(_tiny_backend(model_path, quant=quant)[0])
        tol = fp_ops.tolerance_for(quant)
        assert fp_ops.fp_close(fp_q, fp_f32, rtol=tol), (
            f"{quant} replica diverged beyond tolerance_for({quant!r})={tol}"
        )
    fp_nf4 = digest(_tiny_backend(model_path, quant="nf4")[0])
    assert not fp_ops.fp_close(fp_nf4, fp_f32, rtol=fp_ops.tolerance_for("none")), (
        "nf4 requantization noise should exceed the fp32 cross-replica band — "
        "if this starts passing, the nf4 tolerance can tighten"
    )


# ------------------------------------------------------------- client monitor


def test_monitor_accepts_honest_reply():
    rng = np.random.RandomState(2)
    hidden = rng.randn(1, 1, 64).astype(np.float32)
    server_fp = fp_ops.fingerprint_output(hidden, 64)
    mon = IntegrityMonitor(trace_id="t-honest")
    assert mon.verify_step(
        "peerA", fp_ops.fp_list(server_fp), hidden, start=0, end=4, position=0
    )
    assert mon.checked == 1 and mon.divergences == 0
    # no fingerprint on the reply (old server): skipped, never failed
    assert mon.verify_step("peerA", None, hidden, start=0, end=4, position=1)
    assert mon.checked == 1


def test_monitor_records_divergence_with_both_digests():
    rng = np.random.RandomState(3)
    hidden = rng.randn(1, 1, 64).astype(np.float32)
    server_fp = fp_ops.fingerprint_output(hidden, 64) * 1.5  # corrupted stream
    flight = _FlightStub()
    penalized = []
    mon = IntegrityMonitor(
        trace_id="t-diverge", on_divergence=penalized.append, flight=flight
    )
    assert not mon.verify_step(
        "peerB", fp_ops.fp_list(server_fp), hidden, start=0, end=4, position=0
    )
    assert mon.divergences == 1 and penalized == ["peerB"]
    events = get_journal().events(kind="integrity_divergence", trace_id="t-diverge")
    assert events, "divergence must be journaled"
    ev = events[-1]
    assert ev["peer"] == "peerB" and ev["source"] == "client"
    assert ev["local_digest"] and ev["remote_digest"]
    assert ev["local_digest"] != ev["remote_digest"]
    assert flight.records and flight.records[-1]["kind"] == "integrity_divergence"
    assert flight.records[-1]["local_digest"] == ev["local_digest"]


def test_monitor_lossy_wire_widens_tolerance():
    rng = np.random.RandomState(4)
    hidden = rng.randn(1, 1, 64).astype(np.float32)
    # 2% off: beyond TOL_TRANSPORT (1e-3), inside TOL_LOSSY_WIRE (8e-2)
    server_fp = fp_ops.fingerprint_output(hidden, 64) * 1.02
    strict = IntegrityMonitor(trace_id="t-strict")
    assert not strict.verify_step(
        "peerC", fp_ops.fp_list(server_fp), hidden, start=0, end=4, position=0
    )
    lossy = IntegrityMonitor(trace_id="t-lossy")
    assert lossy.verify_step(
        "peerC", fp_ops.fp_list(server_fp), hidden,
        start=0, end=4, position=0, lossy_wire=True,
    )
    assert lossy.divergences == 0


def test_monitor_continuity_across_replay():
    """A repair/migration that re-drives a position on an adopting replica
    must reproduce the original digest stream; an honest adopter passes, a
    divergent one is recorded with source='continuity'."""
    rng = np.random.RandomState(5)
    hidden = rng.randn(1, 1, 64).astype(np.float32)
    fp = fp_ops.fp_list(fp_ops.fingerprint_output(hidden, 64))
    mon = IntegrityMonitor(trace_id="t-cont")
    assert mon.verify_step("peerA", fp, hidden, start=0, end=4, position=7)
    # honest adopter: same tokens, same digest -> continuity holds
    assert mon.verify_step("peerB", fp, hidden, start=0, end=4, position=7)
    # divergent adopter: internally-consistent reply, WRONG activations
    other = rng.randn(1, 1, 64).astype(np.float32)
    other_fp = fp_ops.fp_list(fp_ops.fingerprint_output(other, 64))
    assert not mon.verify_step("peerEvil", other_fp, other, start=0, end=4, position=7)
    ev = get_journal().events(kind="integrity_divergence", trace_id="t-cont")[-1]
    assert ev["source"] == "continuity" and ev["peer"] == "peerEvil"


# ------------------------------------------------------ canary quorum + chaos


def _digests(**kv):
    return {k: np.asarray(v, np.float32) for k, v in kv.items()}


def test_quorum_majority_names_outlier():
    base = [1.0, -2.0, 0.5]
    outliers, majority = quorum_outliers(
        _digests(a=base, b=base, c=[5.0, 5.0, 5.0]), rtol=1e-3
    )
    assert outliers == ["c"] and sorted(majority) == ["a", "b"]


def test_quorum_two_replicas_no_attribution():
    outliers, majority = quorum_outliers(
        _digests(a=[1.0, 2.0], b=[9.0, 9.0]), rtol=1e-3
    )
    assert outliers == [] and majority == []  # a fault, but whose?
    outliers, majority = quorum_outliers(
        _digests(a=[1.0, 2.0], b=[1.0, 2.0]), rtol=1e-3
    )
    assert outliers == [] and sorted(majority) == ["a", "b"]


def test_quorum_split_and_tie_quarantine_nobody():
    outliers, _ = quorum_outliers(
        _digests(a=[1.0], b=[5.0], c=[9.0]), rtol=1e-3
    )
    assert outliers == []  # three-way split: no majority
    outliers, _ = quorum_outliers(
        _digests(a=[1.0], b=[1.0], c=[9.0], d=[9.0]), rtol=1e-3
    )
    assert outliers == []  # 2-2 tie is not a STRICT majority


def test_canary_prober_quarantines_and_records():
    base = [0.5, -1.5, 2.0, 0.0]
    bad = [9.0, 9.0, 9.0, 9.0]
    fps = {"good1": base, "good2": base, "evil": bad, "dead": None}
    reg = QuarantineRegistry(window_s=60.0)
    flight = _FlightStub()
    prober = CanaryProber(
        lambda peer, fb, nb: fps[peer], quarantine=reg, flight=flight
    )
    report = prober.probe_span((0, 4), ["good1", "good2", "evil", "dead"])
    assert report["outliers"] == ["evil"] and report["errors"] == ["dead"]
    assert report["quorum"] == 2
    assert reg.is_quarantined("evil") and not reg.is_quarantined("good1")
    ev = [
        e for e in get_journal().events(kind="integrity_divergence")
        if e.get("peer") == "evil" and e.get("source") == "canary"
    ][-1]
    assert ev["local_digest"] != ev["remote_digest"] != ""
    assert any(r.get("peer") == "evil" for r in flight.records)


def test_quarantine_registry_decays():
    reg = QuarantineRegistry(window_s=0.05)
    reg.quarantine("p1", reason="test")
    assert reg.is_quarantined("p1") and reg.snapshot() == {"p1": "test"}
    time.sleep(0.08)
    assert not reg.is_quarantined("p1") and reg.snapshot() == {}
    reg.quarantine("p2")
    reg.release("p2")
    assert not reg.is_quarantined("p2")


def test_corrupt_array_is_seeded_and_detectable():
    """The chaos plane's integrity.corrupt payload: deterministic in
    (plane seed, site seed, position), last-token-row only, magnitude-
    preserving — and ALWAYS beyond even the widest honest tolerance, so a
    canary comparison cannot mistake it for quantization noise."""
    from petals_tpu import chaos

    rng = np.random.RandomState(6)
    hidden = rng.randn(1, 3, 64).astype(np.float32)
    chaos.configure(seed=9, rules=[])
    try:
        a = chaos.corrupt_array(hidden, 123, position=5)
        b = chaos.corrupt_array(hidden, 123, position=5)
        np.testing.assert_array_equal(a, b)  # bit-for-bit reproducible
        c = chaos.corrupt_array(hidden, 123, position=6)
        assert not np.array_equal(a, c)  # position perturbs the flip set
        np.testing.assert_array_equal(a[0, :-1], hidden[0, :-1])  # rows 0..n-2 untouched
        np.testing.assert_array_equal(np.abs(a), np.abs(hidden))  # sign flips only
        fp_honest = fp_ops.fingerprint_output(hidden, 64)
        fp_corrupt = fp_ops.fingerprint_output(a, 64)
        assert not fp_ops.fp_close(
            fp_corrupt, fp_honest, rtol=fp_ops.tolerance_for("nf4")
        ), "corruption must be detectable above the widest honest tolerance"
    finally:
        chaos.disable()


# -------------------------------------------------------------- announce cap


def test_cap_announce_payload_bounds_and_counts():
    small = {"quarantined": False, "fp_seed": 1}
    assert cap_announce_payload(small, max_bytes=2048) is small  # under cap: untouched
    before = tm.ANNOUNCE_TRUNCATED.value
    big = {
        "quarantined": True,
        "reason": "x" * 4000,  # the bloated entry
        "fp_seed": 1,
    }
    capped = cap_announce_payload(big, max_bytes=256)
    import json

    assert len(json.dumps(capped, separators=(",", ":"))) <= 256
    assert "reason" not in capped  # largest entry dropped first
    assert capped["quarantined"] is True  # the load-bearing bit survived
    assert tm.ANNOUNCE_TRUNCATED.value > before


# -------------------------------------------- autoscaler quarantine response


def _snap(tick, servers, num_blocks=4):
    from petals_tpu.swarm.policy import ServerSample, SwarmSnapshot

    return SwarmSnapshot(
        tick=tick,
        num_blocks=num_blocks,
        servers=tuple(
            ServerSample(
                peer=p, start=0, end=num_blocks, state="online",
                throughput=1000.0, lanes=2, busy_lanes=1, quarantined=(p in quar),
            )
            for p, quar in servers
        ),
    )


def test_policy_drains_then_replaces_quarantined_replica():
    from petals_tpu.swarm.policy import AutoscalerPolicy, PolicyConfig

    policy = AutoscalerPolicy(PolicyConfig(
        cooldown_global=1, min_replicas=2, max_replicas=4, span_blocks=0,
    ))
    servers = [("A", "A"), ("B", ""), ("C", "")]  # A quarantined
    d1 = policy.observe(_snap(0, servers))
    assert len(d1) == 1 and d1[0].action == "scale_in" and d1[0].target == "A"
    assert "drain divergent" in d1[0].reason
    assert d1[0].evidence["victim"] == "A"
    # next tick: A drained away; the owed replacement fires over A's span
    d2 = policy.observe(_snap(1, [("B", ""), ("C", "")]))
    assert len(d2) == 1 and d2[0].action == "scale_out"
    assert d2[0].reason == "replace drained quarantined replica"
    assert d2[0].span == (0, 4)
    # steady state: no further integrity decisions
    assert policy.observe(_snap(2, [("B", ""), ("C", ""), ("D", "")])) == []


def test_policy_sole_coverage_replaces_first():
    """A quarantined replica that is the only coverage of its blocks must be
    REPLACED before it can be drained — wrong tokens beat no tokens only
    until the replacement is online."""
    from petals_tpu.swarm.policy import AutoscalerPolicy, PolicyConfig

    policy = AutoscalerPolicy(PolicyConfig(
        cooldown_global=1, min_replicas=1, max_replicas=3, span_blocks=0,
    ))
    d1 = policy.observe(_snap(0, [("A", "A")]))
    assert len(d1) == 1 and d1[0].action == "scale_out"
    assert "replace sole-coverage replica" in d1[0].reason
    # replacement online: NOW the drain is safe
    d2 = policy.observe(_snap(1, [("A", "A"), ("B", "")]))
    assert len(d2) == 1 and d2[0].action == "scale_in" and d2[0].target == "A"
    assert "drain divergent" in d2[0].reason


def test_policy_drops_replacement_iou_at_max_replicas():
    from petals_tpu.swarm.policy import AutoscalerPolicy, PolicyConfig

    policy = AutoscalerPolicy(PolicyConfig(
        cooldown_global=1, min_replicas=1, max_replicas=2, span_blocks=0,
    ))
    d1 = policy.observe(_snap(0, [("A", "A"), ("B", ""), ("C", "")]))
    assert d1 and d1[0].action == "scale_in" and d1[0].target == "A"
    # the swarm is already at max_replicas: the owed scale_out is dropped...
    assert policy.observe(_snap(1, [("B", ""), ("C", "")])) == []
    # ...and STAYS dropped (the IOU is consumed, not deferred)
    assert policy.observe(_snap(2, [("B", ""), ("C", "")])) == []
