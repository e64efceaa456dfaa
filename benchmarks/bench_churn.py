"""Swarm churn benchmark: scripted kill + drain + rebalance over N sessions.

The serving promise under churn is (a) no session is lost, (b) token output
is identical to an unperturbed run, (c) repair is cheap. This bench scripts
the three churn events the swarm must absorb — a hard KILL (server process
death), a graceful DRAIN (drain-to-migrate pushes parked KV to a replica),
and a REBALANCE (span reload parks + migrates its pooled sessions) — against
N concurrent inference sessions, and reports:

- sessions survived (out of N),
- token parity against the HF reference (== the unperturbed swarm output,
  which the test suite asserts everywhere),
- repair-step latency p50/p99, comparing ``migrate`` (the p2p redirect +
  kv_adopt path) against ``replay`` (history recompute, forced by disabling
  KV export — the reference's only repair).

Optionally arms the chaos plane on top (``--chaos "seed=1;rpc.call:drop:0.05"``)
so the scripted churn runs under background fault injection.

Self-contained: boots a 4-replica loopback swarm in-process (tiny llama).

Usage: python benchmarks/bench_churn.py [--cpu] [--sessions 4] [--prefix 64]
       [--chaos SPEC]
"""

import argparse
import contextlib
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def run_churn(path, n_sessions, prefix, layers, mode, chaos_spec):
    """One scripted churn pass; returns (survived, parity_ok, repair_times)."""
    from tests.test_full_model import SwarmHarness, _hf_greedy
    from petals_tpu import chaos
    from petals_tpu.client.inference_session import InferenceSession
    from petals_tpu.client.model import AutoDistributedModelForCausalLM

    # four full-span replicas: routing prefers A; the script kills A, drains
    # B, rebalances C — D (and whichever replicas survive) absorb everything
    harness = SwarmHarness(
        path,
        [
            dict(first_block=0, num_blocks=layers, throughput=1000.0),  # A: killed
            dict(first_block=0, num_blocks=layers, throughput=800.0),  # B: drained
            dict(first_block=0, num_blocks=layers, throughput=600.0),  # C: rebalanced
            dict(first_block=0, num_blocks=layers, throughput=1.0),  # D: understudy
        ],
    ).start()
    model = AutoDistributedModelForCausalLM.from_pretrained(
        path, initial_peers=harness.initial_peers, min_backoff=0.05,
    )
    restore_export = None
    if mode == "replay":
        # force the reference's only repair: no KV export, no redirect — every
        # orphaned span recomputes from the recorded history
        restore_export = InferenceSession._try_export

        async def _no_export(self, *a, **kw):
            return None

        InferenceSession._try_export = _no_export
    if chaos_spec:
        seed, rules = chaos.parse_spec(chaos_spec)
        chaos.configure(seed=seed, rules=rules)

    # 4 phases x 2 tokens after the prefill; the HF reference doubles as the
    # unperturbed swarm output (asserted identical throughout the test suite)
    rng = np.random.RandomState(7)
    prompts = [
        rng.randint(0, 100, (1, prefix)).astype(np.int64) for _ in range(n_sessions)
    ]
    expected = [_hf_greedy(path, ids, 8) for ids in prompts]

    repair_times = []
    survived = 0
    parity_ok = 0
    try:
        with contextlib.ExitStack() as stack:
            sessions = [
                stack.enter_context(
                    model.remote.inference_session(max_length=prefix + 16, batch_size=1)
                )
                for _ in range(n_sessions)
            ]
            outs = [
                model.generate(prompts[i], max_new_tokens=2, session=sessions[i])
                for i in range(n_sessions)
            ]

            def step_all(label):
                # the first generate after a churn event pays that session's
                # repair; time it per session
                for i in range(n_sessions):
                    if outs[i] is None:
                        continue
                    t0 = time.perf_counter()
                    try:
                        outs[i] = model.generate(
                            outs[i], max_new_tokens=2, session=sessions[i]
                        )
                        repair_times.append(time.perf_counter() - t0)
                    except Exception as e:
                        print(f"  session {i} LOST at {label}: {e!r}")
                        outs[i] = None

            print(f"[{mode}] KILL server A (hard death)")
            harness.run(harness.servers[0].shutdown())
            dead = harness.servers.pop(0)
            del dead
            step_all("kill")

            print(f"[{mode}] DRAIN server B (drain-to-migrate)")
            harness.run(harness.servers[0].drain(migrate=(mode != "replay")))
            step_all("drain")

            print(f"[{mode}] REBALANCE server C (span reload parks + migrates)")
            harness.run(harness.servers[1]._reload_span(0))
            step_all("rebalance")

            for i in range(n_sessions):
                if outs[i] is None:
                    continue
                survived += 1
                if np.array_equal(outs[i], expected[i]):
                    parity_ok += 1
    finally:
        chaos.disable()
        if restore_export is not None:
            InferenceSession._try_export = restore_export
        model.close()
        harness.run(harness.servers[0].shutdown())  # the drained server
        harness.servers.pop(0)
        harness.stop()
    return survived, parity_ok, repair_times


def run_integrity(path, n_sessions, prefix, layers, seed=7):
    """Integrity observatory end-to-end: one replica of a 3-replica full-span
    swarm silently corrupts its activations (``integrity.corrupt``); the
    canary prober must detect the outlier by quorum, journal AND
    flight-record the divergence with both digests, routing must stop
    selecting it (announce-visible quarantine), the autoscaler must
    drain-and-replace it, and every client session must still finish with
    full token parity. Returns a dict of gate facts."""
    import json as _json

    import jax.numpy as jnp

    from tests.test_full_model import SwarmHarness, _hf_greedy
    from petals_tpu import chaos
    from petals_tpu.client.model import AutoDistributedModelForCausalLM
    from petals_tpu.ops import fingerprint as fp_ops
    from petals_tpu.server.server import Server
    from petals_tpu.swarm import Autoscaler, CallbackActuator, PolicyConfig
    from petals_tpu.swarm.policy import snapshot_from_health
    from petals_tpu.telemetry.integrity import get_quarantine
    from petals_tpu.telemetry.journal import get_journal
    from petals_tpu.telemetry.observatory import get_observatory
    from petals_tpu.utils.health import HealthMonitor

    fp_prev = fp_ops.enabled()
    fp_ops.set_enabled(True)
    facts = {
        "detected_round": None, "journaled": False, "flight_recorded": False,
        "quarantined_only_victim": False, "announce_visible": False,
        "drained": False, "replaced": False,
        "survived": 0, "parity": 0, "false_positives": 0,
        "corrupt_fired_on_session": False,
    }

    # three full-span replicas (quorum needs >= 3): A (fastest,
    # routing-preferred) is the corrupting victim — exactly the replica an
    # unprotected router would send every session to
    spec = dict(
        first_block=0, num_blocks=layers, batch_lanes=2, update_period=0.5,
    )
    harness = SwarmHarness(
        path,
        [
            dict(throughput=1000.0, **spec),  # A: corrupting victim
            dict(throughput=800.0, **spec),  # B: honest
            dict(throughput=600.0, **spec),  # C: honest
        ],
    ).start()
    victim = harness.servers[0].dht.peer_id.to_string()
    chaos.configure(
        seed=seed,
        rules=[
            chaos.ChaosRule(
                site=chaos.SITE_INTEGRITY_CORRUPT, action="corrupt", match=victim
            )
        ],
    )

    monitor = HealthMonitor(harness.initial_peers, port=0)

    async def attach_monitor():
        from petals_tpu.dht import DHTNode

        monitor.dht = await DHTNode.create(
            initial_peers=[harness.bootstrap.own_addr], client_mode=True
        )

    harness.run(attach_monitor())
    model = None
    try:
        # ---- phase 1: canary rounds until the quorum names the victim ----
        for round_i in range(20):
            harness.run(monitor.refresh())
            harness.run(monitor.canary_probe())
            if get_quarantine().is_quarantined(victim):
                facts["detected_round"] = round_i + 1
                break
            time.sleep(0.5)
        facts["quarantined_only_victim"] = set(get_quarantine().snapshot()) == {victim}

        events = [
            _json.loads(line)
            for line in get_journal().to_jsonl(kind="integrity_divergence").splitlines()
            if line.strip()
        ]
        facts["journaled"] = any(
            e.get("peer") == victim
            and e.get("local_digest") and e.get("remote_digest")
            and e["local_digest"] != e["remote_digest"]
            for e in events
        )
        facts["flight_recorded"] = any(
            e.get("peer") == victim
            for e in get_observatory().flight_recorder().entries("integrity_divergence")
        )

        # ---- phase 2: the quarantine becomes announce-visible ----
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            harness.run(monitor.refresh())
            for _prefix, m in monitor._state["models"].items():
                integ = ((m.get("servers") or {}).get(victim) or {}).get("integrity")
                if isinstance(integ, dict) and integ.get("quarantined"):
                    facts["announce_visible"] = True
            if facts["announce_visible"]:
                break
            time.sleep(0.3)

        # ---- phase 3: sessions + autoscaler drain-and-replace ----
        model = AutoDistributedModelForCausalLM.from_pretrained(
            path, initial_peers=harness.initial_peers, min_backoff=0.05,
        )
        rng = np.random.RandomState(seed)
        prompts = [
            rng.randint(0, 100, (1, prefix)).astype(np.int64)
            for _ in range(n_sessions)
        ]
        expected = [_hf_greedy(path, ids, 8) for ids in prompts]

        async def do_scale_out(span):
            server = Server(
                path,
                initial_peers=harness.initial_peers,
                compute_dtype=jnp.float32,
                use_flash=False,
                throughput=700.0,
                first_block=span[0], num_blocks=span[1] - span[0],
                **{k: v for k, v in spec.items() if k not in ("first_block", "num_blocks")},
            )
            await server.start()
            harness.servers.append(server)
            return True

        async def do_scale_in(peer):
            for server in list(harness.servers):
                if server.dht is not None and server.dht.peer_id.to_string() == peer:
                    await server.drain(migrate=True)
                    await server.shutdown()
                    harness.servers.remove(server)
                    return True
            raise RuntimeError(f"scale_in target {peer!r} not found in harness")

        scaler = Autoscaler(
            actuator=CallbackActuator(scale_out=do_scale_out, scale_in=do_scale_in),
            config=PolicyConfig(
                # latency signals are irrelevant here: only the quarantine
                # plane should fire, one decision per tick
                ttft_p99_ms=1e12,
                queue_share_high=1e9,
                cooldown_global=1,
                min_replicas=2,
                max_replicas=4,
                span_blocks=0,
            ),
        )

        with contextlib.ExitStack() as stack:
            sessions = [
                stack.enter_context(
                    model.remote.inference_session(
                        max_length=prefix + 16, batch_size=1
                    )
                )
                for _ in range(n_sessions)
            ]
            outs = [
                model.generate(prompts[i], max_new_tokens=2, session=sessions[i])
                for i in range(n_sessions)
            ]

            tick = 0
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline:
                harness.run(monitor.refresh())
                models = monitor._state["models"]
                if models:
                    mprefix = sorted(models)[0]
                    snap = snapshot_from_health(models[mprefix], tick=tick)
                    harness.run(scaler.step(snap))
                    tick += 1
                reasons = [d.reason for d in scaler.decisions]
                facts["drained"] = any("drain divergent" in r for r in reasons)
                facts["replaced"] = any("replace drained" in r for r in reasons)
                if facts["drained"] and facts["replaced"]:
                    break
                time.sleep(0.5)

            # sessions ride through the drain + replacement to completion
            for i in range(n_sessions):
                try:
                    for _ in range(3):
                        outs[i] = model.generate(
                            outs[i], max_new_tokens=2, session=sessions[i]
                        )
                except Exception as e:
                    print(f"  integrity session {i} LOST: {e!r}")
                    outs[i] = None

            for i in range(n_sessions):
                if outs[i] is None:
                    continue
                facts["survived"] += 1
                if np.array_equal(outs[i], expected[i]):
                    facts["parity"] += 1
            # zero false positives: no honest hop tripped a client cross-check
            facts["false_positives"] = sum(
                s.integrity.divergences for s in sessions
            )
        # the corrupt rule matched only probe traffic — routing never handed
        # the quarantined replica a client step
        facts["corrupt_fired_on_session"] = any(
            not str(e.get("detail", "")).endswith(":probe")
            for e in chaos.get_plane().fired(chaos.SITE_INTEGRITY_CORRUPT)
        )
    finally:
        chaos.disable()
        get_quarantine().release(victim)
        if model is not None:
            with contextlib.suppress(Exception):
                model.close()
        with contextlib.suppress(Exception):
            harness.run(monitor.dht.shutdown())
        harness.stop()
        fp_ops.set_enabled(fp_prev)
    return facts


def integrity_failures(facts, n_sessions):
    """Gate predicate for the integrity pass (shared by --check and tests)."""
    failures = []
    if facts["detected_round"] is None:
        failures.append("canary prober never quarantined the corrupt replica")
    if not facts["quarantined_only_victim"]:
        failures.append("quarantine named the wrong replica set")
    if not facts["journaled"]:
        failures.append("no integrity_divergence journal event with both digests")
    if not facts["flight_recorded"]:
        failures.append("no flight-recorder divergence entry")
    if not facts["announce_visible"]:
        failures.append("quarantine never became announce-visible")
    if not facts["drained"]:
        failures.append("autoscaler never drained the quarantined replica")
    if not facts["replaced"]:
        failures.append("autoscaler never replaced the drained replica")
    if facts["survived"] != n_sessions or facts["parity"] != n_sessions:
        failures.append(
            f"sessions survived {facts['survived']}/{n_sessions}, "
            f"parity {facts['parity']}/{n_sessions}"
        )
    if facts["false_positives"]:
        failures.append(
            f"{facts['false_positives']} client cross-check false positive(s)"
        )
    if facts["corrupt_fired_on_session"]:
        failures.append("a client step was routed through the corrupt replica")
    return failures


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--cpu", action="store_true", help="force the CPU backend")
    parser.add_argument("--sessions", type=int, default=4, help="concurrent sessions (N)")
    parser.add_argument("--prefix", type=int, default=64, help="prompt tokens per session")
    parser.add_argument("--layers", type=int, default=4)
    parser.add_argument(
        "--chaos", default="", help="PETALS_TPU_CHAOS-style spec armed during the run"
    )
    parser.add_argument(
        "--check", action="store_true",
        help="fail (exit 1) unless every session survives with token parity "
        "in migrate mode",
    )
    args = parser.parse_args()
    assert args.sessions >= 4, "the churn script needs N >= 4 concurrent sessions"

    import jax

    if args.cpu or jax.default_backend() != "tpu":
        jax.config.update("jax_platforms", "cpu")

    from tests.utils import make_tiny_llama

    path = make_tiny_llama(tempfile.mkdtemp(), n_layers=args.layers)

    results = {}
    for mode in ("migrate", "replay"):
        survived, parity, times = run_churn(
            path, args.sessions, args.prefix, args.layers, mode, args.chaos
        )
        results[mode] = (survived, parity, times)

    print("\n[integrity] corrupt one replica; canary -> quarantine -> replace")
    integrity = run_integrity(path, args.sessions, args.prefix, args.layers)

    print(
        f"\nchurn: 1 kill + 1 drain + 1 rebalance over {args.sessions} sessions, "
        f"prefix={args.prefix}, {args.layers} blocks"
        + (f", chaos={args.chaos!r}" if args.chaos else "")
    )
    for mode, (survived, parity, times) in results.items():
        p50 = np.percentile(times, 50) * 1e3 if times else float("nan")
        p99 = np.percentile(times, 99) * 1e3 if times else float("nan")
        print(
            f"  {mode:>7}: survived {survived}/{args.sessions}, "
            f"token-parity {parity}/{args.sessions}, "
            f"repair-step p50 {p50:.0f} ms / p99 {p99:.0f} ms ({len(times)} steps)"
        )
    int_failures = integrity_failures(integrity, args.sessions)
    print(
        f"  integrity: detected in {integrity['detected_round']} canary round(s), "
        f"journaled={integrity['journaled']}, flight={integrity['flight_recorded']}, "
        f"announce={integrity['announce_visible']}, "
        f"drained={integrity['drained']}, replaced={integrity['replaced']}, "
        f"survived {integrity['survived']}/{args.sessions}, "
        f"parity {integrity['parity']}/{args.sessions}, "
        f"false-positives {integrity['false_positives']}"
    )

    if args.check:
        survived, parity, _ = results["migrate"]
        if survived != args.sessions or parity != args.sessions:
            sys.exit(
                f"CHECK FAILED: migrate mode survived {survived}/{args.sessions}, "
                f"parity {parity}/{args.sessions}"
            )
        if int_failures:
            sys.exit("CHECK FAILED (integrity): " + "; ".join(int_failures))
        print(
            "CHECK OK: zero sessions lost, token output identical under churn, "
            "corrupt replica quarantined and replaced with zero false positives"
        )


if __name__ == "__main__":
    from petals_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    main()
