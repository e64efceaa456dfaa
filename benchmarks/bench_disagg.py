"""Disaggregated prefill/decode serving benchmark: phase tiers + KV handoff.

One seeded prefill-storm trace (a flat calm stream of short-prompt
sessions + seeded bursts of long prompts with short decodes) is replayed
over a tiny-llama swarm recipe against a DISAGGREGATED swarm (1 prefill-tier
+ 1 decode-tier replica) and a COLOCATED baseline (2 generalists, same lane
count), both under a token-proportional device-time floor: every sized
compute-queue task sleeps ``size * per_token`` on its server's single
compute thread, so a long prefill monopolizes its replica the way it
monopolizes a real accelerator — on any host speed, the queueing is
scripted, not a machine artifact. The disagg swarm runs FIRST so the
process-wide jit cache warms for the baseline (bias, if any, favors
colocated — the gate is conservative). The happy-path handoff contract on
its own (adopt-only, exact ledger attribution, clean source) is
tests/test_disagg.py.

``--check`` fails (exit 1) unless:
- zero lost sessions + full HF token parity, both swarms;
- calm-traffic TTFT p99 strictly better disaggregated than colocated;
- calm-traffic decode tok/s strictly better disaggregated than colocated;
- happy-path handoffs: every storm session adopts exactly once, with
  zero replay fallbacks, zero failed pushes, zero degrade-to-colocated
  journal events, and handoff bytes > 0 (the colocated baseline must
  hand off NOTHING);
- ledger conservation: the migrated-bytes delta equals exactly 2x the
  pushed handoff bytes (the source's closed-peer rollup plus the
  destination's live-session attribution share the in-process ledger
  singleton, and no byte may go missing or get double-counted beyond
  those two attributions);
- the per-tier autoscaler journal replays byte-identically through two
  fresh policies and contains at least one prefill-tier scale_out (the
  storm queues the prefill tier's lanes; the decode tier must not be
  what fires);
- under PETALS_TPU_SANITIZE=1, zero runtime-sanitizer violations.

Usage: python benchmarks/bench_disagg.py [--cpu] [--seed 7] [--check]
"""

import argparse
import asyncio
import contextlib
import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

PREFILL_TIER_TOKENS = 16  # calm prompts (7 tokens) route decode-ward, storms prefill-ward


def _ledger_migrated() -> int:
    from petals_tpu.telemetry.ledger import get_ledger

    return sum(r["migrated_bytes"] for r in get_ledger().top_peers(k=1000))


@contextlib.contextmanager
def _device_floor(per_token_s: float):
    """Token-proportional service floor: every sized compute-queue task
    sleeps ``size * per_token_s`` ON THE COMPUTE THREAD before running, so
    each server behaves like a serial accelerator that takes that long per
    token — a 64-token prefill chunk stalls its replica's decode ticks,
    which is exactly the contention disaggregation exists to remove.
    Size-0 tasks (swap, extract/insert, snapshots) stay free."""
    from petals_tpu.server.task_queue import PriorityTaskQueue

    real_submit = PriorityTaskQueue.submit

    async def floored(self, fn, *args, **kwargs):
        size = kwargs.get("size", 0)
        if size > 0:
            def slow(*a, _fn=fn, **k):
                time.sleep(size * per_token_s)
                return _fn(*a, **k)

            return await real_submit(self, slow, *args, **kwargs)
        return await real_submit(self, fn, *args, **kwargs)

    PriorityTaskQueue.submit = floored
    try:
        yield
    finally:
        PriorityTaskQueue.submit = real_submit


@contextlib.contextmanager
def _replay_spy():
    """Record every client-side handoff replay step: the happy path (a cut
    exactly at the step boundary) must never take it."""
    from petals_tpu.client.inference_session import InferenceSession

    replays = []
    real_replay = InferenceSession._replay_step

    async def spy(self, session, chunk, hypo_step, step_id):
        replays.append(step_id)
        return await real_replay(self, session, chunk, hypo_step, step_id)

    InferenceSession._replay_step = spy
    try:
        yield replays
    finally:
        InferenceSession._replay_step = real_replay


def hf_expected(path, plans):
    """HF greedy reference for every plan, loading the model ONCE. Manual
    argmax loop: the swarm client defaults eos_token_id=None (exactly N
    tokens), while HF generate would stop at the tiny llama's eos."""
    import torch
    from transformers import AutoModelForCausalLM

    model = AutoModelForCausalLM.from_pretrained(path, dtype=torch.float32).eval()
    expected = []
    with torch.no_grad():
        for plan in plans:
            ids = torch.tensor([list(plan.prompt)], dtype=torch.int64)
            for _ in range(plan.new_tokens):
                logits = model(ids).logits
                nxt = logits[:, -1, :].argmax(-1, keepdim=True)
                ids = torch.cat([ids, nxt], dim=1)
            expected.append(ids.numpy())
    return expected


# --------------------------------------------------------------- heavy A/B


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--cpu", action="store_true", help="force the CPU backend")
    parser.add_argument("--seed", type=int, default=7, help="traffic seed")
    parser.add_argument("--duration", type=float, default=24.0, help="trace seconds")
    parser.add_argument(
        "--base_rate", type=float, default=1.2,
        help="calm arrivals/s (flat: the storm supplies the burstiness)",
    )
    parser.add_argument(
        "--storm_rate", type=float, default=0.35,
        help="burst epochs/s inside the storm window",
    )
    parser.add_argument("--storm_burst", type=int, default=5, help="sessions per burst")
    parser.add_argument(
        "--per_token_ms", type=float, default=6.0,
        help="device-time floor per token (the scripted service time)",
    )
    parser.add_argument("--tick", type=float, default=0.5, help="autoscaler tick seconds")
    parser.add_argument(
        "--check", action="store_true",
        help="fail (exit 1) unless every gate above holds",
    )
    args = parser.parse_args()

    sanitize = bool(os.environ.get("PETALS_TPU_SANITIZE"))
    if sanitize:
        from petals_tpu.analysis.sanitizer import SanitizingEventLoopPolicy, get_sanitizer

        asyncio.set_event_loop_policy(SanitizingEventLoopPolicy())
        get_sanitizer().reset()

    import jax

    if args.cpu or jax.default_backend() != "tpu":
        jax.config.update("jax_platforms", "cpu")

    from tests.test_full_model import SwarmHarness
    from tests.utils import make_tiny_llama

    from petals_tpu.client.model import AutoDistributedModelForCausalLM
    from petals_tpu.swarm.policy import AutoscalerPolicy, PolicyConfig, snapshot_from_health
    from petals_tpu.telemetry import get_journal
    from petals_tpu.telemetry import instruments as tm
    from petals_tpu.traffic import TrafficConfig, TrafficGenerator, run_schedule

    path = make_tiny_llama(tempfile.mkdtemp())

    traffic_cfg = TrafficConfig(
        seed=args.seed,
        duration_s=args.duration,
        base_rate=args.base_rate,
        wave_amplitude=0.0,  # flat calm stream: the storm is the only burstiness
        tenants=3,
        prompt_prefix_len=4,
        prompt_suffix_len=3,  # 7-token calm prompts: decode-phase routing
        vocab_size=128,  # the tiny llama's vocab (tests.utils.make_tiny_llama)
        min_new_tokens=2,
        max_new_tokens=5,
        storm_rate=args.storm_rate,
        storm_burst=args.storm_burst,
        storm_start_frac=0.25,
        storm_end_frac=0.75,
        storm_prompt_len=48,  # >= PREFILL_TIER_TOKENS: prefill-phase routing
        storm_prompt_max=96,
        storm_new_tokens=2,  # prefill-bound: 1 decode step after the handoff
    )
    plans = TrafficGenerator(traffic_cfg).schedule()
    assert plans == TrafficGenerator(traffic_cfg).schedule(), "schedule must be seed-deterministic"
    n_storm = sum(1 for p in plans if p.storm)
    n_calm = len(plans) - n_storm
    assert n_storm > 0, "the storm window landed no bursts — raise --storm_rate"
    print(
        f"traffic: {len(plans)} sessions over {args.duration:.0f}s "
        f"({n_calm} calm + {n_storm} storm, seed={args.seed})"
    )
    expected = hf_expected(path, plans)

    policy_cfg = PolicyConfig(
        ttft_p99_ms=60_000.0,
        # silence the swarm-wide queue signal: the per-tier paths are what
        # this bench gates (a share of 5.0 = 5 waiters per lane, unreachable)
        queue_share_high=5.0,
        queue_share_low=0.1,
        prefill_queue_share_high=0.4,
        prefill_queue_share_low=0.1,
        prefill_sustain_out=2,
        prefill_cooldown_out=8,
        decode_occupancy_high=0.9,
        decode_occupancy_low=0.4,
        decode_sustain_out=3,
        decode_cooldown_out=8,
        cooldown_resize=1_000_000,
        cooldown_global=2,
        max_replicas=8,
    )

    lane_spec = dict(
        first_block=0, num_blocks=4, batch_lanes=2, update_period=0.5,
        server_side_generation=False,  # the handoff cuts at the client step boundary
    )

    def run_one(kind):
        """Boot a 2-replica swarm (tiered or colocated), replay the trace,
        return the per-run metrics and telemetry deltas."""
        tiered = kind == "disagg"
        if tiered:
            server_cfgs = [
                dict(throughput=1000.0, phase_tier="prefill", **lane_spec),
                dict(throughput=1000.0, phase_tier="decode", **lane_spec),
            ]
        else:
            # slight throughput split so min-latency routing has a stable
            # deterministic order instead of equal-cost coin flips
            server_cfgs = [
                dict(throughput=1000.0, **lane_spec),
                dict(throughput=995.0, **lane_spec),
            ]
        harness = SwarmHarness(path, server_cfgs).start()
        clients = [
            AutoDistributedModelForCausalLM.from_pretrained(
                path,
                initial_peers=harness.initial_peers,
                min_backoff=0.05,
                update_period=6.0,
                alloc_timeout=8.0,
                prefill_tier_tokens=PREFILL_TIER_TOKENS,
            )
            for _ in range(traffic_cfg.tenants)
        ]

        policy = AutoscalerPolicy(policy_cfg)
        snapshots = []
        stop_control = threading.Event()

        async def control_loop():
            from petals_tpu.dht import DHTNode
            from petals_tpu.utils.health import HealthMonitor

            monitor = HealthMonitor(harness.initial_peers, port=0)
            monitor.dht = await DHTNode.create(
                initial_peers=[harness.bootstrap.own_addr], client_mode=True
            )
            tick = 0
            try:
                while not stop_control.is_set():
                    try:
                        await monitor.refresh()
                        models = monitor._state["models"]
                        if models:
                            snap = snapshot_from_health(
                                models[sorted(models)[0]], tick=tick
                            )
                            snapshots.append(snap)
                            policy.observe(snap)
                            tick += 1
                    except Exception as e:  # a refresh can race a teardown
                        print(f"  control tick {tick} failed: {e!r}")
                    await asyncio.sleep(args.tick)
            finally:
                await monitor.dht.shutdown()

        def session_fn(plan):
            model = clients[plan.tenant]
            ids = np.array([list(plan.prompt)], dtype=np.int64)
            with model.remote.inference_session(
                max_length=len(plan.prompt) + plan.new_tokens + 8, batch_size=1
            ) as sess:
                t0 = time.perf_counter()
                out = model.generate(ids, max_new_tokens=1, session=sess)
                ttft_s = time.perf_counter() - t0
                t1 = time.perf_counter()
                if plan.new_tokens > 1:
                    out = model.generate(
                        out, max_new_tokens=plan.new_tokens - 1, session=sess
                    )
                decode_s = time.perf_counter() - t1
            return {"tokens": np.asarray(out), "ttft_s": ttft_s, "decode_s": decode_s}

        results = []
        control_future = None
        try:
            # warmup (off the clock): compile the storm-sized prefill chunk,
            # the decode step, and — tiered — the handoff/adopt path
            warm_rng = np.random.RandomState(args.seed + 1)
            for plen in (traffic_cfg.storm_prompt_len, 7):
                warm_ids = warm_rng.randint(1, 128, (1, plen)).astype(np.int64)
                with clients[0].remote.inference_session(
                    max_length=plen + 8, batch_size=1
                ) as sess:
                    clients[0].generate(warm_ids, max_new_tokens=2, session=sess)

            baseline_seq = get_journal().event(f"bench_disagg_{kind}_start")["seq"]
            ok0 = tm.HANDOFFS.labels(outcome="ok").value
            failed0 = tm.HANDOFFS.labels(outcome="failed").value
            bytes0 = int(tm.HANDOFF_BYTES.value)
            migrated0 = _ledger_migrated()

            control_future = asyncio.run_coroutine_threadsafe(
                control_loop(), harness.loop
            )
            with _replay_spy() as replays:
                results = run_schedule(plans, session_fn, join_timeout_s=600.0)
        finally:
            stop_control.set()
            if control_future is not None:
                with contextlib.suppress(Exception):
                    control_future.result(timeout=30)
            for model in clients:
                with contextlib.suppress(Exception):
                    model.close()
            harness.stop()

        return {
            "kind": kind,
            "results": results,
            "snapshots": snapshots,
            "live_journal": policy.journal_jsonl(),
            "journal_rows": list(policy.journal),
            "replays": list(replays),
            "handoffs_ok": tm.HANDOFFS.labels(outcome="ok").value - ok0,
            "handoffs_failed": tm.HANDOFFS.labels(outcome="failed").value - failed0,
            "handoff_bytes": int(tm.HANDOFF_BYTES.value) - bytes0,
            "migrated_bytes": _ledger_migrated() - migrated0,
            "fallback_events": len(
                get_journal().events(kind="handoff_fallback", since_seq=baseline_seq)
            ),
        }

    def summarize(run):
        results = run["results"]
        lost = [r for r in results if not r.ok]
        parity = sum(
            1
            for r in results
            if r.ok and np.array_equal(r.value["tokens"], expected[r.index])
        )
        calm = [r for r in results if r.ok and not plans[r.index].storm]
        storm = [r for r in results if r.ok and plans[r.index].storm]

        def ttft_p99(rs):
            ts = sorted(r.value["ttft_s"] for r in rs)
            return ts[min(len(ts) - 1, int(len(ts) * 0.99))] if ts else float("nan")

        def decode_tok_s(rs):
            toks = sum(plans[r.index].new_tokens - 1 for r in rs)
            secs = sum(r.value["decode_s"] for r in rs)
            return toks / secs if secs > 0 else float("nan")

        run.update(
            lost=len(lost),
            lost_errors=[r.error for r in lost][:3],
            parity=parity,
            calm_ttft_p99=ttft_p99(calm),
            storm_ttft_p99=ttft_p99(storm),
            calm_tok_s=decode_tok_s(calm),
        )
        return run

    with _device_floor(args.per_token_ms / 1000.0):
        disagg = summarize(run_one("disagg"))
        colocated = summarize(run_one("colocated"))

    # journal determinism: the per-tier policy is pure — replaying the
    # recorded snapshots through fresh policies must reproduce the live
    # controller's journal byte for byte
    def replay_journal():
        policy = AutoscalerPolicy(policy_cfg)
        for snap in disagg["snapshots"]:
            policy.observe(snap)
        return policy.journal_jsonl()

    replay_a, replay_b = replay_journal(), replay_journal()
    deterministic = replay_a == replay_b == disagg["live_journal"]
    prefill_decisions = [
        row for row in disagg["journal_rows"]
        if row.get("action") == "scale_out" and row.get("tier") == "prefill"
    ]

    print(f"\ndisagg A/B: {len(plans)} sessions, floor {args.per_token_ms:.1f}ms/token")
    for run in (disagg, colocated):
        print(
            f"  {run['kind']:>10}: survived {len(run['results']) - run['lost']}"
            f"/{len(plans)}, parity {run['parity']}/{len(plans)}, "
            f"calm TTFT p99 {run['calm_ttft_p99']:.3f}s, "
            f"calm decode {run['calm_tok_s']:.1f} tok/s, "
            f"storm TTFT p99 {run['storm_ttft_p99']:.3f}s, "
            f"handoffs {run['handoffs_ok']} ok / {run['handoffs_failed']} failed "
            f"({run['handoff_bytes'] / 2**10:.1f} KiB pushed)"
        )
    print(
        f"  autoscaler: {len(disagg['snapshots'])} ticks, "
        f"{len(disagg['journal_rows'])} decisions "
        f"({len(prefill_decisions)} prefill-tier scale_out); "
        f"journal deterministic: {deterministic}"
    )
    for line in disagg["live_journal"].splitlines():
        print(f"    {line}")

    failures = []
    for run in (disagg, colocated):
        if run["lost"]:
            failures.append(
                f"{run['kind']}: {run['lost']} session(s) lost: {run['lost_errors']}"
            )
        if run["parity"] != len(plans):
            failures.append(f"{run['kind']}: token parity {run['parity']}/{len(plans)}")
    if not (disagg["calm_ttft_p99"] < colocated["calm_ttft_p99"]):
        failures.append(
            f"calm TTFT p99 not better: disagg {disagg['calm_ttft_p99']:.3f}s "
            f"vs colocated {colocated['calm_ttft_p99']:.3f}s"
        )
    if not (disagg["calm_tok_s"] > colocated["calm_tok_s"]):
        failures.append(
            f"calm decode tok/s not better: disagg {disagg['calm_tok_s']:.1f} "
            f"vs colocated {colocated['calm_tok_s']:.1f}"
        )
    if disagg["handoffs_ok"] != n_storm:
        failures.append(
            f"expected {n_storm} happy-path handoffs, saw {disagg['handoffs_ok']}"
        )
    if disagg["handoffs_failed"] or disagg["fallback_events"] or disagg["replays"]:
        failures.append(
            f"not a happy path: {disagg['handoffs_failed']} failed pushes, "
            f"{disagg['fallback_events']} fallbacks, {len(disagg['replays'])} replays"
        )
    if disagg["handoff_bytes"] <= 0:
        failures.append("the page-push path moved zero KV bytes")
    if disagg["migrated_bytes"] != 2 * disagg["handoff_bytes"]:
        failures.append(
            f"ledger conservation broken: migrated {disagg['migrated_bytes']} != "
            f"2 * pushed {disagg['handoff_bytes']}"
        )
    if colocated["handoffs_ok"] or colocated["handoff_bytes"]:
        failures.append(
            f"colocated baseline handed off ({colocated['handoffs_ok']} sessions, "
            f"{colocated['handoff_bytes']}B) — tier routing leaked"
        )
    if not deterministic:
        failures.append("per-tier decision journal not byte-identical across replays")
    if not prefill_decisions:
        failures.append("the storm never fired a prefill-tier scale_out decision")
    if sanitize:
        violations = get_sanitizer().violations()
        if violations:
            failures.append(f"{len(violations)} sanitizer violation(s): {violations[:2]}")

    if args.check:
        if failures:
            sys.exit("CHECK FAILED: " + "; ".join(failures))
        print(
            "CHECK OK: disaggregation beat colocated on calm TTFT p99 AND decode "
            "tok/s under the storm, with adopt-only handoffs, exact ledger "
            "attribution, and a byte-replayable per-tier journal"
        )
    elif failures:
        print(f"  (gates not enforced without --check: {'; '.join(failures)})")


if __name__ == "__main__":
    from petals_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    main()
