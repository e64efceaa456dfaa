"""Repair-latency benchmark: KV migration vs history replay.

When a server leaves gracefully (drain), a client has three repair options
for the orphaned span, from slowest to fastest:

- ``replay``  — replay the whole recorded input history into the replacement
  (the reference's only option: recomputing the full prefill);
- ``export``  — pull the dying server's exported KV over the client link and
  import it into the replacement (``ptu.session_export``, drain without p2p);
- ``p2p``     — drain-to-migrate: the server pushes its parked KV directly to
  a replica (``ptu.session_migrate``), the client follows the redirect and
  adopts the cache server-side (``kv_adopt``) — zero KV bytes on the client
  link.

This measures the modes on the same swarm and prefix length, so the benefit
is directly visible: replay cost grows with the prefix while migration moves
bytes instead of recomputing — and p2p moves them over the fast server link.

Self-contained: boots a 2-front-server loopback swarm in-process (tiny llama)
and repairs a session whose prefix is ``--prefix`` tokens long.

Usage:
    python benchmarks/benchmark_migration.py [--cpu] [--prefix 512]
    python benchmarks/benchmark_migration.py --p2p [--check]

``--p2p`` benchmarks the server-to-server path against replay; ``--check``
exits non-zero unless the p2p repair actually used the adopt path AND beat
replay (the CI chaos lane runs ``--p2p --check``).
"""

import argparse
import asyncio
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--cpu", action="store_true", help="force the CPU backend")
    parser.add_argument("--prefix", type=int, default=512, help="session prefix tokens")
    parser.add_argument("--layers", type=int, default=4)
    parser.add_argument(
        "--p2p", action="store_true",
        help="benchmark drain-to-migrate (server-to-server push + kv_adopt) "
        "instead of the client-link export path",
    )
    parser.add_argument(
        "--check", action="store_true",
        help="fail (exit 1) unless the p2p repair adopted server-side and "
        "beat history replay — a functional gate for CI",
    )
    args = parser.parse_args()

    import jax

    if args.cpu or jax.default_backend() != "tpu":
        jax.config.update("jax_platforms", "cpu")

    from tests.test_full_model import SwarmHarness
    from tests.utils import make_tiny_llama
    from petals_tpu.client.model import AutoDistributedModelForCausalLM
    from petals_tpu.telemetry.journal import get_journal

    path = make_tiny_llama(tempfile.mkdtemp(), n_layers=args.layers)
    max_length = args.prefix + 64

    def run_one(mode: str) -> float:
        harness = SwarmHarness(
            path,
            [
                dict(first_block=0, num_blocks=args.layers, throughput=1000.0),
                dict(first_block=0, num_blocks=args.layers, throughput=1.0),
            ],
        ).start()
        model = AutoDistributedModelForCausalLM.from_pretrained(
            path, initial_peers=harness.initial_peers, min_backoff=0.05,
        )
        try:
            rng = np.random.RandomState(0)
            ids = rng.randint(0, 100, (1, args.prefix)).astype(np.int64)
            with model.remote.inference_session(
                max_length=max_length, batch_size=1
            ) as session:
                first = model.generate(ids, max_new_tokens=2, session=session)
                fast = harness.servers[0]
                if mode == "p2p":
                    harness.run(fast.drain())  # pushes KV to the replica
                elif mode == "export":
                    harness.run(fast.drain(migrate=False))  # exports stay served
                else:
                    harness.run(fast.shutdown())  # hard death: replay only
                t0 = time.perf_counter()
                model.generate(first, max_new_tokens=1, session=session)
                repair_s = time.perf_counter() - t0
            return repair_s
        finally:
            model.close()
            if mode in ("p2p", "export"):
                harness.run(harness.servers[0].shutdown())
                harness.servers.pop(0)
            harness.stop()

    fast_mode = "p2p" if args.p2p else "export"
    fast_label = "p2p-migration" if args.p2p else "KV-migration"
    adopts_before = len(get_journal().events(kind="migrate_adopt"))
    t_replay = run_one("replay")
    t_fast = run_one(fast_mode)
    adopted = len(get_journal().events(kind="migrate_adopt")) - adopts_before
    print(
        f"prefix={args.prefix} tokens, {args.layers} blocks: "
        f"replay repair {t_replay * 1e3:.0f} ms, "
        f"{fast_label} repair {t_fast * 1e3:.0f} ms "
        f"({t_replay / max(t_fast, 1e-9):.2f}x faster)"
    )
    if args.p2p:
        print(f"server-side kv_adopt seeds during p2p repair: {adopted}")
    if args.check:
        if not args.p2p:
            sys.exit("--check requires --p2p")
        if adopted < 1:
            sys.exit("CHECK FAILED: p2p repair did not use the kv_adopt path")
        if t_fast >= t_replay:
            sys.exit(
                f"CHECK FAILED: p2p repair ({t_fast * 1e3:.0f} ms) did not beat "
                f"history replay ({t_replay * 1e3:.0f} ms) at prefix {args.prefix}"
            )
        print("CHECK OK: p2p repair adopted server-side and beat replay")


if __name__ == "__main__":
    from petals_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    main()
