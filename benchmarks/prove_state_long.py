#!/usr/bin/env python3
"""perf/prove_chunks.py's proof with a longer prompt: a recurrent state handed
across MORE than three mixed steps and decoded from past 2,048 positions,
held to the reference:

    chiprun --timeout 1500 -- python3 benchmarks/prove_state_long.py --workload qwen3next80b-ctx2k --seeds 2147483723

perf/prove_chunks.py's session is 1,536 + 32 positions, three mixed steps;
the ``ctx2k`` cells' sessions run to 2,560. This calls the same ``prove``
(the same served sessions, the same reference in float32, the same control
that drops the state at the first chunk's boundary, the same limits and the
same rows to ``chiprun_out/chunks_<cell>.jsonl``) with ``--prompt`` positions
(default 2,304: five mixed steps of up to 512) and ``--steps`` decode steps
(default 32), and says what it found as that script does. The prompt and the
steps must fit a lane (``batch_max_length``)."""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True, help="comma-separated")
    parser.add_argument("--prompt", type=int, default=2304)
    parser.add_argument("--steps", type=int, default=32)
    args = parser.parse_args(argv)
    from perf import prove_chunks

    prove_chunks.PROMPT, prove_chunks.STEPS = args.prompt, args.steps
    summary = prove_chunks.prove(json.loads((ROOT / "BENCHMARK.json").read_text()), args.workload, [int(s) for s in args.seeds.split(",")])
    print(f"{args.prompt} + {args.steps} positions: {summary['correct']} of {summary['sessions']} sessions correct, the nearest at "
          f"{100 * summary['nearest']:.0f}% of a limit; with the state dropped at the first chunk's boundary "
          f"{summary['control_not_correct']} of {summary['sessions']} not correct, the nearest at {summary['control_nearest']:.1f} times a limit", flush=True)
    return 0 if summary["correct"] == summary["control_not_correct"] == summary["sessions"] else 1


if __name__ == "__main__":
    sys.exit(main())
