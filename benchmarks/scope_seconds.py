#!/usr/bin/env python3
"""Device seconds a traced run of the benchmark spent under a named scope:

    python3 benchmarks/scope_seconds.py [--root DIR] [--child 0] ptu.attn.paged_decode ptu.span.full_attention

after ``perf/run.py --workload <cell> --trace 1`` in the checkout ``DIR`` (this
one by default), in the same call on the chip: the capture is a run-time
product under ``DIR/perf/.work/runs/<cell>/trace/`` and is not carried back.

``breakdown.device_ops`` of a result line names a step's ten largest
operations; a layer's call that is unrolled into four of ~0.026 s each
(``olmohybrid7b-ctx2k``'s decode walks, PR 45) lies under its tenth place,
and a loop is one ``while`` whatever ran inside it. The scope an operation ran
under is in the capture itself (the ``tf_op`` of its metadata), which
perf/layer_metrics/sparse_attn_roofline_share.py already reads for its own
three scopes with a reader of the capture's wire format: this script borrows
``capture`` and ``named_seconds`` from it and asks for the scopes it is given,
one by one: the union of the intervals of the operations under the scope, a
mean over the capture's device planes. It prints one JSON line a scope and
measures nothing itself; the seconds are those of the traced slice, so compare
two sides on one seed."""
import argparse
import importlib
import json
import sys
from pathlib import Path


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--root", type=Path, default=Path(__file__).resolve().parents[1], help="the checkout whose traced run to read")
    parser.add_argument("--child", type=int, default=0, help="which server child's capture")
    parser.add_argument("scopes", nargs="+", help="named scopes, as jax.named_scope was given them (a part of the name matches)")
    args = parser.parse_args()
    sys.path.insert(0, str(args.root.resolve()))  # that checkout's reader, beside that checkout's runs
    reader = importlib.import_module("perf.layer_metrics.sparse_attn_roofline_share")
    path = reader.capture(args.child)
    if path is None:
        print(f"no capture of child {args.child} under {reader.RUNS_DIR}", file=sys.stderr)
        return 1
    for scope in args.scopes:
        reader.NAMES = (scope,)
        print(json.dumps({"scope": scope, "device_s": reader.named_seconds(path), "capture": str(path)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
