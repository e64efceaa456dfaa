"""The three expert dispatches of models/moe.py head to head on the real chip:
the all-experts einsum, the grouped ``ragged_dot`` and the hit kernel that
reads the experts reached out of the stacked run, one layer's experts at a
family's published shapes, over the shapes the step programs give them (a
decode step's [lanes, 1, h] with 1, 2 or all 8 lanes live, and a prompt
chunk's [1, bucket, h]).

    chiprun -- python3 benchmarks/ablate_moe_dispatch.py [shape ...] [--mode alone|scan|both] [--decode]

Two modes, because "alone" and "in the loop" differ by a copy (PERF.md section
6, PR 31): ``alone`` hands a dispatch one layer's weights as they are;
``scan`` runs it inside a ``lax.scan`` over a stacked run of 2 layers, the
stack a scan const and the layer its counter, which is what a step program's
layer loop does (server/backend.py ``_scan_span``). There ``ragged_dot`` is
handed a copy of the layer's experts and the hit kernel the stack itself.
``--decode`` times the decode-shaped calls only (the ones "hit" can take).

What ``models/moe.grouped_dispatch`` was set from (PERF.md section 6, PRs 26,
31 and 32). Weights ride as jit arguments; a call is timed as the slope
between chains of 2 and 10 calls in one program (a single dispatch is mostly
dispatch floor), each link fed the last one's output so XLA cannot drop it,
scaled down so that every link routes as the first does: two of the three
read what the rows reach, and a chain that feeds on itself overflows bf16 and
routes every row alike. ``reached`` is the held experts the call's live rows
chose. On the CPU the numbers mean nothing and the sizes are cut to a toy.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SHAPES = {  # hidden, expert width, experts held, top k, renormalize, experts routed over (a share: sigmoid rule)
    "olmoe-1b-7b": (2048, 1024, 64, 8, False, 64),
    "mixtral-8x7b": (4096, 14336, 8, 2, True, 8),
    "k-exaone-236b-ep8": (6144, 2048, 16, 8, True, 128),  # one chip's 16 of 128 experts of 75.5 MB, no shared expert here
}
# (batch, seq, live rows): the dead rows of a decode step are zeros, as an idle lane's are (server/batching.py)
DECODE_CALLS = ((8, 1, 8), (8, 1, 2), (8, 1, 1))
CHUNK_CALLS = tuple((1, seq, 1) for seq in (8, 16, 32, 64, 128, 256, 512, 1024))
SCAN_LAYERS = 2
FEED = 2.0 ** -10  # of a link's output into the next one's input: under bf16's step at 1, so the rows stay what they were


def _time(fn, *args) -> float:
    fn(*args).block_until_ready()
    best = float("inf")
    for _ in range(5):
        t = time.perf_counter()
        fn(*args).block_until_ready()
        best = min(best, time.perf_counter() - t)
    return best


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("shapes", nargs="*", help="shape names; all of them by default")
    parser.add_argument("--mode", choices=("alone", "scan", "both"), default="both")
    parser.add_argument("--decode", action="store_true", help="the decode-shaped calls only")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp

    from petals_tpu.models.moe import ExpertStack, MoeDims, Routing, grouped_dispatch, hit_slots, moe_apply, route

    on_chip = jax.default_backend() == "tpu"
    rows = []
    for name, (h, m, n_experts, top_k, renormalize, n_routed) in SHAPES.items():
        if args.shapes and name not in args.shapes:
            continue
        if not on_chip:
            h, m = h // 16, m // 16
        share = n_routed != n_experts
        rule = dict(top_k=top_k, renormalize=renormalize, **(dict(scoring="sigmoid", scale=2.5) if share else {}))
        for mode in ("alone", "scan") if args.mode == "both" else (args.mode,):
            depth = SCAN_LAYERS if mode == "scan" else 1
            keys = jax.random.split(jax.random.PRNGKey(0), 5)
            router = {"gate": jax.random.normal(keys[0], (h, n_routed), jnp.bfloat16) * 0.02}
            if share:
                router["gate_bias"] = jax.random.normal(keys[4], (n_routed,), jnp.float32) * 0.02
            stacks = {
                "w1": jax.random.normal(keys[1], (depth, n_experts, h, m), jnp.bfloat16) * 0.02,
                "w3": jax.random.normal(keys[3], (depth, n_experts, h, m), jnp.bfloat16) * 0.02,
                "w2": jax.random.normal(keys[2], (depth, n_experts, m, h), jnp.bfloat16) * 0.02,
            }
            own = {leaf: stack[0] for leaf, stack in stacks.items()}  # a layer's own weights, for `alone`

            def chain(router, weights, x, live, *, links, dispatch, mode):
                def one(y, layer):
                    if mode == "scan" or dispatch == "hit":  # the stack and the layer's index in it
                        params = {**router, "experts": ExpertStack(weights["w1"], weights["w3"], weights["w2"], layer)}
                    else:
                        params = {**router, **weights}
                    return x + FEED * moe_apply(params, y, dispatch=dispatch, live_rows=live if dispatch == "hit" else None, **rule)

                y = x
                for _ in range(links):  # each link reads the weights again: nothing to hoist
                    if mode == "scan":
                        y, _ = jax.lax.scan(lambda y, layer: (one(y, layer), None), y, jnp.arange(depth, dtype=jnp.int32))
                    else:
                        y = one(y, jnp.int32(0))
                return y

            for batch, seq, n_live in DECODE_CALLS + (() if args.decode else CHUNK_CALLS):
                decode = seq == 1
                if not decode and mode == "scan" and seq not in (64, 256):
                    continue  # in the loop: two chunk shapes show the copy
                live = jnp.arange(batch) < n_live
                x = jax.random.normal(jax.random.PRNGKey(batch * 4096 + seq), (batch, seq, h), jnp.bfloat16)
                if decode:
                    x = jnp.where(live[:, None, None], x, 0)
                dims = MoeDims(n_experts, top_k, h, m, routed=n_routed)
                top_idx, top_w = route(router, x, Routing(top_k, rule.get("scoring", "softmax"), renormalize, rule.get("scale", 1.0)))
                rows_live = jnp.repeat(live, seq) if decode else None
                reached = int(hit_slots(top_idx.reshape(-1, top_k), top_w.reshape(-1, top_k), rows_live, n_experts)[1])
                row = {"shape": name, "mode": mode, "batch": batch, "seq": seq, "live": n_live if decode else batch,
                       "reached": reached, "rule": grouped_dispatch(dims, seq, stacked=mode == "scan")}
                for dispatch in ("dense", "grouped", "hit"):
                    if dispatch == "hit" and not decode:
                        continue  # a chunk never takes it
                    if dispatch != "hit" and n_live != DECODE_CALLS[0][2] and decode:
                        continue  # the other two compute dead rows like any other: timed once, all rows live
                    weights = own if mode == "alone" and dispatch != "hit" else stacks
                    times = {
                        links: _time(jax.jit(functools.partial(chain, links=links, dispatch=dispatch, mode=mode)), router, weights, x, live)
                        for links in (2, 10)
                    }
                    row[f"{dispatch}_ms"] = 1e3 * (times[10] - times[2]) / (8 * depth)  # a layer
                rows.append(row)
                print(json.dumps(row), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(f"chiprun_out/moe_dispatch_{args.mode}.json", "w") as f:
        json.dump({"backend": jax.default_backend(), "device": jax.devices()[0].device_kind, "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
