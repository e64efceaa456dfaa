"""The two expert dispatches of models/moe.py head to head on the real chip:
the all-experts einsum against the grouped ``ragged_dot``, one layer's experts
at a family's published shapes, over the shapes the step programs give them
(a decode step's [lanes, 1, h] and a prompt chunk's [1, bucket, h]).

    chiprun -- python3 benchmarks/ablate_moe_dispatch.py

What ``models/moe.grouped_dispatch`` was set from (PERF.md section 6, PR 26).
Weights ride as jit arguments; a call is timed as the slope between chains of
2 and 10 calls in one program (a single dispatch is mostly dispatch floor),
each link fed the last one's output so XLA cannot drop it. On the CPU the
numbers mean nothing and the sizes are cut to a toy.
"""

from __future__ import annotations

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
CALLS = ((8, 1), (1, 8), (1, 16), (1, 32), (1, 64), (1, 128), (1, 256), (1, 512), (1, 1024))


def main() -> int:
    import jax
    import jax.numpy as jnp

    from petals_tpu.models.moe import MoeDims, grouped_dispatch, moe_apply

    on_chip = jax.default_backend() == "tpu"
    rows = []
    wanted = sys.argv[1:]  # shape names; all of them by default
    for name, (h, m, n_experts, top_k, renormalize, n_routed) in SHAPES.items():
        if wanted and name not in wanted:
            continue
        if not on_chip:
            h, m = h // 16, m // 16
        keys = jax.random.split(jax.random.PRNGKey(0), 5)
        share = n_routed != n_experts
        rule = dict(scoring="sigmoid", scale=2.5) if share else {}
        params = {
            "gate": jax.random.normal(keys[0], (h, n_routed), jnp.bfloat16) * 0.02,
            "w1": jax.random.normal(keys[1], (n_experts, h, m), jnp.bfloat16) * 0.02,
            "w2": jax.random.normal(keys[2], (n_experts, m, h), jnp.bfloat16) * 0.02,
            "w3": jax.random.normal(keys[3], (n_experts, h, m), jnp.bfloat16) * 0.02,
        }
        if share:
            params["gate_bias"] = jax.random.normal(keys[4], (n_routed,), jnp.float32) * 0.02
        for batch, seq in CALLS:
            x = jax.random.normal(jax.random.PRNGKey(batch * 4096 + seq), (batch, seq, h), jnp.bfloat16)
            row = {"shape": name, "batch": batch, "seq": seq, "rule": "grouped" if grouped_dispatch(MoeDims(n_experts, top_k, h, m, routed=n_routed), seq) else "dense"}
            for path in ("dense", "grouped"):
                def chain(params, x, *, links, grouped):
                    y = x
                    for _ in range(links):  # each link reads the weights again: nothing to hoist
                        y = x + moe_apply(params, y, top_k=top_k, renormalize=renormalize, grouped=grouped, **rule)
                    return y

                times = {}
                for links in (2, 10):
                    fn = jax.jit(functools.partial(chain, links=links, grouped=path == "grouped"))
                    fn(params, x).block_until_ready()
                    best = float("inf")
                    for _ in range(5):
                        t = time.perf_counter()
                        fn(params, x).block_until_ready()
                        best = min(best, time.perf_counter() - t)
                    times[links] = best
                row[f"{path}_ms"] = 1e3 * (times[10] - times[2]) / 8
            rows.append(row)
            print(json.dumps(row), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/moe_dispatch.json", "w") as f:
        json.dump({"backend": jax.default_backend(), "device": jax.devices()[0].device_kind, "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
