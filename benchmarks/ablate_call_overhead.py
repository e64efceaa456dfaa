"""Where do the 70B full-row extras go? Same span bytes, different kernel
call structure — measures the fixed cost of each Pallas call at decode.

The r5 on-chip numbers: nf4a pure-span (one 8192x28672 call per block) runs
391 GB/s while the full block row (4 quant calls + attention/norms) runs
304 — this ablation separates per-call fixed cost from attention/norm cost
by chaining the same bytes through 1, 4, and real-block-shaped call
sequences. Usage (chip required):
    [QUANT_KIND=nf4a] python benchmarks/ablate_call_overhead.py [one|four|real]
Run ONE variant per process: a fresh process is a fresh HBM heap.
"""
import os, time, sys, gc, jax, jax.numpy as jnp, numpy as np
from petals_tpu.ops import quant as Q
from petals_tpu.ops.quant import StackedQuantLinear, packed4_matmul_pallas_stacked

def hard_sync(x):
    np.asarray(jax.device_get(jnp.ravel(x)[:1]))

KIND = os.environ.get("QUANT_KIND", "nf4a")
N = 10
key = jax.random.PRNGKey(0)

def stack_for(shape_list):
    """list of (in, out) -> list of (data, scales) stacks over N blocks."""
    stacks = []
    for (fin, fout) in shape_list:
        qs = []
        for i in range(N):
            w = jax.random.normal(jax.random.PRNGKey(i), (fin, fout), jnp.bfloat16) * 0.02
            qs.append(Q.quantize(w, KIND))
        stacks.append((jnp.stack([q.data for q in qs]), jnp.stack([q.scales for q in qs]),
                       fin, fout, sum(q.nbytes for q in qs)))
        del qs; gc.collect()
    return stacks

def bench(label, shapes, take):
    stacks = stack_for(shapes)
    nbytes = sum(s[4] for s in stacks)
    datas = tuple(s[0] for s in stacks)
    scaless = tuple(s[1] for s in stacks)
    meta = tuple((s[2], s[3]) for s in stacks)

    @jax.jit
    def span(v, datas, scaless):
        def body(h, i):
            x = h
            for j, (fin, fout) in enumerate(meta):
                sq = StackedQuantLinear(KIND, datas[j], scaless[j], i, fin, fout)
                o = packed4_matmul_pallas_stacked(x[:, :fin], sq)
                x = o * 1e-2
            return x[:, :take], None
        out, _ = jax.lax.scan(body, v, jnp.arange(N, dtype=jnp.int32))
        return out

    x = jax.random.normal(key, (1, take), jnp.bfloat16) * 0.1
    hard_sync(span(x, datas, scaless))
    times = []
    for _ in range(6):
        t0 = time.perf_counter(); hard_sync(span(x, datas, scaless)); times.append(time.perf_counter() - t0)
    y = jnp.zeros((1,), jnp.float32)
    syncs = []
    for _ in range(6):
        t0 = time.perf_counter(); hard_sync(y); syncs.append(time.perf_counter() - t0)
    sec = min(times) - min(syncs)
    print(f"{KIND} {label}: {sec*1e3/N:.3f} ms/blk, {nbytes/sec/1e9:.0f} GB/s ({len(shapes)} calls/blk)", flush=True)
    del stacks, datas, scaless
    gc.collect()

which = sys.argv[1:] or ["one", "four"]
if "one" in which:
    bench("1-call  8192x28672        ", [(8192, 28672)], 8192)
if "four" in which:
    bench("4-call  8192x8192 x4      ", [(8192, 8192)] * 4, 8192)
if "real" in which:
    # llama-70B-ish block shapes: qkv (fused), o, gate+up (fused), down
    bench("real    qkv/o/gateup/down ", [(8192, 10240), (8192, 8192), (8192, 57344), (28672, 8192)], 8192)
