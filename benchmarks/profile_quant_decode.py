"""Profile the 4-bit decode serving path layer by layer on the real chip.

Round-3 instrument for VERDICT weak #1: quantized decode measured 95 GB/s of
weight streaming (11.6% HBM) in the serving path while bf16 hit 790 GB/s.
This script isolates each level of the stack at decode shape (M=1):

  L0  bf16 dense matmul chain               (the streaming-rate ceiling)
  L1  packed4_matmul_pallas, single weight  (kernel alone, 4 fused shapes)
  L2  packed4_matmul_pallas_stacked         (scalar-prefetch stacked variant)
  L4  backend._inference_step_fn            (the scan the server actually runs)

Methodology: a single dispatch is mostly dispatch floor, so each probe chains
k data-dependent applications inside one jit and reports the slope between
two chain lengths. Probes are INTERLEAVED round-robin over several passes and
the min per probe is reported — never compare numbers from different runs.

Usage (chip required): [QUANT_KIND=int4] python benchmarks/profile_quant_decode.py
"""

import gc
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from petals_tpu.ops import quant as Q

HIDDEN = 8192
QKV = 10240  # 64 q heads + 2*8 kv heads, head_dim 128, fused
GU = 57344  # gate+up fused
INTER = 28672
N_BLOCKS = 4
KIND = os.environ.get("QUANT_KIND", "nf4")


def hard_sync(x):
    np.asarray(jax.device_get(jnp.ravel(x)[:1]))


class Probe:
    """A (label, bytes, {k: jitted_fn}, args) chained-slope measurement."""

    def __init__(self, label, bytes_moved, make_chain, args, k1, k2):
        self.label, self.bytes = label, bytes_moved
        self.k1, self.k2 = k1, k2
        self.fns = {k: jax.jit(make_chain(k)) for k in (k1, k2)}
        self.args = args
        self.ts = {k1: float("inf"), k2: float("inf")}
        for k, f in self.fns.items():  # compile + settle
            hard_sync(f(*self.args))

    def measure_once(self, inner=3):
        for k, f in self.fns.items():
            t0 = time.perf_counter()
            for _ in range(inner):
                out = f(*self.args)
            hard_sync(out)
            self.ts[k] = min(self.ts[k], (time.perf_counter() - t0) / inner)

    def report(self):
        sec = max((self.ts[self.k2] - self.ts[self.k1]) / (self.k2 - self.k1), 1e-9)
        gbs = self.bytes / sec / 1e9
        print(
            f"{self.label:46s} {sec * 1e3:8.3f} ms  {gbs:7.1f} GB/s  "
            f"({100 * gbs / 819:5.1f}% HBM)",
            flush=True,
        )
        return sec, gbs


def main():
    assert jax.default_backend() == "tpu", "profile must run on the real chip"
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (1, HIDDEN), jnp.bfloat16) * 0.1
    probes = []

    # ---------------- L0: bf16 ceiling (up 8192->28672, down 28672->8192)
    wu = jax.random.normal(key, (HIDDEN, INTER), jnp.bfloat16) * 0.02
    wd = jax.random.normal(key, (INTER, HIDDEN), jnp.bfloat16) * 0.02

    def bf16_chain(k):
        def f(v, wu, wd):
            for _ in range(k):
                v = ((v @ wu) @ wd) * 1e-2
            return v
        return f

    probes.append(Probe("L0 bf16 up+down", 2 * HIDDEN * INTER * 2, bf16_chain, (x, wu, wd), 2, 8))

    # ---------------- L1: single-weight pallas kernel, per fused shape
    shapes = {"wqkv": (HIDDEN, QKV), "wo": (HIDDEN, HIDDEN), "wgu": (HIDDEN, GU), "wd": (INTER, HIDDEN)}
    qweights = {}
    for name, (n_in, n_out) in shapes.items():
        w = jax.random.normal(jax.random.fold_in(key, hash(name) % 1000), (n_in, n_out), jnp.bfloat16) * 0.02
        qweights[name] = Q.quantize(w, KIND)
        hard_sync(qweights[name].data)
        del w
        gc.collect()

    total_block_bytes = sum(q.nbytes for q in qweights.values())
    print(f"# one 70B fused block: {total_block_bytes / 2**20:.1f} MiB packed+scales")

    def single_chain(k):
        def f(v, qkv_d, qkv_s, o_d, o_s, gu_d, gu_s, d_d, d_s):
            for _ in range(k):
                a = Q.packed4_matmul_pallas(v, Q.QuantizedLinear(KIND, qkv_d, qkv_s, HIDDEN, QKV))
                v = Q.packed4_matmul_pallas(a[:, :HIDDEN], Q.QuantizedLinear(KIND, o_d, o_s, HIDDEN, HIDDEN))
                b = Q.packed4_matmul_pallas(v, Q.QuantizedLinear(KIND, gu_d, gu_s, HIDDEN, GU))
                v = Q.packed4_matmul_pallas(b[:, :INTER], Q.QuantizedLinear(KIND, d_d, d_s, INTER, HIDDEN))
                v = v * 1e-2
            return v
        return f

    wargs = (x,)
    for name in ("wqkv", "wo", "wgu", "wd"):
        wargs = wargs + (qweights[name].data, qweights[name].scales)
    probes.append(Probe("L1 pallas single, full block (4 mm)", total_block_bytes, single_chain, wargs, 1, 4))

    def one_shape_chain(name, n_in, n_out):
        def make(k):
            def f(v, d, s):
                for j in range(k):
                    o = Q.packed4_matmul_pallas(v, Q.QuantizedLinear(KIND, d, s, n_in, n_out))
                    if n_out >= n_in:
                        v = o[:, :n_in] * 1e-2
                    else:
                        v = jnp.pad(o, ((0, 0), (0, n_in - n_out))) * (1e-2 + j / 128.0)
                return v
            return f
        return make

    for name, (n_in, n_out) in shapes.items():
        q = qweights[name]
        xin = jax.random.normal(key, (1, n_in), jnp.bfloat16) * 0.1
        probes.append(
            Probe(f"L1 pallas single {name} {n_in}x{n_out}", q.nbytes,
                  one_shape_chain(name, n_in, n_out), (xin, q.data, q.scales), 2, 6)
        )

    # ---------------- L2: stacked kernel (scalar prefetch), chain over blocks
    stacked = {}
    for name, q in qweights.items():
        stacked[name] = Q.QuantizedLinear(
            q.kind,
            jnp.stack([q.data] * N_BLOCKS),
            jnp.stack([q.scales] * N_BLOCKS),
            q.in_features,
            q.out_features,
        )
        hard_sync(stacked[name].data)
        gc.collect()

    def stacked_chain(k):
        def f(v, qkv_d, qkv_s, o_d, o_s, gu_d, gu_s, d_d, d_s):
            def sq(dims, d, s, idx):
                return Q.StackedQuantLinear(KIND, d, s, idx, dims[0], dims[1])
            for _ in range(k):
                def body(v, idx):
                    a = Q.packed4_matmul_pallas_stacked(v, sq((HIDDEN, QKV), qkv_d, qkv_s, idx))
                    v = Q.packed4_matmul_pallas_stacked(a[:, :HIDDEN], sq((HIDDEN, HIDDEN), o_d, o_s, idx))
                    b = Q.packed4_matmul_pallas_stacked(v, sq((HIDDEN, GU), gu_d, gu_s, idx))
                    v = Q.packed4_matmul_pallas_stacked(b[:, :INTER], sq((INTER, HIDDEN), d_d, d_s, idx))
                    return v * 1e-2, None
                v, _ = jax.lax.scan(body, v, jnp.arange(N_BLOCKS, dtype=jnp.int32))
            return v
        return f

    sargs = (x,)
    for name in ("wqkv", "wo", "wgu", "wd"):
        sargs = sargs + (stacked[name].data, stacked[name].scales)
    probes.append(
        Probe(f"L2 pallas stacked, {N_BLOCKS}-block scan", total_block_bytes * N_BLOCKS,
              stacked_chain, sargs, 1, 3)
    )

    # ---------------- interleaved measurement
    for p in probes:
        p.measure_once(inner=1)  # settle executables
    for _ in range(6):
        for p in probes:
            p.measure_once()
    print("# interleaved (min over 6 passes):")
    for p in probes:
        p.report()

    # ---------------- L4: the backend's real inference step (separate: needs
    # the probes' HBM back). Timed against an interleaved bf16 matmul probe to
    # anchor against load drift.
    del stacked, sargs, wargs, qweights
    gc.collect()

    from petals_tpu.models.registry import get_family
    from petals_tpu.server.backend import TransformerBackend
    from petals_tpu.server.memory_cache import MemoryCache
    from bench import llama70b_cfg, random_params, params_bytes

    cfg = llama70b_cfg(N_BLOCKS)
    params = random_params(cfg, N_BLOCKS, jnp.bfloat16, quant=KIND)
    backend = TransformerBackend(
        get_family("llama"), cfg, params, first_block=0, n_blocks=N_BLOCKS,
        memory_cache=MemoryCache(None), compute_dtype=jnp.bfloat16,
    )
    wbytes = params_bytes(params)
    kd, vd = backend.cache_descriptors(1, 256, 0, N_BLOCKS)
    kv = (kd.make_zeros(), vd.make_zeros())
    rng = np.random.RandomState(0)
    prefill = rng.randn(1, 128, cfg.hidden_size).astype(np.float32) * 0.02
    step_h = rng.randn(1, 1, cfg.hidden_size).astype(np.float32) * 0.02
    _, kv = backend.inference_step(prefill, kv, 0)
    pos = 128
    out = None
    for _ in range(3):
        out, kv = backend.inference_step(step_h, kv, pos)
        pos += 1
    hard_sync(out)

    anchor = Probe("L0b bf16 up+down (anchor)", 2 * HIDDEN * INTER * 2, bf16_chain, (x, wu, wd), 2, 8)
    best = float("inf")
    for _ in range(5):
        anchor.measure_once()
        t0 = time.perf_counter()
        for _ in range(10):
            out, kv = backend.inference_step(step_h, kv, pos)
            pos += 1
        hard_sync(out)
        best = min(best, (time.perf_counter() - t0) / 10)
    anchor.report()
    gbs = wbytes / best / 1e9
    print(
        f"{'L4 backend inference_step ' + str(N_BLOCKS) + ' blocks':46s} "
        f"{best * 1e3 / N_BLOCKS:8.3f} ms/blk {gbs:7.1f} GB/s  ({100 * gbs / 819:5.1f}% HBM)"
    )


if __name__ == "__main__":
    from petals_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    main()
