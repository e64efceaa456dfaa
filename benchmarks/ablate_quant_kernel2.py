"""Round 2 of the 4-bit decode kernel ablation: tile sizes + int4 v2.

Round 1 (ablate_quant_kernel.py) found, interleaved on the real chip:
  bf16 ceiling 788 GB/s | s0 dma+dot 377 | s1 +mask/shift 335 | s2 +gather 100
  s3 current 98 | s4 blockwise-nf4 99 | s5 blockwise-int4-no-gather 241
i.e. (a) the NF4 table gather costs 3.5x everything else, (b) even decode-free
the 512-wide-tile structure caps at ~46% HBM (per-grid-step overhead across
896 steps), (c) gather-free blockwise int4 is the fast path.

This round: tn/tk scaling for s0/s5, and int4 v2 — per-quant-block sums of x
precomputed OUTSIDE the kernel, affine correction folded into one extra
[tm, nb] @ [nb, tn] dot per tile instead of 16 per-block subtractions.

Usage (chip required): chiprun -- python benchmarks/ablate_quant_kernel2.py
"""

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from petals_tpu.ops import quant as Q

HIDDEN = 8192
GU = 57344
NF4_BLOCK = 64


def hard_sync(x):
    np.asarray(jax.device_get(jnp.ravel(x)[:1]))


def kernel_v2(xs_ref, xe_ref, xo_ref, packed_ref, scales_ref, o_ref, acc_ref,
              *, n_k, mode):
    """int4 v2 / nf4-blockwise with precomputed x block sums.

    xs_ref: [nb, tm] per-quant-block sums of x for this k-tile (int4 only).
    out += sum_b s[b,:] * (xe_b @ lo_b + xo_b @ hi_b) - 8 * (xs.T @ s)
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    half, tn = packed_ref.shape
    hb = NF4_BLOCK // 2
    nb = half // hb

    packed = packed_ref[...].astype(jnp.int32)
    lo = packed & 0x0F
    hi = (packed >> 4) & 0x0F
    c_lo = lo.astype(jnp.bfloat16)
    c_hi = hi.astype(jnp.bfloat16)

    xe = xe_ref[...]
    xo = xo_ref[...]
    scales = scales_ref[...].astype(jnp.float32)  # [nb, tn]
    acc = acc_ref[...]
    for b in range(nb):
        p = jax.lax.dot_general(
            xe[:, b * hb:(b + 1) * hb], c_lo[b * hb:(b + 1) * hb, :],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        p += jax.lax.dot_general(
            xo[:, b * hb:(b + 1) * hb], c_hi[b * hb:(b + 1) * hb, :],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        acc += p * scales[b:b + 1, :]
    # affine correction: one [tm, nb] @ [nb, tn] dot
    xs = xs_ref[...]  # [nb, tm] f32
    acc -= 8.0 * jax.lax.dot_general(
        xs, scales, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    acc_ref[...] = acc

    @pl.when(k == n_k - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def run_v2(x, q, tk, tn):
    m, n_in = x.shape
    n_stored = q.data.shape[-2] * 2
    n_out = q.out_features
    n_k, n_n = n_stored // tk, n_out // tn
    tm = 8
    x = jnp.pad(x, ((0, tm - m), (0, 0)))
    xb = x.astype(jnp.bfloat16)
    xe, xo = xb[:, 0::2], xb[:, 1::2]
    hk = tk // 2
    nb = tk // NF4_BLOCK
    # per-quant-block sums of x, [n_k*nb, tm], f32
    xs = xb.astype(jnp.float32).reshape(tm, n_stored // NF4_BLOCK, NF4_BLOCK).sum(axis=2).T
    out = pl.pallas_call(
        functools.partial(kernel_v2, n_k=n_k, mode="int4"),
        grid=(1, n_n, n_k),
        in_specs=[
            pl.BlockSpec((nb, tm), lambda mi, n, k: (k, 0)),
            pl.BlockSpec((tm, hk), lambda mi, n, k: (mi, k)),
            pl.BlockSpec((tm, hk), lambda mi, n, k: (mi, k)),
            pl.BlockSpec((hk, tn), lambda mi, n, k: (k, n)),
            pl.BlockSpec((tk // NF4_BLOCK, tn), lambda mi, n, k: (k, n)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda mi, n, k: (mi, n)),
        out_shape=jax.ShapeDtypeStruct((tm, n_out), x.dtype),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(xs, xe, xo, q.data, q.scales)
    return out[:m]


# --- round-1 kernels, parameterized tiles ---------------------------------
import benchmarks.ablate_quant_kernel as R1


def run_r1(x, q, kernel, tk, tn, **kw):
    m, n_in = x.shape
    n_stored = q.data.shape[-2] * 2
    n_out = q.out_features
    n_k, n_n = n_stored // tk, n_out // tn
    tm = 8
    x = jnp.pad(x, ((0, tm - m), (0, 0)))
    xb = x.astype(jnp.bfloat16)
    xe, xo = xb[:, 0::2], xb[:, 1::2]
    hk = tk // 2
    out = pl.pallas_call(
        functools.partial(kernel, n_k=n_k, **kw),
        grid=(1, n_n, n_k),
        in_specs=[
            pl.BlockSpec((tm, hk), lambda mi, n, k: (mi, k)),
            pl.BlockSpec((tm, hk), lambda mi, n, k: (mi, k)),
            pl.BlockSpec((hk, tn), lambda mi, n, k: (k, n)),
            pl.BlockSpec((tk // NF4_BLOCK, tn), lambda mi, n, k: (k, n)),
            pl.BlockSpec((8, 128), lambda mi, n, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda mi, n, k: (mi, n)),
        out_shape=jax.ShapeDtypeStruct((tm, n_out), x.dtype),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(xe, xo, q.data, q.scales, Q._decode_table(q.kind))
    return out[:m]


class Probe:
    def __init__(self, label, bytes_moved, fn, args, k1=2, k2=6):
        self.label, self.bytes, self.k1, self.k2 = label, bytes_moved, k1, k2

        def chain(k):
            def f(v, d, s):
                for j in range(k):
                    o = fn(v, d, s)
                    v = o[:, :v.shape[1]] * (1e-2 + j / 128.0)
                return v
            return f

        self.fns = {k: jax.jit(chain(k)) for k in (k1, k2)}
        self.args = args
        self.ts = {k1: float("inf"), k2: float("inf")}
        for f in self.fns.values():
            hard_sync(f(*args))

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
        print(f"{self.label:36s} {sec * 1e3:8.3f} ms  {gbs:7.1f} GB/s  ({100 * gbs / 819:5.1f}% HBM)",
              flush=True)


def main():
    assert jax.default_backend() == "tpu"
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (HIDDEN, GU), jnp.bfloat16) * 0.02
    qn = Q.quantize_nf4(w)
    qi = Q.quantize_int4(w)
    x = jax.random.normal(key, (1, HIDDEN), jnp.bfloat16) * 0.1
    del w
    hard_sync(qn.data)
    hard_sync(qi.data)

    ref_i = (x.astype(jnp.bfloat16) @ Q.dequantize(qi, jnp.bfloat16)).astype(jnp.float32)
    got = run_v2(x, qi, 1024, 1024).astype(jnp.float32)
    err = float(jnp.max(jnp.abs(got - ref_i)) / (jnp.max(jnp.abs(ref_i)) + 1e-9))
    print(f"# int4 v2 rel max err vs XLA dequant: {err:.2e}")

    def mk_r1(kernel, tk, tn, kind="nf4", **kw):
        return lambda v, d, s: run_r1(v, Q.QuantizedLinear(kind, d, s, HIDDEN, GU), kernel, tk, tn, **kw)

    def mk_v2(tk, tn):
        return lambda v, d, s: run_v2(v, Q.QuantizedLinear("int4", d, s, HIDDEN, GU), tk, tn)

    nargs = (x, qn.data, qn.scales)
    iargs = (x, qi.data, qi.scales)
    probes = [
        Probe("bf16 dense (ceiling)", HIDDEN * GU * 2,
              lambda v, d, s: v @ d, (x, jax.random.normal(key, (HIDDEN, GU), jnp.bfloat16), qn.scales)),
        Probe("s0 tn512", qn.nbytes, mk_r1(R1.kernel_stage, 1024, 512, stage=0), nargs),
        Probe("s0 tn1024", qn.nbytes, mk_r1(R1.kernel_stage, 1024, 1024, stage=0), nargs),
        Probe("s0 tn2048", qn.nbytes, mk_r1(R1.kernel_stage, 1024, 2048, stage=0), nargs),
        Probe("s0 tk2048 tn1024", qn.nbytes, mk_r1(R1.kernel_stage, 2048, 1024, stage=0), nargs),
        Probe("s1 tn1024", qn.nbytes, mk_r1(R1.kernel_stage, 1024, 1024, stage=1), nargs),
        Probe("s5 tn1024", qi.nbytes, mk_r1(R1.kernel_blockwise, 1024, 1024, kind="int4", mode="int4"), iargs),
        Probe("v2 int4 tn1024", qi.nbytes, mk_v2(1024, 1024), iargs),
        Probe("v2 int4 tn2048", qi.nbytes, mk_v2(1024, 2048), iargs),
        Probe("v2 int4 tk2048 tn1024", qi.nbytes, mk_v2(2048, 1024), iargs),
        Probe("s2 nf4 tn1024", qn.nbytes, mk_r1(R1.kernel_stage, 1024, 1024, stage=2), nargs),
        Probe("s4 nf4 tn1024", qn.nbytes, mk_r1(R1.kernel_blockwise, 1024, 1024, mode="nf4"), nargs),
        Probe("s5 tk2048 tn1024", qi.nbytes, mk_r1(R1.kernel_blockwise, 2048, 1024, kind="int4", mode="int4"), iargs),
        Probe("s5 tk2048 tn2048", qi.nbytes, mk_r1(R1.kernel_blockwise, 2048, 2048, kind="int4", mode="int4"), iargs),
        Probe("v2 int4 tk2048 tn2048", qi.nbytes, mk_v2(2048, 2048), iargs),
        Probe("v2 int4 tk4096 tn1024", qi.nbytes, mk_v2(4096, 1024), iargs),
        Probe("s4 nf4 tk2048 tn1024", qn.nbytes, mk_r1(R1.kernel_blockwise, 2048, 1024, mode="nf4"), nargs),
    ]
    for p in probes:
        p.measure_once(inner=1)
    for _ in range(6):
        for p in probes:
            p.measure_once()
    print("# interleaved (min over 6 passes):")
    for p in probes:
        p.report()


if __name__ == "__main__":
    from petals_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    main()
