"""Ablate the packed-4-bit decode kernel's per-tile cost on the real chip.

The profiler (profile_quant_decode.py) showed the kernel at ~90 GB/s at M=1
while bf16 streams at ~730 GB/s in the same run: the per-tile DECODE is
VPU-bound. This script times kernel variants that add decode stages one at a
time (wrong results are fine; only timing matters), plus candidate redesigns:

  s0  DMA + dot only (packed bytes cast straight to bf16)      <- upper bound
  s1  + widen/mask/shift (code extraction)
  s2  + table gather (reshape -> take_along_axis -> reshape)
  s3  + scale repeat & multiply                                 == current
  s4  blockwise-scale NF4: gather, single dots, scales applied to
      per-64-block partial sums (64x fewer scale ops)
  s5  blockwise int4: NO gather — raw codes feed the MXU, affine correction
      on the partial sums (exact for int4)
  s6  s4 with gather in bf16 (table pre-cast; skips f32->bf16 on the big tile)

Usage (chip required): chiprun -- python benchmarks/ablate_quant_kernel.py
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
_TK = 1024
_TN = 512
NF4_BLOCK = 64


def hard_sync(x):
    np.asarray(jax.device_get(jnp.ravel(x)[:1]))


# --------------------------------------------------------------------------- kernels


def kernel_stage(xe_ref, xo_ref, packed_ref, scales_ref, table_ref, o_ref, acc_ref,
                 *, n_k, stage):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    half, tn = packed_ref.shape
    xe = xe_ref[...]
    xo = xo_ref[...]

    if stage == 0:
        d_lo = packed_ref[...].astype(jnp.int32).astype(jnp.bfloat16)
        d_hi = d_lo
    else:
        packed = packed_ref[...].astype(jnp.int32)
        lo = packed & 0x0F
        hi = (packed >> 4) & 0x0F
        if stage == 1:
            d_lo = lo.astype(jnp.bfloat16)
            d_hi = hi.astype(jnp.bfloat16)
        else:
            rows = half * tn // 128
            tbl = jnp.broadcast_to(table_ref[0:1, :], (rows, 128))

            def decode(codes):
                return jnp.take_along_axis(tbl, codes.reshape(rows, 128), axis=1).reshape(half, tn)

            if stage == 2:
                d_lo = decode(lo).astype(jnp.bfloat16)
                d_hi = decode(hi).astype(jnp.bfloat16)
            elif stage == 3:
                scales = jnp.repeat(scales_ref[...].astype(jnp.float32), NF4_BLOCK // 2, axis=0)
                d_lo = (decode(lo) * scales).astype(jnp.bfloat16)
                d_hi = (decode(hi) * scales).astype(jnp.bfloat16)

    acc_ref[...] += jax.lax.dot_general(
        xe, d_lo, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    acc_ref[...] += jax.lax.dot_general(
        xo, d_hi, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def kernel_blockwise(xe_ref, xo_ref, packed_ref, scales_ref, table_ref, o_ref, acc_ref,
                     *, n_k, mode):
    """Blockwise-scale decode: partial dots per 64-row quant block, scales
    applied on the [n_blocks, tn] partials instead of the [half, tn] tile.

    mode "nf4": codes -> table gather (no scale mul on the big tile).
    mode "nf4_bf16": same with a bf16 table.
    mode "int4": NO gather; dot raw codes, correct with  s*(P - 8*X_b)  where
                 X_b is the per-block sum of x (exact affine algebra).
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    half, tn = packed_ref.shape
    tm = xe_ref.shape[0]
    hb = NF4_BLOCK // 2  # half-rows per quant block
    nb = half // hb  # quant blocks in this k-tile (=16)

    packed = packed_ref[...].astype(jnp.int32)
    lo = packed & 0x0F
    hi = (packed >> 4) & 0x0F
    if mode == "int4":
        c_lo = lo.astype(jnp.bfloat16)
        c_hi = hi.astype(jnp.bfloat16)
    else:
        rows = half * tn // 128
        dt = jnp.bfloat16 if mode == "nf4_bf16" else jnp.float32
        # gather indices and table must share a bitwidth (Mosaic constraint):
        # bf16 table takes int16 codes
        it = jnp.int16 if mode == "nf4_bf16" else jnp.int32
        tbl = jnp.broadcast_to(table_ref[0:1, :].astype(dt), (rows, 128))

        def decode(codes):
            idx = codes.reshape(rows, 128).astype(it)
            return jnp.take_along_axis(tbl, idx, axis=1).reshape(half, tn)

        c_lo = decode(lo).astype(jnp.bfloat16)
        c_hi = decode(hi).astype(jnp.bfloat16)

    xe = xe_ref[...]
    xo = xo_ref[...]
    scales = scales_ref[...].astype(jnp.float32)  # [nb, tn]
    # per-block dots with static 2-D slices (Mosaic rejects 3-D batched dots):
    # [tm, hb] @ [hb, tn] per quant block, scale applied on the partial sums
    acc = acc_ref[...]
    for b in range(nb):
        lo_b = c_lo[b * hb:(b + 1) * hb, :]
        hi_b = c_hi[b * hb:(b + 1) * hb, :]
        xe_b = xe[:, b * hb:(b + 1) * hb]
        xo_b = xo[:, b * hb:(b + 1) * hb]
        p = jax.lax.dot_general(
            xe_b, lo_b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        p += jax.lax.dot_general(
            xo_b, hi_b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        if mode == "int4":
            xsum = (xe_b.astype(jnp.float32).sum(axis=1)
                    + xo_b.astype(jnp.float32).sum(axis=1))  # [tm]
            p -= 8.0 * xsum[:, None]
        acc += p * scales[b:b + 1, :]
    acc_ref[...] = acc

    @pl.when(k == n_k - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def run_variant(x, q, kernel, **kw):
    m, n_in = x.shape
    n_stored = q.data.shape[-2] * 2
    n_out = q.out_features
    tn = _TN
    n_k, n_n = n_stored // _TK, n_out // tn
    tm = 8
    x = jnp.pad(x, ((0, tm - m), (0, 0)))
    xb = x.astype(jnp.bfloat16)
    xe, xo = xb[:, 0::2], xb[:, 1::2]
    hk = _TK // 2
    out = pl.pallas_call(
        functools.partial(kernel, n_k=n_k, **kw),
        grid=(1, n_n, n_k),
        in_specs=[
            pl.BlockSpec((tm, hk), lambda mi, n, k: (mi, k)),
            pl.BlockSpec((tm, hk), lambda mi, n, k: (mi, k)),
            pl.BlockSpec((hk, tn), lambda mi, n, k: (k, n)),
            pl.BlockSpec((_TK // NF4_BLOCK, tn), lambda mi, n, k: (k, n)),
            pl.BlockSpec((8, 128), lambda mi, n, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda mi, n, k: (mi, n)),
        out_shape=jax.ShapeDtypeStruct((tm, n_out), x.dtype),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(xe, xo, q.data, q.scales, Q._decode_table(q.kind))
    return out[:m]


# --------------------------------------------------------------------------- timing


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
        print(f"{self.label:34s} {sec * 1e3:8.3f} ms  {gbs:7.1f} GB/s  ({100 * gbs / 819:5.1f}% HBM)",
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

    # correctness spot-check of the redesigns vs the XLA dequant path
    ref_n = (x.astype(jnp.bfloat16) @ Q.dequantize(qn, jnp.bfloat16)).astype(jnp.float32)
    ref_i = (x.astype(jnp.bfloat16) @ Q.dequantize(qi, jnp.bfloat16)).astype(jnp.float32)
    got4 = run_variant(x, qn, kernel_blockwise, mode="nf4").astype(jnp.float32)
    got5 = run_variant(x, qi, kernel_blockwise, mode="int4").astype(jnp.float32)
    for name, got, ref in (("s4/nf4", got4, ref_n), ("s5/int4", got5, ref_i)):
        err = float(jnp.max(jnp.abs(got - ref)) / (jnp.max(jnp.abs(ref)) + 1e-9))
        print(f"# {name} rel max err vs XLA dequant: {err:.2e}")

    mk = lambda kern, **kw: (lambda v, d, s: run_variant(
        v, Q.QuantizedLinear(kw.pop("kind", "nf4"), d, s, HIDDEN, GU), kern, **kw))

    probes = [
        Probe("bf16 dense (ceiling)", HIDDEN * GU * 2,
              lambda v, d, s: v @ d, (x, jax.random.normal(key, (HIDDEN, GU), jnp.bfloat16), qn.scales)),
        Probe("s0 dma+dot", qn.nbytes, mk(kernel_stage, stage=0), (x, qn.data, qn.scales)),
        Probe("s1 +mask/shift", qn.nbytes, mk(kernel_stage, stage=1), (x, qn.data, qn.scales)),
        Probe("s2 +gather", qn.nbytes, mk(kernel_stage, stage=2), (x, qn.data, qn.scales)),
        Probe("s3 +scales (current)", qn.nbytes, mk(kernel_stage, stage=3), (x, qn.data, qn.scales)),
        Probe("s4 blockwise nf4", qn.nbytes, mk(kernel_blockwise, mode="nf4"), (x, qn.data, qn.scales)),
        Probe("s5 blockwise int4 no-gather", qi.nbytes, mk(kernel_blockwise, mode="int4", kind="int4"), (x, qi.data, qi.scales)),
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
