"""Ablate the backend decode step: wrapper vs jitted graph vs raw kernel chain.

The e2e serving step realized ~55% of the bandwidth the dedicated kernel
bench proved (July record, deleted). This isolates where the loss is:

  A  backend.inference_step (numpy in, the serving wrapper)   <- production
  B  backend._inference_step_fn (pre-staged device args)      <- jitted graph
  C  bare stacked-kernel matmul chain (no attention/norms)    <- kernel bound

All probes interleaved in one run; min over passes. Chip required
(``chiprun -- python benchmarks/ablate_backend_step.py``).

Usage: [QUANT_KIND=int4] [N_BLOCKS=4] python benchmarks/ablate_backend_step.py
"""

import gc
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

KIND = os.environ.get("QUANT_KIND", "int4")
N_BLOCKS = int(os.environ.get("N_BLOCKS", "4"))


def hard_sync(x):
    np.asarray(jax.device_get(jnp.ravel(x)[:1]))


def main():
    assert jax.default_backend() == "tpu"
    from petals_tpu.models.registry import get_family
    from petals_tpu.server.backend import TransformerBackend
    from petals_tpu.server.memory_cache import MemoryCache
    from petals_tpu.ops import quant as Q
    from bench import llama70b_cfg, random_params, params_bytes

    cfg = llama70b_cfg(N_BLOCKS)
    quant = None if KIND in ("bf16", "none") else KIND
    params = random_params(cfg, N_BLOCKS, jnp.bfloat16, quant=quant)
    wbytes = params_bytes(params)
    print(f"# {N_BLOCKS} blocks {KIND}: {wbytes/2**30:.2f} GiB weights")

    backend = TransformerBackend(
        get_family("llama"), cfg, params, first_block=0, n_blocks=N_BLOCKS,
        memory_cache=MemoryCache(None), compute_dtype=jnp.bfloat16,
    )
    kd, vd = backend.cache_descriptors(1, 256, 0, N_BLOCKS)
    kv = (kd.make_zeros(), vd.make_zeros())
    rng = np.random.RandomState(0)
    step_h = rng.randn(1, 1, cfg.hidden_size).astype(np.float32) * 0.02
    _, kv = backend.inference_step(
        rng.randn(1, 128, cfg.hidden_size).astype(np.float32) * 0.02, kv, 0
    )
    pos = 128
    for _ in range(3):
        out, kv = backend.inference_step(step_h, kv, pos)
        pos += 1
    hard_sync(out)

    # --- B setup: pre-staged device args, direct jit calls
    span_params = backend.params_for(None)
    hidden_dev = jnp.asarray(step_h, jnp.bfloat16)
    prompts_dev = jnp.zeros((N_BLOCKS, 1, 0, cfg.hidden_size), jnp.bfloat16)
    hypo_dev = jnp.zeros((1,), jnp.int32)
    nv_dev = jnp.asarray(1, jnp.int32)

    def run_B(kv, pos, n):
        k_stack, v_stack = kv
        for i in range(n):
            out, k_stack, v_stack = backend._inference_step_fn(
                span_params, k_stack, v_stack, hidden_dev,
                jnp.asarray(pos + i, jnp.int32), nv_dev,
                jnp.asarray(pos + i + 1, jnp.int32), prompts_dev, hypo_dev,
                with_prompts=False, with_hypo=False, padded=False,
            )
        return out, (k_stack, v_stack)

    out, kv = run_B(kv, pos, 2)
    pos += 2
    hard_sync(out)

    # --- C setup: bare stacked matmul chain (fused 70B shapes). Weights ride
    # as jit ARGUMENTS — a closure capture embeds the whole span as XLA
    # constants of the program.
    H, QKV, GU, INTER = cfg.hidden_size, 10240, 57344, cfg.intermediate_size
    import functools
    if quant:
        chain_ws = {n: span_params[n] for n in ("wqkv", "wo", "wgu", "wd")}

        @functools.partial(jax.jit, static_argnames=('n',))
        def chain_C(v, leaves, n):
            def body(v, idx):
                def sq(q):
                    return Q.StackedQuantLinear(
                        q.kind, q.data, q.scales, idx, q.in_features, q.out_features
                    )
                a = Q.packed4_matmul_pallas_stacked(v, sq(leaves["wqkv"]))
                v = Q.packed4_matmul_pallas_stacked(a[:, :H], sq(leaves["wo"]))
                b = Q.packed4_matmul_pallas_stacked(v, sq(leaves["wgu"]))
                v = Q.packed4_matmul_pallas_stacked(b[:, :INTER], sq(leaves["wd"]))
                return v * 1e-2, None
            for _ in range(n):
                v, _ = jax.lax.scan(body, v, jnp.arange(N_BLOCKS, dtype=jnp.int32))
            return v
    else:
        chain_ws = tuple(span_params[n] for n in ("wq", "wo", "wg", "wd"))

        @functools.partial(jax.jit, static_argnames=('n',))
        def chain_C(v, xs, n):
            def body(v, ws):
                wq, wo, wg, wd = ws
                a = v @ wq.reshape(H, -1)
                v = a[:, :H] @ wo
                b = (v @ wg)[:, :INTER]
                v = b @ wd
                return v * 1e-2, None
            for _ in range(n):
                v, _ = jax.lax.scan(body, v, xs)
            return v

    x1 = jnp.asarray(rng.randn(1, H).astype(np.float32) * 0.1, jnp.bfloat16)
    cn1, cn2 = 1, 3
    # compile
    print("# compiling C...", flush=True)
    hard_sync(chain_C(x1, chain_ws, n=cn1)); hard_sync(chain_C(x1, chain_ws, n=cn2))
    print("# C compiled", flush=True)

    tA = tB = float("inf")
    tC = {cn1: float("inf"), cn2: float("inf")}
    STEPS = 10
    for p in range(4):
        print(f"# pass {p}", flush=True)
        t0 = time.perf_counter()
        for _ in range(STEPS):
            out, kv = backend.inference_step(step_h, kv, pos)
            pos += 1
        hard_sync(out)
        tA = min(tA, (time.perf_counter() - t0) / STEPS)

        t0 = time.perf_counter()
        out, kv = run_B(kv, pos, STEPS)
        pos += STEPS
        hard_sync(out)
        tB = min(tB, (time.perf_counter() - t0) / STEPS)

        for n in (cn1, cn2):
            t0 = time.perf_counter()
            o = chain_C(x1, chain_ws, n=n)
            hard_sync(o)
            tC[n] = min(tC[n], time.perf_counter() - t0)

    c_slope = (tC[cn2] - tC[cn1]) / (cn2 - cn1)
    for label, t in (("A inference_step (numpy wrapper)", tA),
                     ("B _inference_step_fn (device args)", tB),
                     ("C bare matmul chain (slope)", c_slope)):
        gbs = wbytes / t / 1e9
        print(f"{label:42s} {t*1e3/N_BLOCKS:7.3f} ms/blk  {gbs:6.1f} GB/s ({100*gbs/819:4.1f}% HBM)")


if __name__ == "__main__":
    from petals_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    main()
