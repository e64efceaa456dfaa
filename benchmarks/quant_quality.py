"""Quantization quality evaluation: bf16 vs int8 vs NF4 vs int4
(VERDICT r3 #4 — quantify the quality cost of each serving format so the
default is chosen on evidence, matching the confidence the reference gets
for free from battle-tested bitsandbytes formats, reference
utils/convert_block.py:87-111).

Zero-egress note: no trained 7B checkpoint is reachable in this environment,
so the evaluation has two transferable tiers plus one end-to-end tier:

1. WEIGHT-SPACE error at exact 7B shapes [4096, 11008] over three weight
   distributions — gaussian, heavy-tailed (student-t), and gaussian with
   outlier input channels (the regime trained transformers actually live in,
   per the LLM.int8 observations). Relative MSE is distribution-dependent but
   FORMAT ORDERING and magnitudes transfer to trained weights.
2. ACTIVATION-SPACE error: || x @ w - x @ dq(q(w)) || / || x @ w || with
   activation outliers aligned to the weight outlier channels (worst case).
3. MODEL-LEVEL: greedy-token divergence + logit error of a tiny llama served
   through convert_block with each format vs f32. Tiny random models OVERSTATE
   divergence (near-uniform logits flip argmax on tiny perturbations), so this
   is a comparative tier, not an absolute one.
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SHAPE_7B_MLP = (4096, 11008)


def _weight_sets(shape, seed=0):
    rng = np.random.RandomState(seed)
    rows, cols = shape
    w_gauss = rng.randn(rows, cols).astype(np.float32) * 0.02
    w_heavy = (rng.standard_t(df=4, size=shape) * 0.02).astype(np.float32)
    w_outlier = w_gauss.copy()
    outlier_rows = rng.choice(rows, size=max(rows // 512, 1), replace=False)
    w_outlier[outlier_rows] *= 20.0  # outlier input channels (LLM.int8 regime)
    sets = {"gaussian": w_gauss, "heavy_tailed": w_heavy, "outlier_channels": w_outlier}
    return sets, outlier_rows


def _quant_roundtrip(w32, kind):
    import jax.numpy as jnp

    from petals_tpu.ops.quant import dequantize, quantize

    w = jnp.asarray(w32, jnp.bfloat16)
    if kind == "bf16":
        return np.asarray(w.astype(jnp.float32))
    q = quantize(w, kind)
    return np.asarray(dequantize(q, jnp.float32))


def weight_space_table(kinds=("bf16", "int8", "nf4", "nf4a", "nf4a+o", "int4"), shape=SHAPE_7B_MLP) -> dict:
    table = {}
    sets, _ = _weight_sets(shape)
    for dist, w in sets.items():
        row = {}
        wn = float(np.square(w).mean())
        for kind in kinds:
            dq = _quant_roundtrip(w, kind)
            err = dq - w
            rel_mse = float(np.square(err).mean()) / wn
            row[kind] = {
                "rel_mse": round(rel_mse, 8),
                "snr_db": round(10 * np.log10(1.0 / max(rel_mse, 1e-12)), 1),
                "max_abs_err": round(float(np.abs(err).max()), 5),
            }
        table[dist] = row
    return table


def activation_space_table(
    kinds=("bf16", "int8", "nf4", "nf4a", "nf4a+o", "int4"), seed=1, shape=SHAPE_7B_MLP
) -> dict:
    """Output error of x @ w per format over outlier-channel weights, with
    activation outliers either ALIGNED to the weight outlier channels or on
    disjoint channels. (Empirically the aligned case is the more benign one
    for RELATIVE output error — the amplified channels dominate the output
    and blockwise scales represent them relatively well — so both are
    reported and the table's headline is the worse of the two.)"""
    rng = np.random.RandomState(seed)
    rows, cols = shape
    sets, outlier_rows = _weight_sets(shape, seed=0)
    w = sets["outlier_channels"]
    other_rows = np.setdiff1d(np.arange(rows), outlier_rows)[: len(outlier_rows)]
    out = {}
    for case, amp_rows in (("aligned", outlier_rows), ("disjoint", other_rows)):
        x = rng.randn(64, rows).astype(np.float32)
        x[:, amp_rows] *= 8.0
        y_ref = x @ w
        yn = float(np.square(y_ref).mean())
        case_out = {}
        for kind in kinds:
            dq = _quant_roundtrip(w, kind)
            y = x @ dq
            rel = float(np.square(y - y_ref).mean()) / yn
            case_out[kind] = {
                "rel_out_mse": round(rel, 8),
                "out_snr_db": round(10 * np.log10(1.0 / max(rel, 1e-12)), 1),
            }
        out[case] = case_out
    out["worst_case"] = {
        kind: min(
            (out["aligned"][kind], out["disjoint"][kind]),
            key=lambda r: r["out_snr_db"],
        )
        for kind in kinds
    }
    return out


def model_level_table(kinds=("int8", "nf4", "nf4a", "int4"), steps=12, prompts=4) -> dict:
    """Greedy divergence + logit error of a tiny llama per format vs f32.
    Comparative tier only (random tiny models overstate divergence)."""
    import tempfile

    import jax.numpy as jnp
    import torch

    from tests.utils import make_tiny_llama

    from petals_tpu.server.from_pretrained import get_block_config, load_block_params
    from petals_tpu.utils.convert_block import convert_block_params

    tmp = tempfile.mkdtemp()
    path = make_tiny_llama(tmp, n_layers=4)
    family, cfg = get_block_config(path)
    blocks = [
        load_block_params(path, i, dtype=jnp.float32, family=family, cfg=cfg)
        for i in range(4)
    ]

    from transformers import AutoModelForCausalLM

    hf = AutoModelForCausalLM.from_pretrained(path, dtype=torch.float32).eval()
    embed = hf.model.embed_tokens.weight.detach().numpy()
    norm_w = hf.model.norm.weight.detach().numpy()
    head = hf.lm_head.weight.detach().numpy()

    def run_chain(params_list, ids):
        h = embed[ids][None].astype(np.float32)
        h = jnp.asarray(h)
        for p in params_list:
            h, _ = family.block_apply(p, h, None, 0, cfg)
        hf32 = np.asarray(h, np.float32)
        normed = hf32 / np.sqrt(np.square(hf32).mean(-1, keepdims=True) + 1e-6) * norm_w
        return normed @ head.T  # [1, seq, vocab]

    rng = np.random.RandomState(0)
    f32_blocks = [{k: jnp.asarray(v, jnp.float32) for k, v in b.items()} for b in blocks]
    out = {}
    for kind in kinds:
        qblocks = [convert_block_params(dict(b), "llama", kind, fuse=False) for b in blocks]
        diverged = total = 0
        logit_errs = []
        for p in range(prompts):
            ids = list(rng.randint(1, 120, size=5))
            for _ in range(steps):
                ref_logits = run_chain(f32_blocks, ids)[0, -1]
                q_logits = run_chain(qblocks, ids)[0, -1]
                logit_errs.append(float(np.abs(q_logits - ref_logits).mean()))
                ref_tok = int(ref_logits.argmax())
                q_tok = int(q_logits.argmax())
                total += 1
                diverged += int(ref_tok != q_tok)
                ids.append(ref_tok)  # follow the reference trajectory
        out[kind] = {
            "greedy_divergence_rate": round(diverged / total, 3),
            "mean_abs_logit_err": round(float(np.mean(logit_errs)), 5),
        }
    return out


def quality_report(include_model_tier: bool = True) -> dict:
    report = {
        "weight_space_7b_shapes": weight_space_table(),
        "activation_space_7b_shapes": activation_space_table(),
        "notes": (
            "No trained checkpoint reachable (zero egress): weight/activation "
            "tiers use 7B-shaped synthetic distributions incl. outlier "
            "channels; model tier is comparative (tiny random models "
            "overstate divergence)."
        ),
        # The evidence-based default (2026-07-30 run): NF4A's cubic-fitted
        # levels match or beat NF4's weight-space SNR on every tested
        # distribution (gaussian/heavy-tailed/outlier-channel) while its
        # decode is pure arithmetic — no VPU gather, so the fused kernel
        # runs in int4's bandwidth class, not NF4's ~110 GB/s gather-bound
        # class. That dissolves the round-4 quality-vs-bandwidth tension:
        # the default 4-bit format is no longer a tradeoff. int4 stays as
        # the uniform-level option; int8 is near-lossless when memory
        # allows. (On-chip GB/s for nf4a: bench.py row decode_70b_nf4a.)
        "serving_default": {
            "4bit": "nf4a",
            "outlier_option": "nf4a+o",  # +0.25 bits, ~+5-6 dB in the outlier-channel regime
            "uniform_option": "int4",
            "quality_option": "int8",
        },
    }
    if include_model_tier:
        report["model_level_tiny_llama"] = model_level_table()
    return report


if __name__ == "__main__":
    from petals_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    import os

    # default to CPU: the table is arithmetic, not a chip measurement. The
    # on-chip path is bench.py's quant_quality row calling quality_report().
    if os.environ.get("PTU_QUALITY_ON_TPU") != "1":
        import jax

        jax.config.update("jax_platforms", "cpu")
    print(json.dumps(quality_report(), indent=2))
