"""What a step has to move and compute, from shapes alone. Kept with the
benchmark so that no PR that claims a gain can change the yardstick.

All functions take the published ``config.json`` keys (``hf``) of a family.
Bytes are bf16 (2 per parameter and per cached scalar)."""

from __future__ import annotations

import json
from pathlib import Path

BYTES = 2
PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks_for(device_kind: str) -> dict:
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"device kind {device_kind!r} is not in {PEAKS_FILE.name}: add it with its source")
    return table[device_kind]


def layer_params(family: str, hf: dict) -> dict:
    """Matrix parameters of one layer, from the family's own
    ``perf/reference/<family>.py`` (found by name, so a new family adds a file
    and edits none): ``attn`` (projections), ``dense`` (run for every token)
    and ``expert`` (one expert), plus ``experts`` and ``top_k``, and the
    attention's shape: ``hidden``, ``q_heads``, ``kv_heads``, ``head_dim``.
    Norm vectors are left out (a few thousand scalars)."""
    from perf.reference import family_of

    return family_of(family).layer_params(hf)


def layer_param_count(family: str, hf: dict) -> int:
    p = layer_params(family, hf)
    return p["attn"] + p["dense"] + p["expert"] * p["experts"]


def kv_bytes_per_token_layer(family: str, hf: dict) -> int:
    p = layer_params(family, hf)
    return 2 * p["kv_heads"] * p["head_dim"] * BYTES


def step_cost(family: str, hf: dict, n_layers: int, *, decode_tokens: float, prefill_tokens: float,
              context_tokens: float, prefill_context: float = 0.0) -> dict:
    """Least work of one batched step over ``n_layers``: ``decode_tokens``
    lanes advance one position each over ``context_tokens`` cached positions
    in total, and ``prefill_tokens`` prompt positions ride along, attending
    over ``prefill_context`` positions on average. Weights are read once a
    step. A sparse layer reads every expert (any batch of a few tokens routes
    to all eight) and computes ``top_k`` experts a token."""
    p = layer_params(family, hf)
    h, hq, d = p["hidden"], p["q_heads"], p["head_dim"]
    tokens = decode_tokens + prefill_tokens
    active = p["attn"] + p["dense"] + p["expert"] * p["top_k"]
    weight_bytes = (p["attn"] + p["dense"] + p["expert"] * p["experts"]) * BYTES
    kv = 2 * p["kv_heads"] * d * BYTES
    attn_flops = 4 * hq * d * (context_tokens + prefill_tokens * prefill_context)
    flops = n_layers * (2 * active * tokens + attn_flops)
    nbytes = n_layers * (weight_bytes + kv * (context_tokens + tokens) + 2 * h * BYTES * tokens)
    return {"flops": flops, "bytes": nbytes}


def least_seconds(cost: dict, peaks: dict) -> tuple:
    """(seconds, which bound) of the roofline: the larger of the two floors."""
    t_mem = cost["bytes"] / peaks["hbm_bytes_per_s"]
    t_flop = cost["flops"] / peaks["bf16_flops_per_s"]
    return (t_mem, "bandwidth") if t_mem >= t_flop else (t_flop, "compute")
