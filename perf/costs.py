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


def layer_params(family: str, hf: dict, layer: int = 0) -> dict:
    """Matrix parameters of layer ``layer``, from the family's own
    ``perf/reference/<family>.py`` (found by name, so a new family adds a file
    and edits none): ``attn`` (projections), ``dense`` (run for every token)
    and ``expert`` (one expert), plus ``experts`` (held here) and ``top_k``,
    and the attention's shape: ``hidden``, ``q_heads``, ``kv_heads``,
    ``head_dim``. Optional: ``experts_routed`` (the router's width, where a
    server holds a share of the experts; default ``experts``) and ``window``
    (the cached positions a windowed layer attends to; default all of them).
    A family whose layers are not all alike defines ``layer_kinds(hf)`` and
    its ``layer_params`` takes the layer's kind as a further argument. Norm
    vectors are left out (a few thousand scalars)."""
    from perf.reference import family_of, kinds_of

    kinds = kinds_of(family, hf)
    return family_of(family).layer_params(hf, *(kinds[layer] if kinds else ()))


def layer_param_count(family: str, hf: dict, layer: int = 0) -> int:
    p = layer_params(family, hf, layer)
    return p["attn"] + p["dense"] + p["expert"] * p["experts"]


def kv_bytes_per_token_layer(family: str, hf: dict, layer: int = 0) -> int:
    p = layer_params(family, hf, layer)
    return 2 * p["kv_heads"] * p["head_dim"] * BYTES


def experts_reached(p: dict, tokens: float) -> float:
    """Of the experts a layer holds, how many ``tokens`` tokens reach when
    each picks ``top_k`` of ``experts_routed`` alike: the expected count."""
    if not p["experts"]:
        return 0.0
    return p["experts"] * (1.0 - (1.0 - p["top_k"] / p.get("experts_routed", p["experts"])) ** tokens)


def step_cost(family: str, hf: dict, n_layers: int, *, decode_tokens: float, prefill_tokens: float,
              context_tokens: float, prefill_context: float = 0.0, first_block: int = 0) -> dict:
    """Least work of one batched step over the ``n_layers`` layers from
    ``first_block`` on, summed layer by layer: ``decode_tokens`` lanes advance
    one position each over ``context_tokens`` cached positions in total, and
    ``prefill_tokens`` prompt positions ride along, attending over
    ``prefill_context`` positions on average. Weights are read once a step.
    An expert layer reads the experts its tokens reach (``experts_reached``)
    and computes, a token, the share of its ``top_k`` that is held here. A
    windowed layer reads, a lane, the lesser of the lanes' mean context and
    its window."""
    tokens = decode_tokens + prefill_tokens
    lane_context = context_tokens / decode_tokens if decode_tokens else 0.0
    flops = nbytes = 0
    for layer in range(first_block, first_block + n_layers):
        p = layer_params(family, hf, layer)
        h, hq, d = p["hidden"], p["q_heads"], p["head_dim"]
        window = p.get("window") or float("inf")
        cached = context_tokens if lane_context <= window else decode_tokens * window
        held = p["experts"] / p.get("experts_routed", p["experts"]) if p["experts"] else 0
        active = p["attn"] + p["dense"] + p["expert"] * p["top_k"] * held
        weight_bytes = (p["attn"] + p["dense"] + p["expert"] * experts_reached(p, tokens)) * BYTES
        kv = 2 * p["kv_heads"] * d * BYTES
        flops += 2 * active * tokens + 4 * hq * d * (cached + prefill_tokens * min(prefill_context, window))
        nbytes += weight_bytes + kv * (cached + tokens) + 2 * h * BYTES * tokens
    return {"flops": flops, "bytes": nbytes}


def least_seconds(cost: dict, peaks: dict) -> tuple:
    """(seconds, which bound) of the roofline: the larger of the two floors."""
    t_mem = cost["bytes"] / peaks["hbm_bytes_per_s"]
    t_flop = cost["flops"] / peaks["bf16_flops_per_s"]
    return (t_mem, "bandwidth") if t_mem >= t_flop else (t_flop, "compute")
