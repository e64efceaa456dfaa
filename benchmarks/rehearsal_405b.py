"""405B rehearsal: placement math + single-stream projection for the north
star (BASELINE.json: Llama-3.1-405B on a v5e-64 private swarm, >= 6 tok/s).

No 405B weights exist on this machine, so the rehearsal checks everything
short of them, end-to-end with the REAL production code paths:

- sizing: per-block bytes at each quant kind via the server's own estimator
  (server/block_utils.py, reference block_utils.py:22-53);
- auto-placement: 16 span-servers (one per v5e-64 host: 4 chips, tp=4) join a
  simulated DHT view one by one, each choosing its span with the production
  ``choose_best_start`` / ``choose_num_blocks`` (reference server.py:403-418),
  then the rebalance predicate must report a settled swarm;
- KV budget: bytes/token from the cache layout, checked against per-host HBM
  after weights;
- projection: measured per-block weight-stream bandwidth (a bench.py details
  dict from a chip run, e.g. chiprun_out/bench_details.json) -> per-block
  decode ms at 405B shapes -> chain latency over the spans -> single-stream
  tok/s. Without one (the July BENCH_DETAILS.json record is deleted) only the
  placement table and the gate scenarios are reported.

Run standalone: ``python benchmarks/rehearsal_405b.py [details.json]``.
"""

from __future__ import annotations

import json
import math
import os
import sys
from typing import Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

V5E_CHIP_HBM = 16 * 2**30
CHIPS_PER_HOST = 4
N_HOSTS = 16
KV_BUDGET_TOKENS = 8192  # per-span KV allocation the placement must absorb
# LAN hop between hosts in the same pod's DCN: server->server push latency.
# This is an ASSUMPTION (not measured on the current chip's host);
# the table reports sensitivity to it. When the bench's chain_hop row exists
# (2 real span servers chained through the RPC stack at hidden=16384), the
# measured per-hop SOFTWARE cost replaces the software part of this guess and
# only the wire RTT below stays assumed.
HOP_MS_LAN = 2.0
WIRE_RTT_MS_DCN = 0.5  # assumed intra-pod DCN round trip added to measured hops
# every 4-bit serving option, serving-default first (one constant so a new
# quant kind can't end up placed-but-never-projected or vice versa)
QUANTS = ("nf4a", "nf4a+o", "int4", "nf4")


def llama405b_cfg(n_layers: int = 126):
    """The 405B block shape — single source of truth (bench.py's chain-hop
    measurement uses the same constants with a shallow layer stack)."""
    from petals_tpu.models.llama.config import LlamaBlockConfig

    return LlamaBlockConfig(
        hidden_size=16384,
        num_attention_heads=128,
        num_key_value_heads=8,
        head_dim=128,
        intermediate_size=53248,
        num_hidden_layers=n_layers,
        rms_norm_eps=1e-5,
        vocab_size=128256,
    )


def kv_bytes_per_token_per_block(cfg, cache_dtype_bytes: int = 2) -> int:
    return 2 * cfg.num_key_value_heads * cfg.head_dim * cache_dtype_bytes


def placement_rehearsal(quant: str = "int4") -> Dict:
    """Join 16 host-servers into an empty swarm with the production placement
    code; return the settled layout + memory accounting."""
    from petals_tpu.data_structures import (
        RemoteModuleInfo,
        ServerInfo,
        ServerState,
    )
    from petals_tpu.models.registry import get_family
    from petals_tpu.server.block_selection import (
        choose_best_start,
        compute_throughputs,
        should_choose_other_blocks,
    )
    from petals_tpu.server.block_utils import (
        choose_num_blocks,
        estimated_block_size_bytes,
    )

    family = get_family("llama")
    cfg = llama405b_cfg()
    n_layers = cfg.num_hidden_layers
    block_bytes = estimated_block_size_bytes(family, cfg, quant)
    host_hbm = V5E_CHIP_HBM * CHIPS_PER_HOST
    kv_bytes = KV_BUDGET_TOKENS * kv_bytes_per_token_per_block(cfg)

    # how many 405B blocks one 4-chip host can serve (tp=4 shards each block,
    # so the whole host's HBM is the budget) alongside the KV allocation
    n_per_host = choose_num_blocks(
        family, cfg, quant_type=quant,
        attn_cache_bytes=kv_bytes * 1,  # refined below once n is known
        memory_limit_bytes=host_hbm,
    )
    # KV budget scales with the span length; fix-point once
    n_per_host = choose_num_blocks(
        family, cfg, quant_type=quant,
        attn_cache_bytes=kv_bytes * n_per_host,
        memory_limit_bytes=host_hbm,
    )

    # sequential joins against the accumulating DHT view
    module_infos: List[Optional[RemoteModuleInfo]] = [
        RemoteModuleInfo(uid=f"m.{i}", servers={}) for i in range(n_layers)
    ]
    spans = {}
    for h in range(N_HOSTS):
        peer = f"host-{h:02d}".encode()
        throughputs = compute_throughputs(module_infos)
        n = min(n_per_host, n_layers)
        start = choose_best_start(throughputs, n)
        spans[peer] = (start, start + n)
        info = ServerInfo(state=ServerState.ONLINE, throughput=1.0)
        for i in range(start, start + n):
            module_infos[i].servers[peer] = info

    # settled? every server runs the production rebalance predicate
    movers = [
        peer
        for peer in spans
        if should_choose_other_blocks(peer, module_infos, spans[peer][1] - spans[peer][0])
    ]

    coverage = [0] * n_layers
    for start, end in spans.values():
        for i in range(start, end):
            coverage[i] += 1
    weights_bytes = n_per_host * block_bytes
    return {
        "quant": quant,
        "block_gib": round(block_bytes / 2**30, 3),
        "total_model_gib": round(block_bytes * n_layers / 2**30, 1),
        "n_per_host": n_per_host,
        "host_weights_gib": round(weights_bytes / 2**30, 1),
        "host_kv_gib": round(kv_bytes * n_per_host / 2**30, 2),
        "host_hbm_gib": round(host_hbm / 2**30, 1),
        "hosts": N_HOSTS,
        "full_coverage": min(coverage) >= 1,
        "min_replication": min(coverage),
        "max_replication": max(coverage),
        "movers_after_join": len(movers),
        "spans": sorted((s, e) for s, e in spans.values()),
    }


def project_single_stream(
    weight_stream_gb_s: float,
    *,
    quant: str = "int4",
    n_per_span: Optional[int] = None,
    hop_ms: float = HOP_MS_LAN,
    device_overhead_frac: float = 0.0,
) -> Dict:
    """Single-stream tok/s from a measured per-chip weight-stream bandwidth.

    Decode is weight-bandwidth-bound: each token must stream every block's
    weights once across the pod. With tp=4 inside a host, a span's weights
    split over 4 chips, so the HOST streams at ~4x one chip's bandwidth.
    ``device_overhead_frac`` models the measured e2e-vs-kernel gap (0.0 =
    kernel-rate serving; BENCH's e2e row supplies the real number).
    """
    from petals_tpu.models.registry import get_family
    from petals_tpu.server.block_utils import estimated_block_size_bytes

    cfg = llama405b_cfg()
    family = get_family("llama")
    block_bytes = estimated_block_size_bytes(family, cfg, quant)
    if n_per_span is None:
        n_per_span = placement_rehearsal(quant)["n_per_host"]
    n_spans = math.ceil(cfg.num_hidden_layers / n_per_span)

    host_gb_s = weight_stream_gb_s * CHIPS_PER_HOST  # tp=4: bytes split 4-way
    per_block_ms = block_bytes / (host_gb_s * 1e9) * 1e3
    per_block_ms *= 1.0 + device_overhead_frac
    compute_ms = cfg.num_hidden_layers * per_block_ms
    network_ms = n_spans * hop_ms  # client->s1 + (n_spans-1) pushes ~= n hops
    step_ms = compute_ms + network_ms
    return {
        "quant": quant,
        "chip_gb_s": round(weight_stream_gb_s, 1),
        "n_spans": n_spans,
        "blocks_per_span": n_per_span,
        "per_block_ms": round(per_block_ms, 3),
        "compute_ms": round(compute_ms, 1),
        "network_ms": round(network_ms, 1),
        "step_ms": round(step_ms, 1),
        "tok_s": round(1000.0 / step_ms, 2),
        "hop_ms": hop_ms,
        "hop_source": "assumed",  # callers override when the hop is measured
        "device_overhead_frac": device_overhead_frac,
    }


def rehearsal_report(bench_details: Optional[dict] = None) -> Dict:
    """The driver-visible artifact: placement + projections, using measured
    bandwidths when a bench.py details dict is given."""
    report = {"placement": {q: placement_rehearsal(q) for q in QUANTS}}

    measured = {}
    if bench_details:
        for q in QUANTS:
            # bench row keys are json-identifier-safe: '+' becomes '_'
            row = bench_details.get(f"decode_70b_{q}".replace("+", "_")) or {}
            if row.get("weight_stream_gb_s"):
                measured[q] = float(row["weight_stream_gb_s"])
    # Device overhead is NOT multiplied on top of the measured rates: the
    # decode_70b rows' weight_stream_gb_s divides weights by the FULL block
    # step (attention, norms, rope, KV update, per-matmul kernel-call costs
    # all included), so block extras are already inside the rate. Earlier
    # rounds additionally multiplied a 7B-e2e-derived device_overhead_frac
    # (~0.46) on top — double-counting the extras, and at the wrong scale:
    # 405B blocks run hidden 16384 vs the 70B rows' 8192, so per-block
    # extras amortize over ~4x the weight bytes and the 70B full-row rate
    # UNDERSTATES the 405B rate. The projection therefore carries the
    # measured-row rate as-is (conservative) and accounts per-span software
    # cost once per hop via the measured chain_hop row below.
    overhead_frac = 0.0

    n_int4 = report["placement"]["int4"]["n_per_host"]
    n_by_quant = {q: report["placement"][q]["n_per_host"] for q in QUANTS}

    # measured per-hop software cost (bench chain_hop row: real RPC chain at
    # hidden=16384) + an assumed DCN wire RTT — replaces the 2.0 ms guess
    hop_ms = HOP_MS_LAN
    hop_source = "assumed"
    chain = (bench_details or {}).get("chain_hop_405b_shapes") or {}
    if chain.get("hop_software_ms") is not None:
        # the chain row derives software cost as a difference of two similar
        # measurements, so small values are noise-limited: hold a 1 ms floor
        # rather than projecting near-free hops
        hop_sw = max(float(chain["hop_software_ms"]), 1.0)
        hop_ms = hop_sw + WIRE_RTT_MS_DCN
        floored = (
            " (floored at 1.0 vs measurement noise)"
            if hop_sw != float(chain["hop_software_ms"]) else ""
        )
        hop_source = (
            f"measured software {chain['hop_software_ms']} ms{floored} "
            f"+ assumed wire {WIRE_RTT_MS_DCN} ms"
        )

    rows = []
    # nf4a first: it is the serving default the north-star claim rides on
    # (nf4a+o: the quality option at 4.5 bits — its span is a block or two
    # shorter per host, the projection shows what that costs)
    for q in QUANTS:
        if q in measured:
            row = project_single_stream(
                measured[q], quant=q, n_per_span=n_by_quant[q],
                hop_ms=hop_ms,
                device_overhead_frac=round(overhead_frac, 3),
            )
            row["hop_source"] = hop_source
            rows.append(row)
    # the gate scenarios: VERDICT's 400 GB/s bar and the bf16-class ceiling
    for gate_gbs in (400.0, 790.0):
        row = project_single_stream(gate_gbs, quant="int4", n_per_span=n_int4, hop_ms=hop_ms)
        row["hop_source"] = hop_source
        rows.append(row)
    report["projection"] = rows
    report["north_star"] = {
        "target_tok_s": 6.0,
        "hop_ms": round(hop_ms, 3),
        "hop_source": hop_source,
        "min_chip_gb_s_for_target": round(
            _solve_required_gbs(6.0, n_per_span=n_int4, hop_ms=hop_ms), 1
        ),
    }
    return report


def _solve_required_gbs(
    target_tok_s: float, quant: str = "int4", n_per_span: Optional[int] = None,
    hop_ms: float = HOP_MS_LAN,
) -> float:
    lo, hi = 10.0, 2000.0
    for _ in range(60):
        mid = (lo + hi) / 2
        if project_single_stream(
            mid, quant=quant, n_per_span=n_per_span, hop_ms=hop_ms
        )["tok_s"] >= target_tok_s:
            hi = mid
        else:
            lo = mid
    return hi


if __name__ == "__main__":
    details = None
    if len(sys.argv) > 1:
        with open(sys.argv[1]) as f:
            details = json.load(f)
    print(json.dumps(rehearsal_report(details), indent=2))
