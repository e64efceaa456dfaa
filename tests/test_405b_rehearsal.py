"""405B rehearsal (VERDICT r2 next-step #4): the production placement / sizing
code must place a 126-layer 405B-shaped swarm on 16 v5e hosts with full
coverage and a settled layout, and the projection arithmetic must reproduce
the north-star gate (BASELINE.json >= 6 tok/s single-stream)."""

import math

from benchmarks.rehearsal_405b import (
    N_HOSTS,
    kv_bytes_per_token_per_block,
    llama405b_cfg,
    placement_rehearsal,
    project_single_stream,
    rehearsal_report,
)


def test_placement_covers_model_and_settles():
    for quant in ("int4", "nf4"):
        p = placement_rehearsal(quant)
        assert p["full_coverage"], p
        assert p["min_replication"] >= 1
        assert p["movers_after_join"] == 0, (
            "production rebalance predicate wants to move right after join: "
            "the join-time placement contradicts the rebalancer"
        )
        # per-host memory accounting: weights + KV fit the 4-chip HBM with the
        # autograd reserve honoured by choose_num_blocks
        assert p["host_weights_gib"] + p["host_kv_gib"] <= p["host_hbm_gib"]
        # 16 hosts of this size comfortably hold a ~200 GiB model
        assert p["total_model_gib"] < p["host_hbm_gib"] * N_HOSTS
        # spans are contiguous, inside the model, and sized by the sizer
        for start, end in p["spans"]:
            assert 0 <= start < end <= llama405b_cfg().num_hidden_layers
            assert end - start == p["n_per_host"]


def test_kv_budget_math():
    cfg = llama405b_cfg()
    # GQA 8 kv heads x 128 dim, k+v, bf16
    assert kv_bytes_per_token_per_block(cfg) == 2 * 8 * 128 * 2


def test_projection_monotone_and_gate():
    slow = project_single_stream(95.0, n_per_span=33)
    fast = project_single_stream(400.0, n_per_span=33)
    ceiling = project_single_stream(790.0, n_per_span=33)
    assert slow["tok_s"] < fast["tok_s"] < ceiling["tok_s"]
    # the round-2 bandwidth (95 GB/s) arithmetically forecloses the target...
    assert slow["tok_s"] < 2.0
    # ...and the VERDICT 400 GB/s gate clears it (the whole point of the gate)
    assert fast["tok_s"] >= 6.0


def test_projection_accounts_overhead_and_hops():
    base = project_single_stream(400.0, n_per_span=33)
    with_overhead = project_single_stream(
        400.0, n_per_span=33, device_overhead_frac=0.5
    )
    assert with_overhead["tok_s"] < base["tok_s"]
    wan = project_single_stream(400.0, n_per_span=33, hop_ms=50.0)
    assert wan["network_ms"] == 50.0 * math.ceil(126 / 33)
    assert wan["tok_s"] < base["tok_s"]


def test_report_consumes_measured_bench_rows():
    details = {
        "decode_70b_int4": {"weight_stream_gb_s": 350.0},
        "decode_70b_nf4": {"weight_stream_gb_s": 110.0},
        "e2e_8xllama7b": {"device_step_ms": 7.18, "weight_gb": 3.02},
    }
    report = rehearsal_report(details)
    by_quant = {r["quant"]: r for r in report["projection"] if r["chip_gb_s"] not in (400.0, 790.0)}
    assert by_quant["int4"]["chip_gb_s"] == 350.0
    assert by_quant["nf4"]["chip_gb_s"] == 110.0
    # NO extra overhead multiplier on measured rows: the decode_70b rates
    # divide weights by the FULL block step, so block extras are already in
    # the rate (an e2e-derived multiplier double-counted them, r5)
    assert by_quant["int4"]["device_overhead_frac"] == 0.0
    assert report["north_star"]["min_chip_gb_s_for_target"] > 0


def test_report_floors_measured_hop_against_noise():
    """The chain row's software-hop derivation subtracts two similar
    measurements; a tiny result must be floored (1 ms) rather than projecting
    near-free hops, and a solidly-measured hop must pass through unfloored."""
    base = {"decode_70b_int4": {"weight_stream_gb_s": 350.0}}
    noisy = rehearsal_report({**base, "chain_hop_405b_shapes": {"hop_software_ms": 0.015}})
    assert noisy["north_star"]["hop_ms"] == 1.5  # 1.0 floor + 0.5 wire
    assert "floored" in noisy["north_star"]["hop_source"]
    solid = rehearsal_report({**base, "chain_hop_405b_shapes": {"hop_software_ms": 3.0}})
    assert solid["north_star"]["hop_ms"] == 3.5


def test_outlier_quant_row_key_translation():
    """The nf4a+o projection reads its measured bandwidth from the bench row
    'decode_70b_nf4a_o' ('+' is not json-identifier-safe): a synthetic row
    must surface as a projection entry, or the quality option silently
    drops out of the report."""
    from benchmarks.rehearsal_405b import rehearsal_report

    report = rehearsal_report({
        "decode_70b_nf4a_o": {"weight_stream_gb_s": 400.0},
    })
    rows = [r for r in report["projection"] if r["quant"] == "nf4a+o"]
    assert rows and rows[0]["chip_gb_s"] == 400.0, report["projection"]
    assert "nf4a+o" in report["placement"]
