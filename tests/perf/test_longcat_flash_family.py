"""CPU tests of what PR 56 adds to the benchmark for the ``longcat_flash``
family (``perf/reference/longcat_flash.py``, ``perf/weights/longcat_flash.py``,
the three readers), at a toy size (``data/longcat-flash-tiny.json``, which no
cell uses: two double layers, 4 of 8 FFN experts held under a router of 12
outputs, 4 of them identities, top 3)."""

import json
import os
from pathlib import Path

import numpy as np
import pytest

from perf import costs, weights
from perf.config import load as load_config
from perf.record import load_reader
from tests.perf.test_keye_vl2_family import _capture, _child, _record

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
CONFIG, CELL = "longcat-flash-span4-ep32", "longcatflash-ctx2k"
READERS = ("scmoe_branch_busy_share", "scmoe_latent_attn_roofline_share", "scmoe_chunk_rows_per_routed")


@pytest.fixture()
def tiny(tmp_path):
    """The toy configuration, and the family and block configuration petals_tpu reads from its ``config.json``."""
    from petals_tpu.server.from_pretrained import get_block_config

    config = load_config(DATA / "longcat-flash-tiny.json", "longcat-flash-tiny")
    (tmp_path / "config.json").write_text(json.dumps(config["config"]))
    return config, *get_block_config(str(tmp_path))


def _tiny_bench() -> dict:
    bench = json.loads((DATA / "benchmark-tiny.json").read_text())
    bench["configs"].append({"name": "longcat-flash-tiny", "source": "toy", "file": "tests/perf/data/longcat-flash-tiny.json", "reduced": [], "why": "toy"})
    bench["workloads"].append({"name": "tiny-longcatflash", "config": "longcat-flash-tiny", "traffic": "tiny-closed", "chips": 1, "why": "toy"})
    return bench


def test_reference_agrees_with_the_served_block_on_the_weights_the_child_makes(tiny):
    """The plain float32 reference against the program's own block code on the
    weights the server child makes (a share of the experts, the router's bias
    at 2**-7 of a weight's draw), both in float32 on the CPU: 100
    positions at once; the margin counts a boundary at which a held expert or
    an identity stands."""
    import jax
    import jax.numpy as jnp

    from perf import reference

    config, family, cfg = tiny
    assert family.name == "longcat_flash" and reference.kinds_of("longcat_flash", config["config"]) is None
    assert (cfg.num_hidden_layers, cfg.num_experts, cfg.num_experts_exist, cfg.router_width) == (2, 4, 8, 12)
    x = np.random.default_rng(0).standard_normal((100, cfg.hidden_size), dtype=np.float32)
    want, margin, checks = reference.run(config, x)
    assert np.isfinite(want).all() and len(set(checks)) == 2 and np.isfinite(margin).any() and (margin > 0).all()
    params, first = weights.span_params(config, 0, 2, jnp.float32)
    assert first == checks[0] and params["w1"].shape == (2, 4, 128, 64) and params["gate"].shape == (2, 128, 12)
    bias = np.asarray(params["gate_bias"])
    assert 1e-4 < bias.std() < 3e-4  # 2**-7 of a weight's draw: it moves picks and fixes none
    with jax.default_matmul_precision("highest"):
        hidden = jnp.asarray(x)[None]
        for i in range(2):
            hidden, _ = family.block_apply(jax.tree_util.tree_map(lambda leaf: leaf[i], params), hidden, None, 0, cfg)
    assert float(np.abs(np.asarray(hidden[0]) - want).max() / np.abs(want).max()) < 1e-4


@pytest.mark.parametrize("control", ["bf16_router", "no_q_scale", "no_kv_scale", "renormalised", "no_identities"])
def test_each_control_of_the_reference_is_another_function_and_leaves_nothing_behind(tiny, control):
    """The controls the chip run makes at the published widths
    (benchmarks/prove_scmoe_matters.py, where each is judged not correct by
    the cell's limits): with one set the reference is another function, and
    the flag leaves nothing behind. At toy widths and weights of std 0.02 the
    sizes mean little (a router over 12 outputs, 4 heads); the toy shows the
    difference, the chip run its size."""
    from perf import reference

    config, _, _ = tiny
    family = reference.family_of("longcat_flash")
    assert control in family.CONTROLS and family.CONTROL is None
    x = np.random.default_rng(1).standard_normal((64, 128), dtype=np.float32)
    want, _, _ = reference.run(config, x)
    family.CONTROL = control
    try:
        other, _, _ = reference.run(config, x)
    finally:
        family.CONTROL = None
    again, _, _ = reference.run(config, x)
    assert np.array_equal(again, want)
    off = np.abs(other - want).max(-1) / np.abs(want).max(-1)
    # a bfloat16 router differs where a pick flips or by a weight's last places, and the cell's limits do not see it (PERF.md section 6, PR 56)
    assert (off.max() > 0) if control == "bf16_router" else (np.median(off) > 1e-4), (control, np.median(off), off.max())


def test_weights_take_the_layout_the_program_gives_a_checkpoint(tiny):
    """``perf/weights/longcat_flash.py`` ``block_params`` mirrors
    ``models/longcat_flash/block.py`` ``hf_to_block_params``: the same leaves,
    shapes and elements from the same HF tensors, both sub-layers' rope
    columns folded alike, the held experts named by their place among those
    that exist."""
    config, family, cfg = tiny
    maker = weights.family_of("longcat_flash")
    tensors = maker.layer_tensors(config["config"], 1, weights.Draws(config["weights_seed"]))
    assert all(str(t.dtype) == "bfloat16" for t in tensors.values())
    assert len(tensors) == 2 * 12 + 2 + 3 * 4 and "mlp.experts.3.up_proj.weight" in tensors and "mlp.experts.4.up_proj.weight" not in tensors
    assert (np.asarray(tensors["self_attn.1.q_a_layernorm.weight"], np.float32) == 1).all()
    assert not np.array_equal(np.asarray(tensors["self_attn.0.q_b_proj.weight"], np.float32), np.asarray(tensors["self_attn.1.q_b_proj.weight"], np.float32))
    mine = maker.block_params(config["config"], tensors)
    theirs = family.hf_to_block_params({k: np.asarray(v, np.float32) for k, v in tensors.items()}, cfg)
    shapes = family.block_param_shapes(cfg)
    assert set(mine) == set(theirs) == set(shapes)
    for name in theirs:
        assert mine[name].shape == theirs[name].shape == shapes[name].shape, name
        assert np.array_equal(np.asarray(mine[name], np.float32), theirs[name]), name


def test_the_family_states_its_costs_and_limits_never_over_and_the_configuration_cuts_two_keys():
    """The published shapes through ``perf/costs.py``: ISSUE 56's arithmetic.
    ``layer_params`` states the block's true parameter count and, for the
    attention, never more flops a pair or bytes a position than the block
    needs (``step_roofline_share`` runs in this cell)."""
    import jax

    from perf import reference
    from petals_tpu.models.registry import get_family
    from petals_tpu.server.from_pretrained import get_block_config  # noqa: F401  (registers the families)

    config = load_config(ROOT / f"perf/configs/{CONFIG}.json", CONFIG)
    hf = config["config"]
    p = costs.layer_params("longcat_flash", hf)
    assert p["attn"] == 2 * 90_570_752 == 181_141_504 and p["dense"] == 2 * 226_492_416 + 4_718_592 and p["expert"] == 37_748_736
    assert (p["experts"], p["experts_routed"], p["top_k"], p["hidden"], p["q_heads"], p["kv_heads"], p["head_dim"]) == (16, 768, 12, 6144, 64, 2, 288)
    assert costs.layer_param_count("longcat_flash", hf) == 1_242_824_704
    assert 0.62 < 4 * 2 * costs.layer_param_count("longcat_flash", hf) / 16e9 < 0.63  # 9.94 GB in bf16: 62% of a chip
    # held to the block's own leaves: every matrix the served block holds, and nothing else
    import tempfile

    with tempfile.TemporaryDirectory() as tmp:
        (Path(tmp) / "config.json").write_text(json.dumps(hf))
        family, cfg = get_block_config(tmp)
    assert family is get_family("longcat_flash")
    leaves = family.block_param_shapes(cfg)
    assert sum(int(np.prod(leaf.shape)) for leaf in leaves.values() if len(leaf.shape) > 1) == costs.layer_param_count("longcat_flash", hf)
    # never over: a position's cache exactly, a pair's flops 10% under the cheaper (expanded) form's in both attentions
    assert costs.kv_bytes_per_token_layer("longcat_flash", hf) == 2 * (512 + 64) * 2 == 2304
    assert 4 * p["q_heads"] * p["head_dim"] == 73_728 <= 2 * (2 * 64 * (128 + 64 + 128)) == 81_920
    assert costs.experts_reached(p, 8) == pytest.approx(16 * (1 - (1 - 12 / 768) ** 8)) and 1.8 < costs.experts_reached(p, 8) < 2.0
    step = costs.step_cost("longcat_flash", hf, 4, decode_tokens=8, prefill_tokens=0, context_tokens=8 * 1800)
    seconds, bound = costs.least_seconds(step, costs.peaks_for("TPU v5 lite"))
    assert bound == "bandwidth" and 6.9e-3 < seconds < 7.2e-3  # 5.68 GB of weights and 0.13 GB of latent rows at 819 GB/s
    limits = reference.limits(config)
    assert 0 < limits["median_bound"] <= limits["row_bound"] < 0.5 and (limits["tie_margin"] > 0) == (limits["positions_allowed"] > 0)
    args = config["server_args"]
    assert (args["batch_lanes"], args["batch_max_length"], args["inference_max_length"], args["num_blocks"]) == (8, 2560, 4096, 4)
    assert config["servers"] == [{"first_block": 0, "num_blocks": 4}] and config["reduced"] == ["num_layers", "n_routed_experts"]
    assert config["published"] == {"num_layers": 28, "n_routed_experts": 512} and hf["expert_share"] == {"routed": 512, "first": 0}
    assert set(config["assumed"]) >= {"weights", "hidden_act", "router_bias", "norm_eps", "scales", "cache", "rotary", "tensor_names", "num_hidden_layers", "mtp"}
    assert "32 v5e chips" in config["deployment"] and "seven servers" in config["deployment"]
    # every key of the catalog's row under the same key with the same value, but the two that are cut
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.is_file():
        row = next(r for r in map(json.loads, catalog.read_text().splitlines()) if r["name"] == "LongCat-Flash-Chat")
        assert config["source"] == row["source_url"]
        assert {k: v for k, v in row["config"].items() if hf[k] != v} == {"num_layers": 28, "n_routed_experts": 512}
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)  # found by name: later PRs append after them
    assert entry["reduced"] == config["reduced"] and entry["file"] == f"perf/configs/{CONFIG}.json" and entry["source"] == config["source"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "ctx2k", 1) and len(cell["why"]) <= 200
    names = [m["name"] for m in bench["per_layer"]]
    assert [names.index(r) for r in READERS] == list(range(names.index(READERS[0]), names.index(READERS[0]) + 3))
    assert names.index(READERS[0]) > names.index("ssm_one_step_row_share")
    assert all(m["workloads"] == [CELL] and m["moves"] == "gap_p50_ms" for m in bench["per_layer"] if m["name"] in READERS)
    assert all(CELL not in m["workloads"] for m in bench["per_layer"] if "workloads" in m and m["name"] not in READERS)  # nothing that was there is edited
    for reader in READERS:
        module, listed = load_reader("layer_metrics", reader), next(m for m in bench["per_layer"] if m["name"] == reader)
        assert (module.UNIT, module.LAYER, module.MOVES) == (listed["unit"], listed["layer"], listed["moves"])


def test_tiny_cell_end_to_end_through_two_cache_rows_a_block_and_a_share_of_the_experts(tmp_path):
    """The whole command at a toy size on the CPU on the toy configuration of
    this family: the server child serves two double layers through ``Server``
    with no flag, the check holds the served rows to the reference, and a
    traced run prints the counters' ratio beside the others; the two readers
    of a device's capture find none and are left out."""
    from perf import run

    bench = _tiny_bench()
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    added = [m for m in real["per_layer"] if m["name"] in READERS]
    shared = [m for m in real["per_layer"] if m["name"] in ("latent_absorbed_row_share", "latent_rows_read_share", "moe_dense_token_share")]
    assert len(added) == 3 and len(shared) == 3
    bench["per_layer"] += [{**m, "workloads": ["tiny-longcatflash"]} for m in added + shared]
    result = run.run_cell(bench, "tiny-longcatflash", 2**31 + 29, 5.0, True, traffic_dir=DATA / "traffic", work_dir=tmp_path, allow_cpu=True)
    detail = result["detail"]
    assert result["correct"] is True and result["failed"] == 0 and detail["sessions_total"] > 0
    assert detail["check"]["repeat_identical"] and detail["recompiled"] == [] and detail["check"]["decode"]["ok"]
    metrics = result["metrics"]
    assert {"scmoe_chunk_rows_per_routed", "latent_absorbed_row_share", "latent_rows_read_share", "moe_dense_token_share",
            "recompiles_in_window", "decode_batch_mean"} <= set(metrics)
    assert metrics["scmoe_chunk_rows_per_routed"]["value"] == pytest.approx(4 / (3 * 4 / 12))  # the einsum: 4 held experts a position for 1 pick here
    assert "scmoe_branch_busy_share" not in metrics and "scmoe_latent_attn_roofline_share" not in metrics  # no device, no capture
    stats = json.loads((tmp_path / "runs/tiny-longcatflash/child0.json").read_text())["marks"]["window_end"]["stats"]
    assert stats["latent_rows_absorbed"] > 0 and stats["latent_rows_absorbed"] % 4 == 0 and stats["moe_hit_tokens"] > 0  # four sub-layers a row


def test_readers_on_a_hand_made_record_and_a_hand_encoded_capture(tmp_path, monkeypatch):
    busy, roofline, ratio = (load_reader("layer_metrics", name) for name in READERS)
    hf = load_config(ROOT / f"perf/configs/{CONFIG}.json", "x")
    kanana = load_config(ROOT / "perf/configs/kanana2-30b-a3b-span6.json", "y")
    peaks = costs.peaks_for("TPU v5 lite")
    # the chunk counters between ``window`` and ``window_end``: 40 chunks of 512 through 4 blocks under the einsum
    window = lambda start, stop: {"marks": {"window": {"mono": 0.0, "stats": start}, "window_end": {"mono": 51.0, "stats": stop}}}
    start = {"moe_chunk_rows_computed": 1000, "moe_chunk_rows_routed": 50.0}
    stop = {"moe_chunk_rows_computed": 1000 + 40 * 512 * 4 * 16, "moe_chunk_rows_routed": 50.0 + 40 * 512 * 4 * 0.25}
    assert ratio.read(_record([window(start, stop)], hf)) == pytest.approx(64.0)
    # no chunk in the window, a program from before the counters, a run without the marks, no child, a configuration without identities
    for children in ([window(start, start)], [window({"batched_steps": 1}, {"batched_steps": 5})], [_child(start, stop)], [{}], []):
        assert ratio.read(_record(children, hf)) is None
    assert ratio.read(_record([window(start, stop)], kanana)) is None and ratio.read(_record([window(start, stop)])) is None

    from perf.layer_metrics import sparse_attn_roofline_share as sparse

    monkeypatch.setattr(sparse, "RUNS_DIR", tmp_path)  # ``capture`` is that file's: it looks under its own directory
    # the latent counters between the trace's marks: 200 decode steps of 8 lanes at a context of 1,800, and 5 chunks of 512 from
    # position 0, all through 8 sub-layers
    rows, chunk_held = 200 * 8 * 1800 * 8, 5 * 512 * 8
    pairs = rows + 5 * (512 * 513 // 2) * 8
    keys = ("latent_rows_held", "latent_positions_held", "latent_score_pairs")
    t0 = dict.fromkeys(keys, 3)
    t1 = {"latent_rows_held": 3 + rows, "latent_positions_held": 3 + chunk_held, "latent_score_pairs": 3 + pairs}
    one = _record([_child(t0, t1)], hf, peaks)
    assert roofline.read(one) is None and busy.read(one) is None  # no capture under the runs' directory
    scope = "jit(paged_mixed_step)/while/body/closed_call/"
    ops = {10: ("%while.60 = (s32[]) while(...)", None),
           11: ("%fusion.379 = bf16[64,512,8] fusion(...)", scope + "ptu.attn.latent_absorb/dot_general:"),
           12: ("%latent_decode_walk.18 = f32[8,64,512] custom-call(...)", scope + "ptu.attn.latent_decode/jit(_decode_kernel_walk)/latent_decode_walk/pallas_call:"),
           13: ("%fusion.390 = bf16[8,12288] fusion(...)", scope + "dot_general:"),
           14: ("%moe_hit_experts.9 = f32[16,6144] custom-call(...)", scope + "ptu.scmoe.shortcut/ptu.moe.experts.hit/pallas_call:"),
           15: ("%select_reduce_fusion.4 = f32[8] fusion(...)", scope + "ptu.scmoe.shortcut/ptu.moe.zero/reduce_sum:"),
           16: ("%fusion.900 = f32[64,512,128] fusion(...)", scope + "ptu.attn.latent_chunk/while/body/ptu.attn.latent_expand/dot_general:")}
    # the loop holds everything; both attentions' absorb and walk (0.1-0.3 s), a dense matmul, the branch's experts (0.5-0.62 s) and
    # its identities (0.62-0.65 s, abutting), a chunk's expansion (1.0-1.1 s)
    events = [(10, 0, 15 * 10**11), (11, 10**11, 5 * 10**10), (12, 15 * 10**10, 15 * 10**10), (13, 35 * 10**10, 10**11), (14, 5 * 10**11, 12 * 10**10),
              (15, 62 * 10**10, 3 * 10**10), (16, 10**12, 10**11)]
    no_chunk = [e for e in events if e[0] != 16]
    stale = tmp_path / "another-cell/trace/child0/plugins/profile/then/host.xplane.pb"
    stale.parent.mkdir(parents=True)
    stale.write_bytes(_capture(ops, no_chunk))
    os.utime(stale, (1, 1))
    need = max((rows + chunk_held) * 1152 / 819e9, pairs * 2 * 64 * 320 / peaks["bf16_flops_per_s"])
    assert roofline.read(one) == pytest.approx(100 * need / 0.2)  # a slice with no chunk: the absorbed form's scopes alone
    assert busy.read(one) == pytest.approx(100 * 0.15 / 1.2)  # over the child's busy seconds (``_child``: 1.2)
    path = tmp_path / f"{CELL}/trace/child0/plugins/profile/now/host.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(_capture(ops, events))
    assert roofline.read(one) == pytest.approx(100 * need / 0.3) and 0 < roofline.read(one) <= 100
    assert busy.read(one) == pytest.approx(100 * 0.15 / 1.2)
    assert sparse.NAMES == ("ptu.attn.index_score", "ptu.attn.select", "ptu.attn.sparse_attend")  # the other reader's names are its own again
    # a capture in which no operation carries the branch's scope (the parent commit's program): None, not 0.0: every step has a router
    bare = {key: (name, tf_op.replace("ptu.scmoe.shortcut/", "") if tf_op else None) for key, (name, tf_op) in ops.items()}
    path.write_bytes(_capture(bare, events))
    assert busy.read(one) is None and roofline.read(one) == pytest.approx(100 * need / 0.3)
    path.write_bytes(_capture(ops, events))
    assert roofline.read(_record([_child(t0, t1)], hf, None)) is None  # off the chip: no peaks
    for reader in (roofline, busy):
        assert reader.read(_record([{**_child(t0, t1), "trace": {}}], hf, peaks)) is None  # the child read no device plane
        assert reader.read(_record([_child(t0, t1)] * 2, hf, peaks)) is None  # a second child that left no capture
        assert reader.read(_record([_child(t0, t1)], kanana, peaks)) is None  # one latent attention a block: the accepted reader's
        assert reader.read(_record([], hf, peaks)) is None
    other = {"batched_steps": 5}  # a program without the counters, a run without the marks
    for children in ([_child(other, other)], [{"marks": {}}], [{}]):
        assert roofline.read(_record(children, hf, peaks)) is None
