"""CPU tests of what PR 42 adds to the benchmark for the ``deepseek_v3`` family
(``perf/reference/deepseek_v3.py``, ``perf/weights/deepseek_v3.py``, the three
latent-attention readers, the configuration and its cell), at a toy size
(``data/deepseek_v3-tiny.json``, which no cell uses: a dense layer and three
expert layers, a latent row of 64 + 16)."""

import json
import os
from pathlib import Path

import numpy as np
import pytest

from perf import correct, costs, weights
from perf.config import load as load_config
from perf.record import load_reader
from tests.perf.test_keye_vl2_family import _capture, _child, _record

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
READERS = ("latent_attn_roofline_share", "latent_rows_read_share", "latent_absorbed_row_share")
CONFIG = "kanana2-30b-a3b-span6"


@pytest.fixture()
def tiny(tmp_path):
    """The toy configuration, and the family and block configuration petals_tpu reads from its ``config.json``."""
    from petals_tpu.server.from_pretrained import get_block_config

    config = load_config(DATA / "deepseek_v3-tiny.json", "deepseek_v3-tiny")
    (tmp_path / "config.json").write_text(json.dumps(config["config"]))
    return config, *get_block_config(str(tmp_path))


def _tiny_bench() -> dict:
    bench = json.loads((DATA / "benchmark-tiny.json").read_text())
    bench["configs"].append({"name": "deepseek_v3-tiny", "source": "toy", "file": "tests/perf/data/deepseek_v3-tiny.json", "reduced": [], "why": "toy"})
    bench["workloads"].append({"name": "tiny-kanana", "config": "deepseek_v3-tiny", "traffic": "tiny-closed-long", "chips": 1, "why": "toy"})
    return bench


def test_reference_agrees_with_the_served_block_in_all_three_forms_and_one_precision_lower_fails_the_check(tiny):
    """The plain float32 reference (expanded, the published interleaved
    rotary) against the program's own block code on the weights the server
    child makes, both in float32 on the CPU: the whole sequence at once (the
    stateless pass's form), then a prompt chunk of 100 padded to 128 (expanded
    inside a walk) and decode steps (absorbed) through pages. perf/correct.py's
    ``judge`` passes those rows under the family's limits, and fails the
    reference itself computed with float8 (e4m3) weights and layer inputs."""
    import jax
    import jax.numpy as jnp

    from perf import reference
    from petals_tpu.ops.latent_attention import latent_pool_rows
    from petals_tpu.ops.paged_attention import PagedKV

    config, family, cfg = tiny
    hf = config["config"]
    kinds = reference.kinds_of("deepseek_v3", hf)
    assert family.name == "deepseek_v3" and kinds == [("dense",), ("sparse",), ("sparse",), ("sparse",)]
    x = np.random.default_rng(0).standard_normal((correct.SEQ, cfg.hidden_size), dtype=np.float32)
    want, margin, checks = reference.run(config, x)
    assert np.isfinite(want).all() and len(set(checks)) == 4 and np.isfinite(margin).all() and (margin >= 0).all()
    (dense, sparse), first = weights.span_params(config, 0, 4, jnp.float32)
    assert first == checks[0] and dense["wq"].shape == (1, 128, 4 * 48) and sparse["w1"].shape == (3, 8, 128, 64) and sparse["ws1"].shape == (3, 128, 128)
    blocks = [("dense", jax.tree_util.tree_map(lambda leaf: leaf[0], dense))]
    blocks += [("sparse", jax.tree_util.tree_map(lambda leaf: leaf[i], sparse)) for i in range(3)]

    def close(got):
        return float(np.abs(got - want[: got.shape[0]]).max() / np.abs(want).max())

    with jax.default_matmul_precision("highest"):
        hidden = jnp.asarray(x)[None]
        for kind, params in blocks:
            hidden, _ = family.apply_for(kind)(params, hidden, None, 0, cfg)
        assert close(np.asarray(hidden[0])) < 1e-4
        # a prompt chunk of 100 in a bucket of 128, then 44 decode steps, through one lane's pages of 16
        programs = {kind: jax.jit(lambda p, h, kv, pos, n, kind=kind: family.apply_for(kind)(p, h, kv, pos, cfg, n_valid=n)) for kind in ("dense", "sparse")}
        tables = jnp.asarray(np.random.default_rng(1).permutation(10).astype(np.int32)[None])
        caches = [tuple(PagedKV(jnp.zeros((10, *row), jnp.float32), tables) for row in latent_pool_rows(16, cfg.kv_lora_rank, cfg.qk_rope_head_dim))
                  for _ in blocks]
        h = jnp.pad(jnp.asarray(x)[None, :100], ((0, 0), (0, 28), (0, 0)))
        for i, (kind, params) in enumerate(blocks):
            h, caches[i] = programs[kind](params, h, caches[i], jnp.int32(0), jnp.int32(100))
        got = [np.asarray(h[0, :100])]
        for pos in range(100, correct.SEQ):
            h = jnp.asarray(x)[None, pos : pos + 1]
            for i, (kind, params) in enumerate(blocks):
                h, caches[i] = programs[kind](params, h, caches[i], jnp.full((1,), pos, jnp.int32), None)
            got.append(np.asarray(h[0]))
        got = np.concatenate(got)
        assert close(got) < 1e-4
        # one precision lower: the reference with float8 weights and layer inputs
        family_ref, maker = reference.family_of("deepseek_v3"), weights.family_of("deepseek_v3")
        f8 = lambda t: jax.lax.reduce_precision(t.astype(jnp.float32), exponent_bits=4, mantissa_bits=3)
        lower = jnp.asarray(x)
        for index, kind in enumerate(kinds):
            w = maker.layer_tensors(hf, index, weights.Draws(config["weights_seed"]), *kind)
            lower, _ = family_ref.block(hf, {k: f8(v) for k, v in w.items()}, f8(lower), *kind)
    rows = [("prefill" if p < 100 else "decode", p, got[p]) for p in range(64, correct.SEQ)]
    limits = reference.limits(config)
    assert correct.judge(rows, want, margin, limits)["ok"]
    lower = np.asarray(lower)
    verdict = correct.judge([(kind, p, lower[p]) for kind, p, _ in rows], want, margin, limits)
    assert not verdict["ok"]


def test_weights_take_the_layout_the_program_gives_a_checkpoint(tiny):
    """``perf/weights/deepseek_v3.py`` ``block_params`` mirrors
    ``models/deepseek_v3/block.py`` ``hf_to_block_params`` for both kinds: the
    same leaves, shapes and elements from the same tensors (the rope columns
    de-interleaved, ``kv_b_proj`` cut into ``wuk`` and ``wuv``), under
    transformers' names, the router's bias drawn and not left at zero."""
    config, family, cfg = tiny
    maker = weights.family_of("deepseek_v3")
    for layer, kind in ((0, "dense"), (2, "sparse")):
        tensors = maker.layer_tensors(config["config"], layer, weights.Draws(config["weights_seed"]), kind)
        assert all(str(t.dtype) == "bfloat16" for t in tensors.values())
        assert len(tensors) == (10 if kind == "dense" else 7 + 2 + 3 * 8 + 3)
        mine = maker.block_params(config["config"], tensors, kind)
        theirs = family.hf_to_block_params({k: np.asarray(v, np.float32) for k, v in tensors.items()}, cfg, kind)
        shapes = family.param_shapes_for(cfg, kind)
        assert set(mine) == set(theirs) == set(shapes)
        for name in theirs:
            assert mine[name].shape == theirs[name].shape == shapes[name].shape, name
            assert np.array_equal(np.asarray(mine[name], np.float32), theirs[name]), name
        if kind == "sparse":
            assert np.asarray(tensors["mlp.gate.e_score_correction_bias"], np.float32).std() > 0.01
    assert maker.span_tree(config["config"], [(0, "a"), (1, "b")]) == ("a", "b")
    named = load_config(ROOT / f"perf/configs/{CONFIG}.json", CONFIG)["assumed"]["tensor_names"]
    for part in ("self_attn.{q_proj,kv_a_proj_with_mqa,kv_a_layernorm,kv_b_proj,o_proj}", "mlp.gate.{weight,e_score_correction_bias}",
                 "mlp.experts.{e}.{gate,up,down}_proj", "mlp.shared_experts.{gate,up,down}_proj"):
        assert part in named


def test_the_family_states_its_costs_and_limits_and_the_configuration_its_cut():
    """The published shapes through ``perf/costs.py``: ISSUE 42's numbers."""
    from perf import reference

    config = load_config(ROOT / f"perf/configs/{CONFIG}.json", CONFIG)
    hf = config["config"]
    assert reference.kinds_of("deepseek_v3", hf)[:6] == [("dense",)] + [("sparse",)] * 5
    dense, sparse = costs.layer_params("deepseek_v3", hf, 0), costs.layer_params("deepseek_v3", hf, 1)
    assert dense["attn"] == sparse["attn"] == 12_582_912 + 1_179_648 + 4_194_304 + 8_388_608 == 26_345_472
    assert dense["dense"] == 37_748_736 and dense["experts"] == 0 and sparse["dense"] == 262_144 + 9_437_184 and sparse["expert"] == 4_718_592
    assert (sparse["experts"], sparse["top_k"], sparse["q_heads"], sparse["kv_heads"], sparse["head_dim"]) == (128, 6, 32, 2, 144)
    assert costs.layer_param_count("deepseek_v3", hf, 0) == 64_094_208 and costs.layer_param_count("deepseek_v3", hf, 1) == 640_024_576
    assert 64_094_208 + 5 * 640_024_576 == 3_264_217_088  # 6.53 GB, 6.08 GiB
    # a cached position costs exactly the latent row's 1,152 B, and the attention's flops a pair are under the cheaper form's
    assert costs.kv_bytes_per_token_layer("deepseek_v3", hf, 1) == 1152 == (hf["kv_lora_rank"] + hf["qk_rope_head_dim"]) * 2
    assert 4 * sparse["q_heads"] * sparse["head_dim"] == 18_432 <= 2 * 32 * (192 + 128) == 20_480
    # eight lanes at a mean context of 24k: 226 MB of latent rows a layer beside the ~41 experts eight tokens reach
    cost = costs.step_cost("deepseek_v3", hf, 6, decode_tokens=8, prefill_tokens=0, context_tokens=8 * 24576)
    reached = costs.experts_reached(sparse, 8)
    assert 40 < reached < 42
    assert 3.6e9 < cost["bytes"] < 3.9e9 and cost["bytes"] / 819e9 > cost["flops"] / 197e12  # 4.6 ms a step by bytes
    limits = reference.limits(config)
    assert limits["tie_margin"] > 0 and 0 < limits["positions_allowed"] <= 2 and 0 < limits["median_bound"] <= limits["row_bound"] < 0.3
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.is_file():  # the published keys verbatim: every one of the catalog row's, but the depth
        row = next(json.loads(line) for line in catalog.read_text().splitlines() if '"kanana-2-30b-a3b-instruct-2601"' in line)
        assert {k: v for k, v in hf.items() if k != "num_hidden_layers"} == {k: v for k, v in row["config"].items() if k != "num_hidden_layers"}
        assert config["source"] == row["source_url"] and config["published"] == {"num_hidden_layers": row["config"]["num_hidden_layers"]}
    assert config["reduced"] == ["num_hidden_layers"] and hf["num_hidden_layers"] == 6 == config["servers"][0]["num_blocks"]
    assert {"weights", "attention", "rotary", "cache", "experts", "tensor_names", "head_dim"} <= set(config["assumed"])
    args = config["server_args"]
    assert args["batch_lanes"] == 8 and args["batch_max_length"] == args["inference_max_length"] == hf["max_position_embeddings"] == 32768
    assert args["prefill_token_budget"] == 2048 and args["num_blocks"] == 6
    mix = json.loads((ROOT / "perf/traffic/ctx32k.json").read_text())
    assert mix["prompt"]["max"] + mix["output"]["value"] <= args["batch_max_length"]
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    added = [m for m in real["per_layer"] if m["name"] in READERS]
    assert [m["name"] for m in real["per_layer"][-3:]] == list(READERS) == [m["name"] for m in added]
    assert all(m["workloads"] == ["kanana2-ctx32k"] and m["moves"] == "gap_p50_ms" and m["unit"] == "%" for m in added)
    assert real["workloads"][-1] == {**real["workloads"][-1], "name": "kanana2-ctx32k", "config": CONFIG, "traffic": "ctx32k", "chips": 1}
    assert real["configs"][-1]["name"] == CONFIG and real["configs"][-1]["reduced"] == ["num_hidden_layers"]
    assert not any("kanana2-ctx32k" in m.get("workloads", ()) for m in real["per_layer"] if m["name"] not in READERS)  # no list was touched


def test_tiny_cell_end_to_end_in_both_forms(tmp_path):
    """The whole command at a toy size on the CPU on the toy configuration of
    this family: the server child serves the span through ``Server`` with no
    flag, the check's sessions hold the served rows to the reference (chunks
    expanded, decode rows absorbed), and a traced run prints the two counter
    metrics; the roofline share finds no capture of a device and is left out."""
    from perf import run

    bench = _tiny_bench()
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["per_layer"] += [{**m, "workloads": ["tiny-kanana"]} for m in real["per_layer"] if m["name"] in READERS]
    result = run.run_cell(bench, "tiny-kanana", 2**31 + 11, 5.0, True, traffic_dir=DATA / "traffic", work_dir=tmp_path, allow_cpu=True)
    detail = result["detail"]
    assert result["correct"] is True and result["failed"] == 0 and detail["sessions_total"] > 0
    assert detail["check"]["repeat_identical"] and detail["recompiled"] == [] and detail["check"]["decode"]["ok"]
    metrics = result["metrics"]
    assert {"latent_rows_read_share", "latent_absorbed_row_share", "recompiles_in_window", "decode_batch_mean"} <= set(metrics)
    assert "latent_attn_roofline_share" not in metrics
    assert 0 < metrics["latent_absorbed_row_share"]["value"] <= 100 and metrics["latent_rows_read_share"]["value"] >= 100


def test_latent_readers_on_a_hand_made_record(tmp_path, monkeypatch):
    roofline, read, absorbed = (load_reader("layer_metrics", name) for name in READERS)
    keys = ("latent_rows_read", "latent_rows_held", "latent_rows_absorbed", "latent_rows_expanded", "latent_positions_expanded",
            "latent_positions_held", "latent_score_pairs")
    start = dict.fromkeys(keys, 7)
    # between the marks: 100 decode steps of 8 lanes at contexts of 24,576 (the longest 28,672: whole blocks of 4,096) through 6 layers
    held, walked = 100 * 8 * 24576 * 6, 100 * 8 * 28672 * 6
    stop = {**start, "latent_rows_read": 7 + walked, "latent_rows_held": 7 + held, "latent_rows_absorbed": 7 + 100 * 8 * 6, "latent_score_pairs": 7 + held}
    record = _record([_child(start, stop)])
    assert read.read(record) == pytest.approx(100 * 28672 / 24576) and absorbed.read(record) == 100.0
    # a slice of mixed steps: 10 steps of 7 decoding lanes and a chunk of 2,048 rows from position 8,192
    pairs = 10 * 6 * (7 * 24576 + 2048 * 8192 + 2048 * 2049 // 2)
    mixed = {**start, "latent_rows_read": 7 + 10 * 8 * 24576 * 6, "latent_rows_held": 7 + 10 * 7 * 24576 * 6, "latent_rows_absorbed": 7 + 10 * 7 * 6,
             "latent_rows_expanded": 7 + 10 * 2048 * 6, "latent_positions_expanded": 7 + 10 * 10240 * 6, "latent_positions_held": 7 + 10 * 10240 * 6,
             "latent_score_pairs": 7 + pairs}
    assert absorbed.read(_record([_child(start, mixed)])) == pytest.approx(100 * 7 / (7 + 2048))
    two = _record([_child(start, stop), _child(start, {**stop, "latent_rows_read": stop["latent_rows_held"]})])
    assert read.read(two) == pytest.approx(100 * (28672 + 24576) / (2 * 24576))  # a chain: summed; a walk that reads each row once reads 100
    for reader in (roofline, read, absorbed):
        assert reader.UNIT == "%" and reader.MOVES == "gap_p50_ms" and reader.LAYER == "latent attention (ops/latent_attention.py)"
    assert (ROOT / "petals_tpu/ops/latent_attention.py").is_file()

    # the roofline share reads the scopes out of the capture the child left
    hf = load_config(ROOT / f"perf/configs/{CONFIG}.json", "x")
    peaks = costs.peaks_for("TPU v5 lite")
    from perf.layer_metrics import sparse_attn_roofline_share as sparse

    monkeypatch.setattr(sparse, "RUNS_DIR", tmp_path)  # ``capture`` is that file's: it looks under its own directory
    one = _record([_child(start, stop)], hf, peaks)
    assert roofline.read(one) is None  # no capture under the runs' directory
    scope = "jit(paged_decode)/ptu.span.sparse/while/body/closed_call/"
    ops = {10: ("%while.60 = (s32[]) while(...)", None), 11: ("%fusion.31 = bf16[8,32,512] fusion(...)", scope + "ptu.attn.latent_absorb/dot_general:"),
           12: ("%fusion.3 = bf16[64,64,512] fusion(...)", scope + "ptu.attn.latent_decode/while/body/jit(_take)/gather:"),
           13: ("%moe_hit_experts.11 = f32[16,2048] custom-call(...)", scope + "ptu.moe.hit/pallas_call:"),
           14: ("%fusion.9 = f32[8,32,512] fusion(...)", scope + "ptu.attn.latent_decode/while/body/dot_general:"),
           15: ("%fusion.12 = bf16[256,32,128] fusion(...)", scope + "ptu.attn.latent_chunk/while/body/ptu.attn.latent_expand/dot_general:")}
    # the loop holds everything; the absorb and a gather overlap (0.1-0.3 s and 0.25-0.55 s), the experts' kernel is none of the scopes
    events = [(10, 0, 12 * 10**11), (11, 10**11, 2 * 10**11), (12, 25 * 10**10, 3 * 10**11), (13, 6 * 10**11, 10**11), (14, 8 * 10**11, 5 * 10**10),
              (15, 9 * 10**11, 5 * 10**10)]
    stale = tmp_path / "another-cell/trace/child0/plugins/profile/then/host.xplane.pb"
    stale.parent.mkdir(parents=True)
    stale.write_bytes(_capture(ops, [(10, 0, 12 * 10**11)]))
    os.utime(stale, (1, 1))
    assert roofline.read(one) is None  # a capture in which nothing ran under the scopes
    path = tmp_path / "kanana2-ctx32k/trace/child0/plugins/profile/now/host.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(_capture(ops, events))
    assert roofline.named_seconds(path) == pytest.approx(0.55)
    nbytes, flops = held * 1152, held * 20480
    assert nbytes / 819e9 > flops / 197e12  # a decode slice is bound by the rows' bytes
    assert roofline.read(one) == pytest.approx(100 * (nbytes / 819e9) / 0.55) and roofline.read(one) < 100
    mixed_need = ((10 * 7 * 24576 + 10 * 10240) * 6 * 1152, pairs * 20480)
    assert mixed_need[1] / 197e12 > mixed_need[0] / 819e9  # a mixed slice by the pairs' flops
    assert roofline.read(_record([_child(start, mixed)], hf, peaks)) == pytest.approx(100 * (mixed_need[1] / 197e12) / 0.55)
    assert roofline.read(_record([_child(start, stop)], hf, None)) is None  # off the chip: no peaks
    assert roofline.read(_record([{**_child(start, stop), "trace": {}}], hf, peaks)) is None  # the child read no device plane
    assert roofline.read(_record([_child(start, stop)] * 2, hf, peaks)) is None  # a second child that left no capture
    # a family without a latent row, a program without the counters (the parent commit), a run without the marks, no step
    other = {"batched_steps": 5}
    keye = load_config(ROOT / "perf/configs/keye-vl2-30b-a3b-span5.json", "y")
    for children in ([_child(other, other)], [{"marks": {}}], [{}], []):
        assert all(reader.read(_record(children, hf, peaks)) is None for reader in (roofline, read, absorbed))
    assert roofline.read(_record([_child(start, stop)], keye, peaks)) is None
    assert read.read(_record([_child(start, start)])) is None and absorbed.read(_record([_child(start, start)])) is None


def test_prove_latent_long_at_a_toy_size_passes_and_its_float8_control_fails(tmp_path):
    """benchmarks/prove_latent_long.py on the CPU at toy widths: a prompt of
    256 fresh rows over four mixed steps of 64 and 32 decode steps beside two
    decoding sessions, inside the family's limits against the reference
    computed in blocks of rows; against the reference with float8 weights and
    layer inputs it is outside them."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("prove_latent_long", ROOT / "benchmarks/prove_latent_long.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    summary = module.prove(_tiny_bench(), "tiny-kanana", 2**31 + 13, 256, work_dir=tmp_path, allow_cpu=True)
    assert summary["correct"] and summary["float8_not_correct"], summary
    assert summary["nearest"] < 0.01 and summary["float8_nearest"] > 1
