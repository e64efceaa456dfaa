"""CPU tests of what PR 59 adds to the benchmark for the ``xing4_0`` family
(``perf/reference/xing4_0.py``, ``perf/weights/xing4_0.py``, ``perf/hc.py``,
the three ``hc_*`` readers, the configuration and its cell), at a toy size
(``data/xing4-tiny.json``, which no cell uses: two dense layers and two expert
layers under a stream of four rows, a latent row of 64 + 16, yarn over a
window of 64)."""

import json
import os
from pathlib import Path

import numpy as np
import pytest

from perf import correct, costs, hc, weights
from perf.config import load as load_config
from perf.record import load_reader
from tests.perf.test_keye_vl2_family import _capture, _child, _record

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
READERS = ("hc_mix_busy_share", "hc_mix_roofline_share", "hc_stream_kib_per_row")
CONFIG, CELL = "xing4-29b-a4b-span8", "xing4-29b-saturated"


@pytest.fixture()
def tiny(tmp_path):
    """The toy configuration, and the family and block configuration petals_tpu reads from its ``config.json``."""
    from petals_tpu.server.from_pretrained import get_block_config

    config = load_config(DATA / "xing4-tiny.json", "xing4-tiny")
    (tmp_path / "config.json").write_text(json.dumps(config["config"]))
    return config, *get_block_config(str(tmp_path))


def _tiny_bench() -> dict:
    bench = json.loads((DATA / "benchmark-tiny.json").read_text())
    bench["configs"].append({"name": "xing4-tiny", "source": "toy", "file": "tests/perf/data/xing4-tiny.json", "reduced": [], "why": "toy"})
    bench["workloads"].append({"name": "tiny-xing", "config": "xing4-tiny", "traffic": "tiny-closed-long", "chips": 1, "why": "toy"})
    return bench


def test_reference_agrees_with_the_served_block_stateless_and_through_pages_and_one_precision_lower_fails_the_check(tiny):
    """The plain float32 reference (einsums over ``[seq, n, C]``, the expanded
    attention, the published interleaved rotary under yarn) against the
    program's own block code on the weights the server child makes, both in
    float32 on the CPU: the whole sequence at once (the stateless pass's
    form), then a prompt chunk of 100 padded to 128 (expanded inside a walk)
    and decode steps (absorbed) through pages, the stream 512 wide all the
    way. perf/correct.py's ``judge`` passes those rows under the family's
    limits, and fails the reference itself computed with float8 (e4m3)
    weights and layer inputs. The reference imports nothing of the program."""
    import jax
    import jax.numpy as jnp

    from perf import reference
    from petals_tpu.ops.latent_attention import latent_pool_rows
    from petals_tpu.ops.paged_attention import PagedKV

    config, family, cfg = tiny
    hf = config["config"]
    source = (ROOT / "perf/reference/xing4_0.py").read_text()
    assert "petals_tpu" not in source.split('"""', 2)[2] and "import petals" not in source
    kinds = reference.kinds_of("xing4_0", hf)
    assert family.name == "xing4_0" and kinds == [("dense",), ("dense",), ("sparse",), ("sparse",)]
    width = costs.layer_params("xing4_0", hf)["hidden"]
    assert width == cfg.stream_width == 512 == family.stream_for(cfg)[0] and cfg.hidden_size == 128
    x = np.random.default_rng(0).standard_normal((correct.SEQ, width), dtype=np.float32)
    want, margin, checks = reference.run(config, x)
    assert want.shape == (correct.SEQ, width) and np.isfinite(want).all() and len(set(checks)) == 4 and (margin >= 0).all()
    (dense, sparse), first = weights.span_params(config, 0, 4, jnp.float32)
    assert first == checks[0] and dense["wqa"].shape == (2, 128, 48) and sparse["w1"].shape == (2, 8, 128, 64) and sparse["ws1"].shape == (2, 128, 64)
    assert dense["hc_phi_attn"].shape == (2, 512, 24) and sparse["hc_bias_mlp"].shape == (2, 24) and dense["hc_alpha_mlp"].shape == (2, 3)
    blocks = [("dense", jax.tree_util.tree_map(lambda leaf: leaf[i], dense)) for i in range(2)]
    blocks += [("sparse", jax.tree_util.tree_map(lambda leaf: leaf[i], sparse)) for i in range(2)]

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
        family_ref, maker = reference.family_of("xing4_0"), weights.family_of("xing4_0")
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
    """``perf/weights/xing4_0.py`` ``block_params`` mirrors
    ``models/xing4_0/block.py`` ``hf_to_block_params`` for both kinds: the
    same leaves, shapes and elements from the same tensors (the rope columns
    of ``q_b_proj`` and ``kv_a_proj_with_mqa`` de-interleaved, ``kv_b_proj``
    cut into ``wuk`` and ``wuv``, a wrap's three ``phi`` side by side as one
    matrix), the router's and the wraps' biases drawn and not left at zero,
    every ``alpha`` 0.4 as bf16 holds it."""
    config, family, cfg = tiny
    maker = weights.family_of("xing4_0")
    for layer, kind in ((0, "dense"), (2, "sparse")):
        tensors = maker.layer_tensors(config["config"], layer, weights.Draws(config["weights_seed"]), kind)
        assert all(str(t.dtype) == "bfloat16" for t in tensors.values())
        assert len(tensors) == 9 + 2 * 9 + (3 if kind == "dense" else 2 + 3 * 8 + 3)
        mine = maker.block_params(config["config"], tensors, kind)
        theirs = family.hf_to_block_params({k: np.asarray(v, np.float32) for k, v in tensors.items()}, cfg, kind)
        shapes = family.param_shapes_for(cfg, kind)
        assert set(mine) == set(theirs) == set(shapes)
        for name in theirs:
            assert mine[name].shape == theirs[name].shape == shapes[name].shape, name
            assert np.array_equal(np.asarray(mine[name], np.float32), theirs[name]), name
        assert np.array_equal(theirs["hc_alpha_attn"], np.full(3, 0.400390625, np.float32))
        assert np.asarray(tensors["mlp_hc.b_res"], np.float32).std() > 0.005 and np.asarray(tensors["attn_hc.phi_res.weight"], np.float32).std() > 0.015
        assert not np.array_equal(np.asarray(tensors["attn_hc.phi_pre.weight"]), np.asarray(tensors["mlp_hc.phi_pre.weight"]))  # streams of their own
        if kind == "sparse":
            assert np.asarray(tensors["mlp.gate.e_score_correction_bias"], np.float32).std() > 0.01
    assert maker.span_tree(config["config"], [(0, "a"), (2, "b")]) == ("a", "b")
    named = load_config(ROOT / f"perf/configs/{CONFIG}.json", CONFIG)["assumed"]["tensor_names"]
    for part in ("self_attn.{q_a_proj,q_a_layernorm,q_b_proj,kv_a_proj_with_mqa,kv_a_layernorm,kv_b_proj,o_proj}", "mlp.gate.{weight,e_score_correction_bias}",
                 "mlp.experts.{e}.{gate,up,down}_proj", "mlp.shared_experts.{gate,up,down}_proj", "{attn_hc,mlp_hc}.phi_{pre,post,res}.weight"):
        assert part in named


def test_the_family_states_its_costs_and_limits_and_the_configuration_its_cut():
    """The published shapes through ``perf/costs.py`` and ``perf/hc.py``: ISSUE 59's numbers."""
    from perf import reference

    config = load_config(ROOT / f"perf/configs/{CONFIG}.json", CONFIG)
    hf = config["config"]
    assert reference.kinds_of("xing4_0", hf)[:8] == [("dense",)] * 2 + [("sparse",)] * 6
    dense, sparse = costs.layer_params("xing4_0", hf, 0), costs.layer_params("xing4_0", hf, 2)
    assert dense["attn"] == sparse["attn"] == 2_752_512 + 4_718_592 + 2_064_384 + 4_194_304 + 14_680_064 == 28_409_856
    assert dense["dense"] == 688_128 + 99_090_432 and dense["experts"] == 0 and sparse["dense"] == 688_128 + 229_376 + 11_010_048 and sparse["expert"] == 11_010_048
    assert (sparse["experts"], sparse["top_k"], sparse["q_heads"], sparse["kv_heads"], sparse["head_dim"], sparse["hidden"]) == (64, 4, 32, 2, 144, 14336)
    assert costs.layer_param_count("xing4_0", hf, 0) == 128_188_416 and costs.layer_param_count("xing4_0", hf, 2) == 744_980_480
    assert 2 * 128_188_416 + 6 * 744_980_480 == 4_726_259_712  # 9.45 GB, 8.80 GiB
    assert costs.kv_bytes_per_token_layer("xing4_0", hf, 2) == 1152 == (hf["kv_lora_rank"] + hf["qk_rope_head_dim"]) * 2
    assert 4 * sparse["q_heads"] * sparse["head_dim"] == 18_432 <= 2 * 32 * (192 + 128)
    # eight decode rows: ~26 of 64 experts reached a layer, the step bound by bytes
    cost = costs.step_cost("xing4_0", hf, 8, decode_tokens=8, prefill_tokens=0, context_tokens=8 * 256)
    assert 25 < costs.experts_reached(sparse, 8) < 27 and cost["bytes"] / 819e9 > cost["flops"] / 197e12
    # a wrap of a row: the stream read and written and a row of C each way; the three phi 688 KB a wrap... a sub-layer pair
    assert hc.dims(hf) == (4, 3584, 20) and hc.row_bytes(hf) == (2 * 14336 + 2 * 3584) * 2 == 71_680 and hc.phi_bytes(hf) == 14336 * 24 * 2 == 688_128
    nbytes, flops = hc.least(hf, 8 * 16, 16)  # one decode step of eight rows through 8 blocks
    assert nbytes == 128 * 71_680 + 16 * 688_128 and nbytes / 819e9 > 40 * flops / 197e12 and 24e-6 < nbytes / 819e9 < 26e-6  # ~25 us a step
    assert all(f({"hidden_size": 2048}) is None for f in (hc.dims, hc.row_bytes, hc.phi_bytes, hc.row_flops)) and hc.least({"hc_mult": 1, "hidden_size": 8}, 1, 1) is None
    limits = reference.limits(config)
    assert limits["tie_margin"] > 0 and 0 < limits["positions_allowed"] <= 2 and 0 < limits["median_bound"] <= limits["row_bound"] < 0.3
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.is_file():  # the published keys verbatim: every one of the catalog row's, but the depth
        row = next(json.loads(line) for line in catalog.read_text().splitlines() if '"Xing4.0-29B-A4B"' in line)
        assert {k: v for k, v in hf.items() if k != "num_hidden_layers"} == {k: v for k, v in row["config"].items() if k != "num_hidden_layers"}
        assert config["source"] == row["source_url"] and config["published"] == {"num_hidden_layers": row["config"]["num_hidden_layers"]}
    assert config["reduced"] == ["num_hidden_layers"] and hf["num_hidden_layers"] == 8 == config["servers"][0]["num_blocks"]
    assert {"weights", "hyper_connections", "entry_and_exit", "attention", "rotary", "cache", "experts", "tensor_names"} <= set(config["assumed"])
    assert "4,726,259,712" in config["deployment"] and "five v5e servers" in config["deployment"]
    olmoe = load_config(ROOT / "perf/configs/olmoe-1b-7b-span8.json", "olmoe-1b-7b-span8")
    assert config["server_args"] == olmoe["server_args"]  # the default pool, as the cell this one is read beside
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    added = [m for m in real["per_layer"] if m["name"] in READERS]
    assert [m["name"] for m in real["per_layer"][-3:]] == list(READERS) == [m["name"] for m in added]
    assert all(m["workloads"] == [CELL] and m["moves"] == "gap_p50_ms" for m in added) and [m["unit"] for m in added] == ["%", "%", "KiB"]
    assert real["workloads"][-1] == {**real["workloads"][-1], "name": CELL, "config": CONFIG, "traffic": "saturated", "chips": 1}
    assert real["configs"][-1]["name"] == CONFIG and real["configs"][-1]["reduced"] == ["num_hidden_layers"] and len(real["workloads"]) == 12
    assert not any(CELL in m.get("workloads", ()) for m in real["per_layer"] if m["name"] not in READERS)  # no list was touched
    for name in READERS:
        reader = load_reader("layer_metrics", name)
        entry = next(m for m in added if m["name"] == name)
        assert (reader.UNIT, reader.LAYER, reader.MOVES) == (entry["unit"], entry["layer"], entry["moves"])
    assert (ROOT / "petals_tpu/models/xing4_0/block.py").is_file()


def test_tiny_cell_end_to_end_with_a_stream_of_four_rows_on_the_wire(tmp_path):
    """The whole command at a toy size on the CPU on the toy configuration of
    this family: the harness sizes its inputs by the stream (512), the server
    child serves the span through ``Server`` with no flag, the check's
    sessions hold the served rows to the reference (chunks expanded, decode
    rows absorbed), and a traced run prints the counter metric, 2 KiB a row;
    the two shares of the device's time find no capture of a device and are
    left out."""
    from perf import run

    bench = _tiny_bench()
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["per_layer"] += [{**m, "workloads": ["tiny-xing"]} for m in real["per_layer"] if m["name"] in READERS]
    result = run.run_cell(bench, "tiny-xing", 2**31 + 13, 5.0, True, traffic_dir=DATA / "traffic", work_dir=tmp_path, allow_cpu=True)
    detail = result["detail"]
    assert result["correct"] is True and result["failed"] == 0 and detail["sessions_total"] > 0
    assert detail["check"]["repeat_identical"] and detail["recompiled"] == [] and detail["check"]["decode"]["ok"]
    metrics = result["metrics"]
    assert {"hc_stream_kib_per_row", "recompiles_in_window", "decode_batch_mean"} <= set(metrics)
    assert "hc_mix_busy_share" not in metrics and "hc_mix_roofline_share" not in metrics
    assert metrics["hc_stream_kib_per_row"] == {"value": 2.0, "unit": "KiB"}


def test_readers_on_a_hand_made_record_and_a_hand_encoded_capture(tmp_path, monkeypatch):
    busy, roofline, kib = (load_reader("layer_metrics", name) for name in READERS)
    hf = load_config(ROOT / f"perf/configs/{CONFIG}.json", "x")
    peaks = costs.peaks_for("TPU v5 lite")
    keys = ("hc_rows", "batched_steps", "stream_bytes_in", "stream_bytes_out", "batched_tokens", "prefill_tokens")
    start = dict.fromkeys(keys, 7)
    # between the marks: 100 decode steps of 8 lanes through 8 blocks of 2 wraps
    stop = {**start, "hc_rows": 7 + 100 * 8 * 16, "batched_steps": 107, "stream_bytes_in": 7 + 800 * 57_344, "stream_bytes_out": 7 + 800 * 57_344,
            "batched_tokens": 807}
    one = _record([_child(start, stop)], hf, peaks)
    assert kib.read(one) == 56.0
    # ten mixed steps: 7 decoding lanes and a chunk of 100 rows
    mixed = {**start, "hc_rows": 7 + 10 * 107 * 16, "batched_steps": 17, "stream_bytes_in": 7 + 1070 * 57_344, "stream_bytes_out": 7 + 1070 * 57_344,
             "batched_tokens": 77, "prefill_tokens": 1007}
    assert kib.read(_record([_child(start, mixed)], hf, peaks)) == 56.0
    collapsed = {**stop, "stream_bytes_out": 7 + 800 * 14_336}  # a server that summed the rows at its span's edge
    assert kib.read(_record([_child(start, collapsed)], hf, peaks)) == pytest.approx(35.0)
    assert kib.read(_record([_child(start, start)], hf, peaks)) is None  # no row stepped

    from perf.layer_metrics import sparse_attn_roofline_share as sparse

    monkeypatch.setattr(sparse, "RUNS_DIR", tmp_path)  # ``capture`` is that file's: it looks under its own directory
    assert busy.read(one) is None and roofline.read(one) is None  # no capture under the runs' directory
    scope = "jit(paged_decode)/ptu.span.sparse/while/body/closed_call/"
    ops = {10: ("%while.60 = (s32[]) while(...)", None), 11: ("%fusion.31 = f32[24,8,1] fusion(...)", scope + "ptu.hc.coef/dot_general:"),
           12: ("%fusion.3 = f32[4,4,8,1] fusion(...)", scope + "ptu.hc.sinkhorn/while/body/div:"),
           13: ("%moe_hit_experts.11 = f32[16,3584] custom-call(...)", scope + "ptu.moe.hit/pallas_call:"),
           14: ("%fusion.9 = bf16[8,1,14336] fusion(...)", scope + "ptu.hc.mix/concatenate:"),
           15: ("%fusion.12 = bf16[8,32,512] fusion(...)", scope + "ptu.attn.latent_absorb/dot_general:")}
    # the loop holds everything; the coefficients and a round of Sinkhorn overlap (0.1-0.3 s and 0.25-0.55 s), the mix runs 0.05 s;
    # the experts' kernel and the attention are none of the scopes
    events = [(10, 0, 12 * 10**11), (11, 10**11, 2 * 10**11), (12, 25 * 10**10, 3 * 10**11), (13, 6 * 10**11, 10**11), (14, 8 * 10**11, 5 * 10**10),
              (15, 9 * 10**11, 5 * 10**10)]
    stale = tmp_path / "another-cell/trace/child0/plugins/profile/then/host.xplane.pb"
    stale.parent.mkdir(parents=True)
    stale.write_bytes(_capture(ops, [(10, 0, 12 * 10**11)]))
    os.utime(stale, (1, 1))
    assert busy.read(one) is None and roofline.read(one) is None  # a capture in which nothing ran under the scopes
    path = tmp_path / f"{CELL}/trace/child0/plugins/profile/now/host.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(_capture(ops, events))
    assert busy.read(one) == pytest.approx(100 * 0.5 / 1.2)  # of the child's 1.2 busy seconds
    nbytes, flops = hc.least(hf["config"], 100 * 8 * 16, 100 * 16)
    assert nbytes / 819e9 > flops / 197e12  # bound by the bytes
    assert roofline.read(one) == pytest.approx(100 * (nbytes / 819e9) / 0.5) and roofline.read(one) < 100
    assert roofline.read(_record([_child(start, stop)], hf, None)) is None  # off the chip: no peaks
    assert busy.read(_record([{**_child(start, stop), "trace": {}}], hf, peaks)) is None  # the child read no device plane
    assert roofline.read(_record([_child(start, stop)] * 2, {**hf, "servers": hf["servers"] * 2}, peaks)) is None  # a second child that left no capture
    # a family without a stream (every other cell's), a program without the counters (the parent commit), a run without the marks, no child
    kanana = load_config(ROOT / "perf/configs/kanana2-30b-a3b-span6.json", "y")
    assert all(reader.read(_record([_child(start, stop)], kanana, peaks)) is None for reader in (busy, roofline, kib))
    other = {"batched_steps": 5}
    for children in ([_child(other, other)], [{"marks": {}}], [{}], []):
        assert roofline.read(_record(children, hf, peaks)) is None and kib.read(_record(children, hf, peaks)) is None
    for reader in (busy, roofline):
        assert reader.UNIT == "%" and reader.MOVES == "gap_p50_ms" and reader.LAYER == "residual stream (models/xing4_0/block.py)"
