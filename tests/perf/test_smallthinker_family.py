"""CPU tests of what PR 64 adds to the benchmark for the ``smallthinker`` family (``perf/reference/smallthinker.py``,
``perf/weights/smallthinker.py``, ``perf/swa.py``, the four ``swa_*`` readers, ``perf/prove_window.py``, the configuration
and its cell), at a toy size (``data/smallthinker-tiny.json``, which no cell uses: two periods of a full layer without
positions and three rotary layers of window 64, 8 ReGLU experts top 3 routed on the layer's input)."""

import json
import os
from pathlib import Path

import numpy as np
import pytest

from perf import correct, costs, swa, weights
from perf.config import load as load_config
from perf.record import load_reader
from tests.perf.test_keye_vl2_family import _capture, _child, _record

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
READERS = ("swa_pages_idle_share", "swa_kv_held_share", "swa_kv_read_share", "swa_attn_roofline_share")
CONFIG, CELL = "smallthinker-21b-a3b-span12", "smallthinker21b-ctx16k"
EIGHTEEN = ("lane_return_ms reply_wake_ms reply_resume_ms reply_build_ms rpc_send_ms rpc_recv_ms request_handle_ms off_server_ms client_recv_ms "
            "client_finish_ms client_wake_ms client_user_ms client_submit_ms client_build_ms client_turn_ms client_away_ms wire_and_loops_ms "
            "intake_direct_share").split()


@pytest.fixture()
def tiny(tmp_path):
    """The toy configuration, and the family and block configuration petals_tpu reads from its ``config.json``."""
    from petals_tpu.server.from_pretrained import get_block_config

    config = load_config(DATA / "smallthinker-tiny.json", "smallthinker-tiny")
    (tmp_path / "config.json").write_text(json.dumps(config["config"]))
    return config, *get_block_config(str(tmp_path))


def _tiny_bench(file: str = "tests/perf/data/smallthinker-tiny.json") -> dict:
    bench = json.loads((DATA / "benchmark-tiny.json").read_text())
    bench["configs"].append({"name": "smallthinker-tiny", "source": "toy", "file": file, "reduced": [], "why": "toy"})
    bench["workloads"].append({"name": "tiny-smallthinker", "config": "smallthinker-tiny", "traffic": "tiny-closed-long", "chips": 1, "why": "toy"})
    return bench


def test_reference_agrees_with_the_served_block_stateless_and_through_pages_and_one_precision_lower_fails_the_check(tiny):
    """The plain float32 reference against the program's own block code on the weights the server child makes, both in
    float32 on the CPU: the whole sequence at once, then a prompt chunk of 100 padded to 128 and decode steps through one
    lane's pages, past the window of 64. ``judge`` passes those rows under the family's limits and fails the reference
    itself computed with float8 (e4m3) weights and layer inputs; a reference that drops a row's third expert moves every row. The
    reference imports nothing of the program, and its blocked attention is its whole attention."""
    import jax
    import jax.numpy as jnp

    from perf import reference
    from petals_tpu.ops.paged_attention import PagedKV

    config, family, cfg = tiny
    hf = config["config"]
    source = (ROOT / "perf/reference/smallthinker.py").read_text()
    assert "petals_tpu" not in source.split('"""', 2)[2] and "import petals" not in source
    kinds = reference.kinds_of("smallthinker", hf)
    assert family.name == "smallthinker" and kinds == [(("nope", "full"),), (("rope", "sliding"),), (("rope", "sliding"),), (("rope", "sliding"),)] * 2
    x = np.random.default_rng(0).standard_normal((correct.SEQ, 64), dtype=np.float32)
    want, margin, checks = reference.run(config, x)
    assert want.shape == (correct.SEQ, 64) and np.isfinite(want).all() and len(set(checks)) == 8 and (margin >= 0).all()
    trees, first = weights.span_params(config, 0, 8, jnp.float32)
    assert first == checks[0] and len(trees) == 4 and trees[0]["w1"].shape == (1, 8, 64, 32) and trees[1]["gate"].shape == (3, 64, 8)
    blocks = [(kinds[i][0], jax.tree_util.tree_map(lambda leaf, j=j: leaf[j], trees[run])) for i, (run, j) in
              enumerate([(0, 0), (1, 0), (1, 1), (1, 2), (2, 0), (3, 0), (3, 1), (3, 2)])]

    def close(got):
        return float(np.abs(got - want[: got.shape[0]]).max() / np.abs(want).max())

    family_ref, maker = reference.family_of("smallthinker"), weights.family_of("smallthinker")
    with jax.default_matmul_precision("highest"):
        hidden = jnp.asarray(x)[None]
        for kind, params in blocks:
            hidden, _ = family.apply_for(kind)(params, hidden, None, 0, cfg)
        assert close(np.asarray(hidden[0])) < 1e-4
        # a prompt chunk of 100 in a bucket of 128, then 44 decode steps, through one lane's pages of 16
        programs = {kind: jax.jit(lambda p, h, kv, pos, n, kind=kind: family.apply_for(kind)(p, h, kv, pos, cfg, n_valid=n)) for kind in dict.fromkeys(k for k, _ in blocks)}
        tables = jnp.asarray(np.random.default_rng(1).permutation(10).astype(np.int32)[None])
        caches = [tuple(PagedKV(jnp.zeros((10, 16, 2, 16), jnp.float32), tables) for _ in range(2)) for _ in blocks]
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
        # the reference's attention in blocks of rows is its whole attention
        w0 = {k: v.astype(jnp.float32) for k, v in maker.layer_tensors(hf, 1, weights.Draws(config["weights_seed"]), *kinds[1]).items()}
        whole, _ = family_ref.block(hf, w0, jnp.asarray(x[:128]), *kinds[1])
        blocked, _ = family_ref.block(hf, w0, jnp.asarray(x[:128]), *kinds[1], rows=32)
        np.testing.assert_allclose(np.asarray(blocked), np.asarray(whole), atol=1e-5)
        # one precision lower: the reference with float8 weights and layer inputs; and one expert fewer
        f8 = lambda t: jax.lax.reduce_precision(t.astype(jnp.float32), exponent_bits=4, mantissa_bits=3)
        lower = fewer = jnp.asarray(x)
        for index, kind in enumerate(kinds):
            w = maker.layer_tensors(hf, index, weights.Draws(config["weights_seed"]), *kind)
            lower, _ = family_ref.block(hf, {k: f8(v) for k, v in w.items()}, f8(lower), *kind)
            fewer, _ = family_ref.block({**hf, "moe_num_active_primary_experts": 2}, {k: v.astype(jnp.float32) for k, v in w.items()}, fewer, *kind)
    rows = [("prefill" if p < 100 else "decode", p, got[p]) for p in range(64, correct.SEQ)]
    limits = reference.limits(config)
    assert correct.judge(rows, want, margin, limits)["ok"]
    assert not correct.judge([(kind, p, np.asarray(lower)[p]) for kind, p, _ in rows], want, margin, limits)["ok"]
    # a dropped expert moves every row (at these toy widths by less than the limits, which are the published widths': the
    # chip's controls are in PERF.md section 6, PR 64)
    moved = np.abs(np.asarray(fewer) - want).max(-1) / np.abs(want).max(-1)
    assert moved[64:].min() > 20 * (np.abs(got - want).max(-1) / np.abs(want).max(-1))[64:].max()


def test_weights_take_the_layout_the_program_gives_a_checkpoint(tiny):
    config, family, cfg = tiny
    maker = weights.family_of("smallthinker")
    for layer, kind in ((0, ("nope", "full")), (1, ("rope", "sliding"))):
        tensors = maker.layer_tensors(config["config"], layer, weights.Draws(config["weights_seed"]), kind)
        assert all(str(t.dtype) == "bfloat16" for t in tensors.values()) and len(tensors) == 7 + 3 * 8
        mine = maker.block_params(config["config"], tensors, kind)
        theirs = family.hf_to_block_params({k: np.asarray(v, np.float32) for k, v in tensors.items()}, cfg, kind)
        shapes = family.param_shapes_for(cfg, kind)
        assert set(mine) == set(theirs) == set(shapes)
        for name in theirs:
            assert mine[name].shape == theirs[name].shape == shapes[name].shape, name
            assert np.array_equal(np.asarray(mine[name], np.float32), theirs[name]), name
    assert maker.span_tree(config["config"], [(0, "a"), (1, "b")]) == ("a", "b")
    named = load_config(ROOT / f"perf/configs/{CONFIG}.json", CONFIG)["assumed"]["tensor_names"]
    for part in ("self_attn.{q,k,v,o}_proj", "block_sparse_moe.primary_router", "block_sparse_moe.experts.{e}.{gate,up,down}"):
        assert part in named


def test_the_family_states_its_costs_with_a_window_and_its_limits_and_the_configuration_its_cut():
    """The published shapes through ``perf/costs.py`` and ``perf/swa.py``: ISSUE 64's numbers."""
    from perf import reference

    config = load_config(ROOT / f"perf/configs/{CONFIG}.json", CONFIG)
    hf = config["config"]
    assert reference.kinds_of("smallthinker", hf) == [(("nope", "full"),), (("rope", "sliding"),), (("rope", "sliding"),), (("rope", "sliding"),)] * 3
    full, windowed = costs.layer_params("smallthinker", hf, 0), costs.layer_params("smallthinker", hf, 1)
    assert full["attn"] == windowed["attn"] == 2560 * (3584 + 512 + 512) + 3584 * 2560 == 20_971_520 and full["dense"] == 163_840
    assert full["expert"] == 5_898_240 and (full["experts"], full["top_k"], full["q_heads"], full["kv_heads"], full["head_dim"], full["hidden"]) == (64, 6, 28, 4, 128, 2560)
    assert "window" not in full and windowed["window"] == 4096
    assert costs.layer_param_count("smallthinker", hf, 0) == 398_627_840 - 5_120 and 12 * 398_627_840 == 4_783_534_080  # 9.57 GB, 8.91 GiB
    assert costs.kv_bytes_per_token_layer("smallthinker", hf, 1) == 2048
    # sixteen decode rows at 8.4k: ~51 of 64 experts reached a layer, the step bound by bytes; a windowed layer reads its window
    assert 50 < costs.experts_reached(full, 16) < 52
    cost = costs.step_cost("smallthinker", hf, 12, decode_tokens=16, prefill_tokens=0, context_tokens=16 * 8400)
    unwindowed = costs.step_cost("smallthinker", {**hf, "sliding_window_size": 1 << 20}, 12, decode_tokens=16, prefill_tokens=0, context_tokens=16 * 8400)
    assert cost["bytes"] / 819e9 > cost["flops"] / 197e12 and unwindowed["bytes"] - cost["bytes"] == pytest.approx(9 * 16 * (8400 - 4096) * 2048)
    assert 0.009 < cost["bytes"] / 819e9 < 0.014
    # perf/swa.py: nine windowed layers and three full ones; a page of a layer is 128 KiB
    assert swa.layers(hf) == (9, 3) and swa.page_bytes(hf, 64) == 131_072 and swa.layers({"hidden_size": 8}) is None
    nbytes, flops = swa.least(hf, 64, window_pages_in_reach=9 * 16 * 65, kv_bytes_unfreed=12 * 16 * 132 * 131_072, score_pairs=16 * (9 * 4096 + 3 * 8400))
    assert nbytes == (9 * 16 * 65 + 3 * 16 * 132) * 131_072 and flops == 16 * (9 * 4096 + 3 * 8400) * 4 * 28 * 128
    assert nbytes / 819e9 > 30 * flops / 197e12  # decode rows: bound by the bytes
    limits = reference.limits(config)
    # every row is compared and none may be outside, as OLMoE's: a flipped sixth expert moves a row by less than half the row bound
    assert limits["tie_margin"] == 0 == limits["positions_allowed"] and 0 < limits["median_bound"] <= limits["row_bound"] < 0.3
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    cut = ("num_hidden_layers", "rope_layout", "sliding_window_layout")
    if catalog.is_file():  # the published keys verbatim: every one of the catalog row's, but the depth and the two layouts cut to it
        row = next(json.loads(line) for line in catalog.read_text().splitlines() if '"SmallThinker-21BA3B-Instruct"' in line)
        assert {k: v for k, v in hf.items() if k not in cut and k != "model_type"} == {k: v for k, v in row["config"].items() if k not in cut}
        assert config["source"] == row["source_url"] and config["published"] == {k: row["config"][k] for k in cut}
        assert all(hf[k] == row["config"][k][:12] for k in cut[1:])
    assert config["reduced"] == list(cut) and hf["num_hidden_layers"] == 12 == config["servers"][0]["num_blocks"]
    assert {"weights", "router_input", "router", "secondary_experts", "experts", "attention", "pre_norm", "tensor_names", "context"} <= set(config["assumed"])
    args = config["server_args"]
    assert (args["batch_lanes"], args["batch_max_length"], args["inference_max_length"], args["prefill_token_budget"], args["prefix_cache_bytes"]) == (16, 16384, 16384, 2048, 0)
    assert 16 * 256 * 3 * 131_072 + 16 * 97 * 9 * 131_072 < args["attn_cache_bytes"] == int(3.5 * 2**30) < 16 * 256 * 12 * 131_072
    mix = json.loads((ROOT / "perf/traffic/ctx16k.json").read_text())
    assert mix["arrival"] == {"kind": "closed", "clients": 16} and mix["ramp_s"] == 8.0 and mix["prefix"] == {"kind": "none"} and mix["max_length"] is None
    assert mix["prompt"] == {"dist": "uniform", "min": 2048, "max": 14336} and mix["output"] == {"dist": "uniform", "min": 256, "max": 768}
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    added = [m for m in real["per_layer"] if m["name"] in READERS]
    assert [m["name"] for m in added] == list(READERS) and all(m["workloads"] == [CELL] and m["moves"] == "gap_p50_ms" and m["unit"] == "%" for m in added)
    cell = next(w for w in real["workloads"] if w["name"] == CELL)
    assert cell == {**cell, "config": CONFIG, "traffic": "ctx16k", "chips": 1} and len(cell["why"]) <= 200
    entry = next(c for c in real["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == list(cut) and entry["file"] == f"perf/configs/{CONFIG}.json" and len(entry["why"]) <= 200
    # the eighteen round-trip metrics that read nothing in ``kanana2-ctx32k``'s traced slice: the accepted cells, in order, and not this one
    accepted = [w["name"] for w in real["workloads"] if w["name"] != CELL]
    assert len(accepted) == 12 and all(next(m for m in real["per_layer"] if m["name"] == name)["workloads"] == accepted for name in EIGHTEEN)
    assert not any(CELL in m.get("workloads", ()) for m in real["per_layer"] if m["name"] not in READERS)  # no other list was touched
    for name in READERS:
        reader = load_reader("layer_metrics", name)
        entry = next(m for m in added if m["name"] == name)
        assert (reader.UNIT, reader.LAYER, reader.MOVES) == (entry["unit"], entry["layer"], entry["moves"])
    assert (ROOT / "petals_tpu/models/smallthinker/block.py").is_file()


def test_tiny_cell_end_to_end_with_pages_that_go_back(tmp_path):
    """The whole command at a toy size on the CPU on the toy configuration of this family: the server child serves the span
    through ``Server`` with no flag, the check's sessions hold the served rows to the reference, and a traced run prints
    the three counter metrics; the share of the device's time finds no capture of a device and is left out."""
    from perf import run

    bench = _tiny_bench()
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["per_layer"] += [{**m, "workloads": ["tiny-smallthinker"]} for m in real["per_layer"] if m["name"] in READERS]
    result = run.run_cell(bench, "tiny-smallthinker", 2**31 + 17, 5.0, True, traffic_dir=DATA / "traffic", work_dir=tmp_path, allow_cpu=True)
    detail = result["detail"]
    assert result["correct"] is True and result["failed"] == 0 and detail["sessions_total"] > 0
    assert detail["check"]["repeat_identical"] and detail["recompiled"] == [] and detail["check"]["decode"]["ok"]
    metrics = result["metrics"]
    assert {"swa_pages_idle_share", "swa_kv_held_share", "swa_kv_read_share", "recompiles_in_window", "decode_batch_mean"} <= set(metrics)
    assert "swa_attn_roofline_share" not in metrics
    assert metrics["swa_pages_idle_share"]["value"] == 0.0 and 0 < metrics["swa_kv_held_share"]["value"] <= 100 and 0 < metrics["swa_kv_read_share"]["value"] < 100


def test_readers_on_a_hand_made_record_and_a_hand_encoded_capture(tmp_path, monkeypatch):
    idle, held, read, roofline = (load_reader("layer_metrics", name) for name in READERS)
    hf = load_config(ROOT / f"perf/configs/{CONFIG}.json", "x")
    peaks = costs.peaks_for("TPU v5 lite")
    keys = ("window_pages_held", "window_pages_in_reach", "kv_bytes_held", "kv_bytes_unfreed", "attn_score_pairs", "attn_pages_gathered", "attn_pages_tabled")
    start = dict.fromkeys(keys, 7)
    # between the marks: 100 decode steps of 16 lanes at a context of 8,448 (132 pages): 65 pages in reach a windowed layer
    page = 131_072
    stop = {"window_pages_held": 7 + 100 * 16 * 9 * 65, "window_pages_in_reach": 7 + 100 * 16 * 9 * 65,
            "kv_bytes_held": 7 + 100 * 16 * (3 * 132 + 9 * 65) * page, "kv_bytes_unfreed": 7 + 100 * 16 * 12 * 132 * page,
            "attn_score_pairs": 7 + 100 * 16 * (3 * 8448 + 9 * 4096), "attn_pages_gathered": 7 + 100 * 16 * (3 * 132 + 9 * 65),
            "attn_pages_tabled": 7 + 100 * 16 * 12 * 256}
    one = _record([_child(start, stop)], hf, peaks)
    assert idle.read(one) == 0.0 and held.read(one) == pytest.approx(100 * (3 * 132 + 9 * 65) / (12 * 132))
    assert read.read(one) == pytest.approx(100 * (3 * 132 + 9 * 65) / (12 * 256))
    unfreed = {**stop, "window_pages_held": 7 + 100 * 16 * 9 * 132, "kv_bytes_held": stop["kv_bytes_unfreed"]}  # a pool that frees nothing
    assert idle.read(_record([_child(start, unfreed)], hf, peaks)) == pytest.approx(100 * (1 - 65 / 132)) and held.read(_record([_child(start, unfreed)], hf, peaks)) == 100.0
    assert all(reader.read(_record([_child(start, start)], hf, peaks)) is None for reader in (idle, held, read))  # no step

    from perf.layer_metrics import sparse_attn_roofline_share as sparse

    monkeypatch.setattr(sparse, "RUNS_DIR", tmp_path)  # ``capture`` is that file's: it looks under its own directory
    assert roofline.read(one) is None  # no capture under the runs' directory
    scope = "jit(paged_decode)/ptu.span.rope-sliding/while/body/closed_call/"
    ops = {10: ("%while.60 = (s32[]) while(...)", None), 11: ("%paged_decode_walk.3 = bf16[16,28,128] custom-call(...)", scope + "ptu.attn.window/ptu.attn.paged_decode/pallas_call:"),
           12: ("%fusion.3 = bf16[16,1,3584] fusion(...)", "jit(paged_decode)/ptu.span.nope-full/while/body/closed_call/ptu.attn.full/dot_general:"),
           13: ("%moe_hit_experts.11 = f32[16,2560] custom-call(...)", scope + "ptu.moe.experts.hit/pallas_call:"),
           14: ("%fusion.9 = bf16[16,1,2560] fusion(...)", scope + "ptu.moe.router/dot_general:")}
    # the loop holds everything; the windowed walk and the full layer's attention overlap (0.1-0.3 s and 0.25-0.55 s); the experts' kernel
    # and the router are none of the scopes
    events = [(10, 0, 12 * 10**11), (11, 10**11, 2 * 10**11), (12, 25 * 10**10, 3 * 10**11), (13, 6 * 10**11, 10**11), (14, 8 * 10**11, 5 * 10**10)]
    path = tmp_path / f"{CELL}/trace/child0/plugins/profile/now/host.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(_capture(ops, events))
    nbytes, flops = swa.least(hf["config"], 64, 100 * 16 * 9 * 65, 100 * 16 * 12 * 132 * page, 100 * 16 * (3 * 8448 + 9 * 4096))
    assert nbytes / 819e9 > flops / 197e12  # bound by the bytes
    assert roofline.read(one) == pytest.approx(100 * (nbytes / 819e9) / 0.45) and roofline.read(one) < 100
    assert roofline.read(_record([_child(start, stop)], hf, None)) is None  # off the chip: no peaks
    # a family without windowed layers (every other cell's), a program without the counters (the parent commit), a run without the marks, no child
    kanana = load_config(ROOT / "perf/configs/kanana2-30b-a3b-span6.json", "y")
    assert roofline.read(_record([_child(start, stop)], kanana, peaks)) is None and read.read(_record([_child(start, stop)], kanana, peaks)) is None
    other = {"batched_steps": 5}
    for children in ([_child(other, other)], [{"marks": {}}], [{}], []):
        assert all(reader.read(_record(children, hf, peaks)) is None for reader in (idle, held, read, roofline))
    assert roofline.LAYER == "kernels (ops/)" and idle.LAYER == held.LAYER == "batcher (server/batching.py)" and read.LAYER == "attention dispatch (ops/paged_attention.py)"


def test_prove_window_at_a_toy_size_passes_with_both_controls_apart_and_a_server_that_ignores_the_window_reads_under_one(tmp_path, monkeypatch):
    """perf/prove_window.py on the CPU at toy widths: a prompt of 256 fresh rows (four windows of 64) in mixed steps of 64
    beside two short sessions, then 32 decode steps: the served rows agree with the reference, pages went back while the
    session ran and held was in reach, and both controls (the whole context; a ring half as long) are several times
    further from the served rows than the reference is. The same script over a server whose windowed layers attend to
    the whole context (the toy configuration with a window no session reaches the end of) reads under 1 against that
    control, and does not pass. Under two windows of rows nothing would be proved, and the script says so."""
    from perf import prove_window

    summary = prove_window.prove(_tiny_bench(), "tiny-smallthinker", 2**31 + 19, 256, work_dir=tmp_path / "a", allow_cpu=True)
    assert summary["passed"] and summary["correct"] and summary["released"] and summary["held_is_in_reach"]
    assert summary["window_pages_released"] >= 12 and summary["mixed_steps"] >= 4  # of the long session's 18 pages a windowed group
    assert summary["whole_context_ratio"] > 10 and summary["half_window_ratio"] > 10 and prove_window.MIN_RATIO == 1.5
    with pytest.raises(SystemExit, match="under two windows"):
        prove_window.prove(_tiny_bench(), "tiny-smallthinker", 1, 96, work_dir=tmp_path / "b", allow_cpu=True)
    # a server that ignores the window, held to the window the model publishes
    toy = json.loads((DATA / "smallthinker-tiny.json").read_text())
    ignoring = tmp_path / "ignoring.json"
    ignoring.write_text(json.dumps({**toy, "sliding_window_size": 4096}))
    published = prove_window.variants
    monkeypatch.setattr(prove_window, "variants", lambda hf, positions: published({**hf, "sliding_window_size": 64}, positions))
    wrong = prove_window.prove(_tiny_bench(str(ignoring)), "tiny-smallthinker", 2**31 + 19, 256, work_dir=tmp_path / "c", allow_cpu=True)
    assert not wrong["passed"] and wrong["whole_context_ratio"] < 1 and not wrong["released"]
