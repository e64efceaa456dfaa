"""CPU tests of what PR 48 adds to the benchmark for the ``qwen3_next`` family
(``perf/reference/qwen3_next.py``, ``perf/weights/qwen3_next.py``,
``perf/linattn.py``, the two readers), at a toy size
(``data/qwen3-next-tiny.json``, which no cell uses: eight layers, three
linear-attention to every full one, 8 of 32 experts held)."""

import json
import os
from pathlib import Path

import numpy as np
import pytest

from perf import costs, linattn, weights
from perf.config import load as load_config
from perf.record import load_reader
from tests.perf.test_keye_vl2_family import _capture, _child, _record

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
LINEAR, FULL = "linear_attention", "full_attention"
KINDS = [LINEAR, LINEAR, LINEAR, FULL] * 2
CONFIG, CELL = "qwen3-next-80b-a3b-span8-ep4", "qwen3next80b-ctx2k"
READERS = ("linattn_state_roofline_share", "moe_chunk_rows_per_routed")


@pytest.fixture()
def tiny(tmp_path):
    """The toy configuration, and the family and block configuration petals_tpu reads from its ``config.json``."""
    from petals_tpu.server.from_pretrained import get_block_config

    config = load_config(DATA / "qwen3-next-tiny.json", "qwen3-next-tiny")
    (tmp_path / "config.json").write_text(json.dumps(config["config"]))
    return config, *get_block_config(str(tmp_path))


def _tiny_bench() -> dict:
    bench = json.loads((DATA / "benchmark-tiny.json").read_text())
    bench["configs"].append({"name": "qwen3-next-tiny", "source": "toy", "file": "tests/perf/data/qwen3-next-tiny.json", "reduced": [], "why": "toy"})
    bench["workloads"].append({"name": "tiny-qwen3next", "config": "qwen3-next-tiny", "traffic": "tiny-closed", "chips": 1, "why": "toy"})
    return bench


def test_reference_agrees_with_the_served_blocks_of_both_kinds(tiny):
    """The plain float32 reference (one position at a time, the held share of
    the experts) against the program's own block code on the weights the
    server child makes, both in float32 on the CPU: 100 positions at once (the
    chunked form over two sub-chunks, the all-experts einsum); then a prompt
    chunk of 70 padded to 128 and decode steps from the state and the keys and
    values it left."""
    import jax
    import jax.numpy as jnp

    from perf import reference

    config, family, cfg = tiny
    assert family.name == "qwen3_next" and family.span_kinds(cfg, 0, 8) == KINDS
    assert reference.kinds_of("qwen3_next", config["config"]) == [(k,) for k in KINDS]
    assert (cfg.num_experts, cfg.num_experts_routed, cfg.first_expert) == (8, 32, 8)
    x = np.random.default_rng(0).standard_normal((100, cfg.hidden_size), dtype=np.float32)
    want, margin, checks = reference.run(config, x)
    # a margin where a held expert stands at the top-4 boundary, infinite elsewhere: both occur
    assert np.isfinite(want).all() and len(set(checks)) == 8 and np.isfinite(margin).any() and (margin > 0).all()
    runs, first = weights.span_params(config, 0, 8, jnp.float32)
    assert first == checks[0] and isinstance(runs, tuple) and [r["wq"].shape[0] for r in runs] == [3, 1, 3, 1]
    assert "conv" in runs[0] and "wqg" in runs[1] and "conv" not in runs[1] and all(r["w1"].shape[1] == 8 for r in runs)
    blocks = [(kind, jax.tree_util.tree_map(lambda leaf: leaf[i], run))
              for kind, run in zip((LINEAR, FULL) * 2, runs) for i in range(run["wq"].shape[0])]

    def close(got):
        return float(np.abs(got - want[: got.shape[0]]).max() / np.abs(want).max())

    with jax.default_matmul_precision("highest"):
        programs = {kind: jax.jit(lambda p, h, kv, pos, n, kind=kind: family.block_apply(p, h, kv, pos, cfg, kind=kind, n_valid=n))
                    for kind in (LINEAR, FULL)}
        hidden = jnp.asarray(x)[None]
        for kind, params in blocks:
            hidden, _ = family.block_apply(params, hidden, None, 0, cfg, kind=kind)
        assert close(np.asarray(hidden[0])) < 1e-4
        state = family.state_for(cfg, LINEAR)
        assert [shape for shape, _ in state] == [(4, 16, 32), (3, 2 * 2 * 16 + 4 * 32)] and family.state_for(cfg, FULL) is None
        caches = [tuple(jnp.ones((1, *shape), dtype or jnp.float32) for shape, dtype in state) if kind == LINEAR  # stale: position 0 clears
                  else tuple(jnp.zeros((1, 128, cfg.num_key_value_heads, cfg.head_dim), jnp.float32) for _ in range(2)) for kind, _ in blocks]
        h = jnp.pad(jnp.asarray(x)[None, :70], ((0, 0), (0, 58), (0, 0)))
        for i, (kind, params) in enumerate(blocks):
            h, caches[i] = programs[kind](params, h, caches[i], jnp.int32(0), jnp.int32(70))
        rows = [np.asarray(h[0, :70])]
        for pos in range(70, 100):
            h = jnp.asarray(x)[None, pos : pos + 1]
            for i, (kind, params) in enumerate(blocks):
                h, caches[i] = programs[kind](params, h, caches[i], jnp.int32(pos), None)
            rows.append(np.asarray(h[0]))
        assert close(np.concatenate(rows)) < 1e-4


@pytest.mark.parametrize("layer", [0, 3])
def test_weights_take_the_layout_the_program_gives_a_checkpoint(tiny, layer):
    """``perf/weights/qwen3_next.py`` ``block_params`` mirrors
    ``models/qwen3_next/block.py`` ``hf_to_block_params`` per kind: the same
    leaves, shapes and elements from the same HF tensors, the fused
    projections taken apart and the zero-centred norms folded alike."""
    config, family, cfg = tiny
    maker, kind = weights.family_of("qwen3_next"), KINDS[layer]
    tensors = maker.layer_tensors(config["config"], layer, weights.Draws(config["weights_seed"]), kind)
    assert all(str(t.dtype) == "bfloat16" for t in tensors.values())
    assert len(tensors) == 4 + 3 * 8 + 3 + (7 if kind == LINEAR else 6)
    assert {f"mlp.experts.{e}.up_proj.weight" for e in range(8, 16)} <= set(tensors) and "mlp.experts.0.up_proj.weight" not in tensors
    assert not np.asarray(tensors["input_layernorm.weight"], np.float32).any()  # zero-centred: 0 scales by 1
    if kind == LINEAR:
        a = np.exp(np.asarray(tensors["linear_attn.A_log"], np.float32))
        assert tensors["linear_attn.conv1d.weight"].shape == (2 * 2 * 16 + 4 * 32, 1, 4) and tensors["linear_attn.in_proj_qkvz.weight"].shape == (320, 128)
        assert (a >= 1).all() and (a <= 16.1).all() and (np.asarray(tensors["linear_attn.norm.weight"], np.float32) == 1).all()
    mine = maker.block_params(config["config"], tensors, kind)
    theirs = family.hf_to_block_params({k: np.asarray(v, np.float32) for k, v in tensors.items()}, cfg, kind)
    assert set(mine) == set(theirs) == set(family.block_param_shapes(cfg, kind))
    for name in theirs:
        assert mine[name].shape == theirs[name].shape == family.block_param_shapes(cfg, kind)[name].shape, name
        assert np.array_equal(np.asarray(mine[name], np.float32), theirs[name]), name
    assert (np.asarray(mine["ln1"], np.float32) == 1).all()


def test_the_family_states_its_costs_and_limits_and_the_configuration_its_cut():
    """The published shapes through ``perf/costs.py`` and ``perf/linattn.py``:
    ISSUE 48's arithmetic."""
    from perf import reference

    config = load_config(ROOT / f"perf/configs/{CONFIG}.json", CONFIG)
    hf = config["config"]
    assert reference.kinds_of("qwen3_next", hf) == [(k,) for k in KINDS] and "layer_types" not in hf
    linear, full = costs.layer_params("qwen3_next", hf, 0), costs.layer_params("qwen3_next", hf, 3)
    # in_proj_qkvz 25.17 M, in_proj_ba 0.13 M, conv 0.03 M, out_proj 8.39 M; q_proj 16.78 M, k and v 1.05 M each, o_proj 8.39 M
    assert linear["attn"] == 2048 * 12288 + 2048 * 64 + 4 * 8192 + 4096 * 2048 == 33_718_272
    assert full["attn"] == 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 == 27_262_976
    assert linear["dense"] == full["dense"] == 2048 * 512 + 3 * 2048 * 512 + 2048 and linear["expert"] == 3_145_728
    assert (linear["experts"], linear["experts_routed"], linear["top_k"]) == (128, 512, 10)
    total = sum(costs.layer_param_count("qwen3_next", hf, i) for i in range(8))
    assert 3.511e9 < total < 3.513e9 and 6.53 < 2 * total / 2**30 < 6.55  # 7.02 GB, 6.54 GiB in bf16
    assert (linear["q_heads"], linear["kv_heads"]) == (0, 0) and (full["q_heads"], full["kv_heads"], full["head_dim"]) == (16, 2, 256)
    assert costs.kv_bytes_per_token_layer("qwen3_next", hf, 0) == 0 and costs.kv_bytes_per_token_layer("qwen3_next", hf, 3) == 2048
    assert 18 < costs.experts_reached(linear, 8) < 20  # of 128 held, what eight tokens reach
    # the state: 2.1 MB a lane a layer, read once and written once a decode row
    assert linattn.state_matrix_bytes(hf) == 32 * 128 * 128 * 4 == 2_097_152 and linattn.one_step_bytes(hf, 8 * 6) == 201_326_592
    falcon = load_config(ROOT / "perf/configs/falcon-40b-span5.json", "x")["config"]
    assert linattn.state_matrix_bytes(falcon) is None and linattn.one_step_bytes(falcon, 48) is None
    limits = reference.limits(config)
    family = reference.family_of("qwen3_next")
    assert (limits["tie_margin"] > 0) == (limits["positions_allowed"] > 0) and limits["positions_allowed"] <= 2
    assert 0 < limits["median_bound"] <= limits["row_bound"] < 0.5 and family.TIE_MARGIN == limits["tie_margin"]
    assert config["server_args"]["batch_lanes"] == 8 and config["server_args"]["batch_max_length"] == 2560
    assert set(config["reduced"]) == {"num_hidden_layers", "num_experts"} and config["published"] == {"num_hidden_layers": 48, "num_experts": 512}
    assert hf["expert_share"] == {"routed": 512, "first": 0} and hf["num_experts"] == 128 and hf["full_attention_interval"] == 4
    # every number of the catalog's row under the same key, but the two in ``reduced``
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.is_file():
        row = next(r for r in map(json.loads, catalog.read_text().splitlines()) if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
        assert config["source"] == row["source_url"]
        assert {k for k, v in row["config"].items() if hf.get(k, "absent") != v} == set(config["reduced"])
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "ctx2k", 1)
    names = [m["name"] for m in bench["per_layer"]]  # found by name: later PRs append after them
    assert names.index(READERS[1]) == names.index(READERS[0]) + 1 > names.index("latent_absorbed_row_share")
    assert all(m["workloads"] == [CELL] for m in bench["per_layer"] if m["name"] in READERS)
    owed = [m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [CELL])]
    assert len(owed) == 36  # the 34 without a list and the two new


def test_tiny_cell_end_to_end_with_a_state_pool_and_an_expert_share(tmp_path):
    """The whole command at a toy size on the CPU on the toy configuration of
    this family: the server child serves a span of both kinds through
    ``Server`` with no flag, the check holds the served rows to the reference,
    and a traced run prints the chunk counters' ratio beside the others; the
    roofline share finds no capture of a device and is left out."""
    from perf import run

    bench = _tiny_bench()
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    added = [m for m in real["per_layer"] if m["name"] in READERS]
    assert {m["moves"] for m in added} == {"gap_p50_ms"}
    shared = [m for m in real["per_layer"] if m["name"] in ("linattn_recurrent_token_share", "state_cache_share", "moe_dense_token_share")]
    bench["per_layer"] += [{**m, "workloads": ["tiny-qwen3next"]} for m in added + shared]
    result = run.run_cell(bench, "tiny-qwen3next", 2**31 + 17, 5.0, True, traffic_dir=DATA / "traffic", work_dir=tmp_path, allow_cpu=True)
    detail = result["detail"]
    assert result["correct"] is True and result["failed"] == 0 and detail["sessions_total"] > 0
    assert detail["check"]["repeat_identical"] and detail["recompiled"] == [] and detail["check"]["decode"]["ok"]
    metrics = result["metrics"]
    assert {"moe_chunk_rows_per_routed", "linattn_recurrent_token_share", "state_cache_share", "moe_dense_token_share",
            "recompiles_in_window", "decode_batch_mean"} <= set(metrics)
    assert "linattn_state_roofline_share" not in metrics
    assert metrics["moe_chunk_rows_per_routed"]["value"] == pytest.approx(8 / (4 * 8 / 32))  # the einsum: 8 held experts a position for 1 routed here
    stats = json.loads((tmp_path / "runs/tiny-qwen3next/child0.json").read_text())["marks"]["window_end"]["stats"]
    assert stats["moe_chunk_rows_computed"] > 0 and stats["linattn_recurrent_tokens"] > 0 and stats["moe_hit_tokens"] > 0


def test_readers_on_a_hand_made_record_and_a_hand_encoded_capture(tmp_path, monkeypatch):
    roofline, ratio = (load_reader("layer_metrics", name) for name in READERS)
    assert (roofline.UNIT, ratio.UNIT) == ("%", "ratio") and roofline.MOVES == ratio.MOVES == "gap_p50_ms"
    assert roofline.LAYER == "linear attention (ops/linear_attention.py)" and ratio.LAYER == "expert dispatch (models/moe.py)"
    # the chunk counters between ``window`` and ``window_end``: 40 chunks of 512 through 8 layers under the einsum
    window = lambda start, stop: {"marks": {"window": {"mono": 0.0, "stats": start}, "window_end": {"mono": 51.0, "stats": stop}}}
    start = {"moe_chunk_rows_computed": 1000, "moe_chunk_rows_routed": 50.0}
    stop = {"moe_chunk_rows_computed": 1000 + 40 * 512 * 128, "moe_chunk_rows_routed": 50.0 + 40 * 512 * 2.5}
    assert ratio.read(_record([window(start, stop)])) == pytest.approx(51.2)
    grouped = {"moe_chunk_rows_computed": 1000 + 40 * 512 * 10, "moe_chunk_rows_routed": stop["moe_chunk_rows_routed"]}
    assert ratio.read(_record([window(start, stop), window(start, grouped)])) == pytest.approx((128 + 10) / 5.0)  # a chain: summed
    # no chunk in the window, a server that holds all it routes over (no counters), a run without the marks, no child
    for children in ([window(start, start)], [window({"batched_steps": 1}, {"batched_steps": 5})], [_child(start, stop)], [{}], []):
        assert ratio.read(_record(children)) is None

    # the roofline share: the decode rows' states between the trace's marks over the scopes' seconds in the capture
    hf = load_config(ROOT / f"perf/configs/{CONFIG}.json", "x")
    peaks = costs.peaks_for("TPU v5 lite")
    from perf.layer_metrics import sparse_attn_roofline_share as sparse

    monkeypatch.setattr(sparse, "RUNS_DIR", tmp_path)  # ``capture`` is that file's: it looks under its own directory
    rows = 200 * 8 * 6  # 200 decode steps of 8 lanes through 6 linear layers
    t0, t1 = {"linattn_recurrent_tokens": 7}, {"linattn_recurrent_tokens": 7 + rows}
    one = _record([_child(t0, t1)], hf, peaks)
    assert roofline.read(one) is None  # no capture under the runs' directory
    scope = "jit(paged_decode)/ptu.span.linear_attention/while/body/closed_call/"
    ops = {10: ("%while.60 = (s32[]) while(...)", None),
           11: ("%multiply_reduce_fusion.22 = f32[8,32,128] fusion(...)", scope + "ptu.linattn.recurrent/reduce_sum:"),
           12: ("%multiply_reduce_fusion.23 = f32[8,32,128] fusion(...)", scope + "ptu.linattn.recurrent/reduce_sum:"),
           13: ("%moe_hit_experts.11 = f32[8,2048] custom-call(...)", scope + "ptu.moe.experts.hit/pallas_call:"),
           14: ("%select_dynamic-update-slice_fusion.4 = f32[6,8,32,128,128] fusion(...)", scope + "ptu.state.write/dynamic_update_slice:"),
           15: ("%fusion.12 = f32[1,32,64,128] fusion(...)", scope + "ptu.linattn.chunk/while/body/dot_general:")}
    # the loop holds everything; two passes that read the state (0.1-0.4 s), the experts' kernel, the pass that writes it (0.7-0.95 s);
    # a chunk's sub-chunks are none of the scopes
    events = [(10, 0, 12 * 10**11), (11, 10**11, 15 * 10**10), (12, 25 * 10**10, 15 * 10**10), (13, 5 * 10**11, 10**11), (14, 7 * 10**11, 25 * 10**10),
              (15, 10**12, 10**11)]
    stale = tmp_path / "another-cell/trace/child0/plugins/profile/then/host.xplane.pb"
    stale.parent.mkdir(parents=True)
    stale.write_bytes(_capture(ops, [(10, 0, 12 * 10**11), (13, 5 * 10**11, 10**11)]))
    os.utime(stale, (1, 1))
    assert roofline.named_seconds(stale) == 0.0 and roofline.read(one) is None  # a capture in which nothing ran under the scopes: zero seconds
    path = tmp_path / f"{CELL}/trace/child0/plugins/profile/now/host.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(_capture(ops, events))
    assert roofline.named_seconds(path) == pytest.approx(0.55)
    need = 2 * 2_097_152 * rows
    assert roofline.read(one) == pytest.approx(100 * (need / 819e9) / 0.55) and 0 < roofline.read(one) <= 100
    assert sparse.NAMES == ("ptu.attn.index_score", "ptu.attn.select", "ptu.attn.sparse_attend")  # the other reader's names are its own again
    assert roofline.read(_record([_child(t0, t1)], hf, None)) is None  # off the chip: no peaks
    assert roofline.read(_record([{**_child(t0, t1), "trace": {}}], hf, peaks)) is None  # the child read no device plane
    assert roofline.read(_record([_child(t0, t1)] * 2, hf, peaks)) is None  # a second child that left no capture
    # a family without a state, a program without the counter (the parent commit), a run without the marks, no child
    falcon = load_config(ROOT / "perf/configs/falcon-40b-span5.json", "y")
    other = {"batched_steps": 5}
    for children in ([_child(other, other)], [{"marks": {}}], [{}], []):
        assert roofline.read(_record(children, hf, peaks)) is None
    assert roofline.read(_record([_child(t0, t1)], falcon, peaks)) is None
    assert (ROOT / "petals_tpu/ops/linear_attention.py").is_file() and (ROOT / "petals_tpu/models/moe.py").is_file()


def test_prove_chunks_at_a_toy_size_passes_and_its_control_stands_apart(tmp_path):
    """perf/prove_chunks.py on the CPU at toy widths: a prompt of 1,536 over
    three mixed steps and 32 decode steps, alone and beside three decoding
    sessions, inside the family's limits. The control (a reference that starts
    its linear layers over at position 512) is what the chip run at the
    published widths must find not correct; at a hidden size of 128 the mixer
    is a small part of a pre-norm residual stream (its gate ``silu(z)`` has
    ``z`` of std 0.23 where the published widths give 0.9), so the toy's
    control lands AT the limits, four orders of magnitude from the served
    rows: a handed-on state is told from a dropped one, which is what the toy
    can show."""
    from perf import prove_chunks

    summary = prove_chunks.prove(_tiny_bench(), "tiny-qwen3next", [2**31 + 19], work_dir=tmp_path, allow_cpu=True)
    assert summary["sessions"] == summary["correct"] == 2, summary
    assert summary["nearest"] < 1e-3 and summary["control_nearest"] > 1e3 * summary["nearest"], summary
