"""CPU tests of what PR 52 adds to the benchmark for the ``jamba`` family
(``perf/reference/jamba.py``, ``perf/weights/jamba.py``, ``perf/ssm.py``, the
three readers), at a toy size (``data/jamba-tiny.json``, which no cell uses:
four layers, a mamba and an attention layer in turns, one kv head)."""

import json
import os
from pathlib import Path

import numpy as np
import pytest

from perf import costs, ssm, weights
from perf.config import load as load_config
from perf.record import load_reader
from tests.perf.test_keye_vl2_family import _capture, _child, _record

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
MAMBA, ATTENTION = "mamba", "attention"
KINDS = [MAMBA, ATTENTION] * 2
CONFIG, CELL = "jamba2-3b-span28", "jamba2-3b-ctx2k"
READERS = ("ssm_scan_roofline_share", "ssm_chunk_busy_share", "ssm_one_step_row_share")


@pytest.fixture()
def tiny(tmp_path):
    """The toy configuration, and the family and block configuration petals_tpu reads from its ``config.json``."""
    from petals_tpu.server.from_pretrained import get_block_config

    config = load_config(DATA / "jamba-tiny.json", "jamba-tiny")
    (tmp_path / "config.json").write_text(json.dumps(config["config"]))
    return config, *get_block_config(str(tmp_path))


def _tiny_bench() -> dict:
    bench = json.loads((DATA / "benchmark-tiny.json").read_text())
    bench["configs"].append({"name": "jamba-tiny", "source": "toy", "file": "tests/perf/data/jamba-tiny.json", "reduced": [], "why": "toy"})
    bench["workloads"].append({"name": "tiny-jamba", "config": "jamba-tiny", "traffic": "tiny-closed", "chips": 1, "why": "toy"})
    return bench


def test_reference_agrees_with_the_served_blocks_of_both_kinds(tiny):
    """The plain float32 reference (one position at a time) against the
    program's own block code on the weights the server child makes, both in
    float32 on the CPU: 100 positions at once (the chunked form of the scan);
    then a prompt chunk of 70 padded to 128 and decode steps from the state and
    the keys and values it left."""
    import jax
    import jax.numpy as jnp

    from perf import reference

    config, family, cfg = tiny
    assert family.name == "jamba" and family.span_kinds(cfg, 0, 4) == KINDS
    assert reference.kinds_of("jamba", config["config"]) == [(k,) for k in KINDS]
    x = np.random.default_rng(0).standard_normal((100, cfg.hidden_size), dtype=np.float32)
    want, margin, checks = reference.run(config, x)
    assert np.isfinite(want).all() and len(set(checks)) == 4 and np.isinf(margin).all()  # nothing routes
    runs, first = weights.span_params(config, 0, 4, jnp.float32)
    assert first == checks[0] and isinstance(runs, tuple) and [r["ln1"].shape[0] for r in runs] == [1, 1, 1, 1]
    assert "conv" in runs[0] and "wq" in runs[1] and "conv" not in runs[1] and runs[0]["a_log"].shape == (1, 16, 256)
    blocks = [(kind, jax.tree_util.tree_map(lambda leaf: leaf[0], run)) for kind, run in zip(KINDS, runs)]

    def close(got):
        return float(np.abs(got - want[: got.shape[0]]).max() / np.abs(want).max())

    with jax.default_matmul_precision("highest"):
        programs = {kind: jax.jit(lambda p, h, kv, pos, n, kind=kind: family.block_apply(p, h, kv, pos, cfg, kind=kind, n_valid=n))
                    for kind in (MAMBA, ATTENTION)}
        hidden = jnp.asarray(x)[None]
        for kind, params in blocks:
            hidden, _ = family.block_apply(params, hidden, None, 0, cfg, kind=kind)
        assert close(np.asarray(hidden[0])) < 1e-4
        state = family.state_for(cfg, MAMBA)
        assert [shape for shape, _ in state] == [(16, 256), (3, 256)] and family.state_for(cfg, ATTENTION) is None
        caches = [tuple(jnp.ones((1, *shape), dtype or jnp.float32) for shape, dtype in state) if kind == MAMBA  # stale: position 0 clears
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


def test_a_reference_without_the_scan_s_term_is_another_function(tiny):
    """The control the chip run makes at the published widths
    (benchmarks/prove_scan_matters.py, where it lands 3 times outside the
    median bound): with ``y = D u`` in place of the scan's output the
    reference is another function, and the flag leaves nothing behind. At a
    hidden size of 128 a mixer of weights of std 0.02 is a small part of the
    residual stream (``in_proj`` gives ``u`` a std of 0.23 where the published
    widths give 1.0), so the toy shows the difference and not its size."""
    from benchmarks.prove_scan_matters import reference_float8, rows_of
    from perf import correct, reference

    config, _, _ = tiny
    x = np.random.default_rng(1).standard_normal((64, 128), dtype=np.float32)
    want, margin, _ = reference.run(config, x)
    family = reference.family_of("jamba")
    family.DROP_STATE_TERM = True
    try:
        without, _, _ = reference.run(config, x)
    finally:
        family.DROP_STATE_TERM = False
    again, _, _ = reference.run(config, x)
    assert np.array_equal(again, want)
    off = np.abs(without - want).max(-1) / np.abs(want).max(-1)
    assert 2e-4 < np.median(off) < 1e-2, np.median(off)
    # one precision lower, judged as the script judges it: far outside the limits even here
    verdict = correct.judge(rows_of(reference_float8(config, x)), want, margin, reference.limits(config))
    assert not verdict["ok"] and verdict["decode"]["rows"] == 16 and verdict["prefill"]["median"] > reference.limits(config)["median_bound"]


@pytest.mark.parametrize("layer", [0, 1])
def test_weights_take_the_layout_the_program_gives_a_checkpoint(tiny, layer):
    """``perf/weights/jamba.py`` ``block_params`` mirrors
    ``models/jamba/block.py`` ``hf_to_block_params`` per kind: the same leaves,
    shapes and elements from the same HF tensors, ``A_log`` turned to the
    state's layout alike."""
    config, family, cfg = tiny
    maker, kind = weights.family_of("jamba"), KINDS[layer]
    tensors = maker.layer_tensors(config["config"], layer, weights.Draws(config["weights_seed"]), kind)
    assert all(str(t.dtype) == "bfloat16" for t in tensors.values())
    assert len(tensors) == 5 + (12 if kind == MAMBA else 4)
    assert (np.asarray(tensors["input_layernorm.weight"], np.float32) == 1).all()
    if kind == MAMBA:
        a = np.exp(np.asarray(tensors["mamba.A_log"], np.float32))
        assert tensors["mamba.A_log"].shape == (256, 16) and np.allclose(a, np.arange(1, 17), rtol=2e-2) and (a[0] == a[-1]).all()
        assert tensors["mamba.conv1d.weight"].shape == (256, 1, 4) and tensors["mamba.in_proj.weight"].shape == (512, 128)
        assert (np.asarray(tensors["mamba.D"], np.float32) == 1).all() and not np.asarray(tensors["mamba.conv1d.bias"], np.float32).any()
        dt = np.log1p(np.exp(np.asarray(tensors["mamba.dt_proj.bias"], np.float32)))
        assert 0.0009 < dt.min() < 0.002 and 0.05 < dt.max() < 0.11  # a step log-uniform in 0.001-0.1
    mine = maker.block_params(config["config"], tensors, kind)
    theirs = family.hf_to_block_params({k: np.asarray(v, np.float32) for k, v in tensors.items()}, cfg, kind)
    assert set(mine) == set(theirs) == set(family.block_param_shapes(cfg, kind))
    for name in theirs:
        assert mine[name].shape == theirs[name].shape == family.block_param_shapes(cfg, kind)[name].shape, name
        assert np.array_equal(np.asarray(mine[name], np.float32), theirs[name]), name


def test_the_family_states_its_costs_and_limits_and_the_configuration_cuts_nothing():
    """The published shapes through ``perf/costs.py`` and ``perf/ssm.py``:
    ISSUE 52's arithmetic."""
    from perf import reference

    config = load_config(ROOT / f"perf/configs/{CONFIG}.json", CONFIG)
    hf = config["config"]
    kinds = [k for (k,) in reference.kinds_of("jamba", hf)]
    assert [i for i, k in enumerate(kinds) if k == ATTENTION] == [7, 21] and len(kinds) == 28
    mamba, attention = costs.layer_params("jamba", hf, 0), costs.layer_params("jamba", hf, 7)
    # in_proj 26.21 M, x_proj 0.98 M, dt_proj 0.82 M, out_proj 13.11 M, the conv's taps and A_log 0.10 M; q and o 6.55 M each, k and v 0.33 M each
    assert mamba["attn"] == 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560 + 5120 * 20 == 41_226_240
    assert attention["attn"] == 2 * 2560 * 2560 + 2 * 2560 * 128 == 13_762_560
    assert mamba["dense"] == attention["dense"] == 3 * 2560 * 8192 == 62_914_560 and mamba["experts"] == mamba["expert"] == 0
    total = sum(costs.layer_param_count("jamba", hf, i) for i in range(28))
    assert total == 26 * 104_140_800 + 2 * 76_677_120 == 2_861_015_040 and 0.35 < 2 * total / 16e9 < 0.36  # 5.72 GB in bf16: 35.8% of a chip
    assert (mamba["q_heads"], mamba["kv_heads"]) == (0, 0) and (attention["q_heads"], attention["kv_heads"], attention["head_dim"]) == (20, 1, 128)
    assert costs.kv_bytes_per_token_layer("jamba", hf, 0) == 0 and costs.kv_bytes_per_token_layer("jamba", hf, 7) == 512
    # the state: 328 KB a lane a layer, read once and written once a decode row
    assert ssm.state_bytes(hf) == 16 * 5120 * 4 == 327_680 and ssm.one_step_bytes(hf, 8 * 26) == 136_314_880
    falcon = load_config(ROOT / "perf/configs/falcon-40b-span5.json", "x")["config"]
    assert ssm.state_bytes(falcon) is None and ssm.one_step_bytes(falcon, 48) is None
    limits = reference.limits(config)
    assert limits["tie_margin"] == 0 and limits["positions_allowed"] == 0 and 0 < limits["median_bound"] <= limits["row_bound"] < 0.5
    assert config["server_args"]["batch_lanes"] == 8 and config["server_args"]["batch_max_length"] == 2560
    assert config["server_args"]["num_blocks"] == 28 and config["servers"] == [{"first_block": 0, "num_blocks": 28}]
    assert config["reduced"] == [] and set(config["assumed"]) >= {"layer_types", "head_dim", "positions", "feed_forward", "norms", "state_layout", "weights"}
    # every key of the catalog's row under the same key with the same value: nothing is cut
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.is_file():
        row = next(r for r in map(json.loads, catalog.read_text().splitlines()) if r["name"] == "AI21-Jamba2-3B")
        assert config["source"] == row["source_url"] and hf == row["config"]
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)  # found by name: later PRs append after them
    assert entry["reduced"] == [] and entry["file"] == f"perf/configs/{CONFIG}.json" and entry["source"] == config["source"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "ctx2k", 1)
    names = [m["name"] for m in bench["per_layer"]]
    assert [names.index(r) for r in READERS] == list(range(names.index(READERS[0]), names.index(READERS[0]) + 3))
    assert names.index(READERS[0]) > names.index("moe_chunk_rows_per_routed")
    assert all(m["workloads"] == [CELL] and m["moves"] == "gap_p50_ms" for m in bench["per_layer"] if m["name"] in READERS)
    owed = [m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [CELL])]
    assert len(owed) == 37  # the 34 without a list and the three new


def test_tiny_cell_end_to_end_with_a_state_pool_under_one_kv_head(tmp_path):
    """The whole command at a toy size on the CPU on the toy configuration of
    this family: the server child serves a span of both kinds through
    ``Server`` with no flag, the check holds the served rows to the reference,
    and a traced run prints the counters' share beside the others; the two
    readers of a device's capture find none and are left out."""
    from perf import run

    bench = _tiny_bench()
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    added = [m for m in real["per_layer"] if m["name"] in READERS]
    assert len(added) == 3 and {m["layer"] for m in added} == {"selective scan (ops/selective_scan.py)"}
    shared = [m for m in real["per_layer"] if m["name"] in ("linattn_recurrent_token_share", "state_cache_share")]
    bench["per_layer"] += [{**m, "workloads": ["tiny-jamba"]} for m in added + shared]
    result = run.run_cell(bench, "tiny-jamba", 2**31 + 23, 5.0, True, traffic_dir=DATA / "traffic", work_dir=tmp_path, allow_cpu=True)
    detail = result["detail"]
    assert result["correct"] is True and result["failed"] == 0 and detail["sessions_total"] > 0
    assert detail["check"]["repeat_identical"] and detail["recompiled"] == [] and detail["check"]["decode"]["ok"]
    metrics = result["metrics"]
    assert {"ssm_one_step_row_share", "linattn_recurrent_token_share", "state_cache_share", "recompiles_in_window", "decode_batch_mean"} <= set(metrics)
    assert metrics["ssm_one_step_row_share"]["value"] == pytest.approx(metrics["linattn_recurrent_token_share"]["value"])
    assert 0 < metrics["ssm_one_step_row_share"]["value"] <= 100 and 0 < metrics["state_cache_share"]["value"] < 100
    assert "ssm_scan_roofline_share" not in metrics and "ssm_chunk_busy_share" not in metrics  # no device, no capture
    stats = json.loads((tmp_path / "runs/tiny-jamba/child0.json").read_text())["marks"]["window_end"]["stats"]
    assert stats["linattn_recurrent_tokens"] > 0 and stats["linattn_chunk_tokens"] > 0 and stats["linattn_kernel_tokens"] == 0


def test_readers_on_a_hand_made_record_and_a_hand_encoded_capture(tmp_path, monkeypatch):
    roofline, busy, share = (load_reader("layer_metrics", name) for name in READERS)
    assert {r.UNIT for r in (roofline, busy, share)} == {"%"} and {r.MOVES for r in (roofline, busy, share)} == {"gap_p50_ms"}
    assert {r.LAYER for r in (roofline, busy, share)} == {"selective scan (ops/selective_scan.py)"}
    hf = load_config(ROOT / f"perf/configs/{CONFIG}.json", "x")
    falcon = load_config(ROOT / "perf/configs/falcon-40b-span5.json", "y")
    peaks = costs.peaks_for("TPU v5 lite")
    # the counters between the trace's marks: 200 decode steps of 8 lanes, and 5 chunks of 512, through 26 state layers
    rows = 200 * 8 * 26
    t0 = {"linattn_recurrent_tokens": 7, "linattn_chunk_tokens": 11}
    t1 = {"linattn_recurrent_tokens": 7 + rows, "linattn_chunk_tokens": 11 + 5 * 512 * 26}
    one = _record([_child(t0, t1)], hf, peaks)
    assert share.read(one) == pytest.approx(100 * rows / (rows + 5 * 512 * 26))
    no_chunk = {**t1, "linattn_chunk_tokens": 11}
    assert share.read(_record([_child(t0, no_chunk)], hf, peaks)) == 100.0  # a slice with no chunk
    assert share.read(_record([_child(t0, t1), _child(t0, no_chunk)], hf, peaks)) == pytest.approx(100 * 2 * rows / (2 * rows + 5 * 512 * 26))
    # a window without a row, a program from before the counters, a run without the marks, no child, a family without such a state
    for children in ([_child(t0, t0)], [_child({"batched_steps": 1}, {"batched_steps": 5})], [{"marks": {}}], [{}], []):
        assert share.read(_record(children, hf, peaks)) is None
    assert share.read(_record([_child(t0, t1)], falcon, peaks)) is None and share.read(_record([_child(t0, t1)])) is None

    from perf.layer_metrics import sparse_attn_roofline_share as sparse

    monkeypatch.setattr(sparse, "RUNS_DIR", tmp_path)  # ``capture`` is that file's: it looks under its own directory
    assert roofline.read(one) is None and busy.read(one) is None  # no capture under the runs' directory
    scope = "jit(paged_mixed_step)/ptu.span.mamba/while/body/closed_call/"
    ops = {10: ("%while.60 = (s32[]) while(...)", None),
           11: ("%fusion.660 = f32[8,5120] fusion(...)", scope + "ptu.ssm.step/reduce_sum:"),
           12: ("%multiply_convert_fusion.6 = bf16[8,1,5120] fusion(...)", scope + "ptu.ssm.conv/ptu.linattn.conv/mul:"),
           13: ("%fusion.662 = bf16[8,8192] fusion(...)", scope + "dot_general:"),
           14: ("%select_dynamic-update-slice_fusion.6 = f32[26,8,16,5120] fusion(...)", scope + "ptu.state.write/dynamic_update_slice:"),
           15: ("%constant_dynamic-update-slice_fusion.64 = f32[8,1,5120] fusion(...)", scope + "ptu.ssm.chunk/while/body/reduce_sum:")}
    # the loop holds everything; the pass that reads the state (0.1-0.25 s), the conv, a matmul, the pass that writes it (0.7-0.95 s);
    # a chunk's positions (1.0-1.3 s) are none of the one-step form's scopes, and the only ones of the chunked form's
    events = [(10, 0, 15 * 10**11), (11, 10**11, 15 * 10**10), (12, 3 * 10**11, 10**11), (13, 5 * 10**11, 10**11), (14, 7 * 10**11, 25 * 10**10),
              (15, 10**12, 3 * 10**11)]
    no_chunk_events = [e for e in events if e[0] != 15]
    stale = tmp_path / "another-cell/trace/child0/plugins/profile/then/host.xplane.pb"
    stale.parent.mkdir(parents=True)
    stale.write_bytes(_capture(ops, no_chunk_events))
    os.utime(stale, (1, 1))
    need = 2 * 327_680 * rows
    assert roofline.named_seconds(stale) == pytest.approx(0.4) and roofline.read(one) == pytest.approx(100 * (need / 819e9) / 0.4)
    assert busy.read(one) == 0.0  # a slice with no chunk: a number, not None
    path = tmp_path / f"{CELL}/trace/child0/plugins/profile/now/host.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(_capture(ops, events))
    assert roofline.named_seconds(path) == pytest.approx(0.4) and roofline.named_seconds(path, busy.NAMES) == pytest.approx(0.3)
    assert roofline.read(one) == pytest.approx(100 * (need / 819e9) / 0.4) and 0 < roofline.read(one) <= 100
    assert busy.read(one) == pytest.approx(100 * 0.3 / 1.2)  # over the child's busy seconds (``_child``: 1.2)
    assert sparse.NAMES == ("ptu.attn.index_score", "ptu.attn.select", "ptu.attn.sparse_attend")  # the other reader's names are its own again
    assert roofline.read(_record([_child(t0, t1)], hf, None)) is None  # off the chip: no peaks
    for reader in (roofline, busy):
        assert reader.read(_record([{**_child(t0, t1), "trace": {}}], hf, peaks)) is None  # the child read no device plane
        assert reader.read(_record([_child(t0, t1)] * 2, hf, peaks)) is None  # a second child that left no capture
        assert reader.read(_record([_child(t0, t1)], falcon, peaks)) is None  # a family without such a state
        assert reader.read(_record([], hf, peaks)) is None
    # a program without the counter (the parent commit), a run without the marks
    other = {"batched_steps": 5}
    for children in ([_child(other, other)], [{"marks": {}}], [{}]):
        assert roofline.read(_record(children, hf, peaks)) is None
    assert (ROOT / "petals_tpu/ops/selective_scan.py").is_file()


def test_prove_chunks_at_a_toy_size_passes_and_its_control_stands_apart(tmp_path):
    """perf/prove_chunks.py on the CPU at toy widths: a prompt of 1,536 over
    three mixed steps and 32 decode steps, alone and beside three decoding
    sessions, inside the family's limits; the control (a reference that
    starts its mamba layers over at position 512: ``layer_params`` gives them
    no kv heads) stands orders of magnitude farther from the served rows."""
    from perf import prove_chunks

    summary = prove_chunks.prove(_tiny_bench(), "tiny-jamba", [2**31 + 29], work_dir=tmp_path, allow_cpu=True)
    assert summary["sessions"] == summary["correct"] == 2, summary
    assert summary["nearest"] < 1e-3 and summary["control_nearest"] > 1e3 * summary["nearest"], summary
