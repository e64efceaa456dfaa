"""CPU tests of what PR 35 adds to the benchmark for the ``olmo_hybrid`` family
(``perf/reference/olmo_hybrid.py``, ``perf/weights/olmo_hybrid.py``, the two
state readers), at a toy size (``data/olmo-hybrid-tiny.json``, which no cell
uses: eight layers, three linear-attention to every full one)."""

import json
from pathlib import Path

import numpy as np
import pytest

from perf import costs, weights
from perf.config import load as load_config
from perf.record import Record, load_reader

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
LINEAR, FULL = "linear_attention", "full_attention"
KINDS = [LINEAR, LINEAR, LINEAR, FULL] * 2


@pytest.fixture()
def tiny(tmp_path):
    """The toy configuration, and the family and block configuration petals_tpu reads from its ``config.json``."""
    from petals_tpu.server.from_pretrained import get_block_config

    config = load_config(DATA / "olmo-hybrid-tiny.json", "olmo-hybrid-tiny")
    (tmp_path / "config.json").write_text(json.dumps(config["config"]))
    return config, *get_block_config(str(tmp_path))


def test_reference_agrees_with_the_served_blocks_of_both_kinds(tiny):
    """The plain float32 reference (one position at a time) against the
    program's own block code on the weights the server child makes, both in
    float32 on the CPU: 1e-4. 100 positions at once, which is the chunked
    form over two sub-chunks; then a prompt chunk of 70 padded to 128 and
    decode steps from the state and the keys and values it left."""
    import jax
    import jax.numpy as jnp

    from perf import reference

    config, family, cfg = tiny
    assert family.name == "olmo_hybrid" and family.span_kinds(cfg, 0, 8) == KINDS
    assert reference.kinds_of("olmo_hybrid", config["config"]) == [(k,) for k in KINDS]
    x = np.random.default_rng(0).standard_normal((100, cfg.hidden_size), dtype=np.float32)
    want, margin, checks = reference.run(config, x)
    assert np.isinf(margin).all() and np.isfinite(want).all() and len(set(checks)) == 8  # nothing routes
    runs, first = weights.span_params(config, 0, 8, jnp.float32)
    assert first == checks[0] and isinstance(runs, tuple) and [r["wq"].shape[0] for r in runs] == [3, 1, 3, 1]
    assert "conv" in runs[0] and "q_norm" in runs[1] and "conv" not in runs[1]
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
        # a prompt chunk of 70 in a bucket of 128, then 30 decode steps, the cache a kind as the family declares it
        state = family.state_for(cfg, LINEAR)
        assert [shape for shape, _ in state] == [(4, 16, 32), (3, 4 * (16 + 16 + 32))] and family.state_for(cfg, FULL) is None
        caches = [tuple(jnp.ones((1, *shape), dtype or jnp.float32) for shape, dtype in state) if kind == LINEAR  # stale: position 0 clears
                  else tuple(jnp.zeros((1, 128, cfg.cache_kv_heads, cfg.head_dim), jnp.float32) for _ in range(2)) for kind, _ in blocks]
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
    """``perf/weights/olmo_hybrid.py`` ``block_params`` mirrors
    ``models/olmo_hybrid/block.py`` ``hf_to_block_params`` per kind: the same
    leaves, shapes and elements from the same HF tensors."""
    config, family, cfg = tiny
    maker, kind = weights.family_of("olmo_hybrid"), KINDS[layer]
    tensors = maker.layer_tensors(config["config"], layer, weights.Draws(config["weights_seed"]), kind)
    assert all(str(t.dtype) == "bfloat16" for t in tensors.values())
    assert len(tensors) == (5 + 11 if kind == LINEAR else 5 + 6)
    if kind == LINEAR:
        a = np.exp(np.asarray(tensors["linear_attn.A_log"], np.float32))
        dt = np.log1p(np.exp(np.asarray(tensors["linear_attn.dt_bias"], np.float32)))
        assert tensors["linear_attn.conv1d.weight"].shape == (4 * 64, 1, 4)
        assert (a >= 1).all() and (a <= 16.1).all() and len(set(a)) > 1 and (dt > 9e-4).all() and (dt < 0.11).all()
        assert 0.25 < float(np.asarray(tensors["linear_attn.conv1d.weight"], np.float32).std()) < 0.4
    mine = maker.block_params(config["config"], tensors, kind)
    theirs = family.hf_to_block_params({k: np.asarray(v, np.float32) for k, v in tensors.items()}, cfg, kind)
    assert set(mine) == set(theirs) == set(family.block_param_shapes(cfg, kind))
    for name in theirs:
        assert mine[name].shape == theirs[name].shape == family.block_param_shapes(cfg, kind)[name].shape, name
        assert np.array_equal(np.asarray(mine[name], np.float32), theirs[name]), name


def test_the_family_states_its_costs_and_limits():
    """The published shapes through ``perf/costs.py``: ISSUE 35's numbers."""
    from perf import reference

    config = load_config(ROOT / "perf/configs/olmo-hybrid-7b-span16.json", "olmo-hybrid-7b-span16")
    hf = config["config"]
    assert reference.kinds_of("olmo_hybrid", hf) == [(k,) for k in [LINEAR, LINEAR, LINEAR, FULL] * 4]
    linear, full = costs.layer_params("olmo_hybrid", hf, 0), costs.layer_params("olmo_hybrid", hf, 3)
    mixer = 2 * 3840 * 2880 + 3 * 3840 * 5760 + 2 * 3840 * 30 + 4 * 11520
    assert linear["attn"] == mixer == 88_750_080 and linear["dense"] == full["dense"] == 3 * 3840 * 11008 == 126_812_160
    assert full["attn"] == 4 * 3840 * 3840 == 58_982_400
    assert costs.layer_param_count("olmo_hybrid", hf, 0) == 215_562_240 and costs.layer_param_count("olmo_hybrid", hf, 3) == 185_794_560
    assert sum(costs.layer_param_count("olmo_hybrid", hf, i) for i in range(16)) == 3_329_925_120  # 6.66 GB in bf16
    # a linear layer caches no keys and values; a full one 2 x 30 x 128 bf16 a position
    assert (linear["q_heads"], linear["kv_heads"]) == (0, 0) and (full["q_heads"], full["kv_heads"], full["head_dim"]) == (30, 30, 128)
    assert costs.kv_bytes_per_token_layer("olmo_hybrid", hf, 0) == 0 and costs.kv_bytes_per_token_layer("olmo_hybrid", hf, 3) == 15360
    cost = costs.step_cost("olmo_hybrid", hf, 16, decode_tokens=8, prefill_tokens=0, context_tokens=8 * 1792)
    assert cost["bytes"] == 2 * 3_329_925_120 + 4 * 15360 * (8 * 1792 + 8) + 16 * 2 * 3840 * 2 * 8  # no term for the state
    assert 7.5e9 < cost["bytes"] < 7.6e9
    limits = reference.limits(config)
    assert limits["tie_margin"] == 0 and limits["positions_allowed"] == 0
    assert 0 < limits["median_bound"] <= limits["row_bound"] < 0.5
    assert not hasattr(reference.family_of("olmo_hybrid"), "TIE_MARGIN")
    assert config["server_args"]["batch_lanes"] == 8 and config["server_args"]["batch_max_length"] == 2560
    assert set(config["reduced"]) == {"num_hidden_layers", "layer_types"} and config["published"]["num_hidden_layers"] == 32
    assert config["config"]["layer_types"] == config["published"]["layer_types"][:16]


def test_tiny_cell_end_to_end_with_a_state_beside_the_pages(tmp_path):
    """The whole command at a toy size on the CPU (tests/perf/test_perf_harness.py
    ``test_tiny_cell_end_to_end``) on the toy configuration of this family: the
    server child serves a span of both kinds through ``Server`` with no flag,
    the check holds the served rows to the reference, and a traced run prints
    the two state metrics beside the others."""
    from perf import run

    bench = json.loads((DATA / "benchmark-tiny.json").read_text())
    bench["configs"].append({"name": "olmo-hybrid-tiny", "source": "toy", "file": "tests/perf/data/olmo-hybrid-tiny.json", "reduced": [], "why": "toy"})
    bench["workloads"].append({"name": "tiny-hybrid", "config": "olmo-hybrid-tiny", "traffic": "tiny-closed", "chips": 1, "why": "toy"})
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    added = [m for m in real["per_layer"] if m["name"] in ("linattn_recurrent_token_share", "state_cache_share")]
    assert [m["workloads"] for m in added] == [["olmohybrid7b-ctx2k"]] * 2 and {m["moves"] for m in added} == {"gap_p50_ms"}
    bench["per_layer"] += [{**m, "workloads": ["tiny-hybrid"]} for m in added]
    result = run.run_cell(bench, "tiny-hybrid", 2**31 + 11, 5.0, True, traffic_dir=DATA / "traffic", work_dir=tmp_path, allow_cpu=True)
    detail = result["detail"]
    assert result["correct"] is True and result["failed"] == 0 and detail["sessions_total"] > 0
    assert detail["check"]["repeat_identical"] and detail["recompiled"] == [] and detail["check"]["decode"]["ok"]
    metrics = result["metrics"]
    assert {"linattn_recurrent_token_share", "state_cache_share", "recompiles_in_window", "decode_batch_mean"} <= set(metrics)
    # prompts of 8-40 beside 4 decode rows a session: a slice of under two seconds may hold rows of one form only
    assert 0 <= metrics["linattn_recurrent_token_share"]["value"] <= 100
    assert 0 < metrics["state_cache_share"]["value"] < 100


def _record(children):
    return Record(config={}, t_process=0.0, t0=1.0, seconds=1.0, t_drained=3.0, sessions=[], children=children)


def _child(start: dict, stop: dict) -> dict:
    return {"marks": {"trace_start": {"mono": 10.0, "stats": start}, "trace_stop": {"mono": 13.0, "stats": stop}}}


def test_state_readers_on_a_hand_made_record():
    rows, cache = load_reader("layer_metrics", "linattn_recurrent_token_share"), load_reader("layer_metrics", "state_cache_share")
    # between the marks: 100 decode steps of 8 lanes and 2 chunks of 512, through 12 state layers
    start = {"linattn_recurrent_tokens": 1200, "linattn_chunk_tokens": 0, "state_bytes_held": 10, "kv_bytes_held": 100}
    stop = {"linattn_recurrent_tokens": 1200 + 800 * 12, "linattn_chunk_tokens": 1024 * 12, "state_bytes_held": 10 + 802 * 27, "kv_bytes_held": 100 + 802 * 110}
    record = _record([_child(start, stop)])
    assert rows.read(record) == pytest.approx(100 * 800 / (800 + 1024)) and cache.read(record) == pytest.approx(100 * 27 / 137)
    no_pages = _record([_child(start, {**stop, "kv_bytes_held": 100})])
    assert cache.read(no_pages) == 100.0  # a span with no block that keeps keys and values
    two = _record([_child(start, stop), _child(start, {**stop, "linattn_chunk_tokens": 0})])
    assert rows.read(two) == pytest.approx(100 * 1600 / (1600 + 1024))  # a chain: summed
    # a family without a state, a program without the counters (the parent commit), a run without the marks, no step
    other = {"batched_steps": 5}
    for children in ([_child(other, other)], [{"marks": {}}], [{}], []):
        assert rows.read(_record(children)) is None and cache.read(_record(children)) is None
    assert rows.read(_record([_child(start, start)])) is None and cache.read(_record([_child(start, start)])) is None
    assert rows.UNIT == cache.UNIT == "%" and rows.MOVES == cache.MOVES == "gap_p50_ms"
    assert rows.LAYER == "linear attention (ops/linear_attention.py)" and cache.LAYER == "batcher (server/batching.py)"
    assert (ROOT / "petals_tpu/ops/linear_attention.py").is_file()


def test_prove_chunks_at_a_toy_size_passes_and_its_control_fails(tmp_path):
    """perf/prove_chunks.py on the CPU at toy widths: a prompt of 1,536 over
    three mixed steps and 32 decode steps, alone and beside three decoding
    sessions, inside the family's limits; against a reference that starts its
    linear layers over at position 512 it is outside them."""
    from perf import prove_chunks

    bench = json.loads((DATA / "benchmark-tiny.json").read_text())
    bench["configs"].append({"name": "olmo-hybrid-tiny", "source": "toy", "file": "tests/perf/data/olmo-hybrid-tiny.json", "reduced": [], "why": "toy"})
    bench["workloads"].append({"name": "tiny-hybrid", "config": "olmo-hybrid-tiny", "traffic": "tiny-closed", "chips": 1, "why": "toy"})
    summary = prove_chunks.prove(bench, "tiny-hybrid", [2**31 + 13], work_dir=tmp_path, allow_cpu=True)
    assert summary["sessions"] == summary["correct"] == summary["control_not_correct"] == 2, summary
    assert summary["nearest"] < 1 < summary["control_nearest"]
