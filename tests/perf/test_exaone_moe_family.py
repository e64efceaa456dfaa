"""CPU tests of what PR 31 adds to the benchmark for the ``exaone_moe`` family
(``perf/reference/exaone_moe.py``, ``perf/weights/exaone_moe.py``, the two
window readers), at a toy size (``data/exaone-moe-tiny.json``, which no cell
uses: five layers of four kinds, 4 of 16 routed experts held from the fifth
on, a window of 8)."""

import json
from pathlib import Path

import numpy as np
import pytest

from perf import costs, weights
from perf.config import load as load_config
from perf.record import Record, load_reader

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
KINDS = [("dense", "sliding"), ("sparse", "sliding"), ("sparse", "sliding"), ("sparse", "full"), ("sparse", "sliding")]


@pytest.fixture()
def tiny(tmp_path):
    """The toy configuration, and the family and block configuration petals_tpu reads from its ``config.json``."""
    from petals_tpu.server.from_pretrained import get_block_config

    config = load_config(DATA / "exaone-moe-tiny.json", "exaone-moe-tiny")
    (tmp_path / "config.json").write_text(json.dumps(config["config"]))
    return config, *get_block_config(str(tmp_path))


def test_reference_agrees_with_the_served_blocks_on_the_span_s_runs(tiny):
    """The plain float32 reference against the program's own block code on
    the weights the server child makes (``weights.span_params``: one stacked
    tree a run of one kind, as ``span_tree`` hands them on), both in float32
    on the CPU: 1e-4. 40 positions at once (one compile a kind), then a
    prompt chunk and decode steps through ``TransformerBackend``'s private
    cache, which is what loads what ``Server._load_span_params`` returns."""
    import jax
    import jax.numpy as jnp

    from perf import reference
    from petals_tpu.server.backend import TransformerBackend
    from petals_tpu.server.memory_cache import MemoryCache

    config, family, cfg = tiny
    assert family.name == "exaone_moe" and (cfg.num_experts, cfg.num_experts_routed, cfg.first_expert) == (4, 16, 4)
    assert reference.kinds_of("exaone_moe", config["config"]) == [(k,) for k in KINDS] == [(k,) for k in family.span_kinds(cfg, 0, 5)]
    x = np.random.default_rng(0).standard_normal((40, cfg.hidden_size), dtype=np.float32)
    want, margin, checks = reference.run(config, x)
    assert margin.shape == (40,) and (margin >= 0).all() and np.isfinite(want).all() and len(set(checks)) == 5
    # the margin counts a boundary only where a held expert stands: most positions have none in some layer... but not in all four
    assert np.isfinite(margin).any()
    runs, first = weights.span_params(config, 0, 5, jnp.float32)
    assert first == checks[0] and isinstance(runs, tuple) and [next(iter(r.values())).shape[0] for r in runs] == [1, 2, 1, 1]
    assert "wg" in runs[0] and "w1" not in runs[0] and runs[1]["w1"].shape == (2, 4, 128, 64) and runs[1]["gate"].shape == (2, 128, 16)
    one_kind, check = weights.span_params(config, 1, 2, jnp.float32)  # a span of one kind: the one stacked tree
    assert isinstance(one_kind, dict) and check == checks[1]
    with jax.default_matmul_precision("highest"):
        programs = {kind: jax.jit(lambda p, h, kind=kind: family.block_apply(p, h, None, 0, cfg, kind=kind, use_flash=False)[0]) for kind in set(KINDS)}
        hidden = jnp.asarray(x)[None]
        for (kind, start, length), run in zip(([KINDS[0], 0, 1], [KINDS[1], 1, 2], [KINDS[3], 3, 1], [KINDS[4], 4, 1]), runs):
            for i in range(length):
                hidden = programs[kind](jax.tree_util.tree_map(lambda leaf: leaf[i], run), hidden)
        assert float(np.abs(np.asarray(hidden[0]) - want).max() / np.abs(want).max()) < 1e-4
        backend = TransformerBackend(family, cfg, runs, first_block=0, n_blocks=5, memory_cache=MemoryCache(None),
                                     compute_dtype=jnp.float32, use_flash=False)
        k, v = (jnp.zeros(d.shape, d.dtype) for d in backend.cache_descriptors(1, 40, 0, 5))
        outs, position = [], 0
        for chunk in (x[None, :13], *(x[None, p : p + 1] for p in range(13, 40))):
            out, (k, v) = backend.inference_step(chunk, (k, v), position)
            outs.append(np.asarray(out))
            position += chunk.shape[1]
        assert float(np.abs(np.concatenate(outs, axis=1)[0] - want).max() / np.abs(want).max()) < 1e-4


@pytest.mark.parametrize("layer", [0, 3])
def test_weights_take_the_layout_the_program_gives_a_checkpoint(tiny, layer):
    """``perf/weights/exaone_moe.py`` ``block_params`` mirrors
    ``models/exaone_moe/block.py`` ``hf_to_block_params`` per kind: the same
    leaves, shapes and elements from the same HF tensors, the held experts
    under their names among the routed."""
    config, family, cfg = tiny
    maker, kind = weights.family_of("exaone_moe"), KINDS[layer]
    tensors = maker.layer_tensors(config["config"], layer, weights.Draws(config["weights_seed"]), kind)
    assert all(str(t.dtype) == "bfloat16" for t in tensors.values())
    assert len(tensors) == (8 + 3 if kind[0] == "dense" else 8 + 2 + 3 * 4 + 3)
    if kind[0] == "sparse":
        assert {f"mlp.experts.{e}.up_proj.weight" for e in range(4, 8)} <= set(tensors) and "mlp.experts.0.up_proj.weight" not in tensors
        assert float(np.abs(np.asarray(tensors["mlp.gate.e_score_correction_bias"], np.float32)).max()) > 0
    assert all(tensors[f"self_attn.{n}_norm.weight"].shape == (32,) for n in "qk")
    mine = maker.block_params(config["config"], tensors, kind)
    theirs = family.hf_to_block_params({k: np.asarray(v, np.float32) for k, v in tensors.items()}, cfg, kind)
    assert set(mine) == set(theirs) == set(family.block_param_shapes(cfg, kind))
    for name in theirs:
        assert mine[name].shape == theirs[name].shape == family.block_param_shapes(cfg, kind)[name].shape, name
        assert np.array_equal(np.asarray(mine[name], np.float32), theirs[name]), name


def test_the_family_states_its_costs_and_limits():
    """The published shapes through ``perf/costs.py``: ISSUE 31's numbers, to
    the digit."""
    from perf import reference

    config = load_config(ROOT / "perf/configs/k-exaone-236b-span5-ep8.json", "k-exaone-236b-span5-ep8")
    hf = config["config"]
    assert reference.kinds_of("exaone_moe", hf) == [(k,) for k in KINDS]
    dense, sparse, full = (costs.layer_params("exaone_moe", hf, i) for i in (0, 1, 3))
    assert dense["attn"] == sparse["attn"] == 6144 * (8192 + 1024 + 1024) + 8192 * 6144 == 113_246_208
    assert (dense["dense"], dense["expert"], dense["experts"], dense["top_k"]) == (3 * 6144 * 18432, 0, 0, 0) and dense["dense"] == 339_738_624
    assert sparse["expert"] == 3 * 6144 * 2048 == 37_748_736 and sparse["dense"] == 786_432 + 37_748_736  # router + shared expert
    assert (sparse["experts"], sparse["experts_routed"], sparse["top_k"]) == (16, 128, 8)
    assert (sparse["hidden"], sparse["q_heads"], sparse["kv_heads"], sparse["head_dim"]) == (6144, 64, 8, 128)
    assert dense["window"] == sparse["window"] == 128 and "window" not in full
    counts = [costs.layer_param_count("exaone_moe", hf, i) for i in range(5)]
    assert counts == [452_984_832, 755_761_152, 755_761_152, 755_761_152, 755_761_152] and sum(counts) == 3_476_029_440  # 6.95 GB
    assert costs.kv_bytes_per_token_layer("exaone_moe", hf, 2) == 4096  # 20 KB a token over the span
    # 7.9 lanes of top 8 of 128 reach 16 x (1 - (15/16)^7.9) = 6.39 of the 16 held; a token computes 8 x 16/128 = 1 expert here
    reached = 16 * (1 - (15 / 16) ** 7.9)
    assert costs.experts_reached(sparse, 7.9) == pytest.approx(reached) and 6.38 < reached < 6.40
    cost = costs.step_cost("exaone_moe", hf, 5, decode_tokens=7.9, prefill_tokens=0, context_tokens=7.9 * 320)
    weight_bytes = 2 * (5 * 113_246_208 + 339_738_624 + 4 * (786_432 + 37_748_736) + 4 * reached * 37_748_736)
    kv_read = 4096 * (4 * 7.9 * 128 + 7.9 * 320)  # a windowed layer reads 128 positions a lane, the full one all 320
    assert cost["bytes"] == pytest.approx(weight_bytes + kv_read + 5 * (4096 * 7.9 + 2 * 6144 * 2 * 7.9))
    assert 4.07e9 < cost["bytes"] < 4.09e9 and 4.05e9 < weight_bytes < 4.07e9  # 4.9 ms at 819 GB/s; every held expert read: 6.95 GB, 8.5 ms
    assert cost["flops"] == pytest.approx(2 * 7.9 * (5 * 113_246_208 + 339_738_624 + 4 * (786_432 + 2 * 37_748_736)) + 4 * 64 * 128 * (4 * 7.9 * 128 + 7.9 * 320))
    limits = reference.limits(config)
    assert limits["tie_margin"] > 0 and 0 < limits["positions_allowed"] <= 2
    assert 0 < limits["median_bound"] <= limits["row_bound"] < 0.3
    assert config["config"]["expert_share"] == {"routed": 128, "first": 0} and config["published"]["num_experts"] == 128
    assert {"pre_norm", "router", "attention", "tensor_names", "weights", "expert_share"} <= set(config["assumed"])


def _record(children):
    return Record(config={}, t_process=0.0, t0=1.0, seconds=1.0, t_drained=3.0, sessions=[], children=children)


def _child(start: dict, stop: dict) -> dict:
    return {"marks": {"trace_start": {"mono": 10.0, "stats": start}, "trace_stop": {"mono": 13.0, "stats": stop}}}


def test_window_readers_on_a_hand_made_record():
    read_share, idle = load_reader("layer_metrics", "attn_window_read_share"), load_reader("layer_metrics", "window_pages_idle_share")
    # 100 decode steps of 8 lanes over five layers of 16 slots between the marks: four windowed layers gather 3 slots, one all 16
    start = {"batched_steps": 1000, "attn_pages_gathered": 5000, "attn_pages_tabled": 9000, "window_pages_held": 700, "window_pages_in_reach": 300}
    stop = {"batched_steps": 1100, "attn_pages_gathered": 5000 + 100 * 8 * 28, "attn_pages_tabled": 9000 + 100 * 8 * 80,
            "window_pages_held": 700 + 100 * 8 * 4 * 5, "window_pages_in_reach": 300 + 100 * 8 * 4 * 3}
    record = _record([_child(start, stop)])
    assert read_share.read(record) == pytest.approx(35.0) and idle.read(record) == pytest.approx(40.0)
    two = _record([_child(start, stop), _child(start, {**stop, "attn_pages_gathered": 5000 + 100 * 8 * 80, "window_pages_in_reach": 300 + 100 * 8 * 4 * 5})])
    assert read_share.read(two) == pytest.approx(67.5) and idle.read(two) == pytest.approx(20.0)  # a chain: summed
    # a family without a windowed layer, a program without the counters (the parent commit), a run without the marks, no step
    other_family = {"batched_steps": 1000}
    for children in ([_child(other_family, other_family)], [{"marks": {}}], [{}], []):
        assert read_share.read(_record(children)) is None and idle.read(_record(children)) is None
    assert read_share.read(_record([_child(start, start)])) is None and idle.read(_record([_child(start, start)])) is None
    assert (read_share.UNIT, idle.UNIT) == ("%", "%") and read_share.MOVES == idle.MOVES == "gap_p50_ms"
    assert read_share.LAYER == "attention dispatch (ops/paged_attention.py)" and idle.LAYER == "batcher (server/batching.py)"


def test_tiny_cell_end_to_end_over_a_span_of_more_than_one_kind(tmp_path):
    """The whole command at a toy size on the CPU (tests/perf/test_perf_harness.py
    ``test_tiny_cell_end_to_end``) on the toy configuration of this family: the
    server child loads what ``span_tree`` returns through ``Server``, the check
    holds the served rows to the reference by kind of layer, and a traced run
    prints the two window metrics and the expert counters' metrics."""
    from perf import run

    bench = json.loads((DATA / "benchmark-tiny.json").read_text())
    bench["configs"].append({"name": "exaone-moe-tiny", "source": "toy", "file": "tests/perf/data/exaone-moe-tiny.json", "reduced": [], "why": "toy"})
    bench["workloads"].append({"name": "tiny-exaone", "config": "exaone-moe-tiny", "traffic": "tiny-closed", "chips": 1, "why": "toy"})
    for name, layer in (("attn_window_read_share", "attention dispatch (ops/paged_attention.py)"), ("window_pages_idle_share", "batcher (server/batching.py)")):
        bench["per_layer"].append({"name": name, "unit": "%", "better": "lower", "source": "program_counter", "layer": layer,
                                   "moves": "gap_p50_ms", "workloads": ["tiny-exaone"]})
    result = run.run_cell(bench, "tiny-exaone", 2**31 + 7, 5.0, True, traffic_dir=DATA / "traffic", work_dir=tmp_path, allow_cpu=True)
    detail = result["detail"]
    assert result["correct"] is True and result["failed"] == 0 and detail["sessions_total"] > 0
    assert detail["check"]["repeat_identical"] and detail["recompiled"] == [] and detail["check"]["decode"]["ok"]
    metrics = result["metrics"]
    assert {"attn_window_read_share", "window_pages_idle_share", "recompiles_in_window", "decode_batch_mean"} <= set(metrics)
    assert 0 < metrics["attn_window_read_share"]["value"] <= 100 and 0 <= metrics["window_pages_idle_share"]["value"] < 100
