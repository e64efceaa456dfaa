"""CPU tests of what PR 26 adds to the benchmark for the ``olmoe`` family
(``perf/reference/olmoe.py``, ``perf/weights/olmoe.py``, the two expert
readers), at a toy size (``data/olmoe-tiny.json``, which no cell uses)."""

import json
from pathlib import Path

import numpy as np
import pytest

from perf import costs, weights
from perf.config import load as load_config
from perf.record import Record, load_reader

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture()
def tiny(tmp_path):
    """The toy configuration, and the family and block configuration petals_tpu reads from its ``config.json``."""
    from petals_tpu.server.from_pretrained import get_block_config

    config = load_config(DATA / "olmoe-tiny.json", "olmoe-tiny")
    (tmp_path / "config.json").write_text(json.dumps(config["config"]))
    return config, *get_block_config(str(tmp_path))


def test_reference_agrees_with_the_served_block(tiny):
    """The plain float32 reference against the program's own block code on
    the weights the server child makes, both in float32 on the CPU: 1e-4,
    because they differ only in the order of float32 sums (the served side
    runs 40 positions at once, so it takes the dispatch a prompt chunk takes).
    Then one position at a time through a KV cache, the decode dispatch."""
    import jax
    import jax.numpy as jnp

    from perf import reference

    config, family, cfg = tiny
    assert family.name == "olmoe" and (cfg.num_experts, cfg.num_experts_per_tok) == (16, 4)
    x = np.random.default_rng(0).standard_normal((40, cfg.hidden_size), dtype=np.float32)
    want, margin, checks = reference.run(config, x)
    assert margin.shape == (40,) and (margin >= 0).all() and np.isfinite(margin).all() and np.isfinite(want).all()
    assert len(set(checks)) == 2  # every layer other weights
    stacked, first = weights.span_params(config, 0, 2, jnp.float32)
    assert first == checks[0] and weights.span_params(config, 1, 1, jnp.float32)[1] == checks[1]
    blocks = [jax.tree_util.tree_map(lambda leaf: leaf[i], stacked) for i in range(2)]
    with jax.default_matmul_precision("highest"):
        hidden = jnp.asarray(x)[None]
        for params in blocks:
            hidden, _ = family.block_apply(params, hidden, None, 0, cfg, use_flash=False)
        assert float(np.abs(np.asarray(hidden[0]) - want).max() / np.abs(want).max()) < 1e-4
        caches = [tuple(jnp.zeros((1, 40, cfg.num_key_value_heads, cfg.head_dim), jnp.float32) for _ in range(2)) for _ in blocks]
        rows = []
        for pos in range(40):
            h = jnp.asarray(x)[None, pos : pos + 1]
            for i, params in enumerate(blocks):
                h, caches[i] = family.block_apply(params, h, caches[i], pos, cfg, use_flash=False)
            rows.append(np.asarray(h[0, 0]))
        assert float(np.abs(np.stack(rows) - want).max() / np.abs(want).max()) < 1e-4


def test_weights_take_the_layout_the_program_gives_a_checkpoint(tiny):
    """``perf/weights/olmoe.py`` ``block_params`` mirrors ``models/olmoe/block.py``
    ``hf_to_block_params``: the same leaves, shapes and elements from the same HF tensors."""
    config, family, cfg = tiny
    maker = weights.family_of("olmoe")
    tensors = maker.layer_tensors(config["config"], 1, weights.Draws(config["weights_seed"]))
    assert len(tensors) == 9 + 3 * 16 and all(str(t.dtype) == "bfloat16" for t in tensors.values())
    assert all(float(np.asarray(tensors[f"self_attn.{n}_norm.weight"], np.float32).min()) == 1.0 for n in "qk")
    mine = maker.block_params(config["config"], tensors)
    theirs = family.hf_to_block_params({k: np.asarray(v, np.float32) for k, v in tensors.items()}, cfg)
    assert set(mine) == set(theirs)
    for name in theirs:
        assert mine[name].shape == theirs[name].shape, name
        assert np.array_equal(np.asarray(mine[name], np.float32), theirs[name]), name


def test_the_family_states_its_costs_and_limits():
    """The published shapes through ``perf/costs.py``: ISSUE 26's numbers. No
    tie margin and nothing allowed outside: every row is compared."""
    from perf import reference

    config = load_config(ROOT / "perf/configs/olmoe-1b-7b-span8.json", "olmoe-1b-7b-span8")
    hf = config["config"]
    p = costs.layer_params("olmoe", hf)
    assert (p["attn"], p["dense"], p["expert"], p["experts"], p["top_k"]) == (4 * 2048**2, 2048 * 64, 3 * 2048 * 1024, 64, 8)
    assert (p["hidden"], p["q_heads"], p["kv_heads"], p["head_dim"]) == (2048, 16, 16, 128)
    assert costs.layer_param_count("olmoe", hf) == 419_561_472  # 402.7 M of them in the experts
    assert costs.kv_bytes_per_token_layer("olmoe", hf) == 8192
    cost = costs.step_cost("olmoe", hf, 8, decode_tokens=8, prefill_tokens=0, context_tokens=0)
    # eight tokens of top 8 of 64 reach 64 x (1 - (7/8)^8) = 42.009 experts (PR 30; every expert until then: 6.71 GB)
    reached = 64 * (1 - 5764801 / 16777216)
    assert costs.experts_reached(p, 8) == reached and 42.009 < reached < 42.0091
    assert cost["bytes"] == 8 * ((4 * 2048**2 + 2048 * 64 + reached * 3 * 2048 * 1024) * 2 + 8192 * 8 + 2 * 2048 * 2 * 8)
    assert 4.50e9 < cost["bytes"] < 4.51e9  # 4.50 GB a step where every expert made 6.71
    assert cost["flops"] == 8 * 2 * (4 * 2048**2 + 2048 * 64 + 8 * 3 * 2048 * 1024) * 8  # eight experts a token computed
    limits = reference.limits(config)
    assert limits["tie_margin"] == 0 and limits["positions_allowed"] == 0
    assert 0 < limits["median_bound"] <= limits["row_bound"] < 0.3


def _record(children):
    return Record(config={}, t_process=0.0, t0=1.0, seconds=1.0, t_drained=3.0, sessions=[], children=children)


def _child(start: dict, stop: dict) -> dict:
    return {"marks": {"trace_start": {"mono": 10.0, "stats": start}, "trace_stop": {"mono": 13.0, "stats": stop}}}


def test_expert_readers_on_a_hand_made_record():
    share, passes = load_reader("layer_metrics", "moe_dense_token_share"), load_reader("layer_metrics", "moe_weight_passes_per_step")
    # 100 steps between the marks, 4 of them mixed: 790 decode tokens through the einsum, 380 chunk tokens grouped
    start = {"batched_steps": 1000, "moe_dense_tokens": 7000, "moe_grouped_tokens": 500, "moe_weight_passes": 1010}
    stop = {"batched_steps": 1100, "moe_dense_tokens": 7790, "moe_grouped_tokens": 880, "moe_weight_passes": 1114}
    record = _record([_child(start, stop)])
    assert share.read(record) == pytest.approx(100 * 790 / (790 + 380))
    assert passes.read(record) == pytest.approx(1.04)
    two = _record([_child(start, stop), _child(start, {**stop, "moe_grouped_tokens": 500, "moe_weight_passes": 1110})])
    assert share.read(two) == pytest.approx(100 * 1580 / (1580 + 380)) and passes.read(two) == pytest.approx(1.02)  # a chain: summed
    # a family without experts, a program without the counters (the parent commit), a run without the marks, no step
    dense_family = {k: v for k, v in start.items() if not k.startswith("moe_")}
    for children in ([_child(dense_family, dense_family)], [{"marks": {}}], [{}], []):
        assert share.read(_record(children)) is None and passes.read(_record(children)) is None
    assert share.read(_record([_child(start, start)])) is None and passes.read(_record([_child(start, start)])) is None
    assert (share.UNIT, passes.UNIT) == ("%", "passes/step") and share.MOVES == passes.MOVES == "gap_p50_ms"
    assert share.LAYER == passes.LAYER == "expert dispatch (models/moe.py)"
