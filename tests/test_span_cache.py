"""server/span_cache.py: what a lane holds for a block, said once.

(a) every registered family's ``SpanCache`` against the family's own hooks,
(b) the refusal matrix, (c) what is refused later, (d) the counters a content
opens and what one step adds to them, against ``span_cache_parent_counts.json``:
literals CAPTURED ON THE PARENT TREE (2608639, before the counters moved out of
``DecodeBatcher`` and ``TransformerBackend``) by driving ``_count_paged`` with the
positions and held pages written below."""

import dataclasses
import json
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import petals_tpu.models  # noqa: F401  (registers the families)
from petals_tpu.models.registry import get_family, known_families, span_runs
from petals_tpu.ops import latent_attention, linear_attention
from petals_tpu.ops import paged_flash_attention as pfa
from petals_tpu.server.from_pretrained import get_block_config
from petals_tpu.server.span_cache import CONTENTS, INDEX_ROWS_RIDE, LATENT_ROWS_RIDE, SpanCache
from tests import utils
from tests.test_new_families import MAKERS

ROOT = Path(__file__).resolve().parents[1]

# ------------------------------------------------------------------ (a) the fields against the family's hooks


def _check_fields(family, cfg, kinds):
    runs = span_runs(kinds)
    cache = SpanCache(family, cfg, runs, cache_dtype=jnp.float32)
    assert (cache.family, cache.n_blocks, cache.kv_quant_type, cache.cache_dtype) == (family.name, len(kinds), "none", jnp.dtype(jnp.float32))
    assert cache.head_dim == cfg.head_dim and cache.kv_heads >= 1
    windows = None if family.block_window is None else tuple(family.block_window(cfg, kind) for kind in kinds)
    assert cache.layer_windows == windows
    assert [extras for _, extras in cache.walk_calls] == [family.attention_for(cfg, kind) for kind in kinds]
    if windows is not None:
        assert tuple(window for window, _ in cache.walk_calls) == windows
    states = [family.state_for(cfg, kind) for kind in kinds]
    assert cache.state_layers == tuple(i for i, s in enumerate(states) if s is not None)
    assert cache.kv_layers == tuple(i for i, s in enumerate(states) if s is None)
    assert sorted(cache.state_layers + cache.kv_layers) == list(range(len(kinds)))
    for sort in (cache.state_layers, cache.kv_layers):
        assert [cache.slots[i] for i in sort] == list(range(len(sort)))
    assert cache.state_kinds == {kind for kind, s in zip(kinds, states) if s is not None}
    declared = next((s for s in states if s is not None), ())
    assert cache.lane_state == tuple((tuple(shape), jnp.dtype(dtype or jnp.float32)) for shape, dtype in declared)
    rows = {family.index_for(cfg, kind) for kind in kinds} - {None}
    assert (cache.index_row is None) == (not rows)
    if rows:
        (width, dtype, keep), = rows
        assert cache.index_row == (width, jnp.dtype(dtype or jnp.float32)) and cache.index_keep == keep
    rows = {family.latent_for(cfg, kind) for kind in kinds} - {None}
    assert cache.latent_row == (tuple(next(iter(rows))) if rows else None)
    assert {cache.block_rows} == {family.sublayers_for(cfg, kind) for kind in kinds}
    assert cache.page_layers == len(cache.kv_layers) * cache.block_rows
    want = "state" if cache.state_layers else "index" if cache.index_row else "latent" if cache.latent_row else None
    assert cache.content == want and cache.paged_only == (want is not None) and cache.pools_beside_pages == len(cache.lane_state) + (want == "index")
    with pytest.raises(AttributeError, match="frozen"):
        cache.block_rows = 3


@pytest.mark.parametrize("name", known_families())
def test_a_family_s_span_cache_is_what_its_hooks_declare(name, tmp_path):
    """The whole toy model as one span, and one block of every kind it has."""
    family, cfg = get_block_config(MAKERS[name](str(tmp_path)))
    kinds = family.span_kinds(cfg, 0, cfg.num_hidden_layers)
    _check_fields(family, cfg, kinds)
    for kind in dict.fromkeys(kinds):
        _check_fields(family, cfg, [kind])


# ------------------------------------------------------------------ (b) the refusal matrix

CFG = types.SimpleNamespace(head_dim=16, num_attention_heads=4, num_key_value_heads=2, hidden_size=64, sliding_window=None)
RUNS = [("a", 0, 2), ("b", 2, 1)]
STATE, INDEX, LATENT = (((4, 8), None), ((3, 8), "float32")), (8, None, 16), (16, 8)


def toy(**hooks):
    """A family of two kinds of block, "a" and "b", that declares ``hooks``: a value stands for every kind, a dict
    says it by kind (a kind left out declares nothing)."""
    def hook(value):
        return lambda cfg, kind: value.get(kind) if isinstance(value, dict) else value

    return dataclasses.replace(get_family("llama"), name="toyfam", block_kind=lambda cfg, i: "ab"[i > 1], **{f"block_{key}": hook(v) for key, v in hooks.items()})


DECLARES = {
    "state": dict(state={"a": STATE}), "index": dict(index=INDEX), "latent": dict(latent=LATENT),
}
DISAGREES = {
    "state": dict(state={"a": STATE, "b": STATE[:1]}), "index": dict(index={"a": INDEX}), "latent": dict(latent={"a": LATENT, "b": (16, 4)}),
}
SPAN = {"state": "a span with a recurrent state", "index": "an index row beside their keys and values", "latent": "a latent row in place of their keys and values"}
REFUSED = {
    **{f"{c}-tp-mesh": (DECLARES[c], dict(mesh=object()), f"a tp mesh is not served for .*{SPAN[c]}: .*mesh") for c in CONTENTS},
    **{f"{c}-{q}": (DECLARES[c], dict(kv_quant_type=q), f"kv_quant_type '{q}' is not served for .*{SPAN[c]}: ") for c in CONTENTS for q in ("int8", "nf4a")},
    **{f"{c}-kinds-disagree": (DISAGREES[c], {}, f"a disagreement between kinds of block is not served for .*{SPAN[c]}: .*2 different ones") for c in CONTENTS},
    "state-and-index": (dict(state=STATE, index=INDEX), {}, f"a recurrent state in the same span is not served for .*{SPAN['index']}"),
    "state-and-latent": (dict(state=STATE, latent=LATENT), {}, f"a recurrent state in the same span is not served for .*{SPAN['latent']}"),
    "index-and-latent": (dict(index=INDEX, latent=LATENT), {}, f"an index row in the same span is not served for .*{SPAN['latent']}"),
    "state-beside-index-by-kind": (dict(state={"a": STATE}, index={"b": INDEX}), {}, f"a disagreement between kinds of block is not served for .*{SPAN['index']}"),
    "two-rows-of-keys-and-values": (dict(sublayers=2), {}, r"more than one cache row a position a block \(\[2\] sub-layers\) is not served for .* latent rows: "),
    "two-rows-beside-a-state": (dict(sublayers=2, **DECLARES["state"]), {}, r"more than one cache row a position a block .* latent rows"),
    "two-rows-beside-an-index-row": (dict(sublayers=2, **DECLARES["index"]), {}, r"more than one cache row a position a block .* latent rows"),
    "latent-rows-that-differ-by-kind": (dict(sublayers={"a": 2, "b": 1}, **DECLARES["latent"]), {}, r"more than one cache row a position a block \(\[1, 2\] sub-layers\)"),
}
SERVED = {
    "keys-and-values": ({}, None, 1), "state": (DECLARES["state"], "state", 1), "index": (DECLARES["index"], "index", 1),
    "latent": (DECLARES["latent"], "latent", 1), "two-latent-rows": (dict(sublayers=2, **DECLARES["latent"]), "latent", 2),
}


@pytest.mark.parametrize("case", [*REFUSED, *SERVED])
def test_what_a_span_declares_is_served_or_refused_by_the_family_s_name(case):
    """ONE validation: content x {a tp mesh, packed pages, kinds that disagree, two contents in one span, more than one
    row a block without a latent row}, each a ``NotImplementedError`` that names the family; and what is served."""
    if case in REFUSED:
        hooks, given, sentence = REFUSED[case]
        with pytest.raises(NotImplementedError, match=f"^toyfam: {sentence}"):
            SpanCache(toy(**hooks), CFG, RUNS, cache_dtype=jnp.bfloat16, **given)
        return
    hooks, content, rows = SERVED[case]
    cache = SpanCache(toy(**hooks), CFG, RUNS, cache_dtype=jnp.bfloat16)
    assert cache.content == content and cache.block_rows == rows and cache.kv_heads == 2
    assert cache.kv_layers == ((2,) if content == "state" else (0, 1, 2)) and cache.page_layers == len(cache.kv_layers) * rows
    assert cache.slots == ((0, 1, 0) if content == "state" else (0, 1, 2)) and cache.state_kinds == ({"a"} if content == "state" else set())
    descs = cache.pool_descriptors(6, 16, 4, 0, 3)
    pages, beside = descs[: len(descs) - cache.pools_beside_pages], descs[len(descs) - cache.pools_beside_pages :]
    if content == "latent":
        assert [d.shape for d in pages] == [(3 * rows, 6, *row) for row in latent_attention.latent_pool_rows(16, 16, 8)] and not beside
        assert cache.kv_bytes_per_token() == cache.cache_bytes_per_token() == 3 * rows * 24 * 2 and cache.pool_row == (24,)
    else:
        assert [d.shape for d in pages] == [(len(cache.kv_layers), 6, 16, 2 * 16)] * 2 and cache.pool_row == (32,)  # a row under 128 lanes: folded
    if content == "state":
        assert [(d.shape, d.dtype) for d in beside] == [((2, 4, 4, 8), jnp.bfloat16), ((2, 4, 3, 8), jnp.float32)]
        assert cache.state_bytes_per_lane() == 2 * (32 * 2 + 24 * 4) and cache.lane_bytes(100) == 100 * cache.cache_bytes_per_token() + 320
    elif content == "index":
        assert [d.shape[:2] for d in beside] == [(3, 6)] and cache.index_bytes_per_token() == 3 * 8 * 2
        assert cache.kv_bytes_per_token() == 3 * (2 * 2 * 16 * 2 + 16)
    else:
        assert not beside and cache.state_bytes_per_lane() == 0 and cache.lane_bytes(10) == 10 * cache.cache_bytes_per_token()
    assert cache.pool_descriptors(6, 16, 4, 2, 3)[0].shape[0] == rows  # block 2 keeps pages in every one of them


# ------------------------------------------------------------------ (c) what is refused later


@pytest.mark.parametrize("content", [None, *CONTENTS])
def test_refuse_paged_only_and_the_prefix_cache_s_refusal(content):
    cache = SpanCache(toy(**DECLARES.get(content, {})), CFG, RUNS, cache_dtype=jnp.bfloat16)
    assert cache.paged_only == (content is not None)
    if content is None:
        assert cache.refuse("a private cache", "because") is None and cache.prefix_cache_refusal() is None
        return
    detail = {"state": r"\(2 of its 3 blocks keep one\)", "index": r"\(8 wide, the key a learned sparse attention scores\)",
              "latent": r"\(16 \+ 8 wide, one for all heads\)"}[content]
    why = {"state": "because it cannot be cut back", "index": INDEX_ROWS_RIDE, "latent": LATENT_ROWS_RIDE}[content]
    with pytest.raises(NotImplementedError, match=f"^toyfam: a private cache is not served for .*{SPAN[content]} {detail}: {why}$"):
        cache.refuse("a private cache", "because it cannot be cut back")
    sentence = cache.prefix_cache_refusal()
    cannot = "which cannot be cut back to a stored prefix" if content == "state" else "which a stored prefix does not carry"
    assert sentence.startswith("Prefix cache off for a span") and SPAN[content].split(" span ")[-1] in sentence and sentence.endswith(cannot)


# ------------------------------------------------------------------ (d) the counters, against the parent's

PARENT = json.loads((ROOT / "tests/span_cache_parent_counts.json").read_text())
TOYS = {
    "falcon": utils.make_tiny_falcon, "mistral": utils.make_tiny_mistral, "gemma2": utils.make_tiny_gemma2, "exaone_moe": utils.make_tiny_exaone_moe,
    "olmo_hybrid": utils.make_tiny_olmo_hybrid, "KeyeVL2": utils.make_tiny_keye_vl2, "KeyeVL2-one-page": utils.make_tiny_keye_vl2,
    "deepseek_v3": utils.make_tiny_deepseek_v3, "longcat_flash": utils.make_tiny_longcat_flash,
}
PREFIXES = ("attn_pages_", "window_pages_", "linattn_", "state_bytes_held", "kv_bytes_held", "sparse_", "index_bytes_held", "latent_")


def _cache_on_shapes(family, cfg, depth, dtype):
    runs = span_runs(family.span_kinds(cfg, 0, depth))
    return SpanCache(family, cfg, runs, cache_dtype=dtype)


def test_the_parent_s_counts_cover_every_content_and_every_published_configuration():
    assert {name.split("@")[0] for name in PARENT if ":" not in name} == set(TOYS)
    # the configurations the parent tree could load; what came later is counted by the tests of its own PR (PR 64's
    # smallthinker-21b-a3b-span12: the grouped pool's counters, below)
    since = {"smallthinker-21b-a3b-span12"}
    assert {name.split(":")[1] for name in PARENT if ":" in name} == {path.stem for path in (ROOT / "perf/configs").glob("*.json")} - since


@pytest.mark.parametrize("name", sorted(PARENT))
def test_a_step_counts_what_the_parent_counted(name, tmp_path, monkeypatch):
    """``new_stats`` opens the keys the parent's batcher opened for the same span, and one decode step, one mixed step
    (and one verify of three rows a lane, for the toys) add to them what the parent's ``_count_paged`` added for the same
    positions and held pages: a toy of each content off the chip and with every kernel's path pretended (``@tpu``), and
    the benchmark's published configurations on shapes alone, as a chip runs them."""
    want = PARENT[name]
    on_chip = name.endswith("@tpu") or name.startswith("published:")
    for module in (pfa, linear_attention, latent_attention):
        monkeypatch.setattr(module, "_on_tpu", lambda: on_chip)
    if name.startswith("published:"):
        from perf.config import load as load_config

        stem = name.split(":")[1]
        config = load_config(ROOT / f"perf/configs/{stem}.json", stem)
        (tmp_path / "config.json").write_text(json.dumps(config["config"]))
        family, cfg = get_block_config(str(tmp_path))
        cache = _cache_on_shapes(family, cfg, config["server_args"]["num_blocks"], jnp.bfloat16)
        lanes, page, max_length = 8, 64, config["server_args"].get("batch_max_length", 2560)
        positions = [1799, 5, max_length, 20, 700, 63, 64, max_length]
        held = [p // page + 1 if p < max_length else 0 for p in positions]
        mixed, mixed_held = list(positions), list(held)
        mixed[1], mixed_held[1] = max_length, 7
        steps = {"decode": (positions, held, {}), "mixed": (mixed, mixed_held, dict(chunk=(1, 128, 300)))}
    else:
        family, cfg = get_block_config(TOYS[name.split("@")[0]](str(tmp_path)))
        cache = _cache_on_shapes(family, cfg, cfg.num_hidden_layers, jnp.float32)
        lanes, page, max_length = 4, 16, 16 if "one-page" in name else 64
        idle = max_length
        if max_length == 64:
            steps = {"decode": ([37, 5, idle, 20], [3, 1, 0, 2], {}), "mixed": ([37, idle, idle, 20], [3, 2, 0, 2], dict(chunk=(1, 16, 9))),
                     "verify": ([37, 5, idle, 20], [3, 1, 0, 2], dict(seq=3))}
        else:
            steps = {"decode": ([7, 5, idle, 15], [1, 1, 0, 1], {}), "mixed": ([7, idle, idle, 15], [1, 1, 0, 1], dict(chunk=(1, 4, 9)))}
    pool = cache.lane_pool(lanes, max_length // page, page)
    stats = pool.new_stats()
    assert sorted(stats) == want["keys"] and all(key.startswith(PREFIXES) for key in stats) and not any(stats.values())
    if "walks" in want:
        assert [list(map(str, walk)) for walk in pool.walks] == want["walks"]
    assert set(steps) == set(want) - {"keys", "walks", "bytes", "lane_pos"}
    for step, (at, holds, kw) in steps.items():
        before = dict(stats)
        pool.count_step(stats, np.asarray(at, np.int32), np.asarray(holds, np.int64), **kw)
        assert {key: n - before[key] for key, n in stats.items() if n != before[key]} == want[step], step
    if "lane_pos" in want:
        assert pool.windows and pool.lane_pos.tolist() == want["lane_pos"]
        live, held = np.flatnonzero(holds), np.asarray(holds, np.int64)  # what occupancy_info reads back: at the last position each fed
        windows = [w for w in cache.layer_windows if w]
        reach = sum(int(np.minimum(pool.lane_pos[live] // page - np.maximum(pool.lane_pos[live] - w + 1, 0) // page + 1, held[live]).sum()) for w in windows)
        assert pool.window_pages(live, held) == (int(held[live].sum()) * len(windows), reach)
    if "bytes" in want:
        assert {key: getattr(cache, key)() for key in want["bytes"]} == want["bytes"]
        a_layer = want["bytes"]["cache_bytes_per_token"] // max(len(cache.kv_layers), 1)
        # a span of more than one page group: a windowed group's layers hold what their window reaches (PR 64)
        lane = sum(len(blocks) * a_layer * min(max_length, window or max_length) for window, blocks in cache.page_groups) if cache.grouped else \
            max_length * want["bytes"]["cache_bytes_per_token"]
        assert cache.lane_bytes(max_length) == lane + want["bytes"]["state_bytes_per_lane"]
