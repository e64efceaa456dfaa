"""Every Pallas kernel the serving code can select on a TPU must LOWER for it.

The ``-m kernel`` / ``-m kvquant`` lanes prove numerics in the Pallas
interpreter, which accepts block shapes and vector ops Mosaic refuses. This
lane proves the compiler takes them: with ``libtpu`` installed,
``jax.experimental.topologies`` hands out compile-only v5e devices on a host
that has no chip, and ``jit(f).lower(<avals placed on one>).compile()`` runs
the real Pallas -> Mosaic -> libtpu pipeline with ``interpret=False``. Each
case is one full-width shape (Llama-2-7B / 70B head geometry); nothing
executes. Numerics on the chip itself are chip_smoke.py's kernel phase.

The last two sections compile a whole step program the same way, the paged
decode step and a mixed step at the benchmark's three configurations, and read
the optimized HLO for what no run on the CPU can show: that the loop reads
every stacked weight where it lies (PERF.md section 5, "The relayout"), and
that the step leaves the page pool where it lies (PERF.md section 5, PR 29).
"""

import functools
import json
import math
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

pytest.importorskip("libtpu")

from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from petals_tpu.ops import paged_flash_attention as pfa  # noqa: E402
from petals_tpu.ops import quant as Q  # noqa: E402
from petals_tpu.ops.flash_attention import flash_attend  # noqa: E402
from petals_tpu.ops.paged_attention import PagedPool, stored_row  # noqa: E402
from petals_tpu.models.registry import span_runs  # noqa: E402
from tests.utils import counted, lane_pools  # noqa: E402

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def v5e():
    """ShapeDtypeStruct factory placing avals on one compile-only v5e chip."""
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    sharding = SingleDeviceSharding(topo.devices[0])
    return functools.partial(jax.ShapeDtypeStruct, sharding=sharding)


def _compile(fn, *avals):
    compiled = jax.jit(fn).lower(*avals).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "hq,hkv,d,window",
    [(32, 32, 128, None), (32, 8, 128, None), (71, 1, 64, 256)],
    ids=["mha", "gqa", "mqa-d64-window"],
)
def test_flash_attend_lowers(v5e, hq, hkv, d, window):
    q = v5e((1, 512, hq, d), BF16)
    kv = v5e((1, 1024, hkv, d), BF16)
    _compile(
        lambda q, k, v: flash_attend(
            q, k, v, q_offset=512, kv_length=1024, sliding_window=window, interpret=False
        ),
        q, kv, kv,
    )


def _pool(v5e, n_pages, page_size, hkv, d, kv_quant):
    """A block's pool as a server stores it: a row under 128 lanes (bf16-d64,
    nf4a's packed half of 128) folded to ``hkv * d_store``."""
    row = stored_row(hkv, d // 2 if kv_quant == "nf4a" else d)
    leaf = v5e((n_pages, page_size, *row), {"none": BF16, "int8": jnp.int8, "nf4a": jnp.uint8}[kv_quant])
    return leaf if kv_quant == "none" else PagedPool(leaf, v5e((n_pages, page_size, hkv), F32))


PAGED_CASES = [
    # hq, hkv, d, kv_quant — page_size 64, the Server default
    (32, 32, 128, "none"),
    (32, 8, 128, "none"),
    (32, 8, 128, "int8"),
    (32, 32, 128, "nf4a"),
    (64, 8, 64, "none"),
    (16, 2, 256, "none"),  # two kv heads: stored folded whatever their width
    (20, 1, 128, "none"),  # one kv head for 20 query heads (not a power of two): a folded row of 128
]
PAGED_IDS = ["bf16-mha", "bf16-gqa", "int8-gqa", "nf4a-mha", "bf16-d64", "bf16-2x256", "bf16-mqa20"]


@pytest.mark.parametrize("hq,hkv,d,kv_quant", PAGED_CASES, ids=PAGED_IDS)
def test_paged_decode_row_lowers(v5e, hq, hkv, d, kv_quant):
    """A decode row's attention (``composed_paged_attend``) compiles for the
    v5e at each class, on the walk ``decode_walk_path`` names for it: the
    kernel over a plain pool of whole tiles, rows of ``[hkv, d]`` or a folded
    row of heads of whole lanes, the composed walk over half a tile of kv
    heads, a quantised pool and a folded row of heads of 64."""
    lanes, max_pages, page_size = 8, 16, 64
    pool = _pool(v5e, lanes * max_pages, page_size, hkv, d, kv_quant)
    q = v5e((lanes, 1, hq, d), BF16)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(pfa, "_on_tpu", lambda: True)  # the backend here is the CPU
        patch.setattr(pfa, "_interpret", lambda: False)
        path = pfa.decode_walk_path(pool, q.shape, (lanes, max_pages))
        assert path == ("composed" if kv_quant != "none" or (hkv, d) in ((8, 128), (8, 64)) else "kernel")
        hlo = jax.jit(
            lambda q, k, v, t, p: pfa.composed_paged_attend(q, k, v, t, q_offset=p, kv_length=p + 1)
        ).lower(q, pool, pool, v5e((lanes, max_pages), I32), v5e((lanes,), I32)).compile().as_text()
    assert ("tpu_custom_call" in hlo) == (path == "kernel")


@pytest.mark.parametrize("hq,hkv,d,kv_quant", PAGED_CASES, ids=PAGED_IDS)
def test_paged_flash_prefill_attend_lowers(v5e, hq, hkv, d, kv_quant):
    max_pages, page_size, chunk = 16, 64, 512  # chunk 512 -> block_q 256
    assert pfa.paged_kernel_unsupported(hkv, d, kv_quant) is None
    pool = _pool(v5e, 8 * max_pages, page_size, hkv, d, kv_quant)
    _compile(
        lambda q, k, v, t, c, n: pfa.paged_flash_prefill_attend(
            q, k, v, t, c, n, interpret=False
        ),
        v5e((1, chunk, hq, d), BF16), pool, pool,
        v5e((max_pages,), I32), v5e((), I32), v5e((), I32),
    )


def test_paged_alibi_and_window_lower(v5e):
    """The slopes operand (an f32 scalar prefetch) and the windowed skip
    predicate ride the same kernel."""
    lanes, max_pages, page_size, hq, hkv, d = 8, 16, 64, 32, 8, 128
    pool = _pool(v5e, lanes * max_pages, page_size, hkv, d, "none")
    _compile(
        lambda q, k, v, t, c, n, s: pfa.paged_flash_prefill_attend(
            q, k, v, t, c, n, alibi_slopes=s, sliding_window=256, interpret=False
        ),
        v5e((1, 128, hq, d), BF16), pool, pool,
        v5e((max_pages,), I32), v5e((), I32), v5e((), I32), v5e((hq,), F32),
    )


def test_unsupported_paged_shape_is_gated_not_compiled():
    """A head width that neither is a lane multiple nor packs into 128 lanes
    is what the static gate exists for; the dispatch must never hand it to
    Mosaic on a TPU."""
    assert pfa.paged_kernel_unsupported(8, 80) is not None


IN, OUT, N_BLOCKS = 4096, 11008, 2  # Llama-2-7B up/gate projection


@pytest.mark.parametrize(
    "kind,m,stacked",
    [("nf4a", 1, True), ("nf4a", 512, False), ("int4", 8, True), ("nf4", 1, False)],
    ids=["nf4a-decode-stacked", "nf4a-prefill", "int4-decode-stacked", "nf4-decode"],
)
def test_packed4_matmul_lowers(v5e, kind, m, stacked):
    lead = (N_BLOCKS,) if stacked else ()
    data = v5e((*lead, IN // 2, OUT), jnp.uint8)
    scales = v5e((*lead, IN // Q.NF4_BLOCK, OUT), BF16)
    if stacked:
        fn = lambda x, d, s, i: Q._packed4_call(x, kind, d, s, index=i, interpret=False)  # noqa: E731
        _compile(fn, v5e((m, IN), BF16), data, scales, v5e((), I32))
    else:
        fn = lambda x, d, s: Q._packed4_call(x, kind, d, s, interpret=False)  # noqa: E731
        _compile(fn, v5e((m, IN), BF16), data, scales)


@pytest.mark.parametrize("m,stacked", [(1, True), (512, False)], ids=["decode-stacked", "prefill"])
def test_int8_matmul_lowers(v5e, m, stacked):
    lead = (N_BLOCKS,) if stacked else ()
    data = v5e((*lead, IN, OUT), jnp.int8)
    scales = v5e((*lead, OUT), F32)
    if stacked:
        fn = lambda x, d, s, i: Q._int8_call(x, d, s, index=i, interpret=False)  # noqa: E731
        _compile(fn, v5e((m, IN), BF16), data, scales, v5e((), I32))
    else:
        fn = lambda x, d, s: Q._int8_call(x, d, s, interpret=False)  # noqa: E731
        _compile(fn, v5e((m, IN), BF16), data, scales)


# ---------------------------------------------------------------- the decode loop reads its weights in place

# HLO ops that move data and compute nothing (a fusion counts when its body holds nothing else)
_MOVES = frozenset({
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast", "reshape", "transpose",
    "copy", "copy-start", "copy-done", "slice", "dynamic-slice",
})
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%(?P<name>[\w.\-]+)\s*=\s*\(?\w+\[(?P<dims>[\d,]*)\]\S*.*?\s(?P<op>[\w\-]+)\((?P<rest>.*)$"
)


def _computations(hlo: str) -> dict:
    """Optimized HLO text -> {computation: [(name, dims, op, rest of the line)]}, in program order."""
    out, current = {}, None
    for line in hlo.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            current = out.setdefault(head.group(1), [])
        elif line.startswith("}"):
            current = None
        elif current is not None:
            m = _INSTRUCTION.match(line)
            if m:
                dims = tuple(int(n) for n in m.group("dims").split(",") if n)
                current.append((m.group("name"), dims, m.group("op"), m.group("rest")))
    return out


def _while_bodies(comps: dict):
    """The instruction list of every while loop's body."""
    for instructions in comps.values():
        for _, _, op, rest in instructions:
            body = re.search(r"body=%([\w.\-]+)", rest) if op == "while" else None
            if body is not None:
                yield comps[body.group(1)]


def _fused(comps: dict, op: str, rest: str):
    """The instructions of the computation a fusion calls, else None."""
    called = re.search(r"calls=%([\w.\-]+)", rest) if op == "fusion" else None
    return comps[called.group(1)] if called is not None else None


def _only_moves(comps: dict, op: str, rest: str) -> bool:
    fused = _fused(comps, op, rest)
    return op in _MOVES or (fused is not None and all(i[2] in _MOVES for i in fused))


def weight_relayouts(hlo: str, stacked_shapes: set, min_elements: int) -> tuple:
    """``(relayouts, weights_seen)`` of the while bodies of an optimized HLO
    module. A relayout is an instruction that only MOVES a stacked weight: its
    operand is a loop-carried array of one of ``stacked_shapes`` (or another
    such move of one), it computes nothing (a ``copy``, or a fusion made of
    slices, copies and bitcasts alone), and it materialises at least
    ``min_elements`` values. A dot fusion that takes the stacked array itself
    (``fusion(bf16[5,8192,32768] ...)``) reads the weight in place and is what
    the loop should be made of. ``weights_seen`` counts the loop-carried arrays
    of those shapes, so that a caller can tell "none found" from "not parsed"."""
    comps = _computations(hlo)
    relayouts, seen = [], 0
    for body in _while_bodies(comps):
        moved = set()  # names in the body that are a stacked weight, or a pure move of one
        for name, dims, op, rest in body:
            if op == "get-tuple-element":
                if dims in stacked_shapes:
                    moved.add(name)
                    seen += 1
                continue
            if _only_moves(comps, op, rest) and moved & set(re.findall(r"%([\w.\-]+)", rest)):
                moved.add(name)
                if op != "bitcast" and math.prod(dims) >= min_elements:
                    relayouts.append(f"%{name} = {op} -> {list(dims)}")
    return relayouts, seen


def entry_weight_moves(hlo: str, stacked_shapes: set, min_elements: int) -> list:
    """Instructions of ``ENTRY`` that only move a parameter of one of
    ``stacked_shapes`` (a ``copy``, or a fusion of slices, copies and bitcasts
    alone) into a buffer of ``min_elements`` values or more: what
    ``weight_relayouts`` finds in a loop body, for the weights of a run of one
    block, whose one-trip loop the compiler unrolls. A ``copy-start`` /
    ``copy-done`` pair into the alternate memory space (``S(1)`` in the
    result's layout) is the compiler's prefetch of a weight the next dot reads
    from there, in the layout it had: one read of HBM, as in place, and not
    counted; whatever then moves the prefetched copy is."""
    comps = _computations(hlo)
    entry = re.search(r"^ENTRY\s+%([\w.\-]+)", hlo, re.MULTILINE).group(1)
    prefetches = set(re.findall(r"^\s*%([\w.\-]+) = \(?\w+\[[\d,]*\]\{[^}]*S\(1\)\}[^=]*copy-(?:start|done)\(", hlo, re.MULTILINE))
    moved, found = set(), []
    for name, dims, op, rest in comps[entry]:
        if op == "parameter":
            if dims in stacked_shapes:
                moved.add(name)
            continue
        if _only_moves(comps, op, rest) and moved & set(re.findall(r"%([\w.\-]+)", rest)):
            moved.add(name)
            if op != "bitcast" and name not in prefetches and math.prod(dims) >= min_elements:
                found.append(f"ENTRY %{name} = {op} -> {list(dims)}")
    return found


STEP_CASES = [
    pytest.param(
        config_name, chunk, id=f"{config_name}-{'mixed-256' if chunk else 'decode'}",
        marks=[pytest.mark.xfail(strict=True, reason=(
            "the chunk's grouped expert dispatch: ragged_dot is a custom call and cannot read the stacked span in "
            "place, so each layer's w1, w3 and w2 (940 MB each) are sliced out first (ROADMAP S7)"
        ))] if (config_name, chunk) == ("mixtral-8x7b-span2", 256) else [],
    )
    for config_name in ("falcon-40b-span5", "mixtral-8x7b-span2", "olmoe-1b-7b-span8", "k-exaone-236b-span5-ep8", "olmo-hybrid-7b-span16")
    for chunk in (0, 256)
]


def _compiled_step(v5e, tmp_path, config_name, chunk, pages_a_lane=16, kv_quant="none", latent_kernel=True, walk_kernel=True, state_kernel=True):
    """``(optimized HLO, stacked params, pool aval, (hkv, d))`` of
    ``TransformerBackend``'s paged decode step, or of its mixed step with a
    prompt chunk of ``chunk`` riding it, at a cell's widths and depth (8 lanes,
    pages of 64, ``pages_a_lane`` slots a table and as many pages a lane in
    the pool, pools donated, in the form the backend's descriptors give them:
    a head_dim of 64 folded to rows of ``hkv * d``), compiled for the v5e. Under
    ``kv_quant`` the pools are ``PagedPool``s and the aval returned is their codes'.
    ``latent_kernel`` False: a latent row's decode walk as off the chip, composed;
    ``walk_kernel`` False: a decode row's walk over plain pages likewise;
    ``state_kernel`` False: a decode row's one-step rule in its plain form."""
    from perf.config import load as load_config
    from petals_tpu.server.backend import TransformerBackend
    from petals_tpu.server.from_pretrained import get_block_config

    config_file = Path(__file__).resolve().parents[1] / "perf" / "configs" / f"{config_name}.json"
    hf_config = load_config(config_file, config_name)["config"]
    (tmp_path / "config.json").write_text(json.dumps(hf_config))
    family, cfg = get_block_config(str(tmp_path))
    depth, lanes, n_pages, page_size = cfg.num_hidden_layers, 8, 8 * pages_a_lane, 64  # the BLOCKS of the span (a double layer is one)
    # one stacked tree a run of consecutive blocks of one kind (K-EXAONE's five blocks: four runs of three trees)
    runs = tuple(
        {name: v5e((length, *leaf.shape), leaf.dtype) for name, leaf in family.param_shapes_for(cfg, kind, BF16).items()}
        for kind, _, length in span_runs(family.span_kinds(cfg, 0, depth))
    )
    params = runs[0] if len(runs) == 1 else runs
    backend = TransformerBackend(family, cfg, params, first_block=0, n_blocks=depth, memory_cache=None, kv_quant_type=kv_quant)
    # as deep as the blocks that keep keys and values: all of them, but for a span with a recurrent state
    descs = lane_pools(backend, n_pages, page_size, end=depth)[0]  # in the form the rule stores them
    pool = v5e(descs[0].shape, BF16 if kv_quant == "none" else descs[0].dtype)
    pools = pool if kv_quant == "none" else PagedPool(pool, v5e(descs[2].shape, descs[2].dtype))
    # the second pool is the first one's twin but for a span that caches a latent row in place of keys and values
    second = pools if backend.cache.latent_row is None else v5e(descs[1].shape, BF16)
    # the lanes' rows and positions as one operand (backend.pack_lanes' form: a float32 row bit for bit and its position), then the tables
    avals = [params, pools, second, v5e((lanes, backend.hidden_size + 1), I32), v5e((lanes, pages_a_lane), I32)]
    step = backend._paged_decode_fn
    if chunk:  # chunk_hidden, then chunk_lane, chunk_pos, chunk_n_valid, chunk_n_total
        step = backend._paged_mixed_step_fn
        avals += [v5e((1, chunk, backend.hidden_size), BF16)] + [v5e((), I32)] * 4
    donated = (1, 2)
    if backend.cache.state_layers:  # the state pool's leaves ride last and are donated with the pages
        avals.append(tuple(v5e(d.shape, d.dtype) for d in lane_pools(backend, 1, 1, lanes)[1]))
        donated += (len(avals) - 1,)
    if backend.cache.index_row is not None:  # as the index pool does
        avals.append(tuple(v5e(d.shape, d.dtype) for d in lane_pools(backend, n_pages, page_size)[1]))
        donated += (len(avals) - 1,)
    step = functools.partial(step.__wrapped__, with_fp=False)  # the raw step under tracked_jit
    with pytest.MonkeyPatch.context() as patch:  # the backend here is the CPU: the hit dispatch's kernel would be interpreted
        patch.setattr("petals_tpu.ops.expert_hit._interpret", lambda: False)
        patch.setattr("petals_tpu.ops.latent_attention._on_tpu", lambda: latent_kernel)  # as would the latent decode walk's kernel
        patch.setattr(pfa, "_on_tpu", lambda: walk_kernel)  # and the plain pages' decode walk's
        patch.setattr(pfa, "_interpret", lambda: False)
        patch.setattr("petals_tpu.ops.linear_attention._on_tpu", lambda: state_kernel)  # and the one-step rule's over the state pool
        patch.setattr("petals_tpu.ops.linear_attention._interpret", lambda: False)
        hlo = jax.jit(step, donate_argnums=donated).lower(*avals).compile().as_text()
    return hlo, runs, pool, (backend.num_kv_heads, backend.head_dim)


@pytest.mark.parametrize("config_name,chunk", STEP_CASES)
def test_paged_step_loop_reads_stacked_weights_in_place(v5e, tmp_path, config_name, chunk):
    """No instruction of the step's loop body may slice a layer's matrix out
    of the stacked span into a buffer of its own, or copy one. Before
    ``models/common.py project_heads`` the body held ``bf16[1,8192,8192]``
    twice a layer for Falcon's ``wq`` (a dynamic-slice fusion, then a
    transposing copy: 27% of the decode loop on the chip), the same pair for
    ``wk`` / ``wv``, and Mixtral's and OLMoE's likewise."""
    hlo, runs, _, _ = _compiled_step(v5e, tmp_path, config_name, chunk)
    attention = [run[name].shape for run in runs if run["wq"].shape[0] > 1 for name in ("wq", "wk", "wv", "wo")]
    attention = attention or [run[name].shape for run in runs for name in ("wq", "wk", "wv", "wo")]  # no run of full layers is a loop
    relayouts, seen = weight_relayouts(
        hlo, {tuple(p.shape) for run in runs for p in run.values()}, min(math.prod(shape[1:]) for shape in attention)
    )
    assert seen >= len(set(attention)), "the loop's stacked weights were not found: has the HLO text changed?"
    assert not relayouts, f"the step's loop relays a weight in every layer of every step: {relayouts}"
    # a run of one block is no loop once compiled: its weights are read where the program was handed them
    moved = entry_weight_moves(hlo, {tuple(p.shape) for run in runs for p in run.values()}, min(math.prod(shape[1:]) for shape in attention))
    assert not moved, f"the step relays a weight of a run of one block in every step: {moved}"


def hit_calls(hlo: str) -> list:
    """``[(name, [(operand's op, its dims)])]`` of every call of the hit
    dispatch's kernel (``ops/expert_hit.py``, named ``moe_hit_experts``) in an
    optimized HLO module: what each operand IS in the computation that holds
    the call (a loop body, or ``ENTRY`` for a run of one block)."""
    calls = []
    for instructions in _computations(hlo).values():
        by_name = {name: (op, dims) for name, dims, op, _ in instructions}
        for name, _, op, rest in instructions:
            if op == "custom-call" and name.startswith("moe_hit_experts") and "tpu_custom_call" in rest:
                operands = re.findall(r"%([\w.\-]+)", rest.split("), custom_call_target")[0])
                calls.append((name, [by_name[o] for o in operands]))
    return calls


@pytest.mark.parametrize("config_name", ["mixtral-8x7b-span2", "olmoe-1b-7b-span8", "k-exaone-236b-span5-ep8"])
def test_decode_step_reads_the_experts_hit_out_of_the_stacked_run(v5e, tmp_path, config_name):
    """The decode step of an expert configuration holds the hit dispatch's
    kernel once a run of expert layers, and the kernel's weight operands are
    the run's stacked ``w1`` / ``w3`` [L, E, h, m] and ``w2`` [L, E, m, h]
    themselves, as the loop carries them or the program was handed them: no
    slice, no copy (what ``ragged_dot`` could not do: PERF.md section 6, PR
    31), and nothing else in the step moves a weight either."""
    hlo, runs, _, _ = _compiled_step(v5e, tmp_path, config_name, 0)
    expert_runs = [run for run in runs if "w1" in run]
    calls = hit_calls(hlo)
    assert len(calls) == len(expert_runs), [name for name, _ in calls]
    want = sorted(sorted(tuple(run[leaf].shape) for leaf in ("w1", "w3", "w2")) for run in expert_runs)
    got = sorted(sorted(dims for _, dims in operands if len(dims) == 4) for _, operands in calls)
    assert got == want
    for name, operands in calls:
        held_as = {op for op, dims in operands if len(dims) == 4}
        assert held_as <= {"get-tuple-element", "parameter"}, f"%{name} is handed a weight something made: {operands}"
    shapes = {tuple(p.shape) for run in runs for p in run.values()}
    least = min(math.prod(run["w1"].shape[2:]) for run in expert_runs)  # one expert's matrix
    relayouts, seen = weight_relayouts(hlo, shapes, least)
    assert seen and not relayouts, relayouts
    assert not entry_weight_moves(hlo, shapes, least)


def test_dispatch_rule_picks_hit_only_where_it_can_read_in_place(tmp_path):
    """``grouped_dispatch`` answers "hit" for a decode-shaped call that was
    handed a stack, with plain weights and no mesh, and in every other case
    what it answered before there was a third dispatch; ``backend.moe_grouped``
    asks it for the lane pool's step programs."""
    from petals_tpu.models.moe import GROUPED_MIN_SEQ, MoeDims, grouped_dispatch
    from petals_tpu.server.backend import TransformerBackend
    from petals_tpu.server.from_pretrained import get_block_config
    from tests.utils import make_tiny_mixtral

    shapes = {"mixtral": MoeDims(8, 2, 4096, 14336), "olmoe": MoeDims(64, 8, 2048, 1024), "k-exaone": MoeDims(16, 8, 6144, 2048, routed=128)}
    chunk = {  # today's choice between the einsum and ragged_dot, by the chunk's length
        "mixtral": lambda seq: "grouped", "olmoe": lambda seq: "grouped" if seq >= 1024 else "dense", "k-exaone": lambda seq: "dense",
    }
    for name, dims in shapes.items():
        for seq in (1, 2, 5, GROUPED_MIN_SEQ - 1):  # decode, a speculative verify's k + 1 rows
            assert grouped_dispatch(dims, seq, stacked=True) == "hit"
            assert grouped_dispatch(dims, seq) == "dense"  # a caller that hands over no stack
            assert grouped_dispatch(dims, seq, stacked=True, quantized=True) == "dense"
            assert grouped_dispatch(dims, seq, stacked=True, mesh=True) == "dense"
        for seq in (GROUPED_MIN_SEQ, 16, 64, 128, 256, 512, 1024):  # chunk-shaped: the stack changes nothing
            assert grouped_dispatch(dims, seq, stacked=True) == grouped_dispatch(dims, seq) == chunk[name](seq)
            assert grouped_dispatch(dims, seq, quantized=True) == "dense" == grouped_dispatch(dims, seq, mesh=True)

    family, cfg = get_block_config(make_tiny_mixtral(str(tmp_path)))
    def backend(**leaves):
        params = {name: jax.ShapeDtypeStruct((2, *leaf.shape), leaf.dtype) for name, leaf in family.block_param_shapes(cfg, BF16).items()}
        return TransformerBackend(family, cfg, {**params, **leaves}, first_block=0, n_blocks=2, memory_cache=None)
    plain = backend()
    assert plain.moe_grouped(1) == "hit" and plain.moe_grouped(5) == "hit" and plain.moe_grouped(40, chunk=True) == "grouped"
    from petals_tpu.ops.quant import QuantizedLinear
    quantized = backend(w1=QuantizedLinear("nf4", None, None, 0, 0))
    assert quantized.moe_grouped(1) == "dense" == quantized.moe_grouped(40, chunk=True)


# ---------------------------------------------------------------- the step leaves the page pool where it lies


def _row_of(dims: tuple, rows: set):
    """Which of ``rows`` (trailing dims of a token row: as the pool stores it,
    as attention sees it) ``dims`` ends in, or None."""
    return next((row for row in rows if dims[-len(row):] == row), None)


def pool_moves(hlo: str, pool_shape: tuple, heads: tuple) -> tuple:
    """``(moves, loops_seen)``: every instruction of an optimized HLO module
    that moves a page pool, or a layer of one, and computes nothing.
    ``pool_shape`` is the stacked pool's as it is stored, ``[layers, n_pages,
    page_size, hkv, d]`` or ``[layers, n_pages, page_size, hkv * d]``
    (ops/paged_attention.py ``stored_row``), ``heads`` its ``(hkv, d)``: a
    row counts in either form. In ``ENTRY``: an ``AllocateBuffer`` custom
    call or a ``copy`` of the
    pool's size (a second pool, and the copy back over the donated one). In a
    while body: an instruction that produces a pool layer's worth of rows of
    ``[hkv, d]`` or more and is one of ``_MOVES``, a fusion made of them alone, or a fusion
    whose root is a ``dynamic-update-slice`` (a layer written back whole). The
    in-place scatter of the new rows is a ``scatter`` fusion and the gather of
    the tables' pages computes a select, so neither counts. ``loops_seen``
    counts the while bodies that carry an array of the pool's size, so that a
    caller can tell "none found" from "not parsed"."""
    comps = _computations(hlo)
    pool_elements = math.prod(pool_shape)
    layer_elements = pool_elements // pool_shape[0]
    rows = {tuple(pool_shape[3:]), tuple(heads)}
    entry = re.search(r"^ENTRY\s+%([\w.\-]+)", hlo, re.MULTILINE).group(1)
    moves = [
        f"ENTRY %{name} = {op} -> {list(dims)}"
        for name, dims, op, rest in comps[entry]
        if math.prod(dims) == pool_elements
        and (op == "copy" or (op == "custom-call" and 'custom_call_target="AllocateBuffer"' in rest))
    ]
    loops_seen = 0
    for body in _while_bodies(comps):
        carried = False
        for name, dims, op, rest in body:
            if op == "get-tuple-element":
                carried = carried or math.prod(dims) == pool_elements
                continue
            if op in ("parameter", "tuple", "bitcast", "constant") or _row_of(dims, rows) is None:
                continue  # not rows of [kv heads, head_dim], folded or not: a weight
            if math.prod(dims) < layer_elements:
                continue
            fused = _fused(comps, op, rest)
            if _only_moves(comps, op, rest) or (fused is not None and fused[-1][2] == "dynamic-update-slice"):
                moves.append(f"%{name} = {op} -> {list(dims)}")
        loops_seen += carried
    return moves, loops_seen


POOL_CASES = [
    pytest.param(config_name, chunk, id=f"{config_name}-{'mixed-256' if chunk else 'decode'}")
    for config_name in ("falcon-40b-span5", "mixtral-8x7b-span2", "olmoe-1b-7b-span8", "k-exaone-236b-span5-ep8")
    for chunk in (0, 256)
]


@pytest.mark.parametrize("config_name,chunk", POOL_CASES)
def test_paged_step_leaves_the_page_pool_in_place(v5e, tmp_path, config_name, chunk):
    """The page pools are the layer loop's carry (``backend._scan_paged_span``):
    the compiled step allocates no second pool, copies none, and no layer of
    one is sliced out, copied or written back whole inside the loop. With the
    pools as the scan's ``xs`` / ``ys`` (until PR 29) OLMoE's decode step held
    two ``AllocateBuffer`` and two ``copy`` of ``bf16[8,128,64,16,128]`` (268
    MB each) in ``ENTRY`` and, a layer and a pool, a ``dynamic-slice`` fusion,
    a ``copy-start`` / ``copy-done`` and a ``dynamic-update-slice`` fusion of
    a whole layer in the body: 2.7 ms of a 19.7 ms step on the chip. Falcon's
    pool of head_dim 64, handed over as ``bf16[5,128,64,8,64]``, lived on the
    device with the page index minor (``{1,4,3,2,0}``: 64 is under the
    128-lane tile) and ``ENTRY`` relaid both pools before the loop and back
    after it, 0.8 ms of a 12.3 ms step (until PR 38); stored as
    ``bf16[5,128,64,512]`` it is handed over in the layout the step computes
    in."""
    hlo, _, pool, heads = _compiled_step(v5e, tmp_path, config_name, chunk)
    if config_name == "falcon-40b-span5":
        assert tuple(pool.shape[3:]) == (heads[0] * heads[1],), pool.shape
    moves, loops_seen = pool_moves(hlo, tuple(pool.shape), heads)
    assert loops_seen, "no loop carries the pool: has the HLO text changed, or the pool left the carry?"
    assert not moves, f"the step moves the page pool around its {pool.shape[0]} layers: {moves}"


@pytest.mark.parametrize("config_name,kv_quant", [("falcon-40b-span5", "int8"), ("mixtral-8x7b-span2", "nf4a")])
def test_paged_step_leaves_a_quantized_pool_of_narrow_codes_in_place(v5e, tmp_path, config_name, kv_quant):
    """The rule is the codes' own: int8 codes of a head_dim of 64 are 64 wide
    and nf4a's packed bytes of a head_dim of 128 are too, and either pool,
    kept as rows of ``[hkv, d_store]``, is relaid whole four times a step in
    ``ENTRY`` as Falcon's bf16 pool was (``s8[5,128,64,8,64]``,
    ``u8[2,128,64,8,64]``: compiled for the v5e, PR 38). Stored folded, none."""
    hlo, _, codes, heads = _compiled_step(v5e, tmp_path, config_name, 0, kv_quant=kv_quant)
    assert len(codes.shape) == 4 and codes.shape[3] == heads[0] * 64  # folded; d_store is 64 in both
    moves, loops_seen = pool_moves(hlo, tuple(codes.shape), (heads[0], 64))
    assert loops_seen, "no loop carries the pool: has the HLO text changed, or the pool left the carry?"
    assert not moves, f"the step moves the codes around their {codes.shape[0]} layers: {moves}"


@pytest.mark.parametrize("config_name,pages_a_lane", [("olmo-hybrid-7b-span16", 40), ("olmoe-1b-7b-span8", 16), ("falcon-40b-span5", 16)])
def test_decode_step_makes_no_dense_view_of_the_lanes_tables(v5e, tmp_path, config_name, pages_a_lane):
    """A decode row walks its lane's pages in blocks (ops/paged_flash_attention.py
    ``composed_paged_attend``): the compiled decode step, at the cell's table
    width, produces no array of ``n_lanes x max_pages x page_size`` rows of
    ``[hkv, d]`` or more besides the pool itself, in any dtype. Until PR 36 a
    layer made four: the bf16 gather of every table slot of every lane, for
    keys and for values, and ``attend_reference``'s float32 copy of each
    (1.0 GB moved a layer and a step at 8 lanes of 40 pages and 32 kv heads).
    Falcon's pool is stored folded (rows of ``hkv * d``): its rows count in
    that form and as the ``[hkv, d]`` a walk's block unfolds them to."""
    hlo, runs, pool, heads = _compiled_step(v5e, tmp_path, config_name, 0, pages_a_lane)
    rows = {tuple(pool.shape[3:]), heads}
    weights = {tuple(p.shape)[cut:] for run in runs for p in run.values() for cut in (0, 1)}  # wk is [hidden, hkv * d] too
    comps = _computations(hlo)
    fused = {m.group(1) for instructions in comps.values() for _, _, op, rest in instructions if op == "fusion" and (m := re.search(r"calls=%([\w.\-]+)", rest))}
    pool_elements, view_rows = math.prod(pool.shape), 8 * pages_a_lane * 64
    seen, views = 0, []
    for computation, instructions in comps.items():
        if computation in fused:
            continue  # what a fusion computes inside it is never an array in memory
        for name, dims, op, _ in instructions:
            row = _row_of(dims, rows)
            if row is None or dims in weights or op in ("parameter", "get-tuple-element", "bitcast", "tuple"):
                continue
            if math.prod(dims) == pool_elements:
                seen += 1  # the pool, written in place by the new rows' scatter
            elif math.prod(dims[:-len(row)]) >= view_rows:
                views.append(f"%{name} = {op} -> {list(dims)}")
    assert seen, "the pool's scatter was not found: has the HLO text changed?"
    assert not views, f"the decode step makes a dense view of the lanes' tables: {views}"


@pytest.mark.parametrize("chunk", [0, 512], ids=["decode", "mixed-512"])
def test_paged_step_leaves_the_state_pool_and_its_pages_in_place(v5e, tmp_path, chunk):
    """A span with a recurrent state carries its state pool through the layer
    loop beside the pages (``backend._scan_paged_span``): the compiled step of
    olmo-hybrid-7b-span16 allocates no second state pool and copies none in
    ``ENTRY`` (708 MB of float32 at 8 lanes and 12 layers), and the chunked
    form's triangular solve compiles for the chip."""
    hlo, _, pool, _ = _compiled_step(v5e, tmp_path, "olmo-hybrid-7b-span16", chunk)
    assert pool.shape[0] == 4
    comps = _computations(hlo)
    entry = re.search(r"^ENTRY\s+%([\w.\-]+)", hlo, re.MULTILINE).group(1)
    # the float32 state pool, and a page pool: four layers deep, each in a run of one block, which is no loop once
    # compiled, so ``pool_moves``'s look into the loop bodies has nothing to see here and ENTRY holds it all
    for what, elements in (("state", 12 * 8 * 30 * 96 * 192), ("page", math.prod(pool.shape))):
        assert any(math.prod(dims) == elements for _, dims, _, _ in comps[entry]), f"the {what} pool was not found in ENTRY"
        moved = [f"%{name} = {op}" for name, dims, op, rest in comps[entry] if math.prod(dims) == elements
                 and (op in ("copy", "copy-start") or (op == "custom-call" and 'custom_call_target="AllocateBuffer"' in rest))]
        assert not moved, f"the step moves the {what} pool: {moved}"
    # the decode rows' walk is a kernel that reads the pools the chunk's scatter writes (PR 45): each full layer holds it once
    assert len(decode_walk_calls(hlo, "paged_decode_walk")) == 4
    # and their one-step rule a kernel on the state pool where it lies (PR 49): one a run of three linear layers
    _the_rule_is_one_kernel_a_linear_run_on_the_pool_as_the_loop_carries_it(hlo, 4, (12, 8, 30, 96, 192))


def _the_rule_is_one_kernel_a_linear_run_on_the_pool_as_the_loop_carries_it(hlo: str, runs: int, pool: tuple) -> None:
    """A compiled step's one-step rule (ops/linear_attention.py
    ``_step_kernel``): one ``tpu_custom_call`` under ``ptu.linattn.recurrent``
    a run of linear layers, handed the state pool as the loop carries it (or
    as ``ENTRY`` was handed it, or as the call before it in the same layer
    left it) and aliased to its own result, and nothing left in the program
    whose result is a whole layer's states (the plain form's passes were
    fusions of ``[lanes, heads, d_k, d_v]``)."""
    calls = decode_walk_calls(hlo, "gated_delta_step")
    assert len(calls) == runs, [op_name for _, op_name, _ in calls]
    for _, op_name, operands in calls:
        assert "ptu.linattn.recurrent" in op_name, op_name
        handed = [op for op, _, dims in operands if tuple(dims) == pool]
        assert handed and set(handed) <= {"get-tuple-element", "parameter"}, f"the kernel is handed a pool something made: {operands}"
    assert hlo.count("output_to_operand_aliasing={{1}: (9, {})}") == runs
    a_layer = "f32[" + ",".join(map(str, pool[1:])) + "]"
    whole = [line.strip()[:160] for line in hlo.splitlines() if re.search(r"= \(?" + re.escape(a_layer), line)]
    assert not whole, f"the step still makes a whole layer's states: {whole}"


STATE_KERNEL_SHAPES = [
    pytest.param((6, 8, 32, 128, 128), 8, id="qwen3next-6-layers-of-32x128x128"),
    pytest.param((12, 8, 30, 96, 192), 6, id="olmohybrid-12-layers-of-30x96x192"),
]


@pytest.mark.parametrize("pool,fewer", STATE_KERNEL_SHAPES)
def test_gated_delta_step_kernel_lowers_at_both_configurations_sizes(v5e, pool, fewer):
    """The one-step rule's kernel alone, through Pallas -> Mosaic -> libtpu for
    the v5e, at ``step_kernel_heads``' grouping (a lane's heads all in one
    grid step) and at ``fewer`` heads a step: the donated pool is aliased to
    the result and nothing copies it (Olmo-Hybrid's ``d_v`` of 192 is the
    array's full last dimension, its 96 rows whole sublane tiles)."""
    from petals_tpu.ops import linear_attention as la

    _, lanes, heads, d_k, d_v = pool
    avals = (v5e(pool, F32), v5e((), I32), *(v5e((lanes, heads, d), F32) for d in (d_k, d_k, d_v)), v5e((lanes, heads), F32), v5e((lanes, heads), F32),
             v5e((lanes,), jnp.bool_), v5e((lanes,), jnp.bool_))
    assert la.step_kernel_unsupported(la.StatePool((avals[0],), 0), 1) is None
    assert la.step_kernel_heads(heads, d_k, d_v) == heads
    for heads_a_step in (None, fewer):
        def rule(matrix, slot, q, k, v, g, beta, live, fresh):
            state, out = la.gated_delta_pooled(la.StatePool((matrix,), slot), q, k, v, g, beta, live=live, fresh=fresh, path="kernel", heads_a_step=heads_a_step)
            return state.leaves[0], out

        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(la, "_interpret", lambda: False)  # the backend here is the CPU: the kernel would be interpreted
            hlo = jax.jit(rule, donate_argnums=(0,)).lower(*avals).compile().as_text()
        assert "tpu_custom_call" in hlo and "output_to_operand_aliasing={{1}: (9, {})}" in hlo
        moved = [line.strip()[:120] for line in hlo.splitlines() if re.search(r"= f32\[" + ",".join(map(str, pool)) + r"\]\S* (copy|fusion)\(", line)]
        assert not moved, moved


SPARSE_CASES = [pytest.param(0, id="decode"), pytest.param(2048, id="mixed-2048")]


@pytest.mark.parametrize("chunk", SPARSE_CASES)
def test_sparse_step_leaves_the_pages_and_the_index_pool_in_place(v5e, tmp_path, chunk):
    """A span whose positions cache an index row carries a third pool through
    the layer loop (``backend._scan_paged_span``): the compiled decode step and
    the mixed step with a chunk of the budget's 2,048 rows of
    keye-vl2-30b-a3b-span5, at the cell's 8 lanes and tables of 512 pages,
    allocate no second pool of keys, values or index rows and copy none in
    ``ENTRY``, and move no layer of one in the loop. The index row is 64 wide,
    the shape PR 38 folded for keys and values: stored a position a row,
    ``bf16[5,4096,64,64]`` lived with the page index minor and ``ENTRY`` relaid
    it whole on its way in and out (met here, compiling for the chip, before
    any chip call); stored two positions to a row of 128
    (ops/sparse_attention.py ``index_pool_row``) it lies as it is handed over."""
    from petals_tpu.ops.sparse_attention import index_pool_row

    hlo, _, pool, heads = _compiled_step(v5e, tmp_path, "keye-vl2-30b-a3b-span5", chunk, pages_a_lane=512)
    assert tuple(pool.shape) == (5, 4096, 64, 4, 128) and index_pool_row(64, 64) == (32, 128)
    moves, loops_seen = pool_moves(hlo, tuple(pool.shape), heads)
    assert loops_seen, "no loop carries the pool: has the HLO text changed, or the pool left the carry?"
    assert not moves, f"the step moves the page pool around its 5 layers: {moves}"
    index_shape = (5, 4096, 32, 128)
    comps = _computations(hlo)
    entry = re.search(r"^ENTRY\s+%([\w.\-]+)", hlo, re.MULTILINE).group(1)
    assert any(tuple(dims) == index_shape for _, dims, _, _ in comps[entry]), "the index pool was not found in ENTRY"
    moved = [f"%{name} = {op} -> {list(dims)}" for name, dims, op, rest in comps[entry] if math.prod(dims) == math.prod(index_shape)
             and (op == "copy" or (op == "custom-call" and 'custom_call_target="AllocateBuffer"' in rest))]
    assert not moved, f"the step moves the index pool: {moved}"
    # in the loop: no layer of index rows (4096 pages x 64 positions x 64) sliced out, copied or written back whole
    layer = math.prod(index_shape[1:])
    inside = [f"%{name} = {op} -> {list(dims)}" for body in _while_bodies(comps) for name, dims, op, rest in body
              if dims[-1:] == (128,) and math.prod(dims) == layer and op not in ("parameter", "tuple", "bitcast", "get-tuple-element")
              and (_only_moves(comps, op, rest) or ((fused := _fused(comps, op, rest)) is not None and fused[-1][2] == "dynamic-update-slice"))]
    assert not inside, f"the loop moves a layer of the index pool: {inside}"


def test_sparse_decode_step_fetches_the_chosen_rows_and_makes_no_view_of_a_lane_s_table(v5e, tmp_path):
    """A decode row of a span that selects scores its lane's index keys where
    they lie and fetches the ``topk`` chosen positions' keys and values
    (ops/sparse_attention.py ``sparse_decode_attend``): the compiled decode
    step of keye-vl2-30b-a3b-span5 at 8 lanes of 512 pages holds no array of
    ``8 x 32,768`` rows of ``[4, 128]`` besides the pools (a gather of the
    whole tables to mask would be 268 MB a pool and a layer); what it does
    hold is ``8 x 2,048`` rows of keys and of values, the chosen ones."""
    hlo, runs, pool, heads = _compiled_step(v5e, tmp_path, "keye-vl2-30b-a3b-span5", 0, pages_a_lane=512)
    weights = {tuple(p.shape)[cut:] for run in runs for p in run.values() for cut in (0, 1)}
    comps = _computations(hlo)
    fused = {m.group(1) for instructions in comps.values() for _, _, op, rest in instructions if op == "fusion" and (m := re.search(r"calls=%([\w.\-]+)", rest))}
    pool_elements, view_rows = math.prod(pool.shape), 8 * 512 * 64
    seen, views, fetched = 0, [], 0
    for computation, instructions in comps.items():
        if computation in fused:
            continue  # what a fusion computes inside it is never an array in memory
        for name, dims, op, _ in instructions:
            if op in ("parameter", "get-tuple-element", "bitcast", "tuple") or dims in weights:
                continue
            if math.prod(dims) == pool_elements and dims[-2:] == heads:
                seen += 1  # a pool, written in place by the new rows' scatter
            elif dims[-2:] == heads and math.prod(dims[:-2]) >= view_rows:
                views.append(f"%{name} = {op} -> {list(dims)}")
            fetched += dims[-2:] == heads and math.prod(dims[:-2]) == 8 * 2048  # as the gather leaves them: [16384, 4, 128]
    assert seen, "the pool's scatter was not found: has the HLO text changed?"
    assert fetched, "the chosen rows' gather, [8, 2048, 4, 128], was not found"
    assert not views, f"the decode step makes a dense view of the lanes' tables: {views}"


def test_sparse_decode_step_runs_nothing_between_the_pool_and_the_dot_but_the_sort_and_the_fetches(v5e, tmp_path):
    """Between the index keys' scoring and the two dots a decode row's call
    holds the sort and the fetches and nothing else that is not arithmetic on
    a megabyte (ops/sparse_attention.py ``select_rows``, ``_take_rows``). In
    the compiled decode step of keye-vl2-30b-a3b-span5 at 8 lanes of 512
    pages, none of the three that PR 39's step ran a layer (PERF.md section
    5): no gather out of the ``s32[8,512]`` tables with 16,384 results (the
    chosen positions' pages looked up a scalar at a time: they are read off
    by a compare with the table's slots), no select that writes a block of
    fetched index pages or the fetched rows (the fill of ``jnp.take``'s
    default mode), no copy of ``[16384,4,128]`` (the fetched rows relaid
    heads-major for the dots: the fill's select had left them in a layout of
    its own; as the gathers leave them the dots take them). The two fetches
    of ``[8,2048,4,128]`` are there, as the gathers leave them
    (``[16384,4,128]``), and the sort is one of two operands, the scores'
    keys and the positions: a stable sort of one key that carried the pool
    rows is compiled as a sort of three (PERF.md section 6, PR 40)."""
    hlo, _, _, heads = _compiled_step(v5e, tmp_path, "keye-vl2-30b-a3b-span5", 0, pages_a_lane=512)
    comps = _computations(hlo)
    fused = {m.group(1) for instructions in comps.values() for _, _, op, rest in instructions if op == "fusion" and (m := re.search(r"calls=%([\w.\-]+)", rest))}
    lookups, fills, relayouts, fetches, sorts = [], [], [], 0, []
    fetched = ((256, 32, 128), (8, 1024, 128), (16384, *heads), (8, 2048, *heads))  # a block's index pages; the chosen rows
    for computation, instructions in comps.items():
        dims_of = {name: dims for name, dims, _, _ in instructions}
        for name, dims, op, rest in instructions:
            if op == "gather":  # in a fusion: its operands are the fusion's parameters, so its operand's shape says what it reads
                operand = dims_of.get(next(iter(re.findall(r"%([\w.\-]+)", rest)), None))
                if operand == (8, 512) and math.prod(dims) == 8 * 2048:
                    lookups.append(f"%{name} in %{computation}")
                fetches += operand == (5 * 4096 * 64, *heads) and math.prod(dims) == 8 * 2048 * math.prod(heads)
            if computation in fused:
                continue  # what follows are arrays in memory: a fusion's root is its caller's instruction
            root = (_fused(comps, op, rest) or [(name, dims, op, rest)])[-1][2]
            if root == "select" and dims in fetched:
                fills.append(f"%{name} -> {list(dims)}")
            if dims == (16384, *heads) and _only_moves(comps, op, rest) and op != "bitcast":
                relayouts.append(f"%{name} = {op}")
            if op == "sort" and dims == (8, 32768):
                sorts.append(re.findall(r"(\w+)\[8,32768\]", hlo.split(f"%{name} = ")[1].split(" sort(")[0]))
    assert fetches == 2, f"the chosen rows' two gathers out of the pool were not found: {fetches}"
    assert sorts == [["u32", "s32"]], f"the one sort of a lane's scores with their positions, two operands, was not found: {sorts}"
    assert not lookups, f"the decode step looks the chosen positions' pages up in the tables: {lookups}"
    assert not fills, f"the decode step selects fetched rows against a fill value: {fills}"
    assert not relayouts, f"the decode step relays the fetched rows: {relayouts}"


# -------------------------------------------------------------------------------------------------
# a latent row in place of keys and values (kanana2-30b-a3b-span6: ``deepseek_v3``, ops/latent_attention.py)
# -------------------------------------------------------------------------------------------------

LATENT_CONFIG = "kanana2-30b-a3b-span6"
LATENT_POOLS = ((6, 4096, 64, 512), (6, 4096, 32, 128))  # the latents a position a row; the rotated keys two positions a row


def _arrays_in_memory(comps: dict):
    """``(computation, name, dims, op, rest)`` of every instruction whose result is an array in memory: not
    inside a fusion, not a parameter, a tuple's element or a bitcast."""
    fused = {m.group(1) for instructions in comps.values() for _, _, op, rest in instructions
             if op == "fusion" and (m := re.search(r"calls=%([\w.\-]+)", rest))}
    for computation, instructions in comps.items():
        if computation in fused:
            continue
        for name, dims, op, rest in instructions:
            if op not in ("parameter", "get-tuple-element", "bitcast", "tuple"):
                yield computation, name, dims, op, rest


@pytest.mark.parametrize("chunk", SPARSE_CASES)
def test_latent_step_leaves_both_pools_in_place_and_reads_its_weights_where_they_lie(v5e, tmp_path, chunk):
    """A span whose positions cache a latent row hands the layer loop two
    pools of different shapes where keys and values would ride
    (``backend.paged_cache_descriptors``): at kanana2-30b-a3b-span6's widths, 8
    lanes and tables of 512 pages, ``bf16[6,4096,64,512]`` of latents and
    ``bf16[6,4096,32,128]`` of rotated keys (64 wide: two positions to a row
    of 128, as an index row is stored; 576 is no multiple of the chip's 128
    lanes, so one row of 576 would pad to 640). The compiled decode step and
    the mixed step with a chunk of the budget's 2,048 rows allocate no second
    pool and copy none, in ``ENTRY`` or in a layer of the loop. The mixed
    step did, before the chip was ever called: nothing ordered the decode
    rows' walk (a loop that reads the pools) against the chunk's writes, the
    compiler wrote the chunk first and kept a copy of both pools for the walk,
    3.2 GB moved a layer (``backend._paged_mixed_step_fn`` ties the pools the
    chunk writes to the walk's result). And the loop slices no stacked weight
    out into a buffer of its own: ``wuk`` / ``wuv`` meet both forms' dots as
    they are stored."""
    hlo, runs, pool, _ = _compiled_step(v5e, tmp_path, LATENT_CONFIG, chunk, pages_a_lane=512)
    assert tuple(pool.shape) == LATENT_POOLS[0]
    comps = _computations(hlo)
    entry = re.search(r"^ENTRY\s+%([\w.\-]+)", hlo, re.MULTILINE).group(1)
    for shape in LATENT_POOLS:
        elements, layer = math.prod(shape), math.prod(shape[1:])
        assert any(tuple(dims) == shape for _, dims, _, _ in comps[entry]), f"the pool {shape} was not found in ENTRY"
        moved = [f"%{name} = {op} -> {list(dims)} in %{computation}" for computation, name, dims, op, rest in _arrays_in_memory(comps)
                 if dims[-1:] == shape[-1:] and math.prod(dims) >= layer
                 and (op == "custom-call" and 'custom_call_target="AllocateBuffer"' in rest or _only_moves(comps, op, rest)
                      or (computation != entry and (fused := _fused(comps, op, rest)) is not None and fused[-1][2] == "dynamic-update-slice"
                          and math.prod(dims) < elements))]
        assert not moved, f"the step moves the pool {shape}: {moved}"
    stacked = {tuple(p.shape) for run in runs for p in run.values()}
    smallest = min(math.prod(run[name].shape[1:]) for run in runs for name in ("wuk", "wuv", "wkva"))
    relayouts, seen = weight_relayouts(hlo, stacked, smallest)
    assert seen, "the loop's stacked weights were not found: has the HLO text changed?"
    # the mixed step's known ones: the chunk's grouped expert dispatch is handed a copy of the layer's w1, w3 and w2
    # (ragged_dot is a custom call: ROADMAP S7, Keye's mixed step alike), and the chunk's walk, a loop of its own,
    # is handed ``wuk`` and ``wuv`` as arrays (4 MB each a layer: 10 us of a mixed step's layer)
    known = {(32, 128, 512), (32, 512, 128), (128, 2048, 768), (128, 768, 2048)} if chunk else set()
    relayouts = [r for r in relayouts if tuple(json.loads(r.split(" -> ")[1])) not in known]
    assert not relayouts, f"the step's loop relays a weight in every layer of every step: {relayouts}"
    moved = entry_weight_moves(hlo, stacked, smallest)
    assert not moved, f"the step relays a weight of the dense run of one block in every step: {moved}"


def decode_walk_calls(hlo: str, kernel: str = "latent_decode_walk") -> list:
    """``[(computation, op_name, [operand dims])]`` of every call of a decode
    walk's kernel (ops/latent_attention.py's, named ``latent_decode_walk``;
    ops/paged_flash_attention.py's ``paged_decode_walk``) in an optimized HLO
    module, an operand traced through bitcasts to what it is a view of."""
    comps, calls = _computations(hlo), []
    for computation, instructions in comps.items():
        by_name = {name: (op, dims, rest) for name, dims, op, rest in instructions}
        for name, _, op, rest in instructions:
            if op == "custom-call" and name.startswith(kernel) and "tpu_custom_call" in rest:
                operands = []
                for operand in re.findall(r"%([\w.\-]+)", rest.split("), custom_call_target")[0]):
                    while by_name[operand][0] == "bitcast":
                        operand = re.search(r"%([\w.\-]+)", by_name[operand][2]).group(1)
                    operands.append((by_name[operand][0], _fused(comps, *by_name[operand][::2]), by_name[operand][1]))
                calls.append((computation, re.search(r'op_name="([^"]*)"', rest).group(1), operands))
    return calls


def latent_decode_findings(hlo: str, runs) -> dict:
    """What a compiled decode step of kanana2-30b-a3b-span6 holds in memory
    that the walk's kernel does without: arrays of latent rows or rotated keys
    beside the two pools (more rows of 512 than the lanes' 8 x 32 absorbed
    queries, more of 128 than their 8 x 64 doubled rotated ones), float32
    scores of a block (``[8, 32, more than a latent row]``), keys or values of
    any head (rows of ``[32, 128 | 192 | 256]`` beyond the lanes' own query
    rows). And the pools it found, so that "none" is not "not parsed"."""
    weights = {tuple(p.shape)[cut:] for run in runs for p in run.values() for cut in (0, 1)}
    found = {"pools": 0, "rows": [], "scores": [], "expanded": []}
    for computation, name, dims, op, rest in _arrays_in_memory(_computations(hlo)):
        line = f"%{name} = {op} -> {list(dims)}"
        if dims in weights or len(dims) < 2:
            continue
        if any(math.prod(dims) == math.prod(shape) and dims[-1:] == shape[-1:] for shape in LATENT_POOLS):
            found["pools"] += 1  # a pool, written in place by the new rows' scatter
        elif math.prod(dims[:-1]) > {512: 8 * 32, 128: 8 * 64}.get(dims[-1], math.inf):
            found["rows"].append(line)
        if dims[:2] == (8, 32) and math.prod(dims[2:]) > 512:
            found["scores"].append(line)
        if dims[-2:] in ((32, 128), (32, 192), (32, 256)) and math.prod(dims[:-2]) > 8:
            found["expanded"].append(line)
    return found


def test_latent_decode_step_makes_no_view_of_the_tables_and_expands_no_lane_s_keys_or_values(v5e, tmp_path):
    """A decode row takes the absorbed form in ONE kernel a layer
    (ops/latent_attention.py ``latent_decode_attend``): the compiled decode
    step holds one ``tpu_custom_call`` under ``ptu.attn.latent_decode`` in
    each layer loop's body (the dense run of one block is unrolled into
    ``ENTRY``), handed the two whole-span pools as the loop carries them and
    the new rows' scatter leaves them: no copy, no slice. Beside the pools
    there is no array of latent rows or rotated keys in memory (the composed
    walk's block was ``bf16[512,64,512]``, 38 MB fetched before a dot), no
    float32 scores of a block or transposing copy of them (``f32[8,32,2048,2]``,
    ``f32[8,32,4096]``), and no key or value of any head. The composed walk's
    step, compiled beside it, shows each of the three: the guard can fail."""
    hlo, runs, _, _ = _compiled_step(v5e, tmp_path, LATENT_CONFIG, 0, pages_a_lane=512)
    calls = decode_walk_calls(hlo)
    assert len(calls) == len(runs) == 2, calls
    for computation, op_name, operands in calls:
        assert "ptu.attn.latent_decode" in op_name, op_name
        pools = [(op, fused, dims) for op, fused, dims in operands if math.prod(dims) >= math.prod(LATENT_POOLS[1])]
        assert sorted(math.prod(dims) for _, _, dims in pools) == sorted(math.prod(shape) for shape in LATENT_POOLS), operands
        for op, fused, dims in pools:  # as the loop carries it, or as the scatter of the new rows (in place: the pools' own test) left it
            assert op in ("get-tuple-element", "parameter") or (fused is not None and any(i[2] in ("dynamic-update-slice", "scatter") for i in fused)), (op, dims)
    found = latent_decode_findings(hlo, runs)
    assert found["pools"], "the pools' scatters were not found: has the HLO text changed?"
    assert not found["rows"], f"the decode step holds latent rows or rotated keys beside the pools: {found['rows']}"
    assert not found["scores"], f"the decode step holds a block's scores in memory: {found['scores']}"
    assert not found["expanded"], f"the decode step expands keys or values: {found['expanded']}"
    composed, _, _, _ = _compiled_step(v5e, tmp_path, LATENT_CONFIG, 0, pages_a_lane=512, latent_kernel=False)
    was = latent_decode_findings(composed, runs)
    assert not decode_walk_calls(composed) and was["pools"] and was["rows"] and was["scores"], was


def test_latent_decode_kernel_lowers_at_the_published_shapes(v5e):
    """The walk's kernel alone, through Pallas -> Mosaic -> libtpu for the v5e
    at kanana2-ctx32k's shapes: 8 lanes of 32 heads, tables of 512 pages, the
    span's pools of 6 x 4,096 pages of ``[64, 512]`` latents and ``[32, 128]``
    rotated keys, which it is handed whole."""
    from petals_tpu.ops import latent_attention as latent
    from petals_tpu.ops.paged_attention import PagedKV

    def walk(q_abs, q_pe, c_pool, pe_pool, tables, positions):
        return latent.latent_decode_attend(q_abs, q_pe, PagedKV(c_pool, tables), PagedKV(pe_pool, tables), positions, scale=192**-0.5, path="kernel")

    avals = (v5e((8, 1, 32, 512), BF16), v5e((8, 1, 32, 64), BF16), v5e((6 * 4096, 64, 512), BF16), v5e((6 * 4096, 32, 128), BF16),
             v5e((8, 512), I32), v5e((8,), I32))
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(latent, "_on_tpu", lambda: True)  # the backend here is the CPU: the kernel would be interpreted
        _compile(walk, *avals)
        body = str(jax.make_jaxpr(walk)(*avals))
    # the copies are a loop and the waits one a pool: written out (64 starts at two sites, 64 waits) they were 2 s of every
    # step program's start, ten programs a server (`setup_s` +40% on the chip)
    assert body.count("dma_start") == 4 and body.count("dma_wait") == 2, (body.count("dma_start"), body.count("dma_wait"))


def test_latent_mixed_step_expands_a_block_of_positions_at_a_time_and_holds_no_whole_score_matrix(v5e, tmp_path):
    """A prompt's chunk takes the expanded form inside a walk
    (``latent_chunk_attend``): the compiled mixed step with a chunk of 2,048
    rows over a table of 32,768 positions holds the scores of a block (``[32,
    2048, 128]`` float32, 33 MB), never the 8.1 GB of the whole ``[32, 2048,
    31k]``, and the keys and values of a block of 128 positions, never a
    lane's (32,768 x 32 x 256 values, 537 MB)."""
    hlo, runs, _, _ = _compiled_step(v5e, tmp_path, LATENT_CONFIG, 2048, pages_a_lane=512)
    weights = {tuple(p.shape)[cut:] for run in runs for p in run.values() for cut in (0, 1)}
    comps = _computations(hlo)
    scores, whole, lane_wide = 0, [], []
    for computation, name, dims, op, _ in _arrays_in_memory(comps):
        if dims in weights or any(math.prod(dims) == math.prod(shape) and dims[-1:] == shape[-1:] for shape in LATENT_POOLS):
            continue
        scores += dims == (32, 2048, 128)
        if math.prod(dims) >= 32 * 2048 * 2048:
            whole.append(f"%{name} = {op} -> {list(dims)}")
        if dims[-2:] in ((32, 128), (32, 192), (32, 256)) and math.prod(dims[:-2]) > 2048:
            lane_wide.append(f"%{name} = {op} -> {list(dims)}")
    assert scores, "a block's scores, [32, 2048, 128], were not found"
    assert not whole, f"the mixed step holds a chunk's scores against more than a block of positions: {whole}"
    assert not lane_wide, f"the mixed step expands more than a chunk's rows or a block's positions: {lane_wide}"


# ---------------------------------------------------------------- a decode row's walk over plain pages, as one kernel

# hq, (hkv, d), table slots a lane as the walk is handed them, window, dtype, lanes. The first two are the pools of rows of
# [hkv, 128] that configurations store and the kernel takes (Olmo-Hybrid's, OLMoE's); the next two its query groups and its
# window at a shape it takes; the float32 ones are NO configuration's (Mixtral's and K-EXAONE's 8 kv heads of 128 are stored
# in bfloat16, half a tile, and refused: the next tests): a float32 pool is what tests and a float32 server store; then the
# widest table the predicate lets through (``WALK_KERNEL_TABLE_BYTES``: 8 lanes of a million positions); then five
# FOLDED rows of up to 4 kv heads (``stored_row``): Qwen3-Next's 2 of 256 and Jamba's one of 128 under 20 query heads,
# as their configurations store them, then a window, float32 and three heads at such a row; the last two are SmallThinker's
# two walks at its cell's shapes (PR 65): 16 lanes, 28 query heads over a folded row of 4 x 128, a full layer's table of 256
# slots and a windowed layer's cut to the 65 its window of 4,096 reaches
WALK_KERNEL_SHAPES = [
    pytest.param(32, (32, 128), 40, None, BF16, 8, id="olmo-hybrid-32x128-over-40"),
    pytest.param(16, (16, 128), 16, None, BF16, 8, id="olmoe-16x128-over-16"),
    pytest.param(64, (16, 128), 16, None, BF16, 8, id="16x128-4-query-heads-a-kv-head"),
    pytest.param(64, (16, 128), 16, 128, BF16, 8, id="16x128-window-128"),
    pytest.param(32, (8, 128), 16, None, F32, 8, id="float32-8x128-4-query-heads-a-kv-head"),
    pytest.param(64, (8, 128), 16, 128, F32, 8, id="float32-8x128-8-query-heads-a-kv-head-window-128"),
    pytest.param(16, (16, 128), pfa.WALK_KERNEL_TABLE_BYTES // (4 * 8), None, BF16, 8, id="16x128-tables-at-the-scalar-memory-budget"),
    pytest.param(16, (2, 256), 40, None, BF16, 8, id="qwen3-next-folded-2x256-over-40"),
    pytest.param(20, (1, 128), 40, None, BF16, 8, id="jamba-folded-1x128-20-query-heads-over-40"),
    pytest.param(16, (2, 256), 40, 128, BF16, 8, id="folded-2x256-window-128"),
    pytest.param(8, (2, 128), 16, None, F32, 8, id="float32-folded-2x128"),
    pytest.param(12, (3, 128), 16, None, BF16, 8, id="folded-3x128-4-query-heads-a-kv-head"),
    pytest.param(28, (4, 128), 256, None, BF16, 16, id="smallthinker-folded-4x128-28-query-heads-16-lanes-over-256"),
    pytest.param(28, (4, 128), 256, 4096, BF16, 16, id="smallthinker-folded-4x128-28-query-heads-16-lanes-window-4096"),
]


@pytest.mark.parametrize("hq,heads,slots,window,dtype,lanes", WALK_KERNEL_SHAPES)
def test_paged_decode_walk_kernel_lowers_at_the_shapes_it_takes(v5e, hq, heads, slots, window, dtype, lanes):
    """The walk's kernel alone (ops/paged_flash_attention.py ``_walk_kernel``),
    through Pallas -> Mosaic -> libtpu for the v5e: ``lanes`` lanes, pages of 64,
    the span's pools of 5 x ``lanes`` x ``slots`` pages handed whole in the form the
    storage rule keeps their row in (``[64, hkv, 128]``, or folded ``[64, hkv
    * d]``; at most 40 slots a lane's worth: the widest table's pool would not fit the
    chip); under the window the table is cut to the slots in reach first (3 of
    16 at a window of 128, 65 of 256 at one of 4,096).
    One slot a lane over the widest table is the predicate's to refuse: its
    budget is the largest that was seen to compile."""
    def walk(q, k_pool, v_pool, tables, positions):
        return pfa.composed_paged_attend(q, k_pool, v_pool, tables, q_offset=positions, kv_length=positions + 1, sliding_window=window, path="kernel")

    pool = v5e((5 * lanes * min(slots, 40), 64, *stored_row(*heads)), dtype)
    assert (len(pool.shape) == 3) == (heads[0] <= 4)
    avals = (v5e((lanes, 1, hq, heads[1]), dtype), pool, pool, v5e((lanes, slots), I32), v5e((lanes,), I32))
    reach = pfa.window_pages(window, 1, 64, slots)
    assert reach == {None: slots, 128: 3, 4096: 65}[window]
    assert pfa.walk_kernel_unsupported(pool, avals[0].shape, (lanes, reach), window=window) is None
    assert "scalar memory" in pfa.walk_kernel_unsupported(pool, avals[0].shape, (lanes, pfa.WALK_KERNEL_TABLE_BYTES // (4 * lanes) + 1), window=window)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(pfa, "_interpret", lambda: False)  # the backend here is the CPU: the kernel would be interpreted
        _compile(walk, *avals)
        body = str(jax.make_jaxpr(walk)(*avals))
    # the copies are a loop and the waits one a pool (ops/latent_attention.py, PR 43: written out they were seconds of every start)
    assert body.count("dma_start") == 4 and body.count("dma_wait") == 2, (body.count("dma_start"), body.count("dma_wait"))


@pytest.mark.parametrize("config_name,pages_a_lane,calls_in", [
    ("olmo-hybrid-7b-span16", 40, "ENTRY"), ("olmoe-1b-7b-span8", 16, "loop"), ("qwen3-next-80b-a3b-span8-ep4", 40, "ENTRY"), ("jamba2-3b-span28", 40, "ENTRY"),
])
def test_decode_step_walks_each_lane_s_pages_in_one_kernel_a_layer(v5e, tmp_path, config_name, pages_a_lane, calls_in):
    """A decode row's attention over plain pages of a head_dim of whole lanes
    is ONE kernel a layer (ops/paged_flash_attention.py
    ``composed_paged_attend``): the compiled decode step holds one
    ``tpu_custom_call`` under ``ptu.attn.paged_decode`` a run of full layers
    (OLMoE's eight are one loop; Olmo-Hybrid's four runs of one block, and
    Qwen3-Next's and Jamba's two, are unrolled into ``ENTRY``), handed the two
    whole-span pools as the loop carries them and the new rows' scatter leaves
    them, in the form they are stored in (Qwen3-Next's two kv heads of 256 and
    Jamba's one of 128: folded rows); there is no gathered block of a page a
    lane (``[8, 64, hkv, d]`` or ``[8, 64, hkv * d]``: the composed walk made
    two a trip and float32 products of each) and no copy of a pool. The
    composed step, compiled beside it, shows the blocks: the guard can fail."""
    hlo, runs, pool, (hkv, d) = _compiled_step(v5e, tmp_path, config_name, 0, pages_a_lane)
    calls = decode_walk_calls(hlo, "paged_decode_walk")
    assert len(calls) == ({"olmo-hybrid-7b-span16": 4, "olmoe-1b-7b-span8": 1}.get(config_name, 2)), calls
    entry = re.search(r"^ENTRY\s+%([\w.\-]+)", hlo, re.MULTILINE).group(1)
    for computation, op_name, operands in calls:
        assert "ptu.attn.paged_decode" in op_name and (computation == entry) == (calls_in == "ENTRY"), (computation, op_name)
        pools = [(op, fused, dims) for op, fused, dims in operands if math.prod(dims) == math.prod(pool.shape)]
        assert len(pools) == 2, operands
        for op, fused, dims in pools:  # as the loop carries it, or as the scatter of the new rows (in place: the pools' own test) left it
            assert op in ("get-tuple-element", "parameter") or (fused is not None and any(i[2] in ("dynamic-update-slice", "scatter") for i in fused)), (op, dims)

    def blocks(text):  # a page a lane of all kv heads in memory, in any dtype
        return [f"%{name} = {op}" for _, name, dims, op, _ in _arrays_in_memory(_computations(text)) if dims in ((8, 64, hkv, d), (8, 64, hkv * d))]

    moves, _ = pool_moves(hlo, tuple(pool.shape), (hkv, d))
    assert not blocks(hlo) and not moves, (blocks(hlo), moves)
    composed, _, _, _ = _compiled_step(v5e, tmp_path, config_name, 0, pages_a_lane, walk_kernel=False)
    # (over one kv head the composed walk keeps no block of [8, 64, 128] in memory: nothing for the guard to show there)
    assert not decode_walk_calls(composed, "paged_decode_walk") and (blocks(composed) or config_name == JAMBA)


def test_a_folded_pool_s_decode_step_is_the_composed_walk_s_whatever_the_backend(v5e, tmp_path):
    """Falcon's pool (8 kv heads of 64 folded: two heads share a tile's lanes)
    is not the kernel's: the decode step compiled where the kernel may run is
    the step compiled where it may not, instruction for instruction. (Handed
    to the kernel, the five layers' loop staged both pools, 42 MB each, whole
    through the chip's fast memory around the call: PERF.md section 7.)"""
    def program(walk_kernel):  # every computation's instructions, without the source lines they were traced from
        hlo, _, _, _ = _compiled_step(v5e, tmp_path, "falcon-40b-span5", 0, walk_kernel=walk_kernel)
        return {name: [(i[0], i[1], i[2], re.sub(r", metadata=\{[^}]*\}", "", i[3])) for i in instructions] for name, instructions in _computations(hlo).items()}

    on, off = program(True), program(False)
    assert len(on) > 10 and "paged_decode_walk" not in str(on) and on == off


# ---------------------------------------------------------------- a state pool AND an expert stack in one span (PR 48)

Q3N = "qwen3-next-80b-a3b-span8-ep4"


@pytest.mark.parametrize("chunk", [0, 512], ids=["decode", "mixed-512"])
def test_a_span_with_a_state_and_experts_leaves_its_pools_states_and_stacks_in_place(v5e, tmp_path, chunk):
    """qwen3-next-80b-a3b-span8-ep4 at the cell's geometry (8 lanes, 40 pages a
    lane): runs of three linear layers carry the state pool, the page pools
    AND the run's expert stacks through one loop. The compiled step copies no
    page pool (two kv heads of 256 stored as a folded row of 512: as rows of
    ``[2, 256]`` both pools were copied whole four times in ``ENTRY``, 84 MB
    each), no state pool (201 MB of float32) and no expert stack (a run of
    three layers' ``w1``: 805 MB), in ``ENTRY`` or in a loop; every layer's
    decode rows reach their experts through the hit kernel on the stacks as
    the loop carries them, and a chunk's all-experts einsum reads them where
    they lie."""
    hlo, runs, pool, heads = _compiled_step(v5e, tmp_path, Q3N, chunk, pages_a_lane=40)
    assert heads == (2, 256) and tuple(pool.shape) == (2, 320, 64, 512)
    moves, _ = pool_moves(hlo, tuple(pool.shape), heads)
    # no loop carries the page pools (the composed walk's did): the two full layers are runs of one block in ``ENTRY``, and
    # each one's decode rows are a call of the walk's kernel on both pools as they lie
    walks = decode_walk_calls(hlo, "paged_decode_walk")
    assert [sum(math.prod(dims) == math.prod(pool.shape) for _, _, dims in operands) for _, _, operands in walks] == [2, 2], walks
    assert not moves, f"the step moves the page pool: {moves}"
    comps = _computations(hlo)
    entry = re.search(r"^ENTRY\s+%([\w.\-]+)", hlo, re.MULTILINE).group(1)
    state_elements = 6 * 8 * 32 * 128 * 128
    assert any(math.prod(dims) == state_elements for _, dims, _, _ in comps[entry]), "the state pool was not found in ENTRY"
    moved = [f"%{name} = {op}" for instructions in comps.values() for name, dims, op, rest in instructions if math.prod(dims) == state_elements
             and (op in ("copy", "copy-start") or (op == "custom-call" and 'custom_call_target="AllocateBuffer"' in rest))]
    assert not moved, f"the step moves the state pool: {moved}"
    # the decode rows' one-step rule is a kernel on the state pool where it lies (PR 49): one a run of three linear layers
    _the_rule_is_one_kernel_a_linear_run_on_the_pool_as_the_loop_carries_it(hlo, 2, (6, 8, 32, 128, 128))
    # the stacks: every run has one, and the hit kernel is handed it as the loop carries it (or as ENTRY was handed it)
    assert all("w1" in run for run in runs) and [run["w1"].shape for run in runs] == [(3, 128, 2048, 512), (1, 128, 2048, 512)] * 2
    calls = hit_calls(hlo)
    assert len(calls) == len(runs), [name for name, _ in calls]
    for name, operands in calls:
        assert {op for op, dims in operands if len(dims) == 4} <= {"get-tuple-element", "parameter"}, f"%{name} is handed a weight something made: {operands}"
    shapes = {tuple(p.shape) for run in runs for p in run.values()}
    least = 2048 * 512  # one expert's matrix, and the smallest projection of either mixer
    # a copy-start / copy-done into the alternate memory space is the compiler's prefetch of a weight in the layout it had
    # (``entry_weight_moves``): the mixed step's loop prefetches a linear run's ``wq`` so
    prefetches = set(re.findall(r"^\s*%([\w.\-]+) = \(?\w+\[[\d,]*\]\{[^}]*S\(1\)\}[^=]*copy-(?:start|done)\(", hlo, re.MULTILINE))
    relayouts, seen = weight_relayouts(hlo, shapes, least)
    relayouts = [r for r in relayouts if r.split(" = ")[0].lstrip("%") not in prefetches]
    assert seen and not relayouts, relayouts
    assert not entry_weight_moves(hlo, shapes, least)


def test_olmo_hybrid_s_decode_step_is_the_program_it_was_before_the_mixer_moved(v5e, tmp_path, monkeypatch):
    """models/gated_delta.py is Olmo-Hybrid's ``_linear_attention`` with the
    head grouping and beta's factor as parameters: with as many key heads as
    value heads the compiled decode step is, instruction for instruction, the
    one the family's own function compiled to (the parent's, kept here). Since
    PR 49 a lane pool's step hands the mixer its layer's states where they lie
    in the pool (``StatePool``): the parent's function is handed the layer's
    slice and its result written back, as ``backend._scan_paged_span`` did
    around it, and both sides keep the one-step rule's plain form (off the
    chip the shared mixer's is that slice, those selects and that write)."""
    import petals_tpu.models.olmo_hybrid.block as olmo
    from petals_tpu.models.common import mm, rms_norm, silu
    from petals_tpu.ops.linear_attention import causal_conv, gated_delta

    def l2_norm(x, eps=1e-6):
        return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + eps)

    def parents(params, x, pool, position, dims, eps, n_valid, live_rows):  # PR 46's olmo_hybrid/block.py _linear_attention
        cfg = CFG[0]
        batch, seq, _ = x.shape
        heads, d_k, d_v = cfg.linear_num_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim
        u = jnp.concatenate([mm(x, params[name]) for name in ("wq", "wk", "wv")], axis=-1)
        fresh = jnp.broadcast_to(jnp.asarray(position, jnp.int32) == 0, (batch,))
        held_tail = pool.read(1)
        tail = jnp.where(fresh[:, None, None], jnp.zeros((), held_tail.dtype), held_tail)
        mixed, tail = causal_conv(u, tail, params["conv"], n_valid)
        q, k, v = jnp.split(mixed, (heads * d_k, 2 * heads * d_k), axis=-1)
        q = l2_norm(q.reshape(batch, seq, heads, d_k)) * (1.0 / math.sqrt(d_k))
        k = l2_norm(k.reshape(batch, seq, heads, d_k))
        v = v.reshape(batch, seq, heads, d_v)
        beta = jax.nn.sigmoid(mm(x, params["wb"]).astype(jnp.float32)) * (2.0 if cfg.linear_allow_neg_eigval else 1.0)
        decay = -jnp.exp(params["a_log"].astype(jnp.float32))
        g = decay * jax.nn.softplus(mm(x, params["wa"]).astype(jnp.float32) + params["dt_bias"].astype(jnp.float32))
        held = pool.read(0)  # the layer's slice, the rule between the two selects, the layer written back whole
        matrix, out = gated_delta(jnp.where(fresh[:, None, None, None], 0.0, held), q, k, v, g, beta, n_valid)
        pool = pool.write(0, jnp.where(live_rows[:, None, None, None], matrix, held))
        with jax.named_scope("ptu.linattn.gate_norm"):
            gate = silu(mm(x, params["wz"]).astype(jnp.float32)).reshape(batch, seq, heads, d_v)
            out = (rms_norm(out, params["o_norm"], cfg.rms_norm_eps) * gate).astype(x.dtype)
        y = mm(out.reshape(batch, seq, heads * d_v), params["wo"])
        tail = jnp.where(live_rows[:, None, None], tail, held_tail)
        return y, pool.write(1, tail)

    CFG = []
    real_dims = olmo.mixer_dims

    def dims_and_cfg(cfg):  # the parent's function read the configuration; the shared one is handed its dims
        CFG[:] = [cfg]
        return real_dims(cfg)

    def program():  # every computation's instructions, without the source lines and scopes they were traced from
        hlo, _, _, _ = _compiled_step(v5e, tmp_path, "olmo-hybrid-7b-span16", 0, pages_a_lane=40, state_kernel=False)
        return {name: [(i[0], i[1], i[2], re.sub(r", metadata=\{[^}]*\}", "", i[3])) for i in instructions] for name, instructions in _computations(hlo).items()}

    now = program()
    monkeypatch.setattr(olmo, "mixer_dims", dims_and_cfg)
    monkeypatch.setattr(olmo, "gated_delta_mixer", parents)
    before = program()
    assert CFG and len(now) > 10 and "paged_decode_walk" in str(now) and now == before


# ---------------------------------------------------------------- one packed operand for the lanes, the tables a plain operand (PR 51)


@pytest.mark.parametrize("config_name,chunk,pages_a_lane", [
    pytest.param(name, chunk, pages, id=f"{name}-{f'mixed-{chunk}' if chunk else 'decode'}")
    for name, pages, chunks in (("falcon-40b-span5", 16, (0, 256)), (Q3N, 40, (0, 512))) for chunk in chunks
])
def test_step_takes_its_lanes_packed_and_still_aliases_what_it_was_donated(v5e, tmp_path, config_name, chunk, pages_a_lane):
    """The lanes' rows and positions reach the compiled decode and mixed
    steps as ONE int32 operand ``[lanes, hidden + 1]`` and the block tables as
    another, both plain parameters of ``ENTRY`` that alias no result (the
    batcher keeps the tables' device copy from step to step, so it must never
    be donated); every donated pool, and each leaf of the state pool, is still
    aliased to a result, and no copy of a page pool is held."""
    hlo, _, pool, heads = _compiled_step(v5e, tmp_path, config_name, chunk, pages_a_lane=pages_a_lane)
    comps = _computations(hlo)
    entry = comps[re.search(r"^ENTRY\s+%([\w.\-]+)", hlo, re.MULTILINE).group(1)]
    numbered = {name.split(".")[0]: (int(rest.split(")")[0]), dims) for name, dims, op, rest in entry if op == "parameter"}
    hidden = json.loads((Path(__file__).resolve().parents[1] / "perf" / "configs" / f"{config_name}.json").read_text())["hidden_size"]
    assert numbered["lanes"][1] == (8, hidden + 1) and numbered["tables"][1] == (8, pages_a_lane)
    for name in ("lanes", "tables"):
        assert re.search(rf"%{name}\.\d+ = s32\[", hlo), f"{name} is not an int32 operand"
    assert not {"hidden", "positions"} & set(numbered), sorted(numbered)
    header = hlo.splitlines()[0]
    aliased = {int(n) for n in re.findall(r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)", header.split("input_output_alias={")[1].split("entry_computation_layout")[0])}
    donated = {name for name in numbered if name in ("k_pool", "v_pool") or name.startswith("state_")}
    assert len(donated) == (4 if config_name == Q3N else 2), sorted(numbered)
    assert aliased == {numbered[name][0] for name in donated}, (aliased, {name: numbered[name][0] for name in donated})
    moves, loops_seen = pool_moves(hlo, tuple(pool.shape), heads)
    # Qwen3-Next's page pools ride no loop since the walk is a kernel: its two full layers are runs of one block, in ``ENTRY``
    assert (loops_seen or config_name == Q3N) and not moves, moves


# ---------------------------------------------------------------- a state-space model's state pool, 26 layers deep (PR 52)

JAMBA = "jamba2-3b-span28"


def _layout_bytes(dims: tuple, layout: str, itemsize: int) -> int:
    """Bytes an array of ``dims`` takes in an HLO layout ``{minor_to_major:T(rows,lanes)...}``: its two minor
    dimensions in whole tiles (a second tile ``(2,1)`` packs two 16-bit values a word: twice the rows)."""
    order = [int(i) for i in re.match(r"\{([\d,]+)", layout).group(1).split(",")]
    rows, lanes = (int(n) for n in re.search(r"T\((\d+),(\d+)\)", layout).groups())
    rows *= 2 if "(2,1)" in layout else 1
    padded = list(dims)
    padded[order[0]] = -(-dims[order[0]] // lanes) * lanes
    padded[order[1]] = -(-dims[order[1]] // rows) * rows
    return math.prod(padded) * itemsize


@pytest.mark.parametrize("chunk", [0, 512], ids=["decode", "mixed-512"])
def test_a_state_space_span_leaves_its_state_pool_and_its_one_head_pages_in_place(v5e, tmp_path, chunk):
    """jamba2-3b-span28 at the cell's geometry (8 lanes, 40 pages a lane), all
    28 blocks of the model in five runs of kinds: the compiled decode and mixed
    steps copy no page pool (ONE kv head of 128: a folded row, two layers deep)
    and no state pool (26 layers x 8 lanes x [16, 5120] float32, 170 MB), in
    ``ENTRY`` or in a run's loop. The state lies as ``block_state`` declares
    it, channels minor: its tile divides [16, 5120] and the pool takes its
    declared bytes (as the checkpoint's [5120, 16] a float32 tile would pad
    16 to 128: 8 times). The conv's tails (6.4 MB declared) lie with the LANES
    second-minor and take twice their bytes (8 lanes in a tile of 16 rows of
    bfloat16), and the mixed step, which takes one lane's out, copies them in
    ``ENTRY``, never in a loop: 0.3 MB a layer, beside 6.9 GB of weights. The
    decode rows' attention is the walk's kernel over the folded row of one kv
    head, one call an attention layer in either step, and a step's only
    custom calls."""
    from petals_tpu.server.from_pretrained import get_block_config

    hlo, runs, pool, heads = _compiled_step(v5e, tmp_path, JAMBA, chunk, pages_a_lane=40)
    assert [run["ln1"].shape[0] for run in runs] == [7, 1, 13, 1, 6] and heads == (1, 128) and tuple(pool.shape) == (2, 320, 64, 128)
    moves, loops_seen = pool_moves(hlo, tuple(pool.shape), heads)
    assert not moves, f"the step moves the page pool: {moves}"
    comps = _computations(hlo)
    entry = re.search(r"^ENTRY\s+%([\w.\-]+)", hlo, re.MULTILINE).group(1)
    family, cfg = get_block_config(str(tmp_path))  # ``_compiled_step`` left the published config.json there
    (state_shape, _), (tail_shape, _) = family.state_for(cfg, "mamba")
    assert state_shape == (16, 5120) and tail_shape == (3, 5120)
    state, tail = (26, 8, *state_shape), (26, 8, *tail_shape)
    copies = lambda dims: [(where, name, op) for where, instructions in comps.items() for name, d, op, rest in instructions if tuple(d) == dims
                           and (op in ("copy", "copy-start") or (op == "custom-call" and 'custom_call_target="AllocateBuffer"' in rest))]
    assert any(tuple(dims) == state for _, dims, _, _ in comps[entry]), "the state pool was not found in ENTRY"
    assert not copies(state), f"the step moves the state pool: {copies(state)}"
    assert {where for where, _, _ in copies(tail)} <= ({entry} if chunk else set()), copies(tail)
    handed = {dims: re.findall(rf"= {dtype}\[{','.join(map(str, dims))}\](\{{[^}}]*\}}) parameter\(\d+\), sharding", hlo)
              for dims, dtype in ((state, "f32"), (tail, "bf16"))}  # ENTRY's parameters carry a sharding
    assert all(len(layouts) == 1 for layouts in handed.values()), handed  # as the program is handed each pool, and hands it back
    assert _layout_bytes(state, handed[state][0], 4) == math.prod(state) * 4 == 26 * 8 * 327_680
    assert _layout_bytes(tail, handed[tail][0], 2) == 2 * math.prod(tail) * 2
    assert set(re.findall(r"f32\[26,8,16,5120\](\{[^}]*\})", hlo)) == set(handed[state])  # one layout from the parameter to the result
    assert hlo.count('custom_call_target="tpu_custom_call"') == len(decode_walk_calls(hlo, "paged_decode_walk")) == 2


def test_the_state_space_span_s_decode_walk_is_the_kernel_s_over_its_folded_row(tmp_path):
    """What the batcher is told its decode rows' attention runs over one kv
    head of 128 under 20 query heads, on a backend that says it is a TPU: the
    kernel, 32 pages of 16 KB a block."""
    from perf.config import load as load_config
    from petals_tpu.server.backend import TransformerBackend
    from petals_tpu.server.from_pretrained import get_block_config

    hf = load_config(Path(__file__).resolve().parents[1] / f"perf/configs/{JAMBA}.json", JAMBA)["config"]
    (tmp_path / "config.json").write_text(json.dumps(hf))
    family, cfg = get_block_config(str(tmp_path))
    S = jax.ShapeDtypeStruct
    runs = tuple({name: S((length, *leaf.shape), leaf.dtype) for name, leaf in family.param_shapes_for(cfg, kind, BF16).items()}
                 for kind, _, length in span_runs(family.span_kinds(cfg, 0, 28)))
    backend = TransformerBackend(family, cfg, runs, first_block=0, n_blocks=28, memory_cache=None)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(pfa, "_on_tpu", lambda: True)
        assert [(layers, block, path) for _, layers, block, _, path in backend.cache.lane_pool(8, 40, 64).walks] == [(2, 32, "kernel")]
        why = pfa.walk_kernel_unsupported(S((320, 64, *backend.cache.pool_row), BF16), (8, 1, 20, 128), (8, 40))
    assert backend.cache.pool_row == (128,) and why is None
    assert [path for *_, path in backend.cache.lane_pool(8, 40, 64).walks] == ["composed"]  # this backend is no TPU
    assert pfa.paged_kernel_unsupported(1, 128, "none") is None  # a prompt's chunk takes the paged prefill kernel


SCMOE = "longcat-flash-span4-ep32"
SCMOE_POOLS = ((8, 320, 64, 512), (8, 320, 32, 128))  # 2 layers of pages a block x 4 blocks, 8 lanes x 40 pages; latents, rotated keys


@pytest.mark.parametrize("chunk", [0, 512], ids=["decode", "mixed-512"])
def test_a_block_of_two_latent_attentions_walks_its_own_layers_of_pages_and_moves_no_weight(v5e, tmp_path, chunk):
    """longcat-flash-span4-ep32's decode and mixed-512 steps (8 lanes, tables
    of 40 pages: the cell's), compiled for the v5e. The pools are as deep as
    the span's ATTENTIONS (2 a block: ``[8, 320, ...]``, 2 x 576 values a
    position a block and nothing padded). The layer loop's body holds the
    absorbed walk's kernel TWICE, both at 64 heads, both handed the two
    whole-span pools; the pool of latents (168 MB, eight ninths of the cache)
    is allocated once, never copied and no layer of it sliced out or written
    back, in ``ENTRY`` or in the loop. The pool of rotated keys is small
    enough at these lanes (21 MB; kanana2-30b-a3b-span6's is 201 MB) that the
    compiler stages it whole through the chip's fast memory around a layer's
    calls: ONE sliced prefetch and ONE copy back a layer, not one a walk
    (PERF.md section 7, Left by PR 56; what Left by PR 53 warned of): held
    here to that, so that a second round trip a layer shows. The hit
    dispatch's kernel reads the run's expert stacks as the loop carries them;
    the identities' weighted add is one fusion a half of the step (compare,
    select, reduce, multiply: no gather, no scatter); no stacked weight is
    relaid in the loop but ``wuk`` / ``wuv``, which the chunk's walk, a loop
    of its own, is handed as arrays (8 MB each an attention: kanana2's
    known one)."""
    hlo, runs, pool, _ = _compiled_step(v5e, tmp_path, SCMOE, chunk, pages_a_lane=40)
    assert tuple(pool.shape) == SCMOE_POOLS[0] and len(runs) == 1 and runs[0]["w1"].shape == (4, 16, 6144, 2048)
    assert sum(math.prod(shape) for shape in SCMOE_POOLS) == 4 * 2 * 576 * 8 * 40 * 64  # 2 x 576 values a position a block
    comps = _computations(hlo)
    entry = re.search(r"^ENTRY\s+%([\w.\-]+)", hlo, re.MULTILINE).group(1)
    staged = []
    for shape in SCMOE_POOLS:
        elements, layer = math.prod(shape), math.prod(shape[1:])
        assert any(tuple(dims) == shape for _, dims, _, _ in comps[entry]), f"the pool {shape} was not found in ENTRY"
        moved = [(computation, name, op, dims) for computation, name, dims, op, rest in _arrays_in_memory(comps)
                 if dims[-1:] == shape[-1:] and math.prod(dims) >= layer and dims not in ((64, 512, 128), (512, 64, 128))  # ``wuv``, below
                 and (op == "custom-call" and 'custom_call_target="AllocateBuffer"' in rest or _only_moves(comps, op, rest)
                      or (computation != entry and (fused := _fused(comps, op, rest)) is not None and fused[-1][2] == "dynamic-update-slice"
                          and math.prod(dims) < elements))]
        if shape == SCMOE_POOLS[0]:
            assert not moved, f"the step moves the pool of latents: {moved}"
        else:
            staged = moved
    assert all(computation != entry for computation, *_ in staged), staged
    back = [name for _, name, op, dims in staged if op == "copy-done" and math.prod(dims) == math.prod(SCMOE_POOLS[1])]
    assert len(back) == 1 and len(staged) == 2, f"the rotated keys' pool crosses the chip's fast memory more than once a layer: {staged}"
    calls = decode_walk_calls(hlo)
    assert len(calls) == 2 and len({computation for computation, _, _ in calls}) == 1, calls  # two walks in the one layer loop's body
    for _, op_name, operands in calls:
        assert "ptu.attn.latent_decode" in op_name and (8, 64, 512) in [dims for _, _, dims in operands], operands  # 64 heads' absorbed queries
        assert sorted(math.prod(dims) for _, _, dims in operands)[-2:] == sorted(math.prod(shape) for shape in SCMOE_POOLS)
    hits = hit_calls(hlo)
    assert len(hits) == 1 and sorted(dims for op, dims in hits[0][1] if len(dims) == 4) == sorted(tuple(runs[0][w].shape) for w in ("w1", "w3", "w2"))
    assert {op for op, dims in hits[0][1] if len(dims) == 4} <= {"get-tuple-element", "parameter"}
    fused = {m.group(1) for instructions in comps.values() for _, _, op, rest in instructions
             if op == "fusion" and (m := re.search(r"calls=%([\w.\-]+)", rest))}
    zero = [(computation in fused, op) for computation, instructions in comps.items() for _, _, op, rest in instructions if "ptu.moe.zero" in rest]
    assert zero, "the scope ptu.moe.zero was not found: has the HLO text changed?"
    assert {op for inside, op in zero if inside} <= {"broadcast", "compare", "convert", "multiply", "reduce", "select", "parameter", "add", "bitcast", "constant"}
    assert sum(op == "fusion" for inside, op in zero if not inside) == (2 if chunk else 1)  # one a half of the step
    assert "ptu.scmoe.shortcut" in hlo and not {op for _, op in zero} & {"gather", "scatter", "sort", "while", "dynamic-update-slice"}
    stacked = {tuple(p.shape) for p in runs[0].values()}
    relayouts, seen = weight_relayouts(hlo, stacked, math.prod(runs[0]["wkva_0"].shape[1:]))
    known = {(64, 128, 512), (64, 512, 128)} if chunk else set()
    relayouts = [r for r in relayouts if tuple(json.loads(r.split(" -> ")[1])) not in known]
    assert seen and not relayouts, f"the step's loop relays a weight in every layer of every step: {relayouts}"
    assert not entry_weight_moves(hlo, stacked, math.prod(runs[0]["wkva_0"].shape[1:]))


def test_the_absorbed_walk_s_kernel_takes_64_heads_and_the_counters_count_it(v5e, tmp_path):
    """The walk's kernel alone at the cell's shapes, through Pallas -> Mosaic
    -> libtpu for the v5e: 8 lanes of 64 heads (``q`` and ``acc`` of ``[64,
    512]`` float32: twice kanana2's 32), tables of 40 pages, the span's pools
    of 8 x 320 pages handed whole. And what the batcher is told runs:
    ``decode_path`` says the kernel for these rows on a TPU backend, and
    ``latent_reads`` counts each live lane's own blocks, in 8 sub-layers."""
    import numpy as np

    from perf.config import load as load_config
    from petals_tpu.ops import latent_attention as latent
    from petals_tpu.ops.paged_attention import PagedKV
    from petals_tpu.server.backend import TransformerBackend
    from petals_tpu.server.from_pretrained import get_block_config

    def walk(q_abs, q_pe, c_pool, pe_pool, tables, positions):
        return latent.latent_decode_attend(q_abs, q_pe, PagedKV(c_pool, tables), PagedKV(pe_pool, tables), positions, scale=2 * 192**-0.5, path="kernel")

    avals = (v5e((8, 1, 64, 512), BF16), v5e((8, 1, 64, 64), BF16), v5e((8 * 320, 64, 512), BF16), v5e((8 * 320, 32, 128), BF16),
             v5e((8, 40), I32), v5e((8,), I32))
    hf = load_config(Path(__file__).resolve().parents[1] / f"perf/configs/{SCMOE}.json", SCMOE)["config"]
    (tmp_path / "config.json").write_text(json.dumps(hf))
    family, cfg = get_block_config(str(tmp_path))
    params = {name: jax.ShapeDtypeStruct((4, *leaf.shape), leaf.dtype) for name, leaf in family.param_shapes_for(cfg, None, BF16).items()}
    backend = TransformerBackend(family, cfg, params, first_block=0, n_blocks=4, memory_cache=None)
    contexts = np.array([1100, 1800, 2049, 2560, 1, 64])  # two lanes idle
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(latent, "_on_tpu", lambda: True)  # the backend here is the CPU: the kernel would be interpreted
        _compile(walk, *avals)
        assert latent.decode_path(*latent.latent_pool_rows(64, 512, 64), BF16) == "kernel"
        reads = counted(backend, 8, 40, 64, contexts - 1)
    block = latent.DECODE_KERNEL_PAGES * 64
    assert reads["latent_rows_read"] == 8 * sum(min(-(-int(ctx) // block) * block, 2 * block) for ctx in contexts)  # a table of 40 pages: two blocks
    assert reads["latent_rows_held"] == reads["latent_score_pairs"] == 8 * int(contexts.sum()) and reads["latent_rows_absorbed"] == 8 * 6
    assert counted(backend, 8, 40, 64, contexts - 1)["latent_rows_read"] == 8 * 8 * 2560  # off the chip: the composed walk, every lane to the longest


STREAM = "xing4-29b-a4b-span8"
STREAM_POOLS = ((8, 128, 64, 512), (8, 128, 32, 128))  # 8 blocks, 8 lanes x 16 pages of 64; latents, rotated keys
STREAM_WIDTH = 4 * 3584


@pytest.mark.parametrize("chunk", [0, 512], ids=["decode", "mixed-512"])
def test_a_stream_of_four_rows_crosses_every_layer_flat_and_no_wrap_relays_it(v5e, tmp_path, chunk):
    """xing4-29b-a4b-span8's decode and mixed-512 steps (8 lanes, tables of 16
    pages, the lanes' rows 14,336 wide), compiled for the v5e. The stream is
    never transposed or copied a sub-layer: in a layer loop's body the only
    instructions that merely move an array 14,336 wide are ONE asynchronous
    copy of the lanes' ``[8, 1, 14336]`` (229 KB) a layer, and none of the
    chunk's ``[1, 512, 14336]``; the three named scopes of the wrap are all
    there. The pool of latents is allocated once and never copied; the pool
    of rotated keys is staged through the chip's fast memory at most once a
    layer (longcat-flash-span4-ep32's finding, PERF.md section 7); the walk's
    kernel and the hit dispatch's are in each loop's body once; no stacked
    weight is relaid in a loop but the mixed step's known ones (the chunk's
    walk is handed ``wuk`` / ``wuv`` as arrays, the chunk's grouped dispatch a
    copy of ``w1`` / ``w3`` / ``w2``: ROADMAP S7), and none of the two ``phi``
    (688 KB a layer: prefetched by an asynchronous slice, not copied)."""
    hlo, runs, pool, _ = _compiled_step(v5e, tmp_path, STREAM, chunk, pages_a_lane=16)
    assert tuple(pool.shape) == STREAM_POOLS[0] and [run["wkva"].shape[0] for run in runs] == [2, 6]
    assert runs[0]["hc_phi_attn"].shape == (2, STREAM_WIDTH, 24) and runs[1]["w1"].shape == (6, 64, 3584, 1024)
    comps = _computations(hlo)
    entry = re.search(r"^ENTRY\s+%([\w.\-]+)", hlo, re.MULTILINE).group(1)
    assert all(scope in hlo for scope in ("ptu.hc.coef", "ptu.hc.sinkhorn", "ptu.hc.mix"))
    in_memory = list(_arrays_in_memory(comps))
    moves = [(computation, op, tuple(dims)) for computation, _, dims, op, rest in in_memory
             if STREAM_WIDTH in dims and computation != entry and (op in ("copy", "transpose", "copy-start") or _only_moves(comps, op, rest))]
    assert all(op in ("copy-start", "copy-done") and dims == (8, 1, STREAM_WIDTH) for _, op, dims in moves), f"a layer moves the stream: {moves}"
    assert sum(op == "copy-start" for _, op, _ in moves) <= 2, moves  # one a loop's body (the dense run's, the sparse run's)
    latents, keys = STREAM_POOLS
    for shape in STREAM_POOLS:
        assert any(tuple(dims) == shape for _, dims, _, _ in comps[entry]), f"the pool {shape} was not found in ENTRY"
    moved = [(computation, op, tuple(dims)) for computation, _, dims, op, rest in in_memory
             if dims[-1:] == latents[-1:] and math.prod(dims) >= math.prod(latents[1:]) and tuple(dims) not in ((32, 128, 512),)
             and (op == "custom-call" and 'custom_call_target="AllocateBuffer"' in rest or _only_moves(comps, op, rest))]
    assert not moved, f"the step moves the pool of latents: {moved}"
    staged = [(computation, op) for computation, _, dims, op, _ in in_memory
              if op == "copy-done" and dims[-1:] == keys[-1:] and math.prod(dims) >= math.prod(keys[1:])]
    assert all(computation != entry for computation, _ in staged) and len(staged) <= 2, staged  # at most once a loop's body
    walks, hits = decode_walk_calls(hlo), hit_calls(hlo)
    assert len(walks) == 2 and len({computation for computation, _, _ in walks}) == 2 and len(hits) == 1
    stacked = {tuple(p.shape) for run in runs for p in run.values()}
    smallest = math.prod(runs[0]["hc_phi_attn"].shape[1:])
    relayouts, seen = weight_relayouts(hlo, stacked, smallest)
    known = {(32, 128, 512), (32, 512, 128), (64, 3584, 1024), (64, 1024, 3584)} if chunk else set()
    relayouts = [r for r in relayouts if tuple(json.loads(r.split(" -> ")[1])) not in known]
    assert seen and not relayouts, f"the step's loop relays a weight in every layer of every step: {relayouts}"
    assert not entry_weight_moves(hlo, stacked, smallest)


# ---------------------------------------------------------------------------
# pages by kind of layer: a pair of pools a page group, tables a group (server/span_cache.py ``page_groups``)


def _compiled_grouped_step(v5e, tmp_path, config_name, chunk, lanes, pages_a_lane, budget):
    """``(optimized HLO, the groups' pool avals, (hkv, d))`` of the paged decode step, or of the mixed step with a chunk of
    ``chunk`` positions, as a GROUPED lane pool hands it over: a pair of pools a page group (the first as the step's own,
    the others where a state pool rides), tables ``[groups, lanes, pages_a_lane]``, every pool donated; the groups' pages
    as ``SpanCache.group_pages`` sizes them for ``lanes`` lanes and a prefill budget of ``budget``."""
    from perf.config import load as load_config
    from petals_tpu.server.backend import TransformerBackend
    from petals_tpu.server.from_pretrained import get_block_config

    config_file = Path(__file__).resolve().parents[1] / "perf" / "configs" / f"{config_name}.json"
    (tmp_path / "config.json").write_text(json.dumps(load_config(config_file, config_name)["config"]))
    family, cfg = get_block_config(str(tmp_path))
    depth, page_size = cfg.num_hidden_layers, 64
    runs = tuple(
        {name: v5e((length, *leaf.shape), leaf.dtype) for name, leaf in family.param_shapes_for(cfg, kind, BF16).items()}
        for kind, _, length in span_runs(family.span_kinds(cfg, 0, depth))
    )
    backend = TransformerBackend(family, cfg, runs, first_block=0, n_blocks=depth, memory_cache=None)
    cache = backend.cache
    assert cache.grouped, cache.page_groups
    n_pages = cache.group_pages(lanes, pages_a_lane, page_size, budget)
    pools = tuple(v5e(d.shape, BF16) for d in cache.pool_descriptors(n_pages, page_size, lanes, 0, depth))
    avals = [runs, pools[0], pools[1], v5e((lanes, backend.hidden_size + 1), I32), v5e((len(n_pages), lanes, pages_a_lane), I32)]
    step = backend._paged_decode_fn
    if chunk:
        step = backend._paged_mixed_step_fn
        avals += [v5e((1, chunk, backend.hidden_size), BF16)] + [v5e((), I32)] * 4
    avals.append(pools[2:])
    step = functools.partial(step.__wrapped__, with_fp=False)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr("petals_tpu.ops.expert_hit._interpret", lambda: False)
        patch.setattr(pfa, "_on_tpu", lambda: True)
        patch.setattr(pfa, "_platform", lambda: "tpu")  # a chunk takes the prefill kernel, as on the chip
        patch.setattr(pfa, "paged_flash_prefill_attend", functools.partial(pfa.paged_flash_prefill_attend, interpret=False))  # and not interpreted
        patch.setattr(pfa, "_interpret", lambda: False)
        hlo = jax.jit(step, donate_argnums=(1, 2, len(avals) - 1)).lower(*avals).compile().as_text()
    return hlo, pools, (backend.num_kv_heads, backend.head_dim)


@pytest.mark.parametrize("config_name,chunk,lanes,pages_a_lane,budget", [
    pytest.param("smallthinker-21b-a3b-span12", 0, 16, 256, 2048, id="smallthinker-decode"),
    pytest.param("smallthinker-21b-a3b-span12", 2048, 16, 256, 2048, id="smallthinker-mixed-2048"),
    pytest.param("k-exaone-236b-span5-ep8", 0, 8, 16, 512, id="k-exaone-decode"),
    pytest.param("k-exaone-236b-span5-ep8", 256, 8, 16, 512, id="k-exaone-mixed-256", marks=pytest.mark.xfail(strict=True, reason=(
        "the full group's pool is ONE layer of 128 pages, 16.8 MB a side: under a kernel call (the chunk's prefill kernel) in a "
        "layer loop, a pool that fits the chip's fast memory is staged through it whole (copy-start / copy-done of "
        "bf16[1,128,64,8,128] into S(1)), the hazard PERF.md section 7 'Left by PR 53' (1) names; a mixed step of this cell, one "
        "in ~250 steps, pays ~40 us for it (PERF.md section 7, Left by PR 64)"
    ))),
])
def test_a_grouped_step_leaves_every_group_s_pool_in_place(v5e, tmp_path, config_name, chunk, lanes, pages_a_lane, budget):
    """Two pools where there was one is where a whole-pool copy or relay would appear: the compiled step of a span whose
    layers keep pages by kind (SmallThinker's three full and nine windowed layers at the cell's 16 lanes of 256 slots;
    K-EXAONE's one full and four windowed) allocates no second pool of either group, copies none in ``ENTRY``, and no
    layer of either is sliced out, copied or written back whole in a run's loop; and every loop carries the pools its
    layers reach."""
    hlo, pools, heads = _compiled_grouped_step(v5e, tmp_path, config_name, chunk, lanes, pages_a_lane, budget)
    assert len(pools) == 4 and pools[0].shape[1] == lanes * pages_a_lane and pools[2].shape[1] < pools[0].shape[1]
    assert pools[0].shape[0] + pools[2].shape[0] == {"smallthinker-21b-a3b-span12": 12, "k-exaone-236b-span5-ep8": 5}[config_name]
    # the decode rows' walks: SmallThinker's folded rows of 4 x 128 take the kernel in BOTH groups since PR 65, one call a run
    # of layers, handed its own group's two pools as the loop carries them or as the new rows' scatter left them (the full
    # layers are runs of ONE block, unrolled into ``ENTRY``; the windowed runs of three are loops); K-EXAONE's 8 x 128 none
    walks = decode_walk_calls(hlo, "paged_decode_walk")
    entry = re.search(r"^ENTRY\s+%([\w.\-]+)", hlo, re.MULTILINE).group(1)
    handed = {0: [], 2: []}
    for computation, op_name, operands in walks:
        assert "ptu.attn.paged_decode" in op_name, op_name
        for g in handed:
            mine = [(op, fused) for op, fused, dims in operands if math.prod(dims) == math.prod(pools[g].shape)]
            assert len(mine) in (0, 2), operands
            for op, fused in mine:
                assert op in ("get-tuple-element", "parameter") or (fused is not None and any(i[2] in ("dynamic-update-slice", "scatter") for i in fused)), (op, operands)
            handed[g] += [computation == entry] * (len(mine) // 2)
    if config_name == "smallthinker-21b-a3b-span12":
        assert pools[0].shape[2:] == pools[2].shape[2:] == (64, 512) and handed == {0: [True] * 3, 2: [False] * 3}, (pools, handed)
    else:
        assert pools[0].shape[2:] == (64, 8, 128) and not walks, walks
    for g, pool in ((0, pools[0]), (2, pools[2])):
        moves, loops_seen = pool_moves(hlo, tuple(pool.shape), heads)
        # a run's loop carries the pools its layers reach; a group whose layers all run in ``ENTRY`` under the walk's kernel
        # (no loop of a composed walk's own either) is found there, as the kernel's operand
        assert loops_seen or any(handed[g]), f"no loop carries the pool {pool.shape}: has the HLO text changed, or the pool left the carry?"
        # a chunk's prefill kernel is handed its block's OWN layer of its group's pool (``PagedKV.own_layer``: the kernel
        # relays what it is handed), as it is of a pool of one group: a slice of ONE layer, keys and values, in the run's
        # loop, and nothing else; a decode step moves nothing at all
        a_layer = f"constant_dynamic-slice_fusion -> {list(pool.shape[1:])}" if chunk else None
        others = [move for move in moves if a_layer is None or not (move.split(" = ")[1].replace("fusion", "constant_dynamic-slice_fusion") == a_layer and "dynamic-slice" in move)]
        assert not others, f"the step moves the page pool {pool.shape} around: {others}"
    sizes = {"bf16[" + ",".join(map(str, pool.shape)) + "]" for pool in (pools[0], pools[2])}
    whole = [line.strip()[:200] for line in hlo.splitlines() if re.search(r"= \(?(bf16\[[\d,]+\])\S* copy-(start|done)\(", line)
             and re.search(r"= \(?(bf16\[[\d,]+\])", line).group(1) in sizes]
    assert not whole, whole
    if config_name == "smallthinker-21b-a3b-span12" and chunk:
        # the folded pool IS the prefill kernel's lane-trailing view: a layer handed to it is sliced out and no more. As rows
        # of [4, 128] (tiles of T(4,128)) every layer's slice was relaid besides, ``reshape`` to [pages, 64, 512] in tiles of
        # T(8,128): 268 MB a side a full layer, 102 MB a windowed one, every layer of every mixed step (compiled at PR 64's tree)
        relaid = [line.strip()[:160] for line in hlo.splitlines() if re.search(r"= bf16\[(4096|1552),64,512\]\S* (reshape|copy|transpose)\(", line)]
        assert not relaid, relaid
