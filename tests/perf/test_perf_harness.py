"""CPU tests of the benchmark's harness (perf/): seconds each, no chip.

They check the yardstick, not the system: that a seed fixes the schedule and
the inputs, that distributions keep to their clips, that latency is timed
from the due time, that the trace reducer and the cost functions give
hand-worked numbers, that each plain reference agrees with the served block
at a tiny size, and that BENCHMARK.json names what exists.
"""

import io
import json
import re
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"

from perf import correct, costs, loadgen, traffic, weights, xplane  # noqa: E402
from perf.config import load as load_config  # noqa: E402
from perf.record import Record, load_reader  # noqa: E402

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

MIXES = {
    "open_rate": {"arrival": {"kind": "open_rate", "rate_rps": 4.0}, "ramp_s": 2.0,
                  "prompt": {"dist": "lognormal", "median": 192, "sigma": 0.8, "min": 32, "max": 768},
                  "output": {"dist": "lognormal", "median": 96, "sigma": 0.7, "min": 16, "max": 256}},
    "burst": {"arrival": {"kind": "burst", "size": 8, "period_s": 2.5}, "ramp_s": 1.0,
              "prompt": {"dist": "uniform", "min": 64, "max": 128}, "output": {"dist": "fixed", "value": 32}},
    "closed": {"arrival": {"kind": "closed", "clients": 5}, "ramp_s": 2.0,
               "prompt": {"dist": "uniform", "min": 64, "max": 128}, "output": {"dist": "fixed", "value": 256}},
    "tree": {"arrival": {"kind": "open_rate", "rate_rps": 3.0}, "ramp_s": 0.0,
             "prompt": {"dist": "uniform", "min": 32, "max": 128}, "output": {"dist": "uniform", "min": 32, "max": 128},
             "prefix": {"kind": "tree", "shared": 512, "tenants": 4, "tenant_len": 128, "branching": [2, 2],
                        "segment": 16, "hot_bias": 0.5}},
    "max_length": {"arrival": {"kind": "closed", "clients": 2}, "ramp_s": 0.0, "max_length": 2048,
                   "prompt": {"dist": "uniform", "min": 1024, "max": 1920}, "output": {"dist": "fixed", "value": 16}},
}


@pytest.mark.parametrize("name", sorted(MIXES))
def test_same_seed_same_schedule_another_seed_another(name):
    mix = MIXES[name]
    a, b, c = (traffic.schedule(mix, seed, 30.0) for seed in (2**31 + 7, 2**31 + 7, 11))
    assert a == b
    assert a != c

    def sizes(s):
        plans = list(s.open_plans) + [p for ps in s.client_plans for p in ps]
        return sorted((p.prompt_len, p.output) for p in plans)

    if name != "tree":  # the seed picks each session's walk through the tree, so the sets differ there
        assert sorted(x for x, _ in sizes(a)) == sorted(x for x, _ in sizes(c))  # the same set of sizes, another order
    assert sorted(y for _, y in sizes(a)) == sorted(y for _, y in sizes(c))


CHAT_MIXES = sorted(p.stem for p in traffic.TRAFFIC_DIR.glob("chat.*.json"))  # a configuration's own: ``chat`` plus its rate


@pytest.mark.parametrize("name", ["toy"] + CHAT_MIXES)
def test_a_mix_may_fix_its_order_and_the_inputs_still_follow_the_seed(name):
    """``order_seed``: every seed runs the same sessions at the same times
    (where the order decides the result, PERF.md section 6, PRs 30 and 34);
    the rows they send are still the seed's. Every committed chat mix fixes
    its order: a few lanes are live at once there, and who decodes beside
    whom moves the median gap."""
    plain = MIXES["open_rate"] if name == "toy" else {k: v for k, v in traffic.load_mix(name).items() if k != "order_seed"}
    fixed = {**plain, "order_seed": 77} if name == "toy" else traffic.load_mix(name)
    assert "order_seed" in fixed
    a, b = traffic.schedule(fixed, 2**31 + 7, 30.0), traffic.schedule(fixed, 11, 30.0)
    assert a == b == traffic.schedule(plain, fixed["order_seed"], 30.0)  # the order that seed would have drawn
    assert a != traffic.schedule({**fixed, "order_seed": fixed["order_seed"] + 1}, 11, 30.0)
    assert traffic.input_pool(2**31 + 7, 16).tobytes() != traffic.input_pool(11, 16).tobytes()


def test_inputs_follow_the_seed():
    a, b, c = (traffic.input_pool(seed, 64) for seed in (5, 5, 6))
    assert a.tobytes() == b.tobytes() and a.tobytes() != c.tobytes()
    assert a.dtype == np.float32 and a.shape == (traffic.POOL_ROWS, 64)
    wrapped = traffic.rows(a, traffic.POOL_ROWS - 2, 5)
    assert np.array_equal(wrapped, np.concatenate([a[-2:], a[:3]]))


@pytest.mark.parametrize("dist", [
    {"dist": "lognormal", "median": 192, "sigma": 0.8, "min": 32, "max": 768},
    {"dist": "lognormal", "median": 96, "sigma": 0.7, "min": 16, "max": 256},
    {"dist": "uniform", "min": 64, "max": 128},
    {"dist": "fixed", "value": 256},
    {"dist": "exponential", "mean": 0.4, "min": 0.0},
])
def test_distribution_respects_its_clip(dist):
    values = traffic.draws(dist, 500, np.random.default_rng(0), integer=False)
    assert min(values) >= dist.get("min", dist.get("value", 0))
    assert max(values) <= dist.get("max", dist.get("value", float("inf")))
    if dist["dist"] == "lognormal":
        assert abs(float(np.median(values)) - dist["median"]) < 0.02 * dist["median"]
        assert min(values) == dist["min"] and max(values) == dist["max"]  # both clips bite at 500 draws


def test_open_loop_arrivals_fill_the_window_at_the_rate():
    s = traffic.schedule(MIXES["open_rate"], 1, 30.0)
    due = [p.due for p in s.open_plans]
    assert due == sorted(due) and due[0] >= -2.0 and due[-1] < 30.0
    assert len(due) == round(4.0 * 32.0)
    assert abs(sum(1 for t in due if t >= 0) - 120) <= 12  # the ramp holds its share
    burst = traffic.schedule(MIXES["burst"], 1, 30.0)
    times = sorted({p.due for p in burst.open_plans})
    assert all(abs((b - a) - 2.5) < 1e-9 for a, b in zip(times, times[1:]))
    assert all(sum(1 for p in burst.open_plans if p.due == t) == 8 for t in times)


def test_tree_prefix_shares_heads_and_nothing_else():
    s = traffic.schedule(MIXES["tree"], 3, 30.0)
    heads = [p.segments[:-1] for p in s.open_plans]
    assert all(h[0] == (0, 512) for h in heads)  # the swarm-shared head
    assert len({h[1] for h in heads}) == 4  # four tenants' preambles
    assert all(len(h) == 4 for h in heads)  # shared + tenant + two tree levels
    own = [p.segments[-1][0] for p in s.open_plans]
    assert len(set(own)) == len(own)  # no two sessions start their own rows at the same place
    flat = traffic.schedule(MIXES["open_rate"], 3, 30.0)
    starts = [p.segments[0][0] for p in flat.open_plans]
    assert len(set(starts)) == len(starts)


def test_max_length_is_the_mix_s_or_what_the_session_needs():
    s = traffic.schedule(MIXES["max_length"], 1, 10.0)
    assert all(p.max_length == 2048 and p.prompt_len + p.output <= 2048 for ps in s.client_plans for p in ps)
    plan = traffic.schedule(MIXES["closed"], 1, 10.0).client_plans[0][0]
    assert plan.max_length == plan.prompt_len + plan.output  # none given: what it needs
    assert plan.decode_offset == plan.segments[-1][0] + plan.segments[-1][1]  # decode inputs follow the prompt's rows
    with pytest.raises(ValueError, match="max_length"):
        traffic.schedule({**MIXES["max_length"], "max_length": 64}, 1, 10.0)
    with pytest.raises(ValueError, match="arrival kind"):
        traffic.schedule({**MIXES["closed"], "arrival": {"kind": "poisson", "rate_rps": 1.0}}, 1, 10.0)


def test_a_closed_mix_may_plan_more_sessions_a_client():
    """``sessions``: a client of short sessions must not run out of plans
    before the window ends (it would fall silent, and a lane with it)."""
    closed = MIXES["closed"]
    more = {**closed, "arrival": {**closed["arrival"], "sessions": 100}}
    assert [len(ps) for ps in traffic.schedule(closed, 5, 30.0).client_plans] == [traffic.CLOSED_SESSIONS_PER_CLIENT] * 5
    assert [len(ps) for ps in traffic.schedule(more, 5, 30.0).client_plans] == [100] * 5
    mix = traffic.load_mix("chat.mixtral8x7b")  # ~40 sessions a client in a window of 51 s (PERF.md section 6, PR 34)
    assert all(len(ps) >= 120 for ps in traffic.schedule(mix, 5, BENCHMARK["run_seconds"]).client_plans)


def test_mix_files_resolve_their_base():
    mix = traffic.load_mix("chat.falcon40b")
    assert mix["arrival"]["kind"] == "open_rate" and mix["arrival"]["rate_rps"] > 0
    assert mix["prompt"]["max"] + mix["output"]["max"] <= 1024  # every session fits a default lane
    for cell in BENCHMARK["workloads"]:
        traffic.schedule(traffic.load_mix(cell["traffic"]), 2**31 + 99, BENCHMARK["run_seconds"])


class _SlowRemote:
    """Stands in for RemoteSequential: a session that takes 20 ms a step."""

    class _Session:
        def __init__(self, hidden):
            self.hidden = hidden

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return None

        def step(self, x):
            time.sleep(0.02)
            return np.zeros_like(x)

    def __init__(self, hidden):
        self.hidden = hidden

    def inference_session(self, max_length):
        return self._Session(self.hidden)


def test_latency_is_timed_from_the_due_time_and_lateness_is_reported(monkeypatch):
    pool = traffic.input_pool(1, 16)
    sched = traffic.schedule({"arrival": {"kind": "open_rate", "rate_rps": 10.0}, "ramp_s": 0.0,
                              "prompt": {"dist": "fixed", "value": 8}, "output": {"dist": "fixed", "value": 3}}, 1, 1.0)
    replay = loadgen.Replay(_SlowRemote(16), pool, traced=False, hidden=16)
    spawn = replay._spawn

    def late_spawn(target, *args):  # a starved generator: every session starts 50 ms after it was due
        time.sleep(0.05)
        spawn(target, *args)

    monkeypatch.setattr(replay, "_spawn", late_spawn)
    t0 = time.perf_counter() + 0.05
    replay.run(sched, t0, 1.0, drain_s=10.0)
    record = Record(config={}, t_process=t0 - 1.0, t0=t0, seconds=1.0, t_drained=time.perf_counter(),
                    sessions=replay.records, children=[])
    assert len(record.counted()) == len(sched.open_plans) == 10
    assert all(s.error is None and s.done is not None for s in replay.records)
    late = load_reader("layer_metrics", "gen_late_ms_p95").read(record)
    assert late >= 50.0  # reported, not hidden
    first = min(replay.records, key=lambda s: s.due)
    # due -> reply holds the generator's lateness on top of the 20 ms step
    assert (first.first_reply - first.due) >= 0.05 + 0.02 - 1e-3
    assert (first.first_reply - first.sent) < (first.first_reply - first.due)
    assert load_reader("layer_metrics", "ttft_p90_ms").read(record) >= 70.0
    gaps = record.gaps_ms()
    assert len(gaps) > 0 and 19.0 < float(np.median(gaps)) < 40.0
    assert abs(load_reader("end_to_end", "setup_s").read(record) - 1.0) < 1e-9


def test_xplane_reducer_gives_known_busy_and_idle_shares():
    """tests/perf/data/small_trace.textproto: two TPU planes and a host plane
    over a 1000 us window. Device 0 runs fusion.1 over [100, 300) and
    [250, 400) us (union 300) and copy.2 over [600, 700): 400 us busy. Device
    1 runs fusion.1 over [0, 200): 200 us busy. The "XLA Modules" line covers
    the same time again and must not be added."""
    from jax.profiler import ProfileData

    data = ProfileData.from_text_proto((DATA / "small_trace.textproto").read_text())
    out = xplane.reduce(data)
    assert out["devices"] == 2
    assert out["window_s"] == pytest.approx(1000e-6)
    assert out["busy_s"] == pytest.approx(300e-6)  # mean of 400 and 200
    assert out["idle_share"] == pytest.approx(0.7)
    assert out["device_ops"][0][0] == "fusion.1" and out["device_ops"][0][1] == pytest.approx(550e-6)
    assert out["device_ops"][1] == ["copy.2", pytest.approx(100e-6)]
    assert xplane.union_seconds([(0, 2), (1, 3), (5, 6)]) == 4
    host_only = ProfileData.from_text_proto('planes { name: "/host:CPU" lines { name: "t" events { offset_ps: 5 duration_ps: 9 } } }')
    assert xplane.reduce(host_only) is None  # nothing to read: the metric is left out


def test_costs_equal_hand_worked_numbers():
    falcon = load_config(ROOT / "perf/configs/falcon-40b-span5.json", "f")["config"]
    mixtral = load_config(ROOT / "perf/configs/mixtral-8x7b-span2.json", "m")["config"]
    assert falcon["hidden_size"] == 8192 and falcon["num_kv_heads"] == 8 and "server_args" not in falcon
    # Falcon-40B: qkv 8192 x (128 + 2*8)*64, dense 8192 x 8192, MLP 2 x 8192 x 32768
    assert costs.layer_param_count("falcon", falcon) == 8192 * 9216 + 8192 * 8192 + 2 * 8192 * 32768 == 679_477_248
    # Mixtral-8x7B: q,o 4096^2 each, k,v 4096 x 1024 each, router 4096 x 8, 8 experts of 3 x 4096 x 14336
    assert costs.layer_param_count("mixtral", mixtral) == 2 * 4096**2 + 2 * 4096 * 1024 + 4096 * 8 + 8 * 3 * 4096 * 14336 == 1_451_261_952
    assert costs.kv_bytes_per_token_layer("falcon", falcon) == 2 * 8 * 64 * 2
    assert costs.kv_bytes_per_token_layer("mixtral", mixtral) == 2 * 8 * 128 * 2
    peaks = costs.peaks_for("TPU v5 lite")
    # one decode step of 8 lanes over 8 Falcon layers, 2400 cached positions in all
    cost = costs.step_cost("falcon", falcon, 8, decode_tokens=8, prefill_tokens=0, context_tokens=2400)
    assert cost["bytes"] == 8 * (679_477_248 * 2 + 2048 * (2400 + 8) + 2 * 8192 * 2 * 8)
    assert cost["flops"] == 8 * (2 * 679_477_248 * 8 + 4 * 128 * 64 * 2400)
    seconds, bound = costs.least_seconds(cost, peaks)
    assert bound == "bandwidth" and seconds == pytest.approx(cost["bytes"] / 819e9)
    # a 512-token chunk riding along makes the step compute-bound
    assert costs.least_seconds(costs.step_cost("falcon", falcon, 8, decode_tokens=8, prefill_tokens=512,
                                               context_tokens=2400, prefill_context=256), peaks)[1] == "compute"
    # Mixtral computes two experts a token and reads the experts its tokens reach: four tokens of top 2 of 8
    # reach 8 x (1 - (3/4)^4) = 8 x 175/256 = 5.46875 of them
    cost = costs.step_cost("mixtral", mixtral, 4, decode_tokens=4, prefill_tokens=0, context_tokens=0)
    shared, expert = 2 * 4096**2 + 2 * 4096 * 1024 + 4096 * 8, 3 * 4096 * 14336
    assert cost["flops"] == 4 * 2 * (shared + 2 * expert) * 4
    assert cost["bytes"] == 4 * ((shared + 5.46875 * expert) * 2 + 4096 * 4 + 2 * 4096 * 2 * 4) == 4 * (2_010_710_016 + 16384 + 65536)
    # one lane reaches its two experts; 1.8 lanes (mixtral8x7b-chat's decode steps) 8 x (1 - 0.75^1.8) = 3.2335;
    # a chunk of 128 riding along reaches all eight, as the rule before PR 30 had it for every step
    p = costs.layer_params("mixtral", mixtral)
    assert costs.experts_reached(p, 1) == 2 and costs.experts_reached(p, 1.8) == pytest.approx(3.2335, abs=1e-4)
    assert costs.experts_reached(p, 130) == pytest.approx(8, rel=1e-12) and costs.experts_reached(costs.layer_params("falcon", falcon), 8) == 0
    mixed = costs.step_cost("mixtral", mixtral, 2, decode_tokens=2, prefill_tokens=128, context_tokens=0)
    assert mixed["bytes"] == pytest.approx(2 * (1_451_261_952 * 2 + 4096 * 130 + 2 * 4096 * 2 * 130), rel=1e-12)
    with pytest.raises(KeyError, match="not in peaks.json"):
        costs.peaks_for("TPU v9 imaginary")


def test_a_family_s_costs_are_found_by_name(tmp_path, monkeypatch):
    """A new family adds perf/reference/<family>.py and edits nothing: the
    cost functions take its shapes from ``layer_params`` there."""
    import perf.reference

    (tmp_path / "toyfam.py").write_text(
        "def layer_params(hf):\n"
        "    return {'attn': 40, 'dense': 60, 'expert': 0, 'experts': 0, 'top_k': 0,\n"
        "            'hidden': hf['n_embed'], 'q_heads': 2, 'kv_heads': 1, 'head_dim': 4}\n"
    )
    monkeypatch.setattr(perf.reference, "__path__", list(perf.reference.__path__) + [str(tmp_path)])
    hf = {"n_embed": 8}  # none of Falcon's or Mixtral's key names
    assert costs.layer_param_count("toyfam", hf) == 100
    assert costs.kv_bytes_per_token_layer("toyfam", hf) == 2 * 1 * 4 * 2
    cost = costs.step_cost("toyfam", hf, 3, decode_tokens=2, prefill_tokens=0, context_tokens=10)
    assert cost == {"flops": 3 * (2 * 100 * 2 + 4 * 2 * 4 * 10), "bytes": 3 * (100 * 2 + 16 * (10 + 2) + 2 * 8 * 2 * 2)}
    with pytest.raises(ModuleNotFoundError):
        costs.layer_params("nofam", hf)


TWO_KINDS_REFERENCE = """
import jax.numpy as jnp

ROW_BOUND_PER_LAYER, MEDIAN_BOUND_PER_LAYER = 1e-2, 5e-3
TRACED = []  # block() runs when a program is traced: once a compile


def layer_kinds(hf):
    return hf["kinds"]


def layer_params(hf, kind):
    shape = {"hidden": hf["n_embed"], "q_heads": 2, "kv_heads": 1, "head_dim": 4, "attn": 40}
    if kind == "mlp":
        return {**shape, "dense": 60, "expert": 0, "experts": 0, "top_k": 0}
    routed = {**shape, "dense": 10, "expert": 6, "experts": 16, "experts_routed": 128, "top_k": 8}
    return {**routed, "window": 4} if kind == "experts-windowed" else routed


def block(hf, w, x, kind):
    TRACED.append(kind)
    if kind == "mlp":
        return x + x @ w["w"], jnp.full(x.shape[0], jnp.inf)
    return x + jnp.tanh(x @ w["a"]) @ w["b"], jnp.full(x.shape[0], 0.5)
"""
TWO_KINDS_WEIGHTS = """
SPAN_TREES = []


def layer_tensors(hf, layer, draws, kind):
    n = hf["n_embed"]
    if kind == "mlp":
        return {"w": draws.normal((n, n), layer, 0)}
    return {"a": draws.normal((n, 4), layer, 1), "b": draws.normal((4, n), layer, 2)}


def block_params(hf, t, kind):
    return {name: tensor.T for name, tensor in t.items()}


def span_tree(hf, runs):
    SPAN_TREES.append([first for first, _ in runs])
    return {"runs": runs}
"""


@pytest.fixture()
def two_kinds(tmp_path, monkeypatch):
    """A family of three kinds of layer written into ``tmp_path`` and found by
    name: a dense MLP, an expert layer that attends over a window, one that
    attends over everything; 16 of 128 routed experts held. No file of perf/
    knows it."""
    import importlib

    import perf.reference

    for package, text in ((perf.reference, TWO_KINDS_REFERENCE), (weights, TWO_KINDS_WEIGHTS)):
        (tmp_path / package.__name__).mkdir()
        (tmp_path / package.__name__ / "twokinds.py").write_text(text)
        monkeypatch.setattr(package, "__path__", list(package.__path__) + [str(tmp_path / package.__name__)])
    yield {"family": "twokinds", "weights_seed": 7, "servers": [{"first_block": 0, "num_blocks": 4}],
           "config": {"n_embed": 8, "kinds": ["mlp", "experts-windowed", "experts", "mlp", "experts"]}}
    for name in ("perf.reference.twokinds", "perf.weights.twokinds"):
        sys.modules.pop(name, None)
    importlib.invalidate_caches()


def test_a_family_of_more_than_one_kind_runs_through_the_reference(two_kinds):
    """``reference.run`` compiles one program a kind, in the order the layers
    meet them, and gives each layer its own kind's weights and block."""
    import jax
    import jax.numpy as jnp

    from perf import reference

    family, maker = reference.family_of("twokinds"), weights.family_of("twokinds")
    hf = two_kinds["config"]
    x = np.random.default_rng(1).standard_normal((6, 8), dtype=np.float32)
    got, margin, checks = reference.run(two_kinds, x)
    assert family.TRACED == ["mlp", "experts-windowed", "experts"]  # four layers, three kinds, three compiles
    want = jnp.asarray(x)
    with jax.default_matmul_precision("highest"):
        for layer, kind in enumerate(hf["kinds"][:4]):
            w = {k: v.astype(jnp.float32) for k, v in maker.layer_tensors(hf, layer, weights.Draws(7), kind).items()}
            want, _ = family.block(hf, w, want, kind)
    assert np.allclose(got, np.asarray(want), rtol=1e-6, atol=1e-6) and not np.allclose(got, x)
    assert (margin == 0.5).all() and len(set(checks)) == 4
    assert reference.limits(two_kinds)["row_bound"] == pytest.approx(4e-2)  # layers counted as for any family


def test_a_span_is_stacked_by_runs_of_one_kind(two_kinds):
    """``span_params``: each run of consecutive blocks of one kind stacked, the
    runs handed to the family's ``span_tree``; a span of one kind is the one
    stacked tree, as it is for the families whose layers are all alike."""
    import jax
    import jax.numpy as jnp

    from perf import reference

    maker, hf = weights.family_of("twokinds"), two_kinds["config"]
    checks = reference.run({**two_kinds, "servers": [{"first_block": 0, "num_blocks": 5}]}, np.zeros((2, 8), np.float32))[2]
    tree, first = weights.span_params(two_kinds, 0, 5, jnp.float32)
    assert maker.SPAN_TREES == [[0, 1, 2, 3, 4]] and first == checks[0]
    assert [(start, {k: v.shape for k, v in run.items()}) for start, run in tree["runs"]][:2] == [
        (0, {"w": (1, 8, 8)}), (1, {"a": (1, 4, 8), "b": (1, 8, 4)})]
    tree, first = weights.span_params({**two_kinds, "config": {**hf, "kinds": ["mlp", "experts", "experts", "mlp", "mlp"]}}, 1, 4, jnp.bfloat16)
    assert maker.SPAN_TREES[-1] == [1, 3] and [run["a" if start == 1 else "w"].shape[0] for start, run in tree["runs"]] == [2, 2]
    assert tree["runs"][0][1]["a"].dtype == jnp.bfloat16
    want = maker.block_params(hf, maker.layer_tensors(hf, 2, weights.Draws(7), "experts"), "experts")
    assert np.array_equal(np.asarray(tree["runs"][0][1]["b"][1], np.float32), np.asarray(want["b"], np.float32))
    one_kind, first = weights.span_params(two_kinds, 1, 1, jnp.float32)  # a span of one kind: no span_tree
    assert len(maker.SPAN_TREES) == 2 and set(one_kind) == {"a", "b"} and first == checks[1]
    # the families whose layers are all alike get the bits they got before PR 30: its span_params, inline
    for config_file in ("falcon-tiny.json", "mixtral-tiny-chain.json"):
        config = load_config(DATA / config_file, "tiny")
        family = weights.family_of(config["family"])

        def make():
            draws = weights.Draws(config["weights_seed"])
            layers = [family.layer_tensors(config["config"], 1 + i, draws) for i in range(2)]
            blocks = [family.block_params(config["config"], t) for t in layers]
            return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs).astype(jnp.bfloat16), *blocks), weights.checksum(layers[0])

        before, check = jax.jit(make)()
        after, first = weights.span_params(config, 1, 2, jnp.bfloat16)
        assert first == int(check) and set(after) == set(before)
        assert all(after[k].dtype == before[k].dtype and np.array_equal(np.asarray(after[k], np.float32), np.asarray(before[k], np.float32)) for k in before)


def test_a_span_of_more_than_one_kind_is_costed_layer_by_layer(two_kinds):
    """One dense layer and two expert layers, one of them windowed, 16 of 128
    experts held, top 8; 8 lanes at 10 cached positions each. Tokens, bytes
    (bf16) and FLOPs by hand:
    experts reached by 8 tokens: 16 x (1 - (15/16)^8) = 1732076671 / 2^28 = 6.4524; computed a token: 8 x 16/128 = 1
    mlp:              weights (40 + 60) x 2 = 200, cache 16 x (80 + 8) = 1408, activations 2 x 8 x 2 x 8 = 256;
                      FLOPs 2 x 100 x 8 + 4 x 2 x 4 x 80 = 4160
    experts-windowed: weights (40 + 10 + 6 r) x 2, cache 16 x (8 x 4 + 8) = 640, activations 256;
                      FLOPs 2 x (40 + 10 + 6) x 8 + 4 x 2 x 4 x 32 = 1920
    experts:          weights the same, cache 1408, activations 256; FLOPs 896 + 2560 = 3456"""
    hf = two_kinds["config"]
    r = 1732076671 / 2**28
    assert costs.experts_reached(costs.layer_params("twokinds", hf, 1), 8) == r == 16 * (1 - (15 / 16) ** 8)
    assert [costs.layer_params("twokinds", hf, i).get("window") for i in range(5)] == [None, 4, None, None, None]
    assert costs.layer_param_count("twokinds", hf, 0) == 100 and costs.layer_param_count("twokinds", hf, 2) == 50 + 6 * 16
    cost = costs.step_cost("twokinds", hf, 3, decode_tokens=8, prefill_tokens=0, context_tokens=80)
    assert cost["bytes"] == (200 + 1408 + 256) + (100 + 12 * r + 640 + 256) + (100 + 12 * r + 1408 + 256) == 4624 + 24 * r
    assert cost["flops"] == 4160 + 1920 + 3456
    tail = costs.step_cost("twokinds", hf, 2, first_block=3, decode_tokens=8, prefill_tokens=0, context_tokens=80)  # mlp, experts
    assert tail == {"bytes": 1864 + 1764 + 12 * r, "flops": 4160 + 3456}
    # lanes under the window read what they hold; a chunk's positions attend over the lesser of their context and the window
    short = costs.step_cost("twokinds", hf, 1, first_block=1, decode_tokens=8, prefill_tokens=0, context_tokens=24)
    assert short["flops"] == 896 + 4 * 2 * 4 * 24 and short["bytes"] == 100 + 12 * r + 16 * (24 + 8) + 256
    chunk = costs.step_cost("twokinds", hf, 1, first_block=1, decode_tokens=0, prefill_tokens=8, context_tokens=0, prefill_context=6)
    assert chunk["flops"] == 896 + 4 * 2 * 4 * 8 * 4 and chunk["bytes"] == 100 + 12 * r + 16 * 8 + 256


def _tiny(config_file, tmp_path):
    """A toy configuration, and the family and block configuration petals_tpu reads from its ``config.json``."""
    from petals_tpu.server.from_pretrained import get_block_config

    config = load_config(DATA / config_file, config_file.removesuffix(".json"))
    (tmp_path / "config.json").write_text(json.dumps(config["config"]))
    return config, *get_block_config(str(tmp_path))


@pytest.mark.parametrize("config_file", ["falcon-tiny.json", "mixtral-tiny-chain.json"])
def test_reference_agrees_with_the_served_block(config_file, tmp_path):
    """The plain float32 reference against the program's own block code on the
    same seeded weights, both in float32 on the CPU: 1e-4, because they
    differ only in the order of float32 sums. The served side gets the span
    as the server child makes it; its first block's checksum is the
    reference's."""
    import jax
    import jax.numpy as jnp

    from perf import reference

    config, family, cfg = _tiny(config_file, tmp_path)
    n_layers = sum(span["num_blocks"] for span in config["servers"])
    x = np.random.default_rng(0).standard_normal((40, config["config"]["hidden_size"]), dtype=np.float32)
    want, margin, checks = reference.run(config, x)
    assert margin.shape == (40,) and (np.isinf(margin).all() if config["family"] == "falcon" else (margin >= 0).all())
    assert len(set(checks)) == n_layers  # every layer other weights
    stacked, first = weights.span_params(config, 0, n_layers, jnp.float32)
    assert first == checks[0] and weights.span_params(config, 1, 1, jnp.float32)[1] == checks[1]
    hidden = jnp.asarray(x)[None]
    with jax.default_matmul_precision("highest"):
        for i in range(n_layers):
            params = jax.tree_util.tree_map(lambda leaf: leaf[i], stacked)
            hidden, _ = family.block_apply(params, hidden, None, 0, cfg, use_flash=False)
    got = np.asarray(hidden[0], np.float32)
    assert np.isfinite(want).all()
    assert float(np.abs(got - want).max() / np.abs(want).max()) < 1e-4


@pytest.mark.parametrize("config_file", ["falcon-tiny.json", "mixtral-tiny-chain.json"])
def test_weights_take_the_layout_the_program_gives_a_checkpoint(config_file, tmp_path):
    """``perf/weights/<family>.py`` ``block_params`` mirrors the family's
    ``hf_to_block_params`` in petals_tpu: the same leaves, shapes and
    elements from the same HF tensors. If this fails the program's layout
    has moved, and a benchmark PR moves the mirror."""
    config, family, cfg = _tiny(config_file, tmp_path)
    maker = weights.family_of(config["family"])
    tensors = maker.layer_tensors(config["config"], 1, weights.Draws(config["weights_seed"]))
    assert all(str(t.dtype) == "bfloat16" for t in tensors.values())
    big = [np.asarray(t, np.float32).ravel() for t in tensors.values() if t.ndim == 2]
    assert all(abs(t.std() / weights.STD - 1) < 0.05 and abs(t.mean()) < 0.1 * weights.STD for t in big if t.size > 4000)
    mine = maker.block_params(config["config"], tensors)
    theirs = family.hf_to_block_params({k: np.asarray(v, np.float32) for k, v in tensors.items()}, cfg)
    assert set(mine) == set(theirs)
    for name in theirs:
        assert mine[name].shape == theirs[name].shape, name
        assert np.array_equal(np.asarray(mine[name], np.float32), theirs[name]), name


DENSE = {"row_bound": 0.05, "median_bound": 0.03, "tie_margin": 0.0, "positions_allowed": 0}
ROUTED = {"row_bound": 0.05, "median_bound": 0.03, "tie_margin": 0.1, "positions_allowed": 2}


def _judged(limits, wrong=(), *, near_tied=(), value=9.0, shift=0.0):
    """Rows that equal the reference but for the ``wrong`` (kind, position)
    ones; ``shift`` moves every decode row a little."""
    rng = np.random.default_rng(0)
    want = rng.standard_normal((correct.SEQ, 16)).astype(np.float32)
    margin = np.full(correct.SEQ, np.inf, np.float32)
    margin[list(near_tied)] = 0.01
    rows = [("prefill", p, want[p].copy()) for p in range(96, 128)] + [("decode", p, want[p].copy()) for p in range(112, 144)]
    for kind, p, row in rows:
        if kind == "decode":
            row += shift * np.abs(want[p]).max()
        if (kind, p) in wrong:
            row[3] = value
    return correct.judge(rows, want, margin, limits)


@pytest.mark.parametrize("case,limits,kw,ok", [
    ("all rows equal the reference", DENSE, {}, True),
    ("one decode row is wrong: 63 of 64 rows inside the bound is not enough", DENSE, {"wrong": [("decode", 130)]}, False),
    ("one prefill row is wrong", DENSE, {"wrong": [("prefill", 100)]}, False),
    ("the wrong row is near-tied in the reference: left out", ROUTED, {"wrong": [("decode", 130)], "near_tied": (130,)}, True),
    ("a dense family leaves nothing out", DENSE, {"wrong": [("decode", 130)], "near_tied": (130,)}, False),
    ("a routed family allows two positions outside", ROUTED, {"wrong": [("decode", 130), ("decode", 131)]}, True),
    ("and not three", ROUTED, {"wrong": [("decode", 130), ("decode", 131), ("decode", 132)]}, False),
    ("every decode row a little off: no row stands out, the median does", ROUTED, {"shift": 0.04}, False),
    ("more than three quarters of the decode rows left out", ROUTED, {"near_tied": tuple(range(112, 138))}, False),
    ("a non-finite reply", ROUTED, {"wrong": [("decode", 120)], "value": float("nan")}, False),
])
def test_correct_holds_decode_rows_and_prefill_rows_to_the_family_s_limits(case, limits, kw, ok):
    verdict = _judged(limits, **kw)
    assert verdict["ok"] is ok, case
    assert verdict["decode"]["rows"] == 32 and verdict["prefill"]["rows"] == 32
    if kw.get("wrong") and "value" not in kw:  # each kind is judged by itself
        assert verdict["prefill"]["ok"] is all(kind == "decode" for kind, _ in kw["wrong"])
    if limits["tie_margin"] > 0:
        assert verdict["decode"]["compared"] == 32 - len(kw.get("near_tied", ()))
    numbers = correct.compared({**verdict, "repeat_identical": True})  # what the result line and stderr show beside each limit
    assert numbers["decode_median"] == [verdict["decode"]["median"], limits["median_bound"]] and numbers["repeat_identical"] == [1, 1]
    assert numbers["decode_outside"] == [len(verdict["decode"]["positions_outside"]), limits["positions_allowed"]]
    assert numbers["prefill_max"][1] == limits["row_bound"] and numbers["decode_compared_share"] == [verdict["decode"]["compared"] / 32, 0.25]


def _states_its_limits(config: dict) -> None:
    """What every configuration's family is held to, whatever its name."""
    from perf import reference

    limits = reference.limits(config)
    depth = sum(span["num_blocks"] for span in config["servers"])
    routed = any(costs.layer_params(config["family"], config["config"], layer)["experts"] > 0 for layer in range(depth))
    assert 0 < limits["median_bound"] <= limits["row_bound"] <= 0.1 * depth
    assert limits["row_bound"] < 0.3  # a wrong kernel lands at 0.3..1
    # a margin leaves rows out, so it is stated with the positions it may still cost, and only by a family
    # that decides something: a layer with routed experts (correct.judge fails a run that leaves out over
    # three quarters of its rows of a kind, so a margin cannot hide a family)
    assert (limits["tie_margin"] > 0) == (limits["positions_allowed"] > 0)
    assert routed or limits["tie_margin"] == 0
    assert limits["positions_allowed"] <= 2


def test_each_family_states_its_limits():
    for entry in BENCHMARK["configs"]:
        _states_its_limits(load_config(ROOT / entry["file"], entry["name"]))


@pytest.mark.parametrize("case,experts,stated,ok", [
    ("a routed family of any name may state a tie margin", 4, "TIE_MARGIN, POSITIONS_ALLOWED_OUTSIDE = 0.04, 1", True),
    ("or none, where a flipped expert moves no row", 4, "", True),
    ("a dense family may not", 0, "TIE_MARGIN, POSITIONS_ALLOWED_OUTSIDE = 0.04, 1", False),
    ("a margin without the positions it may cost", 4, "TIE_MARGIN = 0.04", False),
    ("positions outside without a margin", 4, "POSITIONS_ALLOWED_OUTSIDE = 1", False),
    ("more than two positions", 4, "TIE_MARGIN, POSITIONS_ALLOWED_OUTSIDE = 0.04, 3", False),
])
def test_a_family_s_limits_go_by_what_it_is_not_by_its_name(case, experts, stated, ok, tmp_path, monkeypatch):
    import perf.reference

    (tmp_path / "anyname.py").write_text(
        f"ROW_BOUND_PER_LAYER, MEDIAN_BOUND_PER_LAYER = 2e-2, 1e-2\n{stated}\n"
        "def layer_params(hf):\n"
        f"    return {{'attn': 40, 'dense': 60, 'expert': 6, 'experts': {experts}, 'top_k': 2,\n"
        "            'hidden': 8, 'q_heads': 2, 'kv_heads': 1, 'head_dim': 4}\n"
    )
    monkeypatch.setattr(perf.reference, "__path__", list(perf.reference.__path__) + [str(tmp_path)])
    monkeypatch.delitem(sys.modules, "perf.reference.anyname", raising=False)
    config = {"family": "anyname", "config": {}, "servers": [{"first_block": 0, "num_blocks": 2}]}
    try:
        if ok:
            _states_its_limits(config)
        else:
            with pytest.raises(AssertionError):
                _states_its_limits(config)
    finally:
        sys.modules.pop("perf.reference.anyname", None)


class _CausalRemote:
    """Stands in for RemoteSequential with a "model" whose output at a
    position is the running mean of the inputs up to it, so a session's cache
    matters. ``broken_after`` makes decode steps past that many forget it."""

    def __init__(self, broken_after=None):
        self.broken_after, self.log = broken_after, []

    def inference_session(self, max_length):
        return self._Session(self)

    class _Session:
        def __init__(self, remote):
            self.remote, self.seen, self.decoded = remote, [], 0
            self.sid = remote.opened = getattr(remote, "opened", 0) + 1

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return None

        def step(self, x):
            time.sleep(0.005)
            self.remote.log.append((self.sid, x.shape[1], time.perf_counter()))
            out = []
            for row in x[0]:
                self.seen.append(row)
                out.append(np.mean(self.seen, axis=0))
            if x.shape[1] == 1:
                self.decoded += 1
                if self.remote.broken_after is not None and self.decoded > self.remote.broken_after:
                    out = [x[0, 0]]
            return np.stack(out)[None]


@pytest.mark.parametrize("broken_after,ok", [(None, True), (0, False), (1, False), (5, False)])
def test_check_sessions_hold_decode_steps_to_the_reference(broken_after, ok):
    """A decode path that breaks at its first step, after the one step a lone
    session takes, or only deep into a session makes ``correct`` false."""
    x = correct.inputs(2**31 + 3, 8)
    want = np.cumsum(x, axis=0) / np.arange(1, len(x) + 1)[:, None]
    remote = _CausalRemote(broken_after)
    rows = correct.alone(remote, x) + correct.together(remote, x)
    limits = {"row_bound": 1e-4, "median_bound": 1e-4, "tie_margin": 0.0, "positions_allowed": 0}
    verdict = correct.judge(rows, want.astype(np.float32), np.full(len(x), np.inf, np.float32), limits)
    assert verdict["ok"] is ok
    assert verdict["decode"]["rows"] == 1 + sum(steps for _, steps in correct.TOGETHER)
    # the other sessions sent their prompts while the first still had decode steps to take,
    # and every one of them was decoding before the first had finished
    sessions = {}
    for sid, n, t in remote.log[2:]:  # past the lone session's two steps
        sessions.setdefault(sid, []).append((n, t))
    first, *others = sorted(sessions.values(), key=lambda steps: steps[0][1])
    assert first[0][0] == correct.TOGETHER[0][0] and sorted(o[0][0] for o in others) == sorted(p for p, _ in correct.TOGETHER[1:])
    for other in others:
        assert first[correct.HANDOVER_STEP][1] <= other[0][1] and other[1][1] < first[-1][1]


def test_memory_readers_tell_the_peak_from_what_serving_holds():
    child = {"memory": {"peak_bytes_in_use": 14 * 2**30}, "marks": {"window": {"bytes_in_use": 6.5 * 2**30}}}
    record = Record(config={}, t_process=0.0, t0=1.0, seconds=1.0, t_drained=3.0, sessions=[], children=[child, {"memory": {}}])
    peak, steady = load_reader("layer_metrics", "hbm_peak_gib"), load_reader("layer_metrics", "hbm_steady_gib")
    assert peak.read(record) == 14.0 and peak.MOVES == "setup_s"
    assert steady.read(record) == 6.5 and steady.MOVES == "gap_p50_ms"
    assert steady.read(Record(config={}, t_process=0.0, t0=1.0, seconds=1.0, t_drained=3.0, sessions=[], children=[{}])) is None


def _gap_record(sessions):
    """``sessions``: (first reply, [reply times]) each, seconds; the window is [0, 100]."""
    made = [loadgen.SessionRecord(plan=None, due=0.0, counted=True, first_reply=first, replies=[(t, i) for i, t in enumerate(times)])
            for first, times in sessions]
    return Record(config={}, t_process=0.0, t0=0.0, seconds=100.0, t_drained=101.0, sessions=made, children=[])


def _steps(first, gaps_ms):
    return first, list(first + np.cumsum(np.asarray(gaps_ms) / 1e3))


GAP_RECORDS = {
    # one heap: every step alike, 10 ms give or take 1%
    "one_heap": _gap_record([_steps(1.0, 10.0 + 0.1 * np.sin(np.arange(400)))]),
    # two heaps 2 ms apart with the median between them: 45% of the samples alone at 6.1 ms, 55% side by side at 8.1
    "two_heaps": _gap_record([_steps(1.0, [6.1] * 90 + [8.1] * 55), _steps(1.001 + 90 * 6.1e-3, [8.1] * 55)]),
    "no_gaps": _gap_record([(1.0, [])]),
}


@pytest.mark.parametrize("case,low,high", [("one_heap", 0.0, 3.0), ("two_heaps", 20.0, 35.0), ("no_gaps", None, None), ("declared", None, None)])
def test_gap_mid_width_tells_a_median_on_an_edge(case, low, high):
    """100 x (p60 - p40) / p50 of the gaps: small where they make one heap,
    as wide as the heaps are apart where the median stands between two, and
    nothing where there is nothing to read; ``BENCHMARK.json`` repeats the
    reader's words, for every cell."""
    reader = load_reader("layer_metrics", "gap_mid_width_pct")
    if case == "declared":
        (entry,) = [m for m in BENCHMARK["per_layer"] if m["name"] == "gap_mid_width_pct"]
        assert (entry["unit"], entry["layer"], entry["moves"]) == (reader.UNIT, reader.LAYER, reader.MOVES) == ("%", "service (due time to reply, perf/loadgen.py)", "gap_p50_ms")
        assert entry["better"] == "lower" and entry["source"] == "host_clock" and "workloads" not in entry
        return
    value = reader.read(GAP_RECORDS[case])
    if low is None:
        assert value is None
    else:
        assert low <= value <= high
        assert value == pytest.approx(100 * np.subtract(*np.percentile(GAP_RECORDS[case].gaps_ms(), (60, 40))) / load_reader("end_to_end", "gap_p50_ms").read(GAP_RECORDS[case]))


def test_gap_summary_counts_the_heaps_by_who_was_decoding():
    """perf/gaps.py: the percentiles around the median, the samples by how
    many sessions were decoding when the reply came, the bins of 0.25 ms; and
    a run's standard error gives the summary back."""
    from perf import gaps, prove

    s = gaps.summary(GAP_RECORDS["two_heaps"])
    assert s["n"] == 200 and s["p40"] == pytest.approx(6.1) and s["p60"] == pytest.approx(8.1)
    assert s["mid_width_pct"] == pytest.approx(100 * 2.0 / s["p50"])
    assert set(s["by_lanes"]) == {"1", "2"}
    assert s["by_lanes"]["1"] == [pytest.approx(0.45, abs=0.01), pytest.approx(6.1)]  # (the second session's last reply comes alone)
    assert s["by_lanes"]["2"] == [pytest.approx(0.55, abs=0.01), pytest.approx(8.1)]
    assert {lo: n for lo, n in s["bins"]} == {6.0: 90, 8.0: 110} and s["beyond"] == 0 and s["bin_ms"] == 0.25
    assert gaps.summary(GAP_RECORDS["no_gaps"]) == {"n": 0}
    json.dumps(s)  # goes into the run's detail line as it is
    stderr = f"[perf   100.0s] window and drain over\n[perf   101.0s] detail: {json.dumps({'gaps': s})}\ncompared finite 1 limit 1\n"
    assert prove.detail_of(stderr) == {"gaps": s} and prove.detail_of("nothing of the kind\n") is None
    out = io.StringIO()
    gaps.show("two heaps", s, out)
    assert "200 gaps" in out.getvalue() and out.getvalue().count("#") > 60


@pytest.mark.parametrize("values,whole,trimmed", [
    ([10.0, 10.1, 10.2, 10.3, 10.4, 10.5], 0.35 / 10.25, 0.3 / 10.2),  # evenly spread: the farthest run is an end
    ([10.0, 10.0, 10.1, 10.1, 10.2, 13.0], None, 0.15 / 10.1),  # one run far off does no harm
    ([10.0, 10.1], None, None),  # too few to leave one out
])
def test_a_set_s_spread_with_and_without_its_farthest_run(values, whole, trimmed):
    from perf import prove

    if whole is not None:
        assert prove.spread(values) == pytest.approx(whole)
    if trimmed is None:
        assert prove.trimmed_spread(values) == prove.spread(values)
    else:
        assert prove.trimmed_spread(values) == pytest.approx(trimmed) and prove.trimmed_spread(values) <= prove.spread(values)


def test_benchmark_json_names_only_what_exists():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert BENCHMARK["paths"] == ["perf", "tests/perf"] and 1 <= BENCHMARK["run_seconds"] <= 51
    assert (ROOT / BENCHMARK["command"][1]).is_file()
    configs = {c["name"]: c for c in BENCHMARK["configs"]}
    cells = {w["name"]: w for w in BENCHMARK["workloads"]}
    e2e = {m["name"]: m for m in BENCHMARK["end_to_end"]}
    for c in BENCHMARK["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"} and NAME.match(c["name"])
        body = json.loads((ROOT / c["file"]).read_text())
        assert c["file"].startswith("perf/") and body["reduced"] == c["reduced"] and c["source"] == body["source"]
        assert all(NAME.match(k) and not re.search(r"_dim$|_rank$|hidden_size|intermediate|head|experts_per", k) for k in c["reduced"])
        assert c["name"] in {w["config"] for w in BENCHMARK["workloads"]}
        assert (ROOT / "perf" / "reference" / f"{body['family']}.py").is_file()
        assert (ROOT / "perf" / "weights" / f"{body['family']}.py").is_file()
    assert len({(w["config"], w["traffic"]) for w in BENCHMARK["workloads"]}) == len(cells)
    assert sum(w["chips"] == 4 for w in BENCHMARK["workloads"]) <= max(1, len(cells) // 4)
    for w in BENCHMARK["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"} and w["chips"] in (1, 4)
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["config"] in configs
        assert (ROOT / "perf" / "traffic" / f"{w['traffic']}.json").is_file() and len(w["why"]) <= 200
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for section, kind in (("end_to_end", "end_to_end"), ("per_layer", "layer_metrics")):
        for m in BENCHMARK[section]:
            reader = load_reader(kind, m["name"])
            assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["unit"] == reader.UNIT
            assert m["better"] in ("lower", "higher")
            assert set(m.get("workloads", [])) <= set(cells)
            if section == "end_to_end":
                assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
                assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
            else:
                assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
                assert m["layer"] == reader.LAYER and m["moves"] == reader.MOVES
                assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
                moved = e2e[m["moves"]]  # the metric it moves is reported wherever it is
                assert set(m.get("workloads", cells)) <= set(moved.get("workloads", cells))
    for w in cells:  # every cell: set-up, one more end-to-end metric, one per-layer metric
        assert len([m for m in BENCHMARK["end_to_end"] if w in m.get("workloads", [w])]) >= 2
        assert any(w in m.get("workloads", [w]) for m in BENCHMARK["per_layer"])
    assert len(json.dumps(BENCHMARK)) < 64 * 1024


def test_a_run_off_the_chip_fails_and_prints_no_metric():
    """The command as the driver runs it, here where JAX has only the CPU:
    the server child refuses, the run exits non-zero, stdout stays empty."""
    proc = subprocess.run(
        [sys.executable, str(ROOT / "perf" / "serve_child.py"), "--config-file", str(ROOT / BENCHMARK["configs"][0]["file"]),
         "--model-dir", "/nonexistent", "--initial-peers", "127.0.0.1:1/x", "--dump", "/dev/null", "--trace-dir", "/dev/null"],
        capture_output=True, text=True, timeout=120, env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 2 and proc.stdout == "" and "not a TPU" in proc.stderr


@pytest.mark.parametrize("workload,trace", [("tiny-open", False), ("tiny-chain", True), ("tiny-burst-tree", False)])
def test_tiny_cell_end_to_end(workload, trace, tmp_path):
    """The whole command at a toy size on the CPU: server children, the
    normal client, check, warm-up, window, dumps, the result's keys; two
    children in a chain; burst arrivals over a prefix tree. Numbers from it
    mean nothing and go nowhere."""
    from perf import run

    bench = json.loads((DATA / "benchmark-tiny.json").read_text())
    result = run.run_cell(bench, workload, 2**31 + 5, 5.0, trace, traffic_dir=DATA / "traffic",
                          work_dir=tmp_path, allow_cpu=True)
    detail = result["detail"]
    assert result["correct"] is True and result["failed"] == 0 and detail["sessions_total"] > 0
    assert set(result) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert result["device"]["platform"] == "cpu"  # (conftest gives each child 8 virtual devices)
    assert result["device"]["count"] % (2 if workload == "tiny-chain" else 1) == 0
    assert detail["check"]["repeat_identical"] and detail["recompiled"] == []
    assert detail["check"]["decode"]["ok"] and detail["check"]["decode"]["compared"] >= 9
    assert detail["together_decode_batch_mean"] >= 1.0
    numbers = result["compared"]  # each number the check compared, beside its limit
    assert numbers["repeat_identical"] == [1, 1] and numbers["finite"] == [1, 1] and numbers["decode_outside"][0] == 0
    assert all(0 < numbers[f"{kind}_{what}"][0] < numbers[f"{kind}_{what}"][1] for kind in ("prefill", "decode") for what in ("median", "max"))
    if trace:
        required = {"ttft_p90_ms", "gap_p95_ms", "gen_late_ms_p95", "hop_network_ms", "hop_queue_ms", "decode_batch_mean",
                    "step_compute_ms_p50", "open_route_ms_p50", "recompiles_in_window", "decode_tok_per_s"}
        if result["attempted"] == 0:  # a loaded machine: no closed-loop session began inside the toy window
            required -= {"ttft_p90_ms", "gen_late_ms_p95", "open_route_ms_p50"}
        assert required <= set(result["metrics"])
        assert "device_idle_share" not in result["metrics"]  # no device plane on the CPU: left out, not invented
        assert "breakdown" not in result
    else:
        assert result["attempted"] > 0
        assert {"gap_p50_ms", "setup_s"} == set(result["metrics"])
        assert all(m["value"] > 0 for m in result["metrics"].values())
