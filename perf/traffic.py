"""Seeded traffic: a mix file (perf/traffic/<name>.json) + a seed -> a schedule.

One general generator. A mix is data; its parameters:

``arrival``   ``{"kind": "open_rate", "rate_rps": r}`` open loop at a fixed
              mean rate: round(r x span) sessions whose gaps are the quantiles
              of the exponential distribution in a seeded order -- stratified,
              not sampled, so it is Poisson-shaped but every seed has the same
              count and the same set of gaps |
              ``{"kind": "burst", "size": n, "period_s": p}`` open loop, n
              sessions at once every p seconds |
              ``{"kind": "closed", "clients": k}`` each client opens its next
              session when its last one ended; ``"sessions": n`` plans that
              many a client (default 64): more than it can finish in a run,
              or it falls silent before the window ends.
``prompt`` / ``output``  a distribution: ``lognormal`` (median, sigma) |
              ``uniform`` | ``fixed`` (value), each clipped to ``min``..``max``.
              A session is its prompt in one chunk, then ``output`` decode steps.
``prefix``    ``{"kind": "none"}`` | ``{"kind": "tree", "shared": n,
              "tenants": t, "tenant_len": n, "branching": [..], "segment": n,
              "hot_bias": p}``: a swarm-shared head, a per-tenant preamble,
              one walk through the tenant's tree of segments, then the
              session's own ``prompt`` tokens.
``max_length`` per session; null = what the session needs.
``ramp_s``    sessions due this long before the window fill the pool and are
              not counted.
``base``      the name of another mix whose keys this one overrides.
``order_seed`` (optional) the orders below are drawn from this number and not
              from the run's seed: every seed then runs the same sessions at
              the same times, on other input rows. For a mix in which the
              order decides the result (see "Steadiness").

Steadiness: a distribution is not sampled. For n draws it gives the n
quantiles (i + 0.5) / n of the distribution, in an order drawn from the seed,
so every seed runs the same set of sizes and gaps in another order, and the
seed does not change the amount of work. It can still change the result: at
a rate where a few sessions are live at once, the order decides how many
decode side by side, and a token's gap grows with each (PERF.md section 6,
PR 30: two runs of one seed 0.1-1% apart, three seeds 2-4%). ``order_seed``
fixes the order for such a mix.

Inputs are rows of one pool of standard normals drawn from the seed
(``input_pool``); a session names its rows by offset, so a prompt is a
zero-copy slice, two sessions share a prefix only where the mix says so, and
the same seed gives the same bytes.

``burst`` and ``tree`` are driven end to end only at toy size on the CPU
(tests/perf) until a cell uses them (PERF.md section 7).
"""

from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist
from typing import List, Optional, Tuple

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
POOL_ROWS = 4096  # rows of the input pool; a session's rows wrap around it
CLOSED_SESSIONS_PER_CLIENT = 64  # more than a client of long sessions can finish in a window; a mix of short ones says "sessions"


def load_mix(name: str, traffic_dir: Path = TRAFFIC_DIR) -> dict:
    mix = json.loads((Path(traffic_dir) / f"{name}.json").read_text())
    if "base" in mix:
        base = load_mix(mix.pop("base"), traffic_dir)
        base.update(mix)
        mix = base
    return mix


def quantile(dist: dict, q: float) -> float:
    kind = dist["dist"]
    if kind == "fixed":
        x = float(dist["value"])
    elif kind == "uniform":
        x = dist["min"] + q * (dist["max"] - dist["min"])
    elif kind == "lognormal":
        x = math.exp(math.log(dist["median"]) + dist["sigma"] * NormalDist().inv_cdf(q))
    elif kind == "exponential":
        x = -dist["mean"] * math.log1p(-q)
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    return min(max(x, dist.get("min", -math.inf)), dist.get("max", math.inf))


def draws(dist: dict, n: int, rng: np.random.Generator, *, integer: bool = True) -> list:
    """The n quantiles of ``dist`` in a seeded order (see the module text)."""
    values = [quantile(dist, (i + 0.5) / n) for i in range(n)]
    values = [values[i] for i in rng.permutation(n)]
    return [int(round(v)) for v in values] if integer else values


@dataclasses.dataclass(frozen=True)
class Plan:
    index: int
    client: int  # closed loop: which client; open loop: -1
    due: Optional[float]  # seconds from the window's start (negative: ramp); None: closed loop
    segments: Tuple[Tuple[int, int], ...]  # (pool offset, rows) pieces of the prompt
    output: int  # decode steps after the prompt
    decode_offset: int  # pool row of the first decode input
    max_length: int

    @property
    def prompt_len(self) -> int:
        return sum(n for _, n in self.segments)


@dataclasses.dataclass(frozen=True)
class Schedule:
    kind: str  # "open" | "closed"
    ramp_s: float
    open_plans: Tuple[Plan, ...] = ()  # by due time
    client_plans: Tuple[Tuple[Plan, ...], ...] = ()  # per closed-loop client, in order
    client_starts: Tuple[float, ...] = ()  # each client's first due time


def input_pool(seed: int, hidden: int) -> np.ndarray:
    return np.random.default_rng([int(seed), 1]).standard_normal((POOL_ROWS, hidden), dtype=np.float32)


def rows(pool: np.ndarray, offset: int, n: int) -> np.ndarray:
    offset %= len(pool)
    if offset + n <= len(pool):
        return pool[offset : offset + n]
    return np.take(pool, np.arange(offset, offset + n), axis=0, mode="wrap")


def prompt_rows(pool: np.ndarray, plan: Plan) -> np.ndarray:
    """[1, prompt_len, hidden] float32."""
    parts = [rows(pool, off, n) for off, n in plan.segments]
    return (parts[0] if len(parts) == 1 else np.concatenate(parts))[None]


class _Tree:
    """Prompt-sharing structure of one mix: pool offsets of the shared head,
    each tenant's preamble and each node of each tenant's tree."""

    def __init__(self, spec: dict):
        self.spec = spec
        self.kind = spec.get("kind", "none")
        self.reserved = 0  # pool rows the shared pieces take; sessions' own rows start after them
        if self.kind == "none":
            return
        if self.kind != "tree":
            raise ValueError(f"unknown prefix kind {self.kind!r}")
        cursor = 0

        def take(n: int) -> Tuple[int, int]:
            nonlocal cursor
            piece = (cursor, n)
            cursor += n
            return piece

        self.shared = take(int(spec.get("shared", 0)))
        self.tenants = int(spec.get("tenants", 1))
        self.preambles = [take(int(spec.get("tenant_len", 0))) for _ in range(self.tenants)]
        self.branching = tuple(spec.get("branching", ()))
        seg = int(spec.get("segment", 0))
        self.nodes = {}
        for tenant in range(self.tenants):
            paths = [()]
            for b in self.branching:
                paths = [p + (c,) for p in paths for c in range(b)]
                for p in paths:
                    self.nodes[(tenant, p)] = take(seg)
        self.reserved = cursor
        if self.reserved > POOL_ROWS // 2:
            raise ValueError(f"prefix tree needs {self.reserved} pool rows, over {POOL_ROWS // 2}")

    def head(self, rng: np.random.Generator) -> Tuple[Tuple[int, int], ...]:
        if self.kind == "none":
            return ()
        tenant = int(rng.integers(self.tenants))
        pieces = [self.shared, self.preambles[tenant]]
        path = ()
        for b in self.branching:
            hot = rng.random() < float(self.spec.get("hot_bias", 0.0))
            path += (0 if hot or b == 1 else int(rng.integers(b)),)
            pieces.append(self.nodes[(tenant, path)])
        return tuple(p for p in pieces if p[1] > 0)


def schedule(mix: dict, seed: int, seconds: float) -> Schedule:
    """The whole run's sessions. Pure: same mix, seed and length, same schedule."""
    rng = np.random.default_rng([int(mix.get("order_seed", seed)), 2])
    arrival = mix["arrival"]
    ramp_s = float(mix.get("ramp_s", 0.0))
    tree = _Tree(mix.get("prefix", {"kind": "none"}))

    def plans(n: int, *, clients: int = 0) -> List[Plan]:
        prompt = draws(mix["prompt"], n, rng)
        output = draws(mix["output"], n, rng)
        perm = rng.permutation(POOL_ROWS - tree.reserved)  # distinct starts: no prefix is shared by accident
        out = []
        for i in range(n):
            own = tree.reserved + int(perm[i % len(perm)])
            segments = tree.head(rng) + ((own, prompt[i]),)
            need = sum(rows for _, rows in segments) + output[i]
            max_length = int(mix.get("max_length") or need)
            if need > max_length:
                raise ValueError(f"session needs {need} positions, max_length is {max_length}")
            out.append(Plan(index=i, client=(i % clients if clients else -1), due=None, segments=segments,
                            output=output[i], decode_offset=own + prompt[i], max_length=max_length))
        return out

    kind = arrival["kind"]
    if kind == "closed":
        k = int(arrival["clients"])
        flat = plans(k * int(arrival.get("sessions", CLOSED_SESSIONS_PER_CLIENT)), clients=k)
        per_client = tuple(tuple(p for p in flat if p.client == c) for c in range(k))
        starts = tuple(-ramp_s + ramp_s * c / k for c in range(k))
        return Schedule("closed", ramp_s, client_plans=per_client, client_starts=starts)
    span = ramp_s + float(seconds)
    if kind == "open_rate":
        n = max(int(round(float(arrival["rate_rps"]) * span)), 1)
        gaps = draws({"dist": "exponential", "mean": span / n}, n, rng, integer=False)
        # the quantile gaps sum to a little under the span: stretch them to it
        due = np.cumsum(gaps) * (span / (sum(gaps) + span / n)) - ramp_s
    elif kind == "burst":
        size, period = int(arrival["size"]), float(arrival["period_s"])
        n_bursts = max(int(span // period), 1)
        phase = float(rng.random()) * period
        due = np.repeat(phase + period * np.arange(n_bursts), size) - ramp_s
        n = len(due)
    else:
        raise ValueError(f"unknown arrival kind {kind!r}")
    open_plans = tuple(dataclasses.replace(p, due=float(t)) for p, t in zip(plans(n), due))
    return Schedule("open", ramp_s, open_plans=open_plans)
