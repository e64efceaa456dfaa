"""The "hit" expert dispatch (models/moe.py, ops/expert_hit.py) against the
all-experts einsum, on the CPU with the kernel in Pallas interpret mode: the
same routing, the same weights, float32 throughout, so the two differ in
summation order alone and are held to the tolerance ``tests/test_olmoe.py``
holds the grouped dispatch to."""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from petals_tpu.models.moe import ExpertStack, Routing, hit_slots, moe_apply, route
from petals_tpu.ops import expert_hit

H, ROWS = 64, 8

# name -> (routed, held, first, top_k, expert width, moe_apply's rule)
ROUTERS = {
    "softmax-renormalised-2-of-8": (8, 8, 0, 2, 128, dict(renormalize=True)),
    "softmax-as-is-8-of-64": (64, 64, 0, 8, 128, dict(renormalize=False)),
    "sigmoid-bias-8-of-128-holds-first-16": (128, 16, 0, 8, 128, dict(renormalize=True, scoring="sigmoid", scale=2.5)),
    "sigmoid-bias-8-of-128-holds-16-from-40": (128, 16, 40, 8, 128, dict(renormalize=True, scoring="sigmoid", scale=2.5)),
}
STACKS = {"stack-of-1": (1, 0), "layer-1-of-3": (3, 1)}
CASES = [
    pytest.param(router, live, stack, False, id=f"{router}-{live}-live-{stack}")
    for router, live, stack in itertools.product(ROUTERS, (1, 3, 8), STACKS)
] + [
    pytest.param(router, 3, "layer-1-of-3", True, id=f"{router}-every-live-row-the-same-experts")
    for router in ("softmax-renormalised-2-of-8", "sigmoid-bias-8-of-128-holds-first-16")
]


def _layers(router: str, depth: int, seed: int):
    """``depth`` layers of one expert layer's parameters, the experts stacked [depth, E, ...]."""
    routed, held, first, top_k, m, _ = ROUTERS[router]
    rng = np.random.RandomState(seed)
    params = {"gate": jnp.asarray(rng.randn(H, routed) * 0.3, jnp.float32)}
    if "sigmoid" in router:
        # the bias chooses: it draws the choices to the held experts' neighbourhood, some inside, some out
        near = (np.arange(routed) >= first - 6) & (np.arange(routed) < first + held + 6)
        params["gate_bias"] = jnp.asarray(rng.randn(routed) * 0.1 + near * 0.8, jnp.float32)
    stacks = {
        "w1": jnp.asarray(rng.randn(depth, held, H, m) * 0.1, jnp.float32),
        "w3": jnp.asarray(rng.randn(depth, held, H, m) * 0.1, jnp.float32),
        "w2": jnp.asarray(rng.randn(depth, held, m, H) * 0.1, jnp.float32),
    }
    return params, stacks


@pytest.mark.parametrize("router,n_live,stack,same", CASES)
def test_hit_dispatch_gives_the_all_experts_einsum_on_live_rows(router, n_live, stack, same):
    routed, held, first, top_k, m, rule = ROUTERS[router]
    depth, layer = STACKS[stack]
    params, stacks = _layers(router, depth, seed=len(router) + n_live)
    rng = np.random.RandomState(7 + n_live)
    x = rng.randn(ROWS, 1, H).astype(np.float32)
    live = np.zeros(ROWS, bool)
    live[rng.permutation(ROWS)[:n_live]] = True
    if same:
        x[live] = x[live][0]
    x = jnp.asarray(x)

    of_layer = {**params, **{name: leaf[layer] for name, leaf in stacks.items()}}
    want = np.asarray(moe_apply(of_layer, x, top_k=top_k, first=first, dispatch="dense", **rule))
    handed = {**params, "experts": ExpertStack(stacks["w1"], stacks["w3"], stacks["w2"], jnp.int32(layer))}
    got = np.asarray(jax.jit(
        lambda p, x, live: moe_apply(p, x, top_k=top_k, first=first, dispatch="hit", live_rows=live, **rule)
    )(handed, x, jnp.asarray(live)))
    np.testing.assert_allclose(got[live], want[live], rtol=1e-5, atol=1e-5)
    assert np.abs(want[live]).max() > 1e-3  # the held experts gave the live rows something to compare

    # what the call read: the held experts the LIVE rows chose, ascending, and no others
    top_idx, top_w = route(params, x, Routing(top_k, rule.get("scoring", "softmax"), rule["renormalize"], rule.get("scale", 1.0)))
    among_held = np.asarray(top_idx).reshape(ROWS, top_k) - first
    reached = sorted({int(e) for e in among_held[live].ravel() if 0 <= e < held})
    slot_expert, n_hit, combine = (np.asarray(a) for a in hit_slots(
        jnp.asarray(among_held), top_w.reshape(ROWS, top_k), jnp.asarray(live), held
    ))
    assert n_hit == len(reached) and list(slot_expert[:n_hit]) == reached
    assert (slot_expert[n_hit:] == reached[-1]).all() and not combine[n_hit:].any() and not combine[:, ~live].any()
    if same:
        assert n_hit == (top_k if held == routed else len(reached)) < len(slot_expert)
    if first:  # some of the live rows' choices fell outside the held experts, and some inside
        assert reached and any(not 0 <= e < held for e in among_held[live].ravel())


def test_hit_kernel_walks_the_tiles_of_a_wide_expert(monkeypatch):
    """An expert wider than a tile's budget is read in tiles of its width (a
    real Mixtral expert takes 14 of 1024): the same sum, tile by tile, and
    slots past ``n_hit`` add nothing."""
    monkeypatch.setattr(expert_hit, "WEIGHT_TILES_BYTES", 2 * 3 * H * 128 * 4)
    depth, n_experts, m, rows = 2, 4, 384, 5
    assert expert_hit.tile_width(H, m, 4) == 128
    rng = np.random.RandomState(0)
    w1, w3 = (jnp.asarray(rng.randn(depth, n_experts, H, m) * 0.1, jnp.float32) for _ in range(2))
    w2 = jnp.asarray(rng.randn(depth, n_experts, m, H) * 0.1, jnp.float32)
    x = jnp.asarray(rng.randn(rows, H), jnp.float32)
    slot_expert, n_hit = jnp.asarray([0, 3, 3, 3], jnp.int32), 2
    combine = jnp.asarray(rng.rand(4, rows), jnp.float32)
    got = expert_hit.hit_experts(x, w1, w3, w2, 1, slot_expert, n_hit, combine)
    want = sum(
        (jax.nn.silu(x @ w1[1, e]) * (x @ w3[1, e]) * combine[s][:, None]) @ w2[1, e] for s, e in ((0, 0), (1, 3))
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    none = expert_hit.hit_experts(x, w1, w3, w2, 1, jnp.zeros(4, jnp.int32), 0, jnp.zeros((4, rows), jnp.float32))
    assert not np.asarray(none).any()  # no live row reached a held expert: nothing is added


@pytest.mark.parametrize("hidden,width,itemsize,tile", [
    (4096, 14336, 2, 1024), (6144, 2048, 2, 512), (2048, 1024, 2, 1024), (64, 96, 4, 96),
], ids=["mixtral", "k-exaone", "olmoe", "toy-not-a-multiple-of-128"])
def test_tile_width_follows_the_shapes(hidden, width, itemsize, tile):
    assert expert_hit.tile_width(hidden, width, itemsize) == tile
    assert width % tile == 0 and 2 * 3 * hidden * tile * itemsize <= expert_hit.WEIGHT_TILES_BYTES
