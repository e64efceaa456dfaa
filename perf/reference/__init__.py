"""Plain references: each family's block forward in ``jax.numpy`` float32 at
highest matmul precision, a whole sequence at once: no cache, no kernel, no
batching, and nothing imported from ``petals_tpu``. ``perf/reference/<family>.py``
is found by the configuration's ``family`` and gives:

``block(hf, w, x) -> (out, margin)``  one layer over ``x`` [seq, hidden] with
    the layer's tensors ``w`` (HF names without the layer prefix, float32).
    ``margin`` [seq] says how far the layer's discrete decisions at each
    position (a router's choice of experts) are from going the other way, as
    a share of the largest logit; ``inf`` where the layer takes none.
``ROW_BOUND_PER_LAYER``, ``MEDIAN_BOUND_PER_LAYER``  how far a row, and the
    median row, of the served bf16 path may be from the reference, per layer
    of depth (perf/correct.py), each with the measurement it was set from.
``TIE_MARGIN``  (optional, default 0) a position whose ``margin`` is under
    this at any layer is near-tied: the served path may decide the other way
    without a fault, so the position is left out of the comparison.
``POSITIONS_ALLOWED_OUTSIDE``  (optional, default 0) compared positions that
    may still be outside the row bound: only for a family whose decisions
    upstream can move one (see mixtral.py).
``layer_params(hf)``  the layer's matrix parameters, for perf/costs.py.
``layer_kinds(hf)``  (optional) for a family whose layers are not all alike:
    one hashable per layer of the model. ``block`` and ``layer_params`` here,
    and ``layer_tensors`` and ``block_params`` of perf/weights/<family>.py,
    are then given the layer's kind as a further, last argument.
"""

from __future__ import annotations

import importlib

import numpy as np

from perf import weights


def rotate_half_rotary(x, theta: float):
    """x [seq, heads, d] at positions 0..seq-1, HF "rotate half" convention."""
    import jax.numpy as jnp

    seq, _, d = x.shape
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def causal_gqa_attention(q, k, v):
    """q [seq, hkv, group, d], k and v [seq, hkv, d] -> [seq, hkv, group, d]."""
    import jax
    import jax.numpy as jnp

    seq, d = q.shape[0], q.shape[-1]
    scores = jnp.einsum("qhgd,khd->hgqk", q, k) / jnp.sqrt(jnp.float32(d))
    mask = jnp.tril(jnp.ones((seq, seq), bool))
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    return jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(scores, axis=-1), v)


def family_of(name: str):
    return importlib.import_module(f"perf.reference.{name}")


def kinds_of(name: str, hf: dict):
    """``[(kind,), ...]``, one per layer, where the family defines
    ``layer_kinds``: what its functions take after their other arguments.
    None for a family of one kind, whose functions are called without."""
    kinds = getattr(family_of(name), "layer_kinds", None)
    return [(kind,) for kind in kinds(hf)] if kinds else None


def limits(config: dict) -> dict:
    """The family's limits for perf/correct.py at this configuration's depth."""
    family = family_of(config["family"])
    n_layers = sum(span["num_blocks"] for span in config["servers"])
    return {"row_bound": family.ROW_BOUND_PER_LAYER * n_layers, "median_bound": family.MEDIAN_BOUND_PER_LAYER * n_layers,
            "tie_margin": getattr(family, "TIE_MARGIN", 0.0), "positions_allowed": getattr(family, "POSITIONS_ALLOWED_OUTSIDE", 0)}


def run(config: dict, hidden: np.ndarray) -> tuple:
    """``hidden`` [seq, hidden] through every layer of the configuration, each
    layer's weights made (perf/weights) as it is reached. Returns the output
    [seq, hidden] float32, per position the smallest decision margin over the
    layers, and each layer's weight checksum: a server's, of its first block,
    must equal that layer's."""
    import jax
    import jax.numpy as jnp

    family, maker = family_of(config["family"]), weights.family_of(config["family"])
    hf = config["config"]
    n_layers = sum(span["num_blocks"] for span in config["servers"])
    kinds = kinds_of(config["family"], hf) or [()] * n_layers

    def program(kind: tuple):
        def layer(index, x):
            w = maker.layer_tensors(hf, index, weights.Draws(config["weights_seed"]), *kind)
            out, margin = family.block(hf, {k: v.astype(jnp.float32) for k, v in w.items()}, x, *kind)
            return out, margin, weights.checksum(w)

        return jax.jit(layer)  # the index is traced: one program for every layer of a kind

    with jax.default_matmul_precision("highest"):
        programs = {kind: program(kind) for kind in dict.fromkeys(kinds[:n_layers])}
        x = jnp.asarray(hidden, jnp.float32)
        margin = jnp.full(x.shape[0], jnp.inf)
        checks = []
        for index in range(n_layers):
            x, layer_margin, check = programs[kinds[index]](jnp.uint32(index), x)
            margin = jnp.minimum(margin, layer_margin)
            checks.append(int(check))
        return np.asarray(x, np.float32), np.asarray(margin, np.float32), checks
