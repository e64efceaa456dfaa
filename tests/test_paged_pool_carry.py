"""The paged step programs carry the page pool through their layer loop
(server/backend.py ``_scan_paged_span``): the whole span's pool, flattened,
with every layer's block tables shifted to its own pages. What they write
must be what the program they replaced wrote (bit for bit where no decode
row's walk lies between: the walk's loop compiles to other roundings of
float32 in one program than in the other): that program is pinned here as
the reference (``_per_layer_scan``: the pools as the scan's
``xs`` / ``ys``, a layer sliced out, ``PagedKV`` over that layer alone, the
updated layers stacked), for all four programs, a plain and an int8 pool,
on three layers with holes in the tables and an idle lane. And the pools a
step is given are donated: they are gone after the call."""

import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from petals_tpu.ops.paged_attention import PagedKV, PagedPool, quantize_kv_rows
from petals_tpu.ops.sampling import sampling_vectors
from tests.test_kv_quant import _tiny_backend
from tests.utils import make_tiny_llama

pytestmark = pytest.mark.pages

DEPTH, LANES, PS, MAX_PAGES, N_PAGES = 3, 4, 8, 4, 14
SENTINEL = PS * MAX_PAGES  # an idle lane's position: every write drops
SPEC_ROWS = 3
CHUNK, CHUNK_LANE, CHUNK_POS = 5, 3, 8  # bucket 8: three padded rows drop


@pytest.fixture(scope="module")
def model_path(tmp_path_factory):
    return make_tiny_llama(str(tmp_path_factory.mktemp("models")))


def _backend(model_path, kind):
    backend, cfg = _tiny_backend(model_path, kind, n_blocks=DEPTH)
    return backend, backend.family, cfg


def _state(backend, cfg, kind, seed):
    """Pools full of seeded rows (every page, owned or not: a write that
    lands in another layer's or another lane's page shows), tables with -1
    holes, lane 1 idle, lane 3 the one whose prompt chunk rides a mixed step."""
    rng = np.random.default_rng(seed)
    shape = (DEPTH, N_PAGES, PS, backend.num_kv_heads, backend.head_dim)

    def pool():
        rows = jnp.asarray(rng.standard_normal(shape).astype(np.float32))
        return rows if kind == "none" else PagedPool(*quantize_kv_rows(rows, kind))

    positions = np.array([5, SENTINEL, 2 * PS + 1, SENTINEL], np.int32)
    tables = np.full((LANES, MAX_PAGES), -1, np.int32)
    free = list(rng.permutation(N_PAGES))
    for lane, pages in enumerate((2, 0, 4, 2)):  # lane 0 leaves two holes, lane 1 owns nothing
        for slot in range(pages):
            tables[lane, slot] = free.pop()
    hidden = rng.standard_normal((LANES, 1, cfg.hidden_size)).astype(np.float32) * 0.1
    return pool, positions, tables, hidden, rng


def _per_layer_scan(params, k_pool, v_pool, carry, layer):
    """The layer loop as it was until PR 29: the pools ride the scan as ``xs``
    and come back as ``ys``, and ``layer(carry, p_block, k_layer, v_layer)``
    sees one layer's pool, ``[n_pages, page_size, hkv, d]``, and the lanes'
    own tables."""
    def body(c, xs):
        p_block, k_layer, v_layer = xs
        c, k_layer, v_layer = layer(c, p_block, k_layer, v_layer)
        return c, (k_layer, v_layer)

    carry, (k_pool, v_pool) = jax.lax.scan(body, carry, (params, k_pool, v_pool))
    return carry, k_pool, v_pool


def _reference(backend, family, cfg, program, k_pool, v_pool, hidden, positions, tables, chunk_hidden):
    """What the replaced program returns for ``program``'s inputs: (hidden
    leaves, k_pool, v_pool). ``hidden`` is what enters the first block (the
    programs that embed tokens are given the embedded rows)."""
    def lanes_layer(h, p_block, k_layer, v_layer):
        out, (k_kv, v_kv) = family.block_apply(
            p_block, h, (PagedKV(k_layer, tables), PagedKV(v_layer, tables)),
            positions, cfg, use_flash=False, tp_mesh=None,
        )
        return out, k_kv.pool, v_kv.pool

    def mixed_layer(carry, p_block, k_layer, v_layer):
        h_dec, h_pf = carry
        out_dec, k_layer, v_layer = lanes_layer(h_dec, p_block, k_layer, v_layer)
        row = jnp.asarray(tables)[CHUNK_LANE][None]
        extra = {"n_total": CHUNK_POS + CHUNK} if "n_total" in inspect.signature(family.block_apply).parameters else {}
        out_pf, (k_kv, v_kv) = family.block_apply(
            p_block, h_pf, (PagedKV(k_layer, row), PagedKV(v_layer, row)),
            jnp.int32(CHUNK_POS), cfg, use_flash=False, n_valid=jnp.int32(CHUNK), tp_mesh=None, **extra,
        )
        return (out_dec, out_pf), k_kv.pool, v_kv.pool

    if program == "mixed":
        return jax.jit(lambda p, k, v, h, c: _per_layer_scan(p, k, v, (h, c), mixed_layer))(
            backend.params, k_pool, v_pool, hidden, chunk_hidden
        )
    return jax.jit(lambda p, k, v, h: _per_layer_scan(p, k, v, h, lanes_layer))(
        backend.params, k_pool, v_pool, hidden
    )


def _leaves(pool):
    return [np.asarray(leaf) for leaf in jax.tree_util.tree_leaves(pool)]


def _assert_same(got, want, exact, err_msg=""):
    """Bit for bit, or (a program with a decode row's walk in it) to float32
    rounding: a quantised pool's codes may then fall one step apart in the
    few rows whose rounding flipped."""
    if exact:
        np.testing.assert_array_equal(got, want, err_msg=err_msg)
    elif np.issubdtype(got.dtype, np.integer):
        apart = np.abs(got.astype(np.int32) - want.astype(np.int32))
        assert apart.max() <= 1 and (apart != 0).mean() < 1e-2, err_msg
    else:
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5, err_msg=err_msg)


@pytest.mark.parametrize("kind", ["none", "int8"])
@pytest.mark.parametrize("program", ["decode", "mixed", "gen", "spec"])
def test_step_writes_what_the_per_layer_program_wrote(model_path, program, kind):
    from petals_tpu.client.from_pretrained import load_client_params

    backend, family, cfg = _backend(model_path, kind)
    pool, positions, tables, hidden, rng = _state(backend, cfg, kind, seed=29)
    k_pool, v_pool = pool(), pool()
    copies = jax.tree_util.tree_map(jnp.copy, (k_pool, v_pool))  # the step donates what it is given
    k_before, v_before = _leaves(copies[0]), _leaves(copies[1])  # (a numpy view of a pool would pin it)
    client_params = load_client_params(model_path, dtype=jnp.float32)
    vecs = sampling_vectors(LANES, cfg.vocab_size)
    chunk_hidden = None

    if program == "decode":
        out, (k_new, v_new) = backend.paged_decode_step(hidden, (k_pool, v_pool), positions, tables)
        got = [out]
    elif program == "mixed":
        chunk_hidden = rng.standard_normal((1, CHUNK, cfg.hidden_size)).astype(np.float32) * 0.1
        out, chunk_out, (k_new, v_new) = backend.paged_mixed_step(
            hidden, (k_pool, v_pool), positions, tables, chunk_hidden, CHUNK_LANE, CHUNK_POS
        )
        got = [out, chunk_out]
        chunk_hidden = np.pad(chunk_hidden, ((0, 0), (0, 8 - CHUNK), (0, 0)))  # its bucket
    elif program == "gen":
        tokens = np.array([7, 0, 11, 0], np.int32)
        use_token = np.array([True, False, False, False])
        out, _, (k_new, v_new) = backend.paged_gen_decode_step(
            client_params, hidden, tokens, use_token, (k_pool, v_pool), positions, tables, sampling_vecs=vecs
        )
        got = [out]
        embedded = family.client_embed(client_params, jnp.asarray(tokens)[:, None], cfg)
        hidden = jnp.where(use_token[:, None, None], embedded.astype(jnp.float32), hidden)
    else:
        tokens = rng.integers(1, cfg.vocab_size, (LANES, SPEC_ROWS)).astype(np.int32)
        _, _, (k_new, v_new) = backend.paged_spec_verify_step(
            client_params, tokens, (k_pool, v_pool), positions, tables, sampling_vecs=vecs
        )
        got = []
        hidden = family.client_embed(client_params, jnp.asarray(tokens), cfg).astype(jnp.float32)

    assert k_pool.is_deleted() and v_pool.is_deleted(), "the step no longer donates its pools"
    want, k_want, v_want = _reference(
        backend, family, cfg, program, *copies, hidden, positions, tables, chunk_hidden
    )
    for name, new, ref, before in (("k", k_new, k_want, k_before), ("v", v_new, v_want, v_before)):
        assert type(new) is type(ref) and jax.tree_util.tree_structure(new) == jax.tree_util.tree_structure(ref)
        for leaf, ref_leaf, old in zip(_leaves(new), _leaves(ref), before):
            assert leaf.shape == old.shape and leaf.dtype == old.dtype
            _assert_same(leaf, ref_leaf, program == "spec", err_msg=f"{program}/{kind}: the {name} pool differs")
            assert (leaf != old).any(), "the step wrote nothing: the test holds nothing"
    for out, ref in zip(got, jax.tree_util.tree_leaves(want)):
        _assert_same(np.asarray(out), np.asarray(ref)[:, : out.shape[1]], program == "spec")


@pytest.mark.parametrize("program", ["decode", "mixed"])
def test_prefill_kernel_arm_reads_its_own_layer(model_path, program):
    """On a TPU (faked; the kernel is the interpreter's here, which takes the
    toy's head width too) a prompt's chunk takes the fused prefill kernel:
    each block's attention then takes its own layer out of the carried pool
    and reads it through the lane's own table, and the step agrees with the
    per-layer program composed from XLA in its outputs and in every row it
    wrote, on every layer. A decode step asks for no layer of its own: its
    rows walk the span's pool as the loop carries it."""
    from petals_tpu.ops import paged_flash_attention as pfa
    from petals_tpu.ops.paged_attention import PagedKV

    backend, family, cfg = _backend(model_path, "none")
    pool, positions, tables, hidden, rng = _state(backend, cfg, "none", seed=31)
    k_pool, v_pool = pool(), pool()
    copies = jax.tree_util.tree_map(jnp.copy, (k_pool, v_pool))
    chunk_hidden = rng.standard_normal((1, 8, cfg.hidden_size)).astype(np.float32) * 0.1
    own_layers, own_layer = [], PagedKV.own_layer
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(pfa, "_platform", lambda: "tpu")
        patch.setattr(pfa, "paged_kernel_unsupported", lambda *cls: None)
        patch.setattr(PagedKV, "own_layer", lambda self: own_layers.append(self.layer) or own_layer(self))
        if program == "decode":
            out, (k_new, v_new) = backend.paged_decode_step(hidden, (k_pool, v_pool), positions, tables)
            got = [out]
        else:
            out, chunk_out, (k_new, v_new) = backend.paged_mixed_step(
                hidden, (k_pool, v_pool), positions, tables, chunk_hidden[:, :CHUNK], CHUNK_LANE, CHUNK_POS
            )
            got = [out, chunk_out]
            chunk_hidden[:, CHUNK:] = 0.0  # the bucket's padding
    assert bool(own_layers) == (program == "mixed")
    want, k_want, v_want = _reference(
        backend, family, cfg, program, *copies, hidden, positions, tables, chunk_hidden
    )
    live = positions < SENTINEL  # an idle lane's output is never read
    for new, ref in ((k_new, k_want), (v_new, v_want)):
        np.testing.assert_allclose(np.asarray(new), np.asarray(ref), atol=1e-4, rtol=0)
    np.testing.assert_allclose(
        np.asarray(got[0])[live], np.asarray(jax.tree_util.tree_leaves(want)[0])[live], atol=1e-4, rtol=0
    )
    if program == "mixed":
        np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1])[:, :CHUNK], atol=1e-4, rtol=0)


def test_own_layer_is_the_layer_and_its_tables():
    """``PagedKV.own_layer`` (what the prefill kernel's arm takes): layer 1 of a
    three-layer span pool, and the tables as they were before the shift."""
    rng = np.random.default_rng(3)
    span = jnp.asarray(rng.standard_normal((3 * N_PAGES, PS, 2, 16)).astype(np.float32))
    tables = np.array([[4, -1, 9], [-1, -1, 0]], np.int32)
    first_page = jnp.int32(N_PAGES)
    kv = PagedKV(span, jnp.where(tables >= 0, tables + first_page, -1), (first_page, N_PAGES))
    own = kv.own_layer()
    assert own.layer is None
    np.testing.assert_array_equal(np.asarray(own.pool), np.asarray(span)[N_PAGES : 2 * N_PAGES])
    np.testing.assert_array_equal(np.asarray(own.tables), tables)
    quantized = PagedKV(PagedPool(*quantize_kv_rows(span, "int8")), kv.tables, kv.layer).own_layer()
    assert quantized.pool.codes.shape[0] == quantized.pool.scales.shape[0] == N_PAGES
    alone = PagedKV(span, jnp.asarray(tables))
    assert alone.own_layer() is alone
