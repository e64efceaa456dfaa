"""Every Pallas kernel the serving code can select on a TPU must LOWER for it.

The ``-m kernel`` / ``-m kvquant`` lanes prove numerics in the Pallas
interpreter, which accepts block shapes and vector ops Mosaic refuses. This
lane proves the compiler takes them: with ``libtpu`` installed,
``jax.experimental.topologies`` hands out compile-only v5e devices on a host
that has no chip, and ``jit(f).lower(<avals placed on one>).compile()`` runs
the real Pallas -> Mosaic -> libtpu pipeline with ``interpret=False``. Each
case is one full-width shape (Llama-2-7B / 70B head geometry); nothing
executes. Numerics on the chip itself are chip_smoke.py's kernel phase.
"""

import functools

import jax
import jax.numpy as jnp
import pytest

pytest.importorskip("libtpu")

from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from petals_tpu.ops import paged_flash_attention as pfa  # noqa: E402
from petals_tpu.ops import quant as Q  # noqa: E402
from petals_tpu.ops.flash_attention import flash_attend  # noqa: E402
from petals_tpu.ops.paged_attention import PagedPool  # noqa: E402

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
    if kv_quant == "none":
        return v5e((n_pages, page_size, hkv, d), BF16)
    codes = (
        v5e((n_pages, page_size, hkv, d), jnp.int8)
        if kv_quant == "int8"
        else v5e((n_pages, page_size, hkv, d // 2), jnp.uint8)
    )
    return PagedPool(codes, v5e((n_pages, page_size, hkv), F32))


PAGED_CASES = [
    # hq, hkv, d, kv_quant — page_size 64, the Server default
    (32, 32, 128, "none"),
    (32, 8, 128, "none"),
    (32, 8, 128, "int8"),
    (32, 32, 128, "nf4a"),
    (64, 8, 64, "none"),
]
PAGED_IDS = ["bf16-mha", "bf16-gqa", "int8-gqa", "nf4a-mha", "bf16-d64"]


@pytest.mark.parametrize("hq,hkv,d,kv_quant", PAGED_CASES, ids=PAGED_IDS)
def test_paged_flash_attend_lowers(v5e, hq, hkv, d, kv_quant):
    lanes, max_pages, page_size = 8, 16, 64
    assert pfa.paged_kernel_unsupported(
        pfa.shape_class(lanes, max_pages, page_size, hkv, d, None, kv_quant)
    ) is None
    pool = _pool(v5e, lanes * max_pages, page_size, hkv, d, kv_quant)
    _compile(
        lambda q, k, v, t, p: pfa.paged_flash_attend(q, k, v, t, p, interpret=False),
        v5e((lanes, 1, hq, d), BF16), pool, pool,
        v5e((lanes, max_pages), I32), v5e((lanes,), I32),
    )


@pytest.mark.parametrize("hq,hkv,d,kv_quant", PAGED_CASES, ids=PAGED_IDS)
def test_paged_flash_prefill_attend_lowers(v5e, hq, hkv, d, kv_quant):
    max_pages, page_size, chunk = 16, 64, 512  # chunk 512 -> block_q 256
    pool = _pool(v5e, 8 * max_pages, page_size, hkv, d, kv_quant)
    _compile(
        lambda q, k, v, t, c, n: pfa.paged_flash_prefill_attend(
            q, k, v, t, c, n, interpret=False
        ),
        v5e((1, chunk, hq, d), BF16), pool, pool,
        v5e((max_pages,), I32), v5e((), I32), v5e((), I32),
    )


def test_paged_alibi_and_window_lower(v5e):
    """The slopes operand (a VMEM column for decode, f32 scalar prefetch for
    prefill) and the windowed skip predicate ride the same kernels."""
    lanes, max_pages, page_size, hq, hkv, d = 8, 16, 64, 32, 8, 128
    pool = _pool(v5e, lanes * max_pages, page_size, hkv, d, "none")
    _compile(
        lambda q, k, v, t, p, s: pfa.paged_flash_attend(
            q, k, v, t, p, alibi_slopes=s, sliding_window=256, interpret=False
        ),
        v5e((lanes, 1, hq, d), BF16), pool, pool,
        v5e((lanes, max_pages), I32), v5e((lanes,), I32), v5e((hq,), F32),
    )
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
    key = pfa.shape_class(8, 16, 64, 8, 80, None, "none")
    assert pfa.paged_kernel_unsupported(key) is not None


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
