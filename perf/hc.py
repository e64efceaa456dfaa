"""What a hyper-connection around a sub-layer has to move and compute, from
shapes alone. Kept with the benchmark, as perf/costs.py, perf/ssm.py and
perf/linattn.py are, so that no PR that claims a gain can change the
yardstick (perf/layer_metrics/hc_mix_roofline_share.py reads it; perf/costs.py's
step floor has no stream term: its ``hidden`` is the stream's width, which
counts a row's read and write once a layer).

A position's residual stream is ``n = hc_mult`` rows of ``C = hidden_size``.
One wrap of one row (models/xing4_0/block.py ``stream_wrap``) reads the
stream once (the norm, the three thin products, ``u = Hp @ X`` and ``M @ X``
can all come of one pass) and writes it once, and hands the sub-layer ``u``
and takes its output back, a row of ``C`` each: bf16, 2 B a value. The three
``phi`` of a wrap, ``n*C x (2n + n*n)`` together, are read once a wrap a step
whatever the rows. The flops: the norm (3 a value), the products (2 x (2n +
n*n) a value), Sinkhorn (rounds x 2 x 2 n*n), ``u`` (2n a column) and ``X'``
(2 n*n + 2n a column): about a fortieth of what the chip does in the time
the bytes take, so bandwidth is the bound that binds."""

BYTES = 2  # bf16: the stream in the step programs, and the phi


def dims(hf: dict):
    """``(n, C, Sinkhorn's rounds)``, or None for a configuration without a stream of several rows."""
    n, width = hf.get("hc_mult"), hf.get("hidden_size")
    if not n or n < 2 or not width:
        return None
    return int(n), int(width), int(hf.get("hc_sinkhorn_iters", 20))


def row_bytes(hf: dict):
    """Bytes one wrap of one row moves at the least: the stream read and written, ``u`` out and the sub-layer's output in."""
    shape = dims(hf)
    if shape is None:
        return None
    n, width, _ = shape
    return (2 * n * width + 2 * width) * BYTES


def phi_bytes(hf: dict):
    """Bytes of one wrap's three ``phi``: read once a step."""
    shape = dims(hf)
    if shape is None:
        return None
    n, width, _ = shape
    return n * width * (2 * n + n * n) * BYTES


def row_flops(hf: dict):
    """Flops of one wrap of one row."""
    shape = dims(hf)
    if shape is None:
        return None
    n, width, rounds = shape
    return n * width * (3 + 2 * (2 * n + n * n)) + rounds * 2 * 2 * n * n + width * (2 * n + 2 * n * n + 2 * n)


def least(hf: dict, row_wraps: float, step_wraps: float):
    """``(bytes, flops)`` of ``row_wraps`` (rows x wraps) over ``step_wraps`` (steps x wraps), or None."""
    if dims(hf) is None:
        return None
    return row_bytes(hf) * row_wraps + phi_bytes(hf) * step_wraps, row_flops(hf) * row_wraps
