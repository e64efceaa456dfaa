"""What the gated delta rule's one-step form has to move, from shapes alone.
Kept with the benchmark, as perf/costs.py is, so that no PR that claims a gain
can change the yardstick (perf/layer_metrics/linattn_state_roofline_share.py
reads it; perf/costs.py's step floor has no state term yet: PERF.md section 7).

A linear-attention layer keeps, a lane, a float32 matrix of ``d_k x d_v`` a
VALUE head. One decode row reads it once and writes it once; the row's own q,
k, v, the decay and beta are a few KB beside it and are left out, as is the
conv's tail of ``taps - 1`` rows (it moves under the conv's scope)."""

STATE_BYTES = 4  # float32, whatever the cache's dtype


def state_matrix_bytes(hf: dict):
    """Bytes of one lane's state matrix in one linear layer, or None for a
    configuration without such a layer."""
    heads, d_k, d_v = (hf.get(key) for key in ("linear_num_value_heads", "linear_key_head_dim", "linear_value_head_dim"))
    if not (heads and d_k and d_v):
        return None
    return heads * d_k * d_v * STATE_BYTES


def one_step_bytes(hf: dict, row_layers: float):
    """The least bytes ``row_layers`` (decode rows x linear layers) one-step
    updates move: each state read once and written once."""
    matrix = state_matrix_bytes(hf)
    return None if matrix is None else 2 * matrix * row_layers
