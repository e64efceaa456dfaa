"""What the selective scan's one-step form has to move, from shapes alone.
Kept with the benchmark, as perf/costs.py and perf/linattn.py are, so that no
PR that claims a gain can change the yardstick
(perf/layer_metrics/ssm_scan_roofline_share.py reads it; perf/costs.py's step
floor has no state term yet: PERF.md section 7).

A state-space layer keeps, a lane, a float32 state of ``d_state`` numbers a
channel, ``mamba_expand x hidden_size`` channels. One decode row reads it once
and writes it once; the row's own u, dt, B and C are a few KB beside it and
are left out, as is the conv's tail of ``d_conv - 1`` rows (it moves under
the conv's scope). The scan does a few flops a byte of state, so bandwidth is
its only bound."""

STATE_BYTES = 4  # float32, whatever the cache's dtype


def state_bytes(hf: dict):
    """Bytes of one lane's state in one state-space layer, or None for a
    configuration without such a layer."""
    expand, hidden, d_state = (hf.get(key) for key in ("mamba_expand", "hidden_size", "mamba_d_state"))
    if not (expand and hidden and d_state):
        return None
    return expand * hidden * d_state * STATE_BYTES


def one_step_bytes(hf: dict, row_layers: float):
    """The least bytes ``row_layers`` (decode rows x state-space layers)
    one-step updates move: each state read once and written once."""
    state = state_bytes(hf)
    return None if state is None else 2 * state * row_layers
