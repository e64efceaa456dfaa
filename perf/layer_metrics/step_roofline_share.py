"""The least time the chip could have taken for the steps of the traced
window, over the time its device was busy in it. A step-level share: the
decode steps and the mixed steps of the window are each costed at their mean
shape (perf/costs.py) against the larger of the two floors of perf/peaks.json.
Per-kernel shares wait for named scopes in the program."""
from perf import costs

UNIT, LAYER, MOVES = "%", "kernels (ops/)", "gap_p50_ms"


def parts(record):
    """Per child: (least seconds, busy seconds, {bound: seconds})."""
    out = []
    hf, family = record.config["config"], record.config["family"]
    for child, span in zip(record.children, record.config["servers"]):
        trace, marks = child.get("trace") or {}, child.get("marks", {})
        if not trace.get("busy_s") or "trace_start" not in marks or record.peaks is None:
            return None
        kw = dict(start="trace_start", end="trace_stop")
        steps, tokens = record.stat_delta(child, "batched_steps", **kw), record.stat_delta(child, "batched_tokens", **kw)
        mixed, prefill = record.stat_delta(child, "mixed_steps", **kw), record.stat_delta(child, "prefill_tokens", **kw)
        if not steps:
            return None
        lo, hi = marks["trace_start"]["mono"], marks["trace_stop"]["mono"]
        positions = [pos for s in record.sessions for t, pos in s.replies if lo <= t <= hi]
        # cached positions per step: the lanes' mean context (a windowed layer caps it, perf/costs.py) times the lanes
        context = sum(positions) / max(len(positions), 1) * (tokens / steps)
        prompts = [s.plan.prompt_len for s in record.sessions] or [0]
        n_layers = span["num_blocks"]
        least, bounds = 0.0, {}
        for count, chunk in ((steps - mixed, 0.0), (mixed, prefill / mixed if mixed else 0.0)):
            if count <= 0:
                continue
            cost = costs.step_cost(family, hf, n_layers, first_block=span["first_block"], decode_tokens=tokens / steps,
                                   prefill_tokens=chunk, context_tokens=context, prefill_context=sum(prompts) / len(prompts) / 2)
            seconds, bound = costs.least_seconds(cost, record.peaks)
            least += seconds * count
            bounds[bound] = bounds.get(bound, 0.0) + seconds * count
        out.append((least, trace["busy_s"], bounds))
    return out


def read(record):
    found = parts(record)
    if not found:
        return None
    return 100.0 * sum(p[0] for p in found) / sum(p[1] for p in found)
