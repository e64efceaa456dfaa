"""How many times a batched step's program walks a layer's expert weights, on
average over the traced window: ``moe_weight_passes`` over ``batched_steps``
(``batcher.stats``, between ``trace_start`` and ``trace_stop``). A decode step
walks them once; a mixed step twice as ``server/backend.py`` stands, because
it calls the block once for the decode lanes and once for the prompt chunk
(2 x 6.4 GB a step at OLMoE-1B-7B's 8 layers). A program or a family without
the counter gives None."""
UNIT, LAYER, MOVES = "passes/step", "expert dispatch (models/moe.py)", "gap_p50_ms"


def read(record):
    try:
        return record.ratio_over_children("moe_weight_passes", "batched_steps", start="trace_start", end="trace_stop")
    except KeyError:  # a family without experts, or a program from before the counter
        return None
