"""``moe_chunk_rows_per_routed.py``'s ratio for a router that is wider than
the experts that exist: expert-rows the prompt chunks' dispatch multiplied
(``moe_chunk_rows_computed``: positions x held experts under the all-experts
einsum, positions x top k under the grouped dispatch) over the FFN picks the
routing sent to a HELD expert (``moe_chunk_rows_routed``: positions x top k x
held / the router's width), in the measured window, both counted by the
batcher on the host from the shapes each mixed step was started with. The
identities are counted on neither side: a pick of one multiplies no expert
row, and the router's width, which counts them, is what makes a pick of a held
expert as rare as it is. A server that holds 16 of 512 experts under a router
of 768 outputs and a top 12 reads 16 / (12 x 16 / 768) = 64 while its chunks
take the einsum; 1 would be a dispatch that multiplies what is routed here
and nothing else.

Between the marks ``window`` and ``window_end``, not the 3-second trace slice,
as the file it borrows from. A configuration without identity experts, a
server that holds all it routes over, or a program from before the counters
gives None."""
from perf.layer_metrics import moe_chunk_rows_per_routed as plain

UNIT, LAYER, MOVES = "ratio", "expert dispatch (models/moe.py)", "gap_p50_ms"


def read(record):
    if not record.config.get("config", {}).get("zero_expert_num"):
        return None
    return plain.read(record)
