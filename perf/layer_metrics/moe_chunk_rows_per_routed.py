"""Expert-rows (a position through one expert) the prompt chunks' expert
dispatch multiplied, over those the routing sent to the experts this server
holds, in the measured window: ``moe_chunk_rows_computed`` (positions x held
experts under the all-experts einsum, positions x top k under the grouped
dispatch, which is handed every assignment's row) over
``moe_chunk_rows_routed`` (positions x top k x held / routed), both counted by
the batcher on the host from the shapes each mixed step was started with. 1
would be a dispatch that multiplies what is routed here and nothing else; a
server that holds 128 of 512 experts under a top 10 reads 51.2 while its
chunks take the einsum.

Between the marks ``window`` and ``window_end``, not the 3-second trace slice:
a slice may hold no chunk, and a reader that then gives None costs the cell
its line. A server that holds all it routes over, or a program from before the
counters, gives None."""
UNIT, LAYER, MOVES = "ratio", "expert dispatch (models/moe.py)", "gap_p50_ms"


def read(record):
    try:
        return record.ratio_over_children("moe_chunk_rows_computed", "moe_chunk_rows_routed")
    except KeyError:  # every expert held, a family without experts, or a program from before the counters
        return None
