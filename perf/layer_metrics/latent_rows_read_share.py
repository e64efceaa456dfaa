"""Of the latent rows that the lanes feeding decode rows held over the steps of
the traced window (``latent_rows_held``), the rows those rows' walks read
(``latent_rows_read``): both counted by the batcher on the host, times the
span's layers, from the shapes each step was started with. A walk that reads
each held row once reads 100; the walk of the lane pool reads every lane in
whole blocks up to the longest live lane's last one, so eight lanes of
16k-31k positions read over 100 by what the shorter lanes' tables are walked
past their own length (and an idle lane's table at all); a program that
gathered the whole table of 32,768 a lane would read 8 x 32,768 over the
held. A family that declares no latent row, or a program from before the
counters, gives None."""
UNIT, LAYER, MOVES = "%", "latent attention (ops/latent_attention.py)", "gap_p50_ms"


def read(record):
    try:
        share = record.ratio_over_children("latent_rows_read", "latent_rows_held", start="trace_start", end="trace_stop")
    except KeyError:  # a family that declares no latent row, or a program from before the counters
        return None
    return None if share is None else 100.0 * share
