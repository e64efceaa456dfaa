"""``latent_attn_roofline_share.py``'s share for a block of TWO latent
attentions: the least seconds the chip needs for what both had to do in the
traced window over the device seconds of the operations that carry the latent
attention's names (``ptu.attn.latent_absorb | decode | chunk | expand``, around
both sub-layers' calls). The need is that file's ``need`` (and the time its ``named_seconds``: this reader calls its ``read``): the batcher's
``latent_rows_held``, ``latent_positions_held`` and ``latent_score_pairs``
between the trace's marks count every sub-layer (server/backend.py
``page_layers``: 2 a block), a row at ``(kv_lora_rank + qk_rope_head_dim) x 2
B`` read once a sub-layer, a pair at the expanded form's ``2 x heads x
(qk_nope_head_dim + qk_rope_head_dim + v_head_dim)`` flops; the low-rank
query's two projections and the two scales are no part of the need and of no
named scope. The same kernel's share of its roofline at 64 heads and contexts
of 1-2.5k where ``kanana2-ctx32k`` reads it at 32 heads and 16-31k. A
configuration without a block of more than one latent attention, a program
from before the counters or the scopes, or a run that left no capture of a
device gives None."""
from perf.layer_metrics import latent_attn_roofline_share as latent

UNIT, LAYER, MOVES = "%", "latent attention (ops/latent_attention.py)", "gap_p50_ms"


def read(record):
    if not record.config.get("config", {}).get("zero_expert_num"):
        return None
    return latent.read(record)  # its ``need`` and ``named_seconds``: the counters count the sub-layers, the scopes are around both calls
