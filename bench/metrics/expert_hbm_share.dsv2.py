"""The held experts' grouped matmuls in decode against the chip's memory
roofline: the bytes they require in the traced window's decode calls
(the weights of each held expert a call touched, from the routes, and
each routed pair's activations; ``bench/work/mla_moe.py``) over the
device time of the ops under ``moe/experts`` in ``decode_step``, over
the peak HBM bandwidth.  ``None`` where the record has no time by scope.
Moves ``itl_p95_ms``."""
from work.mla_moe import expert_weight_bytes, routed_pair_bytes


def read(rec):
    scopes = (rec.get("scopes") or {}).get("decode_step")
    calls = [c for c in rec["traced_calls"] or ()
             if c["program"] == "decode_step"]
    if (rec["trace"] is None or not scopes or not scopes.get("moe/experts")
            or not calls or any("held_pairs" not in c for c in calls)):
        return None
    s = rec["sizes"]
    byts = sum(c["experts_touched"] * expert_weight_bytes(s)
               + c["held_pairs"] * routed_pair_bytes(s) for c in calls)
    return (100.0 * byts / scopes["moe/experts"]
            / rec["peaks"]["hbm_bytes_per_s"])
