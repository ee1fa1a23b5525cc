"""Model operations of the request tokens computed in the traced window
(prefill and decode; every matmul at 2*M*N*K, attention's scores and p.v,
the head; no padding rows) over the window and the chip's bf16 peak: the
whole serving step.  Moves ``output_tokens_per_s``."""
from work.decoder import token_flops


def read(rec):
    t, calls = rec["trace"], rec["traced_calls"]
    if t is None or not t["devices"] or not calls or not t["window_s"]:
        return None
    ops = sum(token_flops(rec["sizes"], c) for call in calls
              for c in call["contexts"])
    return 100.0 * ops / t["window_s"] / rec["peaks"]["bf16_flops_per_s"]
