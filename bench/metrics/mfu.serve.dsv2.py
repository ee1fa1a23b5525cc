"""Model operations of the request tokens computed in the traced window
(``bench/work/mla_moe.py``: latent attention absorbed in decode and
expanded in prefill, the dense layer, the router, shared experts, the
routed experts of the pairs that landed on the held experts, the head;
no padding rows) over the window and the chip's bf16 peak: the whole
serving step.  Moves ``output_tokens_per_s``."""
from work.mla_moe import routed_pair_flops, token_flops


def read(rec):
    t, calls = rec["trace"], rec["traced_calls"]
    if (t is None or not t["devices"] or not calls or not t["window_s"]
            or any("held_pairs" not in c for c in calls)):
        return None
    s = rec["sizes"]
    ops = sum(token_flops(s, ctx, c["program"] == "decode_step")
              for c in calls for ctx in c["contexts"])
    ops += routed_pair_flops(s) * sum(c["held_pairs"] for c in calls)
    return 100.0 * ops / t["window_s"] / rec["peaks"]["bf16_flops_per_s"]
