"""Operations and bytes a DeepSeek-V2 decoder's calls require (latent
attention, a dense first layer, routed and shared experts), from their
shapes and the routed pairs that landed on the held experts.

"Required" is the work of the algorithm, whatever implements it: a
matmul of (M, K) by (K, N) is ``2 * M * K * N`` operations.  Per token:

* the query's low-rank path (``d -> q_lora -> heads * (nope + rope)``)
  and the latent projection (``d -> kv_lora + rope``);
* attention over ``context`` positions.  Decode is absorbed: the query's
  128-wide part goes through ``W_uk`` into the latent, scores over the
  latent and the rotary key, the latent sum of values, and ``W_uv`` out.
  Prefill is expanded: each token's key and value are expanded from its
  latent once, then scores and values over the context per head;
* ``W_o``; the dense layer's gated MLP or, in an MoE layer, the router,
  the shared experts, and three matmuls of ``moe_d_ff`` for each routed
  pair on a held expert (``routed_pair_flops``, counted apart: how many
  pairs land here is read from the run);
* the head over the vocabulary slice.

``sizes`` holds the configuration's ``model`` entries.
"""
from __future__ import annotations

from typing import Dict

ACT_BYTES = 2       # bf16 activations
WEIGHT_BYTES = 2    # bf16 weights


def _attn_proj_flops(s: Dict) -> float:
    d, h = s["d_model"], s["num_heads"]
    nope, rope, vd = s["qk_nope_head_dim"], s["qk_rope_head_dim"], s["v_head_dim"]
    q = (d * s["q_lora_rank"] + s["q_lora_rank"] * h * (nope + rope)
         if s["q_lora_rank"] else d * h * (nope + rope))
    return 2.0 * (q + d * (s["kv_lora_rank"] + rope) + h * vd * d)


def attention_flops(s: Dict, context: int, decode: bool) -> float:
    """One token's attention over ``context`` positions (both paths)."""
    h, r = s["num_heads"], s["kv_lora_rank"]
    nope, rope, vd = s["qk_nope_head_dim"], s["qk_rope_head_dim"], s["v_head_dim"]
    if decode:
        return 2.0 * h * (nope * r + context * (r + rope) + context * r
                          + r * vd)
    return 2.0 * h * (r * (nope + vd) + context * (nope + rope)
                      + context * vd)


def moe_layers(s: Dict) -> int:
    return s["num_layers"] - s["first_dense_layers"] if s["num_experts"] else 0


def token_flops(s: Dict, context: int, decode: bool) -> float:
    """Every operation of one token but its routed experts' FFNs."""
    d = s["d_model"]
    n_moe = moe_layers(s)
    dense_mlp = 3 * 2.0 * d * s["d_ff"]
    moe_fixed = 2.0 * d * s["num_experts"] + 3 * 2.0 * d * (
        s["moe_d_ff"] * s["num_shared_experts"])
    per_layer = _attn_proj_flops(s) + attention_flops(s, context, decode)
    return (s["num_layers"] * per_layer + (s["num_layers"] - n_moe) * dense_mlp
            + n_moe * moe_fixed + 2.0 * d * s["vocab_size"])


def routed_pair_flops(s: Dict) -> float:
    """One (token, expert) pair's expert FFN: up, gate and down."""
    return 3 * 2.0 * s["d_model"] * s["moe_d_ff"]


def expert_weight_bytes(s: Dict) -> float:
    """One routed expert's weights (up, gate, down), read once a call by
    the grouped matmul when a token lands on it."""
    return 3.0 * s["d_model"] * s["moe_d_ff"] * WEIGHT_BYTES


def routed_pair_bytes(s: Dict) -> float:
    """One pair's activations through the expert FFN: its row read, the
    up and gate outputs written and read back, the output written."""
    d, f = s["d_model"], s["moe_d_ff"]
    return (d + 2 * f + f + d) * ACT_BYTES
