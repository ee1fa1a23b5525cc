"""Operations and bytes a GQA decoder's calls require, from their shapes.

"Required" is the work of the algorithm, whatever implements it: a
matmul of (M, K) by (K, N) is ``2 * M * K * N`` operations and reads each
input once and writes its output once, at the dtypes the model stores
them (bf16 activations and weights).  Bitplanes, f32 copies of weights,
padding rows and masked cache positions are not required work.

``sizes`` holds the configuration's ``num_layers``, ``d_model``,
``num_heads``, ``num_kv_heads``, ``head_dim``, ``d_ff`` and
``vocab_size``.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

ACT_BYTES = 2       # bf16 activations
WEIGHT_BYTES = 2    # bf16 weights


def layer_matmuls(sizes: Dict) -> List[Tuple[str, int, int, int]]:
    """(name, K, N, weights) of each BP matmul call of one layer; the
    gated MLP's up and gate projections are one call of two weights."""
    d, h, kh, hd, f = (sizes["d_model"], sizes["num_heads"],
                       sizes["num_kv_heads"], sizes["head_dim"],
                       sizes["d_ff"])
    return [("wq", d, h * hd, 1), ("wk", d, kh * hd, 1),
            ("wv", d, kh * hd, 1), ("wo", h * hd, d, 1),
            ("mlp_up_gate", d, f, 2), ("mlp_down", f, d, 1)]


def matmul_work(m: int, k: int, n: int, weights: int = 1) -> Tuple[float, float]:
    """(operations, bytes) of ``weights`` matmuls sharing one (M, K) input."""
    ops = 2.0 * m * k * n * weights
    byts = (m * k * ACT_BYTES + weights * k * n * WEIGHT_BYTES
            + m * n * ACT_BYTES)
    return ops, float(byts)


def token_flops(sizes: Dict, context: int) -> float:
    """Model operations of one token at ``context`` attended positions:
    every matmul of every layer, attention's scores and p.v, the head."""
    d, v = sizes["d_model"], sizes["vocab_size"]
    per_layer = sum(2.0 * k * n * w for _, k, n, w in layer_matmuls(sizes))
    attn = 4.0 * context * sizes["num_heads"] * sizes["head_dim"]
    return sizes["num_layers"] * (per_layer + attn) + 2.0 * d * v
