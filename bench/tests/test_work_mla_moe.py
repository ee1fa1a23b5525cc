"""DeepSeek-V2 operation and byte counts against values worked out by
hand on a small configuration."""
import pytest

from work.mla_moe import (attention_flops, expert_weight_bytes,
                          routed_pair_bytes, routed_pair_flops, token_flops)

SMALL = {"num_layers": 3, "first_dense_layers": 1, "d_model": 8,
         "num_heads": 2, "q_lora_rank": 4, "kv_lora_rank": 4,
         "qk_nope_head_dim": 2, "qk_rope_head_dim": 2, "v_head_dim": 3,
         "d_ff": 16, "num_experts": 8, "moe_d_ff": 5,
         "num_shared_experts": 2, "vocab_size": 10}


def test_attention_absorbed_in_decode_and_expanded_in_prefill():
    # decode: 2*H*(nope*r + c*(r + rope) + c*r + r*vd) = 2*2*(8 + 6c + 4c + 12)
    assert attention_flops(SMALL, 5, decode=True) == 4 * (8 + 30 + 20 + 12)
    # prefill: 2*H*(r*(nope + vd) + c*(nope + rope) + c*vd) = 4*(20 + 4c + 3c)
    assert attention_flops(SMALL, 5, decode=False) == 4 * (20 + 20 + 15)


def test_token_flops_by_hand():
    # projections: q 8*4 + 4*2*4 = 64, kv 8*6 = 48, wo 2*3*8 = 48 -> 2*160
    per_layer = 2 * 160 + attention_flops(SMALL, 5, decode=True)
    dense_mlp = 3 * 2 * 8 * 16
    moe_fixed = 2 * 8 * 8 + 3 * 2 * 8 * 10     # router, 2 shared experts
    head = 2 * 8 * 10
    assert token_flops(SMALL, 5, decode=True) == pytest.approx(
        3 * per_layer + dense_mlp + 2 * moe_fixed + head)


def test_routed_pair_work():
    assert routed_pair_flops(SMALL) == 3 * 2 * 8 * 5
    assert expert_weight_bytes(SMALL) == 3 * 8 * 5 * 2
    # row in (8), up and gate out (2*5), hidden in (5), row out (8); bf16
    assert routed_pair_bytes(SMALL) == (8 + 10 + 5 + 8) * 2
