"""Operation and byte counts against values worked out by hand."""
import pytest

from work.decoder import layer_matmuls, matmul_work, token_flops

DANUBE = {"num_layers": 24, "d_model": 2560, "num_heads": 32,
          "num_kv_heads": 8, "head_dim": 80, "d_ff": 6912,
          "vocab_size": 32000}
TINY = {"num_layers": 2, "d_model": 64, "num_heads": 8, "num_kv_heads": 2,
        "head_dim": 8, "d_ff": 160, "vocab_size": 512}


def test_matmul_work_two_shapes():
    # (32, 2560) x (2560, 2560): 2*32*2560*2560 ops; bf16 x, w, out
    assert matmul_work(32, 2560, 2560) == (419430400.0,
                                          163840 + 13107200 + 163840)
    # the gated MLP's two weights on one input: (512, 2560) x 2 x (2560, 6912)
    ops, byts = matmul_work(512, 2560, 6912, weights=2)
    assert ops == 2 * 2 * 512 * 2560 * 6912 == 36238786560
    assert byts == 512 * 2560 * 2 + 2 * 2560 * 6912 * 2 + 512 * 6912 * 2


def test_layer_matmuls_cover_every_weight_once():
    shapes = layer_matmuls(DANUBE)
    params = sum(k * n * w for _, k, n, w in shapes)
    # wq + wo: 2 * 2560^2; wk + wv: 2 * 2560*640; up, gate, down: 3*2560*6912
    assert params == 2 * 2560 * 2560 + 2 * 2560 * 640 + 3 * 2560 * 6912


def test_token_flops_by_hand():
    # tiny: per layer 2*(64*64 + 2*64*16 + 64*64 + 3*64*160) = 2*(4096+2048
    # +4096+30720) = 81920; attention 4*ctx*8*8; head 2*64*512
    assert token_flops(TINY, 10) == 2 * (81920 + 4 * 10 * 64) + 65536
    per_layer = 2.0 * (2 * 2560 * 2560 + 2 * 2560 * 640 + 3 * 2560 * 6912)
    assert token_flops(DANUBE, 1) == pytest.approx(
        24 * (per_layer + 4 * 32 * 80) + 2 * 2560 * 32000)
