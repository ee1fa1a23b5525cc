"""Roofline machinery: collective parsing, term math, FLOP-formula
validation against XLA's exact per-layer cost analysis."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.roofline import hw
from repro.roofline.analysis import (RooflineTerms, collective_bytes,
                                     model_flops_estimate)
from repro.roofline.model import (MeshSpec, analytic_cell, cell_flops,
                                  fwd_flops_per_layer_tok)

HLO_SAMPLE = """
  %ar = f32[128,256] all-reduce(f32[128,256] %x), replica_groups=[16,32]<=[512]
  %ag.1 = bf16[64,64]{1,0} all-gather(bf16[32,64] %y), replica_groups={{0,1}}
  %cp = f32[16] collective-permute(f32[16] %z), source_target_pairs={{0,1}}
"""


def test_collective_parser():
    out = collective_bytes(HLO_SAMPLE, default_group=4)
    # all-reduce: 128*256*4 bytes * 2*(32-1)/32
    assert out["all-reduce"] == pytest.approx(128 * 256 * 4 * 2 * 31 / 32)
    assert out["all-gather"] == pytest.approx(64 * 64 * 2 * 0.5)
    assert out["collective-permute"] == pytest.approx(16 * 4)
    assert out["_count"] == 3


def test_roofline_terms_math():
    t = RooflineTerms(flops=1e15, hbm_bytes=1e12, coll_bytes_per_chip=1e9,
                      chips=256, model_flops=5e14)
    assert t.t_compute == pytest.approx(1e15 / (256 * hw.PEAK_FLOPS_BF16))
    assert t.t_memory == pytest.approx(1e12 / (256 * hw.HBM_BW))
    assert t.t_collective == pytest.approx(1e9 / hw.ICI_BW_PER_LINK)
    assert t.bottleneck == "collective"
    assert 0 < t.roofline_fraction < 1


def _layer_flops_xla(cfg, batch, seq):
    """Exact XLA count for ONE decoder layer (no scan -> no undercount)."""
    from repro.models.attention import gqa_defs
    from repro.models.model import _decoder_layer_apply
    from repro.models.model import _decoder_layer_defs
    from repro.models.params import abstract_tree
    defs = _decoder_layer_defs(cfg, cfg.num_experts > 0)
    aparams = abstract_tree(defs)
    x = jax.ShapeDtypeStruct((batch, seq, cfg.d_model), jnp.bfloat16)

    def f(p, x):
        out, _, _, _ = _decoder_layer_apply(
            p, cfg, x, jnp.arange(seq), window=seq + 1)
        return out

    low = jax.jit(f).lower(aparams, x)
    return float(low.cost_analysis()["flops"])


@pytest.mark.parametrize("arch", ["h2o_danube_1p8b", "qwen2_72b"])
def test_formula_matches_xla_per_layer(arch):
    """Analytic per-layer FLOPs within 20% of XLA's exact count (XLA adds
    softmax/norm elementwise flops the formula ignores)."""
    cfg = get_config(arch, smoke=True)
    b, s = 2, 64
    got = _layer_flops_xla(cfg, b, s)
    # formula with the average causal kv_len
    want = b * s * fwd_flops_per_layer_tok(cfg, 0, (s + 1) / 2)
    assert got == pytest.approx(want, rel=0.25), (got, want)


def test_cell_flops_monotonicity():
    cfg = get_config("h2o_danube_1p8b")
    tr = ShapeConfig("t", "train", 4096, 256)
    pf = ShapeConfig("p", "prefill", 4096, 256)
    de = ShapeConfig("d", "decode", 4096, 256)
    f_tr = cell_flops(cfg, tr)["total"]
    f_pf = cell_flops(cfg, pf)["total"]
    f_de = cell_flops(cfg, de)["total"]
    assert f_tr == pytest.approx(4 * f_pf)        # fwd+bwd+remat = 4x fwd
    assert f_de < f_pf / 1000                     # one token vs whole seq


def test_moe_active_params():
    cfg = get_config("deepseek_v2_236b")
    tr = ShapeConfig("t", "train", 4096, 256)
    mf = model_flops_estimate(cfg, tr)
    # 6 * N_active * D with N_active ~ 21B
    n_active = mf / (6 * 4096 * 256)
    assert 15e9 < n_active < 30e9, n_active


def test_analytic_cell_terms_positive():
    cfg = get_config("gemma3_12b")
    tr = ShapeConfig("t", "train", 4096, 256)
    out = analytic_cell(cfg, tr, MeshSpec(1, 16, 16), accum=4)
    t = out["terms"]
    assert t.t_compute > 0 and t.t_memory > 0 and t.t_collective > 0
    assert 0 < t.roofline_fraction < 1
    assert 0 < t.useful_flops_fraction <= 1
