"""BP-quantised KV cache (``kv_quant='bp8'``) through the model stack.

The cache stores int8 sign*level codes plus one f32 scale per
(token, kv-head).  Every leaf keeps "batch" at the same index and
"kv_seq" right after it, so the paged block pool handles the quantised
cache with zero engine changes — which the paged engine's token
equality with the reference below demonstrates end to end (decode runs
the fused ``bp8_decode_attention`` kernel over gathered block views).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ShapeConfig, get_config
from repro.launch.inputs import demo_batch
from repro.models import attention as attn
from repro.models import build
from repro.models.params import init_tree
from repro.serve.paged_engine import (PagedEngineConfig, PagedRequest,
                                      PagedServeEngine)

from _reference import serve_alone


def _cfg(name="h2o_danube_1p8b", **kw):
    return dataclasses.replace(get_config(name, smoke=True), **kw)


# ---------------------------------------------------------------------------
# cache spec + axes
# ---------------------------------------------------------------------------

def test_quantized_cache_spec_leaves():
    cfg = _cfg(kv_quant="bp8")
    spec = attn.kv_cache_spec(cfg, batch=2, length=16)
    kh, d = cfg.num_kv_heads, cfg.head_dim
    assert spec["k_codes"].shape == (2, 16, kh, d)
    assert spec["k_codes"].dtype == jnp.int8
    assert spec["k_scale"].shape == (2, 16, kh)
    assert spec["k_scale"].dtype == jnp.float32
    assert spec["v_codes"].dtype == jnp.int8
    assert spec["v_scale"].dtype == jnp.float32
    assert spec["pos"].dtype == jnp.int32
    # bytes at the REAL head_dim: int8 codes + one f32 scale per
    # (token, head) vs bf16 — (2d+8)/(4d), i.e. ~0.53x at d=64
    full = dataclasses.replace(get_config("h2o_danube_1p8b"), kv_quant="bp8")
    spec_q = attn.kv_cache_spec(full, 2, 16)
    spec_b = attn.kv_cache_spec(get_config("h2o_danube_1p8b"), 2, 16)
    q_bytes = sum(np.prod(v.shape) * v.dtype.itemsize
                  for v in spec_q.values())
    b_bytes = sum(np.prod(v.shape) * v.dtype.itemsize
                  for v in spec_b.values())
    assert q_bytes < 0.6 * b_bytes


def test_quantized_cache_axes_pageable():
    """Paged block pool contract: "batch" at a fixed index with "kv_seq"
    immediately after, on EVERY leaf (codes and scales alike)."""
    cfg = _cfg(kv_quant="bp8")
    axes = attn.kv_cache_axes(cfg)
    spec = attn.kv_cache_spec(cfg, 2, 16)
    assert set(axes) == set(spec)
    for name, ax in axes.items():
        i = ax.index("batch")
        assert ax[i + 1] == "kv_seq", (name, ax)
        assert len(ax) == len(spec[name].shape) + 1  # +1 for "stack" prefix


def test_kv_quant_rejected_for_mla():
    cfg = _cfg("minicpm3_4b", kv_quant="bp8")
    with pytest.raises(ValueError, match="MLA"):
        attn.kv_cache_spec(cfg, 1, 8)


def test_kv_quant_unknown_rejected():
    cfg = _cfg(kv_quant="int4")
    with pytest.raises(ValueError, match="unknown kv_quant"):
        attn.kv_cache_spec(cfg, 1, 8)


# ---------------------------------------------------------------------------
# serving equivalence: the reference served alone vs paged, both on bp8 KV
# ---------------------------------------------------------------------------

def _prompts(seed, n, lo, hi, vocab):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, vocab, size=int(rng.integers(lo, hi + 1))
                         ).astype(np.int32) for _ in range(n)]


def test_paged_bp8_kv_matches_contiguous():
    """The paged engine decodes through the fused bp8 attention kernel
    over gathered block views; the reference serves each request alone
    over a contiguous cache, quantised the same way.  Greedy streams must
    match token for token."""
    cfg = _cfg(kv_quant="bp8")
    model = build(cfg)
    params = init_tree(model.schema(), jax.random.key(0))
    prompts = _prompts(0, 4, 3, 14, cfg.vocab_size)
    ref = serve_alone(model, params, cfg, prompts, max_new=5)
    paged = PagedServeEngine(model, params, cfg,
                             PagedEngineConfig(slots=2, block_size=8,
                                               num_blocks=32,
                                               max_prefill_tokens=8))
    got = paged.run([PagedRequest(rid=i, prompt=p, max_new_tokens=5)
                     for i, p in enumerate(prompts)])
    assert got == ref


def test_bp8_kv_decode_close_to_bf16_kv():
    """Quantising the cache perturbs logits by the KV round-trip error
    only — greedy continuations of a tiny random model stay identical or
    near-identical to the bf16 cache's (sanity that the quantised path
    computes attention, not noise)."""
    cfg = _cfg()
    model = build(cfg)
    params = init_tree(model.schema(), jax.random.key(0))
    prompts = _prompts(1, 3, 4, 12, cfg.vocab_size)

    def run(c):
        return serve_alone(build(c), params, c, prompts, max_new=4)

    bf16 = run(cfg)
    bp8 = run(_cfg(kv_quant="bp8"))
    agree = sum(bf16[i] == bp8[i] for i in bf16)
    assert agree >= 2, (bf16, bp8)


# ---------------------------------------------------------------------------
# fused matmul/MLP as a training mode
# ---------------------------------------------------------------------------

def test_bp8_fused_mode_trains():
    """matmul_mode='bp8_fused' routes dense through the fused Pallas
    matmul and the gated MLP through the fused MLP kernel (both STE):
    the loss is finite and every gradient leaf is finite."""
    cfg = _cfg(matmul_mode="bp8_fused")
    model = build(cfg)
    params = init_tree(model.schema(), jax.random.key(0))
    batch = demo_batch(cfg, ShapeConfig("t", "train", 32, 2))
    (loss, _), grads = jax.value_and_grad(
        lambda p: model.loss(p, batch), has_aux=True)(params)
    assert bool(jnp.isfinite(loss))
    leaves = jax.tree_util.tree_leaves(grads)
    assert leaves and all(bool(jnp.all(jnp.isfinite(g))) for g in leaves)


def test_bp8_is_bit_identical_to_bp8_fused():
    """matmul_mode='bp8' (the jnp bitplane formulation) is the reference
    of 'bp8_fused': the same rescale association and the same f32 gated
    MLP, so the loss and the paged engine's greedy tokens match exactly."""
    losses, tokens = {}, {}
    prompts = _prompts(2, 3, 5, 20, 512)
    for mode in ("bp8", "bp8_fused"):
        cfg = _cfg(matmul_mode=mode, kv_quant="bp8")
        model = build(cfg)
        params = init_tree(model.schema(), jax.random.key(0))
        batch = demo_batch(cfg, ShapeConfig("t", "train", 32, 2))
        losses[mode] = float(model.loss(params, batch)[0])
        engine = PagedServeEngine(model, params, cfg,
                                  PagedEngineConfig(slots=2, block_size=8,
                                                    num_blocks=16,
                                                    max_prefill_tokens=8))
        tokens[mode] = engine.run([PagedRequest(rid=i, prompt=p,
                                                max_new_tokens=4)
                                   for i, p in enumerate(prompts)])
    assert losses["bp8"] == losses["bp8_fused"]
    assert tokens["bp8"] == tokens["bp8_fused"]
