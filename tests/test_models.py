"""Per-architecture smoke tests (reduced configs) + consistency checks."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import ShapeConfig
from repro.launch.inputs import demo_batch
from repro.models import build
from repro.models.params import init_tree

TRAIN = ShapeConfig("t", "train", 64, 2)
PREFILL = ShapeConfig("p", "prefill", 64, 2)


@pytest.fixture(scope="module")
def built():
    out = {}
    for arch in ARCH_IDS:
        cfg = get_config(arch, smoke=True)
        model = build(cfg)
        params = init_tree(model.schema(), jax.random.key(0))
        out[arch] = (cfg, model, params)
    return out


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_smoke(arch, built):
    cfg, model, params = built[arch]
    batch = demo_batch(cfg, TRAIN)
    loss, metrics = jax.jit(model.loss)(params, batch)
    assert loss.shape == ()
    assert jnp.isfinite(loss)
    g = jax.grad(lambda p: model.loss(p, batch)[0])(params)
    for leaf in jax.tree.leaves(g):
        assert jnp.isfinite(leaf).all()


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode_shapes(arch, built):
    cfg, model, params = built[arch]
    pb = demo_batch(cfg, PREFILL)
    logits, cache = jax.jit(model.prefill, static_argnums=2)(params, pb, 64)
    assert logits.shape == (2, cfg.vocab_size)
    assert jnp.isfinite(logits).all()
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    logits2, cache2 = jax.jit(model.decode_step)(params, tok, cache,
                                                 jnp.int32(64))
    assert logits2.shape == (2, cfg.vocab_size)
    assert jnp.isfinite(logits2).all()
    # cache structure preserved
    assert jax.tree.structure(cache) == jax.tree.structure(cache2)


@pytest.mark.parametrize("arch", ["h2o_danube_1p8b", "minicpm3_4b",
                                  "zamba2_2p7b", "xlstm_1p3b",
                                  "whisper_base"])
def test_decode_matches_prefill(arch, built):
    """KV-cache/state decode must reproduce fresh-prefill logits."""
    cfg, model, params = built[arch]
    pb = demo_batch(cfg, PREFILL, seed=3)
    toks = pb["tokens"]
    t0 = 32
    pb_short = dict(pb, tokens=toks[:, :t0])
    prefill = jax.jit(model.prefill, static_argnums=2)
    logits, cache = prefill(params, pb_short, 64)
    decode = jax.jit(model.decode_step)
    # MLA's absorbed decode evaluates the same math in a different float
    # summation order than expanded prefill; small divergence is amplified
    # through the layer stack, so it gets a looser numeric bar (argmax must
    # still agree — the serving-relevant criterion).
    atol = 0.15 if cfg.attention_type == "mla" else 2e-2
    for i in range(3):
        nxt = toks[:, t0 + i: t0 + i + 1]
        got, cache = decode(params, nxt, cache, jnp.int32(t0 + i))
        pb_ref = dict(pb, tokens=toks[:, : t0 + i + 1])
        want, _ = prefill(params, pb_ref, 64)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=5e-2, atol=atol)
        assert (np.argmax(np.asarray(got), -1)
                == np.argmax(np.asarray(want), -1)).all()


@pytest.mark.parametrize("arch, scopes", [
    ("h2o_danube_1p8b", {"dense.bf16", "attention", "kv_write", "head"}),
    ("minicpm3_4b", {"dense.bf16", "attention", "kv_write", "head"}),
    ("granite_moe_1b", {"moe", "attention", "kv_write"}),
    ("zamba2_2p7b", {"ssm", "head"}),
    ("xlstm_1p3b", {"ssm", "head"}),
])
def test_decode_step_carries_name_scopes(arch, scopes, built):
    """The compiled ``decode_step`` names its ops by model layer: the
    ``op_name`` metadata of its HLO holds each scope."""
    import re

    cfg, model, params = built[arch]
    b = 2
    cache = model.cache_spec(b, 16)
    cache = jax.tree.map(lambda sp: jnp.zeros(sp.shape, sp.dtype), cache)
    text = jax.jit(model.decode_step).lower(
        params, jnp.zeros((b, 1), jnp.int32), cache,
        jnp.zeros((b,), jnp.int32)).compile().as_text()
    found = {part for name in re.findall(r'op_name="([^"]*)"', text)
             for part in name.split("/")}
    assert scopes <= found, scopes - found


@pytest.mark.parametrize("arch", ["gemma3_12b"])
def test_local_global_pattern(arch, built):
    from repro.models.model import _layer_windows, BIG_WINDOW
    cfg, _, _ = built[arch]
    w = _layer_windows(cfg)
    per = cfg.local_global_pattern + 1
    assert (w[per - 1 :: per] == BIG_WINDOW).all()
    assert (w[: per - 1] == cfg.window_size).all()


def test_matmul_modes_agree_roughly(built):
    """bp8 mode output should correlate with bf16 output (quantised)."""
    cfg, model, params = built["h2o_danube_1p8b"]
    cfg_bp = dataclasses.replace(cfg, matmul_mode="bp8")
    model_bp = build(cfg_bp)
    batch = demo_batch(cfg, TRAIN, seed=5)
    l_bf, _ = jax.jit(model.loss)(params, batch)
    l_bp, _ = jax.jit(model_bp.loss)(params, batch)
    assert jnp.isfinite(l_bp)
    # the BP8-simulated model is a coarse approximation, not garbage
    assert float(l_bp) < float(l_bf) * 3 + 10


def test_paligemma_prefix_attention(built):
    """Suffix tokens must be able to attend to the (bidirectional) prefix."""
    cfg, model, params = built["paligemma_3b"]
    batch = demo_batch(cfg, TRAIN, seed=7)
    p1 = batch["patches"]
    loss1, _ = jax.jit(model.loss)(params, batch)
    batch2 = dict(batch, patches=p1 + 1.0)
    loss2, _ = jax.jit(model.loss)(params, batch2)
    assert abs(float(loss1) - float(loss2)) > 1e-6  # prefix affects loss


def test_ring_cache_decode(built):
    """Ring-buffer SWA cache (window slots only) must reproduce the
    full-length-cache decode logits exactly — the long_500k memory
    optimisation (EXPERIMENTS.md §Perf E)."""
    cfg, model, params = built["h2o_danube_1p8b"]  # uniform SWA window 16
    cfg_ring = dataclasses.replace(cfg, ring_cache=True)
    model_ring = build(cfg_ring)
    pb = demo_batch(cfg, PREFILL, seed=11)
    prefill = jax.jit(model.prefill, static_argnums=2)
    prefill_r = jax.jit(model_ring.prefill, static_argnums=2)
    lf, cache_full = prefill(params, pb, 64)          # cache len 64
    lr, cache_ring = prefill_r(params, pb, 64)        # cache len 16 (window)
    assert cache_ring["layers"]["k"].shape[2] == cfg.window_size
    np.testing.assert_allclose(np.asarray(lr, np.float32),
                               np.asarray(lf, np.float32), rtol=2e-2,
                               atol=2e-2)
    decode = jax.jit(model.decode_step)
    decode_r = jax.jit(model_ring.decode_step)
    tok = jnp.argmax(lf, -1)[:, None].astype(jnp.int32)
    for i in range(3):  # decode past the prefill, wrapping the ring
        gf, cache_full = decode(params, tok, cache_full, jnp.int32(64 + i))
        gr, cache_ring = decode_r(params, tok, cache_ring, jnp.int32(64 + i))
        np.testing.assert_allclose(np.asarray(gr, np.float32),
                                   np.asarray(gf, np.float32), rtol=2e-2,
                                   atol=2e-2)
        tok = jnp.argmax(gf, -1)[:, None].astype(jnp.int32)
        assert (jnp.argmax(gr, -1) == jnp.argmax(gf, -1)).all()
