"""Per-architecture smoke tests (reduced configs) + consistency checks."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import ShapeConfig
from repro.launch.inputs import demo_batch
from repro.models import build
from repro.models.layers import embed_lookup
from repro.models.model import _tied_head
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
    # room for the 64 prompt tokens and the one decoded after them
    logits, cache = jax.jit(model.prefill, static_argnums=2)(params, pb, 65)
    assert logits.shape == (2, cfg.vocab_size)
    assert jnp.isfinite(logits).all()
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    pos = jnp.full((2,), 64 + cfg.num_prefix_tokens, jnp.int32)
    logits2, cache2 = jax.jit(model.decode_step)(params, tok, cache, pos)
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
        got, cache = decode(params, nxt, cache,
                            jnp.full((2,), t0 + i, jnp.int32))
        pb_ref = dict(pb, tokens=toks[:, : t0 + i + 1])
        want, _ = prefill(params, pb_ref, 64)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=5e-2, atol=atol)
        assert (np.argmax(np.asarray(got), -1)
                == np.argmax(np.asarray(want), -1)).all()


def _train_logits(model, params, batch):
    """Last-position logits of the training forward (no cache)."""
    cfg, tokens = model.cfg, batch["tokens"]
    b, s = tokens.shape
    if cfg.family == "decoder":     # prefix tokens ahead of the text
        x = model._embed_in(params, batch)
        positions = jnp.broadcast_to(jnp.arange(x.shape[1])[None],
                                     x.shape[:2])
        h, _, _ = model._stack(params, x, positions, None, model._prefix(b))
        return model._logits(params, h[:, -1:])[:, 0]
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    x = embed_lookup(params["embed"], tokens)
    if cfg.family == "encdec":
        x = x + params["pos_embed"][None, :s]
        h, _, _ = model._decode_stack(params, x, positions,
                                      model.encode(params, batch["frames"]),
                                      None)
    elif cfg.family == "hybrid":
        h, _ = model._forward(params, x, positions, None)
    else:
        h, _ = model._forward(params, x, None)
    return _tied_head(h[:, -1], params["embed"])


@pytest.mark.parametrize("arch", ["h2o_danube_1p8b", "minicpm3_4b",
                                  "zamba2_2p7b", "xlstm_1p3b",
                                  "whisper_base", "paligemma_3b"])
def test_prefill_matches_train_forward(arch, built):
    """``prefill`` is the chunk path at position 0 into a fresh cache: its
    last-position logits are the training forward's at that position
    (GQA windowed, MLA, hybrid, xLSTM, encoder-decoder, and prefix
    tokens through the chunk path)."""
    cfg, model, params = built[arch]
    pb = demo_batch(cfg, PREFILL, seed=3)
    pb = dict(pb, tokens=pb["tokens"][:, :40])
    got, _ = jax.jit(model.prefill, static_argnums=2)(params, pb, 64)
    want = jax.jit(functools.partial(_train_logits, model))(params, pb)
    # the tolerances of test_decode_matches_prefill: MLA's chunk path
    # expands K/V from the bf16 latents it caches, training from f32
    atol = 0.15 if cfg.attention_type == "mla" else 2e-2
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
