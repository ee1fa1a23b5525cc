"""Attention core: chunked == direct, SWA masks, MLA absorbed == expanded."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.models import attention as A
from repro.models.params import empty_cache


def _qkv(rng, b=2, sq=64, skv=64, h=4, kh=2, d=16):
    q = jnp.asarray(rng.standard_normal((b, sq, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, skv, kh, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, skv, kh, d)), jnp.float32)
    return q, k, v


def test_chunked_matches_direct(rng):
    q, k, v = _qkv(rng)
    pos = jnp.broadcast_to(jnp.arange(64)[None], (2, 64))
    direct = A.sdpa(q, k, v, pos, pos, causal=True, chunk=1024)
    chunked = A.sdpa(q, k, v, pos, pos, causal=True, chunk=16)
    np.testing.assert_allclose(np.asarray(direct), np.asarray(chunked),
                               rtol=1e-4, atol=1e-4)


def test_chunked_matches_direct_windowed(rng):
    q, k, v = _qkv(rng)
    pos = jnp.broadcast_to(jnp.arange(64)[None], (2, 64))
    direct = A.sdpa(q, k, v, pos, pos, causal=True, window=8, chunk=1024)
    chunked = A.sdpa(q, k, v, pos, pos, causal=True, window=8, chunk=16)
    np.testing.assert_allclose(np.asarray(direct), np.asarray(chunked),
                               rtol=1e-4, atol=1e-4)


def test_causal_mask_blocks_future(rng):
    """Changing future tokens must not change past outputs."""
    q, k, v = _qkv(rng, sq=16, skv=16)
    pos = jnp.broadcast_to(jnp.arange(16)[None], (2, 16))
    out1 = A.sdpa(q, k, v, pos, pos, causal=True)
    k2 = k.at[:, 10:].set(99.0)
    v2 = v.at[:, 10:].set(99.0)
    out2 = A.sdpa(q, k2, v2, pos, pos, causal=True)
    np.testing.assert_allclose(np.asarray(out1[:, :10]),
                               np.asarray(out2[:, :10]), rtol=1e-5)
    assert np.abs(np.asarray(out1[:, 10:]) - np.asarray(out2[:, 10:])).max() > 0.1


def test_sliding_window_blocks_far_past(rng):
    q, k, v = _qkv(rng, sq=16, skv=16)
    pos = jnp.broadcast_to(jnp.arange(16)[None], (2, 16))
    out1 = A.sdpa(q, k, v, pos, pos, causal=True, window=4)
    k2 = k.at[:, :4].set(77.0)  # beyond the window of the last queries
    v2 = v.at[:, :4].set(77.0)
    out2 = A.sdpa(q, k2, v2, pos, pos, causal=True, window=4)
    np.testing.assert_allclose(np.asarray(out1[:, 12:]),
                               np.asarray(out2[:, 12:]), rtol=1e-5)


def test_prefix_lm_mask(rng):
    """With prefix_len=p, token 0 may attend token p-1 (bidirectional)."""
    q, k, v = _qkv(rng, sq=8, skv=8)
    pos = jnp.broadcast_to(jnp.arange(8)[None], (2, 8))
    prefix = jnp.full((2,), 4, jnp.int32)
    out1 = A.sdpa(q, k, v, pos, pos, causal=True, prefix_len=prefix)
    v2 = v.at[:, 3].set(50.0)  # inside prefix
    out2 = A.sdpa(q, k, v2, pos, pos, causal=True, prefix_len=prefix)
    # token 0 sees position 3 through the bidirectional prefix
    assert np.abs(np.asarray(out1[:, 0]) - np.asarray(out2[:, 0])).max() > 0.1


def _mla_cfg():
    return ModelConfig(
        name="t", family="decoder", num_layers=1, d_model=32, num_heads=4,
        num_kv_heads=4, head_dim=8, d_ff=64, vocab_size=128,
        attention_type="mla", q_lora_rank=16, kv_lora_rank=8,
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, attn_chunk=64)


def test_mla_absorbed_decode_matches_expanded(rng):
    """Decode (absorbed) must equal running prefill over the longer seq."""
    from repro.models.params import init_tree
    cfg = _mla_cfg()
    defs = A.mla_defs(cfg)
    params = init_tree(defs, jax.random.key(1))
    x = jnp.asarray(rng.standard_normal((2, 9, cfg.d_model)), jnp.float32)
    pos_full = jnp.broadcast_to(jnp.arange(9)[None], (2, 9))
    out_full, _ = A.mla_apply(params, cfg, x, pos_full)
    # prefill 8 tokens, then decode token 8
    spec = A.kv_cache_spec(cfg, 2, 9)
    cache = empty_cache(spec)
    _, cache = A.mla_apply(params, cfg, x[:, :8],
                           jnp.broadcast_to(jnp.arange(8)[None], (2, 8)),
                           cache=cache)
    out_dec, _ = A.mla_apply(params, cfg, x[:, 8:9],
                             jnp.full((2, 1), 8, jnp.int32), cache=cache)
    np.testing.assert_allclose(np.asarray(out_dec[:, 0]),
                               np.asarray(out_full[:, 8]),
                               rtol=2e-2, atol=2e-2)
