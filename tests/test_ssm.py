"""SSM blocks: chunked-parallel forms must match step-by-step recurrence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.models import ssm
from repro.models.params import init_tree


def _mamba_cfg():
    return ModelConfig(name="t", family="hybrid", num_layers=1, d_model=32,
                       num_heads=4, num_kv_heads=4, head_dim=8, d_ff=64,
                       vocab_size=128, ssm_state=8, ssm_headdim=8,
                       ssm_expand=2, ssm_conv=4, attn_every=1, lora_rank=4)


def test_mamba2_prefill_then_decode_matches_full(rng):
    cfg = _mamba_cfg()
    params = init_tree(ssm.mamba2_defs(cfg), jax.random.key(0))
    x = jnp.asarray(rng.standard_normal((2, 12, cfg.d_model)) * 0.3,
                    jnp.float32)
    full, _ = ssm.mamba2_apply(params, cfg, x, state=None, chunk=4)
    # prefill the first 8, then decode 9..12 recurrently
    state = {k: jnp.zeros(v.shape, v.dtype)
             for k, v in ssm.mamba2_state_spec(cfg, 2).items()}
    out_pre, state = ssm.mamba2_apply(params, cfg, x[:, :8], state=state,
                                      chunk=4)
    np.testing.assert_allclose(np.asarray(out_pre), np.asarray(full[:, :8]),
                               rtol=2e-3, atol=2e-3)
    for t in range(8, 12):
        out_t, state = ssm.mamba2_apply(params, cfg, x[:, t:t + 1],
                                        state=state)
        np.testing.assert_allclose(np.asarray(out_t[:, 0]),
                                   np.asarray(full[:, t]),
                                   rtol=5e-3, atol=5e-3)


def test_ssd_chunk_invariance(rng):
    """Different chunk sizes must give the same outputs."""
    cfg = _mamba_cfg()
    params = init_tree(ssm.mamba2_defs(cfg), jax.random.key(0))
    x = jnp.asarray(rng.standard_normal((1, 16, cfg.d_model)) * 0.3,
                    jnp.float32)
    a, _ = ssm.mamba2_apply(params, cfg, x, state=None, chunk=4)
    b, _ = ssm.mamba2_apply(params, cfg, x, state=None, chunk=16)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                               atol=2e-3)


def _xlstm_cfg():
    return ModelConfig(name="t", family="xlstm", num_layers=2, d_model=32,
                       num_heads=4, num_kv_heads=4, head_dim=8, d_ff=0,
                       vocab_size=128, slstm_every=2)


def test_mlstm_chunked_matches_recurrent(rng):
    cfg = _xlstm_cfg()
    params = init_tree(ssm.mlstm_defs(cfg), jax.random.key(0))
    x = jnp.asarray(rng.standard_normal((2, 12, cfg.d_model)) * 0.5,
                    jnp.float32)
    full, _ = ssm.mlstm_apply(params, cfg, x, state=None, chunk=4)
    state = {k: jnp.zeros(v.shape, v.dtype) if k != "m"
             else jnp.full(v.shape, -1e30, v.dtype)
             for k, v in ssm.mlstm_state_spec(cfg, 2).items()}
    for t in range(12):
        out_t, state = ssm.mlstm_apply(params, cfg, x[:, t:t + 1],
                                       state=state)
        np.testing.assert_allclose(np.asarray(out_t[:, 0]),
                                   np.asarray(full[:, t]),
                                   rtol=5e-3, atol=5e-3)


def test_mlstm_chunk_invariance(rng):
    cfg = _xlstm_cfg()
    params = init_tree(ssm.mlstm_defs(cfg), jax.random.key(0))
    x = jnp.asarray(rng.standard_normal((1, 16, cfg.d_model)) * 0.5,
                    jnp.float32)
    a, _ = ssm.mlstm_apply(params, cfg, x, state=None, chunk=4)
    b, _ = ssm.mlstm_apply(params, cfg, x, state=None, chunk=16)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-3,
                               atol=5e-3)


def test_slstm_decode_matches_full(rng):
    cfg = _xlstm_cfg()
    params = init_tree(ssm.slstm_defs(cfg), jax.random.key(0))
    x = jnp.asarray(rng.standard_normal((2, 10, cfg.d_model)) * 0.5,
                    jnp.float32)
    full, _ = ssm.slstm_apply(params, cfg, x, state=None)
    state = {k: jnp.zeros(v.shape, v.dtype)          # the empty state
             for k, v in ssm.slstm_state_spec(cfg, 2).items()}
    for t in range(10):
        out_t, state = ssm.slstm_apply(params, cfg, x[:, t:t + 1],
                                       state=state)
        np.testing.assert_allclose(np.asarray(out_t[:, 0]),
                                   np.asarray(full[:, t]),
                                   rtol=5e-3, atol=5e-3)
