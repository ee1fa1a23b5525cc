"""DeepSeek-V2's block at a smoke size of its published mechanisms: latent
attention with YaRN, group-limited routing with unnormalised weights x16,
a share of the routed experts held, dispatch without dropped tokens.

The plain reference is the benchmark's (``bench/reference/mla_moe.py``,
float32 under HIGHEST precision, bf16 where the model stores bf16).
"""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build
from repro.models import moe as moe_mod
from repro.models.params import init_tree
from repro.serve.paged_engine import (PagedEngineConfig, PagedRequest,
                                      PagedServeEngine)

BENCH = Path(__file__).resolve().parents[1] / "bench"
if str(BENCH) not in sys.path:
    sys.path.append(str(BENCH))      # after the stdlib: bench has a trace.py

from reference import mla_moe  # noqa: E402
from reference.decoder import ROW_BLOCK, Plan  # noqa: E402

SMOKE = dict(num_layers=3, d_model=64, num_heads=4, num_kv_heads=4,
             head_dim=16, d_ff=128, vocab_size=256, q_lora_rank=32,
             kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=16, num_experts=16, num_experts_per_tok=3,
             num_shared_experts=1, moe_d_ff=32, first_dense_layers=1,
             moe_topk_method="group_limited_greedy", moe_n_group=4,
             moe_topk_group=2, moe_norm_topk_prob=False,
             moe_routed_scale=16.0, experts_held=4, experts_offset=4,
             rope_yarn_factor=40.0, rope_yarn_original_max_pos=64,
             rope_yarn_mscale=0.707, rope_yarn_mscale_all_dim=0.707,
             norm_eps=1e-6, tie_embeddings=False)
PROMPT, NEW = 13, 6          # prefill chunks of 8, 4 and 1, then decode
# Tolerances on logits (about 3 in size here), program against reference:
# the program stores bf16 where the reference rounds to bf16 too, but
# sums in other orders (absorbed decode and chunked prefill against the
# reference's expanded attention, grouped expert matmuls against one
# matmul an expert), so roundings part them by about 0.05 at most and
# 0.009 on average; each fault below reads above 0.7 at most and 0.09 on
# average.
MAX_GAP, MEAN_GAP = 0.2, 0.03


def smoke_config(**kw):
    return dataclasses.replace(get_config("deepseek_v2_236b", smoke=True),
                               **dict(SMOKE, **kw))


CFG = smoke_config()


@pytest.fixture(scope="module")
def params():
    return init_tree(build(CFG).schema(), jax.random.key(1))


def _serve(cfg, params):
    """One request through the paged engine: (its tokens, the logits of
    every model call that gave one of its tokens)."""
    eng = PagedServeEngine(build(cfg), params, cfg, PagedEngineConfig(
        slots=2, block_size=4, num_blocks=32, max_prefill_tokens=8,
        eos_id=-1))
    got = []
    for attr in ("_prefill_chunk", "_decode"):
        fn = getattr(eng, attr)

        def kept(*args, fn=fn, attr=attr):
            logits, view = fn(*args)
            got.append((attr, np.asarray(logits[0], np.float32)))
            return logits, view

        setattr(eng, attr, kept)
    prompt = np.random.default_rng(0).integers(3, 256, PROMPT).astype(np.int32)
    out = eng.run([PagedRequest(rid=0, prompt=prompt, max_new_tokens=NEW)])[0]
    logits = ([g for a, g in got if a == "_prefill_chunk"][-1:]
              + [g for a, g in got if a == "_decode"])
    return np.concatenate([prompt, out]), np.stack(logits)


def _reference_logits(params, seq, rows):
    """The reference's full forward pass over ``seq``: logits at ``rows``."""
    n = len(seq)
    pad = (-n) % ROW_BLOCK
    plan = Plan(np.pad(seq, (0, pad)).astype(np.int32),
                np.pad(np.arange(n), (0, pad)).astype(np.int32),
                np.zeros(n + pad, np.int32), 1, [(0, 0, n)], (n, pad),
                np.asarray(rows, np.int32), np.zeros(len(rows), np.int32),
                np.zeros(len(rows), np.int32))
    vocab = CFG.vocab_size
    out = mla_moe.replay(dataclasses.asdict(CFG), params, plan,
                         [np.full(len(rows), j, np.int32)
                          for j in range(vocab)])
    return out["at"]


def _gaps(cfg, params):
    seq, logits = _serve(cfg, params)
    rows = list(range(PROMPT - 1, PROMPT - 1 + len(logits)))
    ref = _reference_logits(params, seq[:-1], rows)
    diff = np.abs(logits - ref)
    return float(diff.max()), float(diff.mean())


def test_paged_prefill_then_decode_matches_reference(params):
    widest, mean = _gaps(CFG, params)
    assert widest <= MAX_GAP and mean <= MEAN_GAP


def _drop_at_capacity(monkeypatch):
    real = moe_mod.moe_apply

    def capacity_one(p, cfg, x, capacity=None, dropless=False):
        if "layer" in p:          # serving passes every layer's experts
            p = dict(p, **{w: p[w][p["layer"]]
                           for w in moe_mod.EXPERT_WEIGHTS})
        return real(p, cfg, x, capacity=1, dropless=False)

    monkeypatch.setattr(moe_mod, "moe_apply", capacity_one)
    return CFG


FAULTS = {
    "top-k renormalised": lambda mp: smoke_config(moe_norm_topk_prob=True),
    "x16 left out": lambda mp: smoke_config(moe_routed_scale=1.0),
    "token dropped at capacity": _drop_at_capacity,
    "YaRN mscale^2 left out": lambda mp: smoke_config(
        rope_yarn_mscale=0.0, rope_yarn_mscale_all_dim=0.0),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_each_fault_fails_the_comparison(fault, params, monkeypatch):
    widest, mean = _gaps(FAULTS[fault](monkeypatch), params)
    assert widest > MAX_GAP or mean > MEAN_GAP


def _moe_inputs(seed=0, tokens=24):
    x = jax.random.normal(jax.random.key(seed), (2, tokens // 2, CFG.d_model),
                          jnp.float32)
    full = dataclasses.replace(CFG, experts_held=0, experts_offset=0)
    p = init_tree(moe_mod.moe_defs(full), jax.random.key(seed + 1))
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    return full, p, x


def test_group_shares_add_up_to_the_uncut_layer():
    """Each group's share computes its own experts' part; with the shared
    experts counted once, the parts add up to the reference's whole layer
    (all experts routed and computed, in f32)."""
    full, p, x = _moe_inputs()
    per = full.num_experts // full.moe_n_group
    total = jnp.zeros(x.shape, jnp.float32)
    for g in range(full.moe_n_group):
        cfg = dataclasses.replace(full, experts_held=per, experts_offset=g * per)
        share = dict(p, **{k: p[k][g * per:(g + 1) * per]
                           for k in ("up", "gate", "down")})
        if g:                                   # the shared experts once
            share.update({k: jnp.zeros_like(p[k]) for k in p
                          if k.startswith("shared_")})
        total = total + moe_mod.moe_apply(share, cfg, x, dropless=True)["out"]
    xt = x.reshape(-1, x.shape[-1])
    routing = mla_moe._routing(dataclasses.asdict(full))
    w = mla_moe.route(xt, p["router"], routing)             # (T, E)
    hi = jax.lax.Precision.HIGHEST

    def ffn(up, gate, down):
        return jnp.dot(jax.nn.silu(jnp.dot(xt, gate, precision=hi))
                       * jnp.dot(xt, up, precision=hi), down, precision=hi)

    want = sum(w[:, e:e + 1] * ffn(p["up"][e], p["gate"][e], p["down"][e])
               for e in range(full.num_experts))
    want = want + ffn(p["shared_up"], p["shared_gate"], p["shared_down"])
    # f32 throughout: the parts and the whole differ by summation order
    np.testing.assert_allclose(np.asarray(total).reshape(xt.shape),
                               np.asarray(want), rtol=1e-4, atol=1e-4)


def test_group_limited_routing_keeps_to_the_best_groups():
    full, p, x = _moe_inputs(seed=3, tokens=256)
    xt = x.reshape(-1, x.shape[-1])
    gates, topw, topi = moe_mod.route(p["router"], full, xt)
    per = full.num_experts // full.moe_n_group
    best = np.asarray(gates).reshape(len(xt), full.moe_n_group, per).max(-1)
    kept = np.argsort(-best, axis=1)[:, :full.moe_topk_group]
    chosen = np.asarray(topi) // per
    assert all(set(c) <= set(k) for c, k in zip(chosen, kept))
    # the weights are the scores x16, not renormalised
    np.testing.assert_allclose(
        np.asarray(topw), 16.0 * np.take_along_axis(np.asarray(gates),
                                                    np.asarray(topi), 1),
        rtol=1e-6)


def test_a_rows_logits_do_not_depend_on_its_batch(params):
    """Dispatch drops nothing, so a row computes the same alone as among
    seven other rows."""
    model = build(CFG)
    rng = np.random.default_rng(5)
    batch = rng.integers(3, 256, (8, 12)).astype(np.int32)
    nxt = rng.integers(3, 256, (8, 1)).astype(np.int32)
    prefill, decode = jax.jit(model.prefill, static_argnums=2), \
        jax.jit(model.decode_step)
    out = []
    for rows in (1, 8):
        _, cache = prefill(params, {"tokens": jnp.asarray(batch[:rows])}, 16)
        cache.pop(moe_mod.ROUTES)
        logits, _ = decode(params, jnp.asarray(nxt[:rows]), cache,
                           jnp.full((rows,), 12, jnp.int32))
        out.append(np.asarray(logits[0]))
    # a dropped token moves logits by tenths; the CPU's f32 matmuls sum in
    # another order at another batch size, a few ulps
    np.testing.assert_allclose(out[0], out[1], rtol=0, atol=1e-5)
