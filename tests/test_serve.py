"""Serving engine: batched prefill/decode produces coherent streams."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build
from repro.models.params import init_tree
from repro.serve.paged_engine import (PagedEngineConfig, PagedRequest,
                                      PagedServeEngine)


def _engine(arch="h2o_danube_1p8b", key=0, **kw):
    cfg = get_config(arch, smoke=True)
    model = build(cfg)
    params = init_tree(model.schema(), jax.random.key(key))
    ecfg = dict(slots=2, block_size=8, num_blocks=16, max_prefill_tokens=8,
                temperature=0.0)
    ecfg.update(kw)
    return PagedServeEngine(model, params, cfg, PagedEngineConfig(**ecfg))


@pytest.fixture(scope="module")
def engine():
    return _engine()


def test_engine_serves_batch(engine):
    reqs = [PagedRequest(rid=i, prompt=np.arange(3 + i) % 50 + 3,
                         max_new_tokens=5) for i in range(5)]
    results = engine.run(reqs)
    assert set(results) == {0, 1, 2, 3, 4}
    for rid, toks in results.items():
        assert 1 <= len(toks) <= 5
        assert all(0 <= t < 512 for t in toks)


def test_engine_greedy_deterministic(engine):
    r1 = engine.run([PagedRequest(rid=0, prompt=np.array([5, 6, 7]),
                                  max_new_tokens=4)])
    r2 = engine.run([PagedRequest(rid=0, prompt=np.array([5, 6, 7]),
                                  max_new_tokens=4)])
    assert r1[0] == r2[0]


def test_engine_prompt_sensitivity(engine):
    """Two prompts give different logits at the first output step (the
    last prefill chunk's)."""
    seen = []
    orig = engine._prefill_chunk

    def keeping(*a):
        logits, view = orig(*a)
        seen.append(np.asarray(logits))
        return logits, view

    engine._prefill_chunk = keeping
    try:
        first = []
        for prompt in ([5, 6, 7], [40, 41, 42]):
            engine.run([PagedRequest(rid=0, prompt=np.array(prompt),
                                     max_new_tokens=1)])
            first.append(seen[-1])
    finally:
        engine._prefill_chunk = orig
    assert np.abs(first[0] - first[1]).max() > 1e-3


def test_engine_slot_refill_no_wave_barrier(engine):
    """A finished slot refills without waiting for the whole wave.

    slots=2 with one long and two short requests: a wave scheduler needs
    decode ticks after the long request's 11 for the third request
    (>= 12); slot refill serves it inside the long request's stream
    (<= 11)."""
    ticks = engine.stats.decode_ticks
    reqs = [PagedRequest(rid=0, prompt=np.array([3, 4, 5]), max_new_tokens=2),
            PagedRequest(rid=1, prompt=np.array([6, 7, 8]), max_new_tokens=12),
            PagedRequest(rid=2, prompt=np.array([9, 10, 11]),
                         max_new_tokens=2)]
    results = engine.run(reqs)
    assert set(results) == {0, 1, 2}
    for rid, toks in results.items():
        assert 1 <= len(toks) <= reqs[rid].max_new_tokens
    assert engine.stats.decode_ticks - ticks <= 11


def test_engine_rejects_oversized_prompt(engine):
    with pytest.raises(ValueError, match="exceeds the cache pool"):
        engine.run([PagedRequest(rid=0, prompt=np.arange(200) % 50 + 3,
                                 max_new_tokens=2)])


def test_temperature_sampling_bit_stable():
    """Counter-based sampling keyed on (seed, rid, step): two identical
    runs at temperature > 0 produce bit-identical streams (a shared rng
    consumed in slot order would not even be stable against a neighbour
    retiring)."""
    engine = _engine(temperature=0.8)
    mk = lambda: [PagedRequest(rid=i, prompt=np.arange(3 + i) % 50 + 3,
                               max_new_tokens=6) for i in range(4)]
    r1 = engine.run(mk(), seed=13)
    r2 = engine.run(mk(), seed=13)
    assert r1 == r2
    r3 = engine.run(mk(), seed=14)
    assert r3 != r1            # the seed actually reaches the sampler


def test_sample_row_is_a_pure_counter_function():
    """Same (seed, rid, step) -> same token; any coordinate change
    re-keys the draw."""
    from repro.serve.sampling import sample_row
    rng = np.random.default_rng(0)
    logits = rng.normal(size=256).astype(np.float32)
    base = sample_row(logits, seed=1, rid=2, step=3, temperature=1.0)
    assert base == sample_row(logits, seed=1, rid=2, step=3, temperature=1.0)
    varied = {sample_row(logits, seed=1, rid=2, step=s, temperature=1.0)
              for s in range(16)}
    assert len(varied) > 1         # steps draw independently
    assert sample_row(logits, seed=1, rid=2, step=3, temperature=0.0) \
        == int(np.argmax(logits))  # temperature 0 stays greedy


def test_engine_refill_other_families():
    """Slot refill is family-agnostic (SSM states, not just KV)."""
    eng = _engine("zamba2_2p7b", key=1)
    reqs = [PagedRequest(rid=i, prompt=np.arange(2 + i) % 50 + 3,
                         max_new_tokens=3) for i in range(4)]
    results = eng.run(reqs)
    assert set(results) == {0, 1, 2, 3}
    for toks in results.values():
        assert 1 <= len(toks) <= 3
    assert max(r.admitted_step for r in reqs) > 0   # refilled mid-stream
