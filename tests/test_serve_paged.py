"""Paged serving engine: token-equivalence with the engine-free
reference, bounded retrace, admission control, pool-reuse hygiene.

The reference (``_reference.serve_alone``) serves each request alone
with the model's ``prefill`` and ``decode_step`` over a contiguous
cache: its stream is the model's true greedy/sampled continuation.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build
from repro.models.params import init_tree
from repro.serve.paged_engine import (PagedEngineConfig, PagedRequest,
                                      PagedServeEngine)

from _reference import serve_alone

FAMILIES = ["h2o_danube_1p8b", "whisper_base", "zamba2_2p7b"]


@pytest.fixture(scope="module", params=FAMILIES)
def stack(request):
    cfg = get_config(request.param, smoke=True)
    model = build(cfg)
    params = init_tree(model.schema(), jax.random.key(0))
    return cfg, model, params


def _prompts(seed, n, lo, hi, vocab):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, vocab, size=int(rng.integers(lo, hi + 1))
                         ).astype(np.int32) for _ in range(n)]


def _paged(cfg, model, params, **kw):
    defaults = dict(slots=2, block_size=8, num_blocks=32,
                    max_prefill_tokens=8)
    defaults.update(kw)
    return PagedServeEngine(model, params, cfg,
                            PagedEngineConfig(**defaults))


def test_paged_matches_contiguous_greedy(stack):
    """Heterogeneous paged batch == the reference served alone, tokenwise —
    with more requests than slots, so admission happens mid-stream."""
    cfg, model, params = stack
    prompts = _prompts(0, 5, 3, 20, cfg.vocab_size)
    ref = serve_alone(model, params, cfg, prompts, max_new=6)
    eng = _paged(cfg, model, params, slots=2)
    reqs = [PagedRequest(rid=i, prompt=p, max_new_tokens=6)
            for i, p in enumerate(prompts)]
    got = eng.run(reqs)
    assert got == ref
    # 5 requests through 2 slots: at least one admission happened after
    # the engine had already started stepping (a true mid-stream refill)
    assert eng.stats.decode_ticks > 0
    assert max(r.admitted_step for r in reqs) > 0


def test_paged_pool_reuse_is_scrubbed(stack):
    """Blocks freed by batch A and reused by batch B carry no residue:
    a warm engine's second batch matches a fresh engine's."""
    cfg, model, params = stack
    a = _prompts(1, 4, 3, 16, cfg.vocab_size)
    b = _prompts(2, 4, 3, 16, cfg.vocab_size)
    warm = _paged(cfg, model, params)
    warm.run([PagedRequest(rid=i, prompt=p, max_new_tokens=5)
              for i, p in enumerate(a)])
    second = warm.run([PagedRequest(rid=10 + i, prompt=p, max_new_tokens=5)
                       for i, p in enumerate(b)])
    fresh = _paged(cfg, model, params).run(
        [PagedRequest(rid=10 + i, prompt=p, max_new_tokens=5)
         for i, p in enumerate(b)])
    assert second == fresh
    assert warm.cache.free_blocks == warm.cache.allocator.num_blocks - 1


def test_paged_temperature_matches_contiguous():
    """Counter-based sampling keyed on (seed, rid, step): the sampled
    stream is the reference's, bit for bit."""
    cfg = get_config("h2o_danube_1p8b", smoke=True)
    model = build(cfg)
    params = init_tree(model.schema(), jax.random.key(0))
    prompts = _prompts(3, 4, 3, 14, cfg.vocab_size)
    ref = serve_alone(model, params, cfg, prompts, max_new=6,
                      temperature=0.8, seed=7)
    eng = _paged(cfg, model, params, slots=3, temperature=0.8)
    got = eng.run([PagedRequest(rid=i, prompt=p, max_new_tokens=6)
                   for i, p in enumerate(prompts)], seed=7)
    assert got == ref


def test_paged_batch_composition_independence():
    """A request's sampled stream does not depend on which neighbours
    share its decode batch (slots=2 vs slots=4, temperature > 0)."""
    cfg = get_config("h2o_danube_1p8b", smoke=True)
    model = build(cfg)
    params = init_tree(model.schema(), jax.random.key(0))
    prompts = _prompts(4, 5, 3, 14, cfg.vocab_size)
    reqs = lambda: [PagedRequest(rid=i, prompt=p, max_new_tokens=5)
                    for i, p in enumerate(prompts)]
    narrow = _paged(cfg, model, params, slots=2,
                    temperature=0.8).run(reqs(), seed=11)
    wide = _paged(cfg, model, params, slots=4,
                  temperature=0.8).run(reqs(), seed=11)
    assert narrow == wide


def test_paged_retrace_bound():
    """Bucketed prefill compiles O(log max_len) shapes where the seed
    engine compiled one per refill length: chunk sizes are powers of two
    capped by ``max_prefill_tokens`` and view lengths are power-of-two
    block counts, so 30 distinct prompt lengths must fit in
    (log2(max_prefill_tokens)+1) x (log2(view buckets)+1) shapes; the
    cache's jitted surgery is bounded the same way."""
    cfg = get_config("h2o_danube_1p8b", smoke=True)
    model = build(cfg)
    params = init_tree(model.schema(), jax.random.key(0))
    eng = _paged(cfg, model, params, slots=2, num_blocks=64,
                 max_prefill_tokens=8)
    rng = np.random.default_rng(5)
    lengths = list(range(1, 31))            # every length 1..30
    reqs = [PagedRequest(rid=i, prompt=rng.integers(
        2, cfg.vocab_size, size=n).astype(np.int32), max_new_tokens=2)
        for i, n in enumerate(lengths)]
    eng.run(reqs)
    chunk_kinds = 4                         # 1, 2, 4, 8
    view_kinds = 4                          # 8, 16, 32, 64 tokens
    assert len(eng.stats.prefill_shapes) <= chunk_kinds * view_kinds
    assert len(eng.stats.decode_shapes) <= view_kinds
    counts = eng.compile_counts()
    if counts["prefill_chunk"] >= 0:        # _cache_size available
        assert counts["prefill_chunk"] <= chunk_kinds * view_kinds
        assert counts["decode_step"] <= view_kinds
        # the cache's programs: a gather per (1 or slots rows) x view, one
        # decode commit per view whatever the live rows, a prefill commit
        # per chunk x view, a scrub per power-of-two reservation bucket
        scrub_kinds = 3                     # 1, 2, 4 blocks reserved
        assert counts["cache_gather"] <= 2 * view_kinds
        assert counts["cache_commit_decode"] <= len(eng.stats.decode_shapes)
        assert counts["cache_commit_prefill"] <= chunk_kinds * view_kinds
        assert counts["cache_scrub"] <= scrub_kinds
        bounds = eng.compile_shape_bounds()
        assert all(counts[k] <= bounds[k] for k in bounds), (counts, bounds)
    assert len(eng.stats.prefill_shapes) < len(set(lengths))


def test_paged_admission_defers_until_blocks_free():
    """A pool too small for all requests at once still serves all of
    them: admission defers, blocks recycle, everybody completes."""
    cfg = get_config("h2o_danube_1p8b", smoke=True)
    model = build(cfg)
    params = init_tree(model.schema(), jax.random.key(0))
    # 5 usable blocks of 8; each request reserves 2 -> at most 2 live
    eng = _paged(cfg, model, params, slots=4, num_blocks=6)
    prompts = _prompts(6, 5, 8, 12, cfg.vocab_size)
    got = eng.run([PagedRequest(rid=i, prompt=p, max_new_tokens=4)
                   for i, p in enumerate(prompts)])
    assert set(got) == set(range(5))
    assert all(1 <= len(t) <= 4 for t in got.values())
    assert eng.cache.free_blocks == 5       # everything returned


def test_paged_rejects_unservable_request():
    cfg = get_config("h2o_danube_1p8b", smoke=True)
    model = build(cfg)
    params = init_tree(model.schema(), jax.random.key(0))
    eng = _paged(cfg, model, params, num_blocks=6)
    with pytest.raises(ValueError, match="exceeds the cache pool"):
        eng.submit(PagedRequest(rid=0, prompt=np.arange(60) % 50 + 3,
                                max_new_tokens=4))


def test_paged_priority_admitted_first():
    """With one slot, the priority-0 request admits before an earlier-
    submitted priority-1 request."""
    cfg = get_config("h2o_danube_1p8b", smoke=True)
    model = build(cfg)
    params = init_tree(model.schema(), jax.random.key(0))
    eng = _paged(cfg, model, params, slots=1)
    lo = PagedRequest(rid=0, prompt=np.arange(4) % 50 + 3,
                      max_new_tokens=3, priority=1)
    hi = PagedRequest(rid=1, prompt=np.arange(4) % 50 + 3,
                      max_new_tokens=3, priority=0)
    eng.submit(lo)
    eng.submit(hi)
    eng.drain()
    assert hi.admitted_step < lo.admitted_step


def test_paged_request_clock_stamps_are_monotone():
    """Every request is stamped on the host clock at submit, admit, first
    token and finish, in that order, also when it waits for a slot."""
    cfg = get_config("h2o_danube_1p8b", smoke=True)
    model = build(cfg)
    params = init_tree(model.schema(), jax.random.key(0))
    eng = _paged(cfg, model, params, slots=2)
    reqs = [PagedRequest(rid=i, prompt=p, max_new_tokens=3)
            for i, p in enumerate(_prompts(7, 4, 3, 12, cfg.vocab_size))]
    eng.run(reqs)
    for r in reqs:
        assert (r.arrival_s <= r.admitted_s <= r.first_token_s
                <= r.finish_s), r
    # two slots, four requests: the last admitted waited for a retire
    assert max(r.admitted_s for r in reqs) >= min(r.finish_s for r in reqs)
