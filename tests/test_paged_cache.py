"""PagedCache surgery against a numpy re-implementation of its eager
semantics, cell for cell, on a random pool holding every leaf kind the
model families page: bf16 k/v, int32 positions, bp8 int8 codes with f32
scales, a kv leaf under two stacking axes (the hybrid models' attention),
and dense recurrent rows.

The cache's programs pad (decode rows to ``slots``, scrubbed blocks to a
power of two) and take the pool donated; the reference does neither, so
equality also says that padding writes nothing and that no view is
consumed by a commit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.params import empty_value
from repro.serve.paged_cache import LANES, NULL_BLOCK, PagedCache

SLOTS, NUM_BLOCKS, BS = 4, 12, 4
RESERVED = {0: 3, 1: 1, 2: 4}                       # slot 3 stays empty


class _Model:
    """Cache leaves of every kind, as a model's ``cache_spec`` /
    ``cache_axes`` name them."""

    def cache_spec(self, batch, length):
        s = jax.ShapeDtypeStruct
        return {"k": s((2, batch, length, 2, 3), jnp.bfloat16),
                "pos": s((2, batch, length), jnp.int32),
                "k_codes": s((2, batch, length, 2, 3), jnp.int8),
                "k_scale": s((2, batch, length, 2), jnp.float32),
                "grouped_v": s((2, 3, batch, length, 5), jnp.bfloat16),
                "ssm": s((2, 3, batch, 2, 4), jnp.float32)}

    def cache_axes(self, batch, length):
        return {"k": ("stack", "batch", "kv_seq", "kv_heads", None),
                "pos": ("stack", "batch", "kv_seq"),
                "k_codes": ("stack", "batch", "kv_seq", "kv_heads", None),
                "k_scale": ("stack", "batch", "kv_seq", "kv_heads"),
                "grouped_v": ("stack", "stack2", "batch", "kv_seq", None),
                "ssm": ("stack", "stack2", "batch", "heads", "state")}


def _random(shape, dtype, rng):
    if dtype == jnp.int32:
        return rng.integers(0, 1000, shape).astype(np.int32)
    if dtype == jnp.int8:
        return rng.integers(-127, 128, shape).astype(np.int8)
    return rng.standard_normal(shape).astype(dtype)


def _cache(seed=0, churn=True, num_blocks=NUM_BLOCKS, reserved=RESERVED):
    """A cache with random pool contents and the slots of ``reserved``
    allocated; the null block holds every kv leaf's init value (-1
    positions, zeros), as the pool keeps it."""
    cache = PagedCache(_Model(), slots=SLOTS, num_blocks=num_blocks,
                       block_size=BS)
    rng = np.random.default_rng(seed)
    pool = []
    for leaf, ax in zip(cache._pool, cache._axes):
        a = _random(leaf.shape, leaf.dtype, rng)
        if "kv_seq" in ax:
            a[NULL_BLOCK] = empty_value(leaf.dtype)
        pool.append(jnp.asarray(a))
    cache._pool = pool
    if churn:       # tables scattered over the pool, else runs of blocks
        cache.allocator.free(rng.permutation(
            cache.allocator.alloc(num_blocks - 1)).tolist())
    for slot, n in reserved.items():
        cache.alloc_slot(slot, n)
    return cache


def _kinds(cache):
    return [("kv_seq" in ax, ax.index("batch")) for ax in cache._axes]


def _np(tree):
    # copies: a host view of a device buffer would keep it from donation
    return [np.array(x) for x in jax.tree.leaves(tree)]


def _at(bi, *idx):
    return (slice(None),) * bi + idx


def _cells(cache):
    """The pool in the model's shape: a kv leaf is stored ``(blocks,
    block_size, lanes)``, each token's cells under every layer flattened
    along its row and padded (the padding is never read)."""
    out = []
    for x, spec, (is_kv, bi) in zip(
            _np(cache._pool),
            jax.tree.leaves(_Model().cache_spec(cache.num_blocks, BS)),
            _kinds(cache)):
        if is_kv:
            lead, tail = spec.shape[:bi], spec.shape[bi + 2:]
            x = x[..., :np.prod(lead + tail, dtype=int)].reshape(
                (cache.num_blocks, BS) + lead + tail)
            x = np.moveaxis(x, (0, 1), (bi, bi + 1))
        out.append(x)
    return out


# -- the eager semantics, in numpy ---------------------------------------

def _ref_gather(cache, pool, slot_ids, view):
    nb = view // BS
    table = np.full((len(slot_ids), nb), NULL_BLOCK)
    for r, s in enumerate(slot_ids):
        row = cache.tables[s][:nb]
        table[r, :len(row)] = row
    out = []
    for leaf, (is_kv, bi) in zip(pool, _kinds(cache)):
        if is_kv:
            g = np.take(leaf, table.reshape(-1), axis=bi)
            out.append(g.reshape(g.shape[:bi] + (len(slot_ids), view)
                                 + g.shape[bi + 2:]))
        else:
            out.append(np.take(leaf, slot_ids, axis=bi))
    return out


def _ref_write(cache, pool, view, cells, dense):
    """``cells``: (view row, position, slot) per kv cell written;
    ``dense``: (view row, slot) per dense row written."""
    pool = [p.copy() for p in pool]
    for leaf, vleaf, (is_kv, bi) in zip(pool, view, _kinds(cache)):
        if is_kv:
            for r, p, s in cells:
                leaf[_at(bi, cache.tables[s][p // BS], p % BS)] = \
                    vleaf[_at(bi, r, p)]
        else:
            for r, s in dense:
                leaf[_at(bi, s)] = vleaf[_at(bi, r)]
    return pool


def _ref_free(cache, pool, slot, blocks):
    pool = [p.copy() for p in pool]
    for leaf, (is_kv, bi) in zip(pool, _kinds(cache)):
        if is_kv and leaf.dtype == np.int32:
            leaf[_at(bi, np.asarray(blocks, int))] = -1
        elif not is_kv:
            leaf[_at(bi, slot)] = -1 if leaf.dtype == np.int32 else 0
    return pool


def _random_view(cache, slot_ids, view, seed):
    rng = np.random.default_rng(seed)
    v = cache.gather(slot_ids, view)
    return jax.tree.map(lambda x: jnp.asarray(_random(x.shape, x.dtype, rng)),
                        v)


def _assert_pool(cache, want):
    got = _cells(cache)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g, w)


def _null_block_is_untouched(cache):
    """The null block keeps its init value: padding reads it, nothing
    writes it."""
    for leaf, (is_kv, bi) in zip(cache._pool, _kinds(cache)):
        if is_kv:
            assert (np.array(leaf)[NULL_BLOCK]
                    == empty_value(leaf.dtype)).all()


# -- the cases -----------------------------------------------------------

@pytest.mark.parametrize("churn", [True, False])
@pytest.mark.parametrize("slot_ids,view", [
    ([1], 4), ([0], 8), ([2], 16), ([3], 4),        # prefill: one row
    ([0, 1, 2, 0], 16), ([2, 0, 0, 0], 8),          # decode: slots rows,
    ([1, 0, 0, 0], 4)])                             # padding reuses slot 0
def test_gather_matches_eager(slot_ids, view, churn):
    cache = _cache(churn=churn)
    want = _ref_gather(cache, _cells(cache), slot_ids, view)
    got = _np(cache.gather(slot_ids, view))
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("churn", [True, False])
def test_gather_matches_eager_across_windows(churn):
    """Views wider than one of the gather's 128-token windows: a row that
    fills one window and part of the next, one that ends inside its first,
    an empty one, and windows past a row's end, which hold the null
    block."""
    cache = _cache(40 + churn, churn, num_blocks=90,
                   reserved={0: 45, 1: 3, 3: 33})
    for slot_ids, view in [([0, 1, 2, 3], 256), ([3, 0], 512),
                           ([1], 256)]:
        want = _ref_gather(cache, _cells(cache), slot_ids, view)
        got = _np(cache.gather(slot_ids, view))
        for g, w in zip(got, want):
            assert g.dtype == w.dtype
            np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("live", range(1, SLOTS + 1))
def test_commit_decode_matches_eager(live):
    """1..slots live rows, the rest padding: only the listed rows' cells
    and dense rows change, and the view stays readable."""
    cache = _cache(live)
    slot_ids = [2, 0, 1, 0][:live]
    positions = [13, 9, 2, 5][:live]
    rows = list(range(live))
    view = _random_view(cache, slot_ids + [0] * (SLOTS - live), 16, live)
    before, view_before = _cells(cache), _np(view)
    old = list(cache._pool)
    cache.commit_decode(view, rows, slot_ids, positions)
    _assert_pool(cache, _ref_write(
        cache, before, view_before, list(zip(rows, positions, slot_ids)),
        list(zip(rows, slot_ids))))
    _null_block_is_untouched(cache)
    assert all(x.is_deleted() for x in old)         # written in place
    for a, b in zip(_np(view), view_before):        # the view is not donated
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("slot,pos0,chunk,view", [
    (0, 0, 8, 8), (0, 8, 4, 16), (2, 3, 1, 4), (2, 4, 8, 16), (1, 0, 2, 4),
    # chunks that start or end inside a block, which is written whole: its
    # cells outside the chunk come back as the pool held them
    (0, 2, 8, 16), (2, 1, 13, 16), (2, 5, 2, 8), (1, 1, 3, 4)])
def test_commit_prefill_matches_eager(slot, pos0, chunk, view):
    cache = _cache(slot)
    v = _random_view(cache, [slot], view, pos0 + chunk)
    before, view_before = _cells(cache), _np(v)
    cache.commit_prefill(v, slot, pos0, chunk)
    _assert_pool(cache, _ref_write(
        cache, before, view_before,
        [(0, p, slot) for p in range(pos0, pos0 + chunk)], [(0, slot)]))
    _null_block_is_untouched(cache)
    for a, b in zip(_np(v), view_before):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("slot", range(SLOTS))
def test_free_slot_matches_eager(slot):
    """Freed blocks' positions and the freed dense row go back to the
    init state; nothing else moves, whatever the padded bucket."""
    cache = _cache(10 + slot)
    blocks = list(cache.tables[slot])
    before = _cells(cache)
    free = cache.free_blocks
    cache.free_slot(slot)
    _assert_pool(cache, _ref_free(cache, before, slot, blocks))
    assert cache.tables[slot] == []
    assert cache.free_blocks == free + len(blocks)
    _null_block_is_untouched(cache)


def test_pool_keeps_its_block_major_layout():
    """Every kv pool leaf is made ``(blocks, block_size, whole lanes)``,
    the shape a TPU lays out block-major (``tests/test_tpu_compile.py``
    compiles the programs there), and every program hands the pool back
    in the same shapes and dtypes."""
    cache = _cache(30, churn=False)
    for x, (is_kv, bi) in zip(cache._pool, _kinds(cache)):
        if is_kv:
            assert x.shape[:2] == (NUM_BLOCKS, BS)
            assert x.shape[2] % LANES == 0
    want = [(x.shape, x.dtype) for x in cache._pool]

    def kept():
        assert [(x.shape, x.dtype) for x in cache._pool] == want

    view = cache.gather([0, 1, 2, 0], 16)
    cache.commit_decode(view, [0, 1], [0, 1], [5, 3])
    kept()
    view = cache.gather([2], 16)
    cache.commit_prefill(view, 2, 0, 8)
    kept()
    cache.free_slot(2)
    kept()
