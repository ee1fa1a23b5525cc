"""Paged KV cache: per-slot block tables over a shared physical pool.

A contiguous cache gives every slot ``max_len`` positions up front, so
one long request dictates the allocation of every short one.  Here the
sequence axis is cut into fixed ``block_size`` blocks, pooled
across slots, and each slot holds a *block table* — an ordered list of
physical block ids whose concatenation is that slot's logical cache.
Blocks are reserved at admission and returned when the request retires,
so long and short requests share memory.

Layout falls out of the models' ``cache_axes`` names, family-agnostic:

* leaves with a ``kv_seq`` axis (k/v values, rope'd keys, MLA latents,
  per-token positions) are stored ``(num_blocks, block_size, cells)``:
  the model's batch axis becomes the physical block id, its sequence axis
  the offset in the block, and a token's cells under every other axis
  (layers, heads x dim) lie flattened along the last, padded to whole
  lanes;
* leaves without one (SSM/xLSTM recurrent states, encoder-decoder cross
  attention) are dense per slot, as in a contiguous cache.

Models never see blocks.  For each step the engine *gathers* a dense
view — ``(rows, V)`` tokens, ``V`` a power-of-two bucket — runs the
ordinary jitted ``prefill_chunk`` / ``decode_step`` on it, then *commits*
only the newly written cells back to the pool.  Rows padded past a slot's
table read as physical block 0, the permanently unallocated **null
block**: its position leaf is ``-1`` everywhere, which the attention
mask already treats as empty, so padding needs no extra masking and a
committed write can never touch it.  Never written, it holds the pool's
init value for good.

The surgery is four jitted programs, each named for the device trace:
``cache_gather`` (one per rows x view), ``cache_commit_decode`` (one per
view: live rows are padded to ``slots``), ``cache_commit_prefill`` (one
per chunk x view) and ``cache_scrub`` (one per power-of-two bucket of a
slot's block count).  The gather fills the view with the null block's
init value and copies the windows of it that hold a row's blocks.  The
three that write take the pool
donated, so XLA updates the changed cells in place instead of copying
whole pool leaves.  Every shape is bucketed, so the compile count stays
O(log max_len) regardless of traffic.

Every array keeps the layout XLA gives it by default, and the pool's
shape and each program's slices are chosen so that it needs no relayout
of a whole pool leaf or view.  A TPU lays an array out in tiles of its
last two axes, lanes last, unless another order wastes less: in the
model's shape, ``(layers, blocks, block_size, heads, dim)``, a head
dimension of 80 (h2o-danube) put the block axis in the lanes, and every
gather and prefill commit relaid out both whole pool leaves (1.2 GB each
at danube's widths) to move a few blocks.  ``(num_blocks, block_size,
cells)`` with whole lanes of cells is laid out block-major, a block one
contiguous slab.  The views keep the model's shape, and a view leaf of
heads x 80 has its tokens in the lanes; a program reads or writes it
128 tokens at a lane-aligned offset, since a narrower slice at a
dynamic lane offset makes XLA relay out the whole view first.  Padding
costs little: k and v cells fill whole lanes at danube's and
DeepSeek-V2's widths, positions pad (24 a token at danube's 24 layers
to 128); the engine reports the pool's bytes as ``serve.cache_pool_bytes``.
Layouts are not pinned (``jax.experimental.layout.Format``): JAX 0.9's
persistent compilation cache does not keep a pinned layout, and a program
loaded from it reports the default layout while computing in the pinned
one.
"""
from __future__ import annotations

import collections
import math
from typing import List, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.models.params import empty_cache, empty_value

NULL_BLOCK = 0
LANES = 128     # a TPU vector register's lanes: a pool row's unit


def round_up_pow2(n: int) -> int:
    n = max(int(n), 1)
    return 1 << (n - 1).bit_length()


class BlockAllocator:
    """Free-list over physical blocks ``1..num_blocks-1`` (0 is null)."""

    def __init__(self, num_blocks: int):
        self.num_blocks = num_blocks
        self._free = collections.deque(range(1, num_blocks))
        self._used: set = set()

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> List[int]:
        if n > len(self._free):
            raise RuntimeError(
                f"pool exhausted: want {n} blocks, {len(self._free)} free")
        out = [self._free.popleft() for _ in range(n)]
        self._used.update(out)
        return out

    def free(self, blocks: Sequence[int]) -> None:
        for b in blocks:
            assert b in self._used, f"double free of block {b}"
            self._used.discard(b)
            self._free.append(b)


class _Leaf(NamedTuple):
    """How one pool leaf is stored.  ``bi`` is its batch axis in the
    model's shape: the slot of a dense leaf, the block id of a kv leaf.  A
    dense leaf (``width`` 0) keeps the model's shape.  A kv leaf is stored
    ``(num_blocks, block_size, width)``: a block is its tokens, a token
    its cells under every leading axis of the model's shape (``lead``,
    the layers) and every trailing one (``tail``, heads x dim), flattened
    and padded to whole lanes."""
    bi: int
    lead: tuple
    tail: tuple
    width: int

    @property
    def cells(self) -> int:
        """One token's values in a pool row, padding left out."""
        return math.prod(self.lead) * math.prod(self.tail)

    def to_view(self, cells):
        """``(m, >= cells)`` pool cells as a view piece, ``lead + (1, m) +
        tail``."""
        m, n = cells.shape[0], len(self.lead)
        x = cells[:, :self.cells].reshape((m,) + self.lead + self.tail)
        return jnp.expand_dims(jnp.moveaxis(x, 0, n), n)

    def to_pool(self, piece):
        """A view piece, ``lead + (1, m) + tail``, as ``(1, m, cells)``
        pool cells."""
        n = len(self.lead)
        return jnp.moveaxis(piece, (n, n + 1), (0, 1)).reshape(
            piece.shape[n:n + 2] + (self.cells,))

    def take(self, view, row, pos, n: int):
        """``n`` tokens of view row ``row`` from ``pos`` on (a multiple of
        ``n``) as pool cells, ``(1, n, cells)``: a one-hot sum over the
        aligned window of 128 tokens that holds them."""
        b, w = self.bi, min(view.shape[self.bi + 1], max(LANES, n))
        start = pos // w * w
        win = _piece(view, b, (row, start), (1, w)).reshape(
            self.lead + (w // n, n) + self.tail)
        hot = (lax.broadcasted_iota(jnp.int32, (w // n,), 0)
               == (pos - start) // n)
        hot = hot.reshape((1,) * b + (w // n,) + (1,) * (1 + len(self.tail)))
        return self.to_pool(jnp.sum(jnp.where(hot, win, 0), axis=b,
                                    keepdims=True, dtype=view.dtype))


def _leaves(model, slots: int, num_blocks: int, block_size: int):
    """Each pool leaf's stored ``ShapeDtypeStruct`` and its ``_Leaf``."""
    kv_spec = jax.tree.leaves(model.cache_spec(num_blocks, block_size))
    dense_spec = jax.tree.leaves(model.cache_spec(slots, block_size))
    axes = jax.tree.leaves(model.cache_axes(1, 1),
                           is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for ks, ds, ax in zip(kv_spec, dense_spec, axes):
        bi = ax.index("batch")
        if "kv_seq" not in ax:
            out.append((ds, _Leaf(bi, (), (), 0)))
            continue
        assert ax.index("kv_seq") == bi + 1, ax
        lead, tail = ks.shape[:bi], ks.shape[bi + 2:]
        width = -(-math.prod(lead + tail) // LANES) * LANES
        spec = jax.ShapeDtypeStruct((num_blocks, block_size, width), ks.dtype)
        out.append((spec, _Leaf(bi, lead, tail, width)))
    return out


def _start(x, bi, *idx):
    """Where a piece of ``x`` starts: ``idx`` from axis ``bi`` on, 0 on
    every other axis."""
    return (0,) * bi + idx + (0,) * (x.ndim - bi - len(idx))


def _piece(x, bi, idx, size):
    """The piece of ``x`` of ``size`` from ``idx`` on, both from axis
    ``bi`` on; every other axis whole."""
    return lax.dynamic_slice(x, _start(x, bi, *idx), x.shape[:bi] + size
                             + x.shape[bi + len(size):])


def _update(arrays, ids, count, write):
    """``arrays`` with each one of ``ids`` replaced, for ``k < count`` in a
    loop, by ``write(k, i, arrays[i])``."""
    arrays = list(arrays)
    if ids:
        def body(k, xs):
            return [write(k, i, x) for i, x in zip(ids, xs)]

        for i, x in zip(ids, lax.fori_loop(0, count, body,
                                           [arrays[i] for i in ids])):
            arrays[i] = x
    return arrays


def _window_blocks(n_blk: int, block_size: int) -> int:
    """Blocks in one of the gather's windows of a view ``n_blk`` blocks
    wide: 128 tokens, a view's lanes, or the whole view if narrower."""
    return min(n_blk, max(LANES // block_size, 1))


def _programs(leaves: Sequence[_Leaf], block_size: int):
    """The cache's jitted programs over pool leaves stored as ``leaves``
    say.  The writers take the pool donated.

    Each moves its pieces in a loop, each piece shaped so that XLA cuts
    it out of the array's default layout in place (module docstring): the
    gather one 128-token window of one view row an iteration, only the
    windows that hold a block of the row, its pool blocks taken whole
    along the major block axis and written at a lane-aligned offset; the decode commit one token (index arrays padded
    to ``slots``, a count of the live rows) and the prefill commit one
    block that the chunk touches, whole, its tokens outside the chunk
    written back as the pool holds them, each read out of the view through
    a 128-token window (``_Leaf.take``); the scrub one freed block."""
    kv = [i for i, leaf in enumerate(leaves) if leaf.width]
    dense = [i for i, leaf in enumerate(leaves) if not leaf.width]
    bi = [leaf.bi for leaf in leaves]
    bs = block_size

    def cache_gather(pool, table, wins, count, rows):
        # table: each row's pool blocks, padded with the null block;
        # wins[:, :count]: (row, window) of each window that holds one of
        # the row's blocks; the rest of the view is the null block's
        n_rows, n_blk = table.shape
        width = _window_blocks(n_blk, bs)
        view = [jnp.full(leaf.lead + (n_rows, n_blk * bs) + leaf.tail,
                         empty_value(x.dtype), x.dtype) if leaf.width else
                jnp.zeros(x.shape[:leaf.bi] + (n_rows,)
                          + x.shape[leaf.bi + 1:], x.dtype)
                for x, leaf in zip(pool, leaves)]

        def window(k, i, v):
            r, w = wins[0, k], wins[1, k]
            x, leaf = pool[i], leaves[i]
            cells = jnp.take(x, lax.dynamic_slice_in_dim(
                table[r], w * width, width), axis=0, mode="clip")
            return lax.dynamic_update_slice(
                v, leaf.to_view(cells.reshape(-1, x.shape[-1])),
                _start(v, leaf.bi, r, w * width * bs))

        view = _update(view, kv, count, window)
        return _update(view, dense, n_rows, lambda k, i, v: (
            lax.dynamic_update_slice(v, _piece(pool[i], bi[i], (rows[k],),
                                               (1,)), _start(v, bi[i], k))))

    def cache_commit_decode(pool, view, kv_idx, dense_idx, count):
        # per live row: (view row, position, pool block, offset) and
        # (view row, slot); ``count`` rows are live
        vrow, vpos, block, off = kv_idx
        drow, slot = dense_idx
        pool = _update(pool, kv, count, lambda k, i, x: (
            lax.dynamic_update_slice(
                x, leaves[i].take(view[i], vrow[k], vpos[k], 1)
                .astype(x.dtype), (block[k], off[k], 0))))
        return _update(pool, dense, count, lambda k, i, x: (
            lax.dynamic_update_slice(
                x, _piece(view[i], bi[i], (drow[k],), (1,)).astype(x.dtype),
                _start(x, bi[i], slot[k]))))

    def cache_commit_prefill(pool, view, blocks, count, lo, hi, slot):
        # blocks: (position, pool block) of each block that tokens [lo, hi)
        # fall in, count of them, in view row 0: each is written whole,
        # its tokens outside [lo, hi) as the pool holds them
        p, b = blocks

        def write_block(k, i, x):
            leaf = leaves[i]
            new = leaf.take(view[i], 0, p[k], bs)
            old = lax.dynamic_index_in_dim(x, b[k], 0)
            # whole rows, padding too: a narrower piece of a padded row
            # made XLA relay out the whole leaf (DeepSeek-V2's positions)
            new = jnp.concatenate([new.astype(x.dtype),
                                   old[..., leaf.cells:]], axis=-1)
            token = p[k] + lax.broadcasted_iota(jnp.int32, (1, bs, 1), 1)
            return lax.dynamic_update_slice(
                x, jnp.where((token >= lo) & (token < hi), new, old),
                (b[k], 0, 0))

        pool = _update(pool, kv, count, write_block)
        return [lax.dynamic_update_slice(x, view[i].astype(x.dtype),
                                         _start(x, bi[i], slot))
                if i in dense else x for i, x in enumerate(pool)]

    def cache_scrub(pool, blocks, count, slot):
        # blocks[:count]: the freed blocks, whose positions go back to -1;
        # the slot's dense rows go back to their init value
        pos = [i for i in kv if pool[i].dtype == jnp.int32]
        pool = _update(pool, pos, count, lambda k, i, x: (
            lax.dynamic_update_slice(x, jnp.full((1,) + x.shape[1:], -1,
                                                 x.dtype), (blocks[k], 0, 0))))
        return [lax.dynamic_update_slice(
            x, jnp.full(x.shape[:bi[i]] + (1,) + x.shape[bi[i] + 1:],
                        empty_value(x.dtype), x.dtype), _start(x, bi[i], slot))
                if i in dense else x for i, x in enumerate(pool)]

    return (jax.jit(cache_gather),
            jax.jit(cache_commit_decode, donate_argnums=0),
            jax.jit(cache_commit_prefill, donate_argnums=0),
            jax.jit(cache_scrub, donate_argnums=0))


class PagedCache:
    """Physical pool + block tables + gather/commit cache surgery."""

    def __init__(self, model, *, slots: int, num_blocks: int,
                 block_size: int):
        assert block_size & (block_size - 1) == 0, "block_size must be 2^k"
        assert num_blocks >= 2, "need at least the null block plus one"
        self.slots, self.num_blocks = slots, num_blocks
        self.block_size = block_size
        self.allocator = BlockAllocator(num_blocks)
        self.tables: List[List[int]] = [[] for _ in range(slots)]

        axes = model.cache_axes(1, 1)
        self._treedef = jax.tree.structure(axes,
                                           is_leaf=lambda x: isinstance(x, tuple))
        self._axes = jax.tree.leaves(axes,
                                     is_leaf=lambda x: isinstance(x, tuple))
        specs, leaves = zip(*_leaves(model, slots, num_blocks, block_size))
        self._pool: List[jnp.ndarray] = empty_cache(list(specs))
        (self._gather, self._commit_decode, self._commit_prefill,
         self._scrub) = _programs(leaves, block_size)

    @property
    def pool_bytes(self) -> int:
        """The pool's bytes on the device, padding included."""
        return sum(x.on_device_size_in_bytes() for x in self._pool)

    def programs(self):
        """The jitted cache programs by name (for compile counts)."""
        return {"cache_gather": self._gather,
                "cache_commit_decode": self._commit_decode,
                "cache_commit_prefill": self._commit_prefill,
                "cache_scrub": self._scrub}

    # -- block accounting ----------------------------------------------

    @property
    def free_blocks(self) -> int:
        return self.allocator.free_blocks

    def alloc_slot(self, slot: int, n_blocks: int) -> None:
        assert not self.tables[slot], f"slot {slot} already allocated"
        self.tables[slot] = self.allocator.alloc(n_blocks)

    def free_slot(self, slot: int) -> None:
        """Return the slot's blocks and scrub it back to the init state.

        Scrubbing matters: a freed kv block still holds valid-looking
        positions, and a freed slot row still holds recurrent state.  The
        pool invariant is that every *free* block has ``pos == -1`` and
        every *free* slot row is zeroed, so reallocation needs no reset.
        """
        blocks = self.tables[slot]
        self.tables[slot] = []
        padded = np.zeros(round_up_pow2(len(blocks)), np.int32)
        padded[:len(blocks)] = blocks
        self._pool = self._scrub(self._pool, padded, np.int32(len(blocks)),
                                 np.int32(slot))
        self.allocator.free(blocks)

    # -- gather / commit -----------------------------------------------

    def view_len(self, tokens_needed: int) -> int:
        """Bucketed dense-view length covering ``tokens_needed``: a power
        of two count of blocks, so view shapes (hence compiles) are
        O(log max_len)."""
        blocks = round_up_pow2(-(-tokens_needed // self.block_size))
        return blocks * self.block_size

    def gather(self, slot_ids: Sequence[int], view_tokens: int):
        """Dense cache view for ``slot_ids`` rows, ``view_tokens`` wide.

        Rows may repeat (padding rows reuse a live slot id for the dense
        leaves; their writes are simply never committed)."""
        nb = view_tokens // self.block_size
        width = _window_blocks(nb, self.block_size)
        table = np.full((len(slot_ids), nb), NULL_BLOCK, np.int32)
        wins = np.zeros((2, len(slot_ids) * (nb // width)), np.int32)
        count = 0
        for r, s in enumerate(slot_ids):
            row = self.tables[s][:nb]
            table[r, :len(row)] = row
            n = -(-len(row) // width)
            wins[0, count:count + n], wins[1, count:count + n] = r, range(n)
            count += n
        view = self._gather(self._pool, table, wins, np.int32(count),
                            np.asarray(slot_ids, np.int32))
        return jax.tree.unflatten(self._treedef, view)

    def commit_prefill(self, view, slot: int, pos0: int, chunk: int) -> None:
        """Write a slot's prefilled cells ``[pos0, pos0+chunk)`` — plus
        its dense row — from a gathered batch-1 view back to the pool."""
        bs, table = self.block_size, self.tables[slot]
        first, last = pos0 // bs, (pos0 + chunk - 1) // bs
        blocks = np.zeros((2, chunk // bs + 2), np.int32)
        blocks[0, :last - first + 1] = np.arange(first, last + 1) * bs
        blocks[1, :last - first + 1] = table[first:last + 1]
        self._pool = self._commit_prefill(
            self._pool, jax.tree.leaves(view), blocks,
            np.int32(last - first + 1), np.int32(pos0), np.int32(pos0 + chunk),
            np.int32(slot))

    def commit_decode(self, view, rows: Sequence[int],
                      slot_ids: Sequence[int],
                      positions: Sequence[int]) -> None:
        """Write each live row's newly decoded cell (``positions[j]`` of
        slot ``slot_ids[j]``, view row ``rows[j]``) — plus its dense row
        — back to the pool.  Padding rows are simply not listed; the
        program sees ``slots`` rows and the count of live ones."""
        if not rows:
            return
        n = len(rows)
        pos = np.asarray(positions, np.int32)
        kv_idx = np.zeros((4, self.slots), np.int32)
        kv_idx[:, :n] = (rows, pos,
                         [self.tables[s][p // self.block_size]
                          for s, p in zip(slot_ids, pos)],
                         pos % self.block_size)
        dense_idx = np.zeros((2, self.slots), np.int32)
        dense_idx[:, :n] = (rows, slot_ids)
        self._pool = self._commit_decode(self._pool, jax.tree.leaves(view),
                                         kv_idx, dense_idx, np.int32(n))
