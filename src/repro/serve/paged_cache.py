"""Paged KV cache: per-slot block tables over a shared physical pool.

The contiguous engine gives every slot ``max_len`` cache positions up
front, so one long request dictates the allocation of every short one.
Here the sequence axis is cut into fixed ``block_size`` blocks, pooled
across slots, and each slot holds a *block table* — an ordered list of
physical block ids whose concatenation is that slot's logical cache.
Blocks are reserved at admission and returned when the request retires,
so long and short requests share memory with no left-pad contiguity.

Layout falls out of the models' ``cache_axes`` names, family-agnostic:

* leaves with a ``kv_seq`` axis (k/v values, rope'd keys, MLA latents,
  per-token positions) are stored as ``(..., num_blocks, block_size,
  ...)`` — the batch axis becomes the physical block id, the sequence
  axis the in-block offset;
* leaves without one (SSM/xLSTM recurrent states, encoder-decoder cross
  attention) are dense per slot, exactly as in the contiguous engine.

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
init value and copies only the rows' real blocks.  The three that write
take the pool donated, so XLA updates the changed cells in place instead
of copying whole pool leaves.  Every shape is bucketed, so the compile
count stays O(log max_len) regardless of traffic.
"""
from __future__ import annotations

import collections
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

NULL_BLOCK = 0


def init_value(dtype) -> int:
    """What a pool leaf starts as: -1 for int32 positions (empty), else 0.
    The null block is never written, so it keeps this value for good."""
    return -1 if dtype == jnp.int32 else 0


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


def _copy(dsts, srcs, axes, cells, count, starts):
    """For ``j < count``, copy one piece of every ``src`` into its ``dst``:
    ``cells`` = (src piece, dst piece) sizes on the index axes that start
    at each leaf's batch axis (its entry of ``axes``), every other axis
    whole, and ``starts(j)`` = (dst start, src start) on the index axes.
    The dynamic slices update ``dsts`` in place and touch only the
    pieces."""
    if not dsts:
        return []
    src_cell, dst_cell = cells

    def body(j, dsts):
        d, s = starts(j)
        out = []
        for dst, src, bi in zip(dsts, srcs, axes):
            k = len(src_cell)
            tail = (0,) * (src.ndim - bi - k)
            piece = lax.dynamic_slice(
                src, (0,) * bi + s + tail,
                src.shape[:bi] + src_cell + src.shape[bi + k:])
            piece = piece.reshape(src.shape[:bi] + dst_cell
                                  + src.shape[bi + k:])
            out.append(lax.dynamic_update_slice(
                dst, piece.astype(dst.dtype), (0,) * bi + d + tail))
        return out

    return lax.fori_loop(0, count, body, list(dsts))


def _runs(blocks: Sequence[int]):
    """``(index, first block, log2 length)`` of each piece of ``blocks``
    when cut into runs of consecutive ids and each run into power-of-two
    lengths, longest first."""
    blocks = np.asarray(blocks, np.int64)
    cuts = (np.flatnonzero(np.diff(blocks) != 1) + 1).tolist()
    out = []
    for lo, hi in zip([0, *cuts], [*cuts, len(blocks)]):
        while lo < hi:
            c = (hi - lo).bit_length() - 1
            out.append((lo, int(blocks[lo]), c))
            lo += 1 << c
    return out


def _programs(kinds, block_size):
    """The cache's jitted programs over pool leaves of ``kinds``, one
    ``(is_kv, batch axis)`` per leaf.  The writers take the pool donated.

    On a TPU the pool's default layout puts the block axis in the lanes
    and the view's puts the token axis there, so XLA's gather and scatter
    between them relay out whole arrays first: a fixed cost that a
    prefill chunk's hundreds of cells or a rarely freed slot amortize, and
    a decode step's few cells do not.  So the decode commit copies one
    cell an iteration of a loop (index arrays padded to ``slots``, a count
    of the live rows), the gather one power-of-two run of consecutive pool
    blocks an iteration (the allocator hands a request consecutive blocks,
    so a table is a few runs), and the prefill commit and the scrub
    scatter."""
    kv = [i for i, (is_kv, _) in enumerate(kinds) if is_kv]
    dense = [i for i, (is_kv, _) in enumerate(kinds) if not is_kv]
    axes = [bi for _, bi in kinds]

    def at(bi, *idx):
        return (slice(None),) * bi + idx

    def split(leaves):
        return [leaves[i] for i in kv], [leaves[i] for i in dense]

    def join(kv_leaves, dense_leaves):
        out = [None] * len(kinds)
        for i, x in zip(kv + dense, list(kv_leaves) + list(dense_leaves)):
            out[i] = x
        return out

    def cache_gather(pool, pieces, counts, rows):
        # pieces[c]: (view row, block of the row, pool block) where a run
        # of 2**c consecutive pool blocks starts, counts[c] of them; the
        # rest of the view is the null block's init value
        n_rows = rows.shape[0]
        n_blk = pieces[0].shape[1] // n_rows
        kv_pool, dense_pool = split(pool)
        kv_view = [jnp.full(x.shape[:axes[i]] + (n_rows, n_blk * block_size)
                            + x.shape[axes[i] + 2:], init_value(x.dtype),
                            x.dtype) for i, x in zip(kv, kv_pool)]
        for c, (r, j, b) in enumerate(pieces):
            n = 1 << c
            kv_view = _copy(kv_view, kv_pool, [axes[i] for i in kv],
                            ((n, block_size), (1, n * block_size)), counts[c],
                            lambda k, r=r, j=j, b=b: (
                                (r[k], j[k] * block_size), (b[k], 0)))
        dense_view = [jnp.zeros(x.shape[:axes[i]] + (n_rows,)
                                + x.shape[axes[i] + 1:], x.dtype)
                      for i, x in zip(dense, dense_pool)]
        dense_view = _copy(dense_view, dense_pool, [axes[i] for i in dense],
                           ((1,), (1,)), n_rows,
                           lambda k: ((k,), (rows[k],)))
        return join(kv_view, dense_view)

    def cache_commit_decode(pool, view, kv_idx, dense_idx, count):
        # per live row: (view row, position, pool block, offset) and
        # (view row, slot); ``count`` rows are live
        vrow, vpos, block, off = kv_idx
        drow, slot = dense_idx
        (kv_pool, dense_pool), (kv_view, dense_view) = split(pool), split(view)
        kv_pool = _copy(kv_pool, kv_view, [axes[i] for i in kv],
                        ((1, 1), (1, 1)), count,
                        lambda k: ((block[k], off[k]), (vrow[k], vpos[k])))
        dense_pool = _copy(dense_pool, dense_view, [axes[i] for i in dense],
                           ((1,), (1,)), count,
                           lambda k: ((slot[k],), (drow[k],)))
        return join(kv_pool, dense_pool)

    def cache_commit_prefill(pool, view, kv_idx, slot):
        # per cell: (position, pool block, offset); view row 0
        vpos, block, off = kv_idx
        out = []
        for x, v, (is_kv, bi) in zip(pool, view, kinds):
            if is_kv:
                cells, idx = v[at(bi, 0, vpos)], at(bi, block, off)
            else:
                cells, idx = v[at(bi, 0)], at(bi, slot)
            out.append(x.at[idx].set(cells.astype(x.dtype)))
        return out

    def cache_scrub(pool, blocks, slot):
        # blocks padded with ``num_blocks``, past the pool: dropped
        out = []
        for x, (is_kv, bi) in zip(pool, kinds):
            if is_kv and x.dtype == jnp.int32:  # kv positions: empty again
                x = x.at[at(bi, blocks)].set(-1, mode="drop")
            elif not is_kv:
                x = x.at[at(bi, slot)].set(init_value(x.dtype))
            out.append(x)
        return out

    return (jax.jit(cache_gather),
            jax.jit(cache_commit_decode, donate_argnums=0),
            jax.jit(cache_commit_prefill, donate_argnums=0),
            jax.jit(cache_scrub, donate_argnums=0))


class PagedCache:
    """Physical pool + block tables + gather/commit cache surgery."""

    def __init__(self, model, cfg, *, slots: int, num_blocks: int,
                 block_size: int):
        assert not cfg.ring_cache, "paged cache layers a ring itself"
        assert block_size & (block_size - 1) == 0, "block_size must be 2^k"
        assert num_blocks >= 2, "need at least the null block plus one"
        self.slots, self.num_blocks = slots, num_blocks
        self.block_size = block_size
        self.allocator = BlockAllocator(num_blocks)
        self.tables: List[List[int]] = [[] for _ in range(slots)]

        # one spec per leaf kind: kv leaves indexed by (block, offset),
        # dense leaves by slot row
        kv_spec = jax.tree.leaves(model.cache_spec(num_blocks, block_size))
        dense_spec = jax.tree.leaves(model.cache_spec(slots, block_size))
        axes = model.cache_axes(1, 1)
        self._treedef = jax.tree.structure(axes,
                                           is_leaf=lambda x: isinstance(x, tuple))
        self._axes = jax.tree.leaves(axes,
                                     is_leaf=lambda x: isinstance(x, tuple))
        self._pool: List[jnp.ndarray] = []
        kinds = []
        for ks, ds, ax in zip(kv_spec, dense_spec, self._axes):
            bi = ax.index("batch")
            is_kv = "kv_seq" in ax
            if is_kv:
                assert ax.index("kv_seq") == bi + 1, ax
            sp = ks if is_kv else ds
            self._pool.append(jnp.full(sp.shape, init_value(sp.dtype),
                                       sp.dtype))
            kinds.append((is_kv, bi))
        (self._gather, self._commit_decode, self._commit_prefill,
         self._scrub) = _programs(tuple(kinds), block_size)

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
        padded = np.full(round_up_pow2(len(blocks)), self.num_blocks,
                         np.int32)
        padded[:len(blocks)] = blocks
        self._pool = self._scrub(self._pool, padded, np.int32(slot))
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
        n = len(slot_ids)
        pieces = [np.zeros((3, n * (nb >> c)), np.int32)
                  for c in range(nb.bit_length())]
        counts = np.zeros(len(pieces), np.int32)
        for r, s in enumerate(slot_ids):
            for j, b, c in _runs(self.tables[s][:nb]):
                pieces[c][:, counts[c]] = r, j, b
                counts[c] += 1
        view = self._gather(self._pool, pieces, counts,
                            np.asarray(slot_ids, np.int32))
        return jax.tree.unflatten(self._treedef, view)

    def commit_prefill(self, view, slot: int, pos0: int, chunk: int) -> None:
        """Write a slot's prefilled cells ``[pos0, pos0+chunk)`` — plus
        its dense row — from a gathered batch-1 view back to the pool."""
        pos = np.arange(pos0, pos0 + chunk, dtype=np.int32)
        blocks = np.asarray(self.tables[slot], np.int32)[pos // self.block_size]
        self._pool = self._commit_prefill(
            self._pool, jax.tree.leaves(view),
            np.stack([pos, blocks, pos % self.block_size]), np.int32(slot))

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
