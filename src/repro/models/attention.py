"""Attention: GQA/MQA (+SWA, prefix-LM, qk-norm) and MLA, with KV caches.

Two execution paths share one mask/online-softmax core:

  * direct   — materialise (B, H, Sq, Skv) scores (small sequences)
  * chunked  — lax.scan over KV chunks with a running (max, denom, acc)
               online softmax, so prefill at 32k/500k never materialises a
               quadratic score tensor (flash-attention structure in jnp).

KV caches carry explicit per-slot position arrays (-1 = empty), so masks
come from absolute positions.  With a cache, a layer has two modes, which
the shapes decide: one token a row (Sq == 1) is written at its row's
position, and a longer chunk is appended at its first position.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import (ParamDef, apply_rope, dense, linear_def,
                                 norm_def, rms_norm, yarn_frequencies,
                                 yarn_mscale)

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# mask + softmax core
# ---------------------------------------------------------------------------

def _allowed(q_pos: jax.Array, kv_pos: jax.Array, *, causal: bool,
             window: Optional[int], prefix_len: Optional[jax.Array]) -> jax.Array:
    """(..., Sq, Skv) boolean mask from absolute positions.

    q_pos: (B, Sq); kv_pos: (B, Skv).  kv_pos < 0 marks empty cache slots.
    """
    qp = q_pos[:, :, None]
    kp = kv_pos[:, None, :]
    ok = kp >= 0
    if causal:
        c = kp <= qp
        if prefix_len is not None:
            c = c | (kp < prefix_len[:, None, None])
        ok = ok & c
    if window is not None:
        ok = ok & (qp - kp < window)
    return ok


def _sdpa_direct(q, k, v, mask, softcap=None):
    """q: (B,KH,G,Sq,D) k: (B,KH,Skv,D) v: (B,KH,Skv,Dv) mask: (B,Sq,Skv)."""
    scores = jnp.einsum("bhgqd,bhsd->bhgqs", q.astype(jnp.float32),
                        k.astype(jnp.float32))
    if softcap:
        scores = jnp.tanh(scores / softcap) * softcap
    scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhgqs,bhsv->bhgqv", p, v.astype(jnp.float32))


def _sdpa_chunked(q, k, v, q_pos, kv_pos, *, causal, window, prefix_len,
                  chunk, softcap=None):
    """Online-softmax over KV chunks; never forms (Sq, Skv) in full."""
    b, kh, g, sq, d = q.shape
    skv = k.shape[2]
    dv = v.shape[-1]
    n_chunks = skv // chunk
    qf = q.astype(jnp.float32)

    def body(carry, inputs):
        m, l, acc = carry
        kc, vc, kpc = inputs          # (B,KH,c,D), (B,KH,c,Dv), (B,c)
        s = jnp.einsum("bhgqd,bhsd->bhgqs", qf, kc.astype(jnp.float32))
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        mask = _allowed(q_pos, kpc, causal=causal, window=window,
                        prefix_len=prefix_len)
        s = jnp.where(mask[:, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhgqs,bhsv->bhgqv", p, vc.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    ks = k.reshape(b, kh, n_chunks, chunk, d).transpose(2, 0, 1, 3, 4)
    vs = v.reshape(b, kh, n_chunks, chunk, dv).transpose(2, 0, 1, 3, 4)
    kps = kv_pos.reshape(b, n_chunks, chunk).transpose(1, 0, 2)
    init = (jnp.full((b, kh, g, sq), NEG_INF, jnp.float32),
            jnp.zeros((b, kh, g, sq), jnp.float32),
            jnp.zeros((b, kh, g, sq, dv), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(body, init, (ks, vs, kps))
    return acc / jnp.maximum(l, 1e-30)[..., None]


@jax.named_scope("attention")
def sdpa(q, k, v, q_pos, kv_pos, *, causal=True, window=None,
         prefix_len=None, chunk=1024, softcap=None):
    """Grouped SDPA. q: (B,Sq,H,D) k/v: (B,Skv,KH,D[v]) -> (B,Sq,H,Dv)."""
    b, sq, h, d = q.shape
    skv, kh = k.shape[1], k.shape[2]
    g = h // kh
    qg = q.reshape(b, sq, kh, g, d).transpose(0, 2, 3, 1, 4)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    scale = 1.0 / jnp.sqrt(jnp.float32(d))
    qg = qg * scale
    if skv > chunk and skv % chunk == 0:
        out = _sdpa_chunked(qg, kt, vt, q_pos, kv_pos, causal=causal,
                            window=window, prefix_len=prefix_len,
                            chunk=chunk, softcap=softcap)
    else:
        mask = _allowed(q_pos, kv_pos, causal=causal, window=window,
                        prefix_len=prefix_len)
        out = _sdpa_direct(qg, kt, vt, mask, softcap=softcap)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, -1)


# ---------------------------------------------------------------------------
# ring attention (sequence parallelism over a "seq" mesh axis)
#
# The KV sequence lives sharded across a ring of devices; each device owns
# one contiguous block.  Attention over the full sequence is recovered from
# per-block online-softmax partials (m, l, acc) that are merged in canonical
# block order, so the result is bitwise identical no matter which device
# computed which block or in which order the ring delivered them.  Two
# schedules produce the same partials:
#
#   * rotate="kv"    — queries stay put (sharded or replicated); the KV
#                      blocks travel the ring via ppermute (n-1 hops).
#                      The classic ring-attention schedule for prefill.
#   * rotate="stats" — each device computes its local block's partial once
#                      and the small (m, l, acc) tuple travels the ring
#                      instead.  For decode (Sq == 1) this moves
#                      O(heads * head_dim) bytes per hop instead of the
#                      KV block — the schedule the roofline prices.
#
# Causal masking, sliding windows, prefix-LM prefixes and empty cache
# slots all come from the absolute-position mask (`_allowed`): a block
# whose scores are fully masked yields m = NEG_INF and is wiped exactly
# (alpha = exp(NEG_INF - m_finite) == 0.0) by the merge, so shard
# boundaries never need causal special-casing and striped layouts are
# just a different block->position assignment.
#
# These functions run INSIDE a manual `shard_map` region (see
# repro.dist.seq, which derives the in/out specs from the ambient sharding
# rules and wraps them); `ring_reference` is the single-device oracle the
# equivalence tests pin against, built from the *same* per-block math and
# merge so oracle-vs-ring is exact, not merely close.
# ---------------------------------------------------------------------------

def _block_partials(qg, kb, vb, q_pos, kp_b, *, causal, window, prefix_len,
                    softcap):
    """Online-softmax partial for one KV block.

    qg: (B,KH,G,Sq,D) pre-scaled f32 queries; kb: (B,KH,c,D); vb: (B,KH,c,Dv);
    kp_b: (B,c) absolute positions (-1 = empty slot).  Returns
    (m, l, acc) with shapes (B,KH,G,Sq), (B,KH,G,Sq), (B,KH,G,Sq,Dv).
    """
    s = jnp.einsum("bhgqd,bhsd->bhgqs", qg, kb.astype(jnp.float32))
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    mask = _allowed(q_pos, kp_b, causal=causal, window=window,
                    prefix_len=prefix_len)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    m = s.max(-1)
    p = jnp.exp(s - m[..., None])
    return m, p.sum(-1), jnp.einsum("bhgqs,bhsv->bhgqv", p,
                                    vb.astype(jnp.float32))


def merge_block_partials(ms, ls, accs):
    """Merge per-block partials stacked on axis 0 in canonical block order.

    The left-to-right scan fixes the floating-point summation order, so
    every device of a ring — and the single-device oracle — produces the
    same bits.  Returns acc / l, i.e. the attention output.
    """
    def body(carry, inp):
        m, l, acc = carry
        mj, lj, accj = inp
        mn = jnp.maximum(m, mj)
        a, bcoef = jnp.exp(m - mn), jnp.exp(mj - mn)
        return (mn, l * a + lj * bcoef,
                acc * a[..., None] + accj * bcoef[..., None]), None
    (m, l, acc), _ = jax.lax.scan(body, (ms[0], ls[0], accs[0]),
                                  (ms[1:], ls[1:], accs[1:]))
    return acc / jnp.maximum(l, 1e-30)[..., None]


def _ring_bufs(part_shapes):
    return tuple(jnp.zeros(s, jnp.float32) for s in part_shapes)


def _ring_run(axis_name, n, rotate, local_partial, kv_operands, part_shapes):
    """Shared ring driver: fill (ms, ls, accs) buffers indexed by global
    block id, under either schedule, then merge canonically.

    local_partial(ops) -> (m, l, acc) for the KV operand tuple ``ops``.
    kv_operands is this device's resident block (the t=0 ring payload).
    """
    idx = jax.lax.axis_index(axis_name)
    fwd = [(j, (j + 1) % n) for j in range(n)]

    def put(bufs, j, part):
        return tuple(jax.lax.dynamic_update_index_in_dim(b, p, j, 0)
                     for b, p in zip(bufs, part))

    def rot(tree):
        return jax.tree.map(
            lambda a: jax.lax.ppermute(a, axis_name, fwd), tree)

    bufs = _ring_bufs(part_shapes)
    if rotate == "kv":
        cur = kv_operands
        for t in range(n):
            bufs = put(bufs, (idx - t) % n, local_partial(cur))
            if t + 1 < n:
                cur = rot(cur)
    elif rotate == "stats":
        cur = local_partial(kv_operands)
        for t in range(n):
            bufs = put(bufs, (idx - t) % n, cur)
            if t + 1 < n:
                cur = rot(cur)
    else:
        raise ValueError(f"unknown ring schedule {rotate!r}")
    return merge_block_partials(*bufs)


def ring_sdpa(q, k, v, q_pos, kv_pos, *, axis_name, n_blocks, rotate="kv",
              causal=True, window=None, prefix_len=None, softcap=None):
    """Grouped SDPA over a ring-sharded KV sequence (manual-region local).

    Shapes are per-device: q (B,Sq_loc,H_loc,D), k/v (B,Skv_loc,KH_loc,D[v]),
    q_pos (B,Sq_loc), kv_pos (B,Skv_loc).  ``axis_name`` is the mesh axis
    (or axis tuple) the KV sequence is sharded over; ``n_blocks`` its total
    size, passed statically by the wrapper.  Under rotate="stats" the
    queries must be replicated across ``axis_name``; under rotate="kv" they
    may instead be sharded over exactly that axis.  Both schedules return
    bitwise-identical outputs (same partials, same canonical merge).
    """
    b, sq, h, d = q.shape
    kh = k.shape[2]
    g = h // kh
    qg = (q.reshape(b, sq, kh, g, d).transpose(0, 2, 3, 1, 4)
          .astype(jnp.float32) / jnp.sqrt(jnp.float32(d)))
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    dv = vt.shape[-1]
    n = n_blocks

    def local_partial(ops):
        kb, vb, kp = ops
        return _block_partials(qg, kb, vb, q_pos, kp, causal=causal,
                               window=window, prefix_len=prefix_len,
                               softcap=softcap)

    shp = (n, b, kh, g, sq)
    out = _ring_run(axis_name, n, rotate, local_partial, (kt, vt, kv_pos),
                    (shp, shp, shp + (dv,)))
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, -1)


def ring_reference(q, k, v, q_pos, kv_pos, *, n_blocks, causal=True,
                   window=None, prefix_len=None, softcap=None):
    """Single-device oracle: split KV into ``n_blocks`` contiguous blocks,
    compute the same per-block partials, merge in the same canonical
    order.  ``ring_sdpa`` must match this bit-for-bit."""
    b, sq, h, d = q.shape
    skv, kh = k.shape[1], k.shape[2]
    g = h // kh
    if skv % n_blocks:
        raise ValueError(f"Skv={skv} not divisible into {n_blocks} blocks "
                         "(pad with repro.dist.seq.pad_kv first)")
    c = skv // n_blocks
    qg = (q.reshape(b, sq, kh, g, d).transpose(0, 2, 3, 1, 4)
          .astype(jnp.float32) / jnp.sqrt(jnp.float32(d)))
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    parts = [_block_partials(qg, kt[:, :, j * c:(j + 1) * c],
                             vt[:, :, j * c:(j + 1) * c], q_pos,
                             kv_pos[:, j * c:(j + 1) * c], causal=causal,
                             window=window, prefix_len=prefix_len,
                             softcap=softcap)
             for j in range(n_blocks)]
    ms, ls, accs = (jnp.stack(x) for x in zip(*parts))
    out = merge_block_partials(ms, ls, accs)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, -1)


def _mla_block_partials(qa, qr, ckv_b, kr_b, q_pos, kp_b, *, window, scale):
    """Absorbed-MLA partial for one latent block: scores in latent space,
    accumulator over the latent (not per-head values).

    qa: (B,Sq,H,R) f32; qr: (B,Sq,H,P) f32; ckv_b: (B,c,R); kr_b: (B,c,P).
    Returns (m, l, acc): (B,H,Sq), (B,H,Sq), (B,H,Sq,R).
    """
    s = (jnp.einsum("bqhr,bsr->bhqs", qa, ckv_b.astype(jnp.float32))
         + jnp.einsum("bqhp,bsp->bhqs", qr, kr_b.astype(jnp.float32))) * scale
    mask = _allowed(q_pos, kp_b, causal=True, window=window, prefix_len=None)
    s = jnp.where(mask[:, None], s, NEG_INF)
    m = s.max(-1)
    p = jnp.exp(s - m[..., None])
    return m, p.sum(-1), jnp.einsum("bhqs,bsr->bhqr", p,
                                    ckv_b.astype(jnp.float32))


def ring_mla(qa, q_rope, ckv, krope, q_pos, kv_pos, *, axis_name, n_blocks,
             rotate="stats", window=None, scale):
    """Absorbed-MLA decode over a ring-sharded latent cache (manual-region
    local).  Returns o_lat (B,Sq,H,R); the W_uv expansion stays outside
    the ring, on the auto partitioner."""
    b, sq, h, r = qa.shape
    qa = qa.astype(jnp.float32)
    qr = q_rope.astype(jnp.float32)
    n = n_blocks

    def local_partial(ops):
        cb, kb, kp = ops
        return _mla_block_partials(qa, qr, cb, kb, q_pos, kp,
                                   window=window, scale=scale)

    shp = (n, b, h, sq)
    out = _ring_run(axis_name, n, rotate, local_partial, (ckv, krope, kv_pos),
                    (shp, shp, shp + (r,)))
    return out.transpose(0, 2, 1, 3)          # (B,H,Sq,R) -> (B,Sq,H,R)


def ring_mla_reference(qa, q_rope, ckv, krope, q_pos, kv_pos, *, n_blocks,
                       window=None, scale):
    """Single-device oracle for ``ring_mla`` (same partials, same merge)."""
    skv = ckv.shape[1]
    if skv % n_blocks:
        raise ValueError(f"Skv={skv} not divisible into {n_blocks} blocks")
    c = skv // n_blocks
    qa = qa.astype(jnp.float32)
    qr = q_rope.astype(jnp.float32)
    parts = [_mla_block_partials(qa, qr, ckv[:, j * c:(j + 1) * c],
                                 krope[:, j * c:(j + 1) * c], q_pos,
                                 kv_pos[:, j * c:(j + 1) * c],
                                 window=window, scale=scale)
             for j in range(n_blocks)]
    ms, ls, accs = (jnp.stack(x) for x in zip(*parts))
    return merge_block_partials(ms, ls, accs).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------

def _kv_quantized(cfg: ModelConfig) -> bool:
    if cfg.kv_quant == "none":
        return False
    if cfg.kv_quant != "bp8":
        raise ValueError(f"unknown kv_quant {cfg.kv_quant!r}")
    if cfg.attention_type == "mla":
        raise ValueError("kv_quant='bp8' is GQA/MQA-only; the MLA latent "
                         "cache is already compressed")
    return True


def kv_cache_spec(cfg: ModelConfig, batch: int,
                  length: int) -> Dict[str, jax.ShapeDtypeStruct]:
    """Abstract cache of ``length`` tokens for ONE attention layer."""
    quant = _kv_quantized(cfg)      # raises for mla + kv_quant='bp8'
    bl, sds = (batch, length), jax.ShapeDtypeStruct
    if cfg.attention_type == "mla":
        return {"ckv": sds(bl + (cfg.kv_lora_rank,), jnp.bfloat16),
                "krope": sds(bl + (cfg.qk_rope_head_dim,), jnp.bfloat16),
                "pos": sds(bl, jnp.int32)}
    kh, d = cfg.num_kv_heads, cfg.head_dim
    if quant:
        # int8 sign*level codes + one f32 scale per (token, kv-head): the
        # finest per-block granularity, so appends/writes never re-encode
        # neighbours and the scale pages with its tokens (same kv_seq axis)
        return {"k_codes": sds(bl + (kh, d), jnp.int8),
                "k_scale": sds(bl + (kh,), jnp.float32),
                "v_codes": sds(bl + (kh, d), jnp.int8),
                "v_scale": sds(bl + (kh,), jnp.float32),
                "pos": sds(bl, jnp.int32)}
    return {"k": sds(bl + (kh, d), jnp.bfloat16),
            "v": sds(bl + (kh, d), jnp.bfloat16),
            "pos": sds(bl, jnp.int32)}


def kv_cache_axes(cfg: ModelConfig, prefix: Tuple = ("stack",)) -> Dict[str, Tuple]:
    """Logical axis names for one layer's cache leaves (the names the
    paged block pool keys on: "batch" then "kv_seq" right after it)."""
    def ax(*names):
        return prefix + ("batch",) + names

    quant = _kv_quantized(cfg)      # raises for mla + kv_quant='bp8'
    if cfg.attention_type == "mla":
        return {"ckv": ax("kv_seq", None), "krope": ax("kv_seq", None),
                "pos": ax("kv_seq")}
    if quant:
        return {"k_codes": ax("kv_seq", "kv_heads", None),
                "k_scale": ax("kv_seq", "kv_heads"),
                "v_codes": ax("kv_seq", "kv_heads", None),
                "v_scale": ax("kv_seq", "kv_heads"),
                "pos": ax("kv_seq")}
    return {"k": ax("kv_seq", "kv_heads", None),
            "v": ax("kv_seq", "kv_heads", None),
            "pos": ax("kv_seq")}


@jax.named_scope("kv_write")
def _cache_write(cache: Dict[str, jax.Array], updates: Dict[str, jax.Array],
                 pos: jax.Array) -> Dict[str, jax.Array]:
    """Write one token (Sq=1) a row at the rows' (B,) positions ``pos``
    (requests decode at independent depths)."""
    rows = jnp.arange(cache["pos"].shape[0])
    new = {}
    for key, val in updates.items():
        new[key] = cache[key].at[rows, pos].set(
            val[:, 0].astype(cache[key].dtype))
    new["pos"] = cache["pos"].at[rows, pos].set(pos.astype(jnp.int32))
    return new


@jax.named_scope("kv_write")
def _cache_append(cache: Dict[str, jax.Array], updates: Dict[str, jax.Array],
                  q_pos: jax.Array) -> Dict[str, jax.Array]:
    """Append a contiguous chunk at slots [p0, p0+Sq) (chunked prefill).

    ``q_pos`` is the (B, Sq) position array of the chunk; rows share the
    same contiguous span, so slot addressing comes from row 0.  The caller
    guarantees p0 + Sq <= cache length (``dynamic_update_slice`` silently
    clamps out-of-range starts).
    """
    p0 = q_pos[0, 0]
    new = {}
    for key, val in updates.items():
        new[key] = jax.lax.dynamic_update_slice_in_dim(
            cache[key], val.astype(cache[key].dtype), p0, axis=1)
    new["pos"] = jax.lax.dynamic_update_slice_in_dim(
        cache["pos"], q_pos.astype(jnp.int32), p0, axis=1)
    return new


# ---------------------------------------------------------------------------
# GQA attention layer
# ---------------------------------------------------------------------------

def gqa_defs(cfg: ModelConfig, dtype=jnp.bfloat16):
    h, kh, d, dm = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.d_model
    defs = {
        "wq": linear_def(dm, h * d, "d_model", "heads", dtype),
        "wk": linear_def(dm, kh * d, "d_model", "kv_heads", dtype),
        "wv": linear_def(dm, kh * d, "d_model", "kv_heads", dtype),
        "wo": linear_def(h * d, dm, "heads", "d_model", dtype),
    }
    if cfg.qkv_bias:
        defs["bq"] = ParamDef((h * d,), ("heads",), dtype, "zeros")
        defs["bk"] = ParamDef((kh * d,), ("kv_heads",), dtype, "zeros")
        defs["bv"] = ParamDef((kh * d,), ("kv_heads",), dtype, "zeros")
    if cfg.qk_norm:
        defs["q_norm"] = norm_def(d)
        defs["k_norm"] = norm_def(d)
    return defs


def gqa_apply(p, cfg: ModelConfig, x: jax.Array, positions: jax.Array, *,
              window: Optional[int], cache: Optional[Dict] = None,
              prefix_len: Optional[jax.Array] = None,
              cross_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
              causal: bool = True, rope: bool = True):
    """Returns (out, new_cache).  Modes:
       * train: no cache
       * decode: x is (B, 1, D); each row's token is written at its
         position and attends over the cache
       * chunked prefill (Sq > 1): the chunk is appended into the cache
         at slots [p0, p0+Sq) and attends over the WHOLE cache, so a
         chunk sees every previously appended chunk
       * cross attention: cross_kv supplies (k, v) precomputed; no cache.
    """
    from repro.dist import seq as msq
    from repro.dist import tp as mtp
    b, sq, _ = x.shape
    h, kh, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    mode = cfg.matmul_mode
    ringc = msq.current_ring()
    # manual TP (inside a pipeline stage, train path only): wq/wo — and in
    # "shard" kv_mode wk/wv — hold this device's head slice; head counts
    # come from the local weight shapes so the same code runs sharded and
    # replicated.  wo's output is then a partial sum -> psum at the end.
    tpc = mtp.current_tp()
    tp_attn = (tpc is not None and tpc.shard_heads and cross_kv is None
               and cache is None)
    if tp_attn:
        # column-parallel input marker for the q (and, sharded or grouped,
        # kv) projection paths — identity fwd, see repro.dist.tp
        x = mtp.tp_gather(x, tpc)
    q = dense(x, p["wq"], mode, p.get("bq")).reshape(b, sq, -1, d)
    h_loc = q.shape[2]
    if cross_kv is None:
        k = dense(x, p["wk"], mode, p.get("bk")).reshape(b, sq, -1, d)
        v = dense(x, p["wv"], mode, p.get("bv")).reshape(b, sq, -1, d)
    else:
        k, v = cross_kv
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        if cross_kv is None:
            k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if rope and cross_kv is None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    q_pos = positions if positions.ndim == 2 else jnp.broadcast_to(
        positions[None], (b, sq))
    new_cache = cache
    out = None
    quant = cache is not None and cross_kv is None and _kv_quantized(cfg)
    if quant:
        from repro.kernels import attention as kq
        kc, ks = kq.quantize_kv(k)
        vc, vs = kq.quantize_kv(v)
        updates = {"k_codes": kc, "k_scale": ks, "v_codes": vc, "v_scale": vs}
    else:
        updates = {"k": k, "v": v}
    if cache is not None and cross_kv is None:
        # decode writes one slot a row, a chunk appends; either attends
        # over the whole cache
        new_cache = (_cache_write(cache, updates, q_pos[:, 0]) if sq == 1
                     else _cache_append(cache, updates, q_pos))
        kv_pos = new_cache["pos"]
        if quant and sq == 1 and prefix_len is None and ringc is None:
            # fused path: codes stream into the kernel and dequantise
            # in VMEM — the cache is never expanded to bf16/f32 in HBM
            from repro.kernels import attention as kq
            with jax.named_scope("attention"):
                qg = q[:, 0].reshape(b, kh, h_loc // kh,
                                     d).astype(jnp.float32)
                qg = qg / jnp.sqrt(jnp.float32(d))
                o = kq.bp8_decode_attention(
                    qg, new_cache["k_codes"], new_cache["k_scale"],
                    new_cache["v_codes"], new_cache["v_scale"],
                    kv_pos, q_pos[:, 0], window,
                    softcap=cfg.logit_softcap, causal=causal)
            out = o.reshape(b, 1, h_loc, -1)
        elif quant:
            # a chunk, or a prefix-LM or ring-sharded decode: attend the
            # dequantised cache (the fused kernel is single-device)
            from repro.kernels import attention as kq
            k_all = kq.dequantize_kv(new_cache["k_codes"],
                                     new_cache["k_scale"])
            v_all = kq.dequantize_kv(new_cache["v_codes"],
                                     new_cache["v_scale"])
        else:
            k_all, v_all = new_cache["k"], new_cache["v"]
    else:
        k_all, v_all = k, v
        kv_pos = (q_pos if cross_kv is None else
                  jnp.broadcast_to(jnp.arange(k.shape[1])[None], (b, k.shape[1])))

    if out is None:
        if tp_attn and tpc.kv_mode == mtp.KV_GROUP:
            # kv_heads < tp: wk/wv are replicated (the full k/v is cheap)
            # and each device slices the one kv head its contiguous q-head
            # block maps to — tp % kv_heads == 0 guarantees the block stays
            # inside a single kv group (plan_stage_tp)
            kvh = (mtp.tp_index(tpc) * h_loc) // (h // kh)
            k_all = jax.lax.dynamic_slice_in_dim(k_all, kvh, 1, axis=2)
            v_all = jax.lax.dynamic_slice_in_dim(v_all, kvh, 1, axis=2)
        if ringc is not None and cross_kv is None and not tp_attn:
            # sequence parallelism: ring-attend the seq-sharded KV inside
            # a manual shard_map region; falls through to plain sdpa when
            # the ambient rules leave this KV unsharded on the ring axis
            with jax.named_scope("attention"):
                out = msq.ring_attend(
                    q, k_all, v_all, q_pos, kv_pos,
                    kv_logical="kv_seq" if cache is not None else "seq",
                    causal=causal, window=window, prefix_len=prefix_len,
                    softcap=cfg.logit_softcap)
        if out is None:
            out = sdpa(q, k_all, v_all, q_pos, kv_pos,
                       causal=causal and cross_kv is None, window=window,
                       prefix_len=prefix_len, chunk=cfg.attn_chunk,
                       softcap=cfg.logit_softcap)
    out = dense(out.reshape(b, sq, h_loc * d).astype(x.dtype), p["wo"], mode)
    if tp_attn:
        out = mtp.tp_psum(out, tpc)
    return out, new_cache


# ---------------------------------------------------------------------------
# MLA (Multi-head Latent Attention) — minicpm3 / deepseek-v2
# ---------------------------------------------------------------------------

def mla_defs(cfg: ModelConfig, dtype=jnp.bfloat16):
    dm, h = cfg.d_model, cfg.num_heads
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    defs = {
        "wdkv": linear_def(dm, cfg.kv_lora_rank + cfg.qk_rope_head_dim,
                           "d_model", "lora", dtype),
        "kv_norm": norm_def(cfg.kv_lora_rank),
        "wuk": ParamDef((cfg.kv_lora_rank, h, cfg.qk_nope_head_dim),
                        ("lora", "heads", None), dtype),
        "wuv": ParamDef((cfg.kv_lora_rank, h, cfg.v_head_dim),
                        ("lora", "heads", None), dtype),
        "wo": linear_def(h * cfg.v_head_dim, dm, "heads", "d_model", dtype),
    }
    if cfg.q_lora_rank:
        defs["wdq"] = linear_def(dm, cfg.q_lora_rank, "d_model", "lora", dtype)
        defs["q_norm"] = norm_def(cfg.q_lora_rank)
        defs["wuq"] = linear_def(cfg.q_lora_rank, h * qk, "lora", "heads", dtype)
    else:
        defs["wq"] = linear_def(dm, h * qk, "d_model", "heads", dtype)
    return defs


def mla_rotary(cfg: ModelConfig):
    """(frequencies or None, cos/sin scale, softmax mscale) of MLA's rotary
    part: plain RoPE, or YaRN where ``rope_yarn_factor`` is set, whose
    softmax scale is multiplied by mscale(mscale_all_dim) squared."""
    f = cfg.rope_yarn_factor
    if not f:
        return None, 1.0, 1.0
    freqs = yarn_frequencies(cfg.qk_rope_head_dim, cfg.rope_theta, f,
                             cfg.rope_yarn_original_max_pos,
                             cfg.rope_yarn_beta_fast, cfg.rope_yarn_beta_slow)
    all_dim = yarn_mscale(f, cfg.rope_yarn_mscale_all_dim)
    return freqs, yarn_mscale(f, cfg.rope_yarn_mscale) / all_dim, all_dim


def _mla_q(p, cfg, x, tp_attn=False):
    from repro.dist import tp as mtp
    b, s, _ = x.shape
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    mode = cfg.matmul_mode
    if cfg.q_lora_rank:
        # wdq/q_norm are replicated (computed redundantly per TP shard);
        # the gather marks where the latent enters head-sharded compute
        ql = rms_norm(dense(x, p["wdq"], mode), p["q_norm"], cfg.norm_eps)
        if tp_attn:
            ql = mtp.tp_gather(ql)
        q = dense(ql, p["wuq"], mode)
    else:
        q = dense(mtp.tp_gather(x) if tp_attn else x, p["wq"], mode)
    # head count from the (possibly TP-sharded) up-projection shape
    q = q.reshape(b, s, -1, qk)
    return (q[..., : cfg.qk_nope_head_dim],
            q[..., cfg.qk_nope_head_dim:])        # (nope, rope)


def mla_apply(p, cfg: ModelConfig, x: jax.Array, positions: jax.Array, *,
              cache: Optional[Dict] = None, window=None):
    """MLA attention.  Train and chunked prefill expand K/V from the
    latent; decode (Sq == 1 with a cache) uses the absorbed formulation
    (scores in the kv_lora latent space), so the per-step cost is
    O(S * kv_lora) instead of O(S * H * head_dim).  A chunk's latents are
    appended at [p0, p0+Sq) and K/V are expanded from the WHOLE cache, so
    the chunk attends every previously appended chunk."""
    from repro.dist import tp as mtp
    b, sq, _ = x.shape
    mode = cfg.matmul_mode
    # manual TP (pipeline stage, train path): the latent projections
    # (wdq/wdkv) are replicated — every device computes the small shared
    # latent — while wuq/wuk/wuv/wo hold local head slices; wo's output is
    # a partial sum over heads -> psum.  The absorbed decode path never
    # runs under a TP context (pipelining is train-only).
    tpc = mtp.current_tp()
    tp_attn = tpc is not None and tpc.shard_heads and cache is None
    q_nope, q_rope = _mla_q(p, cfg, x, tp_attn=tp_attn)
    dkv = dense(x, p["wdkv"], mode)
    ckv = rms_norm(dkv[..., : cfg.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    krope = dkv[..., cfg.kv_lora_rank:]           # (B, S, rope_dim)
    q_pos = positions if positions.ndim == 2 else jnp.broadcast_to(
        positions[None], (b, sq))
    freqs, rot_scale, mscale = mla_rotary(cfg)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, freqs, rot_scale)
    krope = apply_rope(krope[:, :, None, :], positions, cfg.rope_theta,
                       freqs, rot_scale)[:, :, 0]

    scale = 1.0 / jnp.sqrt(jnp.float32(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim))
    scale = scale * mscale * mscale

    if cache is not None and sq == 1:
        # ---- absorbed decode ----
        new_cache = _cache_write(cache, {"ckv": ckv, "krope": krope}, q_pos[:, 0])
        kv_pos = new_cache["pos"]
        # absorb W_uk into q: qa (B,1,H,R)
        qa = jnp.einsum("bqhd,rhd->bqhr", q_nope.astype(jnp.float32),
                        p["wuk"].astype(jnp.float32))
        from repro.dist import seq as msq
        o_lat = None
        with jax.named_scope("attention"):
            if msq.current_ring() is not None:
                # sequence parallelism: ring over the seq-sharded latent
                # cache; scores and the latent accumulator stay inside the
                # manual region, the W_uv expansion below runs on the auto
                # partitioner
                o_lat = msq.ring_attend_mla(
                    qa, q_rope.astype(jnp.float32), new_cache["ckv"],
                    new_cache["krope"], q_pos, kv_pos, window=window,
                    scale=scale)
            if o_lat is None:
                ckv_all = new_cache["ckv"].astype(jnp.float32)  # (B, S, R)
                kr_all = new_cache["krope"].astype(jnp.float32)  # (B, S, P)
                s_nope = jnp.einsum("bqhr,bsr->bhqs", qa, ckv_all)
                s_rope = jnp.einsum("bqhp,bsp->bhqs",
                                    q_rope.astype(jnp.float32), kr_all)
                scores = (s_nope + s_rope) * scale
                mask = _allowed(q_pos, kv_pos, causal=True, window=window,
                                prefix_len=None)
                scores = jnp.where(mask[:, None], scores, NEG_INF)
                pr = jax.nn.softmax(scores, axis=-1)
                o_lat = jnp.einsum("bhqs,bsr->bqhr", pr, ckv_all)  # (B,1,H,R)
        out = jnp.einsum("bqhr,rhv->bqhv", o_lat, p["wuv"].astype(jnp.float32))
    else:
        # ---- expanded: train over the sequence, or a chunk appended to
        # the cache over the whole cache (bf16-stored latents, the same
        # rounding absorbed decode reads) ----
        new_cache, ckv_e, kr_e, kv_pos = None, ckv, krope, q_pos
        if cache is not None:
            new_cache = _cache_append(cache, {"ckv": ckv, "krope": krope},
                                      q_pos)
            ckv_e, kr_e = new_cache["ckv"], new_cache["krope"]
            kv_pos = new_cache["pos"]
        ckv_e = ckv_e.astype(jnp.float32)
        kr_e = kr_e.astype(jnp.float32)
        if tp_attn:
            # the shared latents enter head-sharded compute here: the k/v
            # expansions and (kr broadcast into k) per-head scores
            ckv_e = mtp.tp_gather(ckv_e, tpc)
            kr_e = mtp.tp_gather(kr_e, tpc)
        k_nope = jnp.einsum("bsr,rhd->bshd", ckv_e,
                            p["wuk"].astype(jnp.float32))
        v = jnp.einsum("bsr,rhv->bshv", ckv_e,
                       p["wuv"].astype(jnp.float32))
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(kr_e[:, :, None, :],
                                      k_nope.shape[:3] + (cfg.qk_rope_head_dim,))],
            axis=-1)
        q = jnp.concatenate([q_nope.astype(jnp.float32),
                             q_rope.astype(jnp.float32)], axis=-1)
        if mscale != 1.0:
            q = q * (mscale * mscale)
        out = sdpa(q, k, v, q_pos, kv_pos, causal=True, window=window,
                   chunk=cfg.attn_chunk)
    out = out.reshape(b, sq, -1).astype(x.dtype)
    out = dense(out, p["wo"], mode)
    if tp_attn:
        out = mtp.tp_psum(out, tpc)
    return out, new_cache
