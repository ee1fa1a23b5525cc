"""Plain reference of a GQA decoder LM, replayed over the calls that a
serving window made, in the configuration's arithmetic.

A bf16 model (``matmul_mode="bf16"``, ``kv_quant="none"``) is computed in
float32 (``Precision.HIGHEST``), rounding to bf16 where the model stores
bf16: activations between layers, each projection's output, the cache.
Its rows do not depend on each other, so only the requests compared are
computed.

A BP8 model (``bp8_fused`` matmuls, ``kv_quant="bp8"``): BP8, the OISMA
paper's Bent-Pyramid format, maps ``|x| / max|x| * 10`` to one of ten
levels, ``level = clip(round(.), 0, 9)``, keeps the sign, and multiplies
two levels ``a`` (activation, right-biased dataset) and ``b`` (weight,
left-biased dataset) as ``popcount(R[a] & L[b])``, the AND of two fixed
10-bit streams; a matmul sums those integers over K and scales the sum
by ``max|x| * max|w| / 10``.  A matmul's activation scale is the maximum
over every row of the batch it is called with, so what a row computes
depends on the rows it shares a call with: the reference replays the
whole window, each call's rows sharing one scale per layer.  The KV
cache holds BP levels of ``k`` and ``v`` against their own ``max|.|``
per token and KV head.

Both: RMSNorm with ``1 + gain``, rotary embeddings on the two halves of
each head, causal softmax attention, a SiLU-gated MLP, bf16 residual
adds, f32 logits.  Nothing here imports the program or uses an array it
made.

The control (``control=True``) is the same computation one precision
step below the configuration's: int8 (per-row activations, per-tensor
weights, per token and head in the cache) for a bf16 model, BP8 codes
held in int4 (levels clipped at 7) for a BP8 one.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Sequence, Tuple

import numpy as np

# Bent-Pyramid datasets: the block of ones (first, last bit, 0 = left-most)
# of each level 1..9 in the 10-bit stream (OISMA paper, Fig. 3, and the
# construction documented with this repository's BP datasets).
RIGHT_BLOCKS = ((6, 6), (5, 6), (5, 7), (4, 7), (4, 8), (4, 9), (3, 9),
                (2, 9), (1, 9))
LEFT_BLOCKS = ((3, 3), (3, 4), (3, 5), (2, 5), (1, 5), (1, 6), (0, 6),
               (0, 7), (0, 8))
BITS = 10
ROW_BLOCK = 2048
ROWS_BUCKET = 16384
CALLS_BUCKET = 1024
TINY = float(np.finfo(np.float32).tiny)


def bitstreams(blocks) -> np.ndarray:
    """(10, 10) 0/1 table: row = level, column = bit."""
    t = np.zeros((BITS, BITS), np.float32)
    for level, (a, b) in enumerate(blocks, start=1):
        t[level, a:b + 1] = 1.0
    return t


# ---------------------------------------------------------------------------
# the rows a window ran
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Call:
    """One jitted model call: ``tokens`` and ``positions`` (rows, length)."""
    tokens: np.ndarray
    positions: np.ndarray


@dataclasses.dataclass
class Plan:
    tokens: np.ndarray           # (R,) input token of each row
    positions: np.ndarray        # (R,)
    calls: np.ndarray            # (R,) index of the call that ran the row
    n_calls: int
    requests: List[Tuple[int, int, int]]   # (rid, first row, rows)
    solo: Tuple[int, int]        # (first row, rows) attending only to itself
    compare_rows: np.ndarray     # rows whose logits gave a served token
    compare_tokens: np.ndarray   # the served token of each
    compare_rids: np.ndarray


class ReplayError(ValueError):
    """The calls do not account for the served tokens."""


def plan(calls: Sequence[Call], sequences: Dict[int, np.ndarray],
         prompts: Dict[int, int], finished: Sequence[int],
         served: Dict[int, Sequence[int]], pad_to: int = ROW_BLOCK,
         whole_calls: bool = True) -> Plan:
    """Attribute every row of every call to a request and position.

    ``sequences[rid]`` is the prompt followed by the served tokens, so the
    row at position ``p`` of request ``rid`` reads ``sequences[rid][p]``.
    With ``whole_calls`` (BP8, whose rows depend on the rows they share a
    call with) every row must be accounted for: a row that belongs to no
    request and starts at position 0 is a fresh sequence of its own (a
    padding row; identical such rows of one call are one row), any other
    is an error.  Without it only the requests in ``sequences`` are kept
    and other rows are passed over; a kept request whose rows do not
    account for its served tokens is still an error."""
    next_pos = {rid: 0 for rid in sequences}
    at: Dict[int, List[int]] = {0: sorted(sequences)}   # next position -> rids
    rows_of: Dict[int, List[int]] = {rid: [] for rid in sequences}
    solo: List[Tuple[int, int]] = []          # (token, call)
    for c, call in enumerate(calls):
        taken = set()
        seen_solo = set()
        for toks, pos in zip(call.tokens, call.positions):
            p0, n = int(pos[0]), len(toks)
            if np.any(pos != p0 + np.arange(n)):
                raise ReplayError(f"call {c}: positions not contiguous")
            # a request's last served token is never fed back
            rid = next((r for r in at.get(p0, ())
                        if r not in taken and len(sequences[r]) > p0 + n
                        and np.array_equal(sequences[r][p0:p0 + n], toks)),
                       None)
            if rid is None and not whole_calls:
                continue
            if rid is None:
                if p0 != 0 or n != 1:
                    raise ReplayError(
                        f"call {c}: a row at position {p0} of {n} tokens "
                        "matches no request")
                if int(toks[0]) not in seen_solo:
                    seen_solo.add(int(toks[0]))
                    solo.append((int(toks[0]), c))
                continue
            taken.add(rid)
            rows_of[rid].extend([c] * n)
            at[p0].remove(rid)
            next_pos[rid] += n
            at.setdefault(next_pos[rid], []).append(rid)
    tokens, positions, call_ids, requests = [], [], [], []
    cmp_rows, cmp_tokens, cmp_rids = [], [], []
    for rid in sorted(rows_of):
        n = len(rows_of[rid])
        if n == 0:
            continue
        first = len(tokens)
        requests.append((rid, first, n))
        tokens.extend(sequences[rid][:n])
        positions.extend(range(n))
        call_ids.extend(rows_of[rid])
        if rid in finished:
            p, out = prompts[rid], list(served[rid])
            if n != p + len(out) - 1:
                raise ReplayError(
                    f"request {rid}: {n} rows ran for a prompt of {p} and "
                    f"{len(out)} served tokens")
            cmp_rows.extend(first + p - 1 + i for i in range(len(out)))
            cmp_tokens.extend(out)
            cmp_rids.extend([rid] * len(out))
    solo_first = len(tokens)
    for tok, c in solo:
        tokens.append(tok)
        positions.append(0)
        call_ids.append(c)
    n_calls = len(calls)
    pad = (-len(tokens)) % pad_to
    tokens.extend([0] * pad)
    positions.extend([0] * pad)
    call_ids.extend([n_calls] * pad)            # a call of their own
    return Plan(np.asarray(tokens, np.int32), np.asarray(positions, np.int32),
                np.asarray(call_ids, np.int32), n_calls + 1, requests,
                (solo_first, len(solo) + pad),
                np.asarray(cmp_rows, np.int32),
                np.asarray(cmp_tokens, np.int32),
                np.asarray(cmp_rids, np.int32))


# ---------------------------------------------------------------------------
# the arithmetic
# ---------------------------------------------------------------------------

def _jax():
    import jax
    import jax.numpy as jnp
    return jax, jnp


def rms_norm(x, gain, eps):
    jax, jnp = _jax()
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * (1.0 + gain.astype(jnp.float32))).astype(jnp.bfloat16)


def levels(a, scale, cap: int):
    _, jnp = _jax()
    return jnp.clip(jnp.round(jnp.abs(a) / scale * 10.0), 0.0, float(cap))


def planes(a, scale, table, cap: int):
    """Signed bitstreams of ``a`` (..., K) -> (..., K, 10) in bf16."""
    _, jnp = _jax()
    lv = levels(a, scale, cap).astype(jnp.int32)
    bits = jnp.asarray(table, jnp.bfloat16)[lv]
    return bits * jnp.sign(a).astype(jnp.bfloat16)[..., None]


def weight_planes(w, cap: int):
    """(K, N) weight -> ((K * 10, N) bf16 planes, its scale)."""
    _, jnp = _jax()
    wf = w.astype(jnp.float32)
    sw = jnp.maximum(jnp.max(jnp.abs(wf)), TINY)
    wp = planes(wf, sw, bitstreams(LEFT_BLOCKS), cap)      # (K, N, 10)
    k, n = w.shape
    return wp.transpose(0, 2, 1).reshape(k * BITS, n), sw


def bp_matmul(x, s_row, wp, sw, cap: int):
    """BP8 ``x @ w``: x (M, K) f32 with per-row scale (M, 1)."""
    jax, jnp = _jax()
    m, k = x.shape
    xp = planes(x, s_row, bitstreams(RIGHT_BLOCKS), cap).reshape(m, k * BITS)
    acc = jnp.dot(xp, wp, preferred_element_type=jnp.float32)
    return acc * ((s_row * sw) * 0.1)


def prepare_weight(w, arith: Tuple):
    """The weight as ``matmul`` reads it, and its scale."""
    jax, jnp = _jax()
    kind, cap = arith
    if kind == "bp8":
        return weight_planes(w, cap)
    wf = w.astype(jnp.float32)
    if kind == "f32":
        return wf, jnp.float32(1.0)
    sw = jnp.maximum(jnp.max(jnp.abs(wf)), TINY) / 127.0      # int8
    return jnp.round(wf / sw), sw


def matmul(x, s_row, wprep, arith: Tuple):
    """``x @ w`` for f32 rows ``x`` in the reference's arithmetic:
    ``bp8`` (per-call scale ``s_row``), ``f32``, or ``int8`` (the control
    of an f32 model: per-row activation scale, per-tensor weight scale)."""
    jax, jnp = _jax()
    kind, cap = arith
    w, sw = wprep
    if kind == "bp8":
        return bp_matmul(x, s_row, w, sw, cap)
    hi = jax.lax.Precision.HIGHEST
    if kind == "f32":
        return jnp.dot(x, w, precision=hi)
    sx = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True),
                     TINY) / 127.0
    return jnp.dot(jnp.round(x / sx), w, precision=hi) * sx * sw


def kv_values(x, arith: Tuple):
    """(R, KH, D) bf16 -> the f32 values the KV cache gives back."""
    _, jnp = _jax()
    kind, cap = arith
    if kind == "bp8":
        return kv_dequantised(x, cap)
    xf = x.astype(jnp.float32)
    if kind == "f32":
        return xf
    s = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1, keepdims=True),
                    TINY) / 127.0
    return jnp.round(xf / s) * s


def arithmetic(sizes: Dict, control: bool = False) -> Tuple:
    """The configuration's arithmetic, or its control one step below:
    BP8 codes held in int4 (levels clipped at 7) for a BP8 model, int8
    for a bf16 one."""
    bp8 = sizes["matmul_mode"] == "bp8_fused"
    if bp8 != (sizes.get("kv_quant", "none") == "bp8"):
        raise ValueError("the reference takes BP8 matmuls with a BP8 cache, "
                         "or neither")
    if bp8:
        return ("bp8", 7 if control else 9)
    return ("int8", 0) if control else ("f32", 0)


def _by_blocks(fn, *arrays):
    """Apply ``fn`` to row blocks of ``arrays`` (R divisible by ROW_BLOCK)."""
    jax, jnp = _jax()
    r = arrays[0].shape[0]
    blocked = [a.reshape((r // ROW_BLOCK, ROW_BLOCK) + a.shape[1:])
               for a in arrays]
    out = jax.lax.map(lambda xs: fn(*xs), blocked)
    return jax.tree.map(lambda o: o.reshape((r,) + o.shape[2:]), out)


def call_scales(h, calls, n_calls: int):
    """Per-row scale: max |h| over the rows of the row's call (f32)."""
    jax, jnp = _jax()
    row_max = jnp.max(jnp.abs(h.astype(jnp.float32)), axis=-1)
    seg = jax.ops.segment_max(row_max, calls, num_segments=n_calls)
    return jnp.maximum(seg, TINY)[calls][:, None]


def rope(x, positions, theta: float):
    _, jnp = _jax()
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(jnp.bfloat16)


def kv_dequantised(x, cap: int):
    """(R, KH, D) bf16 -> the f32 values the BP8 cache gives back."""
    _, jnp = _jax()
    xf = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1, keepdims=True), TINY)
    codes = jnp.sign(xf) * levels(xf, s, cap)
    return codes / 10.0 * s


def _dims(sizes: Dict) -> Tuple:
    return (int(sizes["num_heads"]), int(sizes["num_kv_heads"]),
            int(sizes["head_dim"]), float(sizes["rope_theta"]),
            float(sizes["norm_eps"]))


def _jit(**kw):
    import jax
    return functools.partial(jax.jit, **kw)


@_jit(static_argnames=("dims", "n_calls", "arith"))
def _attn_in(x, lp, calls, positions, *, dims, n_calls, arith):
    _, jnp = _jax()
    heads, kv_heads, head_dim, theta, eps = dims
    h = rms_norm(x, lp["ln1"], eps)
    s = call_scales(h, calls, n_calls)
    outs = []
    for name in ("wq", "wk", "wv"):
        w = prepare_weight(lp["attn"][name], arith)
        outs.append(_by_blocks(
            lambda hb, sb: matmul(hb.astype(jnp.float32), sb, w,
                                  arith).astype(jnp.bfloat16), h, s))
    r = x.shape[0]
    q = rope(outs[0].reshape(r, heads, head_dim), positions, theta)
    k = rope(outs[1].reshape(r, kv_heads, head_dim), positions, theta)
    v = outs[2].reshape(r, kv_heads, head_dim)
    return q, kv_values(k, arith), kv_values(v, arith)


@_jit(static_argnames=("dims", "bucket"))
def _attend(q, k, v, first, n, *, dims, bucket):
    """Causal attention of one request's rows [first, first + n)."""
    jax, jnp = _jax()
    heads, kv_heads, head_dim, _, _ = dims
    hi = jax.lax.Precision.HIGHEST
    g = heads // kv_heads
    qs = jax.lax.dynamic_slice_in_dim(q, first, bucket, 0)
    ks = jax.lax.dynamic_slice_in_dim(k, first, bucket, 0)
    vs = jax.lax.dynamic_slice_in_dim(v, first, bucket, 0)
    qf = qs.astype(jnp.float32).reshape(bucket, kv_heads, g, head_dim)
    qf = qf / jnp.sqrt(jnp.float32(head_dim))
    s = jnp.einsum("qhgd,khd->hgqk", qf, ks, precision=hi)
    i = jnp.arange(bucket)
    ok = (i[None, :] <= i[:, None]) & (i[None, :] < n)
    s = jnp.where(ok[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hgqk,khd->qhgd", p, vs, precision=hi)
    return o.reshape(bucket, -1).astype(jnp.bfloat16)


@_jit(static_argnames=("dims",))
def _solo(v, *, dims):
    """Every row as if it attended only to itself: its own v."""
    _, jnp = _jax()
    heads, kv_heads = dims[0], dims[1]
    o = jnp.repeat(v[:, :, None, :], heads // kv_heads, axis=2)
    return o.reshape(v.shape[0], -1).astype(jnp.bfloat16)


@_jit(static_argnames=("dims", "n_calls", "arith"))
def _attn_out_mlp(x, o, lp, calls, *, dims, n_calls, arith):
    jax, jnp = _jax()
    eps, bf16 = dims[4], jnp.bfloat16
    s = call_scales(o, calls, n_calls)
    w = prepare_weight(lp["attn"]["wo"], arith)
    x = x + _by_blocks(lambda ob, sb: matmul(
        ob.astype(jnp.float32), sb, w, arith).astype(bf16), o, s)
    m = lp["mlp"]
    h = rms_norm(x, lp["ln2"], eps)
    s = call_scales(h, calls, n_calls)
    up = prepare_weight(m["up"], arith)
    gate = prepare_weight(m["gate"], arith)

    def gated(hb, sb):
        hf = hb.astype(jnp.float32)
        u = matmul(hf, sb, up, arith)
        g = matmul(hf, sb, gate, arith)
        if arith[0] != "bp8":
            # a bf16 model rounds each projection; BP8's fused MLP keeps
            # both in f32 up to the product
            u, g = (a.astype(bf16).astype(jnp.float32) for a in (u, g))
        return (g * jax.nn.sigmoid(g) * u).astype(bf16)

    f = _by_blocks(gated, h, s)
    s = call_scales(f, calls, n_calls)
    down = prepare_weight(m["down"], arith)
    return x + _by_blocks(lambda fb, sb: matmul(
        fb.astype(jnp.float32), sb, down, arith).astype(bf16), f, s)


@_jit(static_argnames=("dims",))
def _logit_stats(x, final_norm, head, probe, *, dims):
    """(max, argmax, logit at each probe token) of the rows ``x``."""
    jax, jnp = _jax()
    h = rms_norm(x, final_norm, dims[4])
    logits = jnp.dot(h.astype(jnp.float32), head.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    at = jnp.take_along_axis(logits, probe, axis=1)
    return jnp.max(logits, axis=1), jnp.argmax(logits, axis=1), at


def replay(sizes: Dict, params, p: Plan, probes: Sequence[np.ndarray] = (),
           control: bool = False) -> Dict[str, np.ndarray]:
    """Logit statistics of every compared row: ``max``, ``argmax`` and
    ``at`` (rows, len(probes)), the logit of each probe's token.
    ``control``: in the arithmetic one step below the configuration's."""
    jax, jnp = _jax()
    dims = _dims(sizes)
    arith = arithmetic(sizes, control)
    biggest = max([1] + [1 << (n - 1).bit_length() for _, _, n in p.requests])
    # rows are padded by the longest bucket, so that a request's slice
    # never runs off the end; those rows join the padding call
    # the row count and the call count are rounded up to buckets, so that
    # runs of one cell reuse the same compiled programs
    extra = biggest + (-(len(p.tokens) + biggest)) % ROWS_BUCKET
    pad_call = p.n_calls - 1
    n_calls = -(-p.n_calls // CALLS_BUCKET) * CALLS_BUCKET
    tokens = jnp.asarray(np.pad(p.tokens, (0, extra)))
    positions = jnp.asarray(np.pad(p.positions, (0, extra)))
    calls = jnp.asarray(np.pad(p.calls, (0, extra),
                               constant_values=pad_call))
    total = len(p.tokens) + extra
    pieces, order, at = [], np.empty(total, np.int64), 0
    for _, first, n in p.requests:
        b = 1 << (n - 1).bit_length()
        order[first:first + n] = at + np.arange(n)
        pieces.append((first, n, b))
        at += b
    s0 = p.solo[0]
    order[s0:] = at + np.arange(s0, total)
    order = jnp.asarray(order)
    x = jnp.take(params["embed"], tokens, axis=0)
    for layer in range(sizes["num_layers"]):
        lp = jax.tree.map(lambda a: a[layer], params["layers"])
        q, k, v = _attn_in(x, lp, calls, positions, dims=dims,
                           n_calls=n_calls, arith=arith)
        outs = [_attend(q, k, v, jnp.int32(first), jnp.int32(n), dims=dims,
                        bucket=b) for first, n, b in pieces]
        outs.append(_solo(v, dims=dims))
        o = jnp.take(jnp.concatenate(outs, axis=0), order, axis=0)
        x = _attn_out_mlp(x, o, lp, calls, dims=dims, n_calls=n_calls,
                          arith=arith)
        del q, k, v, o, outs
    n_cmp = len(p.compare_rows)
    probe = (np.stack([np.asarray(t, np.int32) for t in probes], axis=1)
             if probes else np.zeros((n_cmp, 1), np.int32))
    mx, am, vals = [], [], []
    for lo in range(0, n_cmp, ROW_BLOCK):
        idx = p.compare_rows[lo:lo + ROW_BLOCK]
        m = len(idx)
        a, b, c = _logit_stats(
            jnp.take(x, jnp.asarray(np.pad(idx, (0, ROW_BLOCK - m))), axis=0),
            params["final_norm"], params["head"],
            jnp.asarray(np.pad(probe[lo:lo + ROW_BLOCK],
                               ((0, ROW_BLOCK - m), (0, 0)))), dims=dims)
        mx.append(np.asarray(a)[:m])
        am.append(np.asarray(b)[:m])
        vals.append(np.asarray(c)[:m])
    if not mx:
        return {"max": np.zeros(0), "argmax": np.zeros(0, np.int64),
                "at": np.zeros((0, probe.shape[1]))}
    return {"max": np.concatenate(mx), "argmax": np.concatenate(am),
            "at": np.concatenate(vals)}

