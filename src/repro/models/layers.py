"""Common layers: norms, rotary embeddings, dense/matmul dispatch, MLP.

The matmul dispatch (``dense``) is where the paper's technique plugs into
every architecture: ``matmul_mode='bp8'`` routes the contraction through the
OISMA-simulated Bent-Pyramid matmul (bit-exact bitplane formulation with a
straight-through gradient), ``'fp8'`` through the paper's E4M3 baseline,
``'bf16'`` through the native MXU path.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import bp_matmul as _bpm
from repro.core import quantize as _q
from repro.models.params import ParamDef


# ---------------------------------------------------------------------------
# matmul dispatch
# ---------------------------------------------------------------------------

def dense(x: jax.Array, w: jax.Array, mode: str = "bf16",
          bias: Optional[jax.Array] = None) -> jax.Array:
    """x: (..., K) @ w: (K, N) under the configured matmul mode, in the
    name scope ``dense.<mode>`` (the operand conversions with it)."""
    with jax.named_scope(f"dense.{mode}"):
        if mode == "bf16":
            y = jnp.einsum("...k,kn->...n", x, w.astype(x.dtype))
        elif mode in ("bp8", "bp8_lowrank"):
            impl = "bitplane" if mode == "bp8" else "lowrank"
            lead = x.shape[:-1]
            x2 = _q.exact_f32(x.reshape(-1, x.shape[-1]))
            y = _bpm.bp_matmul_ste(x2, w.astype(jnp.float32), impl=impl)
            y = y.reshape(*lead, w.shape[-1]).astype(x.dtype)
        elif mode == "bp8_fused":
            from repro.kernels import ops as _kops
            lead = x.shape[:-1]
            x2 = _q.exact_f32(x.reshape(-1, x.shape[-1]))
            y = _kops.oisma_matmul_ste(x2, w.astype(jnp.float32))
            y = y.reshape(*lead, w.shape[-1]).astype(x.dtype)
        elif mode == "fp8":
            xq = _q.fake_quantize_e4m3(x.astype(jnp.float32))
            wq = _q.fake_quantize_e4m3(w.astype(jnp.float32))
            y = jnp.einsum("...k,kn->...n", xq, wq).astype(x.dtype)
        else:
            raise ValueError(f"unknown matmul mode {mode!r}")
        if bias is not None:
            y = y + bias.astype(y.dtype)
        return y


def linear_def(d_in: int, d_out: int, in_axis: str, out_axis: str,
               dtype=jnp.bfloat16, scale: float = 1.0) -> ParamDef:
    return ParamDef((d_in, d_out), (in_axis, out_axis), dtype, "normal", scale)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, gamma: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + gamma.astype(jnp.float32))).astype(x.dtype)


def layer_norm(x: jax.Array, gamma: jax.Array, beta: jax.Array,
               eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * gamma.astype(jnp.float32) + beta.astype(jnp.float32)).astype(x.dtype)


def norm_def(d: int) -> ParamDef:
    return ParamDef((d,), (None,), jnp.float32, "zeros")


def ln_defs(d: int):
    return {"gamma": ParamDef((d,), (None,), jnp.float32, "ones"),
            "beta": ParamDef((d,), (None,), jnp.float32, "zeros")}


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's magnitude correction for a context stretched ``factor``-fold."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(head_dim: int, theta: float, factor: float,
                     original_max_pos: int, beta_fast: float,
                     beta_slow: float) -> jax.Array:
    """YaRN's rotary frequencies (arXiv:2309.00071, as DeepSeek-V2 applies
    them): the original frequencies where a channel turns more than
    ``beta_fast`` times over the original context, the frequencies divided
    by ``factor`` where it turns fewer than ``beta_slow`` times, and a
    linear ramp between."""
    def channel(turns):
        return (head_dim * math.log(original_max_pos / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(channel(beta_fast)), 0)
    high = min(math.ceil(channel(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    extra = rope_frequencies(head_dim, theta)
    return extra / factor * ramp + extra * (1.0 - ramp)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               freqs: Optional[jax.Array] = None,
               scale: float = 1.0) -> jax.Array:
    """x: (B, S, H, D); positions: (B, S) or (S,).  ``freqs`` (D/2,)
    replaces the plain frequencies of ``theta`` (YaRN), and ``scale``
    multiplies cos and sin."""
    d = x.shape[-1]
    if freqs is None:
        freqs = rope_frequencies(d, theta)                   # (D/2,)
    pos = positions.astype(jnp.float32)
    if pos.ndim == 1:
        pos = pos[None, :]
    angles = pos[..., None] * freqs[None, None, :]           # (B, S, D/2)
    sin = jnp.sin(angles)[:, :, None, :]
    cos = jnp.cos(angles)[:, :, None, :]
    if scale != 1.0:
        sin, cos = sin * scale, cos * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# activations / MLP
# ---------------------------------------------------------------------------

def activation(x: jax.Array, kind: str) -> jax.Array:
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        return jax.nn.gelu(x, approximate=True)
    if kind == "relu":
        return jax.nn.relu(x)
    raise ValueError(kind)


def mlp_defs(d_model: int, d_ff: int, gated: bool, dtype=jnp.bfloat16):
    defs = {
        "up": linear_def(d_model, d_ff, "d_model", "ffn", dtype),
        "down": linear_def(d_ff, d_model, "ffn", "d_model", dtype),
    }
    if gated:
        defs["gate"] = linear_def(d_model, d_ff, "d_model", "ffn", dtype)
    return defs


def mlp_apply(p, x: jax.Array, act: str, gated: bool, mode: str) -> jax.Array:
    from repro.dist import tp as mtp
    # manual TP (inside a pipeline stage): up/gate are column-parallel over
    # the ffn dim, so `down` is row-parallel and its output a partial sum
    tpc = mtp.current_tp()
    tp_on = tpc is not None and tpc.shard_ffn
    if tp_on:
        x = mtp.tp_gather(x, tpc)
    if mode in ("bp8", "bp8_fused") and gated and act in ("silu", "gelu",
                                                         "relu"):
        # both BP modes keep the gated MLP in f32 and cast once at the end.
        # bp8_fused runs it as one grid: up/gate share one in-kernel BP
        # encode of x and the two (tokens, d_ff) projections never reach
        # HBM.  bp8 is its jnp reference, with the same rounding order.
        with jax.named_scope(f"dense.{mode}"):
            lead = x.shape[:-1]
            x2 = _q.exact_f32(x.reshape(-1, x.shape[-1]))
            w_up = p["up"].astype(jnp.float32)
            w_gate = p["gate"].astype(jnp.float32)
            if mode == "bp8_fused":
                from repro.kernels import ops as _kops
                up = _kops.oisma_mlp_ste(x2, w_up, w_gate, act=act)
            else:
                up = (activation(_bpm.bp_matmul_ste(x2, w_gate), act)
                      * _bpm.bp_matmul_ste(x2, w_up))
            up = up.reshape(*lead, p["up"].shape[-1]).astype(x.dtype)
    else:
        up = dense(x, p["up"], mode)
        if gated:
            up = activation(dense(x, p["gate"], mode), act) * up
        else:
            up = activation(up, act)
    out = dense(up, p["down"], mode)
    if tp_on:
        out = mtp.tp_psum(out, tpc)
    return out


# ---------------------------------------------------------------------------
# embeddings / unembedding with chunked cross-entropy
# ---------------------------------------------------------------------------

def embed_def(vocab: int, d_model: int, dtype=jnp.bfloat16) -> ParamDef:
    return ParamDef((vocab, d_model), ("vocab", "d_model"), dtype, "embed")


def embed_lookup(table: jax.Array, ids: jax.Array, scale: bool = False) -> jax.Array:
    out = jnp.take(table, ids, axis=0)
    if scale:
        out = out * jnp.sqrt(jnp.float32(table.shape[-1])).astype(out.dtype)
    return out


def chunked_softmax_xent(h: jax.Array, embed: jax.Array, labels: jax.Array,
                         mask: jax.Array, chunk: int = 512,
                         softcap: Optional[float] = None) -> Tuple[jax.Array, jax.Array]:
    """Cross-entropy over a large vocab without materialising (B, S, V).

    Scans over sequence chunks; inside each chunk the (B, c, V) logits exist
    only transiently (XLA fuses the reduction).  Returns (sum_loss, sum_mask).
    """
    b, s, d = h.shape
    chunk = min(chunk, s)
    n = s // chunk
    rem = s - n * chunk

    def chunk_loss(hc, lc, mc):
        logits = jnp.einsum("bsd,vd->bsv", hc.astype(jnp.float32),
                            embed.astype(jnp.float32))
        if softcap:
            logits = jnp.tanh(logits / softcap) * softcap
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return ((lse - gold) * mc).sum()

    def body(acc, args):
        hc, lc, mc = args
        return acc + chunk_loss(hc, lc, mc), None

    hs = h[:, : n * chunk].reshape(b, n, chunk, d).transpose(1, 0, 2, 3)
    ls = labels[:, : n * chunk].reshape(b, n, chunk).transpose(1, 0, 2)
    ms = mask[:, : n * chunk].reshape(b, n, chunk).transpose(1, 0, 2)
    total, _ = jax.lax.scan(body, jnp.float32(0.0), (hs, ls, ms))
    if rem:
        total = total + chunk_loss(h[:, n * chunk:], labels[:, n * chunk:],
                                   mask[:, n * chunk:])
    return total, mask.sum()
