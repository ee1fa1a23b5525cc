"""State-space and recurrent blocks: Mamba2 (SSD) and xLSTM (mLSTM/sLSTM).

Mamba2 uses the chunked SSD formulation (quadratic only within a chunk,
linear across chunks) — both training/prefill and O(1)-state decode steps
are provided.  mLSTM uses the analogous chunkwise-parallel form with
max-stabilised exponential gating; sLSTM is inherently sequential and scans
over time.  These blocks give the zamba2/xlstm architectures their
sub-quadratic long-context behaviour (long_500k decode carries constant-size
state instead of a KV cache).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import dense, linear_def, rms_norm
from repro.models.params import ParamDef


def _segsum(a: jax.Array) -> jax.Array:
    """a: (..., Q) -> (..., Q, Q) with S[i, j] = sum_{k=j+1..i} a_k (i >= j)."""
    q = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    s = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((q, q), bool))
    return jnp.where(mask, s, -jnp.inf)


# ---------------------------------------------------------------------------
# Mamba2
# ---------------------------------------------------------------------------

def mamba2_dims(cfg: ModelConfig) -> Tuple[int, int, int]:
    d_inner = cfg.ssm_expand * cfg.d_model
    nheads = d_inner // cfg.ssm_headdim
    return d_inner, nheads, cfg.ssm_state


def mamba2_defs(cfg: ModelConfig, dtype=jnp.bfloat16):
    d_inner, nheads, n = mamba2_dims(cfg)
    conv_dim = d_inner + 2 * n
    return {
        "in_proj": linear_def(cfg.d_model, 2 * d_inner + 2 * n + nheads,
                              "d_model", "ffn", dtype),
        "conv_w": ParamDef((cfg.ssm_conv, conv_dim), ("conv", "ffn"), dtype),
        "conv_b": ParamDef((conv_dim,), ("ffn",), dtype, "zeros"),
        "a_log": ParamDef((nheads,), ("heads",), jnp.float32, "zeros"),
        "dt_bias": ParamDef((nheads,), ("heads",), jnp.float32, "zeros"),
        "d_skip": ParamDef((nheads,), ("heads",), jnp.float32, "ones"),
        "norm": ParamDef((d_inner,), (None,), jnp.float32, "zeros"),
        "out_proj": linear_def(d_inner, cfg.d_model, "ffn", "d_model", dtype),
    }


def _ssd_chunked(x, dt, a, b, c, chunk: int, h0=None, decay_bf16=False):
    """SSD scan.  x: (B,S,H,P) dt: (B,S,H) a: (H,) b,c: (B,S,N).

    Returns (y, h_final) with h: (B,H,P,N).  ``decay_bf16`` stores the
    (B,H,Nc,Q,Q) intra-chunk decay matrix in bf16 — it is the dominant
    training-time activation for mamba2 layers (values in [0,1], so the
    precision cost is ~1e-3 relative; see EXPERIMENTS.md §Perf B)."""
    bs, s, h, p = x.shape
    n = b.shape[-1]
    q = min(chunk, s)
    assert s % q == 0, (s, q)
    nc = s // q
    xr = x.reshape(bs, nc, q, h, p)
    dtr = dt.reshape(bs, nc, q, h)
    br = b.reshape(bs, nc, q, n)
    cr = c.reshape(bs, nc, q, n)
    da = dtr * a[None, None, None, :]                  # (B,Nc,Q,H) log-decay
    da_h = da.transpose(0, 3, 1, 2)                    # (B,H,Nc,Q)
    cs = jnp.cumsum(da_h, axis=-1)                     # (B,H,Nc,Q)
    xdt = xr * dtr[..., None]                          # input * dt

    # intra-chunk (diagonal blocks)
    L = jnp.exp(_segsum(da_h))                         # (B,H,Nc,Q,Q)
    if decay_bf16:
        L = L.astype(jnp.bfloat16)
        y_diag = jnp.einsum("bcln,bcsn,bhcls,bcshp->bclhp",
                            cr.astype(jnp.bfloat16), br.astype(jnp.bfloat16),
                            L, xdt.astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32)
    else:
        y_diag = jnp.einsum("bcln,bcsn,bhcls,bcshp->bclhp", cr, br, L, xdt)

    # per-chunk final states
    decay_states = jnp.exp(cs[..., -1:] - cs)          # (B,H,Nc,Q)
    states = jnp.einsum("bcsn,bhcs,bcshp->bchpn", br, decay_states, xdt)

    # inter-chunk recurrence
    chunk_decay = jnp.exp(cs[..., -1])                 # (B,H,Nc)

    def scan_fn(hprev, inp):
        st, dec = inp                                  # (B,H,P,N), (B,H)
        hnew = hprev * dec[..., None, None] + st
        return hnew, hprev

    init = h0 if h0 is not None else jnp.zeros((bs, h, p, n), jnp.float32)
    hfin, hprevs = jax.lax.scan(
        scan_fn, init,
        (states.transpose(1, 0, 2, 3, 4).astype(jnp.float32),  # (Nc,B,H,P,N)
         chunk_decay.transpose(2, 0, 1)))
    # off-diagonal contribution from previous chunks' state
    y_off = jnp.einsum("bcln,bhcl,cbhpn->bclhp", cr, jnp.exp(cs),
                       hprevs)
    y = (y_diag + y_off).reshape(bs, s, h, p)
    return y, hfin


@jax.named_scope("ssm")
def mamba2_apply(p, cfg: ModelConfig, x: jax.Array,
                 state: Optional[Dict] = None, chunk: int = 256):
    """x: (B,S,D). state (decode): {'conv': (B,W-1,convdim), 'ssm': (B,H,P,N)}.

    Returns (y, new_state).  For S > 1 with state given (prefill), the final
    state is emitted for subsequent decode."""
    bs, s, _ = x.shape
    d_inner, nheads, n = mamba2_dims(cfg)
    conv_dim = d_inner + 2 * n
    proj = dense(x, p["in_proj"], cfg.matmul_mode)
    z, xbc, dtp = jnp.split(proj, [d_inner, d_inner + conv_dim], axis=-1)

    # depthwise causal conv over xbc
    w = p["conv_w"].astype(jnp.float32)                # (W, convdim)
    width = w.shape[0]
    if state is not None and s == 1:
        hist = jnp.concatenate([state["conv"], xbc.astype(jnp.float32)], axis=1)
        conv_out = jnp.einsum("bwc,wc->bc", hist[:, -width:], w)[:, None]
        new_conv = hist[:, -(width - 1):]
    else:
        # conv history: fresh prefill states are zero-initialised, so using
        # the stored history (instead of a zero pad) both preserves the
        # fresh-prefill result and makes chunked prefill an exact
        # continuation — chunk j's first tokens convolve over chunk j-1's
        # tail rather than a spurious zero pad.
        pad = (state["conv"] if state is not None
               else jnp.zeros((bs, width - 1, conv_dim), jnp.float32))
        xf = jnp.concatenate([pad, xbc.astype(jnp.float32)], axis=1)
        conv_out = sum(xf[:, i: i + s] * w[i][None, None] for i in range(width))
        new_conv = xf[:, -(width - 1):]
    conv_out = jax.nn.silu(conv_out + p["conv_b"].astype(jnp.float32))

    xs, b, c = jnp.split(conv_out, [d_inner, d_inner + n], axis=-1)
    xs = xs.reshape(bs, s, nheads, cfg.ssm_headdim)
    dt = jax.nn.softplus(dtp.astype(jnp.float32) + p["dt_bias"][None, None])
    a = -jnp.exp(p["a_log"])                           # (H,) negative

    if state is not None and s == 1:
        # recurrent decode: h' = exp(dt a) h + dt B x
        h = state["ssm"]
        da = jnp.exp(dt[:, 0] * a[None])               # (B,H)
        hb = jnp.einsum("bn,bhp->bhpn", b[:, 0], xs[:, 0] * dt[:, 0, :, None])
        hnew = h * da[..., None, None] + hb
        y = jnp.einsum("bn,bhpn->bhp", c[:, 0], hnew)[:, None]
        new_ssm = hnew
    else:
        y, new_ssm = _ssd_chunked(xs, dt, a, b, c, min(chunk, cfg.ssm_chunk),
                                  state["ssm"] if state is not None else None,
                                  decay_bf16=cfg.ssm_decay_bf16)
    y = y + xs * p["d_skip"][None, None, :, None]
    y = y.reshape(bs, s, d_inner)
    y = rms_norm(y.astype(x.dtype), p["norm"], cfg.norm_eps)
    y = y * jax.nn.silu(z.astype(y.dtype))
    out = dense(y, p["out_proj"], cfg.matmul_mode)
    new_state = ({"conv": new_conv, "ssm": new_ssm}
                 if state is not None else None)
    return out, new_state


def mamba2_state_spec(cfg: ModelConfig, batch: int):
    d_inner, nheads, n = mamba2_dims(cfg)
    conv_dim = d_inner + 2 * n
    return {
        "conv": jax.ShapeDtypeStruct((batch, cfg.ssm_conv - 1, conv_dim), jnp.float32),
        "ssm": jax.ShapeDtypeStruct((batch, nheads, cfg.ssm_headdim, n), jnp.float32),
    }


# ---------------------------------------------------------------------------
# xLSTM — mLSTM (chunkwise parallel) and sLSTM (sequential)
# ---------------------------------------------------------------------------

def mlstm_inner(cfg: ModelConfig) -> int:
    """mLSTM up-projection width: 4/3 * d_model, rounded to 8*num_heads
    (the xLSTM paper's proj_factor with block-diagonal heads)."""
    mult = 8 * cfg.num_heads
    return ((int(cfg.d_model * 4 / 3) + mult - 1) // mult) * mult


def mlstm_defs(cfg: ModelConfig, dtype=jnp.bfloat16):
    d, h = cfg.d_model, cfg.num_heads
    d_inner = mlstm_inner(cfg)
    dk = d_inner // h
    return {
        "up": linear_def(d, 2 * d_inner, "d_model", "ffn", dtype),
        # block-diagonal per-head projections (xLSTM paper)
        "wq": ParamDef((h, dk, dk), ("heads", None, None), dtype),
        "wk": ParamDef((h, dk, dk), ("heads", None, None), dtype),
        "wv": ParamDef((h, dk, dk), ("heads", None, None), dtype),
        "wi": linear_def(d_inner, h, "ffn", "heads", jnp.float32),
        "wf": linear_def(d_inner, h, "ffn", "heads", jnp.float32),
        "norm": ParamDef((d_inner,), (None,), jnp.float32, "zeros"),
        "down": linear_def(d_inner, d, "ffn", "d_model", dtype),
    }


def _mlstm_chunked(q, k, v, log_i, log_f, chunk: int, state=None):
    """Chunkwise mLSTM.  q,k,v: (B,S,H,D); log_i/log_f: (B,S,H).

    Recurrence: C_t = f_t C_{t-1} + i_t k_t v_t^T ; n_t = f_t n_{t-1} + i_t k_t
    y_t = (q C_t) / max(|q n_t|, exp(-m_t)) with running log-stabiliser m.
    Quadratic only inside a chunk; linear scan across chunks.
    """
    bs, s, h, d = q.shape
    qc = min(chunk, s)
    assert s % qc == 0
    nc = s // qc
    qr = q.reshape(bs, nc, qc, h, d)
    kr = k.reshape(bs, nc, qc, h, d) / jnp.sqrt(jnp.float32(d))
    vr = v.reshape(bs, nc, qc, h, d)
    li = log_i.reshape(bs, nc, qc, h).transpose(0, 3, 1, 2)   # (B,H,Nc,Q)
    lf = log_f.reshape(bs, nc, qc, h).transpose(0, 3, 1, 2)
    csf = jnp.cumsum(lf, axis=-1)                      # cumulative log-forget

    # intra-chunk decay matrix: D[l,s] = csf[l]-csf[s]+li[s] for l>=s
    decay = _segsum(lf) + li[..., None, :]             # (B,H,Nc,Q,Q)
    m_intra = decay.max(-1)                            # (B,H,Nc,Q) finite (diag)

    if state is None:
        C0 = jnp.zeros((bs, h, d, d), jnp.float32)
        n0 = jnp.zeros((bs, h, d), jnp.float32)
        m0 = jnp.full((bs, h), -1e30, jnp.float32)
    else:
        C0, n0, m0 = state["C"], state["n"], state["m"]

    # per-chunk end states (log-weight of position s into the chunk end)
    dec_state = csf[..., -1:] - csf + li               # (B,H,Nc,Q)
    chunk_tot = csf[..., -1]                           # (B,H,Nc)
    m_state = dec_state.max(-1)                        # (B,H,Nc)
    w_s = jnp.exp(dec_state - m_state[..., None]).transpose(0, 2, 3, 1)  # (B,Nc,Q,H)
    kw = kr * w_s[..., None]
    Cc = jnp.einsum("bcshd,bcshe->bchde", kw, vr)      # (B,Nc,H,D,D)
    ncs = kw.sum(2)                                    # (B,Nc,H,D)

    def scan_fn(carry, inp):
        C, n, m = carry
        Cci, nci, mi, tot = inp
        m_new = jnp.maximum(m + tot, mi)
        a1 = jnp.exp(m + tot - m_new)
        a2 = jnp.exp(mi - m_new)
        C_new = C * a1[..., None, None] + Cci * a2[..., None, None]
        n_new = n * a1[..., None] + nci * a2[..., None]
        return (C_new, n_new, m_new), (C, n, m)

    (Cf, nf, mf), (Cp, np_, mp) = jax.lax.scan(
        scan_fn, (C0, n0, m0),
        (Cc.transpose(1, 0, 2, 3, 4), ncs.transpose(1, 0, 2, 3),
         m_state.transpose(2, 0, 1), chunk_tot.transpose(2, 0, 1)))

    # combine intra + inter contributions
    m_inter = csf + jnp.moveaxis(mp, 0, 2)[..., None]  # (B,H,Nc,Q)
    m_tot = jnp.maximum(m_intra, m_inter)
    w_intra = jnp.exp(decay - m_tot[..., None])        # (B,H,Nc,Q,Q)
    w_inter = jnp.exp(m_inter - m_tot).transpose(0, 2, 3, 1)  # (B,Nc,Q,H)
    scores = jnp.einsum("bclhd,bcshd->bhcls", qr, kr) * w_intra
    y_intra = jnp.einsum("bhcls,bcshe->bclhe", scores, vr)
    y_inter = jnp.einsum("bclhd,cbhde,bclh->bclhe", qr, Cp, w_inter)
    qn = scores.sum(-1).transpose(0, 2, 3, 1) + jnp.einsum(
        "bclhd,cbhd,bclh->bclh", qr, np_, w_inter)
    y = (y_intra + y_inter) / jnp.maximum(
        jnp.abs(qn), jnp.exp(-m_tot.transpose(0, 2, 3, 1)))[..., None]
    return y.reshape(bs, s, h, d), {"C": Cf, "n": nf, "m": mf}


@jax.named_scope("ssm")
def mlstm_apply(p, cfg: ModelConfig, x: jax.Array, state=None,
                chunk: int = 256):
    bs, s, d = x.shape
    h = cfg.num_heads
    d_inner = mlstm_inner(cfg)
    dk = d_inner // h
    up = dense(x, p["up"], cfg.matmul_mode)
    xi, zg = jnp.split(up, 2, axis=-1)
    xh = xi.reshape(bs, s, h, dk)
    q = jnp.einsum("bshd,hde->bshe", xh, p["wq"].astype(xh.dtype)).astype(jnp.float32)
    k = jnp.einsum("bshd,hde->bshe", xh, p["wk"].astype(xh.dtype)).astype(jnp.float32)
    v = jnp.einsum("bshd,hde->bshe", xh, p["wv"].astype(xh.dtype)).astype(jnp.float32)
    log_i = dense(xi, p["wi"], "bf16").astype(jnp.float32)   # pre-activation
    log_f = jax.nn.log_sigmoid(dense(xi, p["wf"], "bf16").astype(jnp.float32))

    if state is not None and s == 1:
        # recurrent decode step
        C, n, m = state["C"], state["n"], state["m"]
        li, lf = log_i[:, 0], log_f[:, 0]
        m_new = jnp.maximum(lf + m, li)
        i_ = jnp.exp(li - m_new)
        f_ = jnp.exp(lf + m - m_new)
        kv = jnp.einsum("bhd,bhe->bhde", k[:, 0] / jnp.sqrt(jnp.float32(dk)), v[:, 0])
        C_new = C * f_[..., None, None] + kv * i_[..., None, None]
        n_new = n * f_[..., None] + (k[:, 0] / jnp.sqrt(jnp.float32(dk))) * i_[..., None]
        num = jnp.einsum("bhd,bhde->bhe", q[:, 0], C_new)
        den = jnp.abs(jnp.einsum("bhd,bhd->bh", q[:, 0], n_new))
        y = (num / jnp.maximum(den, jnp.exp(-m_new))[..., None])[:, None]
        new_state = {"C": C_new, "n": n_new, "m": m_new}
    else:
        y, new_state = _mlstm_chunked(q, k, v, log_i, log_f, chunk, state)
        if state is None:
            new_state = None
    y = y.reshape(bs, s, d_inner)
    y = rms_norm(y.astype(x.dtype), p["norm"], cfg.norm_eps)
    y = y * jax.nn.silu(zg.astype(y.dtype))
    return dense(y, p["down"], cfg.matmul_mode), new_state


def mlstm_state_spec(cfg: ModelConfig, batch: int):
    d_inner = mlstm_inner(cfg)
    dk = d_inner // cfg.num_heads
    h = cfg.num_heads
    return {"C": jax.ShapeDtypeStruct((batch, h, dk, dk), jnp.float32),
            "n": jax.ShapeDtypeStruct((batch, h, dk), jnp.float32),
            "m": jax.ShapeDtypeStruct((batch, h), jnp.float32)}


def slstm_defs(cfg: ModelConfig, dtype=jnp.bfloat16):
    d, h = cfg.d_model, cfg.num_heads
    hd = d // h
    return {
        "wx": linear_def(d, 4 * d, "d_model", "ffn", dtype),   # i,f,z,o
        "r": ParamDef((4, h, hd, hd), (None, "heads", None, None), dtype),
        "norm": ParamDef((d,), (None,), jnp.float32, "zeros"),
        "wo_proj": linear_def(d, d, "d_model", "d_model", dtype),
    }


@jax.named_scope("ssm")
def slstm_apply(p, cfg: ModelConfig, x: jax.Array, state=None):
    """Sequential sLSTM.  x: (B,S,D).  state: {'c','n','h','m'} each (B,H,hd)
    except m: (B,H)."""
    bs, s, d = x.shape
    h = cfg.num_heads
    hd = d // h
    gx = dense(x, p["wx"], cfg.matmul_mode).astype(jnp.float32)
    gx = gx.reshape(bs, s, 4, h, hd)
    r = p["r"].astype(jnp.float32)

    if state is None:   # the empty state a serving cache starts from
        c0 = n0 = h0 = jnp.zeros((bs, h, hd), jnp.float32)
        m0 = jnp.zeros((bs, h), jnp.float32)
    else:
        c0, n0, h0, m0 = state["c"], state["n"], state["h"], state["m"]

    def step(carry, gxt):
        c, n, hprev, m = carry
        rec = jnp.einsum("ghde,bhd->bghe", r, hprev)   # (B,4,H,hd)
        gi, gf, gz, go = [gxt[:, i] + rec[:, i] for i in range(4)]
        log_i = gi.mean(-1)                             # head-wise stabiliser
        log_f = jax.nn.log_sigmoid(gf.mean(-1))
        m_new = jnp.maximum(log_f + m, log_i)
        i_ = jnp.exp(gi - m_new[..., None])
        f_ = jnp.exp(jax.nn.log_sigmoid(gf) + (m - m_new)[..., None])
        z = jnp.tanh(gz)
        o = jax.nn.sigmoid(go)
        c_new = f_ * c + i_ * z
        n_new = f_ * n + i_
        h_new = o * c_new / jnp.maximum(jnp.abs(n_new), 1.0)
        return (c_new, n_new, h_new, m_new), h_new

    (cf, nf, hf, mf), ys = jax.lax.scan(step, (c0, n0, h0, m0),
                                        gx.transpose(1, 0, 2, 3, 4))
    y = ys.transpose(1, 0, 2, 3).reshape(bs, s, d)
    y = rms_norm(y.astype(x.dtype), p["norm"], cfg.norm_eps)
    out = dense(y, p["wo_proj"], cfg.matmul_mode)
    new_state = ({"c": cf, "n": nf, "h": hf, "m": mf}
                 if state is not None else None)
    return out, new_state


def slstm_state_spec(cfg: ModelConfig, batch: int):
    h = cfg.num_heads
    hd = cfg.d_model // h
    f32 = jnp.float32
    return {"c": jax.ShapeDtypeStruct((batch, h, hd), f32),
            "n": jax.ShapeDtypeStruct((batch, h, hd), f32),
            "h": jax.ShapeDtypeStruct((batch, h, hd), f32),
            "m": jax.ShapeDtypeStruct((batch, h), f32)}
