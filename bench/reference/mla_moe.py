"""Plain reference of a DeepSeek-V2 decoder (latent attention, a dense
first layer, then routed and shared experts), replayed over the calls
that a serving window made, for one chip's share of the experts.

The equations are the published ones (arXiv:2405.04434, and the
``modeling_deepseek.py`` of the checkpoint that ``config.json`` names),
computed in float32 under ``Precision.HIGHEST`` and rounded to bf16
where the model stores bf16: the activations between layers, each
projection's output, the cached latents.  Per layer:

* attention, expanded (not absorbed): ``c_q = RMSNorm(h W_dq)``,
  ``q = c_q W_uq`` split per head into a 128-wide part and a 64-wide
  rotary part; ``[c_kv, k_r] = h W_dkv``, ``c_kv = RMSNorm(c_kv)``;
  ``k = [c_kv W_uk, RoPE(k_r)]`` (the rotary key shared by every head),
  ``v = c_kv W_uv``; causal softmax of ``q k / sqrt(192) * mscale**2``;
  ``o W_o``.  The rotary part uses YaRN's frequencies (factor 40 over an
  original 4096 positions, beta_fast 32, beta_slow 1) and
  ``mscale = 0.1 * 0.707 * ln(40) + 1``;
* the dense first layer: a SiLU-gated MLP;
* an MoE layer: ``s = softmax(h W_router)`` over every routed expert;
  each group of ``E / n_group`` experts is scored by its best expert,
  the ``topk_group`` best groups are kept, and the ``k`` best experts of
  those are chosen (``group_limited_greedy``); their weights are the
  scores times ``routed_scaling_factor`` (``norm_topk_prob`` false).  Of
  the routed sum only the held experts' part is computed (the chip's
  share: ``experts_held`` experts from ``experts_offset``), each expert a
  SiLU-gated MLP, and the shared experts are added whole.  Nothing is
  dropped: every row's result is its own.

Rows do not depend on each other, so only the requests compared are
computed.  One layer's weights are cast to f32 at a time (a whole f32
copy of the model would not fit beside it).

Departures from the published model, none of which the comparison can
tell apart under random weights: the checkpoint's rotary channels are
interleaved and are permuted to two halves before rotation, a fixed
permutation of the ``W_uq`` and ``W_dkv`` columns, so here the halves are
taken as they lie; RMSNorm gains are ``1 + gain`` with the gain stored
(zero in the benchmark's weights: the published weight of one).  Nothing
here imports the program or uses an array it made.

The control (``control=True``) is the same computation one precision
step below the configuration's: int8 matmuls (per-row activations,
per-tensor weights) and the cached latents in int8 (per token).
"""
from __future__ import annotations

import math
from typing import Dict, Sequence, Tuple

import numpy as np

from reference.decoder import (CALLS_BUCKET, ROW_BLOCK, ROWS_BUCKET,  # noqa: F401
                               Call, Plan, ReplayError, _by_blocks, _jax, _jit,
                               kv_values, matmul, plan, prepare_weight,
                               rms_norm)
from reference import decoder as _gqa

HEAD_GROUP = 32        # heads attended at once


def arithmetic(sizes: Dict, control: bool = False) -> Tuple:
    """f32 for a bf16 model, int8 for its control; BP8 is not taken."""
    if sizes["matmul_mode"] != "bf16" or sizes.get("kv_quant", "none") != "none":
        raise ValueError("the MLA/MoE reference takes a bf16 model with a "
                         "bf16 latent cache")
    return _gqa.arithmetic(sizes, control)


def yarn(sizes: Dict) -> Tuple[np.ndarray, float, float]:
    """(rotary frequencies, cos/sin scale, softmax mscale) of YaRN as the
    configuration states it (plain RoPE where its factor is 0)."""
    d, theta = int(sizes["qk_rope_head_dim"]), float(sizes["rope_theta"])
    base = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    factor = float(sizes.get("rope_yarn_factor", 0.0))
    if not factor:
        return base.astype(np.float32), 1.0, 1.0

    def m(scale):
        return 1.0 if factor <= 1 else 0.1 * scale * math.log(factor) + 1.0

    orig = float(sizes["rope_yarn_original_max_pos"])

    def dim_of(turns):          # channel that turns ``turns`` times
        return d * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(theta))

    lo = max(math.floor(dim_of(float(sizes["rope_yarn_beta_fast"]))), 0)
    hi = min(math.ceil(dim_of(float(sizes["rope_yarn_beta_slow"]))), d - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(d // 2) - lo) / (hi - lo), 0.0, 1.0)
    freqs = base / factor * ramp + base * (1.0 - ramp)
    all_dim = m(float(sizes.get("rope_yarn_mscale_all_dim", 0.0)))
    return (freqs.astype(np.float32),
            m(float(sizes.get("rope_yarn_mscale", 1.0))) / all_dim, all_dim)


def rope(x, positions, freqs, scale: float):
    """(R, H, D) bf16 rotated on its two halves -> bf16."""
    _, jnp = _jax()
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(freqs)[None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    cos = (jnp.cos(ang) * scale)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(jnp.bfloat16)


def _bf16_mm(x, w, arith):
    """bf16 rows times a bf16 weight, rounded to bf16 as the model stores
    the projection."""
    _, jnp = _jax()
    return matmul(x.astype(jnp.float32), None, prepare_weight(w, arith),
                  arith).astype(jnp.bfloat16)


def _dims(sizes: Dict) -> Tuple:
    freqs, rot_scale, mscale = yarn(sizes)
    return (int(sizes["num_heads"]), int(sizes["qk_nope_head_dim"]),
            int(sizes["qk_rope_head_dim"]), int(sizes["v_head_dim"]),
            int(sizes["kv_lora_rank"]), float(sizes["norm_eps"]),
            tuple(float(f) for f in freqs), rot_scale, mscale)


def _routing(sizes: Dict) -> Tuple:
    return (int(sizes["num_experts"]), int(sizes["num_experts_per_tok"]),
            int(sizes.get("moe_n_group", 1)), int(sizes.get("moe_topk_group", 1)),
            sizes.get("moe_topk_method", "greedy"),
            bool(sizes.get("moe_norm_topk_prob", True)),
            float(sizes.get("moe_routed_scale", 1.0)),
            int(sizes.get("experts_offset", 0)),
            int(sizes.get("experts_held", 0) or sizes["num_experts"]))


@_jit(static_argnames=("dims", "arith"))
def _latents(x, lp, positions, *, dims, arith):
    """-> (c_q (R, q_lora) bf16, c_kv (R, kv_lora), rotary key (R, rope))
    with the cached two as the cache gives them back."""
    _, jnp = _jax()
    eps, freqs, rot_scale = dims[5], np.asarray(dims[6], np.float32), dims[7]
    a = lp["attn"]
    h = rms_norm(x, lp["ln1"], eps)
    cq = _by_blocks(lambda hb: rms_norm(_bf16_mm(hb, a["wdq"], arith),
                                        a["q_norm"], eps), h)
    dkv = _by_blocks(lambda hb: _bf16_mm(hb, a["wdkv"], arith), h)
    r = dims[4]
    ckv = rms_norm(dkv[:, :r], a["kv_norm"], eps)
    kr = rope(dkv[:, None, r:], positions, freqs, rot_scale)[:, 0]
    ckv = kv_values(ckv[:, None, :], arith)[:, 0]
    kr = kv_values(kr[:, None, :], arith)[:, 0]
    return cq, ckv, kr


@_jit(static_argnames=("dims", "arith", "bucket"))
def _attend(cq, ckv, kr, positions, a, first, n, *, dims, arith, bucket):
    """Expanded causal MLA of one request's rows [first, first + n),
    through ``W_o``."""
    jax, jnp = _jax()
    heads, nope, rd, vd, r, _, freqs, rot_scale, mscale = dims
    hi = jax.lax.Precision.HIGHEST
    sl = lambda t: jax.lax.dynamic_slice_in_dim(t, first, bucket, 0)  # noqa: E731
    q = _bf16_mm(sl(cq), a["wuq"], arith).reshape(bucket, heads, nope + rd)
    q_nope = q[..., :nope].astype(jnp.float32)
    q_rope = rope(q[..., nope:], sl(positions), np.asarray(freqs, np.float32),
                  rot_scale).astype(jnp.float32)
    c = sl(ckv).astype(jnp.float32)
    k_rope = sl(kr).astype(jnp.float32)
    scale = mscale * mscale / math.sqrt(nope + rd)
    i = jnp.arange(bucket)
    ok = (i[None, :] <= i[:, None]) & (i[None, :] < n)
    hg = math.gcd(heads, HEAD_GROUP)
    g = heads // hg

    def group(xs):
        qn, qr, wuk, wuv = xs          # (B, Hg, nope), (B, Hg, rd), (r, Hg, .)
        k_nope = jnp.einsum("sr,rhd->shd", c, wuk.astype(jnp.float32),
                            precision=hi)
        v = jnp.einsum("sr,rhd->shd", c, wuv.astype(jnp.float32),
                       precision=hi)
        s = (jnp.einsum("qhd,khd->hqk", qn, k_nope, precision=hi)
             + jnp.einsum("qhd,kd->hqk", qr, k_rope, precision=hi)) * scale
        s = jnp.where(ok[None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=hi)

    split = lambda t, ax: jnp.moveaxis(  # noqa: E731
        t.reshape(t.shape[:ax] + (g, hg) + t.shape[ax + 1:]), ax, 0)
    o = jax.lax.map(group, (split(q_nope, 1), split(q_rope, 1),
                            split(a["wuk"], 1), split(a["wuv"], 1)))
    o = jnp.moveaxis(o, 0, 1).reshape(bucket, heads * vd)
    return _bf16_mm(o.astype(jnp.bfloat16), a["wo"], arith)


def _gated(h, up, gate, down, arith):
    """SiLU-gated MLP of bf16 rows, each projection rounded to bf16."""
    jax, jnp = _jax()
    u = _bf16_mm(h, up, arith).astype(jnp.float32)
    g = _bf16_mm(h, gate, arith).astype(jnp.float32)
    f = (g * jax.nn.sigmoid(g)).astype(jnp.bfloat16).astype(jnp.float32) * u
    return _bf16_mm(f.astype(jnp.bfloat16), down, arith)


def route(h, router, routing):
    """-> ((R, E) routing weight of every expert, 0 where not chosen)."""
    jax, jnp = _jax()
    e, k, n_group, topk_group, method, norm, scale = routing[:7]
    rows = h.shape[0]
    s = jax.nn.softmax(jnp.dot(h.astype(jnp.float32),
                               router.astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST), axis=-1)
    if method == "group_limited_greedy":
        best = s.reshape(rows, n_group, e // n_group).max(-1)
        _, groups = jax.lax.top_k(best, topk_group)
        keep = jnp.zeros((rows, n_group), bool).at[
            jnp.arange(rows)[:, None], groups].set(True)
        s = jnp.where(jnp.repeat(keep, e // n_group, axis=1), s, 0.0)
    w, idx = jax.lax.top_k(s, k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9) if norm else w * scale
    return jnp.zeros((rows, e), jnp.float32).at[
        jnp.arange(rows)[:, None], idx].add(w)


@_jit(static_argnames=("dims", "routing", "arith"))
def _residual_and_ffn(x, a, lp, *, dims, routing, arith):
    """The attention's residual, then the MLP or the MoE share;
    -> (x, held pairs of each row)."""
    jax, jnp = _jax()
    eps, bf16 = dims[5], jnp.bfloat16
    x = (x.astype(jnp.float32) + a.astype(jnp.float32)).astype(bf16)
    h = rms_norm(x, lp["ln2"], eps)
    if "mlp" in lp:
        m = lp["mlp"]
        y = _by_blocks(lambda hb: _gated(hb, m["up"], m["gate"], m["down"],
                                         arith), h)
        held = jnp.zeros(x.shape[:1], jnp.int32)
    else:
        p = lp["moe"]
        off, n_held = routing[7], routing[8]

        def block(hb):
            w = jax.lax.dynamic_slice_in_dim(route(hb, p["router"], routing),
                                             off, n_held, 1)

            def expert(acc, xs):
                wj, up, gate, down = xs
                y = _gated(hb, up, gate, down, arith).astype(jnp.float32)
                return acc + wj[:, None] * y, None

            acc, _ = jax.lax.scan(expert, jnp.zeros(hb.shape, jnp.float32),
                                  (w.T, p["up"], p["gate"], p["down"]))
            if "shared_up" in p:
                acc = acc + _gated(hb, p["shared_up"], p["shared_gate"],
                                   p["shared_down"], arith).astype(jnp.float32)
            return acc.astype(bf16), (w > 0).sum(-1).astype(jnp.int32)

        y, held = _by_blocks(block, h)
    return (x.astype(jnp.float32) + y.astype(jnp.float32)).astype(bf16), held


@_jit(static_argnames=("eps",))
def _logit_stats(x, final_norm, head, probe, *, eps):
    """(max, argmax, logit at each probe token) of the rows ``x``."""
    jax, jnp = _jax()
    h = rms_norm(x, final_norm, eps)
    logits = jnp.dot(h.astype(jnp.float32), head.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    at = jnp.take_along_axis(logits, probe, axis=1)
    return jnp.max(logits, axis=1), jnp.argmax(logits, axis=1), at


def _layers(params):
    """Each layer's parameters in order: the dense ones, then the MoE."""
    jax, _ = _jax()
    for stack in ("dense_layers", "layers"):
        if stack in params:
            n = jax.tree.leaves(params[stack])[0].shape[0]
            for i in range(n):
                yield jax.tree.map(lambda a: a[i], params[stack])


def replay(sizes: Dict, params, p: Plan, probes: Sequence[np.ndarray] = (),
           control: bool = False) -> Dict[str, np.ndarray]:
    """Logit statistics of every compared row: ``max``, ``argmax`` and
    ``at`` (rows, len(probes)), the logit of each probe's token; and
    ``held_pairs`` (R,), the routed pairs of each planned row that landed
    on a held expert, over every MoE layer.  ``control``: in the
    arithmetic one step below the configuration's."""
    jax, jnp = _jax()
    dims, routing = _dims(sizes), _routing(sizes)
    arith = arithmetic(sizes, control)
    biggest = max([1] + [1 << (n - 1).bit_length() for _, _, n in p.requests])
    # rows are padded by the longest bucket, so that a request's slice
    # never runs off the end, and to a multiple of ROWS_BUCKET, so that
    # runs of one cell reuse the same compiled programs
    extra = biggest + (-(len(p.tokens) + biggest)) % ROWS_BUCKET
    tokens = jnp.asarray(np.pad(p.tokens, (0, extra)))
    positions = jnp.asarray(np.pad(p.positions, (0, extra)))
    total = len(p.tokens) + extra
    pieces, order, at = [], np.empty(total, np.int64), 0
    for _, first, n in p.requests:
        b = 1 << (n - 1).bit_length()
        order[first:first + n] = at + np.arange(n)
        pieces.append((first, n, b))
        at += b
    s0 = p.solo[0]
    order[s0:] = at + np.arange(total - s0)
    order = jnp.asarray(order)
    x = jnp.take(params["embed"], tokens, axis=0)
    held = np.zeros(total, np.int64)
    for lp in _layers(params):
        cq, ckv, kr = _latents(x, lp, positions, dims=dims, arith=arith)
        outs = [_attend(cq, ckv, kr, positions, lp["attn"], jnp.int32(first),
                        jnp.int32(n), dims=dims, arith=arith, bucket=b)
                for first, n, b in pieces]
        # rows past the requests (padding) attend to nothing: zeros
        outs.append(jnp.zeros((total - s0, x.shape[1]), jnp.bfloat16))
        a = jnp.take(jnp.concatenate(outs, axis=0), order, axis=0)
        del outs
        x, h = _residual_and_ffn(x, a, lp, dims=dims, routing=routing,
                                 arith=arith)
        held += np.asarray(h)
        del cq, ckv, kr, a
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
                               ((0, ROW_BLOCK - m), (0, 0)))), eps=dims[5])
        mx.append(np.asarray(a)[:m])
        am.append(np.asarray(b)[:m])
        vals.append(np.asarray(c)[:m])
    out = {"held_pairs": held[:s0]}
    if not mx:
        return dict(out, max=np.zeros(0), argmax=np.zeros(0, np.int64),
                    at=np.zeros((0, probe.shape[1])))
    return dict(out, max=np.concatenate(mx), argmax=np.concatenate(am),
                at=np.concatenate(vals))
