"""Mixture-of-Experts with top-k routing and capacity-based dispatch.

Dispatch is scatter-based (not one-hot-einsum): tokens are scattered into
per-expert capacity buffers, expert FFNs run batched over (E, C, d), and
results are gathered back with the routing weights.  This keeps the dispatch
memory O(T*k + E*C*d) instead of the O(T*E*C) of the classic dispatch-tensor
formulation, which matters at deepseek-v2 scale (160 experts).

Experts are sharded over the 'model' mesh axis (expert parallelism): the
(E, C, d) buffers carry the 'experts' logical axis, so GSPMD inserts the
all-to-all at the dispatch/combine boundaries.

Serving dispatches without dropping (``dropless``): the (token, expert)
pairs are sorted by held expert and each projection is one grouped matmul
(``jax.lax.ragged_dot``) over the sorted rows, so no row's output depends
on which other rows share its batch.  The model's layer scan hands the
grouped matmuls every layer's experts stacked, with the layer's index
(``p["layer"]``), so that no layer's experts are copied out of the
stack.  A layer may hold a share of the experts (``cfg.experts_held``
from ``cfg.experts_offset``, one device's part of an expert-parallel
deployment): the router keeps all ``num_experts`` outputs and the layer
adds only its own experts' part.

Name scopes, inside ``moe``: ``route`` (router, top-k, aux loss),
``experts`` (dispatch, expert FFNs, combine), ``shared``.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import activation, dense
from repro.models.params import ParamDef


ROUTES = "moe_routes"
EXPERT_WEIGHTS = ("up", "gate", "down")


def experts_local(cfg: ModelConfig) -> int:
    """Routed experts whose weights this layer holds."""
    return cfg.experts_held or cfg.num_experts


def moe_defs(cfg: ModelConfig, dtype=jnp.bfloat16):
    dm, dff, e = cfg.d_model, cfg.moe_d_ff, experts_local(cfg)
    defs = {
        "router": ParamDef((dm, cfg.num_experts), ("d_model", "experts"),
                           jnp.float32),
        "up": ParamDef((e, dm, dff), ("experts", "d_model", "ffn"), dtype),
        "gate": ParamDef((e, dm, dff), ("experts", "d_model", "ffn"), dtype),
        "down": ParamDef((e, dff, dm), ("experts", "ffn", "d_model"), dtype),
    }
    if cfg.num_shared_experts:
        sdff = cfg.moe_d_ff * cfg.num_shared_experts
        defs["shared_up"] = ParamDef((dm, sdff), ("d_model", "ffn"), dtype)
        defs["shared_gate"] = ParamDef((dm, sdff), ("d_model", "ffn"), dtype)
        defs["shared_down"] = ParamDef((sdff, dm), ("ffn", "d_model"), dtype)
    return defs


def route(router: jax.Array, cfg: ModelConfig, xt: jax.Array):
    """(T, d) tokens -> (softmax scores (T, E), top-k weights (T, k),
    top-k experts (T, k)) under the configuration's routing."""
    t, e, k = xt.shape[0], cfg.num_experts, cfg.num_experts_per_tok
    gates = jax.nn.softmax(
        jnp.einsum("td,de->te", xt.astype(jnp.float32),
                   router.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST), axis=-1)
    if cfg.moe_topk_method == "group_limited_greedy":
        g = cfg.moe_n_group
        best = gates.reshape(t, g, e // g).max(-1)            # (T, groups)
        _, kept = jax.lax.top_k(best, cfg.moe_topk_group)
        in_kept = jnp.zeros((t, g), bool).at[
            jnp.arange(t)[:, None], kept].set(True)
        scores = jnp.where(jnp.repeat(in_kept, e // g, axis=1), gates, 0.0)
    elif cfg.moe_topk_method == "greedy":
        scores = gates
    else:
        raise ValueError(f"unknown moe_topk_method {cfg.moe_topk_method!r}")
    topw, topi = jax.lax.top_k(scores, k)                     # (T, k)
    if cfg.moe_norm_topk_prob:
        topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
    else:
        topw = topw * cfg.moe_routed_scale
    return gates, topw, topi


def _expert_ffn(cfg, xs, up, gate, down, group_sizes):
    """Gated expert FFNs over rows sorted by expert: one grouped matmul
    per projection, rows past ``sum(group_sizes)`` unused."""
    def mm(a, w):
        return jax.lax.ragged_dot(a, w.astype(a.dtype), group_sizes)
    h = activation(mm(xs, gate), cfg.act) * mm(xs, up)
    return mm(h, down)


def _dropless(p, cfg: ModelConfig, xt, topw, topi, e0: int):
    """Every (token, expert) pair whose expert is held here, none dropped:
    -> ((T, d) f32 sum of weighted expert outputs, (T, k) held-expert
    index of each pair, -1 where not held).  With ``p["layer"]`` the
    expert weights are every layer's, stacked (L, E_local, ...), and the
    grouped matmuls run over all L * E_local groups, this layer's the only
    ones with rows."""
    t, k = topi.shape
    up, gate, down = (p[w] for w in EXPERT_WEIGHTS)
    e_local = up.shape[-3]
    flat = topi.reshape(-1)
    held = (flat >= e0) & (flat < e0 + e_local)
    local = jnp.where(held, flat - e0, e_local)   # not held: after all
    order = jnp.argsort(local, stable=True)
    sizes = jnp.bincount(local, length=e_local + 1)[:e_local].astype(jnp.int32)
    if "layer" in p:
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros(up.shape[0] * e_local, jnp.int32), sizes,
            (p["layer"] * e_local,))
        up, gate, down = (w.reshape((-1,) + w.shape[2:])
                          for w in (up, gate, down))
    tok = order // k
    ys = _expert_ffn(cfg, xt[tok], up, gate, down, sizes)
    keep = held[order]
    w = jnp.where(keep, topw.reshape(-1)[order], 0.0)
    ys = jnp.where(keep[:, None], ys.astype(jnp.float32), 0.0) * w[:, None]
    out = jnp.zeros(xt.shape, jnp.float32).at[tok].add(ys)
    return out, jnp.where(held, flat - e0, -1).reshape(t, k).astype(jnp.int32)


@jax.named_scope("moe")
def moe_apply(p, cfg: ModelConfig, x: jax.Array,
              capacity: Optional[int] = None,
              dropless: bool = False) -> Dict[str, jax.Array]:
    """x: (B, S, d) -> {'out': (B, S, d), 'aux_loss': scalar}, and with
    ``dropless`` also 'routes': (B, S, k) the held expert (0-based within
    the share) each routed pair went to, -1 where it is not held here.

    Without ``dropless``, each expert takes at most ``capacity`` tokens
    (by default ``capacity_factor * T * k / E + 1``) and drops the rest.

    Under a manual-TP context (inside a pipeline stage) the routed experts
    shard over the TP axes: the router stays replicated — every device
    computes the full routing, capacity ranks, and aux loss identically —
    while up/gate/down hold a contiguous block of experts, each device
    dispatches only the tokens routed to its block, and the combine is a
    psum.  Shared experts shard their ffn dim like a dense MLP; both
    partial contributions ride through one all-reduce.
    """
    from repro.dist import tp as mtp
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    t = b * s
    xt = x.reshape(t, d)
    tpc = mtp.current_tp()
    ep = tpc is not None and tpc.shard_experts
    shared_tp = (tpc is not None and tpc.shard_shared
                 and cfg.num_shared_experts > 0)
    e_local = p["up"].shape[-3]

    with jax.named_scope("route"):
        gates, topw, topi = route(p["router"], cfg, xt)
        # load-balance auxiliary loss (Switch-style)
        me = gates.mean(0)                                    # (E,)
        ce = jnp.zeros((e,), jnp.float32).at[topi.reshape(-1)].add(
            1.0) / (t * k)
        aux = e * jnp.sum(me * ce)

    routes = None
    with jax.named_scope("experts"):
        if dropless:
            if ep:
                raise ValueError("dropless dispatch runs outside manual TP")
            out, routes = _dropless(p, cfg, xt, topw, topi, cfg.experts_offset)
        else:
            out = _capacity_dispatch(p, cfg, xt, topw, topi, capacity, tpc,
                                     ep, e_local)

    shared_out = None
    if cfg.num_shared_experts:
        with jax.named_scope("shared"):
            xt_s = mtp.tp_gather(xt, tpc) if shared_tp else xt
            shared = activation(dense(xt_s, p["shared_gate"], cfg.matmul_mode),
                                cfg.act) * dense(xt_s, p["shared_up"],
                                                 cfg.matmul_mode)
            shared_out = dense(shared, p["shared_down"],
                               cfg.matmul_mode).astype(jnp.float32)

    # combine: partial contributions (expert-sharded routed sum, ffn-sharded
    # shared down-projection) go through one all-reduce; anything computed
    # replicated is added after it
    partial = out if ep else None
    full = None if ep else out
    if shared_out is not None:
        if shared_tp:
            partial = shared_out if partial is None else partial + shared_out
        else:
            full = shared_out if full is None else full + shared_out
    total = jnp.zeros((t, d), jnp.float32)
    if partial is not None:
        total = total + mtp.tp_psum(partial, tpc)
    if full is not None:
        total = total + full

    result = {"out": total.astype(x.dtype).reshape(b, s, d), "aux_loss": aux}
    if routes is not None:
        result["routes"] = routes.reshape(b, s, k)
    return result


def _capacity_dispatch(p, cfg, xt, topw, topi, capacity, tpc, ep, e_local):
    """Capacity-bounded dispatch (training): pairs past an expert's
    ``capacity`` are dropped.  -> (T, d) f32 weighted expert outputs."""
    from repro.dist import tp as mtp
    t, d = xt.shape
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    if capacity is None:
        capacity = int(cfg.capacity_factor * t * k / e) + 1
    capacity = max(capacity, 1)

    # position of each (token, slot) within its expert buffer
    flat_e = topi.reshape(-1)                                 # (T*k,)
    # rank within expert via a scan-free trick: sort-based positions
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    seg_start = jnp.concatenate([jnp.array([0]),
                                 jnp.cumsum(jnp.bincount(sorted_e, length=e))[:-1]])
    rank_sorted = jnp.arange(t * k) - seg_start[sorted_e]
    rank = jnp.zeros((t * k,), jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))
    keep = rank < capacity

    # scatter tokens into expert buffers (E_local, C, d); under expert
    # parallelism, or with a share of the experts, only the slots routed
    # to this device's expert block
    if ep or e_local != e:
        e0 = mtp.tp_index(tpc) * e_local if ep else cfg.experts_offset
        sel = keep & (flat_e >= e0) & (flat_e < e0 + e_local)
        loc_e = jnp.clip(flat_e - e0, 0, e_local - 1)
    else:
        sel, loc_e = keep, flat_e
    buf = jnp.zeros((e_local, capacity, d), xt.dtype)
    tok_idx = jnp.repeat(jnp.arange(t), k)
    # the router path above keeps the raw (replicated) xt; only the
    # expert-dispatch path is column-parallel over the expert shards
    xt_e = mtp.tp_gather(xt, tpc) if ep else xt
    buf = buf.at[loc_e, jnp.where(sel, rank, 0)].add(
        jnp.where(sel[:, None], xt_e[tok_idx], 0).astype(xt.dtype))

    # expert FFNs, batched over the local experts
    def ffn(xe, up, gate, down):
        h = activation(jnp.einsum("cd,df->cf", xe, gate.astype(xe.dtype)),
                       cfg.act) * jnp.einsum("cd,df->cf", xe, up.astype(xe.dtype))
        return jnp.einsum("cf,fd->cd", h, down.astype(xe.dtype))

    yb = jax.vmap(ffn)(buf, p["up"], p["gate"], p["down"])    # (E_local, C, d)

    # gather back with routing weights
    gathered = yb[loc_e, jnp.where(sel, rank, 0)]             # (T*k, d)
    gathered = jnp.where(sel[:, None], gathered, 0)
    w = (topw.reshape(-1) * sel).astype(jnp.float32)
    return jnp.zeros((t, d), jnp.float32).at[tok_idx].add(
        gathered.astype(jnp.float32) * w[:, None])
