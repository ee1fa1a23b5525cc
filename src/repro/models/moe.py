"""Mixture-of-Experts with top-k routing and capacity-based dispatch.

Dispatch is scatter-based (not one-hot-einsum): tokens are scattered into
per-expert capacity buffers, expert FFNs run batched over (E, C, d), and
results are gathered back with the routing weights.  This keeps the dispatch
memory O(T*k + E*C*d) instead of the O(T*E*C) of the classic dispatch-tensor
formulation, which matters at deepseek-v2 scale (160 experts).

Experts are sharded over the 'model' mesh axis (expert parallelism): the
(E, C, d) buffers carry the 'experts' logical axis, so GSPMD inserts the
all-to-all at the dispatch/combine boundaries.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import activation, dense
from repro.models.params import ParamDef


def moe_defs(cfg: ModelConfig, dtype=jnp.bfloat16):
    dm, dff, e = cfg.d_model, cfg.moe_d_ff, cfg.num_experts
    defs = {
        "router": ParamDef((dm, e), ("d_model", "experts"), jnp.float32),
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


@jax.named_scope("moe")
def moe_apply(p, cfg: ModelConfig, x: jax.Array,
              capacity: Optional[int] = None) -> Dict[str, jax.Array]:
    """x: (B, S, d) -> {'out': (B, S, d), 'aux_loss': scalar}.

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

    gates = jax.nn.softmax(
        jnp.einsum("td,de->te", xt.astype(jnp.float32),
                   p["router"].astype(jnp.float32)), axis=-1)
    topw, topi = jax.lax.top_k(gates, k)                      # (T, k)
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)

    # load-balance auxiliary loss (Switch-style)
    me = gates.mean(0)                                        # (E,)
    ce = jnp.zeros((e,), jnp.float32).at[topi.reshape(-1)].add(1.0) / (t * k)
    aux = e * jnp.sum(me * ce)

    if capacity is None:
        capacity = int(cfg.capacity_factor * t * k / e) + 1
    capacity = max(capacity, 1)

    # position of each (token, slot) within its expert buffer
    flat_e = topi.reshape(-1)                                 # (T*k,)
    onehot_pos = jnp.zeros((e,), jnp.int32)
    # rank within expert via a scan-free trick: sort-based positions
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    seg_start = jnp.concatenate([jnp.array([0]),
                                 jnp.cumsum(jnp.bincount(sorted_e, length=e))[:-1]])
    rank_sorted = jnp.arange(t * k) - seg_start[sorted_e]
    rank = jnp.zeros((t * k,), jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))
    keep = rank < capacity

    # scatter tokens into expert buffers (E_local, C, d); under expert
    # parallelism only the slots routed to this device's expert block
    e_local = p["up"].shape[0]
    if ep:
        e0 = mtp.tp_index(tpc) * e_local
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
    out = jnp.zeros((t, d), jnp.float32).at[tok_idx].add(
        gathered.astype(jnp.float32) * w[:, None])

    shared_out = None
    if cfg.num_shared_experts:
        xt_s = mtp.tp_gather(xt, tpc) if shared_tp else xt
        shared = activation(dense(xt_s, p["shared_gate"], cfg.matmul_mode),
                            cfg.act) * dense(xt_s, p["shared_up"], cfg.matmul_mode)
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

    return {"out": total.astype(x.dtype).reshape(b, s, d), "aux_loss": aux}
