"""Model composition: one ``Model`` API over four architecture families.

  decoder — gemma3, h2o-danube, minicpm3, qwen2, granite-moe, deepseek-v2,
            paligemma (prefix-LM over stub patch embeddings)
  encdec  — whisper (stub frame embeddings -> encoder; causal decoder with
            cross attention)
  hybrid  — zamba2 (mamba2 backbone + shared attention block every N layers
            with per-invocation LoRA adapters)
  xlstm   — xLSTM (mLSTM blocks with a sLSTM block every N)

API (all functional, pytree params):
  schema()                      -> ParamDef tree
  loss(params, batch)           -> (scalar loss, metrics dict)      [train]
  cache_spec(batch, length)     -> abstract cache pytree (+ logical axes)
  prefill_chunk(params, batch, cache, pos0) -> (last-position logits, cache)
  decode_step(params, tok, cache, pos)      -> (logits, cache), pos (B,)
  prefill(params, batch, cache_len) -> prefill_chunk at position 0 into
                                    a fresh cache (``Serving``)

Layer stacks are scanned (jax.lax.scan over stacked params) so compile time
and HLO size are O(1) in depth; heterogeneous stacks scan over groups.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.dist.sharding import shard
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (ParamDef, chunked_softmax_xent, dense,
                                 embed_def, embed_lookup, layer_norm, ln_defs,
                                 linear_def, mlp_apply, mlp_defs, norm_def,
                                 rms_norm)
from repro.models.params import abstract_tree, axes_tree, empty_cache, stack

BIG_WINDOW = 1 << 30  # "no window" sentinel usable as a traced int


class Serving:
    """The one serving contract of every family: a chunk appended to a
    cache at a traced ``pos0`` (``prefill_chunk``), one token a row at
    (B,) positions (``decode_step``), and ``prefill``, which is the chunk
    path at position 0 into a fresh cache."""

    def prefill(self, params, batch, cache_len: int):
        """-> (last-position logits, a cache of ``cache_len`` tokens, plus
        the prefix where the config has one)."""
        spec = self.cache_spec(batch["tokens"].shape[0],
                               cache_len + self.cfg.num_prefix_tokens)
        return self.prefill_chunk(params, batch, empty_cache(spec), 0)


# =============================================================================
# decoder family
# =============================================================================

def _decoder_layer_defs(cfg: ModelConfig, moe: bool):
    d = {"ln1": norm_def(cfg.d_model), "ln2": norm_def(cfg.d_model)}
    if cfg.attention_type == "mla":
        d["attn"] = attn.mla_defs(cfg)
    else:
        d["attn"] = attn.gqa_defs(cfg)
    if moe:
        d["moe"] = moe_mod.moe_defs(cfg)
    else:
        d["mlp"] = mlp_defs(cfg.d_model, cfg.d_ff, cfg.mlp_gated)
    if cfg.local_global_pattern:  # gemma3 also post-norms
        d["post_ln1"] = norm_def(cfg.d_model)
        d["post_ln2"] = norm_def(cfg.d_model)
    return d


def _layer_windows(cfg: ModelConfig) -> np.ndarray:
    """Per-layer attention windows as an int array (BIG_WINDOW = full)."""
    L = cfg.num_layers
    if cfg.local_global_pattern:
        per = cfg.local_global_pattern + 1
        w = np.full((L,), cfg.window_size or BIG_WINDOW, np.int64)
        w[per - 1 :: per] = BIG_WINDOW          # every per-th layer is global
        return w
    if cfg.window_size:
        return np.full((L,), cfg.window_size, np.int64)
    return np.full((L,), BIG_WINDOW, np.int64)


def _decoder_layer_apply(p, cfg: ModelConfig, x, positions, *, window,
                         cache=None, prefix_len=None):
    """-> (x, new cache, aux loss, the MoE layer's routes: None for a dense
    layer or outside serving)."""
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if cfg.attention_type == "mla":
        a, new_cache = attn.mla_apply(p["attn"], cfg, h, positions,
                                      cache=cache, window=window)
    else:
        a, new_cache = attn.gqa_apply(p["attn"], cfg, h, positions,
                                      window=window, cache=cache,
                                      prefix_len=prefix_len)
    if "post_ln1" in p:
        a = rms_norm(a, p["post_ln1"], cfg.norm_eps)
    x = x + a
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    aux, routes = jnp.float32(0.0), None
    if "moe" in p:
        # serving (a cache) dispatches without dropping tokens
        r = moe_mod.moe_apply(p["moe"], cfg, h, dropless=cache is not None)
        m, aux, routes = r["out"], r["aux_loss"], r.get("routes")
    else:
        m = mlp_apply(p["mlp"], h, cfg.act, cfg.mlp_gated, cfg.matmul_mode)
    if "post_ln2" in p:
        m = rms_norm(m, p["post_ln2"], cfg.norm_eps)
    return x + m, new_cache, aux, routes


@dataclasses.dataclass
class DecoderModel(Serving):
    cfg: ModelConfig

    # ---------------- schema ----------------
    def schema(self):
        cfg = self.cfg
        n_dense = cfg.first_dense_layers
        n_rest = cfg.num_layers - n_dense
        layer_moe = cfg.num_experts > 0
        sch: Dict[str, Any] = {
            "embed": embed_def(cfg.vocab_size, cfg.d_model),
            "final_norm": norm_def(cfg.d_model),
            "layers": stack(_decoder_layer_defs(cfg, layer_moe), n_rest),
        }
        if n_dense:
            sch["dense_layers"] = stack(_decoder_layer_defs(cfg, False), n_dense)
        if not cfg.tie_embeddings:
            sch["head"] = linear_def(cfg.d_model, cfg.vocab_size,
                                     "d_model", "vocab")
        return sch

    # ---------------- shared forward over the stack ----------------
    def _stack(self, params, x, positions, caches, prefix_len):
        cfg = self.cfg
        windows = _layer_windows(cfg)
        aux_total = jnp.float32(0.0)
        n_dense = cfg.first_dense_layers

        def run_stack(stack_params, stack_cache, x, windows_arr, aux_total):
            # serving's grouped expert matmuls (a custom call, which cannot
            # fuse a slice of its operand) read each layer's experts from
            # the stacked weights: scanning over them would copy every
            # held expert out of the stack in every layer of every call
            experts = None
            if caches is not None and "moe" in stack_params:
                moe_p = dict(stack_params["moe"])
                experts = {k: moe_p.pop(k) for k in moe_mod.EXPERT_WEIGHTS}
                stack_params = dict(stack_params, moe=moe_p)

            def layer_fn(x, lp, lcache, w):
                return _decoder_layer_apply(lp, cfg, x, positions, window=w,
                                            cache=lcache,
                                            prefix_len=prefix_len)

            fn = (jax.checkpoint(layer_fn)
                  if (cfg.remat and caches is None) else layer_fn)

            def body(carry, inp):
                x, aux = carry
                lp, lcache, w = inp[:3]
                if experts is not None:      # inp[3]: the layer's index
                    lp = dict(lp, moe=dict(lp["moe"], **experts,
                                           layer=inp[3]))
                lcache = _as_cache(lcache)
                x = shard(x, "batch", "seq", None)
                x2, ncache, aux1, routes = fn(x, lp, lcache, w)
                return (x2, aux + aux1), (ncache if ncache is not None
                                          else jnp.zeros((0,)), routes)

            xs = (stack_params, stack_cache, windows_arr)
            if experts is not None:
                xs += (jnp.arange(len(windows_arr)),)
            (x, aux_total), (new_caches, routes) = jax.lax.scan(
                body, (x, aux_total), xs)
            return x, new_caches, aux_total, routes

        new_cache = {}
        if n_dense:
            wd = jnp.asarray(windows[:n_dense])
            cd = caches["dense_layers"] if caches is not None else _none_like(
                params["dense_layers"])
            x, nc, aux_total, _ = run_stack(params["dense_layers"], cd, x, wd,
                                            aux_total)
            new_cache["dense_layers"] = nc
        wr = jnp.asarray(windows[n_dense:])
        cr = caches["layers"] if caches is not None else _none_like(
            params["layers"])
        x, nc, aux_total, routes = run_stack(params["layers"], cr, x, wr,
                                             aux_total)
        new_cache["layers"] = nc
        if routes is not None and cfg.experts_held:
            # (B, S, MoE layers, k): what the serving engine counts
            new_cache[moe_mod.ROUTES] = jnp.moveaxis(routes, 0, 2)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return x, (new_cache if caches is not None else None), aux_total

    def _embed_in(self, params, batch):
        cfg = self.cfg
        tokens = batch["tokens"]
        x = embed_lookup(params["embed"], tokens,
                         scale=cfg.local_global_pattern > 0 or
                         cfg.num_prefix_tokens > 0)
        if cfg.num_prefix_tokens and "patches" in batch:
            # paligemma: prepend stub patch embeddings (frontend is a STUB;
            # input_specs supplies precomputed patch embeddings)
            x = jnp.concatenate([batch["patches"].astype(x.dtype), x], axis=1)
        return x

    def _prefix(self, b):
        """(B,) prefix lengths for the prefix-LM mask, None without one."""
        n = self.cfg.num_prefix_tokens
        return jnp.full((b,), n, jnp.int32) if n else None

    @jax.named_scope("head")
    def _logits(self, params, h):
        cfg = self.cfg
        w = (params["embed"].T if cfg.tie_embeddings else params["head"])
        logits = jnp.einsum("bsd,dv->bsv", h.astype(jnp.float32),
                            w.astype(jnp.float32))
        if cfg.logit_softcap:
            logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
        return logits

    # ---------------- entry points ----------------
    def loss(self, params, batch):
        cfg = self.cfg
        x = self._embed_in(params, batch)
        b, s, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        h, _, aux = self._stack(params, x, positions, None, self._prefix(b))
        if cfg.num_prefix_tokens:
            h = h[:, cfg.num_prefix_tokens:]
        labels = batch["labels"]
        mask = batch.get("loss_mask", jnp.ones_like(labels, jnp.float32))
        total, denom = chunked_softmax_xent(
            h, params["embed"] if cfg.tie_embeddings else params["head"].T,
            labels, mask, softcap=cfg.logit_softcap)
        loss = total / jnp.maximum(denom, 1.0)
        if cfg.num_experts:
            loss = loss + 0.01 * aux / cfg.num_layers
        return loss, {"loss": loss, "aux_loss": aux}

    def pipeline_loss(self, params, batch, *, num_stages, num_microbatches,
                      mesh, axis_name="stage", batch_axes=(),
                      tp_axes=("model",)):
        """Pipelined train loss: equals ``loss`` up to float reassociation.

        The scanned decoder stack is split into ``num_stages`` pipeline
        stages (``stack_stages``, or ``stack_stages_padded`` for
        non-dividing depths like deepseek-v2's 59 MoE layers) and streamed
        as ``num_microbatches`` GPipe microbatches through
        ``repro.dist.pipeline.pipeline_apply``; ``jax.grad`` through it is
        backward pipelining.  Embedding, the dense prologue
        (``first_dense_layers``), final norm and the vocab-chunked xent
        stay outside the pipeline — replicated over "stage", sharded per
        the ambient rules.  MoE aux losses are computed per pipeline
        microbatch and averaged: the same semantics shift as gradient
        accumulation (dense stacks are unaffected and match exactly).

        Tensor parallelism runs *inside* the stage bodies: per
        ``repro.dist.tp.plan_stage_tp`` over ``tp_axes`` (filtered to the
        mesh), stage weights enter the pipeline's manual region sharded
        over the TP axes at rest — the only boundary gather left is the
        ZeRO d_model/"data" one — and attention/MLP/MoE run on local
        shards with manual psums after the out-projections, mirroring
        what ``pipeline_rules()`` + the auto partitioner produce outside
        the pipe.  ``tp_axes=()`` disables (fully replicated stage
        compute, the pre-TP behaviour).
        """
        import numpy as _np
        from jax.sharding import PartitionSpec as _P
        from repro.dist import tp as mtp
        from repro.dist.pipeline import (pipeline_apply, stack_stages,
                                         stack_stages_padded)
        from repro.models.params import axes_tree
        cfg = self.cfg
        assert cfg.num_prefix_tokens == 0, "pipelined path: no prefix tokens"
        tp_plan = (mtp.plan_stage_tp(cfg, mesh, tuple(tp_axes))
                   if tp_axes else None)
        M, S = num_microbatches, num_stages
        x = self._embed_in(params, batch)
        b, s, _ = x.shape
        assert b % M == 0, (b, M)
        windows = _layer_windows(cfg)
        n_dense = cfg.first_dense_layers
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        aux_outer = jnp.float32(0.0)

        def remat(fn):
            return jax.checkpoint(fn) if cfg.remat else fn

        if n_dense:
            dense_fn = remat(lambda x, lp, w: _decoder_layer_apply(
                lp, cfg, x, positions, window=w, cache=None,
                prefix_len=None))

            def dense_body(carry, inp):
                x, aux = carry
                lp, w = inp
                x = shard(x, "batch", "seq", None)
                x2, _, a1, _ = dense_fn(x, lp, w)
                return (x2, aux + a1), None

            (x, aux_outer), _ = jax.lax.scan(
                dense_body, (x, aux_outer),
                (params["dense_layers"], jnp.asarray(windows[:n_dense])))

        L = cfg.num_layers - n_dense
        wrest = windows[n_dense:]
        if L % S == 0:
            sp = stack_stages(params["layers"], S)
            w_st = jnp.asarray(wrest.reshape(S, L // S))
            v_st = jnp.ones((S, L // S), bool)
        else:
            sp, v_st = stack_stages_padded(params["layers"], S)
            per = v_st.shape[1]
            w_st = jnp.asarray(_np.concatenate(
                [wrest, _np.full(S * per - L, BIG_WINDOW, wrest.dtype)]
            ).reshape(S, per))
        def stage_fn(stage_p, xm):
            def layer_fn(x, lp, w, v):
                # positions from the local shape: inside the shard_map the
                # batch dim is the per-(data-shard, microbatch) slice
                pos = jnp.broadcast_to(jnp.arange(s)[None], (x.shape[0], s))
                x2, _, a1, _ = _decoder_layer_apply(
                    lp, cfg, x, pos, window=w, cache=None,
                    prefix_len=None)
                # padded slots are identities (residual layers), so the
                # pipelined stack equals the sequential unpadded one
                return jnp.where(v, x2, x), jnp.where(v, a1, 0.0)

            lfn = remat(layer_fn)

            def body(carry, inp):
                x, aux = carry
                x2, a1 = lfn(x, *inp)
                return (x2, aux + a1), None

            # the layers consult the ambient TP plan: sharded projections
            # plus manual psums after the out-projections
            with mtp.use_stage_tp(tp_plan):
                (xm, aux), _ = jax.lax.scan(
                    body, (xm, jnp.float32(0.0)),
                    (stage_p["params"], stage_p["windows"], stage_p["valid"]))
            return xm, aux

        if tp_plan is not None:
            pspecs = {"params": mtp.stage_param_specs(
                          tp_plan, axes_tree(self.schema())["layers"],
                          axis_name),
                      "windows": _P(axis_name), "valid": _P(axis_name)}
        else:
            pspecs = None
        xm = x.reshape((M, b // M) + x.shape[1:])
        y, aux_pipe = pipeline_apply(
            stage_fn, {"params": sp, "windows": w_st, "valid": v_st}, xm,
            mesh, axis_name, batch_axes=batch_axes, param_specs=pspecs,
            with_aux=True)
        h = y.reshape(b, s, -1)
        # aux_pipe sums over (microbatch x data-shard) chunks — each data
        # shard computes its own MoE statistics inside the manual region —
        # so normalise to the mean over chunks (grad accumulation makes
        # the same per-chunk redefinition of batch statistics)
        sizes = dict(mesh.shape)
        chunks = M
        for a in batch_axes:
            chunks *= sizes.get(a, 1)
        aux_total = aux_outer + aux_pipe / chunks
        h = rms_norm(h, params["final_norm"], cfg.norm_eps)
        labels = batch["labels"]
        mask = batch.get("loss_mask", jnp.ones_like(labels, jnp.float32))
        total, denom = chunked_softmax_xent(
            h, params["embed"] if cfg.tie_embeddings else params["head"].T,
            labels, mask, softcap=cfg.logit_softcap)
        loss = total / jnp.maximum(denom, 1.0)
        if cfg.num_experts:
            loss = loss + 0.01 * aux_total / cfg.num_layers
        return loss, {"loss": loss, "aux_loss": aux_total}

    def cache_spec(self, batch: int, length: int):
        cfg = self.cfg
        one = attn.kv_cache_spec(cfg, batch, length)
        n_dense = cfg.first_dense_layers
        n_rest = cfg.num_layers - n_dense
        out = {"layers": jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((n_rest,) + s.shape, s.dtype), one)}
        if n_dense:
            out["dense_layers"] = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((n_dense,) + s.shape, s.dtype), one)
        return out

    def cache_axes(self, batch: int, length: int):
        cfg = self.cfg
        one = attn.kv_cache_axes(cfg)
        out = {"layers": one}
        if cfg.first_dense_layers:
            out["dense_layers"] = one
        return out

    def prefill_chunk(self, params, batch, cache, pos0):
        """Chunked prefill: append a chunk at positions [pos0, pos0+C).

        ``cache`` already holds every earlier chunk (offset == absolute
        position, no padding); the chunk attends over the whole cache and
        is written at offsets [pos0, pos0+C).  ``pos0`` is a traced scalar,
        so one compile covers every chunk of the same (C, cache_len) shape
        — the paged engine decomposes prompts into power-of-two chunks for
        an O(log) compile footprint.  A first chunk may carry ``patches``
        (prefix tokens ahead of its text, the prefix-LM mask over them).
        Returns (last-token logits, cache).  With an expert share
        (``experts_held``) the cache also holds ``moe.ROUTES``: (B, C, MoE
        layers, k), the held expert each routed pair went to, -1 where it
        is not held here.
        """
        x = self._embed_in(params, batch)
        b, s, _ = x.shape
        positions = jnp.broadcast_to(
            jnp.asarray(pos0) + jnp.arange(s)[None], (b, s))
        h, cache, _ = self._stack(params, x, positions, cache,
                                  self._prefix(b))
        logits = self._logits(params, h[:, -1:])
        return logits[:, 0], cache

    def decode_step(self, params, tokens, cache, pos):
        """One token a row at per-row positions ``pos`` (B,); -> (logits,
        cache), the cache with ``moe.ROUTES`` as in ``prefill_chunk``."""
        x = self._embed_in(params, {"tokens": tokens})
        b = x.shape[0]
        h, cache, _ = self._stack(params, x, jnp.reshape(pos, (b, 1)), cache,
                                  None)
        logits = self._logits(params, h)
        return logits[:, 0], cache


@jax.named_scope("head")
def _tied_head(h, embed):
    """(B, D) hidden states -> (B, V) logits over the tied embedding."""
    return jnp.einsum("bd,vd->bv", h.astype(jnp.float32),
                      embed.astype(jnp.float32))


def _none_like(tree):
    """A scan-compatible 'no cache' pytree (None leaves break scan xs)."""
    n = jax.tree.leaves(tree)[0].shape[0]
    return jnp.zeros((n, 0))


def _as_cache(x):
    """Scan slices of the _none_like dummy become arrays; map them to None."""
    return x if isinstance(x, dict) else None


# =============================================================================
# encoder-decoder family (whisper)
# =============================================================================

def _enc_layer_defs(cfg: ModelConfig):
    return {"ln1": ln_defs(cfg.d_model), "attn": attn.gqa_defs(cfg),
            "ln2": ln_defs(cfg.d_model),
            "mlp": mlp_defs(cfg.d_model, cfg.d_ff, gated=False)}


def _dec_layer_defs(cfg: ModelConfig):
    return {"ln1": ln_defs(cfg.d_model), "self_attn": attn.gqa_defs(cfg),
            "ln_x": ln_defs(cfg.d_model), "cross_attn": attn.gqa_defs(cfg),
            "ln2": ln_defs(cfg.d_model),
            "mlp": mlp_defs(cfg.d_model, cfg.d_ff, gated=False)}


@dataclasses.dataclass
class EncDecModel(Serving):
    cfg: ModelConfig

    def schema(self):
        cfg = self.cfg
        return {
            "embed": embed_def(cfg.vocab_size, cfg.d_model),
            # decoder learned positions sized for the largest decode shape
            "pos_embed": ParamDef((32_768, cfg.d_model),
                                  (None, "d_model"), jnp.bfloat16, "embed"),
            "enc_pos_embed": ParamDef((cfg.encoder_frames, cfg.d_model),
                                      ("frames", "d_model"), jnp.bfloat16,
                                      "embed"),
            "enc_layers": stack(_enc_layer_defs(cfg), cfg.encoder_layers),
            "enc_norm": ln_defs(cfg.d_model),
            "dec_layers": stack(_dec_layer_defs(cfg), cfg.num_layers),
            "dec_norm": ln_defs(cfg.d_model),
        }

    def encode(self, params, frames):
        cfg = self.cfg
        x = frames.astype(jnp.bfloat16) + params["enc_pos_embed"][None]

        def body(x, lp):
            h = layer_norm(x, lp["ln1"]["gamma"], lp["ln1"]["beta"], cfg.norm_eps)
            a, _ = attn.gqa_apply(lp["attn"], cfg, h,
                                  jnp.arange(x.shape[1]), window=None,
                                  causal=False, rope=False)
            x = x + a
            h = layer_norm(x, lp["ln2"]["gamma"], lp["ln2"]["beta"], cfg.norm_eps)
            x = x + mlp_apply(lp["mlp"], h, "gelu", False, cfg.matmul_mode)
            return x, None

        x, _ = jax.lax.scan(body, x, params["enc_layers"])
        return layer_norm(x, params["enc_norm"]["gamma"],
                          params["enc_norm"]["beta"], cfg.norm_eps)

    def _decode_stack(self, params, x, positions, enc_out, caches,
                      cross_cache=None):
        """enc_out drives cross attention in training and in a first
        prefill chunk; later chunks and decode read the per-layer cross
        K/V that chunk cached (computed once, not re-projected from the
        encoder output every token)."""
        cfg = self.cfg
        kh, hd = cfg.num_kv_heads, cfg.head_dim

        def body(carry, inp):
            x, = carry
            lp, lcache, lcross = inp
            lcache = _as_cache(lcache)
            h = layer_norm(x, lp["ln1"]["gamma"], lp["ln1"]["beta"], cfg.norm_eps)
            a, ncache = attn.gqa_apply(lp["self_attn"], cfg, h, positions,
                                       window=None, cache=lcache, rope=False)
            x = x + a
            h = layer_norm(x, lp["ln_x"]["gamma"], lp["ln_x"]["beta"], cfg.norm_eps)
            if lcross is not None:
                ck, cv = lcross["k"], lcross["v"]
            else:
                b, f = enc_out.shape[0], enc_out.shape[1]
                ck = dense(enc_out, lp["cross_attn"]["wk"],
                           cfg.matmul_mode).reshape(b, f, kh, hd)
                cv = dense(enc_out, lp["cross_attn"]["wv"],
                           cfg.matmul_mode).reshape(b, f, kh, hd)
            a, _ = attn.gqa_apply(lp["cross_attn"], cfg, h, positions,
                                  window=None, cross_kv=(ck, cv), rope=False)
            x = x + a
            h = layer_norm(x, lp["ln2"]["gamma"], lp["ln2"]["beta"], cfg.norm_eps)
            x = x + mlp_apply(lp["mlp"], h, "gelu", False, cfg.matmul_mode)
            new_cross = {"k": ck.astype(jnp.bfloat16),
                         "v": cv.astype(jnp.bfloat16)}
            return (x,), (ncache, new_cross)

        body_fn = (jax.checkpoint(body) if (cfg.remat and caches is None)
                   else body)
        cc = caches if caches is not None else _none_like(params["dec_layers"])
        xc = (cross_cache if cross_cache is not None
              else _none_like(params["dec_layers"]))

        def body_wrap(carry, inp):
            lp, lcache, lcross = inp
            return body_fn(carry, (lp, lcache, _as_cache(lcross)))

        (x,), (new_caches, new_cross) = jax.lax.scan(
            body_wrap, (x,), (params["dec_layers"], cc, xc))
        x = layer_norm(x, params["dec_norm"]["gamma"],
                       params["dec_norm"]["beta"], cfg.norm_eps)
        return x, (new_caches if caches is not None else None), new_cross

    def loss(self, params, batch):
        cfg = self.cfg
        enc_out = self.encode(params, batch["frames"])
        tokens = batch["tokens"]
        b, s = tokens.shape
        x = embed_lookup(params["embed"], tokens) + params["pos_embed"][None, :s]
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        h, _, _ = self._decode_stack(params, x, positions, enc_out, None)
        mask = batch.get("loss_mask", jnp.ones_like(batch["labels"], jnp.float32))
        total, denom = chunked_softmax_xent(h, params["embed"],
                                            batch["labels"], mask)
        loss = total / jnp.maximum(denom, 1.0)
        return loss, {"loss": loss}

    def cache_spec(self, batch: int, length: int):
        cfg = self.cfg
        one = attn.kv_cache_spec(cfg, batch, length)
        stk = lambda t: jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((cfg.num_layers,) + s.shape,
                                           s.dtype), t)
        cross_one = {
            "k": jax.ShapeDtypeStruct(
                (batch, cfg.encoder_frames, cfg.num_kv_heads, cfg.head_dim),
                jnp.bfloat16),
            "v": jax.ShapeDtypeStruct(
                (batch, cfg.encoder_frames, cfg.num_kv_heads, cfg.head_dim),
                jnp.bfloat16),
        }
        return {"self": stk(one), "cross": stk(cross_one)}

    def cache_axes(self, batch: int, length: int):
        one = attn.kv_cache_axes(self.cfg)
        cross = {"k": ("stack", "batch", "frames", "kv_heads", None),
                 "v": ("stack", "batch", "frames", "kv_heads", None)}
        return {"self": one, "cross": cross}

    def prefill_chunk(self, params, batch, cache, pos0):
        """Chunked prefill.  The first chunk carries ``frames`` and runs
        the encoder (filling the per-layer cross K/V cache); later chunks
        read cross K/V from the cache and only append self-attention K/V
        at offsets [pos0, pos0+C).  Returns (last-token logits, cache)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        b, s = tokens.shape
        if "frames" in batch:
            enc_out, cross_cache = self.encode(params, batch["frames"]), None
        else:
            enc_out, cross_cache = None, cache["cross"]
        x = embed_lookup(params["embed"], tokens)
        x = x + jax.lax.dynamic_slice_in_dim(params["pos_embed"],
                                             jnp.asarray(pos0), s,
                                             axis=0)[None]
        positions = jnp.broadcast_to(
            jnp.asarray(pos0) + jnp.arange(s)[None], (b, s))
        h, selfc, cross = self._decode_stack(params, x, positions, enc_out,
                                             cache["self"],
                                             cross_cache=cross_cache)
        logits = _tied_head(h[:, -1], params["embed"])
        return logits, {"self": selfc, "cross": cross}

    def decode_step(self, params, tokens, cache, pos):
        b = tokens.shape[0]
        x = embed_lookup(params["embed"], tokens)
        x = x + jnp.take(params["pos_embed"], pos, axis=0)[:, None]
        h, selfc, cross = self._decode_stack(params, x,
                                             jnp.reshape(pos, (b, 1)), None,
                                             cache["self"],
                                             cross_cache=cache["cross"])
        logits = _tied_head(h[:, 0], params["embed"])
        return logits, {"self": selfc, "cross": cross}


# =============================================================================
# hybrid family (zamba2): mamba2 backbone + shared attention block
# =============================================================================

@dataclasses.dataclass
class HybridModel(Serving):
    cfg: ModelConfig

    def _group_dims(self):
        cfg = self.cfg
        n_groups = cfg.num_layers // cfg.attn_every
        return n_groups, cfg.attn_every

    def schema(self):
        cfg = self.cfg
        n_groups, per = self._group_dims()
        mamba = stack(stack({"block": ssm_mod.mamba2_defs(cfg),
                             "ln": norm_def(cfg.d_model)}, per), n_groups)
        r = cfg.lora_rank
        lora = stack({
            "a_q": ParamDef((cfg.d_model, r), ("d_model", None)),
            "b_q": ParamDef((r, cfg.num_heads * cfg.head_dim), (None, "heads"),
                            jnp.bfloat16, "zeros"),
        }, n_groups)
        return {
            "embed": embed_def(cfg.vocab_size, cfg.d_model),
            "final_norm": norm_def(cfg.d_model),
            "mamba": mamba,
            "shared": {"ln1": norm_def(cfg.d_model),
                       "attn": attn.gqa_defs(cfg),
                       "ln2": norm_def(cfg.d_model),
                       "mlp": mlp_defs(cfg.d_model, cfg.d_ff, True)},
            "lora": lora,
        }

    def _forward(self, params, x, positions, caches):
        cfg = self.cfg
        n_groups, per = self._group_dims()
        shared = params["shared"]

        def group_body(carry, inp):
            x, = carry
            gp, lora_p, gcache = inp
            gcache = _as_cache(gcache)

            def mamba_body(xc, minp):
                mp, mcache = minp
                mcache = _as_cache(mcache)
                h = rms_norm(xc, mp["ln"], cfg.norm_eps)
                y, mstate = ssm_mod.mamba2_apply(mp["block"], cfg, h,
                                                 state=mcache)
                return xc + y, (mstate if mstate is not None
                                else jnp.zeros((0,)))

            mamba_fn = (jax.checkpoint(mamba_body)
                        if (cfg.remat and caches is None) else mamba_body)
            mc = (gcache["mamba"] if gcache is not None else
                  _none_like(gp))
            x, new_mc = jax.lax.scan(mamba_fn, x, (gp, mc))
            # shared attention block with per-group LoRA (parallel adapter)
            h = rms_norm(x, shared["ln1"], cfg.norm_eps)
            ac = gcache["attn"] if gcache is not None else None
            a, new_ac = attn.gqa_apply(shared["attn"], cfg, h, positions,
                                       window=None, cache=ac)
            a = a + dense(dense(h, lora_p["a_q"], "bf16"), lora_p["b_q"],
                          "bf16")
            x = x + a
            h = rms_norm(x, shared["ln2"], cfg.norm_eps)
            x = x + mlp_apply(shared["mlp"], h, cfg.act, True, cfg.matmul_mode)
            new_cache = ({"mamba": new_mc, "attn": new_ac}
                         if gcache is not None else jnp.zeros((0,)))
            return (x,), new_cache

        gc = caches if caches is not None else _none_like(params["lora"])
        (x,), new_caches = jax.lax.scan(group_body, (x,),
                                        (params["mamba"], params["lora"], gc))
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return x, (new_caches if caches is not None else None)

    def loss(self, params, batch):
        tokens = batch["tokens"]
        b, s = tokens.shape
        x = embed_lookup(params["embed"], tokens)
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        h, _ = self._forward(params, x, positions, None)
        mask = batch.get("loss_mask", jnp.ones_like(batch["labels"], jnp.float32))
        total, denom = chunked_softmax_xent(h, params["embed"],
                                            batch["labels"], mask)
        loss = total / jnp.maximum(denom, 1.0)
        return loss, {"loss": loss}

    def cache_spec(self, batch: int, length: int):
        cfg = self.cfg
        n_groups, per = self._group_dims()
        mamba_one = ssm_mod.mamba2_state_spec(cfg, batch)
        attn_one = attn.kv_cache_spec(cfg, batch, length)

        def stk(tree, n):
            return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
                (n,) + s.shape, s.dtype), tree)

        return stk({"mamba": stk(mamba_one, per), "attn": attn_one}, n_groups)

    def cache_axes(self, batch: int, length: int):
        mamba = {"conv": ("stack", "stack2", "batch", None, "ffn"),
                 "ssm": ("stack", "stack2", "batch", "heads", None, "state")}
        return {"mamba": mamba, "attn": attn.kv_cache_axes(self.cfg)}

    def prefill_chunk(self, params, batch, cache, pos0):
        """Chunked prefill: the attention KV caches append at offsets
        [pos0, pos0+C); the mamba conv/SSM states carry across chunks
        (``mamba2_apply`` continues from the stored state).  Returns
        (last-token logits, cache)."""
        tokens = batch["tokens"]
        b, s = tokens.shape
        x = embed_lookup(params["embed"], tokens)
        positions = jnp.broadcast_to(
            jnp.asarray(pos0) + jnp.arange(s)[None], (b, s))
        h, cache = self._forward(params, x, positions, cache)
        logits = _tied_head(h[:, -1], params["embed"])
        return logits, cache

    def decode_step(self, params, tokens, cache, pos):
        b = tokens.shape[0]
        x = embed_lookup(params["embed"], tokens)
        h, cache = self._forward(params, x, jnp.reshape(pos, (b, 1)), cache)
        logits = _tied_head(h[:, 0], params["embed"])
        return logits, cache


# =============================================================================
# xLSTM family
# =============================================================================

@dataclasses.dataclass
class XLSTMModel(Serving):
    cfg: ModelConfig

    def _group_dims(self):
        cfg = self.cfg
        per = cfg.slstm_every
        return cfg.num_layers // per, per

    def schema(self):
        cfg = self.cfg
        n_groups, per = self._group_dims()
        return {
            "embed": embed_def(cfg.vocab_size, cfg.d_model),
            "final_norm": norm_def(cfg.d_model),
            "mlstm": stack(stack({"ln": norm_def(cfg.d_model),
                                  "block": ssm_mod.mlstm_defs(cfg)}, per - 1),
                           n_groups),
            "slstm": stack({"ln": norm_def(cfg.d_model),
                            "block": ssm_mod.slstm_defs(cfg)}, n_groups),
        }

    def _forward(self, params, x, caches):
        cfg = self.cfg

        def group_body(carry, inp):
            x, = carry
            mp, sp, gcache = inp
            gcache = _as_cache(gcache)

            def m_body(xc, minp):
                lp, mstate = minp
                mstate = _as_cache(mstate)
                h = rms_norm(xc, lp["ln"], cfg.norm_eps)
                y, new_state = ssm_mod.mlstm_apply(lp["block"], cfg, h,
                                                   state=mstate)
                return xc + y, (new_state if new_state is not None
                                else jnp.zeros((0,)))

            m_fn = (jax.checkpoint(m_body)
                    if (cfg.remat and caches is None) else m_body)
            mc = gcache["mlstm"] if gcache is not None else _none_like(mp)
            x, new_mc = jax.lax.scan(m_fn, x, (mp, mc))
            h = rms_norm(x, sp["ln"], cfg.norm_eps)
            sc = gcache["slstm"] if gcache is not None else None
            y, new_sc = ssm_mod.slstm_apply(sp["block"], cfg, h, state=sc)
            x = x + y
            new_cache = ({"mlstm": new_mc, "slstm": new_sc}
                         if gcache is not None else jnp.zeros((0,)))
            return (x,), new_cache

        gc = caches if caches is not None else _none_like(params["slstm"])
        (x,), new_caches = jax.lax.scan(group_body, (x,),
                                        (params["mlstm"], params["slstm"], gc))
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return x, (new_caches if caches is not None else None)

    def loss(self, params, batch):
        tokens = batch["tokens"]
        x = embed_lookup(params["embed"], tokens)
        h, _ = self._forward(params, x, None)
        mask = batch.get("loss_mask", jnp.ones_like(batch["labels"], jnp.float32))
        total, denom = chunked_softmax_xent(h, params["embed"],
                                            batch["labels"], mask)
        loss = total / jnp.maximum(denom, 1.0)
        return loss, {"loss": loss}

    def cache_spec(self, batch: int, length: int):
        cfg = self.cfg
        n_groups, per = self._group_dims()

        def stk(tree, n):
            return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
                (n,) + s.shape, s.dtype), tree)

        return stk({"mlstm": stk(ssm_mod.mlstm_state_spec(cfg, batch), per - 1),
                    "slstm": ssm_mod.slstm_state_spec(cfg, batch)}, n_groups)

    def cache_axes(self, batch: int, length: int):
        m = {"C": ("stack", "stack2", "batch", "heads", None, None),
             "n": ("stack", "stack2", "batch", "heads", None),
             "m": ("stack", "stack2", "batch", "heads")}
        s = {"c": ("stack", "batch", "heads", None),
             "n": ("stack", "batch", "heads", None),
             "h": ("stack", "batch", "heads", None),
             "m": ("stack", "batch", "heads")}
        return {"mlstm": m, "slstm": s}

    def prefill_chunk(self, params, batch, cache, pos0):
        """Chunked prefill: pure recurrent state, so a chunk is just a
        forward pass continuing from the stored per-slot state (``pos0``
        is accepted for API uniformity; xLSTM has no positional terms)."""
        del pos0
        tokens = batch["tokens"]
        x = embed_lookup(params["embed"], tokens)
        h, cache = self._forward(params, x, cache)
        logits = _tied_head(h[:, -1], params["embed"])
        return logits, cache

    def decode_step(self, params, tokens, cache, pos):
        x = embed_lookup(params["embed"], tokens)
        h, cache = self._forward(params, x, cache)
        logits = _tied_head(h[:, 0], params["embed"])
        return logits, cache


# =============================================================================

def build(cfg: ModelConfig):
    return {"decoder": DecoderModel, "encdec": EncDecModel,
            "hybrid": HybridModel, "xlstm": XLSTMModel}[cfg.family](cfg)
