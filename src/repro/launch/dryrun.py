"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: builds the
production mesh from 512 placeholder host devices, lowers train_step /
serve_step against ShapeDtypeStruct inputs with the production shardings,
compiles, and records memory_analysis / cost_analysis / collective traffic
for the roofline tables.

Usage:
  python -m repro.launch.dryrun --arch gemma3_12b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --out results/dryrun.json
"""
# MUST be the very first lines, before ANY other import (jax locks the
# device count on first init).  Do NOT set this anywhere else.  The
# placeholder devices are host CPUs, so the dry-run never takes a chip.
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
os.environ["JAX_PLATFORMS"] = "cpu"

import argparse
import contextlib
import dataclasses
import functools
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, SHAPES, get_config, shape_applicable
from repro.configs.base import ModelConfig, ShapeConfig
from repro.dist import sharding as shd
from repro.launch.inputs import batch_axes, input_specs
from repro.launch.mesh import make_mesh, make_production_mesh, mesh_axis_size
from repro.launch.results import cell_key, is_canonical
from repro.models import build
from repro.models.params import abstract_tree, axes_tree
from repro.optim.optimizer import (OptimizerConfig, abstract_opt_state,
                                   opt_state_axes)
from repro.roofline.analysis import (RooflineTerms, collective_bytes,
                                     model_flops_estimate)
from repro.train.train_step import TrainPlan, make_train_step


def _opt_config(cfg: ModelConfig) -> OptimizerConfig:
    big = cfg.num_layers * cfg.d_model * cfg.d_model > 60 * 4096 * 4096
    return OptimizerConfig(
        moment_dtype=jnp.bfloat16 if big else jnp.float32)


def _rules_for(shape: ShapeConfig, mesh, preset: str = "default",
               seq_shards: int = 0) -> shd.Rules:
    if preset != "default":
        if preset not in shd.RULE_PRESETS:
            raise ValueError(
                f"unknown rules preset {preset!r}; valid: "
                f"{sorted(shd.RULE_PRESETS)}")
        return shd.get_rules(preset)
    if seq_shards > 1:
        return shd.get_rules("sequence")
    if shape.kind == "train":
        return shd.get_rules("train")
    if shape.kind == "prefill":
        return shd.get_rules("prefill")
    return shd.get_rules("decode", batch=shape.global_batch,
                         data_size=mesh_axis_size(mesh, "data"))


def _parse_mesh_shape(mesh_shape: str):
    """Parse a "data,model" per-pod reshape; single source of the
    positive-factors and 256-chips/pod invariants for CLI and API."""
    try:
        dd, mm = (int(v) for v in mesh_shape.split(","))
    except ValueError as e:
        raise ValueError(f"mesh_shape must be 'data,model' ints, "
                         f"got {mesh_shape!r}") from e
    if dd <= 0 or mm <= 0 or dd * mm != 256:
        raise ValueError(f"mesh_shape {mesh_shape!r}: need positive "
                         f"data,model with data*model == 256 chips/pod")
    return dd, mm


def _batch_dp_axes(mesh, rules: shd.Rules, global_batch: int):
    """Mesh axes that *actually* shard the global batch under ``rules``.

    partition_spec's divisibility fallback may drop axes the rule asked
    for, so this — not the rule itself — is what the compiled program
    does; TrainPlan and the analytic roofline must agree with it.
    """
    entry = shd.partition_spec(mesh, rules, (global_batch,), ("batch",))[0]
    if entry is None:
        return ()
    return (entry,) if isinstance(entry, str) else tuple(entry)


def smoke_shapes(proxy_seq: int = 2048) -> Dict[str, ShapeConfig]:
    """Reduced shapes for --smoke mode (structure-identical, fast compile).

    Derived from the canonical ``SHAPES`` via ``dataclasses.replace`` so
    name/kind/identity have a single source of truth (re-declaring
    ``ShapeConfig`` literals here once let long_500k silently drift from
    the canonical 524_288 definition).  The long_500k smoke proxy length
    is a deliberate reduction, exposed as ``--proxy-seq``.
    """
    return {
        "train_4k": dataclasses.replace(
            SHAPES["train_4k"], seq_len=128, global_batch=32),
        "prefill_32k": dataclasses.replace(
            SHAPES["prefill_32k"], seq_len=256, global_batch=32),
        "decode_32k": dataclasses.replace(
            SHAPES["decode_32k"], seq_len=256, global_batch=32),
        "long_500k": dataclasses.replace(
            SHAPES["long_500k"], seq_len=proxy_seq),
    }


#: default --smoke shape set (kept as a constant for importers)
SMOKE_SHAPES = smoke_shapes()


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               overrides: Optional[Dict[str, Any]] = None,
               compile_only: bool = True, smoke: bool = False,
               rules_preset: str = "default",
               mesh_shape: Optional[str] = None,
               pipeline_stages: int = 0, seq_shards: int = 0,
               proxy_seq: int = 2048) -> Dict[str, Any]:
    """Lower + compile one cell; returns the roofline record.

    ``mesh_shape`` ("data,model", e.g. "64,4") reshapes the 256 chips/pod
    for §Perf sharding experiments; the canonical dry-run keeps 16x16.
    ``pipeline_stages`` > 0 builds a stage-bearing (S, 16/S, 16) per-pod
    mesh and lowers the *pipelined* train step (train shapes, decoder
    family only); the record carries the stage count, pipeline
    microbatches, and bubble fraction.  ``seq_shards`` > 1 builds a
    seq-bearing (Q, 16/Q, 16) per-pod mesh, applies the "sequence" rules
    preset and traces under ``repro.dist.seq.use_ring`` — ring attention
    over the seq-sharded KV cache, which is what makes long_500k lower
    for full-attention archs.  ``proxy_seq`` is the --smoke long_500k
    proxy length (see ``smoke_shapes``).
    """
    cfg = get_config(arch, smoke=smoke)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = (smoke_shapes(proxy_seq) if smoke else SHAPES)[shape_name]
    base = {"arch": arch, "shape": shape_name,
            "mesh": "multi" if multi_pod else "single"}
    ok, reason = shape_applicable(cfg, shape, seq_shards=seq_shards or 1)
    if not ok:
        return {**base, "status": "skipped", "reason": reason}
    model = build(cfg)
    if pipeline_stages:
        if shape.kind != "train":
            return {**base, "status": "skipped",
                    "reason": "pipeline: train shapes only"}
        if not hasattr(model, "pipeline_loss") or cfg.num_prefix_tokens:
            return {**base, "status": "skipped",
                    "reason": "pipeline: decoder-family stacks only"}
        if mesh_shape:
            return {**base, "status": "skipped",
                    "reason": "pipeline: incompatible with --mesh-shape"}
    if seq_shards > 1 and (pipeline_stages or mesh_shape):
        return {**base, "status": "skipped",
                "reason": "seq: incompatible with --pipeline/--mesh-shape"}

    if mesh_shape:
        dd, mm = _parse_mesh_shape(mesh_shape)
        if multi_pod:
            mesh = make_mesh((2, dd, mm), ("pod", "data", "model"))
        else:
            mesh = make_mesh((dd, mm), ("data", "model"))
    else:
        mesh = make_production_mesh(
            multi_pod=multi_pod, pipeline_stages=pipeline_stages or 1,
            seq_shards=seq_shards if seq_shards > 1 else 1)
    chips = mesh.devices.size
    rules = _rules_for(shape, mesh, rules_preset,
                       seq_shards=seq_shards if seq_shards > 1 else 0)
    if pipeline_stages and rules_preset == "default":
        rules = shd.get_rules("pipeline")
    schema = model.schema()
    aparams = abstract_tree(schema)
    paxes = axes_tree(schema)
    params_sh = shd.tree_shardings(mesh, rules, aparams, paxes)

    abatch = input_specs(cfg, shape)
    baxes = batch_axes(cfg, shape)
    batch_sh = jax.tree.map(
        lambda av, ax: shd.named_sharding(mesh, rules, av.shape, ax),
        abatch, baxes,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))

    if seq_shards > 1:
        from repro.dist import seq as msq
        ring_cm = msq.use_ring(mesh)
    else:
        ring_cm = contextlib.nullcontext()
    t0 = time.time()
    with shd.use_rules(mesh, rules), ring_cm:
        if shape.kind == "train":
            opt_cfg = _opt_config(cfg)
            astate = {"params": aparams,
                      "opt": abstract_opt_state(aparams, opt_cfg)}
            saxes = {"params": paxes, "opt": opt_state_axes(paxes)}
            state_sh = shd.tree_shardings(mesh, rules, astate, saxes)
            dp_shards = 1
            for a in _batch_dp_axes(mesh, rules, shape.global_batch):
                dp_shards *= mesh_axis_size(mesh, a)
            # TrainPlan's transient-stage-weight charge must reflect what
            # plan_stage_tp will ACTUALLY shard inside the region: a config
            # whose dims don't divide the model axis keeps its stage
            # weights fully gathered, so charging 1/tp would underestimate
            # the footprint 16x and pick an M that OOMs.  Require the
            # dominant weight dims (ffn/experts, plus heads) to shard.
            tp_shards = 1
            if pipeline_stages:
                from repro.dist import tp as _tp
                tplan = _tp.plan_stage_tp(cfg, mesh)
                if (tplan is not None and tplan.shard_heads
                        and (tplan.shard_ffn or tplan.shard_experts)):
                    tp_shards = tplan.size
            plan = TrainPlan.for_shape(
                cfg, shape, dp_shards,
                pipeline_stages=pipeline_stages or 1,
                tp_shards=tp_shards)
            step = make_train_step(model, opt_cfg, plan,
                                   mesh=mesh if pipeline_stages else None)
            jitted = jax.jit(step, in_shardings=(state_sh, batch_sh),
                             out_shardings=(state_sh, None),
                             donate_argnums=(0,))
            lowered = jitted.lower(astate, abatch)
        elif shape.kind == "prefill":
            fn = functools.partial(model.prefill, cache_len=shape.seq_len)
            acache = model.cache_spec(shape.global_batch,
                                      shape.seq_len + cfg.num_prefix_tokens)
            caxes = model.cache_axes(shape.global_batch, shape.seq_len)
            cache_sh = shd.tree_shardings(mesh, rules, acache, caxes)
            jitted = jax.jit(fn, in_shardings=(params_sh, batch_sh),
                             out_shardings=(None, cache_sh))
            lowered = jitted.lower(aparams, abatch)
        else:  # decode
            cache_len = shape.seq_len + cfg.num_prefix_tokens
            acache = model.cache_spec(shape.global_batch, cache_len)
            caxes = model.cache_axes(shape.global_batch, cache_len)
            cache_sh = shd.tree_shardings(mesh, rules, acache, caxes)
            apos = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)
            jitted = jax.jit(model.decode_step,
                             in_shardings=(params_sh, batch_sh["tokens"],
                                           cache_sh, None),
                             out_shardings=(None, cache_sh),
                             donate_argnums=(2,))
            lowered = jitted.lower(aparams, abatch["tokens"], acache, apos)
        t_lower = time.time() - t0
        record: Dict[str, Any] = {
            **base, "chips": chips, "t_lower_s": round(t_lower, 1),
        }
        if pipeline_stages:
            record["pipeline_stages"] = plan.pipeline_stages
            record["pipeline_microbatches"] = plan.pipeline_microbatches
            record["bubble_fraction"] = round(plan.bubble, 6)
        if overrides:
            record["overrides"] = {k: str(v) for k, v in overrides.items()}
        if not compile_only:
            record["status"] = "lowered"
            return record
        t0 = time.time()
        compiled = lowered.compile()
        record["t_compile_s"] = round(time.time() - t0, 1)

    # memory_analysis reports PER-DEVICE sizes for the partitioned module
    mem = compiled.memory_analysis()
    arg_b = int(getattr(mem, "argument_size_in_bytes", 0))
    out_b = int(getattr(mem, "output_size_in_bytes", 0))
    tmp_b = int(getattr(mem, "temp_size_in_bytes", 0))
    record["memory"] = {
        "argument_bytes_per_device": arg_b,
        "output_bytes_per_device": out_b,
        "temp_bytes_per_device": tmp_b,
        "xla_peak_bytes_per_device": int(getattr(mem, "peak_memory_in_bytes", 0)),
        # CPU-backend temp lacks TPU liveness optimisation; report args+temp
        # as the pessimistic bound, xla_peak as XLA's own estimate.
        "peak_bytes_per_device": arg_b + tmp_b,
    }
    # raw XLA numbers (cross-check only: while-loop bodies counted once)
    cost = compiled.cost_analysis() or {}
    flops = float(cost.get("flops", 0.0))
    hbm = float(cost.get("bytes accessed", 0.0))
    hlo = compiled.as_text()
    coll = collective_bytes(hlo, default_group=chips)
    record["xla_raw"] = {"flops_per_device": flops, "hbm_bytes_per_device": hbm,
                         "collectives": coll}

    # analytic roofline terms (exact matmul counts; see repro.roofline.model).
    # Only layouts the analytic model describes get terms: the per-shape
    # default, the dp_only fold, and train/sp/prefill presets on their own
    # shape kind ("sp" == the adopted sequence-parallel train layout).
    # Mismatched preset/shape combinations record xla_raw only, so the
    # roofline tables never mix terms from different layouts.
    analytic_ok = (
        rules_preset in ("default", "dp_only")
        or (rules_preset in ("train", "sp") and shape.kind == "train")
        or (rules_preset == "prefill" and shape.kind == "prefill"))
    if not analytic_ok:
        record["status"] = "ok"
        return record
    from repro.roofline.model import MeshSpec, analytic_cell
    # MeshSpec geometry comes from the mesh itself: its data/model sizes
    # drive *parameter*-sharding accounting (FSDP/TP, and the folded
    # decode layout's 256-way weight sharding), which the batch spec says
    # nothing about.  Batch-DP shortfall in non-dividing experiment cells
    # (e.g. --mesh-shape 256,1) is a known analytic approximation; the
    # compiled truth for the train microbatching is carried by
    # ``plan.accum_steps`` below.
    dd = mesh_axis_size(mesh, "data")
    mm = mesh_axis_size(mesh, "model")
    stages = mesh_axis_size(mesh, "stage") if pipeline_stages else 1
    seqs = mesh_axis_size(mesh, "seq")
    if pipeline_stages:
        # composed (stage, data, model) layout: since TP runs inside the
        # stage bodies (repro.dist.tp), the lowered step really does
        # execute a 1/(S*data*model) layer-block slice per chip — the
        # analytic MeshSpec carries the stage axis explicitly so weight
        # sharding uses model*stage while the TP collective group stays
        # the model axis, matching the compiled program (xla_raw remains
        # the cross-check).  The bubble factor is carried by
        # ``pipeline_bubble``.
        record["roofline_layout"] = (
            "composed: stage-block sharding with TP inside the stage "
            "bodies (matches the lowered step)")
    if rules_preset == "dp_only":
        # weights replicate, so only batch DP matters — count the mesh
        # axes that actually divide the batch (fallback may drop some)
        dd = 1
        for a in _batch_dp_axes(mesh, rules, shape.global_batch):
            if a != "pod":
                dd *= mesh_axis_size(mesh, a)
        mm = 1
    mesh_spec = MeshSpec(pod=2 if multi_pod else 1, data=dd, model=mm,
                         stage=stages, seq=seqs)
    accum = 1
    moment_bytes = 4
    if shape.kind == "train":
        accum = plan.accum_steps  # the plan the step was compiled with
        moment_bytes = 2 if _opt_config(cfg).moment_dtype == jnp.bfloat16 else 4
    cell = analytic_cell(cfg, shape, mesh_spec, accum=accum,
                         remat=cfg.remat and shape.kind == "train",
                         moment_bytes=moment_bytes,
                         pipeline_bubble=record.get("bubble_fraction", 0.0))
    record["roofline"] = cell["terms"].as_dict()
    record["roofline"]["flops_breakdown"] = cell["flops"]
    record["roofline"]["hbm_breakdown"] = cell["hbm"]
    record["roofline"]["coll_breakdown"] = cell["coll"]
    # OISMA-engine backend: the same matmul inventory projected onto the
    # paper's engine (repro.sim, double-buffered reprogramming) so every
    # cell carries an engine-projected step time next to the chip roofline.
    from repro.roofline.model import oisma_engine_projection
    try:
        record["roofline"]["oisma_engine"] = oisma_engine_projection(
            cfg, shape)
    except Exception as exc:  # the projection must never kill a cell
        record["roofline"]["oisma_engine"] = {"error": str(exc)}
    record["status"] = "ok"
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--lower-only", action="store_true")
    ap.add_argument("--set", action="append", default=[],
                    help="config overrides key=value (e.g. matmul_mode=bp8)")
    ap.add_argument("--cell-timeout", type=int, default=2400)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced configs/shapes (CI; same code paths)")
    ap.add_argument("--rules", default="default",
                    choices=["default"] + sorted(shd.RULE_PRESETS),
                    help="sharding rules preset (a repro.dist.sharding."
                         "RULE_PRESETS key); 'default' picks per shape "
                         "kind, incl. adaptive decode_rules for decode")
    ap.add_argument("--mesh-shape", default=None,
                    help="data,model reshape of the 256 chips/pod (e.g. 64,4)")
    ap.add_argument("--pipeline", type=int, default=0,
                    help="pipeline stage count S > 1: lower the pipelined "
                         "train step on a (S, 16/S, 16) per-pod stage mesh "
                         "(train shapes, decoder-family archs)")
    ap.add_argument("--seq", type=int, default=0,
                    help="sequence shards Q: ring attention on a "
                         "(Q, 16/Q, 16) per-pod seq mesh.  Default 0 = "
                         "auto: 16 for long_500k cells of full-attention "
                         "archs (the formerly skipped cells), off "
                         "elsewhere.  --seq 1 disables the ring "
                         "explicitly (long_500k skips again)")
    ap.add_argument("--proxy-seq", type=int, default=2048,
                    help="--smoke proxy length for the long_500k shape "
                         "(the canonical 524288 stays the sweep truth)")
    args = ap.parse_args()

    if args.pipeline and (args.pipeline < 2 or 16 % args.pipeline):
        ap.error(f"--pipeline {args.pipeline}: stage count must be >= 2 "
                 f"and divide the 16-way data axis")
    if args.seq and (args.seq < 1 or 16 % args.seq):
        ap.error(f"--seq {args.seq}: sequence shards must divide the "
                 f"16-way data axis")
    if args.seq > 1 and (args.pipeline or args.mesh_shape):
        ap.error("--seq is incompatible with --pipeline/--mesh-shape")

    if args.mesh_shape:  # fail fast, before any cell writes a record
        try:
            _parse_mesh_shape(args.mesh_shape)
        except ValueError as e:
            ap.error(f"--mesh-shape: {e}")

    overrides: Dict[str, Any] = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        try:
            v = json.loads(v)
        except json.JSONDecodeError:
            pass
        overrides[k] = v

    cells = []
    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    if args.pipeline and not args.shape:
        # pipelined cells exist for train shapes only; don't litter the
        # results file with skip records for the other kinds
        shapes = [s for s in shapes if SHAPES[s].kind == "train"]
    meshes = (["single", "multi"] if args.mesh == "both" else [args.mesh])
    for arch in archs:
        for shape in shapes:
            for m in meshes:
                cells.append((arch, shape, m == "multi"))

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    results = []
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)
    # error records don't count as done: a re-run retries them, and the
    # supersede step below replaces the stale error record on success
    done = {cell_key(r) for r in results if r.get("status") != "error"}

    for arch, shape, multi in cells:
        # sequence-shard policy: explicit --seq wins; auto (0) turns the
        # ring on only where it is load-bearing — the long_500k cells of
        # full-attention archs, exactly the cells that used to skip.
        # --seq 1 explicitly disables the ring (the skip comes back).
        if args.seq:
            seq_eff = args.seq
        elif (shape == "long_500k"
              and not get_config(arch, smoke=args.smoke).sub_quadratic):
            seq_eff = 16
        else:
            seq_eff = 0
        key = cell_key({
            "arch": arch, "shape": shape,
            "mesh": "multi" if multi else "single", "rules": args.rules,
            "mesh_shape": args.mesh_shape or "",
            "pipeline_stages": args.pipeline,
            "seq_shards": seq_eff if seq_eff > 1 else 0,
            "overrides": {k: str(v) for k, v in overrides.items()}})
        if key in done:
            print(f"[skip-done] {key}")
            continue
        print(f"[cell] {arch} x {shape} x {'multi' if multi else 'single'}",
              flush=True)
        try:
            import signal

            def _alarm(signum, frame):
                raise TimeoutError(f"cell exceeded {args.cell_timeout}s")

            signal.signal(signal.SIGALRM, _alarm)
            signal.alarm(args.cell_timeout)
            try:
                rec = lower_cell(arch, shape, multi, overrides or None,
                                 compile_only=not args.lower_only,
                                 smoke=args.smoke, rules_preset=args.rules,
                                 mesh_shape=args.mesh_shape,
                                 pipeline_stages=args.pipeline,
                                 seq_shards=seq_eff,
                                 proxy_seq=args.proxy_seq)
            finally:
                signal.alarm(0)
        except Exception as e:
            rec = {"arch": arch, "shape": shape,
                   "mesh": "multi" if multi else "single",
                   "status": "error", "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-2000:]}
        # stamp on every record (incl. errors) so the resume-dedup key
        # distinguishes sharding experiments from the canonical sweep;
        # unstamped legacy records never match a key and simply re-run
        rec["rules"] = args.rules
        rec["mesh_shape"] = args.mesh_shape or ""
        if args.pipeline:   # also on skips/errors, so the key matches
            rec.setdefault("pipeline_stages", args.pipeline)
        if seq_eff > 1:     # seq-bearing cells stamp their shard count
            rec.setdefault("seq_shards", seq_eff)
        if overrides:
            rec.setdefault("overrides",
                           {k: str(v) for k, v in overrides.items()})
        # supersede: drop any same-key predecessor so resumes never leave
        # stale duplicates.  Legacy records lacking the 'rules' stamp (the
        # pre-stamping dry-run only stamped non-default runs) are
        # superseded by a default-rules re-run with the same mesh_shape —
        # rules experiments never touch them.
        ov = json.dumps(rec.get("overrides", {}), sort_keys=True)
        results = [
            r for r in results
            if not ((r["arch"], r["shape"], r["mesh"]) ==
                    (rec["arch"], rec["shape"], rec["mesh"])
                    and json.dumps(r.get("overrides", {}),
                                   sort_keys=True) == ov
                    and (cell_key(r) == cell_key(rec)
                         or ("rules" not in r
                             and r.get("mesh_shape", "") == rec["mesh_shape"]
                             and rec["rules"] == "default")
                         # a seq-bearing ok record retires the canonical
                         # skip it un-skips (their cell_keys differ only
                         # in seq_shards, so the plain dedup misses it)
                         or (rec.get("status") == "ok"
                             and rec.get("seq_shards", 0) > 1
                             and rec["rules"] == "default"
                             and r.get("status") == "skipped"
                             and is_canonical(r)
                             and not r.get("seq_shards"))))]
        results.append(rec)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        status = rec.get("status")
        extra = ""
        if status == "ok" and "roofline" in rec:
            r = rec["roofline"]
            extra = (f" bottleneck={r['bottleneck']}"
                     f" frac={r['roofline_fraction']:.3f}"
                     f" tc={r['t_compute']:.4f} tm={r['t_memory']:.4f}"
                     f" tcoll={r['t_collective']:.4f}")
        elif status == "error":
            extra = " " + rec["error"][:200]
        print(f"  -> {status}{extra}", flush=True)


if __name__ == "__main__":
    main()
