"""Training loop: data + step + checkpointing + fault tolerance.

Single-process reference loop (device count agnostic — the same code runs
under a 1-chip test mesh or the 512-chip production mesh; only the mesh and
shardings differ).  Auto-resumes from the newest checkpoint; saves through
an async ``CheckpointManager`` every ``ckpt_every`` steps (writes overlap
the next train steps); feeds the straggler monitor.

Checkpoints carry more than the train state: the payload is
``{"state": ..., "extra": {"data": ..., "rng": ...}}`` where ``extra``
records the data-iterator geometry (seed, next step, global batch, seq
len) and the RNG key the run was seeded with.  Because the data pipeline
is stateless (``batch_at`` is a pure function of seed and step), that
geometry IS the full iterator state — restore validates it against the
current run's config and resumes at the recorded step, on whatever mesh
carving the restarted process brings up (elastic resume: the restore path
re-shards every leaf onto the new mesh via ``dist.get_rules``).
"""
from __future__ import annotations

import dataclasses
import sys
import time
from typing import Callable, Dict, Optional

import jax
import numpy as np

from repro.ckpt.manager import CheckpointManager
from repro.configs.base import ModelConfig, ShapeConfig
from repro.data.pipeline import DataConfig, batch_at
from repro.optim.optimizer import OptimizerConfig
from repro.runtime.fault_tolerance import (FailureInjector, StragglerMonitor)
from repro.train.train_step import TrainPlan, init_state, make_train_step


@dataclasses.dataclass
class TrainerConfig:
    total_steps: int = 100
    ckpt_every: int = 20
    ckpt_dir: Optional[str] = None
    keep: int = 3
    log_every: int = 10
    seed: int = 0
    metrics_path: Optional[str] = None   # JSONL telemetry (repro.obs)
    ckpt_async: bool = True              # overlap writes with train steps
    ckpt_max_in_flight: int = 2          # bounded writer queue (backpressure)
    ckpt_compress_opt: bool = True       # int8_ef-compress optimizer moments
    ckpt_write_throttle_s: float = 0.0   # test/chaos knob: slow the writer


def _payload(state, dcfg: DataConfig, next_step: int, seed: int):
    """Checkpoint payload: train state + data-iterator state + RNG key."""
    return {"state": state,
            "extra": {"data": np.asarray(
                          [dcfg.seed, next_step, dcfg.global_batch,
                           dcfg.seq_len], np.int64),
                      "rng": np.asarray(
                          jax.random.key_data(jax.random.key(seed)))}}


def _state_shardings(model, opt_cfg, mesh, rules):
    """Per-leaf NamedShardings for the train state on ``mesh``."""
    from repro.dist import sharding as shd
    from repro.models.params import abstract_tree, axes_tree
    from repro.optim.optimizer import abstract_opt_state, opt_state_axes
    schema = model.schema()
    paxes = axes_tree(schema)
    astate = {"params": abstract_tree(schema),
              "opt": abstract_opt_state(abstract_tree(schema), opt_cfg)}
    saxes = {"params": paxes, "opt": opt_state_axes(paxes)}
    return shd.tree_shardings(mesh, rules, astate, saxes)


def train(model, cfg: ModelConfig, shape: ShapeConfig,
          tcfg: TrainerConfig, opt_cfg: Optional[OptimizerConfig] = None,
          injector: Optional[FailureInjector] = None,
          step_fn=None, state=None, start_step: int = 0,
          on_metrics: Optional[Callable[[int, Dict], None]] = None,
          mesh=None, obs=None):
    """Returns (state, history).  Restartable: call again after a crash and
    it resumes from the newest checkpoint — including on a *different* mesh
    carving than the one that wrote it (elastic resume).

    Stage-aware path: pass a mesh carrying a "stage" axis (e.g.
    ``launch.mesh.make_host_mesh(stages=...)``) to train pipelined at the
    mesh's stage count — the TrainPlan then picks pipeline microbatches
    jointly with grad accumulation, and each step is traced under the
    ``pipeline`` sharding preset.  A stage-free mesh trains data/model
    parallel under the ``train`` preset with the state initialised in its
    per-leaf shardings.  Without a mesh the loop is unchanged and
    mesh-agnostic (``cfg.pipeline_stages`` is only launch code's hint for
    *building* a stage mesh, never a trainer switch).
    """
    opt_cfg = opt_cfg or OptimizerConfig(total_steps=tcfg.total_steps,
                                         warmup_steps=5)
    from repro.launch.mesh import mesh_axis_size
    # the mesh is the authority: a stage-bearing mesh is an explicit
    # opt-in, and its stage count wins over the config's preference
    stages = mesh_axis_size(mesh, "stage") if mesh is not None else 1
    data_shards = mesh_axis_size(mesh, "data") if mesh is not None else 1
    plan = TrainPlan.for_shape(cfg, shape, data_shards=data_shards,
                               pipeline_stages=stages)
    rules_ctx = None
    state_sh = None
    if mesh is not None:
        from repro.dist import sharding as shd
        rules = shd.get_rules("pipeline" if stages > 1 else "train")
        rules_ctx = (mesh, rules)
        if stages == 1:
            # DP/TP path: state lives sharded on the mesh; the pipeline
            # path leaves placement to the stage-aware step (its stacked
            # per-stage layout is partitioned inside make_train_step)
            state_sh = _state_shardings(model, opt_cfg, mesh, rules)
    if step_fn is None:
        import contextlib as _ctx
        jitted = jax.jit(make_train_step(
            model, opt_cfg, plan, mesh=mesh if stages > 1 else None))

        def step_fn(state, batch):
            # the rules context matters at trace time (first call);
            # steady-state calls replay the cached jaxpr
            from repro.dist import sharding as shd
            ctx = (shd.use_rules(*rules_ctx) if rules_ctx is not None
                   else _ctx.nullcontext())
            with ctx:
                return jitted(state, batch)
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=shape.seq_len,
                      global_batch=shape.global_batch, seed=tcfg.seed)

    from repro.obs import JsonlLogger, MetricsRegistry, no_span
    manager = None
    if tcfg.ckpt_dir:
        manager = CheckpointManager(
            tcfg.ckpt_dir, keep=tcfg.keep,
            max_in_flight=tcfg.ckpt_max_in_flight,
            compress_opt_state=tcfg.ckpt_compress_opt,
            write_throttle_s=tcfg.ckpt_write_throttle_s, obs=obs)

    # start_step only applies to caller-supplied state (e.g. continuing a
    # returned state mid-schedule); the restore path derives its own start
    start = start_step if state is not None else 0
    if state is None:
        key = jax.random.key(tcfg.seed)
        if state_sh is not None:
            # build each leaf in its own shards: an eager init would first
            # materialise the whole state on one device
            state = jax.jit(lambda k: init_state(model, k, opt_cfg),
                            out_shardings=state_sh)(key)
        else:
            state = init_state(model, key, opt_cfg)
        if manager is not None and manager.latest_step() is not None:
            like = _payload(state, dcfg, 0, tcfg.seed)
            shardings = ({"state": state_sh,
                          "extra": {"data": None, "rng": None}}
                         if state_sh is not None else None)
            payload, ckpt_step = manager.restore(like, shardings=shardings)
            geom = np.asarray(payload["extra"]["data"])
            saved = (int(geom[0]), int(geom[2]), int(geom[3]))
            want = (dcfg.seed, dcfg.global_batch, dcfg.seq_len)
            if saved != want:
                raise ValueError(
                    f"checkpoint data geometry {saved} != run {want} "
                    "(seed, global_batch, seq_len); refusing to resume "
                    "onto a different data stream")
            state = payload["state"]
            start = int(geom[1])
            assert start == ckpt_step, (start, ckpt_step)
    monitor = StragglerMonitor()
    logger = JsonlLogger(tcfg.metrics_path)
    registry = obs.registry if obs is not None else MetricsRegistry()
    span = obs.span if obs is not None else no_span
    history = []
    try:
        for step in range(start, tcfg.total_steps):
            if injector is not None:
                injector.maybe_fail(step)
            with span("train.data", step=step + 1):
                batch = {k: jax.numpy.asarray(v)
                         for k, v in batch_at(dcfg, step).items()}
            # perf_counter for the duration (wall-clock is NTP-skewable and
            # can run backwards mid-step); the logger stamps the one wall
            # timestamp each record keeps for cross-host alignment
            t0 = time.perf_counter()
            with span("train.step", step=step + 1):
                state, metrics = step_fn(state, batch)
                loss = float(metrics["loss"])
            dt = time.perf_counter() - t0
            if manager is not None:
                manager.step_completed()
            straggler = monitor.observe(step, dt)
            logger.log(step + 1, loss=loss, dt=dt,
                       grad_norm=metrics.get("grad_norm", 0.0),
                       straggler=straggler)
            registry.counter("train.steps")
            registry.observe("train.step_time_s", dt)
            registry.gauge("train.loss", loss)
            if straggler:
                registry.counter("train.straggler_events")
            history.append({"step": step + 1, "loss": loss, "dt": dt})
            if on_metrics:
                on_metrics(step + 1, metrics)
            if manager is not None and (step + 1) % tcfg.ckpt_every == 0:
                with span("train.checkpoint", step=step + 1):
                    manager.save(step + 1,
                                 _payload(state, dcfg, step + 1, tcfg.seed),
                                 blocking=not tcfg.ckpt_async)
                registry.counter("train.checkpoints")
        if manager is not None and tcfg.total_steps > start:
            # blocking final save: the manager drains the async queue
            # first, so this can never interleave with an in-flight write
            with span("train.checkpoint", step=tcfg.total_steps,
                      final=True):
                manager.save(tcfg.total_steps,
                             _payload(state, dcfg, tcfg.total_steps,
                                      tcfg.seed),
                             blocking=True)
            registry.counter("train.checkpoints")
    finally:
        if manager is not None:
            # join the writer even on a crash/injected failure so a
            # restart (possibly this same process) sees a quiescent
            # directory; don't let a secondary writer error mask the
            # primary exception already propagating
            in_flight = sys.exc_info()[0] is not None
            try:
                manager.close()
            except Exception:
                if not in_flight:
                    raise
        logger.close()
    return state, history
