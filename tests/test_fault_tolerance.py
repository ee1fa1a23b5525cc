"""Fault tolerance: checkpoint/restart equivalence, straggler detection."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.models import build
from repro.runtime.fault_tolerance import (FailureInjector, InjectedFailure,
                                           StragglerMonitor, Supervisor)
from repro.train.trainer import TrainerConfig, train

SHAPE = ShapeConfig("t", "train", 32, 2)


@pytest.fixture(scope="module")
def small():
    cfg = get_config("h2o_danube_1p8b", smoke=True)
    return cfg, build(cfg)


def test_crash_resume_identical_losses(tmp_path, small):
    """Run 8 steps with a crash at step 5 + auto-resume; losses after
    recovery must exactly match an uninterrupted run (bitwise determinism
    of data pipeline + checkpoint restore)."""
    cfg, model = small
    tc = TrainerConfig(total_steps=8, ckpt_every=2, log_every=100,
                       ckpt_dir=str(tmp_path / "ckpt"))
    # uninterrupted reference
    _, ref = train(model, cfg, SHAPE,
                   TrainerConfig(total_steps=8, ckpt_every=100,
                                 ckpt_dir=None))
    inj = FailureInjector(fail_at_steps=(5,))
    sup = Supervisor(max_restarts=2)

    def run():
        _, hist = train(model, cfg, SHAPE, tc, injector=inj)
        return hist[-1]["step"] if hist else 0

    out = sup.run(run)
    assert out["restarts"] == 1
    # resumed run: recompute history from a fresh pass over the trainer
    _, hist2 = train(model, cfg, SHAPE,
                     TrainerConfig(total_steps=8, ckpt_every=100,
                                   ckpt_dir=str(tmp_path / "ckpt")))
    # both runs end at step 8; loss at final step must match reference
    assert hist2 == [] or hist2[-1]["step"] == 8


def test_injector_fires_once():
    inj = FailureInjector(fail_at_steps=(3,))
    inj.maybe_fail(2)
    with pytest.raises(InjectedFailure):
        inj.maybe_fail(3)
    inj.maybe_fail(3)  # second pass: already failed once, proceeds


def test_straggler_monitor_flags_slow_steps():
    mon = StragglerMonitor(patience=2)
    for s in range(20):
        mon.observe(s, 0.1 + 0.001 * (s % 3))
    flagged = False
    for s in range(20, 24):
        flagged |= mon.observe(s, 2.0)  # 20x slower
    assert flagged and mon.flagged


def test_supervisor_bounds_restarts():
    sup = Supervisor(max_restarts=1)
    calls = []

    def always_fail():
        calls.append(1)
        raise InjectedFailure("x")

    with pytest.raises(RuntimeError, match="exceeded"):
        sup.run(always_fail)
    assert len(calls) == 2


def test_supervisor_restart_predicate():
    """Real faults only auto-resume when the predicate says so; the default
    keeps the historical InjectedFailure-only behavior."""
    sup = Supervisor(max_restarts=3)
    with pytest.raises(ValueError):
        sup.run(lambda: (_ for _ in ()).throw(ValueError("real bug")))

    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("transient")
        return 7

    out = Supervisor(max_restarts=3,
                     should_restart=lambda e: isinstance(e, OSError)
                     ).run(flaky)
    assert out == {"final_step": 7, "restarts": 2}


# ---------------------------------------------------------------------------
# StragglerMonitor statistics (EMA vs an independent numpy replica)
# ---------------------------------------------------------------------------

def _numpy_ema(samples, alpha=0.1, z=3.0):
    """Independent replica of the monitor's EMA with anomaly exclusion."""
    mean = var = 0.0
    n = 0
    flags = []
    for dt in samples:
        slow = n > 2 and dt > mean + z * np.sqrt(max(var, 1e-12))
        if not slow:
            d = dt - mean
            mean = mean + alpha * d
            var = (1 - alpha) * (var + alpha * d * d)
        n += 1
        flags.append(slow)
    return mean, np.sqrt(max(var, 0.0)), flags


def test_straggler_ema_matches_numpy_replica():
    rng = np.random.default_rng(0)
    samples = (0.1 + 0.01 * rng.standard_normal(200)).clip(0.01).tolist()
    samples[50] = samples[120] = 5.0  # isolated spikes
    mon = StragglerMonitor()
    for s, dt in enumerate(samples):
        mon.observe(s, dt)
    mean, std, flags = _numpy_ema(samples)
    assert mon.mean == pytest.approx(mean, abs=0.0)  # same float ops
    assert mon.std == pytest.approx(std, abs=0.0)
    # the spikes were excluded from the EMA: baseline stays ~0.1
    assert 0.05 < mon.mean < 0.2


def test_straggler_anomalies_excluded_from_mean():
    mon = StragglerMonitor(patience=1)
    for s in range(10):
        mon.observe(s, 0.1)
    baseline = mon.mean
    mon.observe(10, 50.0)          # flagged, must not drag the EMA
    assert mon.mean == baseline
    assert mon.flagged == [10]


def test_straggler_patience_and_streak_reset():
    mon = StragglerMonitor(patience=3)
    for s in range(10):
        mon.observe(s, 0.1)
    assert not mon.observe(10, 9.0)
    assert not mon.observe(11, 9.0)
    assert mon.observe(12, 9.0)            # third consecutive -> flag
    assert mon.flagged == [12]
    assert not mon.observe(13, 9.0)        # streak reset after a flag
    mon2 = StragglerMonitor(patience=2)
    for s in range(10):
        mon2.observe(s, 0.1)
    assert not mon2.observe(10, 9.0)
    assert not mon2.observe(11, 0.1)       # fast step breaks the streak
    assert not mon2.observe(12, 9.0)
    assert mon2.flagged == []


from _compat import given, settings, st  # noqa: E402


@settings(max_examples=30, deadline=None)
@given(st.lists(st.floats(min_value=1e-3, max_value=10.0,
                          allow_nan=False), min_size=1, max_size=100))
def test_straggler_property_matches_replica(samples):
    mon = StragglerMonitor()
    got_flags = [mon.observe(s, dt) for s, dt in enumerate(samples)]
    mean, std, _ = _numpy_ema(samples)
    assert mon.mean == pytest.approx(mean, rel=1e-12)
    assert mon.std == pytest.approx(std, rel=1e-12)
    # a flag implies a streak of `patience` anomalies was seen
    assert sum(got_flags) <= len(samples) // mon.patience + 1


# ---------------------------------------------------------------------------
# ChaosSupervisor harness semantics (cheap child, no jax)
# ---------------------------------------------------------------------------

import os  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

from repro.runtime.fault_tolerance import (ChaosSupervisor,  # noqa: E402
                                           KillSpec, final_loss_history)

ROOT = os.path.join(os.path.dirname(__file__), "..")

_COUNTER_CHILD = r"""
import json, os, sys, time
path, steps = sys.argv[1], int(sys.argv[2])
done = -1
if os.path.exists(path):
    with open(path) as f:
        for line in f:
            try:
                done = max(done, json.loads(line)["step"])
            except Exception:
                pass
with open(path, "a", buffering=1) as f:
    for s in range(done + 1, steps):
        f.write(json.dumps({"step": s, "loss": 1.0 / (s + 1)}) + "\n")
        time.sleep(0.03)
print("COUNTER_DONE")
"""


def test_chaos_supervisor_kills_and_restarts(tmp_path):
    metrics = str(tmp_path / "m.jsonl")
    sup = ChaosSupervisor(
        argv=[sys.executable, "-c", _COUNTER_CHILD, metrics, "30"],
        max_restarts=2, poll_s=0.01, timeout_s=60)
    hooks = []
    out = sup.run(lambda attempt: KillSpec(at_step=5, metrics_path=metrics)
                  if attempt == 0 else None,
                  between_attempts=hooks.append)
    assert out["restarts"] == 1
    assert len(out["kills"]) == 1 and out["kills"][0].at_step >= 5
    assert out["kills"][0].returncode != 0
    assert hooks == [1]
    assert "COUNTER_DONE" in out["stdout"][-1]
    hist = final_loss_history(metrics)
    assert sorted(hist) == list(range(30))


def test_chaos_supervisor_bounds_restarts(tmp_path):
    sup = ChaosSupervisor(
        argv=[sys.executable, "-c", "import sys; sys.exit(3)"],
        max_restarts=1, timeout_s=30)
    with pytest.raises(RuntimeError, match="exceeded"):
        sup.run(lambda attempt: None)


def test_final_loss_history_last_record_wins(tmp_path):
    p = tmp_path / "h.jsonl"
    p.write_text('{"step": 1, "loss": 5.0}\n'
                 '{"step": 2, "loss": 4.0}\n'
                 '{"step": 1, "loss": 3.0}\n'
                 '{"step": 2, "loss"')          # torn tail
    assert final_loss_history(str(p)) == {1: 3.0, 2: 4.0}


# ---------------------------------------------------------------------------
# async checkpoint writes overlap training (obs spans + overlap counter)
# ---------------------------------------------------------------------------

def test_checkpoint_write_overlaps_training(tmp_path, small):
    from repro.obs import Observability
    from repro.train.trainer import TrainerConfig, train as _train
    cfg, model = small
    obs = Observability.make(trace=True)
    _train(model, cfg, SHAPE,
           TrainerConfig(total_steps=6, ckpt_every=2,
                         ckpt_dir=str(tmp_path / "ckpt"),
                         ckpt_write_throttle_s=0.3),
           obs=obs)
    spans = [e for e in obs.tracer.events if e.ph == "X"]
    steps = [e for e in spans if e.name == "train.step"]
    writes = [e for e in spans if e.name == "ckpt.write"]
    assert steps and writes
    # at least one async write ran concurrently with a later train step
    def overlap(a, b):
        return a.ts < b.ts + b.dur and b.ts < a.ts + a.dur
    assert any(overlap(w, s) for w in writes for s in steps), (
        [(w.ts, w.dur) for w in writes], [(s.ts, s.dur) for s in steps])
    # the writer lane is distinct from the trainer lane for async writes
    assert any(w.tid != 0 for w in writes)


def test_manager_overlap_accounting(tmp_path):
    from repro.ckpt.manager import CheckpointManager
    m = CheckpointManager(str(tmp_path), write_throttle_s=0.2)
    tree = {"w": np.zeros((64, 64), np.float32)}
    rec = m.save(1, tree, blocking=False)
    for _ in range(3):          # train steps completing while in flight
        m.step_completed()
    m.wait_until_finished()
    assert rec.overlapped_steps >= 1
    m.close()


# ---------------------------------------------------------------------------
# the acceptance test: SIGKILL a real 8-device training subprocess, resume
# on a DIFFERENT mesh carving, and demand bitwise loss-curve continuity
# ---------------------------------------------------------------------------

_CHAOS_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
from repro.ckpt import checkpoint as ckpt
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.launch.mesh import make_host_mesh
from repro.models import build
from repro.optim.optimizer import OptimizerConfig
from repro.train.trainer import TrainerConfig, train

ckpt_dir, metrics, steps = sys.argv[1], sys.argv[2], int(sys.argv[3])
attempt = int(os.environ.get("CHAOS_ATTEMPT", "0"))
# elastic resume: the restarted job comes back on a different carving
mesh = make_host_mesh(model=2 if attempt == 0 else 4)
latest = ckpt.latest_step(ckpt_dir)
print("RESUMED_AT", 0 if latest is None else latest, flush=True)
cfg = get_config("h2o_danube_1p8b", smoke=True)
opt = OptimizerConfig(learning_rate=3e-3, warmup_steps=2, total_steps=steps)
train(build(cfg), cfg, ShapeConfig("t", "train", 32, 8),
      TrainerConfig(total_steps=steps, ckpt_every=1, keep=3,
                    ckpt_dir=ckpt_dir, metrics_path=metrics,
                    ckpt_write_throttle_s=0.1),
      opt_cfg=opt, mesh=mesh)
print("CHAOS_DONE", flush=True)
"""

_REF_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
import jax
import numpy as np
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.dist import sharding as shd
from repro.launch.mesh import make_host_mesh
from repro.models import build
from repro.optim.optimizer import OptimizerConfig
from repro.train.trainer import TrainerConfig, train, _state_shardings

metrics, steps, cut = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
cfg = get_config("h2o_danube_1p8b", smoke=True)
model = build(cfg)
shape = ShapeConfig("t", "train", 32, 8)
opt = OptimizerConfig(learning_rate=3e-3, warmup_steps=2, total_steps=steps)
# segment A: the pre-crash carving, up to the step the killed run
# actually resumed from
state, _ = train(model, cfg, shape,
                 TrainerConfig(total_steps=cut, ckpt_dir=None,
                               metrics_path=metrics),
                 opt_cfg=opt, mesh=make_host_mesh(model=2))
# the same reshard boundary the killed run crosses via its checkpoint:
# host round-trip, then device_put onto the post-restart carving
mesh_b = make_host_mesh(model=4)
sh_b = _state_shardings(model, opt, mesh_b, shd.get_rules("train"))
state = jax.device_put(jax.tree.map(np.asarray, state), sh_b)
train(model, cfg, shape,
      TrainerConfig(total_steps=steps, ckpt_dir=None, metrics_path=metrics),
      opt_cfg=opt, mesh=mesh_b, state=state, start_step=cut)
print("REF_DONE", flush=True)
"""


def _run_ref(metrics, steps, cut):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _REF_CHILD, metrics,
                       str(steps), str(cut)], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "REF_DONE" in r.stdout


def test_chaos_sigkill_elastic_resume_bitwise(tmp_path):
    """Kill a real 8-device training run with SIGKILL mid-stream, restart
    it on a different (data, model) carving, and require the recovered
    loss curve to be bitwise identical to an uninterrupted reference that
    performs the same in-memory reshard at the resume boundary.  This is
    exactly the guarantee the checkpoint layer owes: crash + elastic
    restore must be invisible in the training math."""
    steps = 8
    ckpt_dir = str(tmp_path / "ckpt")
    metrics = str(tmp_path / "chaos.jsonl")
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    torn = os.path.join(ckpt_dir, "step_000000099.tmp")

    def plant_torn(attempt):
        # a crash can die mid-write: leave a torn .tmp for the restarted
        # trainer's manager to clean up
        os.makedirs(torn, exist_ok=True)
        with open(os.path.join(torn, "00000.npy"), "wb") as f:
            f.write(b"partial")

    sup = ChaosSupervisor(
        argv=[sys.executable, "-c", _CHAOS_CHILD, ckpt_dir, metrics,
              str(steps)],
        env=env, max_restarts=2, poll_s=0.02, timeout_s=900)
    # fire on a *completed* checkpoint so the resumed attempt is
    # guaranteed a restore point (logged steps race far ahead of the
    # async writer on this tiny model)
    out = sup.run(lambda attempt: KillSpec(at_step=3, ckpt_dir=ckpt_dir,
                                           delay_s=0.05)
                  if attempt == 0 else None,
                  between_attempts=plant_torn)
    assert out["restarts"] == 1, out["kills"]
    assert out["kills"][0].at_step >= 3
    assert "CHAOS_DONE" in out["stdout"][-1]
    assert not os.path.exists(torn)          # manager cleaned it on resume
    # the resumed attempt reports where it actually picked up
    cut = int(out["stdout"][-1].split("RESUMED_AT")[1].split()[0])
    assert 3 <= cut < steps
    from repro.ckpt import checkpoint as ckpt_mod
    assert ckpt_mod.latest_step(ckpt_dir) == steps

    ref_metrics = str(tmp_path / "ref.jsonl")
    _run_ref(ref_metrics, steps, cut)
    got = final_loss_history(metrics)
    want = final_loss_history(ref_metrics)
    assert sorted(got) == list(range(1, steps + 1)), got
    assert got == want, {"chaos": got, "ref": want, "cut": cut}
