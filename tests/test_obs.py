"""Tier-1 tests for repro.obs: registry semantics, span/trace
well-formedness and Chrome-trace schema, deterministic export under a
fake clock, the retrace watchdog, the JSONL logger contracts, and the
round-timeline adapter's consistency with the mapper's closed form."""
import json

import numpy as np
import pytest

from repro.obs import (JsonlLogger, MetricsRegistry, Observability,
                       RetraceError, RetraceWatchdog, Tracer, percentile,
                       read_metrics, round_walk_chrome_trace,
                       sim_chrome_trace)


class FakeClock:
    """Monotonic fake: every read advances 1 ms."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1e-3
        return self.t


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_counter_gauge_histogram():
    reg = MetricsRegistry()
    reg.counter("req")
    reg.counter("req", 2.0)
    reg.gauge("depth", 7)
    reg.gauge("depth", 3)            # last write wins
    for v in (1.0, 2.0, 3.0, 4.0):
        reg.observe("lat", v)
    assert reg.value("req") == 3.0
    assert reg.value("depth") == 3.0
    snap = {r["name"]: r for r in reg.snapshot()}
    assert snap["lat"]["count"] == 4 and snap["lat"]["sum"] == 10.0
    assert snap["lat"]["min"] == 1.0 and snap["lat"]["max"] == 4.0
    assert snap["lat"]["p50"] == 2.5


def test_registry_labels_are_distinct_series():
    reg = MetricsRegistry()
    reg.counter("hits", callsite="a")
    reg.counter("hits", 5.0, callsite="b")
    assert reg.value("hits", callsite="a") == 1.0
    assert reg.value("hits", callsite="b") == 5.0
    assert reg.value("hits") == 0.0          # unlabeled series never written


def test_registry_rejects_negative_counter_and_kind_conflicts():
    reg = MetricsRegistry()
    with pytest.raises(ValueError):
        reg.counter("c", -1.0)
    reg.counter("x")
    with pytest.raises(ValueError):
        reg.gauge("x", 1.0)


def test_registry_snapshot_deterministic_and_json_safe():
    def build():
        reg = MetricsRegistry()
        reg.gauge("b", 2)
        reg.counter("a", 1, z="1")
        reg.counter("a", 1, y="0")
        reg.observe("h", 1.5)
        return json.dumps(reg.snapshot(), sort_keys=True)

    assert build() == build()
    names = [r["name"] for r in json.loads(build())]
    assert names == sorted(names)


def test_registry_to_jsonl_stamps_one_wall_time(tmp_path):
    reg = MetricsRegistry()
    reg.counter("a")
    reg.observe("h", 2.0)
    path = str(tmp_path / "reg.jsonl")
    n = reg.to_jsonl(path, wall_time=123.0, extra={"run": "t"})
    rows = read_metrics(path)
    assert n == len(rows) == 2
    assert all(r["t"] == 123.0 and r["run"] == "t" for r in rows)


def test_percentile_matches_numpy():
    rng = np.random.default_rng(0)
    for n in (1, 2, 3, 7, 100):
        vals = sorted(rng.normal(size=n).tolist())
        for q in (0, 25, 50, 99, 100):
            assert percentile(vals, q) == float(np.percentile(vals, q))


# ---------------------------------------------------------------------------
# jsonl logger (the satellite fix: bool stays bool; flush-on-close)
# ---------------------------------------------------------------------------

def test_jsonl_logger_preserves_value_types(tmp_path):
    path = str(tmp_path / "m.jsonl")
    lg = JsonlLogger(path)
    lg.log(1, straggler=True, count=3, loss=1.5,
           npf=np.float32(2.5), tag=object())
    lg.close()
    (row,) = read_metrics(path)
    assert row["straggler"] is True           # not coerced to 1.0
    assert row["count"] == 3 and isinstance(row["count"], int)
    assert row["loss"] == 1.5
    assert row["npf"] == 2.5                  # numpy scalar -> float
    assert isinstance(row["tag"], str)


def test_jsonl_logger_flush_on_close_contract(tmp_path):
    path = str(tmp_path / "m.jsonl")
    lg = JsonlLogger(path)
    for step in range(5):
        lg.log(step, loss=float(step))
    lg.close()
    rows = read_metrics(path)                 # every log() call on disk,
    assert [r["step"] for r in rows] == list(range(5))   # complete lines
    assert all("t" in r and "host" in r for r in rows)
    lg.close()                                # idempotent


def test_read_metrics_skips_torn_tail(tmp_path):
    path = str(tmp_path / "m.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"step": 1}) + "\n")
        f.write('{"step": 2, "loss"')          # crash mid-line
    assert read_metrics(path) == [{"step": 1}]


def test_utils_metrics_shim_is_the_obs_logger():
    from repro.utils.metrics import MetricsLogger
    assert MetricsLogger is JsonlLogger


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_span_nesting_well_formed():
    tr = Tracer(FakeClock())
    with tr.span("outer", tid=0):
        assert tr.depth(0) == 1
        with tr.span("inner", tid=0):
            assert tr.depth(0) == 2
        with tr.span("other lane", tid=3):
            assert tr.depth(0) == 1 and tr.depth(3) == 1
    assert tr.open_spans() == 0
    # children close before parents, so inner's interval nests in outer's
    spans = {e.name: e for e in tr.events}
    assert spans["outer"].ts <= spans["inner"].ts
    assert (spans["inner"].ts + spans["inner"].dur
            <= spans["outer"].ts + spans["outer"].dur)


def test_span_closes_on_exception():
    tr = Tracer(FakeClock())
    with pytest.raises(RuntimeError):
        with tr.span("boom"):
            raise RuntimeError("x")
    assert tr.open_spans() == 0
    assert tr.events[0].name == "boom" and tr.events[0].ph == "X"


def test_chrome_trace_schema():
    tr = Tracer(FakeClock())
    tr.set_thread_name(0, "engine")
    with tr.span("step", tid=0, cat="serve", step=1):
        tr.instant("admit", tid=0, rid=7)
        tr.counter("blocks", 3.0)
    doc = tr.chrome_trace()
    events = doc["traceEvents"]
    assert events[0]["ph"] == "M"             # metadata first
    assert events[0]["args"] == {"name": "engine"}
    for e in events:
        assert isinstance(e["name"], str) and isinstance(e["ph"], str)
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        if e["ph"] != "M":
            assert isinstance(e["ts"], float) and e["ts"] >= 0
        if e["ph"] == "X":
            assert isinstance(e["dur"], float) and e["dur"] >= 0
    instant = next(e for e in events if e["ph"] == "i")
    assert instant["s"] == "t" and instant["args"] == {"rid": 7}
    counter = next(e for e in events if e["ph"] == "C")
    assert counter["args"] == {"value": 3.0}
    span = next(e for e in events if e["ph"] == "X")
    assert span["cat"] == "serve" and span["args"] == {"step": 1}


def test_trace_deterministic_under_fake_clock(tmp_path):
    def build(path):
        tr = Tracer(FakeClock())
        tr.set_thread_name(0, "lane")
        with tr.span("a"):
            with tr.span("b", x=1):
                pass
        tr.instant("i")
        tr.export(path)
        with open(path) as f:
            return f.read()

    out1 = build(str(tmp_path / "t1.json"))
    out2 = build(str(tmp_path / "t2.json"))
    assert out1 == out2                       # byte-identical export
    json.loads(out1)                          # and valid JSON


# ---------------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------------

class FakeJitted:
    """Stands in for a jax.jit result: tracks its own compile cache."""

    def __init__(self):
        self.shapes = set()

    def __call__(self, x):
        self.shapes.add(x.shape)
        return x

    def _cache_size(self):
        return len(self.shapes)


def test_watchdog_raises_on_shape_unstable_function():
    wd = RetraceWatchdog()
    fn = wd.watch(FakeJitted(), name="unstable", limit=2)
    fn(np.zeros(1))
    fn(np.zeros(2))
    fn(np.zeros(2))                           # cached shape: fine
    with pytest.raises(RetraceError):
        fn(np.zeros(3))                       # 3rd distinct shape > 2
    assert wd.compiled("unstable") == 3
    with pytest.raises(RetraceError):
        wd.assert_ok()


def test_watchdog_record_mode_counts_and_publishes():
    reg = MetricsRegistry()
    wd = RetraceWatchdog(reg, mode="record", default_limit=1)
    fn = wd.watch(FakeJitted(), name="site", limit=99)   # default wins
    for n in (1, 2, 3):
        fn(np.zeros(n))
    rep = wd.report()["site"]
    assert rep == {"compiled": 3, "limit": 1, "calls": 3, "violations": 2}
    assert reg.value("jit_compiled_shapes", callsite="site") == 3.0
    assert reg.value("jit_retrace_violations", callsite="site") == 2.0
    with pytest.raises(RetraceError):
        wd.assert_ok()


def test_watchdog_signature_fallback_for_plain_callables():
    wd = RetraceWatchdog(mode="record", default_limit=2)
    fn = wd.watch(lambda x, flag=False: x, name="plain")
    fn(np.zeros((2, 2)))
    fn(np.ones((2, 2)))                       # same shape/dtype: no retrace
    fn(np.zeros((2, 2), np.int32))            # dtype change: new signature
    assert wd.compiled("plain") == 2
    wd.assert_ok()


def test_watchdog_forwards_cache_size_through_wrap():
    wd = RetraceWatchdog()
    inner = FakeJitted()
    fn = wd.watch(inner, name="fwd", limit=8)
    fn(np.zeros(4))
    assert fn._cache_size() == 1              # introspection still works
    assert fn.__wrapped__ is inner


def test_observability_make():
    obs = Observability.make(trace=True, watchdog_limit=4, clock=FakeClock())
    assert obs.tracer is not None and obs.watchdog is not None
    assert obs.watchdog.default_limit == 4
    assert obs.watchdog.registry is obs.registry
    bare = Observability()
    assert bare.tracer is None and bare.watchdog is None
    assert isinstance(bare.registry, MetricsRegistry)


def test_registry_peak_keeps_the_running_maximum():
    reg = MetricsRegistry()
    for v in (0.2, 0.5, 0.1):
        reg.peak("serve.phase_max_s", v, phase="commit")
    assert reg.value("serve.phase_max_s", phase="commit") == 0.5
    reg.gauge("serve.phase_max_s", 0.0, phase="commit")     # a reset
    reg.peak("serve.phase_max_s", 0.3, phase="commit")
    assert reg.value("serve.phase_max_s", phase="commit") == 0.3


# ---------------------------------------------------------------------------
# spans on the profiler's clock
# ---------------------------------------------------------------------------

def _profiled_host_events(tmp_path, fn, prefix):
    """Run ``fn`` under a CPU profile (no Python tracer) and return the
    host events whose name starts with ``prefix``: (name, start_ns,
    duration_ns, stats), sorted by start."""
    import glob

    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.name, e.start_ns, e.duration_ns,
                            {k: v for k, v in e.stats})
                           for e in line.events if e.name.startswith(prefix))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def test_span_writes_a_profiler_event_with_args_as_stats(tmp_path):
    from repro.obs import no_span

    def spans():
        with Observability().span("engine.prefill", rid=3, chunk=256):
            pass
        with no_span("engine.nothing", rid=4):
            pass

    events = _profiled_host_events(tmp_path, spans, "engine.")
    assert [e[0] for e in events] == ["engine.prefill"]   # bare name
    assert events[0][3] == {"rid": 3, "chunk": 256}


def test_span_also_records_the_attached_tracer(tmp_path):
    obs = Observability.make(trace=True, clock=FakeClock())

    def spans():
        with obs.span("train.step", step=1):
            with obs.span("ckpt.write", tid=9, step=1):
                pass

    events = _profiled_host_events(tmp_path, spans, ("train.", "ckpt."))
    assert [e[0] for e in events] == ["train.step", "ckpt.write"]
    assert obs.tracer.open_spans() == 0
    mem = {e.name: e for e in obs.tracer.events}
    assert mem["train.step"].args == {"step": 1}
    assert mem["ckpt.write"].tid == 9 and mem["train.step"].tid == 0


PHASES = ("engine.retire", "engine.admit", "engine.gather",
          "engine.prefill", "engine.decode", "engine.commit",
          "engine.sample")


def test_engine_phase_spans_tile_the_step(tmp_path):
    """A tiny paged engine under a CPU profile: every phase span lies in
    an ``engine.step``, and the registry's per-phase host seconds add up
    to the step spans."""
    import jax

    from repro.configs import get_config
    from repro.models import build
    from repro.models.params import init_tree
    from repro.serve.paged_engine import (PagedEngineConfig, PagedRequest,
                                          PagedServeEngine)

    cfg = get_config("h2o_danube_1p8b", smoke=True)
    model = build(cfg)
    params = init_tree(model.schema(), jax.random.key(0))
    obs = Observability()
    eng = PagedServeEngine(model, params, cfg, PagedEngineConfig(
        slots=2, block_size=8, num_blocks=32, max_prefill_tokens=8), obs=obs)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(2, cfg.vocab_size, size=n).astype(np.int32)
               for n in (5, 13, 9)]

    def serve(rid0):
        for k, p in enumerate(prompts):
            eng.submit(PagedRequest(rid=rid0 + k, prompt=p,
                                    max_new_tokens=4))
        while eng.scheduler.pending or eng.live:
            eng.step()

    serve(0)                                 # compiles every shape
    before = {r["labels"]["phase"]: r["value"]
              for r in obs.registry.snapshot() if r["name"] == "serve.phase_s"}
    events = _profiled_host_events(tmp_path, lambda: serve(10), "engine.")
    after = {r["labels"]["phase"]: r["value"]
             for r in obs.registry.snapshot() if r["name"] == "serve.phase_s"}
    steps = [e for e in events if e[0] == "engine.step"]
    phases = [e for e in events if e[0] != "engine.step"]
    assert {e[0] for e in phases} >= set(PHASES) | {"engine.scrub"}
    for name, start, dur, _ in phases:
        assert any(s <= start and start + dur <= s + d
                   for _, s, d, _ in steps), name
    prefill = next(e for e in phases if e[0] == "engine.prefill")
    assert set(prefill[3]) == {"rid", "chunk", "view", "pos"}
    step_s = sum(e[2] for e in steps) * 1e-9
    phase_s = sum(after[k] - before.get(k, 0.0) for k in after)
    assert abs(phase_s - step_s) <= 0.1 * step_s, (phase_s, step_s)
    assert set(after) == {p.split(".")[1] for p in PHASES}
    for p in after:
        assert obs.registry.value("serve.phase_max_s", phase=p) > 0


# ---------------------------------------------------------------------------
# simulator adapters: the timeline must agree with the closed form
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("double_buffered", [False, True])
@pytest.mark.parametrize("stationary", [False, True])
def test_round_timeline_matches_matmul_report(double_buffered, stationary):
    from repro.sim.mapper import EngineConfig, map_matmul, round_timeline

    eng = EngineConfig(banks=4, arrays_per_bank=4,
                       double_buffered=double_buffered,
                       write_ports_per_bank=2)
    for m, k, n in ((64, 1024, 512), (16, 700, 130), (128, 256, 64)):
        rep = map_matmul(m, k, n, eng, stationary=stationary, count=1.0)
        slices = round_timeline(m, k, n, eng, stationary=stationary)
        assert len(slices) == int(rep.rounds)
        compute = sum(s.compute_cycles for s in slices)
        exposed = sum(s.exposed_cycles for s in slices)
        assert compute + exposed == pytest.approx(
            rep.compute_cycles + rep.reprogram_cycles)
        assert exposed == pytest.approx(rep.reprogram_cycles)
        # the walk itself is consistent: monotone starts, no overlap of
        # compute with its own round's exposed stall
        for a, b in zip(slices, slices[1:]):
            assert b.compute_start >= a.compute_end
        if stationary and not double_buffered:
            assert slices[0].program_cycles == 0.0   # preloaded residency


def test_round_timeline_double_buffering_hides_stalls():
    from repro.sim.mapper import EngineConfig, round_timeline

    kw = dict(banks=2, arrays_per_bank=2, write_ports_per_bank=1)
    serial = round_timeline(512, 2048, 1024, EngineConfig(**kw))
    overlap = round_timeline(512, 2048, 1024,
                             EngineConfig(double_buffered=True, **kw))
    assert len(serial) == len(overlap) > 1
    assert (sum(s.exposed_cycles for s in overlap)
            <= sum(s.exposed_cycles for s in serial))


def test_round_walk_chrome_trace_schema():
    from repro.sim.mapper import EngineConfig, round_timeline

    slices = round_timeline(64, 2048, 512,
                            EngineConfig(banks=4, arrays_per_bank=2))
    doc = round_walk_chrome_trace(slices, name="qkv")
    events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert events and all(e["ts"] >= 0 and e["dur"] > 0 for e in events)
    lanes = {e["tid"] for e in doc["traceEvents"]}
    assert 0 in lanes and 1 in lanes          # compute + program lanes


def test_sim_chrome_trace_renders_tile_events():
    from repro.sim.mapper import map_matmul
    from repro.sim.trace import Trace

    trace = Trace()
    map_matmul(64, 1024, 512, trace=trace)
    doc = sim_chrome_trace(trace, freq_hz=50e6)
    events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert len(events) == len(trace.events)
    for e in events:
        assert e["dur"] >= 0 and "macs" in e["args"]


# ---------------------------------------------------------------------------
# lifecycle percentiles: the auditability reduction
# ---------------------------------------------------------------------------

def test_summarize_lifecycle_matches_numpy_percentiles():
    from repro.serve.traffic import summarize_lifecycle

    rng = np.random.default_rng(1)
    records = [{"latency_steps": int(rng.integers(5, 60)),
                "ttft_steps": int(rng.integers(0, 12)),
                "output_tokens": int(rng.integers(1, 20))}
               for _ in range(37)]
    s = summarize_lifecycle(records, slots=4, steps=200, requests=40)
    lat = [r["latency_steps"] for r in records]
    assert s["latency_p50"] == float(np.percentile(lat, 50))
    assert s["latency_p99"] == float(np.percentile(lat, 99))
    assert s["completed"] == 37 and s["requests"] == 40
    toks = sum(r["output_tokens"] for r in records)
    assert s["output_tokens"] == toks
    assert s["goodput_tokens_per_step"] == toks / 200
    assert s["utilization"] == toks / 200 / 4
    # recomputing from a shuffled copy of the records is exact — order
    # independence is what makes the JSONL re-check meaningful
    shuffled = list(records)
    rng.shuffle(shuffled)
    assert summarize_lifecycle(shuffled, slots=4, steps=200,
                               requests=40) == s
