"""A serving cell: seeded open-loop requests into ``PagedServeEngine``.

One process, one chip.  Set-up makes the weights from the seed on the
device, builds the engine, and warms up every shape the seed's traffic
will use.  The window then submits each request when it is due, steps
the engine while it has work, and stamps each request's tokens on the
host clock as ``step()`` returns them (every step ends in a device sync:
the sampled tokens are read back).  After the window the engine goes on,
with arrivals continuing, until every request due in the window has its
first token; tokens after the close do not count towards tokens/s.

End-to-end metrics (all requests due in the window, all of the window):

* ``ttft_p90_ms``: first token minus due time, 90th percentile;
* ``itl_p95_ms``: every gap between consecutive tokens of a request,
  both tokens inside the window, 95th percentile;
* ``output_tokens_per_s``: tokens delivered in the window / its length;
* ``setup_s``: process start to the window's start.

Correctness (``judge``): every model call of the window is kept (its
tokens and positions, as the engine passed them to the jitted step), and
after the window the plain reference replays them (``bench/reference``);
each token a finished request was served is compared with the
reference's logits at its position.

Earlier lines of the run say where host time went in the window: the
garbage collector's pauses and the slowest engine steps.
"""
from __future__ import annotations

import dataclasses
import math
import gc
import shutil
import time
from typing import Dict, List, Optional

import numpy as np

from harness import (BenchError, Cell, CompileCounter, Layout, log,
                     memory_peak_bytes, percentile)
from reference import decoder as ref
from traffic import Arrival, prefill_chunks, schedule
from trace import breakdown, capture, extract, reduce

ANNOTATIONS = ("bench.", "engine.", "model.")


def _program():
    """The system under test."""
    from repro.configs import get_config
    from repro.models import build
    from repro.obs import MetricsRegistry, Observability
    from repro.serve.paged_engine import (PagedEngineConfig, PagedRequest,
                                          PagedServeEngine)
    return (get_config, build, MetricsRegistry, Observability,
            PagedEngineConfig, PagedRequest, PagedServeEngine)


class Probe:
    """The engine's compile watchdog hook (``Observability.watchdog``):
    keeps each jitted entry point, and with ``recording`` on, the tokens
    and positions every call was given (device arrays, read after the
    window)."""

    def __init__(self):
        self.fns: Dict[str, object] = {}
        self.calls: List[tuple] = []
        self.recording = False
        self.call_times: List[tuple] = []

    def watch(self, fn, name=None, limit=16):
        import jax

        self.fns.setdefault(name, fn)
        fn = self.fns[name]

        def watched(*args):
            if self.recording:
                if name == "decode_step":
                    self.calls.append((name, args[1], args[3]))
                else:
                    self.calls.append((name, args[1]["tokens"], args[3]))
                self.call_times.append((name, time.perf_counter()))
            with jax.profiler.TraceAnnotation(f"model.{name}"):
                return fn(*args)

        watched._cache_size = getattr(fn, "_cache_size", None)
        return watched


class GcPauses:
    """A ``gc.callbacks`` entry: (generation, seconds) of each collection
    of the Python garbage collector."""

    def __init__(self):
        self.pauses: List[tuple] = []
        self._start = None

    def __call__(self, phase, info):
        if phase == "start":
            self._start = time.perf_counter()
        elif self._start is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._start))

    def summary(self) -> Dict[int, List[float]]:
        """generation -> [collections, total ms, longest ms]."""
        out: Dict[int, List[float]] = {}
        for g, sec in self.pauses:
            n, tot, mx = out.get(g, [0, 0.0, 0.0])
            out[g] = [n + 1, tot + sec * 1e3, max(mx, sec * 1e3)]
        return out


@dataclasses.dataclass
class Served:
    arrival: Arrival
    req: object
    submit: float = float("nan")
    token_times: List[float] = dataclasses.field(default_factory=list)


class ServeCell:
    def __init__(self, cell: Cell, seed: int, seconds: float,
                 shared: Optional["ServeCell"] = None):
        """``shared``: an earlier cell of the same configuration in this
        process, whose model and compiled entry points are reused."""
        (get_config, build, MetricsRegistry, Observability,
         self.EngineConfig, self.Request, self.Engine) = _program()
        self.cell, self.seed, self.seconds = cell, seed, seconds
        c = cell.config
        self.sizes = c["model"]
        self.cfg = dataclasses.replace(get_config(c["arch"]), **self.sizes)
        self.model = shared.model if shared else build(self.cfg)
        e = c["engine"]
        mix = cell.traffic
        worst = mix["prompt"]["max"] + mix["output"]["max"]
        self.engine_kw = dict(
            slots=e["slots"], block_size=e["block_size"],
            num_blocks=1 + e["slots"] * math.ceil(worst / e["block_size"]),
            max_prefill_tokens=e["max_prefill_tokens"], eos_id=e["eos_id"],
            temperature=e["temperature"], seed=seed)
        self.registry = MetricsRegistry()
        self.probe = Probe()
        if shared:
            self.probe.fns = shared.probe.fns
        self.obs = Observability(registry=self.registry, watchdog=self.probe)
        self.arrivals = schedule(mix, seed, seconds, self.cfg.vocab_size)

    # -- set-up ---------------------------------------------------------

    def make_weights(self):
        from weights import make_params

        self.params = make_params(self.model.schema(), self.seed)
        import jax
        jax.block_until_ready(self.params)

    def new_engine(self):
        self.engine = self.Engine(self.model, self.params, self.cfg,
                                  self.EngineConfig(**self.engine_kw),
                                  obs=self.obs)
        return self.engine

    def warm_up(self) -> Dict[str, int]:
        """Run every program and eager op the window can reach, at every
        shape the seed's traffic implies, on the engine's own cache (its
        contents are written back unchanged and every block is freed)."""
        import jax
        import jax.numpy as jnp

        eng, cache, probe = self.engine, self.engine.cache, self.probe
        e = self.engine_kw
        bs, slots = e["block_size"], e["slots"]
        prefill, decode = probe.fns["prefill_chunk"], probe.fns["decode_step"]
        params = self.params
        # prompt lengths are the same set for every seed; how they pair
        # with output lengths is not, so every reservation and view the
        # mix allows is warmed, and set-up does the same work every seed
        mix = self.cell.traffic
        pairs = set()
        for a in self.arrivals:
            for pos, c in prefill_chunks(len(a.prompt),
                                         e["max_prefill_tokens"]):
                pairs.add((pos, c, cache.view_len(pos + c)))
        shortest = mix["prompt"]["min"]
        longest = mix["prompt"]["max"] + mix["output"]["max"]
        reservations = range(
            math.ceil((shortest + mix["output"]["min"]) / bs),
            math.ceil(longest / bs) + 1)
        took = {}
        t = time.perf_counter()
        done_pairs = set()
        for pos, c, view in sorted(pairs):
            if (c, view) in done_pairs:
                continue
            done_pairs.add((c, view))
            cache.alloc_slot(0, view // bs)
            v = cache.gather([0], view)
            logits, _ = prefill(params, {"tokens": jnp.zeros((1, c), jnp.int32)},
                                v, jnp.int32(pos))
            cache.commit_prefill(v, 0, pos, c)
            int(jnp.argmax(logits[0]))
            cache.free_slot(0)
        took["prefill"] = time.perf_counter() - t
        t = time.perf_counter()
        views = sorted({cache.view_len(n)
                        for n in range(shortest + 1, longest + 1)})
        tokens = jnp.asarray(np.zeros(slots, np.int32))[:, None]
        for view in views:
            v = cache.gather([0] * slots, view)
            logits, _ = decode(params, tokens, v,
                               jnp.asarray(np.zeros(slots, np.int32)))
            np.asarray(jnp.argmax(logits, -1))
            cache.alloc_slot(0, view // bs)
            for n in range(1, slots + 1):
                cache.commit_decode(v, list(range(n)), [0] * n,
                                    [view - 1] * n)
            cache.free_slot(0)
        took["decode"] = time.perf_counter() - t
        t = time.perf_counter()
        for n in reservations:
            cache.alloc_slot(0, n)
            cache.free_slot(0)
        took["free"] = time.perf_counter() - t
        t = time.perf_counter()
        # two short requests through the public path: what is left
        for rid in (-2, -1):
            eng.submit(self.Request(
                rid=rid, prompt=np.full(min(len(a.prompt) for a in
                                            self.arrivals), 3, np.int32),
                max_new_tokens=3))
        eng.drain()
        took["requests"] = time.perf_counter() - t
        return {"prefill_shapes": len(done_pairs), "decode_views": len(views),
                "reservations": len(reservations),
                "seconds": {k: round(v, 3) for k, v in took.items()}}

    # -- the window -----------------------------------------------------

    def serve(self, trace_dir: Optional[str] = None, trace_s: float = 0.0,
              drain_s: float = 60.0):
        """Run the window (and its drain); returns the requests served.
        With ``trace_dir``, the profiler records the window's last
        ``trace_s`` seconds and is stopped only once the loop has ended,
        so that writing the trace delays no request."""
        import jax

        eng = self.engine
        reg0, stats0 = self._counters(), dataclasses.replace(eng.stats)
        self.probe.calls.clear()
        self.probe.call_times.clear()
        self.probe.recording = True
        n_window = sum(a.in_window for a in self.arrivals)
        served: Dict[int, Served] = {}
        active: Dict[int, Served] = {}
        step_times: Dict[int, float] = {}
        steps: List[tuple] = []       # (start, seconds, decode, prefill)
        pending, i = self.arrivals, 0
        tracer, span = None, None
        self.trace_span = self.at_close = None
        gc_pauses = GcPauses()
        gc.callbacks.append(gc_pauses)
        t0 = self.t0 = time.perf_counter()
        while True:
            now = time.perf_counter() - t0
            if now >= self.seconds and self.at_close is None:
                self.at_close = {"queued": eng.scheduler.pending,
                                 "live": eng.live}
            if now >= self.seconds:
                firsts = sum(1 for s in served.values()
                             if s.arrival.in_window and s.token_times)
                if firsts == n_window or now >= self.seconds + drain_s:
                    break
            if trace_dir and tracer is None and now >= self.seconds - trace_s:
                tracer = capture(trace_dir)
                tracer.__enter__()
                span = jax.profiler.TraceAnnotation("bench.trace_window")
                span.__enter__()
                self.trace_span = [now, None]
            if span is not None and now >= self.seconds:
                span.__exit__(None, None, None)
                self.trace_span[1], span = now, None
            while i < len(pending) and pending[i].due <= now:
                a = pending[i]
                with jax.profiler.TraceAnnotation("bench.submit"):
                    req = self.Request(rid=a.rid, prompt=a.prompt,
                                       max_new_tokens=a.max_new)
                    eng.submit(req)
                served[a.rid] = active[a.rid] = Served(
                    a, req, submit=time.perf_counter() - t0)
                i += 1
            if eng.scheduler.pending or eng.live:
                start = step_times[eng.step_count] = time.perf_counter() - t0
                ticks, chunks = eng.stats.decode_ticks, eng.stats.prefill_chunks
                with jax.profiler.TraceAnnotation("engine.step"):
                    eng.step()
                t = time.perf_counter() - t0
                steps.append((start, t - start,
                              eng.stats.decode_ticks - ticks,
                              eng.stats.prefill_chunks - chunks))
                with jax.profiler.TraceAnnotation("bench.record"):
                    for rid in list(active):
                        s = active[rid]
                        new = len(s.req.out_tokens) - len(s.token_times)
                        if new:
                            s.token_times.extend([t] * new)
                        if s.req.done:
                            del active[rid]
            else:
                wait = pending[i].due - now if i < len(pending) else 0.01
                with jax.profiler.TraceAnnotation("bench.wait_arrival"):
                    time.sleep(min(max(wait, 0.0), 0.05))
        if span is not None:
            span.__exit__(None, None, None)
            self.trace_span[1] = time.perf_counter() - t0
        if tracer is not None:
            tracer.__exit__(None, None, None)
        gc.callbacks.remove(gc_pauses)
        self.probe.recording = False
        self.served, self.step_times = served, step_times
        in_window = [x for x in steps if x[0] + x[1] <= self.seconds]
        self.host = {
            "gc": gc_pauses.summary(),
            "slowest_steps": [
                {"start_s": round(a, 3), "ms": round(d * 1e3, 1),
                 "decode_ticks": n, "prefill_chunks": c}
                for a, d, n, c in sorted(in_window, key=lambda x: -x[1])[:5]]}
        self.counters = {k: v - reg0[k] for k, v in self._counters().items()}
        st = eng.stats
        self.stats = {k: getattr(st, k) - getattr(stats0, k)
                      for k in ("steps", "decode_ticks", "prefill_chunks")}
        self.end_s = time.perf_counter() - t0
        return served

    def _counters(self) -> Dict[str, float]:
        names = ("serve.decode_tokens", "serve.prefill_tokens",
                 "serve.admitted_requests", "serve.completed_requests",
                 "serve.deferred_steps")
        return {n: self.registry.value(n) for n in names}

    # -- numbers --------------------------------------------------------

    def end_to_end(self) -> Dict[str, float]:
        w = self.seconds
        due = [s for s in self.served.values() if s.arrival.in_window]
        ttft = [(s.token_times[0] - s.arrival.due) if s.token_times
                else (self.end_s - s.arrival.due) for s in due]
        gaps, delivered = [], 0
        for s in self.served.values():
            ts = s.token_times
            delivered += sum(1 for t in ts if t <= w)
            if s.arrival.in_window:
                gaps.extend(b - a for a, b in zip(ts, ts[1:]) if b <= w)
        late = [s.submit - s.arrival.due for s in self.served.values()]
        self.samples = {
            "due": len(due),
            "missing_first_token": sum(not s.token_times for s in due),
            "completed_in_window": sum(1 for s in due if s.req.done
                                       and s.token_times[-1] <= w),
            "ttft_samples": len(ttft), "itl_samples": len(gaps),
            "tokens_in_window": delivered, **(self.at_close or {}),
            "generator_late_p50_ms": percentile(late, 50) * 1e3,
            "generator_late_max_ms": max(late) * 1e3}
        return {"ttft_p90_ms": percentile(ttft, 90) * 1e3,
                "itl_p95_ms": percentile(gaps, 95) * 1e3 if gaps else 0.0,
                "output_tokens_per_s": delivered / w}

    # -- correctness ----------------------------------------------------

    def record_calls(self) -> List[ref.Call]:
        """The window's calls as host arrays (read after the window)."""
        out = []
        for name, toks, pos in self.probe.calls:
            toks, pos = np.asarray(toks), np.asarray(pos)
            if name == "decode_step":
                out.append(ref.Call(toks.reshape(-1, 1), pos.reshape(-1, 1)))
            else:
                n = toks.shape[1]
                out.append(ref.Call(toks, np.broadcast_to(
                    int(pos) + np.arange(n), toks.shape)))
        self.recorded = out
        return out

    def plan(self) -> ref.Plan:
        seqs, prompts, finished, served = {}, {}, set(), {}
        for rid, s in self.served.items():
            r = s.req
            seqs[rid] = np.concatenate(
                [r.prompt, np.asarray(r.out_tokens, np.int32)])
            prompts[rid] = len(r.prompt)
            served[rid] = list(r.out_tokens)
            if r.done:
                finished.add(rid)
        whole = ref.arithmetic(self.sizes)[0] == "bp8"
        if not whole:
            seqs = {rid: seq for rid, seq in seqs.items() if rid in finished}
        self.the_plan = ref.plan(self.recorded, seqs, prompts, finished,
                                 served, whole_calls=whole)
        return self.the_plan

    def layer_record(self, reduced: Optional[Dict], peaks: Dict) -> Dict:
        """What the per-layer readers (``bench/metrics``) read."""
        lifecycle = [{"rid": s.req.rid, "in_window": s.arrival.in_window,
                      "due_s": s.arrival.due, "submit_s": s.submit,
                      "admitted_step": s.req.admitted_step,
                      "first_token_s": (s.token_times[0] if s.token_times
                                        else None),
                      "output_tokens": len(s.req.out_tokens),
                      "prompt_tokens": len(s.req.prompt)}
                     for s in self.served.values()]
        calls, steps = None, None
        if self.trace_span is not None:
            a, b = self.trace_span
            traced = [k for k, (_, t) in enumerate(self.probe.call_times)
                      if a <= t - self.t0 <= b]
            calls = self._call_work(set(traced))
            steps = sum(1 for t in self.step_times.values() if a <= t <= b)
        return {"window_s": self.seconds, "slots": self.engine_kw["slots"],
                "lifecycle": lifecycle, "step_times": self.step_times,
                "counters": self.counters, "stats": self.stats,
                "trace": reduced, "traced_calls": calls,
                "traced_steps": steps, "peaks": peaks, "sizes": self.sizes}

    def _call_work(self, traced: set) -> List[Dict]:
        """Each traced call: its program, rows, tokens per row, and the
        context (position + 1) of each token of a request.  A padding row
        reads token 0 at position 0, which no request does (prompts draw
        their ids from ``token_id_min`` up, and decode starts past them)."""
        out = []
        for k in sorted(traced):
            call = self.recorded[k]
            toks, pos = call.tokens.reshape(-1), call.positions.reshape(-1)
            real = ~((pos == 0) & (toks == 0))
            out.append({"program": self.probe.calls[k][0],
                        "rows": int(call.tokens.shape[0]),
                        "length": int(call.tokens.shape[1]),
                        "contexts": (pos[real] + 1).tolist()})
        return out

# ---------------------------------------------------------------------------
# the comparison that decides ``correct``
# ---------------------------------------------------------------------------

def judge(sc: ServeCell, limits: Dict[str, float], control: bool = False):
    """Replay the window's calls in the plain reference and read, over
    the served tokens, by how much each one's logit lies below the
    reference's best there: the widest gap (``widest_logit_gap``) and the
    mean (``mean_logit_gap``), each held to its limit in ``limits``.  With
    ``control``, the tokens that the control (the reference one precision
    step below the configuration's) puts first at the same positions are
    held to the same rule, as if served.  -> ([(correct, {number:
    value})], served first, then the control's; the reference's
    statistics; the plan)."""
    plan = sc.plan()
    probes = [plan.compare_tokens]
    if control:
        low = ref.replay(sc.sizes, sc.params, plan, control=True)
        probes.append(low["argmax"])
    stats = ref.replay(sc.sizes, sc.params, plan, probes)
    verdicts = []
    for j in range(len(probes)):
        gaps = stats["max"] - stats["at"][:, j]
        numbers = {"widest_logit_gap": float(gaps.max()) if len(gaps)
                   else float("nan"),
                   "mean_logit_gap": float(gaps.mean()) if len(gaps)
                   else float("nan")}
        verdicts.append((all(np.isfinite(v) and v <= limits[k]
                             for k, v in numbers.items()), numbers))
    return verdicts, stats, plan


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run(cell: Cell, layout: Layout, seed: int, seconds: float, trace: bool,
        t_start: float, require_tpu: bool = True):
    """Set up, serve the window, check it; -> (result line, checks)."""
    import jax

    from harness import device_report, enable_compile_cache

    cache_dir = enable_compile_cache(layout.root)
    device = device_report(cell.chips, require_tpu)
    log(f"device platform={device['platform']} kind={device['kind']} "
        f"count={len(jax.devices())} cell_chips={cell.chips}")
    peaks = layout.peaks(device["kind"])
    counter = CompileCounter()
    sc = ServeCell(cell, seed, seconds)
    sc.make_weights()
    sc.new_engine()
    warm = sc.warm_up()
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f}s: compile cache {cache_dir}, "
        f"{counter.compiles} compiles ({counter.seconds:.1f}s), "
        f"{counter.cache_hits} cache hits; "
        f"warm-up {warm}")
    trace_cfg = cell.config["trace"]
    trace_dir = None
    if trace:
        trace_dir = str(layout.root / ".bench_trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
    mark = counter.mark()
    sc.serve(trace_dir, trace_cfg["seconds"])
    in_window = counter.since(mark)
    e2e = sc.end_to_end()
    log(f"compiles in window: {in_window['compiles']} "
        f"(cache hits {in_window['cache_hits']})")
    log("requests: " + ", ".join(f"{k}={v}" for k, v in sc.samples.items()))
    log(f"engine: {sc.stats}, counters {sc.counters}")
    log(f"host: gc pauses by generation [collections, ms, longest ms] "
        f"{sc.host['gc']}; slowest steps {sc.host['slowest_steps']}")
    device["memory_peak_bytes"] = memory_peak_bytes(cell.chips)
    sc.record_calls()
    sc.engine = None                 # the pool and views go before the
    gc.collect()                     # reference runs

    reduced = None
    if trace:
        events = extract(trace_dir, ANNOTATIONS)
        shutil.rmtree(trace_dir, ignore_errors=True)
        span = next((e for e in events["host"]
                     if e[0] == "bench.trace_window"), None)
        if span is None:
            raise BenchError("the trace holds no bench.trace_window span")
        reduced = reduce(events, span[1], span[1] + span[2])
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        for dev in reduced["devices"]:
            progs = sorted(((n, p["seconds"], len(p["runs"]))
                            for n, p in dev["programs"].items()),
                           key=lambda x: -x[1])
            log(f"trace {dev['device']}: busy {dev['busy_s']:.4f}s of "
                f"{reduced['window_s']:.4f}s; programs (name, s, runs) "
                f"{progs[:40]}")

    limits = {k: float(v) for k, v in cell.config["correct"].items()}
    t_ref = time.perf_counter()
    try:
        [(correct, numbers)], _, plan = judge(sc, limits)
        n_cmp = len(plan.compare_rows)
        log(f"reference: {len(sc.recorded)} calls, {len(plan.calls)} rows, "
            f"{n_cmp} served tokens of {len(set(plan.compare_rids.tolist()))} "
            f"finished requests compared in "
            f"{time.perf_counter() - t_ref:.1f}s")
        checks = {k: {"value": numbers[k], "limit": lim}
                  for k, lim in limits.items()}
    except ref.ReplayError as err:
        log(f"reference: the calls do not account for the tokens: {err}")
        correct = False
        checks = {k: {"value": None, "limit": lim}
                  for k, lim in limits.items()}

    if trace:
        rec = sc.layer_record(reduced, peaks)
        metrics = {}
        for m in cell.per_layer:
            v = layout.reader(m["name"])(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = dict(e2e, setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    result = {"correct": bool(correct), "attempted": sc.samples["due"],
              "failed": sc.samples["missing_first_token"],
              "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = breakdown(reduced)
    return result, checks
