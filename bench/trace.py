"""Device trace: capture with JAX's profiler, extract, reduce to metrics.

``capture`` runs the profiler without its Python tracer (host spans are
the benchmark's own ``TraceAnnotation`` s).  ``extract`` turns the
``.xplane.pb`` into plain events: for each device, its ops and its
programs ("modules"), and the host's spans, all as
``[name, start_ns, duration_ns]`` on one clock.  ``reduce`` works on
those events alone, so a small recorded file checks it
(``bench/tests``).

Reduction, per device, over the traced window ``[t0, t1]``:

* busy: the union of the op intervals, clipped to the window;
* programs: device seconds per program name, the count of runs and each
  run's duration (a module event is one run of one compiled program;
  ``jit_f(123)`` is named ``f``);
* kernels: device seconds and count per op name;
* idle gaps: the holes in the busy union, each labelled with the
  innermost host span that holds the gap's midpoint ("other" where none).

Devices are averaged where a metric is a share (busy over window).
"""
from __future__ import annotations

import glob
import re
from contextlib import contextmanager
from typing import Dict, Iterator, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@contextmanager
def capture(log_dir: str) -> Iterator[None]:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def extract(log_dir: str, annotations: Tuple[str, ...]) -> Dict:
    """Plain events from the newest ``.xplane.pb`` under ``log_dir``.
    Host events are kept only where their name starts with one of
    ``annotations``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(paths[-1])
    devices: Dict[str, Dict[str, List]] = {}
    host: List = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name \
                and "SparseCore" not in plane.name:
            dev = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                dev[key].extend([e.name, int(e.start_ns), int(e.duration_ns)]
                                for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, int(e.start_ns), int(e.duration_ns)]
                            for e in line.events
                            if e.name.startswith(annotations))
    return {"devices": devices, "host": host}


def program_name(module: str) -> str:
    """``jit_decode_step(42)`` -> ``decode_step``."""
    name = re.sub(r"\(\d+\)$", "", module)
    return name[4:] if name.startswith("jit_") else name


def op_name(op: str) -> str:
    """An op event's name without its HLO text: ``%while.39 = (...)`` ->
    ``while.39``."""
    return op.split(" = ", 1)[0].lstrip("%")


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _label(gap: Tuple[int, int], host: List) -> str:
    mid = (gap[0] + gap[1]) // 2
    best: Optional[Tuple[int, str]] = None
    for name, start, dur in host:
        if start <= mid <= start + dur and (best is None or dur < best[0]):
            best = (dur, name)
    return best[1] if best else "other"


def reduce(events: Dict, t0: int, t1: int) -> Dict:
    """Busy, idle gaps, programs and kernels of the window ``[t0, t1]``
    (ns on the trace's clock)."""
    window = t1 - t0
    per_dev = []
    for name in sorted(events["devices"]):
        dev = events["devices"][name]
        spans = [(max(s, t0), min(s + d, t1)) for _, s, d in dev["ops"]
                 if s < t1 and s + d > t0]
        busy = _union([(a, b) for a, b in spans if b > a])
        busy_ns = sum(b - a for a, b in busy)
        edges = [t0] + [x for ab in busy for x in ab] + [t1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        programs: Dict[str, Dict] = {}
        for mod, s, d in dev["modules"]:
            if s < t0 or s + d > t1:
                continue
            p = programs.setdefault(program_name(mod),
                                    {"seconds": 0.0, "runs": []})
            p["seconds"] += d * 1e-9
            p["runs"].append(d * 1e-9)
        kernels: Dict[str, Dict] = {}
        for op, s, d in dev["ops"]:
            if s < t0 or s + d > t1:
                continue
            k = kernels.setdefault(op, {"seconds": 0.0, "count": 0})
            k["seconds"] += d * 1e-9
            k["count"] += 1
        per_dev.append({"device": name, "busy_s": busy_ns * 1e-9,
                        "gaps": [(_label(g, events["host"]),
                                  (g[1] - g[0]) * 1e-9) for g in gaps],
                        "programs": programs, "kernels": kernels})
    n = max(1, len(per_dev))
    return {"window_s": window * 1e-9,
            "busy_s": sum(d["busy_s"] for d in per_dev) / n,
            "devices": per_dev}


def kernel_seconds(reduced: Dict, pattern: str) -> Tuple[float, int]:
    """Device seconds of ops whose name matches ``pattern``, averaged over
    devices, and their count over all devices."""
    rx = re.compile(pattern)
    secs, count = 0.0, 0
    for dev in reduced["devices"]:
        for name, k in dev["kernels"].items():
            if rx.search(name):
                secs += k["seconds"]
                count += k["count"]
    n = max(1, len(reduced["devices"]))
    return secs / n, count


def breakdown(reduced: Dict, top: int = 10) -> Dict[str, List]:
    """The device ops that took most time and the idle seconds by what the
    host was doing, on the first device."""
    if not reduced["devices"]:
        return {"device_ops": [], "idle_gaps": []}
    dev = reduced["devices"][0]
    ops = sorted(([op_name(n), k["seconds"]]
                  for n, k in dev["kernels"].items()),
                 key=lambda x: -x[1])[:top]
    idle: Dict[str, float] = {}
    for label, secs in dev["gaps"]:
        idle[label] = idle.get(label, 0.0) + secs
    gaps = sorted(([n, s] for n, s in idle.items()), key=lambda x: -x[1])
    return {"device_ops": ops, "idle_gaps": gaps[:top]}
