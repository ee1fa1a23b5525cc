"""What every cell of the benchmark shares: where its files are found by
name, the device check, the peaks table, the compile counter, percentiles
and the result line.

A cell is one entry of ``BENCHMARK.json``'s ``workloads``.  Its
configuration is the file that ``configs[].file`` names, and that file's
``kind`` names the module that runs it, ``bench/<kind>.py``; its traffic
mix is ``bench/traffic/<traffic>.json`` and each per-layer metric is read
by ``bench/metrics/<name>.py``.  A later cell, kind, mix or metric is
added as new files and entries; nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import sys
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, unknown device, a
    cell or file that is not there)."""


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One workload with its configuration, traffic mix and metrics."""

    def __init__(self, spec: Dict, workload: Dict, config: Dict,
                 config_file: Dict, traffic: Dict, end_to_end: List[Dict],
                 per_layer: List[Dict]):
        self.spec, self.workload = spec, workload
        self.name = workload["name"]
        self.chips = int(workload["chips"])
        self.config_entry = config
        self.config = config_file
        self.traffic = traffic
        self.end_to_end = end_to_end
        self.per_layer = per_layer


class Layout:
    """Finds a cell's files by name under a checkout ``root``."""

    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.bench = self.root / "bench"

    def spec(self) -> Dict:
        path = self.root / "BENCHMARK.json"
        if not path.is_file():
            raise BenchError(f"{path} not found")
        return load_json(path)

    def cell(self, name: str) -> Cell:
        spec = self.spec()
        work = {w["name"]: w for w in spec["workloads"]}
        if name not in work:
            raise BenchError(f"no workload {name!r} in BENCHMARK.json; "
                             f"known: {sorted(work)}")
        w = work[name]
        config = next(c for c in spec["configs"] if c["name"] == w["config"])
        config_file = load_json(self.root / config["file"])
        traffic = load_json(self.bench / "traffic" / f"{w['traffic']}.json")
        return Cell(spec, w, config, config_file, traffic,
                    self._metrics(spec["end_to_end"], name, spec),
                    self._metrics(spec["per_layer"], name, spec))

    @staticmethod
    def _metrics(entries: List[Dict], cell: str, spec: Dict) -> List[Dict]:
        out = []
        for m in entries:
            cells = m.get("workloads")
            if cells is None and "moves" in m:
                # no list: every cell that reports the metric it moves
                moved = next(e for e in spec["end_to_end"]
                             if e["name"] == m["moves"])
                cells = moved.get("workloads", [cell])
            if cells is None or cell in cells:
                out.append(m)
        return out

    def reader(self, metric: str) -> Callable[[Dict], Optional[float]]:
        """``read(record)`` of ``bench/metrics/<metric>.py``."""
        path = self.bench / "metrics" / f"{metric}.py"
        if not path.is_file():
            raise BenchError(f"no reader for metric {metric!r} at {path}")
        return _load(path, "bench_metric_" + metric).read

    def runner(self, kind: str) -> Callable:
        """``run(cell, layout, seed, seconds, trace, t_start)`` of
        ``bench/<kind>.py``, which returns (result line, checks)."""
        path = self.bench / f"{kind}.py"
        if not path.is_file():
            raise BenchError(f"no module for cell kind {kind!r} at {path}")
        return _load(path, "bench_kind_" + kind).run

    def peaks(self, device_kind: str) -> Dict[str, float]:
        table = load_json(self.bench / "peaks.json")
        if device_kind not in table["devices"]:
            raise BenchError(f"device kind {device_kind!r} is not in "
                             f"bench/peaks.json ({sorted(table['devices'])})")
        return table["devices"][device_kind]


def _load(path: Path, name: str):
    name = name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# devices, compile cache, compile counter
# ---------------------------------------------------------------------------

def enable_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent cache at a fixed path: ``$JAX_COMPILATION_CACHE_DIR``
    where it is set (JAX reads it itself), else ``<checkout>/.jax_cache``.
    Every program is kept, the eager ops' small ones too, so that a second
    run of a cell compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(root / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_report(chips: int, require_tpu: bool = True) -> Dict[str, Any]:
    """The devices as JAX reports them; fails without a TPU or with fewer
    chips than the cell asks for."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}
    if require_tpu and info["platform"] != "tpu":
        raise BenchError(f"no TPU: JAX found {info['platform']} devices")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return info


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the cell's chips."""
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


class CompileCounter:
    """Backend compiles and persistent-cache hits, from JAX's monitoring
    events (as ``chip_smoke.py``'s ``CompileClock``)."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.cache_hits = 0
        self.seconds = 0.0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.seconds += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def mark(self) -> tuple:
        return self.compiles, self.cache_hits

    def since(self, mark: tuple) -> Dict[str, int]:
        return {"compiles": self.compiles - mark[0],
                "cache_hits": self.cache_hits - mark[1]}


# ---------------------------------------------------------------------------
# statistics and output
# ---------------------------------------------------------------------------

def percentile(values: List[float], q: float) -> float:
    """Linear-interpolation percentile (numpy's default); ``q`` in 0..100."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def emit(result: Dict[str, Any], checks: Dict[str, Dict[str, float]]) -> None:
    """The run's last lines: each compared number beside its limit on
    stderr, then the result as one JSON line on stdout with ``checks``
    as its last key."""
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    line = dict(result)
    line["checks"] = checks
    print(json.dumps(line), flush=True)
