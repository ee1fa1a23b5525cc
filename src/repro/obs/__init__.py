"""repro.obs — unified tracing + metrics across serve/train/sim.

  registry.py  process-local counters/gauges/histograms with labeled
               series (snapshot / to_jsonl), plus the append-only JSONL
               step logger that absorbed ``repro.utils.metrics``
  trace.py     in-memory spans (``time.perf_counter`` or an injected
               clock, nesting, lanes) with a Chrome-trace/Perfetto
               exporter, and adapters that render the OISMA engine
               simulator's round walk and tile-class traces onto the
               same timeline
  watchdog.py  JAX compile/retrace watchdog: per-callsite compile-count
               bounds asserted live (the paged engine's O(log) shape
               guarantee as a running metric, not just a test)

``Observability`` is the bundle the instrumented layers accept: the
paged serving engine, the trainer and the checkpoint manager each take
an optional ``obs`` and reach spans only through ``Observability.span``.
That span is a ``jax.profiler.TraceAnnotation``, so while a profile is
captured it lands in the ``.xplane.pb`` host plane on the device
planes' clock, where device idle gaps and ops can be put down to it.
See ``docs/observability.md`` for the metric catalog and span taxonomy.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Optional

from jax.profiler import TraceAnnotation

from repro.obs.registry import (JsonlLogger, MetricsRegistry, percentile,
                                read_metrics, step_time_summary)
from repro.obs.trace import (TraceEvent, Tracer, chrome_doc,
                             round_walk_chrome_trace, sim_chrome_trace)
from repro.obs.watchdog import RetraceError, RetraceWatchdog, call_signature


@dataclasses.dataclass
class Observability:
    """What an instrumented layer needs, in one handle.

    Any field may be None: the registry is the cheap always-on half,
    the tracer opts into an in-memory timeline for the Chrome/Perfetto
    export, the watchdog opts into live compile-bound assertion.
    """
    registry: MetricsRegistry = dataclasses.field(
        default_factory=MetricsRegistry)
    tracer: Optional[Tracer] = None
    watchdog: Optional[RetraceWatchdog] = None

    @classmethod
    def make(cls, *, trace: bool = False, watchdog_limit: Optional[int] = None,
             clock=None) -> "Observability":
        """Convenience: a registry, optionally a tracer (with ``clock``
        injected for deterministic tests) and a raise-mode watchdog
        pinned at ``watchdog_limit`` compiled shapes per callsite."""
        registry = MetricsRegistry()
        tracer = (Tracer(clock) if clock is not None else Tracer()) \
            if trace else None
        wd = (RetraceWatchdog(registry, default_limit=watchdog_limit)
              if watchdog_limit is not None else None)
        return cls(registry=registry, tracer=tracer, watchdog=wd)

    def span(self, name: str, tid: int = 0, **args: Any):
        """A span around the ``with`` block: a profiler annotation named
        ``name`` with ``args`` as its stats (a no-op of about a
        microsecond when no profile is being captured), and where a
        tracer is attached, also its in-memory span on lane ``tid``."""
        if self.tracer is None:
            return TraceAnnotation(name, **args)
        return _traced_span(self.tracer, name, tid, args)


@contextlib.contextmanager
def _traced_span(tracer: Tracer, name: str, tid: int, args: dict):
    with TraceAnnotation(name, **args), tracer.span(name, tid=tid, **args):
        yield


def no_span(name: str, tid: int = 0, **args: Any):
    """``Observability.span`` for a layer given no ``obs``: nothing."""
    return _NO_SPAN


_NO_SPAN = contextlib.nullcontext()


__all__ = [
    "JsonlLogger", "MetricsRegistry", "percentile", "read_metrics",
    "step_time_summary", "TraceEvent", "Tracer", "chrome_doc",
    "round_walk_chrome_trace", "sim_chrome_trace", "RetraceError",
    "RetraceWatchdog", "call_signature", "Observability", "no_span",
]
