"""Median wait of a window's request from its due time to the engine step
that admitted it (the step's start on the host clock): scheduler and
engine.  Moves ``ttft_p90_ms``."""
from harness import percentile


def read(rec):
    waits = [rec["step_times"][r["admitted_step"]] - r["due_s"]
             for r in rec["lifecycle"]
             if r["in_window"] and r["admitted_step"] in rec["step_times"]]
    return percentile(waits, 50) * 1e3 if waits else None
