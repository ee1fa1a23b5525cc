#!/usr/bin/env python3
"""The benchmark's one command: run one cell on the chip.

    python3 bench/run.py --workload danube-chat --seed 7 --seconds 45 --trace 0

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiled run.  The last line of stdout is one
JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``, and last ``checks``,
each compared number beside its limit).  Without a TPU, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
The cell, its configuration, its traffic and its metrics are found by
name from ``BENCHMARK.json``, and the module that runs it by its
configuration's ``kind`` (``bench/harness.py``).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))

from harness import BenchError, Layout, emit, log  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        layout = Layout()
        cell = layout.cell(args.workload)
        run = layout.runner(cell.config["kind"])
        result, checks = run(cell, layout, args.seed, args.seconds,
                             bool(args.trace), T_START)
    except (BenchError, ImportError, FileNotFoundError) as err:
        log(f"bench/run.py: {type(err).__name__}: {err}")
        return 2
    emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
