#!/usr/bin/env python3
"""Readings that set a serving cell's rate and its correctness limit,
many seeds or rates in one process (on the chip, as ``run.py``).

    python3 bench/calibrate.py --workload danube-chat --seconds 20 \\
        --seeds 11,12,13 [--control 1] [--rates 1.0,1.5]

For each seed (and each rate, where ``--rates`` overrides the mix's):
serve the window, then judge it as a run of the benchmark does
(``serve.judge``, against the limit in the configuration's file) and
print the gaps of the served tokens and whether they are correct.
With ``--control 1`` also the control: the reference one precision step
below the configuration's (``reference/decoder.py``) computes the same
calls, and the tokens it puts first are judged by the same rule, as if
served.  ``--check 0`` skips both (a rate sweep).  The benchmark's own
runs do not run the control.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))

import numpy as np  # noqa: E402

from harness import (CompileCounter, Layout, device_report,  # noqa: E402
                     enable_compile_cache, memory_peak_bytes)
from serve import ServeCell, judge  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--check", type=int, default=1)
    ap.add_argument("--root", default=str(BENCH.parent),
                    help="checkout whose BENCHMARK.json names the cell")
    args = ap.parse_args()
    layout = Layout(Path(args.root))
    cell = layout.cell(args.workload)
    enable_compile_cache(layout.root)
    print(json.dumps({"device": device_report(cell.chips)}),
          flush=True)
    counter = CompileCounter()
    seeds = [int(s) for s in args.seeds.split(",")]
    rates = [float(r) for r in args.rates.split(",")] if args.rates else [None]
    shared = None
    for rate in rates:
        for seed in seeds:
            c = copy.copy(cell)
            if rate is not None:
                c.traffic = dict(cell.traffic, rate_per_s=rate)
            t0 = time.perf_counter()
            setup_mark, setup_s0 = counter.mark(), counter.seconds
            if shared is not None:
                shared.engine = None
                shared.params = None
                gc.collect()
            sc = ServeCell(c, seed, args.seconds, shared=shared)
            sc.make_weights()
            sc.new_engine()
            warm = sc.warm_up()
            setup = time.perf_counter() - t0
            warm["compiles"] = counter.since(setup_mark)
            warm["compile_s"] = round(counter.seconds - setup_s0, 1)
            mark = counter.mark()
            sc.serve()
            e2e = sc.end_to_end()
            row = {"seed": seed, "rate": c.traffic["rate_per_s"],
                   "setup_s": setup, "warm": warm,
                   "window_compiles": counter.since(mark), **e2e,
                   **sc.samples, "stats": sc.stats,
                   "offered_tokens_per_s": c.traffic["rate_per_s"] * float(
                       np.mean([a.max_new for a in sc.arrivals
                                if a.in_window])),
                   "memory_peak_bytes": memory_peak_bytes(cell.chips),
                   "host": sc.host}
            sc.record_calls()
            sc.engine = None
            gc.collect()
            if args.check:
                t1 = time.perf_counter()
                limits = cell.config["correct"]
                verdicts, full, plan = judge(sc, limits, bool(args.control))
                gaps = full["max"][:, None] - full["at"]
                row["compared_tokens"] = int(len(plan.compare_rows))
                for j, who in enumerate(["sound", "control"][:gaps.shape[1]]):
                    g = gaps[:, j]
                    row[who] = {"correct": verdicts[j][0], "limits": limits,
                                **verdicts[j][1],
                                "gap_p99": float(np.percentile(g, 99)),
                                "off_best_share": float((g > 0).mean())}
                row["check_s"] = time.perf_counter() - t1
            print(json.dumps(row), flush=True)
            shared = sc
    return 0


if __name__ == "__main__":
    sys.exit(main())
