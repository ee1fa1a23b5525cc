"""Batched serving demo: the paged continuous-batching engine over a small
model, greedy decode with chunked prefill + per-token decode_step (KV
caches / SSM states).

Run: PYTHONPATH=src python examples/serve_batched.py --arch zamba2_2p7b
"""
import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.models import build
from repro.models.params import init_tree
from repro.serve.paged_engine import (PagedEngineConfig, PagedRequest,
                                      PagedServeEngine)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="h2o_danube_1p8b")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=8)
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=True)
    model = build(cfg)
    params = init_tree(model.schema(), jax.random.key(0))
    engine = PagedServeEngine(model, params, cfg,
                              PagedEngineConfig(slots=4, num_blocks=32))

    rng = np.random.default_rng(7)
    reqs = [PagedRequest(rid=i,
                         prompt=rng.integers(3, cfg.vocab_size, 4 + i % 3)
                         .astype(np.int32),
                         max_new_tokens=args.max_new)
            for i in range(args.requests)]
    t0 = time.time()
    results = engine.run(reqs)
    dt = time.time() - t0
    total = sum(len(v) for v in results.values())
    print(f"served {len(results)} requests / {total} tokens in {dt:.1f}s "
          f"on {cfg.name}")
    for rid in sorted(results):
        print(f"  req {rid}: {results[rid]}")


if __name__ == "__main__":
    main()
