"""repro.serve — the serving stack: one engine over a paged cache.

  paged_cache.py  block-pool KV cache: per-slot block tables over a
                  shared physical pool, allocation at admission / free on
                  retire, family-agnostic gather/scatter via the models'
                  ``cache_axes``
  scheduler.py    priority classes, FIFO within a class, admission
                  control against the cache-memory budget
  paged_engine.py the engine: continuous batching over the paged cache,
                  chunked prefill (power-of-two chunks, O(log) compile
                  shapes) interleaved with per-slot-position decode
  sampling.py     counter-based sampling keyed on (seed, rid, step) —
                  bit-stable across runs and batch compositions
  traffic.py      synthetic-traffic harness: Poisson arrivals, mixed
                  length distributions, p50/p99 latency + goodput vs
                  offered load (drives ``benchmarks/serve_bench.py`` and
                  the committed ``BENCH_serve.json``)

``docs/serving.md`` walks the slot lifecycle, block-table layout, and
chunked-prefill schedule end-to-end.
"""
