"""Paged serving engine: chunked prefill interleaved with decode over a
block-pool KV cache, fed by a priority scheduler.

Engine loop (one ``step()``):

1. **retire** — finished slots return their blocks to the pool;
2. **admit** — the scheduler offers queued requests that fit the free
   slots/blocks (strict priority, FIFO within a class); each admitted
   request reserves its worst-case block count so it can always finish;
3. **prefill tick** — every prefilling slot advances by one chunk: the
   largest power of two ≤ min(tokens left, ``max_prefill_tokens``).  A
   long prompt therefore takes several steps and *interleaves* with other
   slots' decode instead of stalling the batch, and the power-of-two
   decomposition (13 → 8+4+1) pads nothing, so chunked prefill is
   bit-identical to one-shot prefill;
4. **decode tick** — all decoding slots advance one token in a single
   batched ``decode_step`` with per-row positions, padded to a constant
   batch of ``slots`` rows (padding rows gather the null block and their
   writes are never committed).

Every jitted call sees only bucketed shapes — chunk lengths are powers
of two capped by ``max_prefill_tokens``, dense-view lengths are
power-of-two block counts, the decode batch is constant — so the compile
count is O(log max_len), not one per prompt length.  ``stats`` records the distinct shapes so tests can pin that
bound.

Time is measured in engine steps (one ``step()`` = one unit), which
keeps the traffic harness's latency numbers deterministic and
platform-independent — see ``docs/serving.md`` for the metric
definitions.

Each request is stamped on ``time.perf_counter`` where its work
happens (``arrival_s`` at submit, ``admitted_s``, ``first_token_s``,
``finish_s``), beside the step counts, with or without ``obs``.

Observability: pass an ``repro.obs.Observability`` to get (1) per-request
lifecycle records (queue wait, TTFT, latency in steps — appended to
``engine.lifecycle`` at retire time and the raw material every
``BENCH_serve.json`` percentile is recomputed from), (2) spans that tile
each step (``Observability.span``, so on the profiler's clock): inside
``engine.step``, ``engine.retire`` (``engine.scrub`` per freed slot),
``engine.admit``, and per prefill chunk and decode tick
``engine.gather``, ``engine.prefill`` / ``engine.decode`` (the model
call), ``engine.commit`` and ``engine.sample``, (3) the host seconds of
each of those phases, ``serve.phase_s{phase}``, and the longest single
one, ``serve.phase_max_s{phase}``, block-pool occupancy and queue-depth
gauges, the pool's bytes on the device (``serve.cache_pool_bytes``, set
once), and admit/reject/defer counters on the obs registry, and (4) the
retrace watchdog wrapped around both jitted entry points so the O(log)
compile bound is asserted *while serving*, not just in tests.  Without
``obs`` the engine only keeps its cheap ``EngineStats``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.moe import ROUTES
from repro.obs import no_span
from repro.serve.paged_cache import PagedCache
from repro.serve.sampling import sample_row, sample_tokens
from repro.serve.scheduler import PriorityScheduler


@dataclasses.dataclass
class PagedRequest:
    rid: int
    prompt: np.ndarray                  # (P,) int32
    max_new_tokens: int = 16
    priority: int = 0                   # lower = more urgent
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # engine-step timestamps (filled in by the engine)
    arrival_step: int = 0
    admitted_step: Optional[int] = None
    first_token_step: Optional[int] = None
    finish_step: Optional[int] = None
    # the same events on time.perf_counter (filled in by the engine)
    arrival_s: Optional[float] = None
    admitted_s: Optional[float] = None
    first_token_s: Optional[float] = None
    finish_s: Optional[float] = None


@dataclasses.dataclass
class PagedEngineConfig:
    slots: int = 4                      # concurrent sequences
    block_size: int = 8                 # tokens per cache block (2^k)
    num_blocks: int = 64                # physical pool incl. null block
    max_prefill_tokens: int = 16        # per-slot chunk budget per step (2^k)
    eos_id: int = 1
    temperature: float = 0.0            # 0 = greedy
    seed: int = 0                       # sampling seed (counter-based)
    max_steps: int = 100_000            # drain-loop safety valve


@dataclasses.dataclass
class EngineStats:
    """Shape/tick accounting the retrace-bound tests and the obs
    registry both consume.  ``snapshot()`` is JSON-serializable (the
    shape sets become sorted lists) — the raw sets stay available for
    in-process asserts."""
    prefill_shapes: Set[Tuple] = dataclasses.field(default_factory=set)
    decode_shapes: Set[Tuple] = dataclasses.field(default_factory=set)
    steps: int = 0
    prefill_chunks: int = 0
    decode_ticks: int = 0
    admitted: int = 0
    rejected: int = 0
    deferred_steps: int = 0                 # steps with a free slot but a
                                            # head-of-line request that
                                            # didn't fit the free blocks

    def snapshot(self) -> Dict[str, Any]:
        return {
            "steps": self.steps,
            "prefill_chunks": self.prefill_chunks,
            "decode_ticks": self.decode_ticks,
            "admitted": self.admitted,
            "rejected": self.rejected,
            "deferred_steps": self.deferred_steps,
            "prefill_shapes": sorted([list(s) for s in self.prefill_shapes]),
            "decode_shapes": sorted([list(s) for s in self.decode_shapes]),
            "prefill_shape_count": len(self.prefill_shapes),
            "decode_shape_count": len(self.decode_shapes),
        }


def lifecycle_record(req: PagedRequest) -> Dict[str, Any]:
    """One finished request's lifecycle as a flat JSON-safe record —
    the unit ``--metrics-out`` emits and percentiles recompute from."""
    return {
        "kind": "request",
        "rid": req.rid,
        "priority": req.priority,
        "prompt_tokens": int(len(req.prompt)),
        "max_new_tokens": req.max_new_tokens,
        "output_tokens": len(req.out_tokens),
        "arrival_step": req.arrival_step,
        "admitted_step": req.admitted_step,
        "first_token_step": req.first_token_step,
        "finish_step": req.finish_step,
        "queue_wait_steps": req.admitted_step - req.arrival_step,
        "ttft_steps": req.first_token_step - req.arrival_step,
        "latency_steps": req.finish_step - req.arrival_step,
    }


@dataclasses.dataclass
class _Slot:
    req: PagedRequest
    pos: int = 0                        # tokens written to the cache so far
    next_token: Optional[int] = None    # sampled, not yet written

    @property
    def prefilling(self) -> bool:
        return self.pos < len(self.req.prompt)


class _Phase:
    """One phase of an engine step: the span ``engine.<phase>``, and with
    a registry its host seconds, added to ``serve.phase_s{phase}`` and
    raised into ``serve.phase_max_s{phase}``."""

    __slots__ = ("registry", "phase", "span", "t0")

    def __init__(self, registry, phase: str, span):
        self.registry, self.phase, self.span = registry, phase, span

    def __enter__(self):
        self.span.__enter__()
        self.t0 = time.perf_counter()

    def __exit__(self, *exc):
        if self.registry is not None:
            dt = time.perf_counter() - self.t0
            self.registry.counter("serve.phase_s", dt, phase=self.phase)
            self.registry.peak("serve.phase_max_s", dt, phase=self.phase)
        self.span.__exit__(*exc)


class PagedServeEngine:
    """model: needs prefill_chunk + decode_step (vector positions)."""

    def __init__(self, model, params, cfg: ModelConfig,
                 ecfg: PagedEngineConfig, obs=None):
        assert cfg.num_prefix_tokens == 0, \
            "paged engine: prefix tokens (vlm) unsupported"
        assert ecfg.max_prefill_tokens & (ecfg.max_prefill_tokens - 1) == 0
        self.model, self.params, self.cfg, self.ecfg = model, params, cfg, ecfg
        self.cache = PagedCache(model, slots=ecfg.slots,
                                num_blocks=ecfg.num_blocks,
                                block_size=ecfg.block_size)
        self.scheduler = PriorityScheduler(ecfg.num_blocks - 1,
                                           ecfg.block_size)
        self._decode = jax.jit(model.decode_step)
        self._prefill_chunk = jax.jit(model.prefill_chunk)
        self.obs = obs
        self._span = obs.span if obs is not None else no_span
        self._registry = obs.registry if obs is not None else None
        if self._registry is not None:
            self._registry.gauge("serve.cache_pool_bytes",
                                 self.cache.pool_bytes)
        if obs is not None and obs.watchdog is not None:
            limits = self.compile_shape_bounds()
            self._prefill_chunk = obs.watchdog.watch(
                self._prefill_chunk, "prefill_chunk",
                limit=limits["prefill_chunk"])
            self._decode = obs.watchdog.watch(
                self._decode, "decode_step", limit=limits["decode_step"])
        self._slots: List[Optional[_Slot]] = [None] * ecfg.slots
        self._routes: List[Tuple[Any, int]] = []    # (routes, live rows)
        self.step_count = 0
        self.results: Dict[int, List[int]] = {}
        self.lifecycle: List[Dict[str, Any]] = []
        self.stats = EngineStats()

    # -- introspection --------------------------------------------------

    @property
    def live(self) -> int:
        return sum(s is not None for s in self._slots)

    def compile_counts(self) -> Dict[str, int]:
        """Distinct compiled specializations per jitted entry point: the
        model's two and the cache's four."""
        out = {}
        for name, fn in (("prefill_chunk", self._prefill_chunk),
                         ("decode_step", self._decode),
                         *self.cache.programs().items()):
            size = getattr(fn, "_cache_size", None)
            out[name] = size() if callable(size) else -1
        return out

    def compile_shape_bounds(self) -> Dict[str, int]:
        """Analytic compile-count ceiling per jitted entry point — the
        O(log) guarantee in numbers: chunk sizes are the powers of two up
        to ``max_prefill_tokens``, view lengths are power-of-two block
        counts up to the pool, the decode batch is constant.  The cache
        gathers one row (prefill) or ``slots`` rows (decode) per view,
        commits a decode per view and a prefill per chunk x view, and
        scrubs per power-of-two bucket of a slot's block count.  The
        watchdog asserts the model's bounds live (a smoke harness may pin
        a tighter empirical bound via ``RetraceWatchdog(default_limit=…)``).
        """
        chunk_kinds = self.ecfg.max_prefill_tokens.bit_length()
        usable = self.ecfg.num_blocks - 1          # pool minus null block
        view_kinds = (1 << max(usable - 1, 1).bit_length()).bit_length()
        encdec = 2 if self.cfg.family == "encdec" else 1
        return {"prefill_chunk": chunk_kinds * view_kinds * encdec,
                "decode_step": view_kinds,
                "cache_gather": 2 * view_kinds,
                "cache_commit_decode": view_kinds,
                "cache_commit_prefill": chunk_kinds * view_kinds,
                "cache_scrub": view_kinds}

    # -- request intake -------------------------------------------------

    def submit(self, req: PagedRequest) -> None:
        req.arrival_step = self.step_count
        req.arrival_s = time.perf_counter()
        if not self.scheduler.submit(req):
            self.stats.rejected += 1
            if self._registry is not None:
                self._registry.counter("serve.rejected_requests")
            raise ValueError(
                f"request {req.rid}: prompt {len(req.prompt)} + max_new "
                f"{req.max_new_tokens} exceeds the cache pool "
                f"({self.ecfg.num_blocks - 1} blocks of "
                f"{self.ecfg.block_size})")
        if self._registry is not None:
            self._registry.counter("serve.submitted_requests")

    # -- engine loop ----------------------------------------------------

    def step(self) -> None:
        """One engine step: retire, admit, prefill one chunk per
        prefilling slot, decode one token for every decoding slot."""
        with self._span("engine.step", step=self.step_count):
            self._retire()
            self._admit()
            self._prefill_tick()
            self._decode_tick()
            self.step_count += 1
            self.stats.steps += 1
            if self._registry is not None:
                used = self.ecfg.num_blocks - 1 - self.cache.free_blocks
                self._registry.gauge("serve.blocks_in_use", used)
                self._registry.gauge("serve.queue_depth",
                                     self.scheduler.pending)
                self._registry.gauge("serve.live_slots", self.live)

    def _phase(self, phase: str, tid: int = 0, **args) -> "_Phase":
        return _Phase(self._registry, phase,
                      self._span("engine." + phase, tid=tid, **args))

    def run(self, requests: List[PagedRequest],
            seed: Optional[int] = None) -> Dict[int, List[int]]:
        """Serve ``requests`` to completion (batch mode: all arrive now)."""
        if seed is not None:
            self.ecfg.seed = seed
        for r in requests:
            self.submit(r)
        self.drain()
        return {r.rid: r.out_tokens for r in requests}

    def drain(self) -> None:
        start = self.step_count
        while self.scheduler.pending or any(self._slots):
            if self.step_count - start > self.ecfg.max_steps:
                raise RuntimeError("engine failed to drain (livelock?)")
            self.step()
        self._retire()                   # collect the last finishers

    # -- phases ---------------------------------------------------------

    def _retire(self) -> None:
        with self._phase("retire"):
            for i, s in enumerate(self._slots):
                if s is not None and s.req.done:
                    self.results[s.req.rid] = s.req.out_tokens
                    self.lifecycle.append(lifecycle_record(s.req))
                    if self._registry is not None:
                        self._registry.counter("serve.completed_requests")
                        self._registry.counter("serve.output_tokens",
                                               len(s.req.out_tokens))
                    with self._span("engine.scrub", slot=i):
                        self.cache.free_slot(i)
                    self._slots[i] = None

    def _admit(self) -> None:
        with self._phase("admit"):
            free = [i for i, s in enumerate(self._slots) if s is None]
            admitted = self.scheduler.admit(len(free), self.cache.free_blocks)
            now = time.perf_counter()
            for req in admitted:
                i = free.pop(0)
                self.cache.alloc_slot(i, self.scheduler.reservation(req))
                req.admitted_step = self.step_count
                req.admitted_s = now
                self._slots[i] = _Slot(req)
            self.stats.admitted += len(admitted)
            if self._registry is not None and admitted:
                self._registry.counter("serve.admitted_requests",
                                       len(admitted))
            # a leftover free slot with a queue behind it means the head-of-
            # line request didn't fit the free blocks: a deferral step
            if free and self.scheduler.pending:
                self.stats.deferred_steps += 1
                if self._registry is not None:
                    self._registry.counter("serve.deferred_steps")

    def _prefill_tick(self) -> None:
        for i, s in enumerate(self._slots):
            if s is None or not s.prefilling:
                continue
            with self._phase("gather"):
                remaining = len(s.req.prompt) - s.pos
                chunk = min(remaining, self.ecfg.max_prefill_tokens)
                chunk = 1 << (chunk.bit_length() - 1)  # largest 2^k <= chunk
                view_tokens = self.cache.view_len(s.pos + chunk)
                view = self.cache.gather([i], view_tokens)
            with self._phase("prefill", tid=1 + i, rid=s.req.rid,
                             chunk=chunk, view=view_tokens, pos=s.pos):
                batch = {"tokens": jnp.asarray(
                    s.req.prompt[s.pos:s.pos + chunk][None].astype(np.int32))}
                if self.cfg.family == "encdec" and s.pos == 0:
                    batch["frames"] = jnp.zeros(
                        (1, self.cfg.encoder_frames, self.cfg.d_model),
                        jnp.bfloat16)
                logits, view = self._prefill_chunk(self.params, batch, view,
                                                   jnp.int32(s.pos))
                self._keep_routes(view, 1)
            with self._phase("commit"):
                self.cache.commit_prefill(view, i, s.pos, chunk)
            self.stats.prefill_shapes.add(
                (chunk, view_tokens, "frames" in batch))
            self.stats.prefill_chunks += 1
            if self._registry is not None:
                self._registry.counter("serve.prefill_tokens", chunk)
            s.pos += chunk
            if not s.prefilling:          # prompt complete: first token
                with self._phase("sample"):
                    tok = sample_row(logits[0], seed=self.ecfg.seed,
                                     rid=s.req.rid, step=0,
                                     temperature=self.ecfg.temperature)
                    self._accept(s, tok)
                    self._count_routes()

    def _decode_tick(self) -> None:
        live = [(i, s) for i, s in enumerate(self._slots)
                if s is not None and not s.prefilling and not s.req.done]
        if not live:
            return
        n = self.ecfg.slots
        with self._phase("gather"):
            slot_ids = np.zeros(n, np.int32)  # padding rows gather slot 0
            tokens = np.zeros(n, np.int32)
            positions = np.zeros(n, np.int32)
            rows = []
            for r, (i, s) in enumerate(live):
                slot_ids[r], tokens[r], positions[r] = i, s.next_token, s.pos
                rows.append((s.req.rid, len(s.req.out_tokens)))
            view_tokens = self.cache.view_len(int(positions.max()) + 1)
            view = self.cache.gather(slot_ids.tolist(), view_tokens)
        with self._phase("decode", rows=len(live), view=view_tokens):
            logits, view = self._decode(self.params,
                                        jnp.asarray(tokens)[:, None], view,
                                        jnp.asarray(positions))
            self._keep_routes(view, len(live))
        with self._phase("commit"):
            self.cache.commit_decode(view, list(range(len(live))),
                                     [i for i, _ in live],
                                     [s.pos for _, s in live])
        self.stats.decode_shapes.add((n, view_tokens))
        self.stats.decode_ticks += 1
        if self._registry is not None:
            self._registry.counter("serve.decode_tokens", len(live))
        with self._phase("sample"):
            rows += [None] * (n - len(rows))
            sampled = sample_tokens(logits, rows, seed=self.ecfg.seed,
                                    temperature=self.ecfg.temperature)
            for r, (i, s) in enumerate(live):
                s.pos += 1                 # the input token is now cached
                self._accept(s, int(sampled[r]))
            self._count_routes()

    def _keep_routes(self, view, rows: int) -> None:
        """Take a model's MoE routes (an expert share's, ``moe.ROUTES``)
        out of the view it returned, to be counted once the step has read
        its tokens back: they are then ready, so counting adds no device
        sync."""
        routes = view.pop(ROUTES, None)
        if routes is not None and self._registry is not None:
            self._routes.append((routes, rows))

    def _count_routes(self) -> None:
        """Held-expert counters over the live rows of the calls kept:
        ``serve.moe_held_assignments`` (routed pairs on a held expert),
        ``serve.moe_held_experts_touched`` (held experts with a token,
        per layer and call) and the peak ``serve.moe_expert_load_max``
        (most pairs on one held expert in one layer of one call)."""
        held = self.cfg.experts_held
        for routes, rows in self._routes:
            r = np.asarray(routes)[:rows]              # (rows, S, L, k)
            layers = r.shape[2]
            r = np.moveaxis(r, 2, 0).reshape(layers, -1)
            at = (r + held * np.arange(layers)[:, None])[r >= 0]
            load = np.bincount(at, minlength=layers * held)
            self._registry.counter("serve.moe_held_assignments",
                                   int(load.sum()))
            self._registry.counter("serve.moe_held_experts_touched",
                                   int((load > 0).sum()))
            self._registry.peak("serve.moe_expert_load_max", int(load.max()))
        self._routes.clear()

    def _accept(self, s: _Slot, tok: int) -> None:
        req = s.req
        if req.first_token_step is None:
            req.first_token_step = self.step_count
            req.first_token_s = time.perf_counter()
        req.out_tokens.append(tok)
        s.next_token = tok
        if tok == self.ecfg.eos_id or len(req.out_tokens) >= req.max_new_tokens:
            req.done = True
            req.finish_step = self.step_count
            req.finish_s = time.perf_counter()
