"""A serving cell of a DeepSeek-V2-style model (latent attention, routed
experts of which the chip holds a share): ``serve.py``'s cell, run and
judge, with this kind's reference and three additions to the record the
per-layer readers see.

* The reference is ``reference/mla_moe.py``.  After the judgement the run
  logs how many routed pairs landed on the held experts, by the program's
  routes and by the reference's, over the same rows (the compared
  requests').  Routing near-ties that bf16 rounding flips part them.
* Each traced model call carries ``held_pairs`` and ``experts_touched``
  over its request rows, from the routes the model returns with its cache
  (``repro.models.moe.ROUTES``).  The registry's MoE counters
  (``serve.moe_held_assignments``, ``serve.moe_held_experts_touched``,
  ``serve.moe_expert_load_max``) join the window's counters.
* ``scopes``: device seconds of each traced model program by the name
  scope its ops were traced under (``moe/route``, ``moe/experts``,
  ``moe/shared``, ``attention``, ...).  The device trace names ops but
  not their scopes, so the ops are put in the program run that holds
  them, and each program is compiled again at the shapes it ran with
  (the persistent cache gives back the same executable) to read its
  HLO's ``op_name`` metadata.  Instruction names repeat across a
  program's shapes with other meanings, so each run is read with the map
  of the shape whose instructions its ops match (``best_map``).
"""
from __future__ import annotations

import bisect
import re
import time
from typing import Dict, List

import numpy as np

import serve
from harness import log
from reference import mla_moe as ref
from trace import op_name, program_name, reduce as plain_reduce

MODEL = ("decode_step", "prefill_chunk")
# innermost first: ``moe/shared`` holds ``dense.bf16`` matmuls
SCOPES = ("moe/route", "moe/experts", "moe/shared", "attention", "kv_write",
          "head", "dense.bf16")
MOE_COUNTERS = ("serve.moe_held_assignments",
                "serve.moe_held_experts_touched")
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%([^\s=]+) = (.*)$')
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_OPERAND = re.compile(r'%([\w.\-]+)')
# ops that hold other ops (a scan's loop): their events span their body's
CONTAINERS = ("while", "conditional", "call")


def scope_of(path: str) -> str:
    """The named scope an op's ``op_name`` path lies in, "other" if none."""
    parts = "/" + path + "/"
    for s in SCOPES:
        if "/" + s + "/" in parts:
            return s
    return "other"


def hlo_scopes(text: str) -> Dict[str, str]:
    """Instruction name -> scope, from a compiled module's HLO text.  An
    instruction whose ``op_name`` carries no scope path (a Mosaic kernel's
    custom call names itself, an async copy has none) takes the scope of
    its first user that has one."""
    out: Dict[str, str] = {}
    users: Dict[str, List[str]] = {}
    pathless = []
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        meta = _OP_NAME.search(rest)
        for operand in _OPERAND.findall(rest.split("metadata=", 1)[0]):
            users.setdefault(operand, []).append(name)
        if meta and "/" in meta.group(1):
            out[name] = scope_of(meta.group(1))
        else:
            pathless.append(name)
    for name in reversed(pathless):          # users come after operands
        out[name] = next((out[u] for u in users.get(name, ()) if u in out),
                         "other")
    return out


def reduce_by_program(events: Dict, t0: int, t1: int) -> Dict:
    """``trace.reduce``, and per device ``program_runs``: each run of a
    program in the window, in order, as ``[program, {op name: device
    seconds}]`` of the ops inside it."""
    out = plain_reduce(events, t0, t1)
    for dev, name in zip(out["devices"], sorted(events["devices"])):
        d = events["devices"][name]
        runs = sorted((s, s + dur, program_name(m)) for m, s, dur
                      in d["modules"] if s >= t0 and s + dur <= t1)
        starts = [r[0] for r in runs]
        per: List[list] = [[r[2], {}] for r in runs]
        for op, s, dur in d["ops"]:
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s >= runs[i][1]:
                continue
            ops = per[i][1]
            ops[op_name(op)] = ops.get(op_name(op), 0.0) + dur * 1e-9
        dev["program_runs"] = per
    return out


def best_map(ops, maps: List[Dict[str, str]]) -> Dict[str, str]:
    """Of the maps of one program's compiled shapes, the one a run with
    these ``ops`` ran: instruction names repeat across shapes with other
    meanings, so the map that holds most of the run's ops is taken, and of
    those, the one whose instructions the run covers best."""
    return max(maps, key=lambda m: (sum(op in m for op in ops),
                                    sum(op in m for op in ops) / len(m)))


def scope_seconds(reduced: Dict, maps: Dict[str, List[Dict[str, str]]]
                  ) -> Dict[str, Dict[str, float]]:
    """program -> scope -> device seconds, averaged over devices, for the
    programs in ``maps`` (per compiled shape, instruction name -> scope),
    each run named by the map of its shape (``best_map``).  A loop's own
    event is left out: the ops of its body are counted one by one."""
    devs = reduced["devices"]
    out: Dict[str, Dict[str, float]] = {}
    for d in devs:
        for prog, ops in d.get("program_runs", ()):
            if not maps.get(prog) or not ops:
                continue
            scopes = best_map(ops, maps[prog])
            acc = out.setdefault(prog, {})
            for op, secs in ops.items():
                if op.split(".")[0] in CONTAINERS:
                    continue
                s = scopes.get(op, "other")
                acc[s] = acc.get(s, 0.0) + secs / len(devs)
    return out


class RouteProbe(serve.Probe):
    """``serve.Probe`` that also keeps, for each recorded call, the routes
    the model returned and the call's shapes, and for each shape one
    abstract copy of the arguments (to compile the program again)."""

    def __init__(self):
        super().__init__()
        self.routes: List = []
        self.keys: List[tuple] = []
        self.signatures: Dict[tuple, tuple] = {}

    def watch(self, fn, name=None, limit=16):
        import jax
        from repro.models.moe import ROUTES

        inner = super().watch(fn, name, limit)

        def watched(*args):
            key = (name,) + tuple(a.shape for a in jax.tree.leaves(args[1:]))
            if key not in self.signatures:
                self.signatures[key] = jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
            out = inner(*args)
            if self.recording:
                self.routes.append(out[1].get(ROUTES))
                self.keys.append(key)
            return out

        watched._cache_size = inner._cache_size
        return watched


class MoeServeCell(serve.ServeCell):
    def __init__(self, cell, seed, seconds, shared=None):
        super().__init__(cell, seed, seconds, shared)
        from repro.obs import Observability

        self.probe = RouteProbe()
        if shared:
            self.probe.fns = shared.probe.fns
        self.obs = Observability(registry=self.registry, watchdog=self.probe)

    def serve(self, *args, **kw):
        self.probe.routes.clear()
        self.probe.keys.clear()
        return super().serve(*args, **kw)

    def _counters(self) -> Dict[str, float]:
        out = super()._counters()
        for n in MOE_COUNTERS:
            out[n] = self.registry.value(n)
        return out

    def record_calls(self):
        out = super().record_calls()
        self.routes = [None if r is None else np.asarray(r)
                       for r in self.probe.routes]
        return out

    def _call_work(self, traced: set) -> List[Dict]:
        out = super()._call_work(traced)
        for k, c in zip(sorted(traced), out):
            call, routes = self.recorded[k], self.routes[k]
            if routes is None:
                continue
            toks, pos = call.tokens, call.positions
            real = ~((pos == 0) & (toks == 0))              # (rows, S)
            r = routes[real]                                # (n, L, k)
            c["held_pairs"] = int((r >= 0).sum())
            c["experts_touched"] = int(sum(
                len(np.unique(r[:, layer][r[:, layer] >= 0]))
                for layer in range(r.shape[1])))
        return out

    def layer_record(self, reduced, peaks) -> Dict:
        rec = super().layer_record(reduced, peaks)
        rec["scopes"] = None
        if reduced is not None and self.trace_span is not None:
            rec["scopes"] = scope_seconds(reduced, self._scope_maps())
            log("device seconds by program and scope: " + "; ".join(
                f"{prog} " + ", ".join(f"{k} {v:.4f}" for k, v in sorted(
                    sc.items(), key=lambda x: -x[1]))
                for prog, sc in rec["scopes"].items()))
            rec["counters"]["serve.moe_expert_load_max"] = \
                self.registry.value("serve.moe_expert_load_max")
        return rec

    def _scope_maps(self) -> Dict[str, List[Dict[str, str]]]:
        """Instruction -> scope of each model program, one map for each
        shape it ran with in the traced window."""
        a, b = self.trace_span
        keys = {self.probe.keys[k]
                for k, (_, t) in enumerate(self.probe.call_times)
                if a <= t - self.t0 <= b and self.probe.keys[k][0] in MODEL}
        t0, maps = time.perf_counter(), {}
        for key in sorted(keys, key=str):
            compiled = self.probe.fns[key[0]].lower(
                *self.probe.signatures[key]).compile()
            maps.setdefault(key[0], []).append(hlo_scopes(compiled.as_text()))
        log(f"scopes: {len(keys)} traced program shapes read in "
            f"{time.perf_counter() - t0:.1f}s")
        return maps


def program_held_pairs(sc: MoeServeCell, plan) -> int:
    """Routed pairs on held experts, by the program's routes, over the
    planned rows (each request position in the call that computed it)."""
    rows: Dict[int, Dict[tuple, List[tuple]]] = {}
    total = 0
    for _, first, n in plan.requests:
        for j in range(n):
            c = int(plan.calls[first + j])
            if sc.routes[c] is None:
                continue
            if c not in rows:
                call, at = sc.recorded[c], {}
                for r in range(call.tokens.shape[0]):
                    for col in range(call.tokens.shape[1]):
                        at.setdefault((int(call.positions[r, col]),
                                       int(call.tokens[r, col])),
                                      []).append((r, col))
                rows[c] = at
            hit = rows[c].get((j, int(plan.tokens[first + j])))
            if hit:
                r, col = hit.pop(0)
                total += int((sc.routes[c][r, col] >= 0).sum())
    return total


def judge(sc, limits, control=False):
    """``serve.judge``, then the program's and the reference's counts of
    routed pairs on the held experts over the compared requests' rows."""
    verdicts, stats, plan = _serve_judge(sc, limits, control)
    mine = program_held_pairs(sc, plan)
    theirs = int(stats["held_pairs"].sum())
    log(f"held pairs over {len(plan.requests)} requests' rows: program "
        f"{mine}, reference {theirs} "
        f"({(mine - theirs) / max(theirs, 1) * 100:+.3f} %); window "
        f"counters {sc.counters}")
    return verdicts, stats, plan


_serve_judge = serve.judge


def run(cell, layout, seed: int, seconds: float, trace: bool,
        t_start: float, require_tpu: bool = True):
    """``serve.run`` with this kind's cell, reference, judgement and trace
    reduction."""
    swapped = {"ref": ref, "ServeCell": MoeServeCell, "judge": judge,
               "reduce": reduce_by_program}
    saved = {k: getattr(serve, k) for k in swapped}
    for k, v in swapped.items():
        setattr(serve, k, v)
    try:
        return serve.run(cell, layout, seed, seconds, trace, t_start,
                         require_tpu)
    finally:
        for k, v in saved.items():
            setattr(serve, k, v)
