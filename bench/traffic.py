"""Seeded open-loop traffic from a mix file (``bench/traffic/<mix>.json``).

A mix of kind ``open_loop`` gives a fixed arrival rate, the
distributions of prompt and output lengths, and ``order_seed``.  The
sizes are the distribution's quantiles at ``(i + 1/2) / n``, the gaps
between arrivals the exponential distribution's (Poisson arrivals), and
their order is drawn from ``order_seed``: every seed gets the same
requests at the same times.  A window of a few tens of requests, most
still running at its close, delivers more or fewer tokens as long
requests come early or late, so an order drawn from the run's seed
would change the work from seed to seed.  The run's seed draws only the
prompt token ids (and, in the cell, the weights).

Requests due in ``[0, seconds)`` make the window.  A tail of further
arrivals at the same rate keeps the load on while the window's last
requests finish.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np


@dataclasses.dataclass
class Arrival:
    rid: int
    due: float                 # seconds after the window opens
    prompt: np.ndarray         # (P,) int32 token ids
    max_new: int
    in_window: bool


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: Dict, n: int) -> np.ndarray:
    """``n`` lengths at the distribution's mid-quantiles, clipped."""
    q = _quantiles(n)
    if dist["dist"] == "lognormal":
        z = np.asarray([NormalDist().inv_cdf(p) for p in q])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "fixed":
        x = np.full(n, float(dist["value"]))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, stream]))


def _block(mix: Dict, seed: int, stream: int, n: int, start: float,
           rid0: int, in_window: bool, vocab: int) -> List[Arrival]:
    order = _rng(int(mix["order_seed"]), stream)
    rng = _rng(seed, stream + 2)
    rate = float(mix["rate_per_s"])
    gaps = -np.log1p(-_quantiles(n)) / rate
    gaps = gaps[order.permutation(n)]
    prompts = lengths(mix["prompt"], n)[order.permutation(n)]
    outputs = lengths(mix["output"], n)[order.permutation(n)]
    lo = int(mix.get("token_id_min", 0))
    due = start + np.cumsum(gaps) - gaps[0]
    out = []
    for i in range(n):
        ids = rng.integers(lo, vocab, int(prompts[i])).astype(np.int32)
        out.append(Arrival(rid0 + i, float(due[i]), ids, int(outputs[i]),
                           in_window))
    return out


def schedule(mix: Dict, seed: int, seconds: float, vocab: int
             ) -> List[Arrival]:
    """The window's arrivals, then the tail's, in order of due time."""
    if mix["kind"] != "open_loop":
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")
    if "order_seed" not in mix:
        raise ValueError("an open_loop mix states its order_seed")
    rate = float(mix["rate_per_s"])
    n = max(1, int(math.floor(rate * seconds)))
    window = _block(mix, seed, 0, n, 0.0, 0, True, vocab)
    # the window's n gaps sum to n / rate less a little, so it ends
    # inside [0, seconds); the tail starts as the window closes
    n_tail = max(1, int(math.ceil(rate * float(mix["tail_s"]))))
    tail = _block(mix, seed, 1, n_tail, float(seconds), n, False, vocab)
    return window + tail


def prefill_chunks(prompt_len: int, max_chunk: int) -> List[tuple]:
    """(position, chunk) of each power-of-two piece a prompt is cut into,
    largest first, each at most ``max_chunk`` tokens."""
    out, pos = [], 0
    while pos < prompt_len:
        c = min(prompt_len - pos, max_chunk)
        c = 1 << (c.bit_length() - 1)
        out.append((pos, c))
        pos += c
    return out
