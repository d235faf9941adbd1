"""One generator for every traffic mix: a mix is a data file of
``mixes/`` and a cell's load (rate or clients) a data file of ``cells/``.

Every draw is stratified: ``n`` lengths are the distribution's quantiles
at ``(i + 0.5) / n`` and ``n`` gaps the exponential's, and the seed only
permutes them. So every seed offers the same set of work in another
order, and the window always holds the same number of arrivals.

Mix keys:
  loop             "open" (arrivals on a schedule) or "closed" (clients
                   that each wait for their reply)
  prompt, output   {"dist": "lognormal", "median", "sigma", "min", "max"}
                   or {"dist": "uniform", "min", "max"}: token counts
  arrivals         open loop: {"kind": "poisson"}, or {"kind": "onoff",
                   "burst": b, "period_s": P}: the same arrivals at the
                   same mean rate, each period's sent in its first 1/b
  warmup_s         seconds of traffic before the window opens
  drain_s          open loop: how long after the window requests due in
                   it may take to reach their first token
  requests_per_client   closed loop: size of each client's list (cycled)
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np


@dataclass
class Req:
    """One request as the client sends it. ``due`` is seconds after the
    schedule's origin (negative: warm-up); closed-loop requests carry
    their ``client`` and have no due time of their own."""
    idx: int
    prompt: np.ndarray
    max_new: int
    due: float = 0.0
    client: int = -1
    in_window: bool = False


def quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified quantiles of a length distribution, as ints."""
    q = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in q])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        x = spec["min"] + q * (spec["max"] + 1 - spec["min"])
        x = np.floor(x)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _gaps(n: int, total: float) -> np.ndarray:
    """``n`` stratified exponential gaps, scaled to sum to ``total``."""
    q = (np.arange(n) + 0.5) / n
    g = -np.log1p(-q)
    return g * (total / g.sum())


def _warp(t: np.ndarray, arrivals: dict) -> np.ndarray:
    """Map Poisson times onto on/off bursts at the same mean rate: each
    period's arrivals are squeezed into its first ``1/burst``."""
    if arrivals.get("kind", "poisson") == "poisson":
        return t
    P, b = float(arrivals["period_s"]), float(arrivals["burst"])
    return np.floor(t / P) * P + np.mod(t, P) / b


def _segment(rng, mix, n, vocab, start, length, first_idx, in_window):
    prompts = rng.permutation(quantiles(mix["prompt"], n))
    outs = rng.permutation(quantiles(mix["output"], n))
    gaps = rng.permutation(_gaps(n, length))
    due = start + _warp(np.concatenate([[0.0], np.cumsum(gaps)[:-1]]),
                        mix.get("arrivals", {"kind": "poisson"}))
    return [Req(first_idx + i, rng.integers(0, vocab, int(p), np.int32),
                int(o), float(d), in_window=in_window)
            for i, (p, o, d) in enumerate(zip(prompts, outs, due))]


def open_loop(mix: dict, rate: float, window_s: float, vocab: int,
              seed: int) -> List[Req]:
    """Warm-up arrivals (due in ``[-warmup_s, 0)``), then the window's
    (due in ``[0, window_s)``), each a stratified set at ``rate``."""
    rng = np.random.default_rng(seed)
    warm = float(mix["warmup_s"])
    n_warm = max(1, round(rate * warm))
    n_win = max(1, round(rate * window_s))
    reqs = _segment(rng, mix, n_warm, vocab, -warm, warm, 0, False)
    reqs += _segment(rng, mix, n_win, vocab, 0.0, window_s, n_warm, True)
    return reqs


def closed_loop(mix: dict, clients: int, vocab: int,
                seed: int) -> List[List[Req]]:
    """Each client's list of requests, drawn from one stratified set of
    ``clients * requests_per_client``."""
    rng = np.random.default_rng(seed)
    n = clients * int(mix["requests_per_client"])
    prompts = rng.permutation(quantiles(mix["prompt"], n))
    outs = rng.permutation(quantiles(mix["output"], n))
    reqs = [Req(i, rng.integers(0, vocab, int(p), np.int32), int(o),
                client=i % clients)
            for i, (p, o) in enumerate(zip(prompts, outs))]
    return [reqs[c::clients] for c in range(clients)]


def primer(reqs: List[Req], max_len: int, vocab: int, seed: int) -> Req:
    """A request that books the page table's widest bucket that the
    traffic reaches and finishes fast: its prompt is as long as the
    longest request's context, and it asks for two tokens. Sent first
    and alone, it compiles the one step program the window runs."""
    longest = max(len(r.prompt) + r.max_new - 1 for r in reqs)
    n = max(1, min(longest, max_len - 1) - 1)
    rng = np.random.default_rng([seed, 1])
    return Req(-1, rng.integers(0, vocab, n, np.int32), 2)


def percentile(values, q: float) -> float:
    """Nearest-rank ``q``-quantile (0 < q <= 1) of ``values``."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of no values")
    return float(v[max(0, math.ceil(q * len(v)) - 1)])
