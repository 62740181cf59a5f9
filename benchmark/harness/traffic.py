"""One general generator of open-loop request traffic from a data file.

The file gives a rate, two clipped log-normal length distributions and
how long a run lasts; ``--seed`` gives the order and the arrival gaps.
Lengths are *stratified*: a run of N requests uses the N evenly spaced
quantiles of each distribution, so every seed offers the same multiset
of prompt and output lengths and only their pairing, their order and the
arrival times differ.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np


def stratified_lognormal(n: int, median: float, sigma: float, lo: int,
                         hi: int) -> np.ndarray:
    """The ``n`` mid-point quantiles of a log-normal, clipped to
    ``[lo, hi]`` and rounded to whole tokens."""
    nd = NormalDist()
    q = [(i + 0.5) / n for i in range(n)]
    vals = [median * math.exp(sigma * nd.inv_cdf(p)) for p in q]
    return np.clip(np.rint(vals), lo, hi).astype(np.int64)


def balanced_permutation(rng: np.random.Generator, n: int,
                         strata: int = 6) -> np.ndarray:
    """A seeded order of ``n`` sorted values in which every run of
    ``strata`` consecutive places holds one value from each of ``strata``
    equal quantile groups, in a random order: any stretch of a run then
    carries nearly the whole mix of lengths, and which stretch the window
    happens to cover matters little."""
    groups = [list(rng.permutation(np.arange(n * g // strata,
                                             n * (g + 1) // strata)))
              for g in range(strata)]
    out = []
    while any(groups):
        rnd = [g.pop() for g in groups if g]
        out.extend(rng.permutation(rnd))
    return np.asarray(out, dtype=np.int64)


def arrival_gaps(rng: np.random.Generator, n: int, rate: float,
                 spec: Dict[str, Any]) -> np.ndarray:
    """Gaps between arrivals at a mean rate of ``rate`` per second:
    exponential (Poisson arrivals), or gamma with a coefficient of
    variation ``cv`` (bursts for ``cv`` above 1)."""
    kind = spec.get("arrivals", "poisson")
    if kind == "poisson":
        return rng.exponential(1.0 / rate, n)
    if kind == "gamma":
        cv = float(spec["cv"])
        shape = 1.0 / (cv * cv)
        return rng.gamma(shape, 1.0 / (rate * shape), n)
    raise ValueError(f"unknown arrivals {kind!r}")


def generate(spec: Dict[str, Any], seed: int, horizon_s: float,
             vocab: int) -> List[Dict[str, Any]]:
    """Requests for ``horizon_s`` seconds of traffic: each has its due
    time (seconds from the start of traffic, all before the horizon),
    prompt tokens (1-based ids) and the number of new tokens.  The count
    is fixed by the rate and the horizon, not drawn, so every seed
    submits the same multiset of lengths in a run."""
    rate = float(spec["rate_rps"])
    n = max(int(round(rate * horizon_s)), 1)
    rng = np.random.default_rng([int(seed), 0x7261])
    p = spec["prompt_tokens"]
    o = spec["new_tokens"]
    prompts = stratified_lognormal(n, p["median"], p["sigma"], p["min"], p["max"])
    outs = stratified_lognormal(n, o["median"], o["sigma"], o["min"], o["max"])
    prompts = prompts[balanced_permutation(rng, n)]
    outs = outs[balanced_permutation(rng, n)]
    gaps = arrival_gaps(rng, n, rate, spec)
    # all n arrivals inside the horizon, for every seed: the first at 0,
    # the gaps scaled to the mean the rate asks for; only the pattern
    # differs from seed to seed
    due = np.cumsum(gaps) - gaps[0]
    if n > 1:
        due *= ((n - 1) / rate) / float(due[-1])
    reqs = []
    for i in range(n):
        toks = rng.integers(1, vocab + 1, int(prompts[i])).astype(np.int32)
        reqs.append({"id": i, "due": float(due[i]), "prompt": toks,
                     "new_tokens": int(outs[i])})
    return reqs
