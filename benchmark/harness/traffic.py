"""One general generator of open-loop request traffic from a data file.

The file gives a rate, two clipped log-normal length distributions and
how long a run lasts; ``--seed`` gives the order and the arrival gaps
(or, with ``"arrivals": "poisson_stratified"``, only their order).
Lengths are *stratified*: a run of N requests uses the N evenly spaced
quantiles of each distribution, so every seed offers the same multiset
of prompt and output lengths and only their pairing, their order and the
arrival times differ.  With ``"order": "rows"`` the pairs, the gaps and
the order inside a row of 36 requests are the same for every seed too,
and the seed orders the rows.
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


def paired_rows(n: int, design: int, strata: int = 6) -> List[np.ndarray]:
    """The fixed design of ``"order": "rows"``: rows of ``strata * strata``
    pairs of places in the sorted prompt and answer lengths, ``[k, 2]``
    each, the same for every seed.

    Both sorted lists are cut into ``strata`` quantile groups.  Block ``b``
    takes one prompt from each group and pairs group ``g`` with the
    answers' group ``g + b`` (mod the groups that still have a member), so
    a block of ``strata`` requests holds every group of both lengths once
    and a row of ``strata`` blocks holds every combination of groups once:
    the rows are alike in their work, and a run is the rows in the seed's
    order.  Which member of a group a block takes, the order of a block's
    requests and of a row's blocks are drawn once, from ``design``."""
    fixed = np.random.default_rng([int(design), 0x726f7773])
    groups = [np.arange(n * g // strata, n * (g + 1) // strata)
              for g in range(strata)]
    members = [(fixed.permutation(g), fixed.permutation(g)) for g in groups]
    blocks = []
    for b in range(max(len(g) for g in groups)):
        live = [g for g in range(strata) if len(groups[g]) > b]
        block = [(members[g][0][b], members[live[(i + b) % len(live)]][1][b])
                 for i, g in enumerate(live)]
        blocks.append([block[i] for i in fixed.permutation(len(block))])
    rows = []
    for r in range(0, len(blocks), strata):
        row = fixed.permutation(np.arange(r, min(r + strata, len(blocks))))
        rows.append(np.asarray([pair for b in row for pair in blocks[b]],
                               dtype=np.int64))
    return rows


def arrival_gaps(rng: np.random.Generator, n: int, rate: float,
                 spec: Dict[str, Any]) -> np.ndarray:
    """Gaps between arrivals at a mean rate of ``rate`` per second:
    exponential (Poisson arrivals), or gamma with a coefficient of
    variation ``cv`` (bursts for ``cv`` above 1).  ``poisson_stratified``
    gives every seed the same gaps, the ``n`` mid-point quantiles of the
    exponential, in a ``balanced_permutation`` order: as many long and
    short gaps in every run and in any stretch of one, so a seed moves
    when the pool is full or empty and not how often."""
    kind = spec.get("arrivals", "poisson")
    if kind == "poisson":
        return rng.exponential(1.0 / rate, n)
    if kind == "poisson_stratified":
        q = (np.arange(n) + 0.5) / n
        order = balanced_permutation(rng, n)
        # ``generate`` puts the first arrival at 0 and so leaves the first
        # gap out: the shortest goes there, and every seed keeps the rest
        first = int(np.flatnonzero(order == 0)[0])
        order[0], order[first] = order[first], order[0]
        return (-np.log1p(-q) / rate)[order]
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
    order = spec.get("order", "drawn")
    if order == "drawn":
        prompts = prompts[balanced_permutation(rng, n)]
        outs = outs[balanced_permutation(rng, n)]
        gaps = arrival_gaps(rng, n, rate, spec)
    elif order == "rows":
        # sizes, pairs and gaps are a fixed design in rows of 36 requests
        # (some 10 s of chat), drawn from the file's ``design``; the seed
        # orders the rows and draws the tokens.  A gap goes with the
        # request it precedes; a row opens with its shortest, which is the
        # one the run's first arrival leaves out
        design = int(spec["design"])
        rows = paired_rows(n, design)
        gaps = arrival_gaps(np.random.default_rng([design, 0x67617073]), n,
                            rate, spec)
        at = np.cumsum([0] + [len(row) for row in rows])
        for lo, hi in zip(at[:-1], at[1:]):
            least = lo + int(np.argmin(gaps[lo:hi]))
            gaps[lo], gaps[least] = gaps[least], gaps[lo]
        seeded = rng.permutation(len(rows))
        pairs = np.concatenate([rows[r] for r in seeded])
        gaps = np.concatenate([gaps[at[r]:at[r + 1]] for r in seeded])
        prompts, outs = prompts[pairs[:, 0]], outs[pairs[:, 1]]
    else:
        raise ValueError(f"unknown order {order!r}")
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
