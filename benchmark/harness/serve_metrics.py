"""Arithmetic on token stamps: gaps, percentiles, tokens in a window.

A request's record is ``{"due": s, "submit": s, "prompt_len": n,
"prefill_calls": n, "stamps": [s, ...]}``: the benchmark's own
``on_token`` callback appends ``time.perf_counter()`` for every token.
"""
from __future__ import annotations

import bisect
from typing import Any, Dict, List, Optional, Sequence, Tuple

ITERATION_MERGE_S = 0.002   # stamps of one engine iteration lie closer


def percentile(values: Sequence[float], q: float) -> float:
    """Linear interpolation between order statistics (numpy's default)."""
    if not values:
        raise ValueError("percentile of nothing")
    v = sorted(values)
    pos = (len(v) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def gaps_in_window(requests: Sequence[Dict[str, Any]], t0: float,
                   t1: float) -> List[Tuple[float, float]]:
    """``(gap seconds, end stamp)`` of every gap between consecutive
    tokens of one request that ends inside ``[t0, t1)``."""
    out = []
    for r in requests:
        s = r["stamps"]
        for a, b in zip(s, s[1:]):
            if t0 <= b < t1:
                out.append((b - a, b))
    return out


def iterations(requests: Sequence[Dict[str, Any]]) -> List[float]:
    """The engine's iteration stamps: all token stamps, those of one
    iteration (all slots emit together) merged to their first."""
    every = sorted(t for r in requests for t in r["stamps"])
    out: List[float] = []
    for t in every:
        if not out or t - out[-1] > ITERATION_MERGE_S:
            out.append(t)
    return out


def iteration_index(its: Sequence[float], t: float) -> int:
    """Index of the iteration that stamp ``t`` belongs to."""
    i = bisect.bisect_right(its, t + 1e-9) - 1
    return max(i, 0)


def prefill_interval(r: Dict[str, Any], its: Sequence[float]) \
        -> Optional[Tuple[float, float]]:
    """When this request's prompt was prefilled: the engine runs one
    prefill call per iteration for the request at the head of its queue,
    and the first token comes out of the decode step that follows the
    last call.  So the prompt's ``prefill_calls`` calls fill the
    iterations before the first token's; the interval never starts
    before the request was submitted."""
    if not r["stamps"]:
        return None
    j = iteration_index(its, r["stamps"][0])
    start = its[max(j - r["prefill_calls"], 0)]
    if j - r["prefill_calls"] < 0:
        start = min(start, r["submit"])
    start = max(start, r["submit"])
    end = r["stamps"][0]
    if start >= end:
        start = r["submit"]
    return (start, end)


def tokens_in_window(requests: Sequence[Dict[str, Any]], t0: float,
                     t1: float) -> Dict[str, float]:
    """Tokens generated inside ``[t0, t1)``, one per stamp, and prompt
    tokens prefilled inside it: a request's prompt is spread evenly over
    its prefill interval and the part inside the window counts."""
    its = iterations(requests)
    generated = 0
    prompt = 0.0
    for r in requests:
        generated += sum(1 for t in r["stamps"] if t0 <= t < t1)
        iv = prefill_interval(r, its)
        if iv is None:
            continue
        s, e = iv
        inside = min(e, t1) - max(s, t0)
        if inside > 0 and e > s:
            prompt += r["prompt_len"] * inside / (e - s)
    return {"generated": float(generated), "prompt": prompt}


def prefill_iterations(requests: Sequence[Dict[str, Any]],
                       its: Sequence[float]) -> List[bool]:
    """For each engine iteration, whether a prefill call ran in it."""
    flags = [False] * len(its)
    for r in requests:
        if not r["stamps"]:
            continue
        j = iteration_index(its, r["stamps"][0])
        for i in range(max(j - r["prefill_calls"] + 1, 0), j + 1):
            flags[i] = True
    return flags


def iteration_lengths(requests: Sequence[Dict[str, Any]], t0: float,
                      t1: float, with_prefill: bool) -> List[float]:
    """Lengths of the engine iterations that end inside ``[t0, t1)`` and
    did (or did not) run a prefill call; pauses of a second or more, when
    the engine had nothing to decode, are not iterations."""
    its = iterations(requests)
    flags = prefill_iterations(requests, its)
    return [b - a for a, b, f in zip(its, its[1:], flags[1:])
            if f == with_prefill and t0 <= b < t1 and b - a < 1.0]


def first_token_delays(requests: Sequence[Dict[str, Any]], t0: float,
                       t1: float) -> List[float]:
    """Due time to first token, of the requests whose first token fell
    inside ``[t0, t1)``."""
    return [r["stamps"][0] - r["due"] for r in requests
            if r["stamps"] and t0 <= r["stamps"][0] < t1]
