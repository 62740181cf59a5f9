#!/usr/bin/env python3
"""A model of the slot pool on the host's CPU: what part of a serving
cell's spread from seed to seed is the traffic's own.

Not the driver's command and no device metric: it prints model numbers,
never to be written under a metric's name.  It plays a mix's requests
through a pool of ``slots`` in which every engine pass prefills one chunk
of the oldest waiting prompt and decodes one token for every slot past its
prefill, and a pass costs ``--pass-ms`` + ``--place-us`` a live place +
``--chunk-ms`` where a chunk rides it.  With OPT-1.3B's numbers after PR 31
(5.2 ms, 0.63 us, 4.3 ms: `PERF.md` section 6) it read both OPT cells' p95,
occupancy and backlog to 2 % of the chip's, and the spread of their p95
over seeds with no noise in it: that spread is what the order of the
requests does, not the host.  Use it before a cell's traffic is changed:
to see what a new ``order``, ``arrivals`` or ``design`` leaves of the
spread, and to choose a ``design`` whose p95 lies near that of freely
drawn orders, before chip time is spent.

    python3 benchmark/pool_model.py --traffic chat_poisson --seeds 48 \
        [--set order=drawn] [--set design=3]
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import manifest, traffic  # noqa: E402


def play(reqs, preroll_s, seconds, slots, chunk, pass_s, place_s, chunk_s):
    """Token gaps that end in the window, the mean number of decoding
    slots, and the requests admitted or waiting at the close."""
    t, nxt, queue, pool, gaps, occ = 0.0, 0, [], [], [], []
    t_open, t_close = preroll_s, preroll_s + seconds
    while t < t_close:
        while nxt < len(reqs) and reqs[nxt]["due"] <= t:
            queue.append(reqs[nxt])
            nxt += 1
        while queue and len(pool) < slots:
            r = queue.pop(0)
            pool.append({"len": len(r["prompt"]), "left": len(r["prompt"]),
                         "new": r["new_tokens"], "made": 0})
        if not pool:
            if nxt == len(reqs):
                break
            t = reqs[nxt]["due"]
            continue
        prefill = next((s for s in pool if s["left"] > 0), None)
        decode = [s for s in pool if s["left"] == 0]
        dt = pass_s + place_s * sum(s["len"] + s["made"] for s in decode) \
            + (chunk_s if prefill is not None else 0.0)
        t += dt
        if t_open <= t < t_close:
            occ.append(len(decode))
            gaps.extend([dt] * len(decode))
        for s in decode:
            s["made"] += 1
        if prefill is not None:
            prefill["left"] -= min(chunk, prefill["left"])
            if prefill["left"] == 0:
                prefill["made"] = 1      # the prefill's own token: no gap
        pool = [s for s in pool if s["left"] > 0 or s["made"] < s["new"]]
    return gaps, float(np.mean(occ)), len(queue) + len(pool)


def spread(values) -> float:
    q = statistics.quantiles(values, n=4)
    return 100.0 * (q[2] - q[0]) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--config", default="opt-1.3b")
    ap.add_argument("--seeds", type=int, default=48)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--pass-ms", type=float, default=5.2)
    ap.add_argument("--place-us", type=float, default=0.63)
    ap.add_argument("--chunk-ms", type=float, default=4.3)
    ap.add_argument("--set", action="append", default=[],
                    help="key=value (JSON) over the traffic file, repeatable")
    args = ap.parse_args(argv)
    spec = manifest.traffic_of(args.traffic)
    for item in args.set:
        key, value = item.split("=", 1)
        try:
            spec[key] = json.loads(value)
        except ValueError:
            spec[key] = value
    serving = manifest.config_of(manifest.manifest(), args.config)["serving"]
    p95, occs, backlogs = [], [], []
    for k in range(args.seeds):
        reqs = traffic.generate(spec, 3_300_001_000 + k,
                                spec["preroll_s"] + args.seconds, 50272)
        gaps, occ, backlog = play(
            reqs, spec["preroll_s"], args.seconds, serving["slots"],
            serving["prefill_chunk"], 1e-3 * args.pass_ms,
            1e-6 * args.place_us, 1e-3 * args.chunk_ms)
        p95.append(1e3 * float(np.percentile(gaps, 95)))
        occs.append(occ)
        backlogs.append(backlog)
    sixes = [spread(p95[i:i + 6]) for i in range(0, len(p95) - 5, 6)]
    print(json.dumps({
        "model": "host CPU, not a device number", "traffic": args.traffic,
        "set": args.set, "seeds": args.seeds,
        "p95_ms_median": statistics.median(p95),
        "p95_spread_pct_all": spread(p95),
        "p95_spread_pct_sets_of_six": [round(s, 2) for s in sixes],
        "decoding_slots_mean": float(np.mean(occs)),
        "backlog_end_mean": float(np.mean(backlogs))}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
