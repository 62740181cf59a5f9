#!/usr/bin/env python3
"""Find a traffic mix's knee on the chip: one process, one set-up,
rising rates, then a saturated segment.

    python3 benchmark/sweep.py --config opt-1.3b --traffic chat_poisson \
        --rates 0.3,0.45,0.6,0.75 --segment 40 [--control float8]

Not the driver's command; run once when a mix is defined, and write the
knee into the traffic file as a number.  For each rate it offers that
mix for ``--segment`` seconds without draining in between and prints the
backlog (requests admitted and not finished) and the queue's depth at
the segment's start and end, the requests and tokens completed, and the
token-gap percentiles.
The knee is the highest rate at which the backlog at the end is no
larger than at the start; the last, saturated segment gives the service
rate (requests finished per second with the queue never empty), which
bounds it from above.  With ``--control`` it ends by reading the
correctness numbers (the program's widest logit gap and the control's)
over a sample of everything that finished.
"""
import argparse
import copy
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH_DIR, os.path.dirname(BENCH_DIR)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--segment", type=float, default=40.0)
    ap.add_argument("--saturated", type=float, default=None,
                    help="rate of the last segment, far above any knee "
                         "(default: twice the highest of --rates)")
    ap.add_argument("--seed", type=int, default=2_718_281_828)
    ap.add_argument("--control", default="")
    ap.add_argument("--slots", type=int, default=0,
                    help="try another pool size than the configuration's")
    ap.add_argument("--check-seeds", type=int, default=1)
    args = ap.parse_args(argv)

    import gc

    import jax
    import numpy as np
    from harness import device, manifest, result, serve_cell, traffic
    from harness import serve_metrics as sm
    man = manifest.manifest()
    cfg = manifest.config_of(man, args.config)
    spec = manifest.traffic_of(args.traffic)
    if args.slots:
        cfg["serving"]["slots"] = args.slots
    device.require_tpu(1)
    device.enable_compile_cache()
    kind = manifest.load_kind(cfg["kind"])
    rates = [float(r) for r in args.rates.split(",")]
    rates.append(2.0 * max(rates) if args.saturated is None
                 else args.saturated)
    plans = []
    for i, rate in enumerate(rates):
        s = copy.deepcopy(spec)
        s["rate_rps"] = rate
        plans.append(traffic.generate(s, args.seed + i, args.segment,
                                      cfg["vocab_size"]))
    every = [r for p in plans for r in p]
    t = time.perf_counter()
    server, engine = kind.build_serve(cfg, args.seed, len(every) + 64)
    shapes = serve_cell.warm_up(server, kind, cfg, every, args.seed)
    result.say("sweep.setup", seconds=time.perf_counter() - t, shapes=shapes,
               cache_gib=engine.pool.cache_nbytes() / 2 ** 30)
    loads = []
    for rate, plan in zip(rates, plans):
        load = serve_cell.Load(server, plan, kind, cfg["serving"])
        b0 = engine.admitted_outstanding()
        q0 = engine.queue_depth()
        s0 = engine.stats()
        t0 = load.start()
        time.sleep(args.segment)
        load.stop()
        t1 = time.perf_counter()
        b1 = engine.admitted_outstanding()
        s1 = engine.stats()
        loads.append(load)
        recs = [r for ld in loads for r in ld.submitted()]
        gaps = [g for g, _ in sm.gaps_in_window(recs, t0, t1)]
        toks = sm.tokens_in_window(recs, t0, t1)
        result.say(
            "sweep.segment", rate_rps=rate, offered=len(plan),
            backlog_start=b0, backlog_end=b1, queue_start=q0,
            queue_end=engine.queue_depth(),
            finished=s1["requests_done"] - s0["requests_done"],
            finished_per_s=(s1["requests_done"] - s0["requests_done"]) / (t1 - t0),
            tokens_per_s=(toks["generated"] + toks["prompt"]) / (t1 - t0),
            generated_per_s=toks["generated"] / (t1 - t0),
            gaps=len(gaps),
            itl_p50_ms=1e3 * sm.percentile(gaps, 50) if gaps else None,
            itl_p90_ms=1e3 * sm.percentile(gaps, 90) if gaps else None,
            itl_p95_ms=1e3 * sm.percentile(gaps, 95) if gaps else None,
            occupancy=(s1["slot_occupancy_mean"] * s1["decode_steps"]
                       - s0["slot_occupancy_mean"] * s0["decode_steps"])
            / max(s1["decode_steps"] - s0["decode_steps"], 1),
            shed=s1["shed"] - s0["shed"])
    done = []
    for load, plan in zip(loads, plans):
        for fut, r in zip(load.futures, plan):
            if fut is not None and fut.done() and fut.exception() is None:
                row = np.asarray(fut.result())
                done.append((r["prompt"], row[len(r["prompt"]):]))
    peak = device.describe(list(jax.devices()[:1]))
    result.say("sweep.device", **peak)
    server.kill()
    server.shutdown(drain=False, timeout=60.0)
    del server, engine, loads
    gc.collect()
    if args.control:
        for i in range(args.check_seeds):
            # the weights are the run's; only the sample differs
            serve_cell.check(kind, cfg, spec, args.seed, done, args.control,
                             sample_seed=args.seed + 101 * i)
    return 0


if __name__ == "__main__":
    sys.exit(main())
