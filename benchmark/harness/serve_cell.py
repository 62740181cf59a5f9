"""A serving cell: open-loop traffic into ``ModelServer`` over a
``GenerationScheduler``, a pre-roll, then the measured window.

The benchmark stamps every token itself (``on_token``); from the
scheduler it takes only counters, read as differences across the
window.  After the window the engine is stopped and freed, and a sample
of the finished requests is compared with the float32 reference.
"""
from __future__ import annotations

import gc
import importlib
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from harness import device as dev
from harness import manifest, result, serve_metrics as sm, traffic
from harness import trace as tr

TRACE_SECONDS = 4.0


class Load:
    """Submits the generated requests at their due times from one thread
    and keeps each request's record."""

    def __init__(self, server, reqs: List[Dict[str, Any]], kind, serving):
        self.server, self.reqs = server, reqs
        self.records: List[Dict[str, Any]] = []
        self.futures: List[Any] = []
        self.rejected = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.t0: Optional[float] = None
        for r in reqs:
            self.records.append({
                "id": r["id"], "due": None, "submit": None,
                "prompt_len": len(r["prompt"]), "new_tokens": r["new_tokens"],
                "prefill_calls": max(len(kind.prefill_plan(
                    len(r["prompt"]), serving)), 1),
                "stamps": []})

    def start(self) -> float:
        self.t0 = time.perf_counter()
        self._thread = threading.Thread(target=self._run, name="bench-load",
                                        daemon=True)
        self._thread.start()
        return self.t0

    def _run(self) -> None:
        import jax
        for r, rec in zip(self.reqs, self.records):
            due = self.t0 + r["due"]
            delay = due - time.perf_counter()
            if delay > 0 and self._stop.wait(delay):
                return
            if self._stop.is_set():
                return
            rec["due"] = due
            stamps = rec["stamps"]
            with jax.profiler.TraceAnnotation("bench.submit"):
                try:
                    fut = self.server.submit_generate_async(
                        r["prompt"], r["new_tokens"], timeout=0.0,
                        on_token=lambda _tok, s=stamps: s.append(
                            time.perf_counter()))
                except Exception as e:  # noqa: BLE001 - a refusal is a failure
                    self.rejected += 1
                    rec["error"] = repr(e)
                    fut = None
            rec["submit"] = time.perf_counter()
            self.futures.append(fut)

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(30.0)
            if self._thread.is_alive():
                raise RuntimeError("the load thread did not stop")

    def submitted(self) -> List[Dict[str, Any]]:
        return [r for r in self.records if r["submit"] is not None]


def warm_up(server, kind, cfg, reqs: List[Dict[str, Any]], seed: int) -> int:
    """One short request for every prefill program the run's prompts
    need (and with them the decode, scatter and seed programs)."""
    serving = cfg["serving"]
    shapes = sorted({s for r in reqs
                     for s in kind.prefill_plan(len(r["prompt"]), serving)})
    rng = np.random.default_rng([int(seed), 0x7761])
    futs = []
    for shape in shapes:
        n = kind.warmup_prompt_len(shape, serving)
        toks = rng.integers(1, cfg["vocab_size"] + 1, n).astype(np.int32)
        futs.append(server.submit_generate_async(toks, 2))
    for f in futs:
        f.result(timeout=1100)
    return len(shapes)


def compiles(engine) -> int:
    total = 0
    for v in engine.pool.trace_counts.values():
        total += v if isinstance(v, int) else sum(v.values())
    return total


def run(man, cell, cfg, spec, args, t_proc0: float, devices,
        rehearsal: bool = False) -> None:
    import jax
    kind = manifest.load_kind(cfg["kind"])
    reported = manifest.end_to_end_names(man, cell["name"])
    serving = cfg["serving"]
    t_imports = time.perf_counter() - t_proc0
    preroll_s = float(spec["preroll_s"])
    horizon = preroll_s + args.seconds
    reqs = traffic.generate(spec, args.seed, horizon, cfg["vocab_size"])

    t = time.perf_counter()
    server, engine = kind.build_serve(cfg, args.seed, len(reqs) + 64)
    weights_s = time.perf_counter() - t
    t = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.warm_up"):
        n_shapes = warm_up(server, kind, cfg, reqs, args.seed)
    warmup_s = time.perf_counter() - t
    compiles0 = compiles(engine)

    load = Load(server, reqs, kind, serving)
    traffic_t0 = load.start()
    t_open = traffic_t0 + preroll_s
    t_close = t_open + args.seconds
    time.sleep(max(t_open - time.perf_counter(), 0.0))
    stats0 = engine.stats()
    setup_s = time.perf_counter() - t_proc0
    trace_dir, traced = None, None
    if args.trace:
        import os
        import shutil
        trace_dir = os.path.join(manifest.ROOT, ".bench_trace", cell["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
        with jax.profiler.TraceAnnotation("bench.wait_window"):
            time.sleep(min(TRACE_SECONDS, args.seconds))
        jax.profiler.stop_trace()
        traced = (t_open, time.perf_counter())
    with jax.profiler.TraceAnnotation("bench.wait_window"):
        time.sleep(max(t_close - time.perf_counter(), 0.0))
    stats1 = engine.stats()
    compiles1 = compiles(engine)
    backlog_end = engine.admitted_outstanding()
    queue_end = engine.queue_depth()
    cache_bytes = engine.pool.cache_nbytes()
    load.stop()
    device = dev.describe(devices)

    # what finished, and what failed, before the engine is stopped
    done, failed = [], load.rejected + int(stats1["shed"] - stats0["shed"])
    for fut, r in zip(load.futures, reqs):
        if fut is None or not fut.done():
            continue
        if fut.exception() is not None:
            failed += 1
            continue
        row = np.asarray(fut.result())
        done.append((r["prompt"], row[len(r["prompt"]):]))
    # stop the engine without draining, then free it for the reference
    server.kill()
    server.shutdown(drain=False, timeout=60.0)
    del server, engine, load.futures
    gc.collect()

    recs = load.submitted()
    gaps = sm.gaps_in_window(recs, t_open, t_close)
    toks = sm.tokens_in_window(recs, t_open, t_close)
    result.say("setup", imports_s=t_imports, weights_s=weights_s,
               warmup_s=warmup_s, warmup_shapes=n_shapes, preroll_s=preroll_s,
               setup_s=setup_s)
    result.say("window", gaps=len(gaps), beyond_p95=int(len(gaps) * 0.05),
               submitted=len(recs), finished=len(done),
               generated=toks["generated"], prompt_tokens=toks["prompt"],
               backlog_end=backlog_end, queue_end=queue_end)
    if len(gaps) < 200:
        raise RuntimeError(f"{len(gaps)} gaps in the window: a 95th "
                           f"percentile needs ten beyond it")

    t = time.perf_counter()
    ok = check(kind, cfg, spec, args.seed, done, args.control)
    reference_s = time.perf_counter() - t
    result.say("reference", seconds=reference_s)

    metrics: Dict[str, Any] = {}
    if not args.trace:
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        metrics["serve_itl_p95_ms"] = {
            "value": 1e3 * sm.percentile([g for g, _ in gaps], 95), "unit": "ms"}
        if "serve_tokens_per_s" in reported:
            metrics["serve_tokens_per_s"] = {
                "value": (toks["generated"] + toks["prompt"]) / args.seconds,
                "unit": "tokens/s"}
        if rehearsal:
            metrics = {k: (v if k == "setup_s" else "not measured")
                       for k, v in metrics.items()}
        result.final_line(ok, len(recs), failed, metrics, device)
        return
    obs: Dict[str, Any] = {
        "kind": cfg["kind"], "cfg": cfg, "traffic": spec, "requests": recs,
        "t_open": t_open, "t_close": t_close, "gaps": gaps, "tokens": toks,
        "stats0": stats0, "stats1": stats1, "backlog_end": backlog_end,
        "cache_bytes": cache_bytes, "compiles_in_window": compiles1 - compiles0,
        "shed": failed, "seconds": args.seconds, "traced": traced,
        "peaks": None if rehearsal else dev.peaks(devices[0].device_kind)}
    breakdown = None
    if trace_dir is not None:
        t_obj = tr.Trace(tr.find_xplane(trace_dir), window="bench.wait_window")
        obs["trace"] = t_obj
        if not rehearsal:
            device["busy_s"] = t_obj.busy_s()
            device["window_s"] = t_obj.window_s
            breakdown = {"device_ops": tr.top(t_obj.category_seconds()),
                         "idle_gaps": tr.top(t_obj.idle_gaps())}
    metrics = result.read_layers(man, cell["name"], reported, obs,
                                 device_metrics=not rehearsal)
    result.final_line(ok, len(recs), failed, metrics, device, breakdown)


def check(kind, cfg, spec, seed: int, done, control: str = "",
          sample_seed=None) -> bool:
    """Compare a seeded sample of the finished requests, the longest
    among them, with the reference: the widest gap by which a served
    token's reference logit lies below the reference's best."""
    import jax
    import jax.numpy as jnp
    from harness import weights
    if not done:
        result.say("correct", number="finished_requests", value=0, limit=1,
                   ok=False)
        return False
    ref = importlib.import_module("reference." + kind.REFERENCE)
    rng = np.random.default_rng(
        [int(seed if sample_seed is None else sample_seed), 0x6368])
    order = sorted(range(len(done)),
                   key=lambda i: -(len(done[i][0]) + len(done[i][1])))
    pick = [order[0]]
    rest = [i for i in order[1:]]
    rng.shuffle(rest)
    pick += rest[:max(int(spec["check_requests"]) - 1, 0)]
    param_spec = kind.param_spec(cfg)
    served_dtype = jnp.dtype(cfg["serving"]["weights_dtype"])
    leaves = weights.make(param_spec, seed, served_dtype)
    params = {p: l.astype(jnp.float32) for (p, _), l in zip(param_spec, leaves)}
    del leaves
    worst, cworst, n_tok = 0.0, None, 0
    served_pad = -(-int(spec["new_tokens"]["max"]) // 128) * 128
    with jax.default_matmul_precision("highest"):
        for i in pick:
            prompt, served = done[i]
            out = ref.served_gaps(params, cfg, prompt, served,
                                  cfg["serving"]["max_len"], served_pad, control)
            worst = max(worst, out["gap_max"])
            n_tok += out["positions"]
            if control:
                cworst = (out["control_gap_max"] if cworst is None
                          else min(cworst, out["control_gap_max"]))
                result.say("control", request=i, **out)
    del params
    gc.collect()
    result.say("sample", requests=len(pick), served_tokens=n_tok,
               control_gap_min_of_max=cworst)
    return result.compare("served_logit_gap_max", worst,
                          cfg["correct"]["serve"]["logit_gap_max"])
