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
STALL_S = 0.05     # an engine pass takes 0.01-0.02 s: one this long stood still or idled


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


class FullCollections:
    """Counts the collector's full (oldest generation) passes and their
    seconds from now on: the whole process stands still for each."""

    def __init__(self):
        self.passes: List[List[float]] = []     # [start, stop]
        gc.callbacks.append(self._note)

    def _note(self, phase: str, info: Dict[str, Any]) -> None:
        if info["generation"] != 2:
            return
        if phase == "start":
            self.passes.append([time.perf_counter(), float("nan")])
        elif self.passes:
            self.passes[-1][1] = time.perf_counter()

    def within(self, t0: float, t1: float) -> Dict[str, Any]:
        inside = [b - a for a, b in self.passes if t0 <= a < t1]
        return {"full_collections": len(inside), "full_collection_s": sum(inside)}

    def close(self) -> None:
        gc.callbacks.remove(self._note)


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
    full_gc = FullCollections()
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
        tr.start(trace_dir)
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
    # a prompt that was being prefilled at the close has no stamp yet, and
    # without one its part of the window cannot be placed: the engine runs
    # on for the mix's ``close_grace_s`` so that its first token comes
    time.sleep(float(spec.get("close_grace_s", 0.0)))
    # stop the engine without draining, then free it for the reference
    server.kill()
    server.shutdown(drain=False, timeout=60.0)
    full_gc.close()
    del server, engine, load.futures
    gc.collect()

    recs = load.submitted()
    gaps = sm.gaps_in_window(recs, t_open, t_close)
    toks = sm.tokens_in_window(recs, t_open, t_close)
    its = sm.iterations(recs)
    stalls = [b - a for a, b in zip(its, its[1:])
              if t_open <= b < t_close and STALL_S <= b - a < 1.0]
    result.say("setup", imports_s=t_imports, weights_s=weights_s,
               warmup_s=warmup_s, warmup_shapes=n_shapes, preroll_s=preroll_s,
               setup_s=setup_s)
    result.say("window", gaps=len(gaps), beyond_p95=int(len(gaps) * 0.05),
               submitted=len(recs), finished=len(done),
               generated=toks["generated"], prompt_tokens=toks["prompt"],
               backlog_end=backlog_end, queue_end=queue_end,
               passes_over_50ms=len(stalls), passes_over_50ms_s=sum(stalls),
               **full_gc.within(t_open, t_close))
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


def logit_gaps(ref_logits, columns, low_logits=None) -> Dict[str, float]:
    """For one request's served tokens (``columns`` of the logits, one per
    row), the widest gap by which a served token's reference logit lies
    below the reference's best at its position.  With ``low_logits`` (the
    control's, a lower precision's) also the gap of the token that
    precision puts first at each position."""
    import jax.numpy as jnp
    best = jnp.max(ref_logits, axis=-1)

    def below_best(cols):
        return best - jnp.take_along_axis(ref_logits, cols[:, None], axis=1)[:, 0]
    gap = below_best(jnp.asarray(columns))
    out = {"gap_max": float(jnp.max(gap)), "positions": len(columns)}
    if low_logits is not None:
        cgap = below_best(jnp.argmax(low_logits, axis=-1))
        out.update(control_gap_max=float(jnp.max(cgap)),
                   control_gap_median=float(jnp.median(cgap)),
                   gap_median=float(jnp.median(gap)))
    return out


def check(kind, cfg, spec, seed: int, done, control: str = "",
          sample_seed=None) -> bool:
    """Compare a seeded sample of the finished requests, the longest
    among them, with the reference: the widest gap by which a served
    token's reference logit lies below the reference's best.

    The reference runs once over each prompt followed by its served
    tokens (padded to ``max_len``; under a causal mask padding changes
    nothing before it), a block of the model at a time: the blocks are
    the outer loop and the sampled requests the inner one, so the device
    holds one block in float32 and every request's hidden states, never
    the model.  With ``control`` (a lower precision) a second stream goes
    through the same blocks in that precision."""
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

    # each request's tokens, and the rows that predicted a served token:
    # ``rows`` consecutive positions from ``start``, the served ones from
    # ``off`` among them
    max_len = cfg["serving"]["max_len"]
    served_pad = -(-int(spec["new_tokens"]["max"]) // 128) * 128
    seqs, spans = [], []
    for i in pick:
        prompt, served = done[i]
        n_p, n_s = len(prompt), len(served)
        seq = np.full((1, max_len), ref.TOKEN_BASE, np.int32)
        seq[0, :n_p] = prompt
        seq[0, n_p:n_p + n_s - 1] = served[:-1]
        rows = min(max(served_pad, n_s), max_len)
        start = min(n_p - 1, max_len - rows)
        seqs.append(seq)
        spans.append((start, rows, (n_p - 1) - start, n_s))

    param_spec = kind.param_spec(cfg)
    blocks = kind.param_blocks(cfg)
    precisions = ["float32"] + ([control] if control else [])
    states: Dict[str, List[Any]] = {}     # one [1, max_len, H] per request
    logits: Dict[str, List[Any]] = {}
    block_bytes = 0
    with jax.default_matmul_precision("highest"):
        for b, (_name, params) in enumerate(weights.blocks_float32(
                param_spec, blocks, seed,
                jnp.dtype(cfg["serving"]["weights_dtype"]))):
            block_bytes = max(block_bytes,
                              sum(l.nbytes for l in params.values()))
            for prec in precisions:
                if b == 0:
                    states[prec] = [ref.embed(params, cfg, jnp.asarray(seq))
                                    for seq in seqs]
                elif b < len(blocks) - 1:
                    states[prec] = [ref.block(params, cfg, b - 1, x, prec)
                                    for x in states[prec]]
                else:
                    logits[prec] = [
                        ref.head(params, cfg, jax.lax.dynamic_slice_in_dim(
                            x[0], start, rows), prec)[off:off + n_s]
                        for x, (start, rows, off, n_s)
                        in zip(states.pop(prec), spans)]
            # the host runs ahead of the device: without this the next
            # block's leaves are allocated while this one's are still held
            jax.block_until_ready((states, logits))
            del params

    worst, cworst, n_tok = 0.0, None, 0
    for r, i in enumerate(pick):
        out = logit_gaps(
            logits["float32"][r], np.asarray(done[i][1], np.int32) - ref.TOKEN_BASE,
            logits[control][r] if control else None)
        worst = max(worst, out["gap_max"])
        n_tok += out["positions"]
        if control:
            cworst = (out["control_gap_max"] if cworst is None
                      else min(cworst, out["control_gap_max"]))
            result.say("control", request=i, **out)
    del logits
    gc.collect()
    stats = jax.local_devices()[0].memory_stats() or {}
    result.say("sample", requests=len(pick), served_tokens=n_tok,
               control_gap_min_of_max=cworst, blocks=len(blocks),
               largest_block_float32_bytes=block_bytes,
               hidden_bytes=len(pick) * len(precisions) * max_len
               * cfg["hidden_size"] * 4,
               process_peak_bytes=stats.get("peak_bytes_in_use"))
    return result.compare("served_logit_gap_max", worst,
                          cfg["correct"]["serve"]["logit_gap_max"])
