"""A training cell: ``Optimizer.optimize()`` over one seeded batch held
on the device, in dispatch windows of k steps, until the window closes.

One ``optimize()`` call builds the compiled step, drives it from the
seeded weights through its first dispatch windows (the first compiles or
loads from the cache: set-up) and goes on into the measured window with
the same program and state.  The losses of the first window are compared
with the float32 reference's; where the configuration gives a limit for
it, so are the trained leaves after that window, which the program
writes as a checkpoint of its own.
"""
from __future__ import annotations

import gc
import importlib
import os
import shutil
import time
from typing import Any, Dict, List, Optional, Tuple

from harness import device as dev
from harness import manifest, result, trace as tr

TRACE_SECONDS = 4.0
# dispatch windows before the measured one: the first compiles or loads,
# and the checkpoint after it leaves the device idle, so a second refills
# the queue
WARM_WINDOWS = 2


def check_plan(limits: Dict[str, float], job: Dict[str, Any]) -> Tuple[int, bool]:
    """How many steps the reference follows, and whether the state after
    the first dispatch window is compared: both from the numbers that the
    configuration gives limits for (``loss_step<i>_gap``, ``update_diff``)."""
    state = "update_diff" in limits
    steps = max(int(n[len("loss_step"):-len("_gap")])
                for n in limits if n.startswith("loss_step"))
    k = job["steps_per_dispatch"]
    if state and steps > k:
        raise ValueError("the state is read after one dispatch window: no "
                         "loss limit beyond steps_per_dispatch")
    return (k if state else steps), state


def update_diff(got: Dict[str, Any], ref: Dict[str, Any]) -> float:
    """The norm of the difference between the trained leaves and the
    reference's over the norm of the reference's change from the seeded
    leaves, all leaves taken as one vector.  (The gap between the two
    changes' norms, leaf by leaf, does not tell bfloat16 from int8: a
    rounding error turns a gradient without lengthening it.  PERF.md,
    Findings of PR 24.)"""
    import numpy as np

    def sq(a):
        return float(np.sum(np.square(a, dtype=np.float64)))
    num = sum(sq(got["trained"][n] - r) for n, r in ref["trained"].items())
    den = sum(sq(r - ref["seeded"][n]) for n, r in ref["trained"].items())
    return (num / den) ** 0.5


def reference_run(kind, cfg, job, seed: int, devices, steps: int, state: bool,
                  precision: str = "float32") -> Dict[str, Any]:
    """The reference over the first ``steps`` steps, on weights and a
    batch made from the same seed by the benchmark's generator: its
    losses and, with ``state``, the seeded leaves and the trained ones
    after those steps, by name and on the host."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from harness import weights
    ref = importlib.import_module("reference." + kind.REFERENCE)
    spec = kind.train_param_spec(cfg)
    shard = weights.row_shardings(spec, devices) if len(devices) > 1 else None
    leaves = weights.make(spec, seed, jnp.float32, shard)
    params = {p: l for (p, _), l in zip(spec, leaves)}
    out: Dict[str, Any] = {}
    if state:
        out["seeded"] = {n: np.asarray(params[n])
                         for n in kind.program_state_names(spec)}
    x, y = kind.batch(cfg, job, seed)
    if len(devices) > 1:
        # rows of the batch over the chips, so that no chip holds the
        # whole batch's float32 logits; the compiler partitions the rest
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        rows = NamedSharding(Mesh(np.asarray(devices), ("w",)), P("w"))
        x, y = jax.device_put(x, rows), jax.device_put(y, rows)
    with jax.default_matmul_precision("highest"):
        out["losses"], trained = kind.reference_train(
            ref, params, x, y, cfg, job, steps, precision)
    if state:
        out["trained"] = {n: np.asarray(a) for n, a in trained.items()}
    del params, leaves, x, y, trained
    gc.collect()
    return out


def program_state(opt, kind, spec) -> Dict[str, Any]:
    """The trained leaves from the checkpoint that the program wrote
    after its first dispatch window, and the steps it had made."""
    import jax
    import numpy as np
    from bigdl_tpu.utils.file import load_checkpoint
    model_state, _optim_state, driver = load_checkpoint(
        opt._ckpt_manager().latest_good())
    flat = jax.tree_util.tree_flatten_with_path(model_state["params"])[0]
    trained = {"." + ".".join(str(k.key) for k in path): np.asarray(leaf)
               for path, leaf in flat}
    shapes = dict(spec)
    if sorted(trained) != sorted(kind.program_state_names(spec)) or any(
            a.shape != tuple(shapes[n]) for n, a in trained.items()):
        raise RuntimeError("the program's checkpoint does not hold the "
                           "leaves the configuration implies")
    return {"steps": int(driver["neval"]) - 1, "trained": trained}


def make_optimizer(built, cfg, job, checkpoint_dir: Optional[str] = None):
    import jax.numpy as jnp
    from bigdl_tpu.dataset.dataset import DataSet, MiniBatch
    from bigdl_tpu.optim import SGD, Adam, Optimizer

    k = job["steps_per_dispatch"]
    data = DataSet.array([MiniBatch(built["x"], built["y"]) for _ in range(k)],
                         shuffle=False).cache_on_device()
    o = job["optimizer"]
    if o["name"] == "sgd":
        method = SGD(o["lr"], momentum=o["momentum"], dampening=0.0)
    elif o["name"] == "adam":
        method = Adam(o["lr"])
    else:
        raise ValueError(f"unknown optimizer {o['name']!r}")
    opt = (Optimizer(built["model"], data, built["criterion"])
           .set_optim_method(method)
           .set_compute_dtype(jnp.dtype(cfg["training"]["compute_dtype"]))
           .set_log_interval(k)
           .set_iterations_per_dispatch(k))
    if job.get("plan"):
        from bigdl_tpu.parallel.plan import PartitionPlan
        opt.set_partition_plan(PartitionPlan(**job["plan"]))
    if checkpoint_dir:
        from bigdl_tpu.optim.trigger import Trigger
        shutil.rmtree(checkpoint_dir, ignore_errors=True)
        opt.set_checkpoint(checkpoint_dir, Trigger(
            lambda st: st["neval"] == k + 1, "after the first dispatch window"))
    return opt


class Window:
    """The end trigger of the run: closes the window on the clock, at a
    boundary between dispatch windows, and (traced runs) starts and stops
    the profiler from the thread that drives the program."""

    def __init__(self, opt, k: int, seconds: float, trace_dir: Optional[str]):
        self.opt, self.k, self.seconds = opt, k, seconds
        self.trace_dir = trace_dir
        self.stop_at: Optional[int] = None
        self.start: Optional[float] = None
        self.tracing = False
        self.trace_t0: Optional[float] = None
        self.trace_t1: Optional[float] = None

    def __call__(self, state: Dict[str, Any]) -> bool:
        import jax
        now = time.perf_counter()
        recs = self.opt.window_records
        if self.start is None and len(recs) >= WARM_WINDOWS:
            self.start = recs[WARM_WINDOWS - 1]["t_device_ready"]
        if self.start is not None and self.trace_dir:
            if not self.tracing and self.trace_t0 is None:
                tr.start(self.trace_dir)
                self.tracing, self.trace_t0 = True, time.perf_counter()
            elif self.tracing and now - self.trace_t0 >= min(
                    TRACE_SECONDS, self.seconds / 4):
                self.stop_trace()
        if (self.stop_at is None and self.start is not None
                and now >= self.start + self.seconds):
            # the end of the dispatch window after the one this iteration
            # belongs to: a window is never trimmed (no second program
            # compiles), and one more is dispatched after the clock has
            # passed, so one completes after it even where the host had
            # fallen behind the device
            n = int(state["neval"]) - 1
            self.stop_at = (-(-max(n, 1) // self.k) + 1) * self.k
        return self.stop_at is not None and state["neval"] > self.stop_at

    def stop_trace(self) -> None:
        import jax
        if self.tracing:
            jax.profiler.stop_trace()
            self.trace_t1 = time.perf_counter()
            self.tracing = False


def reduce_records(recs: List[Dict[str, Any]], seconds: float,
                   after: Optional[float] = None) -> Dict[str, Any]:
    """Steps and seconds of the counted dispatch windows: from the
    completion of the last warm-up window (the end of set-up) to the
    completion of the first window that finishes at or after
    ``seconds``.  With ``after`` (a traced run: the time the profiler
    had stopped), only the windows that began after it, less the first:
    starting and stopping the profiler stalls the host for seconds, and
    the per-layer readings of a step are of steps it did not touch."""
    start = recs[WARM_WINDOWS - 1]["t_device_ready"]
    counted, prev = [], start
    for r in recs[WARM_WINDOWS:]:
        counted.append((prev, r))
        prev = r["t_device_ready"]
        if prev - start >= seconds:
            break
    if not counted or prev - start < seconds:
        raise RuntimeError("the run ended before the window closed")
    if after is not None:
        counted = [(p, r) for p, r in counted if p >= after][1:]
        if not counted:
            raise RuntimeError("no dispatch window of the run lies clear of "
                               "the trace: the window is too short")
    return {"start": start,
            "steps": sum(r["iterations"] for _, r in counted),
            "seconds": sum(r["t_device_ready"] - p for p, r in counted),
            "data_wait_s": sum(r["data_wait_s"] for _, r in counted),
            "host_s": sum(r["host_staging_s"] + r["dispatch_s"]
                          for _, r in counted),
            "windows": len(counted)}


def gaps(got: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, Any]:
    """Each number of ``got`` (the program's, or a control's) against
    the reference's."""
    out: Dict[str, Any] = {}
    for i, (a, b) in enumerate(zip(got["losses"], ref["losses"])):
        # against the first loss, the scale of the problem: a later loss
        # can fall close to zero on the repeated batch
        out[f"loss_step{i + 1}_gap"] = abs(a - b) / abs(ref["losses"][0])
    if "trained" in got:
        out["update_diff"] = update_diff(got, ref)
    return out


def run(man, cell, cfg, job, args, t_proc0: float, devices,
        rehearsal: bool = False) -> None:
    import jax
    kind = manifest.load_kind(cfg["kind"])
    reported = manifest.end_to_end_names(man, cell["name"])
    limits = cfg["correct"]["train"]      # number -> limit, and a "why"
    steps, state = check_plan(limits, job)
    t_imports = time.perf_counter() - t_proc0

    t = time.perf_counter()
    ref = reference_run(kind, cfg, job, args.seed, devices, steps, state)
    reference_s = time.perf_counter() - t

    t = time.perf_counter()
    built = kind.build_train(cfg, job, args.seed, devices)
    jax.block_until_ready(built["x"])
    weights_s = time.perf_counter() - t
    checkpoint_dir = (os.path.join(manifest.ROOT, ".bench_ckpt", cell["name"])
                      if state else None)
    opt = make_optimizer(built, cfg, job, checkpoint_dir)
    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(manifest.ROOT, ".bench_trace", cell["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
    window = Window(opt, job["steps_per_dispatch"], args.seconds, trace_dir)
    opt.set_end_when(window)
    t = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.optimize"):
        opt.optimize()
    window.stop_trace()
    optimize_s = time.perf_counter() - t
    recs = list(opt.window_records)
    # the allocator's high-water mark leaves out what a program holds
    # while it runs (PERF.md, Findings of PR 22): the step's arguments
    # and temporaries, from the compiler, stand in where they are more
    step_bytes = 0
    if not rehearsal:
        m = opt.step_executables[0].memory_analysis()
        step_bytes = int(m.argument_size_in_bytes + m.temp_size_in_bytes)
    red = reduce_records(recs, args.seconds)
    setup_s = red["start"] - t_proc0 - reference_s
    result.say("setup", imports_s=t_imports, weights_s=weights_s,
               lower_s=opt.step_lower_seconds, compile_s=opt.step_compile_seconds,
               first_window_s=recs[0]["wall_s"],
               warm_windows_s=red["start"] - recs[0]["t_device_ready"],
               reference_s=reference_s, setup_s=setup_s,
               compiles=len(opt.step_executables))
    result.say("window", steps=red["steps"], seconds=red["seconds"],
               windows=red["windows"], optimize_s=optimize_s)

    flops_per_step = built["flops_per_step"]
    got: Dict[str, Any] = {"losses": [l for r in recs for l in r["losses"]][:steps]}
    ok = len(opt.step_executables) == 1
    result.say("correct", number="compiled_programs",
               value=len(opt.step_executables), limit=1, ok=ok)
    if state:
        got.update(program_state(opt, kind, built["spec"]))
        shutil.rmtree(checkpoint_dir, ignore_errors=True)
        at = got.pop("steps")
        result.say("correct", number="checkpoint_after_steps", value=at,
                   limit=steps, ok=at == steps)
        ok &= at == steps
    sound = gaps(got, ref)
    for number, limit in limits.items():
        if isinstance(limit, (int, float)):
            ok &= result.compare(number, sound[number], limit)
    result.say("losses", program=got["losses"], reference=ref["losses"])
    result.say("sound", **sound)
    if args.control:
        del opt, built
    for precision in filter(None, args.control.split(",")):
        low = reference_run(kind, cfg, job, args.seed, devices, steps, state,
                            precision)
        result.say("control." + precision, **gaps(low, ref))

    peak = dev.peaks(devices[0].device_kind) if not rehearsal else None
    metrics: Dict[str, Any] = {}
    if not args.trace:
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        if rehearsal:
            metrics["train_mfu"] = "not measured"
        else:
            mfu = (100.0 * flops_per_step * red["steps"]
                   / red["seconds"] / (len(devices) * peak["bf16_flops_per_s"]))
            metrics["train_mfu"] = {"value": mfu, "unit": "%"}
        result.final_line(ok, red["steps"], 0, metrics,
                          _device(devices, rehearsal, step_bytes))
        return
    t_obj = tr.Trace(tr.find_xplane(trace_dir))
    clear = reduce_records(recs, args.seconds, after=window.trace_t1)
    result.say("clear_of_trace", steps=clear["steps"], seconds=clear["seconds"],
               windows=clear["windows"])
    obs: Dict[str, Any] = {"kind": cfg["kind"], "cfg": cfg, "job": job,
                           "records": clear, "chips": len(devices),
                           "peaks": peak, "trace": t_obj}
    # steps the trace covers: its busy time over the device time of one
    # step, read from the whole executions of the window program in it
    mod_s, mod_n = t_obj.module_seconds("jit_window_step")
    k = job["steps_per_dispatch"]
    obs["trace_steps"] = (t_obj.busy_s() / (mod_s / (mod_n * k)) if mod_n
                          else t_obj.window_s / (clear["seconds"] / clear["steps"]))
    metrics = result.read_layers(man, cell["name"], reported, obs,
                                 device_metrics=not rehearsal)
    device = _device(devices, rehearsal, step_bytes)
    breakdown = None
    if not rehearsal:
        device["busy_s"] = t_obj.busy_s()
        device["window_s"] = t_obj.window_s
        breakdown = {"device_ops": tr.top(t_obj.category_seconds()),
                     "idle_gaps": tr.top(t_obj.idle_gaps())}
    result.final_line(ok, red["steps"], 0, metrics, device, breakdown)


def _device(devices, rehearsal: bool, step_bytes: int = 0) -> Dict[str, Any]:
    d = dev.describe(devices)
    d["memory_peak_bytes"] = max(d["memory_peak_bytes"], step_bytes)
    if rehearsal:
        d["note"] = "CPU rehearsal: no device metric is measured here"
    return d
