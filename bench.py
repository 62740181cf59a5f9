"""Benchmark entry: ResNet-50 ImageNet-shape training throughput + MFU on
one TPU chip, with the secondary phases (fused kernels, roofline,
transformer LM, int8 inference, generation serving, fleet loop).

Prints ONE JSON line on stdout:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}

The phases run one after another in this one process.  There is no
fallback: without a TPU, or when any phase raises, the run ends with the
traceback and a non-zero exit code, and prints no result line.  Timed
work ends in ``block_until_ready`` — JAX returns before the device has
finished, so a timing without it measures the enqueue.

The headline number drives the FRAMEWORK loop (``Optimizer.optimize()``
with mesh + bf16 compute + async loss readback), not a hand-rolled
bypass; the raw jitted-step number is reported alongside so a gap
between the two reads as framework overhead to fix.

MFU is reported against two rooflines:
  * ``mfu_vs_spec``     — public peak bf16 FLOP/s for the device kind
    (``telemetry.perf``; an unknown TPU kind is an error).
  * ``mfu_vs_measured`` — an empirically calibrated roofline: a chained
    big-matmul microbench run on the same chip.

Baseline for vs_baseline: the reference's published ResNet-50 recipe —
BigDL trains ResNet-50 at global batch 8192 on 2048 Xeon cores
(reference: models/resnet/README.md:85-150); whitepaper-era Broadwell
measurements imply ~35 img/s per 32-core executor.  vs_baseline = our
img/s on ONE chip / 35 (chip-for-executor speedup).  Per-iteration
throughput telemetry matches optim/DistriOptimizer.scala:425-431.

Rebuilding this file as a table of cells the driver can ledger is
ROADMAP S1.
"""

from __future__ import annotations

import json
import logging
import os
import sys
import time

import numpy as np

T_START = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))

BATCH = 128
SIZE = 224

RESULT = {
    "metric": f"resnet50_train_img_per_sec_bs{BATCH}_{SIZE}px_tpu",
    "value": 0.0,
    "unit": "images/sec/chip",
    "vs_baseline": 0.0,
}


def _log(msg: str) -> None:
    sys.stderr.write(f"[bench +{time.monotonic() - T_START:6.1f}s] {msg}\n")
    sys.stderr.flush()


def _update(**kv) -> None:
    """Record measurements and keep the derived headline fields (value,
    vs_baseline, MFU) in sync: optimizer loop preferred over raw step."""
    RESULT.update(kv)
    head = RESULT.get("optimizer_img_per_sec") or RESULT.get(
        "raw_step_img_per_sec")
    if head:
        RESULT["value"] = round(head, 2)
        RESULT["vs_baseline"] = round(head / 35.0, 2)
    flops = RESULT.get("flops_per_step")
    step = RESULT.get("optimizer_step_time_ms") or RESULT.get(
        "raw_step_time_ms")
    if flops and step:
        sec = step / 1e3
        peak_m = RESULT.get("peak_measured_flops")
        if peak_m:
            RESULT["mfu_vs_measured"] = round(flops / sec / peak_m, 4)
        RESULT["mfu_vs_spec"] = round(
            flops / sec / RESULT["peak_spec_flops"], 4)


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def phase_backend():
    """The TPU this benchmark is defined on, or an error."""
    import jax
    from bigdl_tpu.telemetry.perf import device_peak_flops
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"bench.py measures a TPU; JAX reports platform "
            f"{dev.platform!r} (a CPU run has no number to publish)")
    _log(f"backend up: {dev.platform} / {dev.device_kind} "
         f"x{len(jax.devices())}")
    _update(device_kind=dev.device_kind, platform=dev.platform,
            device_count=len(jax.devices()),
            peak_spec_flops=device_peak_flops(dev.device_kind))


def _build_step(fused: bool = False):
    """Build the jitted fwd+bwd+update for ResNet-50 and AOT-compile it."""
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.core.module import partition, combine, cast_floating
    import bigdl_tpu.nn as nn
    from bigdl_tpu.models import resnet50
    from bigdl_tpu.optim.methods import SGD
    from bigdl_tpu.utils import set_seed

    logging.getLogger("bigdl_tpu.optim").setLevel(logging.WARNING)
    set_seed(0)

    model = resnet50(class_num=1000, fused=fused)
    criterion = nn.CrossEntropyCriterion()
    method = SGD(0.1, momentum=0.9, dampening=0.0)
    params_tree, rest = partition(model)
    opt_state = method.init_state(params_tree)

    def step(params, rest, opt_state, x, y):
        def loss_fn(p):
            m = cast_floating(combine(p, rest), jnp.bfloat16)
            out = m.forward(x.astype(jnp.bfloat16)).astype(jnp.float32)
            return criterion(out, y), m

        (loss, m2), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        params, opt_state2 = method.update(grads, params, opt_state)
        _, rest2 = partition(m2)
        rest2 = cast_floating(rest2, jnp.float32)
        return params, rest2, opt_state2, loss

    jitted = jax.jit(step, donate_argnums=(0, 1, 2))

    rng = np.random.default_rng(0)
    x_np = rng.normal(size=(BATCH, SIZE, SIZE, 3)).astype(np.float32)
    y_np = rng.integers(1, 1001, size=(BATCH,))
    x = jnp.asarray(x_np)
    y = jnp.asarray(y_np)

    t_c = time.monotonic()
    compiled = jitted.lower(params_tree, rest, opt_state, x, y).compile()
    pfx = "fused_" if fused else ""
    _update(**{pfx + "compile_s": round(time.monotonic() - t_c, 1)})
    _log(f"{'fused ' if fused else ''}raw step compiled in "
         f"{time.monotonic() - t_c:.1f}s")

    # FLOPs per step from XLA's own cost analysis of the program we
    # actually execute (fwd+bwd+update)
    from bigdl_tpu.utils.xla_cost import (
        collective_hlo_bytes, compiled_bytes, compiled_flops,
    )
    flops_per_step = compiled_flops(compiled)
    if flops_per_step is None:
        raise RuntimeError("XLA returned no FLOP count for the step")
    _update(**{pfx + "flops_per_step": flops_per_step})
    # XLA's own HBM traffic estimate: the fused-kernel tranche exists to
    # cut bytes/step, so record the compiler's number for both variants
    # (custom-call kernels self-report via pallas cost estimates; the
    # comparison is still apples-to-apples on the XLA-visible traffic)
    by = compiled_bytes(compiled)
    if by:
        _update(**{pfx + "bytes_per_step": by})
    # inter-chip payload of the compiled step (the HLO's collective
    # outputs; 0.0 on a single-device program) — the comm budget the
    # mesh-observability layer cross-checks and the comm-bound roofline
    # verdict consumes
    comm = collective_hlo_bytes(compiled)
    if comm is not None:
        _update(**{pfx + "comm_bytes_per_step": comm["total"]})
    return compiled, (params_tree, rest, opt_state, x, y), (x_np, y_np)


def _time_step(compiled, state, pfx: str) -> float:
    """Warm up once, then time escalating rep counts; returns the
    warm-up loss."""
    import jax
    params_tree, rest, opt_state, x, y = state
    params_tree, rest, opt_state, loss = compiled(
        params_tree, rest, opt_state, x, y)
    warmup_loss = float(loss)
    if not np.isfinite(warmup_loss):
        raise RuntimeError(f"{pfx}step: non-finite loss {warmup_loss}")
    _log(f"{pfx}warmup step done, loss={warmup_loss:.3f}")
    for iters in (5, 20):
        t0 = time.perf_counter()
        for _ in range(iters):
            params_tree, rest, opt_state, loss = compiled(
                params_tree, rest, opt_state, x, y)
        jax.block_until_ready(loss)
        dt = time.perf_counter() - t0
        _update(**{pfx + "step_time_ms": round(dt / iters * 1e3, 2),
                   pfx + "step_img_per_sec": round(BATCH / (dt / iters), 2)})
        _log(f"{pfx}step: {dt / iters * 1e3:.2f} ms/step over {iters} "
             f"iters ({BATCH / (dt / iters):.1f} img/s)")
    return warmup_loss


def phase_raw_step():
    compiled, state, host_batch = _build_step()
    _update(raw_warmup_loss=round(_time_step(compiled, state, "raw_"), 4))
    return host_batch


def phase_fused_step():
    """ResNet-50 with the fused conv+BN+ReLU Pallas bottleneck path
    (ops/conv_bn_kernels.py), measured head to head against the XLA step
    from phase_raw_step; the winner carries the optimizer-loop headline.
    Also records XLA's bytes-accessed for both programs — the tranche's
    purpose is structurally fewer bytes on an HBM-bound step
    (docs/performance.md)."""
    compiled, state, _ = _build_step(fused=True)
    fused_loss = _time_step(compiled, state, "fused_")
    # numerics cross-check: same seed + same batch, so the first-step
    # loss must match the XLA variant to bf16 tolerance — a compiled-mode
    # divergence (Mosaic bug, a layout assumption) must never promote a
    # broken-but-fast variant
    raw_loss = RESULT["raw_warmup_loss"]
    suspect = abs(fused_loss - raw_loss) > 0.05 * max(abs(raw_loss), 1.0)
    if suspect:
        _update(fused_numerics_suspect=True,
                fused_warmup_loss=round(fused_loss, 4))
        _log(f"fused warmup loss {fused_loss:.4f} diverges from raw "
             f"{raw_loss:.4f}; fused will NOT be promoted")
    raw_ms = RESULT["raw_step_time_ms"]
    fused_ms = RESULT["fused_step_time_ms"]
    _update(fused_wins=bool(fused_ms < raw_ms * 0.995 and not suspect),
            fused_speedup_vs_xla=round(raw_ms / fused_ms, 4))
    b0, b1 = RESULT.get("bytes_per_step"), RESULT.get("fused_bytes_per_step")
    if b0 and b1:
        _update(fused_bytes_reduction_pct=round(
            100.0 * (1.0 - b1 / b0), 2))


def phase_optimizer_loop(host_batch):
    """The framework loop: Optimizer.optimize() on a 1-chip mesh.  This
    is the headline path (matches the reference's Throughput telemetry,
    optim/DistriOptimizer.scala:425-431)."""
    import jax.numpy as jnp
    import bigdl_tpu.nn as nn
    from bigdl_tpu import telemetry
    from bigdl_tpu.dataset.dataset import DataSet, MiniBatch
    from bigdl_tpu.models import resnet50
    from bigdl_tpu.optim import Optimizer, Trigger
    from bigdl_tpu.optim.methods import SGD
    from bigdl_tpu.telemetry.export import json_snapshot
    from bigdl_tpu.telemetry.runtime import sample_runtime

    x_np, y_np = host_batch
    # unified telemetry rides along: the step-phase histograms
    # (data-wait vs device step) land in a JSON snapshot next to the
    # result, so a later round can attribute a regression without
    # re-running a TPU profile
    telemetry.enable()
    iters_per_epoch = 10
    # 10 epochs -> 9 steady windows (marginal cost <1s per extra
    # window): the aggregate-span estimator gets enough windows that any
    # residual one-time cost is visible as a leading outlier rather than
    # dominating the mean
    epochs = 10
    # The batches share one host buffer, so the HBM cache holds it once;
    # epochs after the first pay zero host->device transfer
    # (cache_on_device ≙ the reference's CachedDistriDataSet), and the
    # dispatch windows are staged once and reused across epochs.
    data = (DataSet.array([MiniBatch(x_np, y_np)
                           for _ in range(iters_per_epoch)], shuffle=False)
            .cache_on_device())
    use_fused = bool(RESULT.get("fused_wins"))
    if use_fused:
        _update(optimizer_loop_variant="fused")
    opt = (Optimizer(resnet50(class_num=1000, fused=use_fused), data,
                     nn.CrossEntropyCriterion())
           .set_optim_method(SGD(0.1, momentum=0.9, dampening=0.0))
           .set_end_when(Trigger.max_epoch(epochs))
           .set_compute_dtype(jnp.bfloat16)
           .set_log_interval(iters_per_epoch)
           # k steps per compiled dispatch: hides the per-call launch
           # latency (≙ the reference's 1-task-per-node fix for Spark
           # scheduling overhead, whitepaper fig 8)
           .set_iterations_per_dispatch(iters_per_epoch))
    t_c = time.monotonic()
    opt.optimize()
    _log(f"optimizer loop ({epochs} epochs) in {time.monotonic() - t_c:.1f}s")
    # Completion-to-completion window timings from the loss-drain worker
    # (loop dispatches are fully async — wall-clock epoch gaps would
    # measure dispatch rate).  Window 1 bears the compile; steady state
    # = the AGGREGATE span over the later windows (a min() over
    # per-window rates reads impossibly fast whenever the drain lags one
    # window and the next completions bunch together).
    steady = opt.window_timings[1:]
    step_t = sum(dt for _, dt, _ in steady) / sum(n for n, _, _ in steady)
    _update(optimizer_step_time_ms=round(step_t * 1e3, 2),
            optimizer_img_per_sec=round(BATCH / step_t, 2),
            optimizer_overhead_pct=round(
                100.0 * (1.0 - (BATCH / step_t)
                         / RESULT["raw_step_img_per_sec"]), 1))
    # step-time attribution: phases + residual summing to the measured
    # wall step (telemetry.perf); recomputed after the roofline phase
    # so mfu_vs_measured joins the table
    _update(optimizer_flops_per_step=opt.compiled_flops_per_iteration)
    _OPT_WINDOW_RECORDS[:] = list(opt.window_records)
    attribution = _build_attribution()
    _update(attribution=attribution)
    ph = attribution["phases_s"]
    _log("attribution (s/step): "
         + " ".join(f"{k}={v:.6f}" for k, v in ph.items())
         + f" residual={attribution['residual_s']:.6f}"
         + f" wall={attribution['wall_step_s']:.6f}"
         + f" dominant={attribution['dominant_phase']}")
    sample_runtime()
    snap = json_snapshot()
    # the attribution table rides in the snapshot so a later round reads
    # where the time went without re-running a TPU profile
    snap["perf_attribution"] = attribution
    snap_path = os.path.join(HERE, "BENCH_telemetry.json")
    with open(snap_path, "w", encoding="utf-8") as f:
        json.dump(snap, f, default=str)
    _update(telemetry_snapshot=os.path.basename(snap_path))
    # flight-recorder summary: the snapshot embeds the event ring, so a
    # bench run's retries/faults/commits are attributable after the fact
    ev = snap.get("events", {})
    _log(f"telemetry snapshot written to {snap_path}; flight recorder: "
         f"{ev.get('buffered', 0)} events {ev.get('by_kind', {})} "
         f"({ev.get('dropped', 0)} dropped)")


def phase_transformer():
    """Secondary metric: decoder-only transformer LM training through
    the same Optimizer loop (L6 H512 T2048 b8, bf16, flash attention).
    The reference trains its Transformer stack too (nn/Transformer.
    scala:749); long-context throughput is where the Pallas flash
    kernels earn their keep."""
    from bigdl_tpu.examples.perf import main as perf_main

    seq, batch = 2048, 8
    # emit=False: bench's stdout contract is exactly ONE result line
    out = perf_main(["--model", "transformer-lm", "--seq-len",
                     str(seq), "-b", str(batch), "--hidden-size",
                     "512", "--num-layers", "6", "--num-heads", "8",
                     "--vocab-size", "32000", "--bf16",
                     "--iterations", "10", "--epochs", "4"],
                    emit=False)
    step_ms = out["ms_per_iteration"]
    upd = dict(transformer_lm_ms_per_step=step_ms,
               transformer_lm_tokens_per_sec=round(
                   batch * seq / (step_ms / 1e3), 1),
               transformer_lm_config=f"L6-H512-T{seq}-b{batch}-bf16")
    tf = out.get("model_tflops_per_sec")
    if tf:
        upd["transformer_lm_tflops_per_sec"] = tf
        upd["transformer_lm_mfu_vs_measured"] = round(
            tf * 1e12 / RESULT["peak_measured_flops"], 4)
    _update(**upd)


def phase_int8():
    """int8-vs-fp32 inference latency ratio on ResNet-50 shapes — the
    TPU datapoint for the reference's 'up to 2x' int8 claim (reference
    docs/docs/whitepaper.md int8 section; fidelity is already
    test-locked, tests/test_quantized.py)."""
    from bigdl_tpu.examples.perf import main as perf_main

    batch = 32
    out = perf_main(["--model", "resnet50", "-b", str(batch),
                     "--image-size", str(SIZE), "--int8-infer"],
                    emit=False)
    base = out.get("baseline_dtype", "fp32")
    _update(int8_speedup_vs_fp32=out["int8_speedup"],
            int8_infer_ms=out.get("int8_ms"),
            fp32_infer_ms=out.get(f"{base}_ms"),
            int8_config=f"resnet50-b{batch}-{SIZE}px")


def _write_artifact(series: str, kind: str, payload: dict) -> None:
    """One record of a secondary series (GENSERVE_r<N>.json,
    FLEET_r<N>.json) in the RoundArtifact envelope."""
    from bigdl_tpu.telemetry import perf
    tag = os.environ.get("BIGDL_TPU_ROUND", "latest")
    payload = dict(payload, platform=RESULT["platform"])
    art = perf.make_round_artifact(
        payload, kind=kind, timestamp=time.time(),
        device_kind=RESULT["device_kind"], confirmed_on_device=True,
        git_rev=perf.git_revision(HERE))
    path = perf.write_round_artifact(
        os.path.join(HERE, f"{series}_r{tag}.json"), art)
    _log(f"{kind} artifact: {os.path.basename(path)} "
         f"({payload['value']} {payload['unit']})")


def phase_generate_serving():
    """Continuous-batching decode throughput (serving.generation):
    mixed-length prompts through the fixed-shape KV slot pool vs the
    sequential ``generate()`` baseline, plus the prefill-wall probes: a
    shared-system-prompt workload measuring the prefix KV cache's TTFT
    win, and a mixed long/short arrival cadence probe measuring how
    chunked prefill bounds the inter-token tail (both run against a
    larger prefill-dominant model config)."""
    from bigdl_tpu.models import transformer_lm
    from bigdl_tpu.serving.generation import (
        run_cadence_probe, run_mixed_workload,
        run_shared_prefix_workload,
    )
    from bigdl_tpu.utils import set_seed

    set_seed(7)
    model = transformer_lm(vocab_size=32000, hidden_size=512,
                           num_layers=6, num_heads=8,
                           filter_size=1024, max_len=512)
    n_req, slots, seq_sample = 32, 16, 8
    rng = np.random.default_rng(10)
    prompts = [rng.integers(1, 129, rng.integers(8, 65)).astype(np.int32)
               for _ in range(n_req)]
    max_news = [int(rng.integers(16, 129)) for _ in range(n_req)]
    # the UNSHARED workload runs with the defaults (prefix cache off)
    out = run_mixed_workload(model.eval_mode(), prompts, max_news,
                             slots=slots, sequential_sample=seq_sample)

    # prefill-wall probes: a model where prefill compute dominates a
    # decode step (the regime the prefix cache and chunk budget exist
    # for — at tiny-model scale prefill is all dispatch overhead and
    # the probes measure nothing)
    set_seed(7)
    probe_model = transformer_lm(
        vocab_size=32000, hidden_size=256, num_layers=4, num_heads=8,
        filter_size=512, max_len=512).eval_mode()
    out["shared_prefix"] = run_shared_prefix_workload(
        probe_model, n_requests=32, prefix_len=448, tail=(8, 49),
        max_new=8, slots=8, prefix_granularity=64, prefill_chunk=64)
    out["cadence"] = {
        "bounded": run_cadence_probe(probe_model, bounded=True),
        "unbounded": run_cadence_probe(probe_model, bounded=False),
    }
    _update(gen_serving_tokens_per_sec=out["continuous_tokens_per_sec"],
            gen_serving_speedup_vs_sequential=out.get(
                "speedup_vs_sequential"),
            gen_serving_greedy_equal_checked=out.get(
                "greedy_equal_checked"),
            gen_serving_greedy_checked_requests=out.get(
                "greedy_checked_requests"),
            gen_serving_slot_occupancy=out["slot_occupancy_mean"],
            gen_serving_prefix_ttft_p50_speedup=out[
                "shared_prefix"].get("ttft_p50_speedup"),
            gen_serving_cadence_p99_over_steady=out["cadence"][
                "bounded"].get("p99_over_steady_p50"),
            gen_serving_config=f"slots{slots}-req{n_req}-prompts8to64-"
                               f"new16to128")
    _write_artifact("GENSERVE", "generate_serving", dict(
        out, metric="generate_serving_tokens_per_sec",
        value=out["continuous_tokens_per_sec"], unit="new_tokens/sec"))


def phase_fleet():
    """The self-driving-fleet closed loop, measured: chaos kill ->
    controller replacement, spike -> scale-up, new checkpoint
    generation -> rolling zero-drop hot-deploy.  Headline metric is
    train-to-serve freshness (commit timestamp -> last replica
    serving the new generation)."""
    import tempfile

    from bigdl_tpu.fleet.harness import run_fleet_scenario

    with tempfile.TemporaryDirectory(prefix="bench-fleet-") as work:
        r = run_fleet_scenario(work, load_s=2.0, spike_requests=14,
                               wait_scale_down=True)
    out = {
        "freshness_s": r["freshness_s"],
        "deployed_generation": r["deployed_generation"],
        "deploy_swapped_replicas": r["deploy_swapped"],
        "killed_replica": r["killed_replica"],
        "live_after_spike": r["live_after_spike"],
        "live_final": r["live_final"],
        "requests": {"submitted": r["submitted"], "ok": r["ok"],
                     "shed": r["shed"], "dropped": r["dropped"]},
        "greedy_rows_equal": r["greedy_rows_equal"],
        "admitted_outstanding_at_end": r["admitted_outstanding"],
        "events": r["events"],
        "loop_duration_s": r["duration_s"],
    }
    _update(fleet_deploy_freshness_s=r["freshness_s"],
            fleet_zero_drop=(r["dropped"] == 0
                             and r["admitted_outstanding"] == 0),
            fleet_scale_up_events=r["events"]["scale_up"],
            fleet_config="1to3replicas-kill+spike+hotdeploy")
    _write_artifact("FLEET", "fleet", dict(
        out, metric="fleet_deploy_freshness_seconds",
        value=r["freshness_s"], unit="seconds"))


def phase_roofline():
    """Empirical bf16 matmul roofline: chained square matmuls (each
    output feeds the next so XLA cannot elide any), timed after warmup
    with the work forced to completion, at escalating sizes."""
    import jax
    import jax.numpy as jnp

    chain_len = 8

    def measure(n, reps):
        @jax.jit
        def chain(a, b):
            for _ in range(chain_len):
                a = jnp.matmul(a, b, preferred_element_type=jnp.bfloat16)
            return a

        a = jnp.full((n, n), 0.5, jnp.bfloat16)
        b = jnp.full((n, n), 1e-4, jnp.bfloat16)

        def run(r):
            out = a
            for _ in range(r):
                out = chain(out, b)
            jax.block_until_ready(out)

        run(1)  # compile
        t0 = time.perf_counter()
        run(reps)
        dt = time.perf_counter() - t0
        peak = 2.0 * n * n * n * chain_len * reps / dt
        _log(f"roofline n={n}: {peak / 1e12:.1f} TFLOP/s bf16 ({dt:.2f}s)")
        return peak

    best = max(measure(n, reps)
               for n, reps in ((2048, 16), (4096, 16), (8192, 8)))
    _update(peak_measured_flops=best)


# ---------------------------------------------------------------------------
# Perf attribution (telemetry.perf)
# ---------------------------------------------------------------------------

# the optimizer loop's per-window phase records, kept so the
# attribution table can be re-derived AFTER the roofline phase measures
# this run's peak (phase order puts the headline loop first)
_OPT_WINDOW_RECORDS: list = []


def _build_attribution():
    """Attribution report (phases + residual + MFU + boundedness) from
    the optimizer loop's window records and whatever cost/roofline
    numbers have landed in RESULT so far."""
    from bigdl_tpu.telemetry import perf
    pfx = ("fused_" if RESULT.get("optimizer_loop_variant") == "fused"
           else "")
    return perf.attribution_report(
        _OPT_WINDOW_RECORDS,
        # prefer the optimizer loop's own execution-weighted FLOP
        # count (the program the windows actually ran); fall back to
        # the raw-step program's
        flops_per_step=(RESULT.get("optimizer_flops_per_step")
                        or RESULT.get(pfx + "flops_per_step")),
        bytes_per_step=RESULT.get(pfx + "bytes_per_step"),
        peak_spec_flops=RESULT["peak_spec_flops"],
        peak_measured_flops=RESULT.get("peak_measured_flops"),
        device_kind=RESULT["device_kind"],
        comm_bytes_per_step=RESULT.get(pfx + "comm_bytes_per_step"))


def _refresh_attribution():
    """Re-derive the attribution table once the same-run roofline has
    landed (mfu_vs_measured becomes computable), and rewrite the
    telemetry snapshot's embedded copy so artifact and result line
    agree."""
    att = _build_attribution()
    _update(attribution=att)
    path = os.path.join(HERE, RESULT["telemetry_snapshot"])
    with open(path, "r", encoding="utf-8") as f:
        snap = json.load(f)
    snap["perf_attribution"] = att
    with open(path, "w", encoding="utf-8") as f:
        json.dump(snap, f, default=str)


def _run(name: str, fn):
    _log(f"phase {name}: start")
    t0 = time.monotonic()
    out = fn()
    _log(f"phase {name}: done in {time.monotonic() - t0:.1f}s")
    return out


def main() -> None:
    from bigdl_tpu.telemetry import perf
    from bigdl_tpu.utils.compile_cache import enable_compile_cache

    phase_backend()
    _log(f"compile cache: {enable_compile_cache()}")
    host_batch = _run("raw_step", phase_raw_step)
    _run("fused_step", phase_fused_step)
    _run("optimizer_loop", lambda: phase_optimizer_loop(host_batch))
    _run("roofline", phase_roofline)
    # the roofline landed after the optimizer loop: fold the measured
    # peak into the attribution table + snapshot copy
    _refresh_attribution()
    _run("transformer", phase_transformer)
    _run("int8_infer", phase_int8)
    _run("generate_serving", phase_generate_serving)
    _run("fleet", phase_fleet)

    _update(schema_version=perf.ROUND_ARTIFACT_VERSION,
            timestamp=time.time(), git_rev=perf.git_revision(HERE),
            confirmed_on_device=True)
    print(json.dumps(RESULT), flush=True)


if __name__ == "__main__":
    main()
