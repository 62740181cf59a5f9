#!/usr/bin/env python3
"""The quickest proof that bigdl-tpu still starts on the chip.

    python chip_smoke.py             # one TPU chip: train, kernels, serve
    python chip_smoke.py --chips 4   # four chips: the sharded steps only

One process, no child interpreters.  It drives the two main paths through
the entry points a user calls — ``Optimizer.optimize()`` training and
``ModelServer`` -> ``GenerationScheduler`` -> ``SlotPool`` serving — at
the real widths of models the repo supports, with weights and data made
from a seed, and checks what comes out by the repo's own means: finite
falling losses, the Pallas kernels against the XLA paths they replace,
served tokens against the model's own sequential ``generate()``.

Run as ``python chip_smoke.py`` it has exactly one behaviour, and
without a TPU that behaviour is failure: any phase that fails raises,
the exit code is not 0 and no result line is printed.  The last line of
a passing run is one JSON object naming the device as JAX reports it.
Everything printed before it is information, not a benchmark.

The phases take their sizes as an argument so that
``tests/test_chip_smoke.py`` can rehearse them on the CPU at a tiny
size; the script itself only ever passes ``REAL``.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.metadata
import json
import sys
import time
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

SEED = 22


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Every size a phase uses.  The defaults are the real ones; the only
    concession to time is the number of steps and requests."""

    # ResNet-50 training (train phase, fused comparison, dp=4 step)
    resnet_layers: Tuple[int, ...] = (3, 4, 6, 3)   # == models.resnet50
    classes: int = 1000
    image: int = 224
    batch: int = 128
    steps_per_window: int = 10
    windows: int = 3
    fused_steps: int = 3
    # flash attention against XLA attention: (B, H, T, D)
    flash_shape: Tuple[int, int, int, int] = (8, 8, 2048, 64)
    # decoder LM training (kernels phase, fsdp x tp step)
    lm_vocab: int = 32000
    lm_hidden: int = 512
    lm_layers: int = 6
    lm_heads: int = 8
    lm_seq: int = 2048
    lm_batch: int = 8
    lm_steps: int = 6
    # generation serving
    serve_filter: int = 1024
    serve_max_len: int = 512
    serve_slots: int = 16
    serve_prompt_lens: Tuple[int, ...] = (8, 16, 40, 64, 100, 128, 200, 256)
    serve_new_tokens: int = 32
    # four chips
    dp_steps: int = 5
    plan_lm_steps: int = 3


REAL = Sizes()

# bf16 has 8 bits of mantissa (eps 2^-8 = 3.9e-3); a loss or an output
# that went through a few hundred bf16 roundings agrees to about 2e-2
BF16_TOL = 2e-2
# parameter updates after three optimizer steps pass through the whole
# backward in bf16 three times over; they agree to about 1e-1 of the
# update's own norm
BF16_UPDATE_TOL = 1e-1


def check(ok: bool, what: str) -> None:
    """Raise unless ``ok`` (not ``assert``: ``python -O`` removes those)."""
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


def device_tag(devices: Sequence[Any]) -> str:
    d = devices[0]
    return f"{d.platform}/{d.device_kind} x{len(devices)}"


def say(phase: str, tag: str, **fields: Any) -> None:
    """One line of information, naming the device it was measured on."""
    body = " ".join(f"{k}={_fmt(v)}" for k, v in fields.items())
    print(f"[{phase}] on {tag}: {body}", flush=True)


def _fmt(v: Any) -> str:
    if isinstance(v, float):
        return f"{v:.4g}"
    if isinstance(v, (list, tuple)):
        return "[" + ",".join(_fmt(x) for x in v) + "]"
    if isinstance(v, dict):
        return "{" + ",".join(f"{k}:{_fmt(x)}" for k, x in v.items()) + "}"
    return str(v)


def final_line(devices: Sequence[Any]) -> str:
    d = devices[0]
    return json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}})


def peak_bytes(devices: Sequence[Any]) -> List[Any]:
    """Per-device high-water mark since the process started, or None
    where the backend keeps no statistics (the CPU)."""
    out = []
    for d in devices:
        stats = d.memory_stats()
        out.append(stats.get("peak_bytes_in_use") if stats else None)
    return out


def _gib(n: Any) -> str:
    return "n/a" if n is None else f"{n / 2 ** 30:.2f}GiB"


def program_bytes(compiled) -> Dict[str, str]:
    """What one compiled program needs on a device, from the compiler."""
    m = compiled.memory_analysis()
    return {"temp": _gib(m.temp_size_in_bytes),
            "args": _gib(m.argument_size_in_bytes),
            "out": _gib(m.output_size_in_bytes)}


# ---------------------------------------------------------------------------
# the training path: Optimizer.optimize() as a user's job builds it
# ---------------------------------------------------------------------------

def optimize(model, criterion, x, y, *, steps: int, windows: int = 1,
             per_dispatch: int = 1, lr: float, configure=None):
    """``windows`` epochs of ``steps`` iterations over one repeated batch
    held in device memory, bf16 compute, SGD with momentum — the
    construction of ``bigdl_tpu.examples.perf`` and the imagenet
    example.  ``configure(opt)`` sets a mesh or a partition plan."""
    import jax.numpy as jnp
    from bigdl_tpu.dataset.dataset import DataSet, MiniBatch
    from bigdl_tpu.optim import Optimizer, SGD, Trigger

    data = DataSet.array([MiniBatch(x, y) for _ in range(steps)],
                         shuffle=False).cache_on_device()
    opt = (Optimizer(model, data, criterion)
           .set_optim_method(SGD(lr, momentum=0.9, dampening=0.0))
           .set_end_when(Trigger.max_epoch(windows))
           .set_compute_dtype(jnp.bfloat16)
           .set_log_interval(steps)
           .set_iterations_per_dispatch(per_dispatch))
    if configure is not None:
        configure(opt)
    opt.optimize()
    return opt


def losses_of(opt) -> List[float]:
    return [l for rec in opt.window_records for l in rec["losses"]]


def check_losses(losses: Sequence[float], n: int, what: str) -> None:
    check(len(losses) == n, f"{what}: {len(losses)} losses for {n} steps")
    check(bool(np.all(np.isfinite(losses))),
          f"{what}: non-finite loss in {losses}")


def check_on_devices(tree, devices: Sequence[Any], what: str) -> None:
    import jax
    want = set(devices)
    for leaf in jax.tree_util.tree_leaves(tree):
        check(leaf.devices() <= want,
              f"{what} lives on {leaf.devices()}, not on {want}")


def custom_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def resnet_batch(sizes: Sizes):
    rng = np.random.default_rng(SEED)
    x = rng.normal(size=(sizes.batch, sizes.image, sizes.image, 3))
    y = rng.integers(1, sizes.classes + 1, size=(sizes.batch,))
    return x.astype(np.float32), y


def make_resnet(sizes: Sizes, fused=False):
    """ResNet-50 when ``resnet_layers`` is (3, 4, 6, 3): what
    ``bigdl_tpu.models.resnet50(class_num, fused)`` returns."""
    from bigdl_tpu.models.resnet import Bottleneck, ResNet
    from bigdl_tpu.utils import set_seed
    set_seed(SEED)
    return ResNet(Bottleneck, list(sizes.resnet_layers), sizes.classes,
                  fused=fused)


def make_lm(sizes: Sizes):
    """The decoder LM exactly as ``bigdl-tpu-perf --model transformer-lm``
    builds it, with its criterion and one batch of tokens."""
    from bigdl_tpu.examples import perf
    from bigdl_tpu.utils import set_seed
    set_seed(SEED)
    args = argparse.Namespace(
        vocab_size=sizes.lm_vocab, hidden_size=sizes.lm_hidden,
        num_layers=sizes.lm_layers, num_heads=sizes.lm_heads,
        seq_len=sizes.lm_seq, remat=False, image_size=0, classes=0)
    model, criterion, make_batch = perf.build("transformer-lm", args)
    x, y = make_batch(sizes.lm_batch)
    return model, criterion, x, y


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_device(devices: Sequence[Any]) -> str:
    """The chip is a TPU the peak table knows; say what runs on it."""
    import jax
    import jaxlib
    from bigdl_tpu.telemetry.perf import (
        device_hbm_bytes_per_s, device_peak_flops,
    )
    d = devices[0]
    check(d.platform == "tpu",
          f"needs a TPU; JAX reports platform {d.platform!r}")
    tag = device_tag(devices)
    # an unknown TPU kind raises inside the lookups
    say("device", tag, jax=jax.__version__, jaxlib=jaxlib.__version__,
        libtpu=importlib.metadata.version("libtpu"),
        peak_bf16_flops=device_peak_flops(d.device_kind),
        hbm_bytes_per_s=device_hbm_bytes_per_s(d.device_kind))
    return tag


def phase_train(sizes: Sizes, devices: Sequence[Any], tag: str) -> None:
    """ResNet-50 through ``Optimizer.optimize()`` in dispatch windows."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu.core.module import partition

    x, y = resnet_batch(sizes)
    n = sizes.steps_per_window * sizes.windows
    opt = optimize(make_resnet(sizes), nn.CrossEntropyCriterion(), x, y,
                   steps=sizes.steps_per_window, windows=sizes.windows,
                   per_dispatch=sizes.steps_per_window, lr=0.02)
    losses = losses_of(opt)
    check_losses(losses, n, "train")
    k = sizes.steps_per_window
    first, last = float(np.mean(losses[:k])), float(np.mean(losses[-k:]))
    check(last < first, f"train: loss on the repeated batch did not fall "
                        f"(first window {first:.4f}, last {last:.4f})")
    check(len(opt.step_executables) == 1,
          f"train: the step compiled {len(opt.step_executables)} times "
          f"for one batch signature")
    check_on_devices(partition(opt.model)[0], devices, "a parameter")
    step = opt.step_executables[0]
    # the window step returns (params, buffers, optimizer state, losses)
    check(all(s.device_set <= set(devices)
              for s in _leaves(step.output_shardings)),
          "train: the step keeps state off the chip")
    recs = list(opt.window_records)[1:]     # window 1 bears the compile
    check(len(recs) >= 1, "train: needs a window after the compiling one")
    iters = sum(r["iterations"] for r in recs)
    first_rec = list(opt.window_records)[0]
    ms_device = (recs[-1]["t_device_ready"]
                 - first_rec["t_device_ready"]) / iters * 1e3
    ms_readback = (recs[-1]["t_ready"] - first_rec["t_ready"]) / iters * 1e3
    say("train", tag, model="resnet", layers=sizes.resnet_layers,
        batch=sizes.batch, image=sizes.image, dtype="bf16", steps=n,
        windows=sizes.windows,
        loss_first=losses[0], loss_last=losses[-1],
        loss_first_window=first, loss_last_window=last,
        compiles=len(opt.step_executables),
        lower_s=opt.step_lower_seconds, compile_s=opt.step_compile_seconds,
        ms_per_step_block_until_ready=ms_device,
        ms_per_step_readback=ms_readback,
        program=program_bytes(step), custom_calls=custom_calls(step),
        peak_bytes_in_use=[_gib(b) for b in peak_bytes(devices)])


def _leaves(tree) -> List[Any]:
    import jax
    return jax.tree_util.tree_leaves(
        tree, is_leaf=lambda s: hasattr(s, "device_set"))


def phase_kernels(sizes: Sizes, devices: Sequence[Any], tag: str,
                  interpret: bool = False) -> None:
    """The Pallas kernels of the main path, compiled, against what they
    replace.  ``interpret`` is for the CPU rehearsal in the tests: the
    kernels then run in the Pallas interpreter, and no custom call can
    be expected in the compiled text."""
    _flash_against_xla(sizes, tag, interpret)
    gc.collect()
    _lm_through_optimizer(sizes, tag, interpret)
    gc.collect()
    _fused_against_plain(sizes, devices, tag, interpret)


def _flash_against_xla(sizes: Sizes, tag: str, interpret: bool) -> None:
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.ops.attention_kernels import (
        flash_attention, xla_attention,
    )
    keys = jax.random.split(jax.random.key(SEED), 4)
    q, k, v, w = (jax.random.normal(kk, sizes.flash_shape, jnp.bfloat16)
                  for kk in keys)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=interpret)

    def xla(q, k, v):
        return xla_attention(q, k, v, causal=True)

    def grads(attend):
        def loss(q, k, v):
            out = attend(q, k, v).astype(jnp.float32)
            return jnp.sum(out * w.astype(jnp.float32))
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    t0 = time.perf_counter()
    flash_fwd = jax.jit(flash).lower(q, k, v).compile()
    flash_bwd = grads(flash).lower(q, k, v).compile()
    compile_s = time.perf_counter() - t0
    calls = (custom_calls(flash_fwd), custom_calls(flash_bwd))
    if not interpret:
        check(min(calls) > 0,
              "flash attention compiled without a tpu_custom_call")
    errs = {"out": _rel_err(flash_fwd(q, k, v), jax.jit(xla)(q, k, v))}
    for name, a, b in zip(("dq", "dk", "dv"), flash_bwd(q, k, v),
                          grads(xla)(q, k, v)):
        errs[name] = _rel_err(a, b)
    say("kernels.flash", tag, shape=sizes.flash_shape, causal=True,
        dtype="bf16", compile_s=compile_s, custom_calls=calls,
        rel_err_vs_xla=errs, tolerance=BF16_TOL)
    for name, e in errs.items():
        check(np.isfinite(e) and e <= BF16_TOL,
              f"flash attention {name} differs from XLA attention by "
              f"{e:.3g} of its largest value (tolerance {BF16_TOL})")


def _rel_err(a, b) -> float:
    """Largest difference over the reference's largest magnitude."""
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def _lm_through_optimizer(sizes: Sizes, tag: str, interpret: bool) -> None:
    model, criterion, x, y = make_lm(sizes)
    half = sizes.lm_steps // 2
    opt = optimize(model, criterion, x, y, steps=half, windows=2, lr=0.05)
    losses = losses_of(opt)
    check_losses(losses, 2 * half, "lm")
    check(losses[-1] < losses[0],
          f"lm: loss on the repeated batch did not fall: {losses}")
    check(len(opt.step_executables) == 1,
          f"lm: the step compiled {len(opt.step_executables)} times")
    step = opt.step_executables[0]
    calls = custom_calls(step)
    if not interpret:
        check(calls > 0,
              "lm: no tpu_custom_call in the compiled step: attention "
              "did not take the flash kernel")
    say("kernels.lm", tag, layers=sizes.lm_layers, hidden=sizes.lm_hidden,
        heads=sizes.lm_heads, seq=sizes.lm_seq, batch=sizes.lm_batch,
        vocab=sizes.lm_vocab, dtype="bf16", losses=losses,
        lower_s=opt.step_lower_seconds, compile_s=opt.step_compile_seconds,
        custom_calls=calls, program=program_bytes(step))


def _fused_against_plain(sizes: Sizes, devices: Sequence[Any], tag: str,
                         interpret: bool) -> None:
    """ResNet-50 with the fused conv+BN+ReLU bottleneck kernels against
    the plain model: same seed, same batch, three single-step
    dispatches.  Decides nothing about which is faster."""
    import jax
    import bigdl_tpu.nn as nn
    from bigdl_tpu.core.module import partition

    x, y = resnet_batch(sizes)
    init = [np.asarray(l, np.float32) for l in jax.tree_util.tree_leaves(
        partition(make_resnet(sizes))[0])]
    runs = {}
    for name, fused in (("plain", False),
                        ("fused", "force" if interpret else True)):
        opt = optimize(make_resnet(sizes, fused=fused),
                       nn.CrossEntropyCriterion(), x, y,
                       steps=sizes.fused_steps, lr=0.02)
        losses = losses_of(opt)
        check_losses(losses, sizes.fused_steps, name)
        step = opt.step_executables[0]
        runs[name] = {
            "losses": losses,
            "params": [np.asarray(l, np.float32)
                       for l in jax.tree_util.tree_leaves(
                           partition(opt.model)[0])],
            "custom_calls": custom_calls(step),
            "lower_s": opt.step_lower_seconds,
            "compile_s": opt.step_compile_seconds,
            "program": program_bytes(step),
            "peak": [_gib(b) for b in peak_bytes(devices)],
        }
        del opt, step
        gc.collect()
    plain, fused = runs["plain"], runs["fused"]
    if not interpret:
        check(fused["custom_calls"] > 0,
              "fused ResNet-50 compiled without a tpu_custom_call")
    check(plain["custom_calls"] == 0,
          "the plain ResNet-50 step holds a tpu_custom_call")
    loss_err = abs(fused["losses"][0] - plain["losses"][0]) \
        / abs(plain["losses"][0])
    moved = np.sqrt(sum(float(np.sum((p - i) ** 2))
                        for p, i in zip(plain["params"], init)))
    apart = np.sqrt(sum(float(np.sum((f - p) ** 2))
                        for f, p in zip(fused["params"], plain["params"])))
    update_err = float(apart / moved)
    say("kernels.fused", tag, model="resnet", layers=sizes.resnet_layers,
        batch=sizes.batch, image=sizes.image, steps=sizes.fused_steps,
        plain_losses=plain["losses"], fused_losses=fused["losses"],
        first_loss_rel_err=loss_err, loss_tolerance=BF16_TOL,
        update_rel_err=update_err, update_tolerance=BF16_UPDATE_TOL,
        custom_calls=(plain["custom_calls"], fused["custom_calls"]),
        lower_s=(plain["lower_s"], fused["lower_s"]),
        compile_s=(plain["compile_s"], fused["compile_s"]),
        plain_program=plain["program"], fused_program=fused["program"],
        peak_after_plain=plain["peak"], peak_after_fused=fused["peak"])
    check(loss_err <= BF16_TOL,
          f"fused first-step loss {fused['losses'][0]:.5f} is "
          f"{loss_err:.3g} from the plain {plain['losses'][0]:.5f}")
    check(np.isfinite(update_err) and update_err <= BF16_UPDATE_TOL,
          f"after {sizes.fused_steps} steps the fused parameters are "
          f"{update_err:.3g} of the plain update away from the plain ones")


def phase_serve(sizes: Sizes, devices: Sequence[Any], tag: str) -> None:
    """Mixed-length prompts, all in flight at once, through the path
    ``python -m bigdl_tpu.serving --generate`` takes; every row must
    equal the model's own sequential ``generate()``."""
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.models import transformer_lm
    from bigdl_tpu.serving import ModelServer
    from bigdl_tpu.utils import set_seed

    set_seed(SEED)
    model = transformer_lm(
        vocab_size=sizes.lm_vocab, hidden_size=sizes.lm_hidden,
        num_layers=sizes.lm_layers, num_heads=sizes.lm_heads,
        filter_size=sizes.serve_filter,
        max_len=sizes.serve_max_len).eval_mode()
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(1, sizes.lm_vocab + 1, n).astype(np.int32)
               for n in sizes.serve_prompt_lens]
    new = sizes.serve_new_tokens

    server = ModelServer(generator=model, slots=sizes.serve_slots)
    try:
        first_token_at: Dict[int, float] = {}
        t0 = time.perf_counter()
        futures = []
        for i, p in enumerate(prompts):
            def on_token(*_a, i=i):
                first_token_at.setdefault(i, time.perf_counter())
            futures.append((time.perf_counter(),
                            server.submit_generate_async(
                                p, new, on_token=on_token)))
        rows = [np.asarray(f.result(timeout=900)) for _, f in futures]
        total_s = time.perf_counter() - t0
        ttft = [first_token_at[i] - t_sub
                for i, (t_sub, _) in enumerate(futures)]
        stats = server.generation.stats()
        counts = server.generation.pool.trace_counts
        compiles = sum(v if isinstance(v, int) else sum(v.values())
                       for v in counts.values())
    finally:
        server.shutdown()

    # the model's own generate(), one request at a time; jitted with the
    # model as an argument so that each prompt length compiles one
    # program instead of some two hundred single ops
    reference = model.clone().eval_mode()
    generate = jax.jit(lambda m, p: m.generate(p, new))
    t0 = time.perf_counter()
    expected = [np.asarray(generate(
        reference, jnp.asarray(p, jnp.int32)[None]))[0] for p in prompts]
    sequential_s = time.perf_counter() - t0
    for p, row, want in zip(prompts, rows, expected):
        check(row.shape == (len(p) + new,),
              f"serve: a row of shape {row.shape} for a {len(p)}-token "
              f"prompt and {new} new tokens")
        check(bool(np.all(row[len(p):] >= 1)),
              f"serve: the {len(p)}-token prompt was not decoded to the "
              f"end: {row[len(p):]}")
        check(np.array_equal(row, want),
              f"serve: the {len(p)}-token prompt's tokens differ from "
              f"generate(): served {row[len(p):]}, sequential "
              f"{want[len(p):]}")
    say("serve", tag, layers=sizes.lm_layers, hidden=sizes.lm_hidden,
        heads=sizes.lm_heads, vocab=sizes.lm_vocab,
        max_len=sizes.serve_max_len, slots=sizes.serve_slots,
        requests=len(prompts), prompt_lens=sizes.serve_prompt_lens,
        new_tokens=int(stats["tokens_emitted"]),
        equal_to_generate=len(prompts), program_traces=compiles,
        decode_traces=counts["decode"],
        ttft_s_min=float(min(ttft)), ttft_s_max=float(max(ttft)),
        total_s=total_s, sequential_generate_s=sequential_s,
        peak_bytes_in_use=[_gib(b) for b in peak_bytes(devices)])


def phase_four_chips(sizes: Sizes, devices: Sequence[Any], tag: str) -> None:
    """The sharded training steps on four devices against the same steps
    on one: ResNet-50 under ``PartitionPlan(dp=4)`` and the decoder LM
    under ``PartitionPlan(fsdp=2, tp=2)``, through
    ``Optimizer.set_partition_plan``."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu.parallel import MeshConfig
    from bigdl_tpu.parallel.plan import PartitionPlan

    check(len(devices) >= 4, f"needs four devices, has {len(devices)}")
    four = list(devices[:4])

    def one_device(opt):
        opt.set_mesh(MeshConfig(data=1))

    x, y = resnet_batch(sizes)
    _sharded_against_one(
        "chips4.resnet_dp4", tag, four, one_device,
        lambda: (make_resnet(sizes), nn.CrossEntropyCriterion(), x, y),
        PartitionPlan(dp=4), steps=sizes.dp_steps, lr=0.02,
        collectives=("all-reduce",),
        info=dict(model="resnet", layers=sizes.resnet_layers,
                  global_batch=sizes.batch, image=sizes.image))
    gc.collect()
    _sharded_against_one(
        "chips4.lm_fsdp2_tp2", tag, four, one_device,
        lambda: make_lm(sizes), PartitionPlan(fsdp=2, tp=2),
        steps=sizes.plan_lm_steps, lr=0.05,
        collectives=("all-gather", "all-reduce"), sharded_params=True,
        info=dict(layers=sizes.lm_layers, hidden=sizes.lm_hidden,
                  seq=sizes.lm_seq, batch=sizes.lm_batch,
                  vocab=sizes.lm_vocab))


_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "all-to-all", "collective-permute")


def _sharded_against_one(phase: str, tag: str, four: List[Any], one_device,
                         build, plan, *, steps: int, lr: float,
                         collectives: Sequence[str], info: Dict[str, Any],
                         sharded_params: bool = False) -> None:
    import jax
    from bigdl_tpu.core.module import partition

    model, criterion, x, y = build()
    opt = optimize(model, criterion, x, y, steps=steps, lr=lr,
                   configure=lambda o: o.set_partition_plan(plan))
    losses = losses_of(opt)
    check_losses(losses, steps, phase)
    step = opt.step_executables[0]
    text = step.as_text()
    found = {c: text.count(f" {c}(") + text.count(f" {c}-start(")
             for c in _COLLECTIVES}
    for c in collectives:
        check(found[c] > 0, f"{phase}: the plan implies {c}, and the "
                            f"compiled step has none: {found}")
    # the step's first four inputs are (params, buffers, optimizer state,
    # batch input): each must be laid out over all four devices
    in_shardings = step.input_shardings[0]
    for what, tree in zip(("parameters", "buffers", "optimizer state",
                           "the batch"), in_shardings[:4]):
        for s in _leaves(tree):
            check(s.device_set == set(four),
                  f"{phase}: the step takes {what} on "
                  f"{sorted(d.id for d in s.device_set)}, not on four")
    check(not in_shardings[3].is_fully_replicated,
          f"{phase}: the batch is replicated, not sharded")
    params = jax.tree_util.tree_leaves(partition(opt.model)[0])
    ids = {s.device.id for p in params for s in p.addressable_shards}
    check(ids == {d.id for d in four},
          f"{phase}: parameter shards sit on devices {sorted(ids)}")
    split = sum(1 for p in params
                if p.addressable_shards[0].data.shape != p.shape)
    if sharded_params:
        check(split > 0, f"{phase}: no parameter is split across devices")
    peaks = [_gib(b) for b in peak_bytes(four)]
    lower_s, compile_s = opt.step_lower_seconds, opt.step_compile_seconds
    del opt, step, params, model
    gc.collect()

    model, criterion, x, y = build()
    ref = optimize(model, criterion, x, y, steps=steps, lr=lr,
                   configure=one_device)
    ref_losses = losses_of(ref)
    check_losses(ref_losses, steps, f"{phase} on one device")
    ref_ids = {s.device.id for p in jax.tree_util.tree_leaves(
        partition(ref.model)[0]) for s in p.addressable_shards}
    check(len(ref_ids) == 1, f"{phase}: the one-device comparator ran "
                             f"on devices {sorted(ref_ids)}")
    err = float(np.max(np.abs(np.subtract(losses, ref_losses))
                       / np.abs(ref_losses)))
    say(phase, tag, plan=plan.describe(), steps=steps, dtype="bf16",
        **info, losses=losses, one_device_losses=ref_losses,
        loss_rel_err=err, tolerance=BF16_TOL,
        shard_device_ids=sorted(ids), split_parameters=split,
        collectives={c: n for c, n in found.items() if n},
        lower_s=lower_s, compile_s=compile_s, peak_bytes_in_use=peaks)
    check(err <= BF16_TOL,
          f"{phase}: losses {losses} are {err:.3g} from the one-device "
          f"losses {ref_losses}")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run the four-chip sharded steps and their "
                         "one-device comparators, and nothing else")
    args = ap.parse_args(argv)

    import jax
    from bigdl_tpu.utils.compile_cache import enable_compile_cache
    devices = jax.devices()
    tag = phase_device(devices)
    check(len(devices) == args.chips,
          f"--chips {args.chips} on a machine with {len(devices)} "
          f"device(s)")
    say("device", tag, compile_cache=enable_compile_cache())
    if args.chips == 4:
        phase_four_chips(REAL, devices, tag)
    else:
        for phase in (phase_train, phase_kernels, phase_serve):
            phase(REAL, devices, tag)
            gc.collect()    # drop this phase's arrays before the next
    print(final_line(devices), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
