"""``python -m bigdl_tpu.serving`` — stdin/stdout serving demo.

Serves a zoo model behind the dynamic batcher.  Each stdin line is one
sample: whitespace-separated floats, reshaped to the model's per-sample
input shape.  Each stdout line is ``<index>\t<class>\t<score>`` (argmax
1-based, matching ``Predictor.predict_class``).  The final metrics
snapshot goes to stderr as JSON; ``--log-dir`` additionally publishes
TensorBoard event files via the visualization writer.

    # 3 random "MNIST" samples through int8 LeNet-5, batched:
    python -m bigdl_tpu.serving --model lenet5 --quantize --synthetic 3

``--generate N`` switches to continuous-batching generation over an
incremental-decode zoo model: each stdin line is a prompt of
whitespace-separated 1-based token ids, each stdout line is
``<index>\t<generated ids>`` (prompt + up to N new tokens, greedy), and
mixed-length prompts share the fixed KV slot pool mid-flight:

    python -m bigdl_tpu.serving --model transformer_lm_tiny \
        --generate 16 --slots 4 --synthetic 8
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import List

import numpy as np


def _raise(e: Exception):
    raise e


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m bigdl_tpu.serving",
        description="dynamic-batching inference demo over a zoo model")
    p.add_argument("--model", default="lenet5",
                   help="zoo model name (see bigdl_tpu.models.zoo)")
    p.add_argument("--max-batch", type=int, default=8)
    p.add_argument("--batch-timeout-ms", type=float, default=5.0)
    p.add_argument("--queue-capacity", type=int, default=None)
    p.add_argument("--policy", default="block",
                   choices=("block", "reject", "shed_oldest"))
    p.add_argument("--quantize", action="store_true",
                   help="serve the int8-quantized model (nn.quantized)")
    p.add_argument("--synthetic", type=int, default=None, metavar="N",
                   help="serve N random samples instead of reading stdin")
    p.add_argument("--generate", type=int, default=None, metavar="MAX_NEW",
                   help="continuous-batching generation mode: stdin "
                        "lines are token-id prompts; emit up to MAX_NEW "
                        "greedy tokens each through the KV slot pool")
    p.add_argument("--slots", type=int, default=4,
                   help="KV slot-pool width for --generate")
    p.add_argument("--replicas", type=int, default=1, metavar="N",
                   help="with --generate: spawn N in-process replicas "
                        "behind the serving-fabric Router (session-"
                        "affine consistent hashing, health registry, "
                        "SLO shedding) instead of one engine")
    p.add_argument("--no-warmup", action="store_true",
                   help="skip pre-compiling the bucket shapes")
    p.add_argument("--log-dir", default=None,
                   help="publish metrics as TensorBoard event files here")
    return p


def main(argv=None, stdin=None, stdout=None, stderr=None) -> int:
    args = build_parser().parse_args(argv)
    stdin = stdin if stdin is not None else sys.stdin
    stdout = stdout if stdout is not None else sys.stdout
    stderr = stderr if stderr is not None else sys.stderr

    from bigdl_tpu.models import zoo, zoo_sample_shape
    from bigdl_tpu.serving import ModelServer
    from bigdl_tpu.serving.server import install_shutdown_signals

    model = zoo(args.model)
    if args.replicas < 1:
        print(f"error: --replicas must be >= 1, got {args.replicas}",
              file=stderr)
        return 2
    if args.generate is not None:
        if args.quantize:
            # dropping the flag silently would serve fp32 while the
            # operator believes int8; the quantized wrappers also lack
            # the incremental-decode API the slot pool needs
            print("error: --quantize is not supported with --generate "
                  "(the int8 path has no KV-cache decode)", file=stderr)
            return 2
        if args.replicas > 1:
            return _fabric_main(args, model, stdin, stdout, stderr)
        return _generate_main(args, model, stdin, stdout, stderr)
    if args.replicas > 1:
        print("error: --replicas needs --generate (the fabric routes "
              "generation requests)", file=stderr)
        return 2
    shape = zoo_sample_shape(args.model)
    if args.quantize:
        from bigdl_tpu.nn.quantized import quantize
        model = quantize(model)

    server = ModelServer(
        model, max_batch=args.max_batch,
        batch_timeout_ms=args.batch_timeout_ms,
        queue_capacity=args.queue_capacity, admission=args.policy)
    if not args.no_warmup:
        server.warmup(np.zeros(shape, np.float32))

    if args.synthetic is not None:
        rng = np.random.default_rng(0)
        samples = [rng.normal(size=shape).astype(np.float32)
                   for _ in range(args.synthetic)]
    else:
        samples = None  # stream stdin below

    def sample_lines():
        if samples is not None:
            yield from samples
            return
        for line in stdin:
            if not line.strip():
                continue
            yield np.array(line.split(), dtype=np.float32).reshape(shape)

    futures: List = []
    restore_signals = install_shutdown_signals(server)
    try:
        try:
            for s in sample_lines():
                # reject/shed_oldest are part of the demo: an overloaded
                # submit becomes an error row, not a crash
                try:
                    futures.append(server.submit_async(s))
                except Exception as e:
                    futures.append(e)
        except KeyboardInterrupt:
            # SIGTERM/SIGINT mid-stream: stop reading, but the requests
            # already admitted still drain and print below
            print(f"interrupted: draining {len(futures)} in-flight "
                  "requests", file=stderr)
        for i, f in enumerate(futures):
            try:
                row = np.asarray(f.result() if not isinstance(f, Exception)
                                 else _raise(f))
            except Exception as e:
                print(f"{i}\tERROR\t{type(e).__name__}", file=stdout)
                continue
            cls = int(np.argmax(row)) + 1
            print(f"{i}\t{cls}\t{float(np.max(row)):.6f}", file=stdout)
    finally:
        server.shutdown(drain=True)
        restore_signals()

    snap = server.metrics.snapshot()
    print(json.dumps(snap, sort_keys=True), file=stderr)
    if args.log_dir:
        from bigdl_tpu.visualization import ServingSummary
        summary = ServingSummary(args.log_dir, f"serve-{args.model}")
        server.publish_metrics(summary, step=0)
        summary.close()
        print(f"metrics event file: {summary.writer_path}", file=stderr)
    return 0


def _drive_generation(args, model, stdin, stdout, stderr,
                      submit) -> None:
    """The shared --generate prompt harness: build the synthetic or
    stdin prompt stream, submit each line fallibly through
    ``submit(i, prompt) -> Future`` (a malformed line becomes ONE
    ERROR row, never aborting the stream), drain on interrupt, and
    print one ``<index>\\t<tokens>`` row per prompt."""
    if args.synthetic is not None:
        rng = np.random.default_rng(0)
        vocab = model.embedding.weight.shape[0] - 1
        max_p = max(1, min(model.max_len - args.generate, 16))
        prompts = [rng.integers(1, vocab + 1,
                                rng.integers(1, max_p + 1)).astype(np.int32)
                   for _ in range(args.synthetic)]
    else:
        prompts = None

    def prompt_lines():
        if prompts is not None:
            yield from prompts
            return
        for line in stdin:
            if line.strip():
                yield line   # parsed (fallibly) in the submit loop

    futures: List = []
    try:
        for i, p in enumerate(prompt_lines()):
            try:
                if isinstance(p, str):
                    p = np.array(p.split(), dtype=np.int32)
                futures.append(submit(i, p))
            except Exception as e:
                futures.append(e)
    except KeyboardInterrupt:
        print(f"interrupted: draining {len(futures)} in-flight "
              "generations", file=stderr)
    for i, f in enumerate(futures):
        try:
            row = np.asarray(f.result() if not isinstance(f, Exception)
                             else _raise(f))
        except Exception as e:
            print(f"{i}\tERROR\t{type(e).__name__}", file=stdout)
            continue
        print(f"{i}\t" + " ".join(str(int(t)) for t in row),
              file=stdout)


def _generate_main(args, model, stdin, stdout, stderr) -> int:
    """--generate mode: prompt lines in, greedy continuations out, all
    sharing the continuous-batching slot pool."""
    from bigdl_tpu.serving import ModelServer
    from bigdl_tpu.serving.server import install_shutdown_signals

    server = ModelServer(
        generator=model, slots=args.slots,
        gen_queue_capacity=args.queue_capacity, admission=args.policy)
    restore_signals = install_shutdown_signals(server)
    try:
        _drive_generation(
            args, model, stdin, stdout, stderr,
            lambda i, p: server.submit_generate_async(p, args.generate))
    finally:
        server.shutdown(drain=True)
        restore_signals()

    print(json.dumps(server.generation_stats(), sort_keys=True),
          file=stderr)
    return 0


def _fabric_main(args, model, stdin, stdout, stderr) -> int:
    """--generate --replicas N: the local serving fabric — N in-process
    ModelServer replicas behind the session-affine Router, health
    published through the file-transport registry in a temp dir."""
    import shutil
    import tempfile

    from bigdl_tpu.serving import ModelServer, Replica, Router
    from bigdl_tpu.serving.server import install_shutdown_signals

    fleet_dir = tempfile.mkdtemp(prefix="bigdl-fabric-")
    replicas = [
        Replica(i, ModelServer(generator=model, slots=args.slots,
                               gen_queue_capacity=args.queue_capacity,
                               admission=args.policy),
                snapshot_dir=fleet_dir, publish_interval_s=0.1)
        for i in range(args.replicas)]
    router = Router(replicas=replicas, snapshot_dir=fleet_dir,
                    poll_interval_s=0.02)

    fleet = None
    # same SIGTERM/SIGINT contract as the single-engine mode: unwind
    # into the drain instead of dying with futures in flight (the
    # handler only raises KeyboardInterrupt; its argument is unused)
    restore_signals = install_shutdown_signals(router)
    try:
        # a small session-key population so affinity is visible in
        # the stats: same key -> same replica
        _drive_generation(
            args, model, stdin, stdout, stderr,
            lambda i, p: router.submit_generate_async(
                p, args.generate,
                session=f"session-{i % (2 * args.replicas)}"))
        # read the fleet table while the snapshots are still on disk
        # (closing a replica removes its file so the registry forgets
        # it instead of reporting a stale ghost)
        fleet = router.registry.fleet()
    finally:
        router.shutdown(drain=True)
        restore_signals()
        shutil.rmtree(fleet_dir, ignore_errors=True)

    out = {"router": router.stats(), "fleet": fleet}
    print(json.dumps(out, sort_keys=True, default=str), file=stderr)
    return 0


if __name__ == "__main__":
    from bigdl_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
