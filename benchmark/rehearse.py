#!/usr/bin/env python3
"""The CPU rehearsal: every code path of every cell at a tiny size.

    python3 benchmark/rehearse.py [--workload <name>] [--seconds 4]

Not the driver's command.  It runs each cell of BENCHMARK.json on the
CPU (four virtual devices, for a four-chip cell) with the tiny
configurations and traffic under ``benchmark/rehearsal/``, with and
without the trace, names the device as the CPU it is and prints no
device metric: ``train_mfu``, the serving times, roofline and idle
shares read "not measured".  It finds wrong paths, arguments and control
flow before a chip call does.  The serving cells' check walks the model
block by block as on the chip; the plans under ``rehearsal/specs/``
(written-out specs with leaves no cell has yet, such as experts stacked
``[E, out, in]``) go through the same walk (``block_peak.py``).
"""
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH_DIR, os.path.dirname(BENCH_DIR)]


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default=None)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--seed", type=int, default=2_345_678_901)
    args = ap.parse_args(argv)
    import run
    from harness import manifest
    names = [w["name"] for w in manifest.manifest()["workloads"]
             if args.workload in (None, w["name"])]
    for name in names:
        for trace in (0, 1):
            print(f"== rehearsal: {name} --trace {trace} (CPU, tiny sizes)",
                  flush=True)
            run.main(["--workload", name, "--seed", str(args.seed),
                      "--seconds", str(args.seconds), "--trace", str(trace)],
                     rehearsal_dir=os.path.join(BENCH_DIR, "rehearsal"),
                     t_start=time.perf_counter())
    if args.workload is None:
        import glob
        import block_peak
        for plan in sorted(glob.glob(os.path.join(
                BENCH_DIR, "rehearsal", "specs", "*.json"))):
            print(f"== rehearsal: block by block through {plan}", flush=True)
            block_peak.walk(manifest.load_json(plan), args.seed)
    return 0


if __name__ == "__main__":
    sys.exit(main())
