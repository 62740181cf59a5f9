#!/usr/bin/env python3
"""The benchmark's command: one run of one cell.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Reads the cell from BENCHMARK.json, finds its configuration, its traffic
or job and its per-layer readers by name, runs it on the TPU it is
started on (no TPU, or fewer chips than the cell asks for: a non-zero
exit and no result), and prints one JSON object as its last line.
"""
import time

T_PROCESS_START = time.perf_counter()

import argparse    # noqa: E402
import os          # noqa: E402
import sys         # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH_DIR, os.path.dirname(BENCH_DIR)]


def main(argv=None, rehearsal_dir=None, t_start=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default="",
                    help="also read the control's numbers: the reference in "
                         "this lower precision in the program's place "
                         "(float8, int8, bfloat16); never set by the driver")
    args = ap.parse_args(argv)

    from harness import device, manifest
    man = manifest.manifest()
    cell = manifest.cell(man, args.workload)
    if rehearsal_dir is None:
        cfg = manifest.config_of(man, cell["config"])
        spec = manifest.traffic_of(cell["traffic"])
        devices = device.require_tpu(cell["chips"])
        device.enable_compile_cache()
    else:
        import jax
        cfg = manifest.load_json(os.path.join(
            rehearsal_dir, "configs", cell["config"] + ".json"))
        spec = manifest.load_json(os.path.join(
            rehearsal_dir, "traffic", cell["traffic"] + ".json"))
        devices = list(jax.devices()[:cell["chips"]])
    import bigdl_tpu  # noqa: F401  (absent: no result, a non-zero exit)
    t0 = T_PROCESS_START if t_start is None else t_start
    if spec["kind"] == "train":
        from harness import train_cell
        train_cell.run(man, cell, cfg, spec, args, t0, devices,
                       rehearsal=rehearsal_dir is not None)
    elif spec["kind"] == "serve":
        from harness import serve_cell
        serve_cell.run(man, cell, cfg, spec, args, t0, devices,
                       rehearsal=rehearsal_dir is not None)
    else:
        raise SystemExit(f"benchmark: unknown traffic kind {spec['kind']!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
