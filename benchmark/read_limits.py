#!/usr/bin/env python3
"""Read, in one process, the numbers that a training cell's limits are
set from: the program's gaps from the reference on many seeds, and the
control's (the reference in a lower precision in the program's place) on
the first few.

    python3 benchmark/read_limits.py --workload <name> --seeds 11,12,13 \
        --control int8 --control-seeds 3 [--seconds 2] [--rehearsal]

Not the driver's command.  Every seed is one whole run of the cell
(``run.main``) with a short window, so what is read is what a run
compares; the lines to read are ``[sound]`` and ``[control.<precision>]``.
"""
import argparse
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH_DIR, os.path.dirname(BENCH_DIR)]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="int8")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", default="2")
    ap.add_argument("--rehearsal", action="store_true",
                    help="on the CPU, at the tiny sizes under rehearsal/")
    args = ap.parse_args()
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import run
    for i, seed in enumerate(args.seeds.split(",")):
        print(f"== seed {seed}", flush=True)
        run.main(["--workload", args.workload, "--seed", seed,
                  "--seconds", args.seconds, "--trace", "0", "--control",
                  args.control if i < args.control_seeds else ""],
                 rehearsal_dir=(os.path.join(BENCH_DIR, "rehearsal")
                                if args.rehearsal else None))
    return 0


if __name__ == "__main__":
    sys.exit(main())
