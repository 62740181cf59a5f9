#!/usr/bin/env python3
"""What the serving check would hold of a model it walks block by block:
make every block of a written-out spec in the served dtype, cast it to
float32 and free it, beside hidden states of the size the check carries,
and print the bytes in use after each block and the process's peak.

    python3 benchmark/block_peak.py --plan benchmark/tests/data/cut_4p73b.json

Not the driver's command.  A plan is a data file: ``dtype``, ``hidden``
(requests, positions, width of the float32 hidden states), ``layers``,
and the leaves ``[path, shape]`` of the ``embedding``, of one ``layer``
(expanded under ``.blocks[i]``) and of the ``head``.  It runs on the
device it is started on and fails where the peak holds more than one
block twice over and the hidden states; on the CPU (``rehearse.py``),
which reports no peak, it counts bytes only.
"""
import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH_DIR, os.path.dirname(BENCH_DIR)]


def expand(plan):
    """``(spec, blocks)`` as a kind's ``param_spec`` and ``param_blocks``
    would give them."""
    spec = [(p, tuple(s)) for p, s in plan["embedding"]]
    blocks = [("embedding", list(range(len(spec))))]
    for i in range(plan["layers"]):
        first = len(spec)
        spec += [(f".blocks[{i}]{p}", tuple(s)) for p, s in plan["layer"]]
        blocks.append((f"blocks[{i}]", list(range(first, len(spec)))))
    first = len(spec)
    spec += [(p, tuple(s)) for p, s in plan["head"]]
    return spec, blocks + [("head", blocks[0][1] + list(range(first, len(spec))))]


def walk(plan, seed: int = 2_345_678_901):
    import math

    import jax
    import jax.numpy as jnp
    from harness import result, weights
    spec, blocks = expand(plan)
    dev = jax.local_devices()[0]

    def in_use(key):
        return (dev.memory_stats() or {}).get(key)
    n_params = sum(math.prod(s) for _, s in spec)
    hidden = jnp.zeros(tuple(plan["hidden"]), jnp.float32)
    largest = 0
    for name, params in weights.blocks_float32(
            spec, blocks, seed, jnp.dtype(plan["dtype"])):
        nbytes = sum(l.nbytes for l in params.values())
        largest = max(largest, nbytes)
        result.say("block", name=name, leaves=len(params),
                   ranks=sorted({l.ndim for l in params.values()}),
                   float32_bytes=nbytes, bytes_in_use=in_use("bytes_in_use"))
        del params
    out = {"parameters": n_params, "model_float32_bytes": 4 * n_params,
           "largest_block_float32_bytes": largest,
           "hidden_bytes": hidden.nbytes, "platform": dev.platform,
           "peak_bytes_in_use": in_use("peak_bytes_in_use"),
           "bytes_limit": in_use("bytes_limit")}
    result.say("block_peak", **out)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--plan", required=True)
    ap.add_argument("--seed", type=int, default=2_345_678_901)
    args = ap.parse_args(argv)
    with open(args.plan, encoding="utf-8") as f:
        out = walk(json.load(f), args.seed)
    peak = out["peak_bytes_in_use"]
    if peak is not None and peak >= out["largest_block_float32_bytes"] * 1.5 \
            + out["hidden_bytes"] + (1 << 28):
        print("block_peak: the peak holds more than one block twice over "
              "and the hidden states", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
