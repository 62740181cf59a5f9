"""Record the small device trace that tests/test_trace.py reduces.

Run on the chip (``chiprun -- python3 benchmark/tests/record_trace.py``):
a named jitted matmul chain with a host pause in the middle, traced for
well under a second, so the file stays small enough to commit.  Prints
the planes and lines it finds so that the reduction can be checked by eye.
"""
import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 1
    out = os.path.join("chiprun_out", "trace_probe")
    shutil.rmtree(out, ignore_errors=True)

    @jax.jit
    def probe_step(x, w):
        for _ in range(4):
            x = jnp.tanh(x @ w)
        return x

    n = len(jax.devices())
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    w = jnp.ones((1024, 1024), jnp.bfloat16) * 0.01
    if n > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        mesh = Mesh(jax.devices(), ("d",))
        x = jax.device_put(x, NamedSharding(mesh, P("d", None)))
        w = jax.device_put(w, NamedSharding(mesh, P(None, "d")))
    probe_step(x, w).block_until_ready()
    jax.profiler.start_trace(out)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.submit"):
        for _ in range(3):
            x = probe_step(x, w)
        x.block_until_ready()
    with jax.profiler.TraceAnnotation("bench.pause"):
        time.sleep(0.02)
    with jax.profiler.TraceAnnotation("bench.wait_window"):
        for _ in range(3):
            x = probe_step(x, w)
        x.block_until_ready()
    window = time.perf_counter() - t0
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(out, "plugins", "profile", "*", "*.xplane.pb"))[0]
    print("window_s", window, "file", path, os.path.getsize(path))
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  LINE", repr(line.name), len(evs))
            for ev in evs[:6]:
                print("     ", repr(ev.name), ev.start_ns, ev.duration_ns,
                      dict(list(ev.stats)[:8]) if hasattr(ev, "stats") else "")
    shutil.copy(path, os.path.join("chiprun_out", f"probe_{n}chip.xplane.pb"))
    print("memory_stats", {k: v for k, v in (jax.devices()[0].memory_stats() or {}).items()})
    return 0


if __name__ == "__main__":
    sys.exit(main())
