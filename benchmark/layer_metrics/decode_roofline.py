"""Time the bytes of one pooled decode step need at the HBM peak (as the
configuration's kind counts them, ``decode_step_bytes``: every weight
once, and the keys and values of the positions the active slots have
live, from shapes and the stamps), over the decode program's mean device
time in the trace (``jit__decode`` on the XLA Modules line). HBM-bound.
"""
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "serve_itl_p95_ms"
DEVICE = True   # True: only a chip run can give it


def read(obs):
    t, traced = obs.get("trace"), obs.get("traced")
    if t is None or not traced or not t.devices():
        return None
    from harness import manifest
    secs, n = t.module_seconds("jit__decode")
    if n == 0:
        return None
    live, steps = 0, set()
    for r in obs["requests"]:
        for i, s in enumerate(r["stamps"]):
            if traced[0] <= s < traced[1]:
                live += r["prompt_len"] + i
                steps.add(round(s, 2))
    if not steps:
        return None
    size = {"bfloat16": 2, "float16": 2, "float32": 4}
    serving = obs["cfg"]["serving"]
    nbytes = manifest.load_kind(obs["kind"]).decode_step_bytes(
        obs["cfg"], live / len(steps), size[serving["weights_dtype"]],
        size[serving["cache_dtype"]])
    return 100.0 * (nbytes / obs["peaks"]["hbm_bytes_per_s"]) / (secs / n)
