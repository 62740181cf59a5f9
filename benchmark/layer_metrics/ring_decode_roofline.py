"""Least time of the window layers' attention in the traced decode steps,
over the device time of the operations that touch a ring there.

The least time is the larger of the bytes over the HBM peak and the
operations over the bf16 peak, as the configuration's kind counts them
(``ring_step_cost``) from the ring places the steps' queries could attend:
the engine's own count (``stats()``: ``ring_positions_live``, each active
slot's positions up to its window, summed over the window layers at each
decode dispatch), as a difference across the window and carried over to
the trace by the decode steps in each (``jit__decode`` executions on the
XLA Modules line over ``decode_steps``): the cell this reads keeps every
slot taken, so a step of the trace does what a step of the window does.
Live places, not the whole rings that a step reads.

The device time is that of the operations inside the decode programs whose
text names an operand of a ring leaf's shape (``[slots, key/value heads,
ring places, head width]`` in the cache's dtype, from the kind's
``ring_leaf_shape``): the window layers' attention over the rings and the
writes into them (a joint pass's chunk among them), whatever implements
either.  An operation is found by that shape, not by its name or target.
While a step reads every slot's ring whole the share cannot pass the live
places over the places read.  Where the program has no such counter or
leaf nothing is read.
"""
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "serve_itl_p95_ms"
DEVICE = True   # True: only a chip run can give it

_DTYPE = {"bfloat16": "bf16", "float16": "f16", "float32": "f32"}
_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def read(obs):
    t = obs.get("trace")
    if t is None or not t.devices() or not t.modules:
        return None
    from harness import manifest
    from harness import program_spans as ps
    from harness import trace as tr
    kind = manifest.load_kind(obs["kind"])
    if not hasattr(kind, "ring_step_cost"):
        return None
    steps = ps.delta(obs, "decode_steps")
    live = ps.delta(obs, "ring_positions_live")
    if not steps or not live:
        return None
    cfg = obs["cfg"]
    dev = t.devices()[0]
    traced = [(s, e) for s, e, name in t.modules.get(dev, [])
              if name.startswith("jit__decode") and s >= t.lo and e <= t.hi]
    if not traced:
        return None
    dtype = cfg["serving"]["cache_dtype"]
    leaf = "%s[%d,%d,%d,%d]" % ((_DTYPE[dtype],)
                                + tuple(kind.ring_leaf_shape(cfg)))
    inside = tr.union(traced)
    secs = sum(tr.measure(tr.clip(inside, s, e)) for s, e, text in t.ops[dev]
               if leaf in text and tr.hlo_category(text) not in tr.CONTAINERS)
    if secs <= 0.0:
        return None
    cost = kind.ring_step_cost(cfg, live * len(traced) / steps,
                               _BYTES[dtype])
    peaks = obs["peaks"]
    least = max(cost["bytes"] / peaks["hbm_bytes_per_s"],
                cost["flops"] / peaks["bf16_flops_per_s"])
    return 100.0 * least / secs
