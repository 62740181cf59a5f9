"""Least time of the Mamba-1 layers' state updates in the traced decode
steps, over the device time those updates took.

The least time is HBM's: a decode step moves, in every layer that keeps a
state (nine of 32 at the published depth: the kind's ``state_layers``),
every slot's recurrence state once in and once out (float32) and the rows
beside it (``selective_step_cost``), at the HBM peak.  The pool's one
program a layer moves every slot's state whether the slot is active or
not, so the count is of the pool's slots; the cell this reads keeps them
all taken.

The device time is that of the operations inside the decode programs
(``jit__decode`` on the XLA Modules line) whose text names an operand of
the pooled state's shape (``f32[slots, state, inner]``, from the kind's
``selective_state_shape``): the fusions that update the state, and in a
joint pass the chunk's reading and writing of its slot's state.  An
operation is found by that shape, not by its name or target.
"""
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "serve_itl_p95_ms"
DEVICE = True   # True: only a chip run can give it


def read(obs):
    t = obs.get("trace")
    if t is None or not t.devices() or not t.modules:
        return None
    from harness import manifest
    from harness import trace as tr
    kind = manifest.load_kind(obs["kind"])
    if not hasattr(kind, "selective_step_cost"):
        return None
    cfg = obs["cfg"]
    dev = t.devices()[0]
    steps = [(s, e) for s, e, name in t.modules.get(dev, [])
             if name.startswith("jit__decode") and s >= t.lo and e <= t.hi]
    if not steps:
        return None
    state = "f32[%d,%d,%d]" % kind.selective_state_shape(cfg)
    inside = tr.union(steps)
    secs = sum(tr.measure(tr.clip(inside, s, e)) for s, e, text in t.ops[dev]
               if state in text and tr.hlo_category(text) not in tr.CONTAINERS)
    if secs <= 0.0:
        return None
    least = len(steps) * kind.state_layers(cfg) \
        * kind.selective_step_cost(cfg)["bytes"] \
        / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / secs
