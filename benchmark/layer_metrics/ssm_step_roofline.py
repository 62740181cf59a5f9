"""Least time of the state layers' updates in the traced decode steps, over
the device time those updates took.

The least time is HBM's: a decode step moves, in every state layer, every
slot's recurrence state once in and once out (float32) and the rows beside
it (the configuration's kind counts them: ``ssm_step_cost``), at the HBM
peak.  The pool's one program a layer moves every slot's state whether the
slot is active or not, so the count is of the pool's slots; the cell this
reads keeps them all taken.

The device time is that of the operations inside the decode program
(``jit__decode`` on the XLA Modules line) whose text names an operand of
the pooled state's shape (``f32[slots, heads, state, head width]``, from
the kind's ``state_shape``): the fusions that update the state (one a
layer in the program as it stands), or a kernel's calls were the program
to run one.  An operation is found by that shape, not by its name or
target.
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
    if not hasattr(kind, "ssm_step_cost"):
        return None
    cfg = obs["cfg"]
    dev = t.devices()[0]
    steps = [(s, e) for s, e, name in t.modules.get(dev, [])
             if name.startswith("jit__decode") and s >= t.lo and e <= t.hi]
    if not steps:
        return None
    state = "f32[%d,%d,%d,%d]" % kind.state_shape(cfg)
    inside = tr.union(steps)
    secs = sum(tr.measure(tr.clip(inside, s, e)) for s, e, text in t.ops[dev]
               if state in text and tr.hlo_category(text) not in tr.CONTAINERS)
    if secs <= 0.0:
        return None
    least = len(steps) * cfg["num_hidden_layers"] \
        * kind.ssm_step_cost(cfg)["bytes"] / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / secs
