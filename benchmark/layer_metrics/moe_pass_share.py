"""How much of a pass the expert layers are: device time, inside the
traced decode programs (``jit__decode*`` on the XLA Modules line: plain
steps and steps that carry a chunk), of the operations whose text names
an operand with the shape of a held expert stack (``[experts, in, out]`` of
the configuration, in the served dtype: found by that shape, whatever
implements the product), over those programs' device time.  Both from the
same traced executions, so the share cannot pass 100 %; the sort, the
gathers and the combine around the products name no stack and are not in
it.
"""
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "serve_itl_p95_ms"
DEVICE = True   # True: only a chip run can give it

_DTYPE = {"bfloat16": "bf16", "float16": "f16", "float32": "f32"}


def read(obs):
    t = obs.get("trace")
    if t is None or not t.devices() or not t.modules:
        return None
    from harness import manifest
    from harness import trace as tr
    kind = manifest.load_kind(obs["kind"])
    if not hasattr(kind, "expert_stack_shapes"):
        return None
    dev = t.devices()[0]
    traced = [(s, e) for s, e, name in t.modules.get(dev, [])
              if name.startswith("jit__decode") and s >= t.lo and e <= t.hi]
    whole = sum(e - s for s, e in traced)
    if whole <= 0.0:
        return None
    cfg = obs["cfg"]
    dtype = _DTYPE[cfg["serving"]["weights_dtype"]]
    stacks = tuple("%s[%d,%d,%d]" % ((dtype,) + tuple(shape))
                   for shape in kind.expert_stack_shapes(cfg))
    inside = tr.union(traced)
    secs = sum(tr.measure(tr.clip(inside, s, e)) for s, e, text in t.ops[dev]
               if any(k in text for k in stacks)
               and tr.hlo_category(text) not in tr.CONTAINERS)
    if secs <= 0.0:
        return None
    return 100.0 * secs / whole
