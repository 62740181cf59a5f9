"""Least time of every reading of the one shared row in the traced decode
steps, over the device time of the operations that touch the row there.

The least time is the larger of the bytes over the HBM peak and the
operations over the bf16 peak, as the configuration's kind counts them
(``shared_kv_decode_cost``) from the places the steps' queries could
attend **times the layers that read them**: the engine's own counts
(``stats()``: ``decode_positions_live``, one row's live places summed at
each decode dispatch, and ``full_row_readers``, 8 where seven cross layers
attend the full layer's row), the places as a difference across the window
and carried over to the trace by the decode steps in each (``jit__decode``
executions on the XLA Modules line over ``decode_steps``): the cell this
reads keeps every slot taken, so a step of the trace does what a step of
the window does.  Live places, not what a kernel rounds up to.

The device time is that of the operations inside the decode programs whose
text names an operand of the shared leaf's shape (``[slots, key/value
heads / 2, max_len, 2 x head width]`` in the cache's dtype, from the kind's
``shared_leaf_shape``): the eight layers' attention over the row and the
writes into it (a joint pass's chunk among them), whatever implements
either.  An operation is found by that shape, not by its name or target.
Where the program has no such counter or leaf nothing is read.
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
    if not hasattr(kind, "shared_kv_decode_cost"):
        return None
    steps = ps.delta(obs, "decode_steps")
    live = ps.delta(obs, "decode_positions_live")
    readers = (obs.get("stats1") or {}).get("full_row_readers")
    if not steps or not live or not readers:
        return None
    cfg = obs["cfg"]
    dev = t.devices()[0]
    traced = [(s, e) for s, e, name in t.modules.get(dev, [])
              if name.startswith("jit__decode") and s >= t.lo and e <= t.hi]
    if not traced:
        return None
    dtype = cfg["serving"]["cache_dtype"]
    leaf = "%s[%d,%d,%d,%d]" % ((_DTYPE[dtype],)
                                + tuple(kind.shared_leaf_shape(cfg)))
    inside = tr.union(traced)
    secs = sum(tr.measure(tr.clip(inside, s, e)) for s, e, text in t.ops[dev]
               if leaf in text and tr.hlo_category(text) not in tr.CONTAINERS)
    if secs <= 0.0:
        return None
    cost = kind.shared_kv_decode_cost(
        cfg, readers * live * len(traced) / steps, _BYTES[dtype])
    peaks = obs["peaks"]
    least = max(cost["bytes"] / peaks["hbm_bytes_per_s"],
                cost["flops"] / peaks["bf16_flops_per_s"])
    return 100.0 * least / secs
