"""Least time of the latent layers' attention in the traced decode steps,
over the device time of the operations that touch the latent rows there.

The least time is the larger of the bytes over the HBM peak and the
operations over the bf16 peak, as the configuration's kind counts them
(``latent_decode_cost``) from the places the steps' queries could attend:
each live place's row (the compressed row and the rotary key) read once
in every layer, and for each head a product of the absorbed query with the
row and of the weights with the compressed row.  The places are the
engine's own count (``stats()``: ``decode_positions_live``, a layer's,
summed at each decode dispatch), as a difference across the window and
carried over to the trace by the decode steps in each (``jit__decode``
executions on the XLA Modules line over ``decode_steps``): the cell this
reads keeps every slot taken, so a step of the trace does what a step of
the window does.  Live places, not what a kernel rounds up to.

The device time is that of the operations inside the decode program whose
text names an operand of the pooled latent leaf's shape (``[slots, 1,
max_len, kv_lora_rank]`` in the cache's dtype, from the kind's
``latent_leaf_shape``): the attention over the rows and the step's write
into them, whatever implements either (a kernel's calls, or the fusions of
the masked product).  An operation is found by that shape, not by its name
or target.  Where the program has no such counter or leaf (a commit from
before the latent layer) nothing is read.
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
    if not hasattr(kind, "latent_decode_cost"):
        return None
    steps = ps.delta(obs, "decode_steps")
    live = ps.delta(obs, "decode_positions_live")
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
                                + tuple(kind.latent_leaf_shape(cfg)))
    inside = tr.union(traced)
    secs = sum(tr.measure(tr.clip(inside, s, e)) for s, e, text in t.ops[dev]
               if leaf in text and tr.hlo_category(text) not in tr.CONTAINERS)
    if secs <= 0.0:
        return None
    cost = kind.latent_decode_cost(cfg, live * len(traced) / steps,
                                   _BYTES[dtype])
    peaks = obs["peaks"]
    least = max(cost["bytes"] / peaks["hbm_bytes_per_s"],
                cost["flops"] / peaks["bf16_flops_per_s"])
    return 100.0 * least / secs
