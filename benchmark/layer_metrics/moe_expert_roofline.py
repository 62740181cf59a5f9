"""Least time of the expert layers' products in the traced part of the
window, over the device time those products took.

The least time is the larger of their bytes over the HBM peak and their
operations over the bf16 peak, as the configuration's kind counts them
from the program's own counts of the routing (``expert_layer_cost``): the
stacks of the held experts that a token chose, read once a call, each
token-to-held-expert pair's row in and out, and three products a pair.
At a few tokens an expert it is HBM-bound.  A product that reads every
held stack in every call (``HeldExperts`` up to 512 tokens a call: a
step's time then does not follow the routing) reads more than this least,
so at this cut's load, with about six of ten held experts chosen a decode
step, the share cannot pass about that much there.

The counters (``moe_active_experts``, ``moe_pairs_held``) span the whole
window and the trace only its first seconds; the window's sums are carried
over to the trace by the decode steps in each (``jit__decode`` executions
on the XLA Modules line over ``decode_steps``): the cell this reads keeps
every slot taken, so a step of the trace does what a step of the window
does.

The device time is that of the operations (on the XLA Ops line, in any
program: decode steps and prefill chunks) whose text names an operand with
the shape of a held stack (``[experts, in, out]`` of the configuration, in
the served dtype): the fusions of the batched product, or XLA's
``ragged-dot`` kernels (a ``tpu_custom_call``).  An operation is found by
that shape, not by its name or target.
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
    if not hasattr(kind, "expert_layer_cost"):
        return None
    steps = ps.delta(obs, "decode_steps")
    active = ps.delta(obs, "moe_active_experts")
    pairs = ps.delta(obs, "moe_pairs_held")
    if not steps or not active or pairs is None:
        return None
    _, traced_steps = t.module_seconds("jit__decode")
    if not traced_steps:
        return None
    cfg = obs["cfg"]
    dtype = cfg["serving"]["weights_dtype"]
    cost = kind.expert_layer_cost(cfg, active, pairs, _BYTES[dtype])
    peaks = obs["peaks"]
    least = max(cost["bytes"] / peaks["hbm_bytes_per_s"],
                cost["flops"] / peaks["bf16_flops_per_s"])
    stacks = tuple("%s[%d,%d,%d]" % ((_DTYPE[dtype],) + shape)
                   for shape in kind.expert_stack_shapes(cfg))
    secs = t.ops_seconds(lambda cat, text: cat not in tr.CONTAINERS
                         and any(k in text for k in stacks))
    if secs <= 0.0:
        return None
    return 100.0 * least * (traced_steps / steps) / secs
