"""Least time of the chunked scans in the traced prefill programs, over the
device time they took.

A prefill program of more positions than ``mamba_chunk_size`` runs each
state layer's scan as a loop over sub-chunks that carries the layer's
state: a ``while`` on the XLA Ops line whose text names the carried state
(``f32[1, heads, state, head width]``), whose event spans the operations of
its body.  Those loops are the scans this reads, found by that shape: the
full chunks of ``serving.prefill_chunk`` positions (a last chunk padded to
that width among them).  A program of one sub-chunk has no loop and is not
read; nor is it counted.

The least time of one such scan is the larger of its operations over the
bf16 peak and its bytes over the HBM peak, as the configuration's kind
counts them for the chunk's positions, padding included
(``ssm_scan_cost``); it is HBM-bound.  The program computes the scan's
products in float32 (several passes of the bf16 unit each), so the share
is small by construction: it says how far the ``jax.numpy`` scan is from
what a kernel could reach, and ``PERF.md`` says what share of a chunk's
time it is.
"""
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "serve_itl_p95_ms"
DEVICE = True   # True: only a chip run can give it


def read(obs):
    t = obs.get("trace")
    if t is None or not t.devices():
        return None
    from harness import manifest
    from harness import trace as tr
    kind = manifest.load_kind(obs["kind"])
    if not hasattr(kind, "ssm_scan_cost"):
        return None
    cfg = obs["cfg"]
    carried = "f32[1,%d,%d,%d]" % kind.state_shape(cfg)[1:]
    spans = [tr.measure(tr.clip([(s, e)], t.lo, t.hi))
             for s, e, text in t.ops[t.devices()[0]]
             if tr.hlo_category(text) == "while" and carried in text
             and s >= t.lo and e <= t.hi]
    if not spans or sum(spans) <= 0.0:
        return None
    cost = kind.ssm_scan_cost(cfg, cfg["serving"]["prefill_chunk"])
    peaks = obs["peaks"]
    least = max(cost["bytes"] / peaks["hbm_bytes_per_s"],
                cost["flops"] / peaks["bf16_flops_per_s"])
    return 100.0 * len(spans) * least / sum(spans)
