"""Share of the held experts that had at least one token, over the calls
of an expert layer in decode steps and prefill programs (``stats()``:
``moe_active_experts``, ``moe_layer_calls``, as differences across the
window): how much of the held stacks the routing of a call needs (a
step or chunk reads them all: ``HeldExperts``).  Under greedy decoding of
seeded weights a sequence loops and keeps its experts, so this reads what
those loops route, not what sampled text would (PERF.md section 7).
"""
LAYER = "expert layer"
SOURCE = "program_counter"
MOVES = "serve_itl_p95_ms"
DEVICE = False   # True: only a chip run can give it


def read(obs):
    from harness import program_spans as ps
    held = obs["cfg"].get("n_routed_experts")
    calls = ps.delta(obs, "moe_layer_calls")
    if not held or calls is None:
        return None
    return ps.ratio(ps.delta(obs, "moe_active_experts"), held * calls, 100.0)
