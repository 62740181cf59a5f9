"""Tokens a held expert computes in one call of an expert layer, on
average: token-to-held-expert pairs over held experts times expert-layer
calls (``stats()``: ``moe_pairs_held``, ``moe_layer_calls``, as differences
across the window).  The calls are those of decode steps (one token a live
slot) and of prefill programs (a chunk's tokens) alike.  In the deployment
the configuration stands for, every chip's slots send tokens to a held
expert; one chip's slots alone send it a sixteenth of that.
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
    return ps.ratio(ps.delta(obs, "moe_pairs_held"), held * calls)
