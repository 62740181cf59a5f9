"""Prompt positions the prefill programs newly covered per second of the
window, counted by the engine where it advances a request's prefill cursor
(``prefill_prompt_tokens`` as a difference).  Prints beside it what the
benchmark infers from its stamps and the mirrored chunking rule, and the
positions computed (padding and suffix-aligned overlap included).
"""
LAYER = "programs"
SOURCE = "program_counter"
MOVES = "serve_tokens_per_s"
DEVICE = False   # True: only a chip run can give it


def read(obs):
    from harness import program_spans as ps, result
    covered = ps.delta(obs, "prefill_prompt_tokens")
    if covered is None or not obs.get("seconds"):
        return None
    computed = ps.delta(obs, "prefill_positions")
    first_in = [r for r in obs.get("requests", ()) if r["stamps"]
                and obs["t_open"] <= r["stamps"][0] < obs["t_close"]]
    result.say("prefill_counted",
               prompt_tokens=covered, positions=computed,
               recompute_share=ps.ratio(
                   None if computed is None else computed - covered, computed),
               calls=ps.delta(obs, "prefill_calls"),
               mirrored_calls=sum(r["prefill_calls"] for r in first_in),
               mirrored_prompt_tokens=obs.get("tokens", {}).get("prompt"))
    return covered / obs["seconds"]
