"""Token-expert rows the experts' product multiplied over the
token-to-held-expert pairs the routing asked for (``stats()``:
``moe_rows_computed``, ``moe_pairs_held``, as differences across the
window; both summed over the calls of an expert layer in decode steps and
prefill programs): what a product computes beside what is wanted.  The
held experts over the experts a token chooses where every token goes
through every held expert (16 at 64 held and 4 a token); 1 for a grouped
product with no padding; the tiles' padding in between (``HeldExperts``:
each expert's rows padded to whole tiles of 32).
"""
LAYER = "expert layer"
SOURCE = "program_counter"
MOVES = "serve_itl_p95_ms"
DEVICE = False   # True: only a chip run can give it


def read(obs):
    from harness import program_spans as ps
    return ps.ratio(ps.delta(obs, "moe_rows_computed"),
                    ps.delta(obs, "moe_pairs_held"))
