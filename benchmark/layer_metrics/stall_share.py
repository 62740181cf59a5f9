"""Share of the window that its slow passes took: over the pass records of
the window (``stats()["pass_log"]``) whose step gap is over three medians
of their group (plain, joint, alone), the sum of the gap less that median,
over the window's seconds.  No upper cut-off: a stall of seconds counts.
``[slow_passes]`` lists them.  None where the program keeps no pass log.
"""
LAYER = "scheduler"
SOURCE = "program_span"
MOVES = "serve_tokens_per_s"
DEVICE = False   # True: only a chip run can give it


def read(obs):
    from harness import pass_log as pl
    return pl.read(obs, lambda rec: pl.stall_share(
        rec, obs["t_close"] - obs["t_open"]))
