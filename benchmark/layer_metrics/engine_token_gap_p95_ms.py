"""The engine's own reading of the judged tail: the 95th percentile of the
step gaps of the window's pass records (``stats()["pass_log"]``, one record
a decode step's read-back, ``t_open <= t < t_close``), each gap counted
once for every token its read-back gave out.  The benchmark's
``serve_itl_p95_ms`` stamps the same tokens from outside, microseconds
later (``[pass_log_check]`` prints both).  None where the program keeps no
pass log.

The first reader of the log in a run prints its tables
(``harness/pass_log.py``: ``[pass_log]``, the passes by group and chunk
width, where the tail sits; ``[pass_log_check]``; ``[slow_passes]``, every
pass over three medians of its group, with the engine thread's phases,
``gc_s``, ``compiles`` and, in a traced run, the device's busy seconds
inside it).
"""
LAYER = "programs"
SOURCE = "program_span"
MOVES = "serve_itl_p95_ms"
DEVICE = False   # True: only a chip run can give it


def read(obs):
    from harness import pass_log as pl
    return pl.read(obs, pl.token_gap_p95_ms)
