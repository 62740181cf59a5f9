"""Requests admitted and not finished when the window closed
(``admitted_outstanding``): above the knee the queue grows all through
the run.
"""
LAYER = "scheduler"
SOURCE = "program_counter"
MOVES = "serve_tokens_per_s"
DEVICE = False   # True: only a chip run can give it


def read(obs):
    if "backlog_end" not in obs:
        return None
    return float(obs["backlog_end"])
