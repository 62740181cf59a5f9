"""Requests refused, shed or failed over the whole run (the scheduler's
``shed`` counter as a difference, plus refusals at submit and failed
futures). Must read 0.
"""
LAYER = "front"
SOURCE = "program_counter"
MOVES = "serve_itl_p95_ms"
DEVICE = False   # True: only a chip run can give it


def read(obs):
    if "shed" not in obs:
        return None
    return float(obs["shed"])
