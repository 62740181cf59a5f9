"""Milliseconds per training step, from ``t_device_ready`` differences over
the counted dispatch windows.
"""
LAYER = "compiled step"
SOURCE = "program_span"
MOVES = "train_mfu"
DEVICE = False   # True: only a chip run can give it


def read(obs):
    r = obs.get("records")
    if not r:
        return None
    return 1e3 * r["seconds"] / r["steps"]
