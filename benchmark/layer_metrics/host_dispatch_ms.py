"""Host milliseconds per step spent staging and dispatching
(``host_staging_s + dispatch_s`` of the window records).
"""
LAYER = "dispatch loop"
SOURCE = "program_span"
MOVES = "train_mfu"
DEVICE = False   # True: only a chip run can give it


def read(obs):
    r = obs.get("records")
    if not r:
        return None
    return 1e3 * r["host_s"] / r["steps"]
