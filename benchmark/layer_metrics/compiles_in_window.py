"""Programs traced between the end of warm-up and the close of the window
(``SlotPool.trace_counts`` as a difference). Must read 0.
"""
LAYER = "slot pool"
SOURCE = "program_counter"
MOVES = "serve_itl_p95_ms"
DEVICE = False   # True: only a chip run can give it


def read(obs):
    if "compiles_in_window" not in obs:
        return None
    return float(obs["compiles_in_window"])
