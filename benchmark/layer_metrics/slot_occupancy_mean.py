"""Mean number of decoding slots per decode step inside the window: the
scheduler's running mean times its step count, as a difference across
the window.
"""
LAYER = "scheduler"
SOURCE = "program_counter"
MOVES = "serve_itl_p95_ms"
DEVICE = False   # True: only a chip run can give it


def read(obs):
    a, b = obs.get("stats0"), obs.get("stats1")
    if not a or not b or b["decode_steps"] <= a["decode_steps"]:
        return None
    total = (b["slot_occupancy_mean"] * b["decode_steps"]
             - a["slot_occupancy_mean"] * a["decode_steps"])
    return total / (b["decode_steps"] - a["decode_steps"])
