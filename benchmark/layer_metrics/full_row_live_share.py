"""What of the allotted full rows a decode step finds live: the positions
the active slots' queries could attend in a full layer
(``decode_positions_live``, summed at each decode dispatch) over ``slots x
max_len`` for each of those dispatches (``decode_dispatches``), the
scheduler's counters as differences across the window, in per cent.  The
pool allots every slot a row of ``max_len`` whatever its request's length:
the rest of the row is memory that holds nothing (one layer's share; every
full layer has as many).  Where the program lacks a counter nothing is
read.
"""
LAYER = "slot pool"
SOURCE = "program_counter"
MOVES = "serve_itl_p95_ms"
DEVICE = False   # True: only a chip run can give it


def read(obs):
    from harness import program_spans as ps
    dispatches = ps.delta(obs, "decode_dispatches")
    s = (obs.get("cfg") or {}).get("serving") or {}
    if not dispatches or not s.get("slots") or not s.get("max_len"):
        return None
    return ps.ratio(ps.delta(obs, "decode_positions_live"),
                    dispatches * s["slots"] * s["max_len"], 100.0)
