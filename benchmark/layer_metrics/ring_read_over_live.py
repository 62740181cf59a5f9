"""Ring places the pooled decode program read over those its active slots
had live (``ring_positions_read`` over ``ring_positions_live``, the
scheduler's counters as differences across the window, summed over the
window layers).  1 is a step that reads what it attends; a step that reads
every slot's ring whole reads ``slots x places`` (the window, a chunk's
margin and the spare place) whatever is live: short requests, idle slots
and the margin all stand above 1.  Where the program has no such counter,
or no ring, nothing is read.
"""
LAYER = "kernels"
SOURCE = "program_counter"
MOVES = "serve_itl_p95_ms"
DEVICE = False   # True: only a chip run can give it


def read(obs):
    from harness import program_spans as ps
    return ps.ratio(ps.delta(obs, "ring_positions_read"),
                    ps.delta(obs, "ring_positions_live"))
