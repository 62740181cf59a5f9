"""Cache positions the pooled decode program read over those its active
slots had live (``decode_positions_read`` over ``decode_positions_live``,
the scheduler's counters as differences across the window, a full layer).
1 is a step that reads what it attends; a step that reads live key blocks
only stands above it by the rounding to the block; one that reads every
slot's whole row reads ``slots x max_len`` whatever is live.
"""
LAYER = "kernels"
SOURCE = "program_counter"
MOVES = "serve_itl_p95_ms"
DEVICE = False   # True: only a chip run can give it


def read(obs):
    from harness import program_spans as ps
    return ps.ratio(ps.delta(obs, "decode_positions_read"),
                    ps.delta(obs, "decode_positions_live"))
