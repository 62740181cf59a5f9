"""Decode dispatches that lost the read-back overlap: the pending step had
to be read back before the next dispatch, or the pool emptied with a step
in flight (``pipeline_drains`` over ``decode_dispatches``, the scheduler's
counters as differences across the window).
"""
LAYER = "scheduler"
SOURCE = "program_counter"
MOVES = "serve_itl_p95_ms"
DEVICE = False   # True: only a chip run can give it


def read(obs):
    from harness import program_spans as ps
    return ps.ratio(ps.delta(obs, "pipeline_drains"),
                    ps.delta(obs, "decode_dispatches"), 100.0)
