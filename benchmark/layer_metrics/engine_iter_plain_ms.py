"""Mean step gap with no prefill program between the two decode steps:
the seconds between the read-back returns of consecutive pooled decode
steps, flagged by the engine at dispatch (``step_gap_seconds.plain`` over
``step_gaps.plain``, differences across the window).  A mean, not a
median, and under 10 gaps nothing is reported: in docs the engine flagged
859 of 859 gaps of a window ``prefill`` (PR 26, on the chip), so only chat
lists this metric.
"""
LAYER = "programs"
SOURCE = "program_span"
MOVES = "serve_itl_p95_ms"
DEVICE = False   # True: only a chip run can give it


def read(obs):
    from harness import program_spans as ps
    return ps.step_gap_mean_ms(obs, "plain")
