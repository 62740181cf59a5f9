"""Mean step gap with a prefill program between the two decode steps (the
chunk and the pooled decode step that follows it on the device), flagged
by the engine at dispatch: ``step_gap_seconds.prefill`` over
``step_gaps.prefill``, differences across the window.
"""
LAYER = "programs"
SOURCE = "program_span"
MOVES = "serve_itl_p95_ms"
DEVICE = False   # True: only a chip run can give it


def read(obs):
    from harness import program_spans as ps
    return ps.step_gap_mean_ms(obs, "prefill")
