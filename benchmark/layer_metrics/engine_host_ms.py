"""Host work of the engine thread per pass of its loop: the seconds it
spent admitting, dispatching prefill and decode programs, emitting and in
the pass's own bookkeeping (``engine_phase_seconds`` less ``readback_wait``
and ``idle``), over ``iterations``; both from ``GenerationScheduler.stats()``
as differences across the window.
"""
LAYER = "scheduler"
SOURCE = "program_span"
MOVES = "serve_itl_p95_ms"
DEVICE = False   # True: only a chip run can give it


def read(obs):
    from harness import program_spans as ps
    return ps.ratio(ps.host_seconds(obs), ps.delta(obs, "iterations"), 1e3)
