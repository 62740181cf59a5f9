"""Share of the counted gaps whose engine iteration also ran a prefill
call. Which iterations did follows from the stamps and each prompt's
number of prefill calls (``serve_metrics.prefill_iterations``).
"""
LAYER = "scheduler"
SOURCE = "host_clock"
MOVES = "serve_itl_p95_ms"
DEVICE = False   # True: only a chip run can give it


def read(obs):
    gaps, reqs = obs.get("gaps"), obs.get("requests")
    if not gaps:
        return None
    from harness import serve_metrics as sm
    its = sm.iterations(reqs)
    flags = sm.prefill_iterations(reqs, its)
    hit = sum(1 for _g, end in gaps if flags[sm.iteration_index(its, end)])
    return 100.0 * hit / len(gaps)
