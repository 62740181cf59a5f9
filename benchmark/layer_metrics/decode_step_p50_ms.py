"""Median length of an engine iteration that ran no prefill call: the
pooled decode step alone, from the benchmark's iteration stamps.
"""
LAYER = "programs"
SOURCE = "host_clock"
MOVES = "serve_itl_p95_ms"
DEVICE = False   # True: only a chip run can give it


def read(obs):
    reqs = obs.get("requests")
    if not reqs:
        return None
    from harness import serve_metrics as sm
    v = sm.iteration_lengths(reqs, obs["t_open"], obs["t_close"], False)
    return 1e3 * sm.percentile(v, 50) if v else None
