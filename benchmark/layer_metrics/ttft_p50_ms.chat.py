"""Median time from a request's due time to its first token, over the
requests whose first token fell in the window. Reported, not judged: a
window holds some thirty requests.
"""
LAYER = "scheduler"
SOURCE = "host_clock"
MOVES = "serve_itl_p95_ms"
DEVICE = False   # True: only a chip run can give it


def read(obs):
    reqs = obs.get("requests")
    if not reqs:
        return None
    from harness import serve_metrics as sm
    v = sm.first_token_delays(reqs, obs["t_open"], obs["t_close"])
    return 1e3 * sm.percentile(v, 50) if v else None
