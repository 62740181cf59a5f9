"""Median time from a request's due time to its first token, over the
requests whose first token fell in the window. Above the knee this is
mostly queueing.
"""
LAYER = "scheduler"
SOURCE = "host_clock"
MOVES = "serve_tokens_per_s"
DEVICE = False   # True: only a chip run can give it


def read(obs):
    reqs = obs.get("requests")
    if not reqs:
        return None
    from harness import serve_metrics as sm
    v = sm.first_token_delays(reqs, obs["t_open"], obs["t_close"])
    return 1e3 * sm.percentile(v, 50) if v else None
