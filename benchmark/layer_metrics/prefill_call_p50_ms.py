"""Median length of an engine iteration that ran a prefill call (the
chunk and the pooled decode step that shares the iteration), from the
benchmark's iteration stamps.  Less ``decode_step_p50_ms`` of a cell
where plain iterations are common (chat) it is what one chunk adds.
"""
LAYER = "programs"
SOURCE = "host_clock"
MOVES = "serve_tokens_per_s"
DEVICE = False   # True: only a chip run can give it


def read(obs):
    reqs = obs.get("requests")
    if not reqs:
        return None
    from harness import serve_metrics as sm
    v = sm.iteration_lengths(reqs, obs["t_open"], obs["t_close"], True)
    return 1e3 * sm.percentile(v, 50) if v else None
