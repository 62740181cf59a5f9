"""Median gap between consecutive tokens of one request, over every gap
that ends in the window.
"""
LAYER = "programs"
SOURCE = "host_clock"
MOVES = "serve_itl_p95_ms"
DEVICE = False   # True: only a chip run can give it


def read(obs):
    gaps = obs.get("gaps")
    if not gaps:
        return None
    from harness import serve_metrics as sm
    return 1e3 * sm.percentile([g for g, _ in gaps], 50)
