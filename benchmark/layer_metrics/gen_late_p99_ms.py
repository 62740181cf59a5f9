"""How late the benchmark's generator submitted a request after it was due,
99th percentile: a starved generator must not read as a fast server.
"""
LAYER = "front"
SOURCE = "host_clock"
MOVES = "serve_itl_p95_ms"
DEVICE = False   # True: only a chip run can give it


def read(obs):
    reqs = obs.get("requests")
    if not reqs:
        return None
    from harness import serve_metrics as sm
    return 1e3 * sm.percentile([r["submit"] - r["due"] for r in reqs], 99)
