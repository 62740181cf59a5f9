"""Bytes of the window layers' rings in the pooled KV cache (``stats()``:
``cache_bytes_window``), in GiB: what the window layers keep for every slot
in place of ``max_len`` rows.
"""
LAYER = "slot pool"
SOURCE = "program_counter"
MOVES = "serve_itl_p95_ms"
DEVICE = False   # True: only a chip run can give it


def read(obs):
    stats = obs.get("stats1") or {}
    if "cache_bytes_window" not in stats:
        return None
    return stats["cache_bytes_window"] / 2 ** 30
