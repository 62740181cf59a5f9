"""Bytes of the pooled KV cache (``SlotPool.cache_nbytes``), in GiB.
"""
LAYER = "slot pool"
SOURCE = "program_counter"
MOVES = "serve_itl_p95_ms"
DEVICE = False   # True: only a chip run can give it


def read(obs):
    if "cache_bytes" not in obs:
        return None
    return obs["cache_bytes"] / 2 ** 30
