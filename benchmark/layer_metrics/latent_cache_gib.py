"""Bytes of the latent layers' rows in the slot pool (``stats()``:
``cache_bytes_latent``), in GiB: one compressed row and one rotary key a
place and layer, shared by every head, in place of a key and a value a head.
"""
LAYER = "slot pool"
SOURCE = "program_counter"
MOVES = "serve_itl_p95_ms"
DEVICE = False   # True: only a chip run can give it


def read(obs):
    stats = obs.get("stats1") or {}
    if not stats.get("cache_bytes_latent"):
        return None
    return stats["cache_bytes_latent"] / 2 ** 30
