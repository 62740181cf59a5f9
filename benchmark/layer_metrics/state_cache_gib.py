"""Bytes of the state layers' states in the slot pool (``stats()``:
``cache_bytes_state``), in GiB: what every slot keeps in every state layer
whatever its sequence's length, the recurrence's state in float32 and the
convolution's last inputs.  A decode step reads and writes all of it.
"""
LAYER = "slot pool"
SOURCE = "program_counter"
MOVES = "serve_itl_p95_ms"
DEVICE = False   # True: only a chip run can give it


def read(obs):
    stats = obs.get("stats1") or {}
    if not stats.get("cache_bytes_state"):
        return None
    return stats["cache_bytes_state"] / 2 ** 30
