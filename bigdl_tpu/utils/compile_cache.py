"""JAX's persistent compilation cache, placed so that it can be moved
from outside.

A ResNet-50 training step takes about a minute to compile for a TPU; a
process that starts with an empty cache pays that every time.  Every
entry point that owns a process (``chip_smoke.py``, the
``bigdl_tpu.examples`` console scripts, ``python -m bigdl_tpu.serving``)
calls :func:`enable_compile_cache` before its first compile.  Nothing
calls it at library import: a host application that imports
``bigdl_tpu`` keeps its own cache settings.
"""

from __future__ import annotations

import os

__all__ = ["DEFAULT_CACHE_DIR", "enable_compile_cache"]

# A fixed path derived from the package's location (the checkout that
# holds ``bigdl_tpu/``): the directory is part of the cache key, so a
# path made from tempfile, a pid or the time would never hit.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it, and
    no directory is set in code; otherwise the cache lives at
    :data:`DEFAULT_CACHE_DIR`.  JAX's own threshold stays: only a
    program that took a second or more to compile is kept.  (Keeping
    every program was tried on the chip: a thousand single-op entries
    made a size-capped cache spend minutes evicting.)
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
