"""Global random generator with explicit seeding.

Reference: utils/RandomGenerator.scala (seed control for reproducible
init).  TPU-native version: a single process-wide seed feeding
``jax.random`` keys; every consumer derives fresh keys via
:func:`next_key` so model init is reproducible under :func:`set_seed`.
The generator is process-wide (shared across threads, guarded by a
lock) — data-loader threads see the seed set on the main thread.

Key creation is lazy so importing bigdl_tpu never initializes the JAX
backend (which would lock in the platform before user env config).
"""

from __future__ import annotations

import threading

__all__ = ["set_seed", "get_seed", "next_key", "RandomGenerator"]


class RandomGenerator:
    def __init__(self, seed: int = 0):
        self._lock = threading.Lock()
        self.seed = seed
        self._key = None
        self._count = 0

    def set_seed(self, seed: int):
        with self._lock:
            self.seed = seed
            self._key = None
            self._count = 0
        return self

    def next_key(self):
        import jax
        with self._lock:
            if self._key is None:
                # a model may be built first under a trace (``jax.eval_shape``
                # for its shapes): the stream's key must not be that trace's
                with jax.ensure_compile_time_eval():
                    self._key = jax.random.key(self.seed)
            self._count += 1
            return jax.random.fold_in(self._key, self._count)


_GEN = RandomGenerator()


def set_seed(seed: int):
    return _GEN.set_seed(seed)


def get_seed() -> int:
    return _GEN.seed


def next_key():
    return _GEN.next_key()
