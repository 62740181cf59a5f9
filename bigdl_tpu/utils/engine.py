"""Engine — global runtime singleton.

Reference: utils/Engine.scala:49 (parses Spark conf into
(nodeNumber, coreNumber), owns thread pools, engine type, optimizer
version, the ``bigdl.*`` system-property config tier, and the
singleton-per-JVM check) and utils/ThreadPool.scala.

TPU-native mapping: topology comes from the JAX runtime —
``process_count`` (≙ nodeNumber), ``local_device_count`` (≙ executor
cores for device work) — and config from ``BIGDL_TPU_*`` environment
variables (≙ the ``bigdl.*`` sysprops).  The reference's compute thread
pools (model replicas per core) have no TPU analog — XLA owns the
device — so ThreadPool here serves the host side: data loading,
checkpoint IO, metric drains.
"""

from __future__ import annotations

import os
import threading
from concurrent.futures import Future, ThreadPoolExecutor, wait
from typing import Callable, List, Optional, Sequence, Tuple

__all__ = ["Engine", "ThreadPool", "get_property"]


def get_property(name: str, default: str = "") -> str:
    """Config tier (≙ ``bigdl.*`` JVM properties, Engine.scala:53):
    ``bigdl.foo.bar`` → env var ``BIGDL_TPU_FOO_BAR``."""
    env = "BIGDL_TPU_" + name.replace("bigdl.", "").replace(".", "_").upper()
    return os.environ.get(env, default)


class ThreadPool:
    """Host-side pool (≙ utils/ThreadPool.scala): ``invoke_and_wait``
    mirrors invokeAndWait; ``invoke_and_wait2`` returns (done, pending)
    futures under a timeout — the API the reference used for straggler
    dropping (ThreadPool.scala:156), retained for host IO tasks."""

    def __init__(self, size: int):
        self.size = size
        self._pool = ThreadPoolExecutor(max_workers=size)

    def invoke(self, tasks: Sequence[Callable]) -> List[Future]:
        return [self._pool.submit(t) for t in tasks]

    def invoke_and_wait(self, tasks: Sequence[Callable]) -> List:
        futures = self.invoke(tasks)
        return [f.result() for f in futures]

    def invoke_and_wait2(self, tasks: Sequence[Callable],
                         timeout: Optional[float] = None):
        futures = self.invoke(tasks)
        done, pending = wait(futures, timeout=timeout)
        for p in pending:
            p.cancel()
        return done, pending

    def sync(self):
        self.invoke_and_wait([lambda: None])

    def shutdown(self):
        self._pool.shutdown(wait=False)


class _EngineState:
    def __init__(self):
        self.inited = False
        self.node_number = 1
        self.core_number = 1
        self.local_device_count = 1
        self.optimizer_version = get_property(
            "bigdl.optimizerVersion", "optimizerV1")
        self.engine_type = get_property("bigdl.engineType", "xla")
        self._default_pool: Optional[ThreadPool] = None
        self._io_pool: Optional[ThreadPool] = None


class Engine:
    """Singleton runtime facade (reference Engine.init,
    utils/Engine.scala:114)."""

    _state = _EngineState()
    _lock = threading.Lock()

    @classmethod
    def init(cls, node_number: Optional[int] = None,
             core_number: Optional[int] = None) -> None:
        """Discover (or override) the topology.  Reference
        Engine.init:114 parses the Spark master; here the JAX runtime is
        the source of truth: process_count ≙ nodes, local device count ≙
        per-node accelerator parallelism."""
        with cls._lock:
            s = cls._state
            import jax
            # backend errors propagate: a chip that fails to come up
            # must not read as "1 node, 1 device"
            s.node_number = (node_number if node_number is not None
                             else jax.process_count())
            s.local_device_count = jax.local_device_count()
            if core_number is not None:
                s.core_number = core_number
            else:
                env = get_property("bigdl.coreNumber")
                s.core_number = int(env) if env else (os.cpu_count() or 1)
            s.inited = True

    @classmethod
    def init_distributed(cls, coordinator_address: Optional[str] = None,
                         num_processes: Optional[int] = None,
                         process_id: Optional[int] = None,
                         timeout_s: Optional[float] = None) -> None:
        """Bootstrap the multi-host runtime (≙ the reference's cluster
        init: Engine.init parsing the Spark master + AllReduceParameter
        port setup — here it is ``jax.distributed.initialize``, which
        wires the DCN coordinator so every host sees the global device
        set).

        On Cloud TPU pod slices all arguments are auto-discovered (call
        with none); elsewhere pass the coordinator explicitly or set
        BIGDL_TPU_COORDINATOR / BIGDL_TPU_NUM_PROCESSES /
        BIGDL_TPU_PROCESS_ID.  Idempotent: a second call is a no-op, so
        library code may call it defensively.  Single-process runs
        (num_processes == 1 discovered or requested) skip the
        coordinator entirely."""
        coordinator_address = (coordinator_address
                               or get_property("bigdl.coordinator") or None)
        if num_processes is None:
            env = get_property("bigdl.num.processes")
            num_processes = int(env) if env else None
        if process_id is None:
            env = get_property("bigdl.process.id")
            process_id = int(env) if env else None
        # a multi-host run is identifiable by explicit args, the env
        # tier above, a launcher-set coordinator, or a TPU pod slice
        # (worker hostnames published by the TPU runtime); anything
        # else is a single-process run and must NOT touch the
        # coordinator (jax.distributed.initialize would error once any
        # backend work has happened — e.g. under tests)
        multi = (num_processes not in (None, 1)
                 or coordinator_address is not None
                 or os.environ.get("JAX_COORDINATOR_ADDRESS")
                 or os.environ.get("TPU_WORKER_HOSTNAMES", "").count(",")
                 > 0)
        with cls._lock:
            if getattr(cls._state, "dist_inited", False):
                return
            if not multi:
                cls._state.dist_inited = True
                return
            import jax
            kw = {}
            if timeout_s is not None:
                # surface dead-coordinator failures in bounded time
                # (jax's default handshake timeout is 300s); floor at
                # 1s so a sub-second request doesn't truncate to an
                # already-expired deadline
                kw["initialization_timeout"] = max(1, round(timeout_s))
            try:
                jax.distributed.initialize(
                    coordinator_address=coordinator_address,
                    num_processes=num_processes,
                    process_id=process_id, **kw)
            except RuntimeError as e:
                # already initialized elsewhere (e.g. by the launcher)
                if "should only be called once" not in str(e):
                    raise
            cls._state.dist_inited = True
        cls.init()  # re-discover topology with the global view

    @classmethod
    def _ensure(cls):
        if not cls._state.inited:
            cls.init()

    @classmethod
    def node_number(cls) -> int:
        cls._ensure()
        return cls._state.node_number

    @classmethod
    def core_number(cls) -> int:
        cls._ensure()
        return cls._state.core_number

    @classmethod
    def local_device_count(cls) -> int:
        cls._ensure()
        return cls._state.local_device_count

    @classmethod
    def get_engine_type(cls) -> str:
        return cls._state.engine_type

    @classmethod
    def get_optimizer_version(cls) -> str:
        """≙ Engine.getOptimizerVersion (Engine.scala:230)."""
        return cls._state.optimizer_version

    @classmethod
    def set_optimizer_version(cls, v: str) -> None:
        assert v in ("optimizerV1", "optimizerV2"), v
        cls._state.optimizer_version = v

    @classmethod
    def default_pool(cls) -> ThreadPool:
        """Host task pool (≙ Engine.default, core×2 capped — the
        reference's core×50 sizing existed to absorb blocked Spark task
        threads, which have no analog here)."""
        cls._ensure()
        with cls._lock:
            if cls._state._default_pool is None:
                cls._state._default_pool = ThreadPool(
                    min(cls._state.core_number * 2, 64))
            return cls._state._default_pool

    @classmethod
    def io_pool(cls) -> ThreadPool:
        """Dedicated IO pool (checkpoint writes, event files —
        ≙ the reference's wrapperComputing pool)."""
        cls._ensure()
        with cls._lock:
            if cls._state._io_pool is None:
                cls._state._io_pool = ThreadPool(4)
            return cls._state._io_pool

    @classmethod
    def check_singleton(cls) -> bool:
        """≙ Engine.checkSingleton (Engine.scala:286): one Engine per
        process by construction here; kept for API parity."""
        return True

    @classmethod
    def reset(cls) -> None:
        """Test hook."""
        with cls._lock:
            if cls._state._default_pool:
                cls._state._default_pool.shutdown()
            if cls._state._io_pool:
                cls._state._io_pool.shutdown()
            cls._state = _EngineState()
