"""The device as JAX reports it, and its published peaks."""
from __future__ import annotations

import os
from typing import Any, Dict, List

from harness.manifest import BENCH_DIR, ROOT, load_json


def peaks(device_kind: str) -> Dict[str, float]:
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))["by_device_kind"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}: "
                       f"add it to benchmark/peaks.json with its source")
    return table[device_kind]


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout, or
    where JAX_COMPILATION_CACHE_DIR says."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # the default threshold (1 s) would leave the small serving programs
    # (seed, scatter) to compile in every run's set-up
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)
    return path


def require_tpu(chips: int) -> List[Any]:
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"benchmark: needs a TPU; JAX reports platform "
                         f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"benchmark: the cell asks for {chips} chip(s), "
                         f"JAX finds {len(devices)}")
    peaks(devices[0].device_kind)    # an unknown kind is an error
    return list(devices[:chips])


def describe(devices: List[Any]) -> Dict[str, Any]:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}
