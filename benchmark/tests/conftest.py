"""Tests beside the benchmark: run by hand (``python3 -m pytest
benchmark/tests -q``) in the CPU rehearsal; not part of the repo's
tier-1 suite."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=4")
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH_DIR, os.path.dirname(BENCH_DIR)]
