"""Test configuration: run on XLA CPU with 8 virtual devices so the
multi-chip sharding paths are exercised without a pod — the equivalent of
the reference's `new SparkContext("local[1]", ...)` trick
(reference: optim/DistriOptimizerSpec.scala:139).

``JAX_PLATFORMS=cpu`` in the environment (the tier-1 command sets it) is
honoured by JAX itself.  ``_ensure_devices`` adds what the variable
cannot: the host-device-count flag, and the same platform pin for a bare
``pytest`` run that did not set the variable.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from __graft_entry__ import _ensure_devices  # noqa: E402

# BIGDL_TPU_TESTS_ON_TPU=1 keeps the real accelerator visible so the
# on-TPU smoke tests (compiled, non-interpret Pallas numerics in
# test_fused_conv_bn.py) can run on a chip:
#   BIGDL_TPU_TESTS_ON_TPU=1 pytest tests/test_fused_conv_bn.py -k tpu
# Everything else assumes the 8-virtual-CPU mesh and should not be run
# in that mode.
if os.environ.get("BIGDL_TPU_TESTS_ON_TPU") == "1":
    import jax
else:
    jax = _ensure_devices(8)

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed():
    from bigdl_tpu.utils import set_seed
    set_seed(4357)
    yield


@pytest.fixture()
def mesh8():
    """An 8-device CPU mesh shaped (data=2, model=2, pipe=2)."""
    import numpy as np
    from jax.sharding import Mesh
    devs = np.array(jax.devices()[:8]).reshape(2, 2, 2)
    with Mesh(devs, ("data", "model", "pipe")) as m:
        yield m
