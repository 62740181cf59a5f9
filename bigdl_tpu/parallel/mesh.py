"""Device mesh construction and topology discovery.

Reference equivalence: utils/Engine.scala:499-600 parses the Spark master
URL into (nodeNumber, coreNumber); here topology comes from
``jax.devices()`` and the mesh axes replace the reference's
executor×thread grid.  The reference's single parallelism axis (data)
generalizes to the full axis set {data, fsdp, model(tensor), pipe,
seq, expert} — absent in the reference (SURVEY §2.6) but first-class
here.

The canonical axis names used across the framework:

* ``dcn``   — the slow inter-slice network tier (data-center network
  between ICI slices); batch-like, but gradient sync across it should
  go through ``parallel.hierarchy`` (≙ the reference's inter-node
  links, whose slowness motivated FP16CompressedTensor)
* ``data``  — batch sharding (≙ AllReduceParameter data parallelism)
* ``fsdp``  — parameter/optimizer-state sharding combined with data
* ``model`` — tensor parallelism (megatron-style)
* ``pipe``  — pipeline stages
* ``seq``   — sequence/context parallelism (ring attention)
* ``expert``— MoE expert parallelism
"""

from __future__ import annotations

import logging
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["make_mesh", "data_parallel_mesh", "MeshConfig", "P",
           "NamedSharding", "Mesh", "local_device_count",
           "batch_sharding", "shard_map_compat", "ambient_mesh",
           "axis_coord_maps",
           "mesh_axes", "pin_replicated"]


def pin_replicated(tree, mesh):
    """Pin every leaf to the fully-replicated layout before it enters a
    shard_map.  On multi-axis meshes GSPMD mispartitions IN-GRAPH
    producers of shard_map operands — a ``jnp.stack`` of per-stage /
    per-expert parameters or a pad of the microbatch ring compiled
    under jit silently yields values that DIVERGE from the eager result
    (observed on jax 0.4.37 CPU; exercised by the dp×pp / dp×ep
    training-equivalence tests and the partition-plan conformance
    matrix).  Forcing the operand replicated at the boundary removes
    the partitioner's freedom to misplace it; the shard_map's in_specs
    then carve the per-device shards themselves."""
    rep = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(
        lambda l: jax.lax.with_sharding_constraint(l, rep), tree)


def ambient_mesh() -> Optional[Mesh]:
    """The mesh of the enclosing ``with mesh:`` — how the Optimizer
    traces and runs its step — or None outside one, and None inside a
    ``shard_map`` body, where the code is already per-device.  Code
    that must behave differently when its caller's program spans
    several devices (a Pallas kernel cannot be partitioned by the
    compiler) asks here instead of taking a mesh argument through every
    layer above it.  JAX keeps this context for its own use and offers
    no public reader of it inside ``jit``."""
    from jax._src import mesh as _mesh_lib
    if jax.sharding.get_abstract_mesh().manual_axes:
        return None
    mesh = _mesh_lib.thread_resources.env.physical_mesh
    return None if mesh.empty else mesh


def shard_map_compat(f, mesh, in_specs, out_specs):
    """THE one spelling every module maps over a mesh with:
    ``jax.shard_map`` with ``check_vma=False``."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

logger = logging.getLogger("bigdl_tpu.parallel")

# dcn is OUTERMOST (slowest-varying): devices of one slice stay
# contiguous in the flattened device order, so the fast axes ride
# nearest-neighbour ICI while only the dcn axis crosses slices
AXES = ("dcn", "data", "fsdp", "model", "pipe", "seq", "expert")

# the batch-like axes, in AXES order: a batch-leading array shards over
# every one of these present in the mesh
BATCH_AXES = ("dcn", "data", "fsdp")


def local_device_count() -> int:
    """Devices attached to THIS host (jax.local_device_count);
    use len(jax.devices()) for the global count."""
    return jax.local_device_count()


def _infer(shape: Dict[str, int], n: int) -> Dict[str, int]:
    """Resolve a single -1 entry so the product equals n."""
    known = 1
    unknown = None
    for k, v in shape.items():
        if v == -1:
            if unknown is not None:
                raise ValueError("only one mesh axis may be -1")
            unknown = k
        else:
            known *= v
    if unknown is not None:
        if n % known:
            raise ValueError(
                f"mesh axes {shape} don't divide device count {n}")
        shape = dict(shape)
        shape[unknown] = n // known
    else:
        prod = known
        if prod > n:
            raise ValueError(
                f"mesh axes {shape} (={prod}) exceed device count {n}")
        # prod < n: use the first prod devices (≙ running on a subset
        # of executors)
    return shape


def make_mesh(axes: Optional[Dict[str, int]] = None,
              devices=None) -> Mesh:
    """Build a Mesh over the given axes (dict axis→size; one may be -1).

    Axis order follows AXES so that the innermost (fastest-varying,
    best-ICI-locality) axis is the model/tensor axis — collectives for
    TP ride nearest-neighbour ICI links while DP gradients ride the
    outer dimensions, matching create_device_mesh's locality heuristics.
    """
    devices = list(jax.devices()) if devices is None else list(devices)
    n = len(devices)
    if axes is None:
        axes = {"data": n}
    axes = _infer(dict(axes), n)
    names = [a for a in AXES if a in axes]
    extra = [a for a in axes if a not in AXES]
    names += extra
    sizes = tuple(axes[a] for a in names)
    prod = int(np.prod(sizes))
    if prod < n:
        dropped = devices[prod:]
        logger.warning(
            "mesh axes %s cover only %d of %d devices; dropping device "
            "id(s) %s (pass -1 on one axis to use every device)",
            dict(zip(names, sizes)), prod, n,
            [getattr(d, "id", d) for d in dropped])
        devices = devices[:prod]
    mesh_devices = None
    if "dcn" in names and axes["dcn"] > 1:
        # the dcn axis must follow PHYSICAL slice boundaries or the
        # hierarchical sync inverts (full-width gradients over the
        # real DCN, compression on ICI): create_hybrid_device_mesh
        # places the dcn dim by slice_index and keeps ICI locality
        # within each slice.  Fake meshes (CPU devices carry no
        # slice_index) fall through to the flat path below, whose
        # dcn-outermost ordering IS the slice layout being simulated.
        try:
            from jax.experimental import mesh_utils
            ici_shape = tuple(1 if a == "dcn" else axes[a]
                              for a in names)
            dcn_shape = tuple(axes[a] if a == "dcn" else 1
                              for a in names)
            mesh_devices = mesh_utils.create_hybrid_device_mesh(
                ici_shape, dcn_shape, devices=devices)
        except Exception as e:
            if any(getattr(d, "slice_index", None) is not None
                   for d in devices):
                # a REAL multislice allocation where the hybrid layout
                # failed: the flat fallback may place the dcn axis
                # across physical slice boundaries — exactly the
                # inversion named above — so say so instead of
                # silently degrading
                logger.warning(
                    "create_hybrid_device_mesh failed on a multislice "
                    "allocation (%s); falling back to a flat device "
                    "mesh — the 'dcn' axis may not follow physical "
                    "slice boundaries, inverting the hierarchical "
                    "sync's fast/slow tiers", e)
            mesh_devices = None
    if mesh_devices is None:
        try:
            from jax.experimental import mesh_utils
            mesh_devices = mesh_utils.create_device_mesh(
                sizes, devices=devices)
        except Exception:
            mesh_devices = np.array(devices).reshape(sizes)
    return Mesh(mesh_devices, tuple(names))


def axis_coord_maps(mesh: Mesh) -> Dict[str, Dict[int, int]]:
    """``{axis: {logical_device_position: coordinate_along_axis}}`` for
    every mesh axis of size > 1 — the per-axis classifier inputs for
    :func:`bigdl_tpu.utils.xla_cost.per_axis_hlo_bytes`.

    HLO replica groups name devices by their position in the mesh's
    flattened device order (the same convention as
    ``parallel.hierarchy.dcn_slice_map``, which is this map's ``dcn``
    row).  Under the per-axis map a collective "crosses groups" exactly
    when one of its replica groups holds two devices with different
    coordinates along that axis — i.e. when its payload moves over that
    axis's links — so one compiled program classifies into a full
    {op, axis} byte matrix."""
    n = int(np.prod(mesh.devices.shape))
    out: Dict[str, Dict[int, int]] = {}
    for axis in mesh.axis_names:
        if mesh.shape[axis] <= 1:
            continue
        ai = mesh.axis_names.index(axis)
        coords = np.indices(mesh.devices.shape)[ai].reshape(-1)
        out[axis] = {i: int(coords[i]) for i in range(n)}
    return out


def mesh_axes(mesh: Mesh) -> Dict[str, int]:
    """``{axis: size}`` of a mesh — the canonical topology rendering
    the checkpoint manifest records (``utils/file.checkpoint_topology``)
    and the elastic N->M resume compares against the live mesh to
    decide whether a restore is resharding."""
    return {str(a): int(s) for a, s in
            zip(mesh.axis_names, mesh.devices.shape)}


def data_parallel_mesh(devices=None) -> Mesh:
    """All devices on one ``data`` axis — the reference's only strategy
    (AllReduceParameter over nodes; SURVEY §2.6)."""
    return make_mesh({"data": -1}, devices)


def batch_sharding(mesh: Mesh, *, extra_axes: Sequence[str] = ()) \
        -> NamedSharding:
    """Sharding for a batch-leading array: batch dim over every
    data-like axis present in the mesh (``dcn`` included — each slice
    consumes its own sub-batch, which is exactly what makes the
    hierarchical gradient sync's cross-slice hop small)."""
    batch_axes = tuple(a for a in BATCH_AXES if a in mesh.axis_names)
    spec = P(batch_axes if batch_axes else None, *extra_axes)
    return NamedSharding(mesh, spec)


class MeshConfig:
    """Declarative parallelism config used by the Optimizer (the
    TPU-native replacement for the reference's Engine node/core conf).

    Example::

        MeshConfig(data=-1)                      # pure DP (default)
        MeshConfig(data=2, model=4)              # DP×TP
        MeshConfig(data=2, pipe=2, model=2)      # 3D
        MeshConfig(dcn=2, data=-1)               # 2 slices × DP
    """

    def __init__(self, **axes: int):
        self.axes = axes or {"data": -1}

    def build(self, devices=None) -> Mesh:
        return make_mesh(self.axes, devices)
