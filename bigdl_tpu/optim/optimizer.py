"""The Optimizer façade — distributed training runtime.

Reference: optim/Optimizer.scala:48 (user façade: checkpoint trigger,
validation trigger/methods, summaries, gradient clipping, per-submodule
optim methods, setEndWhen) and optim/DistriOptimizer.scala (the
two-Spark-jobs-per-iteration engine, SURVEY §3.1).

TPU-native redesign (SURVEY §7 design stance): the reference's per-
iteration choreography — broadcast weights via BlockManager, fan out
model replicas over executor threads, shard gradients into a parameter-
server ring, FP16-compress wires, drop stragglers — collapses into ONE
jit-compiled SPMD step over a device mesh:

* model replicas        → the mesh's data axis (batch sharding)
* AllReduceParameter    → XLA psum/reduce-scatter inserted by sharding
                          propagation (parameters/AllReduceParameter.scala:81)
* FP16 wire compression → native bf16 compute dtype
* straggler dropping    → unnecessary: SPMD lockstep
* Engine thread pools   → XLA scheduling

Capabilities preserved 1:1: OptimMethod zoo + per-submodule methods,
Triggers, ValidationMethods, checkpoint/resume with epoch position
(DistriOptimizer.scala:137-147), gradient clipping (Optimizer.scala:435,
453), train/validation summaries, per-iteration throughput logging
(DistriOptimizer.scala:425-431).
"""

from __future__ import annotations

import logging
import math
import os
import random
import threading
import time
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from bigdl_tpu.core.module import (
    Module, partition, combine, forward_context,
)
from bigdl_tpu.optim.methods import OptimMethod, SGD, Plateau
from bigdl_tpu.optim.trigger import Trigger
from bigdl_tpu.optim.validation import ValidationMethod, ValidationResult
from bigdl_tpu.parallel.compression import get_codec as _get_wire_codec
from bigdl_tpu.parallel.mesh import (
    MeshConfig, batch_sharding, data_parallel_mesh,
)
from bigdl_tpu.parallel.sharding import (
    ShardingRules, shard_model_params, replicated,
)
from bigdl_tpu import telemetry
from bigdl_tpu.data.pipeline import (
    PipelineState, dataset_seed, epoch_iter, skip_batches,
    skip_samples, supports_epoch, PIPELINE_STATE_VERSION,
)
from bigdl_tpu.telemetry import events as _te
from bigdl_tpu.telemetry import families as _tm, tracing as _tt
from bigdl_tpu.telemetry import perf as _tp
from bigdl_tpu.telemetry.health import HealthWatchdog
from bigdl_tpu.utils import chaos
from bigdl_tpu.utils.file import CheckpointManager, load_checkpoint
from bigdl_tpu.utils.xla_cost import compiled_flops
from bigdl_tpu.optim.metrics import Metrics
from bigdl_tpu.utils.rng import get_seed

logger = logging.getLogger("bigdl_tpu.optim")

# driver scalars persisted in SHARDED checkpoints: a fixed contract so
# the saved orbax tree and the resume-time abstract tree always match
# structurally (self.state grows transient keys during the loop)
_DRIVER_KEYS = ("epoch", "neval", "records", "loss", "score")

# Exception types that signal a PROGRAMMING error: retrying from a
# checkpoint would re-run the same code into the same wall, burning the
# whole retry budget on a bug.  Everything else (OSError, RuntimeError —
# including jaxlib's XlaRuntimeError subclass — ConnectionError,
# chaos.FaultInjected) is treated as transient: preemption, collective
# timeouts, and IO blips all surface as runtime errors.
_NON_RETRYABLE = (ValueError, TypeError, KeyError, IndexError,
                  AttributeError, NameError, AssertionError,
                  NotImplementedError, ZeroDivisionError, ImportError,
                  SyntaxError)


def _is_transient(e: BaseException) -> bool:
    return not isinstance(e, _NON_RETRYABLE)


def _is_oom(e: BaseException) -> bool:
    """Does this exception look like a device allocation failure?
    XLA/PJRT surface HBM exhaustion as an XlaRuntimeError whose status
    is RESOURCE_EXHAUSTED (message also carries "Out of memory"); the
    chaos seam (BIGDL_TPU_CHAOS_OOM) fakes the same token."""
    msg = str(e)
    return "RESOURCE_EXHAUSTED" in msg or "Out of memory" in msg


__all__ = ["Optimizer"]


class Optimizer:
    """``Optimizer(model, dataset, criterion).optimize()``
    (reference optim/Optimizer.scala:48, Optimizer.apply:603)."""

    def __init__(self, model: Module, dataset, criterion,
                 batch_size: Optional[int] = None):
        from bigdl_tpu.dataset.dataset import LocalDataSet, Sample
        from bigdl_tpu.dataset.transformer import SampleToMiniBatch
        if batch_size is not None:
            # convenience: raw Sample sequence + batch size
            # (≙ Optimizer.apply(model, sampleRDD, criterion, batchSize))
            if isinstance(dataset, (list, tuple)):
                dataset = LocalDataSet(list(dataset))
            dataset = dataset.transform(SampleToMiniBatch(batch_size))
        self.model = model
        self.dataset = dataset
        self.criterion = criterion

        self.optim_method: OptimMethod = SGD()
        self.optim_methods: Optional[Dict[str, OptimMethod]] = None
        self.end_when: Trigger = Trigger.max_epoch(1)
        self.val_trigger: Optional[Trigger] = None
        self.val_dataset = None
        self.val_methods: Optional[List[ValidationMethod]] = None
        self.checkpoint_path: Optional[str] = None
        self.checkpoint_sharded = False
        self.checkpoint_trigger: Optional[Trigger] = None
        self.overwrite_checkpoint = True
        self.grad_clip_const: Optional[Tuple[float, float]] = None
        self.grad_clip_norm: Optional[float] = None
        self.mesh_config = MeshConfig(data=-1)
        self.sharding_rules = ShardingRules()
        # declarative parallelism (set_partition_plan): the resolved
        # PartitionPlan, when one drives this optimizer's layout
        self.partition_plan = None
        self.compute_dtype = None  # e.g. jnp.bfloat16 for mixed precision
        # gradient-sync routing (set_gradient_sync): OFF by default —
        # the flat XLA-inserted sync compiles exactly as it always has
        self.grad_sync_hierarchical = False
        self.grad_sync_wire_dtype = None
        # plan resolution runs once in bench (artifact stamping) and
        # again at step build — its warnings dedupe per (key, mesh)
        self._grad_sync_warned: set = set()
        self.log_interval: Optional[int] = None  # None = auto
        self.iters_per_dispatch = 1
        self.profile_dir: Optional[str] = None
        self.profile_steps: Tuple[int, int] = (2, 5)
        self.train_summary = None
        self.metrics = Metrics()
        self.val_summary = None
        self.state: Dict[str, Any] = {"epoch": 1, "neval": 1,
                                      "records": 0, "loss": float("nan"),
                                      "score": float("-inf")}
        # XLA cost analysis of the compiled train program, normalized to
        # one train iteration as an EXECUTION-WEIGHTED average across
        # compiled signatures (a ragged final batch compiles a smaller
        # program; weighting by steps actually run keeps the average
        # honest where a max() would overstate); None until first compile
        self.compiled_flops_per_iteration: Optional[float] = None
        self._executed_flops = 0.0
        self._executed_steps = 0
        # every train program this optimizer compiled, in order: one
        # entry per batch signature is the healthy count (a second entry
        # for the same shapes is the relayout recompile _aot exists to
        # prevent) — and the seconds spent tracing + lowering them in
        # Python, which no cache saves, and compiling them, which the
        # persistent compilation cache does
        self.step_executables: List[Any] = []
        self.step_lower_seconds = 0.0
        self.step_compile_seconds = 0.0
        self._resume_from: Optional[str] = None
        self._last_val_neval = -1
        self._last_ckpt_neval = -1
        self.retry_times = int(os.environ.get(
            "BIGDL_TPU_FAILURE_RETRY_TIMES", "5"))
        self.retry_interval_s = float(os.environ.get(
            "BIGDL_TPU_FAILURE_RETRY_INTERVAL_S", "120"))
        self.retry_backoff_s = float(os.environ.get(
            "BIGDL_TPU_FAILURE_BACKOFF_S", "1.0"))
        self.retry_backoff_cap_s = float(os.environ.get(
            "BIGDL_TPU_FAILURE_BACKOFF_CAP_S", "60.0"))
        self.retry_jitter = 0.25
        self.checkpoint_keep_n: Optional[int] = None
        self._ckpt_mgr: Optional[CheckpointManager] = None
        # preemption (SIGTERM) handling: the handler only sets this
        # flag; the loop acts on it at the next safe step boundary
        self._preempt_requested = False
        self.preempted = False
        # health watchdog + introspection sidecar: both OFF by default
        # (a run without them pays nothing new; see
        # set_health_watchdog / set_debug_server)
        self.watchdog: Optional[HealthWatchdog] = None
        self.watchdog_halted = False
        self._halt_requested = False
        # fleet telemetry (telemetry.fleet): OFF by default — an
        # unarmed run performs no allgather and pays nothing new
        self._fleet_monitor = None
        self.debug_host: Optional[str] = None
        self.debug_port: Optional[int] = None
        self.debug_server = None
        self._last_ckpt_generation: Optional[int] = None
        self._last_ckpt_path: Optional[str] = None
        self._run_started: Optional[float] = None
        # input-pipeline service (bigdl_tpu.data): batches consumed in
        # the CURRENT epoch (the PipelineState offset persisted with
        # every checkpoint), the restore snapshot a resume applies, and
        # the off-by-default async device-prefetch depth
        self._epoch_offset = 0
        self._pipeline_restore: Optional[Dict[str, Any]] = None
        self.device_prefetch_ahead: Optional[int] = None
        self._active_dp = None
        # elastic (N->M) resume bookkeeping: the global batch size the
        # last step consumed (recorded in the pipeline sidecar so a
        # resume at a different width can sanity-check its own), and
        # the topology manifest of the checkpoint being resumed (None
        # = fresh run, or a pre-elastic checkpoint without one)
        self._last_global_batch: Optional[int] = None
        self._resume_topology: Optional[Dict[str, Any]] = None

    # ---- configuration (reference Optimizer.scala setters) -------------

    def set_optim_method(self, method: OptimMethod) -> "Optimizer":
        self.optim_method = method
        return self

    def set_optim_methods(self, methods: Dict[str, OptimMethod]) \
            -> "Optimizer":
        """Per-submodule optim methods keyed by module name
        (reference setOptimMethods, Optimizer.scala:370)."""
        self.optim_methods = methods
        return self

    def set_end_when(self, trigger: Trigger) -> "Optimizer":
        self.end_when = trigger
        return self

    def set_validation(self, trigger: Trigger, dataset,
                       methods: Sequence[ValidationMethod],
                       batch_size: Optional[int] = None) -> "Optimizer":
        from bigdl_tpu.dataset.dataset import LocalDataSet
        from bigdl_tpu.dataset.transformer import SampleToMiniBatch
        if batch_size is not None:
            if isinstance(dataset, (list, tuple)):
                dataset = LocalDataSet(list(dataset), shuffle=False)
            dataset = dataset.transform(SampleToMiniBatch(batch_size))
        self.val_trigger = trigger
        self.val_dataset = dataset
        self.val_methods = list(methods)
        return self

    def set_checkpoint(self, path: str, trigger: Trigger,
                       is_overwrite: bool = True,
                       sharded: bool = False,
                       keep_n: Optional[int] = None) -> "Optimizer":
        """``sharded=True`` writes orbax checkpoint DIRECTORIES whose
        array shards are saved by their owning hosts — required once
        parameters are sharded across hosts (the default ``.npz``
        format gathers every leaf to the saving host).

        ``keep_n`` keeps that many good checkpoint generations and
        garbage-collects older ones (implies numbered checkpoints, so
        ``is_overwrite`` is forced off).  All checkpoints commit
        atomically with a CRC manifest; resume-after-failure walks back
        past corrupt or uncommitted generations (see
        docs/fault_tolerance.md)."""
        self.checkpoint_path = path
        self.checkpoint_trigger = trigger
        self.overwrite_checkpoint = is_overwrite and keep_n is None
        self.checkpoint_sharded = sharded
        self.checkpoint_keep_n = keep_n
        self._ckpt_mgr = None
        return self

    def resume(self, checkpoint_file: str) -> "Optimizer":
        """Resume epoch position + weights + optim state from a
        checkpoint (reference Module.load + OptimMethod.load pattern,
        models/lenet/Train.scala:49,73)."""
        self._resume_from = checkpoint_file
        return self

    def set_gradient_clipping_by_l2_norm(self, clip_norm: float) \
            -> "Optimizer":
        self.grad_clip_norm = float(clip_norm)
        return self

    def set_constant_gradient_clipping(self, min_v: float, max_v: float) \
            -> "Optimizer":
        self.grad_clip_const = (float(min_v), float(max_v))
        return self

    def disable_gradient_clipping(self) -> "Optimizer":
        self.grad_clip_const = None
        self.grad_clip_norm = None
        return self

    def set_mesh(self, mesh_config: MeshConfig,
                 rules: Optional[ShardingRules] = None) -> "Optimizer":
        """Choose the parallelism layout (new capability vs reference)."""
        self.mesh_config = mesh_config
        if rules is not None:
            self.sharding_rules = rules
        return self

    def set_partition_plan(self, plan) -> "Optimizer":
        """Drive the whole parallelism layout from one declarative
        :class:`~bigdl_tpu.parallel.plan.PartitionPlan`: resolve it
        against the model (raising
        :class:`~bigdl_tpu.parallel.plan.PlanError` for compositions
        the planner cannot honor, with the offending axis/leaf named),
        apply the module wirings (ring attention, expert dispatch,
        pipeline staging, embedding-table row sharding), and install
        the composed sharding rules + mesh so ``_build_step``/
        :meth:`compile_step` emit the same program shape for every
        composition — dp/fsdp/tp/sp/ep/pp all lower through the one
        step builder.  Accepts a ``PartitionPlan`` or an
        already-resolved ``ResolvedPlan``.  See docs/parallelism.md
        "Declarative composition"."""
        from bigdl_tpu.parallel.plan import (
            PlanError, ResolvedPlan, resolve,
        )
        rp = plan if isinstance(plan, ResolvedPlan) else resolve(
            plan, self.model,
            hierarchical=self.grad_sync_hierarchical,
            compute_dtype=self.compute_dtype)
        if rp.pp_schedule == "1f1b":
            # the 1F1B schedule means per-microbatch losses — only a
            # mean-reduction criterion keeps the math equal to the
            # full-batch step (the _grad_sync_plan guard's logic)
            crit = self.criterion
            crit_mods = ([m for _, m in crit.named_modules()]
                         if hasattr(crit, "named_modules") else [crit])
            if any(getattr(m, "size_average", True) is False
                   for m in crit_mods):
                raise PlanError(
                    "pp_schedule='1f1b' requires a mean-reduction "
                    "criterion (size_average=True): the schedule "
                    "means per-microbatch losses, which changes the "
                    "math for a sum-reduction criterion")
        rp.apply()
        for desc, _fn in rp.wirings:
            logger.info("partition plan: %s", desc)
        self.partition_plan = rp
        return self.set_mesh(rp.mesh_config, rp.rules)

    def set_compute_dtype(self, dtype) -> "Optimizer":
        """bf16 compute (≙ FP16 gradient compression — but end-to-end)."""
        self.compute_dtype = dtype
        return self

    def set_gradient_sync(self, hierarchical: bool = False,
                          wire_dtype=None) -> "Optimizer":
        """Route the step's gradient mean through
        :func:`bigdl_tpu.parallel.hierarchy.hierarchical_grad_sync`:
        reduce-scatter within each slice over the fast (``data``/
        ``fsdp``) axes, move only the scattered shards across the
        slow ``dcn`` axis — compressed to ``wire_dtype`` (``"bf16"`` ≙
        the reference's FP16CompressedTensor, or ``"int8"`` with
        per-bucket scales and stochastic rounding; fp32 master
        accumulation either way) — then all-gather within-slice.
        Cross-slice traffic drops by the slice size versus the flat
        all-reduce, and the codec shrinks what remains.

        OFF by default: without this call (or with
        ``hierarchical=False``) the step compiles exactly as before —
        the flat XLA-inserted sync behind ``NamedSharding``.  The
        hierarchical path requires a batch-parallel mesh
        (``MeshConfig(dcn=2, data=-1)``) with fully replicated
        parameters; meshes with tensor/pipeline axes or sharding rules
        raise at ``optimize()``.  Models with batch-statistic layers
        (BatchNorm) switch to shard-local statistics under this path
        (warned at ``optimize()``).  See docs/parallelism.md
        "Hierarchical sync & wire compression"."""
        codec = self._resolve_wire(wire_dtype, hierarchical)
        self.grad_sync_hierarchical = bool(hierarchical)
        self.grad_sync_wire_dtype = None if codec is None else wire_dtype
        return self

    @staticmethod
    def _resolve_wire(wire_dtype, hierarchical):
        """The ONE wire-dtype resolver (setter and plan backstop both
        call it): no-compression spellings ("fp32"/"none"/jnp.float32)
        normalize to codec None so every consumer (plan, telemetry
        stamp, estimator) sees one spelling of the uncompressed wire;
        typos fail at configure, not at trace; a real codec without
        hierarchical=True is rejected."""
        codec = _get_wire_codec(wire_dtype)
        if codec is not None and not hierarchical:
            raise ValueError(
                "set_gradient_sync: wire_dtype has no effect "
                "without hierarchical=True — wire compression "
                "applies to the hierarchical sync's dcn hop")
        return codec

    def _grad_sync_warn(self, key, mesh, msg, *args):
        """Warn once per (reason, mesh shape): bench resolves the plan
        for artifact stamping and the step build resolves it again —
        the operator should not read every advisory twice."""
        k = (key, tuple(sorted(dict(mesh.shape).items())))
        if k not in self._grad_sync_warned:
            self._grad_sync_warned.add(k)
            logger.warning(msg, *args)

    def _grad_sync_plan(self, mesh):
        """Resolve the set_gradient_sync config against the mesh the
        step is being built for.  None = flat sync (the default step,
        byte-identical to a build that never saw this feature)."""
        if not self.grad_sync_hierarchical:
            # backstop for a bypassed setter — same resolver, so a
            # no-compression spelling stays a no-op
            self._resolve_wire(self.grad_sync_wire_dtype,
                               hierarchical=False)
            return None
        from bigdl_tpu.parallel.hierarchy import (
            DCN_AXIS, batch_axes_of, fast_batch_axes_of,
        )
        batch_axes = batch_axes_of(mesh)
        n_batch = 1
        for a in batch_axes:
            n_batch *= mesh.shape[a]
        if n_batch <= 1:
            self._grad_sync_warn(
                "no-batch", mesh,
                "hierarchical gradient sync requested but the mesh has "
                "no batch parallelism (axes %s); using the flat step",
                dict(mesh.shape))
            return None
        non_batch = [a for a in mesh.axis_names
                     if a not in batch_axes and mesh.shape[a] > 1]
        if non_batch:
            raise ValueError(
                f"hierarchical gradient sync supports batch-parallel "
                f"meshes (dcn/data/fsdp axes); this mesh also has "
                f"{non_batch} — use the flat sync when composing with "
                f"tensor/pipeline/sequence/expert parallelism")
        if self.sharding_rules is not None and (
                self.sharding_rules.rules or self.sharding_rules.fsdp):
            raise ValueError(
                "hierarchical gradient sync requires fully replicated "
                "parameters (the primitive reduce-scatters the flat "
                "concatenated gradient); drop the sharding rules or "
                "keep the flat sync")
        # the hierarchical step pmean's the per-shard loss and MEANS
        # the per-shard gradients — correct only when the criterion is
        # itself a per-sample mean.  A sum-reduction criterion would
        # silently train at lr/n_devices with an n_devices-smaller
        # logged loss than the flat step.
        crit = self.criterion
        # walk the whole criterion tree (criteria are Modules):
        # composites (MultiCriterion/ParallelCriterion's crits,
        # TimeDistributedCriterion's critrn, CrossEntropyCriterion's
        # inner) must not smuggle a batch-sum sub-criterion past the
        # guard.  TimeDistributedCriterion's OWN flag is excluded: it
        # normalizes over the time axis, whose extent is identical on
        # every shard, so it never changes the batch math.
        from bigdl_tpu.nn.criterion import (
            GaussianCriterion, KLDCriterion, L1HingeEmbeddingCriterion,
            TimeDistributedCriterion,
        )
        # criteria that sum over the batch WITHOUT exposing a
        # size_average flag — the attribute probe below can't see them
        _BATCH_SUM_CRITERIA = (KLDCriterion, GaussianCriterion,
                               L1HingeEmbeddingCriterion)
        crit_mods = ([m for _, m in crit.named_modules()]
                     if hasattr(crit, "named_modules") else [crit])
        if any((getattr(m, "size_average", True) is False
                and not isinstance(m, TimeDistributedCriterion))
               or isinstance(m, _BATCH_SUM_CRITERIA)
               for m in crit_mods):
            raise ValueError(
                "hierarchical gradient sync requires a mean-reduction "
                "criterion (size_average=True): the schedule averages "
                "per-shard losses/gradients, which changes the math "
                "for a sum-reduction criterion — use size_average="
                "True or keep the flat sync")
        # batch-statistic modules (BatchNorm and friends, detected by
        # their running_mean buffer): inside the shard_map each device
        # normalizes with its LOCAL shard's mean/var — the standard
        # data-parallel BatchNorm — whereas the flat GSPMD step reduces
        # the statistics over the global sharded batch.  Legitimate and
        # common (torch DDP's default), but losses will NOT match the
        # flat step bit-for-bit, and the buffer pmean after each step
        # averages per-shard variances (biased low by the variance of
        # the shard means).  Warn, don't reject.
        bn_mods = [f"{prefix} ({mod.name})"
                   for prefix, mod in self.model.named_modules()
                   if "running_mean" in getattr(mod, "_buffers", {})]
        if bn_mods:
            shown = ", ".join(bn_mods[:3])
            if len(bn_mods) > 3:
                shown += f", ... ({len(bn_mods)} total)"
            self._grad_sync_warn(
                "batch-stats", mesh,
                "hierarchical gradient sync: %s keep(s) batch "
                "statistics — each device will normalize with its "
                "local batch shard's mean/var (standard data-parallel "
                "BatchNorm), not the global-batch statistics the flat "
                "step computes, so losses/buffers differ slightly from "
                "flat sync; see docs/parallelism.md 'Hierarchical sync "
                "& wire compression'", shown)
        # weighted normalization (class_weights, and the paddingValue
        # mask it shares a denominator with): the criterion divides by
        # the LOCAL shard's weight sum, so the step's pmean of local
        # means is sum(total_s/W_s)/n, not the flat step's global
        # sum(total_s)/sum(W_s) — per-shard rescaling of loss AND
        # gradients whenever the W_s differ across shards.  Warn, don't
        # reject: with uniform weights and no padding rows W_s is the
        # shard batch size and the two agree exactly.  Detected by a
        # class_weights buffer or an explicitly configured paddingValue
        # anywhere in the criterion tree; the default paddingValue=-1
        # masks too, but whether -1 ever appears in targets is data the
        # plan can't see, so that case is a docs caveat, not a warning.
        if any("class_weights" in getattr(m, "_buffers", {})
               or getattr(m, "padding_value", -1) != -1
               for m in crit_mods):
            self._grad_sync_warn(
                "weighted-criterion", mesh,
                "hierarchical gradient sync: the criterion normalizes "
                "by a per-sample weight sum (class weights and/or "
                "paddingValue masking) — each device divides by its "
                "LOCAL shard's weight sum, so losses/gradients are "
                "rescaled per shard versus the flat step's global "
                "weighted mean when shards draw different class/padding "
                "mixes; see docs/parallelism.md 'Hierarchical sync & "
                "wire compression'")
        wire = self.grad_sync_wire_dtype
        if _get_wire_codec(wire) is None:
            wire = None  # uncompressed spellings: one canonical label
        elif DCN_AXIS not in mesh.axis_names:
            self._grad_sync_warn(
                "no-dcn-wire", mesh,
                "gradient wire compression (%r) requested but the mesh "
                "has no '%s' axis; there is no slow hop to compress — "
                "syncing uncompressed", wire, DCN_AXIS)
            wire = None
        return {"batch_axes": batch_axes,
                "fast_axes": fast_batch_axes_of(mesh),
                "dcn_axis": DCN_AXIS,
                "wire_dtype": wire}

    def set_log_interval(self, n: int) -> "Optimizer":
        """Fetch/log the loss every n iterations instead of every
        iteration.  The device step itself never blocks on the host —
        readback of up to n losses is batched, so the device queue stays
        full (the reference paid one Spark-job barrier per iteration;
        SPMD need not pay an analogous host sync)."""
        self.log_interval = int(n)
        return self

    def set_iterations_per_dispatch(self, k: int) -> "Optimizer":
        """Run up to ``k`` consecutive train steps inside ONE compiled
        dispatch (a ``lax.scan`` over a stacked window of minibatches).
        The TPU-idiomatic fix for per-dispatch launch latency, exactly
        analogous to the reference collapsing ~500 Spark tasks/iteration
        into 1 multithreaded task per node after measuring >10% spent in
        task scheduling (docs/docs/whitepaper.md:171-177, fig 8): on a
        high-latency host<->device link each dispatch pays a fixed
        launch cost; a k-step window pays it once.

        Semantics are preserved: windows are trimmed so that validation,
        checkpoint, and end triggers still fire on the exact iteration
        they would have with ``k=1``, and per-iteration loss/throughput
        logging is unchanged (losses come back as a stacked array).
        Loss-reading triggers (minLoss) force ``k=1``.  Batches inside a
        window must be uniform in shape; ragged tails fall back to
        single-step dispatch so only two programs are ever compiled."""
        self.iters_per_dispatch = max(1, int(k))
        return self

    def set_profiler(self, logdir: str,
                     start_iteration: int = 2,
                     num_iterations: int = 5) -> "Optimizer":
        """Capture a jax.profiler trace of iterations
        [start_iteration, start_iteration + num_iterations) into logdir
        (view in TensorBoard's profile tab)."""
        self.profile_dir = logdir
        self.profile_steps = (int(start_iteration), int(num_iterations))
        return self

    def set_health_watchdog(self, watchdog: Optional[HealthWatchdog]
                            = None, **kwargs) -> "Optimizer":
        """Arm the training-health watchdog: in-graph non-finite
        detection on loss and global gradient norm (the norm reuses the
        grad-clip computation when ``grad_clip_norm`` is set), EWMA
        loss-spike and step-time-outlier detection, and a
        data-starvation detector — each anomaly class with a ``warn`` /
        ``skip_step`` / ``checkpoint_and_halt`` policy (see
        :class:`bigdl_tpu.telemetry.health.HealthWatchdog` and
        docs/observability.md).  Pass a configured watchdog, OR kwargs
        forwarded to its constructor — never both, that raises (the
        kwargs would be silently ignored, and a policy the caller
        believes is set but isn't is exactly the failure this subsystem
        exists to prevent).  No arguments arms the defaults (non-finite
        halts, the rest warn).

        The watchdog needs per-iteration loss readback, so it forces
        ``log_interval`` to 1 and single-step dispatch — health
        monitoring trades the batched-readback optimization for
        detection latency of one step.  Disarm with
        ``self.watchdog = None``."""
        if watchdog is not None and kwargs:
            raise ValueError(
                "set_health_watchdog: pass a configured HealthWatchdog "
                "OR constructor kwargs, not both (the kwargs would be "
                f"silently ignored: {sorted(kwargs)})")
        self.watchdog = (watchdog if watchdog is not None
                         else HealthWatchdog(**kwargs))
        return self

    def set_fleet_monitor(self, monitor=None, **kwargs) -> "Optimizer":
        """Arm cross-process fleet telemetry: once per readback window
        every process contributes a fixed-shape stats vector (step
        wall, data-wait, RSS, HBM in use) via one allgather; the
        derived table — per-host numbers, slowest host, skew ratio —
        serves on ``/statusz`` under ``fleet`` and publishes the
        ``fleet_step_skew`` gauge.  With a health watchdog armed too,
        each sample feeds its ``straggler`` anomaly class (warn by
        default; see :class:`bigdl_tpu.telemetry.fleet.FleetMonitor`
        and docs/observability.md).

        Pass a configured monitor OR constructor kwargs, never both
        (same contract as ``set_health_watchdog``).  In a multi-process
        run EVERY process must arm it — the per-window allgather is a
        collective.  Disarm with ``self._fleet_monitor = None``."""
        from bigdl_tpu.telemetry.fleet import FleetMonitor
        if monitor is not None and kwargs:
            raise ValueError(
                "set_fleet_monitor: pass a configured FleetMonitor OR "
                "constructor kwargs, not both (the kwargs would be "
                f"silently ignored: {sorted(kwargs)})")
        self._fleet_monitor = (monitor if monitor is not None
                               else FleetMonitor(**kwargs))
        return self

    def set_device_prefetch(self, n_ahead: int = 1) -> "Optimizer":
        """Stage batch N+1 into the mesh's data sharding on a
        background thread while step N runs
        (:class:`bigdl_tpu.data.DevicePrefetch`): the synchronous
        host->device transfer leaves the hot loop, at the cost of
        ``n_ahead`` extra batches of device memory.  Off by default —
        without this call the data path performs exactly the staging it
        always did.  ``n_ahead=0`` disables.  Ignored (with a warning)
        under ``iterations_per_dispatch > 1``, whose window staging
        stacks batches itself, and under multi-process training, whose
        loop assembles global batches from per-process locals
        itself."""
        n = int(n_ahead)
        if n < 0:
            raise ValueError("set_device_prefetch: n_ahead must be >= 0")
        self.device_prefetch_ahead = n or None
        return self

    def set_debug_server(self, port: int = 0,
                         host: str = "127.0.0.1") -> "Optimizer":
        """Serve live introspection endpoints — ``GET /statusz`` (step,
        epoch, last good checkpoint generation, watchdog state, recent
        flight-recorder events), ``GET /tracez`` (recent spans), ``POST
        /profilez`` (time-boxed jax.profiler capture), plus
        ``/healthz`` and ``/metrics`` — on a sidecar HTTP thread for
        the duration of ``optimize()``.  ``port=0`` picks an ephemeral
        port (read it from ``self.debug_server.port`` once running).
        Off unless called."""
        self.debug_host = host
        self.debug_port = int(port)
        return self

    def set_train_summary(self, summary) -> "Optimizer":
        self.train_summary = summary
        return self

    def set_val_summary(self, summary) -> "Optimizer":
        self.val_summary = summary
        return self

    # ---- optim-method grouping (per-submodule methods) ------------------

    def _group_indices(self, paths: List[str]) \
            -> List[Tuple[str, List[int]]]:
        """Assign each param leaf (by dotted path) to an optim-method
        group.  Reference setOptimMethods keys by submodule name
        (Optimizer.scala:370); we match method keys against path prefixes
        and against the ``name`` of any module in the tree."""
        if not self.optim_methods:
            return [("__default__", list(range(len(paths))))]
        # module-name → path-prefix map
        name_prefixes: Dict[str, List[str]] = {}
        for prefix, mod in self.model.named_modules():
            name_prefixes.setdefault(mod.name, []).append(prefix)
        groups: Dict[str, List[int]] = {k: [] for k in self.optim_methods}
        for i, p in enumerate(paths):
            target = None
            for key in self.optim_methods:
                prefixes = [key] + name_prefixes.get(key, [])
                if any(p == pre or p.startswith(pre + ".")
                       or p.startswith(pre + "[")
                       for pre in prefixes if pre):
                    target = key
                    break
            if target is None:
                raise ValueError(
                    f"setOptimMethods: no optim method covers parameter "
                    f"'{p}'")
            groups[target].append(i)
        return [(k, v) for k, v in groups.items() if v]

    # ---- the jitted SPMD train step -------------------------------------

    def _build_step(self, mesh, group_names, spec_groups=None,
                    window=False, health=False, raw=False):
        """``raw=True`` returns the bare jitted step (no AOT cache
        wrapper) so :meth:`compile_step` can lower it for HLO
        introspection."""
        assert not (window and health), \
            "watchdog monitoring forces single-step dispatch"
        criterion = self.criterion
        clip_const = self.grad_clip_const
        clip_norm = self.grad_clip_norm
        methods = ([self.optim_method] if group_names == ["__default__"]
                   else [self.optim_methods[g] for g in group_names])
        compute_dtype = self.compute_dtype
        # nonfinite-guard policy is a TRACE-TIME constant: the guard
        # compiles into the step only when the watchdog wants updates
        # discarded (skip_step / checkpoint_and_halt)
        guard_updates = health and self.watchdog is not None \
            and self.watchdog.guard_updates

        def clip(grads):
            """Clip one group's grads; returns (clipped, l2_norm).  The
            norm is computed at most once — the watchdog's in-graph
            monitor reuses the grad-clip norm when ``grad_clip_norm``
            is set instead of paying a second reduction — and is None
            when nothing needs it."""
            if clip_const is not None:
                lo, hi = clip_const
                grads = jax.tree_util.tree_map(
                    lambda g: jnp.clip(g, lo, hi), grads)
            total = None
            if clip_norm is not None or health:
                leaves = jax.tree_util.tree_leaves(grads)
                total = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                                     for g in leaves))
            if clip_norm is not None:
                scale = jnp.minimum(1.0, clip_norm / (total + 1e-12))
                grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
            return grads, total

        merge_groups = self._merge_groups_host  # jit-traceable as-is
        sync_plan = self._grad_sync_plan(mesh)
        # declarative pp (set_partition_plan with pp_schedule="1f1b"):
        # the fwd+loss+bwd all run inside the pipeline schedule, so the
        # step swaps the flat value_and_grad for train_step_on_mesh and
        # re-selects the param-leaf grads in partition() order — clip /
        # regularizers / optim methods / watchdog guard compose after,
        # unchanged.  Statics (block count, param flags) are trace-time
        # constants.
        rp = self.partition_plan
        pipe_1f1b = False
        if rp is not None and getattr(rp, "pp_schedule", None) == "1f1b":
            from bigdl_tpu.parallel.pipeline import Pipeline as _Pipeline
            if isinstance(self.model, _Pipeline) \
                    and rp.pp_axis in mesh.axis_names \
                    and mesh.shape[rp.pp_axis] > 1:
                pipe_1f1b = True
                from bigdl_tpu.core.module import _param_flags
                assert sync_plan is None, \
                    "1F1B does not compose with hierarchical grad sync"
                pipe_axis = rp.pp_axis
                pipe_n_blocks = len(self.model.blocks)
                pipe_flags = _param_flags(self.model.blocks[0])
                group_idx = self._group_idx
        if sync_plan is not None:
            from jax.sharding import PartitionSpec as _PS
            from bigdl_tpu.parallel.hierarchy import (
                hierarchical_grad_sync, shard_map as _shard_map,
            )
            from bigdl_tpu.telemetry import collectives as _tc
            b_axes = sync_plan["batch_axes"]

            def _batch_specs(tree):
                # batch-leading leaves shard over every batch axis;
                # scalars (if any) replicate
                return jax.tree_util.tree_map(
                    lambda l: (_PS(b_axes) if getattr(l, "ndim", 0) >= 1
                               else _PS()), tree)

            def _hier_value_and_grad(loss_of, params_groups, rest, x, y,
                                     rng):
                def local(pg, rest_, x_, y_, rng_):
                    # decorrelate per-shard randomness (dropout, int8
                    # stochastic rounding) by the device's linear
                    # position on the batch axes
                    idx = 0
                    for a in b_axes:
                        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
                    rng_l = jax.random.fold_in(rng_, idx)
                    (loss, m2), grads = jax.value_and_grad(
                        lambda g: loss_of(g, rest_, x_, y_, rng_l),
                        has_aux=True)(pg)
                    grads = hierarchical_grad_sync(
                        grads, mesh, dcn_axis=sync_plan["dcn_axis"],
                        fast_axes=sync_plan["fast_axes"],
                        wire_dtype=sync_plan["wire_dtype"],
                        rng=jax.random.fold_in(rng_l, 0x5deece66))
                    # the logged loss is the global-batch mean, same
                    # number the flat step reports
                    loss = _tc.pmean(loss, b_axes)
                    _, r2 = partition(m2)
                    # buffers (BN stats) computed from the local shard:
                    # average across shards so every device carries
                    # identical buffers.  NOTE this is the mean of
                    # per-shard statistics (data-parallel BatchNorm),
                    # not the flat step's global-batch variance —
                    # _grad_sync_plan warns when the model has such
                    # modules
                    r2 = jax.tree_util.tree_map(
                        lambda b: (_tc.pmean(b, b_axes)
                                   if jnp.issubdtype(b.dtype,
                                                     jnp.floating)
                                   else b), r2)
                    return loss, grads, r2

                fn = _shard_map(
                    local, mesh,
                    in_specs=(_PS(), _PS(), _batch_specs(x),
                              _batch_specs(y), _PS()),
                    out_specs=(_PS(), _PS(), _PS()))
                return fn(params_groups, rest, x, y, rng)

        def apply_reg(gs, ps, specs):
            """Per-layer regularizers + scaleW/scaleB:
            g_eff = scale·(g + l1·sign(p) + l2·p) — the reference's
            accGradParameters algebra (optim/Regularizer.scala,
            nn/Linear.scala:144-166) as a pure leaf transform."""
            out = []
            for g, p, (l1, l2, sc) in zip(gs, ps, specs):
                if l1:
                    g = g + l1 * jnp.sign(p)
                if l2:
                    g = g + l2 * p
                if sc != 1.0:
                    g = g * sc
                out.append(g)
            return out

        def step(params_groups, rest, opt_states, x, y, rng, epoch):
            from bigdl_tpu.core.module import cast_floating

            def loss_of(groups, rest_, x_, y_, rng_):
                m = combine(merge_groups(groups), rest_)
                x_c = x_
                if compute_dtype is not None:
                    # cast the whole compute graph (params + activations)
                    # to the compute dtype; grads flow back to fp32 master
                    # params through the casts
                    m = cast_floating(m, compute_dtype)
                    x_c = cast_floating(x_, compute_dtype)
                with forward_context(rng=rng_):
                    out = m.forward(x_c)
                if compute_dtype is not None:
                    out = cast_floating(out, jnp.float32)
                loss = criterion(out, y_)
                return loss, m

            if pipe_1f1b:
                # grads come back stacked [S, per_stage, ...] under
                # block 0's treedef (params + buffers); unstack to
                # per-block leaves and keep the param slots, which by
                # construction (_param_flags walks the same order as
                # tree flattening) is exactly partition()'s leaf order
                m = combine(merge_groups(params_groups), rest)
                with forward_context(rng=rng):
                    loss, g_stacked, _dx = m.train_step_on_mesh(
                        x, y, lambda out, tgt: criterion(out, tgt),
                        mesh, pipe_axis)
                flat_g = [g.reshape((pipe_n_blocks,) + g.shape[2:])
                          for g in jax.tree_util.tree_leaves(g_stacked)]
                per_leaf = []
                for i in range(pipe_n_blocks):
                    per_leaf.extend(
                        g[i] for g, is_param in zip(flat_g, pipe_flags)
                        if is_param)
                grads_groups = [[per_leaf[j] for j in idxs]
                                for idxs in group_idx]
                m2 = m   # 1F1B mutates no buffers in-schedule
                sync_rest = None
            elif sync_plan is None:
                (loss, m2), grads_groups = jax.value_and_grad(
                    lambda groups: loss_of(groups, rest, x, y, rng),
                    has_aux=True)(params_groups)
                sync_rest = None
            else:
                # hierarchical sync: the whole fwd+bwd runs per-device
                # on the LOCAL batch shard inside a shard_map, and the
                # gradient mean routes through the rs-in-slice /
                # compressed-dcn-hop / ag-in-slice schedule instead of
                # the flat XLA-inserted all-reduce
                loss, grads_groups, sync_rest = _hier_value_and_grad(
                    loss_of, params_groups, rest, x, y, rng)
                m2 = None
            if spec_groups is not None:
                grads_groups = [
                    apply_reg(g, p, sp) for g, p, sp in
                    zip(grads_groups, params_groups, spec_groups)]
            clipped = [clip(g) for g in grads_groups]
            grads_groups = [g for g, _t in clipped]
            gnorm = None
            if health:
                # global (pre-clip-scale) grad L2 norm, fused into the
                # step: per-group norms already exist for clipping, so
                # the global one is one combine away
                totals = [t for _g, t in clipped]
                gnorm = (totals[0] if len(totals) == 1
                         else jnp.sqrt(sum(t ** 2 for t in totals)))
            new_groups, new_states = [], []
            for g, p, s, meth in zip(grads_groups, params_groups,
                                     opt_states, methods):
                np_, ns_ = meth.update(g, p, s, epoch)
                new_groups.append(np_)
                new_states.append(ns_)
            if sync_plan is None:
                _, new_rest = partition(m2)
            else:
                new_rest = sync_rest
            if compute_dtype is not None:
                # buffers (BN stats) ride back to fp32 master copies
                new_rest = cast_floating(new_rest, jnp.float32)
            if guard_updates:
                # watchdog skip/halt policy: a nonfinite loss or grad
                # norm discards the whole update in-graph — params,
                # optimizer state, and buffers keep their pre-step
                # values, so the final checkpoint after a halt holds
                # uncontaminated weights (and skip_step keeps training
                # on the last good state)
                ok = jnp.isfinite(loss) & jnp.isfinite(gnorm)
                keep = lambda n, o: jnp.where(ok, n, o)  # noqa: E731
                new_groups = jax.tree_util.tree_map(
                    keep, new_groups, params_groups)
                new_states = jax.tree_util.tree_map(
                    keep, new_states, opt_states)
                new_rest = jax.tree_util.tree_map(keep, new_rest, rest)
            if health:
                return new_groups, new_rest, new_states, loss, gnorm
            return new_groups, new_rest, new_states, loss

        def _aot(fn, n_out=4, steps_of=lambda args: 1):
            """Compile once on first call, then reuse the executable.
            Plain jax.jit keys its cache on the CONCRETE layouts of the
            incoming arrays: call 1 sees host-staged default layouts,
            while call 2's inputs are call 1's donated outputs in XLA's
            preferred layouts — a different key, so the SECOND window
            of a run recompiles the whole program (observed as a ~27 s
            mid-loop stall on a v5e, poisoning the steady-state
            telemetry).  One AOT executable relayouts call 1's
            inputs once; donation aliasing makes every later call match
            exactly.

            The carried state (params, buffers, optimizer state — the
            three donated arguments) leaves the program in the shardings
            it arrived in.  Left to the partitioner, a tensor-parallel
            weight placed ('model', None) can come back
            ('model', 'fsdp'): the next call then hands the executable a
            layout it was not compiled for."""
            cache: Dict[Tuple, Any] = {}

            def sig(args):
                # shape/dtype signature only — NOT layouts (dodging the
                # relayout recompile is the point) and NOT scalar
                # values (epoch changes every epoch); ragged tails and
                # padded variable-length batches land on their own
                # entries exactly as jit would retrace
                out = []
                for leaf in jax.tree_util.tree_leaves(args):
                    if hasattr(leaf, "shape"):
                        out.append((tuple(leaf.shape), str(leaf.dtype)))
                    else:
                        out.append((type(leaf).__name__,))
                return tuple(out)

            def call(*args):
                key = sig(args)
                entry = cache.get(key)
                if entry is None:
                    exe = self._compile_step_program(fn, args, n_out)
                    f = compiled_flops(exe)
                    # XLA's own FLOP count of the program actually
                    # executed (fwd+bwd+update), normalized by the train
                    # steps THIS program covers (the window length it
                    # was compiled for, not the configured k — ragged
                    # windows normalize correctly) — ≙ the analytic
                    # flops/step the reference's Throughput log never had
                    per_step = (f / max(steps_of(args), 1)) if f else None
                    entry = cache[key] = (exe, per_step)
                exe, per_step = entry
                if per_step:
                    # weight by steps actually executed so mixed batch
                    # signatures (ragged tails) average correctly
                    n = max(steps_of(args), 1)
                    self._executed_flops += per_step * n
                    self._executed_steps += n
                    self.compiled_flops_per_iteration = (
                        self._executed_flops / self._executed_steps)
                return exe(*args)

            return call

        if raw and not window:
            return jax.jit(step, donate_argnums=(0, 1, 2))
        if not window:
            return _aot(step, n_out=5 if health else 4)
        # windowed: args = (params_groups, rest, opt_states, xs, ys,
        # rngs, epoch); xs' leading axis is the steps per dispatch

        def window_step(params_groups, rest, opt_states, xs, ys, rngs,
                        epoch):
            """k steps inside one dispatch: scan over the stacked window
            (leading axis = iteration), losses returned stacked."""
            def body(carry, inp):
                pg, r, os_ = carry
                x, y, rng = inp
                npg, nr, nos, loss = step(pg, r, os_, x, y, rng, epoch)
                return (npg, nr, nos), loss

            (pg, r, os_), losses = jax.lax.scan(
                body, (params_groups, rest, opt_states), (xs, ys, rngs))
            return pg, r, os_, losses

        return _aot(window_step,
                    steps_of=lambda args: int(jax.tree_util.tree_leaves(
                        args[3])[0].shape[0]))

    def _compile_step_program(self, fn, args, n_out: int):
        """Lower and compile ``fn`` for ``args`` with its three donated
        state arguments pinned to come out in the shardings they came in
        (see ``_aot``); records the executable and the seconds spent."""
        def carried(tree):
            # an uncommitted leaf (a fresh scalar counter) has no
            # layout to keep yet: the compiler places it
            return jax.tree_util.tree_map(
                lambda l: (l.sharding if isinstance(l, jax.Array)
                           and l.committed else None), tree)

        # graftlint: disable=trace-safety -- host code: runs once per
        # batch signature, when the loop compiles the step — around the
        # trace, never inside it
        t_l = time.perf_counter()
        lowered = jax.jit(
            fn, donate_argnums=(0, 1, 2),
            out_shardings=tuple(carried(a) for a in args[:3])
            + (None,) * (n_out - 3)).lower(*args)
        # graftlint: disable=trace-safety -- host code, as above
        t_c = time.perf_counter()
        exe = lowered.compile()
        # graftlint: disable=trace-safety -- host code, as above
        t_e = time.perf_counter()
        self.step_lower_seconds += t_c - t_l
        self.step_compile_seconds += t_e - t_c
        self.step_executables.append(exe)
        return exe

    @staticmethod
    def _abstract_opt_state(method, pg):
        """Shape-only opt state for :meth:`compile_step`: the avals the
        concrete ``init_state(pg)`` would produce, WITHOUT allocating
        the momentum/variance buffers (full model size per method) on
        device.  Faithful by the state contract every OptimMethod
        follows: a params-congruent subtree is ``zeros_like``/
        ``full_like`` of the params, so each leaf inherits the matching
        param's committed ``NamedSharding``; everything else (scalar
        counters, LBFGS's flat history) is a fresh eager array the real
        dispatch treats as unspecified-sharding input — so its aval
        carries no sharding, and the lowered program is byte-identical
        either way (asserted in tests/test_hierarchy.py)."""
        from jax.sharding import NamedSharding
        state = jax.eval_shape(method.init_state, pg)
        pg_def = jax.tree_util.tree_structure(pg)

        def leaf_aval(s, p=None):
            sh = getattr(p, "sharding", None)
            if isinstance(sh, NamedSharding):
                return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)
            return jax.ShapeDtypeStruct(s.shape, s.dtype)

        def subtree_avals(v):
            if jax.tree_util.tree_structure(v) == pg_def:
                return jax.tree_util.tree_map(leaf_aval, v, pg)
            return jax.tree_util.tree_map(leaf_aval, v)

        return {k: subtree_avals(v) for k, v in state.items()}

    def _setup_step_state(self, model, abstract_state: bool = False):
        """Flatten an already-sharded model into optim-method groups
        with fresh opt states + per-leaf regularizer spec groups — the
        ONE pipeline both ``_optimize_once`` and :meth:`compile_step`
        feed ``_build_step`` from, so the introspected program can
        never drift from the dispatched one.  ``abstract_state=True``
        (the compile_step path) swaps the concrete opt states for
        their avals so introspection never allocates them."""
        from bigdl_tpu.core.module import param_paths
        from bigdl_tpu.optim.regularizer import leaf_reg_specs
        params_tree, rest = partition(model)
        leaves, self._ptreedef = jax.tree_util.tree_flatten(params_tree)
        self._n_param_leaves = len(leaves)
        paths = param_paths(model)
        assert len(paths) == len(leaves)
        groups = self._group_indices(paths)
        group_names = [g for g, _ in groups]
        self._group_idx = [idxs for _, idxs in groups]
        params_groups = [[leaves[i] for i in idxs] for _, idxs in groups]
        methods = ([self.optim_method] if group_names == ["__default__"]
                   else [self.optim_methods[g] for g in group_names])
        state_of = (self._abstract_opt_state if abstract_state
                    else (lambda m, pg: m.init_state(pg)))
        opt_states = [state_of(m, pg)
                      for m, pg in zip(methods, params_groups)]
        leaf_specs = leaf_reg_specs(model)
        assert len(leaf_specs) == len(leaves)
        spec_groups = ([[leaf_specs[i] for i in idxs]
                        for idxs in self._group_idx]
                       if any(s != (0.0, 0.0, 1.0) for s in leaf_specs)
                       else None)  # None: no per-layer reg/scale anywhere
        return (params_groups, rest, group_names, methods, opt_states,
                spec_groups)

    def compile_step(self, batch):
        """AOT-compile ONE train step for a host ``MiniBatch`` without
        running the loop — the introspection hook comm tooling and
        tests use to read the compiled program ``optimize()`` would
        dispatch (``utils/xla_cost.collective_hlo_bytes`` /
        ``cross_group_hlo_bytes`` over it answer "what does this
        mesh/sync layout actually put on which wire").  Shares the
        mesh build, sharding, and ``_setup_step_state`` grouping
        pipeline with the training loop; the opt states are lowered
        from avals (:meth:`_abstract_opt_state`), so introspecting a
        model near the HBM limit never allocates a second copy of the
        optimizer state.  Read-only: the train-mode flip the lowering
        needs (the program optimize() dispatches IS the training-mode
        program) is restored per-module on exit, so inspecting an
        eval_mode'd model doesn't silently re-enable dropout/BN
        updates for subsequent forwards.

        Always the SINGLE-STEP program: under
        ``iterations_per_dispatch > 1`` optimize() dispatches the
        scan-wrapped window instead, whose per-iteration collectives
        are these same ops inside a scan body (per-STEP byte counts
        from this hook stay the per-iteration truth; multiply by the
        window for per-dispatch totals) — warned once so an HLO-level
        identity comparison isn't attempted against the window
        program."""
        if getattr(self, "iters_per_dispatch", 1) > 1:
            logger.warning(
                "compile_step introspects the single-step program; "
                "optimize() will dispatch a %d-step scan window whose "
                "HLO wraps these same per-iteration collectives in a "
                "scan body", self.iters_per_dispatch)
        mesh = self.mesh_config.build()
        modes = [(m, m.training) for _, m in self.model.named_modules()]
        try:
            model = shard_model_params(self.model.train_mode(), mesh,
                                       self.sharding_rules)
            (params_groups, rest, group_names, _methods, opt_states,
             spec_groups) = self._setup_step_state(
                 model, abstract_state=True)
            # mirror optimize()'s health wiring: a watchdog-armed run
            # dispatches the in-graph grad-norm/guard program, and the
            # introspected HLO must be THAT program, not the bare one
            step = self._build_step(mesh, group_names, spec_groups,
                                    health=self.watchdog is not None,
                                    raw=True)
            x_sharding = batch_sharding(mesh)
            with mesh:
                x = _stage(batch.get_input(), x_sharding)
                y = _stage(batch.get_target(), x_sharding)
                rng = jax.random.fold_in(jax.random.key(get_seed()), 0)
                return step.lower(params_groups, rest, opt_states, x, y,
                                  rng, 1).compile()
        finally:
            for m, flag in modes:
                m.training = flag

    # ---- evaluation ------------------------------------------------------

    def _build_eval_step(self):
        methods = self.val_methods

        def eval_step(model, x, y):
            out = model.forward(x)
            return [m.batch_stats(out, y) for m in methods]

        return jax.jit(eval_step)

    def _validate(self, model, eval_step) -> Dict[str, ValidationResult]:
        results: Optional[List[ValidationResult]] = None
        for batch in self.val_dataset.data(train=False):
            stats = eval_step(model, _stage(batch.get_input()),
                              _stage(batch.get_target()))
            batch_results = [m.to_result(n, d)
                             for m, (n, d) in zip(self.val_methods, stats)]
            results = batch_results if results is None else [
                a + b for a, b in zip(results, batch_results)]
        if results is None:
            raise ValueError(
                "validation dataset produced no batches (empty split, or "
                "fewer samples than one batch)")
        if getattr(self, "_val_sharded", False):
            from bigdl_tpu.optim.validation import (
                aggregate_across_processes,
            )
            results = aggregate_across_processes(results)
        out = {}
        for m, r in zip(self.val_methods, results):
            out[m.fmt] = r
            logger.info("%s is %s", m.fmt, r)
        return out

    def set_failure_retry(self, times: int,
                          interval_s: float = 120.0,
                          backoff_s: Optional[float] = None,
                          backoff_cap_s: Optional[float] = None,
                          jitter: Optional[float] = None) -> "Optimizer":
        """Retry training from the latest GOOD checkpoint after a
        transient failure, up to ``times`` retries; the counter resets
        when more than ``interval_s`` passed since the previous failure
        (reference bigdl.failure.retryTimes / retryTimeInterval,
        DistriOptimizer.scala:901-983).  On TPU pods this covers
        preemption and transient runtime errors.

        Between retries the driver sleeps ``backoff_s * 2**attempt``
        (capped at ``backoff_cap_s``) with ±``jitter`` relative noise —
        a whole pod retrying in lockstep would stampede the storage /
        scheduler that just failed it.  Programming errors (ValueError,
        TypeError, ...) are re-raised immediately without burning
        retries."""
        self.retry_times = int(times)
        self.retry_interval_s = float(interval_s)
        if backoff_s is not None:
            self.retry_backoff_s = float(backoff_s)
        if backoff_cap_s is not None:
            self.retry_backoff_cap_s = float(backoff_cap_s)
        if jitter is not None:
            self.retry_jitter = float(jitter)
        return self

    def _ckpt_manager(self) -> CheckpointManager:
        if self._ckpt_mgr is None \
                or self._ckpt_mgr.directory != self.checkpoint_path:
            self._ckpt_mgr = CheckpointManager(
                self.checkpoint_path, keep_n=self.checkpoint_keep_n)
        return self._ckpt_mgr

    def _latest_checkpoint(self) -> Optional[str]:
        """Newest checkpoint that is committed AND passes integrity
        validation — NOT simply the newest file: the failure this path
        serves (a crash mid-checkpoint) is exactly the one that leaves
        the newest file truncated, and resuming from it would fail
        every retry."""
        if not self.checkpoint_path:
            return None
        try:
            return self._ckpt_manager().latest_good()
        except Exception:
            logger.warning("could not determine latest good checkpoint "
                           "in %s", self.checkpoint_path, exc_info=True)
            return None

    def _backoff_delay(self, attempt: int) -> float:
        base = min(self.retry_backoff_s * (2.0 ** attempt),
                   self.retry_backoff_cap_s)
        j = self.retry_jitter
        return max(base * random.uniform(1.0 - j, 1.0 + j), 0.0)

    # ---- preemption (SIGTERM) handling -----------------------------------

    def _install_preemption_handler(self):
        """SIGTERM (the TPU-pod preemption notice) must not kill the
        process mid-collective — a host dying inside a psum wedges every
        other host in the ring.  The handler only sets a flag; the train
        loop honors it at the next step boundary by writing a final
        checkpoint and returning cleanly.  Returns a restore() callable;
        no-op off the main thread (signal.signal would raise).

        Multi-host note: the flag is process-local.  TPU maintenance
        events deliver the preemption notice to EVERY worker, and each
        host then breaks at the same step boundary (steps are lockstep
        SPMD), so the final-checkpoint collectives line up.  Signaling
        a SUBSET of hosts by hand is outside that contract — the
        signaled hosts would enter the checkpoint collective while the
        rest keep training."""
        self._preempt_requested = False
        self.preempted = False
        if threading.current_thread() is not threading.main_thread():
            return lambda: None
        import signal

        def handler(signum, frame):
            logger.warning(
                "received signal %d (preemption notice): requesting a "
                "final checkpoint at the next step boundary", signum)
            self._preempt_requested = True

        try:
            prev = signal.signal(signal.SIGTERM, handler)
        except (ValueError, OSError):  # pragma: no cover - exotic host
            return lambda: None

        def restore():
            try:
                signal.signal(signal.SIGTERM, prev)
            except (ValueError, OSError):  # pragma: no cover
                pass
        return restore

    # ---- introspection sidecar + watchdog plumbing -----------------------

    def statusz(self) -> Dict[str, Any]:
        """The trainer's contribution to ``GET /statusz`` (see
        :mod:`bigdl_tpu.telemetry.debugz`): live step/epoch/loss, the
        last good checkpoint generation, watchdog state, run flags.
        Non-finite floats are stringified (``events.json_safe``) — the
        page must stay valid strict JSON even while the loss is NaN
        (that being exactly when an operator scrapes it)."""
        _j = _te.json_safe
        st = self.state
        out: Dict[str, Any] = {
            "role": "trainer",
            "epoch": st.get("epoch"),
            "iteration": st.get("neval"),
            "records": st.get("records"),
            "loss": _j(st.get("loss")),
            "score": _j(st.get("score")),
            "run_uptime_s": (None if self._run_started is None
                             else time.perf_counter() - self._run_started),
            "preempted": self.preempted,
            "watchdog_halted": self.watchdog_halted,
            "checkpoint": {
                "path": self.checkpoint_path,
                "last_generation": self._last_ckpt_generation,
                "last_payload": self._last_ckpt_path,
            },
            "pipeline": {
                "epoch_offset": self._epoch_offset,
                "device_prefetch": self.device_prefetch_ahead,
            },
        }
        # step-time attribution so far this run (telemetry.perf): where
        # wall time is going, live, without waiting for the artifact
        try:
            out["perf"] = _tp.optimizer_perf_status(self)
        except Exception:  # pragma: no cover - introspection best effort
            out["perf"] = None
        if self.watchdog is not None:
            out["watchdog"] = self.watchdog.state()
        if self._fleet_monitor is not None:
            try:
                out["fleet"] = self._fleet_monitor.status()
            except Exception:  # pragma: no cover - best effort
                out["fleet"] = None
        # fleet-controller section (autoscaler / deploy watcher /
        # training supervisor) when any is live in this process — the
        # "the controller did something — why?" page
        try:
            from bigdl_tpu.fleet.controller import controller_statusz
            ctl = controller_statusz()
            if ctl is not None:
                out["controller"] = ctl
        except Exception:  # pragma: no cover - best effort
            pass
        return out

    def _start_debug_server(self) -> None:
        if self.debug_port is None or self.debug_server is not None:
            return
        try:
            from bigdl_tpu.telemetry.debugz import Debugz, DebugzServer
            self.debug_server = DebugzServer(
                Debugz(statusz_fn=self.statusz),
                host=self.debug_host or "127.0.0.1",
                port=self.debug_port).start()
        except Exception:
            logger.exception("debug server failed to start (training "
                             "continues without introspection endpoints)")
            self.debug_server = None

    def _stop_debug_server(self) -> None:
        srv = self.debug_server
        self.debug_server = None
        if srv is not None:
            try:
                srv.stop()
            except Exception:  # pragma: no cover - best effort
                logger.exception("debug server failed to stop")

    def _watchdog_step_check(self, wd: HealthWatchdog, loss, gnorm,
                             neval: int) -> None:
        """Per-iteration host check, watchdog mode only: ONE batched
        device transfer of (loss, grad-norm) — the extra readback the
        watchdog trades for one-step detection latency.  With the
        watchdog off this method is never called and the loop performs
        zero additional per-step host transfers.  ``wd`` is the
        attempt-start snapshot, NOT ``self.watchdog`` — the documented
        mid-run disarm (``self.watchdog = None``) must not crash an
        iteration already in flight; it takes effect on the next
        ``optimize()``."""
        lf, gn = (float(v) for v in jax.device_get((loss, gnorm)))
        if telemetry.enabled() and math.isfinite(gn):
            _tm.grad_norm().observe(gn)
        wd.observe_step(neval, lf, gn)
        if wd.halt_requested:
            self._halt_requested = True

    def _postmortem_artifact_path(self, filename: str) -> str:
        """``<checkpoint dir>/<filename>`` — THE location of postmortem
        artifacts (flight recorder, OOM forensics), resolved once so
        the two can never land in different places.  Local dirs are
        created; remote (fsspec) roots pass through for ``open_file``.
        Caller guarantees ``checkpoint_path`` is set."""
        from bigdl_tpu.utils.file import is_remote_path, strip_file_scheme
        root = strip_file_scheme(self.checkpoint_path)
        if is_remote_path(root):
            return root.rstrip("/") + "/" + filename
        os.makedirs(root, exist_ok=True)
        return os.path.join(root, filename)

    def _dump_flight_recorder(self, reason: str,
                              error: Optional[BaseException] = None) \
            -> Optional[str]:
        """Write the flight-recorder ring to ``flight_recorder.json``
        next to the checkpoint — the black box a halted or dead run
        leaves behind.  Best-effort: never raises into the crash path
        it documents; no-op without a checkpoint path (nowhere durable
        to leave it).  Primary process only: in a multi-host run every
        process halts/crashes together, and concurrent writers on a
        shared checkpoint store would tear the one artifact the
        postmortem depends on."""
        if not self.checkpoint_path:
            logger.debug("no checkpoint path configured; skipping "
                         "flight-recorder dump")
            return None
        try:
            from bigdl_tpu.utils.file import _is_primary_process, open_file
            if not _is_primary_process():
                return None
            _te.record_event(
                "flight_recorder_dump", reason=reason,
                **({"error": f"{type(error).__name__}: {error}"}
                   if error is not None else {}))
            path = self._postmortem_artifact_path("flight_recorder.json")
            # dumps_events is THE wire format — same serializer as
            # events.dump_events, just routed through open_file so
            # fsspec checkpoint stores get the dump too
            with open_file(path, "wb") as f:
                f.write(_te.dumps_events().encode("utf-8"))
            logger.warning("flight recorder dumped to %s (%s)", path,
                           reason)
            return path
        except Exception:
            logger.exception("flight-recorder dump failed")
            return None

    def _dump_oom_forensics(self, error: BaseException) \
            -> Optional[str]:
        """RESOURCE_EXHAUSTED postmortem: record the ``oom`` flight-
        recorder event (every process — each ring is its own) and, on
        the primary process with a checkpoint path configured, write
        ``oom_forensics.json`` — device memory_stats, HBM peak
        watermarks, a live-array census, the last attribution window —
        beside the flight recorder.  Best effort; the expensive report
        (live-array enumeration at peak memory pressure) is built ONLY
        where it will actually be written."""
        try:
            _te.record_event(
                "oom", error=f"{type(error).__name__}: "
                f"{str(error)[:500]}",
                iteration=self.state.get("neval"),
                epoch=self.state.get("epoch"))
            from bigdl_tpu.utils.file import _is_primary_process, open_file
            if not _is_primary_process():
                return None
            if not self.checkpoint_path:
                logger.warning(
                    "OOM detected but no checkpoint path is configured; "
                    "forensics report not written (nowhere durable)")
                return None
            from bigdl_tpu.telemetry.runtime import oom_forensics_report
            last = (self.window_records[-1]
                    if getattr(self, "window_records", None) else None)
            report = oom_forensics_report(
                error=f"{type(error).__name__}: {error}",
                last_window=last)
            path = self._postmortem_artifact_path("oom_forensics.json")
            import json as _json
            with open_file(path, "wb") as f:
                f.write(_json.dumps(report, default=str,
                                    indent=2).encode("utf-8"))
            logger.warning("OOM forensics dumped to %s", path)
            return path
        except Exception:  # pragma: no cover - must not mask the OOM
            logger.exception("OOM forensics dump failed")
            return None

    # ---- input-pipeline state (bigdl_tpu.data) ---------------------------

    def _pipeline_snapshot(self) -> Dict[str, Any]:
        """The PipelineState persisted with every checkpoint: the
        shuffle seed, the epoch being consumed, the batches-consumed
        offset within it, and the mixing sampler's configuration when
        the dataset exposes one — everything a resume needs to continue
        at the exact next batch."""
        sampler = None
        sampler_fn = getattr(self.dataset, "sampler_state", None)
        if callable(sampler_fn):
            try:
                sampler = sampler_fn()
            except Exception:  # pragma: no cover - exotic wrapper
                logger.exception("dataset.sampler_state() failed; "
                                 "checkpointing without sampler state")
        # the topology-portable position: state["records"] counts
        # GLOBAL samples consumed this epoch (reset at each epoch
        # start, restored across resumes), which is exactly the prefix
        # of the global epoch permutation the fleet has consumed —
        # independent of how many processes consumed it.  The local
        # batch `offset` stays for same-topology restores of ragged
        # setups; a changed process count resumes from global_offset.
        snap = PipelineState(
            seed=dataset_seed(self.dataset),
            epoch=int(self.state["epoch"]),
            offset=int(self._epoch_offset),
            sampler=sampler,
            # at an epoch boundary `records` still holds the finished
            # epoch's total while the snapshot already names the NEXT
            # epoch — the global offset there is 0, like the local one
            global_offset=(int(self.state.get("records", 0))
                           if self._epoch_offset > 0 else 0),
            process_count=int(jax.process_count()),
            global_batch=self._last_global_batch).snapshot()
        # cross-check token: the payload this snapshot belongs to (the
        # checkpoint generation IS neval).  In overwrite mode a crash
        # between the payload rename and the sidecar write can leave
        # the PREVIOUS generation's sidecar beside a newer payload that
        # the load-probe fallback accepts — restore detects the
        # mismatch and falls back to epoch-start replay instead of
        # silently skipping the wrong batches.
        snap["generation"] = int(self.state["neval"])
        return snap

    def _topology_delta(self, mesh) -> Tuple[bool, str, str]:
        """Did the topology change between the checkpoint being
        resumed and the live fleet?  Returns ``(changed, saved_desc,
        current_desc)``; never raises (a manifest-less checkpoint
        compares as unchanged — the pre-elastic contract)."""
        from bigdl_tpu.parallel.mesh import mesh_axes
        from bigdl_tpu.utils.file import describe_topology
        saved = self._resume_topology
        cur = {"process_count": int(jax.process_count()),
               "device_count": int(jax.device_count()),
               "mesh": mesh_axes(mesh)}
        if not saved:
            return False, describe_topology(saved), \
                describe_topology(cur)
        try:
            changed = (
                int(saved.get("process_count",
                              cur["process_count"]))
                != cur["process_count"]
                or int(saved.get("device_count", cur["device_count"]))
                != cur["device_count"]
                or (saved.get("mesh") is not None
                    and {str(a): int(s)
                         for a, s in saved["mesh"].items()}
                    != cur["mesh"]))
        except (TypeError, ValueError):  # malformed manifest record
            changed = False
        return changed, describe_topology(saved), describe_topology(cur)

    def _note_reshard(self, outcome: str) -> None:
        """One ``checkpoint_reshard_restores_total{outcome}`` tick
        (no-op with telemetry off): resharded / fallback / failed."""
        if telemetry.enabled():
            _tm.checkpoint_reshard_restores_total().labels(outcome).inc()

    def _pipeline_restore_plan(self, ps: Dict[str, Any],
                               epoch: int) -> Tuple[str, int]:
        """How to reposition the epoch iterator for sample-accurate
        resume: ``("batches", n)`` (same-topology legacy skip of n
        post-transform batches), ``("samples", n)`` (topology-portable
        skip of n SAMPLES per process, converted from the sidecar's
        global offset onto the CURRENT process count), or ``("none",
        0)`` (epoch-start replay, the always-safe fallback) whenever
        the snapshot cannot be applied faithfully: version/seed
        mismatch, a different epoch, a changed process count without
        the global-offset fields, a global offset the new topology
        cannot divide, or a dataset whose order isn't replayable
        across restarts.  A mismatched mixing-sampler configuration
        raises instead — that resume would silently train on a
        different sample sequence while claiming accuracy."""
        try:
            if int(ps.get("version", -1)) != PIPELINE_STATE_VERSION:
                logger.warning(
                    "pipeline state version %s unsupported (want %d); "
                    "replaying the epoch from its start",
                    ps.get("version"), PIPELINE_STATE_VERSION)
                return ("none", 0)
            gen = ps.get("generation")
            if gen is not None and int(gen) != int(self.state["neval"]):
                logger.warning(
                    "pipeline state generation %s != restored driver "
                    "iteration %s (stale sidecar from an interrupted "
                    "overwrite commit?); replaying the epoch from its "
                    "start", gen, self.state["neval"])
                return ("none", 0)
            if int(ps.get("epoch", -1)) != int(epoch):
                return ("none", 0)  # epoch-boundary: nothing to skip
            offset = int(ps.get("offset", 0))
            go = ps.get("global_offset")
            go = None if go is None else int(go)
            saved_pc = ps.get("process_count")
            saved_pc = None if saved_pc is None else int(saved_pc)
        except (TypeError, ValueError):
            logger.warning("malformed pipeline state %r; replaying the "
                           "epoch from its start", ps)
            return ("none", 0)
        pc_now = int(jax.process_count())
        if saved_pc is None:
            # legacy sidecar: the checkpoint manifest's topology record
            # is the only witness of the writing process count
            topo_pc = (self._resume_topology or {}).get("process_count")
            saved_pc = None if topo_pc is None else int(topo_pc)
        if go is None:
            # sidecar predates the global-offset fields: its batch
            # offset is a PER-HOST count, only meaningful at the
            # writing topology
            if saved_pc is not None and saved_pc != pc_now:
                logger.warning(
                    "pipeline sidecar was written at process_count=%d "
                    "and carries no global offset; resuming at "
                    "process_count=%d would skip the WRONG samples — "
                    "replaying the epoch from its start (re-checkpoint "
                    "once to upgrade the sidecar)", saved_pc, pc_now)
                self._note_reshard("fallback")
                return ("none", 0)
            if offset <= 0:
                return ("none", 0)
            plan: Tuple[str, int] = ("batches", offset)
        else:
            if go <= 0:
                return ("none", 0)
            if go % pc_now:
                logger.warning(
                    "pipeline global offset %d (written at "
                    "process_count=%s) does not divide across the "
                    "current %d process(es); replaying the epoch from "
                    "its start", go, saved_pc, pc_now)
                self._note_reshard("fallback")
                return ("none", 0)
            plan = ("samples", go // pc_now)
        seed_now = dataset_seed(self.dataset)
        if int(ps.get("seed", seed_now)) != seed_now:
            logger.warning(
                "pipeline state seed %s != current dataset seed %d: the "
                "epoch order differs, so skipping %d %s would drop "
                "the WRONG samples; replaying the epoch from its start",
                ps.get("seed"), seed_now, plan[1], plan[0])
            return ("none", 0)
        if not supports_epoch(self.dataset):
            logger.warning(
                "dataset.data() does not accept the epoch keyword; its "
                "order is not replayable across a restart — replaying "
                "the epoch from its start (see docs/data_pipeline.md)")
            return ("none", 0)
        restore_fn = getattr(self.dataset, "restore_sampler", None)
        if callable(restore_fn):
            restore_fn(ps.get("sampler"))  # raises on config mismatch
        return plan

    # ---- main loop (≙ DistriOptimizer.optimize, :823) --------------------

    def optimize(self) -> Module:
        """Run training, retrying from the latest good checkpoint on
        transient failure with exponential backoff (≙ the reference's
        retry loop around optimize, DistriOptimizer.scala:901-983).
        Programming errors re-raise immediately; SIGTERM triggers a
        final checkpoint and a clean return (``self.preempted`` set),
        and a watchdog ``checkpoint_and_halt`` verdict does the same
        with ``self.watchdog_halted`` set plus a flight-recorder dump
        next to the checkpoint.  An unhandled crash (non-retryable, or
        retries exhausted) also dumps the flight recorder before
        re-raising — the dead run leaves a black box."""
        retries_left = self.retry_times
        last_failure = None
        attempt = 0
        self.watchdog_halted = False
        self._run_started = time.perf_counter()
        restore_signal = self._install_preemption_handler()
        self._start_debug_server()
        try:
            while True:
                try:
                    return self._optimize_once()
                except KeyboardInterrupt:
                    raise
                except Exception as e:
                    self._stop_device_prefetch()
                    self._stop_flush_worker()
                    self._flush_summaries()  # keep the failed tail
                    if isinstance(e, chaos.ReshardInjected):
                        # the fleet regranted capacity at a different
                        # width: the retry resumes from latest_good()
                        # on the RESHAPED mesh — the in-process
                        # simulation of a lost slice rejoining at
                        # whatever the scheduler grants
                        old_axes = dict(self.mesh_config.axes)
                        to = e.reshard_to
                        new_axes = (dict(to) if isinstance(to, dict)
                                    else {"data": int(to)})
                        self.mesh_config = MeshConfig(**new_axes)
                        _te.record_event(
                            "reshard", step=self.state.get("neval"),
                            epoch=self.state.get("epoch"),
                            old_axes=old_axes, new_axes=new_axes)
                        logger.warning(
                            "chaos reshard: fleet width changed — the "
                            "retry will rebuild the mesh as %s (was "
                            "%s) and resume from the latest good "
                            "checkpoint", new_axes, old_axes)
                    if _is_oom(e):
                        # the most common hard-to-debug multi-chip
                        # failure: capture what held the memory BEFORE
                        # the retry (or the crash) tears it down
                        self._dump_oom_forensics(e)
                    if not _is_transient(e):
                        logger.error(
                            "training failed with non-retryable %s: %s "
                            "(programming error — retrying would hit the "
                            "same wall)", type(e).__name__, e)
                        self._dump_flight_recorder("crash", error=e)
                        raise
                    now = time.perf_counter()
                    if last_failure is not None and \
                            now - last_failure > self.retry_interval_s:
                        retries_left = self.retry_times
                        attempt = 0
                    last_failure = now
                    ckpt = self._latest_checkpoint()
                    if retries_left <= 0 or ckpt is None:
                        self._dump_flight_recorder("crash", error=e)
                        raise
                    retries_left -= 1
                    if telemetry.enabled():
                        _tm.optimizer_retries_total().inc()
                    delay = self._backoff_delay(attempt)
                    attempt += 1
                    _te.record_event(
                        "retry", error=f"{type(e).__name__}: {e}",
                        resume_from=ckpt, retries_left=retries_left,
                        backoff_s=round(delay, 3))
                    logger.warning(
                        "training failed (%s: %s); resuming from %s in "
                        "%.1fs (%d retr%s left)", type(e).__name__, e,
                        ckpt, delay, retries_left,
                        "y" if retries_left == 1 else "ies")
                    if delay > 0:
                        time.sleep(delay)
                    self._resume_from = ckpt
        finally:
            restore_signal()
            self._stop_device_prefetch()
            self._stop_debug_server()

    def _flush_summaries(self) -> None:
        for s in (self.train_summary, self.val_summary):
            if s is not None and hasattr(s, "flush"):
                s.flush()

    def _stop_device_prefetch(self) -> None:
        """Close a crashed attempt's DevicePrefetch (no-op if none):
        its producer thread would otherwise spin forever holding
        ``n_ahead`` device-resident batches while the retry builds a
        fresh prefetcher — one leak per retry, compounding exactly in
        the preemption-heavy runs this subsystem serves."""
        dp = getattr(self, "_active_dp", None)
        self._active_dp = None
        if dp is not None:
            try:
                dp.close()
            except Exception:  # pragma: no cover - best effort
                logger.exception("device prefetch failed to close")

    def _stop_flush_worker(self) -> None:
        """Stop the async loss-drain worker (no-op if none is running);
        called on the failure path so a crashed attempt's worker doesn't
        outlive it and race the retry's fresh worker."""
        q = getattr(self, "_flushq", None)
        t = getattr(self, "_flush_thread", None)
        self._flushq = None
        self._flush_thread = None
        if q is not None:
            # drain stale jobs first: the queue is bounded, so a
            # blocking put(None) could wedge behind a worker stuck in a
            # device readback — exactly the hang this path must bound
            import queue as _queue
            while True:
                try:
                    q.get_nowait()
                    q.task_done()
                except _queue.Empty:
                    break
            try:
                q.put_nowait(None)
            except _queue.Full:
                pass  # worker is wedged mid-readback; it's a daemon
        if t is not None:
            t.join(timeout=30.0)

    def _optimize_once(self) -> Module:
        mesh = self.mesh_config.build()
        model = self.model.train_mode()
        wd = self.watchdog
        # attempt-start snapshot, same reasoning as ``wd``: a mid-run
        # disarm must not crash a window already queued for readback
        fm = self._fleet_monitor
        self._halt_requested = False
        if wd is not None:
            wd.start_run()  # fresh EWMA baselines for this attempt
        if jax.process_count() > 1 and not getattr(
                self.dataset, "per_process_sharded", lambda: False)():
            raise ValueError(
                "multi-process training needs a per-process-sharded "
                "dataset (DataSet.sharded); a replicated dataset would "
                "silently feed every sample process_count times per "
                "epoch")
        # Per-process-sharded validation splits are supported: _validate
        # accumulates (n, d) stats process-locally, then psums the
        # counts across processes so every process computes IDENTICAL
        # global scores — score-based triggers (best-score
        # checkpointing, end_when) stay in lockstep and the owning-host
        # sharded-checkpoint collectives never desynchronize.
        self._val_sharded = (
            jax.process_count() > 1 and self.val_dataset is not None
            and getattr(self.val_dataset, "per_process_sharded",
                        lambda: False)())

        from bigdl_tpu.utils.file import (
            is_sharded_checkpoint_path, load_checkpoint_topology,
        )
        resume_sharded = bool(self._resume_from) \
            and is_sharded_checkpoint_path(self._resume_from)
        # the writing topology, from the manifest beside the payload
        # (None for manifest-less / pre-elastic checkpoints): drives
        # the resharded-restore diagnostics and the legacy-sidecar
        # fallback in _pipeline_restore_plan
        self._resume_topology = (load_checkpoint_topology(
            self._resume_from) if self._resume_from else None)
        saved_opt = None
        if self._resume_from and not resume_sharded:
            model_state, saved_opt, driver = load_checkpoint(
                self._resume_from)
            model.load_parameters(model_state["params"])
            if "buffers" in model_state:
                model.load_buffers(model_state["buffers"])
            self.state.update(driver)
            logger.info("resumed from %s at epoch %s iteration %s",
                        self._resume_from, self.state["epoch"],
                        self.state["neval"])

        if resume_sharded:
            # Resharded/sharded resume goes through the ABSTRACT tree
            # end to end: the model is lowered to shape/dtype/sharding
            # structs (no device_put, no leaf read — on the in-process
            # retry path the model's leaves are the crashed attempt's
            # DONATED buffers, which must not be touched, and restore
            # overwrites them anyway), the opt states come from
            # _abstract_opt_state avals (never allocating the
            # momentum/variance buffers restore is about to replace),
            # and orbax reads each shard straight into the CURRENT
            # mesh's shardings — which need not be the writing mesh.
            from bigdl_tpu.parallel.sharding import model_shardings
            from bigdl_tpu.utils.file import load_checkpoint_sharded
            from jax.sharding import NamedSharding, PartitionSpec

            shardings = model_shardings(model, mesh,
                                        self.sharding_rules)
            m_leaves, m_treedef = jax.tree_util.tree_flatten(model)
            s_leaves = jax.tree_util.tree_leaves(
                shardings,
                is_leaf=lambda x: isinstance(x, NamedSharding))
            abs_model = jax.tree_util.tree_unflatten(m_treedef, [
                jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s)
                for l, s in zip(m_leaves, s_leaves)])
            (params_groups, rest, group_names, methods, opt_states,
             spec_groups) = self._setup_step_state(
                 abs_model, abstract_state=True)

            def _abstract(x):
                sh = getattr(x, "sharding", None)
                if not isinstance(sh, NamedSharding):
                    # uncommitted leaves (e.g. fresh scalar step
                    # counters) must come back replicated over THIS
                    # mesh, or the restored single-device arrays clash
                    # with mesh-sharded params inside one jit
                    sh = NamedSharding(mesh, PartitionSpec())
                return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh)

            abstract = jax.tree_util.tree_map(_abstract, {
                "model": {"params": abs_model.parameters(),
                          "buffers": abs_model.buffers()},
                "optim": opt_states,
                # driver scalars live inside the same orbax tree (one
                # atomic commit); current state supplies the dtypes,
                # the fixed key set keeps save/restore structures equal
                "driver": {k: np.asarray(self.state[k])
                           for k in _DRIVER_KEYS if k in self.state},
            })
            ms, opt_restored, driver = load_checkpoint_sharded(
                self._resume_from, abstract_state=abstract)
            model.load_parameters(ms["params"])
            if "buffers" in ms:
                model.load_buffers(ms["buffers"])
            params_tree, rest = partition(model)
            leaves = jax.tree_util.tree_leaves(params_tree)
            params_groups = [[leaves[i] for i in idxs]
                             for idxs in self._group_idx]
            opt_states = opt_restored
            self.state.update(driver)
            logger.info("resumed sharded checkpoint %s at epoch %s "
                        "iteration %s", self._resume_from,
                        self.state["epoch"], self.state["neval"])
        else:
            model = shard_model_params(model, mesh, self.sharding_rules)
            (params_groups, rest, group_names, methods, opt_states,
             spec_groups) = self._setup_step_state(model)
        if self._resume_from and not resume_sharded:
            saved = jax.tree_util.tree_map(jnp.asarray, saved_opt)
            opt_states = saved

        if self._resume_from:
            changed, saved_d, cur_d = self._topology_delta(mesh)
            if changed:
                logger.warning(
                    "resharded resume: checkpoint written by %s, "
                    "restored onto %s — weights/optimizer state "
                    "resharded onto the current mesh; pipeline "
                    "position converts via the sidecar's global "
                    "offset (or falls back to epoch-start replay)",
                    saved_d, cur_d)
                self._note_reshard("resharded")

        # PipelineState sidecar (written by CheckpointManager next to
        # the payload, CRC'd in the same manifest): the iterator
        # position a mid-epoch resume continues from.  Absent for
        # pre-pipeline checkpoints -> epoch-start replay as before.
        self._pipeline_restore = None
        if self._resume_from:
            from bigdl_tpu.utils.file import load_pipeline_state
            self._pipeline_restore = load_pipeline_state(
                self._resume_from)

        step = self._build_step(mesh, group_names, spec_groups,
                                health=wd is not None)
        eval_step = self._build_eval_step() if self.val_methods else None
        x_sharding = batch_sharding(mesh)
        # checkpoints record the mesh they were written from (the
        # manifest's topology record; .npz leaves are gathered to
        # plain numpy, so the mesh cannot be recovered from them)
        self._active_mesh = mesh

        seed_key = jax.random.key(get_seed())
        total_records = self.dataset.size()
        wall_start = time.perf_counter()

        from bigdl_tpu.parallel.mesh import BATCH_AXES
        n_data = 1
        for a in BATCH_AXES:
            if a in mesh.axis_names:
                n_data *= mesh.shape[a]

        # Loss readback cadence: the device step is dispatched without
        # blocking the host; up to `interval` iterations' losses are
        # fetched together (the reference paid one Spark-job barrier per
        # iteration — DistriOptimizer.scala:425; SPMD need not pay an
        # analogous per-step host sync).  Loss-reading triggers force
        # per-iteration freshness.
        needs_loss = any(
            t is not None and getattr(t, "needs_loss", False)
            for t in (self.end_when, self.val_trigger,
                      self.checkpoint_trigger))
        # the watchdog judges every iteration's loss, so it needs the
        # same per-iteration (and synchronous) readback a loss-reading
        # trigger does — detection within one step is the contract
        needs_loss = needs_loss or wd is not None
        interval = self.log_interval
        if interval is None:
            interval = 1 if needs_loss else 8
        elif needs_loss and interval > 1:
            logger.warning(
                "log_interval=%d ignored: a loss-reading trigger "
                "(minLoss) or the health watchdog requires "
                "per-iteration loss readback", interval)
            interval = 1
        # pending: (neval, epoch, n_records, records_cum, loss_device)
        pending: List[Tuple] = []
        window = {"start": time.perf_counter(), "data_t": 0.0,
                  "fetch_t": 0.0,
                  "disp_t": 0.0}
        drain_state = {"last_ready": 0.0}
        # (n_iterations, completion_to_completion_s, data_stage_s) per
        # flushed window — lets harnesses compute steady-state step time
        # with the compile-bearing first window excluded
        self.window_timings: List[Tuple[int, float, float]] = []
        # richer per-window phase records for telemetry.perf step-time
        # attribution (data-wait / host-staging / device-compute /
        # readback + the wall they must sum to); same window boundaries
        # as window_timings, but BOUNDED — a ~14-key dict per window
        # over a multi-million-iteration run would otherwise grow
        # without limit, and /statusz aggregates the whole thing per
        # poll (attribution over the newest windows is what an operator
        # wants anyway)
        from collections import deque
        self.window_records: Any = deque(maxlen=int(os.environ.get(
            "BIGDL_TPU_WINDOW_RECORDS_CAP", "4096")))
        prof_start, prof_num = self.profile_steps
        prof_active = False
        prof_done = False

        def consume_window(entries, wstart, data_t, fetch_t, disp_t,
                           params_groups, opt_states, rest):
            """Readback + log one flushed window.  Minimal device->host
            transfers: per-scalar float() readbacks pay a full round
            trip each, which on a high-latency host<->device link
            dwarfs the payload.  Single-step iterations contribute
            scalar losses (batched into ONE stacked readback); windowed
            dispatches contribute (stacked_losses, idx) pairs — one
            readback per window array, never per iteration.

            Also times the window's attribution phases for
            telemetry.perf: ``device_compute`` = the main loop's time
            inside the dispatch calls (``disp_t`` — an enqueue on an
            async backend, the execution itself on a synchronous one)
            plus the pin below (host blocked on device completion);
            the loss transfer+convert after the pin is ``readback``;
            ``data_t`` splits into the pipeline fetch (``fetch_t``) vs
            H2D staging measured in the main loop."""
            t_enter_pc = time.perf_counter()
            # Pin the completion timestamp FIRST with one blocking
            # transfer of the window's last loss buffer.  A pure
            # transfer blocks exactly until that step's own output
            # exists; anything built with device ops (a jnp.stack of
            # the window) enqueues behind every already-dispatched
            # later step in the stream, so its completion reflects the
            # whole queue and the per-window timings below collapse to
            # host-processing gaps (observed 10x-optimistic step times
            # on the transformer perf CLI before this ordering).
            win_cache: Dict[int, np.ndarray] = {}
            last = entries[-1][-1]
            # two stamps for one completion: the device finishing the
            # window's last loss (block_until_ready) and that loss
            # arriving on the host; a harness compares them to learn
            # whether a timing may end in either on this machine
            jax.block_until_ready(last[0] if isinstance(last, tuple)
                                  else last)
            t_device_ready = time.perf_counter()
            if isinstance(last, tuple):
                win_cache[id(last[0])] = np.asarray(last[0]).astype(float)
            else:
                np.asarray(last)
            # Completion, not dispatch.  Under the async drain several
            # windows can be in flight at once with dispatch-time
            # starts; completion-to-completion (prev window's ready
            # time) is the honest denominator, or the r02
            # async-dispatch lie returns through the back door.
            # ONE clock for completion stamps: perf_counter, the trace
            # clock — window durations, the span endpoints, and the
            # record's t_ready all derive from the same monotonic read
            # (wall time is for timestamps; tracing.wall_time_of
            # converts when an epoch rendering is wanted)
            t_ready_pc = time.perf_counter()
            t_ready = t_ready_pc
            # Value readbacks batch via device_get (one pytree transfer
            # with the copies issued concurrently — per-scalar
            # np.asarray round trips on a high-latency link would
            # throttle the drain and, through queue backpressure, the
            # training loop itself).  NOT a jnp.stack: that is a device
            # op that queues behind every in-flight step, which both
            # lags the drain and once poisoned the timing.
            scalars = [l for *_, l in entries
                       if not isinstance(l, tuple)]
            stacked_host = (np.asarray(jax.device_get(scalars),
                                       dtype=float)
                            if scalars else None)
            losses = []
            si = 0
            for *_, l in entries:
                if isinstance(l, tuple):
                    arr, idx = l
                    host = win_cache.get(id(arr))
                    if host is None:
                        host = np.asarray(arr).astype(float)
                        win_cache[id(arr)] = host
                    losses.append(float(host[idx]))
                else:
                    losses.append(float(stacked_host[si]))
                    si += 1
            readback_s = time.perf_counter() - t_ready_pc
            block_s = disp_t + (t_ready_pc - t_enter_pc)
            stage_t = max(data_t - fetch_t, 0.0)
            window_dt = t_ready - max(wstart, drain_state["last_ready"])
            drain_state["last_ready"] = t_ready
            per_iter = window_dt / len(entries)
            self.metrics.add("device step time",
                             max(window_dt - data_t, 0.0)
                             / len(entries), count=len(entries))
            self.window_timings.append(
                (len(entries), window_dt, data_t))
            self.window_records.append({
                "iterations": len(entries), "wall_s": window_dt,
                "data_wait_s": fetch_t, "host_staging_s": stage_t,
                "device_compute_s": block_s, "readback_s": readback_s,
                # device_compute components, for debugging attribution:
                # dispatch-call time vs the completion-pin wait
                "dispatch_s": disp_t,
                "pin_wait_s": t_ready_pc - t_enter_pc,
                "t_ready": t_ready, "t_device_ready": t_device_ready,
                "sync": not flush_async,
                # per-iteration losses of this window, already on the
                # host: what a harness checks for finite / falling
                "losses": losses,
            })
            if wd is not None:
                # completion-timestamp stream → step-time-outlier and
                # data-starvation judgment (sync in watchdog mode, so a
                # halt verdict is seen before the next dispatch; the
                # attempt-start snapshot, so a mid-run disarm can't
                # crash the drain)
                wd.observe_window(window_dt, data_t, len(entries),
                                  step=entries[-1][0])
                if wd.halt_requested:
                    self._halt_requested = True
            if fm is not None:
                # fleet sample on the same window boundary (the window
                # count is deterministic under SPMD lockstep, so the
                # allgathers line up across processes); the straggler
                # verdict rides the watchdog like every other anomaly
                try:
                    fm.contribute(window_dt, data_t, len(entries),
                                  step=entries[-1][0], watchdog=wd)
                except Exception:
                    # a fleet hiccup must not kill the training loop
                    logger.exception("fleet monitor sample failed")
                if wd is not None and wd.halt_requested:
                    self._halt_requested = True
            if telemetry.enabled():
                # the honest per-iteration device time (same number the
                # "device step time" Metrics line reports), observed
                # once per iteration the window covered; the span marks
                # the completion-to-completion interval in the trace
                amortized = (max(window_dt - data_t, 0.0)
                             / len(entries))
                h = _tm.optimizer_step_seconds()
                for _ in entries:
                    h.observe(amortized)
                # pipeline throughput: global samples this window moved
                # end-to-end per wall second (the number the Throughput
                # log line reports, as a scrapeable gauge)
                _tm.pipeline_samples_per_second().set(
                    sum(e[2] for e in entries) / max(window_dt, 1e-9))
                # per-phase attribution: one observation per window per
                # phase, amortized to per-iteration seconds; the
                # residual fraction gauge tracks what the phases do NOT
                # cover (telemetry.perf turns these same records into
                # the full attribution table)
                ph = _tm.step_phase_seconds()
                for pname, tot in (("data_wait", fetch_t),
                                   ("host_staging", stage_t),
                                   ("device_compute", block_s),
                                   ("readback", readback_s)):
                    ph.labels(pname).observe(tot / len(entries))
                measured = fetch_t + stage_t + block_s + readback_s
                _tm.step_unattributed_fraction().set(
                    max(window_dt - measured, 0.0)
                    / max(window_dt, 1e-9))
                # perf_counter endpoints: tracing's clock (the whole
                # loop stamps on it now — a time.time() stamp here once
                # stranded these spans ~an epoch off the trace timeline,
                # the bug the clock-discipline lint pins)
                _tt.record_span("optimizer/step", t_ready_pc - window_dt,
                                t_ready_pc, iterations=len(entries),
                                data_wait_s=round(data_t, 6),
                                fetch_s=round(fetch_t, 6),
                                stage_s=round(stage_t, 6),
                                device_s=round(block_s, 6),
                                readback_s=round(readback_s, 6))
            n_pend = len(entries)
            for idx, ((neval_i, epoch_i, n_i, cum_i, _), lf) in enumerate(
                    zip(entries, losses)):
                logger.info(
                    "Epoch %d %d/%d][Iteration %d][Wall Clock %.3fs] "
                    "Trained %d records in %.4f seconds. Throughput is "
                    "%.1f records/second. Loss is %.4f.",
                    epoch_i, cum_i, total_records, neval_i,
                    time.perf_counter() - wall_start, n_i, per_iter,
                    n_i / max(per_iter, 1e-9), lf)
                if self.train_summary is not None:
                    self.train_summary.add_scalar("Loss", lf, neval_i)
                    self.train_summary.add_scalar(
                        "Throughput", n_i / max(per_iter, 1e-9), neval_i)
                    # steps_back rewinds the schedule's step counter to
                    # the value it had when iteration neval_i ran
                    lr = _scheduled_lr(methods[0], opt_states[0], epoch_i,
                                       steps_back=n_pend - 1 - idx)
                    if lr is not None:
                        self.train_summary.add_scalar(
                            "LearningRate", lr, neval_i)
            if self.train_summary is not None:
                # Parameter histograms: only the latest iteration's
                # params exist host-side, so snapshots fire at flush
                # granularity (one per window, labeled with the real
                # neval) instead of fabricating a per-step trajectory.
                trig = (self.train_summary.get_summary_trigger(
                    "Parameters")
                    if hasattr(self.train_summary,
                               "get_summary_trigger") else None)
                last_neval = entries[-1][0]
                if trig is not None and any(
                        trig({**self.state, "neval": ne, "epoch": ep})
                        for (ne, ep, *_r) in entries):
                    self.train_summary.save_parameters(
                        combine(self._merge_groups_host(params_groups),
                                rest), last_neval)
            self.state["loss"] = losses[-1]

        # Async loss drain: with no summary writer attached and no
        # loss-reading trigger, nothing in the loop needs the loss value
        # synchronously — a worker thread does the (blocking) readback
        # and logging while the main thread keeps the device queue full.
        # (With a summary writer, consume_window touches params/opt
        # state host-side; those buffers are donated to the next
        # dispatch, so that path stays synchronous.)
        flush_async = self.train_summary is None and not needs_loss
        flushq: Optional["_queue.Queue"] = None
        flush_thread = None
        if flush_async:
            import queue as _queue

            flushq = _queue.Queue(maxsize=4)

            def _drain():
                while True:
                    job = flushq.get()
                    if job is None:
                        return
                    try:
                        consume_window(*job)
                    except Exception:
                        logger.exception("async loss readback failed")
                    finally:
                        flushq.task_done()

            flush_thread = threading.Thread(
                target=_drain, daemon=True, name="bigdl-loss-drain")
            flush_thread.start()
            # expose for the failure path (_stop_flush_worker)
            self._flushq = flushq
            self._flush_thread = flush_thread

        def flush_pending(params_groups, rest, opt_states, sync=False):
            if pending:
                job = (list(pending), window["start"], window["data_t"],
                       window["fetch_t"], window["disp_t"],
                       params_groups, opt_states, rest)
                if flushq is not None:
                    flushq.put(job)
                else:
                    consume_window(*job)
                pending.clear()
                window["start"] = time.perf_counter()
                window["data_t"] = 0.0
                window["fetch_t"] = 0.0
                window["disp_t"] = 0.0
            if sync and flushq is not None:
                flushq.join()

        k_req = max(1, int(self.iters_per_dispatch))
        if wd is not None and k_req > 1:
            logger.warning(
                "iterations_per_dispatch=%d ignored: the health "
                "watchdog needs per-iteration loss readback "
                "(single-step dispatch)", k_req)
            k_req = 1
        wstep = None
        w_sharding = None
        stage_cache: Dict[Tuple[int, ...], Any] = {}
        stage_cache_bytes = [0]
        cacheable_windows = False
        if k_req > 1:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P
            from bigdl_tpu.dataset.dataset import DeviceCachedDataSet
            wstep = self._build_step(mesh, group_names, spec_groups,
                                     window=True)
            w_sharding = NamedSharding(mesh, P(None, *x_sharding.spec))
            # An UNSHUFFLED device-cached dataset serves the same
            # MiniBatch objects in the same order every epoch, so the
            # stacked window can be staged once and reused (stacking k
            # batches is a large HBM copy; on cached data it would
            # recur every epoch for identical bytes).  Shuffled epochs
            # produce fresh window keys every time — caching those
            # would fill HBM with never-reused stacked copies.
            cacheable_windows = (
                isinstance(self.dataset, DeviceCachedDataSet)
                and not getattr(self.dataset._inner, "_shuffle", True))

        def safe_window(sizes: List[int]) -> int:
            """Largest window <= len(sizes) such that no trigger fires
            before its LAST iteration — replays the loop's bookkeeping
            over predicted states.  Loss-reading triggers force 1 (loss
            changes mid-window); score-based triggers are exact because
            score only changes at validation, which ends a window."""
            w = len(sizes)
            if self.profile_dir and not prof_done:
                nv = self.state["neval"]
                if nv < prof_start:
                    w = min(w, prof_start - nv)
                else:
                    w = min(w, max(prof_start + prof_num - nv, 1))
            trigs = [t for t in (self.end_when, self.val_trigger,
                                 self.checkpoint_trigger) if t is not None]
            if any(getattr(t, "needs_loss", False) for t in trigs):
                return 1
            st = dict(self.state)
            st["is_epoch_end"] = False
            nproc_ = jax.process_count()
            for i in range(w):
                st["records"] += sizes[i] * nproc_
                st["neval"] += 1
                if ((self.val_trigger is not None
                     and self.val_trigger(st))
                        or (self.checkpoint_trigger is not None
                            and self.checkpoint_trigger(st))
                        or self.end_when(st)):
                    return i + 1
            return w

        use_dp = bool(self.device_prefetch_ahead)
        if use_dp and k_req > 1:
            logger.warning(
                "device prefetch disabled: iterations_per_dispatch=%d "
                "stages stacked windows itself", k_req)
            use_dp = False
        if use_dp and jax.process_count() > 1:
            # multi-process staging assembles GLOBAL arrays
            # (make_array_from_process_local_data); a pre-staged batch
            # would feed b.size() the global batch, double-counting
            # records through the * nproc bookkeeping — and collective
            # assembly from a background thread races the main
            # thread's dispatches
            logger.warning(
                "device prefetch disabled: single-process only (the "
                "multi-process loop assembles global batches itself)")
            use_dp = False
        pipeline_restore = self._pipeline_restore
        self._pipeline_restore = None
        self._epoch_offset = 0
        saw_batches = False
        with mesh:
            while not self.end_when(self.state):
                epoch = self.state["epoch"]
                epoch_start = time.perf_counter()
                mode, skip = "none", 0
                if pipeline_restore is not None:
                    mode, skip = self._pipeline_restore_plan(
                        pipeline_restore, epoch)
                    pipeline_restore = None  # applies to one epoch only
                if skip <= 0:
                    self.state["records"] = 0
                # else: mid-epoch resume — the restored driver records
                # already count this epoch's consumed samples
                self._epoch_offset = 0
                batch_iter = iter(epoch_iter(self.dataset, epoch=epoch,
                                             train=True))
                if skip > 0:
                    t_skip = time.perf_counter()
                    fell_back = False
                    if mode == "samples":
                        # topology-portable resume: the sidecar's
                        # global offset converted to per-process
                        # SAMPLES on the current fleet width
                        skipped_b, skipped_s = skip_samples(batch_iter,
                                                            skip)
                        if skipped_s > skip:
                            # the skip point lands MID-batch on the
                            # new batch size: a batch cannot be split,
                            # so the only faithful option is replay
                            logger.warning(
                                "pipeline restore: global offset "
                                "lands mid-batch on the current batch "
                                "size (%d samples to skip, batch "
                                "boundary at %d); replaying epoch %d "
                                "from its start", skip, skipped_s,
                                epoch)
                            self._note_reshard("fallback")
                            _te.record_event(
                                "pipeline_restore", epoch=epoch,
                                offset=skip, mode=mode, skipped=0,
                                fallback="mid_batch")
                            self.state["records"] = 0
                            batch_iter = iter(epoch_iter(
                                self.dataset, epoch=epoch, train=True))
                            skipped_b = 0
                            fell_back = True
                        skipped = skipped_b
                        want = skip
                        got = skipped_s
                    else:
                        skipped = skip_batches(batch_iter, skip)
                        want, got = skip, skipped
                    self._epoch_offset = skipped
                    saw_batches = True  # consumed pre-crash, not absent
                    if not fell_back:
                        _te.record_event(
                            "pipeline_restore", epoch=epoch,
                            offset=skip, mode=mode, skipped=skipped,
                            seconds=round(
                                time.perf_counter() - t_skip, 6))
                        if telemetry.enabled():
                            _tm.pipeline_restore_skipped_batches_total(
                            ).inc(skipped)
                        logger.info(
                            "pipeline restore: skipped %d consumed "
                            "batch(es) of epoch %d (%s mode), resuming "
                            "at the next batch (sample-accurate)",
                            skipped, epoch, mode)
                    if not fell_back and got < want:
                        logger.warning(
                            "pipeline restore: epoch %d has only %d "
                            "%s but the checkpoint consumed %d — "
                            "did the dataset shrink since the "
                            "checkpoint?", epoch, got,
                            "sample(s)" if mode == "samples"
                            else "batch(es)", want)
                dp = None
                if use_dp:
                    from bigdl_tpu.data.device_prefetch import (
                        DevicePrefetch,
                    )
                    dp = DevicePrefetch(
                        self.device_prefetch_ahead,
                        sharding=x_sharding).apply(batch_iter)
                    batch_iter = dp
                    # exposed for the failure path (_stop_device_prefetch):
                    # an exception escaping this attempt must not leak
                    # the producer thread + its device-resident batches
                    # into the retry's fresh attempt
                    self._active_dp = dp
                lookahead: List = []
                stop = False
                while not stop:
                    # fetch wait is DATA time: pulling from the input
                    # pipeline (decode, augment, a stalled loader) is
                    # the other half of "the step waited on data"
                    # alongside device staging — the data-starvation
                    # detector and optimizer_data_wait_seconds must see
                    # both or a slow pipeline hides from them
                    fetch_t0 = time.perf_counter()
                    while len(lookahead) < k_req:
                        try:
                            chaos.on_data_batch()
                            lookahead.append(next(batch_iter))
                        except StopIteration:
                            break
                    fetch_t = time.perf_counter() - fetch_t0
                    if not lookahead:
                        break
                    want = (safe_window([b.size() for b in lookahead])
                            if k_req > 1 else 1)
                    group = [lookahead.pop(0)]
                    if want > 1:
                        sig0 = _batch_sig(group[0])
                        while (lookahead and len(group) < want
                               and _batch_sig(lookahead[0]) == sig0):
                            group.append(lookahead.pop(0))
                    if len(group) != k_req:
                        # ragged tail / trimmed window: single-step path
                        # (a window of any OTHER length would compile a
                        # third program; exactly two programs keeps
                        # compile cost flat — pick k dividing trigger
                        # periods to stay on the fast path)
                        lookahead[0:0] = group[1:]
                        group = group[:1]
                    saw_batches = True
                    nproc = jax.process_count()
                    for b in group:
                        # b.size() is the PER-PROCESS batch; the global
                        # batch this step consumes is nproc shards of it
                        if (b.size() * nproc) % n_data:
                            raise ValueError(
                                f"global batch size {b.size() * nproc} "
                                f"({b.size()} per process x {nproc}) is "
                                f"not divisible by the mesh's "
                                f"data-parallel extent {n_data}; choose "
                                f"a batch size that is a multiple of it")
                    if (self.profile_dir and not prof_active
                            and not prof_done
                            and self.state["neval"] >= prof_start):
                        jax.profiler.start_trace(self.profile_dir)
                        prof_active = True
                    # fault-injection hook: raises BEFORE the window
                    # dispatches, so injected failures land between
                    # steps exactly like a real preemption
                    for _ci in range(len(group)):
                        chaos.on_step(self.state["neval"] + _ci)
                    it_start = time.perf_counter()
                    if len(group) > 1:
                        ckey = (tuple(id(b) for b in group)
                                if cacheable_windows else None)
                        hit = (stage_cache.get(ckey)
                               if ckey is not None else None)
                        staged = hit[0] if hit is not None else None
                        if staged is None:
                            staged = (
                                _stage_window([b.get_input()
                                               for b in group],
                                              w_sharding),
                                _stage_window([b.get_target()
                                               for b in group],
                                              w_sharding))
                            if ckey is not None:
                                nbytes = sum(
                                    getattr(a, "nbytes", 0)
                                    for part in staged
                                    for a in jax.tree_util.tree_leaves(
                                        part))
                                budget = int(os.environ.get(
                                    "BIGDL_TPU_WINDOW_CACHE_BYTES",
                                    str(2 << 30)))
                                # bound by BYTES, FIFO-evicting: entry
                                # counts say nothing about HBM held by
                                # stacked k-batch windows
                                while (stage_cache and
                                       stage_cache_bytes[0] + nbytes
                                       > budget):
                                    _, old_b = stage_cache.pop(
                                        next(iter(stage_cache)))
                                    stage_cache_bytes[0] -= old_b
                                if nbytes <= budget:
                                    stage_cache[ckey] = (staged, nbytes)
                                    stage_cache_bytes[0] += nbytes
                        xs, ys = staged
                        base = self.state["neval"]
                        rngs = jax.vmap(
                            lambda i: jax.random.fold_in(seed_key, i))(
                            jnp.arange(base, base + len(group)))
                        t_data = (time.perf_counter() - it_start
                                  + fetch_t)
                        t_disp0 = time.perf_counter()
                        params_groups, rest, opt_states, losses = wstep(
                            params_groups, rest, opt_states, xs, ys, rngs,
                            epoch)
                        window["disp_t"] += time.perf_counter() - t_disp0
                        # (stacked, idx) markers: flush reads the whole
                        # window back in ONE transfer, no per-step slices
                        loss_list = [(losses, i)
                                     for i in range(len(group))]
                    else:
                        batch = group[0]
                        x = _stage(batch.get_input(), x_sharding)
                        y = _stage(batch.get_target(), x_sharding)
                        rng = jax.random.fold_in(seed_key,
                                                 self.state["neval"])
                        t_data = (time.perf_counter() - it_start
                                  + fetch_t)
                        t_disp0 = time.perf_counter()
                        if wd is not None:
                            (params_groups, rest, opt_states, loss,
                             gnorm) = step(params_groups, rest,
                                           opt_states, x, y, rng, epoch)
                            window["disp_t"] += (time.perf_counter()
                                                 - t_disp0)
                            self._watchdog_step_check(
                                wd, loss, gnorm, self.state["neval"])
                        else:
                            params_groups, rest, opt_states, loss = \
                                step(params_groups, rest, opt_states,
                                     x, y, rng, epoch)
                            window["disp_t"] += (time.perf_counter()
                                                 - t_disp0)
                        loss_list = [loss]
                    self.metrics.add("data load and transfer", t_data)
                    if telemetry.enabled():
                        _tm.optimizer_data_wait_seconds().observe(t_data)
                        # span endpoints on tracing's perf_counter
                        # clock (it_start is time.time); the dispatch
                        # call between interval end and here is an
                        # async enqueue, so the shift is negligible
                        pc = time.perf_counter()
                        _tt.record_span("optimizer/data_wait",
                                        pc - t_data, pc)
                    window["data_t"] += t_data
                    window["fetch_t"] += fetch_t
                    for b, loss_i in zip(group, loss_list):
                        # records are GLOBAL: b.size() is per-process
                        n = b.size() * nproc
                        self._last_global_batch = n
                        self.state["records"] += n
                        pending.append((self.state["neval"], epoch, n,
                                        self.state["records"], loss_i))
                        if prof_active and (self.state["neval"]
                                            >= prof_start + prof_num - 1):
                            jax.block_until_ready(
                                loss_i[0] if isinstance(loss_i, tuple)
                                else loss_i)
                            jax.profiler.stop_trace()
                            prof_active = False
                            prof_done = True
                        if len(pending) >= interval:
                            flush_pending(params_groups, rest, opt_states)
                        self.state["neval"] += 1
                        self._epoch_offset += 1
                        self.state["is_epoch_end"] = False
                        if self._want_validate_checkpoint():
                            # sync: the checkpoint records state["loss"],
                            # and validation logs should follow the
                            # iterations they validate
                            flush_pending(params_groups, rest, opt_states,
                                          sync=True)
                            self._maybe_validate_checkpoint(
                                params_groups, rest, opt_states, eval_step)
                            # don't bill validation/checkpoint wall time
                            # to the next window's "device step time"
                            window["start"] = time.perf_counter()
                        # no break: the whole window's updates are
                        # already applied to the params, so the
                        # remaining entries' bookkeeping (neval,
                        # records, loss logging) must complete even if
                        # a custom end trigger fires mid-window —
                        # otherwise checkpoints disagree with weights
                        stop = (stop or bool(self.end_when(self.state))
                                or self._preempt_requested
                                or self._halt_requested)
                if dp is not None:
                    dp.close()  # unblock the producer on an early exit
                    self._active_dp = None
                if self._preempt_requested or self._halt_requested:
                    # SIGTERM, or a watchdog checkpoint_and_halt
                    # verdict, landed: this is the requested safe step
                    # boundary — no collective is in flight.  Write the
                    # final checkpoint (the watchdog's in-graph guard
                    # already discarded any nonfinite update, so the
                    # saved weights are good) and return cleanly
                    # instead of dying mid-epoch (the epoch counter
                    # must NOT advance: the epoch is unfinished and
                    # resume has to replay its remaining batches).
                    halting = self._halt_requested
                    flush_pending(params_groups, rest, opt_states,
                                  sync=True)
                    self._preemption_checkpoint(
                        params_groups, rest, opt_states,
                        reason="watchdog halt" if halting
                        else "preemption")
                    if halting:
                        self.watchdog_halted = True
                        _te.record_event(
                            "watchdog_halt", epoch=epoch,
                            iteration=self.state["neval"],
                            checkpoint_generation=(
                                self._last_ckpt_generation))
                        self._dump_flight_recorder("watchdog_halt")
                        logger.warning(
                            "watchdog: halting training at epoch %d "
                            "iteration %d (final checkpoint written, "
                            "flight recorder dumped)", epoch,
                            self.state["neval"])
                    else:
                        self.preempted = True
                        _te.record_event(
                            "preemption", epoch=epoch,
                            iteration=self.state["neval"])
                        logger.warning(
                            "preemption: exiting training cleanly at "
                            "epoch %d iteration %d", epoch,
                            self.state["neval"])
                    break
                self.state["epoch"] += 1
                self._epoch_offset = 0  # snapshots at the boundary say
                self.state["is_epoch_end"] = True  # "next epoch, batch 0"
                flush_pending(params_groups, rest, opt_states,
                              sync=self._want_validate_checkpoint())
                logger.info("Epoch %d finished in %.2f s", epoch,
                            time.perf_counter() - epoch_start)
                if not saw_batches:
                    raise ValueError(
                        "dataset produced no batches (empty dataset, or "
                        "fewer samples than one batch with drop_last)")
                self._maybe_validate_checkpoint(
                    params_groups, rest, opt_states, eval_step)
                window["start"] = time.perf_counter()
            flush_pending(params_groups, rest, opt_states, sync=True)
            if prof_active:
                jax.profiler.stop_trace()
        if flushq is not None:
            flushq.put(None)  # worker exits after draining earlier jobs
            flush_thread.join(timeout=60.0)
            self._flushq = None
            self._flush_thread = None

        # drain the async summary writers: without this, a run that
        # ends before the writer thread's next flush loses its tail —
        # or, for short runs, every scalar (the daemon thread dies with
        # the process).  The retry/crash path flushes in optimize().
        self._flush_summaries()

        # write trained params back into the user's module (in place)
        trained = combine(self._merge_groups_host(params_groups), rest)
        self._sync_into(self.model, trained)
        logger.info("%s", self.metrics.summary())
        return self.model

    def _merge_groups_host(self, params_groups):
        full = [None] * self._n_param_leaves
        for idxs, glist in zip(self._group_idx, params_groups):
            for i, v in zip(idxs, glist):
                full[i] = v
        return jax.tree_util.tree_unflatten(self._ptreedef, full)

    # ---- helpers ---------------------------------------------------------

    def _want_validate_checkpoint(self) -> bool:
        """Cheap host-side pre-check so the hot loop only flushes pending
        loss readback when validation/checkpoint will actually fire."""
        return ((self.val_trigger is not None
                 and self.val_trigger(self.state)
                 and self._last_val_neval != self.state["neval"])
                or (self.checkpoint_trigger is not None
                    and self.checkpoint_trigger(self.state)
                    and self._last_ckpt_neval != self.state["neval"]))

    def _maybe_validate_checkpoint(self, params_groups, rest,
                                   opt_states, eval_step):
        # fire each action at most once per iteration (the epoch-end call
        # would otherwise re-fire iteration-based triggers that already
        # fired on the last batch)
        do_val = (self.val_trigger is not None
                  and self.val_trigger(self.state)
                  and self._last_val_neval != self.state["neval"])
        do_ckpt = (self.checkpoint_trigger is not None
                   and self.checkpoint_trigger(self.state)
                   and self._last_ckpt_neval != self.state["neval"])
        if not (do_val or do_ckpt):
            return
        merged = self._merge_groups_host(params_groups)
        if do_val:
            self._last_val_neval = self.state["neval"]
            current = combine(merged, rest).eval_mode()
            t_val0 = time.perf_counter()
            with self.metrics.time("validation time"), \
                    _tt.span("optimizer/validation"):
                results = self._validate(current, eval_step)
            if telemetry.enabled():
                _tm.optimizer_validation_seconds().observe(
                    time.perf_counter() - t_val0)
            current.train_mode()
            if results:
                first = next(iter(results.values()))
                self.state["score"] = first.result()[0]
                if self.val_summary is not None:
                    for name, r in results.items():
                        self.val_summary.add_scalar(
                            name, r.result()[0], self.state["neval"])
                for m in ([self.optim_method]
                          if not self.optim_methods
                          else self.optim_methods.values()):
                    sched = getattr(m, "schedule", None)
                    if isinstance(sched, Plateau):
                        sched.on_metric(self.state["score"])
        if do_ckpt:
            self._last_ckpt_neval = self.state["neval"]
            temp = combine(merged, rest)
            driver = {k: v for k, v in self.state.items()
                      if isinstance(v, (int, float))}
            with self.metrics.time("checkpoint time"):
                path = self._write_checkpoint(temp, opt_states, driver)
            logger.info("checkpoint written to %s", path)

    def _plan_record(self) -> Optional[Dict[str, Any]]:
        """The partition-plan stamp for checkpoint topology manifests:
        strategy degrees (>1 only) + pipeline schedule, or None when
        the run never set a plan.  Lets a resume see WHICH strategies
        (tp/pp/...) shaped the saved shardings, not just the mesh."""
        rp = self.partition_plan
        if rp is None:
            return None
        rec: Dict[str, Any] = {
            "degrees": {k: int(v) for k, v in rp.degrees.items()
                        if int(v) > 1}}
        if rp.pp_schedule is not None:
            rec["pp_schedule"] = rp.pp_schedule
        return rec

    def _write_checkpoint(self, temp, opt_states, driver) -> str:
        """One checkpoint generation through the CheckpointManager:
        atomic payload commit, CRC manifest, retention GC."""
        mgr = self._ckpt_manager()
        pipeline_state = self._pipeline_snapshot()
        mesh = getattr(self, "_active_mesh", None)
        plan_rec = self._plan_record()
        if self.checkpoint_sharded:
            # device arrays pass through unchanged: each host writes
            # its own shards, no gather.  The driver rides inside the
            # orbax tree under a FIXED key set (strict orbax restores
            # match structures exactly; self.state grows transient keys
            # mid-loop)
            path = mgr.save(
                {"params": temp.parameters(), "buffers": temp.buffers()},
                [s for s in opt_states],
                {k: driver[k] for k in _DRIVER_KEYS if k in driver},
                generation=self.state["neval"],
                overwrite=self.overwrite_checkpoint, sharded=True,
                pipeline_state=pipeline_state, mesh=mesh,
                plan=plan_rec)
        else:
            path = mgr.save(
                {"params": _to_plain(temp.parameters()),
                 "buffers": _to_plain(temp.buffers())},
                [s for s in opt_states], driver,
                generation=self.state["neval"],
                overwrite=self.overwrite_checkpoint, sharded=False,
                pipeline_state=pipeline_state, mesh=mesh,
                plan=plan_rec)
        # /statusz reports the last generation this run committed
        self._last_ckpt_generation = self.state["neval"]
        self._last_ckpt_path = path
        return path

    def _preemption_checkpoint(self, params_groups, rest, opt_states,
                               reason: str = "preemption"):
        """The final checkpoint a SIGTERM (or a watchdog halt verdict)
        requests; written outside any trigger schedule so no progress
        since the last periodic checkpoint is lost."""
        if not self.checkpoint_path:
            logger.warning("%s: no checkpoint path configured; "
                           "exiting without a final checkpoint", reason)
            return
        if self._last_ckpt_neval == self.state["neval"]:
            return  # this exact boundary is already checkpointed
        self._last_ckpt_neval = self.state["neval"]
        temp = combine(self._merge_groups_host(params_groups), rest)
        driver = {k: v for k, v in self.state.items()
                  if isinstance(v, (int, float))}
        try:
            with self.metrics.time("checkpoint time"):
                path = self._write_checkpoint(temp, opt_states, driver)
            logger.info("%s checkpoint written to %s", reason, path)
        except Exception:
            # best effort: a failed final save must not turn a clean
            # preemption/halt exit into a crash (the periodic
            # checkpoint still exists)
            logger.exception("%s checkpoint failed", reason)

    def _sync_into(self, target: Module, source: Module):
        """Copy arrays from the trained functional copy back into the
        user's original module object (Torch-style UX: optimize() mutates
        the model you built)."""
        target._params.update(source._params)
        target._buffers.update(source._buffers)
        for name in target._modules:
            sub_t = target._modules[name]
            sub_s = source._modules[name]
            from bigdl_tpu.core.module import ModuleList
            if isinstance(sub_t, ModuleList):
                for mt, ms in zip(sub_t._items, sub_s._items):
                    self._sync_into(mt, ms)
            else:
                self._sync_into(sub_t, sub_s)


def _to_plain(tree):
    return jax.tree_util.tree_map(lambda x: np.asarray(x), tree)


def _batch_sig(b):
    """Stackability signature of a minibatch: pytree structure + leaf
    shapes/dtypes of (input, target).  Batches in one dispatch window
    must match so they can be stacked on a new leading axis."""
    leaves, treedef = jax.tree_util.tree_flatten(
        (b.get_input(), b.get_target()))
    return (treedef,
            tuple((tuple(np.shape(l)),
                   str(getattr(l, "dtype", None) or np.asarray(l).dtype))
                  for l in leaves))


def _put_sharded(arr, sharding):
    """Host batch → global device array.  Single-process: device_put.
    Multi-process (jax.distributed): each host holds only ITS shard of
    the global batch (DistributedDataSet), so the global array must be
    assembled from per-process locals — device_put would misread the
    local shard as the whole global value.  ≙ the reference's
    per-partition Sample batches feeding one logical DistriOptimizer
    step (optim/DistriOptimizer.scala taskData)."""
    if jax.process_count() > 1:
        return jax.make_array_from_process_local_data(
            sharding, np.asarray(arr))
    return jax.device_put(jnp.asarray(arr), sharding)


def _stage_window(vals, sharding=None):
    """Stack per-iteration batch pytrees on a new leading axis (window
    dim) and stage to the device; the window dim is unsharded, the batch
    dim keeps the data-parallel sharding.  Multi-process runs stack on
    the host (make_array_from_process_local_data needs host locals);
    single-process keeps the on-device stack so device-cached batches
    never round-trip through the host."""
    multi = jax.process_count() > 1
    if sharding is not None and multi:
        return jax.tree_util.tree_map(
            lambda *ls: _put_sharded(np.stack([np.asarray(l)
                                               for l in ls]), sharding),
            *vals)
    stacked = jax.tree_util.tree_map(
        lambda *ls: jnp.stack([jnp.asarray(l) for l in ls]), *vals)
    if sharding is not None:
        stacked = jax.tree_util.tree_map(
            lambda a: jax.device_put(a, sharding), stacked)
    return stacked


def _stage(value, sharding=None):
    """Batch value (array, or any pytree of arrays — tuple/list/Table —
    for multi-input models) → device arrays, optionally sharded."""
    if value is None:
        return None

    def put(leaf):
        if sharding is None:
            return jnp.asarray(leaf)
        if isinstance(leaf, jax.Array) \
                and getattr(leaf, "sharding", None) == sharding:
            # already staged into the target sharding (DevicePrefetch's
            # background thread, or an HBM-cached dataset): zero host
            # transfer on the hot path
            return leaf
        return _put_sharded(leaf, sharding)

    return jax.tree_util.tree_map(put, value)


def _scheduled_lr(method, opt_state, epoch, steps_back: int = 0):
    """The learning rate applied ``steps_back`` iterations before the
    given (post-update) opt_state: base lr run through the method's
    schedule at the step count that iteration saw."""
    lr = getattr(method, "learning_rate", None)
    if lr is None:
        return None
    sched = getattr(method, "schedule", None)
    if sched is None:
        return float(lr)
    t = opt_state.get("t")
    if t is None:
        return float(lr)
    # opt_state is post-update: the step just taken evaluated the
    # schedule at t-1; earlier window iterations at t-1-steps_back
    t_applied = jnp.maximum(jnp.asarray(t) - 1 - steps_back, 0)
    return float(sched(lr, t_applied, epoch))
