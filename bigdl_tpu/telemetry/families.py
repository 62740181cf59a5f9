"""Canonical metric-family declarations.

Every metric NAME in the codebase is declared exactly once, here, as a
get-or-create accessor; instrumentation sites import the accessor
instead of re-spelling the string.  ``scripts/metrics_lint.py``
enforces this statically (duplicate or non-``snake_case`` names fail,
as do names missing from the table in ``docs/observability.md``).

Two consequences worth the indirection:

* ``preregister()`` can materialize the whole catalog, so a process
  that only serves still exposes the optimizer/checkpoint families
  (at zero) on ``/metrics`` — one scrape config covers every role.
* Renames are single-file diffs that the lint cross-checks against the
  documentation table.
"""

from __future__ import annotations

import weakref
from typing import List

from bigdl_tpu.telemetry.metrics import (
    Counter, Gauge, Histogram, get_registry,
)

__all__ = ["preregister", "bridge_serving_metrics"]


# ---- optimizer step-phase breakdown ---------------------------------------

def optimizer_data_wait_seconds() -> Histogram:
    return get_registry().histogram(
        "optimizer_data_wait_seconds",
        "Host time staging one iteration's batch (fetch + device put)")


def optimizer_step_seconds() -> Histogram:
    return get_registry().histogram(
        "optimizer_step_seconds",
        "Device step time per iteration, amortized over the async "
        "readback window that completed it (completion-to-completion, "
        "minus data-wait)")


def optimizer_validation_seconds() -> Histogram:
    return get_registry().histogram(
        "optimizer_validation_seconds",
        "Wall time of one validation sweep")


def optimizer_retries_total() -> Counter:
    return get_registry().counter(
        "optimizer_retries_total",
        "Transient-failure retries taken by Optimizer.optimize()")


# ---- perf attribution (telemetry.perf) ------------------------------------

def step_phase_seconds() -> Histogram:
    return get_registry().histogram(
        "step_phase_seconds",
        "Per-iteration seconds of each step-time attribution phase "
        "(data_wait / host_staging / device_compute / readback), "
        "amortized over the readback window — one observation per "
        "window per phase",
        labelnames=("phase",),
        buckets=(1e-5, 1e-4, 1e-3, 5e-3, 0.01, 0.025, 0.05, 0.1, 0.25,
                 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, float("inf")))


def step_unattributed_fraction() -> Gauge:
    return get_registry().gauge(
        "step_unattributed_fraction",
        "Fraction of the latest readback window's wall time not "
        "covered by any measured attribution phase (the honest "
        "residual, set per window by the loss-drain worker; the run "
        "aggregate lives in the attribution report — see "
        "docs/performance.md 'Attributing an MFU gap')")


# ---- mesh observability: collectives + fleet -------------------------------

def collective_bytes_total() -> Counter:
    return get_registry().counter(
        "collective_bytes_total",
        "Per-device payload bytes of explicit collectives, accounted "
        "at TRACE time per {op, axis} (one compiled step's comm "
        "budget; see telemetry.collectives for the byte convention)",
        labelnames=("op", "axis"))


def collective_calls_total() -> Counter:
    return get_registry().counter(
        "collective_calls_total",
        "Explicit collective call sites traced, per {op, axis} (one "
        "count per site per trace — loop bodies count once, like the "
        "compiled HLO)",
        labelnames=("op", "axis"))


def fleet_step_skew() -> Gauge:
    return get_registry().gauge(
        "fleet_step_skew",
        "Slowest-host / median-host ratio over the latest fleet "
        "sample (max of the step-wall and data-wait skews; 1.0 = a "
        "balanced fleet, large = a straggler — see telemetry.fleet)")


# ---- training health (watchdog) -------------------------------------------

def training_nonfinite_total() -> Counter:
    return get_registry().counter(
        "training_nonfinite_total",
        "Non-finite loss / gradient-norm detections by the health "
        "watchdog")


def training_anomalies_total() -> Counter:
    return get_registry().counter(
        "training_anomalies_total",
        "Health-watchdog verdicts by anomaly kind",
        labelnames=("kind",))


def grad_norm() -> Histogram:
    return get_registry().histogram(
        "grad_norm",
        "Global (pre-clip-scale) gradient L2 norm per iteration, "
        "observed when the health watchdog is on",
        buckets=(0.0001, 0.001, 0.01, 0.1, 0.5, 1.0, 2.0, 5.0, 10.0,
                 100.0, 1e3, 1e6, float("inf")))


# ---- checkpointing ---------------------------------------------------------

def checkpoint_commit_seconds() -> Histogram:
    return get_registry().histogram(
        "checkpoint_commit_seconds",
        "CheckpointManager.save wall time: payload + manifest + GC")


def checkpoint_torn_generations_total() -> Counter:
    return get_registry().counter(
        "checkpoint_torn_generations_total",
        "Generations latest_good() walked past as corrupt, truncated, "
        "or uncommitted")


def checkpoint_reshard_restores_total() -> Counter:
    return get_registry().counter(
        "checkpoint_reshard_restores_total",
        "Checkpoint restores onto a topology other than the one that "
        "wrote them, by outcome: resharded (N->M resume succeeded), "
        "fallback (pipeline position unportable — epoch-start replay), "
        "failed (a leaf is genuinely unportable and the restore "
        "raised)",
        labelnames=("outcome",))


# ---- chaos (fault injection) ----------------------------------------------

def chaos_faults_injected_total() -> Counter:
    return get_registry().counter(
        "chaos_faults_injected_total",
        "Faults the chaos harness actually fired")


# ---- input pipeline --------------------------------------------------------

def prefetch_queue_depth() -> Gauge:
    return get_registry().gauge(
        "prefetch_queue_depth",
        "Ready minibatches buffered by Prefetch, sampled at each "
        "consumer get")


def prefetch_producer_wait_total() -> Counter:
    return get_registry().counter(
        "prefetch_producer_wait_total",
        "Producer blocked-on-full-queue events (consumer is the "
        "bottleneck)")


def prefetch_consumer_wait_total() -> Counter:
    return get_registry().counter(
        "prefetch_consumer_wait_total",
        "Consumer blocked-on-empty-queue events (input pipeline is the "
        "bottleneck: the step waited on data)")


def pipeline_samples_per_second() -> Gauge:
    return get_registry().gauge(
        "pipeline_samples_per_second",
        "Input-pipeline throughput: global samples consumed per second "
        "over the latest completed readback window")


def device_prefetch_buffer_occupancy() -> Gauge:
    return get_registry().gauge(
        "device_prefetch_buffer_occupancy",
        "Device-resident batches buffered by DevicePrefetch, sampled "
        "at each consumer get (0 = the step waited on H2D staging)")


def pipeline_restore_skipped_batches_total() -> Counter:
    return get_registry().counter(
        "pipeline_restore_skipped_batches_total",
        "Batches skipped while restoring PipelineState (sample-accurate "
        "mid-epoch resume replays the epoch order up to the offset)")


# ---- per-module eager profiling -------------------------------------------

def module_forward_seconds() -> Histogram:
    return get_registry().histogram(
        "module_forward_seconds",
        "Eager per-module forward wall time from optim.profiling",
        labelnames=("module_type",))


# ---- host / device runtime -------------------------------------------------

def process_rss_bytes() -> Gauge:
    return get_registry().gauge(
        "process_rss_bytes", "Resident set size of this process")


def gc_collections_total() -> Counter:
    return get_registry().counter(
        "gc_collections_total",
        "CPython garbage-collector runs", labelnames=("generation",))


def device_memory_bytes_in_use() -> Gauge:
    return get_registry().gauge(
        "device_memory_bytes_in_use",
        "Accelerator memory in use (jax device memory_stats)",
        labelnames=("device",))


def device_memory_bytes_limit() -> Gauge:
    return get_registry().gauge(
        "device_memory_bytes_limit",
        "Accelerator memory capacity (jax device memory_stats)",
        labelnames=("device",))


def hbm_bytes_peak() -> Gauge:
    return get_registry().gauge(
        "hbm_bytes_peak",
        "Peak accelerator memory in use per device: the backend's own "
        "peak_bytes_in_use when memory_stats() provides it, else a "
        "high-water mark over sampled bytes_in_use (telemetry.runtime)",
        labelnames=("device",))


# ---- serving bridge --------------------------------------------------------
# The serving MetricsRegistry keeps its own lock-coherent snapshot (its
# public schema is unchanged); this bridge mirrors that snapshot into
# the telemetry registry at READ time via a collector — the serving hot
# path never touches telemetry.

def serving_latency_ms() -> Gauge:
    return get_registry().gauge(
        "serving_latency_ms",
        "End-to-end request latency quantiles (enqueue to result)",
        labelnames=("quantile",))


def serving_queue_depth() -> Gauge:
    return get_registry().gauge(
        "serving_queue_depth",
        "Mean backlog sampled at each dispatch")


def serving_queue_depth_max() -> Gauge:
    return get_registry().gauge(
        "serving_queue_depth_max", "Max backlog seen at any dispatch")


def serving_requests_total() -> Counter:
    return get_registry().counter(
        "serving_requests_total", "Requests served")


def serving_batches_total() -> Counter:
    return get_registry().counter(
        "serving_batches_total", "Device batches executed")


def serving_shed_total() -> Counter:
    return get_registry().counter(
        "serving_shed_total", "Requests shed by admission control")


def serving_rejected_total() -> Counter:
    return get_registry().counter(
        "serving_rejected_total", "Requests rejected at admission")


def serving_padded_waste_ratio() -> Gauge:
    return get_registry().gauge(
        "serving_padded_waste_ratio",
        "Padded rows / dispatched rows (flops burned on dropped rows)")


def serving_batch_occupancy() -> Gauge:
    return get_registry().gauge(
        "serving_batch_occupancy",
        "Batches executed with this many real rows",
        labelnames=("rows",))


# ---- generation serving (continuous batching, serving.generation) ---------

def generation_tokens_per_second() -> Gauge:
    return get_registry().gauge(
        "generation_tokens_per_second",
        "Aggregate decode throughput of the continuous-batching slot "
        "pool (new tokens only), over a rolling ~0.5 s window")


def generation_slot_occupancy() -> Gauge:
    return get_registry().gauge(
        "generation_slot_occupancy",
        "Active slots / pool size sampled at each pooled decode step "
        "(1.0 = every KV slot is earning tokens; low = admit more or "
        "shrink S)")


def generation_phase_seconds() -> Histogram:
    return get_registry().histogram(
        "generation_phase_seconds",
        "Wall seconds per generation engine phase: one bucketed "
        "prompt prefill+scatter, or one pooled decode step",
        labelnames=("phase",),
        buckets=(1e-4, 5e-4, 1e-3, 5e-3, 0.01, 0.025, 0.05, 0.1, 0.25,
                 0.5, 1.0, 2.5, 10.0, float("inf")))


def generation_queue_to_first_token_seconds() -> Histogram:
    return get_registry().histogram(
        "generation_queue_to_first_token_seconds",
        "Queue-to-first-token latency per generation request (submit "
        "to the first emitted token, the slot-wait + prefill cost a "
        "client observes)",
        buckets=(1e-3, 5e-3, 0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
                 10.0, 30.0, float("inf")))


def generation_inter_token_seconds() -> Histogram:
    return get_registry().histogram(
        "generation_inter_token_seconds",
        "Gap between consecutive emitted tokens of one generation "
        "request (the streaming cadence chunked prefill exists to "
        "bound; the tail shows prefill stalls)",
        buckets=(1e-4, 5e-4, 1e-3, 5e-3, 0.01, 0.025, 0.05, 0.1, 0.25,
                 0.5, 1.0, 2.5, 10.0, float("inf")))


def generation_prefix_cache_events_total() -> Counter:
    return get_registry().counter(
        "generation_prefix_cache_events_total",
        "Prefix KV-cache lookups at admit, labelled hit (>= one cached "
        "chunk copied) or miss", labelnames=("result",))


def generation_prefix_cache_bytes_reused_total() -> Counter:
    return get_registry().counter(
        "generation_prefix_cache_bytes_reused_total",
        "Prefill K/V bytes copied from the prefix cache instead of "
        "recomputed (the prefill compute the cache saved)")


def generation_prefix_cache_resident_bytes() -> Gauge:
    return get_registry().gauge(
        "generation_prefix_cache_resident_bytes",
        "Bytes currently held by the prefix KV cache (LRU-bounded by "
        "its byte budget)")


def generation_prefill_dedup_total() -> Counter:
    return get_registry().counter(
        "generation_prefill_dedup_total",
        "Single-flight prefill dedup decisions at admit: a leader "
        "claimed uncached chunks and prefilled them; a follower "
        "parked on another request's in-flight prefill and re-matched "
        "the cache after its insert (a burst of identical cold "
        "prompts prefills once)", labelnames=("result",))


# ---- serving fabric (router + replica registry, serving.router) -----------

def router_requests_total() -> Counter:
    return get_registry().counter(
        "router_requests_total",
        "Requests reaching a terminal outcome at the router: ok "
        "(served), shed (typed RequestSheddedError under overload), "
        "rejected (no eligible replica / closed / cancelled), failed "
        "(replica-side error)", labelnames=("outcome",))


def router_replica_inflight() -> Gauge:
    return get_registry().gauge(
        "router_replica_inflight",
        "Requests dispatched to a replica and not yet terminal, per "
        "replica id (the quantity the bounded-load affinity fallback "
        "caps)", labelnames=("replica",))


def router_shed_total() -> Counter:
    return get_registry().counter(
        "router_shed_total",
        "Requests shed by the router, by reason: queue_full (bounded "
        "queue overflow, oldest first), slo (every eligible replica "
        "breached its TTFT p99 target), no_replica (nothing healthy "
        "and non-draining), budget (per-model admission budget "
        "exhausted), deadline (the request's end-to-end deadline "
        "budget expired while it waited)", labelnames=("reason",))


# -- request reliability (deadlines, breakers, retry/hedge) ------------------

def router_retries_total() -> Counter:
    return get_registry().counter(
        "router_retries_total",
        "Re-dispatches of a request to a different replica, by "
        "reason: transport (typed submit flake — the request never "
        "reached the replica), replica_failed (the replica failed the "
        "request after admitting it), failover (mid-stream generation "
        "failover — the replay of prompt+emitted onto a survivor)",
        labelnames=("reason",))


def router_hedges_total() -> Counter:
    return get_registry().counter(
        "router_hedges_total",
        "Hedged dispatches (a duplicate sent to a second replica "
        "after the p99-derived delay), by outcome: primary_won, "
        "hedge_won (the duplicate finished first; the loser was "
        "cancelled)", labelnames=("outcome",))


def router_breaker_transitions_total() -> Counter:
    return get_registry().counter(
        "router_breaker_transitions_total",
        "Per-replica circuit-breaker state transitions, by "
        "destination state: open (consecutive submit failures or "
        "stale health snapshots), half_open (open window elapsed; "
        "probe traffic admitted), closed (a probe succeeded)",
        labelnames=("to",))


def request_deadline_exceeded_total() -> Counter:
    return get_registry().counter(
        "request_deadline_exceeded_total",
        "Requests rejected because their end-to-end deadline budget "
        "ran out, by pipeline stage: queue (before a slot was "
        "spent), prefill, decode (evicted mid-stream by the engine "
        "sweep)", labelnames=("stage",))


# -- request-scoped distributed tracing (telemetry.request_trace) ------------

def request_traces_retained_total() -> Counter:
    return get_registry().counter(
        "request_traces_retained_total",
        "Completed request traces kept by tail-based retention, by "
        "reason: deadline (the request's budget expired), shed (typed "
        "rejection under overload), failover (a mid-stream replay "
        "moved it between replicas), hedge_won (the hedged twin beat "
        "the primary), slow_ttft / slow_inter_token (latency above "
        "the rolling percentile watermark) — the p99 requests a "
        "uniform sampler would drop", labelnames=("reason",))


def request_trace_spans_total() -> Counter:
    return get_registry().counter(
        "request_trace_spans_total",
        "Spans recorded into request-scoped traces (admission, "
        "dispatch, queue, prefill, decode, handoff, and every "
        "reliability hop) — volume of the per-trace store, retained "
        "and bulk alike")


def request_traces_dropped_total() -> Counter:
    return get_registry().counter(
        "request_traces_dropped_total",
        "Completed request traces evicted unretained from the bounded "
        "bulk ring (healthy traffic sampled out by design; a retained "
        "trace is never counted here)")


# ---- sharded embedding tables (embedding/) --------------------------------

def embedding_lookup_ids_total() -> Counter:
    return get_registry().counter(
        "embedding_lookup_ids_total",
        "Ids looked up per sharded embedding table (counted at trace "
        "time per compiled batch shape; multiply by executions for "
        "wall totals — the a2a bytes these ids imply are what "
        "collective_bytes_total{op=all_to_all} accounts)",
        labelnames=("table",))


def embedding_unique_id_fraction() -> Gauge:
    return get_registry().gauge(
        "embedding_unique_id_fraction",
        "Unique/total id ratio of the last concrete (non-traced) "
        "lookup batch per table — the dedup leverage: backward "
        "scatters one combined row per UNIQUE id, so 0.3 here means "
        "the sparse gradient is 3.3x smaller than the id count "
        "suggests", labelnames=("table",))


def embedding_shard_rows() -> Gauge:
    return get_registry().gauge(
        "embedding_shard_rows",
        "Rows owned by each shard of a mesh-sharded embedding table "
        "(contiguous-block layout; set at set_mesh time — uniform "
        "today, the gauge exists so a future non-uniform placement "
        "shows its skew)", labelnames=("table", "shard"))


# ---- fleet controller (autoscaler + continuous deployment, fleet/) --------

def fleet_replicas_desired() -> Gauge:
    return get_registry().gauge(
        "fleet_replicas_desired",
        "Replica count the controller currently wants per model pool "
        "(the reconcile target; moves on scale decisions, clamped to "
        "[min_replicas, max_replicas])", labelnames=("model",))


def fleet_replicas_live() -> Gauge:
    return get_registry().gauge(
        "fleet_replicas_live",
        "Healthy, non-draining replicas the registry currently "
        "reports per model pool (the reconcile observation; lags "
        "desired while spawns warm up or drains finish)",
        labelnames=("model",))


def fleet_scale_events_total() -> Counter:
    return get_registry().counter(
        "fleet_scale_events_total",
        "Scaling actions the controller actually took, by direction: "
        "up (spawned a replica — load breach or replacement of a dead "
        "one), down (started a zero-drop drain-out)",
        labelnames=("direction",))


def fleet_deploy_freshness_seconds() -> Gauge:
    return get_registry().gauge(
        "fleet_deploy_freshness_seconds",
        "Train-to-serve freshness: seconds from a checkpoint "
        "generation's commit timestamp (manifest time) to the moment "
        "the LAST serving replica in the pool finished hot-deploying "
        "it — the one number answering how stale serving weights are")


_PREREGISTER = (
    optimizer_data_wait_seconds, optimizer_step_seconds,
    optimizer_validation_seconds, optimizer_retries_total,
    step_phase_seconds, step_unattributed_fraction,
    collective_bytes_total, collective_calls_total, fleet_step_skew,
    hbm_bytes_peak,
    training_nonfinite_total, training_anomalies_total, grad_norm,
    checkpoint_commit_seconds, checkpoint_torn_generations_total,
    checkpoint_reshard_restores_total,
    chaos_faults_injected_total,
    prefetch_queue_depth, prefetch_producer_wait_total,
    prefetch_consumer_wait_total,
    pipeline_samples_per_second, device_prefetch_buffer_occupancy,
    pipeline_restore_skipped_batches_total,
    module_forward_seconds,
    process_rss_bytes, gc_collections_total,
    device_memory_bytes_in_use, device_memory_bytes_limit,
    serving_latency_ms, serving_queue_depth, serving_queue_depth_max,
    serving_requests_total, serving_batches_total, serving_shed_total,
    serving_rejected_total, serving_padded_waste_ratio,
    serving_batch_occupancy,
    generation_tokens_per_second, generation_slot_occupancy,
    generation_phase_seconds, generation_queue_to_first_token_seconds,
    generation_inter_token_seconds,
    generation_prefix_cache_events_total,
    generation_prefix_cache_bytes_reused_total,
    generation_prefix_cache_resident_bytes,
    generation_prefill_dedup_total,
    router_requests_total, router_replica_inflight, router_shed_total,
    router_retries_total, router_hedges_total,
    router_breaker_transitions_total, request_deadline_exceeded_total,
    request_traces_retained_total, request_trace_spans_total,
    request_traces_dropped_total,
    fleet_replicas_desired, fleet_replicas_live,
    fleet_scale_events_total, fleet_deploy_freshness_seconds,
    embedding_lookup_ids_total, embedding_unique_id_fraction,
    embedding_shard_rows,
)


def preregister() -> None:
    """Materialize every family so exports show the full catalog (at
    zero) even in a process that hasn't exercised a subsystem yet —
    the /metrics endpoint of a fresh server already names the
    optimizer/checkpoint families a dashboard will chart."""
    for accessor in _PREREGISTER:
        accessor()


def bridge_serving_metrics(serving_registry) -> None:
    """Mirror a serving ``MetricsRegistry`` into the telemetry registry
    via a pull collector.  Holds only a weakref — once a shut-down
    server's registry is garbage collected the collector unregisters
    itself (returning ``COLLECTOR_DONE``), freezing the last-mirrored
    values at their final reading.

    The serving families are unlabeled: with several serving
    registries LIVE in one process the last-registered collector wins
    each scrape.  One data plane per process is the deployment shape
    (``bigdl-tpu-serve``); a multi-server process should construct one
    shared ``MetricsRegistry`` and pass it to each ``ModelServer``."""
    from bigdl_tpu.telemetry.metrics import COLLECTOR_DONE
    ref = weakref.ref(serving_registry)

    def collect():
        from bigdl_tpu import telemetry
        reg = ref()
        if reg is None:
            return COLLECTOR_DONE
        if not telemetry.enabled():
            # the operator opted out (--no-telemetry): stay inert and
            # create NO families, so the exposition really is empty
            return None
        snap = reg.snapshot()
        lat = snap["latency_ms"]
        g = serving_latency_ms()
        for q in ("p50", "p90", "p99"):
            g.labels(q).set(lat[q])
        serving_queue_depth().set(snap["queue_depth_mean"])
        serving_queue_depth_max().set(snap["queue_depth_max"])
        serving_requests_total().set_total(snap["requests"])
        serving_batches_total().set_total(snap["batches"])
        serving_shed_total().set_total(snap["shed"])
        serving_rejected_total().set_total(snap["rejected"])
        serving_padded_waste_ratio().set(snap["padded_waste"])
        occ = serving_batch_occupancy()
        for rows, n in snap["occupancy"].items():
            occ.labels(rows).set(n)

    get_registry().register_collector(collect)
