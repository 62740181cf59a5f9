"""ModelServer: the serving frontend.

Wires admission control → batch scheduler → a compiled backend into one
object with the reference ``PredictionService`` surface (submit a
sample, get a result) plus the pieces a TPU deployment needs around it:
bucket warmup (pre-compile every batch shape at startup, so the first
user request never pays an XLA compile), metrics, and drain-on-shutdown.

Backends — anything that can run a padded batch:

* a :class:`~bigdl_tpu.core.module.Module` (including ``quantize``-d
  int8 models): cloned to eval mode and jit-compiled, one executable
  shared across all buckets' shapes via the XLA compile cache;
* a :class:`~bigdl_tpu.optim.predictor.PredictionService`: reuses its
  ticketed thread-safe ``predict`` (useful to put one dynamic batcher in
  front of an existing service);
* any callable ``f(batched_input) -> batched_output``.
"""

from __future__ import annotations

import logging
import threading
import time
from concurrent.futures import Future
from typing import Callable, List, Optional, Sequence, Union

import numpy as np

from bigdl_tpu.serving.admission import (
    BoundedRequestQueue, QueueFullError, Request, ServerClosedError,
)
from bigdl_tpu.serving.batching import bucket_sizes
from bigdl_tpu.serving.metrics import MetricsRegistry
from bigdl_tpu.serving.scheduler import BatchScheduler
from bigdl_tpu.telemetry import tracing

__all__ = ["ModelServer", "install_shutdown_signals"]

logger = logging.getLogger(__name__)


def install_shutdown_signals(server: "ModelServer",
                             signals: Optional[Sequence[int]] = None):
    """SIGTERM/SIGINT → graceful drain (mirrors the optimizer's
    preemption handling): the handler raises KeyboardInterrupt in the
    main thread so blocking loops (stdin reads, ``serve_forever``)
    unwind into the caller's ``shutdown(drain=True)`` path — every
    already-admitted request is still served before exit, instead of
    dying with futures in flight.  (The handler deliberately does NOT
    flip the server's shutdown state itself: ``shutdown()`` is
    idempotent-guarded, and pre-marking it would turn the caller's real
    drain call into a no-op.)

    Returns a ``restore()`` callable reinstating the previous handlers.
    No-op (returns a dummy restore) off the main thread, where
    ``signal.signal`` is illegal."""
    import signal as _signal
    if threading.current_thread() is not threading.main_thread():
        return lambda: None
    sigs = tuple(signals) if signals is not None \
        else (_signal.SIGTERM, _signal.SIGINT)
    prev = {}

    def handler(signum, frame):
        # no queue-depth peek here: the handler runs in signal context
        # on the main thread, and taking the queue lock could deadlock
        # against an interrupted put() holding it
        logger.info("signal %s: unwinding to drain queued requests "
                    "before exit", signum)
        raise KeyboardInterrupt

    for s in sigs:
        try:
            prev[s] = _signal.signal(s, handler)
        except (ValueError, OSError):  # pragma: no cover - exotic host
            continue

    def restore():
        for s, h in prev.items():
            try:
                _signal.signal(s, h)
            except (ValueError, OSError):  # pragma: no cover
                pass
    return restore


def _module_backend(model) -> Callable:
    """The shared jit-compiled eval-mode forward, plus serving's own
    host conversion (tuple outputs, blocking device readback)."""
    import jax.numpy as jnp
    from bigdl_tpu.optim.predictor import jit_forward
    model, fn = jit_forward(model)

    def run(x):
        xs = (tuple(jnp.asarray(a) for a in x)
              if isinstance(x, (tuple, list)) else jnp.asarray(x))
        y = fn(model, xs)
        # block until the result is on host so recorded latency covers
        # the device round-trip, not just dispatch
        return (tuple(np.asarray(a) for a in y)
                if isinstance(y, (tuple, list)) else np.asarray(y))
    return run


def _resolve_backend(backend) -> Callable:
    from bigdl_tpu.core.module import Module
    from bigdl_tpu.optim.predictor import PredictionService
    if isinstance(backend, Module):
        return _module_backend(backend)
    if isinstance(backend, PredictionService):
        return backend.predict
    if callable(backend):
        return backend
    raise TypeError(f"cannot serve a {type(backend).__name__}: expected a "
                    "Module, PredictionService, or callable")


class ModelServer:
    """Dynamic-batching inference server.

    >>> server = ModelServer(model, max_batch=16, batch_timeout_ms=3.0)
    >>> server.warmup(np.zeros((784,), np.float32))
    >>> y = server.submit(x)                  # blocking, single sample
    >>> ys = server.submit_many(list_of_x)    # batch of blocking submits
    >>> server.shutdown()                     # drains the queue
    """

    def __init__(self, backend=None, max_batch: int = 32,
                 batch_timeout_ms: float = 5.0,
                 queue_capacity: Optional[int] = None,
                 admission: str = "block",
                 metrics: Optional[MetricsRegistry] = None,
                 generator=None, slots: int = 8,
                 gen_queue_capacity: Optional[int] = None):
        """``backend`` serves one-shot (single-forward) requests through
        the dynamic batcher; ``generator`` — an incremental-decode model
        (e.g. :class:`~bigdl_tpu.models.transformer_lm.TransformerLM`)
        or a pre-built :class:`GenerationScheduler` — serves multi-step
        generation requests through the continuous-batching slot pool
        (``slots`` wide).  Either may be omitted, not both."""
        if backend is None and generator is None:
            raise TypeError(
                "ModelServer needs a backend (one-shot inference), a "
                "generator (continuous-batching generation), or both")
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        # admitted-but-not-terminal one-shot requests; the generation
        # plane keeps its own count (GenerationScheduler) — together
        # they are admitted_outstanding(), the router's drain invariant
        self._outstanding_lock = threading.Lock()
        self._outstanding = 0
        self._run_batch = None
        self._scheduler = None
        self._queue = None
        self.buckets = ()
        self.max_batch = max_batch
        if backend is not None:
            self._run_batch = _resolve_backend(backend)
            self.buckets = bucket_sizes(max_batch)
            cap = (queue_capacity if queue_capacity is not None
                   else 8 * max_batch)
            self._queue = BoundedRequestQueue(
                cap, policy=admission, on_shed=self.metrics.record_shed)
            self._scheduler = BatchScheduler(
                self._queue, self._run_batch,
                self.buckets, batch_timeout_ms, metrics=self.metrics)
            self._scheduler.start()
        self.generation = None
        if generator is not None:
            try:
                from bigdl_tpu.serving.generation import (
                    GenerationScheduler,
                )
                if isinstance(generator, GenerationScheduler):
                    self.generation = generator
                else:
                    self.generation = GenerationScheduler(
                        generator, slots=slots,
                        queue_capacity=gen_queue_capacity,
                        admission=admission)
            except BaseException:
                # the one-shot scheduler thread is already running; a
                # failed generator wiring must not leak it (and its
                # queue) with no handle to shut it down
                if self._queue is not None:
                    self._queue.close(discard=True)
                if self._scheduler is not None:
                    self._scheduler.join(5.0)
                raise
        self._shutdown = False

    # ---- submission ------------------------------------------------------

    def submit_async(self, sample,
                     timeout: Optional[float] = None) -> Future:
        """Admit one sample (an array, or tuple of arrays, WITHOUT a
        batch axis) and return a Future of its output row.  Raises
        QueueFullError / ServerClosedError per the admission policy;
        ``timeout`` bounds the admission wait under the ``block``
        policy (otherwise a wedged backend + full queue would hang the
        submitter forever)."""
        if self._shutdown:
            raise ServerClosedError("server is shut down")
        if self._queue is None:
            raise RuntimeError(
                "this server has no one-shot backend (generation-only); "
                "use submit_generate / submit_generate_async")
        req = Request(sample)
        with self._outstanding_lock:
            self._outstanding += 1
        try:
            self._queue.put(req, timeout=timeout)
        except QueueFullError:
            with self._outstanding_lock:
                self._outstanding -= 1
            self.metrics.record_rejected()
            raise
        except BaseException:
            with self._outstanding_lock:
                self._outstanding -= 1
            raise
        req.future.add_done_callback(self._dec_outstanding)
        return req.future

    def _dec_outstanding(self, _fut) -> None:
        with self._outstanding_lock:
            self._outstanding -= 1

    def admitted_outstanding(self) -> int:
        """Admitted requests not yet terminal across BOTH planes
        (one-shot queued/dispatched + generation queued/prefilling/
        decoding).  A drained replica must reach exactly zero before
        teardown — the router's deploy asserts this instead of
        inferring zero-drop from request counters."""
        with self._outstanding_lock:
            n = self._outstanding
        if self.generation is not None:
            n += self.generation.admitted_outstanding()
        return n

    def submit(self, sample, timeout: Optional[float] = None):
        """Blocking single-sample inference (≙ PredictionService.predict,
        but coalesced with concurrent callers into one device batch).
        ``timeout`` covers admission AND the result wait."""
        deadline = (None if timeout is None
                    else time.perf_counter() + timeout)
        fut = self.submit_async(sample, timeout=timeout)
        remaining = (None if deadline is None
                     else max(deadline - time.perf_counter(), 0.0))
        return fut.result(remaining)

    def submit_many(self, samples: Sequence,
                    timeout: Optional[float] = None) -> List:
        """Submit a burst and wait for all results, preserving order.
        All samples are enqueued before the first wait, so a burst from
        one caller coalesces exactly like concurrent callers do."""
        futures = [self.submit_async(s) for s in samples]
        deadline = (None if timeout is None
                    else time.perf_counter() + timeout)
        out = []
        for f in futures:
            remaining = (None if deadline is None
                         else max(deadline - time.perf_counter(), 0.0))
            out.append(f.result(remaining))
        return out

    # ---- generation (continuous batching) --------------------------------

    def _gen(self):
        if self.generation is None:
            raise RuntimeError(
                "this server has no generation backend; construct with "
                "generator=<TransformerLM or GenerationScheduler>")
        if self._shutdown:
            raise ServerClosedError("server is shut down")
        return self.generation

    def submit_generate_async(self, prompt, max_new_tokens: int,
                              eos_id=None, on_token=None,
                              timeout: Optional[float] = None,
                              deadline=None, trace=None) -> Future:
        """Admit one prompt into the continuous-batching decode engine;
        returns a Future of the full ``[Tp + max_new_tokens]`` token row
        (greedy, bit-identical to a solo ``model.generate()``).  Unlike
        one-shot inference the request is MULTI-STEP: it occupies a KV
        slot for many decode iterations, and drain waits for every
        admitted request's last token.  ``deadline`` (a
        :class:`~bigdl_tpu.serving.reliability.Deadline`) propagates
        the caller's end-to-end budget into the engine; ``trace`` (a
        :class:`~bigdl_tpu.telemetry.request_trace.TraceContext`)
        carries the request's distributed-trace identity so the engine
        files its queue/prefill/decode spans under it."""
        # the caller's thread: validation, admission, enqueue
        with tracing.span("serving/submit"):
            return self._gen().submit_async(
                prompt, max_new_tokens, eos_id=eos_id, on_token=on_token,
                timeout=timeout, deadline=deadline, trace=trace)

    def cancel_generate(self, fut: Future) -> bool:
        """Best-effort cancel of a generation future — queued requests
        drop without a slot, slot-resident ones are evicted by the
        engine sweep (see :meth:`GenerationScheduler.cancel`)."""
        return self._gen().cancel(fut)

    # the replica plane duck-types targets on .cancel/.kill
    cancel = cancel_generate

    def kill(self, exc: Optional[Exception] = None) -> None:
        """Hard-kill the generation engine (no drain): in-flight
        requests fail typed so a router can fail them over."""
        if self.generation is not None:
            self.generation.kill(exc)

    def submit_generate(self, prompt, max_new_tokens: int, eos_id=None,
                        timeout: Optional[float] = None):
        """Blocking single-prompt generation (coalesced into the slot
        pool with concurrent callers).  ``timeout`` covers admission AND
        the full decode."""
        return self._gen().submit(prompt, max_new_tokens, eos_id=eos_id,
                                  timeout=timeout)

    def submit_generate_many(self, prompts: Sequence,
                             max_new_tokens, eos_id=None,
                             timeout: Optional[float] = None) -> List:
        """Submit a burst of prompts and wait for all rows, preserving
        order.  ``max_new_tokens`` may be one int (applied to every
        prompt) or a per-prompt sequence of equal length.  All prompts
        are enqueued before the first wait, so a burst fills the slot
        pool exactly like concurrent callers."""
        try:
            # operator.index: accepts int AND numpy integer scalars
            # (rng.integers budgets), rejects sequences
            import operator
            max_new_tokens = [operator.index(max_new_tokens)] \
                * len(prompts)
        except TypeError:
            max_new_tokens = list(max_new_tokens)
            if len(max_new_tokens) != len(prompts):
                raise ValueError(
                    f"{len(prompts)} prompts but "
                    f"{len(max_new_tokens)} max_new_tokens entries; "
                    f"pass one budget per prompt (or a single int)")
        futures = [self.submit_generate_async(p, m, eos_id=eos_id)
                   for p, m in zip(prompts, max_new_tokens)]
        deadline = (None if timeout is None
                    else time.perf_counter() + timeout)
        out = []
        for f in futures:
            remaining = (None if deadline is None
                         else max(deadline - time.perf_counter(), 0.0))
            out.append(f.result(remaining))
        return out

    def generation_queue_depth(self) -> int:
        return 0 if self.generation is None \
            else self.generation.queue_depth()

    def generation_stats(self):
        return None if self.generation is None \
            else self.generation.stats()

    # ---- lifecycle -------------------------------------------------------

    def warmup(self, example_sample) -> "ModelServer":
        """Pre-compile every bucket shape by running a zeros batch
        through the backend, largest first (the compile cache then holds
        all shapes before traffic arrives)."""
        if self._run_batch is None:
            raise RuntimeError("warmup needs a one-shot backend; the "
                               "generation engine compiles per bucket "
                               "on first use")
        ex = example_sample
        parts = (tuple(np.asarray(a) for a in ex)
                 if isinstance(ex, (tuple, list)) else (np.asarray(ex),))
        tuple_input = isinstance(ex, (tuple, list))
        t0 = time.perf_counter()
        for b in reversed(self.buckets):
            zeros = tuple(np.zeros((b,) + p.shape, p.dtype) for p in parts)
            self._run_batch(zeros if tuple_input else zeros[0])
        logger.info("warmup: compiled %d bucket shapes %s in %.2fs",
                    len(self.buckets), list(self.buckets),
                    time.perf_counter() - t0)
        return self

    def queue_depth(self) -> int:
        return 0 if self._queue is None else len(self._queue)

    def publish_metrics(self, summary, step: int = 0) -> None:
        """Export the metrics snapshot through a visualization Summary
        (see :class:`bigdl_tpu.visualization.ServingSummary`)."""
        self.metrics.publish(summary, step)

    def shutdown(self, drain: bool = True,
                 timeout: Optional[float] = 30.0) -> None:
        """Stop admitting requests.  With ``drain`` (default) every
        already-queued request is still served before the dispatch
        thread exits; otherwise queued requests fail with
        ServerClosedError.  Generation requests are multi-step: drain
        waits for every admitted request's LAST token, and even with
        ``drain=False`` a request already occupying a KV slot finishes
        (only still-queued ones are rejected) — a half-emitted
        generation is never silently dropped."""
        if self._shutdown:
            return
        self._shutdown = True
        if self._queue is not None:
            self._queue.close(discard=not drain)
        if self.generation is not None:
            self.generation.shutdown(drain=drain, timeout=timeout)
        if self._scheduler is not None:
            self._scheduler.join(timeout)
            if self._scheduler.alive:
                logger.warning(
                    "serving scheduler did not drain within %ss", timeout)

    def __enter__(self) -> "ModelServer":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown()
