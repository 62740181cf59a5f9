"""Model-serving CLI over HTTP (reference example/udfpredictor — model
serving behind Spark SQL UDFs — rebuilt on PredictionService, the
reference's thread-safe concurrent inference pool,
optim/PredictionService.scala:56-129).

    bigdl-tpu-serve --model trained.bigdl --port 8500

Protocol (stdlib-only on both ends):

* ``POST /predict`` with an ``.npy``-serialized array body →
  ``.npy``-serialized output array (``application/octet-stream``).
* ``POST /generate`` (with ``--generate MAX_NEW``) with a JSON body
  ``{"prompt": [token ids], "max_new_tokens": n, "eos_id": t}`` →
  ``{"tokens": [...]}`` — greedy continuation through the
  continuous-batching KV slot pool (``bigdl_tpu.serving.generation``):
  concurrent HTTP generations share decode iterations mid-flight
  instead of serializing.
* ``GET /healthz`` → ``{"status": "ok"}``, or **503**
  ``{"status": "draining"}`` once shutdown has begun — a load balancer
  keeps routing to a replica that answers 200, so a draining one must
  stop saying "ok" while it finishes its in-flight work.
* ``GET /metrics`` → Prometheus text exposition from the unified
  ``bigdl_tpu.telemetry`` registry: serving latency quantiles, queue
  depth, batch occupancy — plus every optimizer/checkpoint family (one
  scrape config covers training and serving roles; see
  docs/observability.md).
* ``GET /statusz`` / ``GET /tracez`` / ``POST /profilez`` — live
  introspection (status page, recent spans, on-demand time-boxed
  ``jax.profiler`` capture returning its logdir); see
  docs/observability.md "Health & introspection".

Client::

    buf = io.BytesIO(); np.save(buf, x)
    conn = http.client.HTTPConnection("localhost", 8500)
    conn.request("POST", "/predict", buf.getvalue())
    y = np.load(io.BytesIO(conn.getresponse().read()))
"""

from __future__ import annotations

import argparse
import json
import logging
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

logger = logging.getLogger("bigdl_tpu.serve")


class BatchedBytesFrontend:
    """Adapter giving a ``bigdl_tpu.serving.ModelServer`` the same
    ``predict_bytes`` surface as PredictionService: each request body is
    ONE npy-serialized sample (no batch axis), and concurrent HTTP
    threads coalesce into padded device batches via the dynamic
    batcher."""

    def __init__(self, server):
        self._server = server

    def predict_bytes(self, payload: bytes) -> bytes:
        from bigdl_tpu.optim.predictor import npy_call_bytes
        return npy_call_bytes(self._server.submit, payload)


class GenerateJsonFrontend:
    """JSON adapter for the continuous-batching generation engine: one
    request body in, the full greedy token row out.  ``max_new_cap``
    bounds the per-request decode budget a client may ask for."""

    def __init__(self, server, max_new_cap: int):
        self._server = server
        self.max_new_cap = int(max_new_cap)

    def generate_bytes(self, payload: bytes) -> bytes:
        doc = json.loads(payload.decode("utf-8"))
        prompt = doc["prompt"]
        max_new = int(doc.get("max_new_tokens", self.max_new_cap))
        if not (1 <= max_new <= self.max_new_cap):
            raise ValueError(
                f"max_new_tokens must be in [1, {self.max_new_cap}]")
        row = self._server.submit_generate(
            prompt, max_new, eos_id=doc.get("eos_id"))
        return json.dumps({"tokens": [int(t) for t in row]}).encode()


def make_server(service, host: str, port: int,
                statusz_fn=None, generate_frontend=None
                ) -> ThreadingHTTPServer:
    """ThreadingHTTPServer wired to a PredictionService; concurrency is
    bounded by the service's ticket pool, not the HTTP threads.  The
    returned server carries ``health_state`` (flip ``["draining"]`` to
    make ``/healthz`` answer 503) and ``debugz`` (the
    /statusz|/tracez|/profilez logic; its ``statusz_fn`` may be set
    after construction)."""
    from bigdl_tpu.telemetry.debugz import Debugz, DebugzHandlerMixin

    class Handler(DebugzHandlerMixin, BaseHTTPRequestHandler):
        def log_message(self, fmt, *fargs):
            logger.info("%s " + fmt, self.address_string(), *fargs)

        def _reply(self, code: int, body: bytes,
                   ctype: str = "application/octet-stream"):
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            if self.handle_debugz("GET"):
                return
            if self.path == "/healthz":
                if self.server.health_state.get("draining"):
                    # non-200: the LB must stop routing here while the
                    # in-flight batches finish
                    self._reply(503, json.dumps(
                        {"status": "draining"}).encode(),
                        "application/json")
                else:
                    self._reply(200,
                                json.dumps({"status": "ok"}).encode(),
                                "application/json")
            elif self.path == "/metrics":
                from bigdl_tpu.telemetry import prometheus_text
                self._reply(200, prometheus_text().encode(),
                            "text/plain; version=0.0.4; charset=utf-8")
            else:
                self._reply(404, b"not found", "text/plain")

        def do_POST(self):
            if self.handle_debugz("POST"):
                return
            if self.path == "/generate":
                if generate_frontend is None:
                    self._reply(404, json.dumps(
                        {"error": "generation not enabled; start with "
                                  "--generate MAX_NEW"}).encode(),
                        "application/json")
                    return
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    payload = self.rfile.read(n)
                    self._reply(200,
                                generate_frontend.generate_bytes(payload),
                                "application/json")
                except Exception as e:  # noqa: BLE001 — client-facing
                    self._reply(400, json.dumps(
                        {"error": f"{type(e).__name__}: {e}"}).encode(),
                        "application/json")
                return
            if self.path != "/predict":
                self._reply(404, b"not found", "text/plain")
                return
            try:
                n = int(self.headers.get("Content-Length", 0))
                payload = self.rfile.read(n)
                self._reply(200, service.predict_bytes(payload))
            except Exception as e:  # noqa: BLE001 — client-facing error
                self._reply(400, json.dumps(
                    {"error": f"{type(e).__name__}: {e}"}).encode(),
                    "application/json")

    server = ThreadingHTTPServer((host, port), Handler)
    server.health_state = {"draining": False}
    server.debugz = Debugz(statusz_fn=statusz_fn)
    return server


def main(argv=None):
    p = argparse.ArgumentParser(description="Serve a model over HTTP")
    p.add_argument("--model", required=True, help="bigdl-format model file")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8500)
    p.add_argument("--concurrency", type=int, default=4,
                   help="max in-flight predictions")
    p.add_argument("--dynamic-batch", type=int, default=None,
                   metavar="MAX_BATCH",
                   help="coalesce concurrent requests into padded "
                        "device batches (bigdl_tpu.serving); each POST "
                        "body is then ONE sample without a batch axis")
    p.add_argument("--batch-timeout-ms", type=float, default=5.0,
                   help="max wait before a partial batch is served "
                        "(only with --dynamic-batch)")
    p.add_argument("--generate", type=int, default=None, metavar="MAX_NEW",
                   help="enable POST /generate: continuous-batching "
                        "greedy decoding over the loaded model's KV "
                        "slot pool, at most MAX_NEW tokens per request "
                        "(the model must expose the incremental-decode "
                        "API, e.g. TransformerLM)")
    p.add_argument("--slots", type=int, default=8,
                   help="KV slot-pool width for --generate")
    p.add_argument("--fleet-dir", default=None, metavar="DIR",
                   help="publish this replica's health snapshot into "
                        "DIR via the fleet file transport so a serving-"
                        "fabric Router (bigdl_tpu.serving.router) can "
                        "route to / drain this process; the snapshot "
                        "carries the /healthz drain state")
    p.add_argument("--replica-id", type=int, default=0,
                   help="fleet snapshot id under --fleet-dir (one per "
                        "replica process)")
    p.add_argument("--no-telemetry", action="store_true",
                   help="disable the unified telemetry registry (the "
                        "/metrics endpoint then exposes an empty "
                        "catalog)")
    p.add_argument("-q", "--quiet", action="store_true")
    args = p.parse_args(argv)
    logging.basicConfig(
        level=logging.WARNING if args.quiet else logging.INFO)

    # serving enables telemetry by default: the scrape endpoint is the
    # reason this process exists to an SRE, and the serving hot path
    # only pays pull-time collection (docs/observability.md).  The flag
    # must actively disable — BIGDL_TPU_TELEMETRY=1 in the environment
    # enables at import, and skipping enable() would not undo that.
    from bigdl_tpu import telemetry
    if args.no_telemetry:
        # disable AND clear: BIGDL_TPU_TELEMETRY=1 enables at import,
        # which preregisters the catalog — without the clear, /metrics
        # would still expose every family at zero
        telemetry.disable()
        telemetry.get_registry().clear()
        telemetry.reset_spans()
    else:
        telemetry.enable()

    from bigdl_tpu.optim.predictor import PredictionService
    from bigdl_tpu.utils.serializer import load_module

    loaded = load_module(args.model)
    service = PredictionService(loaded, concurrency=args.concurrency)
    batcher = None
    if args.dynamic_batch is not None:
        # bucket_sizes rejects 0/negative rather than silently ignoring
        batcher = service.serve(max_batch=args.dynamic_batch,
                                batch_timeout_ms=args.batch_timeout_ms)
        service = BatchedBytesFrontend(batcher)
    gen_server = None
    gen_frontend = None
    if args.generate is not None:
        from bigdl_tpu.serving import ModelServer
        gen_server = ModelServer(generator=loaded, slots=args.slots)
        gen_frontend = GenerateJsonFrontend(gen_server, args.generate)
    server = make_server(service, args.host, args.port,
                         generate_frontend=gen_frontend)

    def _statusz():
        info = {"role": "server", "model": args.model,
                "dynamic_batch": args.dynamic_batch,
                "draining": server.health_state.get("draining", False)}
        if batcher is not None:
            info["queue_depth"] = batcher.queue_depth()
        if gen_server is not None:
            info["generation"] = gen_server.generation_stats()
        return info

    server.debugz.statusz_fn = _statusz
    publisher = None
    if args.fleet_dir:
        # the replica side of the serving fabric: drop a periodic
        # health snapshot (queue depth, slot occupancy, TTFT p99,
        # draining flag) for the router's registry — the same file a
        # Replica handle would write, so drain/deploy sees this
        # process exactly like an in-process replica
        from bigdl_tpu.serving.replica import (
            SnapshotPublisher, replica_snapshot,
        )
        from bigdl_tpu.telemetry.fleet import write_host_snapshot

        # incarnation stamp, taken once at process start: a restart
        # under the same --replica-id publishes a strictly larger
        # generation, so the registry can tell the new life's
        # snapshots from the dying publisher's final (draining) write
        # racing them — without it, that stale write masks the
        # restarted replica (ReplicaRegistry.poll rewarming)
        start_generation = int(time.time() * 1000)

        def _publish_snapshot():
            write_host_snapshot(args.fleet_dir, replica_snapshot(
                args.replica_id, gen_server or batcher,
                name=f"serve-{args.replica_id}", role="mixed",
                draining=bool(server.health_state.get("draining")),
                start_generation=start_generation))

        publisher = SnapshotPublisher(_publish_snapshot,
                                      interval_s=0.25)
    logger.info("serving on %s:%d", args.host, server.server_port)
    # SIGTERM (the orchestrator's stop notice) takes the same graceful
    # path as Ctrl-C: unwind serve_forever, then drain the batcher so
    # in-flight batched requests complete before the process exits
    # (mirrors the training loop's preemption handling)
    import signal

    def _sigterm(signum, frame):
        logger.info("signal %d: shutting down, draining in-flight "
                    "requests", signum)
        raise KeyboardInterrupt

    try:
        prev_term = signal.signal(signal.SIGTERM, _sigterm)
    except ValueError:  # non-main thread (tests): keep default handling
        prev_term = None
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        # shutdown has begun: from here /healthz answers 503 draining,
        # so the load balancer stops routing to this replica while the
        # already-admitted requests finish
        server.health_state["draining"] = True
        if publisher is not None:
            # the router registry must see draining:true BEFORE the
            # drain starts, not one publish interval into it
            publisher.publish_now()
        if batcher is not None or gen_server is not None:
            # keep answering HTTP (now-503 health checks, in-flight
            # predicts/generates) on a background accept loop while the
            # batcher and the slot pool drain: the documented drain
            # answers every queued request — and finishes every
            # mid-decode generation — before the scheduler threads exit
            import threading

            t = threading.Thread(target=server.serve_forever,
                                 daemon=True, name="bigdl-serve-drain")
            t.start()
            if batcher is not None:
                batcher.shutdown(drain=True)
            if gen_server is not None:
                gen_server.shutdown(drain=True)
            server.shutdown()
            t.join(timeout=10.0)
        server.server_close()
        if publisher is not None:
            # the draining state was already published when the flag
            # flipped; on exit the snapshot is REMOVED so the registry
            # forgets this replica instead of reporting a dead ghost
            # as stale forever
            publisher.stop(final_publish=False)
            from bigdl_tpu.telemetry.fleet import remove_host_snapshot
            remove_host_snapshot(args.fleet_dir, args.replica_id)
        if prev_term is not None:
            signal.signal(signal.SIGTERM, prev_term)
    return server


def cli():
    """Console entry, the owner of its process: turns the compile cache
    on, and discards main()'s return value so the generated script
    exits 0 (sys.exit(<object>) would exit 1)."""
    from bigdl_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()


if __name__ == "__main__":
    cli()
