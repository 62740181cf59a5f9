"""bigdl_tpu.telemetry: primitives, labels, tracing, exposition, the
serving bridge, thread-safety under fire, and the optimizer/chaos
integration the subsystem exists for — plus the satellite regressions
(utils/logger.log_file level, optim/profiling._timed restore).
"""

import io
import json
import logging
import re
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from bigdl_tpu import nn, telemetry
from bigdl_tpu.telemetry import families, tracing
from bigdl_tpu.telemetry.export import (
    PeriodicExporter, json_snapshot, prometheus_text,
)
from bigdl_tpu.telemetry.metrics import (
    Counter, Gauge, Histogram, TelemetryRegistry, get_registry,
)


@pytest.fixture(autouse=True)
def _telemetry_on():
    """Each test starts enabled with zeroed metrics/spans and leaves
    the process disabled (the repo-wide default other tests assume)."""
    telemetry.enable()
    telemetry.reset()
    yield
    telemetry.reset()
    telemetry.disable()


# --------------------------------------------------------------------------
# primitives
# --------------------------------------------------------------------------

class TestPrimitives:
    def test_counter_semantics(self):
        r = TelemetryRegistry()
        c = r.counter("requests_total", "help text")
        c.inc()
        c.inc(3)
        assert c.value() == 4
        with pytest.raises(ValueError, match="only go up"):
            c.inc(-1)

    def test_gauge_semantics(self):
        r = TelemetryRegistry()
        g = r.gauge("depth")
        g.set(7)
        g.inc()
        g.dec(3)
        assert g.value() == 5

    def test_histogram_buckets_sum_count(self):
        r = TelemetryRegistry()
        h = r.histogram("lat_seconds", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 0.5, 5.0, 50.0):
            h.observe(v)
        snap = h.snapshot()
        # +Inf bucket is appended automatically
        assert snap["buckets"] == [0.1, 1.0, 10.0, float("inf")]
        assert snap["counts"] == [1, 2, 1, 1]
        assert snap["count"] == 5
        assert snap["sum"] == pytest.approx(56.05)

    def test_get_or_create_is_idempotent_and_type_checked(self):
        r = TelemetryRegistry()
        c1 = r.counter("a_total")
        assert r.counter("a_total") is c1
        with pytest.raises(ValueError, match="already registered"):
            r.gauge("a_total")
        r.histogram("h_seconds")
        with pytest.raises(ValueError, match="already registered"):
            r.counter("h_seconds")

    def test_label_cardinality_enforced(self):
        r = TelemetryRegistry()
        c = r.counter("by_kind_total", labelnames=("kind",))
        c.labels("a").inc()
        c.labels("a").inc()
        c.labels("b").inc(5)
        assert c.labels("a").value() == 2
        assert c.labels("b").value() == 5
        with pytest.raises(ValueError, match="label value"):
            c.labels("a", "extra")
        with pytest.raises(ValueError, match=r"\.labels"):
            c.inc()  # labeled metric needs .labels() first
        with pytest.raises(ValueError, match="labels"):
            r.counter("by_kind_total", labelnames=("other",))

    def test_reset_zeroes_in_place_and_handles_stay_valid(self):
        r = TelemetryRegistry()
        c = r.counter("n_total")
        h = r.histogram("t_seconds")
        c.inc(9)
        h.observe(1.0)
        r.reset()
        assert c.value() == 0
        assert h.snapshot()["count"] == 0
        c.inc()  # the pre-reset handle still writes into the registry
        assert r.counter("n_total").value() == 1


# --------------------------------------------------------------------------
# tracing
# --------------------------------------------------------------------------

class TestTracing:
    def test_nesting_parent_child(self):
        with tracing.span("outer") as outer_id:
            with tracing.span("inner") as inner_id:
                assert tracing.current_span() == inner_id
            assert tracing.current_span() == outer_id
        spans = {s.name: s for s in tracing.finished_spans()}
        assert spans["inner"].parent_id == outer_id
        assert spans["outer"].parent_id is None
        assert spans["inner"].t_start >= spans["outer"].t_start
        assert spans["inner"].t_end <= spans["outer"].t_end

    def test_propagation_across_threads(self):
        token = {}

        def worker():
            with tracing.propagate(token["parent"]):
                with tracing.span("child_in_worker"):
                    pass

        with tracing.span("parent_span") as pid:
            token["parent"] = tracing.current_span()
            t = threading.Thread(target=worker)
            t.start()
            t.join()
        spans = {s.name: s for s in tracing.finished_spans()}
        assert spans["child_in_worker"].parent_id == pid
        assert spans["child_in_worker"].thread != spans["parent_span"].thread

    def test_disabled_span_is_noop(self):
        telemetry.disable()
        with tracing.span("invisible") as sid:
            assert sid is None
        assert tracing.finished_spans() == []
        telemetry.enable()

    def test_disabled_span_still_nests_a_profiler_annotation(
            self, monkeypatch):
        """Two sinks: the ring is behind ``telemetry.enabled()``, the
        ``jax.profiler`` annotation is not — a running profiler session
        is what makes a span appear in a device trace."""
        log = []

        class Counting:
            def __init__(self, name, **args):
                self.name, self.args = name, args

            def __enter__(self):
                log.append(("enter", self.name, self.args))

            def __exit__(self, *exc):
                log.append(("exit", self.name, self.args))
                return False

        monkeypatch.setattr(tracing, "_annotation", Counting)
        telemetry.disable()
        with tracing.span("outer", a=1) as outer:
            with tracing.span("inner") as inner:
                assert outer is None and inner is None
        assert tracing.finished_spans() == []
        assert log == [("enter", "outer", {"a": 1}), ("enter", "inner", {}),
                       ("exit", "inner", {}), ("exit", "outer", {"a": 1})]
        # retroactive spans are ring-only: no annotation, on or off
        assert tracing.record_span("late", 1.0, 2.0) is None
        telemetry.enable()
        assert tracing.record_span("late", 1.0, 2.0) is not None
        assert len(log) == 4
        # enabled: both sinks, the annotation outermost
        with tracing.span("both", b=2):
            assert log[-1] == ("enter", "both", {"b": 2})
        assert [s.name for s in tracing.finished_spans()] == ["late", "both"]
        assert log[-1] == ("exit", "both", {"b": 2})

    def test_span_annotation_is_the_profilers_and_resolved_lazily(self):
        import jax
        tracing._annotation = None
        with tracing.span("resolves"):
            pass
        assert tracing._annotation is jax.profiler.TraceAnnotation
        # an exception passes through both sinks and still records
        with pytest.raises(KeyError):
            with tracing.span("raises"):
                raise KeyError("x")
        assert [s.name for s in tracing.finished_spans()] == [
            "resolves", "raises"]

    def test_record_span_retroactive(self):
        t0 = time.perf_counter()
        sid = tracing.record_span("retro", t0 - 1.0, t0, note="x")
        (s,) = tracing.finished_spans()
        assert s.span_id == sid and s.name == "retro"
        assert s.duration_s == pytest.approx(1.0)
        assert s.args == {"note": "x"}

    def test_ring_buffer_bounded(self):
        tracing.set_ring_capacity(8)
        try:
            for i in range(20):
                with tracing.span("s"):
                    pass
            assert len(tracing.finished_spans()) == 8
            assert tracing.dropped_spans() == 12
        finally:
            tracing.reset_spans()
            tracing.set_ring_capacity(16384)

    def test_chrome_trace_json_roundtrip(self):
        with tracing.span("alpha", foo=1):
            with tracing.span("beta"):
                pass
        trace = json.loads(json.dumps(tracing.chrome_trace()))
        events = trace["traceEvents"]
        assert {e["name"] for e in events} == {"alpha", "beta"}
        for e in events:
            for key in ("ph", "name", "cat", "ts", "dur", "pid", "tid",
                        "args"):
                assert key in e
            assert e["ph"] == "X" and e["dur"] >= 0
        beta = next(e for e in events if e["name"] == "beta")
        alpha = next(e for e in events if e["name"] == "alpha")
        assert beta["args"]["parent_id"] == alpha["args"]["span_id"]
        assert alpha["args"]["foo"] == 1

    def test_write_chrome_trace_file(self, tmp_path):
        with tracing.span("disk"):
            pass
        p = tracing.write_chrome_trace(str(tmp_path / "trace.json"))
        with open(p) as f:
            data = json.load(f)
        assert data["traceEvents"][0]["name"] == "disk"


# --------------------------------------------------------------------------
# exposition
# --------------------------------------------------------------------------

_PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? "
    r"(\+Inf|-Inf|NaN|[0-9eE.+-]+)$")


class TestExposition:
    def test_prometheus_text_parses(self):
        r = TelemetryRegistry()
        r.counter("a_total", "with \"quotes\" and\nnewline").inc(2)
        r.gauge("g", labelnames=("k",)).labels('va"l').set(1.5)
        h = r.histogram("h_seconds", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(5.0)
        text = prometheus_text(r)
        for line in text.strip().splitlines():
            if line.startswith("#"):
                assert line.startswith(("# HELP ", "# TYPE "))
            else:
                assert _PROM_LINE.match(line), line
        # histogram: cumulative buckets, +Inf present, count/sum lines
        assert 'h_seconds_bucket{le="0.1"} 1' in text
        assert 'h_seconds_bucket{le="1"} 1' in text
        assert 'h_seconds_bucket{le="+Inf"} 2' in text
        assert "h_seconds_count 2" in text
        assert "a_total 2" in text

    def test_histogram_bucket_counts_monotone(self):
        r = TelemetryRegistry()
        h = r.histogram("m_seconds")
        rng = np.random.default_rng(0)
        for v in rng.exponential(0.3, size=200):
            h.observe(float(v))
        cums = [int(line.rsplit(" ", 1)[1])
                for line in prometheus_text(r).splitlines()
                if line.startswith("m_seconds_bucket")]
        assert cums == sorted(cums) and cums[-1] == 200

    def test_json_snapshot_shape(self):
        families.optimizer_retries_total().inc()
        families.optimizer_step_seconds().observe(0.01)
        with tracing.span("snap_span"):
            pass
        text = json.dumps(json_snapshot())
        # strict RFC-8259: the +Inf histogram bound must never leak as
        # the bare `Infinity` token (jq / JSON.parse reject the file)
        assert "Infinity" not in text
        snap = json.loads(text)
        m = snap["metrics"]["optimizer_retries_total"]
        assert m["kind"] == "counter"
        assert m["values"][0]["value"] == 1
        hist = snap["metrics"]["optimizer_step_seconds"]["values"][0]
        assert hist["value"]["buckets"][-1] == "+Inf"
        assert snap["spans"]["by_name"]["snap_span"]["count"] == 1

    def test_disabled_bridge_stays_inert(self):
        # --no-telemetry contract: with the switch off, a live serving
        # registry must not materialize serving_* families on scrape
        from bigdl_tpu.serving.metrics import MetricsRegistry
        fresh = TelemetryRegistry()
        import bigdl_tpu.telemetry.metrics as tmetrics
        orig = tmetrics._REGISTRY
        tmetrics._REGISTRY = fresh
        try:
            sreg = MetricsRegistry()
            sreg.record_batch(n_real=1, bucket=1, queue_depth=0,
                              latencies_s=[0.01])
            telemetry.disable()
            assert prometheus_text(fresh).strip() == ""
            telemetry.enable()
            assert "serving_requests_total 1" in prometheus_text(fresh)
        finally:
            tmetrics._REGISTRY = orig

    def test_serving_bridge_lands_in_unified_registry(self):
        from bigdl_tpu.serving.metrics import MetricsRegistry
        reg = MetricsRegistry()
        reg.record_batch(n_real=3, bucket=4, queue_depth=2,
                         latencies_s=[0.01, 0.02, 0.03])
        reg.record_shed()
        text = prometheus_text()
        assert re.search(r'serving_latency_ms\{quantile="p50"\} [0-9.]+',
                         text)
        assert "serving_requests_total 3" in text
        assert "serving_batches_total 1" in text
        assert "serving_shed_total 1" in text
        assert 'serving_batch_occupancy{rows="3"} 1' in text
        # the serving registry's own public schema is unchanged
        snap = reg.snapshot()
        assert set(snap) >= {"requests", "batches", "latency_ms",
                             "occupancy", "queue_depth_mean"}

    def test_dead_serving_registry_retires_its_collector(self):
        import gc
        from bigdl_tpu.serving.metrics import MetricsRegistry
        reg = get_registry()
        gc.collect()
        reg.run_collectors()  # purge corpses left by earlier tests
        before = len(reg._collectors)
        sreg = MetricsRegistry()
        assert len(reg._collectors) == before + 1
        del sreg
        gc.collect()
        reg.run_collectors()  # dead weakref -> collector unregisters
        assert len(reg._collectors) == before

    def test_preregistered_catalog_in_fresh_exposition(self):
        # enable() preregisters: a process that never trained still
        # exposes the optimizer/checkpoint families (at zero) — the
        # acceptance contract for one scrape config across roles
        text = prometheus_text()
        for fam in ("optimizer_step_seconds", "optimizer_retries_total",
                    "checkpoint_commit_seconds", "prefetch_queue_depth",
                    "serving_latency_ms"):
            assert f"# TYPE {fam} " in text

    def test_periodic_exporter_writes_and_stops_clean(self, tmp_path):
        families.prefetch_queue_depth().set(4)
        path = str(tmp_path / "telemetry.json")
        exp = PeriodicExporter(interval_s=0.05, path=path)
        exp.start()
        time.sleep(0.2)
        exp.stop(timeout=5.0)
        assert exp.exports >= 2 and exp.errors == 0
        with open(path) as f:
            data = json.load(f)
        vals = data["metrics"]["prefetch_queue_depth"]["values"]
        assert vals[0]["value"] == 4
        # stopped: no further exports
        n = exp.exports
        time.sleep(0.15)
        assert exp.exports == n

    def test_periodic_exporter_survives_raising_callback(self):
        """A callback raising mid-cycle must not kill the daemon: the
        failure is counted, later cycles still export, and stop() still
        runs its clean final export."""
        calls = []
        stop_seen = threading.Event()

        def fn(snap):
            calls.append(snap)
            if len(calls) == 1:
                raise RuntimeError("exporter backend down")
            stop_seen.set()

        exp = PeriodicExporter(interval_s=0.03, fn=fn)
        exp.start()
        assert stop_seen.wait(5.0), "daemon died after the first error"
        n_before_stop = len(calls)
        exp.stop(timeout=5.0)
        assert exp.errors == 1
        assert exp.exports >= 1
        # the clean final export on stop() ran (one more callback at
        # minimum beyond what the interval loop had already done)
        assert len(calls) >= n_before_stop + 1
        assert exp.exports + exp.errors == len(calls)
        # fully stopped: no further callbacks
        n = len(calls)
        time.sleep(0.1)
        assert len(calls) == n

    def test_telemetry_summary_tensorboard_roundtrip(self, tmp_path):
        from bigdl_tpu.visualization import TelemetrySummary
        families.optimizer_retries_total().inc(3)
        families.optimizer_step_seconds().observe(0.2)
        ts = TelemetrySummary(str(tmp_path), "app")
        ts.publish(step=1)
        vals = ts.read_scalar("telemetry/optimizer_retries_total")
        assert vals == [(1, 3.0)]
        ts.close()

    def test_runtime_sampling(self):
        from bigdl_tpu.telemetry.runtime import sample_runtime
        sample_runtime()
        assert families.process_rss_bytes().value() > 1 << 20
        # gc counters exist with per-generation labels
        text = prometheus_text()
        assert 'gc_collections_total{generation="0"}' in text


# --------------------------------------------------------------------------
# thread-safety under fire
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n_writers", [8])
def test_stress_writers_vs_concurrent_snapshots(n_writers):
    """Writers hammer counters/histograms while snapshot/export run
    concurrently: totals must come out exact, and no reader may crash
    on a half-updated structure."""
    c = families.optimizer_retries_total()
    h = families.optimizer_step_seconds()
    per_thread = 2000
    stop_readers = threading.Event()
    reader_errors = []

    def write():
        for i in range(per_thread):
            c.inc()
            h.observe(0.001 * (i % 7))
            if i % 64 == 0:
                with tracing.span("stress"):
                    pass

    def read():
        while not stop_readers.is_set():
            try:
                prometheus_text()
                json_snapshot()
                get_registry().snapshot()
            except Exception as e:  # pragma: no cover - the assertion
                reader_errors.append(e)
                return

    readers = [threading.Thread(target=read) for _ in range(2)]
    writers = [threading.Thread(target=write) for _ in range(n_writers)]
    [t.start() for t in readers + writers]
    [t.join() for t in writers]
    stop_readers.set()
    [t.join(5.0) for t in readers]
    assert not reader_errors
    assert c.value() == n_writers * per_thread
    assert h.snapshot()["count"] == n_writers * per_thread


# --------------------------------------------------------------------------
# optimizer integration (the tentpole's acceptance scenario)
# --------------------------------------------------------------------------

def _samples(n=32, dim=6, classes=4, seed=0):
    from bigdl_tpu.dataset.dataset import Sample
    rng = np.random.default_rng(seed)
    return [Sample(rng.normal(size=(dim,)).astype(np.float32),
                   int(rng.integers(1, classes + 1))) for _ in range(n)]


def _model(dim=6, classes=4):
    return nn.Sequential(nn.Linear(dim, 8), nn.ReLU(),
                         nn.Linear(8, classes), nn.LogSoftMax())


def _dataset(samples, batch=16):
    from bigdl_tpu.dataset import DataSet, SampleToMiniBatch
    return DataSet.array(samples).transform(SampleToMiniBatch(batch))


def test_optimizer_populates_step_phase_histograms(tmp_path):
    from bigdl_tpu.optim import Optimizer, Trigger
    from bigdl_tpu.optim.validation import Top1Accuracy
    samples = _samples()
    opt = (Optimizer(_model(), _dataset(samples), nn.ClassNLLCriterion())
           .set_end_when(Trigger.max_epoch(2))
           .set_validation(Trigger.every_epoch(), _dataset(samples),
                           [Top1Accuracy()])
           .set_checkpoint(str(tmp_path / "ck"), Trigger.every_epoch()))
    opt.optimize()
    # 2 epochs x 2 batches: every phase histogram saw real observations
    assert families.optimizer_step_seconds().snapshot()["count"] == 4
    assert families.optimizer_data_wait_seconds().snapshot()["count"] == 4
    assert families.optimizer_validation_seconds().snapshot()["count"] == 2
    assert families.checkpoint_commit_seconds().snapshot()["count"] == 2
    names = {s.name for s in tracing.finished_spans()}
    assert {"optimizer/step", "optimizer/data_wait",
            "optimizer/validation", "checkpoint/commit"} <= names
    # single timeline: every span (record_span'd from the loop AND
    # span()'d from validation/checkpoint) must share one clock — a
    # time.time() stamp leaking into the perf_counter trace would land
    # ~an epoch away
    ts = [e["ts"] for e in tracing.chrome_trace()["traceEvents"]]
    assert max(ts) - min(ts) < 600e6  # all within 10 minutes


def test_chaos_run_retry_counter_matches_faults_and_trace_breakdown(
        tmp_path):
    """The ISSUE acceptance scenario: a chaos-enabled optimize() whose
    Chrome trace shows the data-wait/step/validation/checkpoint
    breakdown and whose retry counter equals the injected fault
    count."""
    from bigdl_tpu.optim import Optimizer, Trigger
    from bigdl_tpu.optim.validation import Top1Accuracy
    from bigdl_tpu.utils import chaos
    chaos.reset()
    ctrl = chaos.install(fail_at_step=3)
    try:
        samples = _samples()
        opt = (Optimizer(_model(), _dataset(samples),
                         nn.ClassNLLCriterion())
               .set_end_when(Trigger.max_epoch(3))
               .set_validation(Trigger.every_epoch(), _dataset(samples),
                               [Top1Accuracy()])
               .set_checkpoint(str(tmp_path / "ck"),
                               Trigger.every_epoch(), keep_n=3)
               .set_failure_retry(2, interval_s=300, backoff_s=0.01,
                                  backoff_cap_s=0.02))
        opt.optimize()
    finally:
        chaos.reset()
    injected = sum("injected failure" in e for e in ctrl.events)
    assert injected == 1
    assert families.chaos_faults_injected_total().value() == injected
    assert families.optimizer_retries_total().value() == injected
    trace = json.loads(json.dumps(tracing.chrome_trace()))
    by_name = {}
    for e in trace["traceEvents"]:
        by_name.setdefault(e["name"], []).append(e)
    for phase in ("optimizer/data_wait", "optimizer/step",
                  "optimizer/validation", "checkpoint/commit"):
        assert by_name.get(phase), f"missing {phase} spans"
    # the step spans carry the data-wait attribution for the breakdown
    assert all("data_wait_s" in e["args"]
               for e in by_name["optimizer/step"])


def test_prefetch_gauge_and_wait_counters():
    from bigdl_tpu.dataset.prefetch import Prefetch

    out = []
    depths = []
    gauge = families.prefetch_queue_depth()
    # slow consumer: the producer races ahead, fills the n_ahead=2
    # queue, and must wait — the signature of a healthy pipeline
    for item in Prefetch(n_ahead=2).apply(iter(range(6))):
        time.sleep(0.05)
        depths.append(gauge.value())
        out.append(item)
    assert out == list(range(6))
    assert families.prefetch_producer_wait_total().value() >= 1
    assert max(depths) >= 1  # ready batches were buffered ahead


def test_serving_spans_and_http_metrics_endpoint():
    """curl-level acceptance: /metrics under --dynamic-batch load
    returns Prometheus text with serving quantiles, queue depth, AND
    optimizer/checkpoint families from the same registry."""
    import http.client
    from bigdl_tpu.examples.serve import BatchedBytesFrontend, make_server
    from bigdl_tpu.serving import ModelServer

    model = _model(dim=4, classes=3)
    mserver = ModelServer(model, max_batch=4, batch_timeout_ms=50.0)
    httpd = make_server(BatchedBytesFrontend(mserver), "127.0.0.1", 0)
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        port = httpd.server_port
        rng = np.random.default_rng(3)
        xs = [rng.normal(size=(4,)).astype(np.float32) for _ in range(6)]

        def post(x):
            buf = io.BytesIO()
            np.save(buf, x, allow_pickle=False)
            conn = http.client.HTTPConnection("127.0.0.1", port,
                                              timeout=30)
            conn.request("POST", "/predict", buf.getvalue())
            np.load(io.BytesIO(conn.getresponse().read()),
                    allow_pickle=False)
            conn.close()

        threads = [threading.Thread(target=post, args=(x,)) for x in xs]
        [th.start() for th in threads]
        [th.join() for th in threads]

        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        conn.request("GET", "/metrics")
        resp = conn.getresponse()
        body = resp.read().decode()
        assert resp.status == 200
        conn.close()
    finally:
        httpd.shutdown()
        httpd.server_close()
        mserver.shutdown()
    assert "serving_requests_total 6" in body
    assert re.search(r'serving_latency_ms\{quantile="p99"\} [0-9.]+',
                     body)
    assert "serving_queue_depth" in body
    # optimizer + checkpoint families in the SAME exposition
    assert "# TYPE optimizer_step_seconds histogram" in body
    assert "# TYPE checkpoint_commit_seconds histogram" in body
    # request-path spans were recorded
    names = {s.name for s in tracing.finished_spans()}
    assert {"serving/enqueue", "serving/batch", "serving/execute",
            "serving/reply"} <= names


ENGINE_SPANS = ["serving/iteration", "serving/idle", "serving/admit",
                "serving/prefill", "serving/decode_dispatch",
                "serving/readback", "serving/emit", "serving/submit"]


@pytest.mark.parametrize("name", ENGINE_SPANS)
def test_engine_span_is_a_literal_site_in_the_catalog(name):
    """Every span of an engine pass is written as a literal at its site
    (so the metrics-catalog pass sees it) and listed in the span
    inventory of docs/observability.md."""
    import os
    from bigdl_tpu.analysis.astutil import load_tree
    from bigdl_tpu.analysis.passes import metrics_catalog
    tree = load_tree()
    _, spans = metrics_catalog.collect(tree)
    sites = [s for s in spans if s.name == name]
    assert sites and all(s.kind == "span" for s in sites), sites
    where = {s.file for s in sites}
    assert where == ({"bigdl_tpu/serving/server.py"}
                     if name == "serving/submit"
                     else {"bigdl_tpu/serving/generation.py"})
    inventory = metrics_catalog.span_inventory(
        os.path.join(tree.repo, "docs", "observability.md"))
    assert name in inventory


def test_metrics_lint_passes_on_this_tree():
    proc = subprocess.run(
        [sys.executable, "scripts/metrics_lint.py"],
        capture_output=True, text=True,
        cwd=__import__("os").path.dirname(__import__("os").path.dirname(
            __import__("os").path.abspath(__file__))))
    assert proc.returncode == 0, proc.stdout + proc.stderr


# --------------------------------------------------------------------------
# satellite regressions
# --------------------------------------------------------------------------

def test_log_file_captures_info_records(tmp_path):
    """utils/logger.log_file: the bigdl_tpu logger defaulted to the
    root WARNING level, so INFO framework records never reached the
    file — the handler must come with an INFO logger level."""
    from bigdl_tpu.utils.logger import log_file
    path = str(tmp_path / "bigdl.log")
    lg = logging.getLogger("bigdl_tpu")
    prev_level = lg.level
    try:
        lg.setLevel(logging.NOTSET)  # the broken default
        log_file(path)
        logging.getLogger("bigdl_tpu.optim").info("iteration 1 done")
        for h in lg.handlers:
            h.flush()
        with open(path) as f:
            content = f.read()
        assert "iteration 1 done" in content
    finally:
        from bigdl_tpu.utils.logger import _drop_ours
        _drop_ours(lg, path)
        lg.setLevel(prev_level)


def test_log_file_does_not_lower_debug_level(tmp_path):
    from bigdl_tpu.utils.logger import _drop_ours, log_file
    path = str(tmp_path / "bigdl2.log")
    lg = logging.getLogger("bigdl_tpu")
    prev_level = lg.level
    try:
        lg.setLevel(logging.DEBUG)
        log_file(path)
        assert lg.level == logging.DEBUG  # opt-in verbosity kept
    finally:
        _drop_ours(lg, path)
        lg.setLevel(prev_level)


def test_timed_restores_preexisting_instance_forward():
    """optim/profiling._timed: restore must put back a pre-existing
    INSTANCE-level forward binding instead of deleting it (the old
    object.__delattr__ path destroyed user monkeypatches)."""
    from bigdl_tpu.optim.profiling import module_forward_times
    model = _model(dim=4, classes=3)
    lin = model[0]
    calls = []
    orig_forward = lin.forward

    def counting_forward(*a, **k):
        calls.append(1)
        return orig_forward(*a, **k)

    object.__setattr__(lin, "forward", counting_forward)
    x = np.zeros((2, 4), np.float32)
    records = module_forward_times(model, x)
    assert records  # timing ran
    # the instance-level binding survived the restore
    assert lin.__dict__.get("forward") is counting_forward
    n_before = len(calls)
    model.forward(x)
    assert len(calls) == n_before + 1
    # modules with NO prior instance forward got theirs cleanly removed
    assert "forward" not in model[2].__dict__


def test_module_forward_times_routes_into_telemetry():
    from bigdl_tpu.optim.profiling import module_forward_times
    model = _model(dim=4, classes=3)
    module_forward_times(model, np.zeros((2, 4), np.float32))
    hist = families.module_forward_seconds()
    assert hist.labels("Linear").snapshot()["count"] == 2
    assert hist.labels("ReLU").snapshot()["count"] == 1
