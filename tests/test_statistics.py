"""Observability layer tests: histogram math, reporters, tracing, toggling,
per-subscriber error attribution, device budget, and the no-overhead guard.

Reference: modules/siddhi-core/src/test/java/.../managment/StatisticsTestCase
plus the engine-specific additions (siddhi_tpu/observability/)."""

import json
import re
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.observability.metrics import (
    EWMA,
    LatencyTracker,
    LogHistogram,
    ThroughputTracker,
)
from siddhi_tpu.observability.reporters import render_prometheus
from siddhi_tpu.observability.tracing import Tracer


# ---------------------------------------------------------------------------
# histogram percentile math
# ---------------------------------------------------------------------------


class TestLogHistogram:
    def test_quantiles_uniform(self):
        h = LogHistogram()
        for v in range(1, 10_001):  # 1..10000, uniform
            h.record(v)
        assert h.count == 10_000
        for q, expect in ((0.5, 5_000), (0.95, 9_500), (0.99, 9_900)):
            got = h.quantile(q)
            assert abs(got - expect) / expect < 0.05, (q, got)

    def test_quantiles_bimodal_tail(self):
        # 99% fast (~1k ns), 1% slow (~1M ns): p99 must see the slow mode —
        # the whole point of histograms over a mean (BENCH p99 motivation)
        h = LogHistogram()
        for _ in range(990):
            h.record(1_000)
        for _ in range(10):
            h.record(1_000_000)
        assert h.quantile(0.5) < 2_000
        assert h.quantile(0.999) > 900_000
        assert abs(h.mean - (990 * 1_000 + 10 * 1_000_000) / 1000) < 1e-6

    def test_exact_small_values_and_bounds(self):
        h = LogHistogram()
        h.record(0)
        h.record(7)
        h.record(63)
        assert h.min == 0 and h.max == 63 and h.count == 3
        assert h.quantile(0.0) == 0.0
        # one-pass multi-quantile agrees with single reads
        a = h.quantiles([0.1, 0.9])
        assert a == [h.quantile(0.1), h.quantile(0.9)]

    def test_relative_error_bound(self):
        h = LogHistogram()
        for v in (100, 10_000, 123_456_789, 10**12):
            h2 = LogHistogram()
            h2.record(v)
            got = h2.quantile(0.5)
            assert abs(got - v) / v < 1 / 16, (v, got)
        del h

    def test_ewma_decays_when_idle(self):
        e = EWMA(60.0, now=0.0)
        e.update(600, now=0.0)
        r1 = e.rate(now=5.0)  # one tick: 600 events over 5 s
        assert r1 == pytest.approx(120.0)
        r2 = e.rate(now=600.0)  # ten minutes idle: decayed hard
        assert r2 < r1 * 0.01


# ---------------------------------------------------------------------------
# latency tracker nesting semantics (the pre-histogram TLS-t0 bug)
# ---------------------------------------------------------------------------


class TestLatencyTrackerNesting:
    def test_nested_marks_record_both_spans(self):
        lt = LatencyTracker("t")
        lt.mark_in()
        time.sleep(0.02)
        lt.mark_in()  # nested: must NOT overwrite the outer mark
        time.sleep(0.002)
        lt.mark_out()  # closes the inner span (2 ms or more)
        time.sleep(0.02)
        lt.mark_out()  # closes the outer span (the inner's and 40 ms more)
        assert lt.samples == 2
        # outer strictly contains inner: a sleep never returns early, so the
        # two differ by the 40 ms round the inner span however long a busy
        # scheduler stretches either of them
        assert lt.hist.max - lt.hist.min >= 35_000_000
        assert lt.hist.min >= 1_000_000

    def test_stray_mark_out_is_ignored(self):
        lt = LatencyTracker("t")
        lt.mark_out()  # no open mark: must not record garbage
        assert lt.samples == 0
        lt.mark_in()
        lt.mark_out()
        lt.mark_out()  # second out with empty stack: still nothing
        assert lt.samples == 1

    def test_toggle_mid_span_never_records_garbage(self):
        # the gate decision is made at mark_in: disabling between a mark pair
        # must neither leak stack entries nor pair a stale t0 later
        class Gate:
            enabled = True

        g = Gate()
        lt = LatencyTracker("t", gate=g)
        lt.mark_in()
        g.enabled = False
        lt.mark_out()  # popped but not recorded (disabled at out)
        lt.mark_in()   # disabled: pushes a sentinel
        g.enabled = True
        lt.mark_out()  # pops the sentinel — records nothing
        assert lt.samples == 0
        lt.mark_in()
        lt.mark_out()
        assert lt.samples == 1
        assert lt.hist.max < 10**9  # no stale multi-second garbage sample

    def test_timed_context_manager(self):
        from siddhi_tpu.observability.metrics import timed

        lt = LatencyTracker("t")
        with timed(lt):
            pass
        with pytest.raises(ValueError):
            with timed(lt):  # exception-safe: mark_out still runs
                raise ValueError("x")
        assert lt.samples == 2
        with timed(None):  # None tracker is a no-op
            pass


# ---------------------------------------------------------------------------
# reporters: Prometheus text + JSON lines
# ---------------------------------------------------------------------------

_PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? [-+]?[0-9.eE+-]+$"
)


def _assert_prometheus_wellformed(text: str) -> dict:
    """Every non-comment line must parse; returns family -> sample count."""
    families: dict = {}
    typed = set()
    for line in text.strip().splitlines():
        if line.startswith("# TYPE "):
            typed.add(line.split()[2])
            continue
        if line.startswith("#"):
            continue
        assert _PROM_LINE.match(line), f"malformed exposition line: {line!r}"
        name = re.split(r"[{ ]", line, 1)[0]
        base = re.sub(r"_(sum|count)$", "", name)
        assert base in typed or name in typed, f"untyped family: {name}"
        families[base] = families.get(base, 0) + 1
    return families


class TestReporters:
    def test_prometheus_rendering_from_registry(self):
        from siddhi_tpu.observability.registry import StatisticsManager

        sm = StatisticsManager("App1", reporter="none")
        sm.throughput_tracker("stream.S").add(5)
        sm.latency_tracker("query.q").record_ns(1_500_000)
        sm.error_tracker("stream.S").add(1)
        sm.error_tracker("stream.S", subscriber="query.q").add(1)
        sm.device_time_tracker("query.q", "step").record_ns(2_000_000)
        sm.device_counter("stream.S", "h2d_bytes").add(4096)
        text = render_prometheus([sm.report()])
        fams = _assert_prometheus_wellformed(text)
        assert fams["siddhi_events_total"] == 1
        assert fams["siddhi_latency_ms"] >= 6  # 4 quantiles + sum + count
        assert 'subscriber="query.q"' in text
        assert "siddhi_device_time_ms" in fams
        assert "siddhi_h2d_bytes_total" in fams
        # label escaping never produces an unparseable line
        sm.throughput_tracker('we"ird\\n').add(1)
        _assert_prometheus_wellformed(render_prometheus([sm.report()]))

    def test_jsonl_reporter_writes_parseable_lines(self, tmp_path):
        from siddhi_tpu.observability.registry import StatisticsManager

        path = str(tmp_path / "m.jsonl")
        sm = StatisticsManager(
            "App1", reporter="jsonl", interval_s=0.05, options={"file": path}
        )
        sm.throughput_tracker("stream.S").add(3)
        sm.start_reporting()
        t0 = time.time()
        while time.time() - t0 < 5.0:
            with open(path) as f:
                lines = [ln for ln in f.read().splitlines() if ln]
            if len(lines) >= 2:
                break
            time.sleep(0.05)
        sm.stop_reporting()
        assert len(lines) >= 2
        for ln in lines:
            rep = json.loads(ln)
            assert rep["app"] == "App1"
            assert rep["throughput"]["stream.S"] == 3

    def test_custom_reporter_spi(self):
        from siddhi_tpu.observability.registry import StatisticsManager
        from siddhi_tpu.observability.reporters import (
            Reporter,
            register_reporter,
        )

        got = []

        class Capture(Reporter):
            def emit(self, report):
                got.append(report)

        register_reporter("capture_test", lambda app, opts: Capture())
        sm = StatisticsManager("A", reporter="capture_test", interval_s=0.05)
        sm.start_reporting()
        t0 = time.time()
        while not got and time.time() - t0 < 5.0:
            time.sleep(0.02)
        sm.stop_reporting()
        assert got and got[0]["app"] == "A"


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------


class TestTracer:
    def test_sampling_deterministic_under_seed(self):
        def run():
            tr = Tracer(0.3, capacity=1000, seed=1234)
            picks = []
            for _ in range(200):
                tok = tr.start_span("stream.S")
                # a sampled span token is a list; the skip sentinel is not
                picks.append(isinstance(tok, list))
                tr.end_span(tok)
            return picks, tr.sampled_count

        p1, n1 = run()
        p2, n2 = run()
        assert p1 == p2
        assert n1 == n2
        assert 20 < n1 < 120  # ~60 expected at p=0.3

    def test_nested_spans_and_ring_bound(self):
        tr = Tracer(1.0, capacity=4)
        for i in range(10):
            a = tr.start_span("stream.S", 1)
            b = tr.start_span("query.q", 1)
            tr.end_span(b)
            tr.end_span(a)
        traces = tr.traces()
        assert len(traces) == 4  # bounded ring keeps the newest
        spans = traces[-1]["spans"]
        assert [s["component"] for s in spans] == ["stream.S", "query.q"]
        assert spans[0]["depth"] == 0 and spans[1]["depth"] == 1
        assert spans[1]["duration_us"] <= spans[0]["duration_us"]
        json.dumps(traces)  # dumpable as JSON

    def test_unsampled_root_suppresses_children(self):
        tr = Tracer(0.0)
        a = tr.start_span("stream.S")
        b = tr.start_span("query.q")
        tr.end_span(b)
        tr.end_span(a)
        assert tr.traces() == []
        assert tr.sampled_count == 0


# ---------------------------------------------------------------------------
# end-to-end: engine wiring, exposition endpoint, traces across the pipeline
# ---------------------------------------------------------------------------


def _mk_app(mgr, extra=""):
    return mgr.create_siddhi_app_runtime(f"""
    @app:statistics(reporter='none', trace.sample='1.0', trace.seed='7'{extra})
    define stream S (symbol string, price float);
    @sink(type='inMemory', topic='stats_e2e_out')
    define stream Egress (symbol string);
    @info(name='q') from S[price > 10] select symbol insert into Egress;
    """)


class TestEngineWiring:
    def test_report_shape_and_histogram_latency(self):
        mgr = SiddhiManager()
        rt = _mk_app(mgr)
        rt.start()
        h = rt.get_input_handler("S")
        for i in range(20):
            h.send(("A", float(i)))
        rep = rt.statistics_manager.report()
        assert rep["throughput"]["stream.S"] == 20
        assert rep["throughput"]["stream.Egress"] == 9  # price in 11..19
        assert rep["throughput"]["sink.Egress"] == 9
        lat = rep["latency_ms"]["query.q"]
        assert lat["count"] == 20
        assert 0 < lat["p50"] <= lat["p95"] <= lat["p99"]
        # back-compat keys survive (pre-histogram report shape)
        assert rep["latency_avg_ms"]["query.q"] > 0
        # device budget: per-query step time is collected live
        assert rep["device"]["time_ms"]["query.q.step"]["summary"]["count"] == 20
        assert "rates" in rep and "m1" in rep["rates"]["stream.S"]
        mgr.shutdown()

    def test_traces_cross_ingress_query_sink(self):
        mgr = SiddhiManager()
        rt = _mk_app(mgr)
        rt.start()
        rt.get_input_handler("S").send(("A", 99.0))
        traces = rt.traces()
        assert len(traces) == 1
        comps = [s["component"] for s in traces[0]["spans"]]
        depths = [s["depth"] for s in traces[0]["spans"]]
        assert comps == [
            "stream.S", "query.q", "stream.Egress", "sink.Egress[0]"
        ]
        assert depths == [0, 1, 2, 3]
        assert all(s["duration_us"] >= 0 for s in traces[0]["spans"])
        # dump_traces round-trips through JSON
        assert json.loads(rt.dump_traces())[0]["spans"][0]["component"] == "stream.S"
        mgr.shutdown()

    def test_trace_sampling_e2e_deterministic(self):
        counts = []
        for _ in range(2):
            mgr = SiddhiManager()
            rt = mgr.create_siddhi_app_runtime("""
            @app:statistics(reporter='none', trace.sample='0.25',
                            trace.seed='99')
            define stream S (v long);
            @info(name='q') from S select v insert into Out;
            """)
            rt.start()
            h = rt.get_input_handler("S")
            for i in range(80):
                h.send((i,))
            counts.append(len(rt.traces()))
            mgr.shutdown()
        assert counts[0] == counts[1]
        assert 0 < counts[0] < 80

    def test_per_subscriber_error_attribution(self):
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime("""
        @app:statistics(reporter='none')
        @OnError(action='LOG')
        define stream S (v long);
        @info(name='q') from S select v insert into Out;
        """)

        def boom(batch, now):
            raise ValueError("poison")

        rt.junctions["S"].subscribe(boom, name="custom.boom")
        rt.start()
        h = rt.get_input_handler("S")
        for i in range(3):
            h.send((i,))
        rep = rt.statistics_manager.report()
        assert rep["errors"]["stream.S"] == 3  # aggregate (back-compat)
        assert rep["errors"]["stream.S.subscriber.custom.boom"] == 3
        ent = rep["errors_detail"]["stream.S.subscriber.custom.boom"]
        assert ent["component"] == "stream.S"
        assert ent["subscriber"] == "custom.boom"
        text = mgr.prometheus_text()
        assert (
            'siddhi_errors_total{app="SiddhiApp",component="stream.S",'
            'subscriber="custom.boom"} 3' in text
        )
        mgr.shutdown()

    def test_enable_disable_toggling(self):
        mgr = SiddhiManager()
        rt = _mk_app(mgr)
        rt.start()
        h = rt.get_input_handler("S")
        h.send(("A", 50.0))
        assert rt.statistics_manager.report()["throughput"]["stream.S"] == 1
        rt.enable_stats(False)
        for i in range(5):
            h.send(("A", 50.0))
        rep = rt.statistics_manager.report()
        assert rep["throughput"]["stream.S"] == 1  # collection stopped
        assert len(rt.traces()) == 1  # tracing stopped too
        rt.enable_stats(True)
        h.send(("A", 50.0))
        assert rt.statistics_manager.report()["throughput"]["stream.S"] == 2
        mgr.shutdown()

    def test_fused_ingest_stays_engaged_and_records_budget(self):
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime("""
        @app:statistics(reporter='none')
        @app:batch(size='32')
        define stream S (k long, v long);
        @info(name='q') from S select k, sum(v) as t group by k insert into Out;
        """)
        rt.start()
        j = rt.junctions["S"]
        n = 32 * 8
        rt.get_input_handler("S").send_columns(
            np.arange(n, dtype=np.int64),
            {
                "k": np.arange(n, dtype=np.int64) % 4,
                "v": np.ones(n, dtype=np.int64),
            },
        )
        assert j.fused_ingest is not None and j.fused_ingest.eligible()
        rep = rt.statistics_manager.report()
        dev = rep["device"]
        assert dev["counters"]["stream.S.h2d_chunks"]["count"] >= 1
        assert dev["counters"]["stream.S.h2d_bytes"]["count"] > 0
        assert dev["time_ms"]["stream.S.fused_step"]["summary"]["count"] >= 1
        # the query latency histogram records CHUNK dispatch time in fused mode
        assert rep["latency_ms"]["query.q"]["count"] >= 1
        assert rep["throughput"]["stream.S"] == n
        mgr.shutdown()


class TestSinkThroughputSemantics:
    def test_sink_counts_only_delivered_events(self):
        from siddhi_tpu.core.errors import ConnectionUnavailableError
        from siddhi_tpu.core.event import Event
        from siddhi_tpu.core.io import Sink

        class DownSink(Sink):
            def publish(self, payload):
                raise ConnectionUnavailableError("down")

        s = DownSink()
        s.init("S", {"on.error": "LOG"}, None)
        counts = []
        s.on_publish_stats = counts.append
        s.on_events([Event(0, ("a",))])
        assert counts == []  # dropped payloads are not "published events"

        class UpSink(Sink):
            def publish(self, payload):
                pass

        u = UpSink()
        u.init("S", {}, None)
        u.on_publish_stats = counts.append
        u.on_events([Event(0, ("a",)), Event(1, ("b",))])
        assert counts == [2]


class TestMetricsEndpoint:
    def test_serve_metrics_exposition(self):
        mgr = SiddhiManager()
        rt = _mk_app(mgr)

        def boom(batch, now):
            raise ValueError("poison")

        rt.junctions["S"].subscribe(boom, name="custom.boom")
        rt.set_exception_handler(lambda e: None)
        rt.start()
        h = rt.get_input_handler("S")
        for i in range(10):
            h.send(("A", float(i * 3)))
        port = mgr.serve_metrics(0)  # ephemeral port
        assert mgr.serve_metrics(0) == port  # idempotent
        base = f"http://127.0.0.1:{port}"
        text = urllib.request.urlopen(f"{base}/metrics", timeout=5).read().decode()
        fams = _assert_prometheus_wellformed(text)
        # acceptance: throughput, latency quantiles, buffered depth,
        # per-subscriber errors, device-time budget
        assert fams.get("siddhi_events_total", 0) >= 2
        for q in ('quantile="0.5"', 'quantile="0.95"', 'quantile="0.99"'):
            assert q in text
        assert "siddhi_buffered_events" in fams
        assert 'subscriber="custom.boom"' in text
        assert "siddhi_device_time_ms" in fams
        assert "siddhi_traces_sampled_total" in fams
        # JSON + traces endpoints
        rep = json.loads(
            urllib.request.urlopen(f"{base}/metrics.json", timeout=5).read()
        )
        assert rep[0]["app"] == "SiddhiApp"
        tr = json.loads(
            urllib.request.urlopen(f"{base}/traces", timeout=5).read()
        )
        assert tr["SiddhiApp"], "sampled traces must be served"
        mgr.shutdown()  # also stops the endpoint
        assert mgr.metrics_port is None

    def test_unknown_path_is_404(self):
        mgr = SiddhiManager()
        _mk_app(mgr).start()
        port = mgr.serve_metrics(0)
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/nope", timeout=5
            )
        assert ei.value.code == 404
        ei.value.read()  # framed body: the connection is not left hanging
        mgr.shutdown()

    def test_500_response_is_framed(self):
        # satellite: the old handler wrote a raw body after end_headers()
        # with no Content-Length, hanging keep-alive scrapers; send_error
        # frames it. Induce a handler fault by breaking report collection.
        mgr = SiddhiManager()
        _mk_app(mgr).start()
        port = mgr.serve_metrics(0)
        broken = mgr._metrics_server
        orig = broken._reports
        broken._reports = lambda: (_ for _ in ()).throw(RuntimeError("boom"))
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics.json", timeout=5
                )
            assert ei.value.code == 500
            assert ei.value.headers.get("Content-Length") is not None
            body = ei.value.read()
            assert b"boom" in body
        finally:
            broken._reports = orig
        # the server survives and keeps serving after the 500
        rep = json.loads(
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics.json", timeout=5
            ).read()
        )
        assert rep[0]["app"] == "SiddhiApp"
        mgr.shutdown()

    def test_concurrent_scrape_while_app_shutdown(self):
        # scrapes racing an app shutdown must always get well-formed 200s
        # (collection snapshots + manager-level iteration are copy-safe)
        import threading

        mgr = SiddhiManager()
        rt = _mk_app(mgr)
        rt.start()
        h = rt.get_input_handler("S")
        for i in range(10):
            h.send(("A", float(i * 3)))
        port = mgr.serve_metrics(0)
        base = f"http://127.0.0.1:{port}"
        errors: list = []
        stop = threading.Event()

        def scrape_loop():
            paths = ("/metrics", "/metrics.json", "/traces", "/status.json")
            i = 0
            while not stop.is_set():
                try:
                    resp = urllib.request.urlopen(
                        base + paths[i % len(paths)], timeout=5
                    )
                    assert resp.status == 200
                    resp.read()
                except Exception as e:  # pragma: no cover - failure detail
                    errors.append(e)
                    return
                i += 1

        threads = [threading.Thread(target=scrape_loop) for _ in range(3)]
        for t in threads:
            t.start()
        time.sleep(0.05)
        assert mgr.shutdown_siddhi_app_runtime("SiddhiApp")
        time.sleep(0.1)  # keep scraping against the app-less manager
        stop.set()
        for t in threads:
            t.join(timeout=5)
        assert not errors, errors
        # app deregistered: endpoints still serve (empty) well-formed bodies
        assert json.loads(
            urllib.request.urlopen(f"{base}/metrics.json", timeout=5).read()
        ) == []
        mgr.shutdown()


# ---------------------------------------------------------------------------
# zero-cost-when-disabled guard
# ---------------------------------------------------------------------------


class TestNoOverheadWhenDisabled:
    def test_nothing_wired_without_annotation(self):
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime("""
        define stream S (v long);
        @info(name='q') from S select v insert into Out;
        """)
        rt.start()
        assert rt.statistics_manager is None
        assert rt.tracer is None
        j = rt.junctions["S"]
        assert j.on_publish_stats is None
        assert j.on_error_stats is None
        assert j.error_stats_factory is None
        assert j.device_stats is None
        assert j.tracer is None
        qr = rt.queries["q"]
        assert qr.device_step_tracker is None
        assert qr.sync_stall_tracker is None
        # profiler + compile telemetry ride the same wiring: without
        # @app:statistics the hot paths pay one `is None` check
        assert qr.compile_telemetry is None
        assert qr.profiler is None
        assert j.profiler is None
        assert j.compile_telemetry is None
        assert rt.traces() == []
        mgr.shutdown()

    def test_gated_trackers_are_cheap_when_disabled(self):
        # perf-regression assertion: a disabled tracker's mark_in/mark_out is
        # one gate check — it must run far faster than the enabled path that
        # takes timestamps and updates the histogram. Ratio-based with a wide
        # margin so CI jitter cannot flake it.
        class Gate:
            enabled = True

        gate = Gate()
        lt = LatencyTracker("t", gate=gate)
        tt = ThroughputTracker("t", gate=gate)
        n = 20_000

        def run():
            t0 = time.perf_counter()
            for _ in range(n):
                lt.mark_in()
                tt.add(1)
                lt.mark_out()
            return time.perf_counter() - t0

        run()  # warm
        enabled = min(run() for _ in range(3))
        gate.enabled = False
        base = lt.samples
        disabled = min(run() for _ in range(3))
        assert lt.samples == base  # nothing recorded while disabled
        assert disabled < enabled, (
            f"disabled path ({disabled:.4f}s) must be cheaper than enabled "
            f"({enabled:.4f}s)"
        )

    def test_profiler_hooks_are_single_gate_check_when_disabled(self):
        # the profiler/compile-telemetry contract matches the trackers':
        # `enable_stats(False)` stops collection at one gate check —
        # begin() returns None and observe() returns before touching the
        # jit cache or taking a lock's slow path
        from siddhi_tpu.observability.profiler import (
            CompileTelemetry,
            Profiler,
        )

        class Gate:
            enabled = True

        gate = Gate()
        prof = Profiler(gate=gate)
        ct = CompileTelemetry(gate=gate)

        class FakeProg:
            calls = 0

            def _cache_size(self):
                FakeProg.calls += 1
                return 1

        prog = FakeProg()
        gate.enabled = False
        assert prof.begin("S", 8) is None
        ct.observe("c", prog, (8,), 1000)
        assert FakeProg.calls == 0  # never reached the cache probe
        assert prof.report()["chunks"] == 0
        assert ct.report() == {}
        gate.enabled = True
        wf = prof.begin("S", 8)
        assert wf is not None
        prof.end(wf)
        ct.observe("c", prog, (8,), 1000)
        assert FakeProg.calls == 1
        assert prof.report()["chunks"] == 1
        assert ct.report()["c"]["compiles"] == 1
