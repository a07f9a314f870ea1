"""StatisticsManager: per-app metric registry + periodic reporter thread.

Reference: util/statistics/metrics/SiddhiStatisticsManager.java:35-80
(Dropwizard MetricRegistry + reporters), enabled by
`@app:statistics(reporter=..., interval=..., trace.sample=...)`
(SiddhiAppParser.java:106-142) and toggled at runtime
(SiddhiAppRuntime.enableStats :682). Metric naming follows
util/SiddhiConstants.java METRIC_* conventions (`stream.S`, `query.q`,
`table.T`, `sink.S`, ...).

The registry IS the enable gate: every tracker it hands out checks
`registry.enabled` on the hot path, so `enable_stats(False)` stops
collection (not just reporting) with one attribute read per event batch.
"""

from __future__ import annotations

import threading
from typing import Optional

from siddhi_tpu.observability.metrics import (
    BufferedEventsTracker,
    LatencyTracker,
    ThroughputTracker,
)


class JunctionDeviceStats:
    """Device-budget trackers for one junction's dispatch path: fused-step
    dispatch time, h2d wire traffic, and d2h truth-sync stalls."""

    __slots__ = (
        "step", "h2d_bytes", "h2d_chunks", "h2d_events", "h2d_logical",
        "sync_stall",
    )

    def __init__(self, registry: "StatisticsManager", component: str) -> None:
        self.step = registry.device_time_tracker(component, "fused_step")
        self.h2d_bytes = registry.device_counter(component, "h2d_bytes")
        self.h2d_chunks = registry.device_counter(component, "h2d_chunks")
        # events shipped over the wire alongside h2d_bytes: the live
        # roofline attribution (bytes/event) the compact-wire-encoding
        # work targets (BENCH r04 `*_wire_B_per_ev`, but always-on)
        self.h2d_events = registry.device_counter(component, "h2d_events")
        # what the FULL-WIDTH wire would have carried for the same events
        # (core/wire.py logical_row_bytes): the logical side of the
        # logical-vs-encoded bytes/event split
        self.h2d_logical = registry.device_counter(
            component, "h2d_logical_bytes"
        )
        self.sync_stall = registry.device_time_tracker(component, "sync_stall")


class PipelineStats:
    """Per-stage budget of one junction's pipelined fused ingest
    (core/pipeline.py): encode / h2d / dispatch / drain histograms plus the
    measured overlap ratio `pipeline.occupancy` — summed stage busy time
    over send wall time, so 1.0 means fully serial stages and values above
    1.0 mean the pipeline genuinely overlapped them (upper bound: the
    number of concurrently busy stages)."""

    __slots__ = (
        "encode", "h2d", "dispatch", "drain", "depth", "_wall_ns", "_lock",
        "_gate",
    )

    def __init__(self, registry: "StatisticsManager", component: str) -> None:
        self.encode = registry.device_time_tracker(component, "pipeline.encode")
        self.h2d = registry.device_time_tracker(component, "pipeline.h2d")
        self.dispatch = registry.device_time_tracker(
            component, "pipeline.dispatch"
        )
        self.drain = registry.device_time_tracker(component, "pipeline.drain")
        self.depth = 0  # configured max in-flight chunks (0 = pipeline off)
        self._wall_ns = 0
        self._lock = threading.Lock()
        self._gate = registry

    def add_wall(self, ns: int) -> None:
        """Accumulate one pipelined send's wall-clock (the occupancy
        denominator)."""
        if not self._gate.enabled:
            return
        with self._lock:
            self._wall_ns += int(ns)

    def occupancy(self) -> float:
        wall = self._wall_ns
        if wall <= 0:
            return 0.0
        busy = (
            self.encode.total_ns
            + self.h2d.total_ns
            + self.dispatch.total_ns
            + self.drain.total_ns
        )
        return busy / wall


class StatisticsManager:
    """Registry of trackers + reporter thread (one per app runtime)."""

    def __init__(
        self,
        app_name: str,
        reporter: str = "console",
        interval_s: float = 60.0,
        options: Optional[dict] = None,
        tracer=None,
    ):
        self.app_name = app_name
        self.reporter = reporter
        self.interval_s = float(interval_s)
        self.options = dict(options or {})
        self.tracer = tracer
        self.throughput: dict[str, ThroughputTracker] = {}
        self.latency: dict[str, LatencyTracker] = {}
        self.buffered: dict[str, BufferedEventsTracker] = {}
        # failed dispatches / sink publishes per component; per-subscriber
        # attribution keys are `<component>.subscriber.<name>` with the
        # structured (component, subscriber) pair kept on the tracker
        self.errors: dict[str, ThroughputTracker] = {}
        # name -> () -> bytes; the TPU-native analog of the reference's
        # ObjectSizeCalculator memory metric (util/statistics/memory/):
        # device-buffer bytes held by each component's carried state
        self.memory: dict[str, callable] = {}
        # device-time budget: `<component>.<op>` -> histogram / counter
        self.device_time: dict[str, LatencyTracker] = {}
        self.device_counters: dict[str, ThroughputTracker] = {}
        # pipelined fused ingest: component -> PipelineStats (stage
        # histograms ride device_time; occupancy/depth are gauges here)
        self.pipeline: dict[str, PipelineStats] = {}
        # key-sharded queries (parallel/keyshard.py): component -> the
        # query's KeyShardedGroupExec, whose describe_state() -> per-device
        # keys, occupancy and skew feeds the siddhi_keyshard_* Prometheus
        # families
        self.shard: dict[str, object] = {}
        # event-time robustness (core/watermark.py): () -> the watermark
        # runtime's describe_state() — per-stream watermarks/lag, late-event
        # meters, lateness histograms; rendered as the siddhi_watermark_* /
        # siddhi_late_* / siddhi_lateness_ms Prometheus families
        self.watermark_fn = None
        # plan-vs-actual calibration (observability/calibration.py): () ->
        # the ledger's prometheus section — error-ratio pairs + cumulative
        # mispriced counters; rendered as siddhi_calibration_* families
        self.calibration_fn = None
        # SLO burn rates (observability/slo.py): () -> the engine's
        # prometheus section; rendered as siddhi_slo_burn_rate
        self.slo_fn = None
        # continuous profiler: compile telemetry + per-chunk stage
        # waterfalls (observability/profiler.py), gated by this registry
        from siddhi_tpu.observability.profiler import (
            CompileTelemetry,
            Profiler,
        )

        self.compile_telemetry = CompileTelemetry(gate=self)
        self.profiler = Profiler(gate=self)
        self.enabled = True
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._reporter_obj = None

    # ---- tracker factories -------------------------------------------------

    def throughput_tracker(self, name: str) -> ThroughputTracker:
        t = self.throughput.get(name)
        if t is None:
            t = self.throughput[name] = ThroughputTracker(name, gate=self)
        return t

    def latency_tracker(self, name: str) -> LatencyTracker:
        t = self.latency.get(name)
        if t is None:
            t = self.latency[name] = LatencyTracker(name, gate=self)
        return t

    def buffered_tracker(self, name: str) -> BufferedEventsTracker:
        return self.buffered.setdefault(name, BufferedEventsTracker(name))

    def error_tracker(
        self, name: str, subscriber: Optional[str] = None
    ) -> ThroughputTracker:
        key = f"{name}.subscriber.{subscriber}" if subscriber else name
        t = self.errors.get(key)
        if t is None:
            t = self.errors[key] = ThroughputTracker(key, gate=self)
            t.component = name
            t.subscriber = subscriber
        return t

    def register_memory(self, name: str, fn) -> None:
        """fn() -> device bytes held by the named component's state."""
        self.memory[name] = fn

    def device_time_tracker(self, component: str, op: str) -> LatencyTracker:
        key = f"{component}.{op}"
        t = self.device_time.get(key)
        if t is None:
            t = self.device_time[key] = LatencyTracker(key, gate=self)
            t.component = component
            t.op = op
        return t

    def device_counter(self, component: str, op: str) -> ThroughputTracker:
        key = f"{component}.{op}"
        t = self.device_counters.get(key)
        if t is None:
            t = self.device_counters[key] = ThroughputTracker(key, gate=self)
            t.component = component
            t.subscriber = None
            t.op = op
        return t

    def junction_device_stats(self, component: str) -> JunctionDeviceStats:
        return JunctionDeviceStats(self, component)

    def pipeline_stats(self, component: str) -> PipelineStats:
        p = self.pipeline.get(component)
        if p is None:
            p = self.pipeline[component] = PipelineStats(self, component)
        return p

    def register_shard(self, component: str, ex) -> None:
        """Attach a key-sharded query's executor (parallel/keyshard.py
        KeyShardedGroupExec) whose describe_state() feeds the report's
        `shard` section and the siddhi_keyshard_* Prometheus families."""
        self.shard[component] = ex

    def register_watermark(self, fn) -> None:
        """Attach the @app:watermark runtime's describe_state supplier; it
        feeds the report's `watermark` section and the watermark/lateness
        Prometheus families."""
        self.watermark_fn = fn

    def register_calibration(self, fn) -> None:
        """Attach the CalibrationLedger's prometheus-section supplier; it
        feeds the report's `calibration` section and the
        siddhi_calibration_* Prometheus families."""
        self.calibration_fn = fn

    def register_slo(self, fn) -> None:
        """Attach the SloEngine's prometheus-section supplier; it feeds the
        report's `slo` section and siddhi_slo_burn_rate."""
        self.slo_fn = fn

    def roofline(self) -> dict:
        """Live per-stream wire roofline: bytes/event over the fused h2d
        path plus the 1-minute h2d throughput in MB/s — the signal the
        compact-wire-encoding work targets. Keyed by component
        (`stream.<id>`); empty until a fused send ships bytes."""
        out: dict = {}
        for key, t in list(self.device_counters.items()):
            if getattr(t, "op", None) != "h2d_bytes" or t.count <= 0:
                continue
            comp = t.component
            ev = self.device_counters.get(f"{comp}.h2d_events")
            n_ev = ev.count if ev is not None else 0
            lg = self.device_counters.get(f"{comp}.h2d_logical_bytes")
            n_lg = lg.count if lg is not None else 0
            entry = {
                "h2d_bytes": t.count,
                "h2d_events": n_ev,
                "h2d_logical_bytes": n_lg,
                "h2d_mb_s_1m": round(t.rate_1m / 1e6, 3),
            }
            if n_ev > 0:
                # the encoded-vs-logical split (core/wire.py): encoded is
                # what actually crossed the link, logical is the full-width
                # equivalent; their ratio is the live wire reduction
                entry["wire_bytes_per_event"] = round(t.count / n_ev, 3)
                if n_lg > 0:
                    entry["wire_logical_bytes_per_event"] = round(
                        n_lg / n_ev, 3
                    )
                    entry["wire_reduction"] = round(n_lg / t.count, 3)
            out[comp] = entry
        return out

    # ---- reporting ---------------------------------------------------------

    def report(self) -> dict:
        # snapshot each registry dict with one atomic list() first: trackers
        # are created lazily from dispatch threads (first subscriber failure,
        # first store query, ...) while scrape/reporter threads read, and a
        # Python-level comprehension over a mutating dict raises
        mem = {}
        for n, fn in list(self.memory.items()):
            try:
                mem[n] = int(fn())
            except Exception:
                mem[n] = -1
        throughput = list(self.throughput.items())
        latency = list(self.latency.items())
        buffered = list(self.buffered.items())
        errors = list(self.errors.items())
        device_time = list(self.device_time.items())
        device_counters = list(self.device_counters.items())
        pipeline = list(self.pipeline.items())
        rep = {
            "app": self.app_name,
            "throughput": {n: t.count for n, t in throughput},
            "rates": {
                n: {"m1": round(t.rate_1m, 3), "m5": round(t.rate_5m, 3)}
                for n, t in throughput
            },
            # back-compat key (pre-histogram shape) beside the summaries
            "latency_avg_ms": {
                n: round(t.avg_ms, 3) for n, t in latency
            },
            "latency_ms": {
                n: t.summary_ms() for n, t in latency
            },
            "buffered": {n: t.get_size() for n, t in buffered},
            "errors": {n: t.count for n, t in errors},
            "errors_detail": {
                n: {
                    "component": t.component or n,
                    "subscriber": t.subscriber,
                    "count": t.count,
                }
                for n, t in errors
            },
            "memory_bytes": mem,
            "device": {
                "time_ms": {
                    n: {
                        "component": t.component,
                        "op": t.op,
                        "summary": t.summary_ms(),
                    }
                    for n, t in device_time
                },
                "counters": {
                    n: {"component": t.component, "op": t.op, "count": t.count}
                    for n, t in device_counters
                },
            },
            "pipeline": {
                n: {"occupancy": round(p.occupancy(), 3), "depth": p.depth}
                for n, p in pipeline
            },
            "shard": {
                n: r.describe_state() for n, r in list(self.shard.items())
            },
            "watermark": (
                self.watermark_fn() if self.watermark_fn is not None else {}
            ),
            "roofline": self.roofline(),
            # compile-cause taxonomy totals (observability/profiler.py):
            # promoted out of /profile so a recompile storm is alertable as
            # siddhi_compiles_total{cause=,component=}
            "compiles": {
                n: {"compiles": e["compiles"], "causes": dict(e["causes"])}
                for n, e in self.compile_telemetry.report().items()
            },
            "traces_sampled": (
                self.tracer.sampled_count if self.tracer is not None else 0
            ),
        }
        # advisory sections must never take a scrape down with them
        if self.calibration_fn is not None:
            try:
                rep["calibration"] = self.calibration_fn()
            except Exception:
                rep["calibration"] = {}
        if self.slo_fn is not None:
            try:
                rep["slo"] = self.slo_fn()
            except Exception:
                rep["slo"] = {}
        return rep

    def prometheus_text(self) -> str:
        from siddhi_tpu.observability.reporters import render_prometheus

        return render_prometheus([self.report()])

    def profile_report(self) -> dict:
        """The app's `/profile` payload: compile ledger per program, the
        top-K slowest chunk waterfalls, and the high quantiles (p99/p999/
        p9999) of every latency + device-time histogram."""

        def highs(trackers) -> dict:
            out = {}
            for n, t in trackers:
                h = t.hist
                if h.count == 0:
                    continue
                p99, p999, p9999 = h.quantiles([0.99, 0.999, 0.9999])
                out[n] = {
                    "count": h.count,
                    "p99": round(p99 / 1e6, 4),
                    "p999": round(p999 / 1e6, 4),
                    "p9999": round(p9999 / 1e6, 4),
                }
            return out

        return {
            "app": self.app_name,
            "compile": self.compile_telemetry.report(),
            "waterfalls": self.profiler.report(),
            "latency_high_ms": highs(list(self.latency.items())),
            "device_time_high_ms": highs(list(self.device_time.items())),
            "roofline": self.roofline(),
        }

    def start_reporting(self) -> None:
        if self._thread is not None:
            return
        from siddhi_tpu.observability.reporters import make_reporter

        self._reporter_obj = make_reporter(
            self.reporter, self.app_name, self.options
        )
        if self._reporter_obj is None:
            return  # pull-based (prometheus) or disabled (none)
        self._stop.clear()

        def run():
            while not self._stop.wait(self.interval_s):
                if self.enabled:
                    try:
                        self._reporter_obj.emit(self.report())
                    except Exception:
                        import logging

                        logging.getLogger(__name__).exception(
                            "stats reporter for app '%s' raised", self.app_name
                        )

        self._thread = threading.Thread(target=run, daemon=True)
        self._thread.start()

    def stop_reporting(self) -> None:
        self._stop.set()
        t = self._thread
        if t is not None and t is not threading.current_thread():
            t.join(timeout=2.0)
        self._thread = None
        if self._reporter_obj is not None:
            self._reporter_obj.close()
            self._reporter_obj = None
