"""Reporter SPI + exposition formats (console/log back-compat, JSON lines,
Prometheus text format).

Reference: util/statistics/metrics/SiddhiStatisticsManager.java:35-80 wires
Dropwizard Console/JMX reporters behind `@app:statistics(reporter=...)`;
here the SPI is a tiny `emit(report)` object so deployments can register
their own (`register_reporter`). The Prometheus reporter is pull-based: it
registers nothing periodic — `manager.serve_metrics(port)` serves the text
exposition for every app on the manager (see http_server.py).
"""

from __future__ import annotations

import json
import logging
from typing import Callable, Optional


class Reporter:
    """SPI: one `emit(report)` per interval; `close()` at shutdown."""

    def emit(self, report: dict) -> None:
        raise NotImplementedError

    def close(self) -> None:
        pass


class ConsoleReporter(Reporter):
    def emit(self, report: dict) -> None:
        print(f"[siddhi_tpu stats] {report}", flush=True)


class LogReporter(Reporter):
    def __init__(self, app_name: str) -> None:
        self._log = logging.getLogger(f"siddhi_tpu.statistics.{app_name}")

    def emit(self, report: dict) -> None:
        self._log.info("%s", report)


class JsonLinesReporter(Reporter):
    """Appends one JSON object per interval to `file` (default
    `<app>.metrics.jsonl` in the working directory)."""

    def __init__(self, path: str) -> None:
        self.path = path
        self._fh = open(path, "a", encoding="utf-8")

    def emit(self, report: dict) -> None:
        self._fh.write(json.dumps(report, default=str) + "\n")
        self._fh.flush()

    def close(self) -> None:
        try:
            self._fh.close()
        except Exception:
            pass


# name -> factory(app_name, options) -> Reporter | None (None = pull-based /
# disabled: no periodic thread is started)
_REPORTERS: dict[str, Callable[[str, dict], Optional[Reporter]]] = {
    "console": lambda app, opts: ConsoleReporter(),
    "log": lambda app, opts: LogReporter(app),
    "jsonl": lambda app, opts: JsonLinesReporter(
        opts.get("file", f"{app}.metrics.jsonl")
    ),
    "none": lambda app, opts: None,
    # pull-based: the app runtime asks the manager to serve /metrics instead
    "prometheus": lambda app, opts: None,
}


def register_reporter(name: str, factory) -> None:
    """Plug a custom reporter: factory(app_name, options) -> Reporter."""
    _REPORTERS[name.lower()] = factory


def make_reporter(name: str, app_name: str, options: dict) -> Optional[Reporter]:
    factory = _REPORTERS.get(str(name).lower())
    if factory is None:
        logging.getLogger(__name__).warning(
            "unknown @app:statistics reporter '%s'; metrics are collected "
            "but not periodically reported (known: %s)",
            name, sorted(_REPORTERS),
        )
        return None
    return factory(app_name, options)


# ---------------------------------------------------------------------------
# Prometheus text exposition (format version 0.0.4)
# ---------------------------------------------------------------------------


def _esc(v: str) -> str:
    return str(v).replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _labels(**kv) -> str:
    inner = ",".join(
        f'{k}="{_esc(v)}"' for k, v in kv.items() if v is not None and v != ""
    )
    return "{" + inner + "}" if inner else ""


_FAMILIES = {
    "siddhi_events_total": ("counter", "Events published per component"),
    "siddhi_event_rate": (
        "gauge", "EWMA event rate in events/second (window label: 1m/5m)"),
    "siddhi_latency_ms": (
        "summary", "Processing latency quantiles per component (ms)"),
    "siddhi_buffered_events": (
        "gauge", "Queued depth of async ingress buffers"),
    "siddhi_errors_total": (
        "counter",
        "Failed dispatches/publishes per component "
        "(subscriber label: per-subscriber attribution)"),
    "siddhi_memory_bytes": (
        "gauge", "Device buffer bytes held by each component's carried state"),
    "siddhi_device_time_ms": (
        "summary",
        "Device-time budget per component (op label: step/fused_step/"
        "sync_stall) in ms"),
    "siddhi_h2d_bytes_total": (
        "counter", "Host-to-device wire bytes shipped per junction"),
    "siddhi_h2d_chunks_total": (
        "counter", "Host-to-device transfer chunks per junction"),
    "siddhi_h2d_events_total": (
        "counter",
        "Events shipped over the fused h2d wire per junction (the "
        "roofline denominator beside siddhi_h2d_bytes_total)"),
    "siddhi_h2d_logical_bytes_total": (
        "counter",
        "Full-width (logical) bytes the same events would have shipped "
        "with wire encoding off — the logical side of the encoded-vs-"
        "logical split (core/wire.py)"),
    "siddhi_wire_bytes_per_event": (
        "gauge",
        "Live ENCODED wire bytes per event over the fused h2d path — the "
        "roofline attribution the compact wire encodings shrink"),
    "siddhi_wire_logical_bytes_per_event": (
        "gauge",
        "Logical (full-width) bytes per event of the same stream — "
        "encoded/logical is the live wire reduction"),
    "siddhi_h2d_mb_s": (
        "gauge",
        "1-minute EWMA host-to-device wire throughput in MB/s per "
        "junction"),
    "siddhi_pipeline_occupancy": (
        "gauge",
        "Measured overlap ratio of the pipelined fused ingest (summed "
        "stage busy time / send wall time; 1.0 = fully serial stages)"),
    "siddhi_pipeline_depth": (
        "gauge",
        "Configured max in-flight chunks of the pipelined fused ingest "
        "(0 = pipeline disabled)"),
    "siddhi_keyshard_device_keys": (
        "gauge",
        "Group keys owned by each mesh device of a key-sharded query "
        "(parallel/keyshard.py; device label: mesh position)"),
    "siddhi_keyshard_occupancy": (
        "gauge",
        "Per-device group-table fill of a key-sharded query "
        "(owned keys / group capacity)"),
    "siddhi_keyshard_skew": (
        "gauge",
        "Key-ownership skew of a key-sharded query: max per-device keys "
        "over the even-split mean (1.0 = perfectly balanced)"),
    "siddhi_watermark_ms": (
        "gauge",
        "Per-source-stream event-time watermark (max event time minus the "
        "@app:watermark bound) in ms since epoch"),
    "siddhi_watermark_lag_ms": (
        "gauge",
        "Watermark lag per source stream: newest event time seen minus the "
        "watermark (the reorder stage's live slack)"),
    "siddhi_reorder_buffered_events": (
        "gauge",
        "Rows held back by the @app:watermark bounded reorder stage, "
        "awaiting watermark advance"),
    "siddhi_late_events_total": (
        "counter",
        "Events behind the watermark at arrival, by outcome label: "
        "dropped (metered drop), streamed (diverted to !S), applied "
        "(aggregation bucket re-opened + correction row), expired "
        "(beyond allowed.lateness)"),
    "siddhi_lateness_ms": (
        "summary",
        "How far behind the watermark late events arrived, per stream (ms)"),
    "siddhi_traces_sampled_total": ("counter", "Traces sampled per app"),
    "siddhi_compiles_total": (
        "counter",
        "XLA compiles per program component by cause "
        "(observability/profiler.py taxonomy: first_compile, shape_change, "
        "tail_variant_k, full_width_rebuild, deliver_set_change, "
        "donation_mismatch) — alert on recompile storms"),
    "siddhi_calibration_error_ratio": (
        "gauge",
        "EWMA-smoothed live/predicted ratio per calibration pair "
        "(observability/calibration.py; 1.0 = the plan priced this "
        "component exactly; kind label: prediction kind)"),
    "siddhi_calibration_mispriced_total": (
        "counter",
        "Mispricing flags raised by the calibration ledger, by stable "
        "reason code (selectivity_off_4x, wire_full_width_fallback, "
        "unpredicted_recompile_cause, shared_state_refcount_collapsed)"),
    "siddhi_slo_burn_rate": (
        "gauge",
        "Multi-window SLO burn rate per objective (observability/slo.py; "
        "window label: fast/slow; 1.0 = consuming exactly the error "
        "budget)"),
}


def _summary_lines(out, family, app, component, summ, **extra) -> None:
    for q, key in (("0.5", "p50"), ("0.95", "p95"), ("0.99", "p99"),
                   ("0.999", "p999"), ("0.9999", "p9999")):
        out.append(
            f"{family}{_labels(app=app, component=component, quantile=q, **extra)}"
            f" {summ[key]}"
        )
    out.append(
        f"{family}_sum{_labels(app=app, component=component, **extra)} {summ['sum']}"
    )
    out.append(
        f"{family}_count{_labels(app=app, component=component, **extra)} {summ['count']}"
    )


def render_raw_family(name: str, ftype: str, help_text: str,
                      lines: list[str]) -> str:
    """One manager-owned exposition family from pre-rendered sample lines
    (supervisor/admission/churn/incident counters live outside the per-app
    statistics registries so they meter apps with statistics OFF too).
    Empty when there are no samples — absent families must not appear."""
    if not lines:
        return ""
    return (
        f"# HELP {name} {help_text}\n# TYPE {name} {ftype}\n"
        + "\n".join(lines) + "\n"
    )


def render_prometheus(reports: list[dict]) -> str:
    """Render the Prometheus text exposition for a list of `report()` dicts
    (one per app). Families are emitted once each with HELP/TYPE headers."""
    body: dict[str, list[str]] = {f: [] for f in _FAMILIES}
    for rep in reports:
        app = rep.get("app", "")
        for n, v in rep.get("throughput", {}).items():
            body["siddhi_events_total"].append(
                f"siddhi_events_total{_labels(app=app, component=n)} {v}"
            )
        for n, r in rep.get("rates", {}).items():
            for window, key in (("1m", "m1"), ("5m", "m5")):
                body["siddhi_event_rate"].append(
                    f"siddhi_event_rate{_labels(app=app, component=n, window=window)}"
                    f" {r[key]}"
                )
        for n, summ in rep.get("latency_ms", {}).items():
            _summary_lines(body["siddhi_latency_ms"], "siddhi_latency_ms",
                           app, n, summ)
        for n, v in rep.get("buffered", {}).items():
            body["siddhi_buffered_events"].append(
                f"siddhi_buffered_events{_labels(app=app, component=n)} {v}"
            )
        for n, ent in rep.get("errors_detail", {}).items():
            body["siddhi_errors_total"].append(
                "siddhi_errors_total"
                f"{_labels(app=app, component=ent['component'], subscriber=ent.get('subscriber'))}"
                f" {ent['count']}"
            )
        for n, v in rep.get("memory_bytes", {}).items():
            body["siddhi_memory_bytes"].append(
                f"siddhi_memory_bytes{_labels(app=app, component=n)} {v}"
            )
        dev = rep.get("device", {})
        for n, ent in dev.get("time_ms", {}).items():
            _summary_lines(
                body["siddhi_device_time_ms"], "siddhi_device_time_ms",
                app, ent["component"], ent["summary"], op=ent["op"],
            )
        for n, ent in dev.get("counters", {}).items():
            fam = f"siddhi_{ent['op']}_total"
            if fam in body:
                body[fam].append(
                    f"{fam}{_labels(app=app, component=ent['component'])}"
                    f" {ent['count']}"
                )
        for n, ent in rep.get("roofline", {}).items():
            bpe = ent.get("wire_bytes_per_event")
            if bpe is not None:
                body["siddhi_wire_bytes_per_event"].append(
                    f"siddhi_wire_bytes_per_event{_labels(app=app, component=n)}"
                    f" {bpe}"
                )
            lpe = ent.get("wire_logical_bytes_per_event")
            if lpe is not None:
                body["siddhi_wire_logical_bytes_per_event"].append(
                    "siddhi_wire_logical_bytes_per_event"
                    f"{_labels(app=app, component=n)} {lpe}"
                )
            body["siddhi_h2d_mb_s"].append(
                f"siddhi_h2d_mb_s{_labels(app=app, component=n)}"
                f" {ent.get('h2d_mb_s_1m', 0)}"
            )
        # key-sharded queries (parallel/keyshard.py)
        for n, ent in rep.get("shard", {}).items():
            kocc = ent.get("occupancy", [])
            for d, v in enumerate(ent.get("per_device_keys", [])):
                body["siddhi_keyshard_device_keys"].append(
                    "siddhi_keyshard_device_keys"
                    f"{_labels(app=app, component=n, device=str(d))} {v}"
                )
                if d < len(kocc):
                    body["siddhi_keyshard_occupancy"].append(
                        "siddhi_keyshard_occupancy"
                        f"{_labels(app=app, component=n, device=str(d))}"
                        f" {kocc[d]}"
                    )
            if "skew" in ent:
                body["siddhi_keyshard_skew"].append(
                    f"siddhi_keyshard_skew{_labels(app=app, component=n)}"
                    f" {ent['skew']}"
                )
        for n, ent in rep.get("pipeline", {}).items():
            body["siddhi_pipeline_occupancy"].append(
                f"siddhi_pipeline_occupancy{_labels(app=app, component=n)}"
                f" {ent['occupancy']}"
            )
            body["siddhi_pipeline_depth"].append(
                f"siddhi_pipeline_depth{_labels(app=app, component=n)}"
                f" {ent['depth']}"
            )
        for sid, ent in rep.get("watermark", {}).get("streams", {}).items():
            if ent.get("watermark_ms") is not None:
                body["siddhi_watermark_ms"].append(
                    f"siddhi_watermark_ms{_labels(app=app, stream=sid)}"
                    f" {ent['watermark_ms']}"
                )
            if ent.get("lag_ms") is not None:
                body["siddhi_watermark_lag_ms"].append(
                    f"siddhi_watermark_lag_ms{_labels(app=app, stream=sid)}"
                    f" {ent['lag_ms']}"
                )
            body["siddhi_reorder_buffered_events"].append(
                "siddhi_reorder_buffered_events"
                f"{_labels(app=app, stream=sid)} {ent.get('buffered', 0)}"
            )
            for outcome in ("dropped", "streamed", "applied", "expired"):
                body["siddhi_late_events_total"].append(
                    "siddhi_late_events_total"
                    f"{_labels(app=app, stream=sid, outcome=outcome)}"
                    f" {ent.get(outcome, 0)}"
                )
            summ = ent.get("lateness_ms")
            if summ and summ.get("count"):
                _summary_lines(
                    body["siddhi_lateness_ms"], "siddhi_lateness_ms",
                    app, None, summ, stream=sid,
                )
        for n, ent in rep.get("compiles", {}).items():
            for cause, v in sorted(ent.get("causes", {}).items()):
                body["siddhi_compiles_total"].append(
                    "siddhi_compiles_total"
                    f"{_labels(app=app, component=n, cause=cause)} {v}"
                )
        calib = rep.get("calibration", {})
        for ent in calib.get("pairs", []):
            body["siddhi_calibration_error_ratio"].append(
                "siddhi_calibration_error_ratio"
                f"{_labels(app=app, kind=ent['kind'], component=ent['component'])}"
                f" {ent['ratio']}"
            )
        for ent in calib.get("mispriced", []):
            body["siddhi_calibration_mispriced_total"].append(
                "siddhi_calibration_mispriced_total"
                f"{_labels(app=app, reason=ent['reason'], component=ent['component'])}"
                f" {ent['count']}"
            )
        for ent in rep.get("slo", {}).get("burn", []):
            body["siddhi_slo_burn_rate"].append(
                "siddhi_slo_burn_rate"
                f"{_labels(app=app, objective=ent['objective'], component=ent['component'], window=ent['window'])}"
                f" {ent['burn_rate']}"
            )
        body["siddhi_traces_sampled_total"].append(
            "siddhi_traces_sampled_total"
            f"{_labels(app=app)} {rep.get('traces_sampled', 0)}"
        )
    out: list[str] = []
    for family, lines in body.items():
        if not lines:
            continue
        ftype, help_ = _FAMILIES[family]
        out.append(f"# HELP {family} {help_}")
        out.append(f"# TYPE {family} {ftype}")
        out.extend(lines)
    return "\n".join(out) + "\n"
