"""CEP-native self-monitoring: the engine watches itself with SiddhiQL.

Siddhi's own pitch (PAPER.md) is that CEP is the right tool for watching
event systems — so the engine's health should be observable with ordinary
SiddhiQL instead of only an external scraper. The `@app:selfmon` app
annotation injects a system stream:

    SelfMonitorStream (component string, metric string,
                       value double, p99 double)

and arms a recurring scheduler target that, every `interval`, feeds one row
per (component, metric) pair from the app's metrics registry and live
introspection state: latency summaries (`value` = mean ms, `p99` = p99 ms),
throughput counts and 1m rates, error counts, junction queue depths, window
fills, and pipeline occupancy. Users then write plain filters/patterns over
it — alerting via CEP itself:

    @app:selfmon(interval='5 sec')
    from SelfMonitorStream[metric == 'latency_ms' and p99 > 50.0]
    select component, p99 insert into AlertStream;

With no annotation nothing is injected, scheduled, or collected — the
engine pays zero cost (the same contract as `@app:statistics`).
"""

from __future__ import annotations

SELFMON_STREAM_ID = "SelfMonitorStream"
DEFAULT_INTERVAL_MS = 5_000
_MIN_INTERVAL_MS = 10


def selfmon_attrs():
    """The injected stream's schema, shared by the runtime (StreamSchema)
    and the analyzer (symbol table)."""
    from siddhi_tpu.core.types import AttrType

    return [
        ("component", AttrType.STRING),
        ("metric", AttrType.STRING),
        ("value", AttrType.DOUBLE),
        ("p99", AttrType.DOUBLE),
    ]


def _parse_interval(v) -> int | None:
    """'5 sec' / '500 millisec' / bare integer milliseconds -> ms, or None
    when malformed."""
    from siddhi_tpu.compiler.siddhi_compiler import SiddhiCompiler

    s = str(v).strip()
    try:
        ms = int(s)
    except ValueError:
        try:
            ms = SiddhiCompiler.parse_time_constant(s)
        except Exception:
            return None
    return ms if ms >= _MIN_INTERVAL_MS else None


def iter_selfmon_annotation_problems(ann, defined_streams=()):
    """Yield one message per `@app:selfmon` problem — THE validation rules,
    shared by the runtime resolver (raises on the first) and the analyzer's
    SA113 diagnostics (reports them all)."""
    for k, v in ann.elements:
        if k == "interval" or (k is None and len(ann.elements) == 1):
            if _parse_interval(v) is None:
                yield (
                    f"@app:selfmon interval '{v}' must be a time constant of "
                    f"at least {_MIN_INTERVAL_MS} millisec (e.g. '5 sec')"
                )
        else:
            yield (
                f"unknown @app:selfmon option '{k if k is not None else v}' "
                "(expected interval)"
            )
    if SELFMON_STREAM_ID in defined_streams:
        yield (
            f"@app:selfmon reserves the stream name '{SELFMON_STREAM_ID}' "
            "(the engine injects its definition)"
        )


def resolve_selfmon_annotation(ann, defined_streams=()) -> int:
    """Interval in ms for one app's `@app:selfmon` annotation. Raises
    SiddhiAppCreationError on malformed options — the runtime analog of the
    analyzer's SA113 diagnostic."""
    from siddhi_tpu.core.errors import SiddhiAppCreationError

    for problem in iter_selfmon_annotation_problems(ann, defined_streams):
        raise SiddhiAppCreationError(problem)
    v = ann.element("interval") or ann.element(None)
    return _parse_interval(v) if v is not None else DEFAULT_INTERVAL_MS


class SelfMonitor:
    """Recurring scheduler target feeding SelfMonitorStream from the app's
    metrics registry + introspection hooks (owned by SiddhiAppRuntime)."""

    def __init__(self, runtime, interval_ms: int):
        self.runtime = runtime
        self.interval_ms = int(interval_ms)
        self.ticks = 0  # fires observed (introspection: selfmon health)
        # ONE stable target object: the scheduler dedups pending fires by
        # id(target), and `self._fire` would mint a fresh bound method per
        # notify_at call
        self._target = self._fire

    # ---- row collection --------------------------------------------------

    def rows(self) -> list[tuple]:
        """One (component, metric, value, p99) row per live metric. Never
        raises: a collection fault must not take the scheduler down."""
        rt = self.runtime
        out: list[tuple] = []
        sm = rt.statistics_manager
        if sm is not None:
            for name, lt in list(sm.latency.items()):
                if lt.samples:
                    out.append((
                        name, "latency_ms", lt.avg_ms, lt.quantile_ms(0.99)
                    ))
            for name, tt in list(sm.throughput.items()):
                out.append((name, "throughput", float(tt.count), 0.0))
                out.append((name, "rate_1m", tt.rate_1m, 0.0))
            for name, et in list(sm.errors.items()):
                if et.subscriber is None:  # aggregates only: keep rows lean
                    out.append((name, "errors", float(et.count), 0.0))
            # device-budget histograms give JUNCTION-level tails too:
            # (stream.S, device_fused_step_ms, ...) is the fused dispatch p99
            for name, dt in list(sm.device_time.items()):
                if dt.samples:
                    out.append((
                        dt.component, f"device_{dt.op}_ms",
                        dt.avg_ms, dt.quantile_ms(0.99),
                    ))
        for sid, j in list(rt.junctions.items()):
            if sid == SELFMON_STREAM_ID:
                continue  # the engine must not recurse on its own monitor
            out.append((f"stream.{sid}", "queue_depth", float(j.queued()), 0.0))
            ps = j.pipeline_stats
            if ps is not None and ps.depth:
                out.append((
                    f"stream.{sid}", "pipeline_occupancy", ps.occupancy(), 0.0
                ))
        # window fill is a device->host read from the scheduler thread;
        # describe_state() reports fill=None when a concurrent donated-state
        # dispatch deleted the buffers under it
        for wid, nw in list(rt.named_windows.items()):
            d = nw.describe_state()
            if d.get("fill") is not None:
                out.append((f"window.{wid}", "fill", float(d["fill"]), 0.0))
        store = rt.manager._error_store
        if store is not None and hasattr(store, "size"):
            try:
                out.append((
                    "error_store", "depth", float(store.size()), 0.0
                ))
            except Exception:
                pass
        # supervised-runtime health: restart + admission counters ride the
        # same CEP-queryable stream (core/supervision.py, core/admission.py)
        sup = getattr(rt.manager, "_supervisor", None)
        if sup is not None:
            out.append((
                "supervisor", "restarts",
                float(sup.restarts.get(rt.name, 0)), 0.0,
            ))
        adm = getattr(rt, "_admission", None)
        if adm is not None:
            out.append(("admission", "shed", float(adm.shed), 0.0))
            out.append((
                "admission", "blocked_ms", float(adm.blocked_ms), 0.0
            ))
        ap = getattr(rt, "_autopersist", None)
        if ap is not None:
            out.append((
                "autopersist", "persists", float(ap.persists), 0.0
            ))
            out.append((
                "autopersist", "failures", float(ap.failures), 0.0
            ))
        # black-box recorder: incident counts are CEP-queryable, so an app
        # can alert on its own post-mortems (observability/blackbox.py)
        bb = getattr(rt, "_blackbox", None)
        if bb is not None:
            out.append((
                "blackbox", "incidents",
                float(sum(bb.incidents_total.values())), 0.0,
            ))
            out.append((
                "blackbox", "checkpoint_pins", float(bb.pins), 0.0
            ))
        return out

    # ---- scheduling ------------------------------------------------------

    def start(self) -> None:
        """Arm the recurring feed (mirrors the rate-limiter flush timer
        wiring in SiddhiAppRuntime._arm_rate_limiter)."""
        rt = self.runtime
        rt._scheduler.start()
        rt._scheduler.notify_at(rt.clock() + self.interval_ms, self._target)

    def _fire(self, t_ms: int) -> None:
        rt = self.runtime
        if not rt._running:
            return
        try:
            rows = self.rows()
            if rows:
                rt._junction(SELFMON_STREAM_ID).send_rows(
                    [t_ms] * len(rows), rows, now=t_ms
                )
            self.ticks += 1
        except Exception:
            import logging

            logging.getLogger(__name__).exception(
                "selfmon feed for app '%s' raised", rt.name
            )
        finally:
            rt._scheduler.notify_at(t_ms + self.interval_ms, self._target)

    def describe_state(self) -> dict:
        return {"interval_ms": self.interval_ms, "ticks": self.ticks}
