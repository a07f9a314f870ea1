"""SiddhiAppRuntime: app assembly and lifecycle.

Reference: core/SiddhiAppRuntime.java:88-696 + util/parser/SiddhiAppParser.java —
holds junction/query/table/window/aggregation maps, wires receivers into
junctions, start/shutdown ordering, callback registration, store-query API.
Here "parse" is compile: each query becomes a jitted device program; junctions
are host fan-out points between compiled programs.
"""

from __future__ import annotations

import threading
from typing import Callable, Union

import jax
import jax.numpy as jnp

from siddhi_tpu.core.errors import DefinitionNotExistError, SiddhiAppCreationError
from siddhi_tpu.core.event import (
    Event,
    EventBatch,
    KIND_CURRENT,
    KIND_EXPIRED,
    StreamSchema,
)
from siddhi_tpu.core.query_runtime import QueryRuntime
from siddhi_tpu.core.stream_junction import (
    InputHandler,
    StreamJunction,
    system_clock_ms,
)
from siddhi_tpu.observability.profiler import COMPILE_EVENTS, GC_SPANS
from siddhi_tpu.query_api.annotation import find_annotation
from siddhi_tpu.query_api.execution import (
    InsertIntoStream,
    JoinInputStream,
    OutputEventsFor,
    Query,
    SingleInputStream,
    StateInputStream,
)
from siddhi_tpu.query_api.siddhi_app import SiddhiApp

DEFAULT_BATCH = 64


class SiddhiAppRuntime:
    def __init__(self, app: SiddhiApp, manager) -> None:
        self.app = app
        self.manager = manager
        self.interner = manager.interner
        self.name = app.name
        self.clock = system_clock_ms
        self._running = False
        self._gc_spans = False  # holds observability.profiler.GC_SPANS
        self._lock = threading.RLock()
        self._debugger = None

        # @app:playback(idle.time, increment): event-time clock + scheduler
        # (reference: SiddhiAppParser.java:166-212)
        self._playback_clock = None
        pb = find_annotation(app.annotations, "app:playback")
        if pb is not None:
            from siddhi_tpu.compiler.siddhi_compiler import SiddhiCompiler
            from siddhi_tpu.core.timestamp import EventTimeClock, EventTimeScheduler

            idle = pb.element("idle.time")
            inc = pb.element("increment")
            self._playback_clock = EventTimeClock(
                idle_ms=SiddhiCompiler.parse_time_constant(idle) if idle else None,
                increment_ms=SiddhiCompiler.parse_time_constant(inc) if inc else None,
            )
            self.clock = self._playback_clock.now
            self._scheduler = EventTimeScheduler(self._playback_clock)
        else:
            from siddhi_tpu.core.scheduler import SystemTimeScheduler

            self._scheduler = SystemTimeScheduler()

        # @app:watermark(bound, idle.timeout, late.policy, allowed.lateness):
        # event-time robustness — bounded-disorder reordering at every
        # ingress, per-stream watermarks with min-propagation, and late-event
        # policies (core/watermark.py; SIDDHI_TPU_WATERMARK overrides).
        # Without playback, the watermark clock takes over timekeeping so
        # window flushes / pattern deadlines / bucket closes fire on
        # watermark ADVANCE, never on raw (possibly disordered) arrival.
        self._watermark = None
        from siddhi_tpu.core.watermark import resolve_watermark_annotation

        wm_cfg = resolve_watermark_annotation(
            find_annotation(app.annotations, "app:watermark")
        )
        if wm_cfg is not None:
            from siddhi_tpu.core.timestamp import (
                EventTimeClock,
                EventTimeScheduler,
            )
            from siddhi_tpu.core.watermark import WatermarkRuntime

            if self._playback_clock is not None:
                wm_clock = self._playback_clock
            else:
                wm_clock = EventTimeClock()
                self.clock = wm_clock.now
                self._scheduler = EventTimeScheduler(wm_clock)
            self._watermark = WatermarkRuntime(self, wm_cfg, wm_clock)

        # @app:statistics(reporter='console'|'log'|'jsonl'|'prometheus'|'none',
        #                 interval='N', trace.sample='P', trace.seed='S',
        #                 trace.capacity='K', file='...', port='...')
        # (reference: SiddhiAppParser.java:106-142; tracing/exposition are
        # this engine's additions — see siddhi_tpu/observability/)
        self.statistics_manager = None
        self.tracer = None
        st = find_annotation(app.annotations, "app:statistics")
        if st is not None:
            from siddhi_tpu.core.statistics import StatisticsManager

            opts = {k: v for k, v in st.elements if k is not None}
            sample = opts.get("trace.sample")
            if sample is not None:
                from siddhi_tpu.observability.tracing import Tracer

                try:
                    self.tracer = Tracer(
                        float(sample),
                        capacity=int(opts.get("trace.capacity", "256")),
                        seed=(
                            int(opts["trace.seed"])
                            if "trace.seed" in opts
                            else None
                        ),
                    )
                except ValueError as e:
                    raise SiddhiAppCreationError(
                        f"@app:statistics trace options: {e}"
                    ) from e
            try:
                self.statistics_manager = StatisticsManager(
                    self.name,
                    reporter=st.element("reporter", "console"),
                    interval_s=float(st.element("interval", "60")),
                    options=opts,
                    tracer=self.tracer,
                )
            except ValueError as e:
                raise SiddhiAppCreationError(
                    f"@app:statistics options: {e}"
                ) from e
            if str(self.statistics_manager.reporter).lower() == "prometheus":
                try:
                    int(opts.get("port", "9464"))
                except ValueError as e:
                    raise SiddhiAppCreationError(
                        f"@app:statistics(reporter='prometheus'): invalid "
                        f"port '{opts.get('port')}'"
                    ) from e

        self.stream_schemas: dict[str, StreamSchema] = {}
        self.junctions: dict[str, StreamJunction] = {}
        self.queries: dict[str, QueryRuntime] = {}

        batch_ann = find_annotation(app.annotations, "app:batch")
        self.batch_size = int(batch_ann.element("size", str(DEFAULT_BATCH))) if batch_ann else DEFAULT_BATCH
        self.group_capacity = self._capacity_annotation("app:groupCapacity", None)
        # rows a time-bounded window's ring holds (time, externalTime, the
        # time batches, cron): @app:timeCapacity(size='N')
        from siddhi_tpu.core.windows import DEFAULT_TIME_CAPACITY

        self.time_capacity = self._capacity_annotation(
            "app:timeCapacity", DEFAULT_TIME_CAPACITY
        )
        # whole-graph fusion escape hatch: @app:fuse(disable='true') /
        # SIDDHI_TPU_FUSE=1|0 (core/fusion_exec.py; malformed options raise
        # here — the runtime analog of the analyzer's SA125)
        from siddhi_tpu.core.fusion_exec import resolve_fuse_annotation

        self._fuse_enabled = resolve_fuse_annotation(
            find_annotation(app.annotations, "app:fuse")
        )
        # compact wire encodings: @app:wire(disable='true',
        # range/dict/delta.<stream>.<col>=...) / SIDDHI_TPU_WIRE=1|0
        # (core/wire.py; malformed options raise here — the runtime analog
        # of the analyzer's SA132). The per-stream WireSpecs are built when
        # the fused engines form (_build_fused_ingest).
        from siddhi_tpu.core.wire import resolve_wire_annotation

        self._wire_enabled, self._wire_hints = resolve_wire_annotation(
            find_annotation(app.annotations, "app:wire")
        )
        # event lineage & provenance: @app:lineage(capacity='N',
        # mode='full|sample') (observability/lineage.py; malformed options
        # raise here — the runtime analog of the analyzer's SA131).
        # Resolved BEFORE any junction/query construction: arenas arm in
        # _junction() and recorders in _add_query*, all ahead of the first
        # trace so the `__lin.*` lane structure is part of every program.
        from siddhi_tpu.observability.lineage import (
            LineageLedger,
            resolve_lineage_annotation,
        )

        self._lineage_cfg = resolve_lineage_annotation(
            find_annotation(app.annotations, "app:lineage")
        )
        self.lineage_ledger = (
            LineageLedger(self, self._lineage_cfg)
            if self._lineage_cfg is not None
            else None
        )
        # black-box incident recorder: @app:blackbox(window, triggers,
        # keep, ...) (observability/blackbox.py; malformed options raise
        # here — the runtime analog of the analyzer's SA140). Resolved
        # BEFORE any junction construction so _junction() arms a seq-lane
        # ring on every junction, the lineage precedent.
        from siddhi_tpu.observability.blackbox import (
            BlackboxRecorder,
            resolve_blackbox_annotation,
        )

        self._blackbox_cfg = resolve_blackbox_annotation(
            find_annotation(app.annotations, "app:blackbox")
        )
        self._blackbox = (
            BlackboxRecorder(self, self._blackbox_cfg)
            if self._blackbox_cfg is not None
            else None
        )
        # first-class sharded execution: @app:shard(devices='N', axis=...)
        # / SIDDHI_TPU_SHARD (parallel/shard.py; malformed options raise
        # here — the runtime analog of the analyzer's SA129). Resolved now,
        # applied at start() once the fused engines exist.
        from siddhi_tpu.parallel.shard import resolve_shard_annotation

        self._shard_conf = resolve_shard_annotation(
            find_annotation(app.annotations, "app:shard")
        )
        self._shard = None  # ShardRuntime, built at start()
        # one app-level processing lock: receive+route for every query runs
        # under it, so cyclic stream topologies cannot lock-order deadlock and
        # timer/input threads deliver outputs in state-step order (analog of
        # the reference's synchronous junction dispatch + ThreadBarrier)
        self._process_lock = threading.RLock()

        # supervised runtime (core/supervision.py, core/admission.py):
        # @app:persist auto-checkpoint, @app:restart policy (validated here,
        # consumed by manager.supervise()), @app:admission ingress gate.
        # All three raise at creation on malformed options — the runtime
        # analogs of SA126/SA127/SA128.
        from siddhi_tpu.core.admission import (
            AdmissionController,
            resolve_admission_annotation,
        )
        from siddhi_tpu.core.supervision import (
            AutoPersist,
            resolve_persist_annotation,
            resolve_restart_annotation,
        )

        self._autopersist = None
        pa = find_annotation(app.annotations, "app:persist")
        if pa is not None:
            interval_ms, keep = resolve_persist_annotation(pa)
            self._autopersist = AutoPersist(self, interval_ms, keep)
        ra = find_annotation(app.annotations, "app:restart")
        if ra is not None:
            resolve_restart_annotation(ra)  # fail fast; supervisor re-reads
        self._admission = None
        aa = find_annotation(app.annotations, "app:admission")
        if aa is not None:
            self._admission = AdmissionController(
                self.name, resolve_admission_annotation(aa)
            )
            if self._blackbox is not None:  # shed spikes freeze incidents
                self._admission.on_incident = self._blackbox.fire
        # supervision health hook (core/supervision.AppHealth), installed by
        # Supervisor.attach(); _junction() wires it onto every junction
        self._health = None
        # callbacks retained for supervised rebuild: a restart re-creates
        # every junction/runtime, so user callbacks must be re-registered
        self._user_callbacks: list[tuple[str, Callable]] = []
        # hot-deploy wiring staging (core/churn.add_query): while set (a
        # list), _wire_subscribe/_wire_fuse_candidate APPEND deferred
        # actions instead of touching the live junctions, so the whole
        # query builds off-line and the splice applies them atomically
        # under the process lock
        self._staged_wiring = None

        # @OnError(action='LOG'|'STREAM'|'STORE') failure policies
        # (reference: StreamJunction OnErrorAction + util/error/handler/*);
        # STREAM auto-defines the fault stream `!S` = S's attributes + _error
        from siddhi_tpu.core.types import AttrType as _AttrType

        self.on_error_actions: dict[str, str] = {}
        for sid, d in app.stream_definitions.items():
            oe = find_annotation(d.annotations, "OnError")
            if oe is None:
                continue
            action = (oe.element("action") or oe.element(None) or "LOG").upper()
            if action not in ("LOG", "STREAM", "STORE"):
                raise SiddhiAppCreationError(
                    f"stream '{sid}': unknown @OnError action '{action}' "
                    "(expected LOG, STREAM, or STORE)"
                )
            self.on_error_actions[sid] = action
            if action == "STREAM":
                if any(a.name == "_error" for a in d.attributes):
                    raise SiddhiAppCreationError(
                        f"stream '{sid}': @OnError(action='STREAM') reserves "
                        "the attribute name '_error'"
                    )
                fid = "!" + sid
                self.stream_schemas[fid] = StreamSchema(
                    fid,
                    [(a.name, a.type) for a in d.attributes]
                    + [("_error", _AttrType.STRING)],
                )

        # @app:watermark late.policy='stream'|'apply' diverts late/correction
        # rows onto each stream's `!S` side stream — auto-define the schemas
        # through the same @OnError STREAM machinery (skipping streams that
        # already carry one)
        if self._watermark is not None and self._watermark.cfg.late_policy in (
            "stream", "apply",
        ):
            for sid, d in app.stream_definitions.items():
                fid = "!" + sid
                if fid in self.stream_schemas:
                    continue
                if any(a.name == "_error" for a in d.attributes):
                    raise SiddhiAppCreationError(
                        f"stream '{sid}': @app:watermark late.policy="
                        f"'{self._watermark.cfg.late_policy}' reserves the "
                        "attribute name '_error'"
                    )
                self.stream_schemas[fid] = StreamSchema(
                    fid,
                    [(a.name, a.type) for a in d.attributes]
                    + [("_error", _AttrType.STRING)],
                )

        # @pipeline(depth='N') — per-stream depth of the double-buffered
        # fused-ingest pipeline (core/pipeline.py); resolved here and
        # applied when start() builds the junction's FusedJunctionIngest
        from siddhi_tpu.core.pipeline import resolve_pipeline_annotation
        from siddhi_tpu.observability.flight import resolve_flight_annotation

        self._pipeline_conf: dict[str, int] = {}
        for sid, d in app.stream_definitions.items():
            self.stream_schemas[sid] = StreamSchema(
                sid, [(a.name, a.type) for a in d.attributes]
            )
            try:
                self._pipeline_conf[sid] = resolve_pipeline_annotation(
                    find_annotation(d.annotations, "pipeline")
                )
                # @flightRecorder(size='N') — bounded last-N-events ring on
                # this stream's junction (observability/flight.py; the
                # SIDDHI_TPU_FLIGHT env override is folded in by the
                # resolver, and _junction() applies it to internal
                # junctions too)
                flight_size = resolve_flight_annotation(
                    find_annotation(d.annotations, "flightRecorder")
                )
                if flight_size:
                    self._junction(sid).enable_flight(flight_size)
            except SiddhiAppCreationError as e:
                raise SiddhiAppCreationError(f"stream '{sid}': {e}") from e
            # @async(buffer.size, workers, batch.size.max) — buffered ingress
            # ring with worker batching (reference: StreamJunction.java:87-117)
            a = find_annotation(d.annotations, "async")
            if a is not None:
                j = self._junction(sid)
                j.enable_async(
                    buffer_size=int(a.element("buffer.size", "1024")),
                    workers=int(a.element("workers", "1")),
                    batch_max=int(a.element("batch.size.max", "0")) or None,
                )
            if self.statistics_manager is not None:
                sm = self.statistics_manager
                j = self._junction(sid)
                j.on_publish_stats = sm.throughput_tracker(f"stream.{sid}").add
                sm.buffered_tracker(f"stream.{sid}").register(j.queued)
                j.on_error_stats = sm.error_tracker(f"stream.{sid}").add
                # per-subscriber error attribution: failures are ALSO counted
                # under `stream.<id>.subscriber.<name>` (Prometheus exposes
                # the pair as component/subscriber labels)
                j.error_stats_factory = (
                    lambda sub, _sid=sid: sm.error_tracker(
                        f"stream.{_sid}", subscriber=sub
                    ).add
                )
                # live device budget for this junction's fused dispatch path
                j.device_stats = sm.junction_device_stats(f"stream.{sid}")
                # pipelined-ingest stage budget + occupancy overlap gauge
                j.pipeline_stats = sm.pipeline_stats(f"stream.{sid}")
                # continuous profiler: chunk waterfalls + compile telemetry
                # for the fused chunk program (observability/profiler.py)
                j.profiler = sm.profiler
                j.compile_telemetry = sm.compile_telemetry

        # @app:selfmon(interval='5 sec'): CEP-native self-monitoring — inject
        # the SelfMonitorStream system schema (runtime-side only: the user's
        # AST is not mutated; the analyzer injects the same definition from
        # the annotation, analysis/symbols.py) and build the scheduler-fed
        # monitor armed at start() (observability/selfmon.py)
        self._selfmon = None
        sm_ann = find_annotation(app.annotations, "app:selfmon")
        if sm_ann is not None:
            from siddhi_tpu.observability.selfmon import (
                SELFMON_STREAM_ID,
                SelfMonitor,
                resolve_selfmon_annotation,
            )

            interval_ms = resolve_selfmon_annotation(
                sm_ann, defined_streams=app.stream_definitions
            )
            from siddhi_tpu.observability.selfmon import selfmon_attrs

            self.stream_schemas[SELFMON_STREAM_ID] = StreamSchema(
                SELFMON_STREAM_ID, selfmon_attrs()
            )
            self._selfmon = SelfMonitor(self, interval_ms)

        # @app:slo(p99.latency.ms=..., ...): SLO burn-rate engine — inject
        # the SloAlertStream system schema (same runtime-side-only contract
        # as selfmon) and build the scheduler-fed evaluator armed at
        # start() (observability/slo.py)
        self._slo = None
        slo_ann = find_annotation(app.annotations, "app:slo")
        if slo_ann is not None:
            from siddhi_tpu.observability.slo import (
                SLO_STREAM_ID,
                SloEngine,
                resolve_slo_annotation,
                slo_attrs,
            )

            slo_cfg = resolve_slo_annotation(
                slo_ann, defined_streams=app.stream_definitions
            )
            self.stream_schemas[SLO_STREAM_ID] = StreamSchema(
                SLO_STREAM_ID, slo_attrs()
            )
            self._slo = SloEngine(self, slo_cfg)
            if self.statistics_manager is not None:
                self.statistics_manager.register_slo(
                    self._slo.prometheus_section
                )

        # plan-vs-actual calibration ledger: pairs static predictions with
        # live meters (observability/calibration.py). Gated on
        # @app:statistics — without it no ledger exists and every hot-path
        # touchpoint is one `is None` check (the zero-overhead contract)
        self._calibration = None
        if self.statistics_manager is not None:
            from siddhi_tpu.observability.calibration import (
                CalibrationLedger,
            )

            self._calibration = CalibrationLedger(self)
            self.statistics_manager.register_calibration(
                self._calibration.prometheus_section
            )

        for sid, action in self.on_error_actions.items():
            j = self._junction(sid)
            j.fault_policy = action
            j.app_name = self.name
            if action == "STREAM":
                j.fault_junction = self._junction("!" + sid)
            elif action == "STORE":
                j.error_store_fn = lambda: self.manager.error_store

        # `define function f[python] ...` scripts register into the global
        # function registry (reference: script executors via @Extension SPI;
        # the registry is manager-global, so same-name redefinitions win last)
        from siddhi_tpu.core.extension import extension as _ext
        from siddhi_tpu.core.stream_function import make_script_function

        for fid, fdef in app.function_definitions.items():
            _ext("function", fid)(make_script_function(fdef))

        from siddhi_tpu.core.table import DEFAULT_TABLE_CAPACITY, InMemoryTable

        table_capacity = self._capacity_annotation(
            "app:tableCapacity", DEFAULT_TABLE_CAPACITY
        )
        self.tables: dict[str, InMemoryTable] = {
            tid: InMemoryTable(d, self.interner, capacity=table_capacity)
            for tid, d in app.table_definitions.items()
        }
        self._store_query_cache: dict[str, object] = {}

        # @OnError on table definitions: mutation failures (the mutating
        # query's dispatch + record-store flushes) route to the error store
        # or the log instead of propagating to the sender. STREAM is
        # stream/window-only: the failing unit is the mutating query's
        # input batch, which does not carry the table's schema, so there is
        # no well-typed '!T' row to publish (analyzer analog: SA110).
        from siddhi_tpu.core.error_store import (
            iter_definition_onerror_problems,
            resolve_definition_onerror_action,
        )

        self._table_fault: dict[str, str] = {}
        for tid, td in app.table_definitions.items():
            oe = find_annotation(td.annotations, "OnError")
            if oe is None:
                continue
            for _tag, msg in iter_definition_onerror_problems(
                oe, "table", tid
            ):
                raise SiddhiAppCreationError(msg)
            action = resolve_definition_onerror_action(oe)
            self._table_fault[tid] = action
            t = self.tables[tid]
            t.fault_policy = action
            t.app_name = self.name
            if action == "STORE":
                t.error_store_fn = lambda: self.manager.error_store

        # named windows: input junction under the window id, processing runtime
        # in between, output junction feeding `from W` queries
        from siddhi_tpu.core.window_runtime import NamedWindow

        self.named_windows: dict[str, NamedWindow] = {}
        for wid, wd in app.window_definitions.items():
            nw = NamedWindow(wd, self.interner, self.time_capacity)
            self.named_windows[wid] = nw
            in_j = StreamJunction(nw.schema, self.interner, self.batch_size)
            in_j.tracer = self.tracer
            self.junctions[wid] = in_j
            nw.out_junction = StreamJunction(
                nw.schema, self.interner, self.batch_size
            )
            nw.out_junction.tracer = self.tracer
            wlt = (
                self.statistics_manager.latency_tracker(f"window.{wid}")
                if self.statistics_manager is not None
                else None
            )

            def receive(batch: EventBatch, now: int, _nw=nw, _lt=wlt) -> None:
                # mark_out in finally: a poison batch caught by the junction's
                # failure policy must not leak an open mark on the TLS stack
                if _lt is not None:
                    _lt.mark_in()
                try:
                    with self._process_lock:
                        out, aux = _nw.receive(batch, now)
                        _nw.out_junction.publish_batch(out, now)
                finally:
                    if _lt is not None:
                        _lt.mark_out()
                if _nw.needs_scheduler:
                    if _nw.host_next_timer is not None:
                        self._scheduler.start()
                        self._scheduler.notify_at(
                            _nw.host_next_timer(self.clock()), _nw.timer_target
                        )
                    else:
                        self._schedule_at(aux, _nw.timer_target)

            in_j.subscribe(receive, name=f"window.{wid}")
            if nw.needs_scheduler:
                def fire(t_ms: int, _nw=nw, _recv=receive) -> None:
                    _recv(self._timer_batch(_nw.schema, t_ms), t_ms)

                nw.timer_target = fire

        # @OnError on named windows: mutation failures (the shared window
        # processor exploding on an inserted batch) ride the SAME junction
        # failure machinery streams use — the window's input junction
        # carries the window's schema, so STREAM routes to a well-typed
        # fault stream '!W' (attributes + _error)
        for wid, wd in app.window_definitions.items():
            oe = find_annotation(wd.annotations, "OnError")
            if oe is None:
                continue
            for _tag, msg in iter_definition_onerror_problems(
                oe, "window", wid, [a.name for a in wd.attributes]
            ):
                raise SiddhiAppCreationError(msg)
            action = resolve_definition_onerror_action(oe)
            j = self.junctions[wid]
            j.fault_policy = action
            j.app_name = self.name
            if action == "STREAM":
                fid = "!" + wid
                self.stream_schemas[fid] = StreamSchema(
                    fid,
                    [(a.name, a.type) for a in wd.attributes]
                    + [("_error", _AttrType.STRING)],
                )
                j.fault_junction = self._junction(fid)
            elif action == "STORE":
                j.error_store_fn = lambda: self.manager.error_store

        # incremental aggregations: duration tables are registered app tables
        # (reference: AggregationParser.java:701-708 table map registration)
        from siddhi_tpu.core.aggregation import AggregationRuntime

        agg_groups = self._capacity_annotation("app:aggGroupCapacity", 64)
        self.aggregations: dict[str, AggregationRuntime] = {}
        self._agg_inputs: dict[str, str] = {}
        for aid, ad in app.aggregation_definitions.items():
            in_sid = ad.basic_single_input_stream.stream_id
            self._agg_inputs[aid] = in_sid
            in_schema = self.stream_schemas.get(in_sid)
            if in_schema is None:
                raise DefinitionNotExistError(
                    f"aggregation '{aid}': stream '{in_sid}' is not defined"
                )
            ar = AggregationRuntime(
                ad, in_schema, self.interner, group_capacity=agg_groups
            )
            if self._lineage_cfg is not None:
                ar.arm_lineage(self._lineage_cfg)
            self.aggregations[aid] = ar
            for t in ar.tables.values():
                self.tables[t.table_id] = t

            alt = (
                self.statistics_manager.latency_tracker(f"aggregation.{aid}")
                if self.statistics_manager is not None
                else None
            )

            def agg_receive(batch: EventBatch, now: int, _ar=ar, _lt=alt) -> None:
                if _lt is not None:
                    _lt.mark_in()
                try:
                    with self._process_lock:
                        aux = _ar.receive(batch, now)
                finally:
                    if _lt is not None:
                        _lt.mark_out()
                if "next_timer" in aux:
                    self._schedule_at(aux, _ar.timer_target)

            self._junction(in_sid).subscribe(
                agg_receive, name=f"aggregation.{aid}"
            )

            def agg_fire(t_ms: int, _ar=ar, _schema=in_schema, _recv=agg_receive) -> None:
                _recv(self._timer_batch(_schema, t_ms), t_ms)

            ar.timer_target = agg_fire

        # triggers: each defines a stream <id>(triggered_time long)
        from siddhi_tpu.core.trigger import TriggerRuntime
        from siddhi_tpu.core.types import AttrType

        self.triggers: dict[str, TriggerRuntime] = {}
        for tid, td in app.trigger_definitions.items():
            schema = StreamSchema(tid, [("triggered_time", AttrType.LONG)])
            self.stream_schemas[tid] = schema
            self.triggers[tid] = TriggerRuntime(
                td, self._junction(tid), self._scheduler, lambda: self.clock()
            )

        # @source/@sink transports on stream definitions
        # (reference: DefinitionParserHelper.addEventSource/Sink :302,419)
        from siddhi_tpu.core.io import (
            build_sink,
            build_source,
            wire_sink_error_handling,
            wire_source_error_handling,
        )
        from siddhi_tpu.query_api.annotation import find_all

        self.sources: list = []
        self.sinks: list = []
        for sid, d in app.stream_definitions.items():
            schema = self.stream_schemas[sid]
            for ann in find_all(d.annotations, "source"):
                # transport payloads carry no timestamps: sourced events are
                # stamped with the app clock (wall time, or the current
                # virtual time in @app:playback apps)
                src = build_source(
                    ann, sid, schema, self.get_input_handler(sid)
                )
                fault_sender = None
                if self.on_error_actions.get(sid) == "STREAM":
                    fj = self._junction("!" + sid)

                    def fault_sender(rows, err, _fj=fj):
                        now = self.clock()
                        _fj.send_rows(
                            [now] * len(rows),
                            [tuple(r) + (err,) for r in rows],
                            now=now,
                        )

                sm = self.statistics_manager
                wire_source_error_handling(
                    src,
                    lambda: self.manager.error_store,
                    self.name,
                    fault_sender,
                    sm.error_tracker(f"source.{sid}").add
                    if sm is not None
                    else None,
                )
                self.sources.append(src)
            for n_sink, ann in enumerate(find_all(d.annotations, "sink")):
                sink = build_sink(ann, sid, schema)
                sm = self.statistics_manager
                wire_sink_error_handling(
                    sink,
                    lambda: self.manager.error_store,
                    self.name,
                    f"{sid}[{n_sink}]",
                    sm.error_tracker(f"sink.{sid}").add
                    if sm is not None
                    else None,
                    on_publish_stats=(
                        sm.throughput_tracker(f"sink.{sid}").add
                        if sm is not None
                        else None
                    ),
                    latency_tracker=(
                        sm.latency_tracker(f"sink.{sid}")
                        if sm is not None
                        else None
                    ),
                )
                self.sinks.append(sink)
                self._junction(sid).add_stream_callback(
                    lambda rows, _s=sink: _s.on_events(
                        [Event(t, data) for t, data in rows]
                    ),
                    name=f"sink.{sid}[{n_sink}]",
                )

        from siddhi_tpu.core.partition import PartitionRuntime
        from siddhi_tpu.query_api.execution import assign_execution_ids

        # query/partition ids come from the ONE shared assignment (auto-ids
        # must not collide with explicit @info names anywhere in the app;
        # the analyzer and the EXPLAIN plan builder use the same helper)
        self.partitions: list[PartitionRuntime] = []
        for ent in assign_execution_ids(app):
            if ent[0] == "query":
                _kind, qid, q = ent
                self._add_query(qid, q)
            else:
                _kind, pid, elem, inner_ids = ent
                self.partitions.append(
                    PartitionRuntime(elem, self, pid, query_ids=inner_ids)
                )

    # ---- assembly --------------------------------------------------------

    def _capacity_annotation(self, name: str, default):
        ann = find_annotation(self.app.annotations, name)
        if ann is None:
            return default
        v = ann.element("size") or ann.element(None)
        if v is None:
            raise SiddhiAppCreationError(
                f"@{name} needs a size, e.g. @{name}(size='4096')"
            )
        return int(v)

    def _junction(self, stream_id: str) -> StreamJunction:
        j = self.junctions.get(stream_id)
        if j is None:
            schema = self.stream_schemas.get(stream_id)
            if schema is None:
                raise DefinitionNotExistError(f"stream '{stream_id}' is not defined")
            j = StreamJunction(schema, self.interner, self.batch_size)
            j.exception_handler = getattr(self, "_exception_handler", None)
            j.tracer = self.tracer
            # snapshot barrier: the fan-out holds the app process lock so a
            # checkpoint can't capture a torn cross-query state mid-batch
            j.process_lock = self._process_lock
            # supervised apps: unguarded dispatch/worker failures signal the
            # manager's Supervisor through the app's health hook
            health = getattr(self, "_health", None)
            if health is not None:
                j.on_fatal = health.mark_fatal
            # SIDDHI_TPU_FLIGHT=N arms the flight recorder on EVERY junction
            # — internal insert-into targets and fault streams included
            # (explicit @flightRecorder sizes are applied after, and win
            # when larger; see the stream-definition loop)
            from siddhi_tpu.observability.flight import flight_env_size

            env_n = flight_env_size()
            if env_n:
                j.enable_flight(env_n)
            # @app:lineage arms a seq-stamping arena on EVERY junction —
            # internal insert-into targets and fault streams included, so
            # multi-hop resolution can walk any chain
            if self._lineage_cfg is not None:
                j.enable_lineage(self._lineage_cfg.capacity)
            # @app:blackbox arms a seq-lane incident ring on EVERY junction
            # — the incident bundle must carry every stream's last window
            if self._blackbox is not None:
                self._blackbox.arm(j)
            self.junctions[stream_id] = j
        return j

    def _wire_subscribe(self, junction, fn, name: str) -> None:
        """Subscribe `fn` to `junction` — or, during a hot-deploy build
        (core/churn.add_query), stage the subscription for the splice."""
        if self._staged_wiring is not None:
            self._staged_wiring.append(
                lambda _j=junction, _f=fn, _n=name: _j.subscribe(_f, name=_n)
            )
        else:
            junction.subscribe(fn, name=name)

    def _wire_fuse_candidate(self, junction, ep) -> None:
        """Register a FuseEndpoint on `junction` — staged during a
        hot-deploy build, exactly like _wire_subscribe."""
        if self._staged_wiring is not None:
            self._staged_wiring.append(
                lambda _j=junction, _e=ep: _j.fuse_candidates.append(_e)
            )
        else:
            junction.fuse_candidates.append(ep)

    def _wire_insert(self, qr) -> None:
        """Route a query's output batches into its insert-into junction
        (reference: SiddhiAppRuntimeBuilder.addQuery:170-231 output wiring)."""
        out = qr.query.output_stream
        if not isinstance(out, InsertIntoStream):
            return
        target = out.target
        if out.is_fault and target not in self.stream_schemas:
            raise SiddhiAppCreationError(
                f"insert into '{target}': fault streams exist only for "
                f"streams declaring @OnError(action='STREAM') — add it to "
                f"'{target[1:]}'"
            )
        if target in self.tables:
            return  # table writes are compiled into the query step
        existing = self.stream_schemas.get(target)
        if existing is None and target in self.named_windows:
            existing = self.named_windows[target].schema
        inferred = qr.out_schema
        if existing is None:
            self.stream_schemas[target] = inferred
            existing = inferred
        elif [t for _, t in existing.attrs] != [t for _, t in inferred.attrs]:
            raise SiddhiAppCreationError(
                f"insert into '{target}': selector output {inferred.attrs} "
                f"does not match defined stream {existing.attrs}"
            )
        target_junction = self._junction(target)
        transform = _make_insert_transform(out.output_events)
        rename = _make_rename(inferred, existing)

        def publish(
            out_batch: EventBatch, now: int, _t=target_junction, _qr=qr
        ) -> None:
            if (
                not _t.subscribers
                and not _t.stream_callbacks
                and _t.on_publish_stats is None
                and _t.flight is None
                and _t.lineage is None
            ):
                return  # nobody downstream: skip the transform dispatch
            lin = getattr(_qr, "lineage", None)
            if lin is not None and _t.lineage is not None:
                # per-publish producer capture (observability/lineage.py):
                # the arena notes WHICH recorded query stamped this seq
                # range, so multi-producer streams resolve each record to
                # its actual producer instead of listing candidates
                from siddhi_tpu.observability.lineage import (
                    publisher_context,
                )

                with publisher_context(_qr.query_id, lin):
                    _t.publish_batch(rename(transform(out_batch)), now)
                return
            _t.publish_batch(rename(transform(out_batch)), now)

        qr.publish_fn = publish
        # fused-ingest eligibility checks the live target junction directly
        qr._insert_target_junction = target_junction

    def _table_guard(self, qr, receive, in_schema: StreamSchema):
        """Wrap a query receive with the @OnError policy of the table it
        mutates: the mutating query's dispatch is the table's host-side
        failure boundary (mutations compile into the query step), so its
        failures route to the table's policy instead of the input stream's
        — or the sender. Identity when the query mutates no guarded table."""
        tid = getattr(qr, "_mutates_table", None)
        action = self._table_fault.get(tid) if tid is not None else None
        if action is None:
            return receive

        def guarded(batch: EventBatch, now: int, *a, **kw) -> None:
            try:
                receive(batch, now, *a, **kw)
            except Exception as e:
                self._on_table_failure(tid, action, in_schema, batch, now, e)

        return guarded

    def _on_table_failure(
        self, tid: str, action: str, in_schema: StreamSchema,
        batch: EventBatch, now: int, exc: Exception,
    ) -> None:
        import logging

        log = logging.getLogger(__name__)
        sm = self.statistics_manager
        if sm is not None:
            sm.error_tracker(f"table.{tid}").add(1)
        if action == "STORE":
            from siddhi_tpu.core.error_store import ORIGIN_TABLE, make_entry

            store = self.manager.error_store
            try:
                events = in_schema.from_batch(batch, self.interner)
            except Exception:
                events = []
            store.store(make_entry(
                self.name, ORIGIN_TABLE, tid, exc,
                events=[(ts, tuple(d)) for ts, _k, d in events],
                # the mutating query's input stream: replay re-drives the
                # batch through it (the table itself takes no direct input)
                sink_ref=in_schema.stream_id,
            ))
            return
        log.error(
            "table '%s': dropping a failed mutation batch "
            "(@OnError action='LOG'): %s", tid, exc, exc_info=exc,
        )

    def _wire_query_lineage(self, qr) -> None:
        """Arm the query's provenance recorder when @app:lineage is on.
        Runs at construction time — BEFORE anything can trace the jitted
        step, so the `__lin.*` lane structure is part of every program
        (hot-deployed queries ride the same path via _add_query*)."""
        cfg = self._lineage_cfg
        if cfg is None:
            return
        try:
            qr.arm_lineage(cfg)
        except Exception:
            import logging

            logging.getLogger(__name__).warning(
                "lineage could not be armed for query '%s'",
                getattr(qr, "query_id", "?"), exc_info=True,
            )

    def _wire_query_stats(self, qr, qid: str):
        """Attach latency + device-budget trackers to a query runtime;
        returns the latency tracker (or None with statistics off)."""
        sm = self.statistics_manager
        if sm is None:
            return None
        qr.device_step_tracker = sm.device_time_tracker(f"query.{qid}", "step")
        qr.sync_stall_tracker = sm.device_time_tracker(
            f"query.{qid}", "sync_stall"
        )
        # compile telemetry + waterfall sub-stage attribution for the
        # per-batch jitted step (observability/profiler.py)
        qr.compile_telemetry = sm.compile_telemetry
        qr.profiler = sm.profiler
        return sm.latency_tracker(f"query.{qid}")

    def _timer_batch(self, schema: StreamSchema, t_ms: int) -> EventBatch:
        from siddhi_tpu.core.event import KIND_TIMER

        nulls = tuple(None for _ in schema.attrs)
        return schema.to_batch(
            [t_ms], [nulls], self.interner,
            capacity=self.batch_size, kinds=[KIND_TIMER],
        )

    def _add_query(self, qid: str, query: Query) -> None:
        if qid in self.queries:
            raise SiddhiAppCreationError(f"duplicate query name '{qid}'")
        stream = query.input_stream
        if isinstance(stream, JoinInputStream):
            self._add_join_query(qid, query)
            return
        if isinstance(stream, StateInputStream):
            self._add_pattern_query(qid, query)
            return
        if not isinstance(stream, SingleInputStream):
            raise SiddhiAppCreationError(
                f"{type(stream).__name__} queries land in later milestones"
            )
        in_schema = self.stream_schemas.get(stream.stream_id)
        src_junction = None
        if in_schema is None and stream.stream_id in self.named_windows:
            # `from W`: consume the named window's emission stream
            nw = self.named_windows[stream.stream_id]
            in_schema = nw.schema
            src_junction = nw.out_junction
        if in_schema is None:
            raise DefinitionNotExistError(
                f"query '{qid}': stream '{stream.stream_id}' is not defined"
            )
        qr = QueryRuntime(
            query, qid, in_schema, self.interner,
            group_capacity=self.group_capacity,
            tables=self.tables,
            time_capacity=self.time_capacity,
            batch_size=self.batch_size,
        )
        self._wire_query_lineage(qr)
        self.queries[qid] = qr
        self._wire_insert(qr)

        decode = self._decode
        in_junction = src_junction or self._junction(stream.stream_id)
        lt = self._wire_query_stats(qr, qid)

        def receive(
            batch: EventBatch, now: int, _qr=qr, _lt=lt, _qid=qid,
            _schema=in_schema,
        ) -> None:
            dbg = self._debugger
            if dbg is not None:
                from siddhi_tpu.core.debugger import QueryTerminal

                dbg.check(
                    _qid, QueryTerminal.IN,
                    lambda: [Event(t, d) for t, _k, d in decode(_schema, batch)],
                )
            if _lt is not None:
                _lt.mark_in()
            try:
                with self._process_lock:
                    out_batch, aux = _qr.receive(batch, now)
                    _qr.route_output(out_batch, now, decode)
            finally:
                if _lt is not None:
                    _lt.mark_out()
            if dbg is not None:
                dbg.check(
                    _qid, QueryTerminal.OUT,
                    lambda: [
                        Event(t, d)
                        for t, _k, d in decode(_qr.out_schema, out_batch)
                    ],
                )
            self._maybe_schedule(_qr, aux)

        self._wire_subscribe(
            in_junction, self._table_guard(qr, receive, in_schema),
            name=f"query.{qid}",
        )
        from siddhi_tpu.core.ingest import FuseEndpoint

        # under @app:shard(axis='keys') an eligible grouped query joins the
        # fused group like any other: its step and its [D] state are the
        # KeyShardedGroupExec's (parallel/keyshard.py arms it at start,
        # before any program is built), and the chunk program runs on the
        # keys mesh (core/ingest.py _mesh_placement)
        self._wire_fuse_candidate(in_junction, FuseEndpoint(
            qr,
            impl_factory=lambda _qr=qr: (_qr._keyshard or _qr)._step_impl,
            init_state=lambda now, _qr=qr: (
                _qr._keyshard or _qr
            ).init_state(),
            latency_tracker=lt,
        ))

        if qr.needs_scheduler:
            def fire(t_ms: int, _qr=qr, _schema=in_schema) -> None:
                if getattr(_qr, "_removed", False):
                    return  # hot-undeployed with a timer still pending
                batch = self._timer_batch(_schema, t_ms)
                with self._process_lock:
                    out_batch, aux = _qr.receive(batch, t_ms)
                    _qr.route_output(out_batch, t_ms, decode)
                self._maybe_schedule(_qr, aux)

            qr.timer_target = fire

    def _add_pattern_query(self, qid: str, query: Query) -> None:
        from siddhi_tpu.core.pattern_runtime import PatternQueryRuntime

        # pre-validate every referenced stream: the NFA builder indexes
        # stream_schemas directly, which would surface a raw KeyError with no
        # stream/query context (fallback path when analysis is disabled)
        from siddhi_tpu.query_api.execution import iter_state_streams

        for s in iter_state_streams(query.input_stream.state):
            if s.stream_id not in self.stream_schemas:
                raise DefinitionNotExistError(
                    f"query '{qid}': pattern stream '{s.stream_id}' is not "
                    "defined (patterns consume streams, not tables or windows)"
                )

        token_capacity = self._capacity_annotation("app:patternCapacity", 128)
        count_capacity = self._capacity_annotation("app:countCapacity", 8)
        pattern_chunk = self._capacity_annotation("app:patternChunk", 0)
        qr = PatternQueryRuntime(
            query,
            qid,
            self.stream_schemas,
            self.interner,
            group_capacity=self.group_capacity,
            token_capacity=token_capacity,
            count_capacity=count_capacity,
            batch_size=self.batch_size,
            tables=self.tables,
            pattern_chunk=pattern_chunk or None,
        )
        self._wire_query_lineage(qr)
        self.queries[qid] = qr
        self._wire_insert(qr)
        decode = self._decode
        lt = self._wire_query_stats(qr, qid)

        def receive(batch: EventBatch, now: int, sid: str, _qr=qr, _lt=lt) -> None:
            if _lt is not None:
                _lt.mark_in()
            try:
                with self._process_lock:
                    out_batch, aux = _qr.receive(batch, now, sid)
                    _qr.route_output(out_batch, now, decode)
            finally:
                if _lt is not None:
                    _lt.mark_out()
            self._maybe_schedule(_qr, aux)

        from siddhi_tpu.core.ingest import FuseEndpoint

        for sid in qr.prog.stream_ids:
            sj = self._junction(sid)
            self._wire_subscribe(
                sj,
                self._table_guard(
                    qr,
                    lambda b, now, _sid=sid: receive(b, now, _sid),
                    self.stream_schemas[sid],
                ),
                name=f"query.{qid}",
            )
            ep = FuseEndpoint(
                qr,
                impl_factory=lambda _qr=qr, _sid=sid: _qr._make_step(_sid),
                init_state=lambda now, _qr=qr: _qr.init_state(now),
                latency_tracker=lt,
            )
            ep.lineage_tag = sid  # recorder shadows are per input stream
            self._wire_fuse_candidate(sj, ep)

        if qr.needs_scheduler:
            def fire(t_ms: int, _qr=qr) -> None:
                if getattr(_qr, "_removed", False):
                    return
                batch = _pattern_timer_batch(t_ms)
                with self._process_lock:
                    out_batch, aux = _qr.receive_timer(batch, t_ms)
                    _qr.route_output(out_batch, t_ms, decode)
                self._maybe_schedule(_qr, aux)

            qr.timer_target = fire

    def _add_join_query(self, qid: str, query: Query) -> None:
        from siddhi_tpu.core.join import DEFAULT_JOIN_CAPACITY, JoinQueryRuntime

        join = query.input_stream
        # aggregation join sides expose the merged buckets view filtered by
        # the join's within/per clause (reference: AggregationRuntime joins)
        agg_findables = {}
        for s in (join.left, join.right):
            if s.stream_id in self.aggregations:
                from siddhi_tpu.core.aggregation import (
                    AggFindable,
                    parse_per,
                    parse_within_value,
                )
                from siddhi_tpu.query_api.expression import (
                    AttributeFunction,
                    Constant,
                )

                if join.per is None or not isinstance(join.per, Constant):
                    raise SiddhiAppCreationError(
                        "joining an aggregation needs per '<duration>'"
                    )
                within = None
                w = join.within
                if isinstance(w, AttributeFunction) and w.name == "__within_range__":
                    lo, hi = w.parameters
                    if not (isinstance(lo, Constant) and isinstance(hi, Constant)):
                        raise SiddhiAppCreationError(
                            "'within' operands must be constants"
                        )
                    within = (
                        parse_within_value(lo.value)[0],
                        parse_within_value(hi.value)[0],
                    )
                elif isinstance(w, Constant):
                    within = parse_within_value(w.value)
                elif w is not None:
                    raise SiddhiAppCreationError(
                        "'within' operands must be constants"
                    )
                agg_findables[s.stream_id] = AggFindable(
                    self.aggregations[s.stream_id],
                    parse_per(join.per.value),
                    within,
                )
        schemas = []
        for s in (join.left, join.right):
            sch = self.stream_schemas.get(s.stream_id)
            if sch is None and s.stream_id in self.tables:
                sch = self.tables[s.stream_id].schema
            if sch is None and s.stream_id in self.named_windows:
                sch = self.named_windows[s.stream_id].schema
            if sch is None and s.stream_id in agg_findables:
                sch = agg_findables[s.stream_id].schema
            if sch is None:
                raise DefinitionNotExistError(
                    f"query '{qid}': join stream '{s.stream_id}' is not defined"
                )
            schemas.append(sch)
        join_capacity = self._capacity_annotation(
            "app:joinCapacity", DEFAULT_JOIN_CAPACITY
        )
        qr = JoinQueryRuntime(
            query, qid, schemas[0], schemas[1], self.interner,
            group_capacity=self.group_capacity, join_capacity=join_capacity,
            tables=self.tables,
            findables={**self.tables, **self.named_windows, **agg_findables},
            time_capacity=self.time_capacity,
        )
        self._wire_query_lineage(qr)
        self.queries[qid] = qr
        self._wire_insert(qr)
        decode = self._decode
        lt = self._wire_query_stats(qr, qid)

        def receive_side(
            batch: EventBatch, now: int, side: str, _qr=qr, _lt=lt
        ) -> None:
            if _lt is not None:
                _lt.mark_in()
            try:
                with self._process_lock:
                    out_batch, aux = _qr.receive(batch, now, side)
                    _qr.route_output(out_batch, now, decode)
            finally:
                if _lt is not None:
                    _lt.mark_out()
            if "next_timer" in aux:
                self._schedule_at(aux, _qr.timer_targets.get(side))

        from siddhi_tpu.core.ingest import FuseEndpoint

        # self-joins: one subscription drives left then right, in that order
        # (reference: JoinInputStreamParser self-join double dispatch)
        if join.left.stream_id == join.right.stream_id:
            j = self._junction(join.left.stream_id)
            self._wire_subscribe(
                j,
                self._table_guard(
                    qr,
                    lambda b, now: (
                        receive_side(b, now, "l"), receive_side(b, now, "r")
                    ),
                    schemas[0],
                ),
                name=f"query.{qid}",
            )

            def _both_sides_impl(_qr=qr):
                import jax.numpy as jnp

                def impl(st, tst, b, now):
                    st, tst, _o1, aux1 = _qr._step_impl(st, tst, b, now, "l")
                    st, tst, out, aux2 = _qr._step_impl(st, tst, b, now, "r")
                    # lineage lanes must NOT be bool-merged across the two
                    # halves: re-key them side-tagged (`__lin@l.` / `__lin@r.`)
                    # so the recorder replays l then r, the per-batch order
                    merged = {}
                    for side_aux, tag in ((aux1, "l"), (aux2, "r")):
                        for k, v in side_aux.items():
                            if k.startswith("__lin."):
                                merged[f"__lin@{tag}." + k[len("__lin."):]] = v
                    for k, v in aux2.items():
                        if not k.startswith("__lin"):
                            merged[k] = v
                    for k, v in aux1.items():
                        if k == "next_timer" or k.startswith("__lin"):
                            continue
                        if k in merged:
                            merged[k] = (
                                jnp.asarray(v).astype(bool)
                                | jnp.asarray(merged[k]).astype(bool)
                            )
                        else:
                            merged[k] = v
                    return st, tst, out, merged

                return impl

            self._wire_fuse_candidate(j, FuseEndpoint(
                qr, impl_factory=_both_sides_impl,
                init_state=lambda now, _qr=qr: _qr.init_state(),
                latency_tracker=lt,
            ))
        else:
            for side, stream in (("l", join.left), ("r", join.right)):
                nw = qr.window_sides[side]
                if nw is not None:
                    # named-window side: driven by the window's emissions
                    # (no FuseEndpoint: that junction never sees send_columns,
                    # and the missing candidate keeps it per-batch)
                    self._wire_subscribe(
                        nw.out_junction,
                        lambda b, now, _s=side: receive_side(b, now, _s),
                        name=f"query.{qid}",
                    )
                elif not qr.table_sides[side]:
                    sj = self._junction(stream.stream_id)
                    self._wire_subscribe(
                        sj,
                        self._table_guard(
                            qr,
                            lambda b, now, _s=side: receive_side(b, now, _s),
                            schemas[0 if side == "l" else 1],
                        ),
                        name=f"query.{qid}",
                    )
                    ep = FuseEndpoint(
                        qr,
                        impl_factory=lambda _qr=qr, _s=side: (
                            lambda st, tst, b, now: _qr._step_impl(
                                st, tst, b, now, _s
                            )
                        ),
                        init_state=lambda now, _qr=qr: _qr.init_state(),
                        latency_tracker=lt,
                    )
                    ep.lineage_tag = side  # recorder side shadows
                    self._wire_fuse_candidate(sj, ep)

        for side, schema in qr.side_schemas.items():
            if qr.needs_scheduler[side]:
                def fire(t_ms: int, _side=side, _schema=schema, _qr=qr) -> None:
                    if getattr(_qr, "_removed", False):
                        return
                    receive_side(self._timer_batch(_schema, t_ms), t_ms, _side)

                qr.timer_targets[side] = fire

    def _decode(
        self, schema: StreamSchema, batch: EventBatch, *stall_trackers, wf=None
    ):
        return schema.from_batch(
            batch, self.interner, *stall_trackers, wf=wf
        )

    def _maybe_schedule(self, qr: QueryRuntime, aux: dict) -> None:
        hnt = getattr(qr, "host_next_timer", None)
        if hnt is not None:
            if getattr(qr, "timer_target", None) is not None:
                self._scheduler.start()
                self._scheduler.notify_at(hnt(self.clock()), qr.timer_target)
            return
        if not qr.needs_scheduler or "next_timer" not in aux:
            return
        self._schedule_at(aux, qr.timer_target)

    def _arm_rate_limiter(self, qr) -> None:
        """Recurring flush timer for time/snapshot rate limiters
        (reference: time-based OutputRateLimiter scheduler wiring)."""
        rl = getattr(qr, "rate_limiter", None)
        if rl is None or rl.period_ms is None:
            return
        period = rl.period_ms

        def fire(t_ms: int, _qr=qr, _rl=rl) -> None:
            if not self._running or getattr(_qr, "_removed", False):
                return  # stopped, or hot-undeployed: stop re-arming
            with self._process_lock:
                _qr._deliver(_rl.on_timer(t_ms), t_ms)
            self._scheduler.notify_at(t_ms + period, fire)

        self._scheduler.start()
        self._scheduler.notify_at(self.clock() + period, fire)

    def _schedule_at(self, aux: dict, target) -> None:
        if target is None or "next_timer" not in aux:
            return
        from siddhi_tpu.core.windows import NO_TIMER

        t = int(aux["next_timer"])
        if t < int(NO_TIMER):
            self._scheduler.start()
            self._scheduler.notify_at(t, target)

    # ---- public API (reference: SiddhiAppRuntime callbacks/handlers) -----

    def get_input_handler(self, stream_id: str) -> InputHandler:
        j = self._junction(stream_id)
        h = InputHandler(j, lambda: self.clock())
        if self._playback_clock is not None:
            h = _PlaybackInputHandler(h, self._playback_clock)
        if self._watermark is not None:
            # @app:watermark bounded reorder stage, OUTSIDE the playback
            # wrapper so the clock only advances when ordered rows are
            # RELEASED (never on raw disordered arrival), but inside
            # admission/disorder (core/watermark.py)
            h = _WatermarkInputHandler(
                self._watermark, stream_id, h,
                self.stream_schemas[stream_id].attr_names,
            )
        from siddhi_tpu.testing import faults as _faults

        if _faults.ACTIVE is not None:
            # `ingest_disorder` transform site: only wrapped while a fault
            # plan is live, so normal operation pays nothing
            h = _DisorderInputHandler(h, f"{self.name}:{stream_id}")
        if self._admission is not None:
            # @app:admission gate, outermost: over-quota/over-bound sends
            # block/shed/error BEFORE any encode work (core/admission.py)
            from siddhi_tpu.core.admission import AdmittedInputHandler

            h = AdmittedInputHandler(h, self._admission, j)
        return h

    input_handler = get_input_handler

    def _fault_junction_for(self, stream_id: str):
        """The `!S` side junction of a stream (late-event diversion target),
        or None when no fault schema was defined for it."""
        fid = "!" + stream_id
        if fid not in self.stream_schemas:
            return None
        return self._junction(fid)

    def _aggregations_for_stream(self, stream_id: str) -> list:
        """Aggregation runtimes fed by `stream_id` (the late.policy='apply'
        re-open targets)."""
        return [
            ar for aid, ar in self.aggregations.items()
            if self._agg_inputs.get(aid) == stream_id
        ]

    def drain_watermarks(self) -> None:
        """Flush every @app:watermark reorder buffer and catch the clock up
        to the newest event seen — the explicit end-of-feed signal (also
        run automatically at shutdown). No-op when watermarks are off."""
        if self._watermark is not None:
            self._watermark.drain()

    # ---- zero-downtime churn (core/churn.py) ------------------------------

    def add_query(self, query, seed="checkpoint") -> str:
        """Hot-deploy one query into this (possibly running) app without
        draining it: parse -> SA130 lint against the live symbols ->
        construct + prewarm off-line -> splice into the junction fan-out
        under the app process lock, seeding windows/patterns from the last
        checkpoint when a compatible `query:<id>` element exists
        (`seed='checkpoint'`, the default; `seed='cold'` skips).
        Fusion groups re-form around the grown wiring; surviving queries'
        emissions are byte-identical across the splice. Returns the
        assigned query id. The retained AST grows too, so a supervised
        restart rebuilds the app WITH the hot-deployed query."""
        from siddhi_tpu.core.churn import add_query as _add

        return _add(self, query, seed=seed)

    def remove_query(self, qid: str) -> None:
        """Hot-undeploy one top-level query (inverse of add_query): it is
        unspliced under the process lock, dropped from the retained AST,
        and the fusion groups re-form over the shrunk wiring."""
        from siddhi_tpu.core.churn import remove_query as _remove

        _remove(self, qid)

    def replay_target_available(self, entry) -> bool:
        """May `replay_error(entry)` be dispatched WITHOUT blocking? False
        for sink entries whose target transport is still disconnected and
        publishes under `on.error='WAIT'` (the replay would block until the
        transport reconnects) — `manager.replay_errors(skip_unavailable=
        True)` consults this so one dead sink cannot hold every other app's
        entries hostage."""
        from siddhi_tpu.core.error_store import ORIGIN_SINK

        if not self._running:
            return False
        if entry.origin != ORIGIN_SINK:
            return True
        for sink in self.sinks:
            for s in getattr(sink, "sinks", None) or [sink]:
                if s.stream_id != entry.stream_id:
                    continue
                if entry.sink_ref and s.sink_ref != entry.sink_ref:
                    continue
                return s.on_error != "WAIT" or s.connected
        return True  # no matching sink: replay_error returns False quickly

    def replay_error(self, entry) -> bool:
        """Re-drive one stored ErroneousEvent through its origin. Stream
        (and table-mutation) entries re-enter the input handler (and re-run
        every downstream query); sink entries re-publish their mapped
        payload under the sink's on.error policy; source entries re-deliver
        the raw wire payload through the source's mapper. Returns True when
        the replay was dispatched."""
        from siddhi_tpu.core.error_store import (
            ORIGIN_SINK,
            ORIGIN_SOURCE,
            ORIGIN_STREAM,
            ORIGIN_TABLE,
        )

        if entry.app_name != self.name:
            return False
        if not self._running:
            # sinks/sources aren't connected before start(): the entry stays
            # stored until the app is up (supervisor replays AFTER resume)
            return False
        if entry.origin in (ORIGIN_STREAM, ORIGIN_TABLE):
            # table entries re-drive the mutating query's input batch
            # through its input stream (stashed in sink_ref)
            sid = (
                entry.stream_id
                if entry.origin == ORIGIN_STREAM
                else entry.sink_ref
            )
            if sid not in self.stream_schemas or not entry.events:
                return False
            # RAW handler, not get_input_handler(): the admission gate must
            # not apply — these events were admitted once already, and a
            # quota-starved gate would silently shed the replay while the
            # caller purges the entry (permanent loss). Timestamps are
            # explicit, so the playback wrapper is unnecessary too.
            from siddhi_tpu.core.supervision import failure_ownership

            h = InputHandler(self._junction(sid), lambda: self.clock())
            # failure_ownership: a replay that explodes raises to the
            # replay caller and the entry stays stored — it must not ALSO
            # flag the app as crashed, or a poison entry puts a supervised
            # app into a restart->replay->crash loop
            with failure_ownership():
                h.send_many(
                    [row for _ts, row in entry.events],
                    timestamps=[ts for ts, _row in entry.events],
                )
            return True
        if entry.origin == ORIGIN_SOURCE:
            for src in self.sources:
                if src.stream_id != entry.stream_id:
                    continue
                # replay through the mapper again; True means "safe to
                # purge": delivered, or the source's own on.error path
                # re-captured the payload (STORE re-stores on failure)
                if src.paused:
                    # deliver() returns False WITHOUT running the failure
                    # path — nothing was re-stored, so the entry must stay
                    return False
                # raw handler override: the wired one is admission-gated,
                # and a shed replay would report delivered -> purged
                raw = InputHandler(
                    self._junction(src.stream_id), lambda: self.clock()
                )
                ok = src.deliver(entry.payload, handler=raw)
                if ok:
                    return True
                # STORE only re-captured the payload when a store is
                # actually wired; otherwise _on_deliver_failure dropped it
                # and purging here would make the loss permanent
                return (
                    src.on_error == "STORE"
                    and src.error_store_fn is not None
                    and src.error_store_fn() is not None
                )
            return False
        if entry.origin == ORIGIN_SINK:
            # target the exact sink that failed (by sink_ref); fall back to
            # the first stream_id match for entries from older stores. True
            # means "safe to purge": delivered, or the sink's own failure
            # path re-captured the payload (STORE always re-stores; WAIT only
            # drops at shutdown when no store is wired). A LOG/RETRY sink
            # that fails again DROPS the payload, so the entry must survive.
            for sink in self.sinks:
                for s in getattr(sink, "sinks", None) or [sink]:
                    if s.stream_id != entry.stream_id:
                        continue
                    if entry.sink_ref and s.sink_ref != entry.sink_ref:
                        continue
                    ok = s.publish_guarded(entry.payload)
                    return ok or s.on_error == "STORE" or (
                        s.on_error == "WAIT" and s.error_store_fn is not None
                    )
            return False
        return False

    def set_exception_handler(self, handler) -> None:
        """Route subscriber-dispatch failures to `handler(exc)` instead of
        propagating to the sender (reference: SiddhiAppRuntime.handleExceptionWith
        for the Disruptor ExceptionHandler)."""
        for j in self.junctions.values():
            j.exception_handler = handler
        self._exception_handler = handler

    def debug(self):
        """Step-mode debugger (reference: SiddhiAppRuntime.debug:509)."""
        from siddhi_tpu.core.debugger import SiddhiDebugger

        if self._debugger is None:
            self._debugger = SiddhiDebugger(self)
        return self._debugger

    def enable_stats(self, enabled: bool) -> None:
        """Toggle metric collection AND tracing at runtime (reference:
        SiddhiAppRuntime.enableStats:682). Disabling stops every tracker at
        its gate check — the hot path cost becomes one attribute read."""
        if self.statistics_manager is not None:
            self.statistics_manager.enabled = enabled
        if self.tracer is not None:
            self.tracer.enabled = enabled

    def traces(self) -> list:
        """Completed sampled traces (oldest first), each a JSON-serializable
        dict of spans crossing ingress junction -> query -> sink. Empty when
        `@app:statistics(trace.sample=...)` is not configured."""
        return self.tracer.traces() if self.tracer is not None else []

    # ---- EXPLAIN ANALYZE + profiling (observability/explain.py,
    # observability/profiler.py) --------------------------------------------

    def explain(self, fmt: str = "text"):
        """The app's dataflow plan annotated with live counters (events
        in/out, selectivity, latency, device-time share, compile ledger) —
        EXPLAIN ANALYZE for the running app. fmt='text' renders; 'dict'/
        'json' returns the raw plan. Works without `@app:statistics` too
        (topology only, no counters)."""
        from siddhi_tpu.observability.explain import explain

        return explain(self, fmt=fmt)

    def explain_plan(self) -> dict:
        """`explain(fmt='dict')` — the raw node/edge plan."""
        return self.explain(fmt="dict")

    def profile_report(self) -> dict:
        """Compile telemetry + slowest-chunk waterfalls + high latency
        quantiles (`/profile` payload); None without `@app:statistics`.
        Plan-driven fused groups (core/fusion_exec.py) append their
        achieved-vs-predicted dispatch-reduction ledger under
        `fused_groups`, keyed by the cost model's component taxonomy
        (`stream.<S>.fusedgroup.<g>`)."""
        sm = self.statistics_manager
        if sm is None:
            return None
        rep = sm.profile_report()
        groups = []
        for j in list(self.junctions.values()):
            fi = j.fused_ingest
            gr = fi.group_report() if fi is not None else None
            if gr is not None:
                groups.append({"stream": j.schema.stream_id, **gr})
        if groups:
            rep["fused_groups"] = groups
        if self._shard is not None:
            # per-device dispatch/event counts of the sharded runtime mode
            # (parallel/shard.py), beside the fused-group ledger
            rep["shard"] = self._shard.describe_state()
        return rep

    def calibration_report(self):
        """Plan-vs-actual calibration ledger: every static prediction
        paired with its live meter, error ratios + EWMA drift, mispricing
        flags (`/calibration` payload, observability/calibration.py); None
        without `@app:statistics` (the zero-overhead gate)."""
        c = self._calibration
        return c.report() if c is not None else None

    def slo_report(self):
        """Multi-window SLO burn rates for this app's `@app:slo`
        objectives (`/slo` payload, observability/slo.py); None without
        the annotation."""
        s = self._slo
        return s.report() if s is not None else None

    # ---- state introspection (observability/introspect.py) ----------------

    def snapshot_status(self) -> dict:
        """Live per-component state of this app: junction queue depths and
        wiring, window type/fill/capacity, NFA active-instance counts,
        aggregation buckets/watermarks, table row counts, ingest-pipeline
        depth/occupancy/slots in flight. Pull-only: nothing is collected
        until asked (served as `/status` + `/status.json` when
        `manager.serve_metrics()` is up)."""
        # list() snapshots: junctions are created lazily (selfmon's system
        # junction arms from the scheduler thread, store-query targets from
        # callers), and a plain dict iteration racing an insert raises
        status: dict = {
            "app": self.name,
            "running": self._running,
            "streams": {
                sid: j.describe_state()
                for sid, j in list(self.junctions.items())
            },
            "queries": {
                qid: qr.describe_state() for qid, qr in self.queries.items()
            },
            "windows": {
                wid: nw.describe_state()
                for wid, nw in self.named_windows.items()
            },
            "tables": {
                tid: t.describe_state() for tid, t in self.tables.items()
            },
            "aggregations": {
                aid: ar.describe_state()
                for aid, ar in self.aggregations.items()
            },
        }
        if self._watermark is not None:
            status["watermark"] = self._watermark.describe_state()
            # the stream-level watermark beside each aggregation's
            # per-duration bucket watermarks (ISSUE 16 satellite: uniform
            # watermark surfacing)
            for aid, agg_status in status["aggregations"].items():
                agg_status["stream_watermark_ms"] = self._watermark.watermark_of(
                    self._agg_inputs.get(aid, "")
                )
        if self._shard is not None:
            status["shard"] = self._shard.describe_state()
        if self._selfmon is not None:
            status["selfmon"] = self._selfmon.describe_state()
        if self._slo is not None:
            status["slo"] = self._slo.describe_state()
        if self._calibration is not None:
            status["calibration"] = self._calibration.describe_state()
        if self._admission is not None:
            status["admission"] = self._admission.describe_state()
        if self._autopersist is not None:
            status["autopersist"] = self._autopersist.describe_state()
        if self._blackbox is not None:
            status["blackbox"] = self._blackbox.describe_state()
        health = getattr(self, "_health", None)
        if health is not None:
            status["health"] = health.describe_state()
        # churn ledger (core/churn.py; manager-owned so it survives
        # redeploys and supervised restarts)
        churn = self.manager.churn_stats(self.name, create=False)
        if churn is not None:
            status["churn"] = churn.describe_state()
        # process-wide, and served with or without @app:statistics
        status["compile_events"] = COMPILE_EVENTS.snapshot()
        return status

    # ---- flight recorder (observability/flight.py) ------------------------

    def flight_record(self, stream_id: str) -> list[tuple[int, tuple]]:
        """The last-N events through `stream_id`'s junction, oldest first,
        as (timestamp_ms, data_tuple) pairs. Raises when the stream has no
        recorder (enable with @flightRecorder(size='N') or
        SIDDHI_TPU_FLIGHT=N)."""
        j = self.junctions.get(stream_id)
        if j is None:
            raise DefinitionNotExistError(
                f"no stream '{stream_id}' in app '{self.name}'"
            )
        if j.flight is None:
            raise SiddhiAppCreationError(
                f"stream '{stream_id}' has no flight recorder — enable it "
                "with @flightRecorder(size='N') or SIDDHI_TPU_FLIGHT=N"
            )
        return j.flight.events()

    def flight_records(self) -> dict[str, list[tuple[int, tuple]]]:
        """Every recorded junction's ring, keyed by stream id (empty dict
        when no junction has a recorder)."""
        return {
            sid: j.flight.events()
            for sid, j in list(self.junctions.items())
            if j.flight is not None
        }

    # ---- black box & incident replay (observability/blackbox.py) ----------

    def incidents(self) -> list[dict]:
        """Incident bundles frozen by this runtime's black-box recorder,
        oldest first (empty when @app:blackbox is not armed)."""
        if self._blackbox is None:
            return []
        return self._blackbox.incident_index()

    def replay_incident(self, bundle, debug: bool = False, streams=None):
        """Deterministically replay an incident bundle (dict or path):
        rebuild the app from the bundle's retained AST under
        @app:playback, restore the pinned checkpoint, and re-feed the
        recorded rings in arrival order. With `debug=True` the returned
        IncidentReplay holds a live runtime with a SiddhiDebugger
        attached and feeding deferred to the caller."""
        from siddhi_tpu.observability.blackbox import replay_incident

        return replay_incident(bundle, debug=debug, streams=streams)

    # ---- lineage & provenance (observability/lineage.py) ------------------

    def lineage(self, target: str, index: int | None = None,
                depth: int = 6) -> dict:
        """Explain output `index` of `target` back to the exact input
        events (@app:lineage required). `target` is a query id (index = the
        query's k-th recorded output row) or a stream id (index = the
        junction's lineage seq id — its k-th valid CURRENT event); None
        picks the latest. The chain walks insert-into hops backward and
        decodes the contributing events from the per-stream arenas."""
        if self.lineage_ledger is None:
            raise SiddhiAppCreationError(
                f"app '{self.name}' has no lineage — enable it with "
                "@app:lineage(capacity='N')"
            )
        return self.lineage_ledger.resolve(target, index, depth)

    def lineage_report(self, resolve_recent: int = 1) -> dict:
        """The app's /lineage.json payload: per-stream arenas, per-query
        fan-in + recorded provenance, per-aggregation buckets (empty dict
        when @app:lineage is off)."""
        if self.lineage_ledger is None:
            return {}
        return self.lineage_ledger.report(resolve_recent=resolve_recent)

    def dump_traces(self, path: str | None = None, indent: int = 1) -> str:
        """JSON dump of `traces()`; also written to `path` when given."""
        import json as _json

        text = _json.dumps(self.traces(), indent=indent)
        if path is not None:
            with open(path, "w", encoding="utf-8") as f:
                f.write(text)
        return text

    def add_callback(self, name: str, callback: Callable) -> None:
        """Stream callback `cb(events: list[Event])` or query callback
        `cb(timestamp, in_events, removed_events)` — dispatched on arity by
        target: stream name vs @info query name (reference: addCallback overloads).
        """
        # retained for supervised rebuild (core/supervision.Supervisor
        # re-registers these on the replacement runtime after a restart)
        self._user_callbacks.append((name, callback))
        if name in self.queries:
            qr = self.queries[name]

            # all-C construction path: namedtuple __new__ measured ~1.5 us
            # per event against ~0.3 us for map(partial(tuple.__new__, ...))
            import operator
            from functools import partial

            _mk = partial(tuple.__new__, Event)
            _td = operator.itemgetter(0, 2)

            def qcb(ts, ins, removed, _cb=callback, _mk=_mk, _td=_td):
                _cb(
                    ts,
                    list(map(_mk, map(_td, ins))) if ins else None,
                    list(map(_mk, map(_td, removed))) if removed else None,
                )

            qr.query_callbacks.append(qcb)
            # raw-callback registry: the fused egress drain and the
            # per-batch path's `route_output` build Event lists once and
            # invoke user callbacks directly, skipping the triple->Event
            # re-extraction (only valid while the two lists stay in 1:1
            # correspondence; both check)
            if not hasattr(qr, "raw_query_callbacks"):
                qr.raw_query_callbacks = []
            qr.raw_query_callbacks.append(callback)
            # built here, at deploy, so that no send compiles
            from siddhi_tpu.native import load_event_builder

            load_event_builder()
            return
        if name in self.stream_schemas:
            j = self._junction(name)
            j.add_stream_callback(
                lambda rows, _cb=callback: _cb([Event(t, d) for t, d in rows])
            )
            return
        raise DefinitionNotExistError(f"no stream or query named '{name}'")

    def query(self, store_query) -> list:
        """One-shot pull query over tables (reference:
        SiddhiAppRuntime.query:264-299, cached per query string)."""
        from siddhi_tpu.core.store_query import StoreQueryRuntime
        from siddhi_tpu.query_api.execution import StoreQuery

        if isinstance(store_query, str):
            sqr = self._store_query_cache.get(store_query)
            if sqr is None:
                from siddhi_tpu.compiler.siddhi_compiler import SiddhiCompiler

                sq = SiddhiCompiler.parse_store_query(store_query)
                sqr = StoreQueryRuntime(
                    sq, self.tables, self.interner,
                    group_capacity=self.group_capacity,
                    windows=self.named_windows,
                    aggregations=self.aggregations,
                )
                self._store_query_cache[store_query] = sqr
        else:
            assert isinstance(store_query, StoreQuery)
            sqr = StoreQueryRuntime(
                store_query, self.tables, self.interner,
                group_capacity=self.group_capacity,
                windows=self.named_windows,
                aggregations=self.aggregations,
            )
        from siddhi_tpu.observability.metrics import timed

        lt = (
            self.statistics_manager.latency_tracker("storequery")
            if self.statistics_manager is not None
            else None
        )
        with timed(lt):
            with self._process_lock:
                return sqr.execute(self.clock())

    def _build_fused_ingest(self) -> None:
        """(Re)build the per-junction fused ingest engines from the LIVE
        wiring + the current FusionPlan (core/ingest.py, core/fusion_exec.py):
        plan-driven GROUP engines first (the FusionPlan's fusable subset
        runs as one chunk program, blocked queries ride the residual
        per-batch path, shared-window candidates reference one ring), then
        the all-or-nothing engine for junctions where every subscriber
        registered a FuseEndpoint (a plan group needs two queries, so this
        is the engine of every one-query junction). Called by start() and
        by the churn splice (core/churn.py) after the wiring grows/shrinks —
        the fusion groups re-form around the new query set. Key-sharded
        state re-arms on the rebuilt engines."""
        from siddhi_tpu.core.ingest import FusedJunctionIngest
        from siddhi_tpu.core.pipeline import resolve_pipeline_annotation

        chunk = self._capacity_annotation("app:ingestChunk", 32)
        fusion_configs: dict = {}
        try:
            from siddhi_tpu.core.fusion_exec import junction_fusion_configs

            fusion_configs = junction_fusion_configs(self)
        except Exception:
            import logging

            logging.getLogger(__name__).warning(
                "fusion planning failed for app '%s'; falling back to "
                "per-junction fusion only", self.name, exc_info=True,
            )
        from siddhi_tpu.core.wire import (
            build_wire_spec,
            wire_inference_enabled,
        )

        # value-analysis inferred wire hints (analysis/values.py): one
        # cheap AST pass per rebuild, overlaid under the declared hints
        # (declared wins per lane). Inference failure degrades to
        # declared-only, never to no wire.
        inferred: dict = {}
        if self._wire_enabled and wire_inference_enabled():
            from siddhi_tpu.analysis.values import infer_wire_hints_for_app

            inferred = infer_wire_hints_for_app(self.app)
        for j in list(self.junctions.values()):
            sid = j.schema.stream_id
            pipe_depth = self._pipeline_conf.get(
                sid, resolve_pipeline_annotation(None)
            )
            # analyzer-chosen per-column wire encodings (core/wire.py):
            # the static spec from declared types + @app:wire hints +
            # inferred overlay; None when nothing is statically encodable
            # (the sampled narrow wire stands alone) or wire encoding is
            # disabled
            spec = (
                build_wire_spec(
                    sid, j.schema.attrs, self._wire_hints,
                    capacity=j.batch_size, inferred=inferred,
                )
                if self._wire_enabled
                else None
            )
            cfg = fusion_configs.get(sid)
            if cfg is not None:
                j.fused_ingest = FusedJunctionIngest(
                    self, j, cfg["endpoints"], chunk_batches=chunk,
                    pipeline_depth=pipe_depth,
                    component=cfg["component"], residual=cfg["residual"],
                    share_sets=cfg["share_sets"],
                    plan_group=cfg["plan_group"],
                    wire_spec=spec, wire_enabled=self._wire_enabled,
                )
            elif j.fuse_candidates and len(j.fuse_candidates) == len(j.subscribers):
                j.fused_ingest = FusedJunctionIngest(
                    self, j, j.fuse_candidates, chunk_batches=chunk,
                    pipeline_depth=pipe_depth,
                    wire_spec=spec, wire_enabled=self._wire_enabled,
                )
        if self._shard is not None:
            self._shard.rearm_keyshard()
        # re-pair the calibration ledger against the AST that just formed
        # these engines: churn splices and fused re-formations re-price
        # automatically while cumulative mispriced counters survive (the
        # rearm_keyshard precedent — rebuild-owned re-arming)
        if self._calibration is not None:
            self._calibration.pair()

    def _teardown_fused_ingest(self) -> None:
        """Disable and close every fused ingest engine, splitting any
        cross-query aliased chain states first (PR 8's `_maybe_unshare`:
        followers get device copies, losslessly re-shareable by the next
        fused send). MUST run OUTSIDE the app process lock: a pipelined
        sender holds the engine's send lock while acquiring the process
        lock per chunk, so closing under the process lock would deadlock
        against it. While engines are down, sends ride the per-batch path
        — byte-identical by the fuse-on/off CI contract."""
        for j in list(self.junctions.values()):
            fi = j.fused_ingest
            if fi is None:
                continue
            j.fused_ingest = None  # new sends fall back per-batch now
            fi._disabled = True  # senders that already read `fi` bail out
            # close FIRST: it serializes on the engine's send lock, so an
            # in-flight send (already past the _disabled check) finishes —
            # and its writeback may re-alias shared chains — before the
            # unshare below splits them. Unsharing first would leave those
            # late-aliased states guardless: two per-batch steps donating
            # the same ring buffers.
            fi.close()
            try:
                fi._maybe_unshare()
            except Exception:
                import logging

                logging.getLogger(__name__).exception(
                    "unsharing stream '%s' during churn teardown failed",
                    j.schema.stream_id,
                )
            # shared-ring bookkeeping detaches: the members' states are
            # private buffers again until a rebuilt engine re-shares
            for ep in fi.endpoints:
                if getattr(ep.qr, "shared_ring", None) is not None:
                    ep.qr.shared_ring = None
                ep.qr._unshare_guard = None

    def start(self) -> None:
        self._running = True
        if not self._gc_spans:
            # `siddhi:gc` spans while any app runs (one hook per process)
            GC_SPANS.acquire()
            self._gc_spans = True
        # @app:fuse(disable='true') / SIDDHI_TPU_FUSE=0 skips the fused
        # ingest engines entirely (see _build_fused_ingest)
        if self._fuse_enabled:
            self._build_fused_ingest()
        # first-class sharded execution (parallel/shard.py): place
        # partitioned [P] state (and, under axis='keys', group-by and join
        # state) on the device mesh — resolved from @app:shard /
        # SIDDHI_TPU_SHARD at creation
        shard_devices, shard_axis = self._shard_conf
        if shard_devices >= 2:
            from siddhi_tpu.parallel.shard import ShardRuntime

            self._shard = ShardRuntime(self, shard_devices, shard_axis)
            self._shard.apply()
        if self.statistics_manager is not None:
            # device-memory metric per component (reference analog:
            # util/statistics/memory/ObjectSizeCalculator — here the bytes
            # are HBM buffers held by each component's carried state)
            def _tree_bytes(get_tree):
                def fn():
                    return sum(
                        getattr(leaf, "nbytes", 0)
                        for leaf in jax.tree_util.tree_leaves(get_tree())
                    )
                return fn

            sm = self.statistics_manager
            for qid, qr in self.queries.items():
                sm.register_memory(
                    f"query.{qid}", _tree_bytes(lambda _q=qr: _q.state)
                )
            for tid, t in self.tables.items():
                sm.register_memory(
                    f"table.{tid}", _tree_bytes(lambda _t=t: _t.state)
                )
                # table-op accounting: mutating steps + record-store flushes
                # (wired here so aggregation duration tables are covered too)
                t.mutation_stats = sm.throughput_tracker(f"table.{tid}").add
                t.flush_latency = sm.latency_tracker(f"table.{tid}.flush")
            for wid, w in self.named_windows.items():
                sm.register_memory(
                    f"window.{wid}", _tree_bytes(lambda _w=w: _w.state)
                )
            for aid, ar in self.aggregations.items():
                sm.register_memory(
                    f"aggregation.{aid}", _tree_bytes(lambda _a=ar: _a.state)
                )
            # pair the calibration ledger at start when no fused rebuild
            # already did (fuse disabled or no fusable junctions)
            if self._calibration is not None and \
                    self._calibration.generation == 0:
                self._calibration.pair()
            sm.start_reporting()
            if str(sm.reporter).lower() == "prometheus":
                # pull-based exposition: serve every app on this manager
                port = int(sm.options.get("port", "9464"))
                self.manager.serve_metrics(port)
        if self._playback_clock is not None:
            self._playback_clock.start_heartbeat()
        if self._watermark is not None:
            # idle heartbeat: quiet sources flush + go idle after
            # idle.timeout so they cannot stall the app watermark
            self._watermark.start()
            if self.statistics_manager is not None:
                self.statistics_manager.register_watermark(
                    self._watermark.describe_state
                )
        # absent-at-start patterns must arm their timers before any event
        # (reference: SiddhiAppRuntime.start -> eternalReferencedHolders.start)
        for qr in self.queries.values():
            if getattr(qr, "needs_scheduler", False) and hasattr(qr, "prime"):
                aux = qr.prime(self.clock())
                self._maybe_schedule(qr, aux)
            if getattr(qr, "host_next_timer", None) and getattr(qr, "timer_target", None):
                self._scheduler.start()
                self._scheduler.notify_at(
                    qr.host_next_timer(self.clock()), qr.timer_target
                )
            self._arm_rate_limiter(qr)
        # CEP-native self-monitoring: materialize the system junction NOW
        # (its lazy creation would otherwise happen on the scheduler thread,
        # racing concurrent junction-map readers) and arm the recurring feed
        # (observability/selfmon.py) before sources start publishing
        if self._selfmon is not None:
            from siddhi_tpu.observability.selfmon import SELFMON_STREAM_ID

            self._junction(SELFMON_STREAM_ID)
            self._selfmon.start()
        # SLO burn-rate evaluation (observability/slo.py): same junction
        # materialization + recurring-target contract as selfmon
        if self._slo is not None:
            from siddhi_tpu.observability.slo import SLO_STREAM_ID

            self._junction(SLO_STREAM_ID)
            self._slo.start()
        # @app:persist auto-checkpoint (core/supervision.AutoPersist): armed
        # only when a persistence store is actually wired — a missing store
        # would otherwise fail EVERY interval until someone noticed
        if self._autopersist is not None:
            if self.manager.persistence_store is None:
                import logging

                logging.getLogger(__name__).warning(
                    "app '%s' declares @app:persist but the manager has no "
                    "persistence store; auto-checkpointing is disabled "
                    "(call manager.set_persistence_store(...))", self.name,
                )
            else:
                self._autopersist.start()
        # @app:blackbox checkpoint pinner: pin the first base checkpoint
        # and re-pin every checkpoint.interval (default: window) so ring +
        # checkpoint always cover a coherent replayable interval
        if self._blackbox is not None:
            self._blackbox.start()
        # lifecycle ordering (reference: SiddhiAppRuntime.start:353-394):
        # sinks connect before sources so no event finds a dead egress;
        # triggers and sources begin last, into fully-wired queries
        for sink in self.sinks:
            sink.connect_with_retry()
        for src in self.sources:
            src.connect_with_retry()
        for tr in self.triggers.values():
            tr.start()

    def shutdown(self) -> None:
        self._running = False
        if self._gc_spans:
            GC_SPANS.release()
            self._gc_spans = False
        if self._watermark is not None:
            # tail delivery FIRST: release every buffered row through the
            # still-live junctions and fire the timers the final watermark
            # unlocks, before any ingest machinery stops
            self._watermark.stop()
            self._watermark.drain()
        for src in self.sources:
            src.stop()  # cancels pending reconnect retries too
        for tr in self.triggers.values():
            tr.stop()
        for j in self.junctions.values():
            if j.is_async:
                j.stop_async()
            if j.fused_ingest is not None:
                j.fused_ingest.close()  # stops the pipeline drain worker
        for sink in self.sinks:
            sink.stop()
        if self.statistics_manager is not None:
            self.statistics_manager.stop_reporting()
        if self._playback_clock is not None:
            self._playback_clock.stop()
        for qr in self.queries.values():
            qr.flush_aux_warnings()
        self._scheduler.shutdown()
        # flush AFTER the scheduler stops so no timer can re-dirty a table
        for t in self.tables.values():
            t.close_record_store()

    # ---- snapshot / persistence (reference: SiddhiAppRuntime.persist/
    # restore/restoreRevision/restoreLastRevision :560-600) -----------------

    @property
    def snapshot_service(self):
        svc = getattr(self, "_snapshot_service", None)
        if svc is None:
            from siddhi_tpu.core.persistence import SnapshotService

            svc = self._snapshot_service = SnapshotService(self)
        return svc

    def snapshot(self) -> bytes:
        return self.snapshot_service.full_snapshot()

    def restore(self, snapshot: bytes) -> None:
        self.snapshot_service.restore(snapshot)

    def _store(self):
        store = self.manager.persistence_store
        if store is None:
            raise SiddhiAppCreationError(
                "no persistence store set; call "
                "manager.set_persistence_store(...) first"
            )
        return store

    def persist(self) -> str:
        import time as _time

        for t in self.tables.values():
            t.flush_record_store()
        store = self._store()
        svc = self.snapshot_service
        if getattr(store, "incremental", False):
            data = svc.incremental_snapshot()
        else:
            data = svc.full_snapshot(track_base=True)
        # strictly monotone revision ids (two persists can share a millisecond)
        now = int(_time.time() * 1000)
        last = getattr(self, "_last_rev_ms", -1)
        now = max(now, last + 1)
        self._last_rev_ms = now
        revision = f"{now}_{self.name}"
        # fault-injection site `persist_save` (testing/faults.py): a failing
        # store save surfaces to the caller — AutoPersist counts it and
        # retries next interval, a manual persist() raises
        from siddhi_tpu.testing import faults as _faults

        if _faults.ACTIVE is not None:
            _faults.ACTIVE.check("persist_save", self.name)
        store.save(self.name, revision, data)
        # only now is the full payload durable: promote the staged delta
        # base (a failed save must NOT shift it, or every later cycle
        # emits deltas against a base revision that never reached the
        # store and restore silently no-ops or applies the wrong base)
        svc.commit_base()
        return revision

    def restore_revision(self, revision: str) -> None:
        store = self._store()
        # fault-injection site `persist_load` (testing/faults.py)
        from siddhi_tpu.testing import faults as _faults

        if _faults.ACTIVE is not None:
            _faults.ACTIVE.check("persist_load", self.name)
        data = store.load(self.name, revision)
        if data is None:
            raise SiddhiAppCreationError(f"no revision '{revision}'")
        if getattr(store, "incremental", False):
            # replay: the latest full snapshot at-or-before this revision,
            # plus every delta after it up to this revision
            chain = self._incremental_chain(store, upto=revision)
            self.snapshot_service.restore(*chain)
        else:
            self.snapshot_service.restore(data)

    def restore_last_revision(self) -> None:
        store = self._store()
        last = store.get_last_revision(self.name)
        if last is None:
            return
        self.restore_revision(last)

    def _incremental_chain(self, store, upto: str) -> list[bytes]:
        """[latest full at-or-before `upto`] + [the target delta] — every
        delta is diffed against the last persisted FULL snapshot, so earlier
        deltas must NOT be replayed (their leaves may have reverted since)."""
        import pickle as _pickle

        revs = [
            r for r in store.list_revisions(self.name)
            if int(r.split("_", 1)[0]) <= int(upto.split("_", 1)[0])
        ]
        base: bytes | None = None
        target: bytes | None = None
        for r in revs:
            data = store.load(self.name, r)
            if data is None:
                continue
            if _pickle.loads(data)["type"] == "full":
                base, target = data, None
            elif r == upto:
                target = data
        if base is None:
            return []
        return [base] if target is None else [base, target]


def _pattern_timer_batch(t_ms: int) -> EventBatch:
    from siddhi_tpu.core.event import KIND_TIMER
    import jax.numpy as _jnp

    return EventBatch(
        ts=_jnp.asarray([t_ms], dtype=_jnp.int64),
        kind=_jnp.asarray([KIND_TIMER], dtype=_jnp.int8),
        valid=_jnp.asarray([True]),
        cols={},
    )


class _PlaybackInputHandler:
    """Advances the playback clock to each event's timestamp before dispatch
    (reference: EventTimeBasedMillisTimestampGenerator wiring)."""

    def __init__(self, inner: InputHandler, clock):
        self._inner = inner
        self._pb = clock

    def send(self, data, timestamp=None):
        if timestamp is not None:
            self._pb.advance(timestamp)
        self._inner.send(data, timestamp)

    def send_many(self, rows, timestamps=None):
        if timestamps:
            self._pb.advance(max(timestamps))
        self._inner.send_many(rows, timestamps)

    def send_columns(self, timestamps, cols, now=None):
        import numpy as np

        if len(timestamps):
            self._pb.advance(int(np.max(timestamps)))
        self._inner.send_columns(timestamps, cols, now)


class _WatermarkInputHandler:
    """The @app:watermark bounded reorder stage (core/watermark.py): every
    send buffers into the stream's ReorderTracker, which re-emits rows at
    or below the watermark as ONE stably-sorted columnar send through the
    inner handler chain — so the fused/pipelined/sharded paths downstream
    always see ordered input — then drives the app watermark clock."""

    def __init__(self, wm, stream_id: str, inner, attr_names) -> None:
        self._wm = wm
        self._attrs = list(attr_names)
        self._tracker = wm.tracker(
            stream_id,
            deliver=lambda ts, cols, _h=inner: _h.send_columns(ts, cols),
        )

    def send(self, data, timestamp=None):
        import numpy as np

        if timestamp is None:
            timestamp = self._wm.runtime.clock()
        cols = {k: np.asarray([v]) for k, v in zip(self._attrs, data)}
        self._tracker.offer([int(timestamp)], cols)
        self._wm.advance_clock()

    def send_many(self, rows, timestamps=None):
        import numpy as np

        if not rows:
            return
        if timestamps is None:
            timestamps = [self._wm.runtime.clock()] * len(rows)
        cols = {
            k: np.asarray([r[i] for r in rows])
            for i, k in enumerate(self._attrs)
        }
        self._tracker.offer(timestamps, cols)
        self._wm.advance_clock()

    def send_columns(self, timestamps, cols, now=None):
        self._tracker.offer(timestamps, cols)
        self._wm.advance_clock()


class _DisorderInputHandler:
    """testing/faults `ingest_disorder` transform site: shuffles batch
    timestamps within a seeded jitter budget BEFORE the watermark reorder
    stage sees them (installed by get_input_handler only while a fault
    plan is active)."""

    def __init__(self, inner, key: str) -> None:
        self._inner = inner
        self._key = key

    def send(self, data, timestamp=None):
        self._inner.send(data, timestamp)

    def send_many(self, rows, timestamps=None):
        from siddhi_tpu.testing import faults

        if timestamps:
            perm = faults.permutation("ingest_disorder", self._key, timestamps)
            if perm is not None:
                rows = [rows[i] for i in perm]
                timestamps = [timestamps[i] for i in perm]
        self._inner.send_many(rows, timestamps)

    def send_columns(self, timestamps, cols, now=None):
        import numpy as np

        from siddhi_tpu.testing import faults

        perm = faults.permutation(
            "ingest_disorder", self._key, [int(t) for t in timestamps]
        )
        if perm is not None:
            idx = np.asarray(perm)
            timestamps = np.asarray(timestamps)[idx]
            cols = {k: np.asarray(v)[idx] for k, v in cols.items()}
        self._inner.send_columns(timestamps, cols, now)


def _make_insert_transform(output_events: OutputEventsFor):
    @jax.jit
    def t(batch: EventBatch) -> EventBatch:
        if output_events is OutputEventsFor.CURRENT:
            keep = batch.kind == KIND_CURRENT
        elif output_events is OutputEventsFor.EXPIRED:
            keep = batch.kind == KIND_EXPIRED
        else:
            keep = jnp.ones_like(batch.valid)
        return EventBatch(
            ts=batch.ts,
            kind=jnp.zeros_like(batch.kind),  # inserted events become CURRENT
            valid=batch.valid & keep,
            cols=batch.cols,
        )

    return t


def _make_rename(src: StreamSchema, dst: StreamSchema):
    """Map selector output column names onto the target stream's attribute names
    (positional, like the reference's insert-into meta mapping)."""
    if src.attr_names == dst.attr_names:
        return lambda b: b
    dst_names = dst.attr_names

    def rename(b: EventBatch) -> EventBatch:
        cols = dict(zip(dst_names, b.cols.values()))
        return EventBatch(b.ts, b.kind, b.valid, cols)

    return rename
