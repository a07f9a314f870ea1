"""Symbol table: the definitions pass of the semantic analyzer.

Collects every name a query can reference — streams (plus @OnError fault
streams and trigger streams), tables, named windows, aggregations, and script
functions — mirroring what `SiddhiAppRuntime.__init__` registers at creation
time (app_runtime.py stream_schemas / tables / named_windows / aggregations).

A schema is a dict `attr -> AttrType | None`; the whole schema may instead be
`OPEN` (None) meaning "attributes unknown" — e.g. downstream of an extension
stream function — in which case attribute checks are skipped rather than
guessed at.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from siddhi_tpu.core.types import AttrType
from siddhi_tpu.query_api.annotation import find_annotation
from siddhi_tpu.query_api.siddhi_app import SiddhiApp

from siddhi_tpu.analysis.diagnostics import Diagnostic

# schema type: dict[attr] -> AttrType | None (None = unknown attr type)
Schema = dict


@dataclasses.dataclass
class SymbolTable:
    streams: dict[str, Optional[Schema]] = dataclasses.field(default_factory=dict)
    tables: dict[str, Optional[Schema]] = dataclasses.field(default_factory=dict)
    windows: dict[str, Optional[Schema]] = dataclasses.field(default_factory=dict)
    aggregations: dict[str, Optional[Schema]] = dataclasses.field(default_factory=dict)
    # aggregation definitions by id (within/per clause checks need the
    # declared time_period durations)
    aggregation_defs: dict = dataclasses.field(default_factory=dict)
    # script-defined functions: name -> return AttrType
    functions: dict[str, AttrType] = dataclasses.field(default_factory=dict)
    # streams declaring @OnError(action='STREAM') (fault stream '!S' exists)
    fault_parents: set = dataclasses.field(default_factory=set)
    # streams carrying a @source / declared triggers (dataflow producers)
    sourced: set = dataclasses.field(default_factory=set)
    # streams carrying a @sink (dataflow consumers)
    sinked: set = dataclasses.field(default_factory=set)

    def consumable(self, stream_id: str) -> Optional[Schema]:
        """Schema for a `from X` source (stream, fault stream, or window);
        KeyError semantics are the caller's job — returns a sentinel miss."""
        if stream_id in self.streams:
            return self.streams[stream_id]
        if stream_id in self.windows:
            return self.windows[stream_id]
        raise KeyError(stream_id)

    def describe(self, stream_id: str) -> Optional[str]:
        """What a name IS, for better undefined-stream messages."""
        if stream_id in self.tables:
            return "table"
        if stream_id in self.aggregations:
            return "aggregation"
        return None


def _attrs_schema(definition, diags: list[Diagnostic], what: str) -> Schema:
    schema: Schema = {}
    for a in definition.attributes:
        if a.name in schema:
            diags.append(Diagnostic(
                "SA109",
                f"duplicate attribute '{a.name}' in {what} '{definition.id}'",
                getattr(a, "line", None), getattr(a, "col", None),
            ))
        schema[a.name] = a.type
    return schema


def _check_pipeline_annotation(
    sid: str, d, ann, diags: list[Diagnostic]
) -> None:
    """Validate `@pipeline(depth='N')` — the fused
    ingest pipeline's stream-level config. One SA112 per malformed element,
    using the SAME rule set the runtime resolver enforces
    (core/pipeline.py iter_pipeline_annotation_problems)."""
    from siddhi_tpu.core.pipeline import iter_pipeline_annotation_problems

    line, col = getattr(d, "line", None), getattr(d, "col", None)
    for problem in iter_pipeline_annotation_problems(ann):
        diags.append(Diagnostic(
            "SA112", f"stream '{sid}': {problem}", line, col,
        ))


def _check_flight_annotation(
    sid: str, d, ann, diags: list[Diagnostic]
) -> None:
    """Validate `@flightRecorder(size='N')` — the per-junction last-N-events
    ring. One SA114 per malformed element, using the SAME rule set the
    runtime resolver enforces (observability/flight.py)."""
    from siddhi_tpu.observability.flight import (
        iter_flight_annotation_problems,
    )

    line, col = getattr(d, "line", None), getattr(d, "col", None)
    for problem in iter_flight_annotation_problems(ann):
        diags.append(Diagnostic(
            "SA114", f"stream '{sid}': {problem}", line, col,
        ))


def _check_fuse_annotation(app: SiddhiApp, diags: list[Diagnostic]) -> None:
    """Validate `@app:fuse(disable='true|false')` — the whole-graph fusion
    escape hatch. One SA125 per malformed element, using the SAME rule set
    the runtime resolver raises on (core/fusion_exec.py
    iter_fuse_annotation_problems), so the two can never drift."""
    ann = find_annotation(app.annotations, "app:fuse")
    if ann is None:
        return
    from siddhi_tpu.core.fusion_exec import iter_fuse_annotation_problems

    for problem in iter_fuse_annotation_problems(ann):
        diags.append(Diagnostic("SA125", problem))


def _check_shard_annotation(app: SiddhiApp, diags: list[Diagnostic]) -> None:
    """Validate `@app:shard(devices='N', axis='part|keys|auto')` — the
    first-class sharded-execution mode. One SA129 per malformed element,
    using the SAME rule set the runtime resolver raises on
    (parallel/shard.py iter_shard_annotation_problems), so the two can
    never drift."""
    ann = find_annotation(app.annotations, "app:shard")
    if ann is None:
        return
    from siddhi_tpu.parallel.shard import iter_shard_annotation_problems

    for problem in iter_shard_annotation_problems(ann):
        diags.append(Diagnostic("SA129", problem))


def _check_lineage_annotation(app: SiddhiApp, diags: list[Diagnostic]) -> None:
    """Validate `@app:lineage(capacity='N', mode='full|sample',
    sample.every='K')` — event lineage & provenance. One SA131 per
    malformed element, using the SAME rule set the runtime resolver raises
    on (observability/lineage.py iter_lineage_annotation_problems), so the
    two can never drift."""
    ann = find_annotation(app.annotations, "app:lineage")
    if ann is None:
        return
    from siddhi_tpu.observability.lineage import (
        iter_lineage_annotation_problems,
    )

    for problem in iter_lineage_annotation_problems(ann):
        diags.append(Diagnostic("SA131", problem))


def _check_wire_annotation(
    app: SiddhiApp, sym: SymbolTable, diags: list[Diagnostic]
) -> None:
    """Validate `@app:wire(disable='true|false',
    range/dict/delta.<stream>.<col>='...')` — the compact wire-encoding
    layer's config. One SA132 per malformed element, using the SAME rule
    set the runtime resolver raises on (core/wire.py
    iter_wire_annotation_problems); the analyzer additionally passes the
    symbol table so hint targets are checked for existence and
    encoder/type compatibility."""
    ann = find_annotation(app.annotations, "app:wire")
    if ann is None:
        return
    from siddhi_tpu.core.wire import iter_wire_annotation_problems

    for problem in iter_wire_annotation_problems(ann, streams=sym.streams):
        diags.append(Diagnostic("SA132", problem))


def _check_watermark_annotation(app: SiddhiApp, diags: list[Diagnostic]) -> None:
    """Validate `@app:watermark(bound='...', idle.timeout='...',
    late.policy='drop|stream|apply', allowed.lateness='...')` — the
    event-time robustness layer. One SA134 per malformed element, using
    the SAME rule set the runtime resolver raises on (core/watermark.py
    iter_watermark_annotation_problems), so the two can never drift."""
    ann = find_annotation(app.annotations, "app:watermark")
    if ann is None:
        return
    from siddhi_tpu.core.watermark import iter_watermark_annotation_problems

    for problem in iter_watermark_annotation_problems(ann):
        diags.append(Diagnostic("SA134", problem))


def _check_supervision_annotations(
    app: SiddhiApp, diags: list[Diagnostic]
) -> None:
    """Validate the supervised-runtime app annotations — `@app:persist`
    (SA126), `@app:restart` (SA127), `@app:admission` (SA128) — using the
    SAME rule sets the runtime resolvers raise on (core/supervision.py,
    core/admission.py), so analyzer and runtime can never drift."""
    from siddhi_tpu.core.admission import iter_admission_annotation_problems
    from siddhi_tpu.core.supervision import (
        iter_persist_annotation_problems,
        iter_restart_annotation_problems,
    )

    for name, code, rules in (
        ("app:persist", "SA126", iter_persist_annotation_problems),
        ("app:restart", "SA127", iter_restart_annotation_problems),
        ("app:admission", "SA128", iter_admission_annotation_problems),
    ):
        ann = find_annotation(app.annotations, name)
        if ann is None:
            continue
        for problem in rules(ann):
            diags.append(Diagnostic(code, problem))


def _check_blackbox_annotation(app: SiddhiApp, diags: list[Diagnostic]) -> None:
    """Validate `@app:blackbox(window='...', triggers='...', keep='N',
    ring='N', dir='...', checkpoint.interval='...', debounce='...')` — the
    black-box incident recorder. One SA140 per malformed element, using
    the SAME rule set the runtime resolver raises on
    (observability/blackbox.py iter_blackbox_annotation_problems), so the
    two can never drift."""
    ann = find_annotation(app.annotations, "app:blackbox")
    if ann is None:
        return
    from siddhi_tpu.observability.blackbox import (
        iter_blackbox_annotation_problems,
    )

    for problem in iter_blackbox_annotation_problems(ann):
        diags.append(Diagnostic("SA140", problem))


def _apply_selfmon_annotation(
    app: SiddhiApp, sym: SymbolTable, diags: list[Diagnostic]
) -> None:
    """`@app:selfmon(interval='...')`: validate (SA113, same rule set as
    the runtime resolver — observability/selfmon.py) and inject the
    engine-fed `SelfMonitorStream` system definition so queries over it
    resolve — mirroring what `SiddhiAppRuntime.__init__` registers."""
    ann = find_annotation(app.annotations, "app:selfmon")
    if ann is None:
        return
    from siddhi_tpu.observability.selfmon import (
        SELFMON_STREAM_ID,
        iter_selfmon_annotation_problems,
        selfmon_attrs,
    )

    problems = list(iter_selfmon_annotation_problems(
        ann, defined_streams=app.stream_definitions
    ))
    for problem in problems:
        diags.append(Diagnostic("SA113", problem))
    if SELFMON_STREAM_ID not in sym.streams:
        sym.streams[SELFMON_STREAM_ID] = dict(selfmon_attrs())
        sym.sourced.add(SELFMON_STREAM_ID)  # engine-fed, never query-fed


def _apply_slo_annotation(
    app: SiddhiApp, sym: SymbolTable, diags: list[Diagnostic]
) -> None:
    """`@app:slo(p99.latency.ms='...', ...)`: validate (SA139, same rule
    set as the runtime resolver — observability/slo.py) and inject the
    engine-fed `SloAlertStream` system definition so alert subscribers
    resolve — the selfmon precedent."""
    ann = find_annotation(app.annotations, "app:slo")
    if ann is None:
        return
    from siddhi_tpu.observability.slo import (
        SLO_STREAM_ID,
        iter_slo_annotation_problems,
        slo_attrs,
    )

    problems = list(iter_slo_annotation_problems(
        ann, defined_streams=app.stream_definitions
    ))
    for problem in problems:
        diags.append(Diagnostic("SA139", problem))
    if SLO_STREAM_ID not in sym.streams:
        sym.streams[SLO_STREAM_ID] = dict(slo_attrs())
        sym.sourced.add(SLO_STREAM_ID)  # engine-fed, never query-fed


def build_symbols(app: SiddhiApp, diags: list[Diagnostic]) -> SymbolTable:
    sym = SymbolTable()

    for sid, d in app.stream_definitions.items():
        sym.streams[sid] = _attrs_schema(d, diags, "stream")
        if find_annotation(d.annotations, "source") is not None:
            sym.sourced.add(sid)
        if find_annotation(d.annotations, "sink") is not None:
            sym.sinked.add(sid)
        pa = find_annotation(d.annotations, "pipeline")
        if pa is not None:
            _check_pipeline_annotation(sid, d, pa, diags)
        fa = find_annotation(d.annotations, "flightRecorder")
        if fa is not None:
            _check_flight_annotation(sid, d, fa, diags)
        oe = find_annotation(d.annotations, "OnError")
        if oe is None:
            continue
        action = (oe.element("action") or oe.element(None) or "LOG").upper()
        if action not in ("LOG", "STREAM", "STORE"):
            diags.append(Diagnostic(
                "SA110",
                f"stream '{sid}': unknown @OnError action '{action}' "
                "(expected LOG, STREAM, or STORE)",
                getattr(d, "line", None), getattr(d, "col", None),
            ))
            continue
        if action == "STREAM":
            if "_error" in sym.streams[sid]:
                diags.append(Diagnostic(
                    "SA111",
                    f"stream '{sid}': @OnError(action='STREAM') reserves the "
                    "attribute name '_error'",
                    getattr(d, "line", None), getattr(d, "col", None),
                ))
            sym.fault_parents.add(sid)
            fault = dict(sym.streams[sid])
            fault["_error"] = AttrType.STRING
            sym.streams["!" + sid] = fault

    # @app:watermark(late.policy='stream'|'apply') auto-defines `!S` for
    # EVERY stream (the late/expired side channel — app_runtime mirrors
    # this), so `from !S` must resolve even without @OnError(STREAM)
    wm = find_annotation(app.annotations, "app:watermark")
    if wm is not None and (wm.element("late.policy") or "drop") in (
        "stream", "apply"
    ):
        for sid in app.stream_definitions:
            if "!" + sid in sym.streams:
                continue
            if "_error" in sym.streams[sid]:
                diags.append(Diagnostic(
                    "SA111",
                    f"stream '{sid}': @app:watermark late.policy="
                    f"'{wm.element('late.policy')}' reserves the attribute "
                    "name '_error' on every stream",
                ))
                continue
            sym.fault_parents.add(sid)
            fault = dict(sym.streams[sid])
            fault["_error"] = AttrType.STRING
            sym.streams["!" + sid] = fault

    from siddhi_tpu.core.error_store import (
        iter_definition_onerror_problems,
        resolve_definition_onerror_action,
    )

    for tid, d in app.table_definitions.items():
        sym.tables[tid] = _attrs_schema(d, diags, "table")
        oe = find_annotation(d.annotations, "OnError")
        if oe is None:
            continue
        # ONE rule set with the runtime wiring (core/error_store.py —
        # like SA126-128 ride the core/supervision.py resolvers)
        for tag, msg in iter_definition_onerror_problems(oe, "table", tid):
            diags.append(Diagnostic(
                "SA110" if tag == "action" else "SA111", msg,
                getattr(d, "line", None), getattr(d, "col", None),
            ))

    for wid, d in app.window_definitions.items():
        sym.windows[wid] = _attrs_schema(d, diags, "window")
        oe = find_annotation(d.annotations, "OnError")
        if oe is None:
            continue
        schema = sym.windows[wid] or {}
        problems = list(iter_definition_onerror_problems(
            oe, "window", wid, schema
        ))
        for tag, msg in problems:
            diags.append(Diagnostic(
                "SA110" if tag == "action" else "SA111", msg,
                getattr(d, "line", None), getattr(d, "col", None),
            ))
        if any(tag == "action" for tag, _msg in problems):
            continue
        if resolve_definition_onerror_action(oe) == "STREAM":
            sym.fault_parents.add(wid)
            fault = dict(schema)
            fault["_error"] = AttrType.STRING
            sym.streams["!" + wid] = fault

    # triggers each define a stream <id>(triggered_time long)
    # (reference: DefinitionParserHelper trigger stream registration)
    for tid in app.trigger_definitions:
        sym.streams[tid] = {"triggered_time": AttrType.LONG}
        sym.sourced.add(tid)

    for fid, fdef in app.function_definitions.items():
        sym.functions[fid] = fdef.return_type

    for aid, adef in app.aggregation_definitions.items():
        sym.aggregations[aid] = None  # bucket-view schema: leave open
        sym.aggregation_defs[aid] = adef

    _apply_selfmon_annotation(app, sym, diags)
    _apply_slo_annotation(app, sym, diags)
    _check_fuse_annotation(app, diags)
    _check_shard_annotation(app, diags)
    _check_lineage_annotation(app, diags)
    _check_wire_annotation(app, sym, diags)
    _check_watermark_annotation(app, diags)
    _check_supervision_annotations(app, diags)
    _check_blackbox_annotation(app, diags)

    return sym
