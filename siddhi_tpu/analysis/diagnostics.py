"""Diagnostics model for the compile-time semantic analyzer.

Every finding carries a stable `SA###` code (documented in the README and in
`CODES` below), a severity, and — when the analyzed app came out of the
SiddhiQL parser — the 1-based line/column of the offending token, threaded
from the tokenizer through the query-api AST (`SourceLocated`).
"""

from __future__ import annotations

import dataclasses
import json
from typing import Optional

from siddhi_tpu.core.errors import SiddhiAppCreationError

ERROR = "error"
WARNING = "warning"

# Stable diagnostic catalog. Codes are append-only: never renumber.
CODES: dict[str, str] = {
    "SA000": "internal analyzer error (analysis incomplete, not an app defect)",
    "SA001": "SiddhiQL syntax error (reported by the CLI for unparsable apps)",
    # name resolution
    "SA101": "undefined stream / window / input source",
    "SA102": "unknown stream reference in an expression",
    "SA103": "unknown attribute",
    "SA104": "ambiguous unqualified attribute (warning)",
    "SA105": "duplicate query name",
    "SA106": "fault stream '!S' consumed but 'S' does not declare @OnError(action='STREAM')",
    "SA107": "insert into fault stream '!S' but 'S' does not declare @OnError(action='STREAM')",
    "SA108": "unknown table",
    "SA109": "duplicate attribute name in a definition",
    "SA110": "invalid @OnError action",
    "SA111": "reserved attribute name",
    "SA112": "invalid @pipeline annotation (unknown key / bad depth)",
    "SA113": "invalid @app:selfmon annotation (bad interval / unknown key / reserved stream name)",
    "SA114": "invalid @flightRecorder annotation (bad size / unknown key)",
    "SA115": "invalid partition key (OBJECT-typed key expression, or a "
             "partitioned query consumes a stream the partition declares "
             "no key for)",
    "SA116": "aggregation 'aggregate by' attribute must be INT/LONG",
    "SA117": "invalid 'within'/'per' clause (aggregation joins and store "
             "queries; warning when the clause is silently ignored)",
    "SA118": "malformed store query (no from-store and no write output)",
    # cost model / fusion planner (warnings)
    "SA120": "unbounded pattern state: 'every' with no 'within' bound "
             "(token-table growth; warning)",
    "SA121": "unbounded or oversized window/aggregation state (no expiry, "
             "or state beyond the device budget; warning)",
    "SA122": "statically-predicted recompile churn (tail-variant ladder, "
             "re-published batch shapes; warning)",
    "SA123": "identical window state duplicated across queries of one "
             "stream (shareable; warning)",
    "SA124": "fusion blocker: the named hazard excludes this query from "
             "its stream's fusable group (warning)",
    "SA125": "invalid @app:fuse annotation (unknown option or bad "
             "disable value)",
    "SA126": "invalid @app:persist annotation (bad interval / bad keep / "
             "unknown key)",
    "SA127": "invalid @app:restart annotation (unknown policy / bad "
             "max.attempts / bad backoff)",
    "SA128": "invalid @app:admission annotation (unknown policy / bad "
             "rate.limit or max.pending / no bound declared)",
    "SA129": "invalid @app:shard annotation (devices out of range / "
             "unknown axis / unknown option)",
    "SA130": "hot add_query candidate conflicts with the live app "
             "(missing @info name / duplicate query id / undeclared stream)",
    "SA131": "invalid @app:lineage annotation (bad capacity / unknown mode "
             "/ bad sample.every / unknown option)",
    "SA132": "invalid @app:wire annotation (unknown option / bad range "
             "'lo..hi' / bad dict capacity / bad delta dtype / unknown "
             "stream or column / encoder-type mismatch)",
    "SA133": "h2d-dominant wide column: a declared column's type forces a "
             "wide wire encoding that dominates the stream's h2d "
             "bytes/event — declare an int/long range (or dict/delta) via "
             "@app:wire, or use interned strings (warning)",
    "SA134": "invalid @app:watermark annotation (missing/bad bound / bad "
             "idle.timeout / unknown late.policy / allowed.lateness "
             "without late.policy='apply' / unknown option)",
    # value analysis (analysis/values.py; warnings)
    "SA135": "provably-false filter: on the proven value domain the "
             "predicate can never hold, so the query is unreachable "
             "(warning)",
    "SA136": "comparison that can never vary: the proven value domain "
             "decides it always-true or always-false (warning)",
    "SA137": "arithmetic hazard on a proven domain: possible overflow of "
             "the result type, or division/modulo by a domain containing "
             "zero (warning)",
    "SA138": "inferred-encodable wide column: the dominant wide column's "
             "bounds/cardinality/monotonicity are PROVEN by value "
             "analysis, so wire inference compacts it with no annotation "
             "(informational successor to SA133; warning)",
    "SA139": "malformed @app:slo annotation: unknown option, invalid "
             "objective/window/burn threshold, no objective at all, or a "
             "user definition of the reserved SloAlertStream",
    "SA140": "invalid @app:blackbox annotation (bad window / unknown "
             "trigger / bad keep or ring / bad checkpoint.interval or "
             "debounce / unknown option)",
    "SA141": "time-bounded window at the default capacity beside a larger "
             "@app:batch: rows still inside the window's time are expired "
             "early; state the rows it must hold with @app:timeCapacity",
    # typing
    "SA201": "incompatible comparison operand types",
    "SA202": "arithmetic on a non-numeric operand",
    "SA203": "condition is not boolean (filter / having / on / range partition)",
    "SA204": "logical operator on a non-boolean operand",
    "SA205": "insert-into arity mismatch against the target schema",
    "SA206": "insert-into attribute type mismatch against the target schema",
    "SA207": "scalar function argument error",
    "SA208": "unknown function",
    "SA209": "aggregator used outside select / having",
    "SA210": "expression projection needs an 'as' name",
    "SA211": "duplicate output attribute name",
    "SA212": "order by on a STRING/OBJECT attribute",
    # windows / stream functions / aggregators
    "SA301": "unknown window type",
    "SA302": "window or stream-function argument error",
    "SA303": "unknown stream function",
    "SA305": "aggregator argument error",
    # dataflow (warnings)
    "SA401": "dead stream: defined but never produced or consumed (warning)",
    "SA402": "named window consumed but never fed by any query (warning)",
    "SA403": "stream dataflow cycle (warning)",
}


@dataclasses.dataclass(frozen=True)
class Diagnostic:
    code: str
    message: str
    line: Optional[int] = None
    col: Optional[int] = None
    severity: str = ERROR
    query: Optional[str] = None  # query id context, when inside a query

    def format(self, source_name: str = "<app>") -> str:
        loc = f"{source_name}"
        if self.line is not None:
            loc += f":{self.line}:{self.col if self.col is not None else 0}"
        ctx = f" [in {self.query}]" if self.query else ""
        return f"{loc}: {self.severity}: {self.code}: {self.message}{ctx}"

    def to_dict(self) -> dict:
        return {
            "code": self.code,
            "severity": self.severity,
            "message": self.message,
            "line": self.line,
            "col": self.col,
            "query": self.query,
        }


@dataclasses.dataclass
class AnalysisResult:
    diagnostics: list[Diagnostic] = dataclasses.field(default_factory=list)
    app_name: str = "SiddhiApp"
    # static FusionPlan (analysis/fusion.py) built by the same pass; None
    # when the pass was skipped or the analyzer degraded (SA000)
    fusion_plan: object = None

    @property
    def errors(self) -> list[Diagnostic]:
        return [d for d in self.diagnostics if d.severity == ERROR]

    @property
    def warnings(self) -> list[Diagnostic]:
        return [d for d in self.diagnostics if d.severity == WARNING]

    @property
    def ok(self) -> bool:
        return not self.errors

    def format(self, source_name: str = "<app>") -> str:
        lines = [d.format(source_name) for d in self.diagnostics]
        lines.append(
            f"{len(self.errors)} error(s), {len(self.warnings)} warning(s)"
        )
        return "\n".join(lines)

    def to_json(self, source_name: str = "<app>") -> str:
        return json.dumps(
            {
                "app": self.app_name,
                "source": source_name,
                "diagnostics": [d.to_dict() for d in self.diagnostics],
                "errors": len(self.errors),
                "warnings": len(self.warnings),
            },
            indent=2,
        )

    def raise_if_errors(self, source_name: str = "<app>") -> "AnalysisResult":
        if self.errors:
            raise SiddhiAnalysisError(self, source_name)
        return self


class SiddhiAnalysisError(SiddhiAppCreationError):
    """Aggregated semantic errors from `analyze()` (strict mode): one raise
    listing every error diagnostic, instead of dying on the first."""

    def __init__(self, result: AnalysisResult, source_name: str = "<app>"):
        self.result = result
        self.diagnostics = result.errors
        msgs = "\n".join("  " + d.format(source_name) for d in result.errors)
        super().__init__(
            f"semantic analysis of '{result.app_name}' found "
            f"{len(result.errors)} error(s):\n{msgs}"
        )
