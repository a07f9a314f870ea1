"""Static per-query cost model over the analyzer's dataflow graph.

For every query the model predicts, without constructing any runtime stage:

* **device state tensors** — the shapes/dtypes each operator keeps resident
  on device (window rings, batch buckets, pattern token tables + capture
  lanes, join side buffers, group-by key tables, aggregation bucket
  tables), with a byte estimate per operator and per query;
* **jitted programs + predicted compile counts** — one per-batch step
  program per query (two per join side, one per pattern input stream),
  named with the SAME component ids the compile telemetry uses
  (`query.{qid}`, `query.{qid}[sid]`, `stream.{sid}.fused`), and the
  statically-predictable entries of the profiler's recompile-cause
  taxonomy (observability/profiler.py): `first_compile` always,
  `shape_change` for scheduler-armed programs (timer batches carry their
  own shape) and for consumers of query-produced streams (re-published
  slices), `tail_variant_k` for the fused chunk program's power-of-two
  tail ladder (core/ingest.py `_chunk_K`), `full_width_rebuild` when the
  stream wire carries interned STRING/OBJECT columns the narrow-width
  sampling can misfit on;
* **selectivity estimates** — coarse static per-operator output/input
  ratios (documented in `_SEL`), multiplied into a per-query estimate the
  fusion planner and EXPLAIN surface next to the live measured value.

The model mirrors the runtime's sizing rules (`core/windows.py
make_window`, `core/pattern.py PatternProgram`, `core/join.py`,
`core/app_runtime.py` capacity annotations) but never imports a runtime
stage; unknowable quantities (extension windows, non-constant parameters)
degrade to `None`/0 rather than guesses.

Lints emitted by `check_costs` (all warnings — these apps run; they are
hazards, not defects):

* SA120 — `every` pattern with no `within` bound anywhere on the element:
  partial-match tokens are never killed, so the fixed token table
  (@app:patternCapacity) fills and matches drop;
* SA121 — window/aggregation state above the device budget
  (SIDDHI_TPU_STATE_BUDGET_MB, default 64 MiB), or a named window defined
  with no window type at all (unbounded retention);
* SA122 — statically-predicted recompile churn: a fused chunk size whose
  tail-variant ladder alone compiles >= _TAIL_CHURN variants of the whole
  chunk program, or an @app:batch size != 64 on a query consuming a
  query-produced stream (re-published slices arrive <= 64 rows, a second
  shape signature per downstream program).
"""

from __future__ import annotations

import dataclasses
import enum
import os
from typing import Optional

from siddhi_tpu.core.types import AttrType
from siddhi_tpu.query_api.annotation import find_annotation
from siddhi_tpu.query_api.definition import WindowSpec
from siddhi_tpu.query_api.execution import (
    AbsentStreamStateElement,
    CountStateElement,
    EveryStateElement,
    Filter,
    JoinInputStream,
    LogicalStateElement,
    NextStateElement,
    Query,
    SingleInputStream,
    StateInputStream,
    StreamStateElement,
    WindowHandler,
    assign_execution_ids,
    iter_state_streams,
)
from siddhi_tpu.query_api.expression import Constant
from siddhi_tpu.query_api.siddhi_app import SiddhiApp

from siddhi_tpu.analysis.diagnostics import WARNING, Diagnostic

# Runtime sizing defaults, mirrored (NOT imported — the model must not pull
# in runtime stages): app_runtime.DEFAULT_BATCH, windows.DEFAULT_TIME_CAPACITY,
# join.DEFAULT_JOIN_CAPACITY, pattern.DEFAULT_TOKEN_CAPACITY /
# DEFAULT_COUNT_CAPACITY, groupby.DEFAULT_GROUP_CAPACITY, the fused ingest
# chunk default (app_runtime._wire_fused_ingest), and agg group capacity.
DEFAULT_BATCH = 64
DEFAULT_TIME_CAPACITY = 1024
DEFAULT_JOIN_CAPACITY = 512
DEFAULT_TOKEN_CAPACITY = 128
DEFAULT_COUNT_CAPACITY = 8
DEFAULT_CHUNK_BATCHES = 32
DEFAULT_GROUP_CAPACITY = 1024
DEFAULT_AGG_GROUPS = 64
# windows whose ring takes the time capacity (core/windows.py make_window)
_TIME_CAPACITY_WINDOWS = frozenset(
    f"window:{n}" for n in
    ("time", "externaltime", "timebatch", "externaltimebatch", "cron")
)

# physical widths on device (core/types.py PHYSICAL_DTYPE)
_NBYTES = {
    AttrType.STRING: 4,
    AttrType.INT: 4,
    AttrType.LONG: 8,
    AttrType.FLOAT: 4,
    AttrType.DOUBLE: 4,  # runs as f32 on TPU
    AttrType.BOOL: 1,
    AttrType.OBJECT: 4,
}
_DTYPE_NAME = {
    AttrType.STRING: "int32",
    AttrType.INT: "int32",
    AttrType.LONG: "int64",
    AttrType.FLOAT: "float32",
    AttrType.DOUBLE: "float32",
    AttrType.BOOL: "bool",
    AttrType.OBJECT: "int32",
}

# static per-operator selectivity estimates (events out per event in);
# coarse by design — the live ledger replaces them once traffic flows
_SEL = {
    "filter": 0.25,
    "window:sliding": 2.0,   # CURRENT + its later EXPIRED
    "window:batch": 1.0,     # every event leaves in exactly one flush
    "pattern": 0.05,
    "join": 0.1,
    "having": 0.5,
}

# SA121: device state budget per operator
_BUDGET_MB_ENV = "SIDDHI_TPU_STATE_BUDGET_MB"
DEFAULT_STATE_BUDGET_MB = 64

# SA122: tail ladders at least this long are flagged as churn
_TAIL_CHURN = 8

# window classification (batch vs ring, scheduler arming, row bounds)
# lives ON WindowSpec as state-bound metadata (query_api/definition.py)
_BUILTIN_WINDOWS = {
    "length", "time", "timelength", "externaltime", "lengthbatch",
    "timebatch", "externaltimebatch", "sort", "frequent", "lossyfrequent",
    "cron",
}


def state_budget_bytes() -> int:
    try:
        mb = int(os.environ.get(_BUDGET_MB_ENV, DEFAULT_STATE_BUDGET_MB))
    except ValueError:
        mb = DEFAULT_STATE_BUDGET_MB
    return mb << 20


def _fmt_bytes(n: Optional[int]) -> str:
    if n is None:
        return "?"
    if n >= 1 << 20:
        return f"{n / (1 << 20):.1f}MiB"
    if n >= 1 << 10:
        return f"{n / (1 << 10):.1f}KiB"
    return f"{n}B"


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class TensorSpec:
    """One resident device buffer: `{lane: (shape) dtype}`."""

    lane: str
    shape: tuple
    dtype: str

    @property
    def bytes(self) -> int:
        n = 1
        for d in self.shape:
            n *= d
        width = {"int32": 4, "int64": 8, "float32": 4, "bool": 1}[self.dtype]
        return n * width

    def to_dict(self) -> dict:
        return {
            "lane": self.lane,
            "shape": list(self.shape),
            "dtype": self.dtype,
            "bytes": self.bytes,
        }


@dataclasses.dataclass
class OperatorCost:
    """One stateful operator's predicted device footprint."""

    op: str                      # 'window:length', 'pattern', 'join:left', ...
    detail: str                  # 'length(50)', 'pattern 3 slots T=128', ...
    tensors: list = dataclasses.field(default_factory=list)
    est_selectivity: Optional[float] = None
    line: Optional[int] = None
    col: Optional[int] = None

    @property
    def state_bytes(self) -> int:
        return sum(t.bytes for t in self.tensors)

    def to_dict(self) -> dict:
        return {
            "op": self.op,
            "detail": self.detail,
            "state_bytes": self.state_bytes,
            "tensors": [t.to_dict() for t in self.tensors],
            "est_selectivity": self.est_selectivity,
        }


@dataclasses.dataclass
class ProgramCost:
    """One jitted device program: telemetry component name + the compile
    count the profiler is predicted to observe, by cause."""

    component: str
    input_rows: Optional[int] = None
    predicted_causes: dict = dataclasses.field(default_factory=dict)

    @property
    def predicted_compiles(self) -> int:
        return sum(self.predicted_causes.values())

    def to_dict(self) -> dict:
        return {
            "component": self.component,
            "input_rows": self.input_rows,
            "predicted_compiles": self.predicted_compiles,
            "predicted_causes": dict(sorted(self.predicted_causes.items())),
        }


@dataclasses.dataclass
class QueryCost:
    qid: str
    kind: str  # 'single' | 'join' | 'pattern'
    operators: list = dataclasses.field(default_factory=list)
    programs: list = dataclasses.field(default_factory=list)
    scheduler_armed: bool = False
    rate_limited: bool = False
    in_partition: bool = False
    consumed_streams: list = dataclasses.field(default_factory=list)
    # inside a partition: the slots, how the step visits them (`routed`:
    # [P, B'] sub-batches; `masked`: every slot steps the whole batch) and
    # the rows of one step's flow over all slots
    partition: Optional[dict] = None

    @property
    def state_bytes(self) -> int:
        one = sum(o.state_bytes for o in self.operators)
        # every slot holds the inner query's whole state
        return one * (self.partition["capacity"] if self.partition else 1)

    @property
    def predicted_compiles(self) -> int:
        return sum(p.predicted_compiles for p in self.programs)

    @property
    def est_selectivity(self) -> float:
        sel = 1.0
        for o in self.operators:
            if o.est_selectivity is not None:
                sel *= o.est_selectivity
        return round(sel, 4)

    def to_dict(self) -> dict:
        return {
            "qid": self.qid,
            "kind": self.kind,
            "state_bytes": self.state_bytes,
            "est_selectivity": self.est_selectivity,
            "predicted_compiles": self.predicted_compiles,
            "scheduler_armed": self.scheduler_armed,
            "rate_limited": self.rate_limited,
            "in_partition": self.in_partition,
            **({"partition": dict(self.partition)} if self.partition else {}),
            "consumed_streams": list(self.consumed_streams),
            "operators": [o.to_dict() for o in self.operators],
            "programs": [p.to_dict() for p in self.programs],
        }


@dataclasses.dataclass
class StreamCost:
    """Per-stream fused chunk program prediction (core/ingest.py)."""

    stream_id: str
    wire_row_bytes: Optional[int]
    chunk_batches: int
    tail_variants: list = dataclasses.field(default_factory=list)
    narrow_rebuild_hazard: bool = False

    def predicted_causes(self) -> dict:
        causes = {"first_compile": 1}
        if self.tail_variants:
            causes["tail_variant_k"] = len(self.tail_variants)
        if self.narrow_rebuild_hazard:
            causes["full_width_rebuild"] = 1
        return causes

    def to_dict(self) -> dict:
        return {
            "stream": self.stream_id,
            "component": f"stream.{self.stream_id}.fused",
            "wire_row_bytes": self.wire_row_bytes,
            "chunk_batches": self.chunk_batches,
            "tail_variants": list(self.tail_variants),
            "narrow_rebuild_hazard": self.narrow_rebuild_hazard,
            "predicted_compiles": sum(self.predicted_causes().values()),
            "predicted_causes": self.predicted_causes(),
        }


@dataclasses.dataclass
class AppCostModel:
    app_name: str
    batch_size: int
    chunk_batches: int
    queries: dict = dataclasses.field(default_factory=dict)  # qid -> QueryCost
    streams: dict = dataclasses.field(default_factory=dict)  # sid -> StreamCost

    def to_dict(self) -> dict:
        return {
            "app": self.app_name,
            "batch_size": self.batch_size,
            "chunk_batches": self.chunk_batches,
            "queries": {
                qid: qc.to_dict() for qid, qc in sorted(self.queries.items())
            },
            "streams": {
                sid: sc.to_dict() for sid, sc in sorted(self.streams.items())
            },
        }


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _const_int(expr) -> Optional[int]:
    if isinstance(expr, Constant) and isinstance(expr.value, (int, float)) \
            and not isinstance(expr.value, bool):
        return int(expr.value)
    return None


def _capacity_annotation(app: SiddhiApp, name: str, default: int) -> int:
    ann = find_annotation(app.annotations, name)
    if ann is None:
        return default
    v = ann.element("size") or ann.element(None)
    try:
        return int(v)
    except (TypeError, ValueError):
        return default


def _schema_tensors(
    schema: Optional[dict], rows: int, prefix: str = "cols",
    facts: Optional[dict] = None,
) -> list:
    """Per-attribute (rows,) lanes for a resolved schema; [] when open.
    With `facts` (attr -> ValueFact from analysis/values.py), a LONG lane
    whose proven interval fits int32 is sized at the narrowed width — the
    same downcast the wire/state layer applies once the proof holds."""
    if schema is None:
        return []
    out = []
    for name, t in schema.items():
        if t is None:
            t = AttrType.LONG  # unknown attr type: widest assumption
        dt = _DTYPE_NAME[t]
        if facts is not None and t is AttrType.LONG:
            f = facts.get(name)
            if f is not None and f.lo is not None and f.hi is not None \
                    and -(2 ** 31) <= f.lo and f.hi < 2 ** 31:
                dt = "int32"
        out.append(TensorSpec(f"{prefix}.{name}", (rows,), dt))
    return out


def window_signature(handlers) -> Optional[str]:
    """Canonical signature of a source's filter+window handler chain up to
    and including its window — two sources with the same signature over the
    same stream hold byte-identical device state (the fusion planner's
    shared-state test). None when the source has no window."""
    parts: list[str] = []
    saw_window = False
    for h in handlers:
        if isinstance(h, Filter):
            parts.append(f"filter[{expr_signature(h.expression)}]")
        elif isinstance(h, WindowHandler):
            w = h.window
            args = ",".join(expr_signature(p) for p in w.parameters)
            parts.append(f"window.{w.key}({args})")
            saw_window = True
        else:  # stream functions change the flow: state diverges
            parts.append(f"fn.{getattr(h, 'name', '?')}")
    return " ".join(parts) if saw_window else None


def expr_signature(expr) -> str:
    """Canonical structural signature of an expression AST (ignores source
    positions — they are class attributes, not dataclass fields). Compact
    for the common node kinds so SA123 messages stay readable."""
    from siddhi_tpu.query_api import expression as E

    if isinstance(expr, Constant):
        return f"{expr.value!r}"
    if isinstance(expr, E.Variable):
        pre = f"{expr.stream_id}." if expr.stream_id else ""
        idx = f"[{expr.stream_index}]" if getattr(expr, "stream_index", None) is not None else ""
        return f"{pre}{expr.attribute}{idx}"
    if isinstance(expr, E.Compare):
        return (
            f"({expr_signature(expr.left)} {expr.op.value} "
            f"{expr_signature(expr.right)})"
        )
    _ARITH_OPS = {
        E.Add: "+", E.Subtract: "-", E.Multiply: "*", E.Divide: "/",
        E.Mod: "%",
    }
    for cls, op in _ARITH_OPS.items():
        if type(expr) is cls:
            return (
                f"({expr_signature(expr.left)} {op} "
                f"{expr_signature(expr.right)})"
            )
    if isinstance(expr, E.And):
        return f"({expr_signature(expr.left)} and {expr_signature(expr.right)})"
    if isinstance(expr, E.Or):
        return f"({expr_signature(expr.left)} or {expr_signature(expr.right)})"
    if isinstance(expr, E.Not):
        return f"(not {expr_signature(expr.expression)})"
    if isinstance(expr, E.AttributeFunction):
        ns = f"{expr.namespace}:" if expr.namespace else ""
        args = ", ".join(expr_signature(p) for p in expr.parameters)
        return f"{ns}{expr.name}({args})"
    if dataclasses.is_dataclass(expr):
        fields = []
        for f in dataclasses.fields(expr):
            v = getattr(expr, f.name)
            if isinstance(v, (list, tuple)):
                sig = "[" + ",".join(expr_signature(x) for x in v) + "]"
            else:
                sig = expr_signature(v)
            fields.append(f"{f.name}={sig}")
        return f"{type(expr).__name__}({','.join(fields)})"
    if isinstance(expr, (str, int, float, bool)) or expr is None:
        return repr(expr)
    if isinstance(expr, enum.Enum):
        return str(expr.value)
    return type(expr).__name__


def _window_cost(
    spec: WindowSpec, schema: Optional[dict], qid: Optional[str],
    facts: Optional[dict] = None,
    time_capacity: int = DEFAULT_TIME_CAPACITY,
    held_cols=None,
) -> OperatorCost:
    """Mirror core/windows.py make_window sizing for one window handler,
    reading the state-bound metadata WindowSpec itself carries.
    `held_cols`: make_window's (a `length` ring that holds those columns
    alone, and no ordering lane)."""
    name = spec.key
    line, col = getattr(spec, "line", None), getattr(spec, "col", None)
    params = spec.parameters

    detail = f"{name}({', '.join(str(_const_int(p)) if _const_int(p) is not None else '?' for p in params)})"
    if name not in _BUILTIN_WINDOWS:
        return OperatorCost(
            "window:extension", detail, [], None, line, col
        )

    is_batch = spec.is_batch
    rows = spec.length_bound()
    if rows is None:
        if name in ("length", "timelength", "lengthbatch", "sort",
                    "frequent", "lossyfrequent"):
            # declared row bound is non-constant/missing: unknowable
            return OperatorCost(f"window:{name}", detail, [], None, line, col)
        rows = time_capacity  # time-capacity ring family (@app:timeCapacity)

    if held_cols is not None and schema is not None:
        held = {n: t for n, t in schema.items() if n in held_cols}
        return OperatorCost(
            f"window:{name}", detail,
            _schema_tensors(held, rows, prefix="ring", facts=facts),
            _SEL["window:sliding"], line, col,
        )

    buffers = 2 if is_batch else 1  # batch windows carry cur + prev buckets
    tensors = []
    for b in range(buffers):
        pref = ("cur" if b == 0 else "prev") if buffers == 2 else "ring"
        tensors.extend(
            _schema_tensors(schema, rows, prefix=f"{pref}", facts=facts)
        )
        tensors.append(TensorSpec(f"{pref}.ts", (rows,), "int64"))
        if not is_batch:
            # sliding family: wts + seq ordering lanes (windows.py init_state)
            tensors.append(TensorSpec(f"{pref}.wts", (rows,), "int64"))
            tensors.append(TensorSpec(f"{pref}.seq", (rows,), "int64"))
    sel = _SEL["window:batch"] if is_batch else _SEL["window:sliding"]
    return OperatorCost(f"window:{name}", detail, tensors, sel, line, col)


def _source_operators(
    s: SingleInputStream,
    schema: Optional[dict],
    qid: str,
    facts: Optional[dict] = None,
    time_capacity: int = DEFAULT_TIME_CAPACITY,
    held_cols=None,
) -> tuple[list, bool]:
    """(operators, scheduler_armed) for one single-source handler chain.
    With `facts` (attr -> ValueFact), a filter whose predicate narrows a
    PROVEN bounded domain gets an interval-overlap selectivity estimate
    in place of the flat default, and window rings size at narrowed
    widths."""
    ops: list[OperatorCost] = []
    armed = False
    for h in s.handlers:
        if isinstance(h, Filter):
            sel = _SEL["filter"]
            if facts:
                try:
                    from siddhi_tpu.analysis.values import (
                        filter_selectivity,
                    )

                    refined = filter_selectivity(h.expression, facts)
                    if refined is not None:
                        sel = refined
                except Exception:  # pragma: no cover - defect guard
                    pass
            ops.append(OperatorCost(
                "filter", "filter", [], sel,
                getattr(h, "line", None), getattr(h, "col", None),
            ))
        elif isinstance(h, WindowHandler):
            ops.append(
                _window_cost(
                    h.window, schema, qid, facts, time_capacity, held_cols
                )
            )
            armed = armed or h.window.arms_scheduler
    return ops, armed


def _pattern_cost(
    stream: StateInputStream,
    sym,
    app: SiddhiApp,
    qid: str,
) -> OperatorCost:
    """Token table + capture lanes, mirroring core/pattern.py init_state.
    Capture lanes are an upper bound (the runtime prunes to selector-used
    attributes; statically we charge the full schema)."""
    T = _capacity_annotation(app, "app:patternCapacity", DEFAULT_TOKEN_CAPACITY)
    count_cap = _capacity_annotation(
        app, "app:countCapacity", DEFAULT_COUNT_CAPACITY
    )
    tensors = [
        TensorSpec("tok.active", (T,), "bool"),
        TensorSpec("tok.slot", (T,), "int32"),
        TensorSpec("tok.start_ts", (T,), "int64"),
        TensorSpec("tok.entry_ts", (T,), "int64"),
        TensorSpec("tok.seq", (T,), "int64"),
    ]
    n_slots = 0

    def walk(elem) -> None:
        nonlocal n_slots
        if isinstance(elem, CountStateElement):
            mx = elem.max_count
            c = mx if 0 < mx <= count_cap else count_cap
            n_slots += 1
            add_ref(elem.stream.stream, c)
        elif isinstance(elem, NextStateElement):
            walk(elem.state)
            walk(elem.next)
        elif isinstance(elem, EveryStateElement):
            walk(elem.state)
        elif isinstance(elem, LogicalStateElement):
            n_slots += 1
            for side in (elem.left, elem.right):
                if isinstance(side, StreamStateElement):
                    add_ref(side.stream, 1)
        elif isinstance(elem, StreamStateElement):
            n_slots += 1
            add_ref(elem.stream, 1)

    ref_n = [0]

    def add_ref(s: SingleInputStream, cap: int) -> None:
        schema = sym.streams.get(s.stream_id)
        i = ref_n[0]
        ref_n[0] += 1
        tensors.append(TensorSpec(f"cap{i}.n", (T,), "int32"))
        tensors.append(TensorSpec(f"cap{i}.ts", (T, cap), "int64"))
        tensors.extend(
            TensorSpec(f"cap{i}.{t.lane}", (T, cap), t.dtype)
            for t in _schema_tensors(schema, 1)
        )

    walk(stream.state)
    return OperatorCost(
        "pattern",
        f"{stream.type.value} {n_slots} slot(s), {ref_n[0]} ref(s), T={T}",
        tensors,
        _SEL["pattern"],
        getattr(stream, "line", None), getattr(stream, "col", None),
    )


def _pattern_scheduler_armed(stream: StateInputStream) -> bool:
    """Mirrors PatternProgram.needs_scheduler: absent atoms with waiting
    times arm host timers."""
    def walk(elem) -> bool:
        if isinstance(elem, AbsentStreamStateElement):
            return elem.waiting_time_ms is not None
        if isinstance(elem, CountStateElement):
            return walk(elem.stream)
        if isinstance(elem, NextStateElement):
            return walk(elem.state) or walk(elem.next)
        if isinstance(elem, EveryStateElement):
            return walk(elem.state)
        if isinstance(elem, LogicalStateElement):
            return walk(elem.left) or walk(elem.right)
        return False

    return walk(stream.state)


def _tail_variants(K: int) -> list:
    """Distinct smaller-K variants core/ingest.py _chunk_K can compile: the
    powers of two in [2, K)."""
    out = []
    k = 2
    while k < K:
        out.append(k)
        k *= 2
    return out


# ---------------------------------------------------------------------------
# per-app computation
# ---------------------------------------------------------------------------


def iter_query_entries(app: SiddhiApp):
    """Yield (qid, query, in_partition) for every execution element, ids
    matching query_api.execution.assign_execution_ids — the ONE query walk
    shared by the cost model, the lints, and the fusion planner."""
    for ent in assign_execution_ids(app):
        if ent[0] == "query":
            yield ent[1], ent[2], False
        else:
            for qid, q in ent[3]:
                yield qid, q, True


def produced_streams(app: SiddhiApp) -> set:
    """Outer stream ids some query inserts into (re-published batches)."""
    produced: set = set()
    for _qid, q, _in_part in iter_query_entries(app):
        target = getattr(q.output_stream, "target", None)
        if target and not getattr(q.output_stream, "is_inner", False):
            produced.add(target)
    return produced


def _hint_lane_bytes(hint, t: AttrType) -> Optional[int]:
    """Narrowed wire bytes/row one declared-or-inferred hint buys a lane
    of declared type `t`, or None when the hint does not shrink it.
    Mirrors core/wire.py lane widths without the amortized headers (the
    cost model predicts per-row bytes, not per-chunk)."""
    wide = _NBYTES[t or AttrType.LONG]
    if hint is None or t not in (AttrType.INT, AttrType.LONG,
                                 AttrType.STRING, AttrType.OBJECT):
        return None
    if hint[0] == "range" and t in (AttrType.INT, AttrType.LONG):
        lo, hi = int(hint[1]), int(hint[2])
        for width, bound in ((1, 1 << 7), (2, 1 << 15), (4, 1 << 31)):
            if width < wide and -bound <= lo and hi < bound:
                return width
        return None
    if hint[0] == "dict":
        width = 1 if int(hint[1]) <= 256 else 2
        return width if width < wide else None
    if hint[0] == "delta" and t in (AttrType.INT, AttrType.LONG):
        try:
            width = int(getattr(hint[1], "itemsize", 2))
        except (TypeError, ValueError):
            width = 2
        return width if width < wide else None
    return None


def compute_costs(app: SiddhiApp, sym=None, values=None) -> AppCostModel:
    """Build the full static cost model for `app`. Never raises on bad apps:
    unresolvable pieces degrade to empty/None entries. With `values` (a
    ValueAnalysis from analysis/values.py), state tensors size at proven
    narrowed widths, filter selectivities refine from interval overlap,
    and wire-byte predictions price declared @app:wire contracts AND
    inferred encoders instead of full declared widths."""
    from siddhi_tpu.analysis.symbols import build_symbols

    if sym is None:
        sym = build_symbols(app, [])

    B = _capacity_annotation(app, "app:batch", DEFAULT_BATCH)
    K = _capacity_annotation(app, "app:ingestChunk", DEFAULT_CHUNK_BATCHES)
    K = max(2, K)
    model = AppCostModel(app.name, B, K)

    # declared @app:wire contracts price the wire even WITHOUT a value
    # analysis, and their range hints become interval facts for tensor
    # narrowing + filter selectivity below; inferred hints (seeded from
    # the declared ones, so at least as tight) overlay both
    wire_hints: dict = {}
    declared_facts: dict = {}
    try:
        from siddhi_tpu.analysis.values import ValueFact
        from siddhi_tpu.core.wire import parse_wire_hints

        declared = parse_wire_hints(
            find_annotation(app.annotations, "app:wire")
        )
        wire_hints = dict(declared)
        for (sid, col), hint in declared.items():
            if hint[0] != "range":
                continue
            schema = sym.streams.get(sid)
            atype = schema.get(col) if schema else None
            declared_facts.setdefault(sid, {})[col] = ValueFact(
                lo=int(hint[1]), hi=int(hint[2]), atype=atype
            )
    except Exception:  # pragma: no cover - defect guard
        declared_facts = {}
    if values is not None:
        try:
            from siddhi_tpu.analysis.values import infer_wire_hints

            wire_hints.update(infer_wire_hints(values, sym))
        except Exception:  # pragma: no cover - defect guard
            pass

    produced = produced_streams(app)
    for qid, q, in_part in iter_query_entries(app):
        model.queries[qid] = _query_cost(
            q, qid, app, sym, B, in_part, produced, values,
            declared_facts=declared_facts,
        )

    for sid, schema in sym.streams.items():
        consumers = [
            qc for qc in model.queries.values() if sid in qc.consumed_streams
        ]
        if not consumers:
            continue
        row_bytes = None
        if schema is not None:
            row_bytes = 8  # int64 timestamp lane
            for name, t in schema.items():
                narrowed = _hint_lane_bytes(wire_hints.get((sid, name)), t)
                row_bytes += (
                    narrowed if narrowed is not None
                    else _NBYTES[t or AttrType.LONG]
                )
        has_interned = schema is not None and any(
            t in (AttrType.STRING, AttrType.OBJECT) for t in schema.values()
        )
        model.streams[sid] = StreamCost(
            sid,
            wire_row_bytes=row_bytes,
            chunk_batches=K,
            tail_variants=_tail_variants(K),
            narrow_rebuild_hazard=has_interned,
        )
    return model


def _query_cost(
    q: Query,
    qid: str,
    app: SiddhiApp,
    sym,
    B: int,
    in_partition: bool,
    produced: set,
    values=None,
    declared_facts: Optional[dict] = None,
) -> QueryCost:
    stream = q.input_stream
    operators: list[OperatorCost] = []
    programs: list[ProgramCost] = []
    consumed: list[str] = []
    armed = False
    kind = "single"
    held_cols = None
    time_capacity = _capacity_annotation(
        app, "app:timeCapacity", DEFAULT_TIME_CAPACITY
    )

    def stream_facts(sid: str) -> Optional[dict]:
        # declared @app:wire range facts as the base; the value analysis
        # (when supplied) overlays them with its at-least-as-tight facts
        base = dict(declared_facts.get(sid, {})) if declared_facts else {}
        if values is not None:
            facts = values.facts_for(sid)
            if facts:
                base.update(facts)
        return base or None

    def step_causes(extra_shapes: int) -> dict:
        causes = {"first_compile": 1}
        if extra_shapes:
            causes["shape_change"] = extra_shapes
        return causes

    if isinstance(stream, SingleInputStream):
        schema = sym.streams.get(stream.stream_id) or sym.windows.get(
            stream.stream_id
        )
        consumed.append(stream.stream_id)
        if in_partition:
            from siddhi_tpu.core.partition import ring_columns

            held_cols = ring_columns(q)
        ops, armed = _source_operators(
            stream, schema, qid, stream_facts(stream.stream_id), time_capacity,
            held_cols,
        )
        operators.extend(ops)
        extra = (1 if armed else 0) + (
            1 if stream.stream_id in produced and B != 64 else 0
        )
        programs.append(ProgramCost(
            f"query.{qid}", input_rows=B,
            predicted_causes=step_causes(extra),
        ))
    elif isinstance(stream, JoinInputStream):
        kind = "join"
        jc = _capacity_annotation(
            app, "app:joinCapacity", DEFAULT_JOIN_CAPACITY
        )
        for side, s in (("left", stream.left), ("right", stream.right)):
            sid = s.stream_id
            is_stream = sid in sym.streams or sid in sym.windows
            schema = sym.streams.get(sid) or sym.tables.get(sid) \
                or sym.windows.get(sid)
            if sid in sym.streams:
                consumed.append(sid)
            ops, side_armed = _source_operators(
                s, schema, qid, stream_facts(sid), time_capacity
            )
            armed = armed or side_armed
            # a join side buffers its window content at join capacity
            win = [o for o in ops if o.op.startswith("window")]
            operators.extend(ops)
            if is_stream:
                side_tensors = _schema_tensors(
                    schema, jc, prefix="buf", facts=stream_facts(sid)
                )
                operators.append(OperatorCost(
                    f"join:{side}",
                    f"side buffer cap={jc}"
                    + (f" ({win[0].detail})" if win else ""),
                    side_tensors
                    + [TensorSpec("buf.ts", (jc,), "int64")],
                    None,
                    getattr(s, "line", None), getattr(s, "col", None),
                ))
                extra = (1 if side_armed else 0) + (
                    1 if sid in produced and B != 64 else 0
                )
                programs.append(ProgramCost(
                    f"query.{qid}[{side}]", input_rows=B,
                    predicted_causes=step_causes(extra),
                ))
        operators.append(OperatorCost(
            "join", stream.join_type.value, [], _SEL["join"],
            getattr(stream, "line", None), getattr(stream, "col", None),
        ))
    elif isinstance(stream, StateInputStream):
        kind = "pattern"
        operators.append(_pattern_cost(stream, sym, app, qid))
        armed = _pattern_scheduler_armed(stream)
        sids = sorted({
            s.stream_id for s in iter_state_streams(stream.state)
        })
        consumed.extend(sids)
        for sid in sids:
            extra = (1 if armed else 0) + (
                1 if sid in produced and B != 64 else 0
            )
            programs.append(ProgramCost(
                f"query.{qid}[{sid}]", input_rows=B,
                predicted_causes=step_causes(extra),
            ))

    sel = q.selector
    if sel is not None and not sel.select_all:
        if sel.group_by:
            gcap = _capacity_annotation(
                app, "app:groupCapacity", DEFAULT_GROUP_CAPACITY
            )
            operators.append(OperatorCost(
                "groupby",
                f"{len(sel.group_by)} key(s), cap={gcap}",
                [
                    TensorSpec("keys", (gcap, len(sel.group_by)), "int64"),
                    TensorSpec("used", (gcap,), "bool"),
                ],
                None,
                getattr(sel, "line", None), getattr(sel, "col", None),
            ))
        if sel.having is not None:
            operators.append(OperatorCost(
                "having", "having", [], _SEL["having"],
                getattr(sel, "line", None), getattr(sel, "col", None),
            ))

    partition = None
    if in_partition:
        from siddhi_tpu.core.partition import (
            DEFAULT_PARTITIONS,
            sub_batch_rows,
        )

        p = _capacity_annotation(
            app, "app:partitionCapacity", DEFAULT_PARTITIONS
        )
        routed = kind == "single"
        sub = sub_batch_rows(B, p) if routed else B
        if held_cols is not None:
            # a ring that holds some columns only is stepped by slices: a
            # sub-batch no longer than the window (core/partition.py)
            w = stream.handlers[-1].window.length_bound()
            if w is not None:
                sub = min(sub, w)
        partition = {
            "capacity": p,
            "step": "routed" if routed else "masked",
            "sub_batch": sub,
            "flow_rows": p * sub,
        }

    return QueryCost(
        qid=qid,
        kind=kind,
        operators=operators,
        programs=programs,
        scheduler_armed=armed,
        rate_limited=q.output_rate is not None,
        in_partition=in_partition,
        consumed_streams=consumed,
        partition=partition,
    )


# ---------------------------------------------------------------------------
# aggregation state estimate (definitions, not queries)
# ---------------------------------------------------------------------------


def aggregation_state_bytes(ad, app: SiddhiApp) -> Optional[int]:
    """Closed-bucket tables per duration × group capacity × base columns —
    a coarse upper bound mirroring core/aggregation.py table sizing."""
    durations = ad.bucket_durations()
    if not durations or ad.selector is None:
        return None
    groups = _capacity_annotation(app, "app:aggGroupCapacity", DEFAULT_AGG_GROUPS)
    n_base = max(1, len(ad.selector.selection_list)) + len(ad.selector.group_by)
    return len(durations) * groups * n_base * 8  # widest lanes (int64/f64 pairs)


# ---------------------------------------------------------------------------
# lints: SA120 / SA121 / SA122
# ---------------------------------------------------------------------------


def check_costs(
    app: SiddhiApp, sym, diags: list,
    model: Optional[AppCostModel] = None, values=None,
) -> AppCostModel:
    """Run the cost lints; returns the model so callers reuse it."""
    if model is None:
        model = compute_costs(app, sym, values)
    budget = state_budget_bytes()

    # SA120: every with no within, anywhere in a pattern/sequence
    for qid, q, _in_part in iter_query_entries(app):
        stream = q.input_stream
        if isinstance(stream, StateInputStream):
            _check_unbounded_every(stream, qid, diags)

    # SA121: oversized operator state (windows, patterns, join buffers)
    for qid, qc in sorted(model.queries.items()):
        for op in qc.operators:
            if op.state_bytes > budget:
                diags.append(Diagnostic(
                    "SA121",
                    f"{op.op} state is ~{_fmt_bytes(op.state_bytes)} on "
                    f"device ({op.detail}), over the "
                    f"{_fmt_bytes(budget)} budget "
                    f"(raise ${_BUDGET_MB_ENV} or shrink the window)",
                    op.line, op.col, severity=WARNING, query=qid,
                ))

    # SA121: named window defined with no window type = unbounded retention
    for wid, wd in app.window_definitions.items():
        if wd.window is None:
            diags.append(Diagnostic(
                "SA121",
                f"named window '{wid}' has no window type: rows are never "
                "expired (unbounded retention) — give it a bounded window, "
                "e.g. length(N) or time(T)",
                getattr(wd, "line", None), getattr(wd, "col", None),
                severity=WARNING,
            ))

    # SA121: aggregation bucket tables over budget
    for aid, ad in app.aggregation_definitions.items():
        est = aggregation_state_bytes(ad, app)
        if est is not None and est > budget:
            diags.append(Diagnostic(
                "SA121",
                f"aggregation '{aid}' bucket tables are "
                f"~{_fmt_bytes(est)} on device, over the "
                f"{_fmt_bytes(budget)} budget",
                getattr(ad, "line", None), getattr(ad, "col", None),
                severity=WARNING,
            ))

    # SA122: tail-variant ladder explosion on the fused chunk program
    tails = _tail_variants(model.chunk_batches)
    if len(tails) >= _TAIL_CHURN and model.streams:
        ann = find_annotation(app.annotations, "app:ingestChunk")
        diags.append(Diagnostic(
            "SA122",
            f"@app:ingestChunk(size='{model.chunk_batches}') predicts "
            f"{len(tails)} tail-variant compiles of every fused chunk "
            "program (core/ingest.py _chunk_K power-of-two ladder) — each "
            "is a full XLA compile mid-traffic; lower the chunk size",
            getattr(ann, "line", None), getattr(ann, "col", None),
            severity=WARNING,
        ))

    # SA133/SA138: h2d-dominant wide column — a LONG column with no
    # @app:wire encoding hint that alone accounts for >= half the stream's
    # estimated wire bytes/event on a consumed (h2d-riding) stream. SA133
    # (add a hint) only when value analysis CANNOT prove the lane
    # encodable; when it can, SA138 says inference already compacts it.
    _check_wire_dominance(app, sym, model, diags, values)

    # SA141: a time-bounded window left at the default capacity beside an
    # @app:batch larger than it: one full micro-batch already holds more
    # rows than the ring, so rows expire early from the first send on
    if (
        find_annotation(app.annotations, "app:timeCapacity") is None
        and model.batch_size > DEFAULT_TIME_CAPACITY
    ):
        for qid, qc in sorted(model.queries.items()):
            for op in qc.operators:
                if op.op in _TIME_CAPACITY_WINDOWS:
                    diags.append(Diagnostic(
                        "SA141",
                        f"{op.detail} keeps the default capacity of "
                        f"{DEFAULT_TIME_CAPACITY} rows while @app:batch is "
                        f"{model.batch_size}: rows that are still inside "
                        "the window's time are expired early (flagged at "
                        "run time, `window_early_expiry`); state the rows "
                        "the window must hold with @app:timeCapacity(size='N')",
                        op.line, op.col, severity=WARNING, query=qid,
                    ))

    # SA122: @app:batch != 64 downstream of a query insert (re-published
    # slices arrive <= 64 rows: a second shape signature per program)
    if model.batch_size != 64:
        produced = produced_streams(app)
        for qid, qc in sorted(model.queries.items()):
            hit = sorted(set(qc.consumed_streams) & produced)
            if hit:
                diags.append(Diagnostic(
                    "SA122",
                    f"@app:batch(size='{model.batch_size}') with "
                    f"query-produced input '{hit[0]}': re-published batches "
                    "arrive in <=64-row slices, so this query's program "
                    "compiles a second shape signature "
                    "(predicted shape_change recompiles)",
                    None, None, severity=WARNING, query=qid,
                ))
    return model


def _check_wire_dominance(
    app: SiddhiApp, sym, model: AppCostModel, diags: list, values=None
) -> None:
    """SA133/SA138 (see check_costs). Skipped when the app opts out via
    `@app:wire(disable='true')` — the user already declined the wire
    layer, so the hint would be noise. Dominance is judged on the
    DECLARED-only spec (the wide lane is wide until someone encodes it);
    the verdict then splits on whether value analysis proves the lane
    encodable. Specs come from the SAME shared preamble the FusionPlan
    wire section uses (core/wire.py app_wire_specs), at the model's real
    batch size."""
    from siddhi_tpu.core.wire import (
        _hint_entry,
        app_wire_specs,
        estimate_wire_bytes,
        lane_bytes_per_row,
    )

    disabled, specs = app_wire_specs(
        app, sym.streams, sorted(model.streams), model.batch_size
    )
    if disabled:
        return
    inferred: dict = {}
    if values is not None:
        try:
            from siddhi_tpu.analysis.values import infer_wire_hints

            inferred = infer_wire_hints(values, sym)
        except Exception:  # pragma: no cover - defect guard
            inferred = {}
    _HINT_WORD = {"range": "bounded", "dict": "low-cardinality",
                  "delta": "monotone"}
    for sid, (attrs, spec) in specs.items():
        enc = spec.encodings if spec is not None else {}
        total = max(
            estimate_wire_bytes(attrs, spec, capacity=model.batch_size), 1
        )
        d = app.stream_definitions.get(sid)
        for name, t in attrs:
            if t is not AttrType.LONG or name in enc:
                continue
            # STRICTLY dominant: the one wide lane outweighs everything
            # else on the wire combined (a 50/50 split stays quiet — the
            # false-positive net is the whole test corpus)
            if 8.0 / total <= 0.5:
                continue
            hint = inferred.get((sid, name))
            entry = None
            if hint is not None:
                import numpy as np

                entry = _hint_entry(hint, t, np.dtype(np.int64))
                if entry is not None and lane_bytes_per_row(
                    name, np.dtype(np.int64), entry, model.batch_size
                ) >= 8:
                    entry = None
            if entry is not None:
                diags.append(Diagnostic(
                    "SA138",
                    f"stream '{sid}': LONG column '{name}' dominates the "
                    f"h2d wire (8 of ~{total} B/event), and value "
                    f"analysis proves it {_HINT_WORD[hint[0]]} — wire "
                    f"inference {hint[0]}-encodes it with no annotation",
                    getattr(d, "line", None), getattr(d, "col", None),
                    severity=WARNING,
                ))
                continue
            diags.append(Diagnostic(
                "SA133",
                f"stream '{sid}': LONG column '{name}' rides the h2d wire "
                f"full-width and dominates it (8 of ~{total} B/event) — "
                f"declare @app:wire(range.{sid}.{name}='lo..hi') or "
                f"delta.{sid}.{name}='int16', or use interned strings",
                getattr(d, "line", None), getattr(d, "col", None),
                severity=WARNING,
            ))


def _check_unbounded_every(
    stream: StateInputStream, qid: str, diags: list
) -> None:
    """SA120: an `every` pattern with no `within` bound ANYWHERE — neither
    on the whole pattern nor on any state element. A within on a later
    element still bounds the every's forked tokens (they must traverse
    that slot, whose bound kills them — core/pattern.py _min_within), so
    only the fully-unbounded shape warns: there, partial-match tokens are
    never expired, the fixed token table (@app:patternCapacity) fills,
    and further matches silently drop."""
    if stream.within_ms is not None:
        return
    if _subtree_has_within(stream.state):
        return
    every = _find_first_every(stream.state)
    if every is None:
        return
    line = getattr(every, "line", None) or getattr(stream, "line", None)
    col = getattr(every, "col", None) or getattr(stream, "col", None)
    diags.append(Diagnostic(
        "SA120",
        "'every' with no 'within' bound anywhere in the pattern: "
        "partial-match tokens fork per match and are never expired, so "
        "the fixed token table (@app:patternCapacity) fills and further "
        "matches drop — add 'within <time>'",
        line, col, severity=WARNING, query=qid,
    ))


def _find_first_every(elem):
    if isinstance(elem, EveryStateElement):
        return elem
    for child in ("state", "next", "left", "right", "stream"):
        c = getattr(elem, child, None)
        if c is None or isinstance(c, SingleInputStream):
            continue
        found = _find_first_every(c)
        if found is not None:
            return found
    return None


def _subtree_has_within(elem) -> bool:
    if getattr(elem, "within_ms", None) is not None:
        return True
    for child in ("state", "next", "left", "right", "stream"):
        c = getattr(elem, child, None)
        if c is not None and not isinstance(c, SingleInputStream) \
                and _subtree_has_within(c):
            return True
    return False
