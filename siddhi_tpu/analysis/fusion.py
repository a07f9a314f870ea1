"""Fusion-feasibility planner: which queries sharing a stream can compile
into ONE XLA program per chunk.

The fused ingest (core/ingest.py FusedJunctionIngest) already compiles a
junction's entire subscriber fan-out into a single jitted chunk program —
but it only ENGAGES when nothing host-side observes per-batch boundaries
(`eligible()`), and it never reasons about which subset of queries could
fuse when the whole set cannot. This planner decides that statically, from
the AST alone, and emits the contract the whole-graph fusion PR will
implement (ROADMAP "whole-graph query fusion + cross-query state sharing";
TiLT / "To Share or not to Share", PAPERS.md):

* **groups** — per consumed stream, the maximal sets of queries with no
  fusion hazard: every query in a group shares the stream's chunking
  (@app:batch × @app:ingestChunk) and can run inside one `lax.scan` body;
* **blockers** — each query excluded from its stream's group, with the
  specific hazard (mirrors `eligible()` plus static structure):
  `async-ingress` (@async junction has its own worker), `partition`
  (partition boundary: per-key state), `rate-limit` (host-side output
  rate observer), `scheduler` (timer-armed windows/patterns need host
  scheduling between batches), `multi-stream` (joins/patterns spanning
  junctions: cross-junction fusion is out of contract),
  `ordering` (the query's insert target is consumed by another query on
  the same stream: in-group ordering would change delivery);
* **shared-state candidates** — queries over the same stream whose
  filter+window handler chains are structurally identical
  (cost.window_signature): their device window state is byte-identical
  and ONE ring can serve both (reported as SA123 and in the plan with the
  bytes saved).

`build_fusion_plan(app)` returns a versioned `FusionPlan`; `check_fusion`
emits the SA123/SA124 lints from the same computation. Both are pure AST
passes — no runtime, no device.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Optional

from siddhi_tpu.query_api.annotation import find_annotation
from siddhi_tpu.query_api.execution import (
    JoinInputStream,
    Query,
    SingleInputStream,
    StateInputStream,
    WindowHandler,
    iter_state_streams,
)
from siddhi_tpu.query_api.siddhi_app import SiddhiApp

from siddhi_tpu.analysis.cost import (
    AppCostModel,
    _window_cost,
    compute_costs,
    iter_query_entries,
    window_signature,
)
from siddhi_tpu.analysis.diagnostics import WARNING, Diagnostic

# v2: per-stream `wire` section — the versioned WireSpec (core/wire.py)
# naming each consumed stream's analyzer-chosen per-column wire encodings
# plus the predicted logical-vs-encoded bytes/event
# v3: value-analysis facts — `domains` (per-stream inferred abstract
# domains, analysis/values.py), `rewrites` (semantics-preserving rewrite
# opportunities the analysis proved), and wire entries gain inferred-lane
# provenance + prunable dead columns
PLAN_VERSION = 3

# hazard ids, stable (documented in the README; SA124 messages name them)
H_ASYNC = "async-ingress"
H_PARTITION = "partition"
H_RATE = "rate-limit"
H_SCHEDULER = "scheduler"
H_MULTI = "multi-stream"
H_ORDERING = "ordering"

_HAZARD_WHY = {
    H_ASYNC: "@async ingress runs its own worker; the fused chunk path "
             "never engages on an async junction",
    H_PARTITION: "partition boundary: per-key state cannot join a "
                 "whole-stream program",
    H_RATE: "output rate limiter observes per-batch boundaries on the host",
    H_SCHEDULER: "timer-armed operator needs host scheduling between "
                 "batches",
    H_MULTI: "consumes more than one stream; cross-junction fusion is not "
             "in the plan contract",
    H_ORDERING: "its insert target has downstream consumers: the fused "
                "chunk cannot re-publish per batch without reordering "
                "delivery",
}


@dataclasses.dataclass
class FusionPlan:
    """The versioned plan contract consumed by the fusion PR."""

    app_name: str
    batch_size: int
    chunk_batches: int
    groups: list = dataclasses.field(default_factory=list)
    blockers: list = dataclasses.field(default_factory=list)
    shared_state: list = dataclasses.field(default_factory=list)
    # sid -> versioned WireSpec summary (core/wire.py): the static
    # per-column encoding choice for every consumed stream, with the
    # predicted logical-vs-encoded bytes/event
    wire: dict = dataclasses.field(default_factory=dict)
    # v3: semantics-preserving rewrites proven by value analysis
    # (analysis/values.py) — constant folds, always-true conjunct drops,
    # provably-false filters, prunable dead columns
    rewrites: list = dataclasses.field(default_factory=list)
    # v3: sid -> {attr -> abstract-domain dict} from the value fixpoint
    domains: dict = dataclasses.field(default_factory=dict)
    costs: Optional[AppCostModel] = None

    def to_dict(self) -> dict:
        return {
            "version": PLAN_VERSION,
            "app": self.app_name,
            "chunk": {
                "batch_size": self.batch_size,
                "chunk_batches": self.chunk_batches,
            },
            "groups": list(self.groups),
            "blockers": list(self.blockers),
            "shared_state": list(self.shared_state),
            "wire": dict(self.wire),
            "rewrites": list(self.rewrites),
            "domains": dict(self.domains),
            "costs": self.costs.to_dict() if self.costs is not None else None,
        }

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), indent=2, sort_keys=False)

    def summary(self) -> dict:
        """Compact form for EXPLAIN plan annotation."""
        return {
            "version": PLAN_VERSION,
            "groups": [
                {
                    "stream": g["stream"],
                    "queries": g["queries"],
                    "est_dispatch_reduction": g["est_dispatch_reduction"],
                }
                for g in self.groups
            ],
            "blockers": [
                {"query": b["query"], "stream": b["stream"],
                 "hazard": b["hazard"]}
                for b in self.blockers
            ],
            "shared_state": [
                {"stream": s["stream"], "queries": s["queries"],
                 "est_bytes_saved": s["est_bytes_saved"]}
                for s in self.shared_state
            ],
            "rewrites": list(self.rewrites),
        }


@dataclasses.dataclass
class _Consumer:
    qid: str
    query: Query
    in_partition: bool
    streams: list  # every outer STREAM the query consumes (tables/windows/
                   # aggregation sides are passive probes, not consumption)


def _collect_consumers(app: SiddhiApp, defined_streams: set) -> list:
    out: list[_Consumer] = []
    for qid, q, in_part in iter_query_entries(app):
        stream = q.input_stream
        sids: list[str] = []
        if isinstance(stream, SingleInputStream):
            if not stream.is_inner:
                sids = [stream.stream_id]
        elif isinstance(stream, JoinInputStream):
            sids = [
                s.stream_id for s in (stream.left, stream.right)
                if not s.is_inner
            ]
        elif isinstance(stream, StateInputStream):
            sids = [
                s.stream_id
                for s in iter_state_streams(stream.state)
                if not s.is_inner
            ]
        sids = [sid for sid in sids if sid in defined_streams]
        out.append(_Consumer(qid, q, in_part, sids))
    return out


def _query_hazard(
    c: _Consumer, model: AppCostModel, observed_targets: set,
) -> Optional[str]:
    """First fusion hazard excluding query `c` from its stream's group,
    or None when it can fuse. Order matters: report the most structural
    hazard first."""
    if c.in_partition:
        return H_PARTITION
    # distinct streams the query consumes (an aliased self-join is one)
    if len(set(c.streams)) > 1:
        return H_MULTI
    if c.query.output_rate is not None:
        return H_RATE
    qc = model.queries.get(c.qid)
    if qc is not None and qc.scheduler_armed:
        return H_SCHEDULER
    target = getattr(c.query.output_stream, "target", None)
    if target is not None and target in observed_targets:
        return H_ORDERING
    return None


def build_fusion_plan(
    app: SiddhiApp, sym=None, model: Optional[AppCostModel] = None,
    values=None,
) -> FusionPlan:
    """Pure AST pass; never raises on semantically-bad apps (unknown
    streams simply do not form groups)."""
    from siddhi_tpu.analysis.symbols import build_symbols

    if sym is None:
        sym = build_symbols(app, [])
    if values is None:
        try:
            from siddhi_tpu.analysis.values import analyze_values

            values = analyze_values(app, sym)
        except Exception:  # pragma: no cover — plan must survive bad apps
            values = None
    if model is None:
        model = compute_costs(app, sym, values=values)

    plan = FusionPlan(
        app.name, model.batch_size, model.chunk_batches, costs=model
    )
    # @app:shard axis='keys' (or the env overrides): eligible grouped
    # queries keep their place in the group and the group's chunk program
    # runs on the keys mesh (core/ingest.py) — same resolution the runtime
    # uses, so the plan and ShardRuntime placement can never disagree
    mesh = None
    try:
        from siddhi_tpu.parallel.shard import resolve_shard_annotation

        devs, axis = resolve_shard_annotation(
            find_annotation(app.annotations, "app:shard")
        )
        if devs >= 2 and axis == "keys":
            mesh = {"devices": devs, "axis": axis}
    except Exception:  # pragma: no cover — plan must survive bad apps
        pass
    consumers = _collect_consumers(app, set(sym.streams))

    # streams whose defined consumers number >= 2 are fusion-planning
    # targets; single-consumer streams already fuse trivially via the
    # existing per-junction ingest
    by_stream: dict[str, list] = {}
    for c in consumers:
        for sid in sorted(set(c.streams)):
            if sid in sym.streams:
                by_stream.setdefault(sid, []).append(c)

    # streams whose batch boundaries something host-side observes: any
    # query consumes them, or a @sink delivers from them (mirror of
    # eligible()'s insert-target-junction check, core/ingest.py)
    observed_targets: set = set(sym.sinked)
    for c in consumers:
        observed_targets.update(c.streams)

    for sid in sorted(by_stream):
        cs = by_stream[sid]
        if len(cs) < 2:
            continue
        async_ann = None
        d = app.stream_definitions.get(sid)
        if d is not None:
            async_ann = find_annotation(d.annotations, "async")
        fusable: list[_Consumer] = []
        for c in cs:
            hazard = H_ASYNC if async_ann is not None else _query_hazard(
                c, model, observed_targets
            )
            if hazard is None:
                fusable.append(c)
            else:
                plan.blockers.append({
                    "stream": sid,
                    "query": c.qid,
                    "hazard": hazard,
                    "why": _HAZARD_WHY[hazard],
                })
        if len(fusable) >= 2:
            n = len(fusable)
            K = model.chunk_batches
            state_bytes = sum(
                model.queries[c.qid].state_bytes
                for c in fusable if c.qid in model.queries
            )
            plan.groups.append({
                "stream": sid,
                # telemetry component of the group's chunk program — the
                # fusion executor (core/fusion_exec.py) adopts this name, so
                # the static plan, runtime.explain(), and /profile all key
                # the same ledger
                "component": f"stream.{sid}.fusedgroup.{len(plan.groups)}",
                "queries": sorted(c.qid for c in fusable),
                "chunk": {
                    "batch_size": model.batch_size,
                    "chunk_batches": K,
                },
                "state_bytes": state_bytes,
                # today: n per-batch dispatches per micro-batch; fused: one
                # dispatch per K-batch chunk running all n bodies
                "dispatches_per_chunk_before": n * K,
                "dispatches_per_chunk_after": 1,
                "est_dispatch_reduction": round(1.0 - 1.0 / (n * K), 4),
            })
            if mesh is not None:
                # the group's chunk program runs on the keys mesh when a
                # member turns out key-shardable (parallel/keyshard.py
                # keyed_shardable, a runtime predicate)
                plan.groups[-1]["mesh"] = mesh

    _collect_shared_state(app, sym, model, consumers, plan)
    _collect_wire_specs(app, sym, model, plan, values)
    if values is not None:
        plan.rewrites = list(values.rewrites)
        plan.domains = values.domains_dict()
    return plan


def _collect_wire_specs(
    app: SiddhiApp, sym, model: AppCostModel, plan: FusionPlan,
    values=None,
) -> None:
    """Per consumed stream: the static WireSpec (core/wire.py — the same
    builder the runtime's fused ingest consumes, so the plan and the
    engine can never choose different encoders) plus the predicted
    logical-vs-encoded bytes/event. Sampling can only shrink the wire
    further at runtime (narrow tsd, un-hinted int columns)."""
    from siddhi_tpu.core.wire import (
        WIRE_SPEC_VERSION,
        app_wire_specs,
        encoding_label,
        estimate_wire_bytes,
        logical_row_bytes,
    )

    inferred = None
    if values is not None:
        try:
            from siddhi_tpu.analysis.values import infer_wire_hints

            inferred = infer_wire_hints(values, sym)
        except Exception:  # pragma: no cover
            inferred = None
    disabled, specs = app_wire_specs(
        app, sym.streams, sorted(model.streams), model.batch_size,
        inferred=inferred,
    )
    dead = getattr(values, "dead_columns", None) or {}
    for sid, (attrs, spec) in specs.items():
        entry = {
            "version": WIRE_SPEC_VERSION,
            "source": spec.source if spec is not None else "static",
            "encodings": {
                lane: encoding_label(e)
                for lane, e in sorted(
                    (spec.encodings if spec is not None else {}).items()
                )
            },
            "logical_B_per_ev": logical_row_bytes(attrs),
            "encoded_B_per_ev_est": estimate_wire_bytes(
                attrs, spec, capacity=model.batch_size
            ),
        }
        if spec is not None and spec.inferred_lanes:
            entry["inferred_lanes"] = sorted(spec.inferred_lanes)
        if sid in dead:
            entry["pruned"] = list(dead[sid])
        if disabled:
            entry["disabled"] = True
        plan.wire[sid] = entry


def _collect_shared_state(
    app: SiddhiApp, sym, model: AppCostModel, consumers: list,
    plan: FusionPlan,
) -> None:
    """Identical (filter-chain + window) sources over the same stream:
    their device rings hold byte-identical content — one ring can serve
    every query in the set ("To Share or not to Share", PAPERS.md)."""
    sigs: dict[tuple, list] = {}
    for c in consumers:
        stream = c.query.input_stream
        sources = []
        if isinstance(stream, SingleInputStream):
            sources = [stream]
        elif isinstance(stream, JoinInputStream):
            sources = [stream.left, stream.right]
        for s in sources:
            if s.is_inner or s.stream_id not in sym.streams:
                continue
            sig = window_signature(s.handlers)
            if sig is None:
                continue
            sigs.setdefault((s.stream_id, sig), []).append((c.qid, s))
    for (sid, sig), entries in sorted(sigs.items()):
        qids = sorted({qid for qid, _s in entries})
        if len(qids) < 2:
            continue
        # size ONLY the shared source's own window chain — the query may
        # hold other window state (e.g. the opposite join side) that
        # sharing this ring cannot save
        _qid0, s0 = entries[0]
        schema = sym.streams.get(sid)
        per_query = sum(
            _window_cost(h.window, schema, _qid0).state_bytes
            for h in s0.handlers if isinstance(h, WindowHandler)
        )
        plan.shared_state.append({
            "stream": sid,
            "signature": sig,
            "queries": qids,
            "est_bytes_saved": per_query * (len(qids) - 1),
        })


# ---------------------------------------------------------------------------
# lints: SA123 / SA124
# ---------------------------------------------------------------------------


def check_fusion(
    app: SiddhiApp, sym, diags: list, model: Optional[AppCostModel] = None,
    values=None,
) -> FusionPlan:
    plan = build_fusion_plan(app, sym, model, values=values)
    nodes = {qid: q for qid, q, _in_part in iter_query_entries(app)}

    # SA123: identical window duplicated across queries (shareable)
    for entry in plan.shared_state:
        qids = entry["queries"]
        # anchor the diagnostic on the LAST duplicate's window handler
        loc_qid, node = _shared_loc(nodes, entry)
        diags.append(Diagnostic(
            "SA123",
            f"identical window state over stream '{entry['stream']}' in "
            f"queries {', '.join(qids)} ({entry['signature']}): one shared "
            f"ring could serve all of them, saving "
            f"~{entry['est_bytes_saved']} bytes of device state",
            getattr(node, "line", None), getattr(node, "col", None),
            severity=WARNING, query=loc_qid,
        ))

    # SA124: a hazard split a would-be group
    for b in plan.blockers:
        node = nodes.get(b["query"])
        diags.append(Diagnostic(
            "SA124",
            f"query cannot fuse with the other consumers of stream "
            f"'{b['stream']}': {b['hazard']} ({b['why']})",
            getattr(node, "line", None), getattr(node, "col", None),
            severity=WARNING, query=b["query"],
        ))
    return plan


def render_plan_text(plan: FusionPlan) -> str:
    """Human-readable FusionPlan (CLI `--plan` default format)."""
    from siddhi_tpu.analysis.cost import _fmt_bytes

    lines = [
        f"FUSION PLAN v{PLAN_VERSION} — app '{plan.app_name}'  "
        f"(batch={plan.batch_size} x chunk={plan.chunk_batches})"
    ]
    if plan.groups:
        lines.append("fusable groups:")
        for g in plan.groups:
            lines.append(
                f"  stream {g['stream']}: {', '.join(g['queries'])}  "
                f"({g['dispatches_per_chunk_before']} dispatches/chunk -> "
                f"{g['dispatches_per_chunk_after']}, "
                f"-{g['est_dispatch_reduction'] * 100:.1f}% dispatch, "
                f"state={_fmt_bytes(g['state_bytes'])})"
            )
    else:
        lines.append("fusable groups: none (no stream has 2+ fusable consumers)")
    if plan.shared_state:
        lines.append("shared-state candidates:")
        for s in plan.shared_state:
            lines.append(
                f"  stream {s['stream']}: {', '.join(s['queries'])} share "
                f"{s['signature']}  "
                f"(~{_fmt_bytes(s['est_bytes_saved'])} saved)"
            )
    if plan.blockers:
        lines.append("blockers:")
        for b in plan.blockers:
            lines.append(
                f"  {b['query']} on {b['stream']}: {b['hazard']} — {b['why']}"
            )
    encoded_streams = {
        sid: w for sid, w in plan.wire.items() if w.get("encodings")
    }
    if encoded_streams:
        lines.append("wire encodings:")
        for sid, w in sorted(encoded_streams.items()):
            encs = ", ".join(
                f"{lane}={label}"
                + ("*" if lane in w.get("inferred_lanes", []) else "")
                for lane, label in w["encodings"].items()
            )
            suffix = ""
            if w.get("inferred_lanes"):
                suffix += ", *=inferred"
            if w.get("pruned"):
                suffix += f", pruned: {', '.join(w['pruned'])}"
            lines.append(
                f"  stream {sid}: {encs}  "
                f"({w['logical_B_per_ev']} -> ~{w['encoded_B_per_ev_est']} "
                f"B/ev{', DISABLED' if w.get('disabled') else ''}{suffix})"
            )
    if plan.rewrites:
        lines.append("rewrites (value analysis):")
        for r in plan.rewrites:
            detail = ", ".join(
                f"{k}={v}" for k, v in sorted(r.items()) if k != "kind"
            )
            lines.append(f"  {r['kind']}: {detail}")
    if plan.costs is not None:
        lines.append("per-query cost:")
        for qid, qc in sorted(plan.costs.queries.items()):
            progs = ", ".join(
                f"{p.component}~{p.predicted_compiles}c"
                for p in qc.programs
            )
            lines.append(
                f"  {qid} [{qc.kind}]: state={_fmt_bytes(qc.state_bytes)} "
                f"sel~{qc.est_selectivity} compiles~{qc.predicted_compiles}"
                + (f"  ({progs})" if progs else "")
            )
    return "\n".join(lines)


def _shared_loc(nodes: dict, entry: dict):
    """(qid, AST node) of the last duplicated window handler, for SA123's
    source location."""
    last = (entry["queries"][-1], None)
    for qid in entry["queries"]:
        q = nodes.get(qid)
        if q is None:
            continue
        stream = q.input_stream
        sources = []
        if isinstance(stream, SingleInputStream):
            sources = [stream]
        elif isinstance(stream, JoinInputStream):
            sources = [stream.left, stream.right]
        for s in sources:
            if s.stream_id != entry["stream"]:
                continue
            if window_signature(s.handlers) != entry["signature"]:
                continue
            for h in s.handlers:
                if isinstance(h, WindowHandler):
                    last = (qid, h.window)
    return last
