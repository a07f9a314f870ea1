"""EXPLAIN ANALYZE for SiddhiQL apps: the analyzer's dataflow graph
annotated with live runtime counters.

The plan is the analyzer's query-level dataflow (`analysis/analyzer.py
collect_flows`: consumed stream ids -> produced stream id per query),
rendered as nodes + edges. With a running app and `@app:statistics`
configured, every node carries live counters:

* stream nodes — events published, 1m EWMA rate, queue depth, fused/
  pipelined engagement, and the fused chunk program's compile ledger;
* query nodes — dispatch count, latency p50/p99, device-time share (this
  query's jitted-step time over the app's total device time), the step
  program's compile ledger (count + causes, observability/profiler.py),
  and selectivity (output-stream events over input-stream events) when
  both ends are metered;
* table / window / aggregation nodes — row counts and fills from
  `describe_state()`.

Surfaces: `runtime.explain()` (text) / `runtime.explain_plan()` (dict),
`/explain` + `/explain.json` on the MetricsServer, and the analysis CLI's
`--explain` mode (static plan: same graph, no live counters). This plan —
which queries share an input stream, how selective each is, where the
device time actually goes — is exactly what a cross-query fusion planner
needs to decide what to compile together (TiLT's plan-level view argument,
PAPERS.md; ROADMAP whole-graph fusion direction).

Best-effort by construction: every annotation source is independently
guarded, so a half-started app, a stats-off app, or a plan the analyzer
would reject (e.g. invalid partition keys) still renders its topology
instead of raising.
"""

from __future__ import annotations

from typing import Optional

from siddhi_tpu.query_api.execution import (
    DeleteStream,
    Filter,
    InsertIntoStream,
    JoinInputStream,
    Query,
    ReturnStream,
    SingleInputStream,
    StateInputStream,
    StreamFunctionHandler,
    UpdateOrInsertStream,
    UpdateStream,
    WindowHandler,
    iter_state_streams,
)


# ---------------------------------------------------------------------------
# labels
# ---------------------------------------------------------------------------


def _handler_labels(s: SingleInputStream) -> list[str]:
    out = []
    for h in s.handlers:
        if isinstance(h, Filter):
            out.append("[filter]")
        elif isinstance(h, WindowHandler):
            w = h.window
            ns = f"{w.namespace}:" if w.namespace else ""
            out.append(f"#window.{ns}{w.name}")
        elif isinstance(h, StreamFunctionHandler):
            ns = f"{h.namespace}:" if h.namespace else ""
            out.append(f"#{ns}{h.name}")
    return out


def _source_label(query: Query) -> str:
    s = query.input_stream
    if isinstance(s, SingleInputStream):
        return " ".join([s.stream_id] + _handler_labels(s))
    if isinstance(s, JoinInputStream):
        return (
            " ".join([s.left.stream_id] + _handler_labels(s.left))
            + f" {s.join_type.value} "
            + " ".join([s.right.stream_id] + _handler_labels(s.right))
        )
    if isinstance(s, StateInputStream):
        ids = [a.stream_id for a in iter_state_streams(s.state)]
        return f"{s.type.value} over " + ", ".join(dict.fromkeys(ids))
    return type(s).__name__


def _sink_label(query: Query) -> str:
    out = query.output_stream
    if isinstance(out, InsertIntoStream):
        return (
            f"insert into {'#' if out.is_inner else ''}{out.target}"
        )
    if isinstance(out, UpdateOrInsertStream):
        return f"update or insert into {out.target}"
    if isinstance(out, UpdateStream):
        return f"update {out.target}"
    if isinstance(out, DeleteStream):
        return f"delete {out.target}"
    if isinstance(out, ReturnStream):
        return "return"
    return type(out).__name__


def _selector_label(query: Query) -> str:
    sel = query.selector
    parts = []
    if sel.select_all:
        parts.append("select *")
    else:
        n_agg = len(sel.selection_list)
        parts.append(f"select {n_agg} attr{'s' if n_agg != 1 else ''}")
    if sel.group_by:
        parts.append(
            "group by " + ",".join(v.attribute for v in sel.group_by)
        )
    if sel.having is not None:
        parts.append("having")
    return " ".join(parts)


# ---------------------------------------------------------------------------
# plan construction
# ---------------------------------------------------------------------------


def _query_index(app) -> dict[str, Query]:
    """qid -> Query AST node via the ONE shared id assignment the runtime
    and the analyzer use (query_api/execution.py assign_execution_ids)."""
    from siddhi_tpu.query_api.execution import assign_execution_ids

    idx: dict[str, Query] = {}
    for ent in assign_execution_ids(app):
        if ent[0] == "query":
            idx[ent[1]] = ent[2]
        else:
            for qid, q in ent[3]:
                idx[qid] = q
    return idx


def build_plan(app, runtime=None) -> dict:
    """The dataflow plan of `app` as {"app", "nodes": [...], "edges":
    [...]}. With `runtime` (a SiddhiAppRuntime), nodes carry live
    counters; without, the plan is purely static (CLI --explain)."""
    from siddhi_tpu.analysis.analyzer import collect_flows

    flows = collect_flows(app)
    qindex = _query_index(app)

    # static cost model + fusion plan (analysis/cost.py, analysis/fusion.py):
    # predicted state bytes / compile counts / selectivity per query, and the
    # per-stream fusable groups — rendered NEXT TO the live counters so the
    # predicted and measured numbers sit on the same line of the same plan.
    # Independently guarded: a cost-model defect must not take down EXPLAIN.
    static_costs: dict = {}
    fusion_summary = None
    try:
        from siddhi_tpu.analysis.cost import compute_costs
        from siddhi_tpu.analysis.fusion import build_fusion_plan
        from siddhi_tpu.analysis.symbols import build_symbols

        _sym = build_symbols(app, [])
        _values = None
        try:
            from siddhi_tpu.analysis.values import analyze_values

            _values = analyze_values(app, _sym)
        except Exception:
            _values = None
        _model = compute_costs(app, _sym, values=_values)
        static_costs = _model.queries
        fusion_summary = build_fusion_plan(
            app, _sym, model=_model, values=_values
        ).summary()
    except Exception:
        pass

    sm = getattr(runtime, "statistics_manager", None) if runtime else None
    ct = sm.compile_telemetry if sm is not None else None

    # plan-vs-actual calibration pairs (observability/calibration.py),
    # indexed by component so each query node renders its `calib:` line
    # beside the `static:` line. Guarded like the cost model above.
    calib_by_comp: dict = {}
    calib_summary = None
    ledger = getattr(runtime, "_calibration", None) if runtime else None
    if ledger is not None:
        try:
            for p in ledger.observe():
                calib_by_comp.setdefault(p["component"], {})[p["kind"]] = p
            calib_summary = {
                "generation": ledger.generation,
                "flags": sorted({
                    f
                    for kinds in calib_by_comp.values()
                    for p in kinds.values()
                    for f in p.get("flags", ())
                }),
                "mispriced": [
                    {"reason": r, "component": c, "count": n}
                    for (r, c), n in sorted(ledger.mispriced.items())
                ],
            }
        except Exception:
            calib_by_comp = {}
            calib_summary = None

    # total device step time across the app: the device-share denominator
    total_dev_ns = 0
    if sm is not None:
        for t in list(sm.device_time.values()):
            if getattr(t, "op", None) in ("step", "fused_step"):
                total_dev_ns += t.total_ns

    nodes: list[dict] = []
    edges: list[dict] = []
    seen_streams: set[str] = set()

    def stream_events(sid: str) -> Optional[int]:
        if sm is None:
            return None
        t = sm.throughput.get(f"stream.{sid}")
        return t.count if t is not None else None

    def add_stream(sid: str) -> str:
        nid = f"stream:{sid}"
        if sid in seen_streams:
            return nid
        seen_streams.add(sid)
        kind = "stream"
        label = sid
        if "#" in sid:  # partition-namespaced inner stream ('partition0#x')
            pid, inner = sid.split("#", 1)
            kind = "inner_stream"
            label = f"#{inner} ({pid})"
        elif sid.startswith("!"):
            kind = "fault_stream"
        node: dict = {"id": nid, "kind": kind, "label": label}
        counters: dict = {}
        ev = stream_events(sid)
        if ev is not None:
            counters["events"] = ev
            counters["rate_1m"] = round(
                sm.throughput[f"stream.{sid}"].rate_1m, 3
            )
        fused_component = f"stream.{sid}.fused"
        if runtime is not None:
            j = runtime.junctions.get(sid)
            if j is not None:
                try:
                    counters["queue_depth"] = j.queued()
                    fi = j.fused_ingest
                    if fi is not None:
                        counters["fused"] = "pipelined"
                        counters["chunk_batches"] = fi.K
                        # a key-sharded member (parallel/keyshard.py): the
                        # chunk program runs on the keys mesh
                        mesh_devices = fi._mesh_devices()
                        if mesh_devices > 1:
                            counters["mesh_devices"] = mesh_devices
                        # plan-driven group engine: the achieved-vs-predicted
                        # dispatch-reduction ledger (core/fusion_exec.py),
                        # under the cost model's component taxonomy
                        # (stream.<S>.fusedgroup.<g>)
                        fused_component = fi.component
                        gr = fi.group_report()
                        if gr is not None:
                            counters["fusedgroup"] = gr
                        # compact wire encodings (core/wire.py): per-column
                        # encoder choices + encoded-vs-logical bytes/event,
                        # once the first engaged send chose them
                        if fi._narrow is not None:
                            from siddhi_tpu.core.wire import wire_report

                            counters["wire"] = wire_report(
                                j.schema, getattr(fi, "_keep", None),
                                fi._narrow, fi.wire_spec,
                                capacity=j.batch_size,
                            )
                except Exception:
                    pass
            # event-time watermark (core/watermark.py): the reorder stage's
            # frontier + buffer pressure + late-row tally for this source
            wm = getattr(runtime, "_watermark", None)
            if wm is not None:
                tr = wm.trackers.get(sid)
                if tr is not None:
                    d = tr.describe()
                    counters["watermark"] = {
                        "wm_ms": d["watermark_ms"],
                        "lag_ms": d["lag_ms"],
                        "buffered": d["buffered"],
                        "late": d["late_total"],
                    }
            # black-box recorder (observability/blackbox.py): ring totals
            # + app-wide incident count on every armed stream node
            bb = getattr(runtime, "_blackbox", None)
            if bb is not None:
                bbc = bb.stream_counters(sid)
                if bbc is not None:
                    counters["blackbox"] = bbc
        if ct is not None:
            comp = ct.component(fused_component)
            if comp is not None:
                counters["compile"] = comp
        # wire-kind calibration pairs live under `stream.<sid>`; the
        # fused group's dispatch pair under its plan component
        cp = dict(calib_by_comp.get(f"stream.{sid}", ()))
        cp.update(calib_by_comp.get(fused_component, ()))
        if cp:
            node["calib"] = cp
        if counters:
            node["counters"] = counters
        nodes.append(node)
        return nid

    # aggregation flows carry qids like "aggregation 'A'": render those as
    # aggregation nodes, everything else as query nodes
    for f in flows:
        is_agg = f.qid.startswith("aggregation ")
        if is_agg:
            aid = f.qid.split("'")[1] if "'" in f.qid else f.qid
            nid = f"aggregation:{aid}"
            node = {"id": nid, "kind": "aggregation", "label": aid}
            if runtime is not None:
                ar = runtime.aggregations.get(aid)
                if ar is not None:
                    try:
                        node["counters"] = {"state": ar.describe_state()}
                    except Exception:
                        pass
            nodes.append(node)
        else:
            nid = f"query:{f.qid}"
            q = qindex.get(f.qid)
            node = {
                "id": nid,
                "kind": "query",
                "label": f.qid,
            }
            if q is not None:
                node["source"] = _source_label(q)
                node["selector"] = _selector_label(q)
                node["sink"] = _sink_label(q)
            counters = _query_counters(
                f, runtime, sm, ct, total_dev_ns, stream_events
            )
            if counters:
                node["counters"] = counters
            qc = static_costs.get(f.qid)
            if qc is not None:
                node["static"] = {
                    "state_bytes": qc.state_bytes,
                    "est_selectivity": qc.est_selectivity,
                    "predicted_compiles": qc.predicted_compiles,
                    "programs": [p.to_dict() for p in qc.programs],
                }
            cp = calib_by_comp.get(f"query.{f.qid}")
            if cp:
                node["calib"] = cp
            nodes.append(node)
        for sid in sorted(f.consumes):
            edges.append({"from": add_stream(sid), "to": nid})
        if f.produces is not None:
            edges.append({"from": nid, "to": add_stream(f.produces)})

    # stand-alone definition nodes: tables, named windows, plus streams no
    # flow touched (sources/sinks-only apps still render their topology)
    for sid in app.stream_definitions:
        add_stream(sid)
    for tid in app.table_definitions:
        node = {"id": f"table:{tid}", "kind": "table", "label": tid}
        if runtime is not None:
            t = runtime.tables.get(tid)
            if t is not None:
                try:
                    node["counters"] = {"state": t.describe_state()}
                except Exception:
                    pass
        nodes.append(node)
    for wid in app.window_definitions:
        node = {"id": f"window:{wid}", "kind": "window", "label": wid}
        if runtime is not None:
            nw = runtime.named_windows.get(wid)
            if nw is not None:
                try:
                    node["counters"] = {"state": nw.describe_state()}
                except Exception:
                    pass
        nodes.append(node)

    plan = {
        "app": app.name,
        "analyzed": bool(flows),
        "live": sm is not None,
        "nodes": nodes,
        "edges": edges,
        "fusion": fusion_summary,
    }
    if calib_summary is not None:
        plan["calibration"] = calib_summary
    # churn ledger (core/churn.py): deploy/undeploy/redeploy counters, last
    # splice wall time, and the last state-seed outcome per component —
    # manager-owned, so it survives the runtime this plan annotates
    if runtime is not None:
        try:
            churn = runtime.manager.churn_stats(runtime.name, create=False)
            if churn is not None:
                plan["churn"] = churn.describe_state()
        except Exception:
            pass
    return plan


def _query_counters(
    flow, runtime, sm, ct, total_dev_ns, stream_events
) -> dict:
    counters: dict = {}
    qid = flow.qid
    # partition-axis mesh placement (parallel/shard.py): rendered even with
    # statistics off — placement is topology, not a counter
    shard_rt = getattr(runtime, "_shard", None) if runtime is not None else None
    if shard_rt is not None:
        pl = shard_rt.partitioned.get(qid)
        if pl is not None:
            counters["shard"] = pl
        # key-sharded group-by / join state (parallel/keyshard.py): static
        # placement plus the live per-device key-occupancy gauges
        ks = shard_rt.keyshard.get(qid) or shard_rt.joins.get(qid)
        if ks is not None:
            entry = dict(ks)
            qr = runtime.queries.get(qid)
            ex = getattr(qr, "_keyshard", None) if qr is not None else None
            if ex is not None:
                entry.update(ex.describe_state())
            counters["keyshard"] = entry
    # live lineage fan-in (observability/lineage.py): rendered even with
    # statistics off — @app:lineage has its own gate
    if runtime is not None:
        qr = runtime.queries.get(qid)
        lin = getattr(qr, "lineage", None) if qr is not None else None
        if lin is not None:
            counters["lineage"] = lin.fan_in()
    if sm is None:
        return counters
    lt = sm.latency.get(f"query.{qid}")
    if lt is not None and lt.samples:
        counters["dispatches"] = lt.samples
        p50, p99 = lt.hist.quantiles([0.5, 0.99])
        counters["latency_ms"] = {
            "p50": round(p50 / 1e6, 3),
            "p99": round(p99 / 1e6, 3),
        }
    dt = sm.device_time.get(f"query.{qid}.step")
    if dt is not None and dt.samples:
        counters["device_ms"] = round(dt.total_ns / 1e6, 3)
        if total_dev_ns > 0:
            counters["device_share"] = round(dt.total_ns / total_dev_ns, 3)
    if ct is not None:
        comp = ct.component(f"query.{qid}")
        if comp is not None:
            counters["compile"] = comp
    # selectivity: output events over input events, when both junctions are
    # metered (fused-ingest insert targets with no consumers publish
    # nothing, so absence of the out meter means "unknown", not 0)
    ins = [stream_events(sid) for sid in flow.consumes]
    ins = [v for v in ins if v is not None]
    out_ev = (
        stream_events(flow.produces) if flow.produces is not None else None
    )
    if ins:
        counters["events_in"] = int(sum(ins))
    if out_ev is not None:
        counters["events_out"] = int(out_ev)
        if ins and sum(ins) > 0:
            counters["selectivity"] = round(out_ev / sum(ins), 4)
    return counters


# ---------------------------------------------------------------------------
# rendering
# ---------------------------------------------------------------------------


def _fmt_counters(c: Optional[dict]) -> str:
    if not c:
        return ""
    parts = []
    for k in (
        "events", "rate_1m", "queue_depth", "fused", "chunk_batches",
        "mesh_devices", "dispatches", "events_in", "events_out", "selectivity",
        "device_ms", "device_share",
    ):
        if k in c:
            parts.append(f"{k}={c[k]}")
    if "latency_ms" in c:
        lm = c["latency_ms"]
        parts.append(f"p50={lm['p50']}ms p99={lm['p99']}ms")
    if "fusedgroup" in c:
        g = c["fusedgroup"]
        pred = g.get("predicted_dispatch_reduction")
        ach = g.get("achieved_dispatch_reduction")
        parts.append(
            f"fusedgroup[{','.join(g.get('queries', ()))}] "
            f"chunks={g.get('chunks')} "
            f"dispatch {g.get('dispatches_per_chunk_before')}->"
            f"{g.get('dispatches_per_chunk_after')}/chunk"
            + (f" pred=-{pred * 100:.1f}%" if pred is not None else "")
            + (f" meas=-{ach * 100:.1f}%" if ach is not None else "")
            + (
                f" shared={len(g['shared_state'])}"
                if g.get("shared_state") else ""
            )
            + (
                f" residual={len(g['residual'])}"
                if g.get("residual") else ""
            )
        )
    if "shard" in c:
        s = c["shard"]
        if s.get("sharded"):  # query node: partition-axis mesh placement
            parts.append(
                f"shard[devices={s.get('devices')} axis={s.get('axis')} "
                f"local_slots={s.get('local_slots')}]"
            )
        else:
            parts.append(f"shard[off: {s.get('reason')}]")
    if "keyshard" in c:
        k = c["keyshard"]
        if k.get("sharded", True):
            extra = ""
            if "per_device_keys" in k:
                extra = (
                    f" keys={k['per_device_keys']}"
                    f" skew={k.get('skew')}"
                )
            parts.append(
                f"keyshard[devices={k.get('devices')}"
                f" axis={k.get('axis')}{extra}]"
            )
        else:
            parts.append(f"keyshard[off: {k.get('reason')}]")
    if "wire" in c:
        w = c["wire"]
        encs = " ".join(
            f"{lane}:{label}" for lane, label in w.get("lanes", {}).items()
        )
        parts.append(
            f"wire[{w.get('source')}] {encs} "
            f"{w.get('encoded_B_per_ev')}B/ev (logical "
            f"{w.get('logical_B_per_ev')}B/ev)"
        )
    if "watermark" in c:
        w = c["watermark"]
        parts.append(
            f"watermark[wm={w.get('wm_ms')} lag={w.get('lag_ms')}ms "
            f"buffered={w.get('buffered')} late={w.get('late')}]"
        )
    if "lineage" in c:
        li = c["lineage"]
        parts.append(
            f"lineage[fan-in avg={li.get('avg_inputs_per_output')} "
            f"max={li.get('max_inputs_per_output')} "
            f"outputs={li.get('outputs')}]"
        )
    if "blackbox" in c:
        bb = c["blackbox"]
        w_ms = bb.get("window_ms") or 0
        parts.append(
            f"blackbox[window={w_ms / 1000:g}s rings={bb.get('rings')} "
            f"incidents={bb.get('incidents')}]"
        )
    if "compile" in c:
        comp = c["compile"]
        causes = ",".join(
            f"{k}:{v}" for k, v in sorted(comp.get("causes", {}).items())
        )
        parts.append(
            f"compiles={comp['compiles']}"
            + (f"[{causes}]" if causes else "")
            + f" wall={comp['wall_ms_total']}ms"
        )
    if "state" in c:
        st = c["state"]
        for k in ("rows", "fill", "capacity"):
            if isinstance(st, dict) and k in st:
                parts.append(f"{k}={st[k]}")
    return "  (" + " ".join(parts) + ")" if parts else ""


def _fmt_calib(cp: dict) -> str:
    """One `calib:` line per node: live-over-predicted ratio per paired
    kind (observability/calibration.py), rendered beside the `static:`
    prediction it calibrates."""
    parts = []
    flags: list[str] = []
    for kind, p in sorted(cp.items()):
        if p.get("live") is None:
            continue
        parts.append(
            f"{kind} {p['predicted']}->{p['live']} x{p['ratio']}"
        )
        for f in p.get("flags", ()):
            if f not in flags:
                flags.append(f)
    if flags:
        parts.append("!! " + ",".join(flags))
    return " | ".join(parts)


def render_text(plan: dict) -> str:
    """Human-readable plan: one block per query with its inputs/outputs,
    then the remaining definition nodes."""
    nodes = {n["id"]: n for n in plan["nodes"]}
    in_edges: dict[str, list[str]] = {}
    out_edges: dict[str, list[str]] = {}
    for e in plan["edges"]:
        out_edges.setdefault(e["from"], []).append(e["to"])
        in_edges.setdefault(e["to"], []).append(e["from"])

    lines = [
        f"EXPLAIN{' ANALYZE' if plan.get('live') else ''} — app "
        f"'{plan['app']}'"
        + ("" if plan.get("analyzed") else "  [analyzer unavailable]")
    ]
    linked: set[str] = set()
    for n in plan["nodes"]:
        if n["kind"] not in ("query", "aggregation"):
            continue
        linked.add(n["id"])
        head = f"{n['kind']} {n['label']}"
        if n.get("source"):
            head += f"  <- {n['source']}"
        lines.append(head + _fmt_counters(n.get("counters")))
        if n.get("selector"):
            lines.append(f"    {n['selector']}  |  {n['sink']}")
        st = n.get("static")
        if st is not None:
            progs = ",".join(
                f"{p['component']}~{p['predicted_compiles']}"
                for p in st.get("programs", [])
            )
            lines.append(
                f"    static: state={st['state_bytes']}B "
                f"sel~{st['est_selectivity']} "
                f"compiles~{st['predicted_compiles']}"
                + (f" [{progs}]" if progs else "")
            )
        cp = n.get("calib")
        if cp:
            rendered = _fmt_calib(cp)
            if rendered:
                lines.append(f"    calib: {rendered}")
        for src in sorted(in_edges.get(n["id"], [])):
            sn = nodes.get(src)
            if sn is None:
                continue
            linked.add(src)
            lines.append(
                f"    in  <- {sn['label']}" + _fmt_counters(sn.get("counters"))
            )
            scp = sn.get("calib")
            if scp:
                rendered = _fmt_calib(scp)
                if rendered:
                    lines.append(f"      calib: {rendered}")
        for dst in sorted(out_edges.get(n["id"], [])):
            dn = nodes.get(dst)
            if dn is None:
                continue
            linked.add(dst)
            lines.append(
                f"    out -> {dn['label']}" + _fmt_counters(dn.get("counters"))
            )
    rest = [
        n for n in plan["nodes"]
        if n["id"] not in linked and n["kind"] != "query"
    ]
    if rest:
        lines.append("definitions:")
        for n in sorted(rest, key=lambda n: n["id"]):
            lines.append(
                f"  {n['kind']} {n['label']}" + _fmt_counters(n.get("counters"))
            )
    fusion = plan.get("fusion")
    if fusion:
        if fusion.get("groups"):
            lines.append("fusion plan:")
            for g in fusion["groups"]:
                lines.append(
                    f"  stream {g['stream']}: fuse "
                    f"{', '.join(g['queries'])}  "
                    f"(-{g['est_dispatch_reduction'] * 100:.1f}% dispatch)"
                )
        if fusion.get("shared_state"):
            for s in fusion["shared_state"]:
                lines.append(
                    f"  shared state on {s['stream']}: "
                    f"{', '.join(s['queries'])} "
                    f"(~{s['est_bytes_saved']}B saved)"
                )
        if fusion.get("blockers"):
            for b in fusion["blockers"]:
                lines.append(
                    f"  blocked: {b['query']} on {b['stream']} "
                    f"({b['hazard']})"
                )
        if fusion.get("rewrites"):
            lines.append("rewrites (value analysis):")
            for r in fusion["rewrites"]:
                detail = ", ".join(
                    f"{k}={v}" for k, v in sorted(r.items()) if k != "kind"
                )
                lines.append(f"  {r['kind']}: {detail}")
    calib = plan.get("calibration")
    if calib:
        line = f"calibration: generation={calib.get('generation')}"
        if calib.get("flags"):
            line += f"  flags={','.join(calib['flags'])}"
        lines.append(line)
        for m in calib.get("mispriced", ()):
            lines.append(
                f"  mispriced {m['reason']} {m['component']}: {m['count']}"
            )
    churn = plan.get("churn")
    if churn:
        line = (
            f"churn: deploys={churn.get('deploys', 0)} "
            f"undeploys={churn.get('undeploys', 0)} "
            f"redeploys={churn.get('redeploys', 0)} "
            f"rollbacks={churn.get('rollbacks', 0)}"
        )
        if churn.get("last_splice_ms") is not None:
            line += f" last_splice={churn['last_splice_ms']}ms"
        lines.append(line)
        seed = churn.get("last_seed")
        if seed:
            outcomes = ", ".join(
                f"{k}={v}" for k, v in sorted(seed.items())
            )
            lines.append(f"  last seed: {outcomes}")
    return "\n".join(lines)


def explain(runtime, fmt: str = "text"):
    """`runtime.explain()` entry: the live-annotated plan as rendered text
    (fmt='text') or the raw plan dict (fmt='dict'/'json')."""
    plan = build_plan(runtime.app, runtime=runtime)
    if fmt in ("dict", "json"):
        return plan
    return render_text(plan)


def explain_static(app, fmt: str = "text"):
    """CLI `--explain`: the plan with no runtime (topology only)."""
    plan = build_plan(app, runtime=None)
    if fmt in ("dict", "json"):
        return plan
    return render_text(plan)
