"""Static cost model + fusion-feasibility planner (analysis/cost.py,
analysis/fusion.py) and the analyzer satellites that ride with them.

Layers:
* plan snapshot — `--plan --format=json` over a fixed app is byte-stable
  (the FusionPlan is the contract the fusion PR consumes; drift is a
  breaking change);
* planner semantics — hazards (async/partition/rate/scheduler/
  multi-stream/ordering), shared-state candidates, dispatch estimates;
* cost model — window/pattern/join state bytes, tail-variant ladder,
  predicted compile causes;
* explain integration — static cost + fusion summary render in
  `runtime.explain()` next to the live counters;
* satellites — `aggregate by` typing (SA116), aggregation-join and
  store-query `within`/`per` checks (SA117), store-query analysis (SA118).
"""

from __future__ import annotations

import glob
import io
import json
import os
import contextlib

import jax
import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.analysis import (
    analyze,
    analyze_store_query,
    build_fusion_plan,
    compute_costs,
)
from siddhi_tpu.analysis.__main__ import main as lint_main

CORPUS = sorted(
    glob.glob(os.path.join(
        os.path.dirname(__file__), "analysis_corpus", "*.siddhi"
    ))
)

SNAPSHOT_APP = """define stream S (symbol string, price float);
@info(name='avg50') from S[price > 10]#window.length(50)
select symbol, avg(price) as ap insert into AvgOut;
@info(name='max50') from S[price > 10]#window.length(50)
select symbol, max(price) as mx insert into MaxOut;
@info(name='slow') from S#window.time(1 sec)
select symbol insert into SlowOut;
"""

# the FusionPlan contract for SNAPSHOT_APP (costs asserted separately)
SNAPSHOT_PLAN = {
    "version": 3,
    "app": "SiddhiApp",
    "chunk": {"batch_size": 64, "chunk_batches": 32},
    "groups": [
        {
            "stream": "S",
            "component": "stream.S.fusedgroup.0",
            "queries": ["avg50", "max50"],
            "chunk": {"batch_size": 64, "chunk_batches": 32},
            "state_bytes": 3200,
            "dispatches_per_chunk_before": 64,
            "dispatches_per_chunk_after": 1,
            "est_dispatch_reduction": 0.9844,
        }
    ],
    "blockers": [
        {
            "stream": "S",
            "query": "slow",
            "hazard": "scheduler",
            "why": "timer-armed operator needs host scheduling between "
                   "batches",
        }
    ],
    "shared_state": [
        {
            "stream": "S",
            "signature": "filter[(price > 10)] window.length(50)",
            "queries": ["avg50", "max50"],
            "est_bytes_saved": 1600,
        }
    ],
    # v2: the per-stream static WireSpec (core/wire.py) — SNAPSHOT_APP
    # declares no @app:wire hints and no BOOL columns, so nothing is
    # statically encodable; the section still names the predicted
    # logical bytes/event the sampled narrow wire shrinks from
    "wire": {
        "S": {
            "version": 1,
            "source": "static",
            "encodings": {},
            "logical_B_per_ev": 16,
            "encoded_B_per_ev_est": 12,
        }
    },
    # v3: value-analysis sections — SNAPSHOT_APP has no provable rewrite,
    # and the only non-TOP fact is max(price) under the price > 10 filter
    # (float: narrowed to non-null only, never to an interval)
    "rewrites": [],
    "domains": {
        "MaxOut": {"mx": {"non_null": True}},
    },
}


class TestPlanSnapshot:
    def test_plan_dict_is_stable(self):
        plan = build_fusion_plan(SNAPSHOT_APP).to_dict()
        costs = plan.pop("costs")
        assert plan == SNAPSHOT_PLAN
        # cost model invariants for the same app
        avg = costs["queries"]["avg50"]
        assert avg["state_bytes"] == 1600  # 50 x (4+4 attrs + 24 lanes)
        assert avg["est_selectivity"] == 0.5  # filter 0.25 x sliding 2.0
        assert avg["programs"] == [{
            "component": "query.avg50",
            "input_rows": 64,
            "predicted_compiles": 1,
            "predicted_causes": {"first_compile": 1},
        }]
        slow = costs["queries"]["slow"]
        assert slow["scheduler_armed"] is True
        assert slow["programs"][0]["predicted_causes"] == {
            "first_compile": 1, "shape_change": 1,
        }
        assert costs["streams"]["S"] == {
            "stream": "S",
            "component": "stream.S.fused",
            "wire_row_bytes": 16,
            "chunk_batches": 32,
            "tail_variants": [2, 4, 8, 16],
            "narrow_rebuild_hazard": True,
            "predicted_compiles": 6,
            "predicted_causes": {
                "first_compile": 1,
                "tail_variant_k": 4,
                "full_width_rebuild": 1,
            },
        }

    def test_cli_plan_json_matches_api(self, tmp_path, capsys):
        p = tmp_path / "app.siddhi"
        p.write_text(SNAPSHOT_APP)
        assert lint_main(["--plan", "--format=json", str(p)]) == 0
        via_cli = json.loads(capsys.readouterr().out)
        assert via_cli == build_fusion_plan(SNAPSHOT_APP).to_dict()

    def test_cli_plan_text(self, tmp_path, capsys):
        p = tmp_path / "app.siddhi"
        p.write_text(SNAPSHOT_APP)
        assert lint_main(["--plan", str(p)]) == 0
        out = capsys.readouterr().out
        assert "FUSION PLAN v3" in out
        assert "stream S: avg50, max50" in out
        assert "slow on S: scheduler" in out
        assert "shared-state candidates:" in out

    @pytest.mark.parametrize(
        "path", CORPUS, ids=[os.path.basename(p)[:-7] for p in CORPUS]
    )
    def test_plan_never_crashes_on_corpus(self, path, capsys):
        # the CI lint job runs --plan over every corpus + bench app: bad
        # apps still plan (rc 0); only unparsable input is rc 2
        assert lint_main(["--plan", "--format=json", path]) == 0
        json.loads(capsys.readouterr().out)

    def test_plan_over_baseline_workloads(self, capsys):
        from siddhi_tpu.testing.apps import WORKLOADS

        for name, (ql, _stream, _batch) in sorted(WORKLOADS.items()):
            plan = build_fusion_plan(ql).to_dict()
            assert plan["version"] == 3, name
            assert plan["costs"]["queries"], name


class TestPlannerSemantics:
    def test_async_stream_blocks_every_consumer(self):
        plan = build_fusion_plan("""
        @async(buffer.size='128')
        define stream S (a int);
        from S select a insert into Out1;
        from S select a insert into Out2;
        """)
        assert not plan.groups
        assert {b["hazard"] for b in plan.blockers} == {"async-ingress"}
        assert len(plan.blockers) == 2

    def test_partition_blocks_fusion(self):
        r = analyze("""
        define stream S (symbol string, price float);
        from S select symbol insert into Out1;
        partition with (symbol of S) begin
        from S select price insert into #x;
        from #x select price insert into Out2;
        end;
        """)
        assert r.fusion_plan is not None
        hazards = {
            (b["query"], b["hazard"]) for b in r.fusion_plan.blockers
        }
        assert ("partition0_query0", "partition") in hazards
        assert any(d.code == "SA124" for d in r.warnings)

    def test_partitioned_query_is_sized_by_slots_and_sub_batches(self):
        """A partitioned query's state is P x what a slot holds, its flow
        P x B' rows where the step routes rows to their slot and P x B
        where every slot steps the whole batch under a mask. A slot of a
        query that publishes CURRENT rows alone, its `length` window the
        chain's last stage, holds the aggregated columns and no ordering
        lane, and its B' is no longer than the window."""
        text = """
        @app:batch(size='4096')
        @app:partitionCapacity(size='256')
        define stream S (k int, v float);
        define stream T (k int, w float);
        {body}
        """
        inner = ("from S#window.length(50) select k, avg(v) as a "
                 "insert {events} into Out;")
        (one,) = compute_costs(
            text.format(body=inner.format(events="all events"))).queries.values()
        part = compute_costs(text.format(body=f"""
        partition with (k of S, k of T) begin
        @info(name='all') {inner.format(events="all events")}
        @info(name='cur') {inner.format(events="")}
        @info(name='behind')
        from S#window.length(50)[k > 0] select k, avg(v) as a insert into Out3;
        @info(name='j') from S#window.length(2) join T#window.length(2)
        on S.k == T.k select S.k, T.w insert into Out2;
        end;""")).queries
        assert one.partition is None
        ring = 50 * (4 + 4 + 3 * 8)  # k, v and the ts / wts / seq lanes
        (win,) = [o for o in one.operators if o.op.startswith("window")]
        assert win.state_bytes == ring
        for q in ("all", "behind"):  # the whole ring in every slot
            assert part[q].state_bytes == 256 * one.state_bytes
            assert part[q].partition == {
                "capacity": 256, "step": "routed", "sub_batch": 64,
                "flow_rows": 256 * 64}
        # `v` alone, and sub-batches of the window's 50 rows
        assert part["cur"].state_bytes == 256 * (
            one.state_bytes - ring + 50 * 4)
        assert part["cur"].partition == {
            "capacity": 256, "step": "routed", "sub_batch": 50,
            "flow_rows": 256 * 50}
        assert part["j"].partition == {
            "capacity": 256, "step": "masked", "sub_batch": 4096,
            "flow_rows": 256 * 4096}
        assert part["all"].to_dict()["partition"]["flow_rows"] == 16384

    @pytest.mark.parametrize("events,behind,held,sub", [
        ("", "", ["v"], 50), ("all events", "", None, 64),
        ("", "[k > 0]", None, 64)])
    def test_partition_cost_follows_the_deployed_ring(
            self, events, behind, held, sub):
        """What the cost model says of a partitioned query's ring and B'
        is what the deployed runtime holds and traces."""
        text = f"""
        @app:batch(size='4096')
        @app:partitionCapacity(size='256')
        define stream S (k int, v float);
        partition with (k of S) begin
        @info(name='q') from S#window.length(50){behind}
        select k, avg(v) as a insert {events} into Out;
        end;"""
        cost = compute_costs(text).queries["q"]
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime(text)
        try:
            rt.start()
            rt.get_input_handler("S").send_columns(
                np.arange(8, dtype=np.int64),
                {"k": np.arange(8, dtype=np.int32) % 3,
                 "v": np.ones(8, np.float32)})
            status = rt.snapshot_status()["queries"]["q"]
            assert status["window"].get("held_cols") == held
            assert status["partition"]["sub_batch"] == sub
            assert cost.partition["sub_batch"] == sub
            ring = rt.queries["q"].state["chain"]
            ring = {k: v for k, v in ring.items() if k != "total"}
            held_bytes = sum(
                x.nbytes for x in jax.tree_util.tree_leaves(ring))
            (win,) = [o for o in cost.operators if o.op.startswith("window")]
            assert 256 * win.state_bytes == held_bytes
        finally:
            rt.shutdown()
            mgr.shutdown()

    def test_ordering_hazard_intra_group_chain(self):
        plan = build_fusion_plan("""
        define stream S (a int);
        define stream Mid (a int);
        from S select a insert into Mid;
        from S[a > 0] select a insert into Out;
        from Mid select a insert into Out2;
        """)
        # query0 inserts into Mid which query2 consumes -> fusing query0
        # with query1 on S would reorder Mid's delivery
        assert any(b["hazard"] == "ordering" for b in plan.blockers)

    def test_pattern_multi_stream_is_blocked(self):
        plan = build_fusion_plan("""
        define stream A (x int);
        define stream B (y int);
        from A select x insert into OutA;
        from e1=A -> e2=B select e1.x as x insert into OutP;
        """)
        assert any(
            b["hazard"] == "multi-stream" and b["query"] == "query1"
            for b in plan.blockers
        )

    def test_table_join_side_is_not_multi_stream(self):
        # a table side is a passive probe, not stream consumption: two
        # stream-to-table join queries on one stream still fuse
        plan = build_fusion_plan("""
        define stream S (k long, v int);
        define table T (k long, w int);
        from S join T on S.k == T.k select S.k as k, T.w as w
        insert into Out1;
        from S join T on S.k == T.k select S.k as k, S.v as v
        insert into Out2;
        """)
        assert not plan.blockers
        assert len(plan.groups) == 1
        assert plan.groups[0]["queries"] == ["query0", "query1"]

    def test_single_consumer_streams_plan_empty(self):
        plan = build_fusion_plan("""
        define stream S (a int);
        from S select a insert into Out;
        """)
        assert not plan.groups and not plan.blockers
        assert not plan.shared_state

    def test_shared_state_needs_identical_chain(self):
        # different filter => different window content => NOT shareable
        plan = build_fusion_plan("""
        define stream S (a int);
        from S[a > 1]#window.length(10) select a insert into O1;
        from S[a > 2]#window.length(10) select a insert into O2;
        """)
        assert not plan.shared_state
        assert len(plan.groups) == 1  # still fusable, just no shared ring

    def test_every_with_within_is_clean_sa120(self):
        r = analyze("""
        define stream S (a int);
        from every e1=S[a > 1] -> e2=S[a < 0] within 1 sec
        select e1.a as x insert into Out;
        """)
        assert not any(d.code == "SA120" for d in r.diagnostics)

    def test_sa122_batch_shape_drift_downstream(self):
        r = analyze("""
        @app:batch(size='256')
        define stream S (a int);
        define stream Mid (a int);
        from S select a insert into Mid;
        from Mid[a > 0] select a insert into Out;
        """)
        churn = [d for d in r.warnings if d.code == "SA122"]
        assert churn and "256" in churn[0].message


class TestExplainStaticCost:
    def test_static_plan_carries_cost_nodes(self):
        from siddhi_tpu.compiler.siddhi_compiler import SiddhiCompiler
        from siddhi_tpu.observability.explain import explain_static

        app = SiddhiCompiler.parse(SNAPSHOT_APP)
        plan = explain_static(app, fmt="dict")
        nodes = {n["id"]: n for n in plan["nodes"]}
        st = nodes["query:avg50"]["static"]
        assert st["state_bytes"] == 1600
        assert st["predicted_compiles"] == 1
        assert plan["fusion"]["groups"][0]["queries"] == ["avg50", "max50"]
        text = explain_static(app)
        assert "static: state=1600B" in text
        assert "fusion plan:" in text and "blocked: slow on S" in text

    def test_live_explain_renders_static_next_to_counters(self):
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime("""
        @app:statistics(reporter='none')
        define stream S (symbol string, price float);
        @info(name='q') from S[price > 10]#window.length(50)
        select symbol, avg(price) as ap insert into Out;
        """)
        rt.start()
        h = rt.get_input_handler("S")
        for i in range(8):
            h.send(("A", 40.0 + i))
        plan = rt.explain(fmt="dict")
        node = next(n for n in plan["nodes"] if n["id"] == "query:q")
        assert node["static"]["state_bytes"] == 1600
        assert node["counters"]["dispatches"] >= 1  # live ledger present
        text = rt.explain()
        assert "EXPLAIN ANALYZE" in text
        assert "static: state=1600B" in text  # prediction next to counters
        mgr.shutdown()


class TestCostModel:
    def test_tail_variants_ladder(self):
        from siddhi_tpu.analysis.cost import _tail_variants

        assert _tail_variants(32) == [2, 4, 8, 16]
        assert _tail_variants(2) == []
        assert len(_tail_variants(1024)) == 9

    def test_pattern_cost_tensors_and_programs(self):
        model = compute_costs("""
        @app:patternCapacity(size='4096')
        define stream S (a int, b long);
        @info(name='p') from every e1=S[a > 1] -> e2=S[a < 0] within 1 sec
        select e1.a as x insert into Out;
        """)
        qc = model.queries["p"]
        assert qc.kind == "pattern"
        (op,) = [o for o in qc.operators if o.op == "pattern"]
        assert "T=4096" in op.detail and "2 slot(s)" in op.detail
        # one per-stream step program, telemetry component naming
        assert [p.component for p in qc.programs] == ["query.p[S]"]
        # token bookkeeping lanes scale with T
        lanes = {t.lane: t for t in op.tensors}
        assert lanes["tok.active"].shape == (4096,)
        assert lanes["cap0.ts"].shape == (4096, 1)

    def test_join_cost_sides_and_capacity(self):
        model = compute_costs("""
        @app:joinCapacity(size='2048')
        define stream L (k long, v int);
        define stream R (k long, w int);
        @info(name='j') from L#window.length(100) as a
        join R#window.length(100) as b on a.k == b.k
        select a.k as k, b.w as w insert into Out;
        """)
        qc = model.queries["j"]
        assert qc.kind == "join"
        comps = [p.component for p in qc.programs]
        assert comps == ["query.j[left]", "query.j[right]"]
        sides = [o for o in qc.operators if o.op.startswith("join:")]
        assert len(sides) == 2
        assert all("cap=2048" in o.detail for o in sides)

    def test_scheduler_armed_predicts_shape_change(self):
        model = compute_costs("""
        define stream S (ts long, ip string);
        @info(name='q')
        from S#window.externalTimeBatch(ts, 1 sec, 0, 1 sec)
        select ts, count() as c insert into Out;
        """)
        qc = model.queries["q"]
        assert qc.scheduler_armed  # idle-timeout param arms a wall timer
        assert qc.programs[0].predicted_causes["shape_change"] == 1

    def test_state_budget_env_override(self, monkeypatch):
        monkeypatch.setenv("SIDDHI_TPU_STATE_BUDGET_MB", "1")
        r = analyze("""
        define stream S (a int, b long);
        from S#window.length(100000) select a, b insert into Out;
        """)
        assert any(d.code == "SA121" for d in r.warnings)
        monkeypatch.setenv("SIDDHI_TPU_STATE_BUDGET_MB", "1024")
        r = analyze("""
        define stream S (a int, b long);
        from S#window.length(100000) select a, b insert into Out;
        """)
        assert not any(d.code == "SA121" for d in r.warnings)


class TestSatellites:
    APP = """
    define stream Trades (symbol string, price float, volume long, ts long);
    define table Totals (symbol string, total double);
    define aggregation TradeAgg
    from Trades
    select symbol, sum(price) as total
    group by symbol
    aggregate by ts every sec ... hour;
    """

    def test_aggregate_by_long_attr_is_clean(self):
        assert analyze(self.APP).ok

    def test_aggregate_by_string_attr_sa116(self):
        r = analyze("""
        define stream Trades (symbol string, price float);
        define aggregation A
        from Trades select symbol, sum(price) as total group by symbol
        aggregate by symbol every sec ... min;
        """)
        assert [d.code for d in r.errors] == ["SA116"]
        assert "INT/LONG" in r.errors[0].message

    def test_agg_join_clean_and_bad_duration(self):
        ok = analyze(self.APP + """
        from Trades as t join TradeAgg as a
        on t.symbol == a.symbol
        within '2024-**-** **:**:**'
        per 'hours'
        select t.symbol as s, a.total as total insert into Out;
        """)
        assert ok.ok, ok.format()
        bad = analyze(self.APP + """
        from Trades as t join TradeAgg as a
        on t.symbol == a.symbol
        per 'days'
        select t.symbol as s, a.total as total insert into Out;
        """)
        assert [d.code for d in bad.errors] == ["SA117"]
        assert "no 'days' duration" in bad.errors[0].message

    def test_plain_join_within_is_warning_only(self):
        r = analyze(self.APP + """
        define table Ref (symbol string, total double);
        from Trades as t join Ref as r2 on t.symbol == r2.symbol
        per 'hours'
        select t.symbol as s, r2.total as total insert into Out;
        """)
        assert r.ok
        assert any(
            d.code == "SA117" and d.severity == "warning" for d in r.warnings
        )

    def test_store_query_clean(self):
        r = analyze_store_query(
            "from Totals on total > 1.0 select symbol, total", self.APP
        )
        assert r.ok and not r.diagnostics

    def test_store_query_unknown_store(self):
        r = analyze_store_query("from Nope select 1 as x", self.APP)
        assert [d.code for d in r.errors] == ["SA108"]

    def test_store_query_agg_clauses(self):
        no_per = analyze_store_query("from TradeAgg select symbol", self.APP)
        assert [d.code for d in no_per.errors] == ["SA117"]
        bad_range = analyze_store_query(
            "from TradeAgg within '2024-02-01', '2024-01-01' per 'sec' "
            "select symbol",
            self.APP,
        )
        assert [d.code for d in bad_range.errors] == ["SA117"]
        assert "before the end" in bad_range.errors[0].message
        nonagg = analyze_store_query(
            "from Totals within '2024-01-01' per 'sec' select symbol",
            self.APP,
        )
        assert [d.code for d in nonagg.errors] == ["SA117"]

    def test_store_query_shapes(self):
        aimless = analyze_store_query("select 1 as x", self.APP)
        assert [d.code for d in aimless.errors] == ["SA118"]
        bad_target = analyze_store_query(
            "select 'a' as s, 2.0 as t insert into Missing", self.APP
        )
        assert [d.code for d in bad_target.errors] == ["SA108"]
        bad_attr = analyze_store_query("from Totals select nope", self.APP)
        assert [d.code for d in bad_attr.errors] == ["SA103"]
        parse_err = analyze_store_query("from from from", self.APP)
        assert [d.code for d in parse_err.errors] == ["SA001"]

    def test_store_query_runtime_agreement(self):
        # the analyzer's verdict must match StoreQueryRuntime: a clean
        # store query executes; a flagged one raises
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime(self.APP)
        rt.start()
        assert analyze_store_query(
            "from Totals select symbol, total", self.APP
        ).ok
        rows = rt.query("from Totals select symbol, total")
        assert rows == []
        bad = "from Totals within '2024-01-01' per 'sec' select symbol"
        assert not analyze_store_query(bad, self.APP).ok
        with pytest.raises(Exception):
            rt.query(bad)
        mgr.shutdown()


class TestAnalyzeCarriesPlan:
    def test_analyze_result_has_fusion_plan(self):
        r = analyze(SNAPSHOT_APP)
        assert r.fusion_plan is not None
        assert r.fusion_plan.to_dict()["groups"][0]["queries"] == [
            "avg50", "max50",
        ]

    def test_plan_text_renders_without_stdout_noise(self):
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            plan = build_fusion_plan(SNAPSHOT_APP)
        assert buf.getvalue() == ""
        from siddhi_tpu.analysis.fusion import render_plan_text

        assert "FUSION PLAN v3" in render_plan_text(plan)
