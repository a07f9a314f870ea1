"""Continuous profiler + EXPLAIN ANALYZE (observability/profiler.py,
observability/explain.py, /profile + /explain endpoints).

Covers: compile telemetry (count/cause/wall per program, cache-hit
accounting, the recompile-cause taxonomy), per-chunk stage waterfalls on
the fused (serial + pipelined, deliver and non-deliver) and per-batch
paths, the top-K slowest ring bound, `runtime.explain()` live annotations
on a multi-query app, the HTTP endpoints, and the zero-overhead-when-off
contract (companion to the gating tests in tests/test_statistics.py).
"""

from __future__ import annotations

import json
import urllib.request

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.observability.profiler import (
    CAUSE_FIRST,
    CAUSE_TAIL_K,
    CompileTelemetry,
    Profiler,
)


class _Gate:
    enabled = True


def _mk(mgr, extra=""):
    rt = mgr.create_siddhi_app_runtime(f"""
    @app:statistics(reporter='none')
    @app:batch(size='32')
    define stream S (symbol string, price float);
    @info(name='q')
    from S[price > 10]#window.length(8)
    select symbol, avg(price) as ap insert into Out;
    {extra}
    """)
    rt.start()
    return rt


def _feed_columns(mgr, rt, n, start=0):
    h = rt.get_input_handler("S")
    sym = np.full((n,), mgr.interner.intern("A"), dtype=np.int32)
    h.send_columns(
        np.arange(n, dtype=np.int64) + start,
        {"symbol": sym, "price": np.linspace(0, 99, n, dtype=np.float32)},
    )


class TestCompileTelemetryUnit:
    def test_cache_growth_is_a_compile_and_hits_count(self):
        import jax
        import jax.numpy as jnp

        ct = CompileTelemetry(gate=_Gate())
        f = jax.jit(lambda x: x + 1)
        f(jnp.zeros(3))
        ct.observe("c", f, (3,), 1_000_000)
        f(jnp.zeros(3))
        ct.observe("c", f, (3,), 1_000)
        f(jnp.zeros(4))
        ct.observe("c", f, (4,), 2_000_000)
        rep = ct.report()["c"]
        assert rep["compiles"] == 2
        assert rep["cache_hits"] == 1
        assert rep["causes"] == {"first_compile": 1, "shape_change": 1}
        assert rep["signatures"] == 2
        assert rep["wall_ms_total"] == pytest.approx(3.0, abs=0.01)
        assert len(rep["recent"]) == 2
        assert rep["recent"][0]["cause"] == CAUSE_FIRST

    def test_tail_hint_on_first_compile_reads_first_compile(self):
        import jax
        import jax.numpy as jnp

        ct = CompileTelemetry(gate=_Gate())
        f = jax.jit(lambda x: x * 2)
        f(jnp.zeros(2))
        ct.observe("c", f, (2,), 1000, cause_hint=CAUSE_TAIL_K)
        f(jnp.zeros(5))
        ct.observe("c", f, (5,), 1000, cause_hint=CAUSE_TAIL_K)
        causes = ct.report()["c"]["causes"]
        assert causes == {"first_compile": 1, "tail_variant_k": 1}

    def test_gate_off_is_a_noop(self):
        g = _Gate()
        g.enabled = False
        ct = CompileTelemetry(gate=g)
        ct.observe("c", object(), (1,), 1000)
        assert ct.report() == {}
        assert ct.component("c") is None


class TestProfilerUnit:
    def test_top_k_keeps_slowest(self):
        import time

        prof = Profiler(gate=_Gate(), top_k=2)
        # far enough apart that a loaded machine cannot reorder them (the
        # 1 ms sleep read longer than the 3 ms one under six test workers)
        for i, dt in enumerate((0.03, 0.001, 0.06)):
            wf = prof.begin("S", 10)
            wf.stage("encode", int(dt * 1e9))
            time.sleep(dt)
            prof.end(wf)
        rep = prof.report()
        assert rep["chunks"] == 3 and rep["events"] == 30
        tops = [w["seq"] for w in rep["slowest"]]
        assert len(tops) == 2 and 2 not in tops  # the fast one evicted
        assert rep["slowest"][0]["total_ms"] >= rep["slowest"][1]["total_ms"]

    def test_stages_ms_is_over_every_chunk_not_the_top_k(self):
        prof = Profiler(gate=_Gate(), top_k=2)
        for i in range(12):
            wf = prof.begin("S", 10, "fused" if i % 3 else "batch")
            wf.stage("encode", (i + 1) * 1_000_000)
            prof.end(wf)
        rep = prof.report()
        assert len(rep["slowest"]) == 2
        fused = rep["stages_ms"]["fused"]["encode"]
        batch = rep["stages_ms"]["batch"]["encode"]
        assert fused["count"] == 8 and batch["count"] == 4
        # exact sums over all chunks, not over the two kept waterfalls
        assert fused["mean"] == pytest.approx(
            sum(i + 1 for i in range(12) if i % 3) / 8
        )
        assert batch["mean"] == pytest.approx((1 + 4 + 7 + 10) / 4)
        assert batch["p50"] <= batch["p99"] <= 10.5
        assert rep["stages_ms"]["fused"]["total"]["count"] == 8

    def test_gate_off_returns_none_and_records_nothing(self):
        g = _Gate()
        g.enabled = False
        prof = Profiler(gate=g)
        assert prof.begin("S", 1) is None
        prof.end(None)  # must not raise
        assert prof.tls_wf() is None  # no active chunk on this thread
        assert prof.report() == {
            "chunks": 0, "events": 0, "slowest": [], "stages_ms": {},
        }


class TestEngineProfile:
    def test_fused_ingest_records_compiles_and_waterfalls(self):
        mgr = SiddhiManager()
        rt = _mk(mgr)
        _feed_columns(mgr, rt, 1024)  # full chunk, fused deliverless
        _feed_columns(mgr, rt, 256)   # short tail -> tail-variant compile
        prof = rt.profile_report()
        comp = prof["compile"]
        fused = [k for k in comp if k.startswith("stream.S.fused")]
        assert fused, comp
        ledger = comp[fused[0]]
        assert ledger["compiles"] >= 2
        assert CAUSE_FIRST in ledger["causes"]
        assert CAUSE_TAIL_K in ledger["causes"]
        assert ledger["wall_ms_total"] > 0
        ev = ledger["recent"][0]
        assert ev["wall_ms"] > 0 and ev["cause"] == CAUSE_FIRST
        wfs = prof["waterfalls"]
        assert wfs["chunks"] >= 2 and wfs["events"] >= 1024
        stages = wfs["slowest"][0]["stages_ms"]
        assert "encode" in stages and "dispatch" in stages
        mgr.shutdown()

    def test_deliver_mode_waterfall_has_drain_stages(self):
        mgr = SiddhiManager()
        rt = _mk(mgr)
        got = []
        rt.add_callback("q", lambda ts, i, r: got.extend(i or []))
        _feed_columns(mgr, rt, 1024)
        prof = rt.profile_report()
        assert got, "callbacks must deliver"
        stages = prof["waterfalls"]["slowest"][0]["stages_ms"]
        for s in ("encode", "dispatch", "device", "deliver"):
            assert s in stages, stages

    def test_per_batch_waterfall_has_device_and_readback(self):
        mgr = SiddhiManager()
        rt = _mk(mgr)
        got = []
        rt.add_callback("q", lambda ts, i, r: got.extend(i or []))
        _feed_columns(mgr, rt, 32)  # single micro-batch: per-batch path
        prof = rt.profile_report()
        wfs = prof["waterfalls"]["slowest"]
        assert wfs, prof
        stages = wfs[0]["stages_ms"]
        for s in ("encode", "dispatch", "device", "readback"):
            assert s in stages, stages
        mgr.shutdown()

    def test_per_query_step_compile_ledger(self):
        mgr = SiddhiManager()
        rt = _mk(mgr)
        h = rt.get_input_handler("S")
        for i in range(3):
            h.send(("A", float(40 + i)))
        comp = rt.profile_report()["compile"]["query.q"]
        assert comp["compiles"] == 1
        assert comp["causes"] == {"first_compile": 1}
        assert comp["cache_hits"] == 2
        mgr.shutdown()

    def test_high_quantiles_include_p9999(self):
        mgr = SiddhiManager()
        rt = _mk(mgr)
        h = rt.get_input_handler("S")
        for i in range(4):
            h.send(("A", float(i)))
        prof = rt.profile_report()
        lat = prof["latency_high_ms"]["query.q"]
        assert set(lat) == {"count", "p99", "p999", "p9999"}
        assert lat["p9999"] >= lat["p99"] > 0
        # the full report summaries carry p9999 too (Prometheus 0.9999)
        summ = rt.statistics_manager.report()["latency_ms"]["query.q"]
        assert "p9999" in summ
        text = mgr.prometheus_text()
        assert 'quantile="0.9999"' in text
        mgr.shutdown()


class TestExplain:
    def test_explain_multi_query_live_counters(self):
        mgr = SiddhiManager()
        rt = _mk(mgr, extra="""
        @info(name='q2') from S select symbol, price insert into Out2;
        """)
        _feed_columns(mgr, rt, 320)
        # one per-batch send so query.q's own step program compiles too
        # (fused sends run the impls inside the chunk program, whose
        # ledger sits on the stream node)
        rt.get_input_handler("S").send(("A", 50.0))
        plan = rt.explain(fmt="dict")
        assert plan["live"] and plan["analyzed"]
        nodes = {n["id"]: n for n in plan["nodes"]}
        assert "query:q" in nodes and "query:q2" in nodes
        qc = nodes["query:q"]["counters"]
        assert qc["dispatches"] >= 1
        assert qc["events_in"] == 321
        assert "latency_ms" in qc and "compile" in qc
        assert "compile" in nodes["stream:S"]["counters"]
        sc = nodes["stream:S"]["counters"]
        assert sc["events"] == 321
        assert sc["fused"] in ("pipelined", "serial")
        # edges connect S to both queries
        froms = [
            e for e in plan["edges"]
            if e["from"] == "stream:S" and e["to"].startswith("query:")
        ]
        assert len(froms) == 2
        text = rt.explain()
        assert "EXPLAIN ANALYZE" in text and "query q2" in text
        mgr.shutdown()

    def test_explain_without_statistics_is_topology_only(self):
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime("""
        define stream S (a int);
        @info(name='q') from S select a insert into Out;
        """)
        rt.start()
        plan = rt.explain(fmt="dict")
        assert not plan["live"]
        assert any(n["id"] == "query:q" for n in plan["nodes"])
        assert "EXPLAIN —" in rt.explain()
        mgr.shutdown()

    def test_explain_partitioned_app_renders(self):
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime("""
        @app:statistics(reporter='none')
        define stream S (symbol string, price float);
        partition with (symbol of S) begin
        @info(name='pq') from S[price > 20] select symbol, price as ap
        insert into #tmp;
        @info(name='pq2') from #tmp select symbol insert into Out2;
        end;
        """)
        rt.start()
        h = rt.get_input_handler("S")
        for i in range(4):
            h.send(("A", float(10 + i * 20)))
        text = rt.explain()
        assert "query pq" in text and "#tmp" in text
        mgr.shutdown()


class TestProfileEndpoints:
    def test_profile_and_explain_served(self):
        mgr = SiddhiManager()
        rt = _mk(mgr)
        _feed_columns(mgr, rt, 256)
        port = mgr.serve_metrics(0)

        def get(path):
            return urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=10
            ).read().decode()

        prof = json.loads(get("/profile"))
        assert len(prof) == 1 and prof[0]["app"] == "SiddhiApp"
        assert any(
            ent["compiles"] >= 1 and ent["recent"][0]["wall_ms"] > 0
            for ent in prof[0]["compile"].values()
        )
        assert prof[0]["waterfalls"]["chunks"] >= 1
        text = get("/explain")
        assert "EXPLAIN ANALYZE" in text and "query q" in text
        plan = json.loads(get("/explain.json"))["SiddhiApp"]
        assert plan["nodes"] and plan["edges"]
        mgr.shutdown()


class TestZeroOverheadWhenOff:
    def test_no_statistics_annotation_wires_nothing(self):
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime("""
        define stream S (a int);
        @info(name='q') from S select a insert into Out;
        """)
        rt.start()
        qr = rt.queries["q"]
        assert qr.compile_telemetry is None and qr.profiler is None
        j = rt.junctions["S"]
        assert j.profiler is None and j.compile_telemetry is None
        assert rt.profile_report() is None
        mgr.shutdown()

    def test_enable_stats_false_gates_profiler_and_telemetry(self):
        mgr = SiddhiManager()
        rt = _mk(mgr)
        _feed_columns(mgr, rt, 256)
        before = rt.profile_report()
        assert before["waterfalls"]["chunks"] >= 1
        compiles_before = {
            k: v["compiles"] for k, v in before["compile"].items()
        }
        hits_before = {
            k: v["cache_hits"] for k, v in before["compile"].items()
        }
        rt.enable_stats(False)
        _feed_columns(mgr, rt, 256, start=10_000)
        h = rt.get_input_handler("S")
        for i in range(3):
            h.send(("A", 50.0))
        after = rt.profile_report()
        assert after["waterfalls"]["chunks"] == before["waterfalls"]["chunks"]
        assert {
            k: v["compiles"] for k, v in after["compile"].items()
        } == compiles_before
        assert {
            k: v["cache_hits"] for k, v in after["compile"].items()
        } == hits_before  # not even hit-counting while off
        rt.enable_stats(True)
        _feed_columns(mgr, rt, 256, start=20_000)
        assert (
            rt.profile_report()["waterfalls"]["chunks"]
            > before["waterfalls"]["chunks"]
        )
        mgr.shutdown()
