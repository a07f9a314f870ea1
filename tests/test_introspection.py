"""Engine self-observation tests: state introspection (`describe_state` /
`snapshot_status` / `/status`), the `@app:selfmon` CEP-native self-monitoring
stream, the per-junction flight recorder, and the file-backed error store.

Reference analogs: the runtime object graph SiddhiAppRuntime exposes for
inspection plus this engine's additions (siddhi_tpu/observability/).
"""

import json
import time
import urllib.request

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.errors import SiddhiAppCreationError
from siddhi_tpu.core.error_store import FileErrorStore, InMemoryErrorStore
from siddhi_tpu.core.event import StreamSchema
from siddhi_tpu.core.types import AttrType, InternTable
from siddhi_tpu.observability.flight import FlightRecorder


# ---------------------------------------------------------------------------
# flight recorder unit semantics
# ---------------------------------------------------------------------------


def _mk_recorder(size):
    schema = StreamSchema("S", [("k", AttrType.LONG), ("s", AttrType.STRING)])
    return FlightRecorder(schema, InternTable(), size), schema


class TestFlightRecorderUnit:
    def test_ring_keeps_newest_oldest_first(self):
        fr, _ = _mk_recorder(4)
        x = fr.interner.intern("x")
        for i in range(10):
            fr.record_columns(
                np.asarray([i]), {"k": np.asarray([i]), "s": np.asarray([x])},
                1,
            )
        ev = fr.events()
        assert ev == [(6, (6, "x")), (7, (7, "x")), (8, (8, "x")),
                      (9, (9, "x"))]
        assert fr.describe_state()["recorded"] == 4
        assert fr.describe_state()["total"] == 10
        assert fr.describe_state()["oldest_ts"] == 6
        assert fr.describe_state()["newest_ts"] == 9

    def test_oversized_batch_keeps_only_tail(self):
        fr, _ = _mk_recorder(3)
        x = fr.interner.intern("x")
        n = 11
        fr.record_columns(
            np.arange(n), {"k": np.arange(n), "s": np.full(n, x)}, n
        )
        assert [ts for ts, _ in fr.events()] == [8, 9, 10]
        assert fr.describe_state()["total"] == n

    def test_wrap_across_batches(self):
        fr, _ = _mk_recorder(5)
        x = fr.interner.intern("x")
        fr.record_columns(
            np.arange(3), {"k": np.arange(3), "s": np.full(3, x)}, 3
        )
        fr.record_columns(
            np.arange(3, 7), {"k": np.arange(3, 7), "s": np.full(4, x)}, 4
        )
        assert [ts for ts, _ in fr.events()] == [2, 3, 4, 5, 6]
        assert [ts for ts, _ in fr.events(limit=2)] == [5, 6]

    def test_string_attrs_decode_through_interner(self):
        fr, _ = _mk_recorder(4)
        interner = fr.interner
        a, b = interner.intern("A"), interner.intern("B")
        fr.record_columns(
            np.asarray([1, 2]),
            {"k": np.asarray([10, 20]), "s": np.asarray([a, b])},
            2,
        )
        assert fr.events() == [(1, (10, "A")), (2, (20, "B"))]


# ---------------------------------------------------------------------------
# flight recorder in the engine
# ---------------------------------------------------------------------------


class TestFlightRecorderEngine:
    def test_per_batch_sends_recorded(self):
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime("""
        @flightRecorder(size='4')
        define stream S (v long);
        @info(name='q') from S select v insert into Out;
        """)
        rt.start()
        h = rt.get_input_handler("S")
        for i in range(7):
            h.send((i,), timestamp=i)
        ev = rt.flight_record("S")
        assert [data for _ts, data in ev] == [(3,), (4,), (5,), (6,)]
        # un-recorded stream raises a descriptive error
        with pytest.raises(SiddhiAppCreationError):
            rt.flight_record("Out")
        mgr.shutdown()

    def test_fused_columnar_path_recorded(self):
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime("""
        @app:batch(size='32')
        @flightRecorder(size='8')
        define stream S (k long, v long);
        @info(name='q') from S select k, sum(v) as t group by k insert into Out;
        """)
        rt.start()
        n = 32 * 8
        rt.get_input_handler("S").send_columns(
            np.arange(n, dtype=np.int64),
            {
                "k": np.arange(n, dtype=np.int64) % 4,
                "v": np.ones(n, dtype=np.int64),
            },
        )
        j = rt.junctions["S"]
        assert j.fused_ingest is not None and j.fused_ingest.eligible()
        ev = rt.flight_record("S")
        assert len(ev) == 8
        assert [ts for ts, _ in ev] == list(range(n - 8, n))
        mgr.shutdown()

    def test_env_override_arms_every_junction(self, monkeypatch):
        monkeypatch.setenv("SIDDHI_TPU_FLIGHT", "6")
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime("""
        define stream S (v long);
        @info(name='q') from S select v insert into Out;
        """)
        rt.start()
        h = rt.get_input_handler("S")
        for i in range(3):
            h.send((i,), timestamp=i)
        recs = rt.flight_records()
        # the internal insert-into junction records the query's outputs too
        assert set(recs) >= {"S", "Out"}
        assert [d for _t, d in recs["S"]] == [(0,), (1,), (2,)]
        assert [d for _t, d in recs["Out"]] == [(0,), (1,), (2,)]
        mgr.shutdown()

    def test_dispatch_failure_dumps_flight_into_error_store(self):
        # acceptance: on an induced dispatch failure with the recorder
        # enabled, the error-store entry carries the junction's last-N events
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime("""
        @OnError(action='STORE')
        @flightRecorder(size='4')
        define stream S (v long);
        @info(name='q') from S select v insert into Out;
        """)
        fail = [False]

        def maybe_boom(batch, now):
            if fail[0]:
                raise ValueError("poison")

        rt.junctions["S"].subscribe(maybe_boom, name="custom.boom")
        rt.start()
        h = rt.get_input_handler("S")
        for i in range(5):
            h.send((i,), timestamp=i)
        fail[0] = True
        h.send((99,), timestamp=5)
        entries = mgr.error_store.load(app_name="SiddhiApp")
        assert len(entries) == 1
        e = entries[0]
        assert e.events == [(5, (99,))]
        # last-N ring: the 3 events before the failure + the failing one
        assert e.flight == [(2, (2,)), (3, (3,)), (4, (4,)), (5, (99,))]
        mgr.shutdown()

    def test_bad_annotation_rejected(self):
        mgr = SiddhiManager()
        with pytest.raises(SiddhiAppCreationError):
            mgr.create_siddhi_app_runtime("""
            @flightRecorder(size='0')
            define stream S (v long);
            from S select v insert into Out;
            """)


# ---------------------------------------------------------------------------
# state introspection: describe_state / snapshot_status
# ---------------------------------------------------------------------------


MULTI_APP = """
@app:statistics(reporter='none')
define stream S (symbol string, price float, volume long);
define stream T (symbol string, price float, volume long);
define table Prices (symbol string, price float);
define window W (symbol string, price float) length(8) output all events;
@info(name='win') from S#window.length(4)
select symbol, avg(price) as ap insert into Out;
@info(name='pat') from every a1=S[price > 90] -> a2=S[price < 10]
select a1.symbol as s1, a2.symbol as s2 insert into Matches;
@info(name='tab') from S select symbol, price insert into Prices;
@info(name='feedw') from S select symbol, price insert into W;
"""


class TestSnapshotStatus:
    def test_live_multi_component_snapshot(self):
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime(MULTI_APP)
        rt.start()
        h = rt.get_input_handler("S")
        rows = [("A", 95.0, 10), ("B", 50.0, 20), ("C", 40.0, 30)]
        for i, r in enumerate(rows):
            h.send(r, timestamp=i)
        st = rt.snapshot_status()
        assert st["app"] == "SiddhiApp" and st["running"]

        # junctions: queue depth + subscriber wiring
        s_state = st["streams"]["S"]
        assert s_state["queue_depth"] == 0
        assert set(s_state["subscribers"]) == {
            "query.win", "query.pat", "query.tab", "query.feedw"
        }
        assert "pipeline" in s_state  # fused ingest depth/occupancy

        # window runtime inside a query: type/fill/capacity/ts bounds
        w = st["queries"]["win"]["window"]
        assert w["type"] == "SlidingWindow"
        assert w["capacity"] == 4 and w["fill"] == 3
        assert w["oldest_ts"] == 0 and w["newest_ts"] == 2
        # static: which length step the program took, and how the ring
        # holds its 64-bit lanes (ts, wts, seq, long columns)
        assert w["ring_step"] == "scatter" and w["wide_lanes"] == "u32x2"

        # pattern NFA: per-state active instance counts
        pat = st["queries"]["pat"]
        states = pat["states"]
        assert [s["refs"] for s in states] == [["a1"], ["a2"]]
        # one virgin token waits at a1; the price>90 event armed one at a2
        assert states[0]["active"] == 1
        assert states[1]["active"] == 1
        assert pat["active_instances"] == 2
        assert pat["token_capacity"] == 128

        # named window fed by a query
        nw = st["windows"]["W"]
        assert nw["capacity"] == 8 and nw["fill"] == 3
        assert nw["wide_lanes"] == "u32x2"

        # table row count + capacity
        tab = st["tables"]["Prices"]
        assert tab["rows"] == 3 and tab["capacity"] > 0

        # unfed stream still present, empty
        assert st["streams"]["T"]["queue_depth"] == 0
        mgr.shutdown()

    def test_a_batch_window_reports_no_wide_lanes(self):
        # its lanes are plain int64 arrays: the field is a sliding ring's
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime("""
            define stream S (symbol string, volume long);
            @info(name='q') from S#window.lengthBatch(4)
            select symbol, sum(volume) as v insert into Out;""")
        rt.start()
        rt.get_input_handler("S").send(("A", 2**40), timestamp=7)
        w = rt.snapshot_status()["queries"]["q"]["window"]
        assert w["type"] == "BatchWindow" and w["fill"] == 1
        assert (w["oldest_ts"], w["newest_ts"]) == (7, 7)
        assert "wide_lanes" not in w and "ring_step" not in w
        mgr.shutdown()

    def test_aggregation_buckets_and_watermark(self):
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime("""
        define stream S (symbol string, price float, ts long);
        define aggregation AggP
        from S select symbol, sum(price) as total
        group by symbol aggregate by ts every sec, min;
        """)
        rt.start()
        h = rt.get_input_handler("S")
        base = 1_700_000_000_000
        h.send(("A", 10.0, base), timestamp=base)
        h.send(("B", 20.0, base + 100), timestamp=base + 100)
        h.send(("A", 30.0, base + 61_000), timestamp=base + 61_000)
        st = rt.snapshot_status()
        d = st["aggregations"]["AggP"]["durations"]
        assert set(d) == {"SECONDS", "MINUTES"}
        # the open second-bucket moved to base+61s; the first second's two
        # groups closed into the SECONDS duration table
        assert d["SECONDS"]["watermark_ms"] == base + 61_000
        assert d["SECONDS"]["open_groups"] == 1
        assert d["SECONDS"]["closed_rows"] == 2
        # the minute boundary also passed: both groups closed into the
        # MINUTES table and its open bucket advanced to base's next minute
        assert d["MINUTES"]["closed_rows"] == 2
        assert d["MINUTES"]["watermark_ms"] == 1_700_000_040_000
        mgr.shutdown()

    def test_pattern_absent_deadline_exposed(self):
        # within-clause/absent deadlines: an armed `not ... for` atom must
        # surface its pending wall-clock deadline in the NFA snapshot
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime("""
        @app:playback
        define stream S1 (price float);
        define stream S2 (price float);
        @info(name='q')
        from e1=S1[price>20] -> not S2[price>e1.price] for 150 milliseconds
        select e1.price as p insert into Out;
        """)
        rt.start()
        rt.get_input_handler("S1").send((30.0,), timestamp=1_000)
        d = rt.queries["q"].describe_state()
        assert d["states"][1]["absent"]
        assert d["states"][1]["active"] == 1  # armed, waiting on the clock
        assert d["next_deadline_ms"] == 1_150
        mgr.shutdown()

    def test_async_junction_health(self):
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime("""
        @async(buffer.size='64', workers='1')
        define stream S (v long);
        @info(name='q') from S select v insert into Out;
        """)
        rt.start()
        d = rt.junctions["S"].describe_state()
        assert d["async"]["workers"] == 1
        assert d["async"]["workers_alive"] == 1
        mgr.shutdown()

    def test_status_endpoints(self):
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime(MULTI_APP)
        rt.start()
        h = rt.get_input_handler("S")
        for i in range(3):
            h.send(("A", 50.0, 1), timestamp=i)
        port = mgr.serve_metrics(0)
        base = f"http://127.0.0.1:{port}"
        sj = json.loads(
            urllib.request.urlopen(f"{base}/status.json", timeout=5).read()
        )
        app = sj["apps"]["SiddhiApp"]
        assert app["queries"]["win"]["window"]["fill"] == 3
        assert app["streams"]["S"]["queue_depth"] == 0
        assert "depth" in app["streams"]["S"]["pipeline"]
        text = (
            urllib.request.urlopen(f"{base}/status", timeout=5)
            .read().decode()
        )
        assert "app SiddhiApp [running]" in text
        assert "queue_depth" in text and "fill=3" in text
        mgr.shutdown()

    def test_device_derived_fields_are_always_read(self):
        # window fill and table rows live on the device: a status pull
        # reads them back on every backend, with no opt-in
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime("""
        define stream S (v long);
        define table T (v long);
        @info(name='q') from S#window.length(4) select v insert into Out;
        @info(name='t') from S select v insert into T;
        """)
        rt.start()
        h = rt.get_input_handler("S")
        for i in range(3):
            h.send((i,), timestamp=i)
        st = rt.snapshot_status()
        assert st["queries"]["q"]["window"]["fill"] == 3
        assert st["tables"]["T"]["rows"] == 3
        mgr.shutdown()

    @pytest.mark.parametrize(
        "window,ring_step",
        [("length(8)", "slice"), ("length(3)", "scatter"), ("time(1 min)", None)],
    )
    def test_window_reports_its_ring_step(self, window, ring_step):
        # the length step adapts on a shape, capacity >= batch; the status
        # says which step the deployed program took
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime(f"""
        @app:batch(size='8')
        define stream S (v long);
        @info(name='q') from S#window.{window} select v insert into Out;
        """)
        rt.start()
        rt.get_input_handler("S").send((1,))
        w = rt.snapshot_status()["queries"]["q"]["window"]
        assert w["fill"] == 1
        assert w.get("ring_step") == ring_step
        assert ("ring_step" in w) == (ring_step is not None)
        mgr.shutdown()

    def test_manager_snapshot_includes_error_store(self):
        mgr = SiddhiManager()
        mgr.set_error_store(InMemoryErrorStore(capacity=10))
        rt = mgr.create_siddhi_app_runtime("""
        @OnError(action='STORE')
        define stream S (v long);
        @info(name='q') from S select v insert into Out;
        """)

        def boom(batch, now):
            raise ValueError("poison")

        rt.junctions["S"].subscribe(boom, name="custom.boom")
        rt.start()
        h = rt.get_input_handler("S")
        for i in range(3):
            h.send((i,))
        st = mgr.snapshot_status()
        es = st["error_store"]
        assert es["depth"] == 3
        assert es["by_app"] == {"SiddhiApp": 3}
        assert st["apps"]["SiddhiApp"]["streams"]["S"]["on_error"] == "STORE"
        mgr.shutdown()


# ---------------------------------------------------------------------------
# @app:selfmon — CEP over the engine's own health
# ---------------------------------------------------------------------------


class TestSelfMonitor:
    def test_alert_query_fires_on_latency_condition(self):
        # acceptance: a SiddhiQL query over the selfmon stream raises an
        # alert event when a component's p99 crosses a threshold, end to end
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime("""
        @app:selfmon(interval='100 millisec')
        @app:statistics(reporter='none')
        define stream S (v long);
        @info(name='q') from S select v insert into Out;
        @info(name='alerts')
        from SelfMonitorStream[metric == 'latency_ms' and p99 > 0.0]
        select component, p99 insert into AlertStream;
        """)
        alerts = []
        rt.add_callback(
            "alerts", lambda ts, ins, rem: alerts.extend(ins or [])
        )
        rt.start()
        h = rt.get_input_handler("S")
        for i in range(5):
            h.send((i,))
        t0 = time.time()
        while not alerts and time.time() - t0 < 10:
            time.sleep(0.02)
        assert alerts, "selfmon latency alert must fire"
        comps = {e.data[0] for e in alerts}
        assert "query.q" in comps
        assert all(e.data[1] > 0.0 for e in alerts)
        mgr.shutdown()

    def test_error_and_depth_rows_without_statistics(self):
        # selfmon rides introspection even with @app:statistics absent
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime("""
        @app:selfmon(interval='100 millisec')
        define stream S (v long);
        @info(name='q') from S select v insert into Out;
        @info(name='mon')
        from SelfMonitorStream[metric == 'queue_depth']
        select component, value insert into DepthStream;
        """)
        rows = []
        rt.add_callback("mon", lambda ts, ins, rem: rows.extend(ins or []))
        rt.start()
        want = {"stream.S", "stream.Out"}
        t0 = time.time()
        while (
            not {e.data[0] for e in list(rows)} >= want
            and time.time() - t0 < 10
        ):
            time.sleep(0.02)
        assert {e.data[0] for e in rows} >= want
        assert rt.snapshot_status()["selfmon"]["ticks"] >= 1
        mgr.shutdown()

    def test_bad_interval_rejected(self):
        mgr = SiddhiManager()
        for ann in ("interval='soon'", "interval='1 millisec'", "bogus='1'"):
            with pytest.raises(SiddhiAppCreationError):
                mgr.create_siddhi_app_runtime(f"""
                @app:selfmon({ann})
                define stream S (v long);
                from S select v insert into Out;
                """)

    def test_reserved_stream_name_rejected(self):
        mgr = SiddhiManager()
        with pytest.raises(SiddhiAppCreationError):
            mgr.create_siddhi_app_runtime("""
            @app:selfmon(interval='5 sec')
            define stream SelfMonitorStream (component string, metric string,
                                             value double, p99 double);
            from SelfMonitorStream select component insert into Out;
            """)

    def test_nothing_wired_without_annotations(self):
        # acceptance: describe_state/selfmon/flight cost is zero when
        # disabled — nothing scheduled, nothing attached to the junctions
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime("""
        define stream S (v long);
        @info(name='q') from S select v insert into Out;
        """)
        rt.start()
        assert rt._selfmon is None
        assert "SelfMonitorStream" not in rt.stream_schemas
        assert all(j.flight is None for j in rt.junctions.values())
        assert "selfmon" not in rt.snapshot_status()
        # the scheduler has no recurring selfmon target armed
        assert not rt._scheduler._heap
        mgr.shutdown()


# ---------------------------------------------------------------------------
# file-backed error store (ROADMAP satellite)
# ---------------------------------------------------------------------------


def _entry(app="App1", v=1):
    from siddhi_tpu.core.error_store import ORIGIN_STREAM, make_entry

    return make_entry(
        app, ORIGIN_STREAM, "S", ValueError("boom"), events=[(7, (v, "x"))]
    )


class TestFileErrorStore:
    def test_store_load_purge_roundtrip(self, tmp_path):
        store = FileErrorStore(str(tmp_path))
        for v in range(3):
            store.store(_entry(v=v))
        store.store(_entry(app="App2", v=9))
        assert store.size() == 4
        got = store.load(app_name="App1")
        assert [e.events for e in got] == [[(7, (v, "x"))] for v in range(3)]
        assert got[0].error == "ValueError: boom"
        assert store.load(origin="sink") == []
        assert store.purge([got[0].id]) == 1
        assert store.size() == 3
        assert store.purge() == 3
        assert store.size() == 0

    def test_entries_survive_restart_and_ids_stay_unique(self, tmp_path):
        s1 = FileErrorStore(str(tmp_path))
        s1.store(_entry(v=1))
        s1.store(_entry(v=2))
        s2 = FileErrorStore(str(tmp_path))  # "restart"
        assert [e.events[0][1][0] for e in s2.load()] == [1, 2]
        s2.store(_entry(v=3))
        ids = [e.id for e in s2.load()]
        assert len(set(ids)) == 3 and max(ids) == 3
        assert s2.describe_state()["by_app"] == {"App1": 3}

    def test_capacity_evicts_oldest(self, tmp_path):
        store = FileErrorStore(str(tmp_path), capacity=2)
        for v in range(4):
            store.store(_entry(v=v))
        kept = [e.events[0][1][0] for e in store.load()]
        assert kept == [2, 3]
        assert store.dropped == 2

    def test_flight_dump_survives_restart(self, tmp_path):
        e = _entry(v=5)
        e.flight = [(1, (10, "a")), (2, (20, "b"))]
        s1 = FileErrorStore(str(tmp_path))
        s1.store(e)
        got = FileErrorStore(str(tmp_path)).load()[0]
        assert got.flight == [(1, (10, "a")), (2, (20, "b"))]

    def test_store_survives_exception_with_custom_init(self, tmp_path):
        # dataclasses.asdict would deep-copy the live exception in `cause`
        # and blow up on non-default __init__ signatures — from inside the
        # very store() call capturing the failure
        from siddhi_tpu.core.error_store import ORIGIN_STREAM, make_entry

        class CodedError(Exception):
            def __init__(self, code, msg):
                super().__init__(f"{code}: {msg}")

        store = FileErrorStore(str(tmp_path))
        store.store(make_entry(
            "App1", ORIGIN_STREAM, "S", CodedError(7, "bad"),
            events=[(1, (1, "x"))],
        ))
        got = store.load()[0]
        assert got.error == "CodedError: 7: bad"
        assert got.events == [(1, (1, "x"))]

    def test_size_is_constant_time_counter(self, tmp_path):
        # selfmon polls size() every tick: it must come from the running
        # count, not a directory re-parse
        store = FileErrorStore(str(tmp_path))
        store.store(_entry(v=1))
        store.store(_entry(v=2))
        real_iter = store._iter_entries
        store._iter_entries = lambda: (_ for _ in ()).throw(
            AssertionError("size() must not re-read the directory")
        )
        assert store.size() == 2
        store._iter_entries = real_iter

    def test_replay_from_file_store(self, tmp_path):
        mgr = SiddhiManager()
        mgr.set_error_store(FileErrorStore(str(tmp_path)))
        rt = mgr.create_siddhi_app_runtime("""
        @OnError(action='STORE')
        define stream S (v long);
        @info(name='q') from S select v insert into Out;
        """)
        fail = [True]

        def boom(batch, now):
            if fail[0]:
                raise ValueError("poison")

        rt.junctions["S"].subscribe(boom, name="custom.boom")
        rt.start()
        got = []
        rt.add_callback("q", lambda ts, ins, rem: got.extend(ins or []))
        rt.get_input_handler("S").send((42,))
        assert mgr.error_store.size() == 1
        fail[0] = False
        got.clear()
        assert mgr.replay_errors() == 1
        assert [e.data for e in got] == [(42,)]
        assert mgr.error_store.size() == 0  # purged after replay
        mgr.shutdown()
