"""Supervised runtime tests: deterministic fault injection, auto-checkpoint,
crash recovery, and restart policies.

The chaos contract under test (ISSUE 9): with fault injection on, the
supervisor auto-restarts a crashed app within `max.attempts`, restored
window/aggregation state matches a never-crashed control run, and no
`@OnError(action='STORE')` event is lost across the crash. The subprocess
SIGKILL variant of the same proof runs in CI (`tools/chaos_smoke.py`).
"""

import logging
import time

import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.errors import SiddhiAppCreationError
from siddhi_tpu.core.persistence import (
    FileSystemPersistenceStore,
    IncrementalFileSystemPersistenceStore,
    InMemoryPersistenceStore,
)
from siddhi_tpu.core.supervision import prune_revisions
from siddhi_tpu.testing import FaultPlan, FaultRule, InjectedFault, faults


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    yield
    faults.uninstall()


def _wait_for(pred, timeout=30.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        v = pred()
        if v:
            return v
        time.sleep(0.01)
    return pred()


# ---------------------------------------------------------------------------
# fault-injection harness
# ---------------------------------------------------------------------------


class TestFaultPlan:
    def test_after_and_times(self):
        plan = FaultPlan([FaultRule(site="x", after=2, times=2)])
        fired = []
        for i in range(6):
            try:
                plan.check("x")
            except InjectedFault:
                fired.append(i)
        assert fired == [2, 3]
        assert plan.report()["rules"][0] == {
            "site": "x", "match": "", "after": 2, "times": 2, "p": 1.0,
            "hits": 6, "fired": 2,
        }

    def test_match_filters_by_key(self):
        plan = FaultPlan([FaultRule(site="x", match="S:", times=None)])
        plan.check("x", "T:query.q")  # no match, no fire
        with pytest.raises(InjectedFault):
            plan.check("x", "S:query.q")
        assert plan.log == [("x", "S:query.q")]

    def test_probability_is_seed_deterministic(self):
        def run(seed):
            plan = FaultPlan(
                [FaultRule(site="x", p=0.3, times=None)], seed=seed
            )
            fired = []
            for i in range(50):
                try:
                    plan.check("x")
                except InjectedFault:
                    fired.append(i)
            return fired

        a, b, c = run(7), run(7), run(8)
        assert a == b  # same seed, same schedule
        assert a != c  # different seed, different schedule
        assert 0 < len(a) < 50

    def test_parse_grammar(self):
        plan = faults.parse_plan(
            "seed=42;junction_dispatch:after=10,times=2;"
            "sink_publish@Out:p=0.2,times=-1;drain_worker:error=conn,times=1"
        )
        assert plan.seed == 42
        r0, r1, r2 = plan.rules
        assert (r0.site, r0.after, r0.times) == ("junction_dispatch", 10, 2)
        assert (r1.site, r1.match, r1.p, r1.times) == (
            "sink_publish", "Out", 0.2, None,
        )
        assert r2.error == "conn"

    def test_parse_rejects_malformed(self):
        for bad in (
            "site_with_no_opts",
            "x:notkv",
            "x:p=1.5",
            "x:error=boom",
            "x:frobnicate=1",
        ):
            with pytest.raises(ValueError):
                faults.parse_plan(bad)

    def test_sink_site_defaults_to_connection_error(self):
        from siddhi_tpu.core.errors import ConnectionUnavailableError

        plan = FaultPlan([FaultRule(site="sink_publish")])
        with pytest.raises(ConnectionUnavailableError):
            plan.check("sink_publish", "app:Out")

    def test_inactive_plan_is_free(self):
        assert faults.ACTIVE is None
        faults.hit("junction_dispatch", "anything")  # no-op, no raise


# ---------------------------------------------------------------------------
# @app:persist — auto-checkpoint + retention
# ---------------------------------------------------------------------------


PERSIST_APP = """
@app:name('AutoPersistApp')
@app:persist(interval='100 millisec', keep='2')
define stream S (sym string, v long);
@info(name='q')
from S#window.length(3) select sym, sum(v) as total insert into Out;
"""


class TestAutoPersist:
    def test_periodic_persist_and_retention(self, tmp_path):
        store = FileSystemPersistenceStore(str(tmp_path))
        mgr = SiddhiManager()
        mgr.set_persistence_store(store)
        rt = mgr.create_siddhi_app_runtime(PERSIST_APP)
        rt.start()
        rt.get_input_handler("S").send(("A", 10), timestamp=1)
        assert _wait_for(lambda: rt._autopersist.persists >= 3, timeout=10)
        # poll: a FOURTH cycle may be mid-flight (persist done, prune not
        # yet) at the moment the wait above returns — retention converges
        # to keep=2 between cycles
        assert _wait_for(
            lambda: len(store.list_revisions("AutoPersistApp")) <= 2
            and rt._autopersist.pruned >= 1,
            timeout=10,
        ), "retention must prune to keep=2"
        st = rt.snapshot_status()["autopersist"]
        assert st["persists"] >= 3 and st["keep"] == 2
        mgr.shutdown()

    def test_restore_from_auto_checkpoint(self, tmp_path):
        store = FileSystemPersistenceStore(str(tmp_path))
        mgr = SiddhiManager()
        mgr.set_persistence_store(store)
        rt = mgr.create_siddhi_app_runtime(PERSIST_APP)
        rt.start()
        rt.get_input_handler("S").send(("A", 10), timestamp=1)
        rt.get_input_handler("S").send(("A", 20), timestamp=2)
        # wait for a checkpoint taken AFTER both sends: a cycle may be in
        # flight right now with a snapshot from between them, and `persists`
        # counts it only when it ends, so the first increment can be that
        # stale one; the second began after it, hence after both sends
        p0 = rt._autopersist.persists
        assert _wait_for(
            lambda: rt._autopersist.persists > p0 + 1, timeout=10
        )
        mgr.shutdown()

        mgr2 = SiddhiManager()
        mgr2.set_persistence_store(store)
        rt2 = mgr2.create_siddhi_app_runtime(PERSIST_APP)
        got = []
        rt2.add_callback("q", lambda ts, i, r: got.extend(
            e.data for e in i or []
        ))
        rt2.restore_last_revision()
        rt2.start()
        rt2.get_input_handler("S").send(("A", 5), timestamp=3)
        assert _wait_for(lambda: got)
        assert got[-1] == ("A", 35)  # 10 + 20 restored + 5
        mgr2.shutdown()

    def test_persist_save_fault_counts_and_recovers(self, tmp_path):
        store = FileSystemPersistenceStore(str(tmp_path))
        mgr = SiddhiManager()
        mgr.set_persistence_store(store)
        rt = mgr.create_siddhi_app_runtime(PERSIST_APP)
        faults.install(faults.parse_plan("persist_save:times=1"))
        rt.start()
        assert _wait_for(lambda: rt._autopersist.failures >= 1, timeout=10)
        # the next interval succeeds: the injected fault fired once
        assert _wait_for(lambda: rt._autopersist.persists >= 1, timeout=10)
        assert rt._autopersist.last_error is None
        mgr.shutdown()

    def test_incremental_base_not_shifted_by_failed_save(self, tmp_path):
        """A failed FULL-snapshot save must not advance the delta base:
        the next persist must emit a full again (a delta against a base
        that never reached the store restores wrong state or no-ops)."""
        import pickle

        store = IncrementalFileSystemPersistenceStore(str(tmp_path))
        mgr = SiddhiManager()
        mgr.set_persistence_store(store)
        rt = mgr.create_siddhi_app_runtime("""
        @app:name('IncBase')
        define stream S (v long);
        @info(name='q')
        from S#window.length(3) select sum(v) as total insert into Out;
        """)
        rt.start()
        rt.get_input_handler("S").send((10,), timestamp=1)
        faults.install(faults.parse_plan("persist_save:times=1"))
        try:
            with pytest.raises(InjectedFault):
                rt.persist()  # full staged, save fails -> base NOT committed
        finally:
            faults.uninstall()
        rt.get_input_handler("S").send((20,), timestamp=2)
        rev = rt.persist()
        data = pickle.loads(store.load("IncBase", rev))
        assert data["type"] == "full", (
            "first persisted revision must be a full snapshot, not a delta "
            "against a base that never reached the store"
        )
        got = []
        rt.add_callback("q", lambda ts, i, r: got.extend(
            e.data for e in i or []
        ))
        rt.restore_last_revision()
        rt.get_input_handler("S").send((5,), timestamp=3)
        assert _wait_for(lambda: got)
        assert got[-1] == (35,)  # 10 + 20 restored + 5
        mgr.shutdown()

    def test_no_store_disables_autopersist(self):
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime(PERSIST_APP)
        rt.start()  # logs a warning, must not raise or schedule failures
        time.sleep(0.25)
        assert rt._autopersist.persists == 0
        assert rt._autopersist.failures == 0
        mgr.shutdown()

    def test_bad_annotation_rejected_at_creation(self):
        mgr = SiddhiManager()
        with pytest.raises(SiddhiAppCreationError):
            mgr.create_siddhi_app_runtime(
                "@app:persist(interval='sometimes')\n"
                "define stream S (a int);\n"
                "from S select a insert into Out;"
            )
        mgr.shutdown()

    def test_prune_keeps_incremental_base(self, tmp_path):
        store = IncrementalFileSystemPersistenceStore(str(tmp_path))
        mgr = SiddhiManager()
        mgr.set_persistence_store(store)
        rt = mgr.create_siddhi_app_runtime("""
        @app:name('IncPrune')
        define stream S (v long);
        @info(name='q')
        from S#window.length(3) select sum(v) as total insert into Out;
        """)
        rt.start()
        h = rt.get_input_handler("S")
        h.send((1,), timestamp=1)
        rt.persist()  # full
        h.send((2,), timestamp=2)
        rt.persist()  # delta
        h.send((3,), timestamp=3)
        rt.persist()  # delta
        pruned = prune_revisions(store, "IncPrune", keep=1)
        revs = store.list_revisions("IncPrune")
        # the full base must survive: the kept delta replays from it
        import pickle

        kinds = [
            pickle.loads(store.load("IncPrune", r))["type"] for r in revs
        ]
        assert "full" in kinds, (pruned, revs, kinds)
        rt.restore_last_revision()  # must still resolve its chain
        got = []
        rt.add_callback("q", lambda ts, i, r: got.extend(
            e.data for e in i or []
        ))
        h.send((4,), timestamp=4)
        assert _wait_for(lambda: got)
        assert got[-1] == ((2 + 3 + 4),)
        mgr.shutdown()


# ---------------------------------------------------------------------------
# supervisor: crash -> restart -> restore -> replay
# ---------------------------------------------------------------------------


SUP_APP = """
@app:name('SupApp')
@app:restart(policy='on-failure', max.attempts='3')
@OnError(action='STORE')
define stream S (sym string, v long);
define stream C (x long);
@info(name='q')
from S#window.length(3) select sym, sum(v) as total insert into Out;
@info(name='qc')
from C select x insert into COut;
"""


def _sup_setup(tmp_path, app=SUP_APP):
    mgr = SiddhiManager()
    mgr.set_persistence_store(FileSystemPersistenceStore(str(tmp_path)))
    sup = mgr.supervise(poll_interval_s=0.05)
    rt = mgr.create_siddhi_app_runtime(app)
    got = []
    rt.add_callback("q", lambda ts, i, r: got.extend(e.data for e in i or []))
    rt.start()
    return mgr, sup, rt, got


class TestSupervisor:
    def test_crash_restart_restore_replay_matches_control(self, tmp_path):
        # control: the same feed with no faults and no crash
        cmgr = SiddhiManager()
        crt = cmgr.create_siddhi_app_runtime(SUP_APP.replace("SupApp", "Ctl"))
        control = []
        crt.add_callback("q", lambda ts, i, r: control.extend(
            e.data for e in i or []
        ))
        crt.start()
        ch = crt.get_input_handler("S")
        for ts, v in ((1, 10), (2, 20), (3, 30), (4, 40)):
            ch.send(("A", v), timestamp=ts)
        cmgr.shutdown()

        mgr, sup, rt, got = _sup_setup(tmp_path)
        h = sup.input_handler("SupApp", "S")
        h.send(("A", 10), timestamp=1)
        h.send(("A", 20), timestamp=2)
        rt.persist()
        # guarded dispatch failure on S: the batch lands in the error store
        faults.install(faults.parse_plan("junction_dispatch@S:times=1"))
        h.send(("A", 30), timestamp=3)
        assert len(mgr.error_store.load()) == 1
        # unguarded crash on C: fatal signal -> supervised restart
        faults.install(faults.parse_plan("junction_dispatch@C:times=1"))
        with pytest.raises(InjectedFault):
            sup.input_handler("SupApp", "C").send((1,), timestamp=3)
        assert _wait_for(lambda: sup.restarts.get("SupApp", 0) >= 1)
        faults.uninstall()
        # zero STORE'd-event loss: the stored entry was replayed and purged
        assert _wait_for(lambda: not mgr.error_store.load())
        h.send(("A", 40), timestamp=4)
        assert _wait_for(lambda: len(got) >= 4)
        assert got == control, (
            "restored + replayed outputs must match the never-crashed run"
        )
        st = mgr.snapshot_status()
        assert st["supervisor"]["restarts_total"] == 1
        assert 'siddhi_supervisor_restarts_total{app="SupApp"} 1' in (
            mgr.prometheus_text()
        )
        mgr.shutdown()

    def test_restart_within_max_attempts_then_gives_up(self, tmp_path):
        app = SUP_APP.replace("max.attempts='3'", "max.attempts='2'").replace(
            "SupApp", "GiveUp"
        )
        mgr, sup, rt, _got = _sup_setup(tmp_path, app)
        # every dispatch to C fails, forever: each restart crashes again on
        # the next send until the budget runs out
        faults.install(faults.parse_plan("junction_dispatch@C:times=-1"))
        for ts in range(3):
            try:
                sup.input_handler("GiveUp", "C").send((ts,), timestamp=ts)
            except InjectedFault:
                pass
            time.sleep(0.3)
        assert _wait_for(lambda: "GiveUp" in sup.gave_up, timeout=15)
        assert sup.restarts.get("GiveUp", 0) <= 2
        rt2 = mgr.get_siddhi_app_runtime("GiveUp")
        assert rt2 is None or not rt2._running  # left down, not flapping
        mgr.shutdown()

    def test_policy_never_leaves_app_down(self, tmp_path):
        app = SUP_APP.replace(
            "policy='on-failure', max.attempts='3'", "policy='never'"
        ).replace("SupApp", "NeverApp")
        mgr, sup, rt, _got = _sup_setup(tmp_path, app)
        faults.install(faults.parse_plan("junction_dispatch@C:times=1"))
        with pytest.raises(InjectedFault):
            sup.input_handler("NeverApp", "C").send((1,), timestamp=1)
        assert _wait_for(lambda: "NeverApp" in sup.gave_up)
        assert sup.restarts.get("NeverApp", 0) == 0
        mgr.shutdown()

    def test_dead_async_drain_worker_detected(self, tmp_path):
        app = """
        @app:name('AsyncDead')
        @app:restart(max.attempts='3')
        @async(buffer.size='64', workers='1')
        define stream S (v long);
        @info(name='q')
        from S select v insert into Out;
        """
        mgr = SiddhiManager()
        mgr.set_persistence_store(FileSystemPersistenceStore(str(tmp_path)))
        sup = mgr.supervise(poll_interval_s=0.05)
        rt = mgr.create_siddhi_app_runtime(app)
        rt.start()
        # the injected fault fires OUTSIDE the worker's poison-batch guard,
        # killing the drain thread; the supervisor's liveness probe catches
        # the silent death and restarts the app
        faults.install(faults.parse_plan("drain_worker@S:times=1"))
        rt.get_input_handler("S").send((1,))
        assert _wait_for(lambda: sup.restarts.get("AsyncDead", 0) >= 1)
        faults.uninstall()
        # the rebuilt app has a live worker again
        rt2 = mgr.get_siddhi_app_runtime("AsyncDead")
        got = []
        rt2.add_callback("Out", lambda evs: got.extend(e.data for e in evs))
        rt2.get_input_handler("S").send((2,))
        assert _wait_for(lambda: got)
        mgr.shutdown()

    def test_exception_handler_survives_restart(self, tmp_path):
        mgr, sup, rt, _got = _sup_setup(tmp_path)
        seen = []
        rt.set_exception_handler(seen.append)
        faults.install(faults.parse_plan("junction_dispatch@C:times=1"))
        # the handler GUARDS dispatch, so this is not fatal — crash via a
        # dead drain path instead: use device-independent fatal marker
        sup.input_handler("SupApp", "C").send((1,), timestamp=1)
        assert len(seen) == 1  # handler owned it; no restart
        time.sleep(0.3)
        assert sup.restarts.get("SupApp", 0) == 0
        mgr.shutdown()

    def test_intentional_shutdown_not_restarted(self, tmp_path):
        mgr, sup, rt, _got = _sup_setup(tmp_path)
        mgr.shutdown_siddhi_app_runtime("SupApp")
        time.sleep(0.3)
        assert sup.restarts.get("SupApp", 0) == 0
        assert mgr.get_siddhi_app_runtime("SupApp") is None
        mgr.shutdown()

    def test_bad_restart_annotation_rejected(self):
        mgr = SiddhiManager()
        with pytest.raises(SiddhiAppCreationError):
            mgr.create_siddhi_app_runtime(
                "@app:restart(policy='perhaps')\n"
                "define stream S (a int);\n"
                "from S select a insert into Out;"
            )
        mgr.shutdown()


# ---------------------------------------------------------------------------
# device-dispatch + pipeline fault sites
# ---------------------------------------------------------------------------


class TestDeviceFaultSites:
    def test_device_dispatch_fault_rides_failure_policy(self):
        import numpy as np

        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime("""
        @app:name('DevFault')
        define stream S (v long);
        @info(name='q')
        from S#window.length(4) select sum(v) as total insert into Out;
        """)
        got = []
        rt.add_callback("q", lambda ts, i, r: got.extend(
            e.data for e in i or []
        ))
        seen = []
        rt.set_exception_handler(seen.append)
        rt.start()
        h = rt.get_input_handler("S")
        n = 256
        ts = np.arange(1, n + 1, dtype=np.int64)
        cols = {"v": np.ones(n, dtype=np.int64)}
        h.send_columns(ts, cols)  # warm up the fused path
        if not any(
            j.fused_ingest is not None for j in rt.junctions.values()
        ):
            pytest.skip("fused ingest not engaged on this backend")
        before = len(got)
        faults.install(faults.parse_plan("device_dispatch:times=1"))
        h.send_columns(ts, cols)
        faults.uninstall()
        assert seen, "handler must own the injected chunk failure"
        # the engine keeps processing after the failed chunk (donated-state
        # reset path): later sends deliver
        h.send_columns(ts[:8], {"v": cols["v"][:8]})
        assert _wait_for(lambda: len(got) > before)
        mgr.shutdown()


# ---------------------------------------------------------------------------
# restore-then-fused-send parity (restored rings must survive
# _maybe_unshare/donation)
# ---------------------------------------------------------------------------


FUSED_SHARE_APP = """
@app:name('RestoreFuse')
define stream S (v long);
@info(name='q1')
from S#window.length(8) select sum(v) as total insert into O1;
@info(name='q2')
from S#window.length(8) select max(v) as m insert into O2;
"""


class TestRestoreFusedParity:
    def test_restore_then_fused_send_parity(self, tmp_path):
        import numpy as np

        store = FileSystemPersistenceStore(str(tmp_path))

        def build():
            mgr = SiddhiManager()
            mgr.set_persistence_store(store)
            rt = mgr.create_siddhi_app_runtime(FUSED_SHARE_APP)
            got = {"q1": [], "q2": []}
            for q in ("q1", "q2"):
                rt.add_callback(q, lambda ts, i, r, _q=q: got[_q].extend(
                    e.data for e in i or []
                ))
            rt.start()
            return mgr, rt, got

        n = 128
        ts = np.arange(1, n + 1, dtype=np.int64)
        feed_a = {"v": np.arange(n, dtype=np.int64)}
        feed_b = {"v": np.arange(n, 2 * n, dtype=np.int64)}

        mgr, rt, got = build()
        h = rt.get_input_handler("S")
        h.send_columns(ts, feed_a)
        rt.persist()
        for q in got:
            got[q].clear()
        h.send_columns(ts + n, feed_b)
        expected = {q: list(v) for q, v in got.items()}

        # restore into the RUNNING app, then replay the same post-persist
        # feed: a row send in between forces the per-batch path (and the
        # unshare guard) onto the restored states before the fused send
        rt.restore_last_revision()
        for q in got:
            got[q].clear()
        h.send(
            (int(feed_b["v"][0]),), timestamp=int(ts[0] + n)
        )  # per-batch row send on restored state
        h.send_columns(
            ts[1:] + n, {"v": feed_b["v"][1:]}
        )  # fused send resumes
        assert got == expected, (
            "restored rings must survive per-batch donation and fused "
            "re-engagement byte-identically"
        )
        mgr.shutdown()


# ---------------------------------------------------------------------------
# non-blocking replay
# ---------------------------------------------------------------------------


class TestNonBlockingReplay:
    def _wait_sink_setup(self):
        from siddhi_tpu.core.io import SINKS, Sink
        from siddhi_tpu.core.errors import ConnectionUnavailableError

        instances = []

        class _DownSink(Sink):
            def __init__(self):
                self.delivered = []
                self.down = True
                instances.append(self)

            def connect(self):
                if self.down:
                    raise ConnectionUnavailableError("still down")

            def publish(self, payload):
                if self.down:
                    raise ConnectionUnavailableError("still down")
                self.delivered.append(payload)

        mgr = SiddhiManager()
        SINKS["downtest"] = _DownSink
        try:
            rt = mgr.create_siddhi_app_runtime("""
            @app:name('WaitApp')
            define stream In (v int);
            @sink(type='downtest', on.error='WAIT',
                  @map(type='passThrough'))
            define stream Out (v int);
            from In select v insert into Out;
            """)
        finally:
            del SINKS["downtest"]
        return mgr, rt, instances[0]

    def test_skip_unavailable_does_not_block(self):
        from siddhi_tpu.core.error_store import ORIGIN_SINK, make_entry

        mgr, rt, sink = self._wait_sink_setup()
        rt.start()
        mgr.error_store.store(make_entry(
            "WaitApp", ORIGIN_SINK, "Out", "down", payload=[(1,)],
        ))
        t0 = time.monotonic()
        n = mgr.replay_errors(skip_unavailable=True)
        assert time.monotonic() - t0 < 2.0, "skip must not block on WAIT"
        assert n == 0
        assert len(mgr.error_store.load()) == 1  # skipped, not lost
        # transport recovers: the same call now drains the entry
        sink.down = False
        sink.connected = True
        n = mgr.replay_errors(skip_unavailable=True)
        assert n == 1 and not mgr.error_store.load()
        assert sink.delivered == [[(1,)]]
        mgr.shutdown()

    def test_timeout_bounds_the_loop(self):
        from siddhi_tpu.core.error_store import ORIGIN_STREAM, make_entry

        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime("""
        @app:name('TimeoutApp')
        define stream S (v int);
        @info(name='q') from S select v insert into Out;
        """)
        rt.start()
        for i in range(5):
            mgr.error_store.store(make_entry(
                "TimeoutApp", ORIGIN_STREAM, "S", "boom",
                events=[(i, (i,))],
            ))
        n = mgr.replay_errors(timeout=0.0)  # deadline already passed
        assert n == 0 and len(mgr.error_store.load()) == 5
        n = mgr.replay_errors(timeout=30.0)
        assert n == 5 and not mgr.error_store.load()
        mgr.shutdown()


# ---------------------------------------------------------------------------
# analyzer integration (SA126-128 ride the shared rule sets)
# ---------------------------------------------------------------------------


class TestRestartAttemptFailures:
    def test_failed_restart_attempt_retries_until_budget(self, tmp_path):
        """A restart ATTEMPT that itself fails (restore raises) leaves the
        app down but must NOT abandon it: the next poll retries against the
        remaining budget, and only exhaustion lands in gave_up."""
        app = SUP_APP.replace("max.attempts='3'", "max.attempts='2'").replace(
            "SupApp", "RetryDown"
        )
        mgr, sup, rt, _got = _sup_setup(tmp_path, app)
        rt.get_input_handler("S").send(("A", 1), timestamp=1)
        rt.persist()
        # one crash trigger + a PERSISTENT restore fault: every restart
        # attempt dies in restore_last_revision
        faults.install(faults.parse_plan(
            "junction_dispatch@C:times=1;persist_load:times=-1"
        ))
        try:
            with pytest.raises(InjectedFault):
                sup.input_handler("RetryDown", "C").send((1,), timestamp=1)
            assert _wait_for(lambda: "RetryDown" in sup.gave_up, timeout=20)
            # BOTH budgeted attempts were consumed by the retry loop (the
            # old behavior stalled after the first failed attempt)
            assert sup._attempts.get("RetryDown") == 2
            assert sup.restarts.get("RetryDown", 0) == 0
            assert "RetryDown" not in sup._down
        finally:
            faults.uninstall()
        mgr.shutdown()

    def test_redeploy_resets_supervision_budget(self, tmp_path):
        """An operator redeploy under the same name starts a fresh
        supervision life — gave_up and the attempt streak are cleared —
        while the supervisor's OWN rebuild must not reset the streak."""
        app = SUP_APP.replace("max.attempts='3'", "max.attempts='1'").replace(
            "SupApp", "Redeploy"
        )
        mgr, sup, rt, _got = _sup_setup(tmp_path, app)
        faults.install(faults.parse_plan("junction_dispatch@C:times=-1"))
        try:
            for ts in range(2):
                try:
                    sup.input_handler("Redeploy", "C").send(
                        (ts,), timestamp=ts
                    )
                except InjectedFault:
                    pass
                time.sleep(0.2)
            assert _wait_for(lambda: "Redeploy" in sup.gave_up, timeout=15)
        finally:
            faults.uninstall()
        # redeploy: the fixed app is supervised afresh
        rt2 = mgr.create_siddhi_app_runtime(app)
        assert "Redeploy" not in sup.gave_up
        assert sup._attempts.get("Redeploy") is None
        rt2.start()
        faults.install(faults.parse_plan("junction_dispatch@C:times=1"))
        try:
            with pytest.raises(InjectedFault):
                sup.input_handler("Redeploy", "C").send((9,), timestamp=9)
            assert _wait_for(
                lambda: sup.restarts.get("Redeploy", 0) >= 1, timeout=15
            )
        finally:
            faults.uninstall()
        mgr.shutdown()


class TestSupervisionAnalysis:
    def test_clean_supervised_app_lints_clean(self):
        from siddhi_tpu.analysis import analyze
        from siddhi_tpu.compiler.siddhi_compiler import SiddhiCompiler

        app = SiddhiCompiler.parse("""
        @app:name('CleanSup')
        @app:persist(interval='30 sec', keep='5')
        @app:restart(policy='on-failure', max.attempts='3',
                     backoff='2 sec')
        @app:admission(policy='block', rate.limit='50000',
                       max.pending='8192')
        define stream S (v long);
        from S select v insert into Out;
        """)
        result = analyze(app)
        assert result.ok, result.format()

    def test_diagnostics_fire(self):
        from siddhi_tpu.analysis import analyze
        from siddhi_tpu.compiler.siddhi_compiler import SiddhiCompiler

        app = SiddhiCompiler.parse("""
        @app:persist(interval='1 millisec')
        @app:restart(policy='maybe')
        @app:admission(policy='block')
        define stream S (v long);
        from S select v insert into Out;
        """)
        codes = sorted(d.code for d in analyze(app).errors)
        assert codes == ["SA126", "SA127", "SA128"]
