"""Snapshot / persistence tests.

Reference: modules/siddhi-core/src/test/java/org/wso2/siddhi/core/managment/
PersistenceTestCase.java and IncrementalPersistenceTestCase.java — snapshot,
shutdown, recreate the app, restore, continue exactly where it left off.
"""

import pickle

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.persistence import (
    FileSystemPersistenceStore,
    IncrementalFileSystemPersistenceStore,
    InMemoryPersistenceStore,
)

APP = """
@app:name('PersistApp')
define stream S (symbol string, price float, volume long);
define table T (symbol string, volume long);
@info(name='q')
from S#window.length(3) select symbol, sum(volume) as total insert into Out;
from S select symbol, volume insert into T;
"""


def make(store=None):
    mgr = SiddhiManager()
    if store is not None:
        mgr.set_persistence_store(store)
    rt = mgr.create_siddhi_app_runtime(APP)
    got = []
    rt.add_callback("q", lambda ts, i, r: got.extend(e.data for e in i or []))
    rt.start()
    return mgr, rt, got


class TestSnapshotRestore:
    def test_full_snapshot_bytes_roundtrip(self):
        mgr, rt, got = make()
        h = rt.get_input_handler("S")
        h.send(("A", 1.0, 10), timestamp=1)
        h.send(("A", 1.0, 20), timestamp=2)
        snap = rt.snapshot()
        rt.shutdown()

        mgr2, rt2, got2 = make()
        rt2.restore(snap)
        # the window carry continues: next event sums with restored state
        rt2.get_input_handler("S").send(("A", 1.0, 5), timestamp=3)
        assert got2 == [("A", 35)]
        # table contents restored too
        rows = rt2.query("from T select symbol, volume")
        assert [e.data for e in rows][:2] == [("A", 10), ("A", 20)]
        rt2.shutdown()
        mgr.shutdown()
        mgr2.shutdown()

    # volumes whose halves both matter: above 2**32, and negative
    LONGS = [2**40 + 10, -(2**41) - 3, 2**33 + 1, 7, -(2**35)]

    def _sums_after(self, restore_at, prime):
        """Running sums of LONGS through length(3), the app torn down and
        restored from its snapshot after `restore_at` events; with `prime`
        the restoring app has stepped once (its state is live, not None)."""
        mgr, rt, got = make()
        for i, v in enumerate(self.LONGS[:restore_at]):
            rt.get_input_handler("S").send(("A", 1.0, v), timestamp=i)
        snap = rt.snapshot()
        mgr.shutdown()
        mgr2, rt2, got2 = make()
        if prime:
            rt2.get_input_handler("S").send(("A", 1.0, 1), timestamp=0)
            del got2[:]
        rt2.restore(snap)
        for i, v in enumerate(self.LONGS[restore_at:], start=restore_at):
            rt2.get_input_handler("S").send(("A", 1.0, v), timestamp=i)
        mgr2.shutdown()
        return snap, [total for _sym, total in got + got2]

    @pytest.mark.parametrize("prime", [False, True])
    def test_snapshot_holds_logical_long_lanes_and_round_trips(self, prime):
        """The ring keeps its 64-bit lanes as u32 pairs; the snapshot keeps
        them as int64 under the paths it always had, and a restore (onto a
        state not yet materialized, or a live one) continues as an unbroken
        run does."""
        ls = self.LONGS
        unbroken = [sum(ls[max(0, i - 2): i + 1]) for i in range(len(ls))]
        snap, sums = self._sums_after(2, prime)
        assert sums == unbroken
        assert b"U32Pair" not in snap
        chain = pickle.loads(snap)["elements"]["query:q"]["chain"]
        assert sorted(chain) == ["cols", "seq", "total", "ts", "wts"]
        for lane in (chain["ts"], chain["wts"], chain["seq"],
                     chain["cols"]["volume"]):
            assert isinstance(lane, np.ndarray)
            assert lane.dtype == np.int64 and lane.shape == (3,)
        assert chain["seq"].tolist() == [0, 1, -1]
        assert chain["cols"]["volume"].tolist() == ls[:2] + [0]

    def test_restores_a_snapshot_in_the_int64_layout_built_by_hand(self):
        """A snapshot as the engine wrote it before the ring held pairs:
        the window's lanes are plain int64 arrays, here built by hand. The
        rows it holds expire with their own values after the restore."""
        ls = self.LONGS
        snap, _ = self._sums_after(2, False)
        payload = pickle.loads(snap)
        a = payload["interner"].index("A") + 1
        payload["elements"]["query:q"]["chain"] = {
            "cols": {
                "symbol": np.array([a, a, 0], np.int32),
                "price": np.array([1.0, 1.0, 0.0], np.float32),
                "volume": np.array([ls[0], ls[1], 0], np.int64),
            },
            "ts": np.array([0, 1, 0], np.int64),
            "wts": np.array([0, 1, 0], np.int64),
            "seq": np.array([0, 1, -1], np.int64),
            "total": np.array(2, np.int64),
        }
        mgr, rt, got = make()
        rt.restore(pickle.dumps(payload))
        for i, v in enumerate(ls[2:], start=2):
            rt.get_input_handler("S").send(("A", 1.0, v), timestamp=i)
        assert [t for _s, t in got] == [
            sum(ls[max(0, i - 2): i + 1]) for i in range(2, len(ls))]
        win = rt.snapshot_status()["queries"]["q"]["window"]
        assert win["fill"] == 3 and win["wide_lanes"] == "u32x2"
        assert (win["oldest_ts"], win["newest_ts"]) == (2, 4)
        mgr.shutdown()

    def test_in_memory_store_revisions(self):
        store = InMemoryPersistenceStore()
        mgr, rt, got = make(store)
        h = rt.get_input_handler("S")
        h.send(("A", 1.0, 10), timestamp=1)
        rev = rt.persist()
        assert rev.endswith("_PersistApp")
        rt.shutdown()

        mgr2, rt2, got2 = make(store)
        rt2.restore_last_revision()
        rt2.get_input_handler("S").send(("A", 1.0, 7), timestamp=2)
        assert got2 == [("A", 17)]
        rt2.shutdown()
        mgr.shutdown()
        mgr2.shutdown()

    def test_filesystem_store(self, tmp_path):
        store = FileSystemPersistenceStore(str(tmp_path))
        mgr, rt, got = make(store)
        rt.get_input_handler("S").send(("B", 2.0, 100), timestamp=1)
        rt.persist()
        rt.shutdown()

        mgr2, rt2, got2 = make(store)
        rt2.restore_last_revision()
        rt2.get_input_handler("S").send(("B", 2.0, 1), timestamp=2)
        assert got2 == [("B", 101)]
        rt2.shutdown()
        mgr.shutdown()
        mgr2.shutdown()

    def test_incremental_store(self, tmp_path):
        store = IncrementalFileSystemPersistenceStore(str(tmp_path))
        mgr, rt, got = make(store)
        h = rt.get_input_handler("S")
        h.send(("A", 1.0, 10), timestamp=1)
        rt.persist()  # full (first)
        h.send(("A", 1.0, 20), timestamp=2)
        rt.persist()  # delta
        rt.shutdown()

        mgr2, rt2, got2 = make(store)
        rt2.restore_last_revision()
        rt2.get_input_handler("S").send(("A", 1.0, 5), timestamp=3)
        assert got2 == [("A", 35)]
        rt2.shutdown()
        mgr.shutdown()
        mgr2.shutdown()

    def test_interner_conflict_detected(self):
        mgr, rt, got = make()
        rt.get_input_handler("S").send(("A", 1.0, 10), timestamp=1)
        snap = rt.snapshot()
        rt.shutdown()

        mgr2, rt2, got2 = make()
        # divergent interning order: 'ZZZ' now takes the id 'A' had
        mgr2.interner.intern("ZZZ")
        with pytest.raises(ValueError, match="intern table conflict"):
            rt2.restore(snap)
        rt2.shutdown()
        mgr.shutdown()
        mgr2.shutdown()
