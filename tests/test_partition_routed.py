"""The routed step of a partitioned single-stream query (`core/partition.py`):
rows go to their partition's `[P, B']` sub-batch, every partition's emissions
come back as one flat batch in arrival order. The engine against a row-by-row
transcription of the reference's PartitionStreamReceiver: one
`deque(maxlen=W)` per key, the events taken one at a time."""

from __future__ import annotations

import logging
from collections import deque

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.partition import sub_batch_rows

APP = """
@app:batch(size='{batch}')
@app:partitionCapacity(size='{cap}')
define stream S (k int, v float);
partition with ({key} of S) begin
@info(name='q') from S#window.length({w}) select k, v, sum(v) as s
insert {events} into Out;
end;
"""


def transcription(keys, vals, ts, w, expired=True):
    """What the reference emits into Out, event by event: a full window's
    oldest row leaves (EXPIRED, at its trigger's time) before the arriving
    row (CURRENT) is emitted; the sum is the key's running one."""
    held, total, out = {}, {}, []
    for k, v, t in zip(keys, vals, ts):
        d = held.setdefault(k, deque(maxlen=w))
        if len(d) == w:
            ok, ov = d[0]
            total[k] -= ov
            if expired:
                out.append((int(t), (int(ok), float(ov), total[k])))
        d.append((k, v))
        total[k] = total.get(k, 0.0) + v
        out.append((int(t), (int(k), float(v), total[k])))
    return out


def deploy(batch=64, cap=8, w=3, key="k", events="all events", text=None):
    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime(
        text or APP.format(batch=batch, cap=cap, w=w, key=key, events=events))
    got = []
    rt.add_callback("Out", lambda events: got.extend(
        (e.timestamp, tuple(e.data)) for e in events))
    rt.start()
    return mgr, rt, got


def feed(rt, keys, vals, chunk, t0=0):
    """Send in chunks of `chunk` rows; event time counts the rows."""
    h = rt.get_input_handler("S")
    for lo in range(0, len(keys), chunk):
        hi = min(lo + chunk, len(keys))
        h.send_columns(
            np.arange(t0 + lo, t0 + hi, dtype=np.int64),
            {"k": keys[lo:hi].astype(np.int32), "v": vals[lo:hi].astype(np.float32)})


def same(got, want):
    assert len(got) == len(want)
    for (tg, dg), (tw, dw) in zip(got, want):
        assert tg == tw and dg[:2] == dw[:2], (tg, dg, tw, dw)
        assert dg[2] == pytest.approx(dw[2], rel=1e-5, abs=1e-4)


def rows(seed, n, n_keys):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, n_keys, n),
            rng.integers(0, 50, n).astype(np.float64))


@pytest.mark.parametrize("n_keys", [2, 7])
def test_flat_output_is_in_arrival_order(n_keys):
    """Two and many keys in one batch: the flat output interleaves the keys
    as their rows arrived, a trigger's EXPIRED row before its CURRENT row."""
    keys, vals = rows(1, 200, n_keys)
    mgr, rt, got = deploy(batch=64, cap=8, w=3)
    try:
        feed(rt, keys, vals, 50)
        same(got, transcription(keys, vals, range(200), 3))
        times = [t for t, _ in got]
        assert times == sorted(times)
    finally:
        rt.shutdown()
        mgr.shutdown()


@pytest.mark.parametrize("w,step", [(3, "scatter"), (40, "slice")])
def test_window_shorter_and_longer_than_the_sub_batch(w, step):
    """B' is 32 at batch 64 x capacity 8: `length(3)` takes the scatter
    step in every slot, `length(40)` the slice step at per-slot places."""
    assert sub_batch_rows(64, 8) == 32
    keys, vals = rows(2, 640, 5)
    mgr, rt, got = deploy(batch=64, cap=8, w=w)
    try:
        feed(rt, keys, vals, 64)
        same(got, transcription(keys, vals, range(640), w))
        status = rt.snapshot_status()["queries"]["q"]
        assert status["window"]["ring_step"] == step
        assert status["partition"]["sub_batch"] == 32
        assert status["partition"]["extra_passes"] == 0
    finally:
        rt.shutdown()
        mgr.shutdown()


def test_one_hot_key_takes_passes_and_answers_exactly():
    """Every row of a batch carries one key: the slot takes 256 / B' passes
    inside the step, nothing is dropped or deferred."""
    keys = np.zeros(600, dtype=np.int64)
    keys[::97] = 3  # and a second key in between, in order
    vals = np.arange(600, dtype=np.float64) % 11
    mgr, rt, got = deploy(batch=256, cap=8, w=5)
    try:
        feed(rt, keys, vals, 256)
        same(got, transcription(keys, vals, range(600), 5))
        part = rt.snapshot_status()["queries"]["q"]["partition"]
        assert part["sub_batch"] == sub_batch_rows(256, 8) == 128
        assert part["extra_passes"] > 0
        assert part["max_rows_per_slot"] >= 250
    finally:
        rt.shutdown()
        mgr.shutdown()


def test_more_keys_than_capacity_is_flagged_and_the_others_stay_exact(caplog):
    keys, vals = rows(3, 300, 6)  # six keys, four slots
    mgr, rt, got = deploy(batch=64, cap=4, w=3)
    try:
        with caplog.at_level(logging.ERROR, logger="siddhi_tpu"):
            feed(rt, keys, vals, 60)
            rt.queries["q"].flush_aux_warnings()
        flagged = [r for r in caplog.records
                   if "partition key table overflowed" in r.getMessage()]
        assert len(flagged) == 1
        first = list(dict.fromkeys(keys.tolist()))[:4]  # the keys that fit
        fits = np.isin(keys, first)
        same(got, transcription(keys[fits], vals[fits],
                                np.arange(300)[fits], 3))
        assert rt.snapshot_status()["queries"]["q"]["partition"]["used"] == 4
    finally:
        rt.shutdown()
        mgr.shutdown()


BEHIND = """
@app:batch(size='64')
@app:partitionCapacity(size='8')
define stream S (k int, v float);
partition with (k % 3 of S) begin
@info(name='q') from S#window.length(4){stage} select k, v, sum({term}) as s
insert into Out;
end;
"""


@pytest.mark.parametrize("stage,term,keep,value", [
    ("[k > 2]", "v", lambda k, v: k > 2, lambda k, v: v),
    ("#pol2Cart(v, k)", "x", lambda k, v: True,
     lambda k, v: k * np.cos(np.deg2rad(v))),
], ids=["filter", "function"])
def test_a_stage_behind_the_window_reads_the_expired_rows_own_columns(
        stage, term, keep, value):
    """A filter or a stream function behind the window reads columns of an
    EXPIRED row that no aggregate names (`k` differs from row to row inside
    a partition by `k % 3`): the ring holds every lane then, though nobody
    is handed the EXPIRED rows."""
    keys, vals = rows(8, 300, 7)
    mgr, rt, got = deploy(text=BEHIND.format(stage=stage, term=term))
    try:
        feed(rt, keys, vals, 64)
        held, total, want = {}, {}, []
        for t, (k, v) in enumerate(zip(keys.tolist(), vals.tolist())):
            d = held.setdefault(k % 3, deque(maxlen=4))
            if len(d) == 4 and keep(*d[0]):
                total[k % 3] -= value(*d[0])
            d.append((k, v))
            if keep(k, v):
                total[k % 3] = total.get(k % 3, 0.0) + value(k, v)
                want.append((t, (k, v, total[k % 3])))
        same(got, want)
        status = rt.snapshot_status()["queries"]["q"]
        assert "held_cols" not in status["window"]
        assert set(rt.queries["q"].state["chain"]["cols"]) == {"k", "v"}
    finally:
        rt.shutdown()
        mgr.shutdown()


def test_a_window_that_no_aggregate_reads_keeps_its_whole_ring():
    """`select k, v` behind a window, CURRENT rows alone: nothing reads the
    EXPIRED rows, and a ring of no lane at all is not built."""
    keys, vals = rows(9, 100, 3)
    mgr, rt, got = deploy(text=BEHIND.format(stage="", term="v").replace(
        ", sum(v) as s", ""))
    try:
        feed(rt, keys, vals, 64)
        assert got == [(t, (int(k), float(v)))
                       for t, (k, v) in enumerate(zip(keys, vals))]
        assert "held_cols" not in rt.snapshot_status()["queries"]["q"]["window"]
    finally:
        rt.shutdown()
        mgr.shutdown()


def test_current_events_only_keeps_the_aggregated_column_alone():
    """Nobody is handed the EXPIRED rows of `insert into`: a slot's ring
    holds `v`, which `sum(v)` reads of them, and no other lane; and a
    sub-batch is no longer than the window, so the slice step serves."""
    keys, vals = rows(4, 200, 5)
    mgr, rt, got = deploy(batch=64, cap=8, w=4, events="")
    try:
        feed(rt, keys, vals, 64)
        same(got, transcription(keys, vals, range(200), 4, expired=False))
        status = rt.snapshot_status()["queries"]["q"]
        assert status["window"]["held_cols"] == ["v"]
        assert status["window"]["ring_step"] == "slice"
        assert status["window"]["fill"] == sum(
            min(int(n), 4) for n in np.bincount(keys))
        assert status["partition"]["sub_batch"] == 4
        assert status["partition"]["extra_passes"] > 0
        ring = rt.queries["q"].state["chain"]
        assert set(ring) == {"cols", "total"} and set(ring["cols"]) == {"v"}
    finally:
        rt.shutdown()
        mgr.shutdown()


def test_inner_chain_keeps_arrival_order():
    """`#inner` streams carry each row's slot between the inner queries."""
    text = """
    @app:batch(size='64')
    @app:partitionCapacity(size='8')
    define stream S (k int, v float);
    partition with (k of S) begin
    from S select k, v * 2 as v insert into #Doubled;
    @info(name='q') from #Doubled#window.length(3) select k, v, sum(v) as s
    insert all events into Out;
    end;
    """
    keys, vals = rows(5, 200, 5)
    mgr, rt, got = deploy(text=text)
    try:
        feed(rt, keys, vals, 50)
        same(got, transcription(keys, 2 * vals, range(200), 3))
    finally:
        rt.shutdown()
        mgr.shutdown()


def test_range_partition():
    text = """
    @app:batch(size='64')
    @app:partitionCapacity(size='8')
    define stream S (k int, v float);
    partition with (v < 10 as 'low' or v >= 10 and v < 30 as 'mid' of S) begin
    @info(name='q') from S#window.length(4) select k, v, sum(v) as s
    insert all events into Out;
    end;
    """
    keys, vals = rows(6, 200, 5)
    band = np.where(vals < 10, 0, np.where(vals < 30, 1, -1))
    mgr, rt, got = deploy(text=text)
    try:
        feed(rt, keys, vals, 50)
        fits = band >= 0  # rows that match no range are dropped
        want = []
        held, total = {}, {}
        for k, v, t, b in zip(keys[fits], vals[fits], np.arange(200)[fits],
                              band[fits]):
            d = held.setdefault(b, deque(maxlen=4))
            if len(d) == 4:
                ok, ov = d[0]
                total[b] -= ov
                want.append((int(t), (int(ok), float(ov), total[b])))
            d.append((k, v))
            total[b] = total.get(b, 0.0) + v
            want.append((int(t), (int(k), float(v), total[b])))
        same(got, want)
    finally:
        rt.shutdown()
        mgr.shutdown()


def test_snapshot_and_restore_across_the_step():
    """The state keeps the parent's layout (`[P]`-leading leaves of the
    inner query's own state, the shared key table beside it), so a snapshot
    restores into a fresh runtime and the stream goes on as if unbroken."""
    keys, vals = rows(7, 400, 5)
    mgr, rt, got = deploy(batch=64, cap=8, w=40)
    try:
        feed(rt, keys[:200], vals[:200], 50)
        state = rt.queries["q"].state
        assert {"chain", "sel"} == set(state)
        assert all(x.shape[0] == 8 for x in
                   __import__("jax").tree_util.tree_leaves(state))
        snap = rt.snapshot()
    finally:
        rt.shutdown()
        mgr.shutdown()
    mgr2, rt2, got2 = deploy(batch=64, cap=8, w=40)
    try:
        rt2.restore(snap)
        feed(rt2, keys[200:], vals[200:], 50, t0=200)
        same(got + got2, transcription(keys, vals, range(400), 40))
    finally:
        rt2.shutdown()
        mgr2.shutdown()


def test_a_snapshot_of_the_whole_ring_restores_into_the_slim_one():
    """A ring saved with every lane (the layout before PR 32, and still
    that of a query that publishes EXPIRED rows) restores into the ring
    that holds the aggregated column alone."""
    keys, vals = rows(9, 400, 5)
    mgr, rt, _ = deploy(batch=64, cap=8, w=40)  # insert all events
    try:
        feed(rt, keys[:200], vals[:200], 50)
        assert "seq" in rt.queries["q"].state["chain"]
        snap = rt.snapshot()
    finally:
        rt.shutdown()
        mgr.shutdown()
    mgr2, rt2, got2 = deploy(batch=64, cap=8, w=40, events="")
    try:
        rt2.restore(snap)
        assert set(rt2.queries["q"].state["chain"]) == {"cols", "total"}
        feed(rt2, keys[200:], vals[200:], 50, t0=200)
        want = transcription(keys, vals, range(400), 40, expired=False)
        same(got2, want[200:])
    finally:
        rt2.shutdown()
        mgr2.shutdown()


def test_status_block():
    keys, vals = rows(8, 100, 5)
    mgr, rt, _ = deploy(batch=64, cap=8, w=40)
    try:
        before = rt.snapshot_status()["queries"]["q"]["partition"]
        assert before == {"capacity": 8, "step": "routed", "probe": "merge",
                          "used": 0, "extra_passes": 0, "max_rows_per_slot": 0}
        feed(rt, keys, vals, 50)
        status = rt.snapshot_status()["queries"]["q"]
        part = status["partition"]
        assert part["capacity"] == 8 and part["step"] == "routed"
        assert part["used"] == 5 and part["sub_batch"] == 32
        assert part["extra_passes"] == 0
        counts = [np.bincount(keys[lo:lo + 50], minlength=5).max()
                  for lo in (0, 50)]
        assert part["max_rows_per_slot"] == max(counts)
        # one ring per slot: a slot's capacity, every slot's rows
        assert status["window"]["capacity"] == 40
        assert status["window"]["fill"] == sum(
            min(int(n), 40) for n in np.bincount(keys))
        assert status["window"]["per"] == {"capacity": "slot",
                                           "fill": "all slots"}
    finally:
        rt.shutdown()
        mgr.shutdown()


def test_joins_and_patterns_say_that_they_stay_on_masks():
    text = """
    @app:batch(size='16')
    @app:partitionCapacity(size='4')
    define stream A (k int, v float);
    define stream B (k int, w float);
    partition with (k of A, k of B) begin
    @info(name='j') from A#window.length(2) join B#window.length(2)
    on A.v < B.w select A.k, A.v, B.w insert into Out;
    @info(name='p') from every a=A -> b=B[w > a.v] select a.k, b.w
    insert into Out2;
    end;
    """
    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime(text)
    rt.start()
    try:
        queries = rt.snapshot_status()["queries"]
        for q in ("j", "p"):
            assert queries[q]["partition"] == {
                "capacity": 4, "step": "masked", "probe": "merge", "used": 0}
    finally:
        rt.shutdown()
        mgr.shutdown()
