"""A group-by's key table behind a sliding window takes a slot back when its
group's last row has left the window (PR 40: `ops/group.py` `free_stack`,
`release_slots`; `core/groupby.py` `release`; `core/selector.py`
`_pick_reclaim`), so `@app:groupCapacity` is the number of groups alive at
once and not of all groups a stream ever brings.

The engine against a row-by-row loop with a `dict` that forgets a key at
zero (the semantics of `benchmark/configs/nexmark-q5-hot-items/reference.py`)
on seeded streams whose keys come and go: the stream's distinct keys are
several times the table. Values are small integers, exact in float32."""

from __future__ import annotations

import logging
import pickle
from collections import deque

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager

BATCH, CAPACITY, WINDOW = 64, 48, 40

APP = """@app:name('Churn')
@app:batch(size='{batch}')
@app:groupCapacity(size='{capacity}')
@app:timeCapacity(size='512')
define stream Bid (auction long, t long, v float);
@info(name='q')
from Bid#window.externalTime(t, {window}) select auction, {select} group by auction
insert into Out;
"""

# select clause -> (where the table's count of rows comes from, the lanes of
# the loop's (count, sum) that the clause emits)
SELECTS = {
    "count() as num": ("count_lane", lambda c, s: (c,)),
    "sum(v) as total": ("own_lane", lambda c, s: (s,)),
    "avg(v) as mean, count() as num": ("count_lane", lambda c, s: (s / c, c)),
    "avg(v) as mean": ("count_lane", lambda c, s: (s / c,)),
}


def stream(seed: int, n: int, hot_share: float = 0.5):
    """`n` rows (auction, t, v): time moves on by 0-2 a row; half of the rows
    go to the hot auction, the newest multiple of 8, the others fall on the
    last 6 auctions; a new auction every fourth row. Ids only grow."""
    rng = np.random.default_rng(seed)
    t = 1_000 + np.cumsum(rng.integers(0, 3, n))
    last = np.arange(n) // 4 + 8
    auction = np.where(rng.random(n) < hot_share, (last // 8) * 8,
                       last - rng.integers(0, 6, n))
    return (auction.astype(np.int64), t.astype(np.int64),
            rng.integers(1, 9, n).astype(np.float32))


def loop(auction, t, v, window=WINDOW):
    """(per row: (auction, count, sum) once the row is in; per row: the keys
    alive then). A row leaves at the first arrival `window` or more younger;
    a key whose last row has left is forgotten."""
    queue, held, out, live = deque(), {}, [], []
    for a, ti, vi in zip(auction.tolist(), t.tolist(), v.tolist()):
        while queue and queue[0][1] <= ti - window:
            b, _, vb = queue.popleft()
            c, s = held[b]
            if c == 1:
                del held[b]
            else:
                held[b] = (c - 1, s - vb)
        queue.append((a, ti, vi))
        c, s = held.get(a, (0, 0.0))
        held[a] = (c + 1, s + vi)
        out.append((a, c + 1, s + vi))
        live.append(len(held))
    return out, live


def expected(auction, t, v, select, window=WINDOW):
    lanes = SELECTS[select][1]
    return [(a, *lanes(c, s)) for a, c, s in loop(auction, t, v, window)[0]]


class Run:
    """The app over a stream, sent in stretches; collects the emissions and
    what the engine logged at WARNING or above."""

    def __init__(self, select, capacity=CAPACITY, window=WINDOW, batch=BATCH):
        self.text = APP.format(batch=batch, capacity=capacity, window=window,
                               select=select)
        self.mgr = SiddhiManager()
        self.out, self.records = [], []
        run = self

        class Catch(logging.Handler):
            def emit(self, record):
                run.records.append(record.getMessage())

        self.handler = Catch(level=logging.WARNING)
        logging.getLogger("siddhi_tpu").addHandler(self.handler)
        self.rt = self.start()

    def start(self):
        rt = self.mgr.create_siddhi_app_runtime(self.text)
        # a query callback: one on the output stream keeps the fused path off
        rt.add_callback("q", lambda ts, ins, removed: self.out.extend(
            tuple(e[1]) for e in ins))
        rt.start()
        return rt

    def send(self, auction, t, v, lo, hi):
        self.rt.get_input_handler("Bid").send_columns(
            t[lo:hi], {"auction": auction[lo:hi], "t": t[lo:hi], "v": v[lo:hi]})

    def status(self):
        for qr in self.rt.queries.values():
            qr.flush_aux_warnings()
        return self.rt.snapshot_status()["queries"]["q"]

    def restart_from(self, snapshot: bytes):
        self.rt.shutdown()
        self.rt = self.start()
        self.rt.restore(snapshot)

    def close(self):
        self.rt.shutdown()
        self.mgr.shutdown()
        logging.getLogger("siddhi_tpu").removeHandler(self.handler)


def rows_close(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g[0] == w[0] and g[1:] == pytest.approx(w[1:], rel=1e-6), (g, w)


# ---- the stream through the three paths -------------------------------------

@pytest.mark.parametrize("select", sorted(SELECTS))
@pytest.mark.parametrize("path", ["per_batch", "fused", "restored"])
def test_churning_keys_as_the_loop_with_a_forgetting_dict(path, select):
    """Distinct keys several times the table; every row's aggregates as the
    loop's, no row without a slot, slots given back."""
    n = 1600
    auction, t, v = stream(11, n)
    assert len(np.unique(auction)) >= 6 * CAPACITY
    run = Run(select)
    try:
        if path == "fused":
            run.send(auction, t, v, 0, n)
        else:
            for lo in range(0, n, BATCH):
                if path == "restored" and lo == 10 * BATCH:
                    run.restart_from(run.rt.snapshot())
                run.send(auction, t, v, lo, lo + BATCH)
        status = run.status()
        chunks = run.rt.junctions["Bid"].fused_ingest.chunks_dispatched
    finally:
        run.close()
    rows_close(run.out, expected(auction, t, v, select))
    group = status["group"]
    assert group["reclaim"] == SELECTS[select][0]
    assert group["overflow_rows"] == 0 and not run.records
    assert group["used"] == loop(auction, t, v)[1][-1]
    if path != "restored":  # the counter starts again with the new runtime's
        assert group["freed"] == len(np.unique(auction)) - group["used"]
    assert bool(chunks) == (path == "fused")  # the chunk scan, or the step


def test_used_is_the_loops_live_keys_after_every_batch():
    n = 20 * BATCH
    auction, t, v = stream(13, n)
    live = loop(auction, t, v)[1]
    run = Run("count() as num")
    try:
        for lo in range(0, n, BATCH):
            run.send(auction, t, v, lo, lo + BATCH)
            assert run.status()["group"]["used"] == live[lo + BATCH - 1]
    finally:
        run.close()


def test_without_reclaiming_the_same_stream_overflows_and_answers_wrongly(
        monkeypatch):
    """What the table did before it took slots back (it filled after
    `groupCapacity` distinct keys and lost the carry of what did not fit):
    the first case's stream fails, so that case guards what it says."""
    from siddhi_tpu.core.selector import CompiledSelector

    monkeypatch.setattr(CompiledSelector, "_pick_reclaim", lambda self: None)
    n = 1600
    auction, t, v = stream(11, n)
    run = Run("count() as num")
    try:
        for lo in range(0, n, BATCH):
            run.send(auction, t, v, lo, lo + BATCH)
        status = run.status()
    finally:
        run.close()
    assert status["group"]["reclaim"] == "none"
    assert status["group"]["used"] == CAPACITY
    assert any("overflowed" in r for r in run.records)
    assert run.out != expected(auction, t, v, "count() as num")


# ---- the edges, each on a stream made for it ---------------------------------

def edge_stream(case: str):
    """(auction, t, v) on which the edge under test happens inside a batch
    (the batch is 64 rows, the window 40; every row's time is its index
    unless said otherwise)."""
    n = 3 * BATCH
    t = 1_000 + np.arange(n, dtype=np.int64)
    auction = 100 + np.arange(n, dtype=np.int64) // 2
    if case == "empties_and_returns_in_one_batch":
        # key 7 holds one row from the first batch; in the second its row
        # leaves (41 rows later) and, twenty rows on, 7 comes again
        auction[BATCH - 1] = 7
        auction[2 * BATCH - 3] = 7
    elif case == "leaves_in_the_pass_that_brings_it_back":
        # the arrival of 7 that lets its own last row go: exactly 40 later
        auction[BATCH + 3] = 7
        auction[BATCH + 3 + WINDOW] = 7
    elif case == "arrives_and_leaves_in_one_batch":
        # a silence longer than the window inside the batch: the rows before
        # it, new keys among them, come and go in the same step
        t[BATCH + 20:] += 3 * WINDOW
    elif case == "a_gap_empties_the_whole_table":
        t[2 * BATCH:] += 5 * WINDOW
    else:
        raise KeyError(case)
    return auction, t, np.ones(n, np.float32)


EDGES = ["empties_and_returns_in_one_batch",
         "leaves_in_the_pass_that_brings_it_back",
         "arrives_and_leaves_in_one_batch", "a_gap_empties_the_whole_table"]


@pytest.mark.parametrize("send", [BATCH, 3 * BATCH], ids=["per_batch", "fused"])
@pytest.mark.parametrize("case", EDGES)
def test_edges_of_emptying_and_returning(case, send):
    auction, t, v = edge_stream(case)
    select = "count() as num, sum(v) as total"
    run = Run(select, capacity=2 * BATCH + 8)
    try:
        for lo in range(0, len(t), send):
            run.send(auction, t, v, lo, lo + send)
        status = run.status()
    finally:
        run.close()
    want, live = loop(auction, t, v)
    assert run.out == [(a, c, s) for a, c, s in want]
    assert status["group"]["used"] == live[-1]
    assert status["group"]["overflow_rows"] == 0 and not run.records


def test_snapshot_from_before_the_table_took_slots_back_restores():
    """A snapshot of PR 39's layout (`keys`, `used`, `n`) restores: the
    stack of unused slots is laid out from `used`, and the stream goes on as
    in an uninterrupted run."""
    n = 1600
    auction, t, v = stream(17, n)
    cut = 6 * BATCH
    run = Run("count() as num")
    try:
        for lo in range(0, cut, BATCH):
            run.send(auction, t, v, lo, lo + BATCH)
        payload = pickle.loads(run.rt.snapshot())
        table = payload["elements"]["query:q"]["sel"]["group"]
        assert {"free", "freed", "lost"} <= set(table)
        for key in ("free", "freed", "lost"):
            del table[key]
        run.restart_from(pickle.dumps(payload))
        for lo in range(cut, n, BATCH):
            run.send(auction, t, v, lo, lo + BATCH)
        status = run.status()
    finally:
        run.close()
    rows_close(run.out, expected(auction, t, v, "count() as num"))
    assert status["group"]["overflow_rows"] == 0
    assert status["group"]["used"] == loop(auction, t, v)[1][-1]


@pytest.mark.parametrize("query, reclaim", [
    ("from S select k, count() as n group by k", "none"),
    ("from S#window.lengthBatch(4) select k, count() as n group by k", "none"),
    ("from S#window.length(4) select k, count() as n group by k", "count_lane"),
    ("from S#window.length(4) select k, max(v) as m group by k", "own_lane"),
    ("from S#window.length(4) select k, maxForever(v) as m, count() as n "
     "group by k", "none"),
    ("from S#window.time(1 sec) select k, stdDev(v) as d group by k",
     "count_lane"),
], ids=["no_window", "batch_window", "length_count", "length_max",
        "forever", "time_stddev"])
def test_which_tables_take_slots_back(query, reclaim):
    """One rule: a group-by behind a sliding window, which hands it what it
    lets go. No window, or a batch window (whose RESET empties the table),
    keeps the table that counts up; so does a group that is never done
    (minForever / maxForever)."""
    mgr = SiddhiManager()
    try:
        rt = mgr.create_siddhi_app_runtime(
            "@app:batch(size='16') define stream S (k int, v float);\n"
            f"@info(name='q') {query} insert into O;")
        rt.start()
        i = np.arange(12, dtype=np.int32)
        rt.get_input_handler("S").send_columns(
            i.astype(np.int64), {"k": i % 3, "v": i.astype(np.float32)})
        group = rt.snapshot_status()["queries"]["q"]["group"]
        rt.shutdown()
    finally:
        mgr.shutdown()
    assert group["reclaim"] == reclaim
    assert (group["overflow_rows"] is None) == (reclaim == "none")


# ---- the table's operations against a dict, resets included -----------------

@pytest.mark.parametrize("resets", [False, True], ids=["no_reset", "resets"])
@pytest.mark.parametrize("b, g", [(32, 12), (8, 24)], ids=["B>G", "B<=G"])
def test_table_operations_against_a_dict(b, g, resets):
    """`assign_slots(free=...)`, the lane that counts rows and
    `release_slots`, step by step on random flows: a key keeps its slot
    while it holds a row, the stack holds exactly the unused slots, the
    count lane reads zero there, and a RESET empties the table."""
    import jax.numpy as jnp

    from siddhi_tpu.ops.group import (
        assign_slots, free_stack, keyed_running_sum, release_slots)

    rng = np.random.default_rng(5 + b + resets)
    keys = jnp.zeros((g,), jnp.int64)
    used = jnp.zeros((g,), jnp.bool_)
    n = jnp.zeros((), jnp.int32)
    free = free_stack(g)
    rows = jnp.zeros((g,), jnp.int32)
    held: dict = {}     # key -> rows it holds
    slot_of: dict = {}  # key -> its slot while it holds a row
    for step in range(60):
        # keys churn: a sliding range; an EXPIRED row only for a key that
        # holds a row (a window never hands back what it was not given)
        lo = step // 2
        bk, sign, reset = [], [], []
        now = dict(held)
        for _ in range(b):
            if resets and rng.random() < 0.04:
                bk.append(0), sign.append(0), reset.append(True)
                now = {}
                continue
            live = [k for k, c in now.items() if c > 0]
            if live and rng.random() < 0.5:
                k = int(rng.choice(live))
                now[k] -= 1
                bk.append(k), sign.append(-1), reset.append(False)
            else:
                k = int(rng.integers(lo, lo + 6))
                now[k] = now.get(k, 0) + 1
                bk.append(k), sign.append(1), reset.append(False)
        sign_a = jnp.asarray(sign, jnp.int32)
        keys, _, n, slot, grp, overflow = assign_slots(
            keys, used, n, jnp.asarray(bk, jnp.int64), sign_a != 0,
            reset=jnp.asarray(reset), free=free)
        _, rows = keyed_running_sum(sign_a, grp, rows, rows=True)
        free, n, freed = release_slots(grp.free, n, grp)
        used = rows > 0
        assert not bool(overflow)
        held = {k: c for k, c in now.items() if c > 0}
        last_reset = max((i for i, r in enumerate(reset) if r), default=-1)
        if last_reset >= 0:
            slot_of = {}
        slot = np.asarray(slot)
        for i in range(last_reset + 1, b):
            if sign[i] == 0:
                continue
            assert slot[i] < g
            assert slot_of.setdefault(bk[i], int(slot[i])) == slot[i]
        slot_of = {k: s for k, s in slot_of.items() if k in held}
        assert int(n) == len(held)
        stack = np.asarray(free)[: g - int(n)]
        assert sorted(stack.tolist() + list(slot_of.values())) == list(range(g))
        got_rows = np.asarray(rows)
        assert all(got_rows[s] == held[k] for k, s in slot_of.items())
        assert (got_rows[stack] == 0).all()
        assert all(int(np.asarray(keys)[s]) == k for k, s in slot_of.items())
