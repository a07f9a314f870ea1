"""Window behavior tests.

Mirrors the reference window test corpus semantics (reference:
core/src/test/java/.../query/window/LengthWindowTestCase.java,
LengthBatchWindowTestCase.java, ExternalTimeWindowTestCase.java,
TimeWindowTestCase.java): CURRENT/EXPIRED accounting through QueryCallback and
running aggregates over window contents.
"""

import collections
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import core as jax_core
from jax._src.interpreters import partial_eval as pe

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.event import EventBatch, StreamSchema
from siddhi_tpu.core.flow import Flow
from siddhi_tpu.core.types import AttrType
from siddhi_tpu.core.windows import SlidingWindow


def run_app(ql):
    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime(ql)
    rt.start()
    return mgr, rt


def collect(rt, qname):
    got = {"in": [], "removed": [], "events": []}

    def cb(ts, ins, removed):
        got["in"].extend(ins or [])
        got["removed"].extend(removed or [])
        got["events"].append((ts, ins, removed))

    rt.add_callback(qname, cb)
    return got


def test_length_window_sum():
    mgr, rt = run_app(
        """
        define stream S (sym string, p float);
        @info(name='q')
        from S#window.length(3) select sym, sum(p) as total insert all events into O;
        """
    )
    got = collect(rt, "q")
    h = rt.get_input_handler("S")
    for i, v in enumerate([10.0, 20.0, 30.0, 40.0, 50.0]):
        h.send(("A", v), timestamp=1000 + i)
    # running sums: 10, 30, 60, then window slides: 60-10+40=90, 90-20+50=120
    assert [e.data[1] for e in got["in"]] == [10.0, 30.0, 60.0, 90.0, 120.0]
    # expired events carry the evicted payloads
    assert [e.data[0] for e in got["removed"]] == ["A", "A"]
    mgr.shutdown()


def test_length_window_min_max_exact_expiry():
    mgr, rt = run_app(
        """
        define stream S (p float);
        @info(name='q')
        from S#window.length(2) select min(p) as mn, max(p) as mx insert into O;
        """
    )
    got = collect(rt, "q")
    h = rt.get_input_handler("S")
    for v in [5.0, 9.0, 3.0, 7.0, 1.0]:
        h.send((v,))
    # windows: [5], [5,9], [9,3], [3,7], [7,1]
    assert [e.data for e in got["in"]] == [
        (5.0, 5.0), (5.0, 9.0), (3.0, 9.0), (3.0, 7.0), (1.0, 7.0),
    ]
    mgr.shutdown()


def test_length_batch_window():
    mgr, rt = run_app(
        """
        define stream S (sym string, p float);
        @info(name='q')
        from S#window.lengthBatch(3) select sym, sum(p) as total insert all events into O;
        """
    )
    got = collect(rt, "q")
    h = rt.get_input_handler("S")
    for v in [1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0]:
        h.send(("A", v))
    # batch + aggregator + no group-by: only the LAST chunk event survives,
    # carrying the bucket's final aggregate (reference:
    # QuerySelector.processInBatchNoGroupBy lastEvent)
    assert [e.data[1] for e in got["in"]] == [6.0, 15.0]
    # the final CURRENT wins the chunk, so no expired rows are emitted
    assert len(got["removed"]) == 0
    mgr.shutdown()


def test_length_batch_across_large_send():
    mgr, rt = run_app(
        """
        define stream S (v int);
        @info(name='q')
        from S#window.lengthBatch(2) select sum(v) as s insert into O;
        """
    )
    got = collect(rt, "q")
    rt.get_input_handler("S").send_many([(i,) for i in range(1, 8)])  # 1..7
    # buckets (1,2), (3,4), (5,6); 7 pending — one final sum per flush
    assert [e.data[0] for e in got["in"]] == [3, 7, 11]
    mgr.shutdown()


def test_external_time_window():
    mgr, rt = run_app(
        """
        define stream S (ts long, p float);
        @info(name='q')
        from S#window.externalTime(ts, 1 sec) select sum(p) as total
        insert all events into O;
        """
    )
    got = collect(rt, "q")
    h = rt.get_input_handler("S")
    h.send((1000, 10.0), timestamp=1000)
    h.send((1500, 20.0), timestamp=1500)
    h.send((2100, 5.0), timestamp=2100)   # expires ts=1000 first: 30-10+5=25
    h.send((3600, 1.0), timestamp=3600)   # expires 1500 and 2100
    ins = [e.data[0] for e in got["in"]]
    assert ins == [10.0, 30.0, 25.0, 1.0]
    # expired rows emitted before their triggering current; running sums at
    # each removal: 30-10=20, then 25-20=5, then 5-5=0
    rem = [e.data[0] for e in got["removed"]]
    assert rem == [20.0, 5.0, 0.0]
    mgr.shutdown()


def test_time_window_with_system_scheduler():
    mgr, rt = run_app(
        """
        define stream S (p float);
        @info(name='q')
        from S#window.time(200 millisec) select sum(p) as total insert all events into O;
        """
    )
    got = collect(rt, "q")
    h = rt.get_input_handler("S")
    # first send triggers jit compile (can exceed the window duration), so only
    # the timer-driven behaviors are asserted, not inter-send running sums
    h.send((4.0,))
    h.send((6.0,))
    assert got["in"][0].data[0] == 4.0
    # wait for timer-driven expiry with no further events
    deadline = time.time() + 5
    while len(got["removed"]) < 2 and time.time() < deadline:
        time.sleep(0.02)
    assert len(got["removed"]) == 2
    assert got["removed"][-1].data[0] == 0.0  # sum back to 0 after all expired
    mgr.shutdown()


def test_time_length_window():
    mgr, rt = run_app(
        """
        define stream S (ts long, p float);
        @info(name='q')
        from S#window.timeLength(1 sec, 2) select sum(p) as total insert into O;
        """
    )
    got = collect(rt, "q")
    h = rt.get_input_handler("S")
    # wall-clock timestamps (the system scheduler would instantly expire
    # back-dated events); length cap = 2 evicts oldest on the 3rd send
    h.send((0, 1.0))
    h.send((0, 2.0))
    h.send((0, 4.0))
    ins = [e.data[0] for e in got["in"]]
    assert ins[0] == 1.0
    # unless the 1-sec window lapsed between sends (slow CI), the length cap
    # governs: running sums 1, 3, then (3-1)+4
    if len(got["removed"]) == 1:
        assert ins == [1.0, 3.0, 6.0]
    mgr.shutdown()


def test_time_batch_event_driven():
    mgr, rt = run_app(
        """
        define stream S (ts long, p float);
        @info(name='q')
        from S#window.externalTimeBatch(ts, 1 sec) select sum(p) as total
        insert all events into O;
        """
    )
    got = collect(rt, "q")
    h = rt.get_input_handler("S")
    h.send((1000, 1.0), timestamp=1000)
    h.send((1400, 2.0), timestamp=1400)
    h.send((2100, 4.0), timestamp=2100)  # crosses boundary -> flush bucket 1
    h.send((3050, 8.0), timestamp=3050)  # crosses -> flush bucket 2
    # flushes emit one final bucket sum each (processInBatchNoGroupBy)
    assert [e.data[0] for e in got["in"]] == [3.0, 4.0]
    mgr.shutdown()


def test_window_with_groupless_avg_and_filter_downstream():
    mgr, rt = run_app(
        """
        define stream S (p float);
        @info(name='q')
        from S#window.length(2) select avg(p) as a insert into Mid;
        from Mid[a > 5.0] select a insert into Out;
        """
    )
    out = []
    rt.add_callback("Out", lambda events: out.extend(events))
    h = rt.get_input_handler("S")
    for v in [2.0, 6.0, 20.0]:
        h.send((v,))
    # avgs: 2, 4, 13 -> only 13 passes downstream
    assert [e.data[0] for e in out] == [13.0]
    mgr.shutdown()


def test_in_batch_time_eviction_no_double_expiry():
    """Regression: a row time-evicted within its own arrival batch must not be
    re-inserted into the ring (it would expire twice and corrupt sums)."""
    mgr, rt = run_app(
        """
        define stream S (ts long, p float);
        @info(name='q')
        from S#window.externalTime(ts, 1 sec) select sum(p) as total
        insert all events into O;
        """
    )
    got = collect(rt, "q")
    h = rt.get_input_handler("S")
    h.send_many([(1000, 10.0), (2100, 20.0)], timestamps=[1000, 2100])
    h.send((3600, 1.0), timestamp=3600)
    assert [e.data[0] for e in got["in"]] == [10.0, 20.0, 1.0]
    assert [e.data[0] for e in got["removed"]] == [0.0, 0.0]
    mgr.shutdown()


def test_post_window_filter_keeps_timer_scheduling():
    """Regression: a filter after the window must not drop the window's
    next_timer aux, or time windows never expire without new events."""
    mgr, rt = run_app(
        """
        define stream S (p float);
        @info(name='q')
        from S#window.time(300 millisec)[p > 0] select sum(p) as total
        insert all events into O;
        """
    )
    got = collect(rt, "q")
    rt.get_input_handler("S").send((5.0,))
    deadline = time.time() + 5
    while not got["removed"] and time.time() < deadline:
        time.sleep(0.02)
    assert got["removed"], "timer-driven expiry never fired through post-window filter"
    mgr.shutdown()


# ---------------------------------------------------------------------------
# the length step against a plain deque, stage by stage (both ring steps)
# ---------------------------------------------------------------------------

_LANES = StreamSchema(
    "S",
    [
        ("b", AttrType.BOOL), ("i", AttrType.INT), ("l", AttrType.LONG),
        ("f", AttrType.FLOAT), ("d", AttrType.DOUBLE), ("s", AttrType.STRING),
    ],
)


def _lane_batch(rng, valid, base):
    """Rows with every lane type; the long lanes (`l`, and `ts` from 2**33
    up) hold values above 2**32 and `l` negative ones too, so a half of a
    64-bit ring lane that went missing would show."""
    n = len(valid)
    return EventBatch(
        ts=jnp.asarray(2**33 + base + np.arange(n), jnp.int64),
        kind=jnp.zeros((n,), jnp.int8),
        valid=jnp.asarray(valid),
        cols={
            "b": jnp.asarray(rng.integers(0, 2, n).astype(bool)),
            "i": jnp.asarray(rng.integers(-100, 100, n), jnp.int32),
            "l": jnp.asarray(rng.integers(-(2**40), 2**40, n), jnp.int64),
            "f": jnp.asarray(rng.random(n), jnp.float32),
            "d": jnp.asarray(rng.random(n), jnp.float32),
            "s": jnp.asarray(rng.integers(1, 9, n), jnp.int32),
        },
    )


def _length_step(win):
    def step(state, batch):
        state, flow = win.apply(
            state, Flow(batch=batch, ref="S", now=np.int64(0))
        )
        return state, flow.batch

    return step


def _leaves_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        x.dtype == y.dtype and np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb)
    )


@pytest.mark.parametrize(
    "w,bsz",
    [(40, 16), (16, 16), (17, 16), (31, 16), (64, 8), (5, 16), (3, 8), (1, 4)],
)
def test_length_step_matches_deque_and_scatter_state(w, bsz):
    """Output rows in order against a deque; ring state slot by slot against
    the deque's contents and, where the slice step runs (W >= B), byte for
    byte against the scatter step's. The validity patterns cycle through
    half valid, full, empty, one row and nine tenths, so every (W, B) sees
    the fill crossing (total < W <= total + c) and several ring wraps."""
    rng = np.random.default_rng(w * 131 + bsz)
    win = SlidingWindow(_LANES, "S", w)
    step = jax.jit(_length_step(win))
    state = win.init_state()
    ref_win = SlidingWindow(_LANES, "S", w)
    ref_win._pick_ring_step = lambda _bsz: "scatter"
    ref_step = jax.jit(_length_step(ref_win))
    ref_state = ref_win.init_state()

    held: collections.deque = collections.deque()  # (seq, row)
    total = 0
    patterns = [
        lambda: rng.random(bsz) < 0.5,
        lambda: np.ones(bsz, bool),
        lambda: np.zeros(bsz, bool),
        lambda: np.arange(bsz) == bsz // 2,
        lambda: rng.random(bsz) < 0.9,
    ]
    for k in range(3 * len(patterns)):
        valid = patterns[k % len(patterns)]()
        batch = _lane_batch(rng, valid, 1000 * k)
        host = jax.tree_util.tree_map(np.asarray, batch)
        want = []
        for r in np.flatnonzero(valid):
            row = tuple(host.cols[n][r] for n in host.cols)
            if len(held) == w:
                want.append((1, host.ts[r], held.popleft()[1]))
            held.append((total, (host.ts[r], row)))
            total += 1
            want.append((0, host.ts[r], (host.ts[r], row)))

        state, out = step(state, batch)
        o = jax.tree_util.tree_map(np.asarray, out)
        rows = np.flatnonzero(o.valid)
        assert list(rows) == list(range(len(want))), "valid rows are a prefix"
        got = [
            (int(o.kind[p]), o.ts[p], tuple(o.cols[n][p] for n in o.cols))
            for p in rows
        ]
        assert got == [(kind, ts, row[1]) for kind, ts, row in want]

        # the ring's long lanes are stored as halves: read the logical ones
        s = jax.tree_util.tree_map(np.asarray, SlidingWindow.lanes(state))
        assert int(s["total"]) == total
        assert {s[k].dtype for k in ("ts", "wts", "seq")} == {np.dtype("int64")}
        assert s["cols"]["l"].dtype == np.int64
        live = {seq % w: (seq, row) for seq, row in held}
        for slot in range(w):
            if slot in live:
                seq, (ts, row) = live[slot]
                assert s["seq"][slot] == seq and s["ts"][slot] == ts
                assert s["wts"][slot] == ts
                assert tuple(s["cols"][n][slot] for n in s["cols"]) == row
            else:
                assert s["seq"][slot] == -1

        ref_state, ref_out = ref_step(ref_state, batch)
        assert _leaves_equal(out, ref_out)
        assert _leaves_equal(state, ref_state)
    assert win.ring_step == ("slice" if w >= bsz else "scatter")
    assert ref_win.ring_step == "scatter"


@pytest.mark.parametrize("w,dur", [(12, 20), (64, 20), (16, 5), (40, 3)])
def test_time_window_matches_deque_with_long_lanes_as_pairs(w, dur):
    """timeLength(dur, w) on the event's own ts against a deque: every due
    row expires before the CURRENT that finds it due (also one that came in
    the same batch), then the oldest row if the window is full; expired
    rows carry the trigger's ts. The ring afterwards holds the deque, its
    64-bit lanes as halves."""
    bsz = 16
    rng = np.random.default_rng(w * 17 + dur)
    win = SlidingWindow(_LANES, "S", w, duration_ms=dur)
    step = jax.jit(_length_step(win))
    state = win.init_state()
    held: collections.deque = collections.deque()  # (seq, ts, row)
    total = 0
    for k in range(12):
        valid = rng.random(bsz) < (0.9 if k % 3 else 0.4)
        host = jax.tree_util.tree_map(
            np.asarray, _lane_batch(rng, valid, 24 * k))
        want = []
        for r in np.flatnonzero(valid):
            row = tuple(host.cols[n][r] for n in host.cols)
            while held and host.ts[r] - held[0][1] >= dur:
                want.append((1, host.ts[r], held.popleft()[2]))
            if len(held) == w:
                want.append((1, host.ts[r], held.popleft()[2]))
            held.append((total, host.ts[r], row))
            total += 1
            want.append((0, host.ts[r], row))

        state, out = step(state, jax.tree_util.tree_map(jnp.asarray, host))
        o = jax.tree_util.tree_map(np.asarray, out)
        got = [
            (int(o.kind[p]), o.ts[p], tuple(o.cols[n][p] for n in o.cols))
            for p in np.flatnonzero(o.valid)
        ]
        assert got == want

        assert all(
            leaf.dtype.itemsize < 8 or leaf.ndim == 0
            for leaf in jax.tree_util.tree_leaves(state)
        )
        s = jax.tree_util.tree_map(np.asarray, SlidingWindow.lanes(state))
        assert int(s["total"]) == total
        live = {seq % w: (seq, ts, row) for seq, ts, row in held}
        for slot in range(w):
            if slot in live:
                seq, ts, row = live[slot]
                assert (s["seq"][slot], s["ts"][slot], s["wts"][slot]) == (
                    seq, ts, ts)
                assert tuple(s["cols"][n][slot] for n in s["cols"]) == row
            else:
                assert s["seq"][slot] == -1
        cols, ts, mask = jax.tree_util.tree_map(np.asarray, win.view(state))
        assert list(ts[mask]) == [t for _, t, _ in held]
        at = list(host.cols).index("l")
        assert list(cols["l"][mask]) == [row[at] for _, _, row in held]
        assert list(np.asarray(win.view_seq(state))[mask]) == [
            seq for seq, _, _ in held]
    assert total > 2 * w  # the ring wrapped


def _ring_sized_eqns(jaxpr, least):
    """(primitive, shapes) of every equation with an operand or result of
    at least `least` elements, sub-jaxprs included (their call sites are
    not counted: the body's own equations are)."""
    found = []
    for eqn in jaxpr.eqns:
        subs = list(jax_core.jaxprs_in_params(eqn.params))
        for sub in subs:
            found += _ring_sized_eqns(sub, least)
        if subs:
            continue
        shapes = [
            v.aval.shape
            for v in (*eqn.invars, *eqn.outvars)
            if hasattr(v.aval, "shape")
        ]
        if any(int(np.prod(s)) >= least for s in shapes):
            found.append((eqn.primitive.name, shapes))
    return found


def test_length_step_touches_the_ring_through_slices_only():
    """The O(batch) property, held on the CPU: with W >= B and nothing
    reading the membership matrix, no equation that survives dead-code
    elimination has a [W]- or [W + B]-shaped operand except the slices
    that read the run and the dynamic_update_slices that write it."""
    w, bsz = 4096, 64

    def live_ring_eqns(win):
        batch = _lane_batch(np.random.default_rng(0), np.ones(bsz, bool), 0)
        closed = jax.make_jaxpr(_length_step(win))(win.init_state(), batch)
        jaxpr, _ = pe.dce_jaxpr(closed.jaxpr, [True] * len(closed.jaxpr.outvars))
        return _ring_sized_eqns(jaxpr, w)

    win = SlidingWindow(_LANES, "S", w)
    touched = live_ring_eqns(win)
    assert win.ring_step == "slice"
    assert {p for p, _ in touched} == {
        "slice", "dynamic_slice", "dynamic_update_slice"
    }, touched
    # the guard sees what it is there to see: the scatter step's passes
    scatter = SlidingWindow(_LANES, "S", w)
    scatter._pick_ring_step = lambda _bsz: "scatter"
    assert {"concatenate", "select_n", "scatter"} <= {
        p for p, _ in live_ring_eqns(scatter)
    }


def test_no_64_bit_array_of_ring_length_crosses_the_step_boundary():
    """The per-batch step of length(N >= batch) over a stream with a long
    column: no parameter and no result of the lowered program that is as
    long as the ring has a 64-bit element type (XLA:TPU would split or
    combine all of it at the boundary, whatever the step touches), and
    the ring's 32-bit leaves are there to be seen."""
    w, bsz = 4096, 8
    mgr, rt = run_app(f"""@app:batch(size='{bsz}')
        define stream S (k string, v long, p float);
        @info(name='q') from S#window.length({w})
        select k, sum(v) as s, max(p) as m insert all events into O;""")
    qr = rt.queries["q"]
    lowered = qr._step.lower(
        qr.init_state(), qr._collect_table_states(),
        qr.in_schema.empty_batch(bsz), jnp.asarray(0, jnp.int64),
    )
    assert qr.chain.window.ring_step == "slice"
    ring_long = [
        (leaf.shape, np.dtype(leaf.dtype))
        for leaf in jax.tree_util.tree_leaves(
            (lowered.args_info, lowered.out_info))
        if w in leaf.shape
    ]
    # cols k, p and two halves each of v, ts, wts, seq: once in, once out
    assert len(ring_long) == 2 * (2 + 2 * 4), ring_long
    assert all(dtype.itemsize == 4 for _, dtype in ring_long), ring_long
    mgr.shutdown()


@pytest.mark.parametrize("app", ["partition", "shared"])
def test_long_lanes_in_partitioned_and_shared_rings(app):
    """The pair layout under core/partition.py's vmap and in a ring two
    queries share (core/ingest.py share sets): running sums of a long
    column with values round +-2**40 against per-key (or one) deques."""
    n_win = {"partition": 12, "shared": 16}[app]
    mgr, rt = run_app(_RING_APPS[app])
    got = []
    rt.add_callback("q", lambda ts, ins, rem: got.extend(
        (e.timestamp, tuple(e.data)) for e in ins or []))
    rng = np.random.default_rng(11)
    held: dict = collections.defaultdict(
        lambda: collections.deque(maxlen=n_win))
    want = []
    t = 0
    for _ in range(12):
        n = int(rng.integers(1, 30))
        rows = [
            (str(rng.integers(0, 3)), int(rng.integers(-(2**40), 2**40)), 1.0)
            for _ in range(n)
        ]
        rt.get_input_handler("S").send_many(
            rows, timestamps=list(range(t, t + n)))
        for i, (k, v, _p) in enumerate(rows):
            ring = held[k if app == "partition" else ""]
            ring.append(v)
            want.append((t + i, sum(ring)))
        t += n
    win = rt.snapshot_status()["queries"]["q"]["window"]
    assert win["wide_lanes"] == "u32x2" and win["ring_step"] == "slice"
    mgr.shutdown()
    assert sorted((ts, row[1]) for ts, row in got) == want


_HEAD = "@app:batch(size='8')\ndefine stream S (k string, v long, p float);\n"
_RING_APPS = {
    # core/partition.py vmaps the stage: the run's start is batched
    "partition": _HEAD + """partition with (k of S) begin
        @info(name='q') from S#window.length(12)
        select k, sum(v) as s, max(p) as m insert all events into O; end;""",
    # join sides step their rings through the stage and read them by view()
    "join": _HEAD + """define stream T (k string, v long, p float);
        @info(name='q') from S#window.length(9) as a
        join T#window.length(20) as b on a.k == b.k
        select a.k as k, a.v as av, b.v as bv insert all events into O;""",
    # the aggregators that read the membership matrix
    "member": _HEAD + """@info(name='q') from S#window.length(10)
        select k, min(v) as mn, max(p) as mx, distinctCount(k) as dc
        insert all events into O;""",
    # one ring shared between two queries (core/fusion_exec.py)
    "shared": _HEAD + """@info(name='q') from S#window.length(16)
        select k, sum(v) as s insert all events into O;
        @info(name='q2') from S#window.length(16)
        select k, avg(p) as a group by k insert all events into O2;""",
}


@pytest.mark.parametrize("app", sorted(_RING_APPS))
def test_slice_and_scatter_steps_give_the_same_app_output(app, monkeypatch):
    def run():
        mgr, rt = run_app(_RING_APPS[app])
        got = []
        for q in ("q", "q2"):
            if q in _RING_APPS[app]:
                rt.add_callback(q, lambda ts, ins, rem, q=q: got.append(
                    (q, [(e.timestamp, tuple(e.data))
                         for e in (ins or []) + (rem or [])])))
        rng = np.random.default_rng(5)
        t = 0
        for _ in range(10):
            for s in ("S", "T") if app == "join" else ("S",):
                n = int(rng.integers(1, 30))
                rows = [
                    (str(rng.integers(0, 3)), int(rng.integers(0, 100)),
                     float(rng.integers(0, 50)))
                    for _ in range(n)
                ]
                rt.get_input_handler(s).send_many(
                    rows, timestamps=list(range(t, t + n)))
                t += n
        mgr.shutdown()
        return got

    sliced = run()
    monkeypatch.setattr(
        SlidingWindow, "_pick_ring_step", lambda self, bsz: "scatter")
    assert sliced and sliced == run()
