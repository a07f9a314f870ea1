"""The time-bounded window's step as a FIFO, in passes (`core/windows.py`
`fifo_pass`, `core/query_runtime.py` `_step_passes`, PR 30), against a
row-by-row transcription of the reference's loop
(`ExternalTimeWindowProcessor.process`: walk the expired queue from its head,
stop at the first row not yet due, then add the arrival), on seeded streams.

A query that publishes CURRENT rows alone takes the fifo step: each row
comes with the running sum and count behind it, so a row that left early,
late or not at all shows in the next emission. A query that publishes
EXPIRED rows too keeps the matrix step (its output holds every row a step
lets go) and follows the same rule: there every emission is compared, in
the order the window emitted it. Values are small integers, exact in
float32."""

from __future__ import annotations

import logging
from collections import deque

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager

APP = """@app:name('T')
@app:batch(size='{batch}')
{extra}
define stream S (ts long, k int, v float);
@info(name='q')
from S#window.externalTime(ts, {t}) select ts, k, v, {select} {group}
insert {events} into O;
"""


def loop(rows, t, capacity=None, grouped=False, expired=True):
    """The reference's loop, row by row: (ts, k, v, sum, count) of every
    emission in order, the sum and count those of the row's group (or of the
    whole window) once the row has come or gone. With `capacity`, the engine's
    documented policy on top: a full window lets its oldest row go early,
    just before the arrival that needs its place. Returns (emissions, rows
    that left, of them early)."""
    queue, out = deque(), []
    total, count = {}, {}
    left = early = 0

    def emit(row, sign):
        g = row[1] if grouped else 0
        total[g] = total.get(g, 0.0) + sign * row[2]
        count[g] = count.get(g, 0) + sign
        if sign > 0 or expired:
            out.append((row[0], row[1], row[2], total[g], count[g]))

    for row in rows:
        while queue and queue[0][0] - row[0] + t <= 0:
            emit(queue.popleft(), -1)
            left += 1
        if capacity is not None and len(queue) == capacity:
            emit(queue.popleft(), -1)
            left += 1
            early += 1
        queue.append(row)
        emit(row, +1)
    return out, left, early


def drive(rows, t, batch, sends, *, extra="", grouped=False, events="all events",
          select="sum(v) as s, count() as n", restore_at=None):
    """The engine over `rows`, sent in stretches of `sends` rows (a number, or
    a list of stretch lengths): (emissions as `loop` gives them, the window's
    status, what the engine logged). With `restore_at`, the app is
    snapshotted after that many rows, shut down, and a fresh runtime restored
    from the snapshot takes the rest."""
    text = APP.format(batch=batch, extra=extra, t=t, select=select, events=events,
                      group="group by k" if grouped else "")
    ts = np.array([r[0] for r in rows], dtype=np.int64)
    cols = {"ts": ts, "k": np.array([r[1] for r in rows], dtype=np.int32),
            "v": np.array([r[2] for r in rows], dtype=np.float32)}
    if isinstance(sends, int):
        sends = [sends] * -(-len(rows) // sends)
    records = []

    class Catch(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    handler = Catch(level=logging.WARNING)
    logging.getLogger("siddhi_tpu").addHandler(handler)
    mgr = SiddhiManager()
    out = []

    def start():
        rt = mgr.create_siddhi_app_runtime(text)
        rt.add_callback("O", lambda events: out.extend(
            tuple(e.data) for e in events))
        rt.start()
        return rt

    try:
        rt = start()
        at = 0
        for n in sends:
            if restore_at is not None and at >= restore_at:
                snap = rt.snapshot()
                rt.shutdown()
                rt = start()
                rt.restore(snap)
                restore_at = None
            lo, hi = at, min(at + n, len(rows))
            if hi > lo:
                rt.get_input_handler("S").send_columns(
                    ts[lo:hi], {k: c[lo:hi] for k, c in cols.items()})
            at = hi
        for qr in rt.queries.values():
            qr.flush_aux_warnings()
        status = rt.snapshot_status()["queries"]["q"]["window"]
        rt.shutdown()
    finally:
        mgr.shutdown()
        logging.getLogger("siddhi_tpu").removeHandler(handler)
    return out, status, records


def stream(seed: int, n: int, kind: str, t: int):
    """`n` rows (window time, key, value) of one of the shapes under test."""
    rng = np.random.default_rng(seed)
    step = {
        "ordered": rng.integers(1, 4, n),
        "ties": rng.integers(0, 2, n),
        "missing": np.where(rng.random(n) < 0.05, rng.integers(t // 4, t // 2, n), 1),
        # one silence, longer than the window, in the middle of a batch
        "gap": np.where(np.arange(n) == n // 2 + 7, 3 * t, rng.integers(0, 2, n)),
        "span": rng.integers(t // 8, t // 3, n),
    }.get(kind)
    if kind == "disorder":
        wts = np.cumsum(rng.integers(1, 4, n)) + rng.integers(-2 * t, 1, n)
    else:
        wts = 1_000 + np.cumsum(step)
    return [(int(w), int(k), float(v)) for w, k, v in
            zip(wts, rng.integers(0, 7, n), rng.integers(1, 9, n))]


def same(got, want):
    assert len(got) == len(want)
    assert got == want


# ---- one case per property, each against the loop --------------------------

PROPERTIES = {
    # kind, rows, window, batch, capacity, rows per send
    "ordered": ("ordered", 900, 120, 32, 256, 64),
    "ties": ("ties", 900, 40, 32, 256, 64),
    "missing": ("missing", 900, 60, 32, 256, 64),
    "span": ("span", 600, 40, 32, 64, 64),         # a batch outlasts the window
    "wrap": ("ordered", 1200, 100, 32, 100, 64),   # 100 is no multiple of 32
    "per_batch_small_sends": ("ordered", 500, 90, 32, 128, 7),
    "one_fused_send": ("missing", 1500, 60, 32, 256, 1500),
    "disorder": ("disorder", 900, 50, 32, 512, 64),
}


STEP_OF = {"current events": "fifo", "all events": "matrix"}


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
@pytest.mark.parametrize("events", sorted(STEP_OF))
@pytest.mark.parametrize("name", sorted(PROPERTIES))
def test_every_emission_as_the_reference_loop(name, events, seed):
    kind, n, t, batch, cap, send = PROPERTIES[name]
    rows = stream(seed, n, kind, t)
    want, left, early = loop(rows, t, expired=events == "all events")
    got, status, logged = drive(
        rows, t, batch, send, events=events,
        extra=f"@app:timeCapacity(size='{cap}')")
    same(got, want)
    assert status["time_step"] == STEP_OF[events] and status["capacity"] == cap
    assert status["expired_rows"] == left
    assert status["early_expired"] == early == 0 and not logged
    assert status["fill"] == len(rows) - left
    # a flow holds as many EXPIRED rows as the batch has places: a step that
    # lets go of fewer takes one pass, a batch that outlasts the window lets
    # go of more, its own among them
    if name == "per_batch_small_sends" or events == "all events":
        assert status["extra_passes"] == 0
    if name == "span" and events == "current events":
        assert status["extra_passes"] > 0


@pytest.mark.parametrize("grouped", [False, True], ids=["whole", "grouped"])
@pytest.mark.parametrize("send", [64, 700], ids=["per_batch", "fused"])
def test_gap_takes_extra_passes_and_stays_exact(send, grouped):
    """After a silence longer than the window more rows are due than one
    flow holds: the step takes further passes, and every aggregate is the
    loop's. Some 250 rows leave at one arrival, in flows of 32."""
    t, batch = 400, 32
    rows = stream(5, 700, "gap", t)
    want, left, _ = loop(rows, t, grouped=grouped, expired=False)
    got, status, logged = drive(
        rows, t, batch, send, extra="@app:timeCapacity(size='512')",
        grouped=grouped, events="current events")
    same(got, want)
    assert status["time_step"] == "fifo" and left > 200
    assert status["extra_passes"] >= 6 and status["expired_rows"] == left
    assert status["early_expired"] == 0 and not logged


@pytest.mark.parametrize("events", sorted(STEP_OF))
@pytest.mark.parametrize("kind", ["ordered", "ties"])
def test_capacity_exceeded_is_flagged_and_counted(kind, events):
    t, batch, cap = 500, 32, 96
    rows = stream(9, 800, kind, t)
    want, left, early = loop(rows, t, capacity=cap, expired=events == "all events")
    got, status, logged = drive(
        rows, t, batch, 64, extra=f"@app:timeCapacity(size='{cap}')", events=events)
    same(got, want)
    assert status["time_step"] == STEP_OF[events]
    assert early > 0 and status["early_expired"] == early
    assert status["expired_rows"] == left and status["fill"] <= cap
    assert any("expired early" in line and "timeCapacity" in line for line in logged)


@pytest.mark.parametrize("capacity, total, flagged", [(None, 1024.0, True), (3000, 3000.0, False),
                                                      (4096, 3000.0, False)])
def test_three_thousand_rows_in_ten_seconds(capacity, total, flagged):
    """ISSUE 30's case: 3,000 rows 1 ms apart in a 10 s window sum to 3,000
    at a stated capacity that holds them; at the default capacity the window
    holds 1,024, and says so."""
    rows = [(i, 0, 1.0) for i in range(3000)]
    extra = f"@app:timeCapacity(size='{capacity}')" if capacity else ""
    got, status, logged = drive(rows, 10_000, 64, 3000, extra=extra,
                                events="current events")
    assert got[-1][3] == total and len(got) == 3000
    assert (status["early_expired"] > 0) == flagged == bool(logged)
    assert status["capacity"] == (capacity or 1024)


def test_out_of_order_window_time_follows_the_head_of_the_queue():
    """The answer that was kept (CHANGES.md, PR 30): the reference expires
    from the head and stops at the first row not due, so a late row behind a
    younger head stays. Window times 0, 100, 50, 105 under a window of 10:
    100 expires 0; 50 is no trigger for 100; 105 does not reach 100 + 10, so
    50, behind it, stays too."""
    rows = [(0, 0, 1.0), (100, 0, 10.0), (50, 0, 100.0), (105, 0, 1000.0)]
    for batch, send in ((8, 4), (8, 1), (2, 4)):
        got, _, _ = drive(rows, 10, batch, send, events="current events")
        assert [g[3] for g in got] == [1.0, 10.0, 110.0, 1110.0]


@pytest.mark.parametrize("select, batch, step", [
    ("sum(v) as s, count() as n", 32, "fifo"),
    ("max(v) as s, count() as n", 32, "matrix"),   # reads the membership matrix
    ("sum(v) as s, count() as n", 1024, "matrix"),  # capacity < batch
])
def test_both_steps_answer_alike_on_disorder(select, batch, step):
    """min / max read the membership matrix, and a ring shorter than the
    batch cannot be read in runs: both keep the matrix step, which expires
    from the head as well, so an answer does not turn on a shape."""
    t = 50
    rows = stream(21, 300, "disorder", t)
    got, status, _ = drive(rows, t, batch, 64, select=select, events="current events",
                           extra="@app:timeCapacity(size='512')")
    assert status["time_step"] == step
    want, left, _ = loop(rows, t, expired=False)
    assert [g[4] for g in got] == [w[4] for w in want]      # the counts
    assert status["expired_rows"] == left
    if select.startswith("sum"):
        same(got, want)


@pytest.mark.parametrize("kind", ["missing", "gap", "disorder"])
def test_fused_send_and_per_batch_sends_are_bit_equal(kind):
    t, batch = 80, 32
    rows = stream(13, 1100, kind, t)
    extra = "@app:timeCapacity(size='300')"
    fused, st_f, _ = drive(rows, t, batch, len(rows), extra=extra, grouped=True,
                           events="current events")
    single, st_b, _ = drive(rows, t, batch, 11, extra=extra, grouped=True,
                            events="current events")
    same(fused, single)
    same(fused, loop(rows, t, grouped=True, expired=False)[0])
    for key in ("fill", "expired_rows", "early_expired", "oldest_ts", "newest_ts"):
        assert st_f[key] == st_b[key], key


@pytest.mark.parametrize("events", sorted(STEP_OF))
@pytest.mark.parametrize("restore_at", [192, 640])
def test_persist_restore_continue_equals_an_uninterrupted_run(restore_at, events):
    t, batch = 90, 32
    rows = stream(17, 1000, "missing", t)
    extra = "@app:timeCapacity(size='200')"
    whole, st_w, _ = drive(rows, t, batch, 64, extra=extra, grouped=True,
                           events=events)
    parts, st_p, _ = drive(rows, t, batch, 64, extra=extra, grouped=True,
                           events=events, restore_at=restore_at)
    same(parts, whole)
    same(whole, loop(rows, t, grouped=True, expired=events == "all events")[0])
    assert st_w["time_step"] == st_p["time_step"] == STEP_OF[events]
    assert st_p["fill"] == st_w["fill"] and st_p["expired_rows"] == st_w["expired_rows"]


@pytest.mark.parametrize("events", sorted(STEP_OF))
def test_snapshot_from_before_the_head_was_kept_restores(events):
    """A snapshot of PR 29's layout (no head, no running maximum, no
    counters) restores: the ring is laid out again from its live rows."""
    import pickle

    t, batch = 90, 32
    rows = stream(19, 640, "missing", t)
    extra = "@app:timeCapacity(size='200')"
    text = APP.format(batch=batch, extra=extra, t=t, events=events,
                      select="sum(v) as s, count() as n", group="")
    mgr = SiddhiManager()
    try:
        rt = mgr.create_siddhi_app_runtime(text)
        rt.start()
        ts = np.array([r[0] for r in rows], dtype=np.int64)
        cols = {"ts": ts, "k": np.array([r[1] for r in rows], dtype=np.int32),
                "v": np.array([r[2] for r in rows], dtype=np.float32)}
        rt.get_input_handler("S").send_columns(ts[:320], {k: c[:320] for k, c in cols.items()})
        payload = pickle.loads(rt.snapshot())
        chain = payload["elements"]["query:q"]["chain"]
        live = chain["seq"] >= chain["head"]
        chain["seq"] = np.where(live, chain["seq"], -1)     # as PR 29 kept it
        for key in ("head", "wmax", "expired", "passes", "early"):
            del chain[key]
        rt.shutdown()
        rt = mgr.create_siddhi_app_runtime(text)
        out = []
        rt.add_callback("O", lambda events: out.extend(
            tuple(e.data[:5]) for e in events))
        rt.start()
        rt.restore(pickle.dumps(payload))
        rt.get_input_handler("S").send_columns(ts[320:], {k: c[320:] for k, c in cols.items()})
        rt.shutdown()
    finally:
        mgr.shutdown()
    want = loop(rows, t, expired=events == "all events")[0]
    assert out == want[len(want) - len(out):] and len(out) >= 320


@pytest.mark.parametrize("seed", [1, 2])
def test_group_by_behind_the_window_against_numpy(seed):
    """avg / sum / count per key over the rows of the last `t` units of
    window time, computed from the whole stream at once."""
    t, n = 70, 800
    rows = stream(seed, n, "missing", t)
    got, _, _ = drive(rows, t, 32, 96, grouped=True, events="current events",
                      select="sum(v) as s, count() as n, avg(v) as a",
                      extra="@app:timeCapacity(size='256')")
    wts = np.array([r[0] for r in rows])
    key = np.array([r[1] for r in rows])
    val = np.array([r[2] for r in rows])
    assert len(got) == n
    for i, g in enumerate(got):
        inside = (np.arange(n) <= i) & (wts > wts[i] - t) & (key == key[i])
        assert (g[3], g[4]) == (val[inside].sum(), inside.sum())
        assert g[5] == pytest.approx(val[inside].mean(), rel=1e-6)


@pytest.mark.parametrize("capacity, per_bucket, flagged", [(None, None, True), (4096, 2000.0, False)])
def test_time_batch_bucket_takes_the_capacity_and_flags_what_it_drops(
        capacity, per_bucket, flagged):
    """externalTimeBatch buckets of 2,000 rows: at the default capacity a
    bucket keeps 1,024 and the engine says so; at a stated one every bucket
    is whole."""
    text = (f"@app:timeCapacity(size='{capacity}')\n" if capacity else "") + """
    define stream S (ts long, p float);
    @info(name='q') from S#window.externalTimeBatch(ts, 10 sec)
    select sum(p) as total insert into O;"""
    records = []

    class Catch(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    handler = Catch(level=logging.WARNING)
    logging.getLogger("siddhi_tpu").addHandler(handler)
    mgr = SiddhiManager()
    try:
        rt = mgr.create_siddhi_app_runtime(text)
        out = []
        rt.add_callback("O", lambda events: out.extend(e.data[0] for e in events))
        rt.start()
        ts = np.arange(25_000, dtype=np.int64) * 5
        rt.get_input_handler("S").send_columns(
            ts, {"ts": ts, "p": np.ones(len(ts), np.float32)})
        for qr in rt.queries.values():
            qr.flush_aux_warnings()
        rt.shutdown()
    finally:
        mgr.shutdown()
        logging.getLogger("siddhi_tpu").removeHandler(handler)
    assert any("overflowed" in r and "timeCapacity" in r for r in records) == flagged
    assert len(out) >= 10
    if per_bucket:
        assert set(out) == {per_bucket}
    else:
        assert max(out) < 2000.0


def test_the_deployments_chunk_program_touches_its_ring_in_runs_only():
    """`debs14-q1-time`'s chunk program, lowered at the configuration's own
    sizes (batch 32,768, a ring of 15.3 M rows): whatever has the ring's
    length is carried, sliced or updated by a slice: nothing is sorted,
    gathered, scattered or compared at that length, and no [W, B] matrix is
    built."""
    import re

    from tests.test_plug_keys4 import chunk_arguments, chunk_program, deploy, load

    config = "debs14-q1-time"
    sizes = load(config)[2]["sizes"]
    batch, ring = sizes["batch"], sizes["window_rows"]
    mgr, rt, gen, cfg = deploy(config, batch, rehearse=False)
    try:
        gen.make(7, batch)      # `timestamps` reads the pool last made
        fi, prog = chunk_program(rt, gen, cfg, batch)
        text = prog.lower(*chunk_arguments(fi)).as_text()
        window = rt.snapshot_status()["queries"][cfg["query"]]["window"]
    finally:
        rt.shutdown()
        mgr.shutdown()
    assert window["type"] == "SlidingWindow"
    touching = {
        m.group(1) for line in text.splitlines() if f"{ring}x" in line
        for m in [re.search(r"stablehlo\.(\w+)", line)] if m
    }
    assert touching <= {"while", "return", "constant", "slice", "dynamic_slice",
                        "dynamic_update_slice"}, touching
    assert not re.search(rf"{ring}x{batch}x|{batch}x{ring}x|{ring + batch}x", text)
    # the flow behind the window is twice the batch, and is what gets sorted
    assert f"tensor<{2 * batch}xi32>" in text and f"tensor<{ring}xi32>" in text
