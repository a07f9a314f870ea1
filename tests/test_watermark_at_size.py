"""`@app:watermark` ahead of a deployment's windows, at the benchmark's
rehearsal size: `debs14-q1-late` (DEBS 2014 query 1 on event time, a tenth
of the records held back by up to 3 s) through `SiddhiManager`, call by call
against the configuration's plain reference (`reference.Replay`), on the
fused path and on the per-batch path; the reorder stage's meters; a release
that ends in a tail chunk; the chunk program it leaves alone; the idle rule
behind a long call; the stage's spans; and the cell's rehearsal, sound and
broken three ways."""

from __future__ import annotations

import json
import time
from pathlib import Path

import numpy as np
import pytest

# first: it puts benchmark/ on the path, where `harness` and `run` live
from tests.test_plug_keys4 import chunk_arguments, chunk_program, deploy, load
from tests.test_stage_spans import _traced

import harness  # noqa: E402  (benchmark/harness.py)

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.watermark import LatenessHistogram, ReorderTracker

LATE = "debs14-q1-late"
BATCH = 512


def stream(seed: int, rows: int):
    """(gen, reference, sizes, timestamps, columns) of the first `rows`
    records of the seeded stream at the rehearsal's sizes."""
    gen, ref, cfg = load(LATE)
    sizes = {**cfg["sizes"], **cfg["rehearse_sizes"]}
    rows -= rows % 2              # whole readings
    cols = gen.make(seed, rows)
    ts = gen.timestamps(0, rows)
    return gen, ref, sizes, ts, gen.with_index(cols, 0, rows, ts)


def lanes_of(events, outputs) -> dict:
    out = {"event_time": np.array([e[0] for e in events], dtype=np.int64)}
    for k, name in enumerate(outputs):
        out[name] = np.array([e[1][k] for e in events])
    return out


# (batch, rows a call, calls): calls of 64 micro-batches take the fused path.
# Event time counts whole seconds, so the stage lets a second of the
# population through at a time (some 4,040 rows) however small the calls
# are: under a batch of 4,096 that is less than two micro-batches and takes
# the per-batch path, every sixth call of 48
PATHS = {"fused": (BATCH, 64 * BATCH, 5), "per-batch": (4096, 700, 48)}


@pytest.mark.parametrize("path", sorted(PATHS))
def test_engine_equals_the_reference_call_by_call(path):
    """What the query callback has received when each call returns is what
    the reference says that call owes: count, order and every lane; and the
    stage's meter holds its identities against the reference's own count."""
    batch, per_call, calls = PATHS[path]
    got = []
    mgr, rt, gen, cfg = deploy(
        LATE, batch, lambda ts, ins, removed: got.extend(ins or []))
    try:
        _, ref, sizes, ts, cols = stream(2**31 + 4701, per_call * calls + 2)
        replay = ref.Replay(sizes)
        handler = rt.get_input_handler(cfg["stream"])
        limit = cfg["compare"]["avgLoad"]
        for c in range(calls):
            lo, hi = c * per_call, (c + 1) * per_call
            part = {k: v[lo:hi] for k, v in cols.items()}
            before = len(got)
            handler.send_columns(ts[lo:hi], part)
            owed, want = replay.feed(cfg["stream"], ts[lo:hi], part, True)
            assert len(got) - before == owed, (path, c)
            mine = lanes_of(got[before:], cfg["outputs"])
            for lane, rule in cfg["compare"].items():
                gap = harness.lane_gap(mine[lane], want[lane], rule) if owed else 0
                assert gap <= rule["limit"], (path, c, lane, gap)
        status = rt.snapshot_status()
        meter = status["watermark"]["streams"][cfg["stream"]]
        assert replay.late > 0 and limit["limit"] == 2e-4
        assert meter["late_total"] == meter["dropped"] == replay.late
        assert meter["offers"] == calls and meter["offered"] == per_call * calls
        assert meter["offered"] == (
            meter["released"] + meter["buffered"] + meter["late_total"])
        assert meter["lateness_ms"]["count"] == replay.late
        depths = status["streams"][cfg["stream"]]["pipeline"]["chunks_by_depth"]
        assert (sum(depths.values()) > 0) == (path == "fused")
    finally:
        rt.shutdown()
        mgr.shutdown()


def test_a_release_that_ends_in_a_tail_takes_a_short_variant_and_loses_no_row(
        monkeypatch):
    """A call of 64 micro-batches releases a few rows more or fewer than it
    brought (what the call before held against what this one holds): past 64
    the tail rides a shorter variant of the chunk program, and the rows add
    up. Behind the stage a chunk's load records hover round half its rows, a
    power of two, and a tail's are a few hundred: a chunk's first read is
    sized by the last chunk of its own depth, and where it falls short the
    second read asks for what is missing, not for the next power of two,
    and is the last of its depth."""
    import siddhi_tpu.core.ingest as ingest

    delivered, topups = [0], []

    def count(ts, ins, removed):
        delivered[0] += len(ins or [])

    real_read = ingest.read_dense
    monkeypatch.setattr(
        ingest, "read_dense",
        lambda buf, start, n: topups.append(n) or real_read(buf, start, n))
    mgr, rt, gen, cfg = deploy(LATE, BATCH, count)
    try:
        # a call is 8.1 s of stream, so its end moves 0.1 s further into its
        # second each time and fewer rows are held at the tenth than before
        per_call, calls = 64 * BATCH, 12
        _, ref, sizes, ts, cols = stream(2**31 + 4702, per_call * calls)
        replay = ref.Replay(sizes)
        handler = rt.get_input_handler(cfg["stream"])
        owed = 0
        released = []
        for c in range(calls):
            lo, hi = c * per_call, (c + 1) * per_call
            part = {k: v[lo:hi] for k, v in cols.items()}
            handler.send_columns(ts[lo:hi], part)
            owed += replay.feed(cfg["stream"], ts[lo:hi], part, False)[0]
            meter = rt.snapshot_status()["watermark"]["streams"][cfg["stream"]]
            released.append(meter["released"])
        assert delivered[0] == owed
        sizes_released = np.diff([0, *released])
        assert (sizes_released > per_call).any(), sizes_released
        depths = rt.snapshot_status()["streams"][cfg["stream"]]["pipeline"][
            "chunks_by_depth"]
        # the tail is the second the watermark let go of beyond the call's
        # own 64 micro-batches: 8 of 512 rows here (one of 32,768 on the
        # chip, which rides the K = 2 variant)
        tails = int((sizes_released > per_call).sum())
        assert depths == {"8": tails, "32": 2 * calls}
        pipeline = rt.snapshot_status()["streams"][cfg["stream"]]["pipeline"]
        assert sizes_released[-1] <= per_call  # full chunks followed the tail
        # a depth's first shortfall asks for what is missing and makes every
        # later read of that depth ask for that much more: no second one
        assert pipeline["readback_topups"] == len(topups) == 1
        assert topups == [ingest._LEAST_READ_ROWS]
    finally:
        rt.shutdown()
        mgr.shutdown()


def test_reads_behind_ragged_sends_come_in_few_sizes():
    """A size read is a program built, and one built inside a run stalls a
    send for a second. Behind the stage at the deployment's size a full
    chunk's load records hover round 2^19 and a tail's round a thousand
    (`q1-late.bulk` on the chip, PR 47): each depth's reads settle on one
    size after at most one shortfall, whatever the totals do; under a
    least read of one row the tails alone met a dozen sizes (slack + 16,
    + 32, + 64 ... rows)."""
    from types import SimpleNamespace

    import siddhi_tpu.core.ingest as ingest

    rng = np.random.default_rng(47)
    B = 32768
    fi = SimpleNamespace(_drain_guess={})
    sizes, short = {32: [], 2: []}, {32: 0, 2: 0}
    for _ in range(400):
        for K, total in ((32, 2**19 + int(rng.integers(-40, 41))),
                         (32, 2**19 - int(rng.integers(200, 2200))),
                         (2, int(rng.integers(400, 1600)))):
            R = K * B
            guess = ingest.FusedJunctionIngest._first_read_rows(fi, 0, K, R)
            more = ingest.FusedJunctionIngest._note_total(
                fi, 0, K, total, guess, R)
            assert (more > 0) == (total > guess) and guess + more >= total
            assert more in (0, ingest._LEAST_READ_ROWS)
            sizes[K].append(guess)
            short[K] += more > 0
    least = ingest._LEAST_READ_ROWS
    assert short == {32: 1, 2: 1}
    # all rows while no total is known, the total's bucket until that falls
    # short, and from then on the one size with the slack in it
    assert sizes[2][0] == 2 * B and set(sizes[2][1:]) <= {least, 2 * least}
    assert sizes[32][0] == 32 * B
    assert set(sizes[32][1:]) <= {2**19, 2**19 + least}
    assert len(set(sizes[2][10:])) == len(set(sizes[32][10:])) == 1


def test_the_chunk_program_is_the_time_configurations():
    """The stage is host code ahead of `send_columns`: the annotation never
    reaches the lowering, so a change to this deployment's chunk program is
    a change to `debs14-q1-time`'s and is measured in both. The wire is
    chosen from the first micro-batch the junction sees, which behind the
    stage is in event-time order, as that configuration's stream is: both
    programs are built here over its rows."""
    time_gen, _, _ = load("debs14-q1-time")
    texts = []
    for config in (LATE, "debs14-q1-time"):
        mgr, rt, _, cfg = deploy(config, BATCH)
        try:
            fi, prog = chunk_program(rt, time_gen, cfg, BATCH)
            texts.append(prog.lower(*chunk_arguments(fi)).as_text())
        finally:
            rt.shutdown()
            mgr.shutdown()
    assert texts[0] == texts[1]


def test_generator_is_the_time_stream_in_another_order():
    gen, _, cfg = load(LATE)
    time_gen, _, _ = load("debs14-q1-time")
    n = 40 * BATCH
    mine, theirs = gen.make(77, n), time_gen.make(77, n)
    key = lambda c, t: sorted(zip(t.tolist(), *(c[k].tolist() for k in sorted(c))))
    back = gen.timestamps(0, n) + gen._cycles_back * gen._pool_seconds * 1000
    assert key(mine, back) == key(theirs, time_gen.timestamps(0, n))
    assert cfg["sizes"]["delayed_share"] == gen.DELAYED_SHARE
    assert cfg["sizes"]["delay_upto_ms"] == gen.DELAY_UPTO_MS
    assert (np.diff(gen.timestamps(0, n)) < 0).any()


def test_the_pool_is_no_whole_number_of_sends():
    """The harness replays `bulk-2m`'s pool rounded up to the generator's
    cycles. A pool of exactly two sends repeats a seed's two placings of a
    send among the seconds all run long (a seed's luck then reads as a mode);
    the generator's cycle leaves 4,352 rows over, so every send of a run is
    placed anew, and neither kind of send lasts near a whole number of
    seconds (`gen.py`)."""
    gen, _, cfg = load(LATE)
    traffic = json.loads(
        (Path(harness.__file__).parent / "traffic" / "bulk-2m.json").read_text())
    batch = cfg["sizes"]["batch"]
    pool = -(-traffic["pool_batches"] * batch // gen.CYCLE_ROWS) * gen.CYCLE_ROWS
    send = traffic["send_batches"] * batch
    assert gen.CYCLE_ROWS % 2 == 0 and pool % send == 4352
    a_second = 2 * cfg["sizes"]["plugs"] * (1 - cfg["sizes"]["missing_share"])
    inside = send / a_second % 1
    across = (inside + 1 - pool / a_second % 1) % 1
    assert 0.3 < inside < 0.7 and 0.3 < across < 0.7


APP_IDLE = """
@app:batch(size='64')
@app:watermark(bound='2 sec', idle.timeout='300 millisec')
define stream S (k int, v float);
@info(name='q')
from S select k, v insert into Out;
"""


def test_no_flush_behind_a_call_longer_than_idle_timeout():
    """A stream is not quiet while its call runs: the heartbeat, which waits
    on the tracker's lock during the call, must not take the call's start for
    the last sign of life, flush what is held and move the watermark to the
    newest event, or the next call's delayed rows are dropped where they
    were owed. After `idle.timeout` of real quiet it still flushes."""
    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime(APP_IDLE)
    seen = []

    def slow(ts, ins, removed):
        seen.extend(ins or [])
        time.sleep(0.6)  # twice idle.timeout, inside the send

    rt.add_callback("q", slow)
    rt.start()
    try:
        ts = 1_000_000 + np.arange(100, dtype=np.int64) * 100  # 10 s of stream
        cols = {"k": np.arange(100, dtype=np.int32),
                "v": np.ones(100, dtype=np.float32)}
        rt.get_input_handler("S").send_columns(ts, cols)
        assert len(seen) == 80  # the last 2 s are held
        time.sleep(0.2)  # two heartbeats and more
        meter = rt.snapshot_status()["watermark"]["streams"]["S"]
        assert meter["buffered"] == 20 and not meter["idle"], meter
        assert meter["watermark_ms"] == int(ts[-1]) - 2000
        deadline = time.monotonic() + 5
        while len(seen) < 100 and time.monotonic() < deadline:
            time.sleep(0.05)
        meter = rt.snapshot_status()["watermark"]["streams"]["S"]
        assert len(seen) == 100 and meter["idle"] and meter["buffered"] == 0
    finally:
        rt.shutdown()
        mgr.shutdown()


def test_reorder_and_late_spans(tmp_path):
    """`siddhi:reorder` once per call round the stage's own work (the inner
    send lies outside it), `siddhi:late` inside it where rows were late."""
    got = []
    mgr, rt, gen, cfg = deploy(
        LATE, BATCH, lambda ts, ins, removed: got.extend(ins or []))
    try:
        per_call = 64 * BATCH
        _, _, _, ts, cols = stream(2**31 + 4703, 3 * per_call)
        handler = rt.get_input_handler(cfg["stream"])

        def send(c):
            lo, hi = c * per_call, (c + 1) * per_call
            handler.send_columns(ts[lo:hi], {k: v[lo:hi] for k, v in cols.items()})

        send(0)  # builds the program
        events = _traced(tmp_path, lambda: (send(1), send(2)))
        meter = rt.snapshot_status()["watermark"]["streams"][cfg["stream"]]
    finally:
        rt.shutdown()
        mgr.shutdown()
    reorder = [e for e in events if e["name"] == "siddhi:reorder"]
    sends = [e for e in events if e["name"] == "siddhi:send"]
    late = [e for e in events if e["name"] == "siddhi:late"]
    assert len(reorder) == len(sends) == 2
    for r, s in zip(reorder, sends):
        assert r["stream"] == cfg["stream"] and r["rows"] == per_call
        assert r["t1"] <= s["t0"] and r["line"] == s["line"]
        assert r["released"] == s["rows"] and r["held"] > 0
    assert sum(r["late"] for r in reorder) == sum(e["rows"] for e in late)
    assert 0 < sum(e["rows"] for e in late) <= meter["late_total"]
    for e in late:
        assert any(r["t0"] <= e["t0"] and e["t1"] <= r["t1"] for r in reorder)


def test_histogram_takes_a_calls_late_rows_at_once():
    rng = np.random.default_rng(47)
    ms = np.concatenate([rng.integers(0, 5000, 500), [0, 1, 2**40, 2**50, -3]])
    one, many = LatenessHistogram(), LatenessHistogram()
    for v in ms:
        idx = min(max(int(v), 0).bit_length(), one._NBUCKETS - 1)
        one._counts[idx] += 1
        one._sum += int(v)
        one._count += 1
        one._max = max(one._max, int(v))
    many.record_many(ms[:200])
    many.record_many(ms[200:])
    many.record_many(ms[:0])
    assert many.snapshot() == one.snapshot() and many._counts == one._counts


def test_tracker_reads_a_calls_columns_once_and_keeps_no_view_of_them():
    """Late rows are dropped by the one gather that sorts: what is released
    is the stable event-time order of held + fresh rows, and what stays held
    is arrays of its own, not a view of the call's."""
    out, late = [], []
    tr = ReorderTracker(
        "S", 10, lambda ts, cols: out.append((ts, cols)),
        lambda ts, cols, lateness: late.append((ts, cols, lateness)))
    tr.offer([100, 95, 120, 118], {"i": np.arange(4)})
    tr.offer([109, 121, 111, 110, 125, 111], {"i": np.arange(4, 10)})
    assert out[0][0].tolist() == [95, 100]          # wm 110
    assert out[1][0].tolist() == [110, 111, 111]    # wm 115; 109 was late
    assert out[1][1]["i"].tolist() == [7, 6, 9]
    assert late[0][0].tolist() == [109] and late[0][2].tolist() == [1]
    assert tr._held_ts.tolist() == [118, 120, 121, 125]
    assert tr._held_cols["i"].tolist() == [3, 2, 5, 8]
    assert tr._held_cols["i"].base is None and tr._held_ts.base is None
    d = tr.describe()
    assert (d["offers"], d["offered"], d["late_total"]) == (2, 10, 1)
    assert d["offered"] == d["released"] + d["buffered"] + d["late_total"]


# ---- the cell's rehearsal ---------------------------------------------------

def rehearse(capsys, seed: int):
    import run as bench_run

    rc = bench_run.main(
        ["--workload", "q1-late.bulk", "--seed", str(seed), "--seconds", "1",
         "--trace", "0", "--rehearse"])
    out = capsys.readouterr().out
    assert rc == 0, out
    return json.loads(out.strip().splitlines()[-1]), out


@pytest.mark.parametrize("seed", [2**31 + 47, 4_700_000_011, 47])
def test_rehearsal_of_the_late_cell_is_correct(capsys, seed):
    result, out = rehearse(capsys, seed)
    assert result["correct"] is True and result["failed"] == 0, out
    assert result["attempted"] > 0 and result["metrics"] == {}
    for line in ("watermark.config.bound_ms = 2000 (expected 2000)",
                 "watermark.config.late_policy = 'drop' (expected 'drop')",
                 "streams.Plug.pipeline.chunk_batches = 32 (expected 32)",
                 "compared delivered.missing = 0 (limit 0)",
                 "compared order.faults = 0 (limit 0)"):
        assert line in out, line


@pytest.mark.parametrize(
    "fault", ["held_row_dropped", "released_rows_swapped", "reference_bound_1s"])
def test_rehearsal_is_not_correct_when_the_stage_is_broken(
        capsys, monkeypatch, fault):
    real_cut = ReorderTracker._cut

    def broken_cut(self, ts, cols, fresh):
        out = real_cut(self, ts, cols, fresh)
        if fault == "held_row_dropped" and self._held_ts.size:
            # the newest held row: a load or a work record, a send in two
            self._held_ts = self._held_ts[:-1]
            self._held_cols = {k: v[:-1] for k, v in self._held_cols.items()}
            self.buffered -= 1
        if fault == "released_rows_swapped" and out is not None:
            # two load records half a send apart change places
            rel_ts, rel = out
            i, j = np.flatnonzero(rel["property"])[[0, -1]]
            for lane in (rel_ts, *rel.values()):
                lane[[i, j]] = lane[[j, i]]
        return out

    if fault == "reference_bound_1s":
        real_init = harness.Deployment.__init__

        def init(self, *args, **kwargs):
            real_init(self, *args, **kwargs)
            replay = self.reference.Replay
            self.reference.Replay = lambda sizes, control=False: replay(
                {**sizes, "bound_ms": 1000}, control)

        monkeypatch.setattr(harness.Deployment, "__init__", init)
    else:
        monkeypatch.setattr(ReorderTracker, "_cut", broken_cut)
    result, out = rehearse(capsys, 2**31 + 47)
    assert result["correct"] is False, out
    wrong = {k for k, v in result["compared"].items()
             if v["limit"] is not None and v["value"] > v["limit"]}
    assert wrong, out
    if fault == "held_row_dropped":
        assert "delivered.missing" in wrong
    if fault == "released_rows_swapped":
        assert "order.faults" in wrong
