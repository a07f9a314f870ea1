"""The plain reference of `debs14-q1-late` (`@app:watermark(bound='2 sec')`
ahead of the smart-plug average over the last 7,200 s) against a row-by-row
loop of `t-late`'s rules with the window worked out from the definition; its
generator against `debs14-q1-time`'s, whose stream it permutes; the delay
law as the configuration states it; the control; and the reference's speed."""

import json
import time

import numpy as np
import pytest

import harness
from conftest import BENCH

CONFIG = "debs14-q1-late"


def load(config=CONFIG):
    cdir = BENCH / "configs" / config
    return (harness.load_module(cdir / "gen.py"),
            harness.load_module(cdir / "reference.py"),
            json.loads((cdir / "config.json").read_text()))


def stream(gen, seed, n):
    cols = gen.make(seed, n)
    ts = gen.timestamps(0, n)
    return ts, gen.with_index(cols, 0, n, ts)


def by_rows(ts, cols, cuts, sizes):
    """`t-late`'s loop over rows, then the window by its definition: per
    call the emissions owed (event time, ts, key triple, mean of the plug's
    load records let through so far whose ts is less than `window_s` behind),
    and the late count."""
    bound, window = sizes["bound_ms"], sizes["window_s"]
    held, through, late = [], [], 0
    newest = mark = None
    calls = []
    for lo, hi in zip(cuts[:-1], cuts[1:]):
        for i in range(lo, hi):
            t = int(ts[i])
            if mark is not None and t < mark:
                late += 1
                continue
            held.append((t, i))
            if newest is None or t > newest:
                newest = t
        if newest is not None and (mark is None or newest - bound > mark):
            mark = newest - bound
        passing = sorted(h for h in held if h[0] <= mark)
        held = [h for h in held if h[0] > mark]
        out = []
        for t, i in passing:
            if not cols["property"][i]:
                continue
            key = tuple(int(cols[k][i]) for k in ("house_id", "household_id",
                                                  "plug_id"))
            through.append((int(cols["ts"][i]), key, float(cols["value"][i])))
            mine = [v for s, k, v in through
                    if k == key and s > int(cols["ts"][i]) - window]
            out.append((t, int(cols["ts"][i]), *key, float(np.mean(mine))))
        calls.append(out)
    return calls, late


@pytest.mark.parametrize("per_call", [700, 2600])
def test_replay_equals_the_loop_over_rows(per_call):
    gen, ref, cfg = load()
    sizes = {**cfg["sizes"], "window_s": 3}
    n = 7800
    ts, cols = stream(gen, 2**31 + 47, n)
    cuts = list(range(0, n, per_call)) + [n]
    want, want_late = by_rows(ts, cols, cuts, sizes)
    replay = ref.Replay(sizes)
    for c, (lo, hi) in enumerate(zip(cuts[:-1], cuts[1:])):
        emit = c % 3 != 1  # counting alone has to move the state as well
        owed, lanes = replay.feed(
            "Plug", ts[lo:hi], {k: v[lo:hi] for k, v in cols.items()}, emit)
        assert owed == len(want[c]), c
        if emit and owed:
            got = list(zip(*(lanes[k].tolist() for k in (
                "event_time", "ts", "house_id", "household_id", "plug_id"))))
            assert got == [w[:5] for w in want[c]], c
            assert np.allclose(lanes["avgLoad"], [w[5] for w in want[c]],
                               rtol=1e-12, atol=0)
    assert replay.late == want_late > 0
    assert replay.offered == n


def test_generator_is_the_time_stream_permuted_and_nothing_else():
    gen, _, cfg = load()
    time_gen, _, _ = load("debs14-q1-time")
    n = 60 * 4250
    mine, theirs = gen.make(2**31 + 9, n), time_gen.make(2**31 + 9, n)
    assert sorted(mine) == sorted(theirs)

    def rows(cols, t):
        return sorted(zip(t.tolist(), *(cols[k].tolist() for k in sorted(cols))))

    # a record that arrives in the cycle after its own carries that cycle's
    # time: put it back and the two pools are one multiset of rows
    own = gen.timestamps(0, n) + gen._cycles_back * gen._pool_seconds * 1000
    assert rows(mine, own) == rows(theirs, time_gen.timestamps(0, n))
    assert gen._pool_seconds == time_gen._pool_seconds
    assert 0 < gen._cycles_back.sum() < 0.1 * 4 * 4250
    assert gen._cycles_back[:3 * 4250].sum() == gen._cycles_back.sum()
    # the same seed gives the same stream, another seed another
    again = gen.make(2**31 + 9, n)
    assert all(np.array_equal(mine[k], again[k]) for k in mine)
    assert not np.array_equal(gen.make(5, n)["value"], mine["value"])


def test_delays_are_the_configurations_law():
    gen, _, cfg = load()
    time_gen, _, _ = load("debs14-q1-time")
    n = 200 * 4250
    time_gen.make(2**31 + 3, n)
    second = time_gen._second_of_row
    order, delay, wraps = gen.arrival_order(
        second, 2**31 + 3, time_gen._pool_seconds)
    held_back = delay > 0
    assert abs(held_back.mean() - cfg["sizes"]["delayed_share"]) < 0.002
    assert delay[held_back].min() == 1
    assert delay[held_back].max() == cfg["sizes"]["delay_upto_ms"]
    assert abs(delay[held_back].mean() - 1500.5) < 10       # uniform
    # each record by itself: a reading's two records part ways
    assert 0.15 < (held_back[0::2] != held_back[1::2]).mean() < 0.21
    # a record that was not held back keeps its place among those like it
    kept = np.flatnonzero(~held_back[order] & ~wraps[order])
    assert (np.diff(order[kept]) > 0).all()
    # in arrival order time runs backwards by up to 3 s, and no further
    gen.make(2**31 + 3, n)
    back = np.maximum.accumulate(gen.timestamps(0, n)) - gen.timestamps(0, n)
    assert 2000 < back[4250 * 4:].max() <= 3000 + 1000


def test_event_time_follows_the_global_row_index_across_cycles():
    gen, _, _ = load()
    n = 20 * 4250
    gen.make(11, n)
    period = gen._pool_seconds * 1000
    a = gen.timestamps(0, n)
    assert np.array_equal(gen.timestamps(n, 2 * n), a + period)
    assert np.array_equal(gen.timestamps(n - 70, n + 30),
                          np.r_[a[-70:], a[:30] + period])
    # the wrap is like any other place of the stream: the cycle's first
    # rows include those held back from the end of the cycle before
    first = gen.timestamps(n, n + 3 * 4250)
    assert (first < gen.T0_S * 1000 + period).sum() == gen._cycles_back.sum()


def test_control_in_bfloat16_fails_avgload_alone():
    gen, ref, cfg = load()
    sizes = {**cfg["sizes"], "window_s": 400}
    n = 1000 * 4250
    ts, cols = stream(gen, 2_900_000_047, n)
    runs = [ref.Replay(sizes), ref.Replay(sizes, control=True)]
    per_call = 2_097_152 // 8
    worst = {lane: 0.0 for lane in cfg["compare"]}
    for lo in range(0, n, per_call):
        part = {k: v[lo:lo + per_call] for k, v in cols.items()}
        (n_a, want), (n_b, broken) = (
            r.feed("Plug", ts[lo:lo + per_call], part, True) for r in runs)
        assert n_a == n_b
        if lo < n // 2:
            continue  # the window is filling
        for lane, rule in cfg["compare"].items():
            worst[lane] = max(worst[lane],
                              harness.lane_gap(broken[lane], want[lane], rule))
    assert runs[0].late == runs[1].late > 0
    for lane, rule in cfg["compare"].items():
        if lane == "avgLoad":
            assert worst[lane] > 10 * rule["limit"], worst
        else:
            assert worst[lane] == 0


def test_reference_speed(capsys):
    """Fed every call of a run (the fill's 15 and some 130 more of 2,097,152
    rows), a call has to cost well under 2 s."""
    gen, ref, cfg = load()
    n = 2 * 2_097_152
    ts, cols = stream(gen, 2**31 + 1, n)
    replay = ref.Replay(cfg["sizes"])
    t0 = time.perf_counter()
    for lo in (0, n // 2):
        replay.feed("Plug", ts[lo:lo + n // 2],
                    {k: v[lo:lo + n // 2] for k, v in cols.items()}, False)
    rate = n / (time.perf_counter() - t0)
    with capsys.disabled():
        print(f"\n{CONFIG} reference: {rate / 1e6:.2f} M rows/s of feed "
              f"(counting), late {replay.late}")
    assert rate > 2_097_152 / 2
