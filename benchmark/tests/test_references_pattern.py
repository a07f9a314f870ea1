"""`debs14-load-rise-pattern`: its plain reference against a loop over
single rows (where the table fills, in the steady state and across the
pool's end), in steps against the whole, the numbers its configuration
states against the generated stream, its control, and `cost.py`."""

import json

import numpy as np
import pytest

import harness
from conftest import BENCH

CONFIG = "debs14-load-rise-pattern"
SECOND = 4250                    # rows of one second of the population
LANES = ("event_time", "house_id", "household_id", "plug_id",
         "ts1", "load1", "ts2", "load2")


def load():
    cdir = BENCH / "configs" / CONFIG
    return (harness.load_module(cdir / "gen.py"),
            harness.load_module(cdir / "reference.py"),
            harness.load_module(cdir / "cost.py"),
            json.loads((cdir / "config.json").read_text()))


def loop(ts, cols, sizes, held=None):
    """The query row by row: (emissions as tuples in `LANES` order, what is
    still held). A load row completes every held first event of its plug
    that is not older than `within_ms` and at least `rise_w` below it,
    oldest first, and is then held itself."""
    within, rise = sizes["within_ms"], np.float32(sizes["rise_w"])
    held = {} if held is None else held
    out = []
    rows = zip(ts.tolist(), cols["property"].tolist(), cols["house_id"].tolist(),
               cols["household_id"].tolist(), cols["plug_id"].tolist(),
               cols["ts"].tolist(), cols["value"])
    for t, load_, house, household, plug, sec, v in rows:
        if not load_:
            continue
        key = (house, household, plug)
        mine = [h for h in held.get(key, []) if t - h[0] <= within]
        hit = [h for h in mine if v >= h[2] + rise]
        out += [(t, *key, h[1], h[2], sec, v) for h in hit]
        held[key] = [h for h in mine if h not in hit] + [(t, sec, v)]
    return out, held


def as_tuples(lanes) -> list:
    return list(zip(*(lanes[n].tolist() for n in LANES)))


def stretch(seed: int, lo: int, hi: int, pool_seconds: int = 8):
    gen, ref, _, cfg = load()
    stream = harness.Stream(gen, ref, seed, pool_seconds * SECOND)
    return ref, cfg, stream.columns(lo, hi)


@pytest.mark.parametrize("lo_s, seconds, within_ms", [
    (0, 5, 2000),        # the table fills
    (0, 70, 60000),      # ... at the source's 1 min, past its first expiry
    (13, 9, 3000),       # the steady state, across the pool's end (8 s)
])
def test_reference_against_the_loop(lo_s, seconds, within_ms):
    ref, cfg, _ = stretch(3, 0, SECOND)
    sizes = {**cfg["sizes"], "within_ms": within_ms}
    _, _, (ts0, cols0) = stretch(3, 0, lo_s * SECOND)
    _, _, (ts, cols) = stretch(3, lo_s * SECOND, (lo_s + seconds) * SECOND)
    replay = ref.Replay(sizes)
    held = None
    if lo_s:
        replay.feed("Plug", ts0, cols0, False)
        _, held = loop(ts0, cols0, sizes)
    n, lanes = replay.feed("Plug", ts, cols, True)
    want, _ = loop(ts, cols, sizes, held)
    assert n == len(want) > 100
    got = as_tuples(lanes)
    assert got == [(t, a, b, c, s1, float(v1), s2, float(v2))
                   for t, a, b, c, s1, v1, s2, v2 in want]
    # one row's matches in the order their first events arrived
    same = np.diff(lanes["ts2"]) == 0
    assert same.any() and (np.diff(lanes["ts1"])[same & (
        np.diff(lanes["plug_id"]) == 0) & (np.diff(lanes["house_id"]) == 0)
        & (np.diff(lanes["household_id"]) == 0)] > 0).all()


def test_reference_in_steps_is_the_reference_in_one():
    """Calls of any length state what one call states: of 2,097,152-row
    sends, of single micro-batches, cut inside a second."""
    ref, cfg, (ts, cols) = stretch(5, 0, 30 * SECOND)
    sizes = {**cfg["sizes"], "within_ms": 5000}
    whole = ref.Replay(sizes).feed("Plug", ts, cols, True)
    for step in (7 * SECOND + 13, 32768, 999):
        replay, n, parts = ref.Replay(sizes), 0, []
        for lo in range(0, len(ts), step):
            m, lanes = replay.feed(
                "Plug", ts[lo:lo + step],
                {k: v[lo:lo + step] for k, v in cols.items()}, True)
            n += m
            parts.append(lanes)
            assert replay.feed("Plug", ts[:0], {k: v[:0] for k, v in cols.items()},
                               False) == (0, None)
        assert n == whole[0]
        for name in LANES:
            assert (np.concatenate([p[name] for p in parts])
                    == whole[1][name]).all(), (step, name)


def test_the_numbers_the_configuration_states():
    """At most 2,125 x 61 = 129,625 pending matches and about 101,600 on
    average; 0.195 emissions per stream row; one row completes 60 matches
    at most; 1.7 % of the load rows emit, 23 matches each on average."""
    gen, ref, _, cfg = load()
    sizes = cfg["sizes"]
    assert sizes["pending_most"] == sizes["plugs"] * 61 == 129625
    assert sizes["tokens"] % sizes["batch"] == 0
    assert (sizes["tokens"] - sizes["batch"]
            < sizes["pending_most"] + sizes["batch"] // 2 <= sizes["tokens"])
    assert sizes["fill_rows"] == 61 * SECOND
    assert gen.CYCLE_ROWS == SECOND and gen.N_PLUGS == sizes["plugs"]
    stream = harness.Stream(gen, ref, 9, 128 * sizes["batch"])
    replay = ref.Replay(sizes)
    held, owed, rows = [], 0, 0
    per_call = 10 * SECOND
    for lo in range(0, 180 * SECOND, per_call):
        ts, cols = stream.columns(lo, lo + per_call)
        n, lanes = replay.feed("Plug", ts, cols, lo >= 90 * SECOND)
        if lo >= 90 * SECOND:   # the steady state
            held.append(len(replay.held["t"]))
            owed, rows = owed + n, rows + per_call
            emitting = np.unique(np.stack(
                [lanes[k] for k in ("house_id", "household_id", "plug_id", "ts2")]),
                axis=1).shape[1]
            assert 0.012 < emitting / (per_call / 2) < 0.022
            assert 18 < n / emitting < 28
    # the bound holds whatever the loads are (no match ever completes); under
    # the generator's law four in ten complete and the most ever held stays
    # near the mean
    assert sizes["tokens_live_mean"] < replay.max_held <= sizes["pending_most"]
    assert replay.max_held < 1.03 * sizes["tokens_live_mean"]
    # what is held between calls has had its matches of the call taken out
    assert abs(np.mean(held) / sizes["tokens_live_mean"] - 1) < 0.03
    assert abs(owed / rows - 0.195) < 0.01
    assert 45 <= replay.max_per_row <= 60


def test_control_in_bfloat16_fails_by_the_loads_alone():
    """Fed the same calls, the control owes other matches (a rounded load
    passes or misses the threshold) and, where it owes the same ones, states
    other loads and nothing else: keys and times are integers."""
    ref, cfg, _ = stretch(11, 0, SECOND)
    sizes = cfg["sizes"]
    sound, control = ref.Replay(sizes), ref.Replay(sizes, control=True)
    same_pairs = other_counts = 0
    for lo in range(0, 40 * SECOND, SECOND // 2):
        _, _, (ts, cols) = stretch(11, lo, lo + SECOND // 2, pool_seconds=16)
        n, want = sound.feed("Plug", ts, cols, True)
        m, got = control.feed("Plug", ts, cols, True)
        exact = [k for k in LANES if k not in ("load1", "load2")]
        if n != m or any((got[k] != want[k]).any() for k in exact):
            other_counts += 1
            continue
        if n:
            same_pairs += 1
            gaps = {k: harness.lane_gap(got[k], want[k], cfg["compare"][k])
                    for k in LANES}
            assert gaps["load1"] > 0 and gaps["load2"] > 0
            assert all(gaps[k] == 0 for k in exact)
    assert same_pairs > 5 and other_counts > 0


def test_cost_counts_each_byte_once():
    _, _, cost, cfg = load()
    sizes = cfg["sizes"]
    rows, share = sizes["batch"], 0.195
    match = cost.match_bytes_per_microbatch(sizes, share)
    assert match == (sizes["tokens_live_mean"] * 24 + rows / 2 * 24
                     + rows * share * 44 + rows / 2 * 32)
    assert 3.0e6 < match < 4.0e6
    whole = cost.bytes_per_microbatch(sizes, 11.0, share)
    assert whole == rows * 11.0 + match + rows * share * 44
    # nothing of it follows the table's capacity
    assert cost.match_bytes_per_microbatch(
        {**sizes, "tokens": 4 * sizes["tokens"]}, share) == match
