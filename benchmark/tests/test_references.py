"""Both plain references against hand-made inputs, and the controls: the
reference in the next lower precision (or with one guarantee broken) has to
come out as not correct under the configuration's own limits."""

import json

import numpy as np
import pytest

import harness
from conftest import BENCH


def load(config):
    cdir = BENCH / "configs" / config
    return (harness.load_module(cdir / "gen.py"),
            harness.load_module(cdir / "reference.py"),
            json.loads((cdir / "config.json").read_text()))


def plug_rows(plug, value, load):
    """Hand-made records: plug p lies in house p, household 0, plug_id p."""
    plug = np.array(plug, dtype=np.int32)
    n = len(plug)
    return {
        "id": np.arange(n, dtype=np.int64), "ts": np.arange(n, dtype=np.int64),
        "value": np.array(value, dtype=np.float32),
        "property": np.array(load, dtype=bool),
        "plug_id": plug, "household_id": np.zeros(n, np.int32),
        "house_id": plug,
    }


def test_plug_reference_by_hand():
    _, ref, _ = load("debs14-q1-plug")
    cols = plug_rows([0, 1, 0, 0, 1, 0], [10, 20, 99, 30, 40, 50],
                     [1, 1, 0, 1, 1, 1])
    ts = np.arange(6, dtype=np.int64) * 1000
    # window of 3 load records; row 2 is a work record and emits nothing
    out = ref.reference(ts, cols, {"window_rows": 3, "houses": 2})
    assert out["event_time"].tolist() == [0, 1000, 3000, 4000, 5000]
    assert out["ts"].tolist() == [0, 1, 3, 4, 5]
    assert out["plug_id"].tolist() == [0, 1, 0, 1, 0]
    assert out["house_id"].tolist() == [0, 1, 0, 1, 0]
    # kept stream: (0,10) (1,20) (0,30) (1,40) (0,50); the last row's window
    # is the last three kept rows, so plug 0 holds 30 and 50 there
    assert out["avgLoad"].tolist() == [10.0, 20.0, 20.0, 30.0, 40.0]
    assert ref.kept(cols).tolist() == [True, True, False, True, True, True]


def test_plug_reference_in_steps_equals_the_whole():
    """Carried along in steps of any length, emitting or not, the running
    reference gives what one pass over the whole stream gives, and what the
    definition gives when it is worked out row by row."""
    gen, ref, cfg = load("debs14-q1-plug")
    sizes = {**cfg["sizes"], "window_rows": 3 * gen.N_PLUGS + 17}
    n = 6 * gen.CYCLE_ROWS
    cols = gen.with_index(gen.make(11, n), 0, n, gen.timestamps(0, n))
    ts = gen.timestamps(0, n)
    whole = ref.reference(ts, cols, sizes)
    keep = ref.kept(cols)
    kts, kcols = ts[keep], {k: v[keep] for k, v in cols.items()}
    run, w, at, got = ref.Running(sizes), sizes["window_rows"], 0, []
    for step, emit in [(700, False), (5000, True), (1, True), (3333, False),
                       (len(kts), True)]:
        upto = min(at + step, len(kts))
        out = run.step(
            kts[at:upto], {k: v[at:upto] for k, v in kcols.items()},
            {k: v[max(at - w, 0):max(upto - w, 0)] for k, v in kcols.items()},
            emit)
        if emit:
            got.append((at, out))
        at = upto
    for at, out in got:
        for lane, values in out.items():
            want = whole[lane][at:at + len(values)]
            assert np.allclose(values, want, rtol=1e-12, atol=0), lane
    code = ref.plug_code(kcols)
    for i in (0, 5, w - 1, w, w + 1, len(kts) - 1):
        lo = max(i - w + 1, 0)
        mine = code[lo:i + 1] == code[i]
        assert whole["avgLoad"][i] == pytest.approx(
            kcols["value"][lo:i + 1][mine].astype(np.float64).mean(),
            rel=1e-12)


def test_plug_generator_keeps_the_source_schedule():
    gen, ref, cfg = load("debs14-q1-plug")
    n = 3 * gen.CYCLE_ROWS
    cols = gen.with_index(gen.make(5, n), 0, n, gen.timestamps(0, n))
    code = ref.plug_code(cols)
    second = cols["ts"] - cols["ts"][0]
    for s in range(3):  # every plug sends one work and one load record
        for prop in (False, True):
            here = code[(second == s) & (cols["property"] == prop)]
            assert len(here) == len(set(here.tolist())) == cfg["sizes"]["plugs"]
    assert cols["property"][:4].tolist() == [False, True, False, True]
    assert (code[0::2] == code[1::2]).all()       # work, then load, per plug
    assert len(set(cols["house_id"].tolist())) == cfg["sizes"]["houses"]
    assert cols["household_id"].max() < ref.ID_SPAN
    assert cfg["sizes"]["window_rows"] == (
        cfg["sizes"]["plugs"] * cfg["sizes"]["window_minutes"] * 60)
    with pytest.raises(ValueError, match="whole seconds"):
        gen.make(5, 1000)


def test_filter_reference_by_hand():
    gen, ref, _ = load("siddhi-simple-filter")
    cols = gen.make(7, 5)
    ts = gen.timestamps(10, 15)
    cols = gen.with_index(cols, 10, 15, ts)
    cols["price"] = cols["price"].copy()
    cols["price"][2] = 800.0  # the one row upstream's filter would drop
    out = ref.reference(ts, cols, {})
    assert out["event_time"].tolist() == [ts[0], ts[1], ts[3], ts[4]]
    assert out["timestamp"].tolist() == out["event_time"].tolist()
    assert set(out["volume"].tolist()) == {100}
    assert set(np.round(out["price"], 1).tolist()) <= {55.6, 75.6}


@pytest.mark.parametrize("config,lane", [
    ("debs14-q1-plug", "avgLoad"), ("siddhi-simple-filter", "price")])
def test_control_fails_the_limits(config, lane):
    gen, ref, cfg = load(config)
    sizes = {**cfg["sizes"], "window_rows": 8 * 2125}
    n = 12 * 4250
    ts = gen.timestamps(0, n)
    cols = gen.with_index(gen.make(2_900_000_001, n), 0, n, ts)
    want = ref.reference(ts, cols, sizes)
    control = ref.reference(ts, cols, sizes, control=True)
    for name, rule in cfg["compare"].items():
        sound = harness.lane_gap(want[name], want[name], rule)
        assert sound <= rule["limit"]
        broken = harness.lane_gap(control[name], want[name], rule)
        if name == lane:
            assert broken > 3 * max(rule["limit"], 1e-12), (name, broken)
        else:
            assert broken <= rule["limit"]


def test_generators_repeat_and_differ_by_seed():
    for config in ("debs14-q1-plug", "siddhi-simple-filter"):
        gen, _, _ = load(config)
        a, b, c = (gen.make(seed, 8500) for seed in (2**31 + 5, 2**31 + 5, 6))
        for k in a:
            assert np.array_equal(a[k], b[k])
        assert any(not np.array_equal(a[k], c[k]) for k in a)
        ts = gen.timestamps(0, 5000)
        assert (np.diff(ts) >= 0).all()
        assert np.array_equal(gen.timestamps(100, 200), ts[100:200])
