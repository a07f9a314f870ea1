"""The plain reference of `debs14-q1-time` (the smart-plug average over the
last 7,200 s of the stream's own time) against hand-made inputs and against
the definition worked out row by row; its generator; and the controls: the
reference with its per-plug sum in bfloat16, and the window counted in rows
instead of time, both have to come out as not correct under the
configuration's own limits."""

import json

import numpy as np
import pytest

import harness
from conftest import BENCH

CONFIG = "debs14-q1-time"


def load(config=CONFIG):
    cdir = BENCH / "configs" / config
    return (harness.load_module(cdir / "gen.py"),
            harness.load_module(cdir / "reference.py"),
            json.loads((cdir / "config.json").read_text()))


def plug_rows(plug, value, load_record, seconds):
    """Hand-made records: plug p lies in house p, household 0, plug_id p."""
    plug = np.array(plug, dtype=np.int32)
    n = len(plug)
    return {
        "id": np.arange(n, dtype=np.int64),
        "ts": np.array(seconds, dtype=np.int64),
        "value": np.array(value, dtype=np.float32),
        "property": np.array(load_record, dtype=bool),
        "plug_id": plug, "household_id": np.zeros(n, np.int32),
        "house_id": plug,
    }


def test_time_reference_by_hand():
    _, ref, _ = load()
    # a window of 3 s; row 2 is a work record and emits nothing; second 2 is
    # missing for plug 0, and plug 1 is silent from second 1 to second 5
    cols = plug_rows([0, 1, 0, 0, 0, 0, 1], [10, 20, 99, 30, 40, 50, 60],
                     [1, 1, 0, 1, 1, 1, 1], [0, 0, 1, 1, 3, 4, 5])
    ts = cols["ts"] * 1000
    out = ref.reference(ts, cols, {"window_s": 3, "houses": 2})
    assert out["event_time"].tolist() == [0, 0, 1000, 3000, 4000, 5000]
    assert out["plug_id"].tolist() == [0, 1, 0, 0, 0, 1]
    # at second 3 the records of second 0 are 3 s old and leave: plug 0 holds
    # 30, 40; at second 4 the record of second 1 leaves: 40, 50; at second 5
    # plug 1's only record is its new one
    assert out["avgLoad"].tolist() == [10.0, 20.0, 20.0, 35.0, 45.0, 60.0]
    assert ref.kept(cols).tolist() == [True, True, False, True, True, True, True]


def whole_stream(seed: int, n: int, sizes: dict):
    gen, ref, _ = load()
    cols = gen.make(seed, n)
    ts = gen.timestamps(0, n)
    return gen, ref, ts, gen.with_index(cols, 0, n, ts)


def test_time_reference_in_steps_equals_the_whole_and_the_definition():
    """Carried along in steps of any length, emitting or not, the running
    reference gives what one pass over the whole stream gives, and what the
    definition gives row by row: the mean of the plug's load records whose
    second is less than `window_s` behind the arrival's."""
    _, _, cfg = load()
    sizes = {**cfg["sizes"], "window_s": 3}
    gen, ref, ts, cols = whole_stream(11, 12 * 4250, sizes)
    whole = ref.reference(ts, cols, sizes)
    keep = ref.kept(cols)
    kts, kcols = ts[keep], {k: v[keep] for k, v in cols.items()}
    run, at, got = ref.Running(sizes), 0, []
    for step, emit in [(700, False), (5000, True), (1, True), (3333, False),
                       (len(kts), True)]:
        upto = min(at + step, len(kts))
        out = run.step(kts[at:upto], {k: v[at:upto] for k, v in kcols.items()},
                       None, emit)
        if emit:
            got.append((at, out))
        at = upto
    for at, out in got:
        for lane, values in out.items():
            want = whole[lane][at:at + len(values)]
            assert np.allclose(values, want, rtol=1e-12, atol=0), lane
    code, sec = ref.plug_code(kcols), kcols["ts"]
    for i in (0, 5, 2500, 7000, 15000, len(kts) - 1):
        mine = (code[:i + 1] == code[i]) & (sec[:i + 1] > sec[i] - 3)
        assert whole["avgLoad"][i] == pytest.approx(
            kcols["value"][:i + 1][mine].astype(np.float64).mean(), rel=1e-12)


def test_generator_has_the_source_schedule_with_readings_missing():
    gen, ref, cfg = load()
    n = 40 * 4250
    cols = gen.with_index(gen.make(5, n), 0, n, gen.timestamps(0, n))
    code = ref.plug_code(cols)
    second = cols["ts"] - cols["ts"][0]
    assert (np.diff(second) >= 0).all() and second[0] == 0
    shares = []
    for s in range(int(second[-1])):    # the last second is cut to fit
        here = code[(second == s) & cols["property"]]
        assert len(here) == len(set(here.tolist())) <= cfg["sizes"]["plugs"]
        shares.append(1 - len(here) / cfg["sizes"]["plugs"])
    assert abs(np.mean(shares) - cfg["sizes"]["missing_share"]) < 0.005
    assert min(shares) > 0      # no second is whole: time and rows differ
    assert cols["property"][:4].tolist() == [False, True, False, True]
    assert (code[0::2] == code[1::2]).all()       # work, then load, per plug
    assert (second[0::2] == second[1::2]).all()
    assert len(set(cols["house_id"].tolist())) == cfg["sizes"]["houses"]
    assert cfg["sizes"]["window_rows"] == (
        cfg["sizes"]["plugs"] * cfg["sizes"]["window_s"])
    # the pool wraps on a whole second: time never runs backwards, and a
    # stretch is the same whichever way it is asked for
    ts = gen.timestamps(n - 100, n + 100)
    assert (np.diff(ts) >= 0).all() and ts[100] - ts[99] == 1000
    assert np.array_equal(gen.timestamps(n - 50, n + 20), ts[50:120])
    with pytest.raises(ValueError, match="whole readings"):
        gen.make(5, 1001)


def test_generator_repeats_and_differs_by_seed():
    gen, _, _ = load()
    a, b, c = (gen.make(seed, 8500) for seed in (2**31 + 5, 2**31 + 5, 6))
    for k in a:
        assert np.array_equal(a[k], b[k])
    assert any(not np.array_equal(a[k], c[k]) for k in a)


@pytest.mark.parametrize("control", ["bfloat16_sum", "window_in_rows"])
def test_control_fails_the_limits(control):
    """`bfloat16_sum`: the reference in the next lower precision.
    `window_in_rows`: `debs14-q1-plug`'s reference, a window of the same
    length counted in load records, on this stream: with readings missing
    its rows are not the last `window_s` seconds'."""
    _, _, cfg = load()
    sizes = {**cfg["sizes"], "window_s": 400}
    gen, ref, ts, cols = whole_stream(2_900_000_001, 1000 * 4250, sizes)
    want = ref.reference(ts, cols, sizes)
    if control == "bfloat16_sum":
        broken = ref.reference(ts, cols, sizes, control=True)
    else:
        _, rows_ref, _ = load("debs14-q1-plug")
        broken = rows_ref.reference(
            ts, cols, {**sizes, "window_rows": sizes["plugs"] * sizes["window_s"]})
    tail = slice(len(want["avgLoad"]) // 2, None)      # the window is full
    for name, rule in cfg["compare"].items():
        assert harness.lane_gap(want[name], want[name], rule) <= rule["limit"]
        gap = harness.lane_gap(broken[name][tail], want[name][tail], rule)
        if name == "avgLoad":
            assert gap > 10 * rule["limit"], (name, gap)
        else:
            assert gap <= rule["limit"]
