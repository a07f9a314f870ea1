"""The harness's own arithmetic on synthetic logs: the seeded stream and its
kept-row index, percentiles with their sample count, the callback
recorder's samples and the moments they are taken at, order faults, and the
peaks table."""

import numpy as np
import pytest

import harness
import readers
from conftest import BENCH

small = harness.load_module(BENCH / "drivers" / "closed_small.py")


class EveryOther:
    """gen + reference of a toy stream: odd rows are kept."""

    @staticmethod
    def make(seed, n):
        return {"x": np.arange(n, dtype=np.int64)}

    @staticmethod
    def timestamps(lo, hi):
        return np.arange(lo, hi, dtype=np.int64)

    @staticmethod
    def kept(cols):
        return cols["x"] % 2 == 1


def toy_stream(pool=10):
    return harness.Stream(EveryOther, EveryOther, 0, pool)


def test_stream_cycles_and_kept_index():
    s = toy_stream(10)
    ts, cols = s.columns(8, 13)          # wraps round the pool
    assert ts.tolist() == [8, 9, 10, 11, 12]
    assert cols["x"].tolist() == [8, 9, 0, 1, 2]
    assert s.kept_per_cycle == 5
    assert s.kept_before(np.array([0, 1, 2, 10, 13, 25])).tolist() == [0, 0, 1, 5, 6, 12]
    for d in range(23):                   # emission d comes from row 2d+1
        assert s.raw_of_kept(d) == 2 * d + 1
        assert s.kept_before(s.raw_of_kept(d)) == d


def test_send_percentiles_and_sample_count():
    sends = [(0.0, 0.010, 0, 4, True), (1.0, 1.030, 4, 8, True),
             (2.0, 2.020, 8, 12, True), (3.0, 3.5, 12, 16, False)]
    win = {"sends": sends}
    assert small.end_to_end(None, {}, win)["send_p50_ms"] == pytest.approx(20.0)
    text = small.describe(None, {}, win)
    assert text.startswith("closed_small: 4 sends")
    assert small.end_to_end(None, {}, {"sends": []}) == {}


def test_recorder_keeps_the_armed_samples_only(monkeypatch):
    rec = harness.Recorder(3)
    clock = iter(np.arange(0.0, 100.0, 1.0))
    monkeypatch.setattr(harness.time, "perf_counter", lambda: next(clock))
    rec.arm([(4.5, 0), (9.5, 1)])
    for j in range(14):
        rec(0, [(j, ("a",))] * 2, None)
    assert rec.delivered == 28 and rec.n == [2] * 14
    # armed at 4.5: callback 5 trips it and 6, 7, 8 are kept; armed at 9.5
    # with one callback to let pass: 10 trips, 11 passes, 12 and 13 are left
    assert [ins[0][0] for _, ins in rec.samples] == [6, 7, 8, 12, 13]
    assert [d for d, _ in rec.samples] == [12, 14, 16, 24, 26]
    rec(0, None, [(1, ("x",))])
    assert rec.expired_seen == 1


def test_sample_moments_cover_the_window_and_follow_the_seed():
    m = harness.sample_moments(2**31 + 5, 50.0, 40, 63)
    assert m == harness.sample_moments(2**31 + 5, 50.0, 40, 63)
    assert m != harness.sample_moments(6, 50.0, 40, 63)
    # one moment in each fortieth of the window, any place in a burst
    assert [int(t // 1.25) for t, _ in m] == list(range(40))
    assert {skip for _, skip in m} <= set(range(64))
    assert len({skip for _, skip in m}) > 20
    assert all(skip == 0 for _, skip in harness.sample_moments(5, 9.0, 9))


def test_stream_replays_whole_cycles_of_a_schedule():
    class Scheduled(EveryOther):
        CYCLE_ROWS = 6

    s = harness.Stream(Scheduled, Scheduled, 0, 10)
    assert s.n == 12
    ts, cols = s.columns(10, 40)  # more than two wraps, pieced together
    assert cols["x"].tolist() == [r % 12 for r in range(10, 40)]
    ts, cols = s.kept_columns(3, 9)  # emissions 3..8 come from rows 7..17
    assert ts.tolist() == [7, 9, 11, 13, 15, 17]
    assert len(s.kept_columns(4, 4)[0]) == 0


def test_order_faults():
    rec = harness.Recorder(2)
    rec.first, rec.last = [0, 5, 4, 9], [5, 8, 9, 8]
    # callback 2 starts before callback 1 ended; callback 3 runs backwards
    assert harness.order_faults(rec, 0, 4) == 2
    assert harness.order_faults(rec, 0, 2) == 0


def test_emission_of_sends_and_deliver_matching():
    stream = toy_stream(40)
    spans = {
        "stream": stream,
        "sends": np.array([[0.0, 0.1, 0, 10], [0.2, 0.3, 10, 20]]),
        # 5 kept rows per send, delivered 3 + 2, then 5
        "callbacks": np.array([[0.05, 3, 0], [0.08, 2, 3], [0.29, 5, 5]]),
    }
    assert readers.emission_of_sends(spans).tolist() == [0.08, 0.29]


def test_unknown_device_kind_is_an_error():
    assert readers.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no default"):
        readers.peaks("TPU v9 imaginary")


def test_bytes_per_microbatch_is_a_few_megabytes():
    cost = harness.load_module(BENCH / "configs" / "debs14-q1-plug" / "cost.py")
    sizes = {"batch": 32768, "plugs": 2125}
    need = cost.bytes_per_microbatch(sizes, 29.0, 0.5)
    assert 1e6 < need < 4e6
    assert need > cost.bytes_per_microbatch(sizes, 29.0, 0.25)
