"""The reduction from a profiler trace to busy time, program executions and
idle gaps: on a small trace recorded on the chip (0.3 s of the trickle cell,
12 sends; TPU v5 lite, PR 23) and on hand-made spans."""

import gzip

import numpy as np
import pytest

import trace_reduce as tr
from conftest import BENCH


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    packed = BENCH / "tests" / "data" / "trickle_0p3s.xplane.pb.gz"
    path.write_bytes(gzip.decompress(packed.read_bytes()))
    return tr.load(str(path))


def test_recorded_trace_reduces_to_what_the_run_printed(recorded):
    assert [d.name for d in recorded.devices] == ["/device:TPU:0"]
    assert recorded.window_s == pytest.approx(0.318833796, abs=1e-9)
    assert tr.busy_seconds(recorded) == pytest.approx(0.169270799, abs=1e-8)
    # one per-batch step, one pack and one decode per send
    for program in ("jit__step_impl", "jit_pack", "jit_decode"):
        assert len(tr.executions(recorded, program)) == 12
    ex = tr.executions(recorded, "jit__step_impl")
    assert (ex[:, 1] - ex[:, 0]).mean() / 1e6 == pytest.approx(13.61, abs=0.01)
    assert len(tr.executions(recorded, "jit_no_such_program")) == 0
    totals = tr.op_totals(recorded)
    assert totals["program:jit__step_impl"] == pytest.approx(0.163319597, abs=1e-8)
    assert max(totals, key=totals.get) == "program:jit__step_impl"
    assert all(" = " not in name for name in totals)  # names, not HLO text


def test_idle_and_busy_make_up_the_window(recorded):
    dev, w = recorded.devices[0], recorded.window_ns
    idle = tr.idle_spans(dev, w)
    idle_s = (idle[:, 1] - idle[:, 0]).sum() / 1e9
    assert idle_s + tr.busy_seconds(recorded) == pytest.approx(recorded.window_s)
    mid = (w[0] + w[1]) / 2
    by = tr.attribute_gaps(idle, {"in_send_columns": [[w[0], mid]],
                                  "in_callback": [[w[0], w[0] + 1e6]]})
    assert sum(by.values()) == pytest.approx(idle_s)
    assert by["in_callback"] <= 1e-3 and by["in_generator_wait"] == 0.0
    assert by["in_send_columns"] > 0 and by["between_sends"] > 0
    out = tr.breakdown(recorded, {})
    assert len(out["device_ops"]) == 10 and out["device_ops"][0][0].startswith("program:")
    assert out["idle_gaps"][0] == ["between_sends", pytest.approx(idle_s)]


def test_union_clip_and_gap_attribution_by_hand():
    spans = np.array([[0, 4], [2, 6], [6, 7], [10, 12], [11, 11.5]], dtype=float)
    assert tr.union(spans).tolist() == [[0, 7], [10, 12]]  # touching spans merge
    assert tr.clip(spans, 3, 10.5).tolist() == [[3, 4], [3, 6], [6, 7], [10, 10.5]]
    dev = tr.DeviceTrace("d", spans, ["a"] * 5)
    idle = tr.idle_spans(dev, (0.0, 20.0))
    assert idle.tolist() == [[7, 10], [12, 20]]
    # a callback outranks the send it runs inside; the rest is between sends
    by = tr.attribute_gaps(idle, {"in_send_columns": [[8, 14]],
                                  "in_callback": [[9, 13]],
                                  "in_generator_wait": [[18, 30]]})
    assert by == {"in_callback": pytest.approx(2e-9),
                  "in_generator_wait": pytest.approx(2e-9),
                  "in_send_columns": pytest.approx(2e-9),
                  "between_sends": pytest.approx(5e-9)}


def test_names_and_clock():
    assert tr.program_name("jit__step_impl(14725731777589718284)") == "jit__step_impl"
    assert tr.op_name("%fusion.5 = f32[65536]{0:T(1024)} fusion(f32[4096] %x)") == "fusion.5"
    t = tr.Trace(devices=[], window_ns=(1_000.0, 2_000.0))
    assert tr.clock_offset(t, 400) == 600.0
    assert tr.busy_seconds(t) == 0.0
