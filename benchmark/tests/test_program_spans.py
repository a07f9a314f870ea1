"""The reduction of the engine's spans and scopes (`program_spans.py`) on a
hand-made trace whose every number is known, and on the trace recorded on the
chip before the engine had either (PR 23): there every reader finds nothing
and returns None, as it has to on a parent commit."""

import gzip
import json

import numpy as np
import pytest

import harness
import program_spans as ps_mod
import trace_reduce as tr
from conftest import BENCH

US = 1000  # ns
BASE_NS = 1_000_000


# ---- a tiny XSpace writer (xplane.proto's field numbers) --------------------

def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def fld(no: int, value) -> bytes:
    if isinstance(value, int):
        return varint(no << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(no << 3 | 2) + varint(len(value)) + value


class Plane:
    def __init__(self, name):
        self.name, self.lines, self.events, self.stats = name, [], {}, {}

    def stat_id(self, name):
        return self.stats.setdefault(name, len(self.stats) + 1)

    def stat(self, name, value):
        return fld(1, self.stat_id(name)) + fld(
            4 if isinstance(value, int) else 5, value)

    def event_id(self, name, display="", **md_stats):
        if name not in self.events:
            body = fld(1, len(self.events) + 1) + fld(2, name)
            if display:
                body += fld(4, display)
            for k, v in md_stats.items():
                body += fld(5, self.stat(k, v))
            self.events[name] = (len(self.events) + 1, body)
        return self.events[name][0]

    def line(self, name, events):
        """events: (metadata id, start us, end us, {stat: value})"""
        body = fld(1, len(self.lines) + 1) + fld(2, name) + fld(3, BASE_NS)
        for md, t0, t1, stats in events:
            ev = fld(1, md) + fld(2, t0 * US * 1000) + fld(3, (t1 - t0) * US * 1000)
            for k, v in stats.items():
                ev += fld(4, self.stat(k, v))
            body += fld(4, ev)
        self.lines.append(body)

    def encode(self) -> bytes:
        body = fld(1, 1) + fld(2, self.name)
        for ln in self.lines:
            body += fld(3, ln)
        for key, md in self.events.values():
            body += fld(4, fld(1, key) + fld(2, md))
        for name, key in self.stats.items():
            body += fld(5, fld(1, key) + fld(2, fld(1, key) + fld(2, name)))
        return body


PROGRAM_ID = 77
OPS = [  # name, start us, end us, tf_op
    ("copy.1", 400, 410, ""),
    ("while.10", 410, 1390, "jit(fused)/while"),
    ("fusion.1", 420, 700,
     "jit(fused)/while/body/q.q/window.length/ring_update/add"),
    ("fusion.2", 700, 1100, "jit(fused)/while/body/q.q/selector/mul"),
    ("fusion.3", 1100, 1380, "jit(fused)/while/body/wire_decode/convert"),
    ("fusion.9", 1390, 1400, "jit(fused)/deliver_pack/concatenate"),
]


@pytest.fixture(scope="module")
def made(tmp_path_factory):
    """A cell whose `bench_out/<cell>/trace` holds the hand-made trace: one
    chunk; the device runs `jit_fused` from 400 to 1400 us of a 2000 us
    window, so it idles before (400 us) and after (600 us)."""
    dev = Plane("/device:TPU:0")
    dev.line("XLA Modules", [
        (dev.event_id(f"jit_fused({PROGRAM_ID})"), 400, 1400, {})])
    dev.line("XLA Ops", [
        (dev.event_id(f"%{n} = f32[8]{{0}} fusion(f32[8]{{0}} %p), kind=kLoop",
                      display=n, program_id=PROGRAM_ID,
                      **({"tf_op": op} if op else {})), t0, t1, {})
        for n, t0, t1, op in OPS])
    host = Plane("/host:CPU")
    ev = host.event_id
    host.line("python3", [
        (ev("bench:window"), 0, 2000, {}),
        (ev("siddhi:send"), 100, 1900, {"send": 1}),
        (ev("siddhi:encode"), 110, 200, {"send": 1, "chunk": 5}),
        (ev("siddhi:dispatch"), 210, 260, {"send": 1, "chunk": 5}),
        (ev("siddhi:barrier"), 1500, 1890, {"send": 1}),
    ])
    host.line("python3", [
        (ev("siddhi:drain"), 300, 1800, {"send": 1, "chunk": 5, "queued_us": 40}),
        (ev("siddhi:readback_wait"), 310, 1000, {"send": 1, "chunk": 5}),
        (ev("siddhi:decode"), 1000, 1400, {"send": 1, "chunk": 5}),
        (ev("siddhi:callback"), 1400, 1550, {"send": 1, "chunk": 5, "batch": 0}),
        (ev("siddhi:gc"), 1450, 1500, {"generation": 0}),
        (ev("siddhi:callback"), 1550, 1700, {"send": 1, "chunk": 5, "batch": 1}),
        (ev("siddhi:release"), 1700, 1790, {"send": 1, "chunk": 5}),
    ])
    root = tmp_path_factory.mktemp("made")
    out = root / "bench_out" / "made.cell" / "trace" / "plugins" / "profile" / "t"
    out.mkdir(parents=True)
    (out / "t.xplane.pb").write_bytes(
        fld(1, dev.encode()) + fld(1, host.encode()))
    cell = {"name": "made.cell", "bench_dir": root / "benchmark",
            "config": {"stream": "S"}}
    trace = tr.load(str(out / "t.xplane.pb"))
    return cell, trace, ps_mod.of(cell, trace)


def test_device_time_is_exclusive_and_sums_to_the_program(made):
    _cell, trace, ps = made
    own = ps_mod.exclusive_ns(trace.devices[0].ops) / US
    # the while keeps only what its three children leave: 980 - 960
    assert dict(zip(trace.devices[0].op_names, own.tolist())) == {
        "copy.1": 10, "while.10": 20, "fusion.1": 280, "fusion.2": 400,
        "fusion.3": 280, "fusion.9": 10}
    table = ps.device_ms_by_scope("jit_fused")
    assert table == pytest.approx({
        ps_mod.UNSCOPED: 0.030, "q.q/window.length/ring_update": 0.280,
        "q.q/selector": 0.400, "wire_decode": 0.280, "deliver_pack": 0.010})
    (ex,) = tr.executions(trace, "jit_fused")
    assert sum(table.values()) * 1e6 == pytest.approx(ex[1] - ex[0], rel=0.01)
    # against op_totals, which counts the while and its children both
    assert sum(v for k, v in tr.op_totals(trace).items()
               if not k.startswith("program:")) * 1e3 == pytest.approx(1.96)
    assert ps.scope_ms("jit_fused", 2, "window.") == pytest.approx(0.140)
    assert ps.scope_ms("jit_fused", 1, "deliver_mask", "deliver_pack") == (
        pytest.approx(0.010))
    assert ps.scope_ms("jit_fused", 1, ps_mod.UNSCOPED) == pytest.approx(0.030)
    assert ps.device_ms_by_scope("jit_no_such_program") is None


def test_idle_gaps_go_to_the_innermost_open_span(made):
    _cell, _trace, ps = made
    by = ps.idle_by_span()
    us = {role: {k: round(v * 1e6, 3) for k, v in t.items()}
          for role, t in by.items()}
    assert us["sender"] == {
        "siddhi:send": 10 + 10 + 140 + 100 + 10, "siddhi:encode": 90,
        "siddhi:dispatch": 50, "siddhi:barrier": 390,
        ps_mod.NO_SPAN: 100 + 100}
    assert us["drain"] == {
        "siddhi:drain": 10 + 10, "siddhi:readback_wait": 90,
        "siddhi:release": 90, "siddhi:callback": 250, "siddhi:gc": 50,
        ps_mod.NO_SPAN: 300 + 200}
    for table in us.values():
        assert sum(table.values()) == pytest.approx(1000)
    assert ps_mod.innermost([
        {"name": "a", "t0": 0, "t1": 10}, {"name": "b", "t0": 2, "t1": 5},
        {"name": "c", "t0": 3, "t1": 4}, {"name": "d", "t0": 12, "t1": 13},
    ]) == [(0, 2, "a"), (2, 3, "b"), (3, 4, "c"), (4, 5, "b"), (5, 10, "a"),
           (12, 13, "d")]


def test_stage_times_and_a_missing_stage_reads_none(made):
    cell, trace, ps = made
    assert ps.chunks() == 1
    assert ps.by_chunk() == {5: pytest.approx({
        "encode": 0.09, "dispatch": 0.05, "drain": 1.5, "queued": 0.04,
        "readback_wait": 0.69, "decode": 0.4, "callback": 0.3,
        "release": 0.09})}
    assert ps.self_ms("send") == [pytest.approx(1.8 - 0.09 - 0.05 - 0.39)]
    assert ps.total_ms("h2d") is None and ps.per_chunk_ms("h2d") is None
    spans = {"sends": np.array([[0.0, 1.0, 0, 8]])}
    counters = {"status": {
        "streams": {"S": {"pipeline": {"chunk_batches": 2}}},
        "compile_events": {"recent": [
            {"t": -1.0, "name": "jit(early)", "seconds": 0.1},
            {"t": 0.5, "name": "jit(concatenate)", "seconds": 0.1}]}}}
    want = {
        "encode_ms.bulk": 0.09, "dispatch_ms.filter": 0.05,
        "sender_blocked_ms.bulk": 0.39, "drain_queue_ms.bulk": 0.04,
        "readback_wait_ms.filter": 0.69, "decode_ms.bulk": 0.4,
        "callback_ms.bulk": 0.3, "release_ms.filter": 0.09,
        "gc_ms_per_s.bulk": 0.05 / 0.002,
        "compile_events.trickle": 1.0, "window_device_ms.bulk": 0.140,
        "group_device_ms.bulk": 0.200, "pack_device_ms.bulk": 0.005,
        "decode_device_ms.bulk": 0.140, "unscoped_device_ms.bulk": 0.015,
        "host_other_ms.trickle": 1.27,
        # no `siddhi:readback` in this trace: not traced, so None and not 0
        "readback_ms.trickle": None,
    }
    for metric, value in want.items():
        reader = harness.load_module(harness.reader_file(BENCH, metric))
        got = reader.read(trace, spans, counters, cell)
        assert got == (None if value is None else pytest.approx(value)), metric


def test_a_trace_without_spans_or_scopes_reads_none_everywhere(tmp_path):
    """The chip trace of PR 23 stands for any parent commit: the new readers
    find nothing to read there, return None and do not raise."""
    out = tmp_path / "bench_out" / "old.cell" / "trace" / "plugins" / "profile" / "t"
    out.mkdir(parents=True)
    packed = BENCH / "tests" / "data" / "trickle_0p3s.xplane.pb.gz"
    (out / "t.xplane.pb").write_bytes(gzip.decompress(packed.read_bytes()))
    trace = tr.load(str(out / "t.xplane.pb"))
    cell = {"name": "old.cell", "bench_dir": tmp_path / "benchmark",
            "config": {"stream": "S", "query": "q"}, "sizes": {},
            "traffic": {}, "config_dir": tmp_path / "no-such-configuration"}
    ps = ps_mod.of(cell, trace)
    assert ps.threads == [] and ps.idle_by_span() == {}
    # the scopes' road is there all the same: operations with their `tf_op`
    assert ps.scopes[("jit__step_impl", "maximum_select_fusion.5")] == (
        "jit(_step_impl)/jit(_where)/select_n:")
    assert ps.device_ms_by_scope("jit__step_impl") is None
    spans = {"sends": np.zeros((12, 4))}
    counters = {"status": {"streams": {"S": {}}}}
    manifest = json.loads((BENCH.parent / "BENCHMARK.json").read_text())

    def reads_spans(metric):
        file = harness.reader_file(BENCH, metric)
        return file.exists() and ("program_spans" in file.read_text()
                                  or file.stem == "compile_events")

    new = [m["name"] for m in manifest["per_layer"] if reads_spans(m["name"])]
    # every reader file that reads spans, scopes or the compile ring is some
    # entry's: the manifest decides how many entries share one
    files = {p.name for p in (BENCH / "layer_metrics").glob("*.py")
             if reads_spans(p.stem)}
    assert {harness.reader_file(BENCH, m).name for m in new} == files
    assert len(new) >= len(files) >= 20
    for metric in new:
        reader = harness.load_module(harness.reader_file(BENCH, metric))
        assert reader.read(trace, spans, counters, cell) is None, metric
    # and with no device traced at all (a rehearsal on the CPU)
    assert ps_mod.of({**cell, "name": "none"}, None) is None
