"""Event lineage & provenance (observability/lineage.py + @app:lineage).

Covers the acceptance contract of the lineage layer:

* `runtime.lineage()` returns the EXACT contributing input events
  (byte-compared against hand-computed expectations) for a sliding window
  emission, a pattern/sequence match, a join match, and a group-by
  aggregation bucket;
* identical lineage records under whole-graph fusion on/off and the
  8-device mesh on/off;
* emissions byte-identical with lineage on vs off;
* zero overhead when off (no arenas, no recorders, no `__lin.*` lanes in
  the traced step — the profiler/tracing gating contract);
* annotation validation shared between runtime (raises) and analyzer
  (SA131), arena seq addressing + eviction, multi-hop resolution through
  insert-into chains, @OnError STORE seq ranges, trace-span annotation,
  explain fan-in, sample mode, and aggregation buckets.
"""

from __future__ import annotations

import json
import time
import urllib.request

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.errors import SiddhiAppCreationError
from siddhi_tpu.core.event import StreamSchema
from siddhi_tpu.core.types import AttrType, InternTable
from siddhi_tpu.observability.lineage import (
    LineageArena,
    LineageConfig,
    iter_lineage_annotation_problems,
)
from siddhi_tpu.query_api.annotation import Annotation


def _drain():
    time.sleep(0.05)


def _mk(app_text):
    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime(app_text)
    return mgr, rt


def _inputs(chain):
    """[(stream, [(seq, event tuple or None)...])] from a resolved record."""
    out = []
    for inp in chain["inputs"]:
        out.append((
            inp["stream"],
            [
                (e["seq"], tuple(e["event"]) if e.get("event") else None)
                for e in inp.get("events", ())
            ],
        ))
    return sorted(out)


# ---------------------------------------------------------------------------
# annotation validation (SA131 <-> runtime, one rule set)
# ---------------------------------------------------------------------------


class TestAnnotation:
    def test_malformed_capacity_raises_at_creation(self):
        mgr = SiddhiManager()
        with pytest.raises(SiddhiAppCreationError, match="capacity"):
            mgr.create_siddhi_app_runtime(
                "@app:lineage(capacity='nope')\n"
                "define stream S (a int);\n"
                "from S select a insert into Out;"
            )

    def test_malformed_mode_raises_at_creation(self):
        mgr = SiddhiManager()
        with pytest.raises(SiddhiAppCreationError, match="mode"):
            mgr.create_siddhi_app_runtime(
                "@app:lineage(mode='firehose')\n"
                "define stream S (a int);\n"
                "from S select a insert into Out;"
            )

    def test_rule_set_shared_with_analyzer(self):
        ann = Annotation("app:lineage")
        ann.elements = [
            ("capacity", "0"), ("mode", "x"), ("turbo", "on"),
        ]
        assert len(list(iter_lineage_annotation_problems(ann))) == 3

    def test_sa131_from_analyzer(self):
        from siddhi_tpu.analysis import analyze
        from siddhi_tpu.compiler.siddhi_compiler import SiddhiCompiler

        app = SiddhiCompiler.parse(
            "@app:lineage(capacity='zero')\n"
            "define stream S (a int);\n"
            "from S select a insert into Out;"
        )
        res = analyze(app)
        assert any(d.code == "SA131" for d in res.diagnostics)


# ---------------------------------------------------------------------------
# zero overhead when off
# ---------------------------------------------------------------------------


class TestZeroOverheadOff:
    def test_no_recorders_no_arenas_no_lanes(self):
        mgr, rt = _mk(
            "define stream S (v long);\n"
            "@info(name='q') from S#window.length(3) "
            "select sum(v) as s insert into Out;"
        )
        rt.start()
        h = rt.get_input_handler("S")
        h.send([1], timestamp=1000)
        _drain()
        qr = rt.queries["q"]
        assert qr.lineage is None
        assert qr.chain.lineage_probe is None
        assert rt.junctions["S"].lineage is None
        assert rt.lineage_ledger is None
        # the traced step emits no __lin lanes: probe the aux structure
        # exactly like the fused engine does
        import jax

        batch = rt.stream_schemas["S"].empty_batch(rt.batch_size)
        closed = jax.eval_shape(
            lambda s, t, b: qr._step_impl(s, t, b, np.int64(0))[3],
            qr.init_state(), {}, batch,
        )
        assert not any(k.startswith("__lin") for k in closed)
        with pytest.raises(SiddhiAppCreationError, match="@app:lineage"):
            rt.lineage("q")
        assert rt.lineage_report() == {}
        mgr.shutdown()


# ---------------------------------------------------------------------------
# arena unit semantics
# ---------------------------------------------------------------------------


class TestArena:
    def _arena(self, size):
        schema = StreamSchema("S", [("k", AttrType.LONG)])
        return LineageArena(schema, InternTable(), size)

    def test_seq_addressing_and_eviction(self):
        ar = self._arena(4)
        for i in range(10):
            base, n = ar.record_columns(
                np.asarray([100 + i]), {"k": np.asarray([i])}, 1
            )
            assert (base, n) == (i, 1)
        assert ar.next_seq == 10
        evs = ar.events_for_seqs([0, 5, 6, 9, 42])
        assert evs[0] is None  # evicted (ring holds 6..9)
        assert evs[5] is None
        assert evs[6] == (106, (6,))
        assert evs[9] == (109, (9,))
        assert evs[42] is None  # never stamped
        assert ar.describe_state()["next_seq"] == 10

    def test_current_rows_only(self):
        from siddhi_tpu.core.event import KIND_EXPIRED

        schema = StreamSchema("S", [("k", AttrType.LONG)])
        ar = LineageArena(schema, InternTable(), 8)
        batch = schema.to_batch(
            [1, 2], [(7,), (8,)], InternTable(), capacity=4,
            kinds=[0, KIND_EXPIRED],
        )
        base, n = ar.record_batch(batch)
        assert (base, n) == (0, 1)  # the EXPIRED row is not stamped
        assert ar.events_for_seqs([0])[0] == (1, (7,))

    def test_oversized_commit_keeps_seq_slot_mapping(self):
        # one commit larger than the ring: _write trims to the tail and
        # the head advances by size while the seq counter advances by n —
        # decode must follow the head, not seq % size (regression)
        ar = self._arena(4)
        n = 6
        ar.record_columns(
            np.arange(n) + 100, {"k": np.arange(n)}, n
        )
        assert ar.next_seq == 6
        evs = ar.events_for_seqs([0, 1, 2, 3, 4, 5])
        assert evs[0] is None and evs[1] is None  # trimmed away
        assert evs[2] == (102, (2,))
        assert evs[3] == (103, (3,))
        assert evs[4] == (104, (4,))
        assert evs[5] == (105, (5,))

    def test_zero_current_publish_updates_last_range(self):
        # a publish with no CURRENT rows must not leave the PREVIOUS
        # batch's range for the @OnError STORE path (regression)
        from siddhi_tpu.core.event import KIND_EXPIRED

        schema = StreamSchema("S", [("k", AttrType.LONG)])
        ar = LineageArena(schema, InternTable(), 8)
        ar.record_columns(np.asarray([1]), {"k": np.asarray([7])}, 1)
        assert ar.last_range == (0, 1)
        batch = schema.to_batch(
            [2], [(8,)], InternTable(), capacity=4, kinds=[KIND_EXPIRED],
        )
        assert ar.record_batch(batch) == (1, 0)
        assert ar.last_range == (1, 0)
        assert ar.record_columns(np.asarray([]), {"k": np.asarray([])}, 0) \
            == (1, 0)


# ---------------------------------------------------------------------------
# exact provenance goldens (hand-computed)
# ---------------------------------------------------------------------------


WINDOW_APP = """
@app:name('lw')
@app:lineage(capacity='64')
define stream S (v int);
@info(name='q') from S[v > 0]#window.length(3)
select sum(v) as s insert into Out;
"""


class TestSlidingWindowGolden:
    def test_exact_window_contents_with_filter(self):
        mgr, rt = _mk(WINDOW_APP)
        got = []
        rt.add_callback("Out", lambda evs: got.extend(evs))
        rt.start()
        h = rt.get_input_handler("S")
        # seqs:        0  1   2  3  4   (seq 2 fails the filter)
        for i, v in enumerate([1, 2, -5, 3, 4]):
            h.send([v], timestamp=1000 + i)
        _drain()
        assert [(e.timestamp, e.data) for e in got] == [
            (1000, (1,)), (1001, (3,)), (1003, (6,)), (1004, (9,)),
        ]
        # emission 3 (4th CURRENT): window holds the last 3 admitted =
        # seqs 1, 3, 4 — events (2,), (3,), (4,); seq 0 was evicted and
        # seq 2 never admitted
        cur = [
            r for i in range(rt.queries["q"].lineage.out_count)
            for r in [rt.lineage("q", i)] if r["kind"] == "CURRENT"
        ]
        assert _inputs(cur[0]) == [("S", [(0, (1,))])]
        assert _inputs(cur[1]) == [("S", [(0, (1,)), (1, (2,))])]
        assert _inputs(cur[2]) == [("S", [(0, (1,)), (1, (2,)), (3, (3,))])]
        assert _inputs(cur[3]) == [("S", [(1, (2,)), (3, (3,)), (4, (4,))])]
        assert all(not r["approx"] for r in cur)
        assert cur[3]["trigger"] == {"stream": "S", "seq": 4}
        # the eviction emission (EXPIRED) recorded the post-evict window
        exp = [
            r for i in range(rt.queries["q"].lineage.out_count)
            for r in [rt.lineage("q", i)] if r["kind"] == "EXPIRED"
        ]
        assert len(exp) == 1
        mgr.shutdown()

    def test_time_window_contents(self):
        # playback clock: explicit past timestamps drive expiry, not the
        # wall-clock scheduler (which would expire the ring mid-test)
        mgr, rt = _mk(
            "@app:playback\n"
            "@app:lineage(capacity='64')\n"
            "define stream S (v int);\n"
            "@info(name='q') from S#window.time(100)\n"
            "select sum(v) as s insert into Out;"
        )
        rt.start()
        h = rt.get_input_handler("S")
        h.send([1], timestamp=1000)  # seq 0
        h.send([2], timestamp=1050)  # seq 1
        h.send([4], timestamp=1200)  # seq 2: 0 and 1 have expired
        _drain()
        recs = [
            rt.lineage("q", i)
            for i in range(rt.queries["q"].lineage.out_count)
        ]
        cur = [r for r in recs if r["kind"] == "CURRENT"]
        assert _inputs(cur[0]) == [("S", [(0, (1,))])]
        assert _inputs(cur[1]) == [("S", [(0, (1,)), (1, (2,))])]
        assert _inputs(cur[2]) == [("S", [(2, (4,))])]
        mgr.shutdown()


PATTERN_APP = """
@app:name('lp')
@app:lineage(capacity='64')
define stream A (x int);
define stream B (y int);
@info(name='pq') from every e1=A[x > 10] -> e2=B[y > e1.x] within 1 sec
select e1.x as ax, e2.y as by2 insert into M;
"""


class TestPatternGolden:
    def test_sequence_returns_exactly_the_two_contributing_events(self):
        mgr, rt = _mk(PATTERN_APP)
        got = []
        rt.add_callback("M", lambda evs: got.extend(evs))
        rt.start()
        ha, hb = rt.get_input_handler("A"), rt.get_input_handler("B")
        ha.send([5], timestamp=1000)   # A seq 0: fails the e1 filter
        ha.send([20], timestamp=1100)  # A seq 1: arms e1
        hb.send([15], timestamp=1200)  # B seq 0: fails y > 20
        hb.send([25], timestamp=1300)  # B seq 1: completes the match
        _drain()
        assert [(e.timestamp, e.data) for e in got] == [(1300, (20, 25))]
        chain = rt.lineage("pq", 0)
        assert chain["kind"] == "CURRENT" and not chain["approx"]
        assert _inputs(chain) == [
            ("A", [(1, (20,))]),
            ("B", [(1, (25,))]),
        ]
        mgr.shutdown()


JOIN_APP = """
@app:name('lj')
@app:lineage(capacity='64')
define stream L (k int, v int);
define stream R (k int, w int);
@info(name='jq') from L#window.length(4) join R#window.length(4)
on L.k == R.k select L.k as k, L.v as v, R.w as w insert into J;
"""


class TestJoinGolden:
    def test_left_right_seq_pair_per_match(self):
        mgr, rt = _mk(JOIN_APP)
        got = []
        rt.add_callback("J", lambda evs: got.extend(evs))
        rt.start()
        hl, hr = rt.get_input_handler("L"), rt.get_input_handler("R")
        hl.send([1, 100], timestamp=2000)  # L seq 0
        hl.send([2, 200], timestamp=2001)  # L seq 1
        hr.send([2, 999], timestamp=2002)  # R seq 0: matches L seq 1
        hl.send([2, 300], timestamp=2003)  # L seq 2: matches R seq 0
        _drain()
        assert [(e.timestamp, e.data) for e in got] == [
            (2002, (2, 200, 999)), (2003, (2, 300, 999)),
        ]
        c0 = rt.lineage("jq", 0)
        assert _inputs(c0) == [
            ("L", [(1, (2, 200))]),
            ("R", [(0, (2, 999))]),
        ]
        assert c0["trigger"] == {"stream": "R", "seq": 0}
        c1 = rt.lineage("jq", 1)
        assert _inputs(c1) == [
            ("L", [(2, (2, 300))]),
            ("R", [(0, (2, 999))]),
        ]
        assert c1["trigger"] == {"stream": "L", "seq": 2}
        assert not c0["approx"] and not c1["approx"]
        mgr.shutdown()

    def test_partner_without_admission_order_is_flagged(self):
        # a lengthBatch partner window carries no seq lane: the matched
        # partner cannot be resolved, and the record must say so
        # (approx=True) instead of presenting a one-sided chain as exact
        mgr, rt = _mk(
            "@app:lineage(capacity='64')\n"
            "define stream L (k int);\n"
            "define stream R (k int);\n"
            "@info(name='jq') from L#window.length(4) join "
            "R#window.lengthBatch(4)\n"
            "on L.k == R.k select L.k as k insert into J;"
        )
        rt.start()
        hl, hr = rt.get_input_handler("L"), rt.get_input_handler("R")
        hr.send([1], timestamp=5000)  # open R bucket (view shows it)
        hr.send([1], timestamp=5001)
        hl.send([1], timestamp=5010)  # probes the open R bucket
        _drain()
        lin = rt.queries["jq"].lineage
        assert lin.out_count > 0
        rec = rt.lineage("jq", 0)
        assert rec["approx"] is True
        assert rec["trigger"]["stream"] == "L"  # the probe side is exact
        mgr.shutdown()


GROUPBY_APP = """
@app:name('lg')
@app:lineage(capacity='64')
define stream S (sym string, px int);
@info(name='g') from S#window.lengthBatch(4)
select sym, sum(px) as total group by sym insert into G;
"""


class TestGroupByGolden:
    def test_per_key_bucket_members(self):
        mgr, rt = _mk(GROUPBY_APP)
        got = []
        rt.add_callback("G", lambda evs: got.extend(evs))
        rt.start()
        h = rt.get_input_handler("S")
        for i, r in enumerate([("a", 1), ("b", 2), ("a", 3), ("b", 4)]):
            h.send(list(r), timestamp=3000 + i)
        _drain()
        assert sorted(e.data for e in got) == [("a", 4), ("b", 6)]
        ra = rt.lineage("g", 0)
        rb = rt.lineage("g", 1)
        assert _inputs(ra) == [("S", [(0, ("a", 1)), (2, ("a", 3))])]
        assert _inputs(rb) == [("S", [(1, ("b", 2)), (3, ("b", 4))])]
        assert not ra["approx"] and not rb["approx"]
        mgr.shutdown()


# ---------------------------------------------------------------------------
# multi-hop + stream-indexed resolution
# ---------------------------------------------------------------------------


CHAIN_APP = """
@app:name('lc')
@app:lineage(capacity='64')
define stream S (v int);
@info(name='q1') from S[v > 0] select v * 10 as w insert into Mid;
@info(name='q2') from Mid#window.length(2) select sum(w) as t insert into Out;
"""


class TestMultiHop:
    def test_walks_back_to_ingress(self):
        mgr, rt = _mk(CHAIN_APP)
        rt.start()
        h = rt.get_input_handler("S")
        for i, v in enumerate([3, -1, 5]):  # seq 1 filtered out by q1
            h.send([v], timestamp=4000 + i)
        _drain()
        # Out seq 1 = q2's 2nd CURRENT = window {Mid seq 0, Mid seq 1}
        node = rt.lineage("Out", 1)
        assert node["stream"] == "Out" and node["event"] == [80]
        via = node["via"]
        assert via["query"] == "q2"
        (mid,) = via["inputs"]
        assert mid["stream"] == "Mid" and mid["n"] == 2
        # each Mid seq resolves further back to the exact S event
        ups = {u["out_index"]: u for u in mid["via"]}
        s_events = sorted(
            e["seq"] for u in ups.values() for e in u["inputs"][0]["events"]
        )
        assert s_events == [0, 2]  # S seq 1 (v=-1) contributed nowhere
        mgr.shutdown()

    def test_stream_index_accounts_for_expired_records(self):
        mgr, rt = _mk(WINDOW_APP)
        rt.start()
        h = rt.get_input_handler("S")
        for i, v in enumerate([1, 2, -5, 3, 4]):
            h.send([v], timestamp=1000 + i)
        _drain()
        # Out carries only the CURRENT emissions; seq 3 on Out = the 4th
        # CURRENT record even though an EXPIRED record sits between them
        node = rt.lineage("Out", 3)
        assert node["event"] == [9]
        assert node["via"]["kind"] == "CURRENT"
        assert _inputs(node["via"]) == [
            ("S", [(1, (2,)), (3, (3,)), (4, (4,))])
        ]
        mgr.shutdown()

    def test_externally_co_fed_stream_is_not_walked(self):
        # q1 inserts into Mid AND the host sends into Mid directly: the
        # junction seqs interleave both, so attributing seq k to q1's
        # k-th record would be a guess — the walk must decline (regression)
        mgr, rt = _mk(
            "@app:lineage(capacity='64')\n"
            "define stream S (v int);\n"
            "define stream Mid (w int);\n"
            "@info(name='q1') from S select v * 10 as w insert into Mid;\n"
            "@info(name='q2') from Mid select w insert into Out;"
        )
        rt.start()
        rt.get_input_handler("S").send([1], timestamp=1000)
        rt.get_input_handler("Mid").send([999], timestamp=1001)  # external
        rt.get_input_handler("S").send([2], timestamp=1002)
        _drain()
        node = rt.lineage("Mid", 1)
        assert node["event"] == [999]
        assert "via" not in node
        assert node.get("mixed") is True and node["producers"] == ["q1"]
        mgr.shutdown()


# ---------------------------------------------------------------------------
# parity: lineage on/off emissions; fused/sharded record equality
# ---------------------------------------------------------------------------


PARITY_APP = """
@app:name('par')
{LINEAGE}
define stream S (v long, k long);
@info(name='w') from S[v % 3 != 0]#window.length(5)
select sum(v) as s insert into Out;
@info(name='g') from S#window.lengthBatch(8)
select sum(v) as t group by k insert into G;
"""


def _drive_parity(head, n=256):
    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime(
        PARITY_APP.replace("{LINEAGE}", head)
    )
    got = {"w": [], "g": []}
    for qid in ("w", "g"):
        rt.add_callback(
            qid,
            lambda ts, ins, removed, _q=qid: got[_q].extend(ins or []),
        )
    rt.start()
    h = rt.get_input_handler("S")
    ts = np.arange(n, dtype=np.int64) + 10_000
    vs = (np.arange(n, dtype=np.int64) * 7) % 23
    h.send_columns(ts, {"v": vs, "k": vs % 4}, now=int(ts[-1]))
    time.sleep(0.2)
    out = {
        k: [(e.timestamp, tuple(e.data)) for e in v] for k, v in got.items()
    }
    recs = {}
    for qid in ("w", "g"):
        lin = rt.queries[qid].lineage
        if lin is None:
            continue
        recs[qid] = [
            (
                r["out_index"], r["ts"], r["kind"], r["approx"],
                tuple(
                    (i["stream"], tuple(map(tuple, i["ranges"])), i["n"])
                    for i in r["inputs"]
                ),
            )
            for i_ in range(lin.out_count)
            for r in [rt.lineage(qid, i_)]
        ]
    engaged = rt.junctions["S"].fused_ingest
    chunks = engaged.chunks_dispatched if engaged is not None else 0
    mgr.shutdown()
    return out, recs, chunks


class TestParity:
    def test_emissions_byte_identical_lineage_on_vs_off(self, monkeypatch):
        monkeypatch.delenv("SIDDHI_TPU_SHARD", raising=False)
        on, _r, _ = _drive_parity("@app:lineage(capacity='512')")
        off, _r2, _ = _drive_parity("")
        assert on == off

    def test_records_identical_fuse_on_vs_off(self, monkeypatch):
        monkeypatch.delenv("SIDDHI_TPU_SHARD", raising=False)
        monkeypatch.setenv("SIDDHI_TPU_FUSE", "1")
        out1, rec1, chunks1 = _drive_parity("@app:lineage(capacity='512')")
        monkeypatch.setenv("SIDDHI_TPU_FUSE", "0")
        out0, rec0, chunks0 = _drive_parity("@app:lineage(capacity='512')")
        assert chunks1 > 0 and chunks0 == 0  # the A/B really fused vs not
        assert out1 == out0
        assert rec1 == rec0

    def test_records_identical_shard_8_vs_0(self, monkeypatch):
        # stateless query: under the mesh its junction keeps the fused chunk
        # loop, whose chunk order is the recorder's order
        app = (
            "@app:lineage(capacity='4096')\n"
            "define stream S (v long);\n"
            "@info(name='f') from S[v % 2 == 0] select v * 10 as w "
            "insert into Out;"
        )

        def drive():
            mgr = SiddhiManager()
            rt = mgr.create_siddhi_app_runtime(app)
            got = []
            rt.add_callback(
                "f", lambda ts, ins, removed: got.extend(ins or [])
            )
            rt.start()
            h = rt.get_input_handler("S")
            n = 1024
            ts = np.arange(n, dtype=np.int64) + 50_000
            h.send_columns(
                ts, {"v": np.arange(n, dtype=np.int64)}, now=int(ts[-1])
            )
            time.sleep(0.2)
            lin = rt.queries["f"].lineage
            recs = [
                (
                    r["out_index"], r["ts"], r["approx"],
                    tuple(
                        (i["stream"], tuple(map(tuple, i["ranges"])))
                        for i in r["inputs"]
                    ),
                )
                for i_ in range(lin.out_count)
                for r in [rt.lineage("f", i_)]
            ]
            out = [(e.timestamp, tuple(e.data)) for e in got]
            mgr.shutdown()
            return out, recs

        monkeypatch.setenv("SIDDHI_TPU_SHARD", "8")
        out8, rec8 = drive()
        monkeypatch.setenv("SIDDHI_TPU_SHARD", "0")
        out0, rec0 = drive()
        assert out8 == out0
        assert rec8 == rec0


# ---------------------------------------------------------------------------
# surfaces: STORE entries, traces, explain, endpoints, sampling, aggregation
# ---------------------------------------------------------------------------


class TestSurfaces:
    def test_store_entry_carries_seq_range(self):
        mgr, rt = _mk(
            "@app:lineage(capacity='64')\n"
            "@OnError(action='STORE')\n"
            "define stream S (v int);\n"
            "@info(name='q') from S select v insert into Out;"
        )
        boom = {"armed": False}

        def cb(evs):
            if boom["armed"]:
                raise RuntimeError("poison")

        rt.add_callback("S", cb)
        rt.start()
        h = rt.get_input_handler("S")
        h.send([1], timestamp=1000)  # seq 0 (clean)
        boom["armed"] = True
        h.send([2], timestamp=1001)  # seq 1 -> fails, STORE'd
        _drain()
        entries = mgr.error_store.load()
        assert entries, "the failing batch must be stored"
        ent = entries[-1]
        assert ent.lineage == {"stream": "S", "seq_lo": 1, "seq_hi": 1}
        mgr.shutdown()

    def test_trace_span_carries_seq_range(self):
        mgr, rt = _mk(
            "@app:statistics(reporter='none', trace.sample='1.0')\n"
            "@app:lineage(capacity='64')\n"
            "define stream S (v int);\n"
            "@info(name='q') from S select v insert into Out;"
        )
        rt.start()
        h = rt.get_input_handler("S")
        h.send([1], timestamp=1000)
        h.send([2], timestamp=1001)
        _drain()
        spans = [s for t in rt.traces() for s in t["spans"]]
        stamped = [s for s in spans if "lineage_seq" in s]
        assert stamped, spans
        assert stamped[0]["lineage_seq"] == [0, 1]
        mgr.shutdown()

    def test_explain_renders_fan_in(self):
        mgr, rt = _mk(WINDOW_APP)
        rt.start()
        h = rt.get_input_handler("S")
        for i, v in enumerate([1, 2, 3, 4]):
            h.send([v], timestamp=1000 + i)
        _drain()
        text = rt.explain()
        assert "lineage[fan-in avg=" in text
        mgr.shutdown()

    def test_http_endpoints(self):
        mgr, rt = _mk(WINDOW_APP)
        rt.start()
        h = rt.get_input_handler("S")
        for i, v in enumerate([1, 2, 3]):
            h.send([v], timestamp=1000 + i)
        _drain()
        port = mgr.serve_metrics(port=0)
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/lineage.json", timeout=10
        ).read().decode()
        rep = json.loads(body)["lw"]
        assert rep["streams"]["S"]["next_seq"] == 3
        assert rep["queries"]["q"]["outputs"] >= 3
        assert rep["recent"]["q"][-1]["inputs"][0]["stream"] == "S"
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/lineage", timeout=10
        ).read().decode()
        assert "query q" in text and "fan-in" in text
        mgr.shutdown()

    def test_sample_mode_records_every_kth(self):
        mgr, rt = _mk(
            "@app:lineage(capacity='64', mode='sample', sample.every='4')\n"
            "define stream S (v int);\n"
            "@info(name='q') from S select v insert into Out;"
        )
        rt.start()
        h = rt.get_input_handler("S")
        for i in range(12):
            h.send([i], timestamp=1000 + i)
        _drain()
        lin = rt.queries["q"].lineage
        assert lin.out_count == 12  # fan-in counters always run
        assert [r["out_index"] for r in lin.records] == [0, 4, 8]
        assert rt.lineage("q", 1)["error"]  # sampled out
        mgr.shutdown()

    def test_aggregation_buckets(self):
        mgr, rt = _mk(
            "@app:lineage(capacity='64')\n"
            "define stream S (v int, ts long);\n"
            "define aggregation ag\n"
            "from S\n"
            "select sum(v) as total\n"
            "aggregate by ts every sec;"
        )
        rt.start()
        h = rt.get_input_handler("S")
        h.send([1, 1_000], timestamp=1_000)   # seq 0, bucket 1000
        h.send([2, 1_500], timestamp=1_500)   # seq 1, bucket 1000
        h.send([3, 2_200], timestamp=2_200)   # seq 2, bucket 2000
        _drain()
        rep = rt.lineage_report()
        buckets = rep["aggregations"]["ag"]["buckets"]
        assert buckets["1000"] == {"seq_lo": 0, "seq_hi": 1, "count": 2}
        assert buckets["2000"] == {"seq_lo": 2, "seq_hi": 2, "count": 1}
        mgr.shutdown()

    def test_describe_state_surfaces(self):
        mgr, rt = _mk(WINDOW_APP)
        rt.start()
        h = rt.get_input_handler("S")
        h.send([1], timestamp=1000)
        _drain()
        st = rt.snapshot_status()
        assert st["streams"]["S"]["lineage"]["next_seq"] == 1
        assert st["queries"]["q"]["lineage"]["outputs"] >= 1
        mgr.shutdown()


MULTI_PRODUCER_APP = """
@app:lineage(capacity='256')
define stream S (a int);
define stream Mid (a int, tag int);
@info(name='pA') from S[a % 2 == 0] select a, 100 as tag insert into Mid;
@info(name='pB') from S[a % 2 == 1] select a, 200 as tag insert into Mid;
@info(name='c') from Mid#window.length(4) select a, tag insert into Out;
"""


class TestMultiProducer:
    """Per-publish producer capture (LineageArena.pub_log): a stream fed by
    TWO recorded queries resolves each seq to the producer whose publish
    stamped it, instead of listing candidates (the PR 12 carried-forward)."""

    def test_seq_resolves_to_actual_producer(self):
        mgr, rt = _mk(MULTI_PRODUCER_APP)
        rt.start()
        h = rt.get_input_handler("S")
        for i in range(10):
            h.send([i], timestamp=1000 + i)
        _drain()
        arena = rt.junctions["Mid"].lineage
        assert arena.next_seq == 10
        for s in range(10):
            node = rt.lineage("Mid", s)
            a, tag = node["event"]
            want = "pA" if a % 2 == 0 else "pB"
            assert node.get("producer") == want, node
            via = node["via"]
            assert via["query"] == want
            # the producer's record walks back to the exact S event
            (inp,) = via["inputs"]
            assert inp["stream"] == "S"
            assert [e["event"] for e in inp["events"]] == [[a]]
        mgr.shutdown()

    def test_consumer_inputs_walk_through_producers(self):
        mgr, rt = _mk(MULTI_PRODUCER_APP)
        rt.start()
        h = rt.get_input_handler("S")
        for i in range(6):
            h.send([i], timestamp=1000 + i)
        _drain()
        # the window consumer's record on Mid resolves each contributing
        # seq to ITS producer (pA for evens, pB for odds)
        node = rt.lineage("c")
        (mid,) = node["inputs"]
        assert mid["stream"] == "Mid"
        ups = mid.get("via")
        assert ups, node
        for up in ups:
            a = up["inputs"][0]["events"][0]["event"][0]
            assert up["query"] == ("pA" if a % 2 == 0 else "pB"), up
        mgr.shutdown()

    def test_external_interleaved_writer_stays_mixed(self):
        # an input handler ALSO feeds Mid: unlogged seqs must not be
        # mis-attributed — they fall back to the candidate listing
        mgr, rt = _mk(MULTI_PRODUCER_APP)
        rt.start()
        h = rt.get_input_handler("S")
        hm = rt.get_input_handler("Mid")
        h.send([2], timestamp=1000)     # seq 0 <- pA
        hm.send([9, 900], timestamp=1001)  # seq 1 <- external writer
        h.send([3], timestamp=1002)     # seq 2 <- pB
        _drain()
        assert rt.lineage("Mid", 0).get("producer") == "pA"
        ext = rt.lineage("Mid", 1)
        assert "producer" not in ext and ext.get("mixed"), ext
        assert sorted(ext["producers"]) == ["pA", "pB"]
        assert rt.lineage("Mid", 2).get("producer") == "pB"
        mgr.shutdown()
