"""The per-batch path's delivery to query callbacks (`route_output`): rows
are decoded straight to `Event`s (`StreamSchema.events_from_batch`, the
native builder where it is loaded) and handed to the user's callbacks, as
the fused drain hands them over. Held against the triple path it stands in
for (`from_batch` + the `add_callback` wrapper), which every query whose
callbacks did not all come through `add_callback` still takes."""

from __future__ import annotations

import gc

import numpy as np
import pytest

import siddhi_tpu.native as native
from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.event import Event

B = 32
QUERIES = {
    "current": "from S[price > 10] select symbol, price, volume, up insert into Out;",
    "all": "from S#window.length(5) select symbol, price, volume, up "
           "insert all events into Out;",
    "expired": "from S#window.length(5) select symbol, price, volume, up "
               "insert expired events into Out;",
    "sum": "from S#window.length(7) select symbol, sum(price) as total, "
           "count() as n group by symbol insert into Out;",
    "partition": "partition with (symbol of S) begin "
                 "from S#window.length(3) select symbol, avg(price) as mean "
                 "insert into Out; end;",
}


def _run(query: str, how: str, rows: int = 5 * B + 3):
    """Callback calls of `rows` rows sent through the per-batch path.
    `how`: 'events' (the path under test), 'triples' (the wrapper alone, as
    before), 'python' (no native builder loaded)."""
    body = QUERIES[query]
    if not body.startswith("partition"):
        body = "@info(name='q') " + body
    else:
        body = body.replace("from S#", "@info(name='q') from S#")
    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime(
        f"@app:batch(size='{B}')\n"
        "define stream S (symbol string, price float, volume long, up bool);\n"
        + body
    )
    calls = []
    rt.add_callback("q", lambda ts, ins, rem: calls.append((ts, ins, rem)))
    qr = rt.queries["q"]
    if how == "triples":
        qr.raw_query_callbacks = []
    rt.start()
    for j in rt.junctions.values():
        j.fused_ingest = None
    ids = np.array([mgr.interner.intern(s) for s in "ABCD"] + [0], np.int32)
    rng = np.random.default_rng(7)
    price = rng.uniform(0, 100, rows).astype(np.float32)
    price[::11] = np.nan  # a null in the float lane
    rt.get_input_handler("S").send_columns(
        np.arange(rows, dtype=np.int64) + 1_700_000_000_000,
        {"symbol": rng.choice(ids if query != "partition" else ids[:4], rows),
         "price": price,
         "volume": rng.integers(1, 2**40, rows),
         "up": rng.integers(0, 2, rows) > 0},
    )
    rt.shutdown()
    mgr.shutdown()
    return calls


@pytest.fixture
def builder():
    assert native.load_event_builder() is not None, "no compiler / Python.h"


def _same(got, want):
    assert len(got) == len(want) and got
    for (ts, ins, rem), (ts_w, ins_w, rem_w) in zip(got, want):
        assert ts == ts_w and type(ts) is int
        for lst, lst_w in ((ins, ins_w), (rem, rem_w)):
            assert (lst is None) == (lst_w is None)
            if lst is None:
                continue
            assert type(lst) is list and len(lst) == len(lst_w)
            for e, w in zip(lst, lst_w):
                assert type(e) is Event and type(e.data) is tuple
                # nan != nan: nulls are None on both sides, so == is exact
                assert e == w
                assert [type(v) for v in e.data] == [type(v) for v in w.data]


@pytest.mark.parametrize("query", sorted(QUERIES))
def test_events_equal_the_triple_paths(builder, query):
    _same(_run(query, "events"), _run(query, "triples"))


@pytest.mark.parametrize("query", ["current", "all", "partition"])
def test_events_without_the_native_builder(monkeypatch, tmp_path, query):
    want = _run(query, "triples")
    monkeypatch.setattr(native, "_COMPILER", str(tmp_path / "no-such-g++"))
    monkeypatch.setattr(native, "_build_dir", lambda: str(tmp_path))
    monkeypatch.setattr(native, "_DECODE_LIB", None)
    monkeypatch.setattr(native, "_DECODE_FAILED", False)
    got = _run(query, "python")
    _same(got, want)
    assert all(gc.is_tracked(e) for _ts, ins, _rem in got for e in ins or ())


def test_kinds_go_to_their_lists(builder):
    calls = _run("all", "events")
    assert any(rem for _ts, _ins, rem in calls)
    ins = [e for _ts, i, _rem in calls for e in i or ()]
    rem = [e for _ts, _ins, r in calls for e in r or ()]
    # a length(5) window lets go of every row but its last five, in order
    assert [e.data for e in rem] == [e.data for e in ins[:len(rem)]]
    assert len(ins) - len(rem) == 5
    only = _run("expired", "events")
    assert all(i is None for _ts, i, _rem in only)
    assert [e.data for _ts, _i, r in only for e in r] == [e.data for e in rem]


def test_events_leave_the_collector(builder):
    calls = _run("current", "events")
    events = [e for _ts, ins, _rem in calls for e in ins]
    assert events and not any(gc.is_tracked(e) for e in events)


def test_a_callback_appended_by_hand_keeps_the_triple_path(builder):
    """`query_callbacks` may hold callbacks that take triples (appended
    there directly, with no user callback beside them): then every one of
    the query's callbacks is served through its wrapper, as before."""
    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime(
        f"@app:batch(size='{B}')\ndefine stream S (v long);\n"
        "@info(name='q') from S select v insert into Out;"
    )
    events, triples = [], []
    rt.add_callback("q", lambda ts, ins, rem: events.extend(ins))
    rt.queries["q"].query_callbacks.append(
        lambda ts, ins, rem: triples.extend(ins))
    rt.start()
    for j in rt.junctions.values():
        j.fused_ingest = None
    rt.get_input_handler("S").send_columns(
        np.arange(10, dtype=np.int64), {"v": np.arange(10, dtype=np.int64)})
    rt.shutdown()
    mgr.shutdown()
    assert [e.data for e in events] == [(v,) for v in range(10)]
    assert triples == [(v, 0, (v,)) for v in range(10)]
