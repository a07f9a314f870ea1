"""Fused mega-batch ingest (core/ingest.py) must be observationally identical
to the per-batch path.

Each case runs the same columnar feed twice — fused (the default when a
junction's subscribers are all fusable) and per-batch (fused engine detached)
— and compares the full contents of a results table written by the query.
Query callbacks ride the fused path too (deliver mode: device-side packed
egress drained once per chunk) and must see identical events."""

from __future__ import annotations

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager


def _feed(n, seed=42):
    rng = np.random.default_rng(seed)
    return (
        np.arange(n, dtype=np.int64) + 1_700_000_000_000,
        {
            "symbol": rng.integers(1, 5, size=n).astype(np.int32),
            "price": rng.uniform(0.0, 100.0, size=n).astype(np.float32),
            "volume": rng.integers(1, 100, size=n).astype(np.int64),
        },
    )


def _run(ql, n, fused: bool, store_q="from T select *"):
    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime(ql)
    for s in ["A", "B", "C", "D"]:
        mgr.interner.intern(s)
    rt.start()
    junction = rt.junctions["S"]
    if fused:
        assert junction.fused_ingest is not None, "fused engine not built"
    else:
        for j in rt.junctions.values():
            j.fused_ingest = None
    ts, cols = _feed(n)
    rt.get_input_handler("S").send_columns(ts, cols)
    rows = sorted(map(repr, rt.query(store_q)))
    rt.shutdown()
    mgr.shutdown()
    return rows


HEAD = "@app:batch(size='64')\ndefine stream S (symbol string, price float, volume long);\n"

CASES = {
    "filter_table": HEAD + """
        @capacity(size='16384') define table T (symbol string, price float);
        @info(name='q') from S[price > 60] select symbol, price insert into T;
    """,
    "batch_groupby": HEAD + """
        @capacity(size='4096') define table T (symbol string, total long);
        @info(name='q') from S[price > 10]#window.lengthBatch(32)
        select symbol, sum(volume) as total group by symbol insert into T;
    """,
    "sliding_update": HEAD + """
        @capacity(size='64') define table T (symbol string, ap double);
        @info(name='q') from S#window.length(16)
        select symbol, avg(price) as ap group by symbol
        update or insert into T on T.symbol == symbol;
    """,
    "self_join": HEAD + """
        @app:joinCapacity(size='512')
        @capacity(size='16384') define table T (s1 string, s2 string);
        @info(name='q')
        from S#window.length(4) as a join S#window.length(4) as b
        on a.volume == b.volume
        select a.symbol as s1, b.symbol as s2 insert into T;
    """,
    "pattern": HEAD + """
        @app:patternCapacity(size='128')
        @capacity(size='8192') define table T (s1 string, s2 string);
        @info(name='q')
        from every a=S[price > 95] -> b=S[price < 5]
        select a.symbol as s1, b.symbol as s2 insert into T;
    """,
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_fused_matches_per_batch(name):
    ql = CASES[name]
    n = 64 * 40
    fused = _run(ql, n, fused=True)
    per_batch = _run(ql, n, fused=False)
    assert fused == per_batch


DELIVER_CASES = {
    "filter_cb": HEAD
    + "@info(name='q') from S[price > 60] select symbol, price insert into Out;",
    "window_avg_cb": HEAD
    + """@info(name='q') from S#window.length(16)
        select symbol, avg(price) as ap insert into Out;""",
    "groupby_cb": HEAD
    + """@info(name='q') from S#window.lengthBatch(32)
        select symbol, sum(volume) as total group by symbol insert into Out;""",
    "all_events_cb": HEAD
    + """@info(name='q') from S#window.length(8)
        select symbol, price insert all events into Out;""",
    # the deliver pack (core/ingest.py `_build`, deliver_pack): each
    # micro-batch's delivered rows moved to the front, then written as one run
    # behind the rows of the micro-batches before it
    "every_row_cb": HEAD
    + "@info(name='q') from S select symbol, price, volume insert into Out;",
    "expired_only_cb": HEAD
    + """@info(name='q') from S#window.length(8)
        select symbol, price insert expired events into Out;""",
}
FILTER_CB = DELIVER_CASES["filter_cb"]
WIDE_LANES_CB = """@app:batch(size='64')
    define stream S (symbol string, price double, volume long);
    @info(name='q') from S[volume != 0]
    select volume, price, price > 0.0 as up, symbol insert into Out;"""
# (app, rows sent, the least rows delivered, what the feed's prices become)
DELIVER_FEEDS = {
    name: (ql, 64 * 40, 50, None) for name, ql in DELIVER_CASES.items()
}


def _none_in_batch_5(cols):
    cols["price"][64 * 5 : 64 * 6] = 1.0


def _last_batch_alone(cols):
    cols["price"][: 64 * 39] = 1.0
    cols["price"][64 * 39 :] = 99.0


def _both_halves(cols):
    # 64-bit lanes whose high and low words both carry bits: longs beyond
    # 2**32 of either sign, doubles of either sign
    n = len(cols["volume"])
    rng = np.random.default_rng(7)
    cols["volume"] = rng.integers(-(2**62), 2**62, size=n).astype(np.int64)
    cols["volume"][::7] = np.int64(2**32 + 1)
    cols["price"] = rng.uniform(-1e18, 1e18, size=n).astype(np.float64)


DELIVER_FEEDS.update({
    "empty_mid_batch_cb": (FILTER_CB, 64 * 40, 50, _none_in_batch_5),
    "last_batch_alone_cb": (FILTER_CB, 64 * 40, 64, _last_batch_alone),
    # a send that takes the K = 2 and the K = 4 variant of the program
    "short_tail_k2_cb": (FILTER_CB, 64 * 2, 20, None),
    "short_tail_k4_cb": (FILTER_CB, 64 * 3, 30, None),
    "wide_lanes_cb": (WIDE_LANES_CB, 64 * 40, 50, _both_halves),
})


def _run_cb(ql, n, fused: bool, alter=None):
    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime(ql)
    got = []
    rt.add_callback(
        "q",
        lambda ts, ins, rem: got.append(
            (
                ts,
                [(e.timestamp, *e.data) for e in (ins or [])],
                [(e.timestamp, *e.data) for e in (rem or [])],
            )
        ),
    )
    for s in ["A", "B", "C", "D"]:
        mgr.interner.intern(s)
    rt.start()
    engine = rt.junctions["S"].fused_ingest
    if not fused:
        for j in rt.junctions.values():
            j.fused_ingest = None
    else:
        assert engine is not None
    ts, cols = _feed(n)
    if alter is not None:
        alter(cols)
    rt.get_input_handler("S").send_columns(ts, cols)
    if fused:
        assert engine.events_fused == n  # the whole send took the fused path
    rt.shutdown()
    mgr.shutdown()
    return got


@pytest.mark.parametrize("name", sorted(DELIVER_FEEDS))
def test_fused_delivery_matches_per_batch(name):
    """Query callbacks on the fused path: identical events, identical
    per-micro-batch grouping, identical order."""
    ql, n, least, alter = DELIVER_FEEDS[name]
    fused = _run_cb(ql, n, fused=True, alter=alter)
    per_batch = _run_cb(ql, n, fused=False, alter=alter)
    assert fused == per_batch
    assert sum(len(i) + len(r) for _t, i, r in fused) >= least


def test_deliver_pack_lowers_without_an_element_scatter():
    """The chunk program of a filter-only app places its delivered rows by
    shifted reads and one run per micro-batch: as lowered it holds no scatter
    of single elements (the scatter form held four, `c.price`, `c.symbol` and
    the halves of `ts`, over all K x batch output rows), and the status says
    which pack the program takes."""
    import jax
    import jax.numpy as jnp

    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime(FILTER_CB)
    rt.add_callback("q", lambda ts, ins, rem: None)
    rt.start()
    try:
        engine = rt.junctions["S"].fused_ingest
        engine._narrow = {}  # the full-width wire, nothing sent
        engine._build(deliver_set=frozenset({0}))
        K = 32
        state = jax.eval_shape(lambda: engine.endpoints[0].init_state(0))
        text = engine._fused_deliver.lower(
            (state,), {},
            jax.ShapeDtypeStruct((K, engine._wire_bytes), jnp.uint8),
            jax.ShapeDtypeStruct((K,), jnp.int32),
            jax.ShapeDtypeStruct((K,), jnp.int64),
            jax.ShapeDtypeStruct((), jnp.int64),
        ).as_text()
        pack = rt.snapshot_status()["streams"]["S"]["pipeline"]["pack"]
    finally:
        rt.shutdown()
        mgr.shutdown()
    assert "inserted_window_dims = [0]" not in text
    assert "stablehlo.dynamic_update_slice" in text
    assert pack == "slice"
