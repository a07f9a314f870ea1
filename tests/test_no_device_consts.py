"""Regression guard: compiled step programs must capture NO jax.Array consts.

Lowering a jaxpr that holds a concrete jax.Array constant (scalar or array)
reads the buffer back to the host to embed it as an HLO literal: a blocking
device->host transfer inside every trace+lower, queued behind whatever the
device is running, on any backend. Constants must therefore be numpy
(embedded as HLO literals with no readback) or built inside the trace via
lax primitives.

These tests trace representative query programs and assert the invariant
deterministically — no timing, no TPU needed.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest

from siddhi_tpu import SiddhiManager


def _batch_for(rt, mgr, stream, n=64):
    rng = np.random.default_rng(0)
    jn = rt.junctions[stream]
    ts = np.arange(n, dtype=np.int64) + 1_700_000_000_000
    cols = {}
    for name, t in jn.schema.attrs:
        from siddhi_tpu.core.types import AttrType

        if t is AttrType.STRING:
            cols[name] = rng.integers(1, 5, size=n).astype(np.int32)
        elif t in (AttrType.FLOAT, AttrType.DOUBLE):
            cols[name] = rng.uniform(0.0, 100.0, size=n).astype(np.float32)
        elif t is AttrType.BOOL:
            cols[name] = rng.integers(0, 2, size=n).astype(bool)
        else:
            cols[name] = rng.integers(1, 1000, size=n).astype(np.int64)
    return jn.schema.to_batch_cols(ts, cols, mgr.interner, capacity=n)


def _assert_no_device_consts(tag, fn, *args):
    closed = jax.make_jaxpr(fn)(*args)
    bad = [
        f"shape={c.shape} dtype={c.dtype}"
        for c in closed.consts
        if isinstance(c, jax.Array)
    ]
    assert not bad, f"{tag}: jax.Array consts captured: {bad}"


APPS = {
    "filter_const": """
        define stream S (symbol string, price float, volume long);
        @info(name='q') from S[price > 50 and symbol == 'WSO2']
        select symbol, price * 2 as p2 insert into Out;
    """,
    "window_agg": """
        define stream S (symbol string, price float, volume long);
        @info(name='q') from S#window.length(16)
        select symbol, avg(price) as ap, min(price) as mn, max(volume) as mx
        insert into Out;
    """,
    "batch_groupby": """
        define stream S (symbol string, price float, volume long);
        @info(name='q') from S#window.lengthBatch(8)
        select symbol, sum(volume) as tv, count() as c group by symbol
        having tv > 0 insert into Out;
    """,
    "time_window": """
        define stream S (symbol string, price float, volume long);
        @info(name='q') from S#window.time(1 sec)
        select symbol, sum(price) as sp insert into Out;
    """,
    "isnull_cast": """
        define stream S (symbol string, price float, volume long);
        @info(name='q') from S[not (volume is null)]
        select symbol, cast(price, 'double') as pd,
               ifThenElse(price > 50, 'hi', 'lo') as tag
        insert into Out;
    """,
}


@pytest.mark.parametrize("name", sorted(APPS))
def test_single_stream_steps_capture_no_device_consts(name):
    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime("@app:batch(size='64')\n" + APPS[name])
    rt.start()
    try:
        qr = rt.queries["q"]
        b = _batch_for(rt, mgr, "S")
        st = qr._fresh(qr.init_state())
        tst = qr._collect_table_states()
        now = np.int64(1_700_000_000_100)
        _assert_no_device_consts(
            name, lambda s, bb: qr._step_impl(s, tst, bb, now), st, b
        )
    finally:
        rt.shutdown()
        mgr.shutdown()


def test_join_step_captures_no_device_consts():
    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime("""
        @app:batch(size='64') @app:joinCapacity(size='128')
        define stream S (symbol string, price float, volume long);
        @info(name='q')
        from S#window.length(8) as a join S#window.length(8) as b
        on a.volume == b.volume
        select a.symbol as s1, b.symbol as s2 insert into Out;
    """)
    rt.start()
    try:
        qr = rt.queries["q"]
        b = _batch_for(rt, mgr, "S")
        st = qr._fresh(qr.init_state())
        tst = qr._collect_table_states()
        now = np.int64(1_700_000_000_100)
        _assert_no_device_consts(
            "join", lambda s, bb: qr._step_impl(s, tst, bb, now, "l"), st, b
        )
    finally:
        rt.shutdown()
        mgr.shutdown()


def test_pattern_step_captures_no_device_consts():
    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime("""
        @app:batch(size='64') @app:patternCapacity(size='64')
        define stream S (symbol string, price float, volume long);
        @info(name='q')
        from every a=S[price > 90] -> b=S[price < 10] within 1 sec
        select a.symbol as s1, b.symbol as s2 insert into Out;
    """)
    rt.start()
    try:
        qr = rt.queries["q"]
        b = _batch_for(rt, mgr, "S")
        st = qr._fresh(qr.init_state(1_700_000_000_000))
        step = qr._steps["S"]
        impl = getattr(step, "__wrapped__", step)
        now = np.int64(1_700_000_000_100)
        _assert_no_device_consts(
            "pattern", lambda s, bb: impl(s, {}, bb, now), st, b
        )
    finally:
        rt.shutdown()
        mgr.shutdown()
