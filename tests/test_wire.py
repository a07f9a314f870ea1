"""Compact wire encodings (core/wire.py): codec round-trips + runtime
guards, @app:wire resolution (annotation/env precedence, SA132 analyzer =
runtime rule set), static-spec engagement with byte-identical emissions
encode-on vs encode-off, the mid-stream full-width fallback, the
logical-vs-encoded roofline split, the FusionPlan v2 wire section, and the
explain()/describe_state() surfacing."""

from __future__ import annotations

import os

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core import wire as W
from siddhi_tpu.core.event import StreamSchema, WireNarrowMisfit
from siddhi_tpu.core.types import AttrType


SCHEMA = StreamSchema("S", [
    ("sym", AttrType.STRING),
    ("price", AttrType.FLOAT),
    ("vol", AttrType.LONG),
    ("seq", AttrType.LONG),
    ("flag", AttrType.BOOL),
])


def _sample(cap=16):
    ts = np.arange(cap, dtype=np.int64) * 3 + 1_700_000_000_000
    cols = {
        "sym": (np.arange(cap, dtype=np.int32) % 4) + 5,
        "price": np.linspace(0, 10, cap).astype(np.float32),
        "vol": np.arange(cap, dtype=np.int64) * 100,
        "seq": np.arange(cap, dtype=np.int64) + 10**12,
        "flag": (np.arange(cap) % 2 == 0),
    }
    return ts, cols


ENC = {
    "sym": ("dict", np.dtype(np.uint8), 4),
    "vol": ("narrow", np.dtype(np.int16)),
    "seq": ("delta", np.dtype(np.int16)),
    "flag": ("bitpack",),
    "__tsd__": np.dtype(np.int8),
}


class TestCodec:
    def test_round_trip_all_encoders(self):
        cap = 16
        ts, cols = _sample(cap)
        encode, decode, total = SCHEMA.wire_codec(cap, None, ENC)
        # the encoded wire is a fraction of the full-width one
        assert total < W.logical_row_bytes(SCHEMA.attrs) * cap / 2
        buf, base = encode(ts, cols, cap)
        b = decode(buf, np.int32(cap), base)
        assert np.array_equal(np.asarray(b.ts), ts)
        for k, v in cols.items():
            assert np.array_equal(np.asarray(b.cols[k]), v), k
        assert bool(np.asarray(b.valid).all())

    def test_delta_decode_has_no_64bit_cumsum(self):
        """XLA:TPU lowers a 64-bit cumsum to a reduce-window that ran out of
        scoped vmem on the v5e at B=32768 (PR 21's chip smoke): the delta
        lane must rebuild its values with the blocked scan, and still
        round-trip exactly at a size that takes the blocked path."""
        import jax

        cap = 2048
        ts, cols = _sample(cap)
        # sym/vol of _sample outgrow their 16-row encodings at this size
        enc = {k: ENC[k] for k in ("seq", "flag", "__tsd__")}
        encode, decode, _total = SCHEMA.wire_codec(cap, None, enc)
        buf, base = encode(ts, cols, cap)
        text = str(jax.make_jaxpr(decode)(buf, np.int32(cap), base))
        bad = [ln for ln in text.splitlines()
               if "cumsum" in ln and "i64[" in ln]
        assert not bad, bad
        b = decode(buf, np.int32(cap), base)
        assert np.array_equal(np.asarray(b.cols["seq"]), cols["seq"])

    def test_partial_batch(self):
        cap = 16
        ts, cols = _sample(cap)
        encode, decode, _ = SCHEMA.wire_codec(cap, None, ENC)
        buf, base = encode(ts, cols, 5)
        b = decode(buf, np.int32(5), base)
        assert np.array_equal(np.asarray(b.valid), np.arange(cap) < 5)
        for k, v in cols.items():
            assert np.array_equal(np.asarray(b.cols[k])[:5], v[:5]), k

    def test_empty_batch(self):
        cap = 8
        ts, cols = _sample(cap)
        encode, decode, _ = SCHEMA.wire_codec(cap, None, ENC)
        buf, base = encode(ts[:0], {k: v[:0] for k, v in cols.items()}, 0)
        b = decode(buf, np.int32(0), base)
        assert not bool(np.asarray(b.valid).any())

    def test_dict_cardinality_guard(self):
        cap = 16
        ts, cols = _sample(cap)
        encode, _d, _t = SCHEMA.wire_codec(cap, None, ENC)
        bad = dict(cols)
        bad["sym"] = np.arange(cap, dtype=np.int32)  # 16 distinct > 4
        with pytest.raises(WireNarrowMisfit):
            encode(ts, bad, cap)

    def test_narrow_range_guard(self):
        cap = 16
        ts, cols = _sample(cap)
        encode, _d, _t = SCHEMA.wire_codec(cap, None, ENC)
        bad = dict(cols)
        bad["vol"] = np.full(cap, 10**6, np.int64)  # > int16
        with pytest.raises(WireNarrowMisfit):
            encode(ts, bad, cap)

    def test_delta_jump_guard(self):
        cap = 16
        ts, cols = _sample(cap)
        encode, _d, _t = SCHEMA.wire_codec(cap, None, ENC)
        bad = dict(cols)
        s = cols["seq"].copy()
        s[8] = s[7] + 10**6  # diff > int16
        bad["seq"] = s
        with pytest.raises(WireNarrowMisfit):
            encode(ts, bad, cap)

    def test_projection_still_applies(self):
        cap = 8
        ts, cols = _sample(cap)
        keep = frozenset(("sym", "flag"))
        encode, decode, total = SCHEMA.wire_codec(cap, keep, ENC)
        _e, _d, total_all = SCHEMA.wire_codec(cap, None, ENC)
        assert total < total_all
        buf, base = encode(ts, cols, cap)
        b = decode(buf, np.int32(cap), base)
        assert np.array_equal(np.asarray(b.cols["sym"]), cols["sym"])
        assert set(b.cols) == {n for n, _t in SCHEMA.attrs}  # shape kept


class TestSpec:
    def test_build_wire_spec_from_hints(self):
        hints = {
            ("S", "vol"): ("range", 0, 30000),
            ("S", "sym"): ("dict", 16),
            ("S", "seq"): ("delta", np.dtype(np.int16)),
        }
        spec = W.build_wire_spec("S", SCHEMA.attrs, hints)
        assert spec.encodings["vol"] == ("narrow", np.dtype(np.int16))
        assert spec.encodings["sym"] == ("dict", np.dtype(np.uint8), 16)
        assert spec.encodings["seq"] == ("delta", np.dtype(np.int16))
        # BOOL bitpack needs no hint
        assert spec.encodings["flag"] == ("bitpack",)
        d = spec.to_dict()
        assert d["version"] == W.WIRE_SPEC_VERSION
        assert d["encodings"]["sym"] == "dict:uint8[16]"

    def test_spec_none_without_static_material(self):
        attrs = [("a", AttrType.INT), ("b", AttrType.FLOAT)]
        assert W.build_wire_spec("X", attrs, {}) is None

    def test_choose_encodings_disabled_is_full_width(self):
        ts, cols = _sample(8)
        assert W.choose_encodings(SCHEMA, None, None, False, ts, cols) == {}

    def test_choose_encodings_static_beats_sampled(self):
        ts, cols = _sample(8)
        spec = W.build_wire_spec(
            "S", SCHEMA.attrs, {("S", "vol"): ("range", 0, 100000)}
        )
        enc = W.choose_encodings(SCHEMA, None, spec, True, ts, cols)
        # sampled would pick int16 for the small vol sample; the declared
        # 0..100000 contract forces int32 (no mid-stream rebuild when
        # bigger-but-declared values arrive)
        assert enc["vol"] == ("narrow", np.dtype(np.int32))
        assert enc["flag"] == ("bitpack",)

    def test_estimates(self):
        spec = W.build_wire_spec(
            "S", SCHEMA.attrs, {("S", "sym"): ("dict", 16)}
        )
        logical = W.logical_row_bytes(SCHEMA.attrs)
        assert logical == 8 + 4 + 4 + 8 + 8 + 1
        assert W.estimate_wire_bytes(SCHEMA.attrs, spec) < logical


class TestAnnotation:
    def test_resolve_defaults_on(self):
        enabled, hints = W.resolve_wire_annotation(None)
        assert enabled is True and hints == {}

    def test_env_precedence(self, monkeypatch):
        monkeypatch.setenv(W.WIRE_ENV, "0")
        enabled, _ = W.resolve_wire_annotation(None)
        assert enabled is False
        monkeypatch.setenv(W.WIRE_ENV, "1")

        class Ann:
            elements = [("disable", "true")]

            @staticmethod
            def element(k, default=None):
                return "true" if k == "disable" else default

        enabled, _ = W.resolve_wire_annotation(Ann())
        assert enabled is True  # env force-on beats the annotation

    def test_malformed_raises_at_creation(self):
        from siddhi_tpu.core.errors import SiddhiAppCreationError

        mgr = SiddhiManager()
        with pytest.raises(SiddhiAppCreationError):
            mgr.create_siddhi_app_runtime("""
            @app:wire(disable='maybe')
            define stream S (a int);
            from S select a insert into Out;
            """)
        mgr.shutdown()

    def test_sa132_analyzer_same_rules(self):
        from siddhi_tpu.analysis import analyze

        res = analyze("""
        @app:wire(disable='maybe', range.S.price='1..2',
                  dict.Ghost.col='8', zap.S.a='1')
        define stream S (a int, price float);
        from S select a insert into Out;
        """)
        codes = [d for d in res.diagnostics if d.code == "SA132"]
        msgs = "\n".join(d.message for d in codes)
        assert len(codes) == 4, msgs
        assert "must be true or false" in msgs
        assert "FLOAT" in msgs           # encoder-type mismatch
        assert "unknown stream 'Ghost'" in msgs
        assert "unknown @app:wire option" in msgs

    def test_sa133_dominant_long_warns_and_hint_silences(self):
        from siddhi_tpu.analysis import analyze

        base = """
        define stream M (seq long);
        from M[seq > 0] select seq insert into Out;
        """
        res = analyze(base)
        assert any(d.code == "SA133" for d in res.warnings), res.diagnostics
        hinted = "@app:wire(delta.M.seq='int16')" + base
        res2 = analyze(hinted)
        assert not any(d.code == "SA133" for d in res2.diagnostics)


WIRE_APP = """
@app:batch(size='32')
@app:wire(dict.S.symbol='16', range.S.volume='0..30000')
define stream S (symbol string, price float, volume long, up bool);
@info(name='q') from S[price > 20]#window.length(8)
select symbol, up, avg(price) as ap, sum(volume) as tv insert into Out;
"""


def _feed(n=256, seed=3):
    rng = np.random.default_rng(seed)
    ts = np.arange(n, dtype=np.int64) + 1_700_000_000_000
    cols = {
        "symbol": rng.integers(1, 9, n).astype(np.int32),
        "price": rng.uniform(0, 100, n).astype(np.float32),
        "volume": rng.integers(1, 1000, n).astype(np.int64),
        "up": rng.integers(0, 2, n).astype(bool),
    }
    return ts, cols


def _run_app(ql, env_val, feed_calls, seed=3):
    saved = os.environ.get(W.WIRE_ENV)
    os.environ[W.WIRE_ENV] = env_val
    try:
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime(ql)
    finally:
        if saved is None:
            os.environ.pop(W.WIRE_ENV, None)
        else:
            os.environ[W.WIRE_ENV] = saved
    for i in range(1, 20):
        mgr.interner.intern(f"SYM{i}")
    rows = []
    rt.add_callback("q", lambda t, ins, rem: rows.extend(
        [("+",) + tuple(e.data) for e in (ins or [])]
        + [("-",) + tuple(e.data) for e in (rem or [])]
    ))
    rt.start()
    h = rt.get_input_handler("S")
    for ts, cols in feed_calls:
        h.send_columns(ts, cols, now=int(ts[-1]))
    fi = rt.junctions["S"].fused_ingest
    state = {
        "narrow": dict(fi._narrow) if fi and fi._narrow is not None else None,
        "wire_bytes": fi._wire_bytes if fi else None,
        "describe": fi.describe_state() if fi else None,
    }
    rt.shutdown()
    mgr.shutdown()
    return rows, state


class TestEngineIntegration:
    def test_static_spec_engages_and_parity(self):
        ts, cols = _feed()
        on_rows, on_state = _run_app(WIRE_APP, "1", [(ts, cols)])
        off_rows, off_state = _run_app(WIRE_APP, "0", [(ts, cols)])
        assert on_rows == off_rows and on_rows
        assert on_state["wire_bytes"] < off_state["wire_bytes"]
        assert isinstance(on_state["narrow"].get("symbol"), tuple)
        assert on_state["narrow"].get("up") == ("bitpack",)
        assert off_state["narrow"] == {}  # WIRE=0 = full width, no sampling
        w = on_state["describe"]["wire"]
        assert w["source"] in ("static", "static+sampled")
        assert w["encoded_B_per_ev"] < w["logical_B_per_ev"]
        assert "dict" in w["lanes"]["symbol"]

    def test_annotation_disable(self):
        ql = WIRE_APP.replace(
            "@app:wire(dict.S.symbol='16', range.S.volume='0..30000')",
            "@app:wire(disable='true', dict.S.symbol='16')",
        )
        ts, cols = _feed()
        # no env override: the annotation's disable wins
        rows, state = _run_app(ql, "", [(ts, cols)])
        assert state["narrow"] == {}

    def test_mid_stream_range_fallback_byte_identical(self):
        ts, cols = _feed()
        ts2 = ts + len(ts)
        cols2 = dict(cols)
        cols2["volume"] = cols["volume"] + 10**6  # > declared-range dtype
        feed = [(ts, cols), (ts2, cols2)]
        on_rows, on_state = _run_app(WIRE_APP, "1", feed)
        off_rows, _ = _run_app(WIRE_APP, "0", feed)
        assert on_state["narrow"] == {}  # fell back full-width, permanent
        assert on_rows == off_rows

    def test_mid_stream_dict_overflow_fallback(self):
        ts, cols = _feed()
        ts2 = ts + len(ts)
        cols2 = dict(cols)
        cols2["symbol"] = (
            np.arange(len(ts), dtype=np.int32) % 18
        ) + 1  # 18 distinct > declared 16
        feed = [(ts, cols), (ts2, cols2)]
        on_rows, on_state = _run_app(WIRE_APP, "1", feed)
        off_rows, _ = _run_app(WIRE_APP, "0", feed)
        assert on_state["narrow"] == {}
        assert on_rows == off_rows

    def test_roofline_logical_vs_encoded(self):
        saved = os.environ.get(W.WIRE_ENV)
        os.environ[W.WIRE_ENV] = "1"
        try:
            mgr = SiddhiManager()
            rt = mgr.create_siddhi_app_runtime(
                "@app:statistics(reporter='none')\n" + WIRE_APP
            )
        finally:
            if saved is None:
                os.environ.pop(W.WIRE_ENV, None)
            else:
                os.environ[W.WIRE_ENV] = saved
        rt.start()
        ts, cols = _feed()
        rt.get_input_handler("S").send_columns(ts, cols, now=int(ts[-1]))
        roof = rt.statistics_manager.roofline()
        ent = roof.get("stream.S")
        assert ent is not None, roof
        assert 0 < ent["wire_bytes_per_event"] < ent[
            "wire_logical_bytes_per_event"
        ], ent
        assert ent["wire_reduction"] > 1.5, ent
        # the Prometheus exposition carries both gauges
        text = rt.statistics_manager.prometheus_text()
        assert "siddhi_wire_bytes_per_event" in text
        assert "siddhi_wire_logical_bytes_per_event" in text
        rt.shutdown()
        mgr.shutdown()

    def test_explain_renders_wire(self):
        ts, cols = _feed()
        saved = os.environ.get(W.WIRE_ENV)
        os.environ[W.WIRE_ENV] = "1"
        try:
            mgr = SiddhiManager()
            rt = mgr.create_siddhi_app_runtime(WIRE_APP)
        finally:
            if saved is None:
                os.environ.pop(W.WIRE_ENV, None)
            else:
                os.environ[W.WIRE_ENV] = saved
        rt.start()
        rt.get_input_handler("S").send_columns(ts, cols, now=int(ts[-1]))
        text = rt.explain()
        assert "wire[" in text, text
        assert "dict" in text
        rt.shutdown()
        mgr.shutdown()


class TestPlanWireSection:
    def test_plan_carries_versioned_specs(self):
        from siddhi_tpu.analysis import build_fusion_plan

        plan = build_fusion_plan(WIRE_APP).to_dict()
        assert plan["version"] == 3
        w = plan["wire"]["S"]
        assert w["version"] == W.WIRE_SPEC_VERSION
        assert w["encodings"]["symbol"] == "dict:uint8[16]"
        assert w["encodings"]["up"] == "bitpack:1bit"
        assert w["encoded_B_per_ev_est"] < w["logical_B_per_ev"]

    def test_plan_marks_disabled(self):
        from siddhi_tpu.analysis import build_fusion_plan

        ql = WIRE_APP.replace(
            "@app:wire(dict.S.symbol='16', range.S.volume='0..30000')",
            "@app:wire(disable='true', dict.S.symbol='16')",
        )
        plan = build_fusion_plan(ql).to_dict()
        assert plan["wire"]["S"].get("disabled") is True

    def test_plan_text_renders_wire(self):
        from siddhi_tpu.analysis import build_fusion_plan
        from siddhi_tpu.analysis.fusion import render_plan_text

        text = render_plan_text(build_fusion_plan(WIRE_APP))
        assert "wire encodings:" in text
        assert "symbol=dict:uint8[16]" in text
