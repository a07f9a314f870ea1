"""Plan-vs-actual calibration ledger (observability/calibration.py): six
prediction kinds pairing live meters, mispricing reason codes end-to-end
(HTTP + Prometheus + explain), churn re-pairing that preserves cumulative
counters, the zero-overhead gate, and byte parity with the ledger armed."""

import json
import urllib.request

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.observability.calibration import (
    KIND_COMPILES,
    KIND_DISPATCH,
    KIND_SELECTIVITY,
    KIND_STATE_BYTES,
    KIND_WIRE_DECLARED,
    KIND_WIRE_INFERRED,
    REASON_WIRE_FALLBACK,
    _safe_ratio,
)

# the six-kind sentinel shape: two
# shared filter+window queries, one externalTimeBatch query, a declared
# dict wire lane + an inferred delta lane, all fused under one group.
# batch 256: a 64-entry dictionary must amortize under the wide int32
# lane, which it cannot at small chunks (build_wire_spec drops it)
SENTINEL = """@app:statistics(reporter='none')
@app:batch(size='256')
@app:wire(dict.S.symbol='64')
define stream S (symbol string, price float, volume long);
@info(name='q1') from S[price > 50.0]#window.length(16)
select symbol, price insert into Out1;
@info(name='q2') from S[price > 50.0]#window.length(16)
select symbol, max(price) as mp insert into Out2;
@info(name='q3') from S#window.externalTimeBatch(volume, 1000)
select symbol, sum(price) as sp insert into Out3;
"""

ALL_KINDS = sorted((
    KIND_COMPILES, KIND_DISPATCH, KIND_SELECTIVITY,
    KIND_STATE_BYTES, KIND_WIRE_DECLARED, KIND_WIRE_INFERRED,
))


def _boot(ql):
    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime(ql)
    for q in ("q1", "q2", "q3"):
        rt.add_callback(q, lambda ts, ins, rem: None)
    rt.start()
    for s in ("A", "B", "C", "D"):
        mgr.interner.intern(s)
    return mgr, rt


def _feed(rt, chunks=4, n=1024, base=0):
    rng = np.random.default_rng(0)
    cols = {
        "symbol": rng.integers(1, 5, n).astype(np.int32),
        "price": rng.uniform(0, 100, n).astype(np.float32),
        "volume": (np.arange(n, dtype=np.int64) * 7) % 2000,
    }
    ts = np.arange(n, dtype=np.int64) + 1_700_000_000_000 + base
    h = rt.get_input_handler("S")
    for k in range(chunks):
        h.send_columns(ts + k * n, cols, now=int(ts[-1] + k * n))


class TestSafeRatio:
    def test_plain(self):
        assert _safe_ratio(2.0, 4.0) == 0.5

    def test_both_zero_is_perfectly_priced(self):
        assert _safe_ratio(0, 0) == 1.0

    def test_zero_prediction_saturates_finite(self):
        assert _safe_ratio(3.0, 0) == 4.0

    def test_none_and_nan_unpaired(self):
        assert _safe_ratio(None, 1.0) is None
        assert _safe_ratio(float("nan"), 1.0) is None


class TestZeroOverheadGate:
    def test_no_statistics_no_ledger(self):
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime(
            "define stream S (a int);\n"
            "@info(name='q') from S select a insert into Out;\n"
        )
        assert rt._calibration is None
        assert rt.calibration_report() is None
        assert "no calibration-enabled apps" in mgr.calibration_text()
        mgr.shutdown()


class TestSixKindsPairing:
    def test_all_six_kinds_pair_live(self):
        mgr, rt = _boot(SENTINEL)
        _feed(rt)
        rep = rt.calibration_report()
        mgr.shutdown()
        assert rep["generation"] >= 1
        assert rep["kinds_paired"] == ALL_KINDS
        by_key = {(p["kind"], p["component"]): p for p in rep["pairs"]}
        # every paired entry carries a finite ratio + EWMA
        for p in rep["pairs"]:
            if p["live"] is not None:
                assert p["ratio"] is not None and p["ratio"] >= 0
                assert p["ratio_ewma"] is not None
        # the fused group's compile + dispatch predictions join on the
        # group component name (cost model and telemetry share it by design)
        assert (KIND_COMPILES, "stream.S.fusedgroup.0") in by_key
        disp = by_key[(KIND_DISPATCH, "stream.S.fusedgroup.0")]
        assert 0.0 < disp["live"] <= 1.0
        # wire: declared dict lane and inferred delta lane, same live split
        decl = by_key[(KIND_WIRE_DECLARED, "stream.S")]
        inf = by_key[(KIND_WIRE_INFERRED, "stream.S")]
        assert decl["live"] == inf["live"] is not None
        assert decl["live"] < 24  # narrower than the 24 B/ev logical width

    def test_state_bytes_priced_close(self):
        mgr, rt = _boot(SENTINEL)
        _feed(rt)
        rep = rt.calibration_report()
        mgr.shutdown()
        ratios = [
            p["ratio"] for p in rep["pairs"]
            if p["kind"] == KIND_STATE_BYTES and p["ratio"] is not None
        ]
        assert ratios and all(0.5 < r < 2.0 for r in ratios)


class TestMispricedWireFallback:
    def test_reason_code_on_every_surface(self):
        mgr, rt = _boot(SENTINEL)
        _feed(rt, chunks=2)
        fi = rt.junctions["S"].fused_ingest
        assert fi is not None and fi._narrow  # encodings engaged
        fi.force_full_width()
        _feed(rt, chunks=2, base=1 << 20)
        rep = rt.calibration_report()
        assert REASON_WIRE_FALLBACK in rep["flags"]
        assert any(
            m["reason"] == REASON_WIRE_FALLBACK
            and m["component"] == "stream.S"
            for m in rep["mispriced"]
        )
        # HTTP surface
        port = mgr.serve_metrics(0)

        def get(path):
            return urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=10
            ).read().decode()

        blob = json.loads(get("/calibration.json"))["SiddhiApp"]
        assert REASON_WIRE_FALLBACK in blob["flags"]
        assert "mispriced" in get("/calibration")
        # Prometheus surface
        prom = mgr.prometheus_text()
        assert "siddhi_calibration_error_ratio" in prom
        assert (
            'siddhi_calibration_mispriced_total{'
            in prom and REASON_WIRE_FALLBACK in prom
        )
        assert "siddhi_compiles_total" in prom
        # explain surface: calib lines beside static lines
        text = rt.explain()
        assert "calib:" in text
        assert REASON_WIRE_FALLBACK in text
        mgr.shutdown()


class TestChurnRepairing:
    def test_generation_bumps_and_counters_survive(self):
        mgr, rt = _boot(SENTINEL)
        _feed(rt, chunks=2)
        fi = rt.junctions["S"].fused_ingest
        fi.force_full_width()
        _feed(rt, chunks=2, base=1 << 20)
        rep1 = rt.calibration_report()
        g1 = rep1["generation"]
        assert rep1["mispriced_total"] >= 1
        qid = rt.add_query(
            "@info(name='hot') from S[price < 0] "
            "select symbol insert into OutHot;"
        )
        rep2 = rt.calibration_report()
        # the splice rebuilt the fused engine -> the ledger re-paired
        # against the NEW AST, but cumulative mispricings survived
        assert rep2["generation"] > g1
        assert rep2["mispriced_total"] >= rep1["mispriced_total"]
        assert any(
            p["component"] == "query.hot" for p in rep2["pairs"]
        )
        rt.remove_query(qid)
        rep3 = rt.calibration_report()
        assert rep3["generation"] > rep2["generation"]
        assert not any(
            p["component"] == "query.hot" for p in rep3["pairs"]
        )
        assert rep3["mispriced_total"] >= rep1["mispriced_total"]
        mgr.shutdown()


class TestByteParity:
    def _collect(self, ql):
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime(ql)
        rows = {q: [] for q in ("q1", "q2", "q3")}
        for q, acc in rows.items():
            rt.add_callback(
                q,
                lambda ts, ins, rem, _a=acc: _a.extend(
                    tuple(e.data)
                    for e in tuple(ins or ()) + tuple(rem or ())
                ),
            )
        rt.start()
        for s in ("A", "B", "C", "D"):
            mgr.interner.intern(s)
        _feed(rt)
        mgr.shutdown()
        return rows

    def test_outputs_identical_with_ledger_on_and_off(self):
        armed = self._collect(SENTINEL)
        bare = self._collect(
            SENTINEL.replace("@app:statistics(reporter='none')\n", "")
        )
        assert armed == bare
        assert any(len(v) > 0 for v in armed.values())


class TestSnapshotStatus:
    def test_calibration_section_present(self):
        mgr, rt = _boot(SENTINEL)
        _feed(rt, chunks=2)
        status = rt.snapshot_status()
        assert "calibration" in status
        assert status["calibration"]["generation"] >= 1
        mgr.shutdown()
