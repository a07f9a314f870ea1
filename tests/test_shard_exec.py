"""First-class sharded execution (parallel/shard.py): `@app:shard` /
SIDDHI_TPU_SHARD resolved at start().

Covers the runtime half of the mesh contract promoted out of the multichip
dryrun: annotation/env resolution (one SA129 rule set with the analyzer),
a stateless junction's fused chunk loop on one device under the mesh
(byte-identical delivery vs unsharded), partition-axis mesh placement
parity over key churn, and a verify-suite parity sweep under
SIDDHI_TPU_SHARD=8 vs off (the in-process slice of the CI diff; conftest
forces the 8-device CPU mesh)."""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.errors import SiddhiAppCreationError
from siddhi_tpu.parallel.shard import resolve_shard_annotation
from siddhi_tpu.query_api.annotation import Annotation

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


SYMS = ["WSO2", "IBM", "GOOG", "MSFT", "ORCL", "AAPL", "AMZN", "NVDA"]

STATELESS_QL = """@app:batch(size='32')
{HEAD}define stream S (symbol string, price float, volume long);
@info(name='q') from S[price > 50] select symbol, price insert into Out;
@info(name='q2') from S select symbol, volume insert into Out2;
"""


def _feed_cols(n, seed=5):
    rng = np.random.default_rng(seed)
    ts = np.arange(n, dtype=np.int64) + 1_700_000_000_000
    cols = {
        "symbol": rng.integers(1, 9, size=n).astype(np.int32),
        "price": rng.uniform(0, 100, size=n).astype(np.float32),
        "volume": rng.integers(1, 1000, size=n).astype(np.int64),
    }
    return ts, cols


def _run_stateless(head, n=4096, qids=("q", "q2")):
    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime(STATELESS_QL.replace("{HEAD}", head))
    for s in SYMS:
        mgr.interner.intern(s)
    got = {qid: [] for qid in qids}
    for qid in qids:
        rt.add_callback(
            qid,
            lambda ts, ins, rem, _q=qid: got[_q].extend(
                [tuple(e.data) for e in (ins or [])]
            ),
        )
    rt.start()
    ts, cols = _feed_cols(n)
    rt.get_input_handler("S").send_columns(ts, cols, now=int(ts[-1]))
    status = rt.snapshot_status()
    chunks = rt.junctions["S"].fused_ingest.chunks_dispatched
    prom = (
        rt.statistics_manager.prometheus_text()
        if rt.statistics_manager is not None
        else ""
    )
    rt.shutdown()
    mgr.shutdown()
    return got, status, chunks, prom


# ---------------------------------------------------------------------------
# annotation / env resolution (SA129 rule set)
# ---------------------------------------------------------------------------


class TestShardResolution:
    def test_annotation_devices_and_axis(self, monkeypatch):
        monkeypatch.delenv("SIDDHI_TPU_SHARD", raising=False)
        ann = Annotation("app:shard", [("devices", "8"), ("axis", "part")])
        assert resolve_shard_annotation(ann) == (8, "part")

    def test_sole_positional_devices(self, monkeypatch):
        monkeypatch.delenv("SIDDHI_TPU_SHARD", raising=False)
        assert resolve_shard_annotation(
            Annotation("app:shard", [(None, "4")])
        ) == (4, "auto")

    def test_no_annotation_defaults_off(self, monkeypatch):
        monkeypatch.delenv("SIDDHI_TPU_SHARD", raising=False)
        assert resolve_shard_annotation(None) == (0, "auto")

    def test_env_overrides_annotation_both_directions(self, monkeypatch):
        ann = Annotation("app:shard", [("devices", "8")])
        monkeypatch.setenv("SIDDHI_TPU_SHARD", "0")
        assert resolve_shard_annotation(ann)[0] == 0
        monkeypatch.setenv("SIDDHI_TPU_SHARD", "4")
        assert resolve_shard_annotation(None)[0] == 4

    @pytest.mark.parametrize(
        "elements",
        [
            [("devices", "0")],
            [("devices", "-3")],
            [("devices", "many")],
            [("devices", "8"), ("axis", "diagonal")],
            [("devices", "8"), ("axis", "batch")],
            [("devices", "8"), ("turbo", "on")],
        ],
    )
    def test_malformed_annotation_raises(self, monkeypatch, elements):
        monkeypatch.delenv("SIDDHI_TPU_SHARD", raising=False)
        with pytest.raises(SiddhiAppCreationError):
            resolve_shard_annotation(Annotation("app:shard", elements))

    def test_runtime_creation_rejects_malformed(self, monkeypatch):
        monkeypatch.delenv("SIDDHI_TPU_SHARD", raising=False)
        mgr = SiddhiManager()
        with pytest.raises(SiddhiAppCreationError):
            mgr.create_siddhi_app_runtime(
                "@app:shard(devices='8', axis='diagonal')\n"
                "define stream S (a int);\n"
                "from S select a insert into Out;"
            )
        mgr.shutdown()

    def test_analyzer_sa129_same_rule_set(self):
        from siddhi_tpu.analysis import analyze
        from siddhi_tpu.compiler.siddhi_compiler import SiddhiCompiler

        app = SiddhiCompiler.parse(
            "@app:shard(devices='0', axis='diagonal', turbo='on')\n"
            "define stream S (a int);\n"
            "from S select a insert into Out;"
        )
        codes = [d.code for d in analyze(app).diagnostics]
        assert codes.count("SA129") == 3, codes

    def test_axis_batch_refused_with_one_message(self, monkeypatch):
        """`batch` is no axis: creation and SA129 refuse it in the same
        words, which name the axes there are."""
        from siddhi_tpu.analysis import analyze
        from siddhi_tpu.compiler.siddhi_compiler import SiddhiCompiler

        monkeypatch.delenv("SIDDHI_TPU_SHARD", raising=False)
        text = (
            "@app:shard(devices='8', axis='batch')\n"
            "define stream S (a int);\n"
            "from S select a insert into Out;"
        )
        said = [
            d.message
            for d in analyze(SiddhiCompiler.parse(text)).diagnostics
            if d.code == "SA129"
        ]
        assert said == [
            "@app:shard axis 'batch' must be one of auto, part, keys"
        ]
        mgr = SiddhiManager()
        with pytest.raises(SiddhiAppCreationError) as refused:
            mgr.create_siddhi_app_runtime(text)
        mgr.shutdown()
        assert said[0] in str(refused.value)

    def test_env_axis_batch_ignored_with_warning(self, monkeypatch, caplog):
        """SIDDHI_TPU_SHARD_AXIS=batch is a malformed value like any other:
        one WARNING, and the annotation's axis stays in force."""
        monkeypatch.delenv("SIDDHI_TPU_SHARD", raising=False)
        monkeypatch.setenv("SIDDHI_TPU_SHARD_AXIS", "batch")
        ann = Annotation("app:shard", [("devices", "8"), ("axis", "keys")])
        with caplog.at_level("WARNING", logger="siddhi_tpu.parallel.shard"):
            assert resolve_shard_annotation(ann) == (8, "keys")
        warned = [r.getMessage() for r in caplog.records]
        assert warned == [
            "ignoring malformed SIDDHI_TPU_SHARD_AXIS='batch' "
            "(expected one of auto, part, keys)"
        ]


# ---------------------------------------------------------------------------
# a stateless junction under the mesh: the fused chunk loop, on one device
# ---------------------------------------------------------------------------


def test_stateless_junction_runs_the_fused_loop_on_one_device(monkeypatch):
    head = "@app:statistics(reporter='none')\n"
    monkeypatch.setenv("SIDDHI_TPU_SHARD", "8")
    sharded, status, chunks, prom = _run_stateless(head)
    assert chunks > 0
    assert status["shard"]["devices"] == 8
    assert "streams" not in status["shard"]
    assert status["streams"]["S"]["pipeline"]["mesh_devices"] == 1
    assert "siddhi_pipeline_occupancy" in prom
    assert "siddhi_shard_device_" not in prom
    monkeypatch.setenv("SIDDHI_TPU_SHARD", "0")
    unsharded, status0, chunks0, _ = _run_stateless(head)
    assert "shard" not in status0 and chunks0 == chunks
    assert sharded == unsharded
    assert len(sharded["q"]) > 500  # the filter selected rows
    assert len(sharded["q2"]) == 4096


# ---------------------------------------------------------------------------
# partition-axis mesh placement
# ---------------------------------------------------------------------------

PARTITION_QL = """@app:batch(size='64')
@app:partitionCapacity(size='32')
{HEAD}define stream S (symbol string, price float, volume long);
partition with (symbol of S)
begin
    @info(name='q')
    from S[price > 0]#window.length(8)
    select symbol, sum(volume) as total, avg(price) as ap
    insert into Out;
end;
"""


def _run_partitioned(head, steps=30, bsz=64):
    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime(PARTITION_QL.replace("{HEAD}", head))
    for i in range(24):
        mgr.interner.intern(f"SYM{i}")
    got = []
    rt.add_callback(
        "q", lambda ts, ins, rem: got.extend(
            [tuple(e.data) for e in (ins or [])]
        )
    )
    rt.start()
    rng = np.random.default_rng(11)
    h = rt.get_input_handler("S")
    for s in range(steps):
        pool = np.arange(1, 7) if s < 10 else np.arange(1, 21)
        ts = np.arange(bsz, dtype=np.int64) + 1_700_000_000_000 + s * bsz
        cols = {
            "symbol": rng.choice(pool, size=bsz).astype(np.int32),
            "price": rng.uniform(1, 100, size=bsz).astype(np.float32),
            "volume": rng.integers(1, 100, size=bsz).astype(np.int64),
        }
        h.send_columns(ts, cols, now=int(ts[-1]))
    status = rt.snapshot_status()
    rt.shutdown()
    mgr.shutdown()
    return got, status


class TestPartitionMesh:
    def test_parity_over_key_churn(self, monkeypatch):
        monkeypatch.setenv("SIDDHI_TPU_SHARD", "8")
        sharded, status = _run_partitioned("")
        placed = status["shard"]["partitioned"]["q"]
        assert placed == {
            "sharded": True, "devices": 8, "axis": "part", "local_slots": 4,
        }
        monkeypatch.setenv("SIDDHI_TPU_SHARD", "0")
        unsharded, status2 = _run_partitioned("")
        assert "shard" not in status2
        assert len(sharded) > 800
        assert sharded == unsharded

    def test_indivisible_capacity_pads_to_mesh(self, monkeypatch):
        # 32 % 6 != 0: the [P] axis is padded to 36 (6 local slots per
        # device) with dead slots that no key ever hashes to a live
        # position of — results byte-match the unsharded run
        monkeypatch.setenv("SIDDHI_TPU_SHARD", "6")
        sharded, status = _run_partitioned("", steps=8)
        placed = status["shard"]["partitioned"]["q"]
        assert placed == {
            "sharded": True, "devices": 6, "axis": "part",
            "local_slots": 6, "padded_slots": 4,
        }
        monkeypatch.setenv("SIDDHI_TPU_SHARD", "0")
        unsharded, _ = _run_partitioned("", steps=8)
        assert sharded == unsharded


# ---------------------------------------------------------------------------
# verify-suite parity sweep (siddhi_tpu/testing/verify_cases.py)
# ---------------------------------------------------------------------------


class TestVerifyParity:
    def test_verify_cases_byte_identical_shard8_vs_off(self, monkeypatch):
        from siddhi_tpu.testing.verify_cases import run_verify_cases

        results = {}
        for mode in ("8", "0"):
            monkeypatch.setenv("SIDDHI_TPU_SHARD", mode)
            results[mode] = run_verify_cases(columnar=True)["cases"]
        errors = {
            k: v
            for m in results
            for k, v in results[m].items()
            if isinstance(v, str)
        }
        assert not errors, errors
        bad = [
            k for k in sorted(set(results["8"]) | set(results["0"]))
            if results["8"].get(k) != results["0"].get(k)
        ]
        assert not bad, bad
