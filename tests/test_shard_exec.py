"""First-class sharded execution (parallel/shard.py): `@app:shard` /
SIDDHI_TPU_SHARD resolved at start().

Covers the runtime half of the mesh contract promoted out of the multichip
dryrun: annotation/env resolution (one SA129 rule set with the analyzer),
round-robin router key distribution and batch-order merge (byte-identical
delivery vs unsharded), the stateless-only eligibility gate, partition-axis
mesh placement parity over key churn, per-device dispatch counters in
`describe_state()`/`snapshot_status()`/Prometheus, and a verify-suite
parity sweep under SIDDHI_TPU_SHARD=8 vs off (the in-process slice of the
CI diff; conftest forces the 8-device CPU mesh)."""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.errors import SiddhiAppCreationError
from siddhi_tpu.parallel.shard import (
    resolve_shard_annotation,
    router_eligible,
    shardable_stateless,
)
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
    fi = rt.junctions["S"].fused_ingest
    router = getattr(fi, "shard_router", None) if fi is not None else None
    router_state = router.describe_state() if router is not None else None
    prom = (
        rt.statistics_manager.prometheus_text()
        if rt.statistics_manager is not None
        else ""
    )
    rt.shutdown()
    mgr.shutdown()
    return got, status, router_state, prom


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


# ---------------------------------------------------------------------------
# batch-axis router
# ---------------------------------------------------------------------------


class TestBatchRouter:
    def test_round_robin_distribution_and_counts(self, monkeypatch):
        monkeypatch.setenv("SIDDHI_TPU_SHARD", "8")
        n = 4096  # 128 micro-batches of 32 -> 16 per device
        _got, status, router_state, _ = _run_stateless("", n=n)
        assert router_state is not None, "router did not arm"
        assert router_state["devices"] == 8
        assert sum(router_state["per_device_events"]) == n
        # round-robin over equal-size batches: every device gets an equal
        # share, so every occupancy is 1.0
        assert len(set(router_state["per_device_events"])) == 1
        assert all(d >= 1 for d in router_state["per_device_dispatches"])
        assert router_state["occupancy"] == [1.0] * 8
        # surfaced through snapshot_status too
        shard = status["shard"]
        assert shard["devices"] == 8
        assert shard["streams"]["S"]["per_device_events"] == (
            router_state["per_device_events"]
        )

    def test_merge_preserves_delivery_order_byte_identically(
        self, monkeypatch
    ):
        monkeypatch.setenv("SIDDHI_TPU_SHARD", "8")
        sharded, _s, router_state, _ = _run_stateless("", n=4096)
        assert router_state is not None
        monkeypatch.setenv("SIDDHI_TPU_SHARD", "0")
        unsharded, _s2, no_router, _ = _run_stateless("", n=4096)
        assert no_router is None
        assert sharded == unsharded
        assert len(sharded["q"]) > 500  # the filter actually selected rows
        assert len(sharded["q2"]) == 4096

    def test_merged_drain_reads_through_the_dense_prefix(self, monkeypatch):
        """The merged drain has no read of its own: every device's header
        and rows come through the fused drain's prefix program, on that
        device, in the sizes the fused drain reads in."""
        from siddhi_tpu.core import ingest

        reads = []
        start = ingest.start_dense_read

        def spy(buf, at, n):
            reads.append((next(iter(buf.devices())).id, at, n))
            return start(buf, at, n)

        monkeypatch.setattr(ingest, "start_dense_read", spy)
        monkeypatch.setenv("SIDDHI_TPU_SHARD", "8")
        sharded, _s, router_state, _ = _run_stateless("", n=4096)
        assert router_state is not None
        assert {d for d, _at, _n in reads} == set(range(8))
        headers = [r for r in reads if r[1] == 0]
        rows = [r for r in reads if r[1] > 0]
        # per device, chunk and endpoint one header; rows in powers of two
        assert len(headers) >= 16 and len(rows) == len(headers)
        assert all(n & (n - 1) == 0 for _d, _at, n in rows)
        assert len(sharded["q2"]) == 4096

    def test_multi_chunk_per_device_stays_byte_identical(self, monkeypatch):
        """More than two chunks per device in one send: every chunk's wire
        is staged before any dispatch, so staging must never reuse a buffer
        an earlier chunk still occupies (a pooled slot would be re-acquired
        ungated and overwrite staged bytes — duplicated/lost events)."""
        # @app:ingestChunk(size='4'): 3072 events / batch 32 = 96 batches,
        # 12 per device = THREE K=4 chunks each
        head = "@app:ingestChunk(size='4')\n"
        monkeypatch.setenv("SIDDHI_TPU_SHARD", "8")
        sharded, _s, router_state, _ = _run_stateless(head, n=3072)
        assert router_state is not None
        assert min(router_state["per_device_dispatches"]) >= 3
        monkeypatch.setenv("SIDDHI_TPU_SHARD", "0")
        unsharded, _s2, _r, _ = _run_stateless(head, n=3072)
        assert sharded == unsharded
        assert len(sharded["q2"]) == 3072

    def test_guarded_junction_owns_sharded_drain_failures(self, monkeypatch):
        """A poison query callback on a junction with an exception handler:
        the sharded merge drain must route the error through the junction's
        failure machinery (like every single-device drain), not abort the
        send — behavior may not diverge between shard on and off."""
        monkeypatch.setenv("SIDDHI_TPU_SHARD", "8")
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime(
            STATELESS_QL.replace("{HEAD}", "")
        )
        for s in SYMS:
            mgr.interner.intern(s)
        caught = []
        rt.set_exception_handler(caught.append)
        delivered = []
        rt.add_callback("q2", lambda ts, ins, rem: delivered.extend(ins or []))

        def poison(ts, ins, rem):
            raise RuntimeError("poison callback")

        rt.add_callback("q", poison)
        rt.start()
        assert getattr(
            rt.junctions["S"].fused_ingest, "shard_router", None
        ) is not None
        ts, cols = _feed_cols(2048)
        # must not raise: the handler owns the failure (like the
        # single-device _drain_guarded, whose drain also aborts the
        # remaining endpoints of the failed drain call — healthy-endpoint
        # delivery after a poison is not promised on either path)
        rt.get_input_handler("S").send_columns(ts, cols, now=int(ts[-1]))
        assert caught and "poison" in str(caught[0])
        # the engine survives: a later send still reaches the router
        sends_before = rt.junctions["S"].fused_ingest.shard_router.sends
        rt.get_input_handler("S").send_columns(ts, cols, now=int(ts[-1]))
        assert rt.junctions["S"].fused_ingest.shard_router.sends > sends_before
        rt.shutdown()
        mgr.shutdown()

    def test_short_sends_fall_back_to_single_device(self, monkeypatch):
        monkeypatch.setenv("SIDDHI_TPU_SHARD", "8")
        # one micro-batch: M=1 < 2 devices — router declines, single-device
        # path owns the call, rows still delivered
        got, _s, router_state, _ = _run_stateless("", n=32)
        assert len(got["q2"]) == 32
        assert router_state["sends"] == 0

    def test_stateful_endpoints_not_routed(self, monkeypatch):
        monkeypatch.setenv("SIDDHI_TPU_SHARD", "8")
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime(
            "@app:batch(size='32')\n"
            "define stream S (symbol string, price float, volume long);\n"
            "@info(name='q') from S#window.length(8) "
            "select symbol, avg(price) as ap insert into Out;"
        )
        rt.start()
        fi = rt.junctions["S"].fused_ingest
        assert fi is None or getattr(fi, "shard_router", None) is None
        rt.shutdown()
        mgr.shutdown()

    def test_shardable_stateless_predicate(self):
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime(
            "@app:batch(size='32')\n"
            "define stream S (symbol string, price float, volume long);\n"
            "@info(name='stateless') from S[price > 1] "
            "select symbol insert into Out1;\n"
            "@info(name='windowed') from S#window.length(4) "
            "select symbol insert into Out2;\n"
            "@info(name='agg') from S "
            "select sum(volume) as tv insert into Out3;\n"
            "@info(name='limited') from S select symbol "
            "output every 5 events insert into Out4;"
        )
        assert shardable_stateless(rt.queries["stateless"])
        assert not shardable_stateless(rt.queries["windowed"])
        assert not shardable_stateless(rt.queries["agg"])
        assert not shardable_stateless(rt.queries["limited"])
        mgr.shutdown()

    def test_prometheus_shard_families(self, monkeypatch):
        monkeypatch.setenv("SIDDHI_TPU_SHARD", "8")
        _got, _s, router_state, prom = _run_stateless(
            "@app:statistics(reporter='none')\n", n=4096
        )
        assert router_state is not None
        assert "siddhi_shard_device_dispatches_total" in prom
        assert "siddhi_shard_device_events_total" in prom
        assert "siddhi_shard_device_occupancy" in prom
        assert 'device="7"' in prom

    def test_explain_renders_shard_counters(self, monkeypatch):
        monkeypatch.setenv("SIDDHI_TPU_SHARD", "8")
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime(
            STATELESS_QL.replace("{HEAD}", "@app:statistics(reporter='none')\n")
        )
        for s in SYMS:
            mgr.interner.intern(s)
        rt.start()
        ts, cols = _feed_cols(4096)
        rt.get_input_handler("S").send_columns(ts, cols, now=int(ts[-1]))
        plan = rt.explain(fmt="dict")
        snode = next(n for n in plan["nodes"] if n["id"] == "stream:S")
        assert "shard" in snode.get("counters", {}), snode
        text = rt.explain()
        assert "shard[devices=8]" in text
        rt.shutdown()
        mgr.shutdown()


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

    def test_annotation_axis_part_only_skips_batch_router(self, monkeypatch):
        monkeypatch.delenv("SIDDHI_TPU_SHARD", raising=False)
        _got, _s, router_state, _ = _run_stateless(
            "@app:shard(devices='8', axis='part')\n", n=2048
        )
        assert router_state is None  # batch axis not requested


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
