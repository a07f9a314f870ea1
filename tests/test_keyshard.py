"""Key-sharded stateful scale-out (`@app:shard(axis='keys')`).

Non-partitioned group-by aggregation state is hashed across the mesh so
each device owns a DISJOINT key range; join window rings shard via
explicit GSPMD in/out shardings. The contract under test throughout:
keyed-shard emissions are byte-identical to the unsharded run — the
key-routed pre-pass masks rows to their owner, the positional psum fold
(floats bitcast to integer lanes first) reconstructs the exact output.

Reference: the cloud-native deployment framework's key-hash sharding of
detection state (PAPERS.md, arxiv 2401.09960).
"""

from __future__ import annotations

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.analysis import build_fusion_plan, compute_costs
from siddhi_tpu.parallel.keyshard import keyed_shardable, mix64, owner_of

SYMS = ["WSO2", "IBM", "GOOG", "MSFT", "ORCL", "AAPL", "AMZN", "NVDA"]

GB_QL = """@app:batch(size='64')
{HEAD}define stream S (symbol string, price float, volume long);
@info(name='q') from S select symbol, sum(volume) as sv, count() as c,
 min(volume) as mn group by symbol insert into Out;
"""

KEYS8 = "@app:shard(devices='8', axis='keys')\n"
KEYS4 = "@app:shard(devices='4', axis='keys')\n"


def _mgr():
    mgr = SiddhiManager()
    for s in SYMS:
        mgr.interner.intern(s)
    return mgr


def _feed(h, n, seed, base=1_700_000_000_000):
    rng = np.random.default_rng(seed)
    ts = np.arange(n, dtype=np.int64) + base
    cols = {
        "symbol": rng.integers(1, 9, size=n).astype(np.int32),
        "price": rng.uniform(0, 100, size=n).astype(np.float32),
        "volume": rng.integers(1, 1000, size=n).astype(np.int64),
    }
    h.send_columns(ts, cols, now=int(ts[-1]))


def _run(ql, names=("q",), feeds=1, shard=None, monkeypatch=None):
    if monkeypatch is not None:
        monkeypatch.setenv("SIDDHI_TPU_SHARD", shard or "0")
    mgr = _mgr()
    rt = mgr.create_siddhi_app_runtime(ql)
    got = {n: [] for n in names}
    for n in names:
        rt.add_callback(
            n,
            lambda ts, i, r, _n=n: got[_n].extend(
                tuple(e.data) for e in (i or [])
            ),
        )
    rt.start()
    for f in range(feeds):
        _feed(
            rt.get_input_handler("S"), 256, 5 + f,
            base=1_700_000_000_000 + f * 1_000,
        )
    return mgr, rt, got


class TestOwnerHash:
    def test_mix64_host_device_agree(self):
        import jax.numpy as jnp

        keys = np.arange(1, 257, dtype=np.uint64) * np.uint64(7919)
        host = mix64(keys)
        dev = np.asarray(mix64(jnp.asarray(keys)))
        assert (host == dev).all()

    def test_owner_partition_is_total_and_disjoint(self):
        keys = np.arange(4096, dtype=np.int64)
        own = owner_of(keys, 8)
        assert own.min() >= 0 and own.max() < 8
        # splitmix64 scrambles sequential ids off a single stripe
        counts = np.bincount(own, minlength=8)
        assert (counts > 0).all()


class TestEligibility:
    CASES = {
        "exact_ints": (
            "from S select symbol, sum(volume) as v, count() as c, "
            "max(volume) as hi group by symbol insert into Out;",
            True,
        ),
        "extreme_float": (
            "from S select symbol, min(price) as lo "
            "group by symbol insert into Out;",
            True,
        ),
        "avg_float": (
            "from S select symbol, avg(price) as ap "
            "group by symbol insert into Out;",
            False,
        ),
        "stddev_float": (
            "from S select symbol, stddev(price) as sd "
            "group by symbol insert into Out;",
            False,
        ),
        "sum_float": (
            "from S select symbol, sum(price) as sp "
            "group by symbol insert into Out;",
            False,
        ),
        "no_group": (
            "from S select symbol, sum(volume) as v insert into Out;",
            False,
        ),
        "windowed": (
            "from S#window.length(8) select symbol, sum(volume) as v "
            "group by symbol insert into Out;",
            False,
        ),
        "ordered": (
            "from S select symbol, sum(volume) as v group by symbol "
            "order by v insert into Out;",
            False,
        ),
    }

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_predicate(self, case):
        body, want = self.CASES[case]
        mgr = _mgr()
        rt = mgr.create_siddhi_app_runtime(
            "define stream S (symbol string, price float, volume long);\n"
            f"@info(name='q') {body}"
        )
        ok, why = keyed_shardable(rt.queries["q"])
        assert ok is want, (case, why)
        if not ok:
            assert why
        mgr.shutdown()

    def test_float_aggregators_reported_with_reason(self, monkeypatch):
        # reassociation-sensitive float arithmetic falls back single-device
        # AND still matches the unsharded run trivially
        ql = GB_QL.replace("{HEAD}", KEYS8).replace(
            "min(volume) as mn", "avg(price) as ap"
        )
        mgr, rt, got = _run(
            ql, shard="8", monkeypatch=monkeypatch
        )
        assert rt.queries["q"]._keyshard is None
        ks = rt.snapshot_status()["shard"]["keyshard"]["q"]
        assert ks["sharded"] is False
        assert "reassociation-sensitive" in ks["reason"]
        rt.shutdown()
        mgr.shutdown()


class TestGroupByParity:
    def test_byte_parity_and_occupancy(self, monkeypatch):
        mgr, rt, got = _run(
            GB_QL.replace("{HEAD}", KEYS8), feeds=4, shard="8",
            monkeypatch=monkeypatch,
        )
        qr = rt.queries["q"]
        assert qr._keyshard is not None
        desc = qr._keyshard.describe_state()
        status = rt.snapshot_status()
        rt.shutdown()
        mgr.shutdown()

        mgr2, rt2, got2 = _run(
            GB_QL.replace("{HEAD}", ""), feeds=4, shard="0",
            monkeypatch=monkeypatch,
        )
        rt2.shutdown()
        mgr2.shutdown()

        assert got["q"] and got["q"] == got2["q"]
        # per-device key ownership sums to the total key count
        assert desc["devices"] == 8 and desc["axis"] == "keys"
        assert sum(desc["per_device_keys"]) == desc["total_keys"] == 8
        assert len(desc["occupancy"]) == 8 and desc["skew"] >= 1.0
        placed = status["shard"]["keyshard"]["q"]
        assert placed["sharded"] is True and placed["devices"] == 8

    def test_prometheus_keyshard_families(self, monkeypatch):
        mgr, rt, _ = _run(
            GB_QL.replace(
                "{HEAD}", KEYS8 + "@app:statistics(reporter='none')\n"
            ),
            shard="8", monkeypatch=monkeypatch,
        )
        rt.snapshot_status()
        prom = mgr.prometheus_text()
        rt.shutdown()
        mgr.shutdown()
        assert "siddhi_keyshard_device_keys" in prom
        assert "siddhi_keyshard_occupancy" in prom
        assert "siddhi_keyshard_skew" in prom
        assert 'device="7"' in prom

    def test_explain_renders_keyshard(self, monkeypatch):
        mgr, rt, _ = _run(
            GB_QL.replace("{HEAD}", KEYS8), shard="8",
            monkeypatch=monkeypatch,
        )
        text = rt.explain()
        plan = rt.explain(fmt="dict")
        rt.shutdown()
        mgr.shutdown()
        assert "keyshard[devices=8 axis=keys" in text
        qnode = next(n for n in plan["nodes"] if n["id"] == "query:q")
        assert qnode["counters"]["keyshard"]["sharded"] is True


JOIN_QL = """@app:batch(size='64')
{HEAD}define stream S (symbol string, price float, volume long);
define stream B (symbol string, price float, volume long);
@info(name='j')
from S#window.length(8) join B#window.length(8)
 on S.symbol == B.symbol
select S.symbol as s, S.volume as av, B.volume as bv
insert into JOut;
"""


class TestJoinMesh:
    def test_join_parity_and_placement(self, monkeypatch):
        monkeypatch.setenv("SIDDHI_TPU_SHARD", "8")

        def run(head):
            mgr = _mgr()
            rt = mgr.create_siddhi_app_runtime(
                JOIN_QL.replace("{HEAD}", head)
            )
            got = []
            rt.add_callback(
                "j",
                lambda ts, i, r: got.extend(
                    tuple(e.data) for e in (i or [])
                ),
            )
            rt.start()
            _feed(rt.get_input_handler("S"), 256, 3)
            _feed(rt.get_input_handler("B"), 256, 4,
                  base=1_700_000_000_300)
            armed = bool(getattr(rt.queries["j"], "_joinshard", False))
            status = rt.snapshot_status()
            rt.shutdown()
            mgr.shutdown()
            return got, armed, status

        sharded, armed, status = run(KEYS8)
        monkeypatch.setenv("SIDDHI_TPU_SHARD", "0")
        plain, armed0, _ = run("")
        assert armed and not armed0
        assert sharded and sharded == plain
        placed = status["shard"]["joins"]["j"]
        assert placed["sharded"] is True
        assert placed["sharded_leaves"] > 0


class TestSnapshotRebalance:
    @pytest.mark.parametrize("route", ["8->4", "8->0", "0->8", "8->8"])
    def test_restore_across_mesh_sizes(self, route, monkeypatch):
        src, dst = route.split("->")

        def run(shard, snap=None):
            monkeypatch.setenv("SIDDHI_TPU_SHARD", shard)
            head = (
                f"@app:shard(devices='{shard}', axis='keys')\n"
                if shard != "0" else ""
            )
            mgr, rt, got = _run(GB_QL.replace("{HEAD}", head), feeds=0)
            if snap is None:
                _feed(rt.get_input_handler("S"), 256, 5)
                out = rt.snapshot()
            else:
                rt.restore(snap)
                got["q"].clear()
                _feed(rt.get_input_handler("S"), 256, 6,
                      base=1_700_000_001_000)
                out = None
            res = list(got["q"])
            rt.shutdown()
            mgr.shutdown()
            return res, out

        _, snap = run(src)
        _, snap0 = run("0")
        control, _ = run("0", snap=snap0)
        cont, _ = run(dst, snap=snap)
        assert cont and cont == control, route


FUSE_QL = """@app:batch(size='64')
{HEAD}define stream S (symbol string, price float, volume long);
@info(name='f1') from S[price > 10] select symbol, volume insert into F1;
@info(name='q') from S select symbol, sum(volume) as sv
 group by symbol insert into Out;
"""


class TestFusedOnMesh:
    """A key-sharded query is a member of its junction's fused group: the
    chunk program runs its shard_map step on the keys mesh, on the same
    [D] state the per-batch path steps."""

    def test_planner_names_no_keyshard_hazard(self):
        plan = build_fusion_plan(FUSE_QL.replace("{HEAD}", KEYS8))
        assert "keyshard-state" not in {b["hazard"] for b in plan.blockers}
        assert "q" not in {b["query"] for b in plan.blockers}
        group = next(g for g in plan.groups if g["stream"] == "S")
        assert group["queries"] == ["f1", "q"]
        # the plan says where the group's chunk program runs
        assert group["mesh"] == {"devices": 8, "axis": "keys"}
        plan2 = build_fusion_plan(FUSE_QL.replace("{HEAD}", ""))
        assert "mesh" not in plan2.groups[0]

    @pytest.mark.parametrize("devices", [2, 4, 8])
    def test_fused_run_keeps_query_sharded_with_parity(
        self, devices, monkeypatch
    ):
        monkeypatch.setenv("SIDDHI_TPU_FUSE", "1")
        head = f"@app:shard(devices='{devices}', axis='keys')\n"
        mgr, rt, got = _run(
            FUSE_QL.replace("{HEAD}", head), names=("f1", "q"),
            feeds=2, shard=str(devices), monkeypatch=monkeypatch,
        )
        assert rt.queries["q"]._keyshard is not None
        status = rt.snapshot_status()
        text = rt.explain()
        rt.shutdown()
        mgr.shutdown()
        pipe = status["streams"]["S"]["pipeline"]
        assert pipe["enabled"] is True and pipe["mesh_devices"] == devices
        placed = status["shard"]["keyshard"]["q"]
        assert placed["sharded"] is True and placed["path"] == "fused"
        assert sum(placed["per_device_keys"]) == placed["total_keys"] == 8
        assert f"mesh_devices={devices}" in text

        mgr2, rt2, got2 = _run(
            FUSE_QL.replace("{HEAD}", ""), names=("f1", "q"),
            feeds=2, shard="0", monkeypatch=monkeypatch,
        )
        pipe2 = rt2.snapshot_status()["streams"]["S"]["pipeline"]
        rt2.shutdown()
        mgr2.shutdown()
        assert pipe2["enabled"] is True and pipe2["mesh_devices"] == 1
        assert got["q"] and got == got2

    @staticmethod
    def _sends(head, sizes, monkeypatch, shard):
        """One app, one `send_columns` per entry of `sizes`; what the two
        callbacks got, and the key-sharded query's path after each send."""
        monkeypatch.setenv("SIDDHI_TPU_SHARD", shard)
        mgr, rt, got = _run(
            FUSE_QL.replace("{HEAD}", head), names=("f1", "q"), feeds=0,
        )
        paths = []
        for f, n in enumerate(sizes):
            _feed(rt.get_input_handler("S"), n, 11 + f,
                  base=1_700_000_000_000 + f * 10_000)
            ks = rt.queries["q"]._keyshard
            paths.append(ks.path if ks is not None else None)
        rt.shutdown()
        mgr.shutdown()
        return got, paths

    def test_chunk_plus_residual_rows_match_the_unsharded_run(
        self, monkeypatch
    ):
        # 2 * B + 17 rows: two full micro-batches and a short third
        sizes = [2 * 64 + 17]
        got, paths = self._sends(KEYS4, sizes, monkeypatch, "4")
        plain, _ = self._sends("", sizes, monkeypatch, "0")
        assert paths == ["fused"]
        assert len(got["q"]) == sizes[0] and got == plain

    def test_fused_and_per_batch_sends_step_one_state(self, monkeypatch):
        # a chunk, then rows below 2 * B through `receive`, then a chunk:
        # both paths read and write the same [D] state
        sizes = [5 * 64 + 17, 30, 2 * 64, 64 + 1, 4 * 64]
        got, paths = self._sends(KEYS4, sizes, monkeypatch, "4")
        plain, _ = self._sends("", sizes, monkeypatch, "0")
        assert paths == ["fused", "batch", "fused", "batch", "fused"]
        assert len(got["q"]) == sum(sizes) and got == plain

    @pytest.mark.parametrize("dst", ["2", "0"])
    def test_snapshot_after_fused_send_restores_on_a_smaller_mesh(
        self, dst, monkeypatch
    ):
        def run(shard, snap=None):
            monkeypatch.setenv("SIDDHI_TPU_SHARD", shard)
            head = (
                f"@app:shard(devices='{shard}', axis='keys')\n"
                if shard != "0" else ""
            )
            mgr, rt, got = _run(GB_QL.replace("{HEAD}", head), feeds=0)
            if snap is None:
                _feed(rt.get_input_handler("S"), 256, 5)
                out = rt.snapshot()
            else:
                rt.restore(snap)
                got["q"].clear()
                # a chunk, then a per-batch send, on the restored state
                _feed(rt.get_input_handler("S"), 256, 6,
                      base=1_700_000_001_000)
                _feed(rt.get_input_handler("S"), 40, 7,
                      base=1_700_000_002_000)
                out = None
            ks = rt.queries["q"]._keyshard
            path = ks.path if ks is not None else None
            res = list(got["q"])
            rt.shutdown()
            mgr.shutdown()
            return res, out, path

        _, snap, path = run("4")
        assert path == "fused"
        _, snap0, _ = run("0")
        control, _, _ = run("0", snap=snap0)
        cont, _, _ = run(dst, snap=snap)
        assert cont and cont == control


PAD_QL = """@app:batch(size='64')
@app:partitionCapacity(size='6')
{HEAD}define stream S (symbol string, price float, volume long);
partition with (symbol of S)
begin
    @info(name='p')
    from S[price > 0]#window.length(8)
    select symbol, sum(volume) as total
    insert into POut;
end;
"""


class TestPartitionPadding:
    def test_capacity_6_on_8_device_mesh(self, monkeypatch):
        # 6 % 8 != 0: the [P] axis pads to 8 with dead slots; overflow
        # drops (8 live symbols > 6 logical slots) behave IDENTICALLY to
        # the unsharded run because padded lanes never receive a key
        monkeypatch.setenv("SIDDHI_TPU_SHARD", "8")
        mgr, rt, got = _run(PAD_QL.replace("{HEAD}", KEYS8), names=("p",))
        placed = rt.snapshot_status()["shard"]["partitioned"]["p"]
        rt.shutdown()
        mgr.shutdown()

        monkeypatch.setenv("SIDDHI_TPU_SHARD", "0")
        mgr2, rt2, got2 = _run(PAD_QL.replace("{HEAD}", ""), names=("p",))
        rt2.shutdown()
        mgr2.shutdown()

        # the placed record names the partition mesh's own axis ("part")
        # even when the app requested keys — keys = partition mesh + keyed
        # state arming
        assert placed == {
            "sharded": True, "devices": 8, "axis": "part",
            "local_slots": 1, "padded_slots": 2,
        }
        assert got["p"] == got2["p"]


class TestWireHintCosts:
    def test_declared_range_narrows_state_and_wire(self):
        # satellite: with NO value analysis, declared @app:wire range
        # hints size window state lanes and wire rows at proven widths
        base = """
        define stream S (sym string, vol long);
        @info(name='q') from S[vol > 1000]#window.length(64)
        select sym, sum(vol) as v insert into Out;
        """
        hinted = "@app:wire(range.S.vol='0..30000')\n" + base
        m0 = compute_costs(base)
        m1 = compute_costs(hinted)
        # wire row narrows by 6 bytes (int64 -> int16 vol lane: 0..30000
        # fits the declared 16-bit range encoding)
        assert m1.streams["S"].wire_row_bytes == \
            m0.streams["S"].wire_row_bytes - 6
        win = {
            o.op: o for o in m1.queries["q"].operators
        }.get("window:length")
        lanes = {t.lane: t for t in win.tensors}
        vol = next(v for k, v in lanes.items() if k.endswith(".vol"))
        assert vol.dtype == "int32"
        # filter selectivity refines off the declared interval: vol > 1000
        # over [0, 30000] keeps ~29/30 of rows, not the flat default
        f1 = next(o for o in m1.queries["q"].operators if o.op == "filter")
        f0 = next(o for o in m0.queries["q"].operators if o.op == "filter")
        assert f1.est_selectivity != f0.est_selectivity
        assert f1.est_selectivity > 0.9
