"""The hashed bucket index beside a large group-by key table (PR 41:
`ops/group.py` `empty_index`, `_probe_heads`, `_index_insert`,
`release_index`; `core/groupby.py` `CompiledGroupBy.probe`): where a table
has many slots for every row of its selector's flow, a row's slot is found
through `[NB, 128]` lanes of (key, slot) looked up once per segment head of
the sorted view, and not by `probe_table`'s sort of the whole table.

The two probes share one contract, "the slot of the key or -1": the table
with the index is held against the table without it slot for slot, step by
step; the engine with the shape rule engaged (a tiny batch under a table of
512 slots) against `tests/test_group_reclaim.py`'s row-by-row loop."""

from __future__ import annotations

import pickle

import numpy as np
import pytest

from tests.test_group_reclaim import (
    BATCH, EDGES, SELECTS, Run, edge_stream, expected, loop, rows_close, stream)

# the engine's cases: 16-row batches (a flow of 32) under 512 slots
SMALL, SLOTS = 16, 512


# ---- the table with the index against the table without ---------------------

class Tables:
    """Two key tables of `g` slots stepped side by side through
    `assign_slots`, the lane that counts rows and the release: one probed by
    the merge, one through its index. Every step holds the second to the
    first: slots, overflow, keys, `used`, the count of groups; and the index
    to its table: it holds exactly the used slots, each under its key, in
    its key's bucket."""

    def __init__(self, g: int, resets: bool = False, reclaim: bool = True):
        import jax
        import jax.numpy as jnp

        from siddhi_tpu.ops.group import (
            assign_slots, empty_index, free_stack, keyed_running_sum,
            release_index, release_slots)

        self.g = g
        table = {
            "keys": jnp.zeros((g,), jnp.int64), "used": jnp.zeros((g,), jnp.bool_),
            "n": jnp.zeros((), jnp.int32), "rows": jnp.zeros((g,), jnp.int32),
            "free": free_stack(g) if reclaim else None}
        self.merge, self.bucket = dict(table), dict(table, index=empty_index(g))

        @jax.jit
        def step(table, keys, sign, reset):
            new_keys, used, n, slot, grp, overflow = assign_slots(
                table["keys"], table["used"], table["n"], keys, sign != 0,
                reset=reset if resets else None, free=table["free"],
                index=table.get("index"))
            out = {"keys": new_keys, "used": used, "n": n,
                   "rows": table["rows"], "free": None}
            if reclaim:
                _, out["rows"] = keyed_running_sum(
                    sign, grp, table["rows"], rows=True)
                out["free"], out["n"], _ = release_slots(grp.free, n, grp)
                out["used"] = out["rows"] > 0
            if "index" in table:
                out["index"] = (release_index(grp.index, grp) if reclaim
                                else grp.index)
            return out, slot, overflow

        self._step = step

    def step(self, keys, sign, reset=None):
        """One flow: `sign` +1 a CURRENT row, -1 an EXPIRED one, 0 a row
        that carries no key. Returns the rows' slots."""
        import jax.numpy as jnp

        from siddhi_tpu.ops.group import bucket_of

        args = (jnp.asarray(keys, jnp.int64), jnp.asarray(sign, jnp.int32),
                jnp.asarray(np.zeros(len(keys), bool) if reset is None else reset))
        self.merge, slot_m, over_m = self._step(self.merge, *args)
        self.bucket, slot_b, over_b = self._step(self.bucket, *args)
        assert np.array_equal(slot_b, slot_m), (slot_b, slot_m)
        assert bool(over_b) == bool(over_m)
        for lane in ("keys", "used", "n", "rows"):
            assert np.array_equal(self.bucket[lane], self.merge[lane]), lane
        index = {k: np.asarray(v) for k, v in self.bucket["index"].items()}
        if not index["full"]:
            held = index["slot"] >= 0
            used = np.flatnonzero(np.asarray(self.merge["used"]))
            assert sorted(index["slot"][held].tolist()) == used.tolist()
            keys_held = (index["hi"].astype(np.int64) << 32) | index["lo"]
            assert np.array_equal(
                keys_held[held], np.asarray(self.merge["keys"])[index["slot"][held]])
            assert np.array_equal(
                np.nonzero(held)[0],
                bucket_of(keys_held[held], index["slot"].shape[0], np))
        return np.asarray(slot_m)

    @property
    def tiles(self) -> int:
        return int(self.bucket["index"]["tiles"])

    @property
    def full(self) -> bool:
        return bool(self.bucket["index"]["full"])


def churn(tables: Tables, b: int, steps: int, seed: int, resets: bool = False,
          expire: bool = True):
    """Random flows over a sliding range of keys, negative ones and 0 among
    them; an EXPIRED row only for a key that holds a row; some rows carry
    no key; a RESET now and then."""
    rng = np.random.default_rng(seed)
    held: dict = {}
    for step in range(steps):
        keys, sign, reset = [], [], []
        for _ in range(b):
            live = [k for k, c in held.items() if c > 0]
            r = rng.random()
            if resets and r < 0.04:
                keys.append(0), sign.append(0), reset.append(True)
                held = {}
            elif r < 0.12:
                keys.append(int(rng.integers(-5, 5))), sign.append(0)
                reset.append(False)
            elif live and expire and r < 0.55:
                k = int(rng.choice(live))
                held[k] -= 1
                keys.append(k), sign.append(-1), reset.append(False)
            else:
                k = int(rng.integers(3 * step - 3, 3 * step + 10))
                k = -k if rng.random() < 0.3 else k
                held[k] = held.get(k, 0) + 1
                keys.append(k), sign.append(1), reset.append(False)
        tables.step(keys, sign, reset)


@pytest.mark.parametrize("reclaim", [True, False], ids=["reclaims", "counts_up"])
@pytest.mark.parametrize("resets", [False, True], ids=["no_reset", "resets"])
@pytest.mark.parametrize("b, g", [(32, 12), (8, 24), (64, 700)],
                         ids=["B>G", "B<=G", "G>>B"])
def test_random_flows_slot_for_slot(b, g, resets, reclaim):
    tables = Tables(g, resets=resets, reclaim=reclaim)
    churn(tables, b, 25, seed=b + g, resets=resets, expire=reclaim)
    assert tables.tiles == 25 and not tables.full


def test_all_rows_one_key():
    tables = Tables(300)
    for _ in range(3):
        slot = tables.step([77] * 40, [1] * 40)
        assert (slot == slot[0]).all()
    assert tables.tiles == 3


@pytest.mark.parametrize("b, tile", [(64, 16), (100, 16), (20000, None)],
                         ids=["4_tiles", "a_tile_cut_short", "the_tile_as_it_is"])
def test_every_row_a_key_of_its_own_takes_several_tiles(monkeypatch, b, tile):
    """The trips follow the heads: B / T of them when every row brings a key
    of its own, one when the keys are few, none when no row carries a key."""
    from siddhi_tpu.ops import group

    if tile is not None:
        monkeypatch.setattr(group, "PROBE_TILE", tile)
    tile = group.PROBE_TILE
    tables = Tables(4 * b)
    keys = np.arange(b, dtype=np.int64) * 7919 - 3 * b
    slot = tables.step(keys, [1] * b)
    assert sorted(slot.tolist()) == list(range(b))
    assert tables.tiles == -(-b // tile)
    assert np.array_equal(tables.step(keys[::-1], [1] * b), slot[::-1])
    assert tables.tiles == 2 * -(-b // tile)
    tables.step(keys[:5].repeat(b // 5), [-1] * (b // 5 * 5))
    assert tables.tiles == 2 * -(-b // tile) + 1
    tables.step(keys[:7], [0] * 7)
    assert tables.tiles == 2 * -(-b // tile) + 1


def test_key_zero_and_negative_keys():
    """0 is a legal key and what an empty lane's words hold."""
    tables = Tables(200)
    keys = [0, -1, 1, -(1 << 62), (1 << 62), 0, -1, np.iinfo(np.int64).min]
    first = tables.step(keys, [1] * len(keys))
    assert first[0] == first[5] and first[1] == first[6]
    assert len(set(first.tolist())) == 6
    assert np.array_equal(tables.step(keys, [1] * len(keys)), first)


def test_inactive_rows_take_the_dead_lane_and_no_lane_of_the_index():
    tables = Tables(200)
    slot = tables.step([5, 6, 5, 9, 6], [1, 0, 1, 0, 1])
    assert slot[1] == 200 and slot[3] == 200 and slot[0] == slot[2]
    assert int((np.asarray(tables.bucket["index"]["slot"]) >= 0).sum()) == 2
    # 9 never came with a key: it is new when it does
    assert tables.step([9], [1])[0] not in (slot[0], slot[4])


def test_a_key_freed_and_inserted_again_in_later_steps():
    tables = Tables(200)
    a = tables.step([41, 42, 43], [1, 1, 1])
    tables.step([42], [-1])  # 42 empties: its slot and its lane are free
    assert int((np.asarray(tables.bucket["index"]["slot"]) >= 0).sum()) == 2
    b = tables.step([44, 42], [1, 1])
    assert b[0] == a[1]  # the slot on top of the stack
    assert b[1] not in a.tolist()
    assert np.array_equal(tables.step([41, 42, 43, 44], [1] * 4),
                          [a[0], b[1], a[2], b[0]])


def test_a_key_that_appears_and_empties_inside_one_step():
    tables = Tables(200)
    tables.step([1, 2], [1, 1])
    slot = tables.step([9, 9, 2, 9, 9], [1, 1, 1, -1, -1])
    assert slot[0] == slot[1] == slot[3] == slot[4]
    assert int(tables.bucket["n"]) == 2
    assert int((np.asarray(tables.bucket["index"]["slot"]) >= 0).sum()) == 2
    # its slot went back on the stack and is handed out again
    assert tables.step([10], [1])[0] == slot[0]


def test_capacity_overflow_goes_to_the_dead_lane_and_not_into_the_index():
    g = 6
    tables = Tables(g)
    slot = tables.step(list(range(100, 110)), [1] * 10)
    assert sorted(slot[:6].tolist()) == list(range(6)) and (slot[6:] == g).all()
    assert int((np.asarray(tables.bucket["index"]["slot"]) >= 0).sum()) == g
    assert not tables.full  # the table was full, no bucket was
    tables.step([100, 101], [-1, -1])
    again = tables.step([108, 109, 107], [1, 1, 1])
    assert sorted(again[:2].tolist()) == sorted(slot[:2].tolist()) and again[2] == g


# ---- a full bucket ------------------------------------------------------------

def colliding_keys(n: int, g: int, bucket: int = 3) -> np.ndarray:
    """`n` distinct keys that a table of `g` slots puts into one bucket."""
    from siddhi_tpu.ops.group import bucket_of, index_buckets

    cand = np.arange(1, 200 * n * index_buckets(g), dtype=np.int64)
    keys = cand[bucket_of(cand, index_buckets(g), np) == bucket][:n]
    assert len(keys) == n
    return keys


@pytest.mark.parametrize("resets", [False, True], ids=["no_reset", "a_reset_after"])
def test_a_full_bucket_loses_nothing(resets):
    """129 and more keys of one bucket: the 129th finds no lane, `full` is
    set and stays, and the table answers by the merge from then on, slot for
    slot (`keys` and `used` are the truth); a RESET, which empties the table
    and the index with it, clears the flag."""
    from siddhi_tpu.ops.group import INDEX_LANES

    g = 400
    keys = colliding_keys(INDEX_LANES + 40, g)
    tables = Tables(g, resets=resets)
    seen = {}
    for lo in range(0, len(keys), 24):
        part = keys[lo:lo + 24]
        slot = tables.step(part, [1] * len(part))
        seen.update(zip(part.tolist(), slot.tolist()))
        assert tables.full == (lo + 24 > INDEX_LANES)
    assert len(set(seen.values())) == len(keys)
    tiles = tables.tiles
    # every key is found where it was put, those without a lane too; groups
    # empty and come back; no tile is looked up any more
    assert np.array_equal(tables.step(keys[::-1], [1] * len(keys)),
                          [seen[k] for k in keys[::-1].tolist()])
    tables.step(keys[100:140].repeat(2), [-1] * 80)
    tables.step(keys[100:140], [1] * 40)
    churn(tables, 32, 6, seed=3)
    assert tables.full and tables.tiles == tiles
    if resets:
        reset = np.zeros(8, bool)
        reset[2] = True
        tables.step(keys[:8], [1, 1, 0, 1, 1, 1, 1, 1], reset)
        assert not tables.full and int(tables.bucket["n"]) == 5
        churn(tables, 32, 6, seed=4, resets=True)
        assert tables.tiles > tiles


def test_the_engine_over_a_full_bucket_answers_as_the_loop_and_says_so():
    keys = colliding_keys(150, SLOTS)
    n = 30 * SMALL  # the ring's 512 rows hold them all
    rng = np.random.default_rng(5)
    # time stands still, so no row leaves: every key stays alive
    auction = np.concatenate([keys, rng.choice(keys, n - len(keys))])
    t = np.full(n, 1_000, np.int64)
    v = np.ones(n, np.float32)
    run = Run("count() as num, sum(v) as total", capacity=SLOTS, batch=SMALL)
    try:
        for lo in range(0, n, 4 * SMALL):
            run.send(auction, t, v, lo, lo + 4 * SMALL)
        group = run.status()["group"]
    finally:
        run.close()
    assert run.out == [(a, c, s) for a, c, s in loop(auction, t, v)[0]]
    assert group["probe"] == "bucket" and group["index_overflow"] == 1
    assert group["index_max_fill"] == 128 and group["index_buckets"] == 8
    assert group["used"] == 150 and group["overflow_rows"] == 0
    assert not run.records


# ---- the engine with the shape rule engaged -------------------------------------

def test_the_shape_rule():
    from siddhi_tpu.ops.group import BUCKET_SLOTS_PER_ROW, index_buckets, probe_for

    assert probe_for(2_228_224, 65_536) == "bucket"      # nexmark-q5-hot-items
    assert probe_for(4_096, 65_536) == "merge"           # debs14-q1-plug, -time
    assert probe_for(4_096, 1_024) == "merge"            # their rehearsal
    assert probe_for(3_072, 1_024) == "merge"            # q5's rehearsal
    assert probe_for(1 << 30, None) == "merge"           # no flow known: no index
    assert probe_for(BUCKET_SLOTS_PER_ROW * 64, 64) == "bucket"
    assert probe_for(BUCKET_SLOTS_PER_ROW * 64 - 1, 64) == "merge"
    assert index_buckets(2_228_224) == 65_536 and index_buckets(SLOTS) == 8
    assert index_buckets(1) == 1


@pytest.mark.parametrize("select", sorted(SELECTS))
@pytest.mark.parametrize("path", ["per_batch", "fused", "restored"])
def test_churning_keys_through_the_index_as_the_loop(path, select):
    """`test_group_reclaim`'s stream and loop, with a batch so small under a
    table so large that the table keeps an index."""
    n = 1600
    auction, t, v = stream(11, n)
    run = Run(select, capacity=SLOTS, batch=SMALL)
    try:
        if path == "fused":
            run.send(auction, t, v, 0, n)
        else:
            # a send of one batch steps the query; one of four is a chunk
            send = SMALL if path == "per_batch" else BATCH
            for lo in range(0, n, send):
                if path == "restored" and lo == 10 * BATCH:
                    run.restart_from(run.rt.snapshot())
                run.send(auction, t, v, lo, lo + send)
        status = run.status()
        chunks = run.rt.junctions["Bid"].fused_ingest.chunks_dispatched
    finally:
        run.close()
    rows_close(run.out, expected(auction, t, v, select))
    group = status["group"]
    assert group["probe"] == "bucket" and group["reclaim"] == SELECTS[select][0]
    assert group["overflow_rows"] == 0 and not run.records
    assert group["used"] == loop(auction, t, v)[1][-1]
    assert group["index_overflow"] == 0 and group["index_buckets"] == 8
    assert 1 <= group["index_max_fill"] <= group["used"]
    # a pass of the time step looks up one tile, or none where it brought
    # no row; the counter starts again with a restored runtime
    assert 0 < group["probe_tiles"] <= 3 * n // SMALL
    assert bool(chunks) == (path != "per_batch")


@pytest.mark.parametrize("send", [SMALL, 3 * BATCH], ids=["per_batch", "fused"])
@pytest.mark.parametrize("case", EDGES)
def test_edges_of_emptying_and_returning_through_the_index(case, send):
    auction, t, v = edge_stream(case)
    run = Run("count() as num, sum(v) as total", capacity=SLOTS, batch=SMALL)
    try:
        for lo in range(0, len(t), send):
            run.send(auction, t, v, lo, lo + send)
        status = run.status()
    finally:
        run.close()
    want, live = loop(auction, t, v)
    assert run.out == [(a, c, s) for a, c, s in want]
    assert status["group"]["probe"] == "bucket"
    assert status["group"]["used"] == live[-1]
    assert status["group"]["overflow_rows"] == 0 and not run.records


def test_a_batch_window_resets_the_index_with_the_table():
    """`lengthBatch` flushes with a RESET, which empties table and index;
    a table large enough under it keeps an index too."""
    from siddhi_tpu import SiddhiManager

    mgr = SiddhiManager()
    out = []
    try:
        rt = mgr.create_siddhi_app_runtime(
            "@app:batch(size='8') @app:groupCapacity(size='256')\n"
            "define stream S (k long, v int);\n"
            "@info(name='q') from S#window.lengthBatch(6) select k, sum(v) as s "
            "group by k insert into O;")
        rt.add_callback("q", lambda ts, ins, removed: out.extend(
            tuple(e[1]) for e in ins))
        rt.start()
        k = np.arange(48, dtype=np.int64) % 5 + (np.arange(48) // 12) * 100
        for lo in range(0, 48, 8):
            rt.get_input_handler("S").send_columns(
                np.arange(lo, lo + 8, dtype=np.int64),
                {"k": k[lo:lo + 8], "v": np.ones(8, np.int32)})
        group = rt.snapshot_status()["queries"]["q"]["group"]
        rt.shutdown()
    finally:
        mgr.shutdown()
    want = []
    for lo in range(0, 48, 6):  # a flush emits each key's last row
        part = k[lo:lo + 6].tolist()
        want += [(key, part.count(key)) for key in dict.fromkeys(part)]
    assert sorted(out) == sorted(want)
    assert group["probe"] == "bucket" and group["index_overflow"] == 0
    assert group["used"] <= 5 and group["index_max_fill"] >= 1


# ---- snapshots ---------------------------------------------------------------------

def walk(tree, path=()):
    if isinstance(tree, dict):
        for k, v in tree.items():
            yield from walk(v, path + (k,))
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            yield from walk(v, path + (i,))
    else:
        yield path, tree


@pytest.mark.parametrize("layout", ["as_saved", "before_pr_40"])
def test_a_snapshot_holds_no_index_and_restores_with_one(layout):
    """The index is not part of a snapshot: `keys` and `used` are saved and
    the index is laid out from them, so a table saved by this tree, by PR
    40's (the same lanes) or by PR 39's (no stack either) restores, and the
    stream goes on as in an uninterrupted run."""
    n = 1600
    auction, t, v = stream(17, n)
    cut = 6 * BATCH
    run = Run("count() as num", capacity=SLOTS, batch=SMALL)
    try:
        for lo in range(0, cut, BATCH):
            run.send(auction, t, v, lo, lo + BATCH)
        assert "index" in run.rt.queries["q"].state["sel"]["group"]
        payload = pickle.loads(run.rt.snapshot())
        table = payload["elements"]["query:q"]["sel"]["group"]
        assert set(table) == {"keys", "used", "n", "free", "freed", "lost"}
        assert not any("index" in path for path, _ in walk(payload["elements"]))
        if layout == "before_pr_40":
            for key in ("free", "freed", "lost"):
                del table[key]
        run.restart_from(pickle.dumps(payload))
        index = run.rt.queries["q"].state["sel"]["group"]["index"]
        held = np.asarray(index["slot"])
        assert sorted(held[held >= 0].tolist()) == np.flatnonzero(
            table["used"]).tolist()
        for lo in range(cut, n, BATCH):
            run.send(auction, t, v, lo, lo + BATCH)
        status = run.status()
    finally:
        run.close()
    rows_close(run.out, expected(auction, t, v, "count() as num"))
    assert status["group"]["overflow_rows"] == 0
    assert status["group"]["index_overflow"] == 0
    assert status["group"]["used"] == loop(auction, t, v)[1][-1]


def test_a_restored_table_with_a_crowded_bucket_says_full():
    from siddhi_tpu.ops.group import INDEX_LANES, index_from_table

    g = 400
    keys = np.zeros(g, np.int64)
    used = np.zeros(g, bool)
    crowd = colliding_keys(INDEX_LANES + 1, g)
    keys[:len(crowd)], used[:len(crowd)] = crowd, True
    keys[300], used[300] = 0, True  # key 0 in a used slot
    index = index_from_table(keys, used)
    assert index["full"] and (index["slot"] >= 0).sum() == INDEX_LANES + 1
    # a leading axis passes through: the table short of one key of the
    # crowd, and the table of key 0 alone
    used[5] = False
    index = index_from_table(
        np.stack([keys, keys]), np.stack([used, np.arange(g) == 300]))
    assert index["slot"].shape == (2, 8, INDEX_LANES)
    assert index["full"].tolist() == [False, False]
    assert index["tiles"].tolist() == [0, 0]
    assert (index["slot"][0] >= 0).sum() == INDEX_LANES + 1
    assert index["slot"][1][index["slot"][1] >= 0].tolist() == [300]


@pytest.mark.parametrize("query", [
    "from S select k, count() as n group by k",
    "from S#window.length(4) select k, count() as n group by k",
    "partition with (k of S) begin from S#window.length(4) select k, "
    "count() as n group by v insert into O; end",
], ids=["no_window", "length", "partition"])
def test_small_tables_and_partitions_keep_no_index(query):
    """A table no larger than eight slots a row of its flow, and any table
    inside a partition, is probed by the merge and holds no index state."""
    from siddhi_tpu import SiddhiManager

    mgr = SiddhiManager()
    try:
        insert = "" if query.startswith("partition") else " insert into O"
        rt = mgr.create_siddhi_app_runtime(
            "@app:batch(size='16') @app:groupCapacity(size='100')\n"
            "define stream S (k int, v int);\n" + query + insert + ";")
        rt.start()
        i = np.arange(12, dtype=np.int32)
        rt.get_input_handler("S").send_columns(
            i.astype(np.int64), {"k": i % 3, "v": i})
        (qid, qr), = rt.queries.items()
        status = rt.snapshot_status()["queries"][qid]
        assert status.get("group", status.get("partition"))["probe"] == "merge"
        assert "index_buckets" not in status.get("group", {})
        assert not any("index" in path for path, _ in walk(qr.state))
        rt.shutdown()
    finally:
        mgr.shutdown()
