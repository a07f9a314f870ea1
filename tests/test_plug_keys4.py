"""The benchmark's four-chip deployment `debs14-plug-keys4` (the smart-plug
group-by key-sharded over the keys mesh, on the fused ingest path) held on
the 8-device virtual CPU mesh: its rehearsal, its reference against the
engine, its generator against the one-chip plug configuration's, the chunk
program of the standing configurations (unchanged by the mesh path), and a
compile of the sharded chunk program for a described v5e:2x2."""

from __future__ import annotations

import hashlib
import json
import os
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "benchmark"
for p in (str(ROOT), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402  (benchmark/harness.py)
from tests.test_group_segment_read import flow_gathers  # noqa: E402

CONFIGS = BENCH / "configs"
KEYS4 = "debs14-plug-keys4"


def load(config: str):
    cdir = CONFIGS / config
    return (harness.load_module(cdir / "gen.py"),
            harness.load_module(cdir / "reference.py"),
            json.loads((cdir / "config.json").read_text()))


def deploy(config: str, batch: int, callback=None, rehearse: bool = True):
    """The configuration's app at `batch`, started, with a query callback;
    its other sizes the rehearsal's, or the configuration's own."""
    from siddhi_tpu import SiddhiManager

    gen, _, cfg = load(config)
    small = cfg.get("rehearse_sizes", {}) if rehearse else {}
    sizes = {**cfg["sizes"], **small, "batch": batch}
    text = (CONFIGS / config / "app.siddhi").read_text().format(**sizes)
    mgr = SiddhiManager()
    for names in gen.STRINGS.values():
        for s in names:
            mgr.interner.intern(s)
    rt = mgr.create_siddhi_app_runtime(text)
    rt.add_callback(cfg["query"], callback or (lambda ts, ins, removed: None))
    rt.start()
    return mgr, rt, gen, cfg


def chunk_program(rt, gen, cfg, batch: int):
    """(fused ingest engine, its deliver-mode chunk program) with the wire
    chosen from the configuration's own rows, nothing sent."""
    from siddhi_tpu.core.wire import choose_encodings

    fi = rt.junctions[cfg["stream"]].fused_ingest
    n = -(-batch // getattr(gen, "CYCLE_ROWS", 1)) * getattr(gen, "CYCLE_ROWS", 1)
    cols = gen.make(7, n)  # first: the time stream's seconds come from its pool
    ts = gen.timestamps(0, n)
    index = {c: np.arange(1, len(v) + 1, dtype=np.int32)
             for c, v in gen.STRINGS.items()}
    cols = {k: (index[k][v] if k in index else v) for k, v in cols.items()}
    cols = gen.with_index(cols, 0, n, ts)
    fi._narrow = choose_encodings(
        fi.junction.schema, fi._compute_keep(), fi.wire_spec, fi.wire_enabled,
        ts[:batch], {k: np.asarray(v)[:batch] for k, v in cols.items()})
    fi._build(deliver_set=frozenset({0}))
    return fi, fi._fused_deliver


def chunk_arguments(fi, K: int = 32, place=None):
    """Shapes of one call of the chunk program; `place` = (state sharding,
    replicated) puts them on a mesh."""
    import jax
    import jax.numpy as jnp

    st_sh, repl = place or (None, None)

    def shape(l, sh):
        return jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=sh)

    state = jax.eval_shape(lambda: fi.endpoints[0].init_state(0))
    return (
        (jax.tree_util.tree_map(lambda l: shape(l, st_sh), state),), {},
        jax.ShapeDtypeStruct((K, fi._wire_bytes), jnp.uint8, sharding=repl),
        jax.ShapeDtypeStruct((K,), jnp.int32, sharding=repl),
        jax.ShapeDtypeStruct((K,), jnp.int64, sharding=repl),
        jax.ShapeDtypeStruct((), jnp.int64, sharding=repl),
    )


def lowered(config: str, rehearse: bool = True):
    """(`jit_fused` of `config` lowered, without debug metadata; the status of
    its query), at its rehearse sizes or at the configuration's own, nothing
    sent; a key-sharded deployment on the virtual CPU mesh."""
    _, _, cfg = load(config)
    batch = (cfg["rehearse_sizes"] if rehearse else cfg["sizes"])["batch"]
    mgr, rt, gen, cfg = deploy(config, batch, rehearse=rehearse)
    try:
        fi, prog = chunk_program(rt, gen, cfg, batch)
        place = None
        if fi._mesh_place is not None:
            place = (fi._mesh_place[0][0], fi._mesh_place[1])  # one endpoint
        text = prog.lower(*chunk_arguments(fi, place=place)).as_text()
        return text, rt.snapshot_status()["queries"][cfg["query"]]
    finally:
        rt.shutdown()
        mgr.shutdown()


def lowered_text(config: str) -> str:
    return lowered(config)[0]


# sha256 of `lowered_text`: the chunk program of the standing configurations
# (computed on a checkout of the commit named with this very function). The
# mesh path may not move the one-chip ones: an app without @app:shard takes
# the same `jax.jit(fused, donate_argnums=(0,))`. A PR that changes a chunk
# program on purpose replaces its hash, and knows by that that the cell's
# device time may have moved. The two windowless programs are as the parents
# of PR 26 and PR 27 lowered them; PR 27 replaced the plug program's
# (51b48512...: its window's ring now holds its 64-bit lanes as u32 pairs).
# PR 29 replaced both plug programs' (f999fb96..., 99929b79...): the group-by
# reads its per-group values once per segment of its sorted view, so
# `assign_slots` and the keyed running lanes lower without their per-row
# gathers (ops/group.py). The filter has no group-by and kept its hash.
# PR 33 replaced all three (fddb6ddd..., 233fdbcf..., 222eae96...): the
# deliver pack, which every one of them ends with, places each micro-batch's
# rows by shifted reads and one run per micro-batch where it scattered every
# 32-bit word of the chunk's output rows (core/ingest.py `_build`).
# PR 39 replaced both plug programs' (4e35c5a0..., 07b60a94...): `assign_slots`
# finds a row's slot by a sort-merge with the key table where it compared
# every row with every slot (ops/group.py `probe_table`). The filter has no
# group-by and kept its hash: the control.
# PR 40 replaced the plug program's (7a92bb52...): its group-by stands behind
# the window, so its key table takes a slot back when its group's last row
# has left (ops/group.py `free_stack`, `release_slots`), with `avg`'s count
# lane as the table's count of rows. The key-sharded program has no window
# ahead and the filter no group-by: both kept their hash. The same PR added
# `nexmark-q5-hot-items`, the first table whose keys come and go.
# PR 43 replaced none and added `debs14-load-rise-pattern`, the first chunk
# program that holds a pattern's NFA step (core/pattern.py `apply_batch_fast`,
# its tokens found by key): the four above hold no pattern.
STANDING_PROGRAMS = {
    "debs14-load-rise-pattern": "e6f145513d5b21943458c961435d5d28f9aa8f21415f9647832f6497f1e3e89d",
    "debs14-q1-plug": "b63ddfe821c0da2206bbfa1c0dde441375f003a43321677cd12b12fa4603dd16",
    "nexmark-q5-hot-items": "8deedbf12f5b611c70ef6c18f63178542c853dab6eaacbb9a67c4e35513b2836",
    "siddhi-simple-filter": "e57dc6766097200e83d5fedc19d003b611a6acb4532be6940bfab8eaf418132b",
    KEYS4: "50b02c340189d849666e40535fed124dacc5d30d5911035482b1c44bd9e4ef2b",
}


# PR 41 replaced no hash above: a table is probed through a bucket index only
# where it has eight slots or more for every row of its selector's flow
# (ops/group.py `probe_for`), and every rehearsal's table is smaller than that
# (q5's: 3,072 slots for a flow of 1,024 rows), so the standing programs, the
# rehearsal of `nexmark-q5-hot-items` among them, lower as the parent's did.
# The one program that changed is q5's at its own sizes (2,228,224 slots for
# 65,536 rows), pinned here (the parent's: 001fd4ec...).
Q5_AT_SIZE = "96bba447e1b2f1e74cfc6eee2e5079e10dce10f4f4c0c4b184ba41e3237782c7"


def test_q5_at_its_own_sizes_lowers_through_the_bucket_index():
    text, status = lowered("nexmark-q5-hot-items", rehearse=False)
    assert status["group"]["probe"] == "bucket"
    assert hashlib.sha256(text.encode()).hexdigest() == Q5_AT_SIZE


@pytest.mark.parametrize("config", sorted(STANDING_PROGRAMS))
def test_standing_chunk_programs_lower_as_before(config):
    text = lowered_text(config)
    on_mesh = config == KEYS4
    assert ("sharding" in text and "all_reduce" in text) == on_mesh
    assert hashlib.sha256(text.encode()).hexdigest() == STANDING_PROGRAMS[config]


@pytest.mark.parametrize("config", ["debs14-q1-plug", KEYS4])
def test_plug_programs_read_per_group_values_by_segment(config):
    """At the configuration's own sizes the group-by gathers nothing per row
    of its flow (the batch, or CURRENT + EXPIRED behind the window): what is
    left at that length is the wire decode's dictionary lookup. Where the
    flow is no longer than the table, as at the rehearsal's sizes, a row
    reads for itself."""
    _, _, cfg = load(config)
    for rehearse, want in ((False, "segment"), (True, "row")):
        text, status = lowered(config, rehearse=rehearse)
        batch = (cfg["rehearse_sizes"] if rehearse else cfg["sizes"])["batch"]
        # the plug table stands behind the window and takes its slots back
        # by avg's count of rows; the key-sharded one has no window ahead
        assert status["group"] == {
            "capacity": cfg["sizes"]["group_capacity"], "carry_read": want,
            "probe": "merge",
            "reclaim": "none" if config == KEYS4 else "count_lane"}
        assert (batch > cfg["sizes"]["group_capacity"]) == (want == "segment")
        left = flow_gathers(text, {batch, 2 * batch})
        if want == "segment":
            assert left == [f"{batch}xui8"], left
        else:
            assert len(left) == 3 and f"{batch}xui8" in left, left


def pair_tensors(stablehlo: str, sizes) -> list:
    """The tensor types of a lowered program that have one element for every
    pair of a token and a row of a micro-batch, or of two rows: T x B (the
    match matrix `apply_batch_fast` built for every state before PR 43, T x C
    for its chunk C = min(B, T // 2), which is B at these sizes) or B x B,
    in any order and with further axes."""
    import re

    T, B = sizes["tokens"], sizes["batch"]
    pairs = {T * B, B * B}
    found = set()
    for m in re.finditer(r"tensor<((?:\d+x)+)\w+>", stablehlo):
        dims = [int(d) for d in m.group(1).rstrip("x").split("x")]
        if any(a * b in pairs for i, a in enumerate(dims) for b in dims[i + 1:]):
            found.add(m.group(0))
    return sorted(found)


def test_the_pattern_program_holds_nothing_of_tokens_by_rows():
    """At `debs14-load-rise-pattern`'s own sizes (163,840 tokens, micro-batches
    of 32,768 rows) the chunk program holds no tensor of T x B, T x C or
    B x B elements: every state finds its tokens by key, the `every` state
    filters rows alone, and the status says so. What is sorted is T + B
    long; the longest lanes are the pack's."""
    _, _, cfg = load("debs14-load-rise-pattern")
    sizes = cfg["sizes"]
    text, status = lowered("debs14-load-rise-pattern", rehearse=False)
    assert status["pattern"]["match"] == "keyed"
    assert status["pattern"]["token_capacity"] == sizes["tokens"]
    # two matches a row of a micro-batch, not the table
    assert status["pattern"]["emit_capacity"] == 2 * sizes["batch"]
    assert pair_tensors(text, sizes) == []
    merged = sizes["tokens"] + sizes["batch"]
    assert f"tensor<{merged}xi32>" in text
    assert [n for _, n in long_sorts(text.splitlines(), merged)] == []
    # the scan finds the matrix where there is one: the same app with the
    # key taken out of its second state
    from siddhi_tpu import SiddhiManager
    import jax

    app = (CONFIGS / "debs14-load-rise-pattern" / "app.siddhi").read_text()
    for attr in ("house_id", "household_id", "plug_id"):
        app = app.replace(f"{attr} == e1.{attr}", f"{attr} <= e1.{attr}")
    small = {**sizes, "tokens": 2048, "batch": 1024}
    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime(app.format(**small))
    try:
        qr = rt.queries[cfg["query"]]
        assert rt.snapshot_status()["queries"][cfg["query"]]["pattern"][
            "match"] == "matrix"
        dense = jax.jit(qr._make_step("Plug")).lower(
            jax.eval_shape(lambda: qr._fresh(qr.init_state(0))), {},
            rt.junctions["Plug"].schema.empty_batch(1024),
            np.int64(0)).as_text()
        assert "tensor<2048x1024xi1>" in pair_tensors(dense, small)
    finally:
        rt.shutdown()
        mgr.shutdown()


def table_slots(sizes) -> int:
    return sizes.get("group_capacity", sizes.get("partition_capacity"))


def dense_probes(stablehlo: str, sizes) -> list:
    """The tensor types of a lowered program that have one element per pair
    of a flow's row and a key table's slot: `[B, G]`, or `[2B, G]` behind a
    window, as `assign_slots`' compare made them before PR 39."""
    import re

    flows = {n * sizes["batch"] for n in (1, 2)}
    table = table_slots(sizes)
    return sorted({
        m.group(0) for m in re.finditer(r"tensor<(\d+)x(\d+)x\w+>", stablehlo)
        if int(m.group(1)) in flows and int(m.group(2)) == table})


def lowered_partition_step(config: str) -> tuple:
    """(`jit__pstep_outer_impl` of `config` lowered at the configuration's
    own sizes, the status of its query): nothing sent, no state made."""
    import jax
    import jax.numpy as jnp

    _, _, cfg = load(config)
    mgr, rt, gen, cfg = deploy(config, cfg["sizes"]["batch"], rehearse=False)
    try:
        qr = rt.queries[cfg["query"]]
        shapes = jax.eval_shape(lambda: (
            qr.partition_runtime.ptable, qr._fresh(qr.init_state()),
            {"extra_passes": jnp.zeros((), jnp.int64),
             "max_rows": jnp.zeros((), jnp.int32)},
            rt.junctions[cfg["stream"]].schema.empty_batch(cfg["sizes"]["batch"]),
            jnp.zeros((), jnp.int64)))
        return (qr._pstep_outer.lower(*shapes).as_text(),
                rt.snapshot_status()["queries"][cfg["query"]])
    finally:
        rt.shutdown()
        mgr.shutdown()


@pytest.mark.parametrize("config", [
    "debs14-q1-plug", "debs14-q1-time", KEYS4, "debs14-q1-partition",
    "nexmark-q5-hot-items"])
def test_no_program_compares_every_row_with_every_slot(config):
    """At the configurations' own sizes no program holds a tensor with a
    flow's rows along one axis and the key table's slots along the other
    (65,536 x 4,096 behind a window, 32,768 x 4,096 without; 65,536 x
    2,228,224 where the table takes its slots back): the probe is a
    sort-merge, and the status says so. The one table with many slots for
    every row of its flow, `nexmark-q5-hot-items`' (34 a row), is probed
    through its bucket index instead (PR 41), and its program sorts nothing
    longer than a few flows: the merge's two sorts of B + G = 2,293,760 rows
    are behind a `cond` that a full bucket alone takes."""
    _, _, cfg = load(config)
    if "partition_capacity" in cfg["sizes"]:
        text, status = lowered_partition_step(config)
        assert status["partition"]["probe"] == "merge"
    else:
        text, status = lowered(config, rehearse=False)
        assert status["group"]["probe"] == (
            "bucket" if config == "nexmark-q5-hot-items" else "merge")
    assert dense_probes(text, cfg["sizes"]) == []
    # the merged sort is there, B + G rows long (2B + G behind a window)
    merged = {n * cfg["sizes"]["batch"] + table_slots(cfg["sizes"]) for n in (1, 2)}
    assert any(f"tensor<{n}xi64>" in text for n in merged)
    if status.get("group", {}).get("probe") == "bucket":
        flow = 2 * cfg["sizes"]["batch"]
        assert "tensor<65536x128xi32>" in text  # the index: NB x 128 >= 2 G
        lines = text.splitlines()
        long = long_sorts(lines, 4 * flow)
        assert [n for _, n in long] == [flow + table_slots(cfg["sizes"])] * 2
        assert all(any(lo < at < hi for lo, hi in cond_regions(lines))
                   for at, _ in long)


def long_sorts(lines: list, rows: int) -> list:
    """(line, length) of the sorts of a lowered program that take more than
    `rows` rows."""
    import re

    found = []
    for at, line in enumerate(lines):
        if '"stablehlo.sort"(' in line:
            # the comparator's region closes with the operands' types
            close = next(i for i in range(at, len(lines))
                         if lines[i].lstrip().startswith("}) : ("))
            n = int(re.search(r"\(tensor<(\d+)x", lines[close]).group(1))
            if n > rows:
                found.append((at, n))
    return found


def cond_regions(lines: list) -> list:
    """(first line, last line) of every `stablehlo.case` of a lowered
    program, branches included."""
    regions = []
    for at, line in enumerate(lines):
        if '"stablehlo.case"(' in line:
            indent = line[:len(line) - len(line.lstrip())]
            regions.append((at, next(
                i for i in range(at + 1, len(lines))
                if lines[i].startswith(indent + "}) : ("))))
    return regions


def test_the_scan_for_dense_probes_finds_the_matrix_where_there_is_one():
    import jax

    from tests.test_group_segment_read import REF, init, make_batch, step

    sizes = {"batch": 2048, "group_capacity": 128}
    bt = make_batch(np.random.default_rng(39), 2 * sizes["batch"], 100)
    text = jax.jit(lambda s, x: step(REF, s, x)[:2]).lower(init(128), bt).as_text()
    assert "tensor<4096x128xi1>" in dense_probes(text, sizes)


def test_both_plug_configurations_generate_one_stream():
    gen4, ref4, cfg4 = load(KEYS4)
    gen1, ref1, cfg1 = load("debs14-q1-plug")
    for seed in (5, 2**31 + 77):
        n = 3 * gen1.CYCLE_ROWS
        a, b = gen4.make(seed, n), gen1.make(seed, n)
        assert sorted(a) == sorted(b)
        for k in a:
            assert a[k].dtype == b[k].dtype and np.array_equal(a[k], b[k]), k
        ts = gen4.timestamps(17, 17 + n)
        assert np.array_equal(ts, gen1.timestamps(17, 17 + n))
        ia, ib = (g.with_index(c, 17, 17 + n, ts) for g, c in ((gen4, a), (gen1, b)))
        assert all(np.array_equal(ia[k], ib[k]) for k in ib)
        assert np.array_equal(ref4.kept(ia), ref1.kept(ib))
    assert gen4.CYCLE_ROWS == gen1.CYCLE_ROWS and gen4.STRINGS == gen1.STRINGS
    for k in ("plugs", "houses", "batch", "group_capacity"):
        assert cfg4["sizes"][k] == cfg1["sizes"][k]


def test_reference_by_hand_and_in_steps():
    gen, ref, cfg = load(KEYS4)
    plug = np.array([0, 1, 0, 0, 1, 0], dtype=np.int32)
    cols = {
        "ts": np.arange(6, dtype=np.int64),
        "value": np.array([10, 20, 99, 5, 40, 50], dtype=np.float32),
        "property": np.array([1, 1, 0, 1, 1, 1], dtype=bool),
        "plug_id": plug, "household_id": np.zeros(6, np.int32), "house_id": plug,
    }
    out = ref.reference(np.arange(6, dtype=np.int64) * 1000, cols, {"houses": 2})
    assert out["event_time"].tolist() == [0, 1000, 3000, 4000, 5000]
    assert out["plug_id"].tolist() == [0, 1, 0, 1, 0]
    assert out["maxLoad"].tolist() == [10.0, 20.0, 10.0, 40.0, 50.0]
    assert out["n"].tolist() == [1, 1, 2, 2, 3]
    assert out["maxLoad"].dtype == np.float32 and out["n"].dtype == np.int64
    # carried along in steps of any length, emitting or not, it gives what
    # one pass gives; negative and zero loads keep their order
    n = 4 * gen.CYCLE_ROWS
    ts = gen.timestamps(0, n)
    cols = gen.with_index(gen.make(11, n), 0, n, ts)
    cols["value"] = cols["value"] - np.float32(30.0)
    whole = ref.reference(ts, cols, cfg["sizes"])
    keep = ref.kept(cols)
    kts, kcols = ts[keep], {k: v[keep] for k, v in cols.items()}
    run, at = ref.Running(cfg["sizes"]), 0
    for step, emit in [(700, False), (3000, True), (1, True), (2222, False),
                       (len(kts), True)]:
        upto = min(at + step, len(kts))
        out = run.step(kts[at:upto], {k: v[at:upto] for k, v in kcols.items()},
                       None, emit)
        for lane, values in (out or {}).items():
            assert np.array_equal(values, whole[lane][at:upto]), lane
        at = upto
    code = ref.plug_code(kcols)
    for i in (0, 2125, 2126, len(kts) - 1):
        mine = code[:i + 1] == code[i]
        assert whole["maxLoad"][i] == kcols["value"][:i + 1][mine].max()
        assert whole["n"][i] == mine.sum()


def test_control_in_bfloat16_fails_maxload_alone():
    gen, ref, cfg = load(KEYS4)
    n = 12 * gen.CYCLE_ROWS
    ts = gen.timestamps(0, n)
    cols = gen.with_index(gen.make(2_900_000_001, n), 0, n, ts)
    want = ref.reference(ts, cols, cfg["sizes"])
    control = ref.reference(ts, cols, cfg["sizes"], control=True)
    for name, rule in cfg["compare"].items():
        assert rule["limit"] == 0
        assert harness.lane_gap(want[name], want[name], rule) == 0
        broken = harness.lane_gap(control[name], want[name], rule)
        assert (broken > 0.9 * len(want[name])) if name == "maxLoad" else broken == 0


def test_engine_against_the_reference_at_batch_512():
    """Every emission of a seeded stream, fused sends and a per-batch one,
    lane for lane against the configuration's NumPy reference."""
    got = []
    mgr, rt, gen, cfg = deploy(
        KEYS4, 512, lambda ts, ins, removed: got.extend(ins or []))
    _, ref, _ = load(KEYS4)
    n = 10 * gen.CYCLE_ROWS
    ts = gen.timestamps(0, n)
    cols = gen.with_index(gen.make(2**31 + 12345, n), 0, n, ts)
    handler = rt.get_input_handler(cfg["stream"])
    paths = []
    for lo, hi in ((0, 64 * 512), (64 * 512, 64 * 512 + 300), (64 * 512 + 300, n)):
        handler.send_columns(ts[lo:hi], {k: v[lo:hi] for k, v in cols.items()})
        paths.append(rt.queries[cfg["query"]]._keyshard.path)
    status = rt.snapshot_status()
    rt.shutdown()
    mgr.shutdown()
    assert paths == ["fused", "batch", "fused"]
    placed = status["shard"]["keyshard"][cfg["query"]]
    assert placed["sharded"] is True and placed["devices"] == 4
    assert placed["total_keys"] == cfg["sizes"]["plugs"]
    assert status["streams"][cfg["stream"]]["pipeline"]["mesh_devices"] == 4
    want = ref.reference(ts, cols, cfg["sizes"])
    assert len(got) == len(want["event_time"]) == n // 2
    assert np.array_equal([e[0] for e in got], want["event_time"])
    for k, name in enumerate(cfg["outputs"]):
        lane = np.array([e[1][k] for e in got])
        assert np.array_equal(lane, want[name]), name


def test_new_readers_find_nothing_in_a_trace_without_the_mesh(tmp_path):
    """The cell's per-layer readers on the chip trace the benchmark keeps of
    an older tree (no chunk program, no span, no scope): each returns None
    and none raises, which is what the parent's side of a check needs."""
    import gzip

    import trace_reduce

    out = tmp_path / "bench_out" / "old.cell" / "trace" / "plugins" / "profile" / "t"
    out.mkdir(parents=True)
    packed = BENCH / "tests" / "data" / "trickle_0p3s.xplane.pb.gz"
    (out / "t.xplane.pb").write_bytes(gzip.decompress(packed.read_bytes()))
    trace = trace_reduce.load(str(out / "t.xplane.pb"))
    cell = {"name": "old.cell", "bench_dir": tmp_path / "benchmark",
            "config": {"stream": "S", "query": "q"}, "sizes": {},
            "config_dir": CONFIGS / KEYS4}
    spans = {"sends": np.zeros((12, 4))}
    counters = {"status": {"streams": {"S": {}}}}
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    mine = [m for m in manifest["per_layer"]
            if m.get("workloads") == ["plug-keys4.bulk"]]
    assert len(mine) == 20
    assert {m["moves"] for m in mine} == {"events_per_s.filter"}
    for m in mine:
        reader = harness.load_module(harness.reader_file(BENCH, m["name"]))
        assert reader.read(trace, spans, counters, cell) is None, m["name"]


def rehearse(capsys):
    import run as bench_run

    rc = bench_run.main(
        ["--workload", "plug-keys4.bulk", "--seed", str(2**31 + 26),
         "--seconds", "1.5", "--trace", "0", "--rehearse"])
    out = capsys.readouterr().out
    assert rc == 0, out
    return json.loads(out.strip().splitlines()[-1]), out


def test_rehearsal_of_the_four_chip_cell_is_correct(capsys):
    result, out = rehearse(capsys)
    assert result["correct"] is True and result["failed"] == 0, out
    assert result["attempted"] > 0 and result["metrics"] == {}
    assert result["device"]["platform"] == "cpu"
    for line in ("shard.devices = 4 (expected 4)",
                 "shard.keyshard.load_peak.sharded = True (expected True)",
                 "shard.keyshard.load_peak.total_keys = 2125 (expected 2125)",
                 "streams.Plug.pipeline.enabled = True (expected True)",
                 "streams.Plug.pipeline.chunk_batches = 32 (expected 32)",
                 "compared maxLoad.gap = 0.0 (limit 0)",
                 "compared n.gap = 0.0 (limit 0)"):
        assert line in out, line


def test_rehearsal_is_not_correct_when_route_and_merge_disagree(
        capsys, monkeypatch):
    """`owner_of` read twice per step, once to route and once to merge: let
    the second reading name the next device, and rows come back from a
    device that masked them away."""
    import siddhi_tpu.parallel.keyshard as keyshard

    real, calls = keyshard.owner_of, [0]

    def disagreeing(keys, n_devices):
        calls[0] += 1
        own = real(keys, n_devices)
        return own if calls[0] % 2 else (own + 1) % n_devices

    monkeypatch.setattr(keyshard, "owner_of", disagreeing)
    result, out = rehearse(capsys)
    assert calls[0] >= 2 and calls[0] % 2 == 0
    assert result["correct"] is False, out


# ---- the sharded chunk program, compiled for the chip it is measured on ----

@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def compile_for(topo, batch: int):
    """The deployment's deliver-mode `jit_fused` at `batch`, K = 32, with
    its keys mesh made of the described chips: (compiled, engine)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from siddhi_tpu.parallel.keyshard import KeyShardedGroupExec

    # such a compile can be written to the persistent cache but not read back
    # without a chip: keep it out
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    mgr, rt, gen, cfg = deploy(KEYS4, batch)
    try:
        qr = rt.queries[cfg["query"]]
        assert qr._keyshard is not None and qr.state is None
        qr._keyshard = KeyShardedGroupExec(qr, topo.devices)
        fi, prog = chunk_program(rt, gen, cfg, batch)
        assert fi._mesh_devices() == 4
        place = (fi._mesh_place[0][0], fi._mesh_place[1])  # one endpoint
        return prog.lower(*chunk_arguments(fi, place=place)).compile(), fi
    finally:
        rt.shutdown()
        mgr.shutdown()
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        compilation_cache.reset_cache()


def test_sharded_chunk_program_compiles_for_v5e_2x2(topo):
    compiled, fi = compile_for(topo, 2048)
    text = compiled.as_text()
    # the merge is the program's only traffic between chips
    assert "all-reduce" in text
    for scope in ("keyshard.route", "keyshard.exchange", "selector",
                  "wire_decode", "deliver_pack"):
        assert f"/{scope}" in text, scope
    mem = compiled.memory_analysis()
    per_device = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                  + mem.temp_size_in_bytes)
    assert 0 < per_device < 1e9


@pytest.mark.slow
def test_sharded_chunk_program_compiles_at_the_cell_size(topo):
    compiled, fi = compile_for(topo, 32768)  # about two minutes
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes < 1e9
