"""The plain reference of `debs14-q1-partition` (the smart-plug average over
each plug's own last 7,200 load records): against the definition worked out
row by row; against `debs14-q1-plug`'s reference on that configuration's
gap-free stream, where a window per plug and one window over all plugs hold
the same rows; against it and `debs14-q1-time`'s on this configuration's
stream, where they do not; its control; and `cost.py`'s byte counts from
shapes worked by hand."""

import json
from collections import deque

import numpy as np

import harness
from conftest import BENCH

CONFIG = "debs14-q1-partition"


def load(config=CONFIG):
    cdir = BENCH / "configs" / config
    return (harness.load_module(cdir / "gen.py"),
            harness.load_module(cdir / "reference.py"),
            json.loads((cdir / "config.json").read_text()))


def stream(config: str, seed: int, n: int):
    gen, ref, cfg = load(config)
    cols = gen.make(seed, n)
    ts = gen.timestamps(0, n)
    return ref, cfg, ts, gen.with_index(cols, 0, n, ts)


def gap(got, ref, floor=60.0):
    return float((np.abs(got - ref) / np.maximum(np.abs(ref), floor)).max())


SECONDS = 40  # of stream; the windows below are a few seconds long


def test_reference_in_steps_equals_the_whole_and_the_definition():
    """Carried along in steps of any length, emitting or not, the running
    reference gives what one pass over the whole stream gives, and what a
    `deque(maxlen=window_rows)` per plug gives row by row."""
    ref, cfg, ts, cols = stream(CONFIG, 5, SECONDS * 4250)
    sizes = {**cfg["sizes"], "window_rows": 6}
    whole = ref.reference(ts, cols, sizes)
    keep = ref.kept(cols)
    kts, kcols = ts[keep], {k: v[keep] for k, v in cols.items()}
    run, at, got = ref.Running(sizes), 0, []
    for step, emit in [(700, False), (5000, True), (1, True), (0, True),
                       (3333, False), (len(kts), True)]:
        upto = min(at + step, len(kts))
        out = run.step(kts[at:upto], {k: v[at:upto] for k, v in kcols.items()},
                       None, emit)
        assert (out is None) == (not emit)
        if emit:
            got.append((at, out["avgLoad"]))
        at = upto
    for lo, avg in got:
        assert np.array_equal(avg, whole["avgLoad"][lo:lo + len(avg)])
    held, want = {}, []
    for code, v in zip(ref.plug_code(kcols).tolist(), kcols["value"].tolist()):
        d = held.setdefault(code, deque(maxlen=6))
        d.append(v)
        want.append(sum(d) / len(d))
    assert gap(whole["avgLoad"], np.array(want)) < 1e-12
    assert whole["plug_id"].tolist() == kcols["plug_id"].tolist()
    assert whole["event_time"].tolist() == kts.tolist()


def test_on_the_gap_free_stream_it_equals_the_plug_reference():
    """Where every plug reports every second, each plug's last W load records
    and the last 2,125 x W load records of all plugs are the same rows: the
    two references are two formulations of one answer."""
    W = 5
    plug_ref, plug_cfg, ts, cols = stream("debs14-q1-plug", 3, SECONDS * 4250)
    _, part_ref, part_cfg = load()
    one = plug_ref.reference(ts, cols, {**plug_cfg["sizes"],
                                        "window_rows": 2125 * W})
    per = part_ref.reference(ts, cols, {**part_cfg["sizes"], "window_rows": W})
    assert set(one) == set(per)
    for lane in one:
        if lane != "avgLoad":
            assert np.array_equal(one[lane], per[lane]), lane
    assert gap(per["avgLoad"], one["avgLoad"]) < 1e-12


def test_on_its_own_stream_the_sibling_references_fail():
    """With readings missing, one window over all plugs (`debs14-q1-plug`)
    and the last W seconds (`debs14-q1-time`) are other rows than each
    plug's last W load records: put in this reference's place, either has
    to come out as not correct under the configuration's own limit."""
    W = 5
    ref, cfg, ts, cols = stream(CONFIG, 7, SECONDS * 4250)
    limit = cfg["compare"]["avgLoad"]["limit"]
    per = ref.reference(ts, cols, {**cfg["sizes"], "window_rows": W})
    _, plug_ref, plug_cfg = load("debs14-q1-plug")
    one = plug_ref.reference(ts, cols, {**plug_cfg["sizes"],
                                        "window_rows": 2125 * W})
    _, time_ref, time_cfg = load("debs14-q1-time")
    in_time = time_ref.reference(ts, cols, {**time_cfg["sizes"], "window_s": W})
    # while the windows fill, all three hold every row
    early = slice(0, 2125 * 2)
    assert gap(one["avgLoad"][early], per["avgLoad"][early]) < 1e-12
    assert gap(one["avgLoad"], per["avgLoad"]) > 100 * limit
    assert gap(in_time["avgLoad"], per["avgLoad"]) > 100 * limit


def test_the_control_fails_by_ten_times_the_limit():
    ref, cfg, ts, cols = stream(CONFIG, 9, SECONDS * 4250)
    sizes = {**cfg["sizes"], "window_rows": 6}
    sound = ref.reference(ts, cols, sizes)
    control = ref.reference(ts, cols, sizes, control=True)
    for lane in sound:
        if lane != "avgLoad":
            assert np.array_equal(sound[lane], control[lane])
    assert gap(control["avgLoad"], sound["avgLoad"]) > (
        10 * cfg["compare"]["avgLoad"]["limit"])


def test_cost_counts_bytes_from_shapes():
    cost = harness.load_module(BENCH / "configs" / CONFIG / "cost.py")
    _, _, cfg = load()
    # one send of 4,096 rows, half of them load records: a routed row is
    # 41 B (8 + 8 + 8 + 4 + 1 + 12), an emitted row 32 B (8 + 8 + 12 + 4)
    assert cost.ROW_IN == 41 and cost.ROW_OUT == 32
    assert cost.route_bytes(4096, 0.5) == 2 * 4096 * 41 + 2 * 2048 * 32
    # the windows: the load of each row entering and of each row leaving
    assert cost.window_bytes(4096, 0.5) == 2 * 2048 * 4
    assert cost.window_bytes_per_microbatch(cfg["sizes"], 0.5) == (
        2 * 16384 * 4)
    assert cost.bytes_per_send(cfg["sizes"], 4096, 0.5) == (
        4096 * 41 + cost.route_bytes(4096, 0.5) + cost.window_bytes(4096, 0.5)
        + 2 * 2048 * 12 + 2048 * 32)
    # a full micro-batch touches every plug once, not every row's
    assert cost.bytes_per_microbatch(cfg["sizes"], 9.9, 0.5) == (
        32768 * 41 + cost.route_bytes(32768, 0.5)
        + cost.window_bytes(32768, 0.5) + 2 * 2125 * 12 + 16384 * 32)


def test_the_fill_fills_every_plugs_window():
    """`fill_rows` load records bring every plug `window_rows` of its own,
    on any seed: the pool of 128 batches is replayed, so a plug misses the
    same seconds in every cycle."""
    gen, ref, cfg = load()
    sizes = cfg["sizes"]
    pool = 128 * sizes["batch"]
    for seed in (1, 2**31 + 5):
        cols = gen.make(seed, pool)
        load_rows = ref.plug_code(cols)[ref.kept(cols)]
        _, plug = np.unique(load_rows, return_inverse=True)
        per_cycle = np.bincount(plug)
        assert len(per_cycle) == sizes["plugs"]
        cycles, rest = divmod(sizes["fill_rows"], len(load_rows))
        sent = cycles * per_cycle + np.bincount(plug[:rest],
                                                minlength=sizes["plugs"])
        assert sent.min() >= sizes["window_rows"]
        assert sizes["state_rows"] == sizes["plugs"] * sizes["window_rows"]


def test_the_step_readers_scopes_and_the_rest_add_up_to_the_step(
        tmp_path, monkeypatch):
    """`part_scopes.device_ms_per_send` on the trace recorded on the chip in
    PR 23, its per-batch step standing in for the partitioned one: the time
    under a named scope and the time under none of `SCOPES` add up to the
    step's operations; a trace without the partitioned step reads None."""
    import gzip

    import part_scopes
    import program_spans
    import trace_reduce

    out = tmp_path / "bench_out" / "c" / "trace" / "plugins" / "profile" / "t"
    out.mkdir(parents=True)
    packed = BENCH / "tests" / "data" / "trickle_0p3s.xplane.pb.gz"
    (out / "t.xplane.pb").write_bytes(gzip.decompress(packed.read_bytes()))
    trace = trace_reduce.load(str(out / "t.xplane.pb"))
    cell = {"name": "c", "bench_dir": tmp_path / "benchmark",
            "config": {"stream": "S"}}
    spans = {"sends": np.zeros((12, 4))}
    assert part_scopes.device_ms_per_send(trace, spans, cell) is None
    assert part_scopes.device_ms_per_send(
        trace, spans, cell, "partition.route") is None

    monkeypatch.setattr(part_scopes, "STEP_PROGRAM", "jit__step_impl")
    monkeypatch.setattr(part_scopes, "SCOPES", ("jit(_where)", "scatter"))
    where = part_scopes.device_ms_per_send(trace, spans, cell, "jit(_where)")
    scatter = part_scopes.device_ms_per_send(trace, spans, cell, "scatter")
    rest = part_scopes.device_ms_per_send(trace, spans, cell)
    ex = trace_reduce.executions(trace, "jit__step_impl")
    dev = trace.devices[0]
    own = program_spans.exclusive_ns(dev.ops)
    k = np.searchsorted(ex[:, 0], dev.ops[:, 0], side="right") - 1
    inside = (k >= 0) & (dev.ops[:, 1] <= ex[np.maximum(k, 0), 1])
    whole = own[inside].sum() / 1e6 / 12
    assert where > 0 and scatter > 0 and rest > 0
    assert abs(where + scatter + rest - whole) < 1e-9 * whole
