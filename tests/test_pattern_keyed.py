"""The pattern's batch kernel against a row-by-row loop (PR 43): a state
whose condition ties the arriving row to a capture by equality finds its
tokens by key (`core/pattern.py` `_match_keyed`), and the matches that one
event completes come in the order in which their first events arrived, on the
keyed, matrix, count and scan paths. The loop below is the reference's
semantics written out (`benchmark/tests/data/configs/t-pattern/reference.py`
is its two-state form): per row, the pending matches of the last state are
tried first, oldest first, then the earlier states', and the row is held as
a first event last, so that no row completes what it started itself."""

from __future__ import annotations

import json
import logging
import pickle
import sys
from pathlib import Path

import numpy as np
import pytest

import siddhi_tpu.core.pattern as pattern_mod
from siddhi_tpu import SiddhiManager

ROOT = Path(__file__).resolve().parents[1]
T0 = 1_700_000_000_000
STEP_MS = 5

HEAD = """
@app:batch(size='{batch}')
@app:patternCapacity(size='{tokens}')
define stream S (id long, k int, g int, v float);
@info(name='q')
"""
F32 = np.float32

# name -> (query text, [predicate of state p: (row, captures so far) -> bool],
#          within in ms or None, what the status has to say of `match`)
PATTERNS = {
    "capture_residual": (
        "from every e1=S[v > 20.0] -> e2=S[k == e1.k and v >= e1.v + 30.0] "
        "within 1 sec select e1.id as a, e2.id as b, e1.v as v1, e2.v as v2 "
        "insert into Out;",
        [lambda r, c: r["v"] > F32(20.0),
         lambda r, c: r["k"] == c[0]["k"] and r["v"] >= c[0]["v"] + F32(30.0)],
        1000, "keyed"),
    "row_residual": (
        "from every e1=S[v > 90.0] -> e2=S[k == e1.k and v < 10.0] "
        "within 1 sec select e1.id as a, e2.id as b, e1.v as v1, e2.v as v2 "
        "insert into Out;",
        [lambda r, c: r["v"] > F32(90.0),
         lambda r, c: r["k"] == c[0]["k"] and r["v"] < F32(10.0)],
        1000, "keyed"),
    "two_key_attributes": (
        "from every e1=S[v > 60.0] -> e2=S[g == e1.g and e1.k == k and v < e1.v - 40.0] "
        "within 1 sec select e1.id as a, e2.id as b, e1.v as v1, e2.v as v2 "
        "insert into Out;",
        [lambda r, c: r["v"] > F32(60.0),
         lambda r, c: (r["g"] == c[0]["g"] and r["k"] == c[0]["k"]
                       and r["v"] < c[0]["v"] - F32(40.0))],
        1000, "keyed"),
    "three_states_keyed": (
        "from every e1=S[v > 70.0] -> e2=S[k == e1.k and v < e1.v - 30.0] "
        "-> e3=S[k == e2.k and g == e1.g and v > e2.v + 20.0] "
        "within 1 sec select e1.id as a, e3.id as b, e2.id as m, e3.v as v3 "
        "insert into Out;",
        [lambda r, c: r["v"] > F32(70.0),
         lambda r, c: r["k"] == c[0]["k"] and r["v"] < c[0]["v"] - F32(30.0),
         lambda r, c: (r["k"] == c[1]["k"] and r["g"] == c[0]["g"]
                       and r["v"] > c[1]["v"] + F32(20.0))],
        1000, "keyed"),
    "key_on_second_hop_only": (
        "from every e1=S[v > 97.0] -> e2=S[v < e1.v - 90.0] "
        "-> e3=S[k == e1.k and v > e2.v] "
        "within 300 milliseconds select e1.id as a, e3.id as b, e2.id as m, e3.v as v3 "
        "insert into Out;",
        [lambda r, c: r["v"] > F32(97.0),
         lambda r, c: r["v"] < c[0]["v"] - F32(90.0),
         lambda r, c: r["k"] == c[0]["k"] and r["v"] > c[1]["v"]],
        300, "matrix"),
    "no_equality": (
        "from every e1=S[v > 97.0] -> e2=S[v < e1.v - 95.0] "
        "within 400 milliseconds select e1.id as a, e2.id as b, e1.v as v1, e2.v as v2 "
        "insert into Out;",
        [lambda r, c: r["v"] > F32(97.0),
         lambda r, c: r["v"] < c[0]["v"] - F32(95.0)],
        400, "matrix"),
    "no_within": (
        "from every e1=S[v > 99.0] -> e2=S[k == e1.k and g == e1.g and v < 1.0] "
        "select e1.id as a, e2.id as b, e1.v as v1, e2.v as v2 "
        "insert into Out;",
        [lambda r, c: r["v"] > F32(99.0),
         lambda r, c: (r["k"] == c[0]["k"] and r["g"] == c[0]["g"]
                       and r["v"] < F32(1.0))],
        None, "keyed"),
}


def stream(seed: int, n: int, keys: int = 8) -> dict:
    rng = np.random.default_rng(seed)
    return {"id": np.arange(n, dtype=np.int64),
            "k": rng.integers(0, keys, n).astype(np.int32),
            "g": rng.integers(0, 2, n).astype(np.int32),
            "v": np.round(rng.uniform(0, 100, n), 3).astype(np.float32)}


def times(n: int) -> np.ndarray:
    return T0 + np.arange(n, dtype=np.int64) * STEP_MS


class Loop:
    """The pattern row by row. `feed` takes one batch and returns its
    emissions (the captures of each completed match, in order); the counters
    are the engine's: a pending match whose `within` has run out by the
    batch's last row is let go at the batch's end."""

    def __init__(self, preds, within):
        self.preds, self.within = preds, within
        self.pending = []  # [state it waits at, start time, captures]
        self.armed = self.completed = self.expired = self.max_row = 0

    def feed(self, ts, cols) -> list:
        out = []
        last = len(self.preds) - 1
        names = list(cols)
        for i, t in enumerate(ts.tolist()):
            row = {n: cols[n][i] for n in names}
            emitted = 0
            for p in range(last, 0, -1):
                for tok in [x for x in self.pending if x[0] == p]:
                    if self.within is not None and t - tok[1] > self.within:
                        continue
                    if not self.preds[p](row, tok[2]):
                        continue
                    tok[2] = tok[2] + [row]
                    tok[0] = p + 1
                    if p == last:
                        out.append((t, tok[2]))
                        self.pending.remove(tok)
                        emitted += 1
            # the tokens that moved wait at their new state from the next row
            if self.preds[0](row, []):
                self.pending.append([1, t, [row]])
                self.armed += 1
            self.max_row = max(self.max_row, emitted)
        self.completed += len(out)
        if self.within is not None and len(ts):
            old = [x for x in self.pending if ts[-1] - x[1] > self.within]
            self.expired += len(old)
            self.pending = [x for x in self.pending if x not in old]
        return out

    def counters(self) -> dict:
        return {"tokens": len(self.pending), "armed": self.armed,
                "completed": self.completed, "expired": self.expired,
                "max_emits_per_row": self.max_row, "overflow": 0}


def deploy(name: str, batch: int, tokens: int, force_scan: bool = False):
    """(manager, runtime, the rows its query callback receives). The steps
    are built when the runtime is and, for the fused path, with its first
    long send: `FORCE_SCAN` stays set until the test ends (`_no_forced_scan`)."""
    text = HEAD.format(batch=batch, tokens=tokens) + PATTERNS[name][0]
    pattern_mod.FORCE_SCAN = force_scan
    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime(text)
    got = []
    rt.add_callback(
        "q", lambda ts, ins, rem: got.extend((e[0], *e[1]) for e in ins or []))
    rt.start()
    return mgr, rt, got


def send(rt, ts, cols, lo, hi):
    rt.get_input_handler("S").send_columns(
        ts[lo:hi], {n: c[lo:hi] for n, c in cols.items()})


def owed(name: str, emissions: list) -> list:
    """The loop's emissions as the query selects them."""
    three = len(PATTERNS[name][1]) == 3
    rows = []
    for t, caps in emissions:
        first, final = caps[0], caps[-1]
        if three:
            rows.append((t, int(first["id"]), int(final["id"]),
                         int(caps[1]["id"]), float(final["v"])))
        else:
            rows.append((t, int(first["id"]), int(final["id"]),
                         float(first["v"]), float(final["v"])))
    return rows


def received(got: list) -> list:
    return [(t, int(a), int(b), float(F32(c)) if isinstance(c, float) else int(c),
             float(F32(d))) for t, a, b, c, d in got]


def status_of(rt) -> dict:
    return rt.snapshot_status()["queries"]["q"]["pattern"]


@pytest.fixture(autouse=True)
def _no_forced_scan():
    yield
    pattern_mod.FORCE_SCAN = False


# (pattern, path, batch, tokens, rows per send): the per-batch path takes a
# send below two batches, the fused path a longer one; T x B of the keyed
# cases is 16 to 64 times the 128 x 128 that a matrix chunk of such a table
# would be cut to
CASES = [
    ("capture_residual", "per_batch", 1024, 2048, 1024),
    ("capture_residual", "fused", 512, 2048, 2048),
    ("row_residual", "fused", 512, 1024, 2048),
    ("two_key_attributes", "fused", 512, 2048, 1024),
    ("three_states_keyed", "fused", 1024, 2048, 2048),
    ("three_states_keyed", "per_batch", 2048, 4096, 2048),
    ("key_on_second_hop_only", "fused", 256, 512, 1024),
    ("no_equality", "per_batch", 256, 512, 256),
    ("no_within", "fused", 512, 1024, 1024),
    ("capture_residual", "scan", 64, 1024, 256),
]


@pytest.mark.parametrize("name, path, batch, tokens, per_send", CASES,
                         ids=[f"{c[0]}-{c[1]}" for c in CASES])
def test_engine_against_the_loop(name, path, batch, tokens, per_send):
    """Every emission, in order, and the table's counters after every send:
    the residual reading the capture, a row-only residual, two key
    attributes, three states with the key on both hops (several hops inside
    one batch), a key on the second hop only, `within` running out between
    and inside batches, no `within` at all."""
    _, preds, within, match = PATTERNS[name]
    n = 4 * per_send
    cols, ts = stream(43, n), times(n)
    mgr, rt, got = deploy(name, batch, tokens, force_scan=path == "scan")
    loop = Loop(preds, within)
    try:
        want = []
        for lo in range(0, n, per_send):
            send(rt, ts, cols, lo, lo + per_send)
            for b in range(lo, lo + per_send, batch):
                want += owed(name, loop.feed(
                    ts[b:b + batch], {k: c[b:b + batch] for k, c in cols.items()}))
            assert received(got) == want
            st = status_of(rt)
            if path != "scan":  # the scan lets a token go at the next arrival
                assert {k: st[k] for k in loop.counters()} == loop.counters()
        assert len(want) > 20
        assert st["match"] == ("scan" if path == "scan" else match)
        assert st["token_capacity"] == tokens
        fused = rt.snapshot_status()["streams"]["S"].get("pipeline", {})
        # the engine starts its drain thread with the first fused send
        assert bool(fused.get("drain_thread")) == (path != "per_batch")
        # three states: some match took all its hops inside one batch
        if len(preds) == 3 and path != "scan":
            assert any(a // batch == b // batch for _, a, b, *_ in want)
    finally:
        rt.shutdown()
        mgr.shutdown()


def emissions_of(text: str, cols, ts, per_send: int, force_scan: bool):
    pattern_mod.FORCE_SCAN = force_scan
    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime(text)
    got = []
    rt.add_callback(
        "q", lambda t, ins, rem: got.extend((e[0], *e[1]) for e in ins or []))
    rt.start()
    try:
        for lo in range(0, len(ts), per_send):
            send(rt, ts, cols, lo, lo + per_send)
        return got, status_of(rt)
    finally:
        rt.shutdown()
        mgr.shutdown()
        pattern_mod.FORCE_SCAN = False


ONE_ROW_MANY = {
    # every pending first event of the key goes with one row
    "keyed": "from every e1=S[v > 50.0] -> e2=S[k == e1.k and v < 2.0] "
             "select e1.id as a, e2.id as b insert into Out;",
    # every pending first event at all goes with one row
    "matrix": "from every e1=S[v > 80.0] -> e2=S[v < 2.0] "
              "select e1.id as a, e2.id as b insert into Out;",
    # every generation whose count is full goes with one row
    "count": "from every e1=S[v > 60.0]<2:> -> e2=S[v < 2.0] "
             "select e1[0].id as a, e2.id as b insert into Out;",
}


@pytest.mark.parametrize("path", sorted(ONE_ROW_MANY))
def test_matches_of_one_row_come_in_the_order_of_their_first_events(path):
    """One row completes many pending matches: they are delivered in the
    order in which their first events arrived, on the keyed, the matrix and
    the count kernel, and the per-event scan (`FORCE_SCAN`, the batch
    kernels' differential oracle) delivers the very same rows."""
    text = HEAD.format(batch=256, tokens=1024) + ONE_ROW_MANY[path]
    n = 2048
    cols, ts = stream(7, n, keys=3), times(n)
    got, st = emissions_of(text, cols, ts, 512, force_scan=False)
    oracle, scan_st = emissions_of(text, cols, ts, 512, force_scan=True)
    assert st["match"] == path and scan_st["match"] == "scan"
    assert st["overflow"] == 0 and scan_st["overflow"] == 0
    assert got == oracle
    firsts = np.array([a for _, a, _ in got])
    seconds = np.array([b for _, _, b in got])
    assert (np.diff(seconds) >= 0).all()
    same = np.diff(seconds) == 0
    assert same.sum() > 50                     # rows that completed several
    assert (np.diff(firsts)[same] > 0).all()   # oldest first event first
    assert st["max_emits_per_row"] == np.bincount(seconds).max() > 3
    assert scan_st["max_emits_per_row"] == st["max_emits_per_row"]


@pytest.mark.parametrize("layout", ["new", "before_pr43"])
def test_a_restored_table_goes_on_where_it_stood(layout):
    """A snapshot taken between sends, restored into a fresh runtime: the
    layout this PR writes, and PR 42's (no `seq`, no `head`, no counters, the
    tokens in whatever lanes were free: shuffled here)."""
    name, batch, tokens, per_send = "capture_residual", 512, 2048, 1024
    _, preds, within, _ = PATTERNS[name]
    n = 4 * per_send
    cols, ts = stream(11, n), times(n)
    loop = Loop(preds, within)
    want = []
    for b in range(0, n, batch):
        want += owed(name, loop.feed(
            ts[b:b + batch], {k: c[b:b + batch] for k, c in cols.items()}))
    mgr, rt, got = deploy(name, batch, tokens)
    send(rt, ts, cols, 0, per_send)
    send(rt, ts, cols, per_send, 2 * per_send)
    snap = rt.snapshot()
    before = status_of(rt)
    rt.shutdown()
    mgr.shutdown()
    if layout == "before_pr43":
        payload = pickle.loads(snap)
        tok = payload["elements"]["query:q"]["tok"]
        for k in ("seq", "next_seq", "head", "armed", "expired", "max_row",
                  "completed", "refused"):
            del tok[k]
        lanes = np.random.default_rng(3).permutation(tokens)
        payload["elements"]["query:q"]["tok"] = {
            k: (np.asarray(x)[lanes] if k != "caps" else [
                {"n": np.asarray(c["n"])[lanes], "ts": np.asarray(c["ts"])[lanes],
                 "cols": {a: np.asarray(v)[lanes] for a, v in c["cols"].items()}}
                for c in x])
            for k, x in tok.items()}
        snap = pickle.dumps(payload)
    mgr2, rt2, got2 = deploy(name, batch, tokens)
    try:
        rt2.restore(snap)
        st = status_of(rt2)
        assert st["tokens"] == before["tokens"] > 50
        if layout == "new":
            assert st == before
        send(rt2, ts, cols, 2 * per_send, 3 * per_send)
        send(rt2, ts, cols, 3 * per_send, n)
        assert received(got) + received(got2) == want
        assert status_of(rt2)["overflow"] == 0
    finally:
        rt2.shutdown()
        mgr2.shutdown()


@pytest.mark.parametrize("short_of", ["lanes", "room"])
def test_overflow_is_counted_and_logged(short_of, caplog):
    """A table too small for the tokens a batch arms, and an emission buffer
    too small for the matches one row completes: what is refused is counted
    in the status and logged as an ERROR, once."""
    if short_of == "lanes":
        # 512 rows arm some 400 tokens into 64 lanes
        text = HEAD.format(batch=512, tokens=64) + PATTERNS["capture_residual"][0]
        cols, ts = stream(5, 1024), times(1024)
    else:
        # one row of key 0 completes 600 pending matches; the buffer holds
        # two for every row of a 128-row batch
        text = HEAD.format(batch=128, tokens=4096) + (
            "from every e1=S[v > 50.0] -> e2=S[k == e1.k and v < 1.0] "
            "select e1.id as a, e2.id as b insert into Out;")
        n = 768
        cols = {"id": np.arange(n, dtype=np.int64), "k": np.zeros(n, np.int32),
                "g": np.zeros(n, np.int32), "v": np.full(n, 60.0, np.float32)}
        cols["v"][700] = 0.5
        ts = times(n)
    with caplog.at_level(logging.ERROR, logger="siddhi_tpu"):
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime(text)
        got = []
        rt.add_callback("q", lambda t, ins, rem: got.extend(ins or []))
        rt.start()
        try:
            for lo in range(0, len(ts), 256):
                send(rt, ts, cols, lo, lo + 256)
            rt.queries["q"].flush_aux_warnings()
            st = status_of(rt)
        finally:
            rt.shutdown()
            mgr.shutdown()
    logged = [r for r in caplog.records if "pattern token table" in r.getMessage()]
    assert len(logged) == 1 and logged[0].levelno == logging.ERROR
    assert st["overflow"] > 0
    if short_of == "room":
        assert st["emit_capacity"] == 256
        assert st["overflow"] == 700 - 256 and len(got) == 256
        assert st["armed"] == 767 and st["completed"] == 256
    else:
        assert st["armed"] + st["overflow"] > 64


def test_a_pattern_without_an_equality_keeps_the_matrix():
    """No conjunct ties the row to a capture: the status says `matrix`, and
    the step holds the [T, C] match matrix it always did; the keyed one of
    the same sizes holds nothing of T x C."""
    import jax

    def step_text(name):
        mgr, rt, _ = deploy(name, 512, 1024)
        try:
            qr = rt.queries["q"]
            batch = rt.junctions["S"].schema.empty_batch(512)
            state = jax.eval_shape(lambda: qr._fresh(qr.init_state(0)))
            text = jax.jit(qr._make_step("S")).lower(
                state, {}, batch, np.int64(0)).as_text()
            return text, status_of(rt)["match"]
        finally:
            rt.shutdown()
            mgr.shutdown()

    dense, kind = step_text("no_equality")
    assert kind == "matrix" and "tensor<1024x512xi1>" in dense
    keyed, kind = step_text("capture_residual")
    assert kind == "keyed" and "1024x512x" not in keyed
    assert "tensor<1536xi32>" in keyed   # tokens and rows, sorted together


@pytest.mark.parametrize("seed", [2147483725, 2147487890, 4200001666])
def test_the_pattern_fixture_of_the_benchmark_rehearses_correct(
        seed, tmp_path, capsys):
    """`benchmark/tests/data/configs/t-pattern` states the source's order;
    PERF.md section 7 (PR 42) found 7 to 11 rows of `v1` out of place on
    these seeds. Its sound case in `benchmark/tests/test_replay_contract.py`
    is still marked `xfail(strict=True)`: a file of the benchmark, not this
    PR's to edit."""
    for p in (str(ROOT / "benchmark"), str(ROOT / "benchmark" / "tests")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import run as bench_run
    from fixtures import tree

    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    rc = bench_run.main(
        ["--workload", "t-pattern.sends", "--seed", str(seed), "--seconds", "1",
         "--trace", "0", "--rehearse"], manifest=tree(tmp_path, manifest))
    out = capsys.readouterr().out
    assert rc == 0, out
    result = json.loads(out.strip().splitlines()[-1])
    assert result["correct"] is True, out
    assert result["compared"]["v1.gap"]["value"] == 0
    assert result["compared"]["rows_compared"]["value"] > 100
