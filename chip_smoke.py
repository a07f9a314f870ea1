"""Chip smoke: the fused ingest path, end to end, on the attached TPU.

    python chip_smoke.py                  # real size; needs a TPU
    python chip_smoke.py --dry-run        # small size, any backend (CPU here)
    python chip_smoke.py --shard 4        # stage C alone, on a four-chip host

One process, public entry points only (`SiddhiManager` -> `send_columns` ->
query callback). Exits non-zero unless every stage passed; without
`--dry-run` it also exits non-zero, before building anything, unless
`jax.devices()[0].platform == "tpu"`. The last two lines of stdout are JSON
objects: the run's summary (stages, cache, set-up seconds, `"claim": null`),
then the result, `{"ok": true, "device": {"platform", "kind", "count"}}` with
exactly those keys and the device as JAX reports it. A run that fails prints
no result line. No number printed here is a speed claim: wall and compile
seconds are set-up facts of this run.

Stage A — the deployment. Smart-plug load aggregation, the shape the north
star names. Provenance: `BASELINE.json` calls it "DEBS-2013 smart-grid"; as
known to the authors (no network here, nothing was looked up) it is the
DEBS 2014 grand challenge: ~2,125 smart plugs in 40 houses, one reading per
plug per second carrying (id, timestamp, value, property[work|load],
plug_id, household_id, house_id), queries = load averages over sliding
windows of 1 min .. 120 min. Assumed here, not taken from the source:
- one stream row carries both `load` (W, float) and `work` (long) of a plug,
  where the source sends them as separate property rows;
- the window is `length(1048576)` rows (~8 min of the whole population at
  2,125 rows/s), a row-count stand-in for the source's time windows;
- plug activity is Zipf-skewed (exponent 1.1) over the 2,125 ids, as
  ROADMAP R1 asks, where the source has every plug report every second;
- 10 % of readings are `load == 0` (plug idle) so the `[load > 0]` filter
  does work; `work` is a per-reading integer in [0, 1000);
- timestamps advance by 1000/2125 ms per row.
The delivered rows are compared with a plain NumPy reference (per-key
prefix sums over the filtered stream with a row-count expiry): row count,
plug, ts and sum(work) exactly; avgLoad within AVG_TOL, because the engine
keeps running f32 sums by design and the reference sums in f64.

Stage B — the five `BASELINE.json` programs (texts from
`siddhi_tpu/testing/apps.py`) compile and run at a deployment's width.
Stage C (`--shard N` runs it alone; never in the default run) — stage A's
query without the window under `@app:shard(axis='keys')`, and a partitioned
twin under `axis='part'`, on N chips: state on N distinct devices, emissions
byte-identical to the unsharded run.
Stage D — a few thousand rows through an `@async` stream must ride the
native C++ ring.
Stage E — `#log`, `convert(x, 'string')` and `UUID()` need host callbacks
from inside a device program: the probe must find them and they must work.
Stage F — the declared wire encodings (dict gather, delta cumsum, bit
unpack; texts from `testing/apps.py` plus a BOOL lane) decode inside the chunk
program, and what is delivered equals a NumPy filter of what was sent.
Stage G — device->host reads off the main thread while the chip is busy:
the periodic aux-flag drain must fire from an `@async` worker and from a
user thread inside `send_columns` (a deliberately overflowing group table
makes it observable: the engine's one ERROR must come from that thread,
with nobody flushing), an explicit flush must run from a helper thread
while the main thread is mid-send, and the rows must equal a NumPy
reference throughout.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys
import threading
import time

import numpy as np

N_PLUGS = 2125
ZIPF_S = 1.1
T0_MS = 1_700_000_000_000
LOAD_MEAN_W = 60.0
# avgLoad: engine f32 running sums (add on arrival, subtract on expiry) vs
# the f64 reference: |got - ref| <= AVG_TOL * max(|ref|, LOAD_MEAN_W). The
# floor is there because a slot that held a large sum keeps that sum's
# absolute rounding error after most of its rows expire, so a cold plug's
# tiny reading (0.06 W) shows 8e-4 purely relative at any size. Observed
# maxima of the floored measure: 1.6e-6 at full size on the chip, 1.8e-6 and
# 3.3e-6 in CPU runs at batch 512 and 4096 (PR 21).
AVG_TOL = 5e-5

# part_batch: before PR 32 a partitioned query's output batch was
# partitionCapacity x batch row slots; at 4096 x 32768 its per-batch readback
# pack asked for 19 GB of HBM on the one-chip run (PR 21). The routed step
# (PR 32) emits a flat batch of the rows sent and runs that size on one chip
# (the benchmark's `q1-part.trickle`), but its program across four chips
# (`apply_partition_mesh`: the state's [P] axis on the mesh, the [P, B']
# sub-batches and the merge's sorts left to the partitioner) has not run on
# the chip at that size: stage C's partitioned twin keeps the smaller batch
# until one `--shard 4` call at 32768 has (ROADMAP M1)
REAL = {"batch": 32768, "join_batch": 8192, "part_batch": 2048,
        "async_rows": 4096}
DRY = {"batch": 512, "join_batch": 256, "part_batch": 256, "async_rows": 512}
SEND_BATCHES = 64   # one send_columns call = 64 micro-batches = 2 chunks
WINDOW_BATCHES = 32  # stage A window = 32 micro-batches (2^20 rows at 32768)


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class _EngineLog(logging.Handler):
    """Collects what the engine logged: any WARNING or above, and any
    record carrying a traceback (an exception the engine caught and
    carried on from). Either fails the stage that produced it."""

    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.bad: list[tuple[str, int]] = []  # (text, emitting thread)
        self.hash_log: list[str] = []  # what `#log` stages printed

    def emit(self, record: logging.LogRecord) -> None:
        if record.levelno >= logging.WARNING or record.exc_info:
            self.bad.append((f"{record.levelname} {record.name}: "
                             f"{record.getMessage()}", record.thread))
        elif record.name.startswith("siddhi_tpu.log."):
            self.hash_log.append(record.getMessage())

    def take(self, needle: str) -> list[int]:
        """Remove the records whose text holds `needle`; their threads."""
        hit = [t for text, t in self.bad if needle in text]
        self.bad = [(text, t) for text, t in self.bad if needle not in text]
        return hit

    def require_clean(self, stage: str) -> None:
        bad, self.bad = self.bad, []
        check(not bad, f"stage {stage}: engine logged {[b[0] for b in bad]}")


# --------------------------------------------------------------------------
# stage A
# --------------------------------------------------------------------------

def plug_app(batch: int, window: int) -> str:
    return f"""
    @app:name('PlugLoad')
    @app:statistics(reporter='none')
    @app:batch(size='{batch}')
    @app:groupCapacity(size='4096')
    define stream Plug (plug string, load float, work long);
    @info(name='load_avg')
    from Plug[load > 0]#window.length({window})
    select plug, avg(load) as avgLoad, sum(work) as work
    group by plug insert into LoadOut;
    """


def make_plug_data(seed: int, n: int) -> dict:
    """Seeded plug readings: `plug` is an index into the 2,125 plug names."""
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, N_PLUGS + 1) ** ZIPF_S
    rank_to_plug = rng.permutation(N_PLUGS)
    plug = rank_to_plug[rng.choice(N_PLUGS, size=n, p=p / p.sum())]
    load = rng.exponential(LOAD_MEAN_W, size=n).astype(np.float32)
    load[rng.random(n) < 0.10] = 0.0
    return {
        "ts": T0_MS + (np.arange(n, dtype=np.int64) * 1000) // N_PLUGS,
        "plug": plug.astype(np.int32),
        "load": load,
        "work": rng.integers(0, 1000, size=n).astype(np.int64),
    }


def plug_reference(data: dict, window: int) -> dict:
    """Plain NumPy semantics of stage A's query, independent of the engine:
    keep rows with load > 0; for kept row i the window holds kept rows
    (i - window, i]; emit (ts_i, plug_i, mean of load, sum of work) over the
    window rows of the same plug. Per-key prefix sums in f64 / int64."""
    keep = data["load"] > 0
    ts, plug = data["ts"][keep], data["plug"][keep].astype(np.int64)
    load = data["load"][keep].astype(np.float64)
    work = data["work"][keep]
    n = len(ts)
    idx = np.arange(n, dtype=np.int64)
    order = np.argsort(plug, kind="stable")  # by plug, arrival order within
    comp = plug[order] * n + idx[order]      # sorted (plug, arrival) code
    c_load = np.concatenate([[0.0], np.cumsum(load[order])])
    c_work = np.concatenate([[0], np.cumsum(work[order])])
    hi = np.arange(1, n + 1)  # this row's own position, inclusive
    # first same-plug row still inside the window: arrival index > i - window
    lo = np.searchsorted(
        comp, plug[order] * n + np.maximum(idx[order] - window, -1),
        side="right",
    )
    avg = np.empty(n)
    wsum = np.empty(n, dtype=np.int64)
    avg[order] = (c_load[hi] - c_load[lo]) / (hi - lo)
    wsum[order] = c_work[hi] - c_work[lo]
    return {"ts": ts, "plug": plug, "avgLoad": avg, "work": wsum}


def _compile_ledger(rt) -> dict:
    """program -> compiles, from the engine's CompileTelemetry."""
    return {
        name: ent["compiles"]
        for name, ent in rt.profile_report()["compile"].items()
    }


def _compile_seconds(rt) -> float:
    return round(sum(
        ent["wall_ms_total"] for ent in rt.profile_report()["compile"].values()
    ) / 1e3, 1)


def _require_warm_only(what: str, ledgers: list) -> None:
    """One warm-up send explains every compile: each program compiled in
    the first send and none compiled in a later one."""
    check(ledgers[0] and all(c >= 1 for c in ledgers[0].values()),
          f"{what}: no compile recorded in the warm-up send: {ledgers[0]}")
    check(all(later == ledgers[0] for later in ledgers[1:]),
          f"{what}: a program compiled after warm-up: {ledgers}")


def _require_fused(status: dict, stream: str, chunks: int, prof: dict) -> dict:
    """The fused + pipelined path engaged for every chunk of every send: a
    `try_send` that returns False falls to the per-batch path silently."""
    pl = status["streams"][stream].get("pipeline")
    check(pl is not None, f"{stream}: no fused ingest engine was built")
    check(pl["enabled"] is True, f"{stream}: fused ingest disabled itself")
    check(pl["pipeline_enabled"] is True, f"{stream}: pipeline is off")
    check(pl["chunk_batches"] == 32,
          f"{stream}: chunk_batches {pl['chunk_batches']} != 32")
    check(pl.get("drain_thread") is True,
          f"{stream}: drain worker never started: {pl}")
    got = prof["waterfalls"]["chunks"]
    check(got == chunks,
          f"{stream}: {got} chunks went host->device, expected {chunks}")
    return pl


def stage_a(SiddhiManager, sizes: dict, seed: int, log: _EngineLog) -> dict:
    t_stage = time.perf_counter()
    B = sizes["batch"]
    window = B * WINDOW_BATCHES
    n_send = B * SEND_BATCHES
    sends = 3
    data = make_plug_data(seed, n_send * sends)

    mgr = SiddhiManager()
    names = [f"plug-{i:04d}" for i in range(N_PLUGS)]
    ids = np.array([mgr.interner.intern(s) for s in names], dtype=np.int32)
    index_of = {s: i for i, s in enumerate(names)}
    rt = mgr.create_siddhi_app_runtime(plug_app(B, window))

    got_ts, got_plug, got_avg, got_work = [], [], [], []

    def on_rows(ts, ins, removed):
        check(not removed, "load_avg delivered expired rows")
        rows = [e.data for e in ins]
        got_ts.append(np.fromiter((e.timestamp for e in ins), np.int64,
                                  len(ins)))
        got_plug.append(np.fromiter((index_of[r[0]] for r in rows), np.int64,
                                    len(rows)))
        got_avg.append(np.fromiter((r[1] for r in rows), np.float64,
                                   len(rows)))
        got_work.append(np.fromiter((r[2] for r in rows), np.int64,
                                    len(rows)))

    rt.add_callback("load_avg", on_rows)
    rt.start()
    h = rt.get_input_handler("Plug")

    ledgers, send_s = [], []
    for s in range(sends):
        lo, hi = s * n_send, (s + 1) * n_send
        t0 = time.perf_counter()
        h.send_columns(data["ts"][lo:hi], {
            "plug": ids[data["plug"][lo:hi]],
            "load": data["load"][lo:hi],
            "work": data["work"][lo:hi],
        })
        send_s.append(round(time.perf_counter() - t0, 2))
        ledgers.append(_compile_ledger(rt))
    # send_columns barriers on delivery, so everything is on the host now
    for qr in rt.queries.values():
        qr.flush_aux_warnings()  # capacity-overflow flags surface as logs
    log.require_clean("A")

    status = rt.snapshot_status()
    prof = rt.profile_report()
    pl = _require_fused(status, "Plug", sends * SEND_BATCHES // 32, prof)
    wire = pl["wire"]
    check(wire["encoded_B_per_ev"] < wire["logical_B_per_ev"],
          f"wire fell back to full width: {wire}")
    roof = prof["roofline"]["stream.Plug"]
    check(roof["h2d_events"] == n_send * sends,
          f"h2d carried {roof['h2d_events']} events, sent {n_send * sends}")
    win = status["queries"]["load_avg"]["window"]
    check(win["fill"] == window,
          f"window fill read from the device is {win['fill']} != {window}")
    _require_warm_only("A", ledgers)
    for name, ent in prof["compile"].items():
        check(set(ent["causes"]) == {"first_compile"},
              f"{name}: compile causes {ent['causes']}")
    compile_s = _compile_seconds(rt)
    rt.shutdown()
    mgr.shutdown()

    t0 = time.perf_counter()
    ref = plug_reference(data, window)
    got = {
        "ts": np.concatenate(got_ts), "plug": np.concatenate(got_plug),
        "avgLoad": np.concatenate(got_avg), "work": np.concatenate(got_work),
    }
    check(len(got["ts"]) == len(ref["ts"]),
          f"delivered {len(got['ts'])} rows, reference has {len(ref['ts'])}")
    for lane in ("ts", "plug", "work"):
        check(np.array_equal(got[lane], ref[lane]),
              f"lane {lane} differs from the NumPy reference at row "
              f"{int(np.argmax(got[lane] != ref[lane]))}")
    check(bool(np.isfinite(got["avgLoad"]).all()), "avgLoad has non-finite rows")
    rel = np.abs(got["avgLoad"] - ref["avgLoad"]) / np.maximum(
        np.abs(ref["avgLoad"]), LOAD_MEAN_W)
    max_rel = float(rel.max())
    check(max_rel <= AVG_TOL,
          f"avgLoad off by {max_rel:.3e} (> {AVG_TOL}) at row "
          f"{int(rel.argmax())}")
    out = {
        "rows_sent": n_send * sends,
        "rows_delivered": int(len(got["ts"])),
        "window_rows": window,
        "wire_B_per_event": wire["encoded_B_per_ev"],
        "compiles": ledgers[-1],
        "avg_max_err_vs_tol": [max_rel, AVG_TOL],
        "setup_compile_s": compile_s,
        "setup_send_wall_s": send_s,
        "reference_wall_s": round(time.perf_counter() - t0, 2),
        "stage_wall_s": round(time.perf_counter() - t_stage, 1),
    }
    print(f"stage A ok: {json.dumps(out)}", flush=True)
    return out


# --------------------------------------------------------------------------
# stage B
# --------------------------------------------------------------------------

def stage_b(SiddhiManager, sizes: dict, seed: int, log: _EngineLog) -> dict:
    from siddhi_tpu.testing import apps

    out = {}
    for name, (ql, stream, batch_override) in apps.WORKLOADS.items():
        t_stage = time.perf_counter()
        B = sizes["join_batch"] if batch_override else sizes["batch"]
        n_send = B * SEND_BATCHES
        data = apps.make_stock_data(2 * n_send, seed=seed)
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime(
            f"@app:statistics(reporter='none')\n@app:batch(size='{B}')\n" + ql
        )
        apps.prime_interner(mgr, data["names"])
        emitted = [0]

        def on_rows(ts, ins, removed, _n=emitted):
            _n[0] += len(ins or ()) + len(removed or ())

        rt.add_callback("q", on_rows)
        rt.start()
        h = rt.get_input_handler(stream)
        cols = {k: v for k, v in data.items() if k not in ("ts", "names")}
        ledgers = []
        for s in range(2):
            lo, hi = s * n_send, (s + 1) * n_send
            h.send_columns(data["ts"][lo:hi],
                           {k: v[lo:hi] for k, v in cols.items()})
            ledgers.append(_compile_ledger(rt))
        # `q` has a callback, so each send returned only after its last
        # chunk was read back and delivered: nothing is still queued
        for qr in rt.queries.values():
            qr.flush_aux_warnings()
        log.require_clean(f"B/{name}")
        _require_fused(rt.snapshot_status(), stream,
                       2 * SEND_BATCHES // 32, rt.profile_report())
        _require_warm_only(name, ledgers)
        check(emitted[0] > 0, f"{name}: no emission reached the callback")
        out[name] = {
            "batch": B,
            "rows_sent": 2 * n_send,
            "rows_delivered": emitted[0],
            "compiles": ledgers[-1],
            "setup_compile_s": _compile_seconds(rt),
            "stage_wall_s": round(time.perf_counter() - t_stage, 1),
        }
        rt.shutdown()
        mgr.shutdown()
        print(f"stage B/{name} ok: {json.dumps(out[name])}", flush=True)
    return out


# --------------------------------------------------------------------------
# stage C (explicit --shard N only)
# --------------------------------------------------------------------------

def _shard_apps(sizes: dict) -> dict:
    """axis -> (micro-batch rows, query id, app text)."""
    head = """
    @app:groupCapacity(size='4096')
    @app:partitionCapacity(size='4096')
    define stream Plug (plug string, load float, work long);
    """
    # axis='keys' shards integer/min/max aggregators only (float sums are
    # reassociation-sensitive, parallel/keyshard.py), so stage A's avg(load)
    # becomes max(load) + count() there; the partitioned twin keeps avg
    return {
        "keys": (sizes["batch"], "load_avg", head + """
        @info(name='load_avg')
        from Plug[load > 0]
        select plug, max(load) as maxLoad, count() as n, sum(work) as work
        group by plug insert into LoadOut;
        """),
        "part": (sizes["part_batch"], "load_avg", head + """
        partition with (plug of Plug) begin
        @info(name='load_avg')
        from Plug[load > 0]
        select plug, avg(load) as avgLoad, sum(work) as work
        insert into LoadOut;
        end;
        """),
    }


def _state_devices(state) -> tuple[set, int]:
    """(devices holding any leaf, leaves split across more than one)."""
    import jax

    devs, split = set(), 0
    for leaf in jax.tree_util.tree_leaves(state):
        sh = getattr(leaf, "sharding", None)
        if sh is None:
            continue
        devs |= set(sh.device_set)
        if len(sh.device_set) > 1 and not sh.is_fully_replicated:
            split += 1
    return devs, split


def stage_c(SiddhiManager, sizes: dict, seed: int, n_dev: int,
            log: _EngineLog) -> dict:
    out = {}
    for axis, (B, qid, ql) in _shard_apps(sizes).items():
        t_stage = time.perf_counter()
        # one send of 16 micro-batches: under axis='keys' the sharded step
        # runs inside the fused chunk program (one chunk of 16 on the mesh,
        # both runs on the fused path); the partitioned twin's steps
        # dispatch per micro-batch
        n_rows = B * 16
        data = make_plug_data(seed + 1, n_rows)
        ql = f"@app:batch(size='{B}')\n" + ql
        rows_of, placed_on = {}, None
        for sharded in (False, True):
            mgr = SiddhiManager()
            ids = np.array(
                [mgr.interner.intern(f"plug-{i:04d}") for i in range(N_PLUGS)],
                dtype=np.int32,
            )
            text = ql
            if sharded:
                text = (f"@app:shard(devices='{n_dev}', axis='{axis}')\n"
                        + ql)
            rt = mgr.create_siddhi_app_runtime(text)
            rows = []
            rt.add_callback(
                qid, lambda ts, ins, rem, _r=rows: _r.extend(
                    (e.timestamp, e.data) for e in ins or ())
            )
            rt.start()
            rt.get_input_handler("Plug").send_columns(data["ts"], {
                "plug": ids[data["plug"]], "load": data["load"],
                "work": data["work"],
            })
            for qr in rt.queries.values():
                qr.flush_aux_warnings()
            log.require_clean(f"C/{axis}")
            if sharded:
                sh = rt.snapshot_status().get("shard")
                check(sh is not None and sh["devices"] == n_dev,
                      f"{axis}: ShardRuntime.n != {n_dev}: {sh}")
                if axis == "keys":
                    placed = sh.get("keyshard", {}).get(qid, {})
                    check(placed.get("sharded"),
                          f"keys: query not key-sharded: {sh}")
                    check(placed.get("path") == "fused",
                          f"keys: the send left the fused path: {placed}")
                    state = rt.queries[qid].state
                else:
                    placed = sh.get("partitioned", {}).get(qid, {})
                    check(placed.get("sharded") is True,
                          f"part: query not placed on the mesh: {sh}")
                    state = [q.state for p in rt.partitions
                             for q in p.queries]
                devs, split = _state_devices(state)
                check(len(devs) == n_dev and split > 0,
                      f"{axis}: state lives on {len(devs)} device(s), "
                      f"{split} leaves split")
                placed_on = {"devices": sorted(str(d) for d in devs),
                             "split_leaves": split}
            rows_of[sharded] = rows
            rt.shutdown()
            mgr.shutdown()
        check(len(rows_of[False]) > 0,
              f"{axis}: the unsharded run emitted nothing")
        # repr() keeps float bits apart that == would merge (-0.0, NaN)
        check(repr(rows_of[True]) == repr(rows_of[False]),
              f"{axis}: sharded emissions differ from the one-chip run")
        out[axis] = {
            **placed_on, "batch": B, "rows_sent": n_rows,
            "rows_delivered": len(rows_of[True]),
            "stage_wall_s": round(time.perf_counter() - t_stage, 1),
        }
        if B != sizes["batch"]:
            out[axis]["reduced"] = {
                "batch": f"{B} of {sizes['batch']}, ROADMAP M1"}
        print(f"stage C/{axis} ok: {json.dumps(out[axis])}", flush=True)
    return out


# --------------------------------------------------------------------------
# stage D
# --------------------------------------------------------------------------

def stage_d(SiddhiManager, sizes: dict, log: _EngineLog) -> dict:
    t_stage = time.perf_counter()
    n = sizes["async_rows"]
    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime("""
    @app:batch(size='256')
    @async(buffer.size='1024', workers='1')
    define stream A (k long, v float);
    @info(name='q') from A select count() as n, sum(k) as s insert into AOut;
    """)
    last = [None]
    rt.add_callback(
        "q", lambda ts, ins, rem: last.__setitem__(0, ins[-1].data)
    )
    rt.start()
    h = rt.get_input_handler("A")
    for i in range(n):
        h.send((i, 0.5))
    deadline = time.monotonic() + 120
    want = (n, n * (n - 1) // 2)
    while last[0] != want and time.monotonic() < deadline:
        time.sleep(0.02)
    check(last[0] == want, f"@async delivered {last[0]}, expected {want}")
    a = rt.snapshot_status()["streams"]["A"]["async"]
    check(a["native_ring"] is True,
          f"@async fell back to the Python queue: {a}")
    check(a["workers_alive"] == a["workers"] == 1, f"@async workers: {a}")
    log.require_clean("D")
    rt.shutdown()
    mgr.shutdown()
    out = {"rows": n, "native_ring": True,
           "stage_wall_s": round(time.perf_counter() - t_stage, 1)}
    print(f"stage D ok: {json.dumps(out)}", flush=True)
    return out


# --------------------------------------------------------------------------
# stage E
# --------------------------------------------------------------------------

def stage_e(SiddhiManager, log: _EngineLog) -> dict:
    t_stage = time.perf_counter()
    from siddhi_tpu.utils.backend import host_callbacks_supported

    check(host_callbacks_supported() is True,
          "the backend's probe rejected host callbacks")
    n = 256
    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime(f"""
    @app:batch(size='{n}')
    define stream H (k long, v float);
    @info(name='q') from H#log('chip smoke')
    select convert(k, 'string') as ks, UUID() as id insert into HOut;
    """)
    rows = []
    rt.add_callback("q", lambda ts, ins, rem: rows.extend(e.data for e in ins))
    rt.start()
    k = np.arange(n, dtype=np.int64) * 7
    rt.get_input_handler("H").send_columns(
        T0_MS + np.arange(n, dtype=np.int64),
        {"k": k, "v": np.ones(n, dtype=np.float32)},
    )
    check([r[0] for r in rows] == [str(int(x)) for x in k],
          f"convert(k, 'string') delivered {rows[:3]}...")
    ids = [r[1] for r in rows]
    check(len(set(ids)) == n and all(len(i or "") == 36 for i in ids),
          f"UUID() delivered {ids[:3]}...")
    check(any(m.startswith(f"chip smoke : {n} event(s)")
              for m in log.hash_log), f"#log printed {log.hash_log}")
    log.require_clean("E")
    rt.shutdown()
    mgr.shutdown()
    out = {"rows": n, "host_callbacks": True,
           "stage_wall_s": round(time.perf_counter() - t_stage, 1)}
    print(f"stage E ok: {json.dumps(out)}", flush=True)
    return out


# --------------------------------------------------------------------------
# stage F
# --------------------------------------------------------------------------

BITPACK_APP = ("""
    define stream Flags (armed bool, k long);
    @info(name='q') from Flags[armed] select k insert into Out;
    """, "Flags")


def stage_f(SiddhiManager, sizes: dict, seed: int, log: _EngineLog) -> dict:
    from siddhi_tpu.testing.apps import WIRE_WORKLOADS

    B = sizes["batch"]
    n = 2 * B * SEND_BATCHES
    rng = np.random.default_rng(seed)
    ts = T0_MS + np.arange(n, dtype=np.int64)
    qty = rng.integers(0, 1000, n).astype(np.int64)
    v = rng.uniform(-1, 10, n).astype(np.float32)
    armed = rng.random(n) < 0.5
    k = rng.integers(0, 1 << 40, n).astype(np.int64)
    # name -> (app, stream, columns, lane -> label, delivered column,
    #          reference = that column of the rows the filter keeps)
    cases = {
        "wire_dict": (
            *WIRE_WORKLOADS["wire_dict"],
            {"sym": rng.integers(1, 33, n).astype(np.int32),
             "price": rng.uniform(0, 100, n).astype(np.float32), "qty": qty},
            {"sym": "dict"}, 1, qty[qty > 10]),
        "wire_delta": (
            *WIRE_WORKLOADS["wire_delta"],
            {"seq": np.arange(n, dtype=np.int64) + 10**12, "v": v},
            {"seq": "delta"}, 0,
            (np.arange(n, dtype=np.int64) + 10**12)[v >= 0]),
        "wire_bitpack": (
            *BITPACK_APP, {"armed": armed, "k": k},
            {"armed": "bitpack"}, 0, k[armed]),
    }
    out = {}
    for name, (ql, stream, cols, want, col, ref) in cases.items():
        t_stage = time.perf_counter()
        mgr = SiddhiManager()
        for i in range(1, 64):
            mgr.interner.intern(f"SYM{i}")
        rt = mgr.create_siddhi_app_runtime(
            f"@app:statistics(reporter='none')\n@app:batch(size='{B}')\n"
            + ql
        )
        got = []
        rt.add_callback("q", lambda t, ins, rem, _g=got, _c=col: _g.append(
            np.fromiter((e.data[_c] for e in ins), np.int64, len(ins))))
        rt.start()
        h = rt.get_input_handler(stream)
        ledgers = []
        for lo in (0, n // 2):
            h.send_columns(ts[lo:lo + n // 2],
                           {c: a[lo:lo + n // 2] for c, a in cols.items()})
            ledgers.append(_compile_ledger(rt))
        log.require_clean(f"F/{name}")
        pl = _require_fused(rt.snapshot_status(), stream,
                            2 * SEND_BATCHES // 32, rt.profile_report())
        lanes = pl["wire"]["lanes"]
        for lane, label in want.items():
            check(lanes[lane].startswith(label),
                  f"{name}: lane {lane} rides {lanes[lane]}, not {label}")
        _require_warm_only(name, ledgers)
        got = np.concatenate(got)
        check(np.array_equal(got, ref),
              f"{name}: {len(got)} delivered rows differ from the "
              f"{len(ref)} the NumPy filter keeps")
        out[name] = {
            "lanes": lanes, "rows_sent": n, "rows_delivered": int(len(got)),
            "setup_compile_s": _compile_seconds(rt),
            "stage_wall_s": round(time.perf_counter() - t_stage, 1),
        }
        rt.shutdown()
        mgr.shutdown()
        print(f"stage F/{name} ok: {json.dumps(out[name])}", flush=True)
    return out


# --------------------------------------------------------------------------
# stage G
# --------------------------------------------------------------------------

OVERFLOW_ERROR = "group-by slot table overflowed"
HOT_KEYS = 16


def overflow_app(batch: int, async_ann: str) -> str:
    return f"""
    @app:statistics(reporter='none')
    @app:batch(size='{batch}')
    @app:groupCapacity(size='{HOT_KEYS}')
    {async_ann}
    define stream K (k long, v long);
    @info(name='q') from K select k, count() as n, sum(v) as s
    group by k insert into KOut;
    """


def make_overflow_data(seed: int, n: int) -> dict:
    """The first HOT_KEYS rows claim every slot of the group table; after
    them odd rows revisit those keys and each even row is a key seen once.
    An overflowed key loses only its carry ACROSS micro-batches, so with one
    row per cold key the exact per-key running count and sum are the right
    answer wherever the micro-batch boundaries fall."""
    i = np.arange(n, dtype=np.int64)
    hot = (i < HOT_KEYS) | (i % 2 == 1)
    return {
        "ts": T0_MS + i,
        "k": np.where(hot, i % HOT_KEYS, 10**6 + i),
        "v": np.random.default_rng(seed).integers(0, 1000, n).astype(np.int64),
    }


def running_by_key(k: np.ndarray, v: np.ndarray) -> tuple:
    """Per-key running (count, sum) in arrival order, plain NumPy."""
    order = np.argsort(k, kind="stable")
    ks, vs = k[order], v[order]
    start = np.concatenate([[True], ks[1:] != ks[:-1]])
    first = np.flatnonzero(start)[np.cumsum(start) - 1]  # own segment's head
    c = np.cumsum(vs)
    n = np.empty(len(k), np.int64)
    s = np.empty(len(k), np.int64)
    n[order] = np.arange(len(k)) - first + 1
    s[order] = c - (c - vs)[first]
    return n, s


def _drain_period_s() -> float:
    """The aux-flag pool's periodic-drain cadence, read as the engine reads
    it when `siddhi_tpu` is imported (core/query_runtime.py); stage G has to
    outwait it."""
    period = float(os.environ.get("SIDDHI_TPU_AUX_DRAIN_S", "5.0"))
    check(period > 0, "SIDDHI_TPU_AUX_DRAIN_S <= 0 turns the periodic drain "
          "off; stage G cannot run")
    return period


def _in_thread(fn) -> None:
    """Run fn to its end on a fresh thread; its failure is the caller's."""
    err = []

    def body():
        try:
            fn()
        except BaseException as e:  # re-raised on the caller's thread below
            err.append(e)

    t = threading.Thread(target=body, name="smoke-sender")
    t.start()
    t.join()
    if err:
        raise err[0]


def _require_helper_thread_drain(leg: str, log: _EngineLog) -> None:
    threads = log.take(OVERFLOW_ERROR)
    check(len(threads) == 1,
          f"G/{leg}: the overflow ERROR surfaced {len(threads)} times with "
          "nobody flushing; the periodic drain owed exactly one")
    check(threads[0] != threading.main_thread().ident,
          f"G/{leg}: the periodic drain ran on the main thread")
    log.require_clean(f"G/{leg}")


def stage_g(SiddhiManager, sizes: dict, seed: int, period: float,
            log: _EngineLog) -> dict:
    out = {}

    # ---- leg 1: per-batch path; the @async worker submits the flags, so
    # every periodic drain of this leg is a blocking read on that worker
    t_stage = time.perf_counter()
    n = sizes["async_rows"]
    data = make_overflow_data(seed, n)
    want_n, want_s = running_by_key(data["k"], data["v"])
    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime(
        overflow_app(256, "@async(buffer.size='1024', workers='1')"))
    rows = []
    rt.add_callback("q", lambda ts, ins, rem: rows.extend(e.data for e in ins))
    rt.start()
    h = rt.get_input_handler("K")

    def feed(lo, hi):
        for i in range(lo, hi):
            h.send((int(data["k"][i]), int(data["v"][i])))
        deadline = time.monotonic() + 120
        while len(rows) < hi and time.monotonic() < deadline:
            time.sleep(0.02)
        check(len(rows) == hi, f"G/async: {len(rows)} of {hi} rows delivered")

    feed(0, n // 2)
    time.sleep(period + 0.5)  # the next submit finds the drain overdue
    feed(n // 2, n)
    _require_helper_thread_drain("async", log)
    check(rt.snapshot_status()["streams"]["K"]["async"]["native_ring"] is True,
          "G/async: the stream fell back to the Python queue")
    got = np.array(rows, dtype=np.int64).reshape(n, 3)
    check(np.array_equal(got[:, 0], data["k"])
          and np.array_equal(got[:, 1], want_n)
          and np.array_equal(got[:, 2], want_s),
          "G/async: delivered rows differ from the NumPy reference")
    rt.shutdown()
    mgr.shutdown()
    out["async"] = {"rows": n, "drain_thread_is_main": False,
                    "stage_wall_s": round(time.perf_counter() - t_stage, 1)}
    print(f"stage G/async ok: {json.dumps(out['async'])}", flush=True)

    # ---- leg 2: fused path. Sends 1 and 2 come from a user thread (the
    # periodic drain fires inside its send_columns, beside the pipeline's
    # own drain worker); during send 3, from the main thread, a helper
    # thread flushes in a loop
    t_stage = time.perf_counter()
    B = sizes["batch"]
    n_send = B * SEND_BATCHES
    data = make_overflow_data(seed + 1, 3 * n_send)
    want_n, want_s = running_by_key(data["k"], data["v"])
    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime(overflow_app(B, ""))
    got = []
    rt.add_callback("q", lambda ts, ins, rem: got.append(
        np.array([e.data for e in ins], dtype=np.int64).reshape(len(ins), 3)))
    rt.start()
    h = rt.get_input_handler("K")
    qr = rt.queries["q"]
    ledgers = []

    def send(part):
        lo, hi = part * n_send, (part + 1) * n_send
        h.send_columns(data["ts"][lo:hi],
                       {"k": data["k"][lo:hi], "v": data["v"][lo:hi]})
        ledgers.append(_compile_ledger(rt))

    _in_thread(lambda: send(0))
    time.sleep(period + 0.5)
    _in_thread(lambda: send(1))
    _require_helper_thread_drain("fused", log)

    sending = threading.Event()
    sending.set()
    flushes = [0]

    def flusher():
        while sending.is_set():
            qr.flush_aux_warnings()
            flushes[0] += 1
            time.sleep(0.01)

    t = threading.Thread(target=flusher, name="smoke-flusher")
    t.start()
    try:
        send(2)
    finally:
        sending.clear()
        t.join()
    check(flushes[0] >= 1, "G/fused: no flush completed during the send")
    log.require_clean("G/fused")
    _require_fused(rt.snapshot_status(), "K", 3 * SEND_BATCHES // 32,
                   rt.profile_report())
    _require_warm_only("G/fused", ledgers)
    got = np.concatenate(got)
    check(got.shape == (3 * n_send, 3)
          and np.array_equal(got[:, 0], data["k"])
          and np.array_equal(got[:, 1], want_n)
          and np.array_equal(got[:, 2], want_s),
          "G/fused: delivered rows differ from the NumPy reference")
    out["fused"] = {
        "batch": B, "rows": 3 * n_send, "drain_thread_is_main": False,
        "flushes_during_send": flushes[0],
        "setup_compile_s": _compile_seconds(rt),
        "stage_wall_s": round(time.perf_counter() - t_stage, 1),
    }
    rt.shutdown()
    mgr.shutdown()
    print(f"stage G/fused ok: {json.dumps(out['fused'])}", flush=True)
    return out


# --------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=2014)
    ap.add_argument("--dry-run", action="store_true",
                    help="small size, any backend: for running this same "
                    "command on the CPU before spending chip time")
    ap.add_argument("--shard", type=int, default=0, metavar="N",
                    help="run stage C alone: @app:shard on N chips")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"jax {jax.__version__} platform={device['platform']} "
          f"device_kind={device['kind']} count={device['count']}", flush=True)
    if args.dry_run:
        print("DRY RUN: small size, not a chip check", flush=True)
    elif device["platform"] != "tpu":
        print(f"chip_smoke: platform is {device['platform']!r}, not 'tpu' — "
              "nothing was built or run (use --dry-run for the CPU size)",
              file=sys.stderr)
        return 1
    if args.shard:
        check(device["count"] >= args.shard,
              f"--shard {args.shard} needs {args.shard} devices, "
              f"{device['count']} visible")

    drain_period = _drain_period_s()
    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.observability.profiler import jit_cache_size
    from siddhi_tpu.utils.backend import configure_compile_cache

    cache_dir = configure_compile_cache()
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(f"compile cache: {cache_dir} ({entries} entries at start)",
          flush=True)

    # CompileTelemetry counts compiles from the jit cache size; if the
    # private hook it reads went away it would silently change heuristic
    probe = jax.jit(lambda x: x + 1)
    probe(np.int32(0))
    size = jit_cache_size(probe)
    check(isinstance(size, int) and size == 1,
          f"jit_cache_size returned {size!r}, not the int 1")

    log = _EngineLog()
    root = logging.getLogger("siddhi_tpu")
    root.addHandler(log)
    root.setLevel(logging.DEBUG)

    sizes = DRY if args.dry_run else REAL
    if args.shard:
        stages = {"C": stage_c(SiddhiManager, sizes, args.seed, args.shard,
                               log)}
    else:
        stages = {"A": stage_a(SiddhiManager, sizes, args.seed, log),
                  "B": stage_b(SiddhiManager, sizes, args.seed, log),
                  "D": stage_d(SiddhiManager, sizes, log),
                  "E": stage_e(SiddhiManager, log),
                  "F": stage_f(SiddhiManager, sizes, args.seed, log),
                  "G": stage_g(SiddhiManager, sizes, args.seed, drain_period,
                               log)}

    print(json.dumps({
        "dry_run": args.dry_run,
        "jax": jax.__version__,
        "seed": args.seed,
        "cache_dir": cache_dir,
        "cache_entries_at_start": entries,
        "setup_wall_s": round(time.perf_counter() - t_start, 1),
        "stages": stages,
        "claim": None,
    }), flush=True)
    # the result line: these keys and no others, last on stdout
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
