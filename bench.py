"""Benchmark driver: end-to-end engine throughput on the BASELINE.json configs.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "detail"}.

Methodology (completion-rate timing):
- JAX dispatch is asynchronous, so a clock stopped after the last send
  measures an ENQUEUE rate, not a completion rate. Every timed region here
  therefore ends with a "truth sync": a tiny scalar derived from the final
  query state is read back to the host, which forces real completion of the
  whole dependent chain before the clock stops.
- EACH LEG RUNS IN ITS OWN SUBPROCESS, so a leg that wedges or crashes
  cannot take the suite down and per-leg numbers are reproducible in
  isolation (`python bench.py --leg filter_window_avg`). One process holds
  the chip at a time: the parent imports no JAX (see main()). Whether the
  isolation is still worth ~15 s of start-up per leg on a directly attached
  chip is ROADMAP S0's to judge.
- `timebudget` (in detail) publishes a PER-LEG budget of the fused-ingest
  program itself: wire bytes/event, host encode rate, effective per-chunk
  h2d cost, device rate, the predicted bound, and the leg's binding wall —
  plus the shared sync floor (the p99 denominator), bulk h2d bandwidth,
  and a pipelined-vs-serial A/B of the real engine send path
  (`*_overlap_meas` vs `*_overlap_pred`, see core/pipeline.py).

The baseline denominator is the reference's published production throughput
claim — 20B events/day ~= 300k events/s on a JVM cluster
(reference: README.md:33-34; see BASELINE.md).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import subprocess
import sys
import time

import numpy as np

REFERENCE_EVENTS_PER_SEC = 300_000.0

# keep the engine's periodic aux drain from injecting a blocking
# device->host read into a timed region
os.environ.setdefault("SIDDHI_TPU_AUX_DRAIN_S", "0")


def _make_stock_data(n: int, seed: int = 7):
    rng = np.random.default_rng(seed)
    symbols = np.array(["WSO2", "IBM", "GOOG", "MSFT", "ORCL", "AAPL", "AMZN", "NVDA"])
    return {
        "ts": np.arange(n, dtype=np.int64) + 1_700_000_000_000,
        "symbol": rng.integers(1, 9, size=n).astype(np.int32),  # pre-interned ids
        "price": rng.uniform(0.0, 100.0, size=n).astype(np.float32),
        "volume": rng.integers(1, 1000, size=n).astype(np.int64),
        "names": symbols,
    }


def _prime_interner(mgr, names):
    for s in names:
        mgr.interner.intern(str(s))


def _truth_sync(rt):
    """Force REAL completion of all queued work: read back one tiny scalar
    depending on ONE state leaf of EVERY stateful holder (query, table,
    window, aggregation) — projection-only queries have empty query state,
    and sampling globally could skip a holder whose work is still pending."""
    import jax
    import jax.numpy as jnp

    leaves = []
    holders = list(rt.queries.values()) + (
        list(rt.tables.values())
        + list(getattr(rt, "named_windows", {}).values())
        + list(getattr(rt, "aggregations", {}).values())
    )
    for h in holders:
        st = getattr(h, "state", None)
        if st is None:
            continue
        for leaf in jax.tree_util.tree_leaves(st):
            if hasattr(leaf, "dtype"):
                leaves.append(leaf)
                break
    if not leaves:
        return 0.0
    acc = sum(jnp.sum(x.ravel()[:1]).astype(jnp.float32) for x in leaves)
    return float(np.asarray(acc))


def _snapshot_status(rt):
    """Steady-state engine shape at the end of a leg (runtime.snapshot_status
    per the observability layer), stashed into the detail blob. Guarded: a
    snapshot failure must never fail a leg. Statistics-armed legs also
    persist the plan-vs-actual calibration blob + the roofline split so
    tools/calib_report.py can diff two runs' prediction errors."""
    try:
        status = rt.snapshot_status()
    except Exception:
        return None
    try:
        rep = rt.calibration_report()
        if rep is not None:
            status["calibration"] = rep
        sm = rt.statistics_manager
        if sm is not None:
            status["roofline"] = sm.roofline()
    except Exception:
        pass
    return status


_LAST_STATUS: list = [None]  # snapshot of the most recent _run_workload leg


def _run_workload(ql, query_stream, data, n_events, batch_size, callback=None):
    """TRUE throughput of one SiddhiQL app: events/sec through the full
    engine (host pack -> h2d -> fused/step dispatch), timed to completion
    via a truth sync. With `callback`, delivered throughput: the callback is
    registered on query 'q' and every output row is materialized on host
    before the clock stops (the reference's number includes delivery —
    QueryCallback.java:52-105)."""
    from siddhi_tpu import SiddhiManager

    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime(ql)
    _prime_interner(mgr, data["names"])
    if callback is not None:
        rt.add_callback("q", callback)
    rt.start()
    h = rt.get_input_handler(query_stream)

    cols = {k: v for k, v in data.items() if k not in ("ts", "names")}
    # delivered mode sends everything in ONE call: fewer, larger fused chunks
    # amortize the fixed per-transfer cost
    stride = n_events if callback is not None else batch_size * 64
    # warm with the SAME send size as the timed loop so the engaged program
    # (per-batch or fused, at the same chunking) compiles before the clock
    warm_n = min(stride, n_events)
    h.send_columns(data["ts"][:warm_n], {k: v[:warm_n] for k, v in cols.items()})
    _truth_sync(rt)  # compile + complete everything queued before timing
    t0 = time.perf_counter()
    sent = 0
    while sent < n_events:  # data arrays are sized >= n_events by main()
        end = min(sent + stride, n_events)
        h.send_columns(data["ts"][sent:end], {k: v[sent:end] for k, v in cols.items()})
        sent = end
    _truth_sync(rt)
    dt = time.perf_counter() - t0
    _LAST_STATUS[0] = _snapshot_status(rt)
    rt.shutdown()
    mgr.shutdown()
    return sent / dt


WORKLOADS = {
    # BASELINE.json config 1: SiddhiQL quickstart — filter + length-window avg
    "filter_window_avg": (
        """
        define stream StockStream (symbol string, price float, volume long);
        @info(name='q')
        from StockStream[price > 50]#window.length(50)
        select symbol, avg(price) as ap
        insert into Out;
        """,
        "StockStream",
        2.0,   # events multiplier
        None,  # batch override
    ),
    # BASELINE.json config 2: tumbling window group-by aggregation
    "tumbling_groupby": (
        """
        define stream StockStream (symbol string, price float, volume long);
        @info(name='q')
        from StockStream#window.lengthBatch(1024)
        select symbol, sum(volume) as total, avg(price) as ap
        group by symbol
        insert into Out;
        """,
        "StockStream",
        2.0,
        None,
    ),
    # BASELINE.json config 3: two-sided sliding-window join (self-join form)
    "sliding_join": (
        """
        @app:joinCapacity(size='8192')
        define stream StockStream (symbol string, price float, volume long);
        @info(name='q')
        from StockStream#window.length(100) as a join StockStream#window.length(100) as b
        on a.volume == b.volume
        select a.symbol as s1, b.symbol as s2
        insert into Out;
        """,
        "StockStream",
        1.0,
        8192,
    ),
    # BASELINE.json config 4: pattern `every A -> B within` (2-state NFA,
    # vectorized token-matrix fast path)
    "pattern_2state": (
        """
        @app:patternCapacity(size='4096')
        define stream StockStream (symbol string, price float, volume long);
        @info(name='q')
        from every a1=StockStream[price > 95] -> a2=StockStream[price < 5]
        within 1 sec
        select a1.symbol as s1, a2.symbol as s2
        insert into Out;
        """,
        "StockStream",
        1.0,
        None,
    ),
    # BASELINE.json config 5: DEBS-style count sequence with a kleene bound.
    # patternCapacity/patternChunk are ENGINE BUFFER knobs, not workload
    # semantics: the reference's pending lists are unbounded, and at this
    # data rate (10% match rate, min-count 2 -> ~410 armed generations per
    # 8192-row chunk < 512 lanes) the outputs are identical to any larger
    # sizing (overflow would be flagged + warned). The r5 kernel's wall is
    # gather/scatter ELEMENT traffic (~1 elem/cycle on the TPU scalar core),
    # so small token table + big chunk is the fast shape: 13.3 Mev/s device
    # vs r4's 1.6 at T=4096=chunk.
    "count_sequence": (
        """
        @app:patternCapacity(size='512')
        @app:patternChunk(size='8192')
        define stream StockStream (symbol string, price float, volume long);
        @info(name='q')
        from every a1=StockStream[price > 90]<2:4> -> a2=StockStream[price < 10]
        select a2.symbol as s2
        insert into Out;
        """,
        "StockStream",
        0.5,
        None,  # same batch as the sibling legs (VERDICT r2 item 2)
    ),
}


def _leg_throughput(name: str, n: int, batch: int) -> float:
    delivered = name.endswith("_delivered")
    ql, stream, mult, batch_override = WORKLOADS[
        name[: -len("_delivered")] if delivered else name
    ]
    batch = batch_override or batch
    events = max(int(n * mult), batch * 4)
    ql = f"@app:batch(size='{batch}')\n" + ql
    callback = None
    if delivered:
        # bigger fused chunks amortize the fixed per-transfer cost (sized
        # through the earlier network-attached set-up; unmeasured on a
        # directly attached chip)
        ql = "@app:ingestChunk(size='128')\n" + ql
        sink = [0]

        def callback(ts, ins, removed):
            # every delivered row is already a decoded host Event here
            sink[0] += len(ins or ()) + len(removed or ())

    needed = events + batch * 4
    data = _make_stock_data(needed)
    return _run_workload(ql, stream, data, events, batch, callback=callback)


def _leg_table_scaling(rows_list=(100_000, 1_000_000), batches=128) -> dict:
    """Events/s of a stream query probing+updating a table at capacity N.
    batch-1024 legs are the reproducible evidence for the exhaustive-scan-vs-
    index decision (VERDICT r1 item 9 / r2 weak #3); batch-8192 legs are the
    throughput-shaped extras. Reference analog: table/holder/IndexEventHolder
    primary-key fast path."""
    from siddhi_tpu import SiddhiManager

    out = {}
    for batch, pk, label_sfx in (
        (1024, False, "_b1024"),
        (8192, False, ""),
        (8192, True, "_pk"),  # @PrimaryKey -> O(B log C) sorted probe path
    ):
        for n_rows in rows_list:
            mgr = SiddhiManager()
            rt = mgr.create_siddhi_app_runtime(f"""
            @app:batch(size='{batch}')
            define stream Loader (k long, v long);
            define stream S (k long, v long);
            {"@PrimaryKey('k')" if pk else ""}
            @capacity(size='{n_rows}')
            define table T (k long, v long);
            @info(name='load') from Loader insert into T;
            @info(name='upd')
            from S select k, v update T on T.k == k;
            """)
            rt.start()
            lk = np.arange(n_rows, dtype=np.int64)
            rt.get_input_handler("Loader").send_columns(
                np.arange(n_rows, dtype=np.int64),
                {"k": lk, "v": lk},
            )
            rng = np.random.default_rng(3)
            ks = rng.integers(0, n_rows, size=batch * batches).astype(np.int64)
            vs = np.arange(batch * batches, dtype=np.int64)
            h = rt.get_input_handler("S")
            # warm with the SAME send size so the fused-ingest program
            # compiles before the clock starts (updates are key-idempotent)
            h.send_columns(np.arange(batch * batches, dtype=np.int64), {"k": ks, "v": vs})
            _truth_sync(rt)
            t0 = time.perf_counter()
            h.send_columns(np.arange(batch * batches, dtype=np.int64), {"k": ks, "v": vs})
            _truth_sync(rt)
            dt = time.perf_counter() - t0
            status = _snapshot_status(rt)
            rt.shutdown()
            mgr.shutdown()
            label = f"{n_rows // 1000}k" if n_rows < 1_000_000 else f"{n_rows // 1_000_000}m"
            out[f"table_update_{label}{label_sfx}"] = round(batch * batches / dt, 1)
            if status is not None:
                out[f"table_update_{label}{label_sfx}_status"] = status
    return out


def _leg_p99(batch=256, batches=96) -> dict:
    """p99/p99.99 detection latency: wall time from the START of a
    micro-batch send to the query callback having DELIVERED that batch's
    matches, vs the measured per-batch floor of this backend (dispatch +
    completion + readback). Target: p99 <=
    floor + 10 ms. The app runs with statistics on so the engine's
    continuous profiler (observability/profiler.py) attributes the WORST
    batch's stages (encode/dispatch/device/readback) into the detail blob —
    with <10k samples p9999 is the top sample, which is still the honest
    answer to "what did the worst send cost".

    The floor probe runs INTERLEAVED with the detection sends (one probe
    after each batch) so both distributions sample the SAME host conditions:
    a one-chip machine shares its host's cores, and a floor measured minutes
    later compares engine samples against a different load, not engine
    overhead."""
    import jax
    import jax.numpy as jnp

    from siddhi_tpu import SiddhiManager

    data = _make_stock_data(batch * (batches + 6))
    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime(f"""@app:batch(size='{batch}')
    @app:statistics(reporter='none')
    @app:patternCapacity(size='256')
    define stream StockStream (symbol string, price float, volume long);
    @info(name='q')
    from every a1=StockStream[price > 95] -> a2=StockStream[price < 5]
    within 1 sec
    select a1.symbol as s1, a2.symbol as s2
    insert into Out;
    """)
    _prime_interner(mgr, data["names"])
    fired = [0.0]
    rt.add_callback("q", lambda ts, i, r: fired.__setitem__(0, time.perf_counter()))
    rt.start()
    h = rt.get_input_handler("StockStream")
    cols = {k: v for k, v in data.items() if k not in ("ts", "names")}

    # floor probe: one dispatch + ready-wait + tiny readback, the same
    # round trip the callback path pays
    x = jnp.zeros((batch,), jnp.float32)
    f = jax.jit(lambda v: v.sum())
    np.asarray(f(x))

    lat = []
    floors = []
    for i in range(batches + 5):
        lo, hi = i * batch, (i + 1) * batch
        fired[0] = 0.0
        t0 = time.perf_counter()
        h.send_columns(data["ts"][lo:hi], {k: v[lo:hi] for k, v in cols.items()})
        t1 = fired[0] if fired[0] > 0.0 else time.perf_counter()
        t2 = time.perf_counter()
        np.asarray(f(x))  # paired floor sample, same host conditions
        t3 = time.perf_counter()
        if i >= 5:  # skip compile warmup
            lat.append((t1 - t0) * 1000)
            floors.append((t3 - t2) * 1000)
    status = _snapshot_status(rt)
    profile = None
    try:
        profile = rt.profile_report()
    except Exception:
        pass
    rt.shutdown()
    mgr.shutdown()
    # paired deltas isolate ENGINE overhead from host noise: each
    # detection sample is compared against its own immediately-following
    # floor probe, and the median delta is robust to a heavy-tailed
    # round-trip distribution (a p99-vs-p99 comparison is the single worst
    # sample of 60 draws on each side)
    deltas = sorted(a - b for a, b in zip(lat, floors))
    lat.sort()
    floors.sort()
    p99 = lat[max(0, math.ceil(len(lat) * 0.99) - 1)]
    out = {
        "p99_detect_ms": round(p99, 2),
        "p9999_detect_ms": round(
            lat[max(0, math.ceil(len(lat) * 0.9999) - 1)], 2
        ),
        "p99_floor_ms": round(floors[max(0, math.ceil(len(floors) * 0.99) - 1)], 2),
        "p9999_floor_ms": round(
            floors[max(0, math.ceil(len(floors) * 0.9999) - 1)], 2
        ),
        "p50_floor_ms": round(floors[len(floors) // 2], 2),
        "p50_detect_ms": round(lat[len(lat) // 2], 2),
        "engine_overhead_p50_ms": round(deltas[len(deltas) // 2], 2),
    }
    if profile is not None:
        # stage-attributed waterfall of the WORST chunk (continuous
        # profiler top-K ring) + the leg's compile ledger: the per-stage
        # measurement behind "the sync floor bounds p99"
        slowest = profile.get("waterfalls", {}).get("slowest") or []
        if slowest:
            out["p99_worst_chunk_waterfall"] = slowest[0]
        out["p99_compiles"] = {
            comp: {"compiles": ent["compiles"], "causes": ent["causes"]}
            for comp, ent in profile.get("compile", {}).items()
        }
    if status is not None:
        out["p99_status"] = status
    return out


def _leg_calibration(batch=256, chunks=6) -> dict:
    """Plan-vs-actual calibration sentinel (`--leg calibration`): a fused
    app shaped to exercise every prediction kind the ledger pairs —
    shared filter+window queries (selectivity, state bytes, dispatch
    reduction), a declared dict wire lane plus an inferred delta lane
    (both wire B/ev kinds), compiling under the fused group (compiles).
    The full calibration blob lands in the detail JSON; the CI sentinel
    asserts all six kinds pair and tools/calib_report.py diffs the blob
    against the committed baseline to catch prediction-error drift."""
    from siddhi_tpu import SiddhiManager

    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime(f"""@app:statistics(reporter='none')
    @app:batch(size='{batch}')
    @app:wire(dict.S.symbol='64')
    define stream S (symbol string, price float, volume long);
    @info(name='q1') from S[price > 50.0]#window.length(16)
    select symbol, price insert into Out1;
    @info(name='q2') from S[price > 50.0]#window.length(16)
    select symbol, max(price) as mp insert into Out2;
    @info(name='q3') from S#window.externalTimeBatch(volume, 1000)
    select symbol, sum(price) as sp insert into Out3;
    """)
    delivered = [0]
    for q in ("q1", "q2", "q3"):
        rt.add_callback(
            q,
            lambda ts, ins, rem, _d=delivered: _d.__setitem__(
                0, _d[0] + len(ins or ()) + len(rem or ())
            ),
        )
    rt.start()
    for s in ("A", "B", "C", "D"):
        mgr.interner.intern(s)
    n = batch * 4
    rng = np.random.default_rng(7)
    cols = {
        "symbol": rng.integers(1, 5, n).astype(np.int32),
        "price": rng.uniform(0, 100, n).astype(np.float32),
        "volume": (np.arange(n, dtype=np.int64) * 7) % 2000,
    }
    ts = np.arange(n, dtype=np.int64) + 1_700_000_000_000
    h = rt.get_input_handler("S")
    for k in range(chunks):
        h.send_columns(ts + k * n, cols, now=int(ts[-1] + k * n))
    _truth_sync(rt)
    rep = rt.calibration_report()
    status = _snapshot_status(rt)
    rt.shutdown()
    mgr.shutdown()
    out: dict = {"calibration_delivered_rows": delivered[0]}
    if rep is not None:
        out["calibration"] = rep
        out["calibration_kinds"] = rep.get("kinds_paired", [])
    if status is not None and "roofline" in status:
        out["calibration_roofline"] = status["roofline"]
    return out


def _leg_timebudget(batch=32768) -> dict:
    """Per-leg budget of the FUSED-INGEST PROGRAM ITSELF (VERDICT r3 item 1):
    for every headline leg, the wire width, host encode rate, one-chunk h2d
    time, and the device rate of the exact fused program the engine runs
    (pre-staged device wire, states donated, truth-synced). These terms
    provably bound the leg's end-to-end number and name its binding wall:
    e2e ~ K*B / (t_encode + t_h2d + t_device) per chunk, with h2d/d2h each
    paying a fixed per-transfer cost."""
    import jax
    import jax.numpy as jnp

    from siddhi_tpu import SiddhiManager

    out = {}

    # shared fixed costs: sync floor + bulk h2d bandwidth
    f = jax.jit(lambda v: v.sum())
    x = jnp.zeros((16,), jnp.float32)
    np.asarray(f(x))  # compile + one completed readback
    floors = []
    for _ in range(10):
        t0 = time.perf_counter()
        np.asarray(f(x))
        floors.append(time.perf_counter() - t0)
    floors.sort()
    out["sync_floor_ms"] = round(floors[len(floors) // 2] * 1e3, 1)
    host = np.zeros((64 << 20,), dtype=np.uint8)
    t0 = time.perf_counter()
    dev = jax.device_put(host)
    np.asarray(dev[:1])
    out["h2d_mb_s"] = round(64 / (time.perf_counter() - t0), 1)
    del dev, host

    for name, (ql, stream, _mult, batch_override) in WORKLOADS.items():
        bsz = batch_override or batch
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime(f"@app:batch(size='{bsz}')\n" + ql)
        _prime_interner(mgr, _make_stock_data(8)["names"])
        rt.start()
        fi = rt.junctions[stream].fused_ingest
        if fi is None or not fi.eligible():
            out[f"{name}_budget"] = "fused-ineligible"
            rt.shutdown(); mgr.shutdown()
            continue
        K = fi.K
        data = _make_stock_data(bsz * K)
        cols = {k: v for k, v in data.items() if k not in ("ts", "names")}
        # same narrow wire the engine would sample from this data
        encode, wire_bytes = fi.staged_codec(
            data["ts"][:bsz], {k: v[:bsz] for k, v in cols.items()}
        )
        t0 = time.perf_counter()
        bufs, counts, bases = [], np.full((K,), bsz, np.int32), np.zeros((K,), np.int64)
        for k in range(K):
            lo = k * bsz
            buf, base = encode(
                data["ts"][lo:lo + bsz],
                {kk: v[lo:lo + bsz] for kk, v in cols.items()}, bsz)
            bufs.append(buf)
            bases[k] = base
        wire = np.stack(bufs)
        t_encode = time.perf_counter() - t0
        ev = K * bsz

        def run_once(w):
            states = []
            for ep in fi.endpoints:
                if ep.qr.state is None:
                    ep.qr.state = ep.qr._fresh(ep.init_state(0))
                states.append(ep.qr.state)
            tstates = {}
            for ep in fi.endpoints:
                tstates.update(ep.qr._collect_table_states())
            ns, _t, _a, _lin, _p = fi._fused(
                tuple(states), tstates, w, counts, bases,
                np.int64(1_700_000_000_000))
            for ep, st in zip(fi.endpoints, ns):
                ep.qr.state = st
            return ns

        ns = run_once(wire)  # compile
        np.asarray(jax.tree_util.tree_leaves(ns)[0].ravel()[:1])
        dw = jax.device_put(wire)
        np.asarray(dw.ravel()[:1])
        # device-only: pre-staged wire, 3 calls, one truth sync
        t0 = time.perf_counter()
        for _ in range(3):
            ns = run_once(dw)
        np.asarray(jax.tree_util.tree_leaves(ns)[0].ravel()[:1])
        t_dev = (time.perf_counter() - t0) / 3
        # whole call as the ENGINE pays it: host wire shipped per call
        t0 = time.perf_counter()
        for _ in range(3):
            ns = run_once(wire)
        np.asarray(jax.tree_util.tree_leaves(ns)[0].ravel()[:1])
        t_call = (time.perf_counter() - t0) / 3
        t_h2d = max(t_call - t_dev, 0.0)
        walls = {"encode": t_encode, "h2d": t_h2d, "device": t_dev}
        out[f"{name}_wire_B_per_ev"] = round(wire.nbytes / ev, 1)
        # logical = what the FULL-WIDTH packed wire would ship for the same
        # events (core/wire.py); the ratio is the leg's wire reduction —
        # the acceptance signal of the compact-wire-encoding work
        from siddhi_tpu.core.wire import logical_row_bytes

        logical = logical_row_bytes(rt.junctions[stream].schema.attrs)
        out[f"{name}_logical_B_per_ev"] = logical
        out[f"{name}_wire_reduction"] = round(
            logical / max(wire.nbytes / ev, 0.1), 2
        )
        out[f"{name}_encode_mev_s"] = round(ev / t_encode / 1e6, 1)
        out[f"{name}_h2d_eff_ms"] = round(t_h2d * 1e3, 1)
        out[f"{name}_device_mev_s"] = round(ev / t_dev / 1e6, 2)
        # the engine PIPELINES encode with async dispatch, so the budget is
        # an interval, not a point: ceiling = perfectly overlapped (the
        # slowest single stage binds), floor = fully sequential. A measured
        # leg outside [floor, ceiling] means the budget's terms don't
        # describe the program it ran — main() flags it.
        out[f"{name}_ceiling_mev_s"] = round(
            ev / max(walls.values()) / 1e6, 2)
        out[f"{name}_floor_mev_s"] = round(
            ev / (t_encode + t_h2d + t_dev) / 1e6, 2)
        out[f"{name}_wall"] = max(walls, key=walls.get)
        # pipelined-vs-serial A/B through the REAL engine send path: the
        # same four-chunk send, once fully serialized and once with the
        # chunk pipeline (core/pipeline.py), so the measured overlap can be
        # compared against the budget's predicted interval — overlap_pred =
        # serial-sum / slowest-stage is the ceiling a perfect pipeline
        # could reach, overlap_meas = t_serial / t_pipelined is what the
        # engine actually got (four chunks: the first chunk has nothing to
        # overlap with, so a two-chunk send under-reports the steady state).
        data2 = _make_stock_data(bsz * K * 4)
        cols2 = {k: v for k, v in data2.items() if k not in ("ts", "names")}
        h = rt.get_input_handler(stream)
        ab = {}
        # 'raw' runs LAST: force_full_width discards the encoded programs
        # permanently (the same state a runtime misfit fallback lands in),
        # so enc (= the pipelined encoded send) vs raw is the engine-path
        # A/B of the wire encoding itself
        for mode, pipe_on in (
            ("serial", False), ("pipe", True), ("raw", True),
        ):
            fi.pipeline_enabled = pipe_on
            if mode == "raw":
                fi.force_full_width()
            h.send_columns(data2["ts"], cols2)  # warm this mode's path
            _truth_sync(rt)
            t0 = time.perf_counter()
            h.send_columns(data2["ts"], cols2)
            _truth_sync(rt)
            ab[mode] = time.perf_counter() - t0
        ev2 = bsz * K * 4
        out[f"{name}_serial_mev_s"] = round(ev2 / ab["serial"] / 1e6, 2)
        out[f"{name}_pipe_mev_s"] = round(ev2 / ab["pipe"] / 1e6, 2)
        out[f"{name}_enc_mev_s"] = out[f"{name}_pipe_mev_s"]
        out[f"{name}_raw_mev_s"] = round(ev2 / ab["raw"] / 1e6, 2)
        out[f"{name}_raw_B_per_ev"] = round(fi._wire_bytes / bsz, 1)
        out[f"{name}_overlap_meas"] = round(ab["serial"] / ab["pipe"], 2)
        out[f"{name}_overlap_pred"] = round(
            (t_encode + t_h2d + t_dev) / max(walls.values()), 2)
        rt.shutdown()
        mgr.shutdown()
    out.update(_fusedgroup_budget(batch))
    return out


# a stream with THREE fusable consumers, two of them sharing an identical
# filter+window chain: the shape the FusionPlan forms a group + shared ring
# on (core/fusion_exec.py). The unfused side of the A/B runs the same app
# with @app:fuse(disable='true') — per-batch dispatch to every consumer.
FUSED_GROUP_QL = """
define stream StockStream (symbol string, price float, volume long);
@info(name='q1') from StockStream[price > 50]#window.length(64)
select symbol, avg(price) as ap insert into Out1;
@info(name='q2') from StockStream[price > 50]#window.length(64)
select symbol, max(price) as mx insert into Out2;
@info(name='q3') from StockStream#window.lengthBatch(1024)
select sum(volume) as tv insert into Out3;
"""


def _fusedgroup_budget(batch: int) -> dict:
    """Whole-graph fusion A/B (timebudget detail, `fusedgroup_*` keys): one
    stream feeding a 3-query fusable group (two share a window ring). The
    fused run reports the group engine's achieved-vs-predicted dispatch
    reduction (n*K per-batch dispatches -> 1 per chunk) and the unfused run
    (@app:fuse(disable='true')) is the same app on the per-batch path —
    the dispatch-amortization headroom this engine's multi-query apps get."""
    # the A/B is driven by the per-mode @app:fuse annotation — a process-wide
    # SIDDHI_TPU_FUSE (as the CI parity steps export) overrides annotations
    # and would silently neutralize one side (=1 fuses the "unfused" control,
    # =0 never forms the group), so pin it off for the measurement
    saved_fuse = os.environ.pop("SIDDHI_TPU_FUSE", None)
    try:
        return _fusedgroup_budget_modes(batch)
    finally:
        if saved_fuse is not None:
            os.environ["SIDDHI_TPU_FUSE"] = saved_fuse


def _fusedgroup_budget_modes(batch: int) -> dict:
    from siddhi_tpu import SiddhiManager

    out: dict = {}
    K = None
    for mode, head in (("fused", ""), ("unfused", "@app:fuse(disable='true')\n")):
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime(
            f"{head}@app:batch(size='{batch}')\n" + FUSED_GROUP_QL
        )
        _prime_interner(mgr, _make_stock_data(8)["names"])
        rt.start()
        fi = rt.junctions["StockStream"].fused_ingest
        if mode == "fused":
            if fi is None or fi.plan_group is None:
                out["fusedgroup_budget"] = "group-not-formed"
                rt.shutdown(); mgr.shutdown()
                return out
            K = fi.K
        n = batch * (K or 32)
        data = _make_stock_data(n)
        cols = {k: v for k, v in data.items() if k not in ("ts", "names")}
        h = rt.get_input_handler("StockStream")
        h.send_columns(data["ts"], cols)  # warm: compile this mode's path
        _truth_sync(rt)
        t0 = time.perf_counter()
        h.send_columns(data["ts"], cols)
        _truth_sync(rt)
        dt = time.perf_counter() - t0
        out[f"fusedgroup_{mode}_mev_s"] = round(n / dt / 1e6, 2)
        if mode == "fused":
            rep = fi.group_report() or {}
            for k in (
                "component", "queries", "chunks", "batches",
                "dispatches_per_chunk_before", "dispatches_per_chunk_after",
                "predicted_dispatch_reduction",
                "achieved_dispatch_reduction", "shared_state",
            ):
                if k in rep:
                    out[f"fusedgroup_{k}"] = rep[k]
        rt.shutdown()
        mgr.shutdown()
    if out.get("fusedgroup_unfused_mev_s"):
        out["fusedgroup_speedup"] = round(
            out["fusedgroup_fused_mev_s"] / out["fusedgroup_unfused_mev_s"], 2
        )
    return out


# stateless multi-query app for the sharded-execution leg: both consumers
# are batch-axis shardable (parallel/shard.py router_eligible), so the whole
# junction round-robins micro-batches across the mesh. Checksums are integer
# sums over delivered rows — exact, so sharded == unsharded is a hard assert.
SHARD_WORKLOADS = {
    "shard_filter": """
        define stream StockStream (symbol string, price float, volume long);
        @info(name='q')
        from StockStream[price > 50] select symbol, volume insert into Out;
        """,
    "shard_project": """
        define stream StockStream (symbol string, price float, volume long);
        @info(name='q')
        from StockStream select symbol, volume * 2 as v2, volume % 7 as v7
        insert into Out;
        """,
}


def _leg_shard(n_shard: int, batch=4096, events=1_000_000) -> dict:
    """Sharded-vs-unsharded A/B of the batch-axis router (`--shard N`,
    meant to run under XLA_FLAGS=--xla_force_host_platform_device_count=N
    on CPU): for each stateless workload, the same columnar feed runs once
    with SIDDHI_TPU_SHARD=N and once unsharded; the leg reports per-device
    dispatch/event counts (their sum must equal the unsharded event count),
    an exact delivered-row checksum on both sides, per-workload scaling,
    and the geomean scaling vs 1 device."""
    import jax

    from siddhi_tpu import SiddhiManager

    out: dict = {
        "shard_devices_requested": n_shard,
        "shard_devices_visible": len(jax.devices()),
        "shard_batch": batch,
    }
    data = _make_stock_data(events)
    cols = {k: v for k, v in data.items() if k not in ("ts", "names")}
    scalings = []
    for name, ql in SHARD_WORKLOADS.items():
        ql = f"@app:batch(size='{batch}')\n" + ql
        res = {}
        for mode, env_val in (("unsharded", "0"), ("sharded", str(n_shard))):
            saved = os.environ.get("SIDDHI_TPU_SHARD")
            os.environ["SIDDHI_TPU_SHARD"] = env_val
            try:
                mgr = SiddhiManager()
                rt = mgr.create_siddhi_app_runtime(ql)
            finally:
                if saved is None:
                    os.environ.pop("SIDDHI_TPU_SHARD", None)
                else:
                    os.environ["SIDDHI_TPU_SHARD"] = saved
            _prime_interner(mgr, data["names"])
            sink = [0, 0]  # rows, integer checksum

            def cb(ts, ins, removed, _s=sink):
                for e in ins or ():
                    _s[0] += 1
                    _s[1] += int(e.data[-1])
            rt.add_callback("q", cb)
            rt.start()
            h = rt.get_input_handler("StockStream")
            warm = batch * 8
            h.send_columns(
                data["ts"][:warm], {k: v[:warm] for k, v in cols.items()}
            )
            _truth_sync(rt)
            sink[0] = sink[1] = 0
            t0 = time.perf_counter()
            h.send_columns(data["ts"], cols)
            _truth_sync(rt)
            dt = time.perf_counter() - t0
            res[mode] = {
                "mev_s": round(events / dt / 1e6, 3),
                "rows": sink[0],
                "checksum": sink[1],
            }
            if mode == "sharded":
                fi = rt.junctions["StockStream"].fused_ingest
                sr = getattr(fi, "shard_router", None) if fi else None
                if sr is not None:
                    res["per_device_dispatches"] = list(sr.dispatches)
                    res["per_device_events"] = list(sr.events)
            rt.shutdown()
            mgr.shutdown()
        out[f"{name}_unsharded_mev_s"] = res["unsharded"]["mev_s"]
        out[f"{name}_sharded_mev_s"] = res["sharded"]["mev_s"]
        out[f"{name}_scaling"] = round(
            res["sharded"]["mev_s"] / res["unsharded"]["mev_s"], 3
        )
        scalings.append(out[f"{name}_scaling"])
        out[f"{name}_per_device_dispatches"] = res.get(
            "per_device_dispatches", []
        )
        out[f"{name}_per_device_events"] = res.get("per_device_events", [])
        # warmup events ride the router too, so compare the TIMED window
        # via delivered rows + checksum, and the full per-device event sum
        # against everything sent (warm + timed)
        out[f"{name}_per_device_events_sum"] = int(
            sum(res.get("per_device_events", []))
        )
        out[f"{name}_events_sent_total"] = events + batch * 8
        out[f"{name}_rows_match"] = (
            res["sharded"]["rows"] == res["unsharded"]["rows"]
        )
        out[f"{name}_checksum_match"] = (
            res["sharded"]["checksum"] == res["unsharded"]["checksum"]
        )
        out[f"{name}_checksum"] = res["sharded"]["checksum"]
    out["shard_scaling_geomean"] = round(
        math.exp(sum(math.log(max(s, 1e-9)) for s in scalings) / len(scalings)),
        3,
    ) if scalings else 0.0
    return out


# key-sharded STATEFUL workloads (`--leg shardstate`, parallel/keyshard.py):
# the keys axis hashes group-by aggregation state and join window rings
# across the mesh. Both sides of each A/B must deliver identical rows AND
# an identical integer checksum (the byte-parity contract), and the
# sharded group-by's per-device key ownership must sum to the total key
# count. Integer aggregators only — float scans are reassociation-
# sensitive under the owner mask and deliberately ineligible.
SHARDSTATE_GROUPBY = """
        define stream StockStream (symbol string, price float, volume long);
        @info(name='q')
        from StockStream
        select symbol, sum(volume) as sv, min(volume) as mn, count() as c
        group by symbol insert into Out;
        """

SHARDSTATE_JOIN = """
        @app:joinCapacity(size='65536')
        define stream StockStream (symbol string, price float, volume long);
        define stream QuoteStream (symbol string, price float, volume long);
        @info(name='q')
        from StockStream#window.length(8) join QuoteStream#window.length(8)
            on StockStream.symbol == QuoteStream.symbol
        select StockStream.symbol as s, QuoteStream.price as qp,
            StockStream.volume as av
        insert into Out;
        """


def _make_keyed_data(n: int, n_keys: int, seed: int = 7):
    rng = np.random.default_rng(seed)
    return {
        "ts": np.arange(n, dtype=np.int64) + 1_700_000_000_000,
        "symbol": rng.integers(1, n_keys + 1, size=n).astype(np.int32),
        "price": rng.uniform(0.0, 100.0, size=n).astype(np.float32),
        "volume": rng.integers(1, 1000, size=n).astype(np.int64),
        "names": [f"K{i}" for i in range(n_keys)],
    }


def _leg_shardstate(n_shard: int, batch=4096, events=400_000) -> dict:
    """Keyed-shard A/B (`--leg shardstate --shard N`): group-by-heavy and
    join workloads run the same feed with SIDDHI_TPU_SHARD=N +
    SIDDHI_TPU_SHARD_AXIS=keys and once unsharded. Reports per-workload
    throughput and scaling, exact row/checksum parity, per-device key
    ownership (must sum to the total), a key-count scaling sweep, and the
    geomean scaling."""
    import jax

    from siddhi_tpu import SiddhiManager

    out: dict = {
        "shardstate_devices_requested": n_shard,
        "shardstate_devices_visible": len(jax.devices()),
        "shardstate_batch": batch,
    }

    def run(ql, data, sharded: bool, join_feed=False):
        saved = {
            k: os.environ.get(k)
            for k in ("SIDDHI_TPU_SHARD", "SIDDHI_TPU_SHARD_AXIS")
        }
        os.environ["SIDDHI_TPU_SHARD"] = str(n_shard) if sharded else "0"
        os.environ["SIDDHI_TPU_SHARD_AXIS"] = "keys"
        try:
            mgr = SiddhiManager()
            rt = mgr.create_siddhi_app_runtime(
                f"@app:batch(size='{batch}')\n" + ql
            )
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        _prime_interner(mgr, data["names"])
        sink = [0, 0]  # rows, integer checksum

        def cb(ts, ins, removed, _s=sink):
            for e in ins or ():
                _s[0] += 1
                _s[1] += int(e.data[-1])
        rt.add_callback("q", cb)
        rt.start()
        cols = {k: v for k, v in data.items() if k not in ("ts", "names")}
        n = len(data["ts"])
        if join_feed:
            # prime the quote ring once so both sides probe identical state
            qn = batch
            rt.get_input_handler("QuoteStream").send_columns(
                data["ts"][:qn], {k: v[:qn] for k, v in cols.items()}
            )
        h = rt.get_input_handler("StockStream")
        warm = min(batch * 4, n)
        h.send_columns(
            data["ts"][:warm], {k: v[:warm] for k, v in cols.items()}
        )
        _truth_sync(rt)
        sink[0] = sink[1] = 0
        t0 = time.perf_counter()
        h.send_columns(data["ts"], cols)
        _truth_sync(rt)
        dt = time.perf_counter() - t0
        res = {
            "mev_s": round(n / dt / 1e6, 3),
            "rows": sink[0],
            "checksum": sink[1],
        }
        qr = rt.queries["q"]
        ks = getattr(qr, "_keyshard", None)
        if ks is not None:
            desc = ks.describe_state()
            res["per_device_keys"] = desc.get("per_device_keys", [])
            res["total_keys"] = desc.get("total_keys", 0)
            res["skew"] = desc.get("skew")
        res["join_sharded"] = bool(getattr(qr, "_joinshard", False))
        rt.shutdown()
        mgr.shutdown()
        return res

    scalings = []
    for name, ql, join_feed in (
        ("keyshard_groupby", SHARDSTATE_GROUPBY, False),
        ("keyshard_join", SHARDSTATE_JOIN, True),
    ):
        data = _make_keyed_data(events, 8)
        a = run(ql, data, sharded=False, join_feed=join_feed)
        b = run(ql, data, sharded=True, join_feed=join_feed)
        out[f"{name}_unsharded_mev_s"] = a["mev_s"]
        out[f"{name}_sharded_mev_s"] = b["mev_s"]
        out[f"{name}_scaling"] = round(b["mev_s"] / a["mev_s"], 3)
        scalings.append(out[f"{name}_scaling"])
        out[f"{name}_rows_match"] = a["rows"] == b["rows"]
        out[f"{name}_checksum_match"] = a["checksum"] == b["checksum"]
        out[f"{name}_checksum"] = b["checksum"]
        if name == "keyshard_groupby":
            out[f"{name}_per_device_keys"] = b.get("per_device_keys", [])
            out[f"{name}_total_keys"] = b.get("total_keys", 0)
            out[f"{name}_keys_sum_match"] = (
                sum(b.get("per_device_keys", [])) == b.get("total_keys", -1)
            )
            out[f"{name}_skew"] = b.get("skew")
        else:
            out[f"{name}_join_sharded"] = b["join_sharded"]
    # key-count sweep: same sharded group-by at rising key cardinality —
    # occupancy spreads, throughput should hold or improve per key
    sweep = {}
    for n_keys in (8, 64, 512):
        data = _make_keyed_data(min(events, 200_000), n_keys, seed=11)
        b = run(SHARDSTATE_GROUPBY, data, sharded=True)
        sweep[str(n_keys)] = {
            "mev_s": b["mev_s"],
            "total_keys": b.get("total_keys", 0),
            "keys_sum_match": (
                sum(b.get("per_device_keys", [])) == b.get("total_keys", -1)
            ),
        }
    out["keyshard_key_sweep"] = sweep
    out["shardstate_scaling_geomean"] = round(
        math.exp(sum(math.log(max(s, 1e-9)) for s in scalings) / len(scalings)),
        3,
    ) if scalings else 0.0
    return out


# compact-wire-encoding workloads (`--leg wire`, core/wire.py): one
# dictionary-heavy stream (low-cardinality interned symbols + a declared
# qty range) and one delta-timestamp stream (monotone LONG seq). Each runs
# the SAME columnar feed with SIDDHI_TPU_WIRE=1 vs =0 (full width) and
# must deliver identical rows; the leg reports both sides' bytes/event,
# throughput, and the encoded-over-raw reduction, plus a forced MID-STREAM
# fallback case (cardinality overflow after the encoded steady state).
WIRE_WORKLOADS = {
    "wire_dict": (
        """
        @app:wire(dict.Ticks.sym='64', range.Ticks.qty='0..30000')
        define stream Ticks (sym string, price float, qty long);
        @info(name='q') from Ticks[qty > 10] select sym, qty insert into Out;
        """,
        "Ticks",
    ),
    "wire_delta": (
        """
        @app:wire(delta.Meters.seq='int16')
        define stream Meters (seq long, v float);
        @info(name='q') from Meters[v >= 0] select seq, v insert into Out;
        """,
        "Meters",
    ),
    # the UN-annotated twin of wire_delta: no @app:wire at all — the value
    # analysis (analysis/values.py) must PROVE seq monotone from its use as
    # externalTimeBatch's event-time variable and delta-encode it with no
    # hint. The leg reports how much of wire_delta's hinted reduction the
    # inference recovers (`wire_delta_inferred_recovery`).
    "wire_delta_inferred": (
        """
        define stream Meters (seq long, v float);
        @info(name='q') from Meters#window.externalTimeBatch(seq, 1000)
        select seq, v insert into Out;
        """,
        "Meters",
    ),
}


def _leg_wire(batch=4096, events=400_000) -> dict:
    """Wire-encoding A/B (`--leg wire`): per workload, the same feed runs
    encoded (SIDDHI_TPU_WIRE=1: the @app:wire static spec engages) and raw
    (=0: full-width wire), with exact delivered-row counts + integer
    checksums on both sides, per-side wire bytes/event, and the byte
    reduction. Ends with the runtime-guard case: a batch violating the
    declared dictionary cardinality arrives AFTER the encoded steady
    state, the engine falls back full-width mid-stream, and the delivered
    rows must still match the raw run exactly."""
    from siddhi_tpu import SiddhiManager

    out: dict = {"wire_batch": batch}
    rng = np.random.default_rng(11)
    n = max(batch * 16, min(events, 1_000_000))
    feeds = {
        "wire_dict": (
            np.arange(n, dtype=np.int64) + 1_700_000_000_000,
            {
                "sym": rng.integers(1, 33, n).astype(np.int32),
                "price": rng.uniform(0, 100, n).astype(np.float32),
                "qty": rng.integers(0, 1000, n).astype(np.int64),
            },
        ),
        "wire_delta": (
            np.arange(n, dtype=np.int64) + 1_700_000_000_000,
            {
                "seq": np.arange(n, dtype=np.int64) + 10**12,
                "v": rng.uniform(0, 10, n).astype(np.float32),
            },
        ),
    }
    feeds["wire_delta_inferred"] = feeds["wire_delta"]

    def run(name, ql, stream, env_val, feed, cb_col):
        saved = os.environ.get("SIDDHI_TPU_WIRE")
        os.environ["SIDDHI_TPU_WIRE"] = env_val
        try:
            mgr = SiddhiManager()
            rt = mgr.create_siddhi_app_runtime(
                f"@app:batch(size='{batch}')\n" + ql
            )
        finally:
            if saved is None:
                os.environ.pop("SIDDHI_TPU_WIRE", None)
            else:
                os.environ["SIDDHI_TPU_WIRE"] = saved
        for i in range(1, 400):
            mgr.interner.intern(f"SYM{i}")
        sink = [0, 0]  # rows, integer checksum

        def cb(ts, ins, removed, _s=sink):
            for e in ins or ():
                _s[0] += 1
                _s[1] += int(e.data[cb_col])
        rt.add_callback("q", cb)
        rt.start()
        h = rt.get_input_handler(stream)
        ts_arr, cols = feed
        warm = batch * 4
        h.send_columns(
            ts_arr[:warm], {k: v[:warm] for k, v in cols.items()}
        )
        _truth_sync(rt)
        sink[0] = sink[1] = 0
        t0 = time.perf_counter()
        h.send_columns(ts_arr, cols)
        _truth_sync(rt)
        dt = time.perf_counter() - t0
        fi = rt.junctions[stream].fused_ingest
        res = {
            "mev_s": round(len(ts_arr) / dt / 1e6, 3),
            "rows": sink[0],
            "checksum": sink[1],
            "B_per_ev": round(fi._wire_bytes / batch, 2) if fi else None,
        }
        rt.shutdown()
        mgr.shutdown()
        return res

    for name, (ql, stream) in WIRE_WORKLOADS.items():
        cb_col = 1 if name == "wire_dict" else 0
        enc = run(name, ql, stream, "1", feeds[name], cb_col)
        raw = run(name, ql, stream, "0", feeds[name], cb_col)
        out[f"{name}_enc_mev_s"] = enc["mev_s"]
        out[f"{name}_raw_mev_s"] = raw["mev_s"]
        out[f"{name}_enc_B_per_ev"] = enc["B_per_ev"]
        out[f"{name}_raw_B_per_ev"] = raw["B_per_ev"]
        if enc["B_per_ev"] and raw["B_per_ev"]:
            out[f"{name}_reduction"] = round(
                raw["B_per_ev"] / enc["B_per_ev"], 2
            )
        out[f"{name}_rows_match"] = enc["rows"] == raw["rows"]
        out[f"{name}_checksum_match"] = enc["checksum"] == raw["checksum"]
        out[f"{name}_rows"] = enc["rows"]
    # how much of the DECLARED delta hint's byte reduction pure inference
    # recovers on the un-annotated twin (ISSUE: must be >= 0.8 in CI)
    if out.get("wire_delta_reduction") and out.get(
        "wire_delta_inferred_reduction"
    ):
        out["wire_delta_inferred_recovery"] = round(
            out["wire_delta_inferred_reduction"]
            / out["wire_delta_reduction"], 3
        )

    # forced mid-stream fallback: after the dict-encoded steady state, a
    # burst with 300 distinct symbols (> the declared 64) arrives — the
    # runtime guard rebuilds full-width and NOTHING may be lost or differ
    ql, stream = WIRE_WORKLOADS["wire_dict"]
    ts_arr, cols = feeds["wire_dict"]
    nb = batch * 8
    burst = {
        "sym": (np.arange(nb, dtype=np.int32) % 300) + 1,
        "price": np.full(nb, 50.0, np.float32),
        "qty": np.full(nb, 500, np.int64),
    }
    sides = {}
    for env_val in ("1", "0"):
        saved = os.environ.get("SIDDHI_TPU_WIRE")
        os.environ["SIDDHI_TPU_WIRE"] = env_val
        try:
            mgr = SiddhiManager()
            rt = mgr.create_siddhi_app_runtime(
                f"@app:batch(size='{batch}')\n" + ql
            )
        finally:
            if saved is None:
                os.environ.pop("SIDDHI_TPU_WIRE", None)
            else:
                os.environ["SIDDHI_TPU_WIRE"] = saved
        for i in range(1, 400):
            mgr.interner.intern(f"SYM{i}")
        rows = []
        rt.add_callback(
            "q", lambda t, ins, rem, _r=rows: _r.extend(
                tuple(e.data) for e in (ins or ())
            )
        )
        rt.start()
        h = rt.get_input_handler(stream)
        steady = batch * 8
        h.send_columns(
            ts_arr[:steady], {k: v[:steady] for k, v in cols.items()}
        )
        h.send_columns(ts_arr[steady : steady + nb], burst)
        _truth_sync(rt)
        fi = rt.junctions[stream].fused_ingest
        sides[env_val] = (rows, fi._narrow if fi else None)
        rt.shutdown()
        mgr.shutdown()
    out["wire_fallback_rows_match"] = sides["1"][0] == sides["0"][0]
    out["wire_fallback_rows"] = len(sides["1"][0])
    out["wire_fallback_full_width"] = sides["1"][1] == {}
    return out


VERIFY_HEAD = (
    "@app:batch(size='32')\n"
    "define stream S (symbol string, price float, volume long);\n"
)

# ~20 representative behaviors for the CPU-vs-TPU differential (VERDICT r2
# item 4): the same app + events run on both backends; rows must match within
# float tolerance. Each case: (QL, store-queries to read afterwards).
VERIFY_CASES = {
    "filter_num": VERIFY_HEAD + "@info(name='q') from S[price > 50 and volume < 800] select symbol, price insert into Out;",
    "filter_str": VERIFY_HEAD + "@info(name='q') from S[symbol == 'IBM' or symbol == 'WSO2'] select symbol, volume insert into Out;",
    "arith_promote": VERIFY_HEAD + "@info(name='q') from S select symbol, price * 2 as p2, volume / 7 as v7, volume % 5 as v5 insert into Out;",
    "builtins": VERIFY_HEAD + "@info(name='q') from S select ifThenElse(price > 50, 'hi', 'lo') as tag, cast(volume, 'double') as vd, maximum(price, 50.0) as mx insert into Out;",
    "len_window_avg": VERIFY_HEAD + "@info(name='q') from S#window.length(7) select symbol, avg(price) as ap, sum(volume) as tv insert into Out;",
    "len_window_minmax": VERIFY_HEAD + "@info(name='q') from S#window.length(5) select min(price) as mn, max(price) as mx insert into Out;",
    "len_batch_group": VERIFY_HEAD + "@info(name='q') from S#window.lengthBatch(8) select symbol, sum(volume) as tv, count() as c group by symbol insert into Out;",
    "time_window": "@app:playback\n" + VERIFY_HEAD + "@info(name='q') from S#window.time(40) select symbol, sum(volume) as tv insert into Out;",
    "external_time": VERIFY_HEAD + "@info(name='q') from S#window.externalTime(volume, 500) select symbol, count() as c insert into Out;",
    "stddev_distinct": VERIFY_HEAD + "@info(name='q') from S#window.length(9) select stdDev(price) as sd, distinctCount(symbol) as dc insert into Out;",
    "having_order": VERIFY_HEAD + "@info(name='q') from S#window.lengthBatch(8) select symbol, sum(volume) as tv group by symbol having tv > 100 order by tv desc limit 3 insert into Out;",
    "self_join": VERIFY_HEAD + """@app:joinCapacity(size='256')
        @info(name='q') from S#window.length(4) as a join S#window.length(4) as b
        on a.volume == b.volume select a.symbol as s1, b.symbol as s2 insert into Out;""",
    "pattern_within": VERIFY_HEAD + """@app:patternCapacity(size='64')
        @info(name='q') from every a=S[price > 90] -> b=S[price < 10] within 100 milliseconds
        select a.symbol as s1, b.symbol as s2 insert into Out;""",
    "count_seq": VERIFY_HEAD + """@app:patternCapacity(size='64')
        @info(name='q') from every a=S[price > 80]<2:3> -> b=S[price < 20]
        select b.symbol as s2 insert into Out;""",
    "logical_pattern": VERIFY_HEAD + """@app:patternCapacity(size='64')
        @info(name='q') from every (a=S[price > 90] and b=S[volume > 500])
        select a.price as pa, b.volume as vb insert into Out;""",
    "sort_window": VERIFY_HEAD + "@info(name='q') from S#window.sort(5, price) select min(price) as mn, count() as c insert into Out;",
    "frequent": VERIFY_HEAD + "@info(name='q') from S#window.frequent(3, symbol) select symbol, count() as c insert into Out;",
    "stream_fn": VERIFY_HEAD + "@info(name='q') from S#log('v') select symbol, price insert into Out;",
    # multi-query-per-stream app: q/q2 share an identical filter+window
    # chain (one FusionPlan shared ring), q3 fuses alongside, and q4's rate
    # limiter is an SA124 hazard riding the residual per-batch path — rows
    # are collected PER QUERY so the fuse-on/off CI diff compares each
    # consumer's own delivery order (core/fusion_exec.py)
    "multi_query_shared": VERIFY_HEAD + """@info(name='q') from S[price > 40]#window.length(6) select symbol, avg(price) as ap insert into Out1;
        @info(name='q2') from S[price > 40]#window.length(6) select symbol, max(price) as mx insert into Out2;
        @info(name='q3') from S#window.lengthBatch(8) select sum(volume) as tv insert into Out3;
        @info(name='q4') from S[volume > 300] select symbol, volume output every 5 events insert into Out4;""",
}

# cases observed via store queries over tables instead of callbacks
VERIFY_TABLE_CASES = {
    "table_crud": (
        VERIFY_HEAD + """@capacity(size='512') define table T (symbol string, total long);
        @info(name='w') from S#window.lengthBatch(8)
        select symbol, sum(volume) as total group by symbol
        update or insert into T on T.symbol == symbol;""",
        "from T select symbol, total",
    ),
    "partitioned": (
        VERIFY_HEAD + """@app:partitionCapacity(size='16')
        @capacity(size='2048') define table T (symbol string, ap float);
        partition with (symbol of S) begin
        @info(name='w') from S[price > 20] select symbol, price as ap
        insert into T;
        end;""",
        "from T select symbol, ap",
    ),
}


def _leg_verify() -> dict:
    """Run every verify case on the CURRENT backend and return its rows.

    With SIDDHI_TPU_VERIFY_COLUMNAR=1 the same events are ingested
    COLUMNARLY (one send_columns call, symbols pre-interned) so the fused
    path actually engages — the CI parity step runs the leg twice in this
    mode, SIDDHI_TPU_PIPELINE=1 vs =0, and diffs the rows; holding the
    ingestion mode fixed isolates the pipeline (row-by-row vs columnar
    feeds legitimately batch differently), and a per-row feed would never
    reach try_send at all."""
    from siddhi_tpu import SiddhiManager

    columnar = os.environ.get("SIDDHI_TPU_VERIFY_COLUMNAR", "").lower() in (
        "1", "on", "true",
    )
    rng = np.random.default_rng(99)
    n = 96
    ts = np.arange(n, dtype=np.int64) * 7 + 1_700_000_000_000
    rows = [
        (
            ["WSO2", "IBM", "GOOG", "MSFT"][int(rng.integers(0, 4))],
            float(np.round(rng.uniform(0.0, 100.0), 3)),
            int(rng.integers(1, 1000)),
        )
        for _ in range(n)
    ]

    def feed(mgr, h):
        if columnar:
            cols = {
                "symbol": np.array(
                    [mgr.interner.intern(r[0]) for r in rows], np.int32
                ),
                "price": np.array([r[1] for r in rows], np.float32),
                "volume": np.array([r[2] for r in rows], np.int64),
            }
            h.send_columns(ts, cols, now=int(ts[-1]))
        else:
            for i, r in enumerate(rows):
                h.send(r, timestamp=int(ts[i]))

    out: dict = {}
    def _collector(rows: list):
        return lambda t, ins, rem: rows.extend(
            [("+",) + tuple(e.data) for e in (ins or [])]
            + [("-",) + tuple(e.data) for e in (rem or [])]
        )

    for name, ql in VERIFY_CASES.items():
        try:
            mgr = SiddhiManager()
            rt = mgr.create_siddhi_app_runtime(ql)
            if len(rt.queries) > 1:
                # multi-query app: one row list per query, so the fused
                # group's per-endpoint drain order is compared per consumer
                got: dict = {qid: [] for qid in rt.queries}
                for qid in rt.queries:
                    rt.add_callback(qid, _collector(got[qid]))
            else:
                got = []
                rt.add_callback("q", _collector(got))
            rt.start()
            feed(mgr, rt.get_input_handler("S"))
            rt.shutdown()
            mgr.shutdown()
            out[name] = got
        except Exception as e:
            out[name] = f"ERROR: {type(e).__name__}: {e}"
    for name, (ql, sq) in VERIFY_TABLE_CASES.items():
        try:
            mgr = SiddhiManager()
            rt = mgr.create_siddhi_app_runtime(ql)
            rt.start()
            feed(mgr, rt.get_input_handler("S"))
            out[name] = sorted(
                tuple(e.data) for e in rt.query(sq)
            )
            rt.shutdown()
            mgr.shutdown()
        except Exception as e:
            out[name] = f"ERROR: {type(e).__name__}: {e}"
    import jax

    return {"cases": out, "backend": jax.default_backend()}


def _rows_match(a, b, tol=2e-4):
    if type(a) is not type(b):
        return False
    if isinstance(a, dict):  # multi-query cases: rows keyed per query
        return set(a) == set(b) and all(
            _rows_match(a[k], b[k], tol) for k in a
        )
    if isinstance(a, (list, tuple)):
        return len(a) == len(b) and all(_rows_match(x, y, tol) for x, y in zip(a, b))
    if isinstance(a, float):
        if b == 0:
            return abs(a) < tol
        return abs(a - b) <= tol * max(1.0, abs(a), abs(b))
    return a == b


def _verify_tpu_vs_cpu(args) -> dict:
    """Run the verify cases on the default (TPU) backend and on CPU in
    separate subprocesses; diff per case with float tolerance."""
    results = {}
    backends = {}
    for plat in ("tpu", "cpu"):
        cmd = [sys.executable, os.path.abspath(__file__), "--leg", "verify_cases"]
        env = dict(os.environ)
        env["SIDDHI_TPU_AUX_DRAIN_S"] = "0"
        if plat == "cpu":
            env["JAX_PLATFORMS"] = "cpu"
        else:
            # the accelerator side must not inherit a dev shell's CPU pin,
            # or the differential silently compares CPU against CPU
            env.pop("JAX_PLATFORMS", None)
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=650, env=env,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        line = proc.stdout.strip().splitlines()[-1] if proc.stdout.strip() else "{}"
        got = json.loads(line) if proc.returncode == 0 else {}
        results[plat] = got.get("cases", {})
        backends[plat] = got.get("backend", "subprocess-failed")
    per_case = {}
    for name in sorted(set(results["tpu"]) | set(results["cpu"])):
        a, b = results["tpu"].get(name), results["cpu"].get(name)
        if isinstance(a, str) or isinstance(b, str):
            per_case[name] = "FAIL"  # an ERROR on either side never passes
            continue
        # JSON round-trip turns tuples into lists on both sides equally
        per_case[name] = "pass" if _rows_match(a, b) else "FAIL"
    if backends["tpu"] == backends["cpu"]:
        # same backend on both sides = no differential at all; fail loudly
        per_case = {k: "FAIL(same-backend)" for k in per_case}
    n_pass = sum(1 for v in per_case.values() if v == "pass")
    artifact = {
        "n_pass": n_pass,
        "n_cases": len(per_case),
        "backends": backends,
        "per_case": per_case,
    }
    try:
        with open(
            os.path.join(os.path.dirname(os.path.abspath(__file__)), "VERIFY.json"),
            "w",
        ) as f:
            json.dump(
                {**artifact, "tpu": results["tpu"], "cpu": results["cpu"]},
                f, indent=1, default=str,
            )
    except Exception:
        pass
    return {"verify_pass": n_pass, "verify_cases": len(per_case)}


def _leg_disorder(events: int) -> dict:
    """A/B disorder run under @app:watermark: an ordered feed vs the SAME
    feed shuffled within the watermark bound by the seeded `ingest_disorder`
    fault site, pushed through the bounded reorder stage. Reports the
    shuffled run's throughput, reorder-buffer occupancy, watermark-lag p99
    across the feed, late-event counts, and whether the two runs' emissions
    (rows + checksum) match exactly — the engine-level parity headline."""
    import zlib

    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.testing import faults

    n = max(4_096, min(int(events), 200_000))
    base = 1_700_000_000_000
    step_ms = 7
    jitter_ms = 1500  # < the 2 sec bound below; displaces rows ~214 slots
    ql = """
    @app:watermark(bound='2 sec')
    define stream S (sym string, price double, vol long);
    @info(name='q')
    from S#window.length(64)
    select sym, sum(price) as total, count() as cnt
    insert into Out;
    """
    rng = np.random.default_rng(5)
    ts = base + np.arange(n, dtype=np.int64) * step_ms
    syms = np.asarray([f"S{i % 8}" for i in range(n)])
    price = np.round(rng.uniform(10.0, 100.0, n), 2)
    vol = rng.integers(1, 500, n).astype(np.int64)
    chunk = 2048

    def run(disorder: bool) -> dict:
        if disorder:
            faults.install(faults.parse_plan(
                f"seed=29;ingest_disorder:jitter={jitter_ms},times=-1"
            ))
        try:
            mgr = SiddhiManager()
            rt = mgr.create_siddhi_app_runtime(ql)
            crc = [0]
            rows = [0]

            def on_out(evs):
                for e in evs:
                    s = f"{e.timestamp}|{e.data[0]}|{e.data[1]:.3f}|{e.data[2]}"
                    crc[0] = zlib.crc32(s.encode(), crc[0])
                rows[0] += len(evs)

            rt.add_callback("Out", on_out)
            rt.start()
            tracker = rt._watermark.trackers
            lags, occupancy = [], []
            h = rt.get_input_handler("S")
            t0 = time.perf_counter()
            for i in range(0, n, chunk):
                h.send_columns(
                    ts[i:i + chunk],
                    {
                        "sym": syms[i:i + chunk],
                        "price": price[i:i + chunk],
                        "vol": vol[i:i + chunk],
                    },
                )
                d = tracker["S"].describe()
                if d["lag_ms"] is not None:
                    lags.append(d["lag_ms"])
                occupancy.append(d["buffered"])
            rt.drain_watermarks()
            wall = time.perf_counter() - t0
            ws = rt.snapshot_status()["watermark"]["streams"]["S"]
            rt.shutdown()
            mgr.shutdown()
            return {
                "events_per_s": n / wall if wall > 0 else 0.0,
                "rows": rows[0],
                "crc": crc[0],
                "lag_p99_ms": (
                    float(np.percentile(np.asarray(lags), 99)) if lags else 0.0
                ),
                "mean_buffered": (
                    float(np.mean(occupancy)) if occupancy else 0.0
                ),
                "peak_buffered": ws["peak_buffered"],
                "released": ws["released"],
                "late_total": ws["late_total"],
            }
        finally:
            if disorder:
                faults.uninstall()

    ordered = run(disorder=False)
    shuffled = run(disorder=True)
    return {
        "disorder": round(shuffled["events_per_s"], 1),
        "disorder_parity": (
            ordered["rows"] == shuffled["rows"]
            and ordered["crc"] == shuffled["crc"]
            and ordered["rows"] > 0
        ),
        "disorder_rows": shuffled["rows"],
        "disorder_lag_p99_ms": round(shuffled["lag_p99_ms"], 1),
        "disorder_peak_buffered": shuffled["peak_buffered"],
        "disorder_mean_buffered": round(shuffled["mean_buffered"], 1),
        "disorder_released": shuffled["released"],
        "disorder_late_total": shuffled["late_total"],
        "disorder_ordered_events_per_s": round(ordered["events_per_s"], 1),
    }


def _leg_blackbox(events: int, batch: int) -> dict:
    """A/B cost of the always-on black-box recorder (ISSUE 20): the SAME
    columnar feed runs with `@app:blackbox` armed and unarmed, reporting
    the recorder's throughput overhead (ring writes are preallocated
    column copies — the FlightRecorder budget), then fires a synthetic
    incident and replays the frozen bundle in-process, reporting whether
    the replay reproduced the live emissions byte-identical."""
    import tempfile

    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.observability.blackbox import (
        attach_emission_collector, emissions_checksum, replay_incident,
    )

    n = max(4_096, min(int(events), 400_000))
    base = 1_700_000_000_000
    rng = np.random.default_rng(11)
    ts = base + np.arange(n, dtype=np.int64) * 3
    price = np.round(rng.uniform(5.0, 100.0, n), 2)
    vol = rng.integers(1, 500, n).astype(np.int64)
    ql = """
    @app:name('bbbench')
    {ann}
    define stream S (price double, vol long);
    @info(name='q')
    from S[price > 20.0]#window.length(64)
    select sum(price) as total, count() as cnt insert into Out;
    """

    def run(armed: bool, bb_dir: str) -> dict:
        ann = (
            f"@app:blackbox(window='30 sec', triggers='crash', "
            f"ring='65536', keep='2', dir='{bb_dir}')" if armed else ""
        )
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime(ql.format(ann=ann))
        rows = [0]
        rt.add_callback("Out", lambda evs: rows.__setitem__(
            0, rows[0] + len(evs)
        ))
        rt.start()
        h = rt.get_input_handler("S")
        t0 = time.perf_counter()
        for i in range(0, n, batch):
            h.send_columns(
                ts[i:i + batch],
                {"price": price[i:i + batch], "vol": vol[i:i + batch]},
            )
        wall = time.perf_counter() - t0
        out = {
            "events_per_s": n / wall if wall > 0 else 0.0,
            "rows": rows[0],
        }
        if armed:
            iid = rt._blackbox.fire("crash", "bench synthetic")
            out["incident"] = iid
            out["bundle"] = rt.incidents()[-1]["path"] if iid else None
        mgr.shutdown()
        return out

    with tempfile.TemporaryDirectory(prefix="bench_blackbox_") as d:
        off = run(False, d)
        on = run(True, d)
        parity = False
        replay_rows = 0
        if on.get("bundle"):
            # the synthetic incident's ring only holds the last `ring`
            # rows; replay that tail against a fresh live run of the tail
            replay = replay_incident(on["bundle"])
            tail = min(n, 65536)
            mgr = SiddhiManager()
            rt = mgr.create_siddhi_app_runtime(ql.format(ann=""))
            ref = attach_emission_collector(rt)
            rt.start()
            rt.get_input_handler("S").send_columns(
                ts[n - tail:],
                {"price": price[n - tail:], "vol": vol[n - tail:]},
            )
            mgr.shutdown()
            replay_rows = sum(len(v) for v in replay.emissions.values())
            parity = (
                replay.emissions == ref
                and replay.checksum() == emissions_checksum(ref)
            )
    ratio = (
        on["events_per_s"] / off["events_per_s"]
        if off["events_per_s"] else 0.0
    )
    return {
        "blackbox": round(on["events_per_s"], 1),
        "blackbox_off_events_per_s": round(off["events_per_s"], 1),
        "blackbox_overhead_ratio": round(ratio, 3),
        "blackbox_rows_match": on["rows"] == off["rows"],
        "blackbox_replay_rows": replay_rows,
        "blackbox_replay_parity": parity,
    }


def _run_leg(name: str, args) -> dict:
    if name in WORKLOADS or name.endswith("_delivered"):
        v = _leg_throughput(name, args.events, args.batch)
        out = {name: round(v, 1)}
        if _LAST_STATUS[0] is not None:
            out[f"{name}_status"] = _LAST_STATUS[0]
        return out
    if name == "tables":
        return _leg_table_scaling()
    if name == "p99":
        return _leg_p99()
    if name == "timebudget":
        return _leg_timebudget(args.batch)
    if name == "calibration":
        return _leg_calibration()
    if name == "verify_cases":
        return _leg_verify()
    if name == "blackbox":
        return _leg_blackbox(args.events, args.batch)
    if name == "disorder":
        return _leg_disorder(args.events)
    if name == "verify":
        return _verify_tpu_vs_cpu(args)
    if name == "wire":
        # keep this leg's own default batch (a 4096 chunk shape shows the
        # dict/delta amortization honestly) unless --batch was passed
        batch = args.batch if getattr(args, "batch_explicit", True) else 4096
        return _leg_wire(batch=batch, events=min(args.events, 1_000_000))
    if name == "shard":
        if not args.shard:
            return {"shard_error": "pass --shard N (e.g. --shard 8 under "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=8)"}
        # honor --batch like every other leg, but keep this leg's own
        # default: at the driver-wide 32768 a 200k-event feed is fewer
        # micro-batches than devices and the router can't even engage
        batch = args.batch if getattr(args, "batch_explicit", True) else 4096
        return _leg_shard(
            args.shard, batch=batch, events=min(args.events, 1_000_000)
        )
    if name == "shardstate":
        if not args.shard:
            return {"shardstate_error": "pass --shard N (e.g. --shard 8 "
                    "under XLA_FLAGS=--xla_force_host_platform_device_"
                    "count=8)"}
        batch = args.batch if getattr(args, "batch_explicit", True) else 4096
        return _leg_shardstate(
            args.shard, batch=batch, events=min(args.events, 400_000)
        )
    raise SystemExit(f"unknown leg {name!r}")


def main():
    """Driver (no --leg) or one leg (--leg NAME).

    Invariant: the driver process imports no JAX and no siddhi_tpu — a
    process that has touched JAX holds the chip, and a leg child that needs
    it would then fail or hang. Everything that touches the device runs in a
    `--leg` child, one at a time. The driver always prints its final JSON
    line, and exits non-zero when any leg failed or was skipped."""
    ap = argparse.ArgumentParser()
    # 1M events (r05 ran 2M): throughput is a rate, halving the volume
    # halves each headline leg's wall without moving the number — part of
    # fitting the full suite back under the harness budget (ROADMAP item)
    ap.add_argument("--events", type=int, default=1_000_000)
    # default=None so an EXPLICIT `--batch 32768` is distinguishable from
    # "unset": the shard/wire legs keep their own smaller defaults only
    # when the caller didn't pick a batch
    ap.add_argument("--batch", type=int, default=None,
                    help="micro-batch size (default 32768)")
    ap.add_argument(
        "--shard", type=int, default=0,
        help="device count for the sharded-execution leg (`--leg shard`); "
        "also appends the leg to a full run. Run under XLA_FLAGS="
        "--xla_force_host_platform_device_count=N for a virtual CPU mesh",
    )
    ap.add_argument("--leg", help="run ONE leg in-process and print its JSON")
    ap.add_argument(
        "--deadline", type=float,
        default=float(os.environ.get("SIDDHI_BENCH_DEADLINE_S", "") or 2400),
        help="overall wall-clock budget in seconds. BENCH_r05 exited rc=124 "
        "with NO output: the harness's outer `timeout` matched the old "
        "2700 s default, leaving zero slack for the final JSON line — the "
        "default is now 2400 s and a snapshot JSON line is printed after "
        "every completed leg, so even an uncooperative SIGKILL leaves the "
        "last snapshot as a parseable tail. Pass 0 to opt out; legs that "
        "would not fit are skipped so the final JSON line always prints",
    )
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args()
    args.batch_explicit = args.batch is not None
    if args.batch is None:
        args.batch = 32768

    # SIDDHI_TPU_BENCH_BUDGET=<seconds>: one knob for constrained harnesses —
    # trims the overall deadline AND the per-leg subprocess caps (no single
    # leg may eat more than a third of the budget), so the suite provably
    # finishes (or skip-records) inside the budget
    try:
        budget = float(os.environ.get("SIDDHI_TPU_BENCH_BUDGET", "") or 0)
    except ValueError:
        budget = 0.0
    if budget > 0:
        args.deadline = (
            min(args.deadline, budget) if args.deadline else budget
        )

    if args.leg:
        if args.leg != "verify":  # that leg only starts children of its own
            from siddhi_tpu.utils.backend import configure_compile_cache

            configure_compile_cache()
        print(json.dumps(_run_leg(args.leg, args)))
        return

    # driver resilience contract (BENCH_r05 shipped rc=124 and NO output when
    # one wedged leg ate the harness budget): every leg runs under its own
    # subprocess timeout, the overall --deadline skips legs that cannot fit,
    # and the final JSON line is emitted exactly once on EVERY exit path —
    # normal completion, per-leg timeout, driver crash, or SIGTERM/SIGINT
    # from an outer `timeout`.
    import signal

    detail: dict = {}
    failed: list = []
    current_leg = [None]
    current_child = [None]
    emitted = [False]

    def _line(extra: dict | None = None) -> str:
        d = dict(detail)
        if extra:
            d.update(extra)
        if failed:
            d["failed_legs"] = list(failed)
        per = [d.get(k) for k in WORKLOADS]
        per = [v for v in per if v]
        geomean = (
            math.exp(sum(math.log(v) for v in per) / len(per)) if per else 0.0
        )
        return json.dumps(
            {
                "metric": "engine_throughput_geomean",
                "value": round(geomean, 1),
                "unit": "events/s",
                "vs_baseline": round(geomean / REFERENCE_EVENTS_PER_SEC, 3),
                "detail": d,
            }
        )

    def _emit(via_fd: bool = False):
        """Print the final JSON line exactly once. `via_fd` (signal path)
        bypasses the buffered stdout object with one os.write straight to
        fd 1: a SIGKILL 10 s later (`timeout -k 10`) cannot lose an
        unflushed buffer, and os.write is async-signal-safe where print +
        flush on a partially-written buffer is not (BENCH_r05 shipped
        rc=124 with NO JSON at all — this path plus the per-leg snapshot
        lines below are the fix, held by tests/test_bench_driver.py +
        tier1.yml)."""
        if emitted[0]:
            return
        emitted[0] = True
        line = _line()
        if via_fd:
            try:
                os.write(1, (line + "\n").encode())
            except OSError:
                pass
            return
        print(line)
        sys.stdout.flush()

    def _on_signal(signum, frame):
        # EMIT FIRST: the JSON must be on fd 1 before anything that could
        # block (killing a wedged child can); the outer `timeout -k` only
        # grants a grace window, not cooperation
        leg = current_leg[0]
        if leg is not None:
            failed.append({"leg": leg, "error": f"signal{signum}"})
            detail[f"{leg}_error"] = f"signal{signum}"
        _emit(via_fd=True)
        child = current_child[0]
        if child is not None:  # don't orphan a leg burning the machine
            try:
                child.kill()
            except Exception:
                pass
        os._exit(1)  # an interrupted run is not a completed one

    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGINT, _on_signal)
    if hasattr(signal, "SIGALRM") and args.deadline:
        # belt-and-suspenders: even if the per-leg timeouts wedge (a child
        # that ignores kill, a hung communicate()), the alarm fires shortly
        # after the deadline and the handler emits from in-process
        signal.signal(signal.SIGALRM, _on_signal)
        signal.alarm(int(args.deadline) + 60)

    t_start = time.monotonic()
    legs = list(WORKLOADS) + [
        "filter_window_avg_delivered", "pattern_2state_delivered",
        "tumbling_groupby_delivered", "p99", "tables", "wire", "timebudget",
        "calibration", "disorder", "verify",
    ]
    if args.shard:
        legs.append("shard")
    try:
        for leg in legs:
            current_leg[0] = leg
            # trimmed per-leg caps (was 1200/2800): one wedged leg can no
            # longer eat half the suite budget before the deadline logic
            # even gets a say
            leg_timeout = 1500 if leg == "verify" else 900
            if budget > 0:
                leg_timeout = min(leg_timeout, max(20.0, budget / 3.0))
            if args.deadline:
                remaining = args.deadline - (time.monotonic() - t_start)
                if remaining < 60:
                    failed.append({"leg": leg, "error": "skipped(deadline)"})
                    detail[f"{leg}_error"] = "skipped(deadline)"
                    print(_line({"partial_through_leg": leg}))
                    sys.stdout.flush()
                    continue
                # keep ~30 s of slack so the driver itself always finishes
                leg_timeout = min(leg_timeout, remaining - 30)
            cmd = [sys.executable, os.path.abspath(__file__), "--leg", leg,
                   "--events", str(args.events)]
            if args.batch_explicit:
                # forward --batch only when the caller chose one, so leg
                # subprocesses keep their own defaults otherwise
                cmd += ["--batch", str(args.batch)]
            if args.shard:
                cmd += ["--shard", str(args.shard)]
            env = dict(os.environ)
            env["SIDDHI_TPU_AUX_DRAIN_S"] = "0"
            env.setdefault(
                "PYTHONPATH", os.path.dirname(os.path.abspath(__file__))
            )
            out_text, err_text = "", ""
            try:
                child = subprocess.Popen(
                    cmd, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                    text=True, env=env,
                    cwd=os.path.dirname(os.path.abspath(__file__)),
                )
                current_child[0] = child
                try:
                    out_text, err_text = child.communicate(timeout=leg_timeout)
                except subprocess.TimeoutExpired:
                    child.kill()
                    child.communicate()
                    raise
                line = (
                    out_text.strip().splitlines()[-1]
                    if out_text.strip()
                    else "{}"
                )
                got = json.loads(line)
                if child.returncode != 0:
                    if not got:
                        raise RuntimeError(f"rc={child.returncode}")
                    failed.append(
                        {"leg": leg, "error": f"rc={child.returncode}"}
                    )
            except subprocess.TimeoutExpired:
                failed.append({"leg": leg, "error": "timeout"})
                got = {f"{leg}_error": "timeout"}
            except Exception as e:
                if args.verbose:
                    print(f"# leg {leg} FAILED: {e}", file=sys.stderr)
                    if err_text:
                        print(err_text[-2000:], file=sys.stderr)
                failed.append({"leg": leg, "error": type(e).__name__})
                got = {f"{leg}_error": f"{type(e).__name__}"}
            finally:
                current_child[0] = None
            detail.update(got)
            if args.verbose:
                print(f"# {leg}: {got}")
            # crash-proof progress: a snapshot of everything measured so far
            # after EVERY leg — if anything (even SIGKILL) takes the driver
            # down mid-suite, the tail line on fd 1 is still parseable JSON
            # (consumers read the LAST line; _emit prints the final one)
            print(_line({"partial_through_leg": leg}))
            sys.stdout.flush()
        current_leg[0] = None

        # budget sanity: every measured leg must fall inside its published
        # [floor, ceiling] interval (10% tolerance for run-to-run drift
        # between the leg subprocess and the budget subprocess)
        for leg in WORKLOADS:
            v = detail.get(leg)
            ceil_v = detail.get(f"{leg}_ceiling_mev_s")
            floor_v = detail.get(f"{leg}_floor_mev_s")
            if not v or not ceil_v or not floor_v:
                continue
            if v > ceil_v * 1e6 * 1.1 or v < floor_v * 1e6 * 0.5:
                detail[f"{leg}_budget_flag"] = (
                    f"measured {v:.0f} outside [{floor_v}M/2, {ceil_v}M*1.1]"
                )
    finally:
        _emit()
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
