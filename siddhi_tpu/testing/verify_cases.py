"""The parity cases: ~20 small apps over one seeded feed of 96 events, and
the runner that returns every case's delivered rows.

`tests/test_shard_exec.py` `TestVerifyParity` runs them in process under
two settings of a switch. Run as a program they print one JSON object,
`{"cases": ..., "backend": ...}`, for a diff across processes, which is how
two backends are compared:

    python -m siddhi_tpu.testing.verify_cases --columnar > tpu.json
    JAX_PLATFORMS=cpu python -m siddhi_tpu.testing.verify_cases --columnar > cpu.json
    python -m siddhi_tpu.testing.verify_cases --diff tpu.json cpu.json

This module imports no JAX at module level and sets nothing in
`os.environ`.
"""

from __future__ import annotations

import numpy as np

VERIFY_HEAD = (
    "@app:batch(size='32')\n"
    "define stream S (symbol string, price float, volume long);\n"
)

# ~20 representative behaviors for a differential run: the same app + events
# on two backends or under two settings of a switch; rows must match
# (`rows_match` within float tolerance across backends, `==` on one).
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
    # are collected PER QUERY so a fuse-on/off diff compares each
    # consumer's own delivery order (core/fusion_exec.py)
    "multi_query_shared": VERIFY_HEAD + """@info(name='q') from S[price > 40]#window.length(6) select symbol, avg(price) as ap insert into Out1;
        @info(name='q2') from S[price > 40]#window.length(6) select symbol, max(price) as mx insert into Out2;
        @info(name='q3') from S#window.lengthBatch(8) select sum(volume) as tv insert into Out3;
        @info(name='q4') from S[volume > 300] select symbol, volume output every 5 events insert into Out4;""",
}

# cases observed via store queries over tables instead of callbacks:
# name -> (app text, the store query to read afterwards)
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


def run_verify_cases(columnar: bool) -> dict:
    """Run every verify case on the CURRENT backend, under whatever
    switches the environment sets, and return `{"cases": name -> rows or
    "ERROR: ...", "backend"}`.

    `columnar` ingests the same events COLUMNARLY (one send_columns call,
    symbols pre-interned) so the fused path actually engages; a per-row
    feed never reaches try_send. An on/off parity holds the ingestion mode
    fixed on both sides: row-by-row and columnar feeds legitimately batch
    differently."""
    from siddhi_tpu import SiddhiManager

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


def rows_match(a, b, tol=2e-4):
    if type(a) is not type(b):
        return False
    if isinstance(a, dict):  # multi-query cases: rows keyed per query
        return set(a) == set(b) and all(
            rows_match(a[k], b[k], tol) for k in a
        )
    if isinstance(a, (list, tuple)):
        return len(a) == len(b) and all(rows_match(x, y, tol) for x, y in zip(a, b))
    if isinstance(a, float):
        if b == 0:
            return abs(a) < tol
        return abs(a - b) <= tol * max(1.0, abs(a), abs(b))
    return a == b


def diff_cases(a: dict, b: dict) -> dict:
    """name -> "pass" / "FAIL" over two `run_verify_cases` results; an
    ERROR on either side never passes."""
    ca, cb = a["cases"], b["cases"]
    return {
        k: "pass"
        if not isinstance(ca.get(k), str) and rows_match(ca.get(k), cb.get(k))
        else "FAIL"
        for k in sorted(set(ca) | set(cb))
    }


if __name__ == "__main__":
    import argparse
    import json
    import sys

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--columnar", action="store_true",
        help="one send_columns call per case (the fused path) instead of "
        "a send per row",
    )
    ap.add_argument(
        "--diff", nargs=2, metavar="JSON",
        help="run nothing: compare two printed results case by case",
    )
    args = ap.parse_args()
    if args.diff:
        # a JSON round trip turns tuples into lists on both sides equally
        verdict = diff_cases(*(json.load(open(f)) for f in args.diff))
        print(json.dumps(verdict))
        sys.exit(0 if set(verdict.values()) == {"pass"} else 1)
    print(json.dumps(run_verify_cases(args.columnar)))
