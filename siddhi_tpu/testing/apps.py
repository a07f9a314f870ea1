"""App texts and input data shared by the tests, `chip_smoke.py` and
`tools/plan_apps.py`: the five `BASELINE.json` configurations, one app that
forms a fused group, and the wire-encoding apps.

Test fixtures, not a benchmark: the benchmark is `benchmark/run.py` with
`BENCHMARK.json`. This module imports no JAX and sets nothing in
`os.environ`, so importing it cannot change the engine under test.
"""

from __future__ import annotations

import numpy as np


def make_stock_data(n: int, seed: int = 7):
    rng = np.random.default_rng(seed)
    symbols = np.array(["WSO2", "IBM", "GOOG", "MSFT", "ORCL", "AAPL", "AMZN", "NVDA"])
    return {
        "ts": np.arange(n, dtype=np.int64) + 1_700_000_000_000,
        "symbol": rng.integers(1, 9, size=n).astype(np.int32),  # pre-interned ids
        "price": rng.uniform(0.0, 100.0, size=n).astype(np.float32),
        "volume": rng.integers(1, 1000, size=n).astype(np.int64),
        "names": symbols,
    }


def prime_interner(mgr, names):
    """Intern `names` in order, so that `make_stock_data`'s symbol ids
    1..8 are theirs."""
    for s in names:
        mgr.interner.intern(str(s))


# name -> (app text, input stream, batch override: truthy where the
# configuration needs a smaller batch than its siblings)
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
        None,
    ),
    # BASELINE.json config 5: DEBS-style count sequence with a kleene bound.
    # patternCapacity/patternChunk are ENGINE BUFFER knobs, not workload
    # semantics: the reference's pending lists are unbounded, and at this
    # data rate (10% match rate, min-count 2 -> ~410 armed generations per
    # 8192-row chunk < 512 lanes) the outputs are identical to any larger
    # sizing (overflow would be flagged + warned).
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
        None,
    ),
}


# a stream with THREE fusable consumers, two of them sharing an identical
# filter+window chain: the shape the FusionPlan forms a group + shared ring
# on (core/fusion_exec.py)
FUSED_GROUP_QL = """
define stream StockStream (symbol string, price float, volume long);
@info(name='q1') from StockStream[price > 50]#window.length(64)
select symbol, avg(price) as ap insert into Out1;
@info(name='q2') from StockStream[price > 50]#window.length(64)
select symbol, max(price) as mx insert into Out2;
@info(name='q3') from StockStream#window.lengthBatch(1024)
select sum(volume) as tv insert into Out3;
"""


# dictionary-heavy stream (low-cardinality interned symbols + a declared
# qty range) and one delta-timestamp stream (monotone LONG seq):
# name -> (app text, input stream)
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
    # hint
    "wire_delta_inferred": (
        """
        define stream Meters (seq long, v float);
        @info(name='q') from Meters#window.externalTimeBatch(seq, 1000)
        select seq, v insert into Out;
        """,
        "Meters",
    ),
}
