"""The smart-plug stream of `debs14-q1-time` (readings missing and all; its
`make` is loaded, not copied) in the order a collector would hand it on:
late and out of order, by the law Apache Beam's `nexmark` generator delays
its events by (`probDelayedEvent` 0.1, `occasionalDelaySec` 3).

A record's place in arrival time is its second, in ms, plus its rank among
the records of that second spread evenly over the second. With probability
`DELAYED_SHARE`, drawn from the seed for each record by itself (a reading's
work and load record part ways, as Beam delays each event), the record is
held back by a whole number of ms, uniform on 1..`DELAY_UPTO_MS`. The
stream is the records in the stable order of place + delay; each keeps its
own event time (its second: `timestamps`, and `ts` through `with_index`),
so event time runs backwards by up to 3 s where a held record arrives.
`id` counts records in arrival order.

The harness replays the pool in cycles, and a cycle advances stream time by
the pool's seconds, as in `debs14-q1-time`. A record of the pool's last 3 s
that is held back past the pool's end arrives that much into the NEXT cycle,
with the time of the cycle it belongs to: its row of the pool carries the
event time of the cycle before (`_cycles_back`), so the law holds across the
wrap and every send meets records held back from before it. The first cycle
starts with those of a cycle before the first (event times up to 3 s before
`T0_S`). Event time stays a function of the global row index.

The pool is NOT a whole number of sends. The harness rounds its pool up to
whole `CYCLE_ROWS`, and 4,928 makes `bulk-2m`'s 4,194,304 rows 4,198,656:
two sends of 2,097,152 and 4,352 rows (1.08 s of stream) over. So a send's
ends move 4,352 rows along the pool from one cycle to the next, and the
hundred-odd sends of a run meet as many placings of their ends and of their
chunks' ends among the seconds. A pool of exactly two sends repeats the
seed's two: whether a call's release passes 64 micro-batches (a tail
chunk), and whether a chunk holds a few load records more or fewer than
2^19 (the size the engine reads a chunk's rows by), were then drawn once
by the seed and held for the whole run, and the runs of one program came
in two modes 9-13 % apart (PERF.md section 6, PR 47). The 4,352 are chosen
so that neither kind of send lasts near a whole number of seconds: one
inside the pool is 519.42 s of stream, one that crosses the pool's end
(whose last second is cut short, at 0.91 of it) 519.51 s, each +- 0.1 s by
the seed's count of readings a second. A send that ends on an earlier part
of its second than it began holds fewer rows than it found and releases a
tail chunk: 0.42 and 0.51 of the one and the other kind, far enough from 0
and from 1 that no seed tips either over, so every seed reads about the
same share of tails (0.457 a send in a replay of ten seeds). With 1,866
rows over, the second kind lasts 520.12 s and seeds fell on both sides of
the whole number: 0.26 tails a send on eight seeds, 0.75 on two."""

import importlib.util
from pathlib import Path

import numpy as np

_SOURCE = Path(__file__).resolve().parents[1] / "debs14-q1-time" / "gen.py"
_spec = importlib.util.spec_from_file_location("bench_time_stream", _SOURCE)
_time = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_time)

T0_S = _time.T0_S
CYCLE_ROWS = 4928                 # whole readings; why this many: above
STRINGS = _time.STRINGS
with_index = _time.with_index

DELAYED_SHARE = 0.1               # NexmarkConfiguration.probDelayedEvent
DELAY_UPTO_MS = 3000              # occasionalDelaySec = 3

_second_of_row = np.zeros(0, dtype=np.int64)
_cycles_back = np.zeros(0, dtype=np.int64)
_pool_seconds = 0


def arrival_order(second: np.ndarray, seed: int, pool_seconds: int) -> tuple:
    """(the rows in arrival order, each row's delay in ms, whether it
    arrives in the cycle after its own) for rows whose seconds, in schedule
    order, are `second`."""
    n = len(second)
    first = np.flatnonzero(np.r_[True, second[1:] != second[:-1]])
    per_second = np.diff(np.r_[first, n])
    rank = np.arange(n) - np.repeat(first, per_second)
    place = second * 1000 + rank * 1000 // np.repeat(per_second, per_second)
    # a stream of its own, so that the records are `debs14-q1-time`'s
    rng = np.random.default_rng([seed, DELAY_UPTO_MS])
    delay = np.where(rng.random(n) < DELAYED_SHARE,
                     rng.integers(1, DELAY_UPTO_MS + 1, n), 0)
    arrival = place + delay
    wraps = arrival >= pool_seconds * 1000
    arrival -= wraps * pool_seconds * 1000
    return np.argsort(arrival, kind="stable"), delay, wraps


def make(seed: int, n: int) -> dict:
    """Columns of `n` records in arrival order, without `id` and `ts`;
    remembers each row's second for `timestamps`."""
    global _second_of_row, _cycles_back, _pool_seconds
    cols = _time.make(seed, n)
    second, _pool_seconds = _time._second_of_row, _time._pool_seconds
    order, _, wraps = arrival_order(second, seed, _pool_seconds)
    _second_of_row = second[order]
    _cycles_back = wraps[order].astype(np.int64)
    return {k: v[order] for k, v in cols.items()}


def timestamps(lo: int, hi: int) -> np.ndarray:
    """Event time (ms) of stream rows lo..hi-1 of the pool last made."""
    i = np.arange(lo, hi, dtype=np.int64)
    n = len(_second_of_row)
    at = i % n
    return (T0_S + (i // n - _cycles_back[at]) * _pool_seconds
            + _second_of_row[at]) * 1000
