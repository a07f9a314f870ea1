"""The smart-plug stream of `debs14-q1-plug` with measurements missing, as
the source's recording has them: each plug's reading of a second (its work
record and its load record together) is absent with probability
`MISSING_SHARE`, drawn from the seed. Layout, the plugs' order inside a
second, the values and `with_index` are that file's. Row i of the stream is
record i % 2 of reading i // 2; the readings of a second are those of its
plugs that report, in the seed's order.

A pool of n rows is whole seconds: `make` draws seconds until n / 2 readings
are there (the last second loses what does not fit), and keeps the second of
every row. One cycle of the pool advances stream time by the pool's seconds,
so event time stays a function of the global row index: `timestamps` reads
the table the last `make` drew."""

import importlib.util
from pathlib import Path

import numpy as np

_SOURCE = Path(__file__).resolve().parents[1] / "debs14-q1-plug" / "gen.py"
_spec = importlib.util.spec_from_file_location("bench_plug_stream", _SOURCE)
_plug = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_plug)

N_PLUGS = _plug.N_PLUGS
N_HOUSES = _plug.N_HOUSES
T0_S = _plug.T0_S
LOAD_MEAN_W = _plug.LOAD_MEAN_W
STRINGS = _plug.STRINGS
layout = _plug.layout
with_index = _plug.with_index

MISSING_SHARE = 0.05
CYCLE_ROWS = 2                    # a reading is a work and a load record

_second_of_row = np.zeros(0, dtype=np.int64)
_pool_seconds = 0


def make(seed: int, n: int) -> dict:
    """Columns of `n` records (whole readings), without `id` and `ts` (see
    `with_index`); remembers each row's second for `timestamps`."""
    global _second_of_row, _pool_seconds
    if n % CYCLE_ROWS:
        raise ValueError(f"{n} rows are not whole readings of {CYCLE_ROWS}")
    rng = np.random.default_rng(seed)
    order = rng.permutation(N_PLUGS)
    readings = n // 2
    seconds = int(readings / (N_PLUGS * (1 - MISSING_SHARE))) + 8
    present = rng.random((seconds, N_PLUGS)) >= MISSING_SHARE
    while present.sum() < readings:
        present = np.concatenate(
            [present, rng.random((8, N_PLUGS)) >= MISSING_SHARE])
    there = np.flatnonzero(present.ravel())[:readings]
    second, plug = there // N_PLUGS, order[there % N_PLUGS]
    house, household, plug_id = layout()
    load = np.round(rng.exponential(LOAD_MEAN_W, size=readings), 3)
    work = np.round(rng.uniform(0, 500, N_PLUGS)[plug] + 1e-3 * second, 3)
    value = np.empty(n, dtype=np.float32)
    value[0::2], value[1::2] = work, load
    _second_of_row = np.repeat(second, 2).astype(np.int64)
    _pool_seconds = int(second[-1]) + 1 if readings else 0
    return {
        "value": value,
        "property": np.arange(n) % 2 == 1,
        "plug_id": np.repeat(plug_id[plug], 2),
        "household_id": np.repeat(household[plug], 2),
        "house_id": np.repeat(house[plug], 2),
    }


def timestamps(lo: int, hi: int) -> np.ndarray:
    """Event time (ms) of stream rows lo..hi-1 of the pool last made."""
    i = np.arange(lo, hi, dtype=np.int64)
    n = len(_second_of_row)
    return (T0_S + (i // n) * _pool_seconds + _second_of_row[i % n]) * 1000
