"""Seeded rows for the join fixture: one merged stream, a row every 5 ms of
event time, each row A's (3 in 5, from the seed) or B's. `make` returns one
pool per stream and remembers which rows of the merged pool are whose;
`split` and `timestamps` read that table, so event time stays a function of
the merged row index across the pool's cycles."""

import numpy as np

T0_MS = 1_700_000_000_000
STEP_MS = 5
STRINGS = {}

_rows = {}      # stream -> merged pool rows that are its own
_before = {}    # stream -> its rows among merged pool rows 0..i-1
_n = 0


def make(seed: int, n: int) -> dict:
    global _n
    rng = np.random.default_rng(seed)
    is_a = rng.random(n) < 0.6
    _n = n
    pools = {}
    for name, mine, lane in (("A", is_a, "x"), ("B", ~is_a, "y")):
        _rows[name] = np.flatnonzero(mine)
        _before[name] = np.concatenate([[0], np.cumsum(mine)])
        m = len(_rows[name])
        pools[name] = {
            "k": rng.integers(0, 8, m).astype(np.int32),
            lane: np.round(rng.uniform(0, 100, m), 3).astype(np.float32)}
    return pools


def split(lo: int, hi: int) -> dict:
    """stream -> (a, b): its rows a..b-1, in its own count, are the ones
    inside merged rows lo..hi-1."""
    def own(name, i):
        return (i // _n) * len(_rows[name]) + int(_before[name][i % _n])
    return {name: (own(name, lo), own(name, hi)) for name in _rows}


def timestamps(a: int, b: int, stream: str) -> np.ndarray:
    own = np.arange(a, b, dtype=np.int64)
    m = len(_rows[stream])
    merged = (own // m) * _n + _rows[stream][own % m]
    return T0_MS + merged * STEP_MS
