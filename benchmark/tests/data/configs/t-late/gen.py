"""Seeded rows for the disorder fixture: a key of 8 and a value uniform on
0..100 (3 decimals); row i has its place at 5 ms x i of event time, and 5 %
of the rows (from the seed) carry a time up to 4 s behind their place."""

import numpy as np

T0_MS = 1_700_000_000_000
STEP_MS = 5
LATE_SHARE = 0.05
LATE_UPTO_MS = 4000
STRINGS = {}

_behind = np.zeros(1, dtype=np.int64)


def make(seed: int, n: int) -> dict:
    global _behind
    rng = np.random.default_rng(seed)
    _behind = np.where(rng.random(n) < LATE_SHARE,
                       rng.integers(1, LATE_UPTO_MS + 1, n), 0)
    return {"k": rng.integers(0, 8, n).astype(np.int32),
            "v": np.round(rng.uniform(0, 100, n), 3).astype(np.float32)}


def timestamps(lo: int, hi: int) -> np.ndarray:
    i = np.arange(lo, hi, dtype=np.int64)
    return T0_MS + i * STEP_MS - _behind[i % len(_behind)]
