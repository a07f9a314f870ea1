"""Seeded rows for the pattern fixture: a key of 8 and a value uniform on
0..100 (3 decimals), one row every 5 ms of event time."""

import numpy as np

T0_MS = 1_700_000_000_000
STEP_MS = 5
STRINGS = {}


def make(seed: int, n: int) -> dict:
    rng = np.random.default_rng(seed)
    return {"k": rng.integers(0, 8, n).astype(np.int32),
            "v": np.round(rng.uniform(0, 100, n), 3).astype(np.float32)}


def timestamps(lo: int, hi: int) -> np.ndarray:
    return T0_MS + np.arange(lo, hi, dtype=np.int64) * STEP_MS
