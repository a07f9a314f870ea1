"""Seeded rows of upstream's SimpleFilterSingleQueryPerformance: the two
events it sends, ('WSO2', 55.6f, 100, ts) and ('IBM', 75.6f, 100, ts), in
an order drawn from the seed. `symbol` is an index into STRINGS["symbol"]."""

import numpy as np

T0_MS = 1_700_000_000_000
STRINGS = {"symbol": ["WSO2", "IBM"]}
PRICES = np.array([55.6, 75.6], dtype=np.float32)


def make(seed: int, n: int) -> dict:
    """Columns of `n` rows, without `timestamp` (see `with_index`)."""
    coin = np.random.default_rng(seed).integers(0, 2, size=n, dtype=np.int32)
    return {
        "symbol": coin,
        "price": PRICES[coin],
        "volume": np.full(n, 100, dtype=np.int32),
    }


def timestamps(lo: int, hi: int) -> np.ndarray:
    """Event time of stream rows lo..hi-1: 1 ms per row."""
    return T0_MS + np.arange(lo, hi, dtype=np.int64)


def with_index(cols: dict, lo: int, hi: int, ts: np.ndarray) -> dict:
    """The `timestamp` attribute is the event time."""
    return {**cols, "timestamp": ts}
