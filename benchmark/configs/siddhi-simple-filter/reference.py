"""Plain NumPy semantics of `from cseEventStream[700 > price] select *`:
a mask. Stateless, so every lane is exact and nothing is carried along."""

import numpy as np


def kept(cols: dict) -> np.ndarray:
    """Which input rows produce an emission."""
    return 700 > cols["price"]


class Running:
    """Same shape as a stateful configuration's reference; holds nothing.
    `control` breaks 'every lane is exact': price carried in bfloat16."""

    def __init__(self, sizes: dict, control: bool = False):
        self.control = control

    def step(self, ts, cols, leaving, emit=True):
        if not emit:
            return None
        price = cols["price"]
        if self.control:
            import ml_dtypes

            price = price.astype(ml_dtypes.bfloat16)
        return {
            "event_time": ts,
            "symbol": cols["symbol"].astype(np.int64),
            "price": price.astype(np.float64),
            "volume": cols["volume"].astype(np.int64),
            "timestamp": cols["timestamp"],
        }


def reference(ts: np.ndarray, cols: dict, sizes: dict,
              control: bool = False) -> dict:
    """Output lanes for a whole stream."""
    keep = kept(cols)
    return Running(sizes, control).step(
        ts[keep], {k: v[keep] for k, v in cols.items()}, None)
