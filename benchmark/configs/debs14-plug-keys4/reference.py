"""Plain NumPy semantics of the per-plug peak query, independent of the
engine: keep the load records; each arriving load record emits (event time,
ts, house, household, plug, the largest load that plug has reported since
the stream began, how many load records it has sent), the arriving record
included. Kept as a running state, as the stream is far longer than memory:
per plug the float32 peak and the int64 count. Nothing ever leaves (there is
no window), so `leaving` is always empty."""

import numpy as np

KEYS = ("house_id", "household_id", "plug_id")
ID_SPAN = 64  # household and plug ids are below this


def kept(cols: dict) -> np.ndarray:
    """Which input rows produce an emission."""
    return cols["property"]


def plug_code(cols: dict) -> np.ndarray:
    return ((cols["house_id"].astype(np.int64) * ID_SPAN
             + cols["household_id"]) * ID_SPAN + cols["plug_id"])


def ordered_bits(x: np.ndarray) -> np.ndarray:
    """float32 -> int64 in 0..2^32-1 that sort as the floats do."""
    b = x.astype(np.float32).view(np.int32).astype(np.int64)
    return np.where(b < 0, -b - 1, b + (1 << 31))


def from_ordered_bits(u: np.ndarray) -> np.ndarray:
    b = np.where(u < (1 << 31), -u - 1, u - (1 << 31))
    return b.astype(np.int32).view(np.float32)


class Running:
    """Per plug the peak load and the count of load records, carried along
    the stream.

    `control` is the check's own control: the peak is kept in bfloat16, so
    every load is rounded to that type as it is taken in. A maximum has no
    rounding of its own; the type it is kept in is all its precision."""

    def __init__(self, sizes: dict, control: bool = False):
        slots = sizes["houses"] * ID_SPAN * ID_SPAN
        self.peak = np.full(slots, -np.inf, dtype=np.float32)
        self.count = np.zeros(slots, dtype=np.int64)
        self.control = control

    def step(self, ts, cols, leaving, emit=True):
        """Take in kept rows `cols` (event times `ts`), in order. Returns
        the rows' output lanes, or None where `emit` is false (the state
        alone moves on)."""
        key = plug_code(cols)
        val = cols["value"].astype(np.float32)
        if self.control:
            import ml_dtypes

            val = val.astype(ml_dtypes.bfloat16).astype(np.float32)
        out = None
        if emit:
            # rows sorted by plug and, within a plug, by arrival: the running
            # peak of a plug is a running maximum that starts again at each
            # plug, made exact by carrying the plug's place in the high bits
            order = np.argsort(key, kind="stable")
            k = key[order]
            first = np.flatnonzero(np.r_[True, k[1:] != k[:-1]])
            lengths = np.diff(np.r_[first, len(k)])
            seg = np.repeat(np.arange(len(first), dtype=np.int64), lengths)
            run = np.maximum.accumulate((seg << 32) | ordered_bits(val[order]))
            peak = np.maximum(from_ordered_bits(run & 0xFFFFFFFF), self.peak[k])
            nth = np.arange(len(k)) - np.repeat(first, lengths) + 1
            max_load = np.empty(len(k), dtype=np.float32)
            n = np.empty(len(k), dtype=np.int64)
            max_load[order] = peak
            n[order] = self.count[k] + nth
            out = {"event_time": ts, "ts": cols["ts"], "maxLoad": max_load,
                   "n": n,
                   **{name: cols[name].astype(np.int64) for name in KEYS}}
        np.maximum.at(self.peak, key, val)
        self.count += np.bincount(key, minlength=len(self.count))
        return out


def reference(ts: np.ndarray, cols: dict, sizes: dict,
              control: bool = False) -> dict:
    """Output lanes for a whole stream that starts with empty state."""
    keep = kept(cols)
    ts, cols = ts[keep], {k: v[keep] for k, v in cols.items()}
    return Running(sizes, control).step(ts, cols, None)
