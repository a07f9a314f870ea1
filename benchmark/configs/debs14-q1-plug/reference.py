"""Plain NumPy semantics of the smart-plug query, independent of the engine:
keep the load records; the window holds the last `window_rows` of them; each
arriving load record emits (event time, ts, house, household, plug, mean load
of that plug's records in the window). Kept as a running state, as the
stream is far longer than memory: per plug the float64 sum and the count of
its records in the window; a record that arrives adds, the record that it
pushes out of the window subtracts."""

import numpy as np

KEYS = ("house_id", "household_id", "plug_id")
ID_SPAN = 64  # household and plug ids are below this


def kept(cols: dict) -> np.ndarray:
    """Which input rows produce an emission."""
    return cols["property"]


def plug_code(cols: dict) -> np.ndarray:
    return ((cols["house_id"].astype(np.int64) * ID_SPAN
             + cols["household_id"]) * ID_SPAN + cols["plug_id"])


class Running:
    """The window's per-plug sum and count, carried along the stream.

    `control` is the check's own control: the per-plug window sum of load is
    rounded to bfloat16 before the division. Rounding the exact sum once is
    the least error any running sum kept in that type can have."""

    def __init__(self, sizes: dict, control: bool = False):
        slots = sizes["houses"] * ID_SPAN * ID_SPAN
        self.sum = np.zeros(slots)
        self.count = np.zeros(slots, dtype=np.int64)
        self.control = control

    def step(self, ts, cols, leaving, emit=True):
        """Take in kept rows `cols` (event times `ts`), in order; `leaving`
        are the kept rows that they push out, in order: the last of them
        leaves as the last row arrives. Returns the rows' output lanes, or
        None where `emit` is false (the state alone moves on)."""
        key_in, key_out = plug_code(cols), plug_code(leaving)
        val_in = cols["value"].astype(np.float64)
        val_out = leaving["value"].astype(np.float64)
        n, m = len(key_in), len(key_out)
        out = None
        if emit:
            # one line of +arrivals and -leavers, sorted by plug and, within
            # a plug, by position; a leaver goes just before the arrival
            # that pushes it out. Running totals per plug follow by cumsum.
            key = np.concatenate([key_out, key_in])
            pos = np.concatenate([(np.arange(m) + n - m) * 2,
                                  np.arange(n) * 2 + 1])
            d_sum = np.concatenate([-val_out, val_in])
            d_cnt = np.concatenate([-np.ones(m, np.int64),
                                    np.ones(n, np.int64)])
            order = np.lexsort((pos, key))
            k = key[order]
            c_sum, c_cnt = np.cumsum(d_sum[order]), np.cumsum(d_cnt[order])
            first = np.flatnonzero(np.r_[True, k[1:] != k[:-1]])
            seg = np.repeat(first, np.diff(np.r_[first, len(k)]))
            s = self.sum[k] + c_sum - (c_sum[seg] - d_sum[order][seg])
            c = self.count[k] + c_cnt - (c_cnt[seg] - d_cnt[order][seg])
            arrival = order >= m
            if self.control:
                import ml_dtypes

                s = s.astype(ml_dtypes.bfloat16).astype(np.float64)
            avg = np.empty(n)
            avg[order[arrival] - m] = s[arrival] / c[arrival]
            out = {"event_time": ts, "ts": cols["ts"], "avgLoad": avg,
                   **{name: cols[name].astype(np.int64) for name in KEYS}}
        slots = len(self.sum)
        self.sum += (np.bincount(key_in, val_in, slots)
                     - np.bincount(key_out, val_out, slots))
        self.count += (np.bincount(key_in, minlength=slots)
                       - np.bincount(key_out, minlength=slots))
        return out


def reference(ts: np.ndarray, cols: dict, sizes: dict,
              control: bool = False) -> dict:
    """Output lanes for a whole stream that starts with empty state."""
    keep = kept(cols)
    ts, cols = ts[keep], {k: v[keep] for k, v in cols.items()}
    pushed_out = max(len(ts) - sizes["window_rows"], 0)
    leaving = {k: v[:pushed_out] for k, v in cols.items()}
    return Running(sizes, control).step(ts, cols, leaving)
