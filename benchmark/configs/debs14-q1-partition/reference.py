"""Plain NumPy semantics of the smart-plug query per plug, independent of the
engine: keep the load records; every plug has a window of its own, its last
`window_rows` load records; each arriving load record is held in its plug's
window, pushes that window's oldest record out once it is full, and emits
(event time, ts, house, household, plug, mean load of the plug's window).
Kept as a running state, as the stream is far longer than memory: per plug a
ring of its last `window_rows` loads ([plugs, window_rows] float32, the
values as they arrive), how many it has been sent, and the float64 sum of
the ring."""

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
    """Every plug's window, carried along the stream.

    `control` is the check's own control: the plug's window sum of load is
    rounded to bfloat16 before the division. Rounding the exact sum once is
    the least error any running sum kept in that type can have."""

    def __init__(self, sizes: dict, control: bool = False):
        self.w = sizes["window_rows"]
        self.row_of = np.full(sizes["houses"] * ID_SPAN * ID_SPAN, -1)
        self.ring = np.zeros((sizes["plugs"], self.w), dtype=np.float32)
        self.seen = np.zeros(sizes["plugs"], dtype=np.int64)
        self.sum = np.zeros(sizes["plugs"])
        self.plugs = 0
        self.control = control

    def rows_of(self, code: np.ndarray) -> np.ndarray:
        """Each plug's row of the state, given out at its first record."""
        new = np.unique(code[self.row_of[code] < 0])
        self.row_of[new] = self.plugs + np.arange(len(new))
        self.plugs += len(new)
        return self.row_of[code]

    def step(self, ts, cols, leaving=None, emit=True):
        """Take in kept rows `cols` (event times `ts`), in order. `leaving`,
        the rows that one window over all plugs would push out, is not
        looked at: a record leaves when its own plug has sent `window_rows`
        more. Returns the rows' output lanes, or None where `emit` is false
        (the state alone moves on)."""
        plug = self.rows_of(plug_code(cols))
        n = len(plug)
        if not n:
            return None if not emit else {
                "event_time": ts, "ts": cols["ts"], "avgLoad": np.empty(0),
                **{name: cols[name].astype(np.int64) for name in KEYS}}
        # the batch by plug, a plug's rows in arrival order: row i is its
        # plug's record number `at[i]` since the stream began
        order = np.argsort(plug, kind="stable")
        p = plug[order]
        v32 = cols["value"].astype(np.float32)[order]
        v = v32.astype(np.float64)
        first = np.flatnonzero(np.r_[True, p[1:] != p[:-1]])
        length = np.diff(np.r_[first, n])
        run, length = np.repeat(first, length), np.repeat(length, length)
        nth = np.arange(n) - run
        at = self.seen[p] + nth
        # the record that `at` pushes out is number at - w: a row of the
        # ring, or younger than every row of the ring: one of this batch
        out = np.zeros(n)
        old = (at >= self.w) & (nth < self.w)
        out[old] = self.ring[p[old], at[old] % self.w]
        own = np.flatnonzero(nth >= self.w)
        out[own] = v[own - self.w]
        delta = v - out
        total = np.cumsum(delta)
        s = self.sum[p] + total - (total[run] - delta[run])
        result = None
        if emit:
            s_emit = s
            if self.control:
                import ml_dtypes

                s_emit = s.astype(ml_dtypes.bfloat16).astype(np.float64)
            avg = np.empty(n)
            avg[order] = s_emit / np.minimum(at + 1, self.w)
            result = {"event_time": ts, "ts": cols["ts"], "avgLoad": avg,
                      **{name: cols[name].astype(np.int64) for name in KEYS}}
        # a plug's last `w` rows of the batch go into its ring
        stays = nth >= length - self.w
        self.ring[p[stays], at[stays] % self.w] = v32[stays]
        last = run + length - 1 == np.arange(n)
        self.sum[p[last]] = s[last]
        self.seen[p[last]] = at[last] + 1
        return result


def reference(ts: np.ndarray, cols: dict, sizes: dict,
              control: bool = False) -> dict:
    """Output lanes for a whole stream that starts with empty state."""
    keep = kept(cols)
    ts, cols = ts[keep], {k: v[keep] for k, v in cols.items()}
    return Running(sizes, control).step(ts, cols)
