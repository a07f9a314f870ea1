"""`every e1=Plug[load] -> e2=Plug[load, same plug, value >= e1.value + rise]
within 1 min`, independent of the engine. Replay form.

Every load row is held as a first event of its plug (house, household,
plug). A load row completes every held first event of its plug that is not
older than `within_ms` and whose load lies at least `rise_w` below its own
(the sum taken in float32, as the query's `float + int` is), oldest first,
and lets those go; it is then held itself, the completing rows too, and a row
never completes its own. A held event that no row completes in time is let
go unmatched.

Held events do not touch one another: each is completed by the FIRST later
row of its plug that passes, so a call is worked out without a loop over
rows. The held events and the call's load rows are sorted by plug, in
arrival order within one; a row can complete something only if its load is
`rise_w` above the smallest there is (one load row in fifty, under the
generator's law), and each such row is held against the d-th entry before it
in its plug, d = 1, 2, ..., all rows at once, until none lies within
`within_ms` (60 lags on the source's schedule of one load record a plug a
second). Lags grow with the distance in arrival order, so of the rows that
pass for one held event the first takes it."""

import numpy as np

KEY = ("house_id", "household_id", "plug_id")


class Replay:
    """`control` reads `value` rounded to bfloat16: other rows pass the
    threshold, and the loads it states are the rounded ones."""

    def __init__(self, sizes: dict, control: bool = False):
        self.within = int(sizes["within_ms"])
        self.rise = np.float32(sizes["rise_w"])
        self.control = control
        self.seen = 0           # load rows so far: a first event's arrival
        # held first events, oldest first: key columns, event time, `ts`,
        # load, arrival number
        self.held = {**{k: np.zeros(0, np.int64) for k in KEY},
                     "t": np.zeros(0, np.int64), "ts": np.zeros(0, np.int64),
                     "v": np.zeros(0, np.float32), "seq": np.zeros(0, np.int64)}
        self.max_held = 0       # the most first events held at once
        self.max_per_row = 0    # the most matches one row has completed

    def feed(self, stream, ts, cols, emit):
        load = np.flatnonzero(np.asarray(cols["property"], dtype=bool))
        value = np.asarray(cols["value"], dtype=np.float32)[load]
        if self.control:
            import ml_dtypes

            value = value.astype(ml_dtypes.bfloat16).astype(np.float32)
        m = len(load)
        new = {**{k: np.asarray(cols[k])[load].astype(np.int64) for k in KEY},
               "t": np.asarray(ts, dtype=np.int64)[load],
               "ts": np.asarray(cols["ts"], dtype=np.int64)[load],
               "v": value, "seq": self.seen + np.arange(m, dtype=np.int64)}
        self.seen += m
        h = len(self.held["t"])
        both = {k: np.concatenate([self.held[k], new[k]]) for k in new}
        n = h + m
        if n == 0:
            return 0, self._lanes(both, load, load) if emit else None
        # by plug, in arrival order within one (the held ones came first)
        plug = self._plug_numbers(both)
        order = np.argsort(plug, kind="stable")
        plug, t, v = plug[order], both["t"][order], both["v"][order]
        # rows of this call that could complete anything at all
        cand = np.flatnonzero((order >= h) & (v >= v.min() + self.rise))
        firsts, seconds = [], []
        taken = np.zeros(n, bool)
        d = 0
        while len(cand):
            d += 1
            i = cand - d
            near = i >= 0
            i = np.maximum(i, 0)
            near &= (plug[i] == plug[cand]) & (t[cand] - t[i] <= self.within)
            # lags grow, so rows come in arrival order: of the rows that
            # pass for one held event, the first takes it
            hit = near & (v[cand] >= v[i] + self.rise) & ~taken[i]
            taken[i[hit]] = True
            firsts.append(i[hit])
            seconds.append(cand[hit])
            cand = cand[near]   # a plug's entries only get older
        none = np.zeros(0, np.int64)
        first = order[np.concatenate(firsts)] if firsts else none
        second = order[np.concatenate(seconds)] if seconds else none
        # a call's matches in the order of their second events, those of one
        # second event in the order their first events arrived: `both` is in
        # arrival order
        by = np.argsort(second * n + first)
        first, second = first[by], second[by]
        if len(second):
            self.max_per_row = max(self.max_per_row,
                                   int(np.bincount(second - h).max()))
        # still held: not completed, and not too old for a row yet to come
        # (event time never runs backwards, so those are the youngest)
        young = int(np.searchsorted(both["t"], both["t"][-1] - self.within))
        done = np.zeros(n, bool)
        done[first] = True
        keep = young + np.flatnonzero(~done[young:])
        self.held = {k: x[keep] for k, x in both.items()}
        self.max_held = max(self.max_held, len(keep))
        return len(second), self._lanes(both, first, second) if emit else None

    @staticmethod
    def _plug_numbers(both) -> np.ndarray:
        """One number per (house, household, plug), the same for equal
        keys: 16 bits wide where the key's ranges allow it (NumPy then sorts
        by radix, in one pass), else 64."""
        a, b, c = (both[k] for k in KEY)
        if min(a.min(), b.min(), c.min()) < 0:
            _, number = np.unique(np.stack([a, b, c], axis=1), axis=0,
                                  return_inverse=True)
            return number.reshape(-1)
        number = (a * (int(b.max()) + 1) + b) * (int(c.max()) + 1) + c
        return number.astype(np.uint16) if number.max() < 1 << 16 else number

    @staticmethod
    def _lanes(both, first, second):
        return {
            "event_time": both["t"][second],
            **{k: both[k][first] for k in KEY},
            "ts1": both["ts"][first], "load1": both["v"][first],
            "ts2": both["ts"][second], "load2": both["v"][second],
        }
