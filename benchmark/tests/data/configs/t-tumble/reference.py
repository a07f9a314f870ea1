"""`S#window.externalTimeBatch(t, bucket_ms) select t, k, sum(v), count()
group by k`, row by row, independent of the engine: the first row opens a
bucket at its own `t`; a row whose `t` is at or past the open bucket's end
closes it first (the next opens where it ended) and then joins the new one.
A bucket that closes emits one row per key it holds: the key's last row's
event time and `t`, the float64 sum and the count of its rows, in the order
of those last rows. Replay form."""

import numpy as np


class Replay:
    """`control` rounds each sum to bfloat16."""

    def __init__(self, sizes: dict, control: bool = False):
        self.span = sizes["bucket_ms"]
        self.control = control
        self.end = None
        self.groups = {}  # k -> [last event time, last t, sum, count]

    def feed(self, stream, ts, cols, emit):
        out = []
        for t, tt, k, v in zip(ts.tolist(), cols["t"].tolist(),
                               cols["k"].tolist(), cols["v"].tolist()):
            if self.end is None:
                self.end = tt + self.span
            if tt >= self.end:
                out += sorted(
                    [(g[0], g[1], key, g[2], g[3])
                     for key, g in self.groups.items()],
                    key=lambda row: row[4:] and row[0])
                self.groups = {}
                while tt >= self.end:
                    self.end += self.span
            g = self.groups.pop(k, [0, 0, 0.0, 0])
            self.groups[k] = [t, tt, g[2] + v, g[3] + 1]
        if not emit:
            return len(out), None
        total = np.array([o[3] for o in out], dtype=np.float64)
        if self.control:
            import ml_dtypes

            total = total.astype(ml_dtypes.bfloat16).astype(np.float64)
        return len(out), {
            "event_time": np.array([o[0] for o in out], dtype=np.int64),
            "t": np.array([o[1] for o in out], dtype=np.int64),
            "k": np.array([o[2] for o in out], dtype=np.int64),
            "total": total,
            "n": np.array([o[4] for o in out], dtype=np.int64)}
