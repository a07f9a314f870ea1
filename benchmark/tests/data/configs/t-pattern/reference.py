"""`every e1=S[v > hi] -> e2=S[k == e1.k and v < lo] within 1 sec`, row by
row, independent of the engine: a row above `hi` is held as a first event;
a row below `lo` completes every held first event of its key that is not
older than `within_ms`, oldest first, and each completed one is let go. A
held event older than that is let go unmatched. Replay form."""

import numpy as np


class Replay:
    """`control` reads `v` rounded to bfloat16: other rows pass the two
    thresholds, and the values it states are the rounded ones."""

    def __init__(self, sizes: dict, control: bool = False):
        self.hi, self.lo = sizes["hi"], sizes["lo"]
        self.within = sizes["within_ms"]
        self.control = control
        self.held = []  # (event time, k, v) of first events, oldest first

    def feed(self, stream, ts, cols, emit):
        v = cols["v"]
        if self.control:
            import ml_dtypes

            v = v.astype(ml_dtypes.bfloat16).astype(np.float32)
        out = []
        for t, k, x in zip(ts.tolist(), cols["k"].tolist(), v.tolist()):
            self.held = [h for h in self.held if t - h[0] <= self.within]
            if x < self.lo:
                out += [(t, k, h[2], x) for h in self.held if h[1] == k]
                self.held = [h for h in self.held if h[1] != k]
            elif x > self.hi:
                self.held.append((t, k, x))
        if not emit:
            return len(out), None
        return len(out), {
            "event_time": np.array([o[0] for o in out], dtype=np.int64),
            "k": np.array([o[1] for o in out], dtype=np.int64),
            "v1": np.array([o[2] for o in out], dtype=np.float32),
            "v2": np.array([o[3] for o in out], dtype=np.float32)}
