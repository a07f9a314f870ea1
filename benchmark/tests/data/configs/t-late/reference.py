"""`@app:watermark(bound='2 sec')` ahead of `S[v > above] select k, v`, row
by row, independent of the engine. The watermark moves once per call: rows
of a call that are older than the watermark the calls before left are late
and gone; the others are held; then the watermark goes to the newest event
time seen less the bound, and every held row at or behind it is let through
in the order of event time, rows of one time in arrival order. A row let
through emits if its `v` is above the threshold. Replay form."""

import numpy as np


class Replay:
    """`control` reads `v` rounded to bfloat16."""

    def __init__(self, sizes: dict, control: bool = False):
        self.bound, self.above = sizes["bound_ms"], sizes["above"]
        self.control = control
        self.held = []  # (event time, arrival number, k, v)
        self.arrived = 0
        self.newest = self.watermark = None

    def feed(self, stream, ts, cols, emit):
        v = cols["v"]
        if self.control:
            import ml_dtypes

            v = v.astype(ml_dtypes.bfloat16).astype(np.float32)
        for t, k, x in zip(ts.tolist(), cols["k"].tolist(), v.tolist()):
            self.arrived += 1
            if self.watermark is not None and t < self.watermark:
                continue
            self.held.append((t, self.arrived, k, x))
            if self.newest is None or t > self.newest:
                self.newest = t
        if self.newest is not None:
            mark = self.newest - self.bound
            if self.watermark is None or mark > self.watermark:
                self.watermark = mark
        through = sorted(h for h in self.held if h[0] <= self.watermark)
        self.held = [h for h in self.held if h[0] > self.watermark]
        out = [h for h in through if h[3] > self.above]
        if not emit:
            return len(out), None
        return len(out), {
            "event_time": np.array([o[0] for o in out], dtype=np.int64),
            "k": np.array([o[2] for o in out], dtype=np.int64),
            "v": np.array([o[3] for o in out], dtype=np.float32)}
