"""`A#window.length(W) as a join B#window.length(W) as b on a.k == b.k`, row
by row, independent of the engine: an arriving row is held by its side's
window (the oldest of W + 1 leaves) and paired with every row the other
side's window holds under its key, oldest first. The rows come in the calls
in which they were sent: a call's rows meet the other window as the calls
before left it. Replay form."""

from collections import deque

import numpy as np


class Replay:
    """`control` carries x and y in bfloat16."""

    def __init__(self, sizes: dict, control: bool = False):
        self.windows = {"A": deque(maxlen=sizes["window_rows"]),
                        "B": deque(maxlen=sizes["window_rows"])}
        self.control = control

    def feed(self, stream, ts, cols, emit):
        mine = self.windows[stream]
        other = self.windows["B" if stream == "A" else "A"]
        value = cols["x" if stream == "A" else "y"]
        if self.control:
            import ml_dtypes

            value = value.astype(ml_dtypes.bfloat16).astype(np.float32)
        out = []
        for t, k, v in zip(ts.tolist(), cols["k"].tolist(), value.tolist()):
            mine.append((k, v))
            for k2, v2 in other:
                if k2 == k:
                    out.append((t, k, v, v2) if stream == "A" else
                               (t, k, v2, v))
        if not emit:
            return len(out), None
        return len(out), {
            "event_time": np.array([o[0] for o in out], dtype=np.int64),
            "k": np.array([o[1] for o in out], dtype=np.int64),
            "x": np.array([o[2] for o in out], dtype=np.float32),
            "y": np.array([o[3] for o in out], dtype=np.float32)}
