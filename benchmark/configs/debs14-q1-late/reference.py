"""`@app:watermark(bound='2 sec')` ahead of the smart-plug query on event
time, in plain NumPy, independent of the engine. Replay form: fed every
`send_columns` call in order, it says what the query callback is owed by
the time that call returns.

The watermark moves once per call. Of a call's records, those older than
the watermark the calls before left are late: counted (`late`), never let
through. The others are held. Then the watermark goes to the newest event
time seen less the bound, and every held record at or behind it is let
through in the order of event time, records of one time in arrival order
(the rules of `benchmark/tests/data/configs/t-late`, without its loop over
rows). What is let through meets `debs14-q1-time`'s query: its load records
enter the window of the last `window_s` seconds and emit the plug's mean
load. That part is `debs14-q1-time`'s `Running`, loaded from its file, not
copied: one statement of the window's semantics.

Work records move the watermark and are counted when late like any other,
and emit nothing, so only load records are held."""

import importlib.util
from pathlib import Path

import numpy as np

_SOURCE = Path(__file__).resolve().parents[1] / "debs14-q1-time" / "reference.py"
_spec = importlib.util.spec_from_file_location("bench_time_reference", _SOURCE)
_time = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_time)

KEYS = _time.KEYS
LANES = ("ts", "value", *KEYS)


class Replay:
    """`control` keeps the per-plug window sum in bfloat16, as
    `debs14-q1-time`'s control does."""

    def __init__(self, sizes: dict, control: bool = False):
        self.bound = sizes["bound_ms"]
        self.window = _time.Running(sizes, control)
        self.newest = self.watermark = None
        self.late = 0             # records behind the watermark, all calls
        self.offered = 0          # records fed, all calls
        # load records not let through yet, in arrival order
        self.held_t = np.empty(0, np.int64)
        self.held = {}

    def feed(self, stream, ts, cols, emit):
        ts = np.asarray(ts, dtype=np.int64)
        self.offered += len(ts)
        fresh = np.ones(len(ts), bool)
        if self.watermark is not None:
            fresh = ts >= self.watermark
            self.late += len(ts) - int(fresh.sum())
        if fresh.any():
            newest = int(ts[fresh].max())
            if self.newest is None or newest > self.newest:
                self.newest = newest
        if self.newest is not None and (
                self.watermark is None
                or self.newest - self.bound > self.watermark):
            self.watermark = self.newest - self.bound
        take = fresh & cols["property"]
        t = np.concatenate([self.held_t, ts[take]])
        lanes = {k: np.concatenate([self.held[k], cols[k][take]])
                 if len(self.held_t) else cols[k][take] for k in LANES}
        if self.watermark is None:
            through = np.zeros(len(t), bool)
        else:
            through = t <= self.watermark
        self.held_t = t[~through]
        self.held = {k: v[~through] for k, v in lanes.items()}
        # held records came before the call's, so arrival order is row order
        order = np.flatnonzero(through)
        order = order[np.argsort(t[order], kind="stable")]
        out = self.window.step(
            t[order], {k: v[order] for k, v in lanes.items()}, None, emit)
        return len(order), out
