"""Plain NumPy semantics of the smart-plug query on event time, independent
of the engine: keep the load records; the window holds the load records of
the last `window_s` seconds of the stream's own time (`ts`); each arriving
load record first lets go, from the oldest on, of every held record whose
`ts` is `window_s` or more behind its own, then is held itself, and emits
(event time, ts, house, household, plug, mean load of that plug's held
records). Kept as a running state, as the stream is far longer than memory:
the held records in arrival order (time, plug, load: 20 B each) and per plug
the float64 sum and the count of its held records."""

import numpy as np

KEYS = ("house_id", "household_id", "plug_id")
ID_SPAN = 64  # household and plug ids are below this


def kept(cols: dict) -> np.ndarray:
    """Which input rows produce an emission."""
    return cols["property"]


def plug_code(cols: dict) -> np.ndarray:
    return ((cols["house_id"].astype(np.int64) * ID_SPAN
             + cols["household_id"]) * ID_SPAN + cols["plug_id"])


class Queue:
    """Rows in arrival order, taken off at the head: three flat lanes with
    room at the end, moved to the front when the room is used up."""

    def __init__(self):
        self.lanes = [np.empty(0, np.int64), np.empty(0, np.int32),
                      np.empty(0, np.float32)]
        self.head = self.tail = 0

    def push(self, *rows):
        n = len(rows[0])
        if self.tail + n > len(self.lanes[0]):
            live = self.tail - self.head
            room = max(2 * (live + n), 1024)
            for k, lane in enumerate(self.lanes):
                grown = np.empty(room, lane.dtype)
                grown[:live] = lane[self.head:self.tail]
                self.lanes[k] = grown
            self.head, self.tail = 0, live
        for lane, new in zip(self.lanes, rows):
            lane[self.tail:self.tail + n] = new
        self.tail += n

    def held(self):
        return [lane[self.head:self.tail] for lane in self.lanes]


class Running:
    """The window's held records and per-plug sum and count, carried along
    the stream.

    `control` is the check's own control: the per-plug window sum of load is
    rounded to bfloat16 before the division. Rounding the exact sum once is
    the least error any running sum kept in that type can have."""

    def __init__(self, sizes: dict, control: bool = False):
        slots = sizes["houses"] * ID_SPAN * ID_SPAN
        self.sum = np.zeros(slots)
        self.count = np.zeros(slots, dtype=np.int64)
        self.window_s = sizes["window_s"]
        self.queue = Queue()
        self.control = control

    def step(self, ts, cols, leaving=None, emit=True):
        """Take in kept rows `cols` (event times `ts`), in order. `leaving`,
        the rows a window counted in rows would push out, is not looked at:
        which rows leave follows from their time. Returns the rows' output
        lanes, or None where `emit` is false (the state alone moves on)."""
        t_in = cols["ts"].astype(np.int64)
        key_in = plug_code(cols)
        n = len(t_in)
        before = self.queue.tail - self.queue.head
        self.queue.push(t_in, key_in, cols["value"])
        q_time, q_key, q_val = self.queue.held()
        # the loop expires from the head and stops at the first record not
        # yet due; with `ts` that never runs backwards that is every record
        # at least `window_s` old (checked: the stream's never does)
        if n and (np.diff(q_time[max(before - 1, 0):]) < 0).any():
            raise ValueError("ts runs backwards: the head rule needs the loop")
        # records gone once arrival i has been taken in (never itself)
        gone = np.searchsorted(q_time, t_in - self.window_s, side="right")
        m = int(gone[-1]) if n else 0
        key_out = q_key[:m].astype(np.int64)
        val_out = q_val[:m].astype(np.float64)
        val_in = cols["value"].astype(np.float64)
        out = None
        if emit:
            # one line of +arrivals and -leavers, sorted by plug and, within
            # a plug, by position; a leaver goes just before the arrival
            # that lets it go. Running totals per plug follow by cumsum.
            pusher = np.searchsorted(gone, np.arange(m), side="right")
            key = np.concatenate([key_out, key_in])
            pos = np.concatenate([pusher * 2, np.arange(n) * 2 + 1])
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
        self.queue.head += m
        return out


def reference(ts: np.ndarray, cols: dict, sizes: dict,
              control: bool = False) -> dict:
    """Output lanes for a whole stream that starts with empty state."""
    keep = kept(cols)
    ts, cols = ts[keep], {k: v[keep] for k, v in cols.items()}
    return Running(sizes, control).step(ts, cols)
