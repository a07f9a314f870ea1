"""Plain NumPy / Python semantics of NEXMark query 5's count, independent of
the engine: the window holds the bids of the last `window_ms` of the
stream's own time (`dateTime`); each arriving bid first lets go, from the
oldest on, of every held bid whose `dateTime` is `window_ms` or more behind
its own, then is held itself, and emits (event time, auction, the number of
held bids on that auction, itself included). Kept as a running state, as the
stream is far longer than memory: the held bids in arrival order (time,
auction: 16 B each) and a `dict` auction -> count that forgets an auction
when its last bid has left."""

import numpy as np


def kept(cols: dict) -> np.ndarray:
    """Which input rows produce an emission: every bid."""
    return np.ones(len(next(iter(cols.values()))), dtype=bool)


class Queue:
    """Rows in arrival order, taken off at the head: flat lanes with room at
    the end, moved to the front when the room is used up."""

    def __init__(self, lanes: int):
        self.lanes = [np.empty(0, np.int64) for _ in range(lanes)]
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
    """The window's held bids and the count of every auction that holds one,
    carried along the stream.

    `control` is the check's own control: the count is carried in bfloat16,
    the nearest type below the integers the query states, so a count beyond
    256 that is no multiple of its spacing there reads wrong: two fifths of
    the hot auctions' rows (they hold up to 770 bids), a fifth of all."""

    def __init__(self, sizes: dict, control: bool = False):
        self.window_ms = sizes["window_ms"]
        self.counts: dict = {}
        self.queue = Queue(2)
        self.control = control

    def live(self) -> int:
        """Auctions that hold a bid of the window."""
        return len(self.counts)

    def step(self, ts, cols, leaving=None, emit=True):
        """Take in bids `cols` (event times `ts`), in order. `leaving`, the
        rows a window counted in rows would push out, is not looked at:
        which bids leave follows from their time. Returns the rows' output
        lanes, or None where `emit` is false (the state alone moves on)."""
        t_in = cols["dateTime"].astype(np.int64)
        key_in = cols["auction"].astype(np.int64)
        n = len(t_in)
        before = self.queue.tail - self.queue.head
        self.queue.push(t_in, key_in)
        q_time, q_key = self.queue.held()
        # the loop expires from the head and stops at the first bid not yet
        # due; with times that never run backwards that is every bid at
        # least `window_ms` old
        if n and (np.diff(q_time[max(before - 1, 0):]) < 0).any():
            raise ValueError("dateTime runs backwards: the head rule needs the loop")
        # bids gone once arrival i has been taken in (never itself)
        gone = np.searchsorted(q_time, t_in - self.window_ms, side="right")
        m = int(gone[-1]) if n else 0
        key_out = q_key[:m]
        keys, inv = np.unique(np.concatenate([key_out, key_in]),
                              return_inverse=True)
        held = self.counts
        out = None
        if emit:
            # one line of +arrivals and -leavers, sorted by auction and,
            # within an auction, by position; a leaver goes just before the
            # arrival that lets it go. Counts follow by a running sum that
            # starts again, from what the auction held, at each auction.
            pusher = np.searchsorted(gone, np.arange(m), side="right")
            pos = np.concatenate([pusher * 2, np.arange(n) * 2 + 1])
            delta = np.concatenate([-np.ones(m, np.int64), np.ones(n, np.int64)])
            order = np.lexsort((pos, inv))
            k = inv[order]
            run = np.cumsum(delta[order])
            first = np.flatnonzero(np.r_[True, k[1:] != k[:-1]])
            seg = np.repeat(first, np.diff(np.r_[first, len(k)]))
            base = np.fromiter((held.get(a, 0) for a in keys.tolist()),
                               np.int64, len(keys))
            count = base[k] + run - (run[seg] - delta[order][seg])
            arrival = order >= m
            num = np.empty(n, np.int64)
            num[order[arrival] - m] = count[arrival]
            if self.control:
                import ml_dtypes

                num = num.astype(ml_dtypes.bfloat16).astype(np.int64)
            out = {"event_time": ts, "auction": key_in, "num": num}
        net = (np.bincount(inv[m:], minlength=len(keys))
               - np.bincount(inv[:m], minlength=len(keys)))
        for a, d in zip(keys.tolist(), net.tolist()):
            c = held.get(a, 0) + d
            if c:
                held[a] = c
            else:
                held.pop(a, None)  # the auction's last bid has left
        self.queue.head += m
        return out


def reference(ts: np.ndarray, cols: dict, sizes: dict,
              control: bool = False) -> dict:
    """Output lanes for a whole stream that starts with empty state."""
    return Running(sizes, control).step(ts, cols)
