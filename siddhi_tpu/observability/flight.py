"""Per-junction flight recorder: a bounded ring of the last N events.

The black-box analog for stream debugging (Hazelcast Jet's tail-debugging
argument, PAPERS.md): when a dispatch fails, the question is never just
"what failed" but "what flowed through immediately before". Each opted-in
junction keeps a fixed columnar arena of the last N events (timestamp +
physical attribute values) that is:

* written on every publish with NO per-event Python allocation — the arena
  is preallocated once and rows are copied in with (at most two) slice
  assignments per batch;
* decoded to host rows only on demand (`events()`), via the same vectorized
  `rows_from_arrays` path the junction's own host decode uses;
* dumped automatically into the error-store entry when a dispatch failure
  is captured by `@OnError(action='STORE')`, and readable on demand via
  `runtime.flight_record(stream_id)` or the `/flight` endpoint.

Enabled per stream with `@flightRecorder(size='256')` or process-wide with
`SIDDHI_TPU_FLIGHT=N`. When not enabled the junction's hot path pays one
`is None` check (the same contract as the statistics wiring).

Cost when ENABLED: the fused send_columns path records from the host-side
wire columns (free), but the per-batch publish path must read the device
batch back (`np.asarray` per lane) — one d2h sync per publish. That is the
price of the black box: negligible on CPU, a real per-batch readback on
accelerators (its cost on a directly attached chip is unmeasured) — there,
prefer arming only ingress streams fed by columnar sends.
"""

from __future__ import annotations

import os
import threading

import numpy as np

DEFAULT_FLIGHT_SIZE = 256
_MAX_FLIGHT_SIZE = 65536

FLIGHT_ENV = "SIDDHI_TPU_FLIGHT"


def flight_env_size() -> int:
    """Process-wide flight-recorder override: N > 0 enables a ring of N
    events on EVERY junction; 0/unset defers to the stream's
    `@flightRecorder` annotation. A malformed value warns LOUDLY instead
    of silently disarming — an operator who believes the black box is
    armed must not discover otherwise at the next crash; oversized values
    clamp to the maximum."""
    import logging

    v = os.environ.get(FLIGHT_ENV, "").strip()
    if not v:
        return 0
    try:
        n = int(v)
    except ValueError:
        logging.getLogger(__name__).warning(
            "%s=%r is not an integer — the flight recorder is NOT armed",
            FLIGHT_ENV, v,
        )
        return 0
    if n < 0:
        logging.getLogger(__name__).warning(
            "%s=%d is negative — the flight recorder is NOT armed",
            FLIGHT_ENV, n,
        )
        return 0
    if n > _MAX_FLIGHT_SIZE:
        logging.getLogger(__name__).warning(
            "%s=%d exceeds the maximum; clamping the ring to %d events",
            FLIGHT_ENV, n, _MAX_FLIGHT_SIZE,
        )
        return _MAX_FLIGHT_SIZE
    return n


def iter_flight_annotation_problems(ann):
    """Yield one message per malformed `@flightRecorder` element — THE
    validation rules, shared by the runtime resolver (raises on the first)
    and the analyzer's SA114 diagnostics (reports them all)."""
    for k, v in ann.elements:
        if k == "size" or (k is None and len(ann.elements) == 1):
            try:
                ok = 1 <= int(v) <= _MAX_FLIGHT_SIZE
            except (TypeError, ValueError):
                ok = False
            if not ok:
                yield (
                    f"@flightRecorder size '{v}' must be an integer in "
                    f"1..{_MAX_FLIGHT_SIZE}"
                )
        else:
            yield (
                f"unknown @flightRecorder option '{k if k is not None else v}'"
                " (expected size)"
            )


def resolve_flight_annotation(ann) -> int:
    """Ring size for one stream from its `@flightRecorder` annotation (or
    None), before the SIDDHI_TPU_FLIGHT env override; 0 = not enabled.
    Raises SiddhiAppCreationError on malformed options — the runtime analog
    of the analyzer's SA114 diagnostic."""
    from siddhi_tpu.core.errors import SiddhiAppCreationError

    size = 0
    if ann is not None:
        for problem in iter_flight_annotation_problems(ann):
            raise SiddhiAppCreationError(problem)
        size = int(
            ann.element("size") or ann.element(None) or DEFAULT_FLIGHT_SIZE
        )
    env = flight_env_size()
    return max(size, env)


class FlightRecorder:
    """Fixed columnar arena of the last `size` events through one junction.

    The arena (one [size] array per attribute + ts/kind lanes) is allocated
    once; `record_*` copies the batch tail in circularly, so steady-state
    recording does zero per-event allocation. Thread-safe: publishes arrive
    from sender/async-drain/scheduler threads while `events()` reads.
    """

    def __init__(self, schema, interner, size: int = DEFAULT_FLIGHT_SIZE):
        from siddhi_tpu.core.types import PHYSICAL_DTYPE

        if size <= 0:
            raise ValueError("flight recorder size must be positive")
        self.schema = schema
        self.interner = interner
        self.size = int(size)
        self._ts = np.zeros((self.size,), np.int64)
        self._kind = np.zeros((self.size,), np.int8)
        self._cols = {
            n: np.zeros((self.size,), np.dtype(PHYSICAL_DTYPE[t]))
            for n, t in schema.attrs
        }
        self._head = 0  # next write slot
        self._count = 0  # total events ever recorded
        self._lock = threading.Lock()

    # ---- recording -------------------------------------------------------

    def _write(self, ts, kind, cols, n: int) -> None:
        """Copy the last min(n, size) rows into the ring (caller holds the
        lock); `cols` maps attr -> [n] physical host array."""
        if n <= 0:
            return
        if n > self.size:  # only the tail can survive anyway
            ts = ts[n - self.size:]
            kind = None if kind is None else kind[n - self.size:]
            cols = {k: v[n - self.size:] for k, v in cols.items()}
            self._count += n - self.size
            n = self.size
        h = self._head
        first = min(n, self.size - h)
        dsts = [(h, 0, first)]
        if first < n:
            dsts.append((0, first, n))
        for dst, lo, hi in dsts:
            m = hi - lo
            self._ts[dst:dst + m] = ts[lo:hi]
            if kind is None:
                self._kind[dst:dst + m] = 0
            else:
                self._kind[dst:dst + m] = kind[lo:hi]
            for name, arena in self._cols.items():
                arena[dst:dst + m] = cols[name][lo:hi]
        self._head = (h + n) % self.size
        self._count += n

    def record_batch(self, batch) -> None:
        """Record a device batch's valid rows (the per-batch publish path)."""
        valid = np.asarray(batch.valid)
        idx = np.nonzero(valid)[0]
        if idx.size == 0:
            return
        ts = np.asarray(batch.ts)[idx]
        kind = np.asarray(batch.kind)[idx]
        cols = {n: np.asarray(c)[idx] for n, c in batch.cols.items()}
        with self._lock:
            self._write(ts, kind, cols, idx.size)

    def record_columns(self, timestamps, cols, n: int) -> None:
        """Record host columnar rows (the fused-ingest path: all rows are
        valid CURRENT events and the arrays never touched the device)."""
        if n <= 0:
            return
        ts = np.asarray(timestamps)[:n]
        host = {name: np.asarray(cols[name])[:n] for name in self._cols}
        with self._lock:
            self._write(ts, None, host, n)

    # ---- reading ---------------------------------------------------------

    def events(self, limit: int | None = None) -> list[tuple[int, tuple]]:
        """Decode the recorded ring, oldest first, as (timestamp, data_tuple)
        pairs — the exact shape ErroneousEvent.events uses."""
        from siddhi_tpu.core.event import rows_from_arrays

        with self._lock:
            n = min(self._count, self.size)
            if n == 0:
                return []
            # ring order -> insertion order
            order = (np.arange(n) + (self._head - n)) % self.size
            ts = self._ts[order].copy()
            kind = self._kind[order].copy()
            cols = {name: a[order].copy() for name, a in self._cols.items()}
        if limit is not None and limit < n:
            ts, kind = ts[n - limit:], kind[n - limit:]
            cols = {k: v[n - limit:] for k, v in cols.items()}
            n = limit
        triples = rows_from_arrays(
            self.schema, ts, kind, cols, n, self.interner
        )
        return [(t, data) for t, _k, data in triples]

    def describe_state(self) -> dict:
        with self._lock:  # one atomic read: recorded/total/ts must agree
            n = min(self._count, self.size)
            total = self._count
            newest = int(self._ts[(self._head - 1) % self.size]) if n else None
            oldest = (
                int(self._ts[(self._head - n) % self.size]) if n else None
            )
        return {
            "size": self.size,
            "recorded": n,
            "total": total,
            "oldest_ts": oldest,
            "newest_ts": newest,
        }
