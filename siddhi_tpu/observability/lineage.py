"""Event lineage & provenance: explain every output back to its input events.

The missing observability layer after metrics (PR 3), introspection/selfmon
(PR 5) and the profiler/EXPLAIN (PR 6): when an alert fires, the operator's
first question is not "how fast" but **"which input events caused this
output?"** — the match-explainability axis CEP frameworks are judged on
("A Comprehensive Scalable Framework for Cloud-Native Pattern Detection",
PAPERS.md) and the per-event causality that tail-latency debugging needs
beyond aggregate histograms ("Hazelcast Jet: Low-latency Stream Processing
at the 99.99th Percentile", PAPERS.md).

Opt-in with `@app:lineage(capacity='N', mode='full|sample')`. Three layers:

1. **Ingress stamping** — every stream junction gets a `LineageArena`
   (riding the flight-recorder columnar arena: preallocated ring, circular
   slice-copy writes, zero per-event allocation) that assigns each valid
   CURRENT event a monotonically increasing per-stream sequence id and
   keeps the last `capacity` events decodable on demand. Seq ids survive
   fusion, pipelining and sharding because every delivery path
   in this engine is order-preserving per stream (the byte-parity CI
   contract): a consumer's k-th CURRENT row IS the junction's seq k.

2. **Per-operator provenance** — each query runtime, when armed, emits
   `__lin.*` lanes beside its normal aux outputs (extra jitted-program
   outputs; the emissions themselves are untouched, so lineage on/off is
   byte-parity-safe by construction):

   * windows: the admit mask (post-filter) plus the window flow's
     valid/kind/ts lanes drive an exact host-side membership replay —
     each emitted row records the seq range currently in the ring/bucket;
   * pattern/sequence NFAs: the per-ref capture-lane timestamps already
     materialized in the emission buffer surface per match, resolved back
     to per-stream seq ids;
   * joins: each matched output row carries (probe row index, partner
     window seq) — the (left seq, right seq) pair;
   * group-by: admitted rows carry their group key, emissions carry the
     out-row key, and the bucket is filtered per key;
   * aggregations: per time-bucket contributing seq ranges and counts.

   In fused mode the `__lin.*` lanes bypass the chunk program's boolean
   aux reduction and are stacked across the K micro-batches; the
   recorder consumes them in chunk order.

3. **Serving** — `runtime.lineage(stream_or_query, index)` walks the
   recorded graph backward (multi-hop through insert-into chains) to the
   exact input events, decoded on demand from the arenas; `/lineage` +
   `/lineage.json` on the MetricsServer; `@OnError(action='STORE')`
   entries and trace spans gain the contributing seq range; and
   `runtime.explain()` query nodes render live fan-in (avg/max
   inputs-per-output).

Costs: zero when off — one `is None` / attribute check per hot-path site,
the same contract as statistics/tracing/flight. When ON, each observed
step pays one device→host read of its small `__lin.*` lanes (the
flight-recorder caveat again: a blocking read per observed step), and host
memory is bounded by `capacity` per arena / recorder ring
with oldest-first eviction.

Known degradations (recorded as `approx` on the affected records instead
of guessing): order-by/limit queries (positions permuted device-side),
expired-probe join rows, join partners in windows without an admission
order (batch windows, tables, named windows), duplicate-timestamp pattern
captures, exotic windows whose host replay desynchronizes, and
evicted-arena seqs (resolution returns the seq id with `event: None`).
Stream-indexed resolution walks through a producing query only when every
stamped event is attributable to it (arena stamp count == producer publish
count); multi-writer and externally-co-fed streams are listed as `mixed`,
not walked. Partitioned queries are not recorded.
"""

from __future__ import annotations

import threading
from collections import deque
from typing import Optional

import numpy as np

from siddhi_tpu.observability.flight import FlightRecorder

# lane-name prefix for lineage aux outputs; `__lin@l.` / `__lin@r.` tag the
# two halves of a fused self-join impl whose aux dicts merge into one
LIN = "__lin."
LIN_SIDE = "__lin@"

DEFAULT_CAPACITY = 1024
_MAX_CAPACITY = 1 << 20
_MODES = ("full", "sample")

# thread-local "current publisher" set around a lineage-recorded query's
# insert-target publish (app_runtime._wire_insert): the arena stamping
# inside StreamJunction._publish_batch reads it to attribute the seq range
# to its actual producer (multi-producer resolution)
_PUB_TLS = threading.local()


class publisher_context:
    """Context manager marking (qid, recorder) as the publisher of every
    arena stamp inside the block. Re-entrant per thread (insert-into
    chains nest): the previous publisher is restored on exit."""

    __slots__ = ("_pub", "_prev")

    def __init__(self, qid: str, recorder):
        self._pub = (qid, recorder)
        self._prev = None

    def __enter__(self):
        self._prev = getattr(_PUB_TLS, "pub", None)
        _PUB_TLS.pub = self._pub
        return self

    def __exit__(self, *exc):
        _PUB_TLS.pub = self._prev
        return False


def current_publisher() -> Optional[tuple]:
    return getattr(_PUB_TLS, "pub", None)
DEFAULT_SAMPLE_EVERY = 16

# resolution expands at most this many individual seqs per input-stream
# set; wider sets stay as ranges with counts
_EXPAND_LIMIT = 512


class LineageConfig:
    __slots__ = ("capacity", "mode", "sample_every")

    def __init__(
        self,
        capacity: int = DEFAULT_CAPACITY,
        mode: str = "full",
        sample_every: int = DEFAULT_SAMPLE_EVERY,
    ):
        self.capacity = int(capacity)
        self.mode = mode
        self.sample_every = int(sample_every)


def iter_lineage_annotation_problems(ann):
    """Yield one message per malformed `@app:lineage` element — THE rule
    set, shared by the runtime resolver (raises on the first) and the
    analyzer's SA131 diagnostics (reports them all), so the two can never
    drift (same contract as SA113/SA114/SA125-SA130)."""
    for k, v in ann.elements:
        if k == "capacity" or (k is None and len(ann.elements) == 1):
            try:
                ok = 1 <= int(v) <= _MAX_CAPACITY
            except (TypeError, ValueError):
                ok = False
            if not ok:
                yield (
                    f"@app:lineage capacity '{v}' must be an integer in "
                    f"1..{_MAX_CAPACITY}"
                )
        elif k == "mode":
            if str(v) not in _MODES:
                yield (
                    f"@app:lineage mode '{v}' must be one of "
                    f"{'|'.join(_MODES)}"
                )
        elif k == "sample.every":
            try:
                ok = int(v) >= 1
            except (TypeError, ValueError):
                ok = False
            if not ok:
                yield (
                    f"@app:lineage sample.every '{v}' must be a positive "
                    "integer"
                )
        else:
            yield (
                f"unknown @app:lineage option "
                f"'{k if k is not None else v}' (expected capacity, mode, "
                "sample.every)"
            )


def resolve_lineage_annotation(ann) -> Optional[LineageConfig]:
    """LineageConfig from `@app:lineage(...)` (None when absent). Raises
    SiddhiAppCreationError on malformed options — the runtime analog of the
    analyzer's SA131 diagnostic."""
    if ann is None:
        return None
    from siddhi_tpu.core.errors import SiddhiAppCreationError

    for problem in iter_lineage_annotation_problems(ann):
        raise SiddhiAppCreationError(problem)
    cap = ann.element("capacity")
    if cap is None and len(ann.elements) == 1 and ann.elements[0][0] is None:
        cap = ann.elements[0][1]
    return LineageConfig(
        capacity=int(cap) if cap is not None else DEFAULT_CAPACITY,
        mode=str(ann.element("mode") or "full"),
        sample_every=int(ann.element("sample.every") or DEFAULT_SAMPLE_EVERY),
    )


# ---------------------------------------------------------------------------
# ingress stamping: the seq-addressable arena
# ---------------------------------------------------------------------------


class LineageArena(FlightRecorder):
    """Flight-recorder arena with sequence addressing: each recorded valid
    CURRENT event gets seq id = its zero-based position in the stream's
    publish order (`_count` before the write). `next_seq` is the stamp
    high-water; seq `s` is still decodable while `next_seq - size <= s`.

    Thread-safety rides the parent's lock; `last_range` is the (base, n)
    of the most recent record — read under the junction lock by the
    @OnError STORE path and the publish trace span."""

    def __init__(self, schema, interner, size: int):
        super().__init__(schema, interner, size)
        self.last_range: tuple[int, int] = (0, 0)
        # per-publish producer capture: (base_seq, n, qid, pub_base)
        # appended when a lineage-recorded query's publish stamped the
        # range (see publisher_context / StreamJunction._publish_batch) —
        # multi-producer streams then resolve seq s to the producer whose
        # publish covered it, instead of just listing candidates
        self.pub_log: deque = deque(maxlen=max(int(size), 64))

    @property
    def next_seq(self) -> int:
        with self._lock:
            return self._count

    def note_producer(
        self, base: int, n: int, qid: str, pub_base: int
    ) -> None:
        with self._lock:
            self.pub_log.append((int(base), int(n), qid, int(pub_base)))

    def producer_for_seq(self, seq: int) -> Optional[tuple]:
        """(qid, producer pub_index) of the recorded publish covering
        `seq`, or None (unlogged: an external input handler, a fused
        commit, or an evicted log entry)."""
        s = int(seq)
        with self._lock:
            for base, n, qid, pub_base in reversed(self.pub_log):
                if base <= s < base + n:
                    return qid, pub_base + (s - base)
                if base + n <= s:
                    break  # log is base-ordered: older entries only below
        return None

    def record_batch(self, batch) -> tuple[int, int]:
        """Stamp + record a device batch's valid CURRENT rows; returns the
        (base_seq, n) range assigned (n may be 0). `last_range` is updated
        on EVERY call — a zero-CURRENT publish must not leave the previous
        batch's range for the @OnError STORE path to pick up."""
        from siddhi_tpu.core.event import KIND_CURRENT

        valid = np.asarray(batch.valid)
        kind = np.asarray(batch.kind)
        idx = np.nonzero(valid & (kind == KIND_CURRENT))[0]
        if idx.size == 0:
            with self._lock:
                self.last_range = (self._count, 0)
                return self.last_range
        ts = np.asarray(batch.ts)[idx]
        cols = {n: np.asarray(c)[idx] for n, c in batch.cols.items()}
        with self._lock:
            base = self._count
            self._write(ts, None, cols, idx.size)
            self.last_range = (base, idx.size)
        return (base, idx.size)

    def record_columns(self, timestamps, cols, n: int) -> tuple[int, int]:
        """Stamp + record host columnar rows (fused-ingest commit: all rows
        are valid CURRENT events)."""
        if n <= 0:
            with self._lock:
                self.last_range = (self._count, 0)
                return self.last_range
        ts = np.asarray(timestamps)[:n]
        host = {name: np.asarray(cols[name])[:n] for name in self._cols}
        with self._lock:
            base = self._count
            self._write(ts, None, host, n)
            self.last_range = (base, n)
        return (base, n)

    def events_for_seqs(self, seqs) -> dict:
        """Decode specific seq ids (those still in the ring) to
        (timestamp, data_tuple); evicted/future seqs map to None."""
        from siddhi_tpu.core.event import rows_from_arrays

        want = sorted({int(s) for s in seqs if s is not None and s >= 0})
        out: dict = {int(s): None for s in seqs if s is not None}
        if not want:
            return out
        with self._lock:
            count = self._count
            live = [s for s in want if count - self.size <= s < count]
            if not live:
                return out
            # slot from the write head, NOT seq % size: an oversized
            # publish trims to the tail (head advances by size while the
            # seq counter advances by n), permanently shifting the phase
            head = self._head
            slots = np.asarray(
                [(head - (count - s)) % self.size for s in live]
            )
            ts = self._ts[slots].copy()
            cols = {n: a[slots].copy() for n, a in self._cols.items()}
        kind = np.zeros((len(live),), np.int8)
        triples = rows_from_arrays(
            self.schema, ts, kind, cols, len(live), self.interner
        )
        for s, (t, _k, data) in zip(live, triples):
            out[s] = (t, data)
        return out

    def describe_state(self) -> dict:
        d = super().describe_state()
        d["next_seq"] = d.pop("total")
        return d


# ---------------------------------------------------------------------------
# seq-set compression helpers
# ---------------------------------------------------------------------------


def _ranges(seqs) -> list[list[int]]:
    """Sorted seq ids -> inclusive [lo, hi] runs."""
    runs: list[list[int]] = []
    for s in seqs:
        s = int(s)
        if runs and s == runs[-1][1] + 1:
            runs[-1][1] = s
        elif runs and s == runs[-1][1]:
            continue
        else:
            runs.append([s, s])
    return runs


def _expand(runs, limit: int = _EXPAND_LIMIT) -> list[int]:
    out: list[int] = []
    for lo, hi in runs:
        for s in range(lo, hi + 1):
            out.append(s)
            if len(out) >= limit:
                return out
    return out


def _seqset(stream: str, seqs, truncated: bool = False) -> dict:
    seqs = sorted({int(s) for s in seqs if s is not None and s >= 0})
    return {
        "stream": stream,
        "ranges": _ranges(seqs),
        "n": len(seqs),
        "truncated": bool(truncated),
    }


# ---------------------------------------------------------------------------
# per-query recorders
# ---------------------------------------------------------------------------


class _Entry:
    """One admitted input row in a recorder's shadow: (stream seq id,
    event ts, window-time, group key)."""

    __slots__ = ("seq", "ts", "wts", "key")

    def __init__(self, seq, ts, wts=None, key=None):
        self.seq = seq
        self.ts = ts
        self.wts = wts if wts is not None else ts
        self.key = key


class QueryLineage:
    """Base recorder: bounded record ring + fan-in accounting. Subclasses
    implement `_observe` per runtime shape. Observation is serialized by
    the owning runtime's receive lock (per-batch path) or the fused
    engine's in-order chunk loop; `_lock` only guards reads from scrape /
    resolution threads."""

    kind_name = "query"

    def __init__(self, cfg: LineageConfig, query_id: str, published_kinds):
        self.cfg = cfg
        self.query_id = query_id
        # kinds this query's insert-into actually publishes (the insert
        # transform re-kinds them CURRENT on the target): maps the target
        # junction's seq k back to this recorder's k-th published record
        self.published_kinds = frozenset(published_kinds)
        self.records: deque = deque(maxlen=cfg.capacity)
        self.out_count = 0
        self.pub_count = 0
        self.total_inputs = 0
        self.max_inputs = 0
        self.approx_count = 0
        self.desync = False
        # RLock: observe() holds it across the whole replay (observations
        # normally serialize on the receive lock / fused send loop, but a
        # per-batch publish CAN interleave with a fused send on another
        # thread — structure corruption is worse than best-effort order),
        # and _record() re-enters it from inside the replay
        self._lock = threading.RLock()

    # -- observation entry point (handles fused self-join side tagging) ----

    def observe(self, lanes: dict, now: int, tag=None) -> None:
        with self._lock:
            self._observe_locked(lanes, now, tag)

    def _observe_locked(self, lanes: dict, now: int, tag=None) -> None:
        if any(k.startswith(LIN_SIDE) for k in lanes):
            # a fused self-join impl ran both sides in one program; their
            # lanes arrive side-tagged in one dict — replay l then r, the
            # per-batch dispatch order
            for side in ("l", "r"):
                pre = f"{LIN_SIDE}{side}."
                sub = {
                    LIN + k[len(pre):]: v
                    for k, v in lanes.items()
                    if k.startswith(pre)
                }
                if sub:
                    self._observe(sub, now, side)
            return
        self._observe(lanes, now, tag)

    def _observe(self, lanes: dict, now: int, tag) -> None:
        raise NotImplementedError

    # -- recording ---------------------------------------------------------

    def _record(
        self, kind: int, ts, inputs: list[dict], approx: bool,
        trigger=None,
    ) -> None:
        from siddhi_tpu.core.event import KIND_CURRENT, KIND_EXPIRED

        out_index = self.out_count
        self.out_count += 1
        pub_index = None
        if kind in self.published_kinds:
            pub_index = self.pub_count
            self.pub_count += 1
        n_in = sum(s["n"] for s in inputs)
        self.total_inputs += n_in
        if n_in > self.max_inputs:
            self.max_inputs = n_in
        if approx:
            self.approx_count += 1
        if (
            self.cfg.mode == "sample"
            and out_index % self.cfg.sample_every != 0
        ):
            return
        rec = {
            "out_index": out_index,
            "pub_index": pub_index,
            "ts": int(ts),
            "kind": (
                "CURRENT" if kind == KIND_CURRENT
                else "EXPIRED" if kind == KIND_EXPIRED
                else int(kind)
            ),
            "inputs": inputs,
            "approx": bool(approx),
        }
        if trigger is not None:
            rec["trigger"] = {"stream": trigger[0], "seq": int(trigger[1])}
        with self._lock:
            self.records.append(rec)

    # -- reading -----------------------------------------------------------

    def record_for_out_index(self, k: int) -> Optional[dict]:
        with self._lock:
            for rec in reversed(self.records):
                if rec["out_index"] == k:
                    return rec
        return None

    def record_for_pub_index(self, k: int) -> Optional[dict]:
        with self._lock:
            for rec in reversed(self.records):
                if rec["pub_index"] == k:
                    return rec
        return None

    def last_record(self) -> Optional[dict]:
        with self._lock:
            return self.records[-1] if self.records else None

    def fan_in(self) -> dict:
        n = self.out_count
        return {
            "outputs": n,
            "inputs": self.total_inputs,
            "avg_inputs_per_output": (
                round(self.total_inputs / n, 3) if n else 0.0
            ),
            "max_inputs_per_output": self.max_inputs,
        }

    def describe(self) -> dict:
        d = {
            "kind": self.kind_name,
            "mode": self.cfg.mode,
            "capacity": self.cfg.capacity,
            "recorded": len(self.records),
            "approx_records": self.approx_count,
        }
        if self.desync:
            d["desync"] = True
        d.update(self.fan_in())
        return d


class SingleQueryLineage(QueryLineage):
    """Recorder for plain single-stream queries: stateless filters, sliding
    and batch windows, group-by — an exact host-side membership replay of
    the device window driven by the step's `__lin.*` lanes."""

    kind_name = "single"

    def __init__(
        self, cfg, query_id, published_kinds, *, input_stream: str,
        window=None, grouped: bool = False, aggregated: bool = False,
        order_limited: bool = False,
    ):
        super().__init__(cfg, query_id, published_kinds)
        self.input_stream = input_stream
        self.window = window
        self.is_batch = bool(window is not None and window.is_batch)
        self.sliding = window is not None and not self.is_batch
        self.grouped = grouped
        self.aggregated = aggregated
        # order-by/limit permutes out positions device-side: records become
        # step-granular approximations
        self.order_limited = order_limited
        self.in_seen = 0  # stream seq high-water for this consumer
        self.pending: deque = deque()  # admitted, not yet born in the flow
        self.live: deque = deque()  # current window/bucket members
        self.live_truncated = False

    def _observe(self, lanes: dict, now: int, tag) -> None:
        from siddhi_tpu.core.event import (
            KIND_CURRENT,
            KIND_EXPIRED,
            KIND_RESET,
        )

        in_mask = lanes.get(LIN + "in")
        if in_mask is None:
            return
        in_ts = lanes[LIN + "in_ts"]
        admit = lanes.get(LIN + "admit", in_mask)
        keys = lanes.get(LIN + "key")
        wts = lanes.get(LIN + "wts")
        base = self.in_seen
        self.in_seen += int(in_mask.sum())

        # admitted rows, in batch order, with their stream seqs
        ranks = np.cumsum(in_mask.astype(np.int64)) - in_mask.astype(np.int64)
        for p in np.nonzero(admit & in_mask)[0]:
            self.pending.append(_Entry(
                base + int(ranks[p]),
                int(in_ts[p]),
                int(wts[p]) if wts is not None else None,
                keys[p].item() if keys is not None else None,
            ))

        w_valid = lanes[LIN + "w_valid"]
        w_kind = lanes[LIN + "w_kind"]
        w_ts = lanes[LIN + "w_ts"]
        out_valid = lanes[LIN + "out_valid"]
        out_kind = lanes[LIN + "out_kind"]
        gkey = lanes.get(LIN + "gkey")
        bound = self.cfg.capacity

        step_approx = self.order_limited
        for p in np.nonzero(w_valid | out_valid)[0]:
            p = int(p)
            k = int(w_kind[p])
            e = None
            if w_valid[p]:
                if k == KIND_RESET:
                    if self.is_batch:
                        self.live.clear()
                        self.live_truncated = False
                    continue
                if k == KIND_CURRENT:
                    if self.pending:
                        e = self.pending.popleft()
                    else:
                        self.desync = True
                        step_approx = True
                    if e is not None:
                        self.live.append(e)
                        if len(self.live) > bound:
                            self.live.popleft()
                            self.live_truncated = True
                elif k == KIND_EXPIRED and self.sliding and self.live:
                    # sliding evictions are always oldest-first (the seq
                    # lane orders the candidate sort; capacity eviction
                    # rides the same path)
                    self.live.popleft()
            if not out_valid[p]:
                continue
            ok = int(out_kind[p])
            approx = step_approx
            trigger = None
            if e is not None:
                trigger = (self.input_stream, e.seq)
            if self.window is None and not self.aggregated and not self.grouped:
                # stateless: the single admitted row is the provenance
                seqs = [e.seq] if e is not None else []
                approx = approx or e is None
            else:
                members = self.live
                if self.grouped and gkey is not None:
                    kv = gkey[p].item()
                    seqs = [m.seq for m in members if m.key == kv]
                else:
                    seqs = [m.seq for m in members]
                approx = approx or self.live_truncated
            self._record(
                ok, w_ts[p] if w_valid[p] else now,
                [_seqset(self.input_stream, seqs,
                         truncated=self.live_truncated)],
                approx, trigger=trigger,
            )
        if self.sliding or self.window is None:
            # sliding/stateless semantics: every admitted row is born in
            # the same step; leftovers mean the replay desynchronized
            # (e.g. emission-buffer overflow) — absorb them so counts
            # stay aligned, and flag it
            while self.pending:
                self.desync = True
                self.live.append(self.pending.popleft())
                if len(self.live) > bound:
                    self.live.popleft()
                    self.live_truncated = True


class JoinQueryLineage(QueryLineage):
    """Recorder for two-sided joins: per matched output row the (left seq,
    right seq) pair, via the probe-row index and the partner ring's device
    seq lane surfaced by `_assemble`."""

    kind_name = "join"

    def __init__(
        self, cfg, query_id, published_kinds, *, left_stream: str,
        right_stream: str, batch_capacity: int = 0,
    ):
        super().__init__(cfg, query_id, published_kinds)
        self.streams = {"l": left_stream, "r": right_stream}
        self.in_seen = {"l": 0, "r": 0}
        # per-side shadow of the window ring keyed by the DEVICE's window
        # admission seq (the SlidingWindow `seq` lane): win seq k is the
        # k-th filter-passing row this side admitted, in arrival order
        self.win: dict[str, dict[int, _Entry]] = {"l": {}, "r": {}}
        self.win_count = {"l": 0, "r": 0}

    def _observe(self, lanes: dict, now: int, tag) -> None:
        side = tag if tag in ("l", "r") else "l"
        other = "r" if side == "l" else "l"
        in_mask = lanes.get(LIN + "in")
        if in_mask is None:
            return
        in_ts = lanes[LIN + "in_ts"]
        base = self.in_seen[side]
        self.in_seen[side] += int(in_mask.sum())
        ranks = (
            np.cumsum(in_mask.astype(np.int64)) - in_mask.astype(np.int64)
        )

        admit = lanes.get(LIN + "admit")
        if admit is not None:
            shadow = self.win[side]
            for p in np.nonzero(admit & in_mask)[0]:
                k = self.win_count[side]
                self.win_count[side] = k + 1
                shadow[k] = _Entry(base + int(ranks[p]), int(in_ts[p]))
                old = k - self.cfg.capacity
                if old in shadow:
                    del shadow[old]

        out_valid = lanes.get(LIN + "out_valid")
        if out_valid is None:
            return
        out_kind = lanes[LIN + "out_kind"]
        out_ts = lanes[LIN + "out_ts"]
        pi = lanes[LIN + "j_pi"]
        pseq = lanes[LIN + "j_pseq"]
        for p in np.nonzero(out_valid)[0]:
            p = int(p)
            approx = False
            probe = int(pi[p])
            my_seq = None
            if 0 <= probe < in_mask.shape[0] and in_mask[probe]:
                my_seq = base + int(ranks[probe])
            else:
                approx = True  # expired-probe row: not an input position
            partner = self.win[other].get(int(pseq[p]))
            inputs = []
            trigger = None
            mine: dict[str, list] = {}
            if my_seq is not None:
                mine.setdefault(self.streams[side], []).append(my_seq)
                trigger = (self.streams[side], my_seq)
            if partner is not None:
                mine.setdefault(self.streams[other], []).append(partner.seq)
            elif int(pseq[p]) >= 0:
                approx = True  # partner evicted from the bounded shadow
            elif int(pseq[p]) == -2:
                # a real matched partner whose window tracks no admission
                # order (batch window / table / named window): flagged,
                # never guessed — -1 stays "outer join, no partner"
                approx = True
            for sid, seqs in mine.items():
                inputs.append(_seqset(sid, seqs))
            self._record(
                int(out_kind[p]), out_ts[p], inputs, approx, trigger=trigger
            )


class PatternQueryLineage(QueryLineage):
    """Recorder for pattern/sequence NFAs: the per-ref capture-lane
    timestamps the emission buffer already carries, resolved back to seq
    ids through a bounded per-stream (seq, ts) shadow."""

    kind_name = "pattern"

    def __init__(
        self, cfg, query_id, published_kinds, *, refs: list[tuple[str, str]],
    ):
        super().__init__(cfg, query_id, published_kinds)
        # [(ref name, stream id)] in linearized ref order
        self.refs = list(refs)
        self.in_seen: dict[str, int] = {}
        self.shadow: dict[str, deque] = {}

    def _observe(self, lanes: dict, now: int, tag) -> None:
        stream_id = tag
        in_mask = lanes.get(LIN + "in")
        if in_mask is None:
            return
        if stream_id is not None and int(in_mask.sum()):
            in_ts = lanes[LIN + "in_ts"]
            base = self.in_seen.get(stream_id, 0)
            sh = self.shadow.get(stream_id)
            if sh is None:
                sh = self.shadow[stream_id] = deque(
                    maxlen=self.cfg.capacity
                )
            for p in np.nonzero(in_mask)[0]:
                sh.append((base, int(in_ts[p])))
                base += 1
            self.in_seen[stream_id] = base

        out_valid = lanes.get(LIN + "out_valid")
        if out_valid is None:
            return
        out_kind = lanes[LIN + "out_kind"]
        out_ts = lanes[LIN + "out_ts"]
        for p in np.nonzero(out_valid)[0]:
            p = int(p)
            per_stream: dict[str, list] = {}
            approx = False
            for i, (_ref, sid) in enumerate(self.refs):
                n_lane = lanes.get(f"{LIN}p_n{i}")
                ts_lane = lanes.get(f"{LIN}p_ts{i}")
                if n_lane is None or ts_lane is None:
                    continue
                n = int(n_lane[p])
                sh = self.shadow.get(sid, ())
                for c in range(min(n, ts_lane.shape[1])):
                    t = int(ts_lane[p, c])
                    seq = None
                    matches = 0
                    for s, sts in reversed(sh):
                        if sts == t:
                            if seq is None:
                                seq = s
                            matches += 1
                            if matches > 1:
                                break
                    if seq is None:
                        approx = True
                    else:
                        per_stream.setdefault(sid, []).append(seq)
                        if matches > 1:
                            # duplicate timestamps: the capture lane only
                            # carries ts, so the attribution is ambiguous
                            # — flagged, never guessed
                            approx = True
            inputs = [
                _seqset(sid, seqs) for sid, seqs in per_stream.items()
            ]
            self._record(int(out_kind[p]), out_ts[p], inputs, approx)


class AggregationLineage:
    """Per-bucket provenance for an incremental aggregation: contributing
    seq range + count per (finest-duration) time bucket, bounded to the
    last `capacity` buckets. Host-side only — aggregations always ride the
    per-batch path."""

    kind_name = "aggregation"

    def __init__(self, cfg: LineageConfig, agg_id: str, input_stream: str,
                 duration):
        self.cfg = cfg
        self.agg_id = agg_id
        self.input_stream = input_stream
        self.duration = duration  # the finest Duration bucketing events
        self.in_seen = 0
        self.buckets: dict = {}  # bucket_ts -> [lo, hi, count]
        self._order: deque = deque()
        self._lock = threading.Lock()

    def observe_batch(self, batch, ts_col: Optional[np.ndarray]) -> None:
        from siddhi_tpu.core.event import KIND_CURRENT

        valid = np.asarray(batch.valid)
        kind = np.asarray(batch.kind)
        mask = valid & (kind == KIND_CURRENT)
        n = int(mask.sum())
        if n == 0:
            return
        ts = (
            ts_col if ts_col is not None else np.asarray(batch.ts)
        )[np.nonzero(mask)[0]]
        base = self.in_seen
        self.in_seen += n
        from siddhi_tpu.core.aggregation import align_bucket

        bts = np.asarray(align_bucket(ts.astype(np.int64), self.duration))
        with self._lock:
            for i, b in enumerate(bts):
                b = int(b)
                ent = self.buckets.get(b)
                seq = base + i
                if ent is None:
                    self.buckets[b] = [seq, seq, 1]
                    self._order.append(b)
                    while len(self._order) > self.cfg.capacity:
                        self.buckets.pop(self._order.popleft(), None)
                else:
                    ent[0] = min(ent[0], seq)
                    ent[1] = max(ent[1], seq)
                    ent[2] += 1

    def describe(self) -> dict:
        with self._lock:
            return {
                "kind": self.kind_name,
                "stream": self.input_stream,
                "duration": getattr(self.duration, "name", str(self.duration)),
                "events": self.in_seen,
                "buckets": {
                    str(b): {
                        "seq_lo": e[0], "seq_hi": e[1], "count": e[2],
                    }
                    for b, e in self.buckets.items()
                },
            }


# ---------------------------------------------------------------------------
# the per-app ledger: resolution + reporting
# ---------------------------------------------------------------------------


class LineageLedger:
    """App-level lineage surface: owns the config, walks records backward
    through insert-into chains, and renders the /lineage payloads."""

    def __init__(self, runtime, cfg: LineageConfig):
        self.runtime = runtime
        self.cfg = cfg

    # -- wiring views ------------------------------------------------------

    def recorders(self) -> dict:
        out = {}
        for qid, qr in list(self.runtime.queries.items()):
            lin = getattr(qr, "lineage", None)
            if lin is not None:
                out[qid] = lin
        return out

    def agg_recorders(self) -> dict:
        out = {}
        for aid, ar in getattr(self.runtime, "aggregations", {}).items():
            lin = getattr(ar, "lineage", None)
            if lin is not None:
                out[aid] = lin
        return out

    def producers(self, stream_id: str) -> list[str]:
        """Queries with a lineage recorder inserting into `stream_id`."""
        from siddhi_tpu.query_api.execution import InsertIntoStream

        out = []
        for qid, qr in list(self.runtime.queries.items()):
            if getattr(qr, "lineage", None) is None:
                continue
            o = qr.query.output_stream
            if isinstance(o, InsertIntoStream) and o.target == stream_id:
                out.append(qid)
        return out

    def arena(self, stream_id: str) -> Optional[LineageArena]:
        j = self.runtime.junctions.get(stream_id)
        return getattr(j, "lineage", None) if j is not None else None

    def _sole_producer(self, stream_id: str, recs: dict):
        """(qid, producers) when every stamped event on `stream_id` is
        attributable to exactly one recorded producer query — the junction
        seq k is then that query's k-th published record. An external
        input-handler writer (or any unrecorded publisher) interleaves
        seqs the producer's pub counter knows nothing about, so the walk
        is declined unless the arena's stamp count matches the producer's
        publish count exactly."""
        prods = self.producers(stream_id)
        if len(prods) != 1:
            return None, prods
        lin = recs.get(prods[0])
        arena = self.arena(stream_id)
        if (
            lin is None
            or arena is None
            or arena.next_seq != lin.pub_count
        ):
            return None, prods
        return prods[0], prods

    # -- resolution --------------------------------------------------------

    def resolve(self, target: str, index: Optional[int] = None,
                depth: int = 6) -> dict:
        """Explain output `index` of `target` (a query id or a stream id)
        back to the exact input events. Stream indices are the junction's
        lineage seq ids (valid CURRENT events in publish order)."""
        recs = self.recorders()
        if target in recs:
            rec = (
                recs[target].record_for_out_index(index)
                if index is not None
                else recs[target].last_record()
            )
            if rec is None:
                return {
                    "query": target, "out_index": index,
                    "error": "no record (evicted, sampled out, or not yet "
                             "emitted)",
                }
            return self._resolve_record(target, rec, depth, recs)
        if target in self.runtime.junctions:
            return self._resolve_stream(target, index, depth, recs)
        raise KeyError(
            f"'{target}' is neither a lineage-recorded query nor a stream"
        )

    def _resolve_stream(self, stream_id: str, index: Optional[int],
                        depth: int, recs: Optional[dict] = None) -> dict:
        arena = self.arena(stream_id)
        if index is None:
            if arena is None or arena.next_seq == 0:
                return {"stream": stream_id, "error": "no events stamped"}
            index = arena.next_seq - 1
        node: dict = {"stream": stream_id, "seq": int(index)}
        if arena is not None:
            ev = arena.events_for_seqs([index]).get(int(index))
            if ev is not None:
                node["ts"], node["event"] = ev[0], list(ev[1])
            else:
                node["event"] = None
                node["evicted"] = index < arena.next_seq
        if recs is None:
            recs = self.recorders()
        sole, prods = self._sole_producer(stream_id, recs)
        if sole is not None and depth > 0:
            rec = recs[sole].record_for_pub_index(int(index))
            if rec is not None:
                node["via"] = self._resolve_record(sole, rec, depth - 1, recs)
            else:
                node["via"] = {
                    "query": sole,
                    "error": "record evicted or sampled out",
                }
        elif prods:
            # multi-writer stream: the arena's per-publish producer log
            # (note_producer) resolves WHICH recorded query stamped this
            # seq — walk that producer's record. Unlogged seqs (external
            # input handler interleaved, or the log entry evicted) fall
            # back to listing the candidates.
            hit = (
                arena.producer_for_seq(int(index))
                if arena is not None
                else None
            )
            if hit is not None and hit[0] in recs and depth > 0:
                qid, pub_idx = hit
                node["producer"] = qid
                rec = recs[qid].record_for_pub_index(pub_idx)
                if rec is not None:
                    node["via"] = self._resolve_record(
                        qid, rec, depth - 1, recs
                    )
                else:
                    node["via"] = {
                        "query": qid,
                        "error": "record evicted or sampled out",
                    }
            else:
                node["producers"] = prods
                node["mixed"] = True
        return node

    def _resolve_record(
        self, qid: str, rec: dict, depth: int, recs: Optional[dict] = None
    ) -> dict:
        node = {
            "query": qid,
            "out_index": rec["out_index"],
            "ts": rec["ts"],
            "kind": rec["kind"],
            "approx": rec["approx"],
            "inputs": [],
        }
        if "trigger" in rec:
            node["trigger"] = rec["trigger"]
        for ss in rec["inputs"]:
            sid = ss["stream"]
            entry: dict = {
                "stream": sid,
                "ranges": ss["ranges"],
                "n": ss["n"],
            }
            if ss.get("truncated"):
                entry["truncated"] = True
            seqs = _expand(ss["ranges"])
            arena = self.arena(sid)
            if arena is not None and seqs:
                evs = arena.events_for_seqs(seqs)
                entry["events"] = [
                    {
                        "seq": s,
                        **(
                            {"ts": evs[s][0], "event": list(evs[s][1])}
                            if evs[s] is not None
                            else {"event": None}
                        ),
                    }
                    for s in seqs
                ]
            if depth > 0:
                if recs is None:
                    recs = self.recorders()
                sole, _prods = self._sole_producer(sid, recs)
                if sole is not None:
                    ups = []
                    for s in seqs[:8]:  # bound the recursive fan-out
                        up = recs[sole].record_for_pub_index(s)
                        if up is not None:
                            ups.append(
                                self._resolve_record(sole, up, depth - 1, recs)
                            )
                    if ups:
                        entry["via"] = ups
                else:
                    # multi-producer upstream: resolve each contributing
                    # seq to ITS producer via the arena's publish log
                    ups = []
                    for s in seqs[:8]:
                        hit = (
                            arena.producer_for_seq(s)
                            if arena is not None
                            else None
                        )
                        if hit is None or hit[0] not in recs:
                            continue
                        up = recs[hit[0]].record_for_pub_index(hit[1])
                        if up is not None:
                            ups.append(
                                self._resolve_record(
                                    hit[0], up, depth - 1, recs
                                )
                            )
                    if ups:
                        entry["via"] = ups
            node["inputs"].append(entry)
        return node

    # -- reporting ---------------------------------------------------------

    def report(self, resolve_recent: int = 1) -> dict:
        streams = {}
        for sid, j in list(self.runtime.junctions.items()):
            ar = getattr(j, "lineage", None)
            if ar is not None:
                streams[sid] = ar.describe_state()
        queries = {}
        recent = {}
        recs = self.recorders()
        for qid, lin in recs.items():
            queries[qid] = lin.describe()
            if resolve_recent:
                chains = []
                with lin._lock:
                    tail = list(lin.records)[-resolve_recent:]
                for rec in tail:
                    try:
                        chains.append(
                            self._resolve_record(qid, rec, 4, recs)
                        )
                    except Exception:  # resolution must never break a scrape
                        pass
                if chains:
                    recent[qid] = chains
        rep = {
            "config": {
                "capacity": self.cfg.capacity,
                "mode": self.cfg.mode,
            },
            "streams": streams,
            "queries": queries,
            "aggregations": {
                aid: lin.describe()
                for aid, lin in self.agg_recorders().items()
            },
        }
        if recent:
            rep["recent"] = recent
        return rep


def render_lineage_text(reports: dict) -> str:
    """Human-readable /lineage (reports: app name -> ledger.report())."""
    lines: list[str] = []
    for app, rep in reports.items():
        lines.append(f"== app: {app} ==")
        cfg = rep.get("config", {})
        lines.append(
            f"  lineage capacity={cfg.get('capacity')} mode={cfg.get('mode')}"
        )
        for sid, st in sorted(rep.get("streams", {}).items()):
            lines.append(
                f"  stream {sid}: next_seq={st.get('next_seq')} "
                f"ring={st.get('recorded')}/{st.get('size')}"
            )
        for qid, q in sorted(rep.get("queries", {}).items()):
            lines.append(
                f"  query {qid} [{q.get('kind')}]: outputs={q.get('outputs')}"
                f" fan-in avg={q.get('avg_inputs_per_output')}"
                f" max={q.get('max_inputs_per_output')}"
                f" recorded={q.get('recorded')}"
                + (" DESYNC" if q.get("desync") else "")
            )
        for aid, a in sorted(rep.get("aggregations", {}).items()):
            lines.append(
                f"  aggregation {aid}: events={a.get('events')} "
                f"buckets={len(a.get('buckets') or {})}"
            )
        for qid, chains in sorted(rep.get("recent", {}).items()):
            for ch in chains:
                lines.append(f"  last {qid}: {_chain_line(ch)}")
    return "\n".join(lines) + "\n"


def _chain_line(node: dict) -> str:
    parts = [
        f"out#{node.get('out_index')} ts={node.get('ts')} "
        f"{node.get('kind')}"
    ]
    for inp in node.get("inputs", ()):
        rng = ",".join(
            f"{lo}..{hi}" if lo != hi else str(lo)
            for lo, hi in inp.get("ranges", ())
        )
        parts.append(f"<- {inp['stream']}[{rng}] (n={inp['n']})")
    return " ".join(parts)
