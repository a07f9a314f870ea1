"""Event-time robustness: watermarks, bounded-disorder reordering, and
late-event policies (`@app:watermark`).

    @app:watermark(bound='5 sec', idle.timeout='30 sec',
                   late.policy='drop|stream|apply', allowed.lateness='1 min')

The annotation installs three cooperating pieces:

* A bounded columnar REORDER STAGE at every stream's ingress
  (`_WatermarkInputHandler` in app_runtime.py -> `ReorderTracker` here).
  Arrivals buffer up to `bound` of event-time slack; whenever the
  watermark (max event time seen minus `bound`) advances, all buffered
  rows at or below it are released in one stably-sorted columnar send, so
  the fused / pipelined / sharded send paths downstream all see ordered
  input. Rows older than the watermark at arrival are LATE and never reach
  the junction; they are metered and handled by `late.policy`. The
  watermark moves ONCE PER CALL: a call's rows are judged by the watermark
  the calls before left, so how many rows are late depends on how the
  caller cuts its sends. The stage runs on the caller's thread, ahead of
  the inner send (span `siddhi:reorder`, `siddhi:late` inside it; counters
  `offers`, `offered`, `released`, `buffered`, `late_total`, with
  `offered == released + buffered + late_total`).

* A WATERMARK CLOCK. Each source stream tracks its own watermark; the
  app-level watermark is the minimum over non-idle sources (classic
  min-propagation; a source that has been quiet for `idle.timeout` is
  flushed and excluded so it cannot stall the app; quiet is measured from
  the RETURN of the stream's last call, never from its start: a stream is
  not quiet while its call runs, however long a first send compiles;
  `idle.timeout='0'` turns the heartbeat off). The clock drives an
  EventTimeScheduler, so window flushes, pattern within/absent deadlines
  and aggregation bucket closes fire on WATERMARK ADVANCE, not raw
  arrival. Insert-into targets inherit min-over-inputs watermarks
  (`watermark_of`), reported in snapshot_status()/explain().

* LATE-EVENT POLICIES — late events are never silently lost:
    drop    count + lateness histogram, then discard (the meter is the
            contract: `late_total == dropped`).
    stream  divert to the auto-defined `!S` side stream (the @OnError
            STREAM machinery) with `_error='late[<ms> ms]'`.
    apply   best-effort: within `allowed.lateness`, re-open the closed
            aggregation bucket the event belongs to (update duration
            tables in place) and emit the late event on `!S` flagged
            `_error='applied[<ms> ms]'` as the correction signal; beyond
            the allowance it is metered `expired` and emitted flagged
            `_error='expired[<ms> ms]'`.

Validation is ONE rule set (`iter_watermark_annotation_problems`) shared by
the runtime resolver and the analyzer's SA134 diagnostic, the same contract
as SA125-SA133. `SIDDHI_TPU_WATERMARK` overrides the annotation
process-wide (same spec grammar as the annotation, `;`-joined `k=v`; `off`
or `0` force-disables) so the CI disorder-parity leg can arm the reorder
stage without editing apps. With no annotation and no env the runtime
never instantiates any of this — the only cost is one `is None` check at
input-handler creation (the lineage/flight/stats zero-cost contract).
"""

from __future__ import annotations

import dataclasses
import threading
import time as _time
from typing import Callable, Optional

import numpy as np

from siddhi_tpu.observability.profiler import stage

WATERMARK_ENV = "SIDDHI_TPU_WATERMARK"

_POLICIES = ("drop", "stream", "apply")
_OPTIONS = ("bound", "idle.timeout", "late.policy", "allowed.lateness")
DEFAULT_IDLE_TIMEOUT_MS = 30_000
DEFAULT_ALLOWED_LATENESS_MS = 60_000  # when late.policy='apply' and unset


@dataclasses.dataclass(frozen=True)
class WatermarkConfig:
    bound_ms: int
    idle_timeout_ms: int = DEFAULT_IDLE_TIMEOUT_MS
    late_policy: str = "drop"
    allowed_lateness_ms: int = 0


def _parse_time_ms(v) -> int:
    from siddhi_tpu.compiler.siddhi_compiler import SiddhiCompiler

    return SiddhiCompiler.parse_time_constant(str(v))


def _iter_option_problems(pairs):
    """Shared over annotation elements AND the env-override spec so the two
    surfaces can never drift."""
    seen = {}
    for k, v in pairs:
        seen[k] = v
        if k in ("bound", "idle.timeout", "allowed.lateness"):
            try:
                ms = _parse_time_ms(v)
                ok = ms > 0 if k == "bound" else ms >= 0
            except Exception:
                ok = False
            if not ok:
                yield (
                    f"@app:watermark {k} '{v}' must be a "
                    f"{'positive ' if k == 'bound' else ''}time constant "
                    "(e.g. '5 sec')"
                )
        elif k == "late.policy":
            if str(v) not in _POLICIES:
                yield (
                    f"@app:watermark late.policy '{v}' must be one of "
                    f"{'|'.join(_POLICIES)}"
                )
        else:
            yield (
                f"unknown @app:watermark option '{k}' "
                f"(expected {', '.join(_OPTIONS)})"
            )
    if "bound" not in seen:
        yield (
            "@app:watermark needs bound='<time>' — the reorder slack and "
            "watermark lag (e.g. bound='5 sec')"
        )
    if "allowed.lateness" in seen and str(seen.get("late.policy", "drop")) != "apply":
        yield (
            "@app:watermark allowed.lateness only takes effect with "
            "late.policy='apply'"
        )


def _ann_pairs(ann):
    pairs = []
    for k, v in ann.elements:
        if k is None and len(ann.elements) == 1:
            k = "bound"  # @app:watermark('5 sec') shorthand
        pairs.append((k, v))
    return pairs


def iter_watermark_annotation_problems(ann):
    """Yield one message per malformed `@app:watermark` element — THE rule
    set, shared by the runtime resolver (raises on the first) and the
    analyzer's SA134 diagnostics (reports them all), so the two can never
    drift (same contract as SA113/SA114/SA125-SA133)."""
    yield from _iter_option_problems(_ann_pairs(ann))


def parse_watermark_spec(spec: str):
    """Parse a SIDDHI_TPU_WATERMARK override: `;`-joined `k=v` pairs in the
    annotation's option vocabulary, or `off`/`0`/`none` to force-disable.
    Returns 'off', a {option: value} dict, or None for an empty spec.
    Raises ValueError on malformed entries — a parity run with a typo'd
    override must fail loudly, not run watermark-free."""
    s = (spec or "").strip()
    if not s:
        return None
    if s.lower() in ("0", "off", "none"):
        return "off"
    out = {}
    for part in s.split(";"):
        part = part.strip()
        if not part:
            continue
        k, sep, v = part.partition("=")
        if not sep:
            raise ValueError(f"watermark option '{part}' is not k=v")
        out[k.strip()] = v.strip()
    return out


def resolve_watermark_annotation(ann, env: Optional[str] = None):
    """WatermarkConfig from `@app:watermark(...)` plus the
    SIDDHI_TPU_WATERMARK env override (which wins per option; `off`
    disables even an annotated app; a bare env spec with a bound arms an
    unannotated one — the CI disorder-parity leg). None = watermark off.
    Raises SiddhiAppCreationError on malformed options — the runtime
    analog of the analyzer's SA134 diagnostic."""
    import os

    from siddhi_tpu.core.errors import SiddhiAppCreationError

    if env is None:
        env = os.environ.get(WATERMARK_ENV, "")
    try:
        override = parse_watermark_spec(env)
    except ValueError as e:
        raise SiddhiAppCreationError(str(e)) from e
    if override == "off":
        return None
    opts = dict(_ann_pairs(ann)) if ann is not None else {}
    if override:
        opts.update(override)
    if not opts:
        return None
    for problem in _iter_option_problems(list(opts.items())):
        raise SiddhiAppCreationError(problem)
    policy = str(opts.get("late.policy", "drop"))
    allowed = opts.get("allowed.lateness")
    return WatermarkConfig(
        bound_ms=_parse_time_ms(opts["bound"]),
        idle_timeout_ms=(
            _parse_time_ms(opts["idle.timeout"])
            if "idle.timeout" in opts else DEFAULT_IDLE_TIMEOUT_MS
        ),
        late_policy=policy,
        allowed_lateness_ms=(
            _parse_time_ms(allowed) if allowed is not None
            else (DEFAULT_ALLOWED_LATENESS_MS if policy == "apply" else 0)
        ),
    )


# ---------------------------------------------------------------------------
# lateness histogram (log2 buckets; summary shape matches LatencyTracker's)
# ---------------------------------------------------------------------------


class LatenessHistogram:
    """Fixed log2-bucketed histogram over lateness in ms. Quantiles are
    bucket upper bounds — coarse but allocation-free on the late path."""

    _NBUCKETS = 48

    def __init__(self) -> None:
        self._counts = [0] * self._NBUCKETS
        self._sum = 0
        self._count = 0
        self._max = 0
        self._lock = threading.Lock()

    def record(self, ms: int) -> None:
        self.record_many(np.asarray([ms], dtype=np.int64))

    def record_many(self, ms) -> None:
        """A call's late rows at once: the same buckets, sums and quantiles
        as one `record` per row."""
        ms = np.asarray(ms, dtype=np.int64)
        if ms.size == 0:
            return
        # bit_length of max(ms, 0): frexp's exponent, exact below 2**53 and
        # clipped to the last bucket long before
        idx = np.minimum(
            np.frexp(np.maximum(ms, 0).astype(np.float64))[1],
            self._NBUCKETS - 1,
        )
        per_bucket = np.bincount(idx, minlength=self._NBUCKETS).tolist()
        total, mx = int(ms.sum()), int(ms.max())
        with self._lock:
            self._counts = [a + b for a, b in zip(self._counts, per_bucket)]
            self._sum += total
            self._count += int(ms.size)
            if mx > self._max:
                self._max = mx

    def snapshot(self) -> dict:
        with self._lock:
            counts = list(self._counts)
            total, s, mx = self._count, self._sum, self._max
        out = {"count": total, "sum": s, "max": mx}
        for key, q in (("p50", 0.5), ("p95", 0.95), ("p99", 0.99),
                       ("p999", 0.999), ("p9999", 0.9999)):
            if total == 0:
                out[key] = 0
                continue
            target = q * total
            acc = 0
            val = 0
            for i, c in enumerate(counts):
                acc += c
                if acc >= target:
                    val = min((1 << i) - 1, mx)
                    break
            out[key] = val
        return out


# ---------------------------------------------------------------------------
# the bounded reorder stage
# ---------------------------------------------------------------------------


class ReorderTracker:
    """Per-source-stream watermark + bounded columnar reorder buffer.

    `offer()` takes one columnar chunk, splits off rows already behind the
    watermark (late — handed to `on_late`), advances the watermark to
    `max event time - bound`, and releases everything at or below it in a
    single stably-sorted columnar `deliver()` call. The stable sort makes
    the released sequence a pure function of the row multiset and the
    watermark trajectory — the disorder-parity gate's foundation.

    What is held between calls is one run of rows sorted by event time
    (`_held_ts`, `_held_cols`: arrays of their own, a few seconds of
    stream). A call's columns are read once: the sort runs on the event
    times alone, late rows never enter it, and each column is gathered
    through the order straight into what is released and what stays."""

    def __init__(
        self,
        stream_id: str,
        bound_ms: int,
        deliver: Callable,          # (ts: np.int64[n], cols: {name: np[n]})
        on_late: Callable,          # (ts, cols, lateness: np.int64[n])
    ) -> None:
        self.stream = stream_id
        self.bound = int(bound_ms)
        self._deliver = deliver
        self._on_late = on_late
        self._lock = threading.RLock()
        self._held_ts = np.empty(0, dtype=np.int64)
        self._held_cols: dict = {}
        self.max_ts: Optional[int] = None
        self.wm: Optional[int] = None
        self.buffered = 0
        self.peak_buffered = 0
        self.released = 0
        self.late_total = 0
        self.offers = 0             # calls that brought rows
        self.offered = 0            # rows they brought
        self.idle = False
        self.last_event_monotonic: Optional[float] = None

    def offer(self, timestamps, cols) -> None:
        ts = np.asarray(timestamps, dtype=np.int64)
        if ts.size == 0:
            return
        cols = {k: np.asarray(v) for k, v in cols.items()}
        with self._lock:
            self.idle = False
            self.last_event_monotonic = _time.monotonic()
            try:
                with stage(
                    "reorder", stream=self.stream, rows=int(ts.size),
                ) as span:
                    late_before = self.late_total
                    out = self._take(ts, cols)
                    span.set(
                        released=0 if out is None else int(out[0].size),
                        held=self.buffered,
                        late=self.late_total - late_before,
                    )
                if out is not None:
                    self._deliver(*out)
            finally:
                # the stream is not quiet while its call runs: the idle
                # heartbeat, which waited for this lock, measures from here
                self.last_event_monotonic = _time.monotonic()

    def _take(self, ts, cols):
        """Count a call's rows, split off the late ones, hold the rest,
        advance the watermark; returns what it lets through."""
        self.offers += 1
        self.offered += int(ts.size)
        fresh = None  # the call's rows that are not late (None: all)
        if self.wm is not None:
            late = ts < self.wm
            n_late = int(np.count_nonzero(late))
            if n_late:
                where = np.flatnonzero(late)
                late_ts = ts[where]
                self.late_total += n_late
                self._on_late(
                    late_ts, {k: v[where] for k, v in cols.items()},
                    self.wm - late_ts,
                )
                if n_late == ts.size:
                    return None
                fresh = np.flatnonzero(~late)
                ts = ts[fresh]
        self.buffered += int(ts.size)
        if self.buffered > self.peak_buffered:
            self.peak_buffered = self.buffered
        m = int(ts.max())
        if self.max_ts is None or m > self.max_ts:
            self.max_ts = m
        new_wm = self.max_ts - self.bound
        if self.wm is None or new_wm > self.wm:
            self.wm = new_wm
        return self._cut(ts, cols, fresh)

    def _cut(self, ts, cols, fresh):
        """Join a call's rows (`cols[fresh]`, event times `ts`) to what is
        held, in event-time order, rows of one time in arrival order (the
        held ones came first), and cut at the watermark: the head is
        returned, the rest is held."""
        n_held = int(self._held_ts.size)
        both = np.concatenate([self._held_ts, ts]) if n_held else ts
        order = np.argsort(both, kind="stable")
        both = both[order]
        n = int(np.searchsorted(both, self.wm, side="right"))
        # where each sorted row lies: a held row at `order`, a row of the
        # call at `order - n_held` of its non-late rows
        from_held = np.flatnonzero(order < n_held)
        src = order - n_held
        src[from_held] = 0
        if fresh is not None:
            src = fresh[src]
        held_src = order[from_held]

        def gather(v, held_v):
            out = np.take(v, src)  # faster than v[src] at a call's size
            if from_held.size:
                out[from_held] = held_v[held_src]
            return out

        sorted_cols = {
            k: gather(v, self._held_cols.get(k)) for k, v in cols.items()
        }
        if n == both.size:
            self._held_ts = both[:0]
            self._held_cols = {}
        else:
            # copies: a view would keep the whole call alive until the next
            self._held_ts = both[n:].copy()
            self._held_cols = {k: v[n:].copy() for k, v in sorted_cols.items()}
        if n == 0:
            return None
        self.buffered -= n
        self.released += n
        return both[:n], {k: v[:n] for k, v in sorted_cols.items()}

    def flush(self) -> None:
        """Idle timeout / drain: advance the watermark to the newest event
        seen and release the whole buffer; the tracker goes idle (excluded
        from the app-level min) until the next arrival."""
        with self._lock:
            if self.max_ts is not None and (
                self.wm is None or self.max_ts > self.wm
            ):
                self.wm = self.max_ts
            n = int(self._held_ts.size)
            if n:
                ts, cols = self._held_ts, self._held_cols
                self._held_ts, self._held_cols = ts[:0], {}
                self.buffered -= n
                self.released += n
                self._deliver(ts, cols)
            self.idle = True

    def describe(self) -> dict:
        with self._lock:
            return {
                "watermark_ms": self.wm,
                "max_event_ms": self.max_ts,
                "lag_ms": (
                    self.max_ts - self.wm
                    if self.wm is not None and self.max_ts is not None
                    else None
                ),
                "buffered": self.buffered,
                "peak_buffered": self.peak_buffered,
                "released": self.released,
                "late_total": self.late_total,
                "offers": self.offers,
                "offered": self.offered,
                "idle": self.idle,
            }


# ---------------------------------------------------------------------------
# app-level runtime: min-propagation, idle heartbeat, late policies
# ---------------------------------------------------------------------------


def _query_input_ids(query) -> list:
    """Source stream ids of a query's input (single / join / state)."""
    from siddhi_tpu.query_api.execution import (
        JoinInputStream,
        SingleInputStream,
        StateInputStream,
        iter_state_streams,
    )

    s = query.input_stream
    if isinstance(s, SingleInputStream):
        return [s.stream_id]
    if isinstance(s, JoinInputStream):
        return [s.left.stream_id, s.right.stream_id]
    if isinstance(s, StateInputStream):
        return [a.stream_id for a in iter_state_streams(s.state)]
    return []


class WatermarkRuntime:
    """Owns the per-stream `ReorderTracker`s, the watermark clock, the idle
    heartbeat, and the late-event policies for one app runtime."""

    def __init__(self, runtime, cfg: WatermarkConfig, clock) -> None:
        self.runtime = runtime
        self.cfg = cfg
        self.clock = clock          # EventTimeClock driven to the app watermark
        self.trackers: dict = {}
        self.meters: dict = {}      # stream -> policy counters
        self.lateness: dict = {}    # stream -> LatenessHistogram
        self._lock = threading.Lock()
        self._edges = None          # insert-into topology (lazy)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- ingress wiring ------------------------------------------------------

    def tracker(self, stream_id: str, deliver: Callable) -> ReorderTracker:
        with self._lock:
            tr = self.trackers.get(stream_id)
            if tr is None:
                tr = ReorderTracker(
                    stream_id, self.cfg.bound_ms, deliver,
                    on_late=lambda ts, cols, lat, _s=stream_id: (
                        self._handle_late(_s, ts, cols, lat)
                    ),
                )
                self.trackers[stream_id] = tr
                self.meters[stream_id] = {
                    "dropped": 0, "streamed": 0, "applied": 0, "expired": 0,
                }
                self.lateness[stream_id] = LatenessHistogram()
            return tr

    def advance_clock(self) -> None:
        """Drive the app watermark clock to min over non-idle source
        watermarks (all idle -> max, so a quiet app catches up fully)."""
        active = [
            tr.wm for tr in self.trackers.values()
            if tr.wm is not None and not tr.idle
        ]
        if active:
            self.clock.advance(min(active))
            return
        all_wm = [tr.wm for tr in self.trackers.values() if tr.wm is not None]
        if all_wm:
            self.clock.advance(max(all_wm))

    # -- late policies -------------------------------------------------------

    def _handle_late(self, stream_id, ts, cols, lateness) -> None:
        with stage("late", stream=stream_id, rows=int(len(ts))):
            self._apply_late_policy(stream_id, ts, cols, lateness)

    def _apply_late_policy(self, stream_id, ts, cols, lateness) -> None:
        self.lateness[stream_id].record_many(lateness)
        meters = self.meters[stream_id]
        policy = self.cfg.late_policy
        if policy == "drop":
            meters["dropped"] += int(len(ts))
            return
        if policy == "stream":
            meters["streamed"] += int(len(ts))
            self._divert(stream_id, ts, cols, lateness, "late")
            return
        # apply: re-open closed aggregation buckets within allowed.lateness
        allowed = self.cfg.allowed_lateness_ms
        aggs = self.runtime._aggregations_for_stream(stream_id)
        for i in range(len(ts)):
            lat = int(lateness[i])
            one = (ts[i : i + 1], {k: v[i : i + 1] for k, v in cols.items()})
            if lat > allowed or not aggs:
                meters["expired"] += 1
                self._divert(stream_id, one[0], one[1], [lat], "expired")
                continue
            row = {k: v[i] for k, v in cols.items()}
            for agg in aggs:
                agg.apply_late(int(ts[i]), row)
            meters["applied"] += 1
            self._divert(stream_id, one[0], one[1], [lat], "applied")

    def _divert(self, stream_id, ts, cols, lateness, tag: str) -> None:
        """Publish late rows on the stream's auto-defined `!S` side stream
        flagged `_error='<tag>[<ms> ms]'` (the @OnError STREAM contract)."""
        fj = self.runtime._fault_junction_for(stream_id)
        if fj is None:  # pragma: no cover - schemas are pre-defined
            return
        names = [a for a in fj.schema.attr_names if a != "_error"]
        rows = []
        for i in range(len(ts)):
            vals = tuple(
                v.item() if hasattr(cols[k][i], "item") else cols[k][i]
                for k, v in ((k, cols[k]) for k in names)
            )
            rows.append(vals + (f"{tag}[{int(lateness[i])} ms]",))
        now = self.clock.now()
        fj.send_rows([int(t) for t in ts], rows, now=now)

    # -- idle heartbeat / drain ---------------------------------------------

    def start(self) -> None:
        idle_ms = self.cfg.idle_timeout_ms
        if not idle_ms or self._thread is not None:
            return
        self._stop.clear()
        period = max(idle_ms / 4000.0, 0.05)

        def run():
            while not self._stop.wait(period):
                flushed = False
                for tr in list(self.trackers.values()):
                    with tr._lock:
                        quiet = (
                            not tr.idle
                            and tr.last_event_monotonic is not None
                            and (_time.monotonic() - tr.last_event_monotonic)
                            * 1000.0 >= idle_ms
                        )
                    if quiet:
                        tr.flush()
                        flushed = True
                if flushed:
                    self.advance_clock()

        self._thread = threading.Thread(
            target=run, daemon=True, name="siddhi-watermark-idle",
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        t = self._thread
        if t is not None and t is not threading.current_thread():
            t.join(timeout=2.0)
        self._thread = None

    def drain(self) -> None:
        """Release every buffered row and catch the clock up to the newest
        event seen — shutdown's tail-delivery guarantee."""
        for tr in list(self.trackers.values()):
            tr.flush()
        self.advance_clock()

    # -- propagation + introspection ----------------------------------------

    def _topology(self) -> dict:
        if self._edges is None:
            edges: dict = {}
            for qr in self.runtime.queries.values():
                target = getattr(qr.query.output_stream, "target", None)
                if not target:
                    continue
                edges.setdefault(target, set()).update(
                    _query_input_ids(qr.query)
                )
            self._edges = edges
        return self._edges

    def watermark_of(self, stream_id: str, _seen=None) -> Optional[int]:
        """Stream watermark with min-propagation through insert-into
        chains: a source stream reports its tracker's watermark; a derived
        stream the min over its contributing inputs."""
        tr = self.trackers.get(stream_id)
        if tr is not None:
            return tr.wm
        if _seen is None:
            _seen = set()
        if stream_id in _seen:
            return None
        _seen.add(stream_id)
        inputs = self._topology().get(stream_id)
        if not inputs:
            return None
        vals = [
            v for v in (self.watermark_of(i, _seen) for i in sorted(inputs))
            if v is not None
        ]
        return min(vals) if vals else None

    def describe_state(self) -> dict:
        streams = {}
        for sid in sorted(self.trackers):
            d = self.trackers[sid].describe()
            d.update(self.meters[sid])
            d["lateness_ms"] = self.lateness[sid].snapshot()
            streams[sid] = d
        derived = {}
        for target in sorted(self._topology()):
            if target in self.trackers or target.startswith("!"):
                continue
            wm = self.watermark_of(target)
            if wm is not None:
                derived[target] = {"watermark_ms": wm}
        return {
            "config": {
                "bound_ms": self.cfg.bound_ms,
                "idle_timeout_ms": self.cfg.idle_timeout_ms,
                "late_policy": self.cfg.late_policy,
                "allowed_lateness_ms": self.cfg.allowed_lateness_ms,
            },
            "clock_ms": self.clock.now(),
            "streams": streams,
            "derived": derived,
        }
