"""Stream junctions and input handlers — host-side event routing.

Reference: stream/StreamJunction.java:58-404 (per-stream pub/sub fan-out) and
stream/input/InputManager.java / InputHandler.java. The device does all per-event
math; the junction packs host events into fixed-capacity columnar micro-batches
and fans them out to subscriber step functions. Synchronous dispatch mirrors the
reference's default pass-through mode; @async batching rides the same path via
send_batch.
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Any, Callable, Sequence

import numpy as np

from siddhi_tpu.core.event import EventBatch, StreamSchema
from siddhi_tpu.core.types import InternTable
from siddhi_tpu.observability.profiler import stage
from siddhi_tpu.testing import faults as _faults

# subscriber: fn(batch: EventBatch, now_ms: int) -> None
Subscriber = Callable[[EventBatch, int], None]


class StreamJunction:
    def __init__(
        self,
        schema: StreamSchema,
        interner: InternTable,
        batch_size: int = 64,
    ):
        self.schema = schema
        self.interner = interner
        self.batch_size = batch_size
        self.subscribers: list[Subscriber] = []
        self.subscriber_names: list[str] = []
        self.stream_callbacks: list[Callable] = []
        self.stream_callback_names: list[str] = []
        # fused-ingest wiring (core/ingest.py): subscribers that also register
        # a FuseEndpoint here can be run K-batches-per-dispatch by send_columns
        self.fuse_candidates: list = []
        self.fused_ingest = None
        # RLock: a query may legally insert into its own input stream
        # (reference allows self-feeding junctions); recursion stays on-thread
        self.lock = threading.RLock()
        # the owning app's process RLock (set by app_runtime._junction):
        # held across the whole per-batch fan-out so the snapshot barrier
        # (SnapshotService.full_snapshot) can never observe a torn
        # cross-query state mid-batch; None for junctions outside an app
        self.process_lock = None
        self.on_publish_stats: Callable[[int], None] | None = None
        self.on_error_stats: Callable[[int], None] | None = None
        # per-subscriber error attribution: factory(subscriber_name) -> add fn
        # for the `stream.<id>.subscriber.<name>` counter; adders cached here
        self.error_stats_factory: Callable[[str], Callable[[int], None]] | None = None
        self._sub_error_stats: dict[str, Callable[[int], None]] = {}
        # sampled event tracing (observability.tracing.Tracer); spans are
        # recorded per publish + per named subscriber when a trace is active
        self.tracer = None
        # device-budget trackers (JunctionDeviceStats) used by the fused
        # ingest path: step dispatch time, h2d bytes/chunks, sync stalls
        self.device_stats = None
        # pipelined-ingest stage budget (PipelineStats): encode/h2d/dispatch/
        # drain histograms + the pipeline.occupancy overlap gauge
        self.pipeline_stats = None
        # continuous profiler (observability/profiler.py): per-chunk stage
        # waterfalls + compile telemetry for the fused chunk program; both
        # None (one attribute check) when statistics are off
        self.profiler = None
        self.compile_telemetry = None
        # numbers the send_columns calls: the `send` id of their stage
        # spans (observability/profiler.py)
        self.send_ids = itertools.count(1)
        # flight recorder (observability.flight.FlightRecorder): bounded
        # ring of the last N events through this junction, opt-in via
        # @flightRecorder(size='N') / SIDDHI_TPU_FLIGHT=N; None = one
        # attribute check on the hot path
        self.flight = None
        # lineage arena (observability.lineage.LineageArena): stamps every
        # valid CURRENT event with a monotonically increasing seq id and
        # keeps the last N decodable, opt-in via @app:lineage; None = one
        # attribute check on the hot path (same contract as flight)
        self.lineage = None
        # black-box incident ring (observability.blackbox.BlackboxRing):
        # seq-stamped ring of the last events through this junction,
        # opt-in via @app:blackbox; None = one attribute check on the hot
        # path (same contract as flight/lineage). on_incident is the
        # recorder's trigger hook — called with (trigger, detail) on
        # dispatch failures and unguarded crashes.
        self.blackbox = None
        self.on_incident: Callable[[str, str], None] | None = None
        # user hook for subscriber failures (reference: the pluggable
        # Disruptor ExceptionHandler, SiddhiAppRuntime.java:664)
        self.exception_handler: Callable[[Exception], None] | None = None
        # supervisor health signal (core/supervision.AppHealth.mark_fatal):
        # called with (exc, who) on UNGUARDED dispatch failures and worker
        # errors so manager.supervise() can restart the app; None when the
        # app is not supervised (one attribute check)
        self.on_fatal: Callable[[Exception, str], None] | None = None
        # @OnError policy (reference: StreamJunction.handleError + OnErrorAction):
        # None propagates to the sender; 'LOG' logs and drops the failing
        # batch; 'STREAM' redirects it (plus the error) to fault_junction;
        # 'STORE' spills it to the manager's ErrorStore via error_store_fn
        self.fault_policy: str | None = None
        self.fault_junction: "StreamJunction | None" = None
        self.error_store_fn: Callable[[], object] | None = None
        self.app_name: str = ""
        # churn ingress gate (core/churn.IngressGate): when set, input
        # handlers buffer (hold) or forward their sends instead of
        # publishing — the redeploy swap window and the paused replay mode
        # ride this. None = one attribute check on the ingest path.
        self.ingress_gate = None

    def enable_flight(self, size: int) -> None:
        """Attach a flight recorder of the last `size` events. Idempotent
        for an unchanged size: re-arming (e.g. the annotation resolving to
        the same ring the SIDDHI_TPU_FLIGHT env already applied) must not
        allocate a second arena and discard the recorded history."""
        if self.flight is not None and self.flight.size == int(size):
            return
        from siddhi_tpu.observability.flight import FlightRecorder

        self.flight = FlightRecorder(self.schema, self.interner, size)

    def enable_lineage(self, size: int) -> None:
        """Attach a lineage arena stamping + retaining the last `size`
        CURRENT events. Idempotent for an unchanged size (the recorded
        seq counter must survive re-arming)."""
        if self.lineage is not None and self.lineage.size == int(size):
            return
        from siddhi_tpu.observability.lineage import LineageArena

        self.lineage = LineageArena(self.schema, self.interner, size)

    def enable_blackbox(self, size: int, counter) -> None:
        """Attach a black-box incident ring of the last `size` events,
        seq-stamped from the app-wide arrival `counter`. Idempotent for an
        unchanged size (recorded history must survive re-arming)."""
        if self.blackbox is not None and self.blackbox.size == int(size):
            return
        from siddhi_tpu.observability.blackbox import BlackboxRing

        self.blackbox = BlackboxRing(self.schema, self.interner, size, counter)

    def describe_state(self) -> dict:
        """Cheap live-state snapshot (no device reads): queue depth, wiring,
        async worker health, fused/pipeline engagement, flight ring."""
        d: dict = {
            "queue_depth": self.queued(),
            "subscribers": list(self.subscriber_names),
            "callbacks": len(self.stream_callbacks),
            "batch_size": self.batch_size,
        }
        if self.is_async:
            workers = getattr(self, "_workers", [])
            d["async"] = {
                "workers": len(workers),
                "workers_alive": sum(1 for t in workers if t.is_alive()),
                "native_ring": getattr(self, "_ring", None) is not None,
            }
        if self.fault_policy is not None:
            d["on_error"] = self.fault_policy
        fi = self.fused_ingest
        if fi is not None:
            d["pipeline"] = fi.describe_state()
        if self.flight is not None:
            d["flight"] = self.flight.describe_state()
        if self.lineage is not None:
            d["lineage"] = self.lineage.describe_state()
        if self.blackbox is not None:
            d["blackbox"] = self.blackbox.describe_state()
        return d

    def subscribe(self, fn: Subscriber, name: str | None = None) -> None:
        """`name` labels this subscriber in error attribution and trace spans
        (e.g. 'query.q'); unnamed subscribers get a positional label."""
        self.subscribers.append(fn)
        self.subscriber_names.append(
            name if name else f"subscriber{len(self.subscribers) - 1}"
        )

    def unsubscribe(self, name: str) -> int:
        """Remove every subscriber registered under `name` (hot undeploy,
        core/churn.py). Caller holds the app process lock, so no fan-out
        can be mid-iteration over the lists. Returns how many were
        removed."""
        removed = 0
        with self.lock:
            keep = [
                (fn, n)
                for fn, n in zip(self.subscribers, self.subscriber_names)
                if n != name
            ]
            removed = len(self.subscribers) - len(keep)
            if removed:
                self.subscribers = [fn for fn, _n in keep]
                self.subscriber_names = [n for _fn, n in keep]
        return removed

    def add_stream_callback(self, fn: Callable, name: str | None = None) -> None:
        self.stream_callbacks.append(fn)
        self.stream_callback_names.append(
            name if name else f"callback{len(self.stream_callbacks) - 1}"
        )

    # ---- @async ingress (reference: StreamJunction.java:262-298 Disruptor
    # ring + StreamHandler batching into EventExchangeHolders) --------------

    def enable_async(
        self, buffer_size: int = 1024, workers: int = 1, batch_max: int | None = None
    ) -> None:
        import queue

        # a packed batch can never exceed the junction's device batch shape
        self._batch_max = min(
            int(batch_max) if batch_max else self.batch_size, self.batch_size
        )
        self._async_stop = threading.Event()
        self._workers = []
        self._ring = None
        from siddhi_tpu.core.types import AttrType

        if all(t is not AttrType.OBJECT for _, t in self.schema.attrs):
            # native lock-free ring (C++, the Disruptor analog); values ride
            # as doubles — exact for f32/f64/bool/interned-string ids and for
            # integers up to 2^53
            try:
                from siddhi_tpu.native import NativeIngressRing

                # +1 payload lane carries the per-row `now` clock value
                self._ring = NativeIngressRing(
                    int(buffer_size), len(self.schema.attrs) + 1
                )
            except Exception:
                self._ring = None  # no toolchain: python queue fallback
        if self._ring is None:
            self._queue = queue.Queue(maxsize=int(buffer_size))
        if self._ring is not None:
            workers = 1  # the native ring is single-consumer (MPSC)
        for _ in range(max(1, int(workers))):
            t = threading.Thread(
                target=self._drain_ring if self._ring is not None else self._drain,
                daemon=True,
            )
            t.start()
            self._workers.append(t)
        self.is_async = True

    def _encode_row(self, row) -> list[float]:
        from siddhi_tpu.core.types import AttrType, null_value

        out = []
        for v, (_n, t) in zip(row, self.schema.attrs):
            if t in (AttrType.STRING, AttrType.OBJECT):
                out.append(float(self.interner.intern(v)))
            elif v is None:
                nv = null_value(t)
                out.append(float(nv) if nv is not None else float("nan"))
            else:
                out.append(float(v))
        return out

    def _drain_ring(self) -> None:
        import numpy as np

        from siddhi_tpu.core.types import PHYSICAL_DTYPE

        dtypes = [np.dtype(PHYSICAL_DTYPE[t]) for _n, t in self.schema.attrs]
        names = self.schema.attr_names
        while not self._async_stop.is_set():
            # fault-injection site `drain_worker` (testing/faults.py):
            # OUTSIDE the poison-batch guard, so an injected fault kills the
            # worker thread — the "drain worker death" failure mode the
            # supervisor's health probe detects
            if _faults.ACTIVE is not None:
                _faults.ACTIVE.check("drain_worker", self.schema.stream_id)
            try:
                ring = self._ring
                if ring is None:
                    return
                ts, rows = ring.pop_batch(self._batch_max)
                if ts.shape[0] == 0:
                    self._async_stop.wait(0.001)
                    continue
                cols = {
                    n: rows[:, j].astype(dt)
                    for j, (n, dt) in enumerate(zip(names, dtypes))
                }
                batch = self.schema.to_batch_cols(
                    ts, cols, self.interner, capacity=self.batch_size
                )
                # the trailing payload lane carries the send-time clock
                self.publish_batch(batch, int(rows[-1, -1]))
            except Exception as e:
                self._on_worker_error(e, "async ring worker")

    def queued(self) -> int:
        ring = getattr(self, "_ring", None)
        if ring is not None:
            return ring.size()
        q = getattr(self, "_queue", None)
        return q.qsize() if q is not None else 0

    def _drain(self) -> None:
        import queue as _q

        while not self._async_stop.is_set():
            try:
                item = self._queue.get(timeout=0.1)
            except _q.Empty:
                continue
            # fault-injection site `drain_worker`: outside the poison-batch
            # guard — an injected fault KILLS the worker thread (the failure
            # mode the supervisor's health probe watches for), unlike a
            # poison batch which _on_worker_error survives
            if _faults.ACTIVE is not None:
                _faults.ACTIVE.check("drain_worker", self.schema.stream_id)
            ts_list, rows, now = [item[0]], [item[1]], item[2]
            # opportunistically batch up to batch_max (reference:
            # batch.size.max on the Disruptor consumer)
            while len(rows) < self._batch_max:
                try:
                    nxt = self._queue.get_nowait()
                except _q.Empty:
                    break
                ts_list.append(nxt[0])
                rows.append(nxt[1])
                now = nxt[2]
            try:
                batch = self.schema.to_batch(
                    ts_list, rows, self.interner, capacity=self.batch_size
                )
                self.publish_batch(batch, now)
            except Exception as e:  # a poisoned batch must not kill the worker
                self._on_worker_error(e, "async worker")

    def _on_worker_error(self, exc: Exception, who: str) -> None:
        """A poison batch (bad arity, un-packable value, downstream explosion
        that escaped per-subscriber guards) must not kill the drain worker:
        log, notify the app's exception handler, count it, and keep draining."""
        import logging
        import traceback

        logging.getLogger(__name__).error(
            "%s for stream '%s' dropped a batch:\n%s",
            who, self.schema.stream_id, traceback.format_exc(),
        )
        if self.on_error_stats is not None:
            self.on_error_stats(1)
        oi = self.on_incident
        if (
            oi is not None
            and self.exception_handler is None
            and self.fault_policy is None
        ):
            # unowned worker poison = crash incident (same ownership rule
            # as the supervisor health signal below)
            oi(
                "crash",
                f"{who} for stream '{self.schema.stream_id}': "
                f"{type(exc).__name__}: {exc}",
            )
        nf = self.on_fatal
        if (
            nf is not None
            and self.exception_handler is None
            and self.fault_policy is None
        ):
            # supervised apps treat a poisoned worker as a health signal —
            # but only when NOBODY owns the failure: with an exception
            # handler or an @OnError policy configured, the operator chose
            # handle-and-continue, and restarting would roll state back
            # over a handled poison batch. This also matters on the replay
            # path: failure_ownership is thread-local, so a poison entry
            # replayed into an @async stream fails HERE on the drain
            # worker thread, and an unconditional flag would put a
            # supervised app into a restart->replay->crash loop over one
            # bad stored entry.
            nf(exc, who)
        handler = self.exception_handler
        if handler is not None:
            try:
                handler(exc)
            except Exception:
                logging.getLogger(__name__).exception(
                    "exception handler for stream '%s' raised",
                    self.schema.stream_id,
                )

    def stop_async(self) -> None:
        ev = getattr(self, "_async_stop", None)
        if ev is None:
            return
        # drain what's left before stopping
        import time as _time

        t0 = _time.monotonic()
        while self.queued() > 0 and _time.monotonic() - t0 < 5.0:
            _time.sleep(0.01)
        dropped = self.queued()
        if dropped:
            import logging

            logging.getLogger(__name__).error(
                "async shutdown for stream '%s' timed out with %d events "
                "still queued — they were dropped",
                self.schema.stream_id, dropped,
            )
        # leave the async path BEFORE tearing the ring down so late sends fall
        # through to the synchronous publish path instead of crashing
        self.is_async = False
        ring = getattr(self, "_ring", None)
        self._ring = None  # detach first: queued()/producers now see None
        ev.set()
        joined = True
        for t in self._workers:
            if t is not threading.current_thread():
                t.join(timeout=2.0)
                joined = joined and not t.is_alive()
        self._workers = []
        if ring is not None and joined:
            # only free the native arena once no thread can still touch it;
            # an unjoined worker leaks the ring to the GC instead of UAF-ing
            ring.close()

    # ---- publishing ------------------------------------------------------

    def publish_batch(self, batch: EventBatch, now: int) -> None:
        """Fan a device batch out to all subscribers (already this stream's schema)."""
        pl = self.process_lock
        if pl is None:
            return self._publish_batch(batch, now)
        # hold the app's snapshot barrier across the WHOLE fan-out: each
        # subscriber's receive takes the same RLock (nested, free), but
        # without the outer hold a checkpoint could land BETWEEN two
        # queries' dispatches of one batch — a torn cross-query snapshot
        # that diverges on restore+refeed (the chaos harness caught this).
        # Acquired BEFORE self.lock so lock order is process -> junction
        # on every path (insert-into chains re-enter under the same RLock)
        with pl:
            return self._publish_batch(batch, now)

    def _publish_batch(self, batch: EventBatch, now: int) -> None:
        with self.lock:
            fl = self.flight
            if fl is not None:
                fl.record_batch(batch)
            bb = self.blackbox
            if bb is not None:
                bb.record_batch(batch)
            la = self.lineage
            seq_range = None
            if la is not None:
                # stamp the batch's valid CURRENT rows with seq ids; the
                # range is read under this same lock by the @OnError STORE
                # path (la.last_range) and attached to the publish span
                seq_range = la.record_batch(batch)
                if seq_range[1]:
                    from siddhi_tpu.observability.lineage import (
                        current_publisher,
                    )

                    pub = current_publisher()
                    if pub is not None:
                        # per-publish producer capture: this stamp came
                        # from a recorded query's insert — note which, so
                        # multi-producer streams resolve seq -> producer.
                        # pub_base: the recorder counted this batch's
                        # published records in observe() (receive runs
                        # before the publish), so the range starts
                        # n records back from its pub_count.
                        qid, rec = pub
                        la.note_producer(
                            seq_range[0], seq_range[1], qid,
                            max(rec.pub_count - seq_range[1], 0),
                        )
            n_valid = -1
            if self.on_publish_stats is not None:
                n_valid = int(np.asarray(batch.valid).sum())
                self.on_publish_stats(n_valid)
            tr = self.tracer
            root = (
                tr.start_span(f"stream.{self.schema.stream_id}", n_valid)
                if tr is not None
                else None
            )
            if root is not None and seq_range is not None and seq_range[1]:
                tr.annotate(root, "lineage_seq", list(seq_range))
            try:
                guarded = (
                    self.exception_handler is not None or self.fault_policy is not None
                )
                routed = self._fan_out(
                    zip(self.subscribers, self.subscriber_names),
                    batch, now, tr, n_valid, guarded,
                )
                if self.stream_callbacks:
                    try:
                        events = self.schema.from_batch(batch, self.interner)
                    except Exception as e:
                        if not guarded:
                            raise
                        self._on_dispatch_error(batch, now, e, routed)
                        return
                    if events:
                        rows = [(ts, data) for ts, kind, data in events]
                        for i, cb in enumerate(self.stream_callbacks):
                            sp = (
                                tr.start_span(
                                    self.stream_callback_names[i], len(rows)
                                )
                                if tr is not None
                                else None
                            )
                            try:
                                if not guarded:
                                    cb(rows)
                                else:
                                    try:
                                        cb(rows)
                                    except Exception as e:
                                        routed |= self._on_dispatch_error(
                                            batch, now, e, routed,
                                            subscriber=self.stream_callback_names[i],
                                        )
                            finally:
                                if sp is not None:
                                    tr.end_span(sp)
            finally:
                if root is not None:
                    tr.end_span(root)

    def _fan_out(
        self, pairs, batch: EventBatch, now: int, tr, n_valid: int,
        guarded: bool,
    ) -> bool:
        """Dispatch one batch to [(fn, name)] pairs — THE per-subscriber
        loop, shared by publish_batch (all subscribers) and dispatch_subset
        (the fused group engine's residual subset), so failure-policy and
        tracing semantics cannot drift between the two paths. Returns the
        routed flag: one STREAM/STORE routing per batch even when several
        subscribers fail on it — fault consumers must not double-count a
        failure."""
        routed = False
        for fn, name in pairs:
            sp = tr.start_span(name, n_valid) if tr is not None else None
            try:
                try:
                    # fault-injection site `junction_dispatch` (testing/
                    # faults.py): inside the dispatch so an injected
                    # failure rides the exact path a real subscriber
                    # explosion takes — the guarded branch routes it per
                    # the failure policy, the unguarded branch propagates
                    # it to the sender
                    if _faults.ACTIVE is not None:
                        _faults.ACTIVE.check(
                            "junction_dispatch",
                            f"{self.schema.stream_id}:{name}",
                        )
                    fn(batch, now)
                except Exception as e:
                    if not guarded:
                        # unguarded: freeze a crash incident and raise a
                        # fatal health signal for the supervisor, then on
                        # to the sender
                        oi = self.on_incident
                        if oi is not None:
                            oi(
                                "crash",
                                f"stream '{self.schema.stream_id}' dispatch "
                                f"to {name}: {type(e).__name__}: {e}",
                            )
                        nf = self.on_fatal
                        if nf is not None:
                            nf(e, f"dispatch to {name}")
                        raise
                    routed |= self._on_dispatch_error(  # user-owned policy
                        batch, now, e, routed, subscriber=name,
                    )
            finally:
                if sp is not None:
                    tr.end_span(sp)
        return routed

    def dispatch_subset(self, batch: EventBatch, now: int, subset) -> None:
        """Fan one batch out to an explicit [(fn, name)] subscriber subset —
        the fused group engine's residual path (core/ingest.py
        `_residual_dispatch`): the plan's SA124-blocked consumers get every
        micro-batch per batch, exactly as publish_batch would run them.
        Throughput stats and the flight ring are NOT touched here — the
        fused commit already counted and recorded these events; recording
        again would double them. Per-subscriber failure policy and trace
        spans ride the same _fan_out loop publish_batch uses."""
        pl = self.process_lock
        if pl is None:
            return self._dispatch_subset(batch, now, subset)
        with pl:  # same snapshot-barrier hold as publish_batch
            return self._dispatch_subset(batch, now, subset)

    def _dispatch_subset(self, batch: EventBatch, now: int, subset) -> None:
        with self.lock:
            tr = self.tracer
            n_valid = (
                int(np.asarray(batch.valid).sum()) if tr is not None else -1
            )
            guarded = (
                self.exception_handler is not None
                or self.fault_policy is not None
            )
            self._fan_out(subset, batch, now, tr, n_valid, guarded)

    def _on_dispatch_error(
        self,
        batch: EventBatch,
        now: int,
        exc: Exception,
        routed: bool = False,
        subscriber: str | None = None,
    ) -> bool:
        """Apply the stream's failure policy to one failed dispatch; returns
        True when the batch's events were routed (fault stream / error store).
        With `routed` set, the handler/stats/log still run for this failure
        but the payload is not re-routed. The batch never propagates to the
        sender once a handler or @OnError policy owns the failure
        (reference: StreamJunction.handleError:390-404)."""
        import logging

        log = logging.getLogger(__name__)
        oi = self.on_incident
        if oi is not None:  # black box: a dispatch failure is an incident
            oi(
                "dispatch_error",
                f"stream '{self.schema.stream_id}'"
                + (f" subscriber {subscriber}" if subscriber else "")
                + f": {type(exc).__name__}: {exc}",
            )
        if self.on_error_stats is not None:
            self.on_error_stats(1)
        factory = self.error_stats_factory
        if factory is not None and subscriber is not None:
            add = self._sub_error_stats.get(subscriber)
            if add is None:
                add = self._sub_error_stats[subscriber] = factory(subscriber)
            add(1)
        if self.exception_handler is not None:
            try:
                self.exception_handler(exc)
            except Exception:
                log.exception(
                    "exception handler for stream '%s' raised", self.schema.stream_id
                )
        policy = self.fault_policy
        if policy is None:
            return False  # handler-only: existing set_exception_handler semantics
        if policy == "LOG":
            log.error(
                "stream '%s': dropping a failed batch (@OnError action='LOG'): %s",
                self.schema.stream_id, exc, exc_info=exc,
            )
            return False
        if routed:
            return False  # another subscriber already routed this batch
        from siddhi_tpu.core.event import KIND_CURRENT, KIND_EXPIRED

        try:
            events = self.schema.from_batch(batch, self.interner)
        except Exception:
            log.exception(
                "stream '%s': could not decode a failed batch for @OnError "
                "routing; the batch was dropped", self.schema.stream_id,
            )
            return False
        # only payload rows route onward: TIMER/RESET rows are synthetic
        # all-null scheduler artifacts, not user events
        events = [e for e in events if e[1] in (KIND_CURRENT, KIND_EXPIRED)]
        if policy == "STREAM":
            fj = self.fault_junction
            if fj is None or not events:
                return False
            err = f"{type(exc).__name__}: {exc}"
            try:
                # publish per-chunk with the kind lane preserved — an EXPIRED
                # row must not resurface on !S as a CURRENT event
                cap = fj.batch_size
                for ofs in range(0, len(events), cap):
                    chunk = events[ofs : ofs + cap]
                    fb = fj.schema.to_batch(
                        [ts for ts, _k, _d in chunk],
                        [tuple(d) + (err,) for _ts, _k, d in chunk],
                        fj.interner,
                        capacity=cap,
                        kinds=[k for _ts, k, _d in chunk],
                    )
                    fj.publish_batch(fb, now)
            except Exception:
                log.exception(
                    "fault stream '%s' dispatch failed; the batch was dropped",
                    fj.schema.stream_id,
                )
            return True
        if policy == "STORE":
            from siddhi_tpu.core.error_store import ORIGIN_STREAM, make_entry

            store = self.error_store_fn() if self.error_store_fn is not None else None
            if store is None:
                log.error(
                    "stream '%s': @OnError action='STORE' but no error store "
                    "is available; the batch was dropped", self.schema.stream_id,
                )
                return False
            if not events:
                return False
            # replay re-injects through the input handler, i.e. as CURRENT
            # events; EXPIRED rows are recorded for inspection all the same
            entry = make_entry(
                self.app_name, ORIGIN_STREAM, self.schema.stream_id, exc,
                events=[(ts, tuple(d)) for ts, _k, d in events],
            )
            if self.lineage is not None:
                # contributing seq ids: the failing batch was stamped at
                # the top of this publish (same junction lock) — last_range
                # is exactly its rows
                base, n = self.lineage.last_range
                if n:
                    entry.lineage = {
                        "stream": self.schema.stream_id,
                        "seq_lo": base,
                        "seq_hi": base + n - 1,
                    }
            if self.flight is not None:
                # black-box dump: the last-N events through this junction
                # BEFORE the failure, decoded host-side (the failing batch's
                # own rows are already in the ring — it was recorded at
                # publish time)
                try:
                    entry.flight = self.flight.events()
                except Exception:
                    log.exception(
                        "stream '%s': flight-recorder dump failed",
                        self.schema.stream_id,
                    )
            store.store(entry)
            return True
        return False

    is_async = False

    def send_rows(
        self,
        timestamps: Sequence[int],
        rows: Sequence[Sequence[Any]],
        now: int | None = None,
    ) -> None:
        """Pack host rows and publish, chunking to the junction batch size.
        In @async mode rows enqueue into the ingress ring (blocking when full
        = back-pressure) and a worker thread batches + publishes."""
        if self.is_async:
            ring = getattr(self, "_ring", None)
            if ring is not None:
                import time as _time

                stop = self._async_stop
                for ts, row in zip(timestamps, rows):
                    enc = self._encode_row(row)
                    enc.append(float(now if now is not None else ts))
                    while not ring.push(ts, enc):
                        if stop.is_set():
                            return  # shutting down: drop instead of hanging
                        _time.sleep(0.0005)  # back-pressure without a hot spin
            else:
                for ts, row in zip(timestamps, rows):
                    self._queue.put((ts, tuple(row), now if now is not None else ts))
            return
        n = len(rows)
        for ofs in range(0, max(n, 1), self.batch_size):
            ts_chunk = list(timestamps[ofs : ofs + self.batch_size])
            row_chunk = list(rows[ofs : ofs + self.batch_size])
            if not row_chunk:
                return
            batch = self.schema.to_batch(
                ts_chunk, row_chunk, self.interner, capacity=self.batch_size
            )
            self.publish_batch(batch, now if now is not None else (ts_chunk[-1] if ts_chunk else 0))


class InputHandler:
    """Reference: stream/input/InputHandler.java:27-68."""

    def __init__(self, junction: StreamJunction, clock: Callable[[], int]):
        self.junction = junction
        self.clock = clock

    def send(self, data: Sequence[Any], timestamp: int | None = None) -> None:
        ts = timestamp if timestamp is not None else self.clock()
        g = self.junction.ingress_gate
        if g is not None and g.intercept(
            "rows", ([ts], [tuple(data)], self.clock()), 1
        ):
            return
        self.junction.send_rows([ts], [tuple(data)], now=self.clock())

    def send_many(
        self, rows: Sequence[Sequence[Any]], timestamps: Sequence[int] | None = None
    ) -> None:
        if timestamps is None:
            t = self.clock()
            timestamps = [t] * len(rows)
        timestamps = list(timestamps)
        rows = [tuple(r) for r in rows]
        g = self.junction.ingress_gate
        if g is not None and g.intercept(
            "rows", (timestamps, rows, self.clock()), len(rows)
        ):
            return
        self.junction.send_rows(timestamps, rows, now=self.clock())

    def send_columns(
        self,
        timestamps: np.ndarray,
        cols: dict[str, np.ndarray],
        now: int | None = None,
    ) -> None:
        """High-throughput columnar ingest: one device batch per junction
        batch-size chunk, no per-row Python work (the analog of the reference's
        @async batched Disruptor path, StreamJunction.java:262-298).

        All-numeric chunks (pre-interned string ids included) ride the packed
        codec: ONE contiguous host->device transfer per batch, bitcast-split
        on device (see StreamSchema.packed_codec).
        """
        j = self.junction
        n = len(timestamps)
        if now is None:
            now = self.clock()  # same wall-clock default as send/send_many
        g = j.ingress_gate
        if g is not None and g.intercept("cols", (timestamps, cols, now), n):
            return
        numeric = all(np.asarray(v).dtype.kind not in "OUS" for v in cols.values())
        fi = j.fused_ingest
        send_id = next(j.send_ids)
        if numeric and fi is not None and fi.try_send(
            timestamps, cols, now, send_id
        ):
            return
        if numeric:
            sid = j.schema.stream_id
            with stage("send", send=send_id, stream=sid, rows=n, path="batch"):
                self._send_packed(timestamps, cols, n, now)
            return
        for ofs in range(0, n, j.batch_size):
            ts_chunk = timestamps[ofs : ofs + j.batch_size]
            chunk = {k: v[ofs : ofs + j.batch_size] for k, v in cols.items()}
            batch = j.schema.to_batch_cols(
                ts_chunk, chunk, j.interner, capacity=j.batch_size
            )
            j.publish_batch(batch, now)

    def _send_packed(self, timestamps, cols, n: int, now: int) -> None:
        """The per-batch path of all-numeric columns: one packed transfer
        and one publish per micro-batch. Each is a chunk of the waterfall
        (observability/profiler.py): `encode` and `publish` here (the
        waterfall's `dispatch`), and the query step adds its device and
        readback stages through the profiler's thread-local chunk."""
        j = self.junction
        encode, decode = j.schema.packed_codec(j.batch_size)
        prof = j.profiler
        for ofs in range(0, n, j.batch_size):
            end = min(ofs + j.batch_size, n)
            m = end - ofs
            # None when statistics are off or disabled (one check)
            wf = (
                prof.begin(j.schema.stream_id, m, "batch")
                if prof is not None else None
            )
            with stage("encode", wf=wf):
                buf = encode(
                    timestamps[ofs:end],
                    {k: v[ofs:end] for k, v in cols.items()},
                    m,
                )
                batch = decode(buf, np.int32(m))
            if wf is not None:
                prof.tls_begin(wf)
            try:
                with stage("publish", wf=wf, wf_name="dispatch"):
                    j.publish_batch(batch, now)
            finally:
                if wf is not None:
                    prof.tls_end()
                    prof.end(wf)


def system_clock_ms() -> int:
    return int(time.time() * 1000)
