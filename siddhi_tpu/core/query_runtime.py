"""Per-query compilation and runtime container.

Reference: query/QueryRuntime.java:45-200 wires receiver -> processor chain ->
selector -> rate limiter -> callback as runtime objects. Here the whole chain is
compiled once into a single pure jax step function
`(state, in_batch, now) -> (state', out_batch)` and jitted; the runtime object
owns the device state and the host-side output routing.
"""

from __future__ import annotations

import threading
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from siddhi_tpu.core.errors import SiddhiAppCreationError
from siddhi_tpu.core.event import (
    EventBatch,
    KIND_CURRENT,
    KIND_EXPIRED,
    KIND_TIMER,
    StreamSchema,
)
from siddhi_tpu.core.executor import Scope, compile_expression
from siddhi_tpu.core.flow import Flow
from siddhi_tpu.core.selector import CompiledSelector
from siddhi_tpu.core.types import AttrType, InternTable
from siddhi_tpu.observability.profiler import stage
from siddhi_tpu.ops.group import RECLAIM_NONE
from siddhi_tpu.query_api.annotation import find_annotation
from siddhi_tpu.query_api.execution import (
    Filter,
    InsertIntoStream,
    OutputEventsFor,
    Query,
    ReturnStream,
    SingleInputStream,
    StreamFunctionHandler,
    WindowHandler,
)


class CompiledSingleChain:
    """Ordered filter / stream-function / window stages over one input stream
    (reference: SingleInputStreamParser.generateProcessor chain assembly).
    Stream functions append attribute columns; the chain's effective output
    schema is `out_attrs`."""

    def __init__(
        self,
        stream: SingleInputStream,
        schema: StreamSchema,
        scope: Scope,
        window_factory: Optional[Callable] = None,
    ):
        from siddhi_tpu.core.stream_function import make_stream_function

        self.schema = schema
        self.ref = stream.alias or stream.stream_id
        self.window = None
        # lineage probe (observability/lineage.py): called on the post-
        # filter/fn, pre-window flow during tracing to emit the admit mask
        # (+ group key / window-time) as `__lin.*` aux lanes; None (no
        # call) when @app:lineage is off
        self.lineage_probe = None
        self.stages: list[tuple[str, object]] = []
        # each stage's device scope (jax.named_scope): `filter`,
        # `fn.<name>`, `window.<type>`
        self.scopes: list[str] = []
        attrs = dict(schema.attr_types)
        for h in stream.handlers:
            if isinstance(h, Filter):
                cond = compile_expression(h.expression, scope)
                if cond.type is not AttrType.BOOL:
                    raise SiddhiAppCreationError("filter must be a boolean expression")
                self.stages.append(("filter", cond))
                self.scopes.append("filter")
            elif isinstance(h, WindowHandler):
                if self.window is not None:
                    raise SiddhiAppCreationError("only one window per stream")
                if window_factory is None:
                    raise SiddhiAppCreationError(
                        "windows are not available at this site"
                    )
                win_schema = StreamSchema(schema.stream_id, list(attrs.items()))
                self.window = window_factory(h.window, win_schema, self.ref)
                self.stages.append(("window", self.window))
                self.scopes.append(f"window.{h.window.name.lower()}")
            elif isinstance(h, StreamFunctionHandler):
                fn = make_stream_function(
                    h, attrs, self.ref, scope, schema.stream_id
                )
                for name, t in fn.new_attrs:
                    if name in attrs:
                        raise SiddhiAppCreationError(
                            f"stream function '#{h.name}' output '{name}' "
                            "collides with an existing attribute"
                        )
                    attrs[name] = t
                    # later filters/selectors resolve the appended attrs
                    scope.add_stream(self.ref, attrs)
                self.stages.append(("fn", fn))
                self.scopes.append(f"fn.{h.name}")
        self.out_attrs: list[tuple[str, AttrType]] = list(attrs.items())

    def init_state(self):
        return self.window.init_state() if self.window is not None else ()

    def split_at_window(self):
        """(stages before the window, the window's scope, stages after it),
        for a caller that steps the window itself (`QueryRuntime`'s fifo
        passes): each part is `flow -> flow`, under the stages' scopes."""
        at = [kind for kind, _ in self.stages].index("window")

        def part(lo, hi):
            def run(flow):
                for (kind, op), scope in zip(self.stages[lo:hi], self.scopes[lo:hi]):
                    with jax.named_scope(scope):
                        flow = (
                            self._filter(flow, [op]) if kind == "filter"
                            else op.apply(flow)
                        )
                return flow

            return run

        return part(0, at), self.scopes[at], part(at + 1, len(self.stages))

    def apply(self, state, flow: Flow):
        probe = self.lineage_probe
        for (kind, op), scope in zip(self.stages, self.scopes):
            if kind == "window" and probe is not None:
                probe(flow)  # admit mask = post-filter, pre-window
                probe = None
            with jax.named_scope(scope):
                if kind == "filter":
                    flow = self._filter(flow, [op])
                elif kind == "fn":
                    flow = op.apply(flow)
                else:  # window
                    state, flow = op.apply(state, flow)
        if probe is not None:
            probe(flow)  # windowless chain: probe the final flow
        return state, flow

    @staticmethod
    def _filter(flow: Flow, conds) -> Flow:
        if not conds:
            return flow
        env = flow.env()
        mask = None
        for c in conds:
            m = c(env)
            mask = m if mask is None else (mask & m)
        is_timer = flow.batch.kind == KIND_TIMER  # timers bypass filters
        valid = flow.batch.valid & (is_timer | mask)
        batch = EventBatch(flow.batch.ts, flow.batch.kind, valid, flow.batch.cols)
        import dataclasses

        return dataclasses.replace(flow, batch=batch)


def _join_outputs(acc: EventBatch, out: EventBatch) -> EventBatch:
    """The CURRENT rows of a step's earlier passes and of its next one as
    one batch of the same capacity, in order, moved to the front: at most
    one per input row, so they fit. The rare path: only a step with more
    than one pass comes here."""
    from siddhi_tpu.ops.prefix import compact_front

    def packed(b):
        keep = b.valid & (b.kind == KIND_CURRENT)
        lanes = compact_front(keep, {"ts": b.ts, "kind": b.kind, "cols": b.cols})
        return lanes, keep.sum(dtype=jnp.int32)

    cap = acc.valid.shape[0]
    (a, n_a), (o, n_o) = packed(acc), packed(out)
    pos = jnp.arange(cap, dtype=jnp.int32)
    joined = jax.tree_util.tree_map(
        lambda x, y: jnp.where(
            pos < n_a, x,
            jax.lax.dynamic_slice(jnp.pad(y, (cap, 0)), (cap - n_a,), (cap,)),
        ),
        a, o,
    )
    return EventBatch(joined["ts"], joined["kind"], pos < n_a + n_o, joined["cols"])


class _AuxWarnPool:
    """Deferred aux-flag checks with NO background thread.

    The hot dispatch path never blocks on device scalars and does no device
    work at all: submitted flags accumulate in a bounded host-side backlog,
    and the one blocking device->host read happens only (a) in `flush()` and
    (b) at most once per `drain_every_s`, from whichever thread submits
    then. What that read costs a running pipeline on a directly attached
    chip is unmeasured.

    Backlog entries hold weakrefs to the query runtime, so a shut-down app is
    collectable even if nobody flushes."""

    COALESCE_AT = 32

    def __init__(self):
        import os
        import time as _time
        import weakref

        self._weakref = weakref
        self._lock = threading.Lock()
        # id(qr) -> [qr_weakref, {flag_kind: [device bools]}]
        self._pending: dict = {}
        self._last_drain = _time.monotonic()
        # periodic-drain cadence; 0 or negative disables automatic drains
        # (flush()/shutdown still drain)
        try:
            self.drain_every_s = float(
                os.environ.get("SIDDHI_TPU_AUX_DRAIN_S", "5.0")
            )
        except ValueError:
            self.drain_every_s = 5.0

    def submit(self, qr, flags: dict) -> None:
        with self._lock:
            ent = self._pending.get(id(qr))
            # id() values are reused after GC: a stale dead entry at this
            # address must not swallow a live runtime's flags
            if ent is not None and ent[0]() is not qr:
                ent = None
            if ent is None:
                ent = [self._weakref.ref(qr), {k: [] for k in flags}]
                self._pending[id(qr)] = ent
            acc = ent[1]
            for k, v in flags.items():
                vs = acc.setdefault(k, [])
                vs.append(v)
                # bound the backlog with NO device work: keep the first
                # COALESCE_AT flags (overflows usually start early) plus a
                # ring of the most recent ones
                if len(vs) > 2 * self.COALESCE_AT:
                    del vs[self.COALESCE_AT]
        import time as _time

        if 0 < self.drain_every_s < _time.monotonic() - self._last_drain:
            self.flush()

    def flush(self) -> None:
        """Drain everything with ONE blocking wait for the whole backlog
        (all runtimes, all flag kinds; see `_drain`)."""
        import time as _time

        with self._lock:
            pending, self._pending = self._pending, {}
            self._last_drain = _time.monotonic()
        if not pending:
            return
        flags = sum(
            len(vs) for _ref, acc in pending.values() for vs in acc.values()
        )
        with stage("aux_drain", flags=flags):
            self._drain(pending)

    @staticmethod
    def _drain(pending: dict) -> None:
        """Read every pending flag to the host and reduce there. No device
        program runs: a stacked reduction is a program of its own per
        backlog length and per placement (one device, a mesh), built or
        loaded in the middle of traffic. Every transfer is started before
        the first is waited for, so the drain still blocks once."""
        import logging

        import numpy as np

        plan = []  # (qr, {flag kind: [device bools]})
        for _qid, (qr_ref, acc) in pending.items():
            qr = qr_ref()
            if qr is None:
                continue  # app GC'd un-flushed: drop its backlog
            plan.append((qr, acc))
            for vs in acc.values():
                for v in vs:
                    start = getattr(v, "copy_to_host_async", None)
                    if start is not None:
                        try:
                            start()
                        except Exception:
                            pass  # the read below reports it
        for qr, acc in plan:
            try:
                host = jax.device_get(acc)
                qr._check_aux_flags({
                    k: any(bool(np.asarray(v).any()) for v in vs)
                    for k, vs in host.items()
                })
            except Exception:  # never let a warning path kill the app
                logging.getLogger(__name__).debug(
                    "aux flag drain failed", exc_info=True
                )


_AUX_WORKER = _AuxWarnPool()


class BaseQueryRuntime:
    """Shared host-side half of a compiled query: output schema inference,
    callback/junction routing, state container (reference: QueryRuntime.java:45
    + OutputParser callback construction)."""

    @property
    def used_attrs(self):
        """Input attribute names this query can ever read (from the compile
        scope's resolved keys), or None when unknown/everything (select *).
        Fused ingest drops un-read columns from the wire."""
        scope = getattr(self, "_scope", None)
        if scope is None or getattr(self.query.selector, "select_all", False):
            return None
        return {k[2] for k in scope.used_keys}

    def _setup_output(self, query: "Query", query_id: str) -> None:
        out = query.output_stream
        if isinstance(out, InsertIntoStream):
            target = out.target
        else:
            target = f"__ret_{query_id}"
        self.out_schema = StreamSchema(target, self.selector.out_attrs)
        self.output_events = out.output_events
        # ungrouped batch-mode collapse needs the kind filter at selector level
        # (reference: QuerySelector currentOn/expiredOn gate lastEvent)
        self.selector.output_events_for_batch = out.output_events
        self.query_callbacks: list[Callable] = []
        self.publish_fn: Optional[Callable] = None
        self._receive_lock = threading.RLock()
        # armed by a fused group engine for cross-query shared-window members
        # (core/ingest.py): called before every donated-state per-batch step
        # to split chain buffers a fused dispatch aliased across queries
        self._unshare_guard: Optional[Callable] = None
        # armed by parallel/keyshard.py (@app:shard axis='keys'): the
        # KeyShardedGroupExec that replaced self._step and owns the [D]
        # state layout, occupancy gauges and the snapshot canonical form
        self._keyshard = None
        # device-budget trackers (wired by the app runtime when statistics
        # are on): jitted-step dispatch time and host-blocking decode stalls
        self.device_step_tracker = None
        self.sync_stall_tracker = None
        # continuous profiler (observability/profiler.py): compile ledger
        # for the jitted step + waterfall sub-stage attribution; both None
        # (one check) when statistics are off
        self.compile_telemetry = None
        self.profiler = None
        # lineage recorder (observability/lineage.py QueryLineage), armed by
        # arm_lineage() when @app:lineage is on; None = one attribute check
        # per receive (same contract as the trackers above)
        self.lineage = None
        self.state = None
        self.tables = {}
        self.table_op = None
        self._warned_overflow = False
        self._warned_join_overflow = False
        self._warned_table_overflow = False

        from siddhi_tpu.core.ratelimit import (
            EventAllLimiter,
            TimeAllLimiter,
            build_rate_limiter,
        )

        grouped = bool(query.selector.group_by)
        self.rate_limiter = build_rate_limiter(query.output_rate, grouped)
        if (
            self.rate_limiter is not None
            and grouped
            and not isinstance(self.rate_limiter, (EventAllLimiter, TimeAllLimiter))
        ):
            # per-group limiters need the group key beside each output row
            self.selector.emit_group_key = True

    def _attach_tables(self, tables: dict, interner) -> None:
        """Compile this query's table-output op and attach ONLY the tables the
        query actually reads (in-conditions, join sides) or writes (output
        target) — table-free queries skip table-state plumbing entirely
        (reference: OutputParser constructing Insert/Update/Delete/
        UpdateOrInsertIntoTableCallback, query/output/callback/*)."""
        from siddhi_tpu.core.table import collect_used_tables, compile_table_output

        self._interner = interner
        tables = dict(tables or {})
        self.table_op = compile_table_output(
            self.query.output_stream, self.out_schema, tables, interner
        )
        if self.table_op is not None and self.rate_limiter is not None:
            raise SiddhiAppCreationError(
                "output rate limiting into a table is not supported yet"
            )
        used = collect_used_tables(self.query, tables)
        self.tables = {tid: tables[tid] for tid in sorted(used)}
        target = getattr(self.query.output_stream, "target", None)
        self._mutates_table = target if self.table_op is not None else None

    def _collect_table_states(self) -> dict:
        st = {tid: t.state for tid, t in self.tables.items()}
        # join sides backed by other findables (named windows) are read-only
        for fid, f in getattr(self, "join_findables", {}).items():
            st[fid] = f.state
        return st

    def _writeback_table_states(self, tstates: dict) -> None:
        mutated = getattr(self, "_mutates_table", None)
        for tid, t in self.tables.items():
            t.state = tstates[tid]
            if tid == mutated:
                t.notify_change()  # record-store write-through

    def init_state(self):
        raise NotImplementedError

    def describe_state(self) -> dict:
        """Introspection snapshot (pull-only; see observability/introspect).
        Subclasses add their stateful internals (window fill, NFA instance
        counts, join-side buffers)."""
        d = {
            "kind": type(self).__name__,
            "callbacks": len(self.query_callbacks),
            "rate_limited": self.rate_limiter is not None,
            "tables": sorted(self.tables),
        }
        # cross-query state sharing (core/fusion_exec.py): this query's
        # window ring is one refcounted buffer serving every query in the set
        shared = getattr(self, "shared_ring", None)
        if shared is not None:
            d["shared_ring"] = dict(shared)
        lin = getattr(self, "lineage", None)
        if lin is not None:
            d["lineage"] = lin.describe()
        return d

    def _published_kinds(self):
        """Event kinds this query's insert-into actually publishes (the
        insert transform re-kinds them all CURRENT on the target) — maps a
        downstream junction's lineage seq back to this query's records."""
        from siddhi_tpu.core.event import KIND_CURRENT, KIND_EXPIRED
        from siddhi_tpu.query_api.execution import OutputEventsFor

        if self.output_events is OutputEventsFor.CURRENT:
            return frozenset((KIND_CURRENT,))
        if self.output_events is OutputEventsFor.EXPIRED:
            return frozenset((KIND_EXPIRED,))
        return frozenset((KIND_CURRENT, KIND_EXPIRED))

    def _lin_observe(self, lin, aux: dict, now: int, tag=None) -> dict:
        """Pull the step's `__lin.*` lanes to host, feed the recorder, and
        return aux with the lanes stripped (callers downstream only ever
        see the ordinary flag keys). Runs under the receive lock so
        observation order matches dispatch order."""
        import numpy as np

        lanes = {}
        rest = {}
        for k, v in aux.items():
            if k.startswith("__lin"):
                lanes[k] = np.asarray(v)
            else:
                rest[k] = v
        if lanes:
            try:
                lin.observe(lanes, now, tag)
            except Exception:  # provenance must never break dispatch
                import logging

                logging.getLogger(__name__).debug(
                    "lineage observe failed for query '%s'",
                    self.query_id, exc_info=True,
                )
        return rest

    @staticmethod
    def _fresh(state):
        """Deep-copy an initial state pytree: jnp constant caching can alias
        identical zero leaves, which breaks buffer donation (the same buffer
        must not be donated twice in one call)."""
        import jax.numpy as _jnp

        return jax.tree_util.tree_map(lambda x: _jnp.array(x, copy=True), state)

    def _warn_aux(self, aux: dict) -> None:
        """Surface overflow flags WITHOUT stalling the dispatch pipeline:
        flags accumulate in the process-wide `_AuxWarnPool`; the one
        blocking device read happens in its periodic drain or in
        `flush_aux_warnings`."""
        flags = {
            k: v
            for k, v in aux.items()
            if k != "next_timer" and not k.startswith("__lin")
        }
        if flags:
            _AUX_WORKER.submit(self, flags)

    def flush_aux_warnings(self) -> None:
        _AUX_WORKER.flush()

    def _check_aux_flags(self, aux: dict) -> None:
        if (
            not self._warned_overflow
            and "groupby_overflow" in aux
            and bool(aux["groupby_overflow"])
        ):
            self._warned_overflow = True
            import logging

            group = self.selector.group
            logging.getLogger(__name__).error(
                "query '%s': group-by slot table overflowed (capacity %d%s); "
                "rows of overflowed keys lose their cross-batch carry "
                "(`overflow_rows` of the query's `group` status counts them) "
                "— raise it with @app:groupCapacity(size='N')",
                self.query_id,
                group.capacity if group else -1,
                ", of the groups alive in the window at once"
                if group and group.reclaim != RECLAIM_NONE
                else ", of all groups seen: no window ahead takes any back",
            )
        if (
            not getattr(self, "_warned_pattern_overflow", False)
            and "pattern_overflow" in aux
            and bool(aux["pattern_overflow"])
        ):
            self._warned_pattern_overflow = True
            import logging

            logging.getLogger(__name__).error(
                "query '%s': pattern token table or emission buffer "
                "overflowed; partial matches or emissions were dropped "
                "(`overflow` of the query's `pattern` status counts them) "
                "— raise @app:patternCapacity(size='N'): the table has to "
                "hold the partial matches alive at once and the tokens one "
                "micro-batch arms; the emission buffer holds two matches "
                "for every row of a micro-batch (or the table, if smaller)",
                self.query_id,
            )
        if (
            not getattr(self, "_warned_partition_overflow", False)
            and "partition_overflow" in aux
            and bool(aux["partition_overflow"])
        ):
            self._warned_partition_overflow = True
            import logging

            logging.getLogger(__name__).error(
                "query '%s': partition key table overflowed; events of "
                "overflowed keys were dropped — raise it with "
                "@app:partitionCapacity(size='N')",
                self.query_id,
            )
        if (
            not getattr(self, "_warned_window_overflow", False)
            and "window_overflow" in aux
            and bool(aux["window_overflow"])
        ):
            self._warned_window_overflow = True
            import logging

            logging.getLogger(__name__).warning(
                "query '%s': window emission/key buffer overflowed; events "
                "were dropped — reduce batch size or raise window capacity "
                "(a time batch's bucket: @app:timeCapacity(size='N'))",
                self.query_id,
            )
        if (
            not getattr(self, "_warned_window_early", False)
            and "window_early_expiry" in aux
            and bool(aux["window_early_expiry"])
        ):
            self._warned_window_early = True
            import logging

            logging.getLogger(__name__).warning(
                "query '%s': a time window held more live rows than its "
                "capacity (%d); the oldest were expired early — raise it "
                "with @app:timeCapacity(size='N')",
                self.query_id,
                getattr(getattr(getattr(self, "chain", None), "window", None), "w", -1),
            )
        if (
            not self._warned_table_overflow
            and "table_overflow" in aux
            and bool(aux["table_overflow"])
        ):
            self._warned_table_overflow = True
            import logging

            logging.getLogger(__name__).error(
                "query '%s': table ran out of capacity; inserts were dropped — "
                "raise it with @capacity(size='N') on the table definition",
                self.query_id,
            )
        if (
            not self._warned_join_overflow
            and "join_overflow" in aux
            and bool(aux["join_overflow"])
        ):
            self._warned_join_overflow = True
            import logging

            logging.getLogger(__name__).warning(
                "query '%s': join output overflowed its capacity; matches were "
                "dropped — raise it with @app:joinCapacity(size='N')",
                self.query_id,
            )
        if (
            not getattr(self, "_warned_pk_duplicate", False)
            and "table_pk_duplicate_dropped" in aux
            and bool(aux["table_pk_duplicate_dropped"])
        ):
            self._warned_pk_duplicate = True
            import logging

            logging.getLogger(__name__).error(
                "query '%s': dropping inserted event(s) — an event with the "
                "same primary key is already stored (use `update or insert "
                "into` to overwrite)",
                self.query_id,
            )
        if (
            not getattr(self, "_warned_pk_conflict", False)
            and "table_pk_conflict" in aux
            and bool(aux["table_pk_conflict"])
        ):
            self._warned_pk_conflict = True
            import logging

            logging.getLogger(__name__).error(
                "query '%s': update failed — rekeying matched rows would "
                "collide with an existing primary key; the update event was "
                "skipped",
                self.query_id,
            )

    def _tls_wf(self):
        """send_columns' active per-batch chunk on this thread, if any."""
        prof = self.profiler
        return prof.tls_wf() if prof is not None else None

    def _step_stage(self) -> stage:
        """The `step` stage every receive path (single/pattern/join) runs
        its jitted step in: device-time histogram and the waterfall's
        `device` sub-stage."""
        return stage(
            "step", self.device_step_tracker, wf=self._tls_wf(),
            wf_name="device", query=self.query_id,
        )

    def _observe_compile(self, prog, signature, wall_ns: int) -> None:
        """Compile telemetry for `prog`, called `wall_ns` ago, under
        `query.<id>[signature]`-scoped ledgers.

        `signature` must identify the PROGRAM as well as the call shape
        when the runtime jits several (pattern per-stream steps, join
        sides): telemetry tracks one jit cache per component, so the
        component key embeds everything up to the batch capacity."""
        ct = self.compile_telemetry
        if ct is not None and wall_ns:
            prog_key, shape = signature
            comp = f"query.{self.query_id}"
            if prog_key:
                comp += f"[{prog_key}]"
            ct.observe(comp, prog, shape, wall_ns)

    def _timed_decode(self, decode, schema, out):
        """Host decode, its blocking read recorded as the d2h truth-sync
        stall: reading a device batch forces real completion of the
        dependent chain (the waterfall's `readback` sub-stage)."""
        return decode(
            schema, out, self.sync_stall_tracker, wf=self._tls_wf()
        )

    def route_output(self, out: EventBatch, now: int, decode) -> None:
        """Dispatch a step's output to query callbacks / downstream junction.

        `decode` = app-runtime host decoder (batch -> event triples).
        """
        if self.rate_limiter is not None:
            rows = self._timed_decode(decode, self.out_schema, out)
            keys = None
            if "__group_key__" in out.cols:
                import numpy as np

                idx = np.nonzero(np.asarray(out.valid))[0]
                keys = np.asarray(out.cols["__group_key__"])[idx]
            rows4 = [
                (ts, kind, data, int(keys[i]) if keys is not None else None)
                for i, (ts, kind, data) in enumerate(rows)
            ]
            # only the kinds this query OUTPUTS enter the limiter — an
            # un-requested EXPIRED row must not consume a chunk slot or
            # shadow a group's held row (reference: the selector's
            # currentOn/expiredOn gate sits before OutputRateLimiter)
            want = self.output_events
            if want is OutputEventsFor.CURRENT:
                rows4 = [r for r in rows4 if r[1] == KIND_CURRENT]
            elif want is OutputEventsFor.EXPIRED:
                rows4 = [r for r in rows4 if r[1] == KIND_EXPIRED]
            else:
                rows4 = [
                    r for r in rows4 if r[1] in (KIND_CURRENT, KIND_EXPIRED)
                ]
            released = self.rate_limiter.process(rows4, now)
            self._deliver(released, now)
            return
        raw = getattr(self, "raw_query_callbacks", None)
        if raw and len(raw) == len(self.query_callbacks):
            # every callback came through `add_callback`, which keeps the
            # user's own beside its wrapper: decode straight to `Event`s and
            # call those, as the fused drain does (core/ingest.py
            # `deliver_endpoint`), instead of triples that each wrapper
            # turns into `Event`s again
            got = self.out_schema.events_from_batch(
                out, self._interner, self.sync_stall_tracker,
                wf=self._tls_wf(),
            )
            if got is not None:
                ts, ins, removed = got
                want = self.output_events
                if want is OutputEventsFor.CURRENT:
                    removed = []
                elif want is OutputEventsFor.EXPIRED:
                    ins = []
                if ins or removed:
                    with stage("callback", rows=len(ins) + len(removed)):
                        for cb in raw:
                            cb(ts, ins or None, removed or None)
        elif self.query_callbacks:
            events = self._timed_decode(decode, self.out_schema, out)
            if events:
                ins = [e for e in events if e[1] == KIND_CURRENT]
                removed = [e for e in events if e[1] == KIND_EXPIRED]
                want = self.output_events
                if want is OutputEventsFor.CURRENT:
                    removed = []
                elif want is OutputEventsFor.EXPIRED:
                    ins = []
                if ins or removed:
                    ts = events[-1][0]
                    with stage("callback", rows=len(events)):
                        for cb in self.query_callbacks:
                            cb(ts, ins or None, removed or None)
        if self.publish_fn is not None:
            self.publish_fn(out, now)

    def _deliver(self, rows4: list, now: int) -> None:
        """Route rate-limiter-released rows to callbacks and the downstream
        junction (re-encoded into a device batch)."""
        if not rows4:
            return
        if self.query_callbacks:
            ins = [(ts, kind, data) for ts, kind, data, _k in rows4 if kind == KIND_CURRENT]
            removed = [(ts, kind, data) for ts, kind, data, _k in rows4 if kind == KIND_EXPIRED]
            want = self.output_events
            if want is OutputEventsFor.CURRENT:
                removed = []
            elif want is OutputEventsFor.EXPIRED:
                ins = []
            if ins or removed:
                ts = rows4[-1][0]
                for cb in self.query_callbacks:
                    cb(ts, ins or None, removed or None)
        if self.publish_fn is not None:
            # pad to a fixed capacity so downstream jitted steps keep one
            # stable shape (variable sizes would each trigger a recompile)
            cap = 64
            for ofs in range(0, len(rows4), cap):
                chunk = rows4[ofs : ofs + cap]
                batch = self.out_schema.to_batch(
                    [r[0] for r in chunk],
                    [r[2] for r in chunk],
                    self._interner,
                    capacity=cap,
                    kinds=[r[1] for r in chunk],
                )
                self.publish_fn(batch, now)


class QueryRuntime(BaseQueryRuntime):
    """Compiled query + device state + host output routing."""

    def __init__(
        self,
        query: Query,
        query_id: str,
        in_schema: StreamSchema,
        interner: InternTable,
        window_factory: Optional[Callable] = None,
        group_capacity: Optional[int] = None,
        tables: Optional[dict] = None,
        time_capacity: Optional[int] = None,
        held_cols=None,
        batch_size: Optional[int] = None,
    ):
        # `batch_size`: the app's micro-batch, where the query's group table
        # may keep a bucket index (a plain query: `CompiledGroupBy.probe`)
        # `held_cols`: `make_window`'s, for the subclass that knows nobody
        # reads its window's EXPIRED rows beyond them (core/partition.py)
        self.query = query
        self.query_id = query_id
        self.in_schema = in_schema
        stream = query.input_stream
        assert isinstance(stream, SingleInputStream)
        self.ref = stream.alias or stream.stream_id

        scope = Scope(interner)
        scope.add_stream(self.ref, in_schema.attr_types)
        if self.ref != in_schema.stream_id:
            scope.add_stream(in_schema.stream_id, in_schema.attr_types)
        scope.default_ref = self.ref
        for t in (tables or {}).values():
            scope.add_table(t)

        if window_factory is None:
            from siddhi_tpu.core.windows import make_window

            def window_factory(spec, schema, ref, _scope=scope):
                return make_window(
                    spec, schema, ref, _scope, time_capacity=time_capacity,
                    held_cols=held_cols,
                )

        self.chain = CompiledSingleChain(stream, in_schema, scope, window_factory)
        self._scope = scope
        self.selector = CompiledSelector(
            query.selector,
            scope,
            self.chain.out_attrs,  # includes stream-function appended attrs
            batch_mode=self.chain.window is not None and self.chain.window.is_batch,
            group_capacity=group_capacity,
            # a sliding window hands the selector what it lets go
            reclaim=self.chain.window is not None
            and not self.chain.window.is_batch,
            # a window's flow holds what a batch brings and what it pushes out
            flow_rows=None if batch_size is None
            else batch_size * (1 if self.chain.window is None else 2),
        )

        self._setup_output(query, query_id)
        self._attach_tables(tables, interner)
        # batch windows skip their EXPIRED candidate lanes when nothing can
        # observe them: `insert [current] into` output, no rate limiter, and
        # no membership-consuming aggregator (min/max/distinctCount). Halves
        # the flow length every selector op runs over.
        win = self.chain.window
        from siddhi_tpu.core.aggregators import (
            DistinctCountAggregator,
            ExtremeAggregator,
        )

        needs_member = any(
            isinstance(a, DistinctCountAggregator)
            or (isinstance(a, ExtremeAggregator) and not a.forever)
            for a in self.selector.aggregators
        )
        # a time-bounded window steps as a FIFO, in passes (`_step_passes`),
        # where nothing reads what only the matrix step makes: the
        # membership matrix, one window flow per batch (table writes), or
        # an output that holds every EXPIRED row of a step (a query that
        # publishes them: the passes' join keeps the CURRENT rows)
        self._fifo_site = (
            type(self) is QueryRuntime
            and not needs_member
            and self.table_op is None
            and self.output_events is OutputEventsFor.CURRENT
        )
        if win is not None and win.is_batch and hasattr(win, "emit_expired"):
            if (
                self.output_events is OutputEventsFor.CURRENT
                and self.rate_limiter is None
                and not needs_member
            ):
                win.emit_expired = False
        self.needs_scheduler = (
            self.chain.window is not None and self.chain.window.needs_scheduler
        )
        # cron-driven windows compute their next fire host-side
        cron = getattr(self.chain.window, "cron_schedule", None)
        self.host_next_timer = cron.next_fire_ms if cron is not None else None
        # the state pytree is exclusively this query's: donate it so XLA
        # reuses the buffers in place instead of allocating fresh ones
        self._step = jax.jit(self._step_impl, donate_argnums=(0,))

    # ---- device program --------------------------------------------------

    def init_state(self):
        return {"chain": self.chain.init_state(), "sel": self.selector.init_state()}

    def describe_state(self) -> dict:
        d = super().describe_state()
        if self._keyshard is not None:
            d["keyshard"] = self._keyshard.describe_state()
        group = self.selector.group
        if group is not None and group.carry_read is not None:
            with self._receive_lock:  # as the window's, below
                d["group"] = group.describe_state(
                    (self.state or {}).get("sel", {}).get("group"))
        win = self.chain.window
        if win is not None:
            # under the receive lock: the step donates the old state buffers,
            # so an unlocked read could touch already-deleted device arrays
            with self._receive_lock:
                d["window"] = (
                    win.describe_state(self.state["chain"])
                    if self.state is not None
                    else {"type": type(win).__name__, "fill": 0}
                )
        return d

    def arm_lineage(self, cfg) -> None:
        """Enable provenance recording for this query (@app:lineage): the
        chain probe + `__lin.*` step lanes feed a SingleQueryLineage.
        Must run before the first dispatch traces the step (lane structure
        is part of the traced program). Emissions are untouched — lineage
        on/off is byte-parity-safe."""
        from siddhi_tpu.observability.lineage import LIN, SingleQueryLineage

        sel = self.selector
        grouped = sel.group is not None
        if grouped:
            # out rows carry their group key beside them (the rate-limiter
            # mechanism); the key col is NOT part of the out schema, so
            # downstream decode/publish/deliver are unaffected
            sel.emit_group_key = True
        win = self.chain.window
        time_attr = getattr(win, "time_attr", None)

        def probe(flow, _sel=sel, _grouped=grouped, _ta=time_attr):
            b = flow.batch
            flow.aux[LIN + "admit"] = b.valid & (b.kind == KIND_CURRENT)
            if _grouped:
                flow.aux[LIN + "key"] = _sel.group.key_of(flow.env())
            if _ta is not None:
                flow.aux[LIN + "wts"] = b.cols[_ta].astype(jnp.int64)

        self.chain.lineage_probe = probe
        self.lineage = SingleQueryLineage(
            cfg, self.query_id, self._published_kinds(),
            input_stream=self.in_schema.stream_id,
            window=win,
            grouped=grouped,
            aggregated=bool(sel.aggregators),
            order_limited=bool(
                sel.order_by or sel.limit is not None
                or sel.offset is not None
            ),
        )

    def _step_impl(self, state, tstates, batch: EventBatch, now):
        win = self.chain.window
        if (
            self._fifo_site
            and self.lineage is None
            and win is not None
            and win.takes_fifo(batch.capacity)
        ):
            return self._step_passes(state, tstates, batch, now)
        flow = Flow(batch=batch, ref=self.ref, now=now, tables=tstates)
        chain_state, flow = self.chain.apply(state["chain"], flow)
        with jax.named_scope("selector"):
            sel_state, out = self.selector.apply(state["sel"], flow)
        if self.table_op is not None:
            with jax.named_scope("table_op"):
                tstates = self.table_op(tstates, out, now, flow.aux)
        if self.lineage is not None:
            # provenance lanes (observability/lineage.py): extra program
            # OUTPUTS only — the emission lanes above are untouched
            from siddhi_tpu.observability.lineage import LIN

            aux = flow.aux
            aux[LIN + "in"] = batch.valid & (batch.kind == KIND_CURRENT)
            aux[LIN + "in_ts"] = batch.ts
            aux[LIN + "w_valid"] = flow.batch.valid
            aux[LIN + "w_kind"] = flow.batch.kind
            aux[LIN + "w_ts"] = flow.batch.ts
            aux[LIN + "out_valid"] = out.valid
            aux[LIN + "out_kind"] = out.kind
            if "__group_key__" in out.cols:
                aux[LIN + "gkey"] = out.cols["__group_key__"]
        return {"chain": chain_state, "sel": sel_state}, tstates, out, flow.aux

    def _step_passes(self, state, tstates, batch: EventBatch, now):
        """The step behind a time-bounded window that steps as a FIFO
        (`SlidingWindow.fifo_pass`): each pass hands the selector a flow of
        2B rows, at most B of them EXPIRED. One pass is the whole step
        unless more than B rows are due (the first batch after a gap in
        event time): then further passes run, each reading on in the ring
        and carrying the selector's state, until the batch's last CURRENT
        row is out. Their CURRENT outputs are joined in order (the query
        publishes no other kind: `_fifo_site`), so the aggregates stay exact
        however many rows leave. One `while_loop`, so that the
        selector is traced once; the ring is read inside it and written
        after it."""
        before, wscope, after = self.chain.split_at_window()
        win = self.chain.window
        wstate = state["chain"]
        flow = before(Flow(batch=batch, ref=self.ref, now=now, tables=tstates))
        with jax.named_scope(wscope):
            plan = win.fifo_plan(wstate, flow)

        def one_pass(cur, sel_state):
            with jax.named_scope(wscope):
                cur, wflow = win.fifo_pass(wstate, plan, cur, flow)
            wflow = after(wflow)
            with jax.named_scope("selector"):
                sel_state, out = self.selector.apply(sel_state, wflow)
            return cur, sel_state, out, wflow.aux

        cur0 = win.fifo_cursor()
        _, _, out0, aux0 = jax.eval_shape(one_pass, cur0, state["sel"])
        zeros = lambda tree: jax.tree_util.tree_map(  # noqa: E731
            lambda x: jnp.zeros(x.shape, x.dtype), tree
        )

        def body(carry):
            cur, sel_state, acc, aux = carry
            first = cur["passes"] == 0
            cur, sel_state, out, aux_p = one_pass(cur, sel_state)
            acc = jax.lax.cond(
                first, lambda: out, lambda: _join_outputs(acc, out)
            )
            aux = {
                k: aux[k] | jnp.asarray(v).astype(bool).any()
                for k, v in aux_p.items()
            }
            return cur, sel_state, acc, aux

        aux_init = {k: jnp.zeros((), jnp.bool_) for k in aux0}
        cur, sel_state, out, aux = jax.lax.while_loop(
            lambda carry: carry[0]["more"],
            body,
            (cur0, state["sel"], zeros(out0), aux_init),
        )
        with jax.named_scope(wscope):
            chain_state, flags = win.fifo_commit(wstate, plan, cur)
        aux.update(flags)
        return {"chain": chain_state, "sel": sel_state}, tstates, out, aux

    # ---- host side -------------------------------------------------------

    def receive(self, batch: EventBatch, now: int) -> tuple[EventBatch, dict]:
        # shared-window member (core/ingest.py share sets): split any chain
        # buffers a fused dispatch aliased across queries BEFORE this step
        # donates them. Callers hold the app process lock (the lock the
        # fused writeback runs under), so the split cannot race an in-flight
        # fused send. None — one attribute check — for every other query.
        if self._unshare_guard is not None:
            self._unshare_guard()
        with self._receive_lock:
            ks = self._keyshard
            if ks is not None:
                ks.path = "batch"
            if self.state is None:
                self.state = self._fresh((ks or self).init_state())
            tstates = self._collect_table_states()
            with self._step_stage() as clock:
                self.state, tstates, out, aux = self._step(
                    self.state, tstates, batch,
                    jnp.asarray(now, dtype=jnp.int64),
                )
            # compile telemetry: the jit retraces per batch capacity (timer
            # batches, downstream cap-64 re-publishes); a recompile at a
            # seen capacity means the carried state pytree drifted
            # (donation_mismatch)
            self._observe_compile(
                self._step, ("", int(batch.ts.shape[0])), clock.ns
            )
            self._writeback_table_states(tstates)
            lin = self.lineage
            if lin is not None:
                # observe under the receive lock: recorder order must
                # match dispatch order (the lanes are stripped from aux)
                aux = self._lin_observe(lin, aux, now)
        self._warn_aux(aux)
        return out, aux
