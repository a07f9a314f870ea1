"""Join runtime: two windowed sides probing each other on device.

Reference: query/input/stream/join/JoinProcessor.java:34-200 — each arriving
event locks, probes the *other* side's window via FindableProcessor.find,
builds joined StateEvents; JoinInputStreamParser.java wires
filter -> preJoinProcessor -> window -> postJoinProcessor per side, with
left/right/full outer null-filling and unidirectional trigger control.

Here each side's probe is one masked [B, W] condition evaluation on device:
arriving rows broadcast against the other window's stored contents, matched
pairs compacted to a fixed-capacity joined output batch, outer-join misses
ride an extra "null partner" column of the mask.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from siddhi_tpu.core.errors import SiddhiAppCreationError
from siddhi_tpu.core.event import (
    EventBatch,
    KIND_CURRENT,
    KIND_EXPIRED,
    KIND_TIMER,
    StreamSchema,
)
from siddhi_tpu.core.executor import Env, Scope, TS_ATTR, compile_expression
from siddhi_tpu.core.flow import Flow
from siddhi_tpu.core.selector import CompiledSelector
from siddhi_tpu.core.types import AttrType, null_value
from siddhi_tpu.core.windows import WindowStage, make_window
from siddhi_tpu.query_api.execution import (
    Filter,
    JoinEventTrigger,
    JoinInputStream,
    JoinType,
    OutputEventsFor,
    Query,
    SingleInputStream,
    StreamFunctionHandler,
    WindowHandler,
)

DEFAULT_JOIN_CAPACITY = 512


class NoWindow(WindowStage):
    """A join side with no #window: arrivals probe but are never retained
    (reference: JoinInputStreamParser wraps windowless sides in a zero-length
    LengthWindowProcessor, JoinInputStreamParser.java:128-146)."""

    def __init__(self, schema: StreamSchema, ref: str):
        self.schema = schema
        self.ref = ref

    def init_state(self):
        return {}

    def apply(self, state, flow: Flow):
        b = flow.batch
        empty = EventBatch(b.ts, b.kind, jnp.zeros_like(b.valid), b.cols)
        return state, dataclasses.replace(flow, batch=empty)

    def view(self, state):
        cols = {
            n: jnp.zeros((1,), a.dtype)
            for n, a in self.schema.empty_batch(1).cols.items()
        }
        return cols, jnp.zeros((1,), jnp.int64), jnp.zeros((1,), jnp.bool_)

    def view_seq(self, state):
        return jnp.full((1,), -1, jnp.int64)


class TableSide:
    """A join side backed by a shared findable: a table (reference:
    TableWindowProcessor — probe-only, never triggers) or a named window
    (reference: WindowWindowProcessor — its emission stream actively drives
    the join while probes read the shared buffer)."""

    is_table = True

    def __init__(self, stream: SingleInputStream, table):
        if stream.handlers:
            raise SiddhiAppCreationError(
                f"'{stream.stream_id}' cannot carry filters/windows "
                "on a join side"
            )
        self.stream_id = stream.stream_id
        self.ref = stream.ref
        self.schema = table.schema
        self.table = table
        self.window = None
        # tables are passive probe targets; named windows also trigger
        self.passive = not getattr(table, "is_named_window", False)

    def init_state(self):
        return {}

    def filter_batch(self, batch: EventBatch, now) -> EventBatch:
        return batch

    def probe_view(self, state_slice, tstates):
        return self.table.view(tstates[self.table.table_id])

    def probe_seq(self, state_slice):
        return None  # findables carry no admission order


class JoinSide:
    """One side of the join: pre-window filters + window stage."""

    is_table = False
    passive = False

    def __init__(
        self,
        stream: SingleInputStream,
        schema: StreamSchema,
        scope: Scope,
        time_capacity: Optional[int] = None,
    ):
        self.stream_id = stream.stream_id
        self.ref = stream.ref
        self.schema = schema
        side_scope = scope.child()
        side_scope.default_ref = self.ref
        self.pre_filters = []
        self.window: WindowStage | None = None
        for h in stream.handlers:
            if isinstance(h, Filter):
                if self.window is not None:
                    raise SiddhiAppCreationError(
                        "filters after the window are not supported on join sides"
                    )
                cond = compile_expression(h.expression, side_scope)
                if cond.type is not AttrType.BOOL:
                    raise SiddhiAppCreationError("filter must be a boolean expression")
                self.pre_filters.append(cond)
            elif isinstance(h, WindowHandler):
                if self.window is not None:
                    raise SiddhiAppCreationError("only one window per join side")
                self.window = make_window(
                    h.window, schema, self.ref, side_scope,
                    time_capacity=time_capacity,
                )
            elif isinstance(h, StreamFunctionHandler):
                raise SiddhiAppCreationError(
                    f"stream function '{h.name}' not supported on join sides yet"
                )
        if self.window is None:
            self.window = NoWindow(schema, self.ref)

    def init_state(self):
        return self.window.init_state()

    def probe_view(self, state_slice, tstates):
        return self.window.view(state_slice)

    def probe_seq(self, state_slice):
        """Window admission seq per view slot (lineage), or None."""
        return self.window.view_seq(state_slice)

    def filter_batch(self, batch: EventBatch, now) -> EventBatch:
        if not self.pre_filters:
            return batch
        cols = {(self.ref, None, n): c for n, c in batch.cols.items()}
        cols[(self.ref, None, TS_ATTR)] = batch.ts
        env = Env(cols, now=now)
        mask = None
        for c in self.pre_filters:
            m = c(env)
            mask = m if mask is None else (mask & m)
        is_timer = batch.kind == KIND_TIMER  # timers bypass filters
        return EventBatch(
            batch.ts, batch.kind, batch.valid & (is_timer | mask), batch.cols
        )


class CompiledJoin:
    """Device-side join core: per-arrival-side step producing a joined batch
    whose columns carry both refs (left primary, right in extra cols)."""

    def __init__(
        self,
        join: JoinInputStream,
        left_schema: StreamSchema,
        right_schema: StreamSchema,
        scope: Scope,
        out_capacity: int = DEFAULT_JOIN_CAPACITY,
        output_expired: bool = False,
        tables: Optional[dict] = None,
        time_capacity: Optional[int] = None,
    ):
        tables = tables or {}

        def make_side(stream, schema):
            t = tables.get(stream.stream_id)
            if t is not None:
                return TableSide(stream, t)
            return JoinSide(stream, schema, scope, time_capacity)

        self.left = make_side(join.left, left_schema)
        self.right = make_side(join.right, right_schema)
        if self.left.passive and self.right.passive:
            raise SiddhiAppCreationError("cannot join two tables; use a store query")
        if self.left.ref == self.right.ref:
            raise SiddhiAppCreationError(
                f"join sides must have distinct references; alias one: "
                f"'from {self.left.stream_id} as a join ...'"
            )
        self.join_type = join.join_type
        self.out_capacity = int(out_capacity)
        self.output_expired = output_expired
        # unidirectional narrows the trigger side
        # (reference: JoinInputStreamParser.java:214-231)
        trigger = join.trigger
        if join.unidirectional == "left":
            if self.left.passive:
                raise SiddhiAppCreationError(
                    "unidirectional cannot be set on the table side of a join"
                )
            trigger = JoinEventTrigger.LEFT
        elif join.unidirectional == "right":
            if self.right.passive:
                raise SiddhiAppCreationError(
                    "unidirectional cannot be set on the table side of a join"
                )
            trigger = JoinEventTrigger.RIGHT
        self.emit_left = (
            trigger in (JoinEventTrigger.ALL, JoinEventTrigger.LEFT)
            and not self.left.passive
        )
        self.emit_right = (
            trigger in (JoinEventTrigger.ALL, JoinEventTrigger.RIGHT)
            and not self.right.passive
        )
        self.on = None
        if join.on is not None:
            cond = compile_expression(join.on, scope)
            if cond.type is not AttrType.BOOL:
                raise SiddhiAppCreationError("join 'on' must be a boolean expression")
            self.on = cond
        # lineage (observability/lineage.py): when True the step emits
        # `__lin.*` aux lanes — per matched output row the probe-row index
        # and the partner ring's admission seq. Set by
        # JoinQueryRuntime.arm_lineage before the first trace.
        self.lineage = False

    def init_state(self):
        return {"l": self.left.init_state(), "r": self.right.init_state()}

    # ---- device step for one arriving side -------------------------------

    def step(self, state, batch: EventBatch, now, side: str, tstates=None):
        """side: 'l' | 'r'. Returns (state', joined Flow, aux)."""
        arr = self.left if side == "l" else self.right
        other = self.right if side == "l" else self.left
        other_key = "r" if side == "l" else "l"
        emits = self.emit_left if side == "l" else self.emit_right
        batch = arr.filter_batch(batch, now)
        aux: dict = {}
        if self.lineage:
            from siddhi_tpu.observability.lineage import LIN

            # the arriving side's window admissions: its filter-passing
            # CURRENT rows (table/named-window arrivals never re-buffer)
            aux[LIN + "admit"] = (
                batch.valid & (batch.kind == KIND_CURRENT)
                if not arr.is_table
                else jnp.zeros_like(batch.valid)
            )

        vcols, vts, vmask = other.probe_view(state[other_key], tstates or {})
        vseq = other.probe_seq(state[other_key]) if self.lineage else None

        # probe 1: arriving CURRENT rows against the other window
        # (reference: preJoinProcessor — probe happens BEFORE own-window insert)
        cur_rows = batch.valid & (batch.kind == KIND_CURRENT)

        if arr.is_table:
            # named-window side: arrivals are the window's emission stream —
            # they probe the other side but never re-buffer (the shared window
            # state already holds them); its EXPIRED emissions feed probe 2
            wstate = state[side]
            exp_src = batch
        else:
            # own-window insert; its EXPIRED output feeds probe 2
            flow_in = Flow(batch=batch, ref=arr.ref, now=now)
            wstate, wflow = arr.window.apply(state[side], flow_in)
            if "next_timer" in wflow.aux:
                aux["next_timer"] = wflow.aux["next_timer"]
            exp_src = wflow.batch

        probes = [(batch, cur_rows, np.int8(KIND_CURRENT))]
        if self.output_expired and emits:
            exp_rows = exp_src.valid & (exp_src.kind == KIND_EXPIRED)
            probes.append((exp_src, exp_rows, np.int8(KIND_EXPIRED)))
        if not emits:
            probes = []

        joined = self._assemble(
            probes, arr, other, vcols, vts, vmask, now, side, aux, tstates,
            vseq=vseq,
        )

        new_state = dict(state)
        new_state[side] = wstate
        return new_state, joined, aux

    def _assemble(
        self, probes, arr, other, vcols, vts, vmask, now, side, aux,
        tstates=None, vseq=None,
    ):
        """Evaluate the on-condition for each probe set, compact matched pairs
        (plus outer misses) into one fixed-capacity joined Flow."""
        cap = self.out_capacity
        w = vmask.shape[0]
        outer = (
            self.join_type is JoinType.FULL_OUTER
            or (side == "l" and self.join_type is JoinType.LEFT_OUTER)
            or (side == "r" and self.join_type is JoinType.RIGHT_OUTER)
        )

        if probes:
            row_ts = jnp.concatenate([b.ts for b, _, _ in probes])
            row_mask = jnp.concatenate([m for _, m, _ in probes])
            row_kind = jnp.concatenate(
                [jnp.full(m.shape, k, jnp.int8) for _, m, k in probes]
            )
            row_cols = {
                n: jnp.concatenate([b.cols[n] for b, _, _ in probes])
                for n in probes[0][0].cols
            }
        else:  # non-triggering side: empty probe set
            row_ts = jnp.zeros((1,), jnp.int64)
            row_mask = jnp.zeros((1,), jnp.bool_)
            row_kind = jnp.zeros((1,), jnp.int8)
            row_cols = {
                n: jnp.zeros((1,), a.dtype)
                for n, a in arr.schema.empty_batch(1).cols.items()
            }

        env_cols = {(arr.ref, None, n): c[:, None] for n, c in row_cols.items()}
        env_cols[(arr.ref, None, TS_ATTR)] = row_ts[:, None]
        env_cols.update({(other.ref, None, n): c[None, :] for n, c in vcols.items()})
        env_cols[(other.ref, None, TS_ATTR)] = vts[None, :]
        env = Env(env_cols, now=now)

        pair = row_mask[:, None] & vmask[None, :]
        if self.on is not None:
            pair = pair & self.on(env)

        if outer:
            missed = row_mask & ~pair.any(axis=1)
            pair = jnp.concatenate([pair, missed[:, None]], axis=1)  # col w = nulls
        wj = pair.shape[1]

        n_matches = pair.sum()
        aux["join_overflow"] = n_matches > cap

        flat = pair.reshape(-1)
        # compact match indices WITHOUT a device sort (nonzero lowers to one):
        # rank matched cells by prefix count and scatter their indices
        rank = jnp.cumsum(flat.astype(jnp.int32)) - flat
        pos = jnp.where(flat & (rank < cap), rank, cap)
        idx = (
            jnp.full((cap,), -1, jnp.int32)
            .at[pos]
            .set(jnp.arange(flat.shape[0], dtype=jnp.int32), mode="drop")
        )
        valid_out = idx >= 0
        pi = jnp.clip(idx // wj, 0, row_mask.shape[0] - 1)
        pj_raw = jnp.where(idx >= 0, idx % wj, w)
        is_null_partner = pj_raw >= w
        pj = jnp.clip(pj_raw, 0, w - 1)

        def partner_col(name, t):
            base = vcols[name][pj]
            return jnp.where(is_null_partner, np.asarray(null_value(t), base.dtype), base)

        if self.lineage:
            from siddhi_tpu.observability.lineage import LIN

            # per matched output row: the triggering probe-row index and
            # the partner window's admission seq (-1 = null/unknown) —
            # the host recorder turns these into the (left seq, right seq)
            # provenance pair (observability/lineage.py JoinQueryLineage)
            aux[LIN + "j_pi"] = jnp.where(valid_out, pi, np.int32(-1))
            if vseq is not None:
                aux[LIN + "j_pseq"] = jnp.where(
                    valid_out & ~is_null_partner, vseq[pj], np.int64(-1)
                )
            else:
                # no admission order on this partner (batch window, table,
                # named window): -2 = "partner unknown" — the recorder
                # flags the record approximate, distinct from -1 = "outer
                # join, legitimately no partner"
                aux[LIN + "j_pseq"] = jnp.where(
                    valid_out & ~is_null_partner,
                    np.int64(-2), np.int64(-1),
                )

        arr_out = {n: c[pi] for n, c in row_cols.items()}
        other_out = {
            n: partner_col(n, t) for n, t in other.schema.attr_types.items()
        }
        other_ts = jnp.where(is_null_partner, np.int64(0), vts[pj])

        out_ts = row_ts[pi]
        # primary batch always carries LEFT-side cols for a stable selector
        # layout; only the per-ref timestamps depend on the arrival side
        if side == "l":
            left_cols, right_cols = arr_out, other_out
            left_ts, right_ts = out_ts, other_ts
        else:
            left_cols, right_cols = other_out, arr_out
            left_ts, right_ts = other_ts, out_ts

        batch = EventBatch(out_ts, row_kind[pi], valid_out, left_cols)
        extra = {(self.right.ref, None, n): c for n, c in right_cols.items()}
        extra[(self.right.ref, None, TS_ATTR)] = right_ts
        extra[(self.left.ref, None, TS_ATTR)] = left_ts
        return Flow(
            batch=batch, ref=self.left.ref, now=now, extra_cols=extra, aux=aux,
            tables=tstates or {},
        )


from siddhi_tpu.core.query_runtime import BaseQueryRuntime


class JoinQueryRuntime(BaseQueryRuntime):
    """Compiled join query + device state + host routing
    (reference: JoinStreamRuntime + QueryRuntime)."""

    def __init__(
        self,
        query: Query,
        query_id: str,
        left_schema: StreamSchema,
        right_schema: StreamSchema,
        interner,
        group_capacity: Optional[int] = None,
        join_capacity: int = DEFAULT_JOIN_CAPACITY,
        tables: Optional[dict] = None,
        findables: Optional[dict] = None,
        time_capacity: Optional[int] = None,
    ):
        join = query.input_stream
        assert isinstance(join, JoinInputStream)
        self.query = query
        self.query_id = query_id

        scope = Scope(interner)
        self._scope = scope
        lref, rref = join.left.ref, join.right.ref
        scope.add_stream(lref, left_schema.attr_types)
        scope.add_stream(rref, right_schema.attr_types)
        scope.default_ref = lref
        for t in (tables or {}).values():
            scope.add_table(t)

        output_expired = query.output_stream.output_events is not OutputEventsFor.CURRENT
        self.join = CompiledJoin(
            join,
            left_schema,
            right_schema,
            scope,
            out_capacity=join_capacity,
            output_expired=output_expired,
            tables=findables if findables is not None else tables,
            time_capacity=time_capacity,
        )
        # findable join sides that are NOT app tables (named windows): their
        # live state is read-only threaded into the step
        self.join_findables = {}
        for side_obj in (self.join.left, self.join.right):
            if side_obj.is_table and side_obj.table.table_id not in (tables or {}):
                self.join_findables[side_obj.table.table_id] = side_obj.table
        combined_attrs = [
            (n, t) for n, t in left_schema.attrs
        ] + [(n, t) for n, t in right_schema.attrs]
        self.selector = CompiledSelector(
            query.selector,
            scope,
            input_attrs=combined_attrs,
            batch_mode=False,
            group_capacity=group_capacity,
        )
        self._setup_output(query, query_id)
        self._attach_tables(tables, interner)

        self.needs_scheduler = {
            "l": not self.join.left.is_table and self.join.left.window.needs_scheduler,
            "r": not self.join.right.is_table and self.join.right.window.needs_scheduler,
        }
        # findable sides have no junction of their own; active (named-window)
        # sides are instead driven by the window's emission junction
        self.table_sides = {
            "l": self.join.left.is_table,
            "r": self.join.right.is_table,
        }
        self.window_sides = {
            "l": self.join.left.table
            if self.join.left.is_table and not self.join.left.passive
            else None,
            "r": self.join.right.table
            if self.join.right.is_table and not self.join.right.passive
            else None,
        }
        self.side_schemas = {"l": left_schema, "r": right_schema}
        self.timer_targets: dict[str, object] = {}
        self._steps = {
            "l": jax.jit(
                lambda st, ts, b, now: self._step_impl(st, ts, b, now, "l"),
                donate_argnums=(0,),
            ),
            "r": jax.jit(
                lambda st, ts, b, now: self._step_impl(st, ts, b, now, "r"),
                donate_argnums=(0,),
            ),
        }

    def init_state(self):
        return {"join": self.join.init_state(), "sel": self.selector.init_state()}

    def describe_state(self) -> dict:
        """Introspection: per-side window buffers (table/named-window sides
        are shared findables reported under their own component)."""
        d = super().describe_state()
        for key, side in (("left", self.join.left), ("right", self.join.right)):
            w = getattr(side, "window", None)
            if w is None:
                d[key] = {"type": "findable", "ref": side.stream_id}
                continue
            sk = "l" if key == "left" else "r"
            # under the receive lock: the step donates old state buffers, so
            # an unlocked read could touch already-deleted device arrays
            with self._receive_lock:
                d[key] = (
                    w.describe_state(self.state["join"][sk])
                    if self.state is not None
                    else {"type": type(w).__name__, "fill": 0}
                )
        return d

    def arm_lineage(self, cfg) -> None:
        """Enable provenance recording (@app:lineage): the join step emits
        `__lin.*` lanes — (probe row, partner ring seq) per matched output
        row — feeding a JoinQueryLineage. Must run before the first trace;
        emissions are untouched."""
        from siddhi_tpu.observability.lineage import JoinQueryLineage

        self.join.lineage = True
        self.lineage = JoinQueryLineage(
            cfg, self.query_id, self._published_kinds(),
            left_stream=self.join.left.stream_id,
            right_stream=self.join.right.stream_id,
            batch_capacity=0,  # recorder sizes probes off the in-lane
        )

    def _step_impl(self, state, tstates, batch: EventBatch, now, side: str):
        jstate, flow, aux = self.join.step(state["join"], batch, now, side, tstates)
        sel_state, out = self.selector.apply(state["sel"], flow)
        if self.table_op is not None:
            tstates = self.table_op(tstates, out, now, flow.aux)
        aux.update(flow.aux)
        if self.lineage is not None:
            from siddhi_tpu.core.event import KIND_CURRENT
            from siddhi_tpu.observability.lineage import LIN

            aux[LIN + "in"] = batch.valid & (batch.kind == KIND_CURRENT)
            aux[LIN + "in_ts"] = batch.ts
            aux[LIN + "out_valid"] = out.valid
            aux[LIN + "out_kind"] = out.kind
            aux[LIN + "out_ts"] = out.ts
        return {"join": jstate, "sel": sel_state}, tstates, out, aux

    def receive(self, batch: EventBatch, now: int, side: str):
        with self._receive_lock:
            if self.state is None:
                self.state = self._fresh(self.init_state())
            tstates = self._collect_table_states()
            with self._step_stage() as clock:
                self.state, tstates, out, aux = self._steps[side](
                    self.state, tstates, batch,
                    jnp.asarray(now, dtype=jnp.int64),
                )
            # one jitted program per join side: the telemetry component
            # embeds the side (see BaseQueryRuntime._observe_compile)
            self._observe_compile(
                self._steps[side], (side, int(batch.ts.shape[0])), clock.ns
            )
            self._writeback_table_states(tstates)
            lin = self.lineage
            if lin is not None:
                # under the receive lock: recorder order == dispatch order
                aux = self._lin_observe(lin, aux, now, tag=side)
        self._warn_aux(aux)
        return out, aux
