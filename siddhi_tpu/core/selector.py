"""Selector compilation: projection + aggregation + group-by + having +
order-by/limit/offset.

Reference: query/selector/QuerySelector.java:44-430 — attribute processors over
each event, aggregator state mutation, group-by key via GroupByKeyGenerator,
having filter, order-by/limit (OrderByEventComparator), then output. Here the
whole selector is one vectorized transform over the Flow; aggregator calls inside
selection expressions are lifted out, computed as running columns, and re-injected
as synthetic attributes of a pseudo-stream "__agg__".
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from siddhi_tpu.core.aggregators import (
    CompiledAggregator,
    ExtremeAggregator,
    FlowInfo,
    build_aggregator,
)
from siddhi_tpu.core.errors import SiddhiAppCreationError
from siddhi_tpu.core.event import EventBatch, KIND_CURRENT, KIND_EXPIRED
from siddhi_tpu.core.executor import (
    CompiledExpr,
    Env,
    Scope,
    compile_expression,
    is_aggregator,
)
from siddhi_tpu.core.flow import Flow
from siddhi_tpu.core.groupby import CompiledGroupBy
from siddhi_tpu.core.types import AttrType
from siddhi_tpu.ops.group import (
    RECLAIM_COUNT,
    RECLAIM_NONE,
    RECLAIM_OWN,
    keep_last_in_sorted,
    keep_last_per_group,
)
from siddhi_tpu.query_api.execution import OutputAttribute, Selector
from siddhi_tpu.query_api.expression import AttributeFunction, Expression, Variable

_AGG_REF = "__agg__"
_BIG = jnp.iinfo(jnp.int32).max


def _lift_aggregators(expr: Expression, found: list[AttributeFunction]) -> Expression:
    """Replace aggregator calls with Variables into the __agg__ pseudo-stream."""
    if is_aggregator(expr):
        found.append(expr)
        return Variable(f"a{len(found) - 1}", stream_id=_AGG_REF)
    if dataclasses.is_dataclass(expr):
        kwargs = {}
        changed = False
        for f in dataclasses.fields(expr):
            v = getattr(expr, f.name)
            if isinstance(v, Expression):
                nv = _lift_aggregators(v, found)
                changed |= nv is not v
                kwargs[f.name] = nv
            elif isinstance(v, list) and v and isinstance(v[0], Expression):
                nv = [_lift_aggregators(x, found) for x in v]
                changed |= any(a is not b for a, b in zip(nv, v))
                kwargs[f.name] = nv
            else:
                kwargs[f.name] = v
        if changed:
            return type(expr)(**kwargs)
    return expr


def _variables(expr, found: set) -> set:
    """Attribute names of every Variable inside `expr`."""
    if isinstance(expr, Variable):
        found.add(expr.attribute)
    elif dataclasses.is_dataclass(expr):
        for f in dataclasses.fields(expr):
            v = getattr(expr, f.name)
            for x in v if isinstance(v, (list, tuple)) else (v,):
                if isinstance(x, Expression):
                    _variables(x, found)
    return found


def aggregate_calls(selector: Selector) -> list[AttributeFunction]:
    """The aggregator calls of a selector's projections and `having`."""
    found: list[AttributeFunction] = []
    for oa in selector.selection_list:
        _lift_aggregators(oa.expression, found)
    if selector.having is not None:
        _lift_aggregators(selector.having, found)
    return found


def aggregate_reads(selector: Selector) -> frozenset:
    """What a row is read for where its own output is not: the attributes
    in the aggregators' arguments and in the group key (an EXPIRED row of
    a query that publishes CURRENT rows alone is read for nothing else)."""
    return frozenset().union(
        *(_variables(p, set()) for call in aggregate_calls(selector)
          for p in call.parameters),
        *(_variables(g, set()) for g in selector.group_by),
    )


class CompiledSelector:
    """Stateful selector stage: (state, Flow) -> (state, output EventBatch)."""

    def __init__(
        self,
        selector: Selector,
        scope: Scope,
        input_attrs: list[tuple[str, AttrType]] | None = None,
        batch_mode: bool = False,
        group_capacity: int | None = None,
        reclaim: bool = False,
        flow_rows: int | None = None,
    ):
        # `flow_rows`: the length of the flows this selector is built for,
        # where the caller knows it and its group table may keep a bucket
        # index (`CompiledGroupBy.probe`).
        # `reclaim`: a window ahead hands this selector the EXPIRED rows of
        # what it lets go, so a group can be seen to empty and its slot in
        # the table be taken back (`_pick_reclaim`)
        self.selector = selector
        self.batch_mode = batch_mode
        sel_list = list(selector.selection_list)
        if selector.select_all:
            if input_attrs is None:
                raise SiddhiAppCreationError("select * unsupported for this input")
            sel_list = [OutputAttribute(None, Variable(n)) for n, _ in input_attrs]

        # group-by (reference: GroupByKeyGenerator over the input meta)
        self.group: CompiledGroupBy | None = None
        if selector.group_by:
            if group_capacity is not None:
                self.group = CompiledGroupBy(
                    selector.group_by, scope, capacity=group_capacity,
                    flow_rows=flow_rows,
                )
            else:
                self.group = CompiledGroupBy(
                    selector.group_by, scope, flow_rows=flow_rows)

        # lift aggregator calls out of the selection expressions
        agg_calls: list[AttributeFunction] = []
        lifted = [(oa.name, _lift_aggregators(oa.expression, agg_calls)) for oa in sel_list]
        self.aggregators: list[CompiledAggregator] = []
        agg_types: dict[str, AttrType] = {}
        for i, call in enumerate(agg_calls):
            args = [compile_expression(p, scope) for p in call.parameters]
            agg = build_aggregator(call.name, args, group=self.group)
            self.aggregators.append(agg)
            agg_types[f"a{i}"] = agg.type

        inner = scope.child()
        inner.add_stream(_AGG_REF, agg_types)
        if inner.default_ref == _AGG_REF:
            inner.default_ref = scope.default_ref

        self.projections: list[tuple[str, CompiledExpr]] = []
        names = set()
        for name, expr in lifted:
            if name in names:
                raise SiddhiAppCreationError(f"duplicate output attribute '{name}'")
            names.add(name)
            self.projections.append((name, compile_expression(expr, inner)))

        self.out_attrs: list[tuple[str, AttrType]] = [
            (n, c.type) for n, c in self.projections
        ]

        # having can reference output attrs (by name) or input attrs
        # (reference: QuerySelector having executor compiled over output meta)
        self.having = None
        if selector.having is not None:
            hav_scope = inner.child()
            hav_scope.add_stream("__out__", dict(self.out_attrs))
            hav_scope.default_ref = scope.default_ref
            lifted_h = _lift_aggregators(selector.having, agg_calls)
            if len(agg_calls) > len(self.aggregators):
                for i in range(len(self.aggregators), len(agg_calls)):
                    call = agg_calls[i]
                    args = [compile_expression(p, scope) for p in call.parameters]
                    agg = build_aggregator(call.name, args, group=self.group)
                    self.aggregators.append(agg)
                    agg_types[f"a{i}"] = agg.type
                inner.add_stream(_AGG_REF, agg_types)  # refresh
            self.having = compile_expression(lifted_h, hav_scope)
            if self.having.type is not AttrType.BOOL:
                raise SiddhiAppCreationError("having must be a boolean expression")

        self._rows_agg: int | None = None
        if self.group is not None and reclaim:
            self._pick_reclaim()

        # order-by: keys resolve against output attrs first, then input streams
        # (reference: OrderByEventComparator over output stream attributes)
        self.order_by: list[tuple[CompiledExpr, bool]] = []
        for ob in selector.order_by:
            var = ob.variable
            out_names = dict(self.out_attrs)
            if var.stream_id is None and var.attribute in out_names:
                cexpr = compile_expression(
                    Variable(var.attribute, stream_id="__out__"), _out_scope(inner, self.out_attrs)
                )
            else:
                cexpr = compile_expression(var, scope)
            if cexpr.type in (AttrType.STRING, AttrType.OBJECT):
                raise SiddhiAppCreationError(
                    "order by on STRING/OBJECT attributes is not supported yet "
                    "(interned ids are not lexicographic)"
                )
            self.order_by.append((cexpr, ob.order.name == "DESC"))
        self.limit = selector.limit
        self.offset = selector.offset

    def _pick_reclaim(self) -> None:
        """Where the table's count of a group's rows comes from: the first
        aggregator that keeps one, else a lane of the table's own. A group
        under minForever / maxForever is never done (the reference's
        canDestroy() is false for them): that table takes nothing back."""
        no_lane = CompiledAggregator.rows_of
        if any(isinstance(a, ExtremeAggregator) and a.forever
               for a in self.aggregators):
            return
        self._rows_agg = next(
            (i for i, a in enumerate(self.aggregators)
             if type(a).rows_of is not no_lane), None)
        if self._rows_agg is None:
            self.group.reclaim = RECLAIM_OWN
        else:
            self.group.reclaim = RECLAIM_COUNT
            self.aggregators[self._rows_agg].counts_rows = True

    def init_state(self):
        st = {"aggs": [a.init() for a in self.aggregators]}
        if self.group is not None:
            st["group"] = self.group.init_state()
        return st

    def apply(self, state, flow: Flow):
        env = flow.env()
        keyed_rows = flow.sign != 0
        group_state = state.get("group")
        ctx = None
        if self.group is not None:
            group_state, ctx = self.group.assign(
                group_state, env, keyed_rows, reset=flow.reset, sign=flow.sign
            )
            # surfaced to the host, which warns on slot-table exhaustion
            flow.aux["groupby_overflow"] = ctx.overflow
        info = FlowInfo(
            sign=flow.sign,
            active=flow.current,
            reset=flow.reset,
            member=flow.member,
            member_env=flow.member_env,
            group=ctx,
        )
        order = list(range(len(self.aggregators)))
        if self._rows_agg is not None:  # says which groups are empty: first
            order.insert(0, order.pop(self._rows_agg))
        new_aggs = [None] * len(order)
        agg_cols: dict = {}
        for i in order:
            new_aggs[i], col = self.aggregators[i].apply(
                state["aggs"][i], info, env
            )
            agg_cols[(_AGG_REF, None, f"a{i}")] = col
        if ctx is not None and self.group.reclaim != RECLAIM_NONE:
            k = self._rows_agg
            group_state = self.group.release(
                group_state, ctx,
                group_state["rows"] if k is None
                else self.aggregators[k].rows_of(new_aggs[k]),
            )
        env2 = Env({**env.columns, **agg_cols}, now=flow.now, tables=env.tables)

        out_cols = {}
        out_col_keys = {}
        for name, cexpr in self.projections:
            col = cexpr(env2)
            col = jnp.broadcast_to(col, flow.batch.valid.shape)
            out_cols[name] = col
            out_col_keys[("__out__", None, name)] = col

        valid = flow.batch.valid & (
            (flow.batch.kind == KIND_CURRENT) | (flow.batch.kind == KIND_EXPIRED)
        )
        env3 = Env({**env2.columns, **out_col_keys}, now=flow.now, tables=env.tables)
        if self.having is not None:
            valid = valid & self.having(env3)

        # batch-mode group-by: one output per key per flush bucket — the last
        # *having-passing* event of each (kind, bucket, key) survives
        # (reference: QuerySelector.processInBatchGroupBy checks having BEFORE
        # groupedEvents.put, so having order matches; the reference's map is
        # kind-agnostic per chunk — we key by (kind, bucket), which only
        # diverges for `output all events` where a bucket's CURRENT would
        # shadow the previous bucket's EXPIRED of the same key)
        if self.batch_mode and ctx is not None:
            # the (reset-era, key) segments of the group-by's sorted view are
            # exactly the (bucket, key) groups — collapse inside it instead of
            # re-lexsorting (ops/group.py:keep_last_in_sorted)
            valid = keep_last_in_sorted(ctx.sorted, flow.batch.kind, valid)
        elif self.batch_mode and self.aggregators:
            # batch + aggregators + no group-by: only the LAST allowed-kind
            # event of each flush chunk survives, carrying the final running
            # aggregate (reference: QuerySelector.processInBatchNoGroupBy —
            # lastEvent spans kinds, restricted by currentOn/expiredOn)
            from siddhi_tpu.query_api.execution import OutputEventsFor

            # a flush CHUNK is [prev-bucket EXPIREDs, RESET, bucket CURRENTs]:
            # expireds precede their reset, so they shift one segment forward
            # to land with their flush's currents
            kind = flow.batch.kind
            seg = jnp.cumsum(flow.reset.astype(jnp.int32)) + (
                kind == KIND_EXPIRED
            ).astype(jnp.int32)
            want = getattr(self, "output_events_for_batch", None)
            if want is OutputEventsFor.EXPIRED:
                allowed = valid & (kind == KIND_EXPIRED)
            elif want is OutputEventsFor.ALL:
                allowed = valid
            else:  # CURRENT (the reference default)
                allowed = valid & (kind == KIND_CURRENT)
            valid = keep_last_per_group([seg], allowed)

        # per-group rate limiters need each row's group key beside it
        # (reference: GroupByKeyGenerator key threading into rate limiters)
        if getattr(self, "emit_group_key", False) and ctx is not None:
            out_cols["__group_key__"] = jnp.broadcast_to(
                ctx.key, flow.batch.valid.shape
            )

        out = EventBatch(
            ts=flow.batch.ts, kind=flow.batch.kind, valid=valid, cols=out_cols
        )
        out = self._order_limit(out, env3)
        new_state = {"aggs": new_aggs}
        if self.group is not None:
            new_state["group"] = group_state
        return new_state, out

    def _order_limit(self, out: EventBatch, env: Env) -> EventBatch:
        """Per-chunk order-by + offset/limit (reference: QuerySelector
        orderEventChunk/limitEventChunk)."""
        if not self.order_by and self.limit is None and self.offset is None:
            return out
        if self.order_by:
            keys = []
            for cexpr, desc in self.order_by:
                col = cexpr(env)
                col = jnp.broadcast_to(col, out.valid.shape)
                if desc:
                    col = -col.astype(jnp.float32) if col.dtype == jnp.bool_ else -col
                keys.append(col)
            # primary = validity (valid rows first), then keys in order;
            # jnp.lexsort treats the LAST key as primary
            perm = jnp.lexsort(tuple(reversed(keys)) + (~out.valid,)).astype(jnp.int32)
            out = EventBatch(
                ts=out.ts[perm],
                kind=out.kind[perm],
                valid=out.valid[perm],
                cols={n: c[perm] for n, c in out.cols.items()},
            )
        if self.limit is not None or self.offset is not None:
            rank = jnp.cumsum(out.valid.astype(jnp.int32)) - out.valid.astype(jnp.int32)
            lo = 0 if self.offset is None else int(self.offset)
            hi = _BIG if self.limit is None else lo + int(self.limit)
            out = EventBatch(
                ts=out.ts,
                kind=out.kind,
                valid=out.valid & (rank >= lo) & (rank < hi),
                cols=out.cols,
            )
        return out


def _out_scope(parent: Scope, out_attrs):
    s = parent.child()
    s.add_stream("__out__", dict(out_attrs))
    return s
