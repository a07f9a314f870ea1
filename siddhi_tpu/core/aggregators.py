"""Attribute aggregators: streaming sum/count/avg/min/max/stdDev/... over batches.

Reference: query/selector/attribute/aggregator/*.java — per-event add on CURRENT,
remove on EXPIRED, zero on RESET, type-specialized inner classes; group-by wraps
each in a per-key map (GroupByAggregationAttributeExecutor.java). Batched here:
per-event running outputs become reset-aware prefix reductions (ops/prefix.py),
or keyed segment reductions over a slot table when a group-by is present
(ops/group.py); min/max/distinct under an upstream window use the window's
membership matrix (exact expiry accounting) instead of incremental remove, which
is the TPU-shaped equivalent of the reference's value-deque bookkeeping.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp
import numpy as np

from siddhi_tpu.core.executor import CompiledExpr, Env
from siddhi_tpu.core.groupby import CompiledGroupBy, GroupCtx
from siddhi_tpu.core.types import AttrType, PHYSICAL_DTYPE, null_value
from siddhi_tpu.ops.group import keyed_running_extreme, keyed_running_sum
from siddhi_tpu.ops.prefix import extreme_identity, running_extreme, running_sum


@dataclasses.dataclass
class FlowInfo:
    """Per-batch signals handed to aggregators by the selector.

    sign:   [B] +1 valid CURRENT, -1 valid EXPIRED, 0 otherwise
    active: [B] valid CURRENT rows
    reset:  [B] valid RESET rows
    member / member_env: optional [B, K] window membership matrix (row i = the
        window contents as seen just after event i) and an Env over the K-long
        window columns — provided by window stages for exact min/max/distinct.
    group:  optional GroupCtx when the selector has a group-by.
    """

    sign: jnp.ndarray
    active: jnp.ndarray
    reset: jnp.ndarray
    member: Optional[jnp.ndarray] = None
    member_env: Optional[Env] = None
    group: Optional[GroupCtx] = None


class CompiledAggregator:
    """One aggregator instance in a selector; owns a slice of query state.

    When `group` is set, state arrays gain a leading [G] axis indexed by the
    GroupCtx slot lane.
    """

    type: AttrType
    group: Optional[CompiledGroupBy] = None
    # set by the selector on the one aggregator whose count of rows tells a
    # table that takes slots back which groups are empty (ops/group.py
    # `keyed_running_sum(rows=True)`); it then runs before the others
    counts_rows: bool = False

    @staticmethod
    def rows_of(state):
        """The [G] lane of `state` that counts each group's rows of the
        window, where the aggregator keeps one (count, avg, stdDev)."""
        return None

    def _shape(self):
        return (self.group.capacity,) if self.group is not None else ()

    def init(self):  # -> pytree of device arrays
        raise NotImplementedError

    def apply(self, state, flow: FlowInfo, env: Env):  # -> (state', [B] col)
        raise NotImplementedError

    def _run_sum(self, state, contrib, flow: FlowInfo, rows: bool = False):
        if flow.group is not None:
            return keyed_running_sum(contrib, flow.group.sorted, state, rows=rows)
        run, carry = running_sum(contrib, flow.reset, state)
        return run, carry


def _null_arr(t: AttrType):
    # numpy (NOT jnp): trace-time const — a jax.Array here would be read
    # back from the device at every lowering (see executor._const_expr).
    return np.asarray(null_value(t), dtype=PHYSICAL_DTYPE[t])


class SumAggregator(CompiledAggregator):
    """sum(): LONG for int/long input, DOUBLE for float/double
    (reference: SumAttributeAggregator.java type matrix)."""

    def __init__(self, arg: CompiledExpr, group=None):
        self.arg = arg
        self.group = group
        self.type = (
            AttrType.LONG if arg.type in (AttrType.INT, AttrType.LONG) else AttrType.DOUBLE
        )
        self.dtype = PHYSICAL_DTYPE[self.type]

    def init(self):
        return jnp.zeros(self._shape(), dtype=self.dtype)

    def apply(self, state, flow: FlowInfo, env: Env):
        x = self.arg(env).astype(self.dtype)
        contrib = jnp.where(flow.sign != 0, x * flow.sign.astype(self.dtype), 0)
        return _swap(self._run_sum(state, contrib, flow))


class CountAggregator(CompiledAggregator):
    type = AttrType.LONG

    def __init__(self, group=None):
        self.group = group

    def init(self):
        return jnp.zeros(self._shape(), dtype=jnp.int64)

    @staticmethod
    def rows_of(state):
        return state

    def apply(self, state, flow: FlowInfo, env: Env):
        return _swap(self._run_sum(
            state, flow.sign.astype(jnp.int64), flow, rows=self.counts_rows
        ))


def _swap(t):
    run, carry = t
    return carry, run


class AvgAggregator(CompiledAggregator):
    """DOUBLE average; null (NaN) when count == 0, matching the reference
    (reference: AvgAttributeAggregator.java:164-166 returns null on count 0)."""

    type = AttrType.DOUBLE

    def __init__(self, arg: CompiledExpr, group=None):
        self.arg = arg
        self.group = group

    def init(self):
        z = jnp.zeros(self._shape(), dtype=jnp.float32)
        return {"sum": z, "count": z}

    @staticmethod
    def rows_of(state):
        return state["count"]

    def apply(self, state, flow: FlowInfo, env: Env):
        x = self.arg(env).astype(jnp.float32)
        sgn = flow.sign.astype(jnp.float32)
        # the count first: where it tells the table which groups are empty,
        # the sum writes its identity there
        c_run, c_carry = self._run_sum(
            state["count"], sgn, flow, rows=self.counts_rows
        )
        s_run, s_carry = self._run_sum(
            state["sum"], jnp.where(flow.sign != 0, x * sgn, 0.0), flow
        )
        out = jnp.where(c_run != 0, s_run / jnp.where(c_run != 0, c_run, 1.0), jnp.nan)
        return {"sum": s_carry, "count": c_carry}, out


class StdDevAggregator(CompiledAggregator):
    """Population std-dev from running sum/sumsq/count
    (reference: StdDevAttributeAggregator.java)."""

    type = AttrType.DOUBLE

    def __init__(self, arg: CompiledExpr, group=None):
        self.arg = arg
        self.group = group

    def init(self):
        z = jnp.zeros(self._shape(), dtype=jnp.float32)
        return {"sum": z, "sumsq": z, "count": z}

    @staticmethod
    def rows_of(state):
        return state["count"]

    def apply(self, state, flow: FlowInfo, env: Env):
        x = self.arg(env).astype(jnp.float32)
        sgn = flow.sign.astype(jnp.float32)
        c_run, c_c = self._run_sum(state["count"], sgn, flow, rows=self.counts_rows)
        s_run, s_c = self._run_sum(state["sum"], jnp.where(flow.sign != 0, x * sgn, 0.0), flow)
        q_run, q_c = self._run_sum(state["sumsq"], jnp.where(flow.sign != 0, x * x * sgn, 0.0), flow)
        safe_n = jnp.where(c_run != 0, c_run, 1.0)
        mean = s_run / safe_n
        var = jnp.maximum(q_run / safe_n - mean * mean, 0.0)
        out = jnp.where(c_run != 0, jnp.sqrt(var), jnp.nan)
        return {"sum": s_c, "sumsq": q_c, "count": c_c}, out


class ExtremeAggregator(CompiledAggregator):
    """min/max. Exact under windows via the membership matrix; running
    (monotone) otherwise. minForever/maxForever always run monotone
    (reference: MinForeverAttributeAggregator.java ignores expiry)."""

    def __init__(self, arg: CompiledExpr, is_min: bool, forever: bool, group=None):
        self.arg = arg
        self.group = group
        self.type = arg.type
        self.dtype = PHYSICAL_DTYPE[arg.type]
        self.is_min = is_min
        self.forever = forever

    def init(self):
        ident = extreme_identity(self.dtype, self.is_min)
        return jnp.full(self._shape(), ident, dtype=self.dtype)

    def apply(self, state, flow: FlowInfo, env: Env):
        ident = extreme_identity(self.dtype, self.is_min)
        if not self.forever and flow.member is not None:
            vals = self.arg(flow.member_env).astype(self.dtype)
            member = flow.member
            if flow.group is not None:
                # restrict membership to window elements in the same group
                elem_key = flow.group.key_of(flow.member_env)
                member = member & (elem_key[None, :] == flow.group.key[:, None])
            masked = jnp.where(member, vals[None, :], ident)
            red = masked.min(axis=-1) if self.is_min else masked.max(axis=-1)
            return state, jnp.where(red == ident, _null_arr(self.type), red)
        x = self.arg(env).astype(self.dtype)
        if flow.group is not None:
            run, carry = keyed_running_extreme(
                x, flow.active, flow.group.sorted, state, self.is_min,
                forever=self.forever,
            )
        else:
            reset = jnp.zeros_like(flow.reset) if self.forever else flow.reset
            run, carry = running_extreme(x, flow.active, reset, state, self.is_min)
        return carry, jnp.where(run == ident, _null_arr(self.type), run)


class DistinctCountAggregator(CompiledAggregator):
    """distinctCount under a window: per-event distinct member values via the
    membership matrix (reference: DistinctCountAttributeAggregator.java keeps a
    value->count map; the window columns make this a pure reduction here)."""

    type = AttrType.LONG

    def __init__(self, arg: CompiledExpr, group=None):
        self.arg = arg
        self.group = group

    def init(self):
        return jnp.zeros((), dtype=jnp.int64)

    def apply(self, state, flow: FlowInfo, env: Env):
        if flow.member is None:
            raise NotImplementedError(
                "distinctCount requires an upstream window (unbounded distinct "
                "state is capacity-unbounded; the reference grows a map forever)"
            )
        vals = self.arg(flow.member_env)
        member = flow.member
        if flow.group is not None:
            elem_key = flow.group.key_of(flow.member_env)
            member = member & (elem_key[None, :] == flow.group.key[:, None])
        k = vals.shape[-1]
        eq = vals[None, :] == vals[:, None]  # [K, K]
        earlier = jnp.tril(jnp.ones((k, k), dtype=bool), k=-1)
        # member j is a duplicate within row i if some earlier member j' < j
        # holds an equal value
        dup = ((eq & earlier)[None, :, :] & member[:, None, :]).any(axis=-1)
        firsts = member & ~dup
        return state, firsts.sum(axis=-1).astype(jnp.int64)


# the aggregators that read the window's membership view, by their lowered
# names (a non-forever `ExtremeAggregator`, `DistinctCountAggregator`)
MEMBER_AGGREGATORS = frozenset({"min", "max", "distinctcount"})


def build_aggregator(
    name: str, args: list[CompiledExpr], group: Optional[CompiledGroupBy] = None
) -> CompiledAggregator:
    low = name.lower()
    if low == "count":
        return CountAggregator(group=group)
    if not args:
        raise TypeError(f"aggregator '{name}' needs an argument")
    arg = args[0]
    if low == "sum":
        return SumAggregator(arg, group=group)
    if low == "avg":
        return AvgAggregator(arg, group=group)
    if low == "stddev":
        return StdDevAggregator(arg, group=group)
    if low == "min":
        return ExtremeAggregator(arg, is_min=True, forever=False, group=group)
    if low == "max":
        return ExtremeAggregator(arg, is_min=False, forever=False, group=group)
    if low == "minforever":
        return ExtremeAggregator(arg, is_min=True, forever=True, group=group)
    if low == "maxforever":
        return ExtremeAggregator(arg, is_min=False, forever=True, group=group)
    if low == "distinctcount":
        return DistinctCountAggregator(arg, group=group)
    raise TypeError(f"unknown aggregator '{name}'")
