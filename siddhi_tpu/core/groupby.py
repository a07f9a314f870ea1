"""Compiled group-by: key generation + persistent slot table.

Reference: query/selector/GroupByKeyGenerator.java builds a string key per
event; QuerySelector.java:167-226 keeps per-key aggregator state in maps keyed
by that string. Here the key is an int64 device column, the map is a
fixed-capacity device key table (ops/group.py:assign_slots), and aggregator
state is a [G]-array slice per aggregator.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from siddhi_tpu.core.errors import SiddhiAppCreationError
from siddhi_tpu.core.executor import CompiledExpr, Env, Scope, compile_expression
from siddhi_tpu.core.types import AttrType
from siddhi_tpu.ops.group import (
    PROBE_BUCKET,
    RECLAIM_NONE,
    RECLAIM_OWN,
    SortedGroups,
    assign_slots,
    empty_index,
    free_stack,
    keyed_running_sum,
    mix_keys,
    probe_for,
    release_index,
    release_slots,
)
from siddhi_tpu.query_api.expression import Variable

DEFAULT_GROUP_CAPACITY = 1024


def _as_key_col(col: jnp.ndarray, t: AttrType) -> jnp.ndarray:
    """Integer-encode one key column (floats are bitcast so distinct payloads
    stay distinct; strings are already interned ids)."""
    if t in (AttrType.FLOAT, AttrType.DOUBLE):
        return jnp.asarray(col).view(jnp.int32).astype(jnp.int64)
    return col.astype(jnp.int64)


@dataclasses.dataclass
class GroupCtx:
    """Per-batch group context handed to aggregators via FlowInfo."""

    slot: jnp.ndarray    # [B] int32; == capacity for non-keyed rows
    key: jnp.ndarray     # [B] int64
    sorted: SortedGroups  # lexsorted (era, key) view for segmented reductions,
    # with the step's read plan for the groups' carried values
    capacity: int
    key_of: Callable[[Env], jnp.ndarray]  # env -> int64 key column (any length)
    overflow: jnp.ndarray = None  # scalar bool


class CompiledGroupBy:
    def __init__(
        self,
        group_by: list[Variable],
        scope: Scope,
        capacity: int = DEFAULT_GROUP_CAPACITY,
        flow_rows: Optional[int] = None,
    ):
        if not group_by:
            raise SiddhiAppCreationError("empty group by")
        self.capacity = int(capacity)
        # how a row finds its slot (ops/group.py PROBE_*): by the merge, or,
        # where the table is much larger than the flow the selector is built
        # for (`flow_rows`; None where the caller keeps no index), through
        # the bucket index in the table's state
        self.probe: str = probe_for(self.capacity, flow_rows)
        # how the last trace read the groups' carried values: "segment" (once
        # per segment of the sorted view) or "row"; None before the first trace
        self.carry_read: Optional[str] = None
        # how the table learns that a group holds no row of the window any
        # more (ops/group.py RECLAIM_*). The selector sets it where a window
        # hands it EXPIRED rows; then a group's slot is unused again at the
        # end of the step in which its last row left (reference: Siddhi 5
        # drops a group's state once its aggregators say canDestroy()), and
        # the capacity is of the groups alive at once, not of all ever seen
        self.reclaim: str = RECLAIM_NONE
        self.keys: list[CompiledExpr] = [
            compile_expression(v, scope) for v in group_by
        ]
        for v, c in zip(group_by, self.keys):
            if c.type is AttrType.OBJECT:
                raise SiddhiAppCreationError(
                    f"cannot group by OBJECT attribute '{v.attribute}'"
                )

    def key_of(self, env: Env) -> jnp.ndarray:
        return mix_keys([_as_key_col(c(env), c.type) for c in self.keys])

    def init_state(self):
        g = self.capacity
        state = {
            "keys": jnp.zeros((g,), jnp.int64),
            "used": jnp.zeros((g,), jnp.bool_),
            "n": jnp.zeros((), jnp.int32),  # groups held: used slots
        }
        if self.reclaim != RECLAIM_NONE:
            # the stack of unused slots (its first G - n places), slots given
            # back and rows that found no slot since deploy
            state["free"] = free_stack(g)
            state["freed"] = jnp.zeros((), jnp.int64)
            state["lost"] = jnp.zeros((), jnp.int64)
        if self.reclaim == RECLAIM_OWN:
            state["rows"] = jnp.zeros((g,), jnp.int32)
        if self.probe == PROBE_BUCKET:
            state["index"] = empty_index(g)
        return state

    def describe_state(self, state=None) -> dict:
        """The `group` block of a query's status: the static fields and,
        from the table's `state`, the groups it holds now (`used`), the slots
        it has given back (`freed`) and the rows that found none
        (`overflow_rows`; None where the table takes no slot back and so
        keeps no count). Summed: a sharded table's counts lead with [D].
        Of a bucket index: `index_buckets`, the keys in its fullest bucket
        now (`index_max_fill`, of the 128 a bucket holds), whether a bucket
        ever had no lane for a new key (`index_overflow`, sticky: the table
        is probed by the merge from then on) and the head tiles looked up
        since deploy (`probe_tiles`)."""
        d = {"capacity": self.capacity, "carry_read": self.carry_read,
             "probe": self.probe, "reclaim": self.reclaim}
        if state is not None:
            def total(k, absent):
                return int(np.asarray(state[k]).sum()) if k in state else absent

            d.update(used=total("n", 0), freed=total("freed", 0),
                     overflow_rows=total("lost", None))
            index = state.get("index")
            if index is not None:
                d.update(
                    index_buckets=int(index["slot"].shape[-2]),
                    index_max_fill=int(
                        (jnp.asarray(index["slot"]) >= 0).sum(axis=-1).max()),
                    index_overflow=int(np.asarray(index["full"]).any()),
                    probe_tiles=int(np.asarray(index["tiles"]).sum()),
                )
        return d

    def assign(self, state, env: Env, active: jnp.ndarray,
               reset: jnp.ndarray = None, sign: jnp.ndarray = None):
        """`sign` (+1 CURRENT, -1 EXPIRED, 0 other rows) feeds the table's
        own row count where no aggregator keeps one."""
        bk = self.key_of(env)
        keys, used, n, slot, grp, overflow = assign_slots(
            state["keys"], state["used"], state["n"], bk, active, reset=reset,
            free=state.get("free"), index=state.get("index"),
        )
        self.carry_read = grp.carry_read
        ctx = GroupCtx(
            slot=slot, key=bk, sorted=grp, capacity=self.capacity,
            key_of=self.key_of, overflow=overflow,
        )
        new = {"keys": keys, "used": used, "n": n}
        if self.reclaim != RECLAIM_NONE:
            lost = (active & (slot >= self.capacity)).sum(dtype=jnp.int32)
            new.update(free=grp.free, freed=state["freed"],
                       lost=state["lost"] + lost.astype(jnp.int64))
        if self.reclaim == RECLAIM_OWN:
            with jax.named_scope("group.reclaim"):
                _, new["rows"] = keyed_running_sum(
                    sign.astype(jnp.int32), grp, state["rows"], rows=True
                )
        if grp.index is not None:
            new["index"] = grp.index
        return new, ctx

    def release(self, state, ctx: GroupCtx, rows: jnp.ndarray):
        """The end of a reclaiming table's step, once every lane has run:
        the slots of the groups that hold no row any more (`rows`, the [G]
        lane that counts them, as the step leaves it) go back on the stack
        and read unused."""
        with jax.named_scope("group.reclaim"):
            free, n, freed = release_slots(state["free"], state["n"], ctx.sorted)
            state = {
                **state, "free": free, "n": n, "used": rows > 0,
                "freed": state["freed"] + freed.astype(jnp.int64),
            }
        if "index" in state:  # its upkeep is the probe's cost, not the stack's
            with jax.named_scope("group.probe"):
                state["index"] = release_index(state["index"], ctx.sorted)
        return state
