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

import jax.numpy as jnp

from siddhi_tpu.core.errors import SiddhiAppCreationError
from siddhi_tpu.core.executor import CompiledExpr, Env, Scope, compile_expression
from siddhi_tpu.core.types import AttrType
from siddhi_tpu.ops.group import SortedGroups, assign_slots, mix_keys
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
    ):
        if not group_by:
            raise SiddhiAppCreationError("empty group by")
        self.capacity = int(capacity)
        # how the last trace read the groups' carried values: "segment" (once
        # per segment of the sorted view) or "row"; None before the first trace
        self.carry_read: Optional[str] = None
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
        return {
            "keys": jnp.zeros((g,), jnp.int64),
            "used": jnp.zeros((g,), jnp.bool_),
            "n": jnp.zeros((), jnp.int32),
        }

    def assign(self, state, env: Env, active: jnp.ndarray, reset: jnp.ndarray = None):
        bk = self.key_of(env)
        keys, used, n, slot, grp, overflow = assign_slots(
            state["keys"], state["used"], state["n"], bk, active, reset=reset
        )
        self.carry_read = grp.carry_read
        ctx = GroupCtx(
            slot=slot, key=bk, sorted=grp, capacity=self.capacity,
            key_of=self.key_of, overflow=overflow,
        )
        return {"keys": keys, "used": used, "n": n}, ctx
