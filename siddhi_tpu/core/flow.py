"""The Flow — the trace-time object threaded through a compiled query chain.

The reference threads `ComplexEventChunk`s through a linked `Processor` chain
(reference: query/processor/Processor.java); here the chain is a compile-time
composition of stages, each a pure function over this Flow during jit tracing.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp

from siddhi_tpu.core.event import EventBatch, KIND_CURRENT, KIND_EXPIRED, KIND_RESET
from siddhi_tpu.core.executor import Env, TS_ATTR, VALID_ATTR, VarKey


@dataclasses.dataclass
class Flow:
    """batch: events flowing through (padding/filtered rows have valid=False)
    refs: stream refs whose attributes the batch columns carry (cols keyed
          (ref, None, attr) in `extra`; primary single-stream cols live in
          batch.cols under plain attr names for ref `ref`)
    member/member_env: window membership view (see aggregators.FlowInfo)
    cause: per row, the row of the step's input batch whose arrival put it
          in the flow (an EXPIRED row's is its trigger's), int32; None
          unless the caller asked by setting it on the flow it starts
          (core/partition.py, to put a partition's output back in arrival
          order). A stage that builds a new flow and cannot say drops it.
    slot_rows: None, or, where the step runs under `vmap` over a partition's
          slots, each with a state of its own (core/partition.py), the most
          rows all slots' flows hold together (static): a length window
          then reads and writes its ring by places, not by slices.
    """

    batch: EventBatch
    ref: str
    now: jnp.ndarray  # scalar int64 wall/playback clock
    extra_cols: dict[VarKey, jnp.ndarray] = dataclasses.field(default_factory=dict)
    member: Optional[jnp.ndarray] = None
    member_env: Optional[Env] = None
    # device scalars surfaced to the host after the step (e.g. next_timer)
    aux: dict = dataclasses.field(default_factory=dict)
    # live table states keyed by table id (for `in <table>` conditions)
    tables: dict = dataclasses.field(default_factory=dict)
    cause: Optional[jnp.ndarray] = None
    slot_rows: Optional[int] = None

    def env(self) -> Env:
        cols: dict[VarKey, jnp.ndarray] = {
            (self.ref, None, name): arr for name, arr in self.batch.cols.items()
        }
        cols[(self.ref, None, TS_ATTR)] = self.batch.ts
        cols[(self.ref, None, VALID_ATTR)] = self.batch.valid
        cols.update(self.extra_cols)
        return Env(cols, now=self.now, tables=self.tables)

    # ---- kind masks ----
    @property
    def current(self) -> jnp.ndarray:
        return self.batch.valid & (self.batch.kind == KIND_CURRENT)

    @property
    def expired(self) -> jnp.ndarray:
        return self.batch.valid & (self.batch.kind == KIND_EXPIRED)

    @property
    def reset(self) -> jnp.ndarray:
        return self.batch.valid & (self.batch.kind == KIND_RESET)

    @property
    def sign(self) -> jnp.ndarray:
        return self.current.astype(jnp.int8) - self.expired.astype(jnp.int8)
