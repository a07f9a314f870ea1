"""Named windows: `define window W (...) <window> [output <events>]`.

Reference: core/window/Window.java:63-300 — a shared window processor; queries
insert into it, read its emission stream, join against its live buffer
(find :261), and pull it in store queries. Here the buffer is one shared
device-state pytree owned by this runtime; its emission stream is an output
junction; joins/store-queries read the live state through the same
findable-state threading used for tables.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from siddhi_tpu.core.errors import SiddhiAppCreationError
from siddhi_tpu.core.event import (
    EventBatch,
    KIND_CURRENT,
    KIND_EXPIRED,
    StreamSchema,
)
from siddhi_tpu.core.executor import Scope
from siddhi_tpu.core.flow import Flow
from siddhi_tpu.core.windows import make_window
from siddhi_tpu.query_api.definition import WindowDefinition


class NamedWindow:
    """Shared window processor + live findable buffer."""

    is_named_window = True

    def __init__(self, definition: WindowDefinition, interner, time_capacity=None):
        if definition.window is None:
            raise SiddhiAppCreationError(
                f"window '{definition.id}' needs a window type, "
                "e.g. define window W (...) length(10)"
            )
        self.definition = definition
        self.window_id = definition.id
        self.schema = StreamSchema(
            definition.id, [(a.name, a.type) for a in definition.attributes]
        )
        scope = Scope(interner)
        scope.add_stream(definition.id, self.schema.attr_types)
        self.stage = make_window(
            definition.window, self.schema, definition.id, scope,
            time_capacity=time_capacity,
        )
        self.out_events = definition.output_events  # current | expired | all
        self.state = self.stage.init_state()
        self.needs_scheduler = self.stage.needs_scheduler
        cron = getattr(self.stage, "cron_schedule", None)
        self.host_next_timer = cron.next_fire_ms if cron is not None else None
        self.out_junction = None  # wired by the app runtime
        self.timer_target = None
        self._step = jax.jit(self._step_impl)

    def describe_state(self) -> dict:
        """Introspection: the shared buffer's type/fill/capacity plus this
        runtime's wiring (see observability/introspect.py)."""
        d = self.stage.describe_state(self.state)
        d["output_events"] = self.out_events
        return d

    # findable protocol (shared with InMemoryTable)
    @property
    def table_id(self) -> str:
        return self.window_id

    def view(self, state):
        return self.stage.view(state)

    def _step_impl(self, state, batch: EventBatch, now):
        flow = Flow(batch=batch, ref=self.window_id, now=now)
        state, out_flow = self.stage.apply(state, flow)
        b = out_flow.batch
        # `output current|expired events` narrows what downstream queries see
        # (reference: Window.java outputEventType dispatch)
        if self.out_events == "current":
            keep = b.kind != np.int8(KIND_EXPIRED)
        elif self.out_events == "expired":
            keep = b.kind != np.int8(KIND_CURRENT)
        else:
            keep = jnp.ones_like(b.valid)
        out = EventBatch(b.ts, b.kind, b.valid & keep, b.cols)
        return state, out, out_flow.aux

    def receive(self, batch: EventBatch, now: int):
        """Process inserts (or a TIMER batch); caller holds the app lock."""
        self.state, out, aux = self._step(
            self.state, batch, jnp.asarray(now, dtype=jnp.int64)
        )
        return out, aux
