"""First-class sharded execution: `@app:shard(devices='N', axis=...)`.

The multichip dryrun (`__graft_entry__.py` + `parallel/mesh.py`) proved the
hard part — an 8-device mesh with the partition axis sharded and the batch
axis key-routed per device, checksum-identical to unsharded execution — but
none of it was reachable from a real app. This module promotes that contract
to an engine runtime mode, resolved at `start()`:

* **axis='part'** — every `PartitionedQueryRuntime`'s existing leading `[P]`
  state axis is placed on a `jax.sharding.Mesh` over the first N devices:
  windows/aggregators of different partition keys advance in parallel on
  different chips, with XLA inserting the cross-device collectives (the
  psum/min aux reduction, the output gather at decode). The input batch is
  REPLICATED to every device — emission order is part of the engine contract,
  and the dryrun's key-routed batch pre-pass compacts each device's
  sub-batch, which reorders emissions ACROSS partition slots within a batch
  (set-identical, order-different). The routed variant stays available as
  `mesh.shard_partitioned_query(routed=True)` for checksum workloads.

* **axis='keys'** — the above, plus key-sharded group-by and join state
  (`parallel/keyshard.py`).

* **axis='auto'** (default) is `part`. Non-partitioned queries keep the
  single-device fused path (key-routed sharding for those is the partition
  construct: `partition with (key of S)`, or axis='keys').

`SIDDHI_TPU_SHARD=N` overrides the annotation process-wide (0 forces off) —
the verify-parity CI leg runs the whole suite under `SIDDHI_TPU_SHARD=8`
with `XLA_FLAGS=--xla_force_host_platform_device_count=8` and diffs every
case's rows against the unsharded run.

Validation is ONE rule set (`iter_shard_annotation_problems`) shared by the
runtime resolver (raises at app creation) and the analyzer's SA129
diagnostic, like SA125–SA128.

Grounding: the cloud-native pattern-detection framework shards detection by
key exactly this way (PAPERS.md, arxiv 2401.09960); "To Share, or not to
Share" (arxiv 2101.00361) motivates keeping shared state local to a shard —
here each device owns its partition slots' windows outright.
"""

from __future__ import annotations

import logging
import os
from typing import Optional

import numpy as np

log = logging.getLogger(__name__)

SHARD_ENV = "SIDDHI_TPU_SHARD"
SHARD_AXIS_ENV = "SIDDHI_TPU_SHARD_AXIS"
MAX_DEVICES = 64
_AXES = ("auto", "part", "keys")


# ---------------------------------------------------------------------------
# annotation / env resolution (one rule set for runtime + analyzer SA129)
# ---------------------------------------------------------------------------


def shard_env_override() -> Optional[int]:
    """Process-wide device-count override: N (force N-device sharding),
    0 (force off), or None (defer to the app's @app:shard annotation)."""
    v = os.environ.get(SHARD_ENV, "").strip().lower()
    if not v:
        return None
    if v in ("off", "false", "no"):
        return 0
    try:
        return max(0, int(v))
    except ValueError:
        log.warning("ignoring malformed %s=%r", SHARD_ENV, v)
        return None


def shard_axis_override() -> Optional[str]:
    """Process-wide axis override (SIDDHI_TPU_SHARD_AXIS): one of the
    `_AXES` names, or None to defer to the app's @app:shard annotation.
    Lets CI drive the same app through every placement strategy."""
    v = os.environ.get(SHARD_AXIS_ENV, "").strip().lower()
    if not v:
        return None
    if v not in _AXES:
        log.warning(
            "ignoring malformed %s=%r (expected one of %s)",
            SHARD_AXIS_ENV, v, ", ".join(_AXES),
        )
        return None
    return v


def iter_shard_annotation_problems(ann):
    """Yield one message per malformed `@app:shard` element — THE validation
    rules, shared by the runtime resolver (raises on the first) and the
    analyzer's SA129 diagnostics (reports them all), so the two can never
    drift. Accepted shapes:
    @app:shard(devices='N'[, axis='part|keys|auto'])
    or the sole-positional @app:shard('N')."""
    sole_positional = len(ann.elements) == 1 and ann.elements[0][0] is None
    for k, v in ann.elements:
        if k == "devices" or (k is None and sole_positional):
            try:
                ok = 1 <= int(v) <= MAX_DEVICES
            except (TypeError, ValueError):
                ok = False
            if not ok:
                yield (
                    f"@app:shard devices '{v}' must be an integer in "
                    f"1..{MAX_DEVICES}"
                )
        elif k == "axis":
            if str(v).strip().lower() not in _AXES:
                yield (
                    f"@app:shard axis '{v}' must be one of "
                    f"{', '.join(_AXES)}"
                )
        else:
            yield (
                f"unknown @app:shard option '{k if k is not None else v}' "
                "(expected devices, axis)"
            )


def resolve_shard_annotation(ann) -> tuple[int, str]:
    """(requested_devices, axis) for one app from its `@app:shard`
    annotation (or None) plus the SIDDHI_TPU_SHARD env override (which wins,
    in both directions). requested_devices == 0 means sharding is off.
    Raises SiddhiAppCreationError on malformed options — the runtime analog
    of the analyzer's SA129 diagnostic."""
    from siddhi_tpu.core.errors import SiddhiAppCreationError

    devices = 0
    axis = "auto"
    if ann is not None:
        for problem in iter_shard_annotation_problems(ann):
            raise SiddhiAppCreationError(problem)
        v = ann.element("devices")
        if v is None and len(ann.elements) == 1 and ann.elements[0][0] is None:
            v = ann.elements[0][1]  # strict sole-positional fallback
        devices = int(v) if v is not None else 0
        ax = ann.element("axis")
        if ax is not None:
            axis = str(ax).strip().lower()
    env = shard_env_override()
    if env is not None:
        devices = env
    env_axis = shard_axis_override()
    if env_axis is not None:
        axis = env_axis
    return devices, axis


# ---------------------------------------------------------------------------
# partition-axis mesh placement
# ---------------------------------------------------------------------------


def apply_partition_mesh(app_runtime, devices) -> dict:
    """Place every plain `PartitionedQueryRuntime`'s `[P]` state axis on a
    mesh over `devices`, swapping the runtime's outer jitted step for one
    with explicit in/out shardings (the replicated-batch mode: each device
    advances only its own partition slots; the merged flat output — and so
    delivery order — is bit-identical to the unsharded step's). Returns
    qid -> placement info for `/status.json` and explain()."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from siddhi_tpu.core.partition import PartitionedQueryRuntime

    D = len(devices)
    placed: dict = {}
    mesh = None
    for pr in app_runtime.partitions:
        for qr in pr.queries:
            if type(qr) is not PartitionedQueryRuntime or qr.key_of is None:
                # joins/patterns/#inner-fed queries keep the single-device
                # vmapped step (their [P] axes are shardable the same way;
                # scoped out until the mesh contract covers their timers)
                continue
            qid = qr.query_id
            padded = 0
            if qr.p % D != 0:
                if qr.state is not None:
                    # live [P] buffers can't be resized in place; only a
                    # pre-first-event placement pads
                    placed[qid] = {
                        "sharded": False,
                        "reason": (
                            f"partitionCapacity {qr.p} % devices {D} != 0 "
                            "with live state"
                        ),
                    }
                    continue
                # pad the [P] axis to the next multiple of D with DEAD
                # slots: the shared ptable keeps its original capacity so
                # key->slot allocation (and its overflow threshold) is
                # untouched, and the padded lanes behave exactly like
                # never-allocated lanes — timer rows run on fresh init
                # state and emit nothing, so emissions stay byte-identical
                target = -(-qr.p // D) * D
                padded = target - qr.p
                log.info(
                    "query '%s': padding @app:partitionCapacity %d to %d "
                    "(%d dead slot(s)) for the %d-device mesh",
                    qid, qr.p, target, padded, D,
                )
                qr.p = target
            if mesh is None:
                mesh = Mesh(np.array(devices), ("part",))
            shard = NamedSharding(mesh, P("part"))
            repl = NamedSharding(mesh, P())
            # same computation as the unsharded _pstep_outer (identical
            # emission lanes), state resharded [P] across the mesh; the aux
            # any()/min() reductions become XLA cross-device collectives and
            # the output decode gathers — the cross-device merge step.
            # donate_argnums matches the unsharded jit: the [P] state is the
            # largest tensor set in the system and must update in place
            # (the first call's host-built state isn't donatable — one
            # ignorable warning — every later call donates sharded buffers)
            # (ptable, states, route counters, batch, now) ->
            # (ptable, states, route counters, flat output, its slots, aux):
            # the routed [P, B'] sub-batches and the [P, K'] emissions
            # follow the state's sharding inside the program
            qr._pstep_outer = jax.jit(
                qr._pstep_outer_impl,
                in_shardings=(repl, shard, repl, repl, repl),
                out_shardings=(repl, shard, repl, repl, repl, repl),
                donate_argnums=(1,),
            )
            placed[qid] = {
                "sharded": True,
                "devices": D,
                "axis": "part",
                "local_slots": qr.p // D,
            }
            if padded:
                placed[qid]["padded_slots"] = padded
    return placed


# ---------------------------------------------------------------------------
# the app-level shard runtime (built at start())
# ---------------------------------------------------------------------------


class ShardRuntime:
    """Resolved sharded-execution mode of one app. Built by
    `SiddhiAppRuntime.start()` from the creation-time `@app:shard` /
    SIDDHI_TPU_SHARD resolution; `apply()` places partitioned state on the
    mesh and arms key-sharded group-by and join state."""

    def __init__(self, app_runtime, requested: int, axis: str):
        import jax

        self.app = app_runtime
        self.axis = axis
        self.requested = int(requested)
        devs = jax.devices()
        n = min(self.requested, len(devs))
        if n < self.requested:
            log.warning(
                "app '%s': @app:shard requested %d devices but only %d are "
                "visible; clamping (set XLA_FLAGS="
                "--xla_force_host_platform_device_count=N for a virtual "
                "CPU mesh)",
                app_runtime.name, self.requested, len(devs),
            )
        self.devices = devs[:n]
        self.partitioned: dict = {}
        self.keyshard: dict = {}
        self.joins: dict = {}

    @property
    def n(self) -> int:
        return len(self.devices)

    def apply(self) -> None:
        if self.n < 2:
            log.warning(
                "app '%s': sharded execution disabled (%d device(s) "
                "available)", self.app.name, self.n,
            )
            return
        self.partitioned = apply_partition_mesh(self.app, self.devices)
        self.rearm_keyshard()

    def rearm_keyshard(self) -> None:
        """(Re)arm key-sharded group-by and join state (axis='keys' only —
        parallel/keyshard.py). Called by apply() at start AND by the churn
        splice after fused engines are rebuilt: a hot-deployed grouped
        query (state still None) gets armed before its first event;
        already-armed queries keep their live [D] state and jitted step."""
        if self.n < 2 or self.axis != "keys":
            return
        from siddhi_tpu.parallel.keyshard import (
            apply_join_mesh,
            apply_keyshard,
        )

        self.keyshard.update(apply_keyshard(self.app, self.devices))
        self.joins.update(apply_join_mesh(self.app, self.devices))

    def describe_state(self) -> dict:
        d: dict = {
            "devices": self.n,
            "requested": self.requested,
            "axis": self.axis,
        }
        if self.partitioned:
            d["partitioned"] = dict(self.partitioned)
        if self.keyshard:
            ks = {}
            for qid, info in self.keyshard.items():
                qr = self.app.queries.get(qid)
                ex = getattr(qr, "_keyshard", None)
                live = ex.describe_state() if ex is not None else {}
                ks[qid] = {**info, **live}
            d["keyshard"] = ks
        if self.joins:
            d["joins"] = dict(self.joins)
        return d
