"""Mesh sharding for partitioned queries — the multi-chip execution path.

Reference analog: the reference is single-JVM (SURVEY §2.7); its only data
parallelism is `partition with (key of S)` cloning query graphs per key.
Here that same construct IS the scale-out axis: a PartitionedQueryRuntime
already carries a leading [P] partition axis on every state leaf, so placing
that axis on a `jax.sharding.Mesh` spreads the partitions across devices —
windows/aggregators of different keys advance in parallel on different chips,
with XLA inserting any needed collectives over ICI/DCN.

Usage:

    from jax.sharding import Mesh
    from siddhi_tpu.parallel.mesh import shard_partitioned_query

    mesh = Mesh(np.array(jax.devices()), ("part",))
    sharded = shard_partitioned_query(runtime.queries["q"], mesh)
    outs, aux = sharded.step(batch, now)     # one sharded engine step
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class ShardedPartitionedQuery:
    """A partitioned query whose [P] state axis lives across a device mesh."""

    qr: object  # PartitionedQueryRuntime
    mesh: object
    axis: str
    _fn: object
    _ptable: object
    _state: object

    def step(self, batch, now):
        """Run one full partitioned step with the partition axis sharded."""
        self._ptable, self._state, outs, aux = self._fn(
            self._ptable, self._state, batch, jnp.asarray(now, jnp.int64)
        )
        return outs, aux

    @property
    def state(self):
        return self._state

    def total_emitted(self, outs) -> int:
        """psum the per-shard emission counts across the mesh (an explicit
        ICI collective, mostly useful for validation/monitoring)."""
        from jax import lax
        from jax.sharding import PartitionSpec as P

        def count(valid):
            return lax.psum(valid.sum()[None], self.axis)

        counted = jax.shard_map(
            count,
            mesh=self.mesh,
            in_specs=P(self.axis),
            out_specs=P(None),
            check_vma=False,
        )
        return int(counted(outs.valid)[0])


def shard_partitioned_query(
    qr, mesh, axis: Optional[str] = None, routed: bool = True
) -> ShardedPartitionedQuery:
    """Jit a PartitionedQueryRuntime's outer step with its [P] partition axis
    sharded over `mesh`.

    routed=True (default): a replicated pre-pass (key extraction + slot
    assignment over the small [B] batch) gives each event its slot, slots
    are STRIPED across the mesh — device = slot % D, local state row =
    slot // D, so the first D live keys land on D different chips instead
    of filling device 0's block first — and a shard_map has each device
    run the runtime's own routed step (`PartitionedQueryRuntime._routed`:
    its rows laid out as [P/D, B'] sub-batches, in passes, TIMER rows to
    every slot) over its LOCAL slots and its own events (the TPU-native
    analog of the reference's per-key routing,
    PartitionStreamReceiver.java:81-140). The devices' flat outputs are
    merged into one flat batch in arrival order, as the unsharded step's.

    routed=False hands the whole step to the partitioner with the state's
    [P] axis on the mesh and everything else replicated (what
    parallel/shard.py `apply_partition_mesh` deploys).

    The partition capacity (@app:partitionCapacity) must be divisible by the
    mesh size so every device holds an equal slice of partition slots.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    axis = axis or mesh.axis_names[0]
    n_dev = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    if qr.p % n_dev != 0:
        raise ValueError(
            f"partition capacity {qr.p} is not divisible by the mesh size "
            f"{n_dev}; set @app:partitionCapacity(size='<multiple of {n_dev}>')"
        )

    shard = NamedSharding(mesh, P(axis))
    repl = NamedSharding(mesh, P())

    state0 = jax.device_put(qr._fresh(qr.init_state()), shard)
    ptable0 = jax.device_put(
        {
            "keys": jnp.zeros((qr.p,), jnp.int64),
            "used": jnp.zeros((qr.p,), jnp.bool_),
            "n": jnp.zeros((), jnp.int32),
        },
        repl,
    )
    if not routed:
        def replicated_step(ptable, states, batch, now):
            # the runtime's own step (rows routed to their slot's [P, B']
            # sub-batch, core/partition.py) with the state's [P] axis on
            # the mesh; its output is the merged flat batch
            counters = {"extra_passes": jnp.zeros((), jnp.int64),
                        "max_rows": jnp.zeros((), jnp.int32)}
            ptable, states, _, flat, _slot, aux = qr._pstep_outer_impl(
                ptable, states, counters, batch, now
            )
            return ptable, states, flat, aux

        fn = jax.jit(
            replicated_step,
            in_shardings=(repl, shard, repl, repl),
            out_shardings=(repl, shard, repl, repl),
        )
        return ShardedPartitionedQuery(qr, mesh, axis, fn, ptable0, state0)

    fn = jax.jit(
        _make_routed_step(qr, mesh, axis, n_dev),
        in_shardings=(repl, shard, repl, repl),
        out_shardings=(repl, shard, shard, repl),
    )
    return ShardedPartitionedQuery(qr, mesh, axis, fn, ptable0, state0)


def _make_routed_step(qr, mesh, axis: str, n_dev: int):
    """Build the routed sharded step (see shard_partitioned_query)."""
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from siddhi_tpu.core.event import EventBatch, KIND_CURRENT
    from siddhi_tpu.core.executor import Env, TS_ATTR
    from siddhi_tpu.core.partition import BIG, _in_order
    from siddhi_tpu.ops.group import assign_slots

    D = n_dev
    PL = qr.p // D  # local partition slots per device

    def routed_step(ptable, states, batch: EventBatch, now):
        cols = {(qr.ref, None, n): c for n, c in batch.cols.items()}
        cols[(qr.ref, None, TS_ATTR)] = batch.ts
        env = Env(cols, now=now)
        keys, matched = qr.key_of(env)
        active = batch.valid & (batch.kind == KIND_CURRENT) & matched
        pk, pu, pn, slot, _grp, povf = assign_slots(
            ptable["keys"], ptable["used"], ptable["n"], keys, active
        )
        active = active & (slot < qr.p_logical)

        # device d owns slots {s : s % D == d} (STRIPED, not blocked —
        # first-seen slot allocation hands out low slot numbers first, so a
        # block map slot // PL leaves high devices idle until > PL live keys
        # exist). Slot s's state lives at block-sharded state row
        # (s % D) * PL + s // D, i.e. device s % D, local row s // D. Every
        # device is handed the whole batch and routes its own rows (and the
        # TIMER rows, which reach every slot) to its local slots' sub-
        # batches: the same [B'] rows a slot is sent by the unsharded step.
        def local(states_sl, batch_, slot_, active_, now_):
            d = lax.axis_index(axis)
            counters = {"extra_passes": jnp.zeros((), jnp.int64),
                        "max_rows": jnp.zeros((), jnp.int32)}
            states2, _, flat, lslot, cause, aux = qr._routed(
                states_sl, counters, batch_, slot_ // D,
                active_ & (slot_ % D == d), now_, p=PL,
            )
            aux_red = {
                k: lax.psum(jnp.asarray(v).astype(jnp.int32), axis) > 0
                for k, v in aux.items() if k != "next_timer"
            }
            if "next_timer" in aux:
                aux_red["next_timer"] = lax.pmin(aux["next_timer"], axis)
            emitted = flat.valid.sum(dtype=jnp.int32)[None]
            return states2, (flat, lslot * D + d, cause, emitted), aux_red

        states2, (flat, gslot, cause, emitted), aux = jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(P(axis), P(), P(), P(), P()),
            out_specs=(P(axis), P(axis), P()),
            check_vma=False,
        )(states, batch, slot, active, now)
        # the devices' flat outputs, each in arrival order, as one: by the
        # input row that caused each (every slot's answers to one TIMER row
        # by slot), cut to what the unsharded step's flat output holds
        rows = qr._flat_rows(
            batch.capacity, qr.p, flat.valid.shape[0] // D // PL)
        key, lanes, lost = _in_order(
            jnp.where(flat.valid, cause, BIG),
            {"ts": flat.ts, "kind": flat.kind, "cols": flat.cols},
            rows, gslot,
        )
        outs = EventBatch(lanes["ts"], lanes["kind"], key != BIG, lanes["cols"])
        aux = dict(aux)
        # [D]: the rows each device's slots emitted in this step
        aux["emitted_per_device"] = emitted
        aux["window_overflow"] = aux.get("window_overflow", False) | lost
        aux["partition_overflow"] = (
            jnp.asarray(aux.get("partition_overflow", False)) | povf
        )
        return {"keys": pk, "used": pu, "n": pn}, states2, outs, aux

    return routed_step
