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

    routed=True (default): the BATCH AXIS is sharded too. A replicated
    routing pre-pass (key extraction + slot assignment over the small [B]
    batch) computes each event's owning device by STRIPING slots across the
    mesh — device = slot % D, local state row = slot // D, so the first D
    live keys land on D different chips instead of filling device 0's block
    first — packs per-device sub-batches [D, B] sharded on the mesh axis,
    and a shard_map advances each device's LOCAL partition slice against
    only its own events — each chip decodes B rows, not D*B (the TPU-native
    analog of the reference's per-key routing,
    PartitionStreamReceiver.java:81-140).
    Timer rows are broadcast to every device, interleaved at their original
    row positions so time-driven operators fire in the unsharded order.

    routed=False replicates the batch to every device (the r3 behavior;
    correctness baseline).

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
        fn = jax.jit(
            qr._pstep_outer_impl,
            in_shardings=(repl, shard, repl, repl),
            out_shardings=(repl, shard, shard, repl),
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

    from siddhi_tpu.core.event import (
        EventBatch,
        KIND_CURRENT,
        KIND_TIMER,
    )
    from siddhi_tpu.core.executor import Env, TS_ATTR
    from siddhi_tpu.ops.group import assign_slots

    D = n_dev
    PL = qr.p // D  # local partition slots per device

    def routed_step(ptable, states, batch: EventBatch, now):
        B = batch.ts.shape[0]
        cols = {(qr.ref, None, n): c for n, c in batch.cols.items()}
        cols[(qr.ref, None, TS_ATTR)] = batch.ts
        env = Env(cols, now=now)
        keys, matched = qr.key_of(env)
        active = batch.valid & (batch.kind == KIND_CURRENT) & matched
        pk, pu, pn, slot, _grp, povf = assign_slots(
            ptable["keys"], ptable["used"], ptable["n"], keys, active
        )
        is_timer = batch.valid & (batch.kind == KIND_TIMER)

        # ---- route the batch axis: device d owns slots {s : s % D == d}
        # (STRIPED, not blocked — first-seen slot allocation hands out low
        # slot numbers first, so a block map slot//PL leaves high devices
        # idle until >PL live keys exist; striping spreads the first D keys
        # across all D devices, the analog of key-hash routing in the
        # reference's PartitionStreamReceiver.java:81-140). Slot s's state
        # lives at block-sharded state row (s % D)*PL + s//D, i.e. device
        # s % D, local row s // D.
        # Each device's sub-batch = its own active rows UNION all timer rows,
        # kept in ORIGINAL row order (a [D, B] mask + per-row cumsum), so
        # timer-driven operators see timers interleaved exactly as the
        # unsharded path does. |actives_d ∪ timers| <= B always, so the
        # sub-batch capacity B can never overflow.
        idx = jnp.arange(B, dtype=jnp.int32)
        dev_of = jnp.where(active & (slot < qr.p), slot % D, D)
        take = (dev_of[None, :] == jnp.arange(D)[:, None]) | is_timer[None, :]
        rank = jnp.cumsum(take.astype(jnp.int32), axis=1) - 1  # [D, B]
        dst = jnp.where(take, jnp.arange(D)[:, None] * B + rank, D * B)
        routed = (
            jnp.full((D * B,), B, jnp.int32)
            .at[dst.reshape(-1)]
            .set(jnp.broadcast_to(idx[None, :], (D, B)).reshape(-1),
                 mode="drop")
            .reshape(D, B)
        )
        pad = routed >= B
        ri = jnp.clip(routed, 0, B - 1)

        def lane(x, fill=0):
            return jnp.where(pad, np.asarray(fill, x.dtype), x[ri])

        r_ts = lane(batch.ts)
        r_kind = lane(batch.kind)
        r_valid = ~pad
        r_cols = {n: lane(c) for n, c in batch.cols.items()}
        r_slot = lane(jnp.where(active, slot, qr.p), fill=qr.p)

        # ---- per-device local advance over its own sub-batch
        def local(states_sl, ts_sl, kind_sl, valid_sl, cols_sl, slot_sl, now_):
            d = lax.axis_index(axis)
            ts1 = ts_sl[0]
            kind1 = kind_sl[0]
            valid1 = valid_sl[0]
            cols1 = {n: c[0] for n, c in cols_sl.items()}
            slot1 = slot_sl[0]
            is_t = valid1 & (kind1 == KIND_TIMER)

            def one(state, p_local):
                gp = p_local * D + d
                v = (valid1 & (slot1 == gp)) | is_t
                b2 = EventBatch(ts1, kind1, v, cols1)
                st, _ts, out, aux = qr._step_impl(state, {}, b2, now_)
                return st, out, aux

            states2, outs, auxs = jax.vmap(one)(
                states_sl, jnp.arange(PL)
            )
            aux_red = {
                k: lax.psum(
                    jnp.asarray(v).astype(jnp.int32).sum(), axis
                )
                > 0
                for k, v in auxs.items()
                if k != "next_timer"
            }
            if "next_timer" in auxs:
                aux_red["next_timer"] = lax.pmin(
                    jnp.min(auxs["next_timer"]), axis
                )
            return states2, outs, aux_red

        local_sharded = jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(
                P(axis), P(axis), P(axis), P(axis), P(axis), P(axis), P()
            ),
            out_specs=(P(axis), P(axis), P()),
            check_vma=False,
        )
        states2, outs, aux = local_sharded(
            states, r_ts, r_kind, r_valid, r_cols, r_slot, now
        )
        aux = dict(aux)
        aux["partition_overflow"] = (
            jnp.asarray(aux.get("partition_overflow", False)) | povf
        )
        return {"keys": pk, "used": pu, "n": pn}, states2, outs, aux

    return routed_step
