"""Key-sharded stateful scale-out: group-by aggregation and join state on
the `@app:shard` mesh (axis='keys').

`axis='part'` shards partitioned `[P]` state; without this module every
non-partitioned group-by aggregation and join window lives on one
device. This module hashes group keys to mesh devices so each device owns a
DISJOINT key range of the aggregation table:

- `KeyShardedGroupExec` wraps an eligible single-stream grouped query's
  jitted step in a `shard_map` program. Every device sees the full
  replicated micro-batch, runs the (stateless) chain, then masks away rows
  whose group key it does not own — the key-routed pre-pass. The selector
  advances only the owned groups' aggregator lanes. Because emissions are
  POSITIONAL (row b of the output corresponds to row b of the input), the
  merge restores exact order for free: out rows are owner-masked and
  psum-folded across the mesh (the `total_emitted` psum in parallel/mesh.py
  is the seed pattern), reconstructing the unsharded output byte-for-byte —
  float lanes are bitcast to integer bits before the masked psum so -0.0
  and NaN payloads survive exactly.
- `apply_join_mesh` places join window ring buffers across the mesh via
  explicit in/out shardings on the sides' jitted steps (GSPMD): each device
  holds a per-device sub-window and the join probe's cross-device gather is
  realized by the partitioner. The program itself is unchanged, so
  `WindowStage.view_seq()` lineage lanes — and byte parity — are preserved
  trivially.

Eligibility is deliberately narrow (`keyed_shardable`): a plain
windowless grouped query with no host-side ordering state. Everything
else keeps the single-device step and is reported with a reason in
`ShardRuntime.describe_state()["keyshard"]`.

Both ingest paths run the same sharded step on the same `[D]` state: the
per-batch path through the jitted `_step` that `arm()` installs, and the
fused chunk program (core/ingest.py), whose scan body calls `_step_impl` as
the endpoint's `impl` — one `jit_fused` over the mesh per chunk, state
donated, wire replicated. `describe_state()["path"]` says which of the two
the last dispatch took. Device scopes inside `q.<query>`: `filter` (the
chain's), `keyshard.route` (owner hash and mask), `selector`,
`keyshard.exchange` (the collectives).

Snapshot SPI (core/persistence.py): `export_state` canonicalizes the
`[D, G]` sharded group table into the SINGLE-device layout, so a snapshot
taken on an 8-device mesh restores onto any mesh size — `import_state`
re-hashes every group key to its new owner. That is how PR 11's
rebalance rides mesh-size changes.

Grounding: the cloud-native pattern-detection framework shards detection
state by key hash (PAPERS.md, arxiv 2401.09960); TiLT's time-centric merge
(arxiv 2301.12030) motivates the positional psum fold.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Optional

import numpy as np

log = logging.getLogger(__name__)

KEY_AXIS = "keys"

# splitmix64 finalizer constants — group keys from `mix_keys` pass single
# columns through UN-mixed (ops/group.py), so the owner hash must scramble
# low bits itself or sequential interned ids would stripe the mesh
_M1 = np.uint64(0xBF58476D1CE4E5B9)
_M2 = np.uint64(0x94D049BB133111EB)


def mix64(k):
    """splitmix64 finalizer over uint64 lanes. Works on BOTH numpy and
    jax arrays (same operators, same wraparound) — the device step and the
    host-side snapshot re-hash MUST agree bit-for-bit on ownership."""
    k = k ^ (k >> np.uint64(30))
    k = k * _M1
    k = k ^ (k >> np.uint64(27))
    k = k * _M2
    k = k ^ (k >> np.uint64(31))
    return k


def owner_of(keys, n_devices: int):
    """Owning device index in [0, n_devices) for each int64 group key.
    Dual-use: jnp arrays inside the sharded step, numpy arrays in the
    snapshot import re-hash."""
    return (mix64(keys.astype("uint64")) % np.uint64(n_devices)).astype(
        "int32"
    )


def keyed_shardable(qr) -> tuple[bool, Optional[str]]:
    """(eligible, reason-when-not) for key-sharding one query runtime.

    Exactly ONE kind of cross-batch state is allowed: the group-by slot
    table plus its aggregator lanes. A windowless grouped query's per-group
    values depend only on that group's rows, and a group's rows always
    hash to one device — so per-device selectors advancing disjoint key
    ranges reproduce the unsharded output at every owned row position."""
    from siddhi_tpu.core.query_runtime import QueryRuntime

    if type(qr) is not QueryRuntime:
        return False, "not a plain single-stream query runtime"
    sel = qr.selector
    if sel.group is None:
        return False, "no group-by key to shard on"
    if qr.chain.window is not None:
        return False, "windowed chain state is not key-shardable yet"
    if sel.order_by or sel.limit is not None or sel.offset is not None:
        return False, "order by / limit reorders rows across groups"
    if qr.rate_limiter is not None:
        return False, "output rate limiter holds host-side state"
    if qr.table_op is not None or qr.tables:
        return False, "table reads/writes stay single-device"
    if getattr(qr, "join_findables", None):
        return False, "in-condition table probes stay single-device"
    # Byte parity requires every aggregator to be exact under scan-tree
    # reassociation: the owner mask flips non-owned rows inactive, which
    # changes the (active, era, key, idx) sorted layout feeding
    # `segmented_cumsum`, which changes how the blocked scan associates
    # additions. Integer adds and min/max commute exactly; float adds
    # drift by ULPs (observed: 1-ULP avg() divergence at 8 devices).
    from siddhi_tpu.core.aggregators import (
        CountAggregator,
        ExtremeAggregator,
        SumAggregator,
    )
    from siddhi_tpu.core.types import AttrType

    for agg in sel.aggregators:
        if isinstance(agg, (CountAggregator, ExtremeAggregator)):
            continue
        if isinstance(agg, SumAggregator) and agg.type is AttrType.LONG:
            continue
        return False, (
            f"{type(agg).__name__} float arithmetic is "
            "reassociation-sensitive under the key-routed mask"
        )
    return True, None


class KeyShardedGroupExec:
    """Key-sharded execution of one eligible grouped query.

    Owns the mesh, the shard_map step (same 4-arg signature as
    `QueryRuntime._step_impl`: jitted for `receive()`, whose
    timing/writeback path is untouched, and called un-jitted as the fused
    chunk program's endpoint `impl`), the `[D]`-stacked initial state, live
    per-device key-occupancy gauges, and the snapshot canonicalize/re-hash
    pair."""

    def __init__(self, qr, devices):
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        self.qr = qr
        self.devices = list(devices)
        self.n = len(self.devices)
        self.mesh = Mesh(np.array(self.devices), (KEY_AXIS,))
        shard = self.state_sharding = NamedSharding(self.mesh, P(KEY_AXIS))
        repl = self.replicated = NamedSharding(self.mesh, P())
        # which ingest path the last dispatch took: "fused" (the chunk
        # program) or "batch" (`receive`); None before the first
        self.path = None
        # donate_argnums matches the unsharded jit: the [D] state updates
        # in place (the first call's host-built state isn't donatable —
        # one ignorable warning, same as the partition mesh path)
        self._jit = jax.jit(
            self._step_impl,
            in_shardings=(shard, repl, repl, repl),
            out_shardings=(shard, repl, repl, repl),
            donate_argnums=(0,),
        )

    # ---- arming ----------------------------------------------------------

    def arm(self) -> None:
        """Swap the query's jitted step for the sharded one. Must run
        before the first receive materializes state (the state layout is
        part of the traced program)."""
        qr = self.qr
        if qr.state is not None:  # pragma: no cover — callers pre-check
            raise RuntimeError(
                f"query '{qr.query_id}': cannot key-shard after state "
                "materialized"
            )
        # a device's share of the keys is probed by the merge: the bucket
        # index (ops/group.py) has no form that import_state could re-hash
        from siddhi_tpu.ops.group import PROBE_MERGE

        qr.selector.group.probe = PROBE_MERGE
        qr._keyshard = self
        qr._step = self._jit

    def init_state(self):
        """The unsharded init pytree with a leading [D] device axis — every
        device starts with an EMPTY group table; keys claim slots on their
        owner as they arrive (first-appearance allocation, per device)."""
        import jax
        import jax.numpy as jnp

        one = self.qr.init_state()
        return jax.tree_util.tree_map(
            lambda x: jnp.stack([jnp.asarray(x)] * self.n), one
        )

    # ---- device program --------------------------------------------------

    def _step_impl(self, state, tstates, batch, now):
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from siddhi_tpu.core.event import EventBatch, KIND_CURRENT
        from siddhi_tpu.core.flow import Flow
        from siddhi_tpu.observability.lineage import LIN

        qr = self.qr
        D = self.n

        def local(state_blk, b, t):
            st = jax.tree_util.tree_map(lambda l: l[0], state_blk)
            d = lax.axis_index(KEY_AXIS)
            flow = Flow(batch=b, ref=qr.ref, now=t, tables={})
            chain_state, flow = qr.chain.apply(st["chain"], flow)
            # the pre-mask flow batch == what the unsharded selector sees
            pre = flow.batch
            with jax.named_scope("keyshard.route"):
                key = qr.selector.group.key_of(flow.env())
                mine = owner_of(key, D) == d
                # key-routed pre-pass: CURRENT/EXPIRED rows advance state
                # only on their owner; TIMER/RESET (and invalid) rows
                # broadcast so group eras advance in lockstep on every device
                keep = jnp.where(flow.sign != 0, mine, True)
                masked = EventBatch(
                    pre.ts, pre.kind, pre.valid & keep, pre.cols
                )
                flow = dataclasses.replace(flow, batch=masked)
            with jax.named_scope("selector"):
                sel_state, out = qr.selector.apply(st["sel"], flow)

            # ---- exact positional merge (the psum tree fold) ----
            # `mine` partitions EVERY row across the mesh, so the masked
            # psum reconstructs each lane's unsharded value exactly: the
            # owner computed it from the identical replicated inputs plus
            # the only aggregator lanes that row's group ever touches.
            # (the device scope `keyshard.exchange`: the collectives)
            with jax.named_scope("keyshard.exchange"):
                # the merge reads ownership from the hash itself, not from
                # whatever the route masked (XLA folds the two into one)
                mine = owner_of(key, D) == d
                merged_valid = lax.psum(out.valid.astype(jnp.int32), KEY_AXIS) > 0

                def merge_col(c):
                    if jnp.issubdtype(c.dtype, jnp.floating):
                        # bitcast BEFORE masking: summing float identities
                        # would flip -0.0 to +0.0 and canonicalize NaNs
                        bits_dt = {2: jnp.uint16, 4: jnp.uint32, 8: jnp.uint64}[
                            c.dtype.itemsize
                        ]
                        bits = lax.bitcast_convert_type(c, bits_dt)
                        summed = lax.psum(
                            jnp.where(mine, bits, jnp.zeros((), bits_dt)),
                            KEY_AXIS,
                        )
                        return lax.bitcast_convert_type(summed, c.dtype)
                    if c.dtype == jnp.bool_:
                        return (
                            lax.psum(
                                jnp.where(mine, c, False).astype(jnp.int32),
                                KEY_AXIS,
                            )
                            > 0
                        )
                    return lax.psum(
                        jnp.where(mine, c, jnp.zeros((), c.dtype)), KEY_AXIS
                    )

                out2 = EventBatch(
                    out.ts,
                    out.kind,
                    merged_valid,
                    {nm: merge_col(c) for nm, c in out.cols.items()},
                )

            if qr.lineage is not None:
                # same lanes as QueryRuntime._step_impl, from the same
                # tensors: raw input, pre-mask chain output, merged out
                aux_d = flow.aux
                aux_d[LIN + "in"] = b.valid & (b.kind == KIND_CURRENT)
                aux_d[LIN + "in_ts"] = b.ts
                aux_d[LIN + "w_valid"] = pre.valid
                aux_d[LIN + "w_kind"] = pre.kind
                aux_d[LIN + "w_ts"] = pre.ts
                aux_d[LIN + "out_valid"] = out2.valid
                aux_d[LIN + "out_kind"] = out2.kind
                if "__group_key__" in out2.cols:
                    aux_d[LIN + "gkey"] = out2.cols["__group_key__"]

            aux_out = {}
            with jax.named_scope("keyshard.exchange"):
                for k, v in flow.aux.items():
                    if k.startswith(LIN):
                        aux_out[k] = v  # replicated provenance lanes
                    elif k == "next_timer":
                        aux_out[k] = lax.pmin(
                            jnp.min(jnp.asarray(v)), KEY_AXIS
                        )
                    else:
                        # host-warned flags stay SCALAR bools
                        # (_check_aux_flags)
                        aux_out[k] = (
                            lax.psum(
                                jnp.asarray(v).astype(jnp.int32).sum(),
                                KEY_AXIS,
                            )
                            > 0
                        )

            new_st = {"chain": chain_state, "sel": sel_state}
            return (
                jax.tree_util.tree_map(lambda l: l[None], new_st),
                out2,
                aux_out,
            )

        fn = jax.shard_map(
            local,
            mesh=self.mesh,
            in_specs=(P(KEY_AXIS), P(), P()),
            out_specs=(P(KEY_AXIS), P(), P()),
            check_vma=False,
        )
        st2, out, aux = fn(state, batch, now)
        return st2, tstates, out, aux

    # ---- observability ---------------------------------------------------

    def describe_state(self) -> dict:
        """Per-device key occupancy and skew for /status.json, Prometheus
        (siddhi_keyshard_* families) and explain(). Device-derived fields
        are omitted until the query has state."""
        qr = self.qr
        g = qr.selector.group.capacity
        d: dict = {
            "query": qr.query_id,
            "devices": self.n,
            "axis": KEY_AXIS,
            "group_capacity": g,
            "path": self.path,
        }
        if qr.state is None:
            return d
        import jax

        with qr._receive_lock:
            n_dev = np.asarray(jax.device_get(qr.state["sel"]["group"]["n"]))
        keys = [int(x) for x in n_dev.reshape(-1)]
        total = sum(keys)
        d["per_device_keys"] = keys
        d["total_keys"] = total
        d["occupancy"] = [round(k / g, 4) for k in keys] if g else []
        mean = total / self.n if self.n else 0.0
        d["skew"] = round(max(keys) / mean, 3) if mean else 0.0
        return d

    # ---- snapshot SPI (core/persistence.py) ------------------------------

    def export_state(self, state):
        """Canonical single-device state tree for the snapshot: the [D, G]
        group tables collapse into one G-table (device-major slot order)
        and the [D, G]-leading aggregator lanes gather alongside. A
        restore re-hashes keys to owners, so the snapshot survives
        mesh-size changes (the rebalance path). Falls back to the raw
        sharded tree when the layout is not the canonical grouped shape."""
        import jax

        host = jax.tree_util.tree_map(
            lambda l: np.array(jax.device_get(l)), state
        )
        g = self.qr.selector.group.capacity
        sel = host.get("sel") if isinstance(host, dict) else None
        grp = sel.get("group") if isinstance(sel, dict) else None
        agg_leaves = (
            jax.tree_util.tree_leaves(sel.get("aggs"))
            if isinstance(sel, dict)
            else []
        )
        canonical = (
            grp is not None
            and isinstance(host, dict)
            and set(host) == {"chain", "sel"}
            and set(sel) <= {"aggs", "group"}
            and all(
                l.ndim >= 2 and l.shape[0] == self.n and l.shape[1] == g
                for l in agg_leaves
            )
        )
        if canonical:
            order = [
                (dd, s)
                for dd in range(self.n)
                for s in range(g)
                if grp["used"][dd, s]
            ]
            canonical = len(order) <= g
        if not canonical:
            return {"__keyshard_raw__": self.n, "state": host}

        one = jax.tree_util.tree_map(
            lambda l: np.array(jax.device_get(l)), self.qr.init_state()
        )
        pg = one["sel"]["group"]
        for i, (dd, s) in enumerate(order):
            pg["keys"][i] = grp["keys"][dd, s]
            pg["used"][i] = True
        pg["n"] = np.int32(len(order)).reshape(())

        def gather(dst, src):
            dst = np.array(dst)
            for i, (dd, s) in enumerate(order):
                dst[i] = src[dd, s]
            return dst

        one["sel"]["aggs"] = jax.tree_util.tree_map(
            gather, one["sel"]["aggs"], sel["aggs"]
        )
        return one

    def import_state(self, value):
        """Rebuild the [D]-sharded state from a canonical (or raw) snapshot
        tree, re-hashing every group key to its owner on THIS mesh."""
        import jax
        import jax.numpy as jnp

        if isinstance(value, dict) and "__keyshard_raw__" in value:
            snap_d = int(value["__keyshard_raw__"])
            if snap_d != self.n:
                raise ValueError(
                    f"query '{self.qr.query_id}': raw key-sharded snapshot "
                    f"taken on {snap_d} devices cannot restore onto "
                    f"{self.n} (canonical export required for rebalance)"
                )
            return jax.tree_util.tree_map(jnp.asarray, value["state"])

        host = jax.tree_util.tree_map(
            lambda l: np.array(jax.device_get(l)), value
        )
        g = self.qr.selector.group.capacity
        grp = host["sel"]["group"]
        ns = jax.tree_util.tree_map(
            lambda l: np.array(jax.device_get(l)), self.init_state()
        )
        ng = ns["sel"]["group"]
        owners = owner_of(np.asarray(grp["keys"], np.int64), self.n)
        counts = [0] * self.n
        place: dict = {}  # canonical slot -> (device, local slot)
        for s in range(g):
            if not grp["used"][s]:
                continue
            dd = int(owners[s])
            i = counts[dd]
            counts[dd] += 1
            ng["keys"][dd, i] = grp["keys"][s]
            ng["used"][dd, i] = True
            place[s] = (dd, i)
        ng["n"] = np.asarray(counts, np.int32)

        def scatter(dst, src):
            for s, (dd, i) in place.items():
                dst[dd, i] = src[s]
            return dst

        ns["sel"]["aggs"] = jax.tree_util.tree_map(
            scatter, ns["sel"]["aggs"], host["sel"]["aggs"]
        )
        return jax.tree_util.tree_map(jnp.asarray, ns)


# ---------------------------------------------------------------------------
# placement (called by ShardRuntime when axis == 'keys')
# ---------------------------------------------------------------------------


def apply_keyshard(app_runtime, devices) -> dict:
    """Arm key-sharded execution on every eligible grouped query. Returns
    qid -> placement info for /status.json and explain(); ineligible
    GROUPED queries get a {"sharded": False, "reason"} entry so the veto
    is observable (SA124-style). Idempotent: already-armed queries (churn
    re-arms) are left with their live [D] state."""
    from siddhi_tpu.core.query_runtime import QueryRuntime

    placed: dict = {}
    for qid, qr in list(app_runtime.queries.items()):
        if getattr(qr, "_keyshard", None) is not None:
            placed[qid] = {
                "sharded": True,
                "devices": qr._keyshard.n,
                "axis": KEY_AXIS,
                "group_capacity": qr.selector.group.capacity,
            }
            continue
        ok, why = keyed_shardable(qr)
        grouped = (
            type(qr) is QueryRuntime
            and getattr(qr.selector, "group", None) is not None
        )
        if not ok:
            if grouped:
                placed[qid] = {"sharded": False, "reason": why}
            continue
        if qr.state is not None:
            placed[qid] = {
                "sharded": False,
                "reason": "state already materialized",
            }
            continue
        ex = KeyShardedGroupExec(qr, devices)
        ex.arm()
        placed[qid] = {
            "sharded": True,
            "devices": ex.n,
            "axis": KEY_AXIS,
            "group_capacity": qr.selector.group.capacity,
        }
        sm = app_runtime.statistics_manager
        if sm is not None:
            sm.register_shard(f"query.{qid}", ex)
        log.info(
            "query '%s': group-by state key-sharded across %d devices",
            qid, ex.n,
        )
    return placed


def apply_join_mesh(app_runtime, devices) -> dict:
    """Place join window state across the mesh: every join-side state leaf
    whose leading (ring) axis divides the device count is sharded on
    P('keys'); the sides' jitted steps are re-jitted with explicit in/out
    shardings. The traced program is UNCHANGED — GSPMD realizes the probe
    as a cross-device gather — so emissions and `view_seq()` lineage stay
    byte-identical. Returns qid -> placement info."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from siddhi_tpu.core.join import JoinQueryRuntime

    D = len(devices)
    placed: dict = {}
    mesh = None
    for qid, qr in list(app_runtime.queries.items()):
        if type(qr) is not JoinQueryRuntime:
            continue
        if getattr(qr, "_joinshard", False):
            placed[qid] = {"sharded": True, "devices": D, "axis": KEY_AXIS}
            continue
        spec = jax.eval_shape(qr.init_state)

        def eligible(l):
            return l.ndim >= 1 and l.shape[0] >= D and l.shape[0] % D == 0

        n_sharded = sum(
            1 for l in jax.tree_util.tree_leaves(spec["join"]) if eligible(l)
        )
        if n_sharded == 0:
            placed[qid] = {
                "sharded": False,
                "reason": f"no join-state axis divisible by {D} devices",
            }
            continue
        if qr.state is not None:
            placed[qid] = {
                "sharded": False,
                "reason": "state already materialized",
            }
            continue
        if mesh is None:
            mesh = Mesh(np.array(devices), (KEY_AXIS,))
        shard = NamedSharding(mesh, P(KEY_AXIS))
        repl = NamedSharding(mesh, P())
        state_sh = {
            "join": jax.tree_util.tree_map(
                lambda l: shard if eligible(l) else repl, spec["join"]
            ),
            "sel": repl,
        }
        qr._steps = {
            side: jax.jit(
                lambda st, ts, b, now, _s=side: qr._step_impl(
                    st, ts, b, now, _s
                ),
                in_shardings=(state_sh, repl, repl, repl),
                out_shardings=(state_sh, repl, repl, repl),
                donate_argnums=(0,),
            )
            for side in ("l", "r")
        }
        qr._joinshard = True
        placed[qid] = {
            "sharded": True,
            "devices": D,
            "axis": KEY_AXIS,
            "sharded_leaves": n_sharded,
        }
        log.info(
            "query '%s': join window state sharded across %d devices "
            "(%d leaves)", qid, D, n_sharded,
        )
    return placed
