"""Partitions: per-key isolated query state.

Reference: core/partition/PartitionRuntime.java:68-370 — `partition with (expr
of Stream) begin ... end` lazily clones the whole inner query graph per key
value (:256-315) and routes events into per-key local junctions; range
partitions pick the first matching condition (executor/RangePartitionExecutor).

TPU-native design: instead of cloned object graphs, the inner query's carried
state gets a leading partition axis [P] and the step is `jax.vmap`ed over it —
one compiled program, every partition's windows/aggregators advancing in
parallel on device (SURVEY §2.7: partition -> vmap/segment over the key
dimension). A shared key->slot table (same machinery as group-by) maps key
values to partition slots; `#inner` streams stay [P]-shaped between inner
queries, never flattening until output leaves the partition.

A single-stream inner query steps ROUTED (`PartitionedQueryRuntime`): the
batch's rows are ordered by slot and laid out as `[P, B']` sub-batches, B'
a small multiple of a slot's even share of the batch (`sub_batch_rows`),
so the vmapped step costs what P * B' rows cost, not P * B. A slot that is
sent more than B' rows in one batch takes further passes inside the same
step. Every slot's emissions are then merged into one flat batch in the
order of the input rows that caused them, the order in which the reference's
PartitionStreamReceiver, which walks a chunk event by event, emits them.
Joins and patterns inside partitions still step every slot over the whole
batch under a mask (`step: masked` in the status).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from siddhi_tpu.core.aggregators import MEMBER_AGGREGATORS
from siddhi_tpu.core.errors import SiddhiAppCreationError
from siddhi_tpu.core.event import (
    EventBatch,
    KIND_CURRENT,
    KIND_TIMER,
    StreamSchema,
)
from siddhi_tpu.core.executor import Env, Scope, TS_ATTR, compile_expression
from siddhi_tpu.core.flow import Flow
from siddhi_tpu.core.join import JoinQueryRuntime
from siddhi_tpu.core.query_runtime import QueryRuntime
from siddhi_tpu.core.selector import aggregate_calls, aggregate_reads
from siddhi_tpu.core.types import AttrType
from siddhi_tpu.core.windows import SlidingWindow, _lanes32
from siddhi_tpu.ops.group import PROBE_MERGE, assign_slots
from siddhi_tpu.ops.prefix import compact_front, cummax, spread_back
from siddhi_tpu.query_api.execution import (
    InsertIntoStream,
    OutputEventsFor,
    Partition,
    Query,
    RangePartitionType,
    SingleInputStream,
    ValuePartitionType,
    WindowHandler,
)

DEFAULT_PARTITIONS = 32
NO_TIMER = jnp.iinfo(jnp.int64).max
BIG = jnp.iinfo(jnp.int32).max
# a slot's sub-batch: this many times its even share of the batch, and at
# least the floor
SUB_BATCH_SHARES = 4
SUB_BATCH_FLOOR = 8


def sub_batch_rows(bsz: int, p: int) -> int:
    """B': the rows one slot's sub-batch holds, from the batch's capacity
    and the partition capacity alone. Keys that arrive evenly fill a
    quarter of it; a slot that is sent more takes further passes."""
    return min(bsz, max(SUB_BATCH_FLOOR, SUB_BATCH_SHARES * -(-bsz // p)))


def _tile(x, p):
    return jnp.repeat(x[None], p, axis=0)


def _reduce_paux(auxs: dict, povf=None) -> dict:
    """Collapse vmapped per-partition aux values: timers take the earliest,
    boolean flags OR together; the key-table overflow folds in."""
    aux = {
        k: (v.min() if k == "next_timer" else v.any()) for k, v in auxs.items()
    }
    if povf is not None:
        aux["partition_overflow"] = aux.get(
            "partition_overflow", np.bool_(False)
        ) | povf
    return aux


def _in_order(key, lanes, rows: int, *then):
    """`lanes` by `key` (int32, BIG on a row that holds nothing), then by
    the `then` keys, rows of equal keys as they lie, cut to the first
    `rows` rows (the rows that hold something are never more); returns
    (key, lanes, whether a row that holds something was cut off). ONE sort
    that carries every lane: the compiler joins payload sorts on one key
    into one anyway, and the operation it makes for them carries no scope
    of ours (the merge's largest operation read under no scope, PERF.md
    PR 32)."""
    idx = jnp.arange(key.shape[0], dtype=jnp.int32)
    parts, rejoin = _lanes32(lanes)
    n_keys = 2 + len(then)
    key, *moved = jax.lax.sort((key, *then, idx, *parts), num_keys=n_keys)
    lanes = rejoin(moved[n_keys - 1:])
    lost = (key[rows:] != BIG).any()
    return jax.tree_util.tree_map(lambda x: x[:rows], (key, lanes)) + (lost,)


def _partition_block(qr, step: str) -> dict:
    """`snapshot_status()["queries"][q]["partition"]`: the key table's
    capacity and the keys it has seen, which step the query takes and how
    the key table is probed (`ops/group.py` `PROBE_MERGE`: a partition's
    table keeps no bucket index)."""
    part = {"capacity": getattr(qr, "p_logical", qr.p), "step": step,
            "probe": PROBE_MERGE}
    pr = getattr(qr, "partition_runtime", None)
    if pr is not None:
        try:
            part["used"] = int(pr.ptable["n"])
        except Exception:  # donated under us: introspection degrades
            part["used"] = None
    return part


def ring_columns(query: Query) -> Optional[frozenset]:
    """The columns a slot's `length` ring has to hold (`make_window`'s
    `held_cols`), None for all of them. Where nobody is handed the query's
    EXPIRED rows (it publishes CURRENT rows alone, through no rate limiter)
    and nothing reads them but the aggregates (the window is the chain's
    last stage: a filter or a stream function behind it reads an EXPIRED
    row's own columns; no aggregator reads the membership view), the ring
    holds what the aggregates and the group key read, no other lane."""
    handlers = query.input_stream.handlers
    if (
        not handlers or not isinstance(handlers[-1], WindowHandler)
        or handlers[-1].window.key != "length"
    ):
        return None
    if (
        query.output_stream.output_events is not OutputEventsFor.CURRENT
        or query.output_rate is not None
    ):
        return None
    calls = aggregate_calls(query.selector)
    if any(c.name.lower() in MEMBER_AGGREGATORS for c in calls):
        return None
    # nothing read at all (no aggregate, no group key): the whole ring,
    # whose step does not need a lane to go by
    return aggregate_reads(query.selector) or None


class PartitionedQueryRuntime(QueryRuntime):
    """One inner query with a leading [P] partition axis on its state.

    `key_of(env) -> (keys [B] int64, matched [B] bool)` routes outer-stream
    batches; None means the input is an `#inner` stream, whose batches come
    flat, in arrival order, with each row's slot beside them.
    """

    def __init__(
        self,
        query: Query,
        query_id: str,
        in_schema: StreamSchema,
        interner,
        p_capacity: int,
        key_of: Optional[Callable],
        group_capacity=None,
        time_capacity=None,
    ):
        super().__init__(
            query, query_id, in_schema, interner,
            group_capacity=group_capacity, tables={},
            time_capacity=time_capacity, held_cols=ring_columns(query),
        )
        self.p = int(p_capacity)
        # the DECLARED capacity: parallel/shard.py may pad self.p up to a
        # multiple of the mesh size with dead lanes; the shared ptable (and
        # so assign_slots' overflow threshold) stays at p_logical
        self.p_logical = self.p
        self.key_of = key_of
        self.inner_publish = None  # set when inserting into an #inner stream
        # B' of the last trace, and the route's counters since deploy
        # (device scalars, carried through the step; not part of a snapshot)
        self.sub_batch: Optional[int] = None
        self.route_stats = None
        self._pstep_outer = jax.jit(self._pstep_outer_impl, donate_argnums=(1,))
        self._pstep_inner = jax.jit(self._pstep_inner_impl, donate_argnums=(0,))

    def init_state(self):
        one = super().init_state()
        return jax.tree_util.tree_map(lambda x: _tile(x, self.p), one)

    def describe_state(self) -> dict:
        d = super().describe_state()
        if "window" in d:
            # one ring per slot: `capacity` is a slot's, `fill` the sum
            d["window"]["per"] = {"capacity": "slot", "fill": "all slots"}
        part = _partition_block(self, "routed")
        if self.sub_batch is not None:
            part["sub_batch"] = self.sub_batch
        with self._receive_lock:
            stats = self.route_stats
            if stats is not None:
                stats = jax.device_get(stats)
        part["extra_passes"] = int(stats["extra_passes"]) if stats else 0
        part["max_rows_per_slot"] = int(stats["max_rows"]) if stats else 0
        d["partition"] = part
        return d

    # ---- device ------------------------------------------------------------

    def _slot_step(self, state, batch: EventBatch, cause, now, slot_rows):
        """One slot's step over its sub-batch: `QueryRuntime._step_impl`'s
        plain path, with each output row's cause (the batch row whose
        arrival emitted it) beside it. Where the chain or the selector
        cannot say (a window that reports none, `order by`), the slot's
        rows all count as caused by the last row of its sub-batch.
        `slot_rows`: the most rows all slots' sub-batches hold together."""
        flow = Flow(batch=batch, ref=self.ref, now=now, cause=cause,
                    slot_rows=slot_rows)
        chain_state, flow = self.chain.apply(state["chain"], flow)
        with jax.named_scope("selector"):
            sel_state, out = self.selector.apply(state["sel"], flow)
        out_cause = flow.cause
        if out_cause is None or self.selector.order_by:
            last = jnp.where(batch.valid, cause, np.int32(-1)).max()
            out_cause = jnp.broadcast_to(last, out.valid.shape)
        return {"chain": chain_state, "sel": sel_state}, out, out_cause, flow.aux

    def _flat_rows(self, bsz: int, p: int, k_sub: int) -> int:
        """Capacity of the flat output: what `bsz` rows can emit at most
        where every row emits a bounded number (no window: itself; a length
        window: itself and the row it pushes out), over all the passes of
        a step; else what each of the `p` slots can emit in one pass."""
        win = self.chain.window
        per_row = 1 if win is None else win.emits_per_row
        if per_row is None:
            return p * k_sub
        return per_row * bsz

    def _routed(self, states, stats, batch: EventBatch, slot, active, now,
                p: Optional[int] = None):
        """The step over one flat batch whose `active` rows carry their
        slot: route, vmapped step over `[P, B']`, merge; in passes while a
        slot has rows left. TIMER rows cut the batch into segments: the
        rows before one are stepped first, then every slot is sent the
        TIMER row, in a pass of its own. `states` may hold `p` of the
        partition's slots only (parallel/mesh.py: a device's own), `slot`
        then counts among those. Returns (states, stats, flat output, its
        rows' slots, its rows' causes, aux)."""
        bsz = batch.capacity
        p = self.p if p is None else p
        sub = sub_batch_rows(bsz, self.p_logical)
        win = self.chain.window
        if isinstance(win, SlidingWindow) and win.held_cols is not None:
            # such a ring is stepped by slices alone: a sub-batch no longer
            # than the window
            sub = min(sub, win.w)
        self.sub_batch = sub
        n = p * sub
        pos = jnp.arange(bsz, dtype=jnp.int32)
        with jax.named_scope("partition.route"):
            active = active & (slot < p)
            is_timer = batch.valid & (batch.kind == KIND_TIMER)
            n_timers = is_timer.sum(dtype=jnp.int32)
            t32 = is_timer.astype(jnp.int32)
            seg = jnp.cumsum(t32) - t32
            # the sorted view: active rows by (segment, slot), in arrival
            # order inside a run; every other row behind them. One sort
            # carries the rows' lanes along (see `_in_order`)
            parts, rejoin = _lanes32({"ts": batch.ts, "cols": batch.cols})
            s_seg, s_slot, perm, *moved = jax.lax.sort(
                (jnp.where(active, seg, BIG), slot, pos, *parts), num_keys=3
            )
            rows = rejoin(moved)
            s_active = s_seg != BIG
            start = jnp.concatenate([
                jnp.ones((1,), jnp.bool_),
                (s_seg[1:] != s_seg[:-1]) | (s_slot[1:] != s_slot[:-1]),
            ])
            rank = pos - cummax(jnp.where(start, pos, 0))
            max_rows = jnp.where(s_active, rank + 1, 0).max()
            # the sorted rows are spread inside lanes that hold both them
            # and the [P, B'] lay-out
            room = max(n, bsz)
            wide = lambda x: jnp.pad(x, (0, room - bsz))  # noqa: E731

        def sub_batches(s, k):
            """([P, B'] sub-batches, their rows' positions in the batch,
            whether this is segment s's TIMER pass) of pass k of segment
            s: the rows of rank k B' .. (k + 1) B' - 1 of every run."""
            lo = k * sub
            keep = s_active & (s_seg == s) & (rank >= lo) & (rank < lo + sub)
            timer_pass = ~keep.any()
            lanes = {"rows": rows, "pos": perm,
                     "dest": s_slot * sub + rank - lo}
            # kept rows to the front: they are there already unless a run
            # before them was longer than B'
            n_keep = keep.sum(dtype=jnp.int32)
            lanes = jax.lax.cond(
                (keep == (pos < n_keep)).all(),
                lambda: lanes, lambda: compact_front(keep, lanes),
            )
            lanes = jax.tree_util.tree_map(wide, lanes)
            at = jnp.arange(room, dtype=jnp.int32)
            valid, lanes = spread_back(
                at < n_keep, lanes.pop("dest") - at, lanes
            )
            valid, lanes, at = jax.tree_util.tree_map(
                lambda x: x[:n], (valid, lanes, at))
            # a TIMER pass: the TIMER row of segment s, first in every
            # slot's sub-batch and alone there
            tau = jnp.argmax(is_timer & (seg == s)).astype(jnp.int32)
            first = at % sub == 0
            timer_row = {
                "rows": jax.tree_util.tree_map(
                    lambda x: x[tau], {"ts": batch.ts, "cols": batch.cols}),
                "pos": tau,
            }
            lanes = jax.tree_util.tree_map(
                lambda x, t: jnp.where(
                    timer_pass, jnp.where(first, t, jnp.zeros((), x.dtype)), x),
                lanes, timer_row,
            )
            valid = jnp.where(timer_pass, first, valid)
            kind = jnp.where(
                timer_pass, np.int8(KIND_TIMER), np.int8(KIND_CURRENT)
            )
            valid, lanes = jax.tree_util.tree_map(
                lambda x: x.reshape(p, sub), (valid, lanes))
            pb = EventBatch(
                lanes["rows"]["ts"], jnp.broadcast_to(kind, (p, sub)),
                valid, lanes["rows"]["cols"],
            )
            return pb, lanes["pos"], timer_pass

        def one_pass(states, s, k):
            with jax.named_scope("partition.route"):
                pb, pcause, timer_pass = sub_batches(s, k)
            states, outs, cause, auxs = jax.vmap(
                lambda st, b2, c: self._slot_step(st, b2, c, now, bsz)
            )(states, pb, pcause)
            return states, outs, cause, _reduce_paux(auxs), timer_pass

        _, outs0, _, aux0, _ = jax.eval_shape(
            one_pass, states, jnp.int32(0), jnp.int32(0))
        k_sub = outs0.valid.shape[1]
        flat_rows = self._flat_rows(bsz, p, k_sub)
        out_slot = jnp.repeat(jnp.arange(p, dtype=jnp.int32), k_sub)

        def merged(acc, outs, cause, first):
            """The flat output so far and one pass's [P, K'] emissions, in
            the order of their causes."""
            key = jnp.where(outs.valid, cause, BIG).reshape(-1)
            lanes = {"ts": outs.ts.reshape(-1), "kind": outs.kind.reshape(-1),
                     "cols": {c: x.reshape(-1) for c, x in outs.cols.items()},
                     "slot": out_slot}
            short = max(flat_rows - p * k_sub, 0)
            key = jnp.pad(key, (0, short), constant_values=BIG)
            lanes = jax.tree_util.tree_map(
                lambda x: jnp.pad(x, (0, short)), lanes)
            key, lanes, lost = _in_order(key, lanes, flat_rows)

            def joined():
                both = jax.tree_util.tree_map(
                    lambda a, b: jnp.concatenate([a, b]), acc, (key, lanes))
                k2, l2, lost2 = _in_order(*both, flat_rows)
                return k2, l2, lost | lost2

            return jax.lax.cond(first, lambda: (key, lanes, lost), joined)

        def body(carry):
            states, acc, aux, s, k, passes, extra, overflow, _ = carry
            states, outs, cause, aux_p, timer_pass = one_pass(states, s, k)
            with jax.named_scope("partition.merge"):
                key, lanes, lost = merged(acc, outs, cause, passes == 0)
            # a timer (min over the slots) is the last pass's; a flag any's
            aux = {
                name: v if name == "next_timer" else aux[name] | v
                for name, v in aux_p.items()
            }
            extra = extra + (~timer_pass & (k > 0)).astype(jnp.int32)
            s, k = (jnp.where(timer_pass, s + 1, s),
                    jnp.where(timer_pass, 0, k + 1))
            return (states, (key, lanes), aux, s, k, passes + 1, extra,
                    overflow | lost, more(s, k))

        def more(s, k):
            left = s_active & (s_seg == s) & (rank >= k * sub)
            return left.any() | (s < n_timers)

        acc0 = (
            jnp.full((flat_rows,), BIG, jnp.int32),
            jax.tree_util.tree_map(
                lambda x: jnp.zeros((flat_rows,), x.dtype),
                {"ts": outs0.ts, "kind": outs0.kind, "cols": outs0.cols,
                 "slot": out_slot},
            ),
        )
        zero = jnp.int32(0)
        aux_init = jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, x.dtype), aux0)
        if "next_timer" in aux_init:
            aux_init["next_timer"] = jnp.asarray(NO_TIMER, jnp.int64)
        states, (key, lanes), aux, _, _, _, extra, overflow, _ = (
            jax.lax.while_loop(
                lambda carry: carry[-1], body,
                (states, acc0, aux_init, zero, zero, zero, zero,
                 jnp.zeros((), jnp.bool_), more(zero, zero)),
            )
        )
        aux["window_overflow"] = aux.get("window_overflow", False) | overflow
        stats = {
            "extra_passes": stats["extra_passes"] + extra,
            "max_rows": jnp.maximum(stats["max_rows"], max_rows),
        }
        flat = EventBatch(lanes["ts"], lanes["kind"], key != BIG, lanes["cols"])
        return states, stats, flat, lanes["slot"], key, aux

    def _pstep_outer_impl(self, ptable, states, stats, batch: EventBatch, now):
        with jax.named_scope(f"q.{self.query_id}"):
            with jax.named_scope("partition.keys"):
                cols = {(self.ref, None, n): c for n, c in batch.cols.items()}
                cols[(self.ref, None, TS_ATTR)] = batch.ts
                env = Env(cols, now=now)
                keys, matched = self.key_of(env)
                active = batch.valid & (batch.kind == KIND_CURRENT) & matched
                pk, pu, pn, slot, _grp, povf = assign_slots(
                    ptable["keys"], ptable["used"], ptable["n"], keys, active
                )
                # assign_slots' dead slot equals the ptable capacity
                # (= p_logical): a key beyond it is routed nowhere
                active = active & (slot < self.p_logical)
            states, stats, flat, out_slot, _cause, aux = self._routed(
                states, stats, batch, slot, active, now
            )
        aux["partition_overflow"] = aux.get(
            "partition_overflow", np.bool_(False)
        ) | povf
        return {"keys": pk, "used": pu, "n": pn}, states, stats, flat, out_slot, aux

    def _pstep_inner_impl(self, states, stats, batch: EventBatch, slot, now):
        """`batch`: an inner query's flat output, `slot` its rows' slots."""
        with jax.named_scope(f"q.{self.query_id}"):
            active = batch.valid & (batch.kind == KIND_CURRENT)
            states, stats, flat, out_slot, _cause, aux = self._routed(
                states, stats, batch, slot, active, now
            )
        return states, stats, flat, out_slot, aux

    # ---- host ----------------------------------------------------------------

    def _ready(self):
        if self.state is None:
            self.state = self._fresh(self.init_state())
        if self.route_stats is None:
            self.route_stats = {
                "extra_passes": jnp.zeros((), jnp.int64),
                "max_rows": jnp.zeros((), jnp.int32),
            }

    def receive_partitioned(self, ptable, batch: EventBatch, now: int):
        """Outer-stream arrival. Returns (ptable', flat_out, its rows'
        slots, aux)."""
        with self._receive_lock:
            self._ready()
            with self._step_stage() as clock:
                ptable, self.state, self.route_stats, flat, slot, aux = (
                    self._pstep_outer(
                        ptable, self.state, self.route_stats, batch,
                        jnp.asarray(now, jnp.int64),
                    )
                )
            self._observe_compile(
                self._pstep_outer, ("", int(batch.ts.shape[0])), clock.ns
            )
        self._warn_aux(aux)
        return ptable, flat, slot, aux

    def receive_inner(self, batch: EventBatch, slot, now: int):
        with self._receive_lock:
            self._ready()
            with self._step_stage() as clock:
                self.state, self.route_stats, flat, out_slot, aux = (
                    self._pstep_inner(
                        self.state, self.route_stats, batch, slot,
                        jnp.asarray(now, jnp.int64),
                    )
                )
            self._observe_compile(
                self._pstep_inner, ("inner", int(batch.ts.shape[0])), clock.ns
            )
        self._warn_aux(aux)
        return flat, out_slot, aux


class PartitionedJoinQueryRuntime(JoinQueryRuntime):
    """A join whose per-side state carries a leading [P] partition axis —
    both sides' events route to their key's partition and probe only that
    partition's windows (reference: per-key cloned JoinStreamRuntimes,
    PartitionTestCase join coverage)."""

    def __init__(
        self,
        query: Query,
        query_id: str,
        left_schema: StreamSchema,
        right_schema: StreamSchema,
        interner,
        p_capacity: int,
        key_of_by_side: dict,  # side -> key fn
        group_capacity=None,
        join_capacity: int = 512,
        time_capacity=None,
    ):
        super().__init__(
            query, query_id, left_schema, right_schema, interner,
            group_capacity=group_capacity, join_capacity=join_capacity,
            tables={}, time_capacity=time_capacity,
        )
        if self.needs_scheduler["l"] or self.needs_scheduler["r"]:
            raise SiddhiAppCreationError(
                "time windows on join sides inside partitions are not "
                "supported yet"
            )
        self.p = int(p_capacity)
        self.key_of_by_side = key_of_by_side
        self._psteps = {
            side: jax.jit(
                lambda pt, st, b, now, _s=side: self._pstep_impl(pt, st, b, now, _s),
                donate_argnums=(1,),
            )
            for side in ("l", "r")
        }

    def init_state(self):
        one = super().init_state()
        return jax.tree_util.tree_map(lambda x: _tile(x, self.p), one)

    def describe_state(self) -> dict:
        d = super().describe_state()
        d["partition"] = _partition_block(self, "masked")
        return d

    def _pstep_impl(self, ptable, states, batch: EventBatch, now, side: str):
        sid = (self.join.left if side == "l" else self.join.right).stream_id
        cols = {(sid, None, n): c for n, c in batch.cols.items()}
        cols[(sid, None, TS_ATTR)] = batch.ts
        keys, matched = self.key_of_by_side[side](Env(cols, now=now))
        active = batch.valid & (batch.kind == KIND_CURRENT) & matched
        pk, pu, pn, slot, _grp, povf = assign_slots(
            ptable["keys"], ptable["used"], ptable["n"], keys, active
        )
        is_timer = batch.valid & (batch.kind == KIND_TIMER)

        def one(state, p):
            sub_valid = (active & (slot == p)) | is_timer
            b2 = EventBatch(batch.ts, batch.kind, sub_valid, batch.cols)
            st, _ts, out, aux = self._step_impl(state, {}, b2, now, side)
            return st, out, aux

        states2, outs, auxs = jax.vmap(one)(states, jnp.arange(self.p))
        aux = _reduce_paux(auxs, povf)
        return {"keys": pk, "used": pu, "n": pn}, states2, outs, aux

    def receive_partitioned(self, ptable, batch: EventBatch, now: int, side: str):
        with self._receive_lock:
            if self.state is None:
                self.state = self._fresh(self.init_state())
            ptable, self.state, outs, aux = self._psteps[side](
                ptable, self.state, batch, jnp.asarray(now, jnp.int64)
            )
        self._warn_aux(aux)
        return ptable, _flatten(outs), outs, aux


class PartitionedPatternQueryRuntime:
    """A pattern/sequence whose token table carries a leading [P] axis —
    each key value runs an independent NFA (reference: per-key cloned
    state runtimes, PartitionTestCase pattern/sequence coverage)."""

    def __init__(
        self,
        query: Query,
        query_id: str,
        schemas: dict,
        interner,
        p_capacity: int,
        key_fns: dict,  # stream_id -> key fn
        group_capacity=None,
        token_capacity: int = 128,
        count_capacity: int = 8,
        batch_size: int = 64,
    ):
        from siddhi_tpu.core.pattern_runtime import PatternQueryRuntime

        self._inner = PatternQueryRuntime(
            query, query_id, schemas, interner,
            group_capacity=group_capacity, token_capacity=token_capacity,
            count_capacity=count_capacity, batch_size=batch_size, tables={},
        )
        inner = self._inner
        self.query = query
        self.query_id = query_id
        self.prog = inner.prog
        self.out_schema = inner.out_schema
        self.output_events = inner.output_events
        self.query_callbacks = inner.query_callbacks
        self.rate_limiter = inner.rate_limiter
        self.table_op = None
        self.tables = {}
        # absent deadlines: every partition's NFA shares the TIMER feed;
        # next_timer min-reduces across the [P] axis (_reduce_paux)
        self.needs_scheduler = inner.needs_scheduler
        self.timer_target = None
        self.inner_publish = None
        self.p = int(p_capacity)
        self.state = None
        self._receive_lock = inner._receive_lock
        for sid in self.prog.stream_ids:
            if sid not in key_fns:
                raise SiddhiAppCreationError(
                    f"partition has no key for pattern stream '{sid}'"
                )
        self.key_fns = key_fns
        self.schemas = schemas
        self._psteps = {
            sid: jax.jit(
                lambda pt, st, b, now, _sid=sid: self._pstep_impl(pt, st, b, now, _sid),
                donate_argnums=(1,),
            )
            for sid in self.prog.stream_ids
        }

    # routing shared with BaseQueryRuntime via delegation
    @property
    def publish_fn(self):
        return self._inner.publish_fn

    @publish_fn.setter
    def publish_fn(self, fn):
        self._inner.publish_fn = fn

    def route_output(self, out, now, decode):
        self._inner.route_output(out, now, decode)

    def _warn_aux(self, aux):
        self._inner._warn_aux(aux)

    def flush_aux_warnings(self):
        self._inner.flush_aux_warnings()

    def init_state(self, now: int = 0):
        one = self._inner.init_state(now)
        return jax.tree_util.tree_map(lambda x: _tile(x, self.p), one)

    def describe_state(self) -> dict:
        return {
            "kind": type(self).__name__,
            "callbacks": len(self.query_callbacks),
            "partition": _partition_block(self, "masked"),
        }

    def _pstep_impl(self, ptable, states, batch: EventBatch, now, stream_id: str):
        cols = {(stream_id, None, n): c for n, c in batch.cols.items()}
        cols[(stream_id, None, TS_ATTR)] = batch.ts
        keys, matched = self.key_fns[stream_id](Env(cols, now=now))
        active = batch.valid & (batch.kind == KIND_CURRENT) & matched
        pk, pu, pn, slot, _grp, povf = assign_slots(
            ptable["keys"], ptable["used"], ptable["n"], keys, active
        )
        # a lane allocated to a key seen for the FIRST time must start with
        # freshly-stamped token state: all lanes share the vmapped state and
        # have had their virgin tokens' absent deadlines advancing since app
        # start, so a late key would otherwise inherit an already-elapsed
        # absence window (reference: AbsentStreamPreStateProcessor is armed
        # at partition-INSTANCE creation, PartitionRuntime.java:256-315)
        fresh = pu & ~ptable["used"]
        init_lane = self._inner.init_state(now)

        def _do_refresh(st):
            def _refresh(cur, init):
                mask = fresh.reshape((self.p,) + (1,) * (cur.ndim - 1))
                return jnp.where(mask, jnp.broadcast_to(init, cur.shape), cur)

            return jax.tree_util.tree_map(_refresh, st, init_lane)

        # steady state allocates no lanes: skip the full-state rewrite
        states = jax.lax.cond(fresh.any(), _do_refresh, lambda st: st, states)
        is_timer = batch.valid & (batch.kind == KIND_TIMER)
        step = self._inner._make_step(stream_id)

        def one(state, p):
            sub_valid = (active & (slot == p)) | is_timer
            b2 = EventBatch(batch.ts, batch.kind, sub_valid, batch.cols)
            st, _ts, out, aux = step(state, {}, b2, now)
            return st, out, aux

        states2, outs, auxs = jax.vmap(one)(states, jnp.arange(self.p))
        # TIMER rows riding a stream batch reach every lane; outputs and
        # timer re-arms from lanes with no live key must be masked just like
        # the dedicated timer path does
        outs = EventBatch(
            outs.ts, outs.kind, outs.valid & pu[:, None], outs.cols
        )
        if "next_timer" in auxs:
            auxs = {
                **auxs,
                "next_timer": jnp.where(
                    pu, auxs["next_timer"], np.int64(NO_TIMER)
                ),
            }
        aux = _reduce_paux(auxs, povf)
        return {"keys": pk, "used": pu, "n": pn}, states2, outs, aux

    def _ptimer_impl(self, states, used, batch: EventBatch, now):
        def one(state):
            st, _ts, out, aux = self._inner._make_step(None)(state, {}, batch, now)
            return st, out, aux

        states2, outs, auxs = jax.vmap(one)(states)
        # only lanes holding a live key may emit/schedule — unused lanes
        # still carry armed virgin tokens (absent-at-start would fire on
        # every empty lane otherwise)
        outs = EventBatch(
            outs.ts, outs.kind, outs.valid & used[:, None], outs.cols
        )
        if "next_timer" in auxs:
            auxs = {
                **auxs,
                "next_timer": jnp.where(
                    used, auxs["next_timer"], np.int64(NO_TIMER)
                ),
            }
        return states2, outs, _reduce_paux(auxs)

    def prime(self, now: int) -> dict:
        """Arm absent-at-start deadlines across every partition lane."""
        from siddhi_tpu.core.query_runtime import BaseQueryRuntime

        with self._receive_lock:
            if self.state is None:
                self.state = BaseQueryRuntime._fresh(self.init_state(now))
            t = jax.vmap(self.prog.next_timer)(self.state["tok"]).min()
        return {"next_timer": t}

    def receive_timer_partitioned(self, ptable, batch: EventBatch, t_ms: int):
        with self._receive_lock:
            if self.state is None:
                from siddhi_tpu.core.query_runtime import BaseQueryRuntime

                self.state = BaseQueryRuntime._fresh(self.init_state(t_ms))
            if not hasattr(self, "_ptimer"):
                self._ptimer = jax.jit(self._ptimer_impl, donate_argnums=(0,))
            self.state, outs, aux = self._ptimer(
                self.state, ptable["used"], batch, jnp.asarray(t_ms, jnp.int64)
            )
        self._warn_aux(aux)
        return _flatten(outs), aux

    def receive_partitioned(self, ptable, batch: EventBatch, now: int, stream_id: str):
        with self._receive_lock:
            if self.state is None:
                from siddhi_tpu.core.query_runtime import BaseQueryRuntime

                self.state = BaseQueryRuntime._fresh(self.init_state())
            ptable, self.state, outs, aux = self._psteps[stream_id](
                ptable, self.state, batch, jnp.asarray(now, jnp.int64)
            )
        self._warn_aux(aux)
        return ptable, _flatten(outs), outs, aux


def _flatten(outs: EventBatch) -> EventBatch:
    """[P, K] partitioned output -> [K*P] flat batch ordered by output
    position first (temporal order), partition second."""
    def f(x):
        return jnp.swapaxes(x, 0, 1).reshape(-1)

    return EventBatch(
        ts=f(outs.ts),
        kind=f(outs.kind),
        valid=f(outs.valid),
        cols={n: f(c) for n, c in outs.cols.items()},
    )


class PartitionRuntime:
    """Host orchestration of one `partition with (...) begin ... end` block."""

    def __init__(
        self, partition: Partition, app_runtime, pid: str, query_ids=None
    ):
        self.partition = partition
        self.app = app_runtime
        self.pid = pid
        self.p = app_runtime._capacity_annotation(
            "app:partitionCapacity", DEFAULT_PARTITIONS
        )
        interner = app_runtime.interner

        # key executors per partitioned stream
        # (reference: Value/RangePartitionExecutor)
        self.key_fns: dict[str, Callable] = {}
        for pt in partition.partition_types:
            schema = app_runtime.stream_schemas.get(pt.stream_id)
            if schema is None:
                raise SiddhiAppCreationError(
                    f"partition: stream '{pt.stream_id}' is not defined"
                )
            scope = Scope(interner)
            scope.add_stream(pt.stream_id, schema.attr_types)
            if isinstance(pt, ValuePartitionType):
                from siddhi_tpu.core.groupby import _as_key_col

                ce = compile_expression(pt.expression, scope)
                if ce.type is AttrType.OBJECT:
                    raise SiddhiAppCreationError("cannot partition by OBJECT")

                def key_of(env, _ce=ce):
                    k = _as_key_col(_ce(env), _ce.type)
                    return k, jnp.ones_like(k, dtype=jnp.bool_)

            else:
                assert isinstance(pt, RangePartitionType)
                conds = []
                for rp in pt.ranges:
                    c = compile_expression(rp.condition, scope)
                    if c.type is not AttrType.BOOL:
                        raise SiddhiAppCreationError(
                            "range partition conditions must be boolean"
                        )
                    conds.append(c)

                def key_of(env, _conds=tuple(conds)):
                    key = None
                    matched = None
                    for i, c in enumerate(_conds):
                        m = c(env)
                        if key is None:
                            key = jnp.where(m, np.int64(i), np.int64(-1))
                            matched = m
                        else:
                            key = jnp.where(~matched & m, np.int64(i), key)
                            matched = matched | m
                    return key, matched  # unmatched rows are dropped

            self.key_fns[pt.stream_id] = key_of

        # shared partition key table (one key space per partition block,
        # reference: PartitionRuntime per-key instance map)
        self.ptable = {
            "keys": jnp.zeros((self.p,), jnp.int64),
            "used": jnp.zeros((self.p,), jnp.bool_),
            "n": jnp.zeros((), jnp.int32),
        }

        # inner (#stream) plumbing: [P]-shaped pub/sub
        self.inner_schemas: dict[str, StreamSchema] = {}
        self.inner_subscribers: dict[str, list] = {}

        self.queries: list[PartitionedQueryRuntime] = []
        if query_ids is None:
            # direct construction (app_runtime passes the shared
            # assignment): fall back to the same helper for this block
            from siddhi_tpu.query_api.annotation import find_annotation

            query_ids = []
            unnamed = 0
            for q in partition.queries:
                info = find_annotation(q.annotations, "info")
                qid = (
                    info.element("name") if info else None
                ) or f"{pid}_query{unnamed}"
                unnamed += 1
                query_ids.append((qid, q))
        for qid, q in query_ids:
            self._add_query(qid, q)

    def _add_query(self, qid: str, query: Query) -> None:
        app = self.app
        stream = query.input_stream
        from siddhi_tpu.query_api.execution import (
            JoinInputStream,
            StateInputStream,
        )

        if isinstance(stream, JoinInputStream):
            self._add_join_query(qid, query)
            return
        if isinstance(stream, StateInputStream):
            self._add_pattern_query(qid, query)
            return
        if not isinstance(stream, SingleInputStream):
            raise SiddhiAppCreationError(
                f"{type(stream).__name__} queries inside partitions are not "
                "supported yet"
            )
        is_inner = stream.is_inner
        if is_inner:
            in_schema = self.inner_schemas.get(stream.stream_id)
            if in_schema is None:
                raise SiddhiAppCreationError(
                    f"inner stream '#{stream.stream_id}' is not produced by an "
                    "earlier query in this partition"
                )
            key_of = None
        else:
            in_schema = app.stream_schemas.get(stream.stream_id)
            if in_schema is None:
                raise SiddhiAppCreationError(
                    f"stream '{stream.stream_id}' is not defined"
                )
            key_of = self.key_fns.get(stream.stream_id)
            if key_of is None:
                raise SiddhiAppCreationError(
                    f"partition has no key for stream '{stream.stream_id}'"
                )

        qr = PartitionedQueryRuntime(
            query, qid, in_schema, app.interner,
            p_capacity=self.p, key_of=key_of,
            group_capacity=app.group_capacity,
            time_capacity=app.time_capacity,
        )
        qr.partition_runtime = self
        self.queries.append(qr)
        app.queries[qid] = qr

        out = query.output_stream
        inner_target = isinstance(out, InsertIntoStream) and out.is_inner
        if inner_target:
            self.inner_schemas[out.target] = StreamSchema(
                out.target, qr.out_schema.attrs
            )
            subs = self.inner_subscribers.setdefault(out.target, [])
            from siddhi_tpu.core.app_runtime import _make_insert_transform

            # honor `insert [current|expired|all] events into #T` and rewrite
            # inserted kinds to CURRENT, like the outer insert path
            transform = _make_insert_transform(out.output_events)

            def publish_inner(flat, slot, now, _subs=subs, _t=transform):
                flat = _t(flat)
                for fn in _subs:
                    fn(flat, slot, now)

            qr.inner_publish = publish_inner
        else:
            app._wire_insert(qr)

        decode = app._decode
        table_apply = self._attach_table_output(qr, query)

        if is_inner:
            def recv_inner(batch, slot, now, _qr=qr):
                flat, out_slot, aux = _qr.receive_inner(batch, slot, now)
                self._route(_qr, flat, out_slot, now, decode)
                if table_apply is not None:
                    table_apply(flat, now)
                app._maybe_schedule(_qr, aux)

            self.inner_subscribers[stream.stream_id].append(recv_inner)

            if qr.needs_scheduler:
                # a TIMER row reaches every slot, whatever its own says
                def fire_inner(t_ms: int, _qr=qr, _schema=in_schema) -> None:
                    batch = app._timer_batch(_schema, t_ms)
                    slot = jnp.zeros((batch.capacity,), jnp.int32)
                    with app._process_lock:
                        flat, out_slot, aux = _qr.receive_inner(batch, slot, t_ms)
                        self._route(_qr, flat, out_slot, t_ms, decode)
                    app._maybe_schedule(_qr, aux)

                qr.timer_target = fire_inner
        else:
            def receive(batch: EventBatch, now: int, _qr=qr) -> None:
                with app._process_lock:
                    self.ptable, flat, out_slot, aux = _qr.receive_partitioned(
                        self.ptable, batch, now
                    )
                    self._route(_qr, flat, out_slot, now, decode)
                    if table_apply is not None:
                        table_apply(flat, now)
                app._maybe_schedule(_qr, aux)

            app._junction(stream.stream_id).subscribe(
                receive, name=f"query.{qid}"
            )

            if qr.needs_scheduler:
                def fire(t_ms: int, _qr=qr, _schema=in_schema) -> None:
                    batch = app._timer_batch(_schema, t_ms)
                    with app._process_lock:
                        self.ptable, flat, out_slot, aux = _qr.receive_partitioned(
                            self.ptable, batch, t_ms
                        )
                        self._route(_qr, flat, out_slot, t_ms, decode)
                    app._maybe_schedule(_qr, aux)

                qr.timer_target = fire

    def _check_output_target(self, query: Query, allow_inner: bool = False) -> None:
        out = query.output_stream
        if not allow_inner and getattr(out, "is_inner", False):
            raise SiddhiAppCreationError(
                "#inner outputs from joins/patterns inside partitions are "
                "not supported yet"
            )

    def _attach_table_output(self, qr, query: Query):
        """Table writes from inside a partition apply OUTSIDE the vmapped
        step, on the flattened [P*K] output: every partition's rows merge
        into the ONE shared table in output order (reference: cloned inner
        runtimes all write the same shared table instance,
        PartitionRuntime.java:256-315 + TablePartitionTestCase).

        Returns an `apply(flat_batch, now)` host hook, or None."""
        from siddhi_tpu.core.table import compile_table_output

        app = self.app
        top = compile_table_output(
            query.output_stream, qr.out_schema, app.tables, app.interner
        )
        if top is None:
            return None
        target = query.output_stream.target
        tids = sorted(app.tables)

        @jax.jit
        def step(tstates, batch, now):
            aux = {}
            return top(tstates, batch, now, aux), aux

        def apply(flat: EventBatch, now: int) -> None:
            tstates = {tid: app.tables[tid].state for tid in tids}
            tstates, aux = step(tstates, flat, jnp.asarray(now, jnp.int64))
            for tid in tids:
                app.tables[tid].state = tstates[tid]
            app.tables[target].notify_change()
            qr._warn_aux(aux)

        return apply

    def _add_join_query(self, qid: str, query: Query) -> None:
        app = self.app
        self._check_output_target(query)
        join = query.input_stream
        schemas = []
        key_by_side = {}
        for side, s in (("l", join.left), ("r", join.right)):
            if s.is_inner:
                raise SiddhiAppCreationError(
                    "#inner streams on join sides inside partitions are not "
                    "supported yet"
                )
            sch = app.stream_schemas.get(s.stream_id)
            if sch is None:
                raise SiddhiAppCreationError(
                    "only plain streams can join inside partitions"
                )
            kf = self.key_fns.get(s.stream_id)
            if kf is None:
                raise SiddhiAppCreationError(
                    f"partition has no key for stream '{s.stream_id}'"
                )
            key_by_side[side] = kf
            schemas.append(sch)
        qr = PartitionedJoinQueryRuntime(
            query, qid, schemas[0], schemas[1], app.interner,
            p_capacity=self.p, key_of_by_side=key_by_side,
            group_capacity=app.group_capacity,
            join_capacity=app._capacity_annotation("app:joinCapacity", 512),
            time_capacity=app.time_capacity,
        )
        qr.partition_runtime = self
        self.queries.append(qr)
        app.queries[qid] = qr
        app._wire_insert(qr)
        decode = app._decode
        table_apply = self._attach_table_output(qr, query)

        def receive_side(batch: EventBatch, now: int, side: str, _qr=qr) -> None:
            with app._process_lock:
                self.ptable, flat, _p_out, aux = _qr.receive_partitioned(
                    self.ptable, batch, now, side
                )
                _qr.route_output(flat, now, decode)
                if table_apply is not None:
                    table_apply(flat, now)

        if join.left.stream_id == join.right.stream_id:
            j = app._junction(join.left.stream_id)
            j.subscribe(
                lambda b, now: (receive_side(b, now, "l"), receive_side(b, now, "r")),
                name=f"query.{qid}",
            )
        else:
            app._junction(join.left.stream_id).subscribe(
                lambda b, now: receive_side(b, now, "l"),
                name=f"query.{qid}",
            )
            app._junction(join.right.stream_id).subscribe(
                lambda b, now: receive_side(b, now, "r"),
                name=f"query.{qid}",
            )

    def _add_pattern_query(self, qid: str, query: Query) -> None:
        app = self.app
        self._check_output_target(query)
        # guard the NFA builder's raw stream_schemas indexing with a named
        # error (fallback path when semantic analysis is disabled)
        from siddhi_tpu.query_api.execution import iter_state_streams

        for s in iter_state_streams(query.input_stream.state):
            if s.stream_id not in app.stream_schemas:
                raise SiddhiAppCreationError(
                    f"query '{qid}': pattern stream '{s.stream_id}' is not "
                    "defined (patterns consume streams, not tables or windows)"
                )
        qr = PartitionedPatternQueryRuntime(
            query, qid, app.stream_schemas, app.interner,
            p_capacity=self.p, key_fns=self.key_fns,
            group_capacity=app.group_capacity,
            token_capacity=app._capacity_annotation("app:patternCapacity", 128),
            count_capacity=app._capacity_annotation("app:countCapacity", 8),
            batch_size=app.batch_size,
        )
        qr.partition_runtime = self
        self.queries.append(qr)
        app.queries[qid] = qr
        app._wire_insert(qr)
        decode = app._decode
        table_apply = self._attach_table_output(qr, query)

        def receive(batch: EventBatch, now: int, sid: str, _qr=qr) -> None:
            with app._process_lock:
                self.ptable, flat, _p_out, aux = _qr.receive_partitioned(
                    self.ptable, batch, now, sid
                )
                _qr.route_output(flat, now, decode)
                if table_apply is not None:
                    table_apply(flat, now)
                app._maybe_schedule(_qr, aux)

        for sid in qr.prog.stream_ids:
            app._junction(sid).subscribe(
                lambda b, now, _sid=sid: receive(b, now, _sid),
                name=f"query.{qid}",
            )

        if qr.needs_scheduler:
            from siddhi_tpu.core.app_runtime import _pattern_timer_batch

            def fire(t_ms: int, _qr=qr) -> None:
                batch = _pattern_timer_batch(t_ms)
                with app._process_lock:
                    flat, aux = _qr.receive_timer_partitioned(
                        self.ptable, batch, t_ms
                    )
                    _qr.route_output(flat, t_ms, decode)
                    if table_apply is not None:
                        table_apply(flat, t_ms)
                app._maybe_schedule(_qr, aux)

            qr.timer_target = fire

    def _route(self, qr, flat: EventBatch, slot, now: int, decode) -> None:
        if qr.inner_publish is not None:
            qr.inner_publish(flat, slot, now)
            # callbacks on inner-targeted queries still see the flat view
            if qr.query_callbacks:
                qr.route_output(flat, now, decode)
        else:
            qr.route_output(flat, now, decode)
