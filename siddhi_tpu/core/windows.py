"""Window processors — device-resident ring buffers with batched emission.

Reference: query/processor/stream/window/*.java (17 built-ins). The reference
mutates per-event queues inside synchronized blocks; here each window is a pure
stage over the Flow with a fixed-capacity slot-indexed ring as carried state, and
the interleaved CURRENT/EXPIRED/RESET emission order of the reference is
reproduced by assigning every candidate output event a sort key
(trigger_row, kind, seq) and lexsorting — one vectorized program, no per-event
control flow.

Emission-order contracts reproduced (validated against the reference sources):
- length: per arrival when full, evictee EXPIRED emitted before the CURRENT
  (LengthWindowProcessor.java:102-138 insertBeforeCurrent)
- time/externalTime: all due EXPIREDs flush before the triggering CURRENT
  (TimeWindowProcessor.java:79+)
- lengthBatch/timeBatch: on flush, prev-batch EXPIREDs, then RESET, then the
  bucket's CURRENTs (LengthBatchWindowProcessor.java:108-160)
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from siddhi_tpu.core.errors import SiddhiAppCreationError
from siddhi_tpu.core.event import (
    EventBatch,
    KIND_CURRENT,
    KIND_EXPIRED,
    KIND_RESET,
    KIND_TIMER,
    StreamSchema,
)
from siddhi_tpu.core.executor import Env, Scope, TS_ATTR, compile_expression
from siddhi_tpu.ops.prefix import (
    PICK_ROWS as _PICK_ROWS,
    compact_front as _compact_front,
    cummax as _cummax,
    segmented_carry as _segmented_carry,
)
from siddhi_tpu.ops.group import permute_by as _permute_by
from siddhi_tpu.ops.scatter import (
    U32Pair,
    compact_set_at as _compact_set_at,
    is_pair as _is_pair,
    _is_wide,
    _join64,
    _split64,
    join_pairs as _join_pairs,
    ring_swap as _ring_swap,
    set_at as _set_at,
    split_like as _split_like,
)
from siddhi_tpu.core.flow import Flow
from siddhi_tpu.core.types import AttrType
from siddhi_tpu.query_api.definition import WindowSpec
from siddhi_tpu.query_api.expression import Constant

BIG = jnp.iinfo(jnp.int32).max
NO_TIMER = jnp.iinfo(jnp.int64).max

DEFAULT_TIME_CAPACITY = 1024
# "no window time yet": far below any event time, and `NO_TIME - T` still
# does not wrap
NO_TIME = -(1 << 62)
# what a time-bounded ring carries beside its lanes: the seq of its oldest
# live row, the running maximum of window time, and three counters
_FIFO_COUNTERS = ("expired", "passes", "early")
_FIFO_SCALARS = ("head", "wmax") + _FIFO_COUNTERS


def _const_raw(spec: WindowSpec, i: int, what: str):
    if i >= len(spec.parameters) or not isinstance(spec.parameters[i], Constant):
        raise SiddhiAppCreationError(f"window {spec.name}: parameter {i} must be a constant {what}")
    return spec.parameters[i].value


def _const_param(spec: WindowSpec, i: int, what: str) -> int:
    return int(_const_raw(spec, i, what))


class WindowStage:
    """Base: (state, Flow) -> (state', Flow') with out-capacity growth."""

    needs_scheduler = False
    # tumbling windows flip the selector into batch group-by output mode
    # (reference: QueryParser batchProcessingAllowed -> QuerySelector)
    is_batch = False
    # cron-driven windows schedule fire times host-side (CronSchedule)
    cron_schedule = None
    # the most rows one arriving row can put in the flow, itself included,
    # where that is bounded; None where a trigger may release any number
    # (core/partition.py sizes a partition's flat output by it)
    emits_per_row: Optional[int] = None

    def init_state(self):
        raise NotImplementedError

    def apply(self, state, flow: Flow):
        raise NotImplementedError

    def takes_fifo(self, bsz: int) -> bool:
        """Whether the caller may step this window in passes instead of
        `apply` (`SlidingWindow.fifo_pass`); no other window can."""
        return False

    def view(self, state):
        """Stored window contents for probing: `(cols, ts, mask)` with rows in
        insertion order (reference: FindableProcessor.find iterating the window
        buffer, query/processor/stream/window/LengthWindowProcessor.java:144)."""
        raise NotImplementedError(f"{type(self).__name__} is not findable")

    def share_signature(self):
        """Canonical runtime identity for cross-query state sharing
        (core/fusion_exec.py `_chain_share_key`): two window stages whose
        signatures are equal and non-None hold byte-identical device state
        under identical input, so one ring/bucket can serve both. The base
        answer is None (never share) — only the plain ring (SlidingWindow)
        and bucket (BatchWindow) shapes opt in; exotic windows (sort,
        frequent, cron, ...) carry parameters this tuple cannot see."""
        return None

    def view_seq(self, state):
        """Per-slot window admission seq ids, permuted like `view()` (the
        SlidingWindow monotone `seq` lane; -1 = empty slot). None when this
        window type tracks no admission order — join lineage then records
        the partner as unresolved (observability/lineage.py)."""
        return None

    def describe_state(self, state) -> dict:
        """Introspection snapshot of the live buffer: type, fill, capacity,
        oldest/newest stored timestamps. Pull-only (one host read per call);
        rides `view()` so every findable window gets it for free."""
        d: dict = {"type": type(self).__name__}
        cap = getattr(self, "w", None)
        if cap is not None:
            d["capacity"] = int(cap)
        dur = getattr(self, "t", None)
        if dur is not None:
            d["duration_ms"] = int(dur)
        try:
            fill, oldest, newest = self._fill_summary(state)
        except NotImplementedError:
            return d
        except Exception:
            # a concurrent donated-state dispatch (fused ingest) can delete
            # the buffers under us; introspection degrades, never raises
            d["fill"] = None
            return d
        d["fill"] = int(fill)
        if fill:
            d["oldest_ts"] = int(oldest)
            d["newest_ts"] = int(newest)
        return d

    def _fill_summary(self, state):
        """(fill, oldest ts, newest ts) of the stored rows; the last two
        mean nothing when fill is 0."""
        _cols, ts, mask = self.view(state)
        m = np.asarray(mask)
        lived = np.asarray(ts)[m]
        if not lived.size:
            return 0, 0, 0
        return lived.size, lived.min(), lived.max()


# ---------------------------------------------------------------------------
# sliding family: length / time / timeLength / externalTime / delay
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _ElementView:
    """SlidingWindow._element_view: [W + B] lanes, ring slots then batch rows."""

    ts: jnp.ndarray
    wts: jnp.ndarray
    seq: jnp.ndarray
    cols: dict
    present: jnp.ndarray
    trig_rank: jnp.ndarray
    len_trig_valid: jnp.ndarray
    perm: jnp.ndarray  # [B]: rank -> batch row


class SlidingWindow(WindowStage):
    """Generic ring: capacity W (always length-evicts at W) plus optional time
    predicate over a per-event 'window time' (event ts, or an attribute for
    externalTime). Covers length(N) [W=N], time(T), timeLength(T, N),
    externalTime(tsAttr, T).

    Overflow policy for time windows: if more than W events are simultaneously
    live, the oldest are evicted EARLY — they are still emitted as EXPIRED (the
    capacity eviction rides the same candidate path), so downstream aggregates
    stay exactly consistent; only the expiry *time* is early. The reference has
    no such bound (unbounded Java queues): the step raises the aux flag
    `window_early_expiry` and counts the rows (`early`), and the capacity
    comes from the app, `@app:timeCapacity(size='N')`.

    A time-bounded window is a FIFO, as the reference's is: it expires from
    the head of its queue and stops at the first row not yet due. Two steps
    keep that rule, chosen at trace time (`time_step`): `apply`, which sets
    every ring row against every batch row (a `[W + B, B]` matrix, any
    shape, TIMER rows, the membership matrix), and `fifo_plan` /
    `fifo_pass` / `fifo_commit`, whose cost is the rows that enter and
    leave, for the caller that can run passes (`takes_fifo`)."""

    def __init__(
        self,
        schema: StreamSchema,
        ref: str,
        capacity: int,
        duration_ms: Optional[int] = None,
        time_attr: Optional[str] = None,
        use_scheduler: bool = False,
        capacity_is_limit: bool = False,
        held_cols=None,
    ):
        self.schema = schema
        self.ref = ref
        self.w = int(capacity)
        self.t = duration_ms
        self.time_attr = time_attr
        self.needs_scheduler = use_scheduler
        # time / externalTime: the capacity is the engine's bound on the live
        # rows, not the query's (timeLength states its own), so a row that
        # leaves for want of room is flagged (`window_early_expiry`)
        self.capacity_is_limit = capacity_is_limit
        # which length step the last trace took: "slice" (O(batch)) or
        # "scatter"; None for time-bounded windows and before the first trace
        self.ring_step: Optional[str] = None
        # which step a time-bounded window's last trace took: "fifo" (the
        # rows that enter and leave, in passes: `fifo_plan`) or "matrix"
        # (every ring row against every batch row: `apply`)
        self.time_step: Optional[str] = None
        # the columns the ring holds where that is not all of them; then
        # it holds no `ts`, `wts` or `seq` lane either. For the caller whose
        # EXPIRED rows are read for nothing else (a length window that is
        # its chain's last stage, behind a query that publishes its CURRENT
        # rows alone; core/partition.py), and who steps the window by
        # `_apply_length_slice` only. Every lane less is a scatter less
        # per step
        self.held_cols: Optional[frozenset] = None
        if held_cols is not None:
            assert self.t is None
            self.held_cols = frozenset(held_cols) & set(schema.attr_names)

    @property
    def emits_per_row(self) -> Optional[int]:
        # a length window: the row and the one it pushes out
        return 2 if self.t is None else None

    def describe_state(self, state) -> dict:
        d = super().describe_state(state)
        if self.held_cols is not None:
            d["held_cols"] = sorted(self.held_cols)
            d.pop("oldest_ts", None), d.pop("newest_ts", None)
        if self.ring_step is not None:
            d["ring_step"] = self.ring_step
        if any(map(_is_pair, jax.tree_util.tree_leaves(state, is_leaf=_is_pair))):
            d["wide_lanes"] = "u32x2"
        if self.t is not None:
            if self.time_step is not None:
                d["time_step"] = self.time_step
            try:
                n = jax.device_get({k: state[k] for k in _FIFO_COUNTERS})
            except Exception:  # donated under us: see WindowStage
                return d
            # rows expired; passes beyond a step's first, taken because a
            # flow was full; rows that left for want of capacity
            d["expired_rows"] = int(np.sum(n["expired"]))
            d["extra_passes"] = int(np.sum(n["passes"]))
            d["early_expired"] = int(np.sum(n["early"]))
        return d

    def _fill_summary(self, state):
        if self.held_cols is not None:
            # no `seq` lane: a ring (or each of a [P] stack) holds what it
            # was sent, up to its capacity
            sent = np.asarray(jax.device_get(state["total"]))
            return int(np.minimum(sent, self.w).sum()), 0, 0
        # one reduction over two lanes: view() would sort and gather the
        # whole ring, every column of it
        return jax.device_get(
            _ring_summary(state["seq"], state["ts"], state.get("head"))
        )

    @staticmethod
    def lanes(state) -> dict:
        """The state with its logical lanes: every 64-bit ring lane (`ts`,
        `wts`, `seq`, long columns), held as a U32Pair, joined to `int64`.
        For readers of the whole ring; inside a program the join fuses
        into what consumes it."""
        return _join_pairs(state)

    def share_signature(self):
        if self.needs_scheduler or self.held_cols is not None:
            return None  # timer-armed: host scheduling owns per-query state
        return (
            "SlidingWindow", self.w, self.t, self.time_attr,
        )

    def init_state(self):
        """The ring. A lane whose dtype is 8 bytes wide is a U32Pair of two
        [W] halves (ops/scatter.py): no 64-bit array of ring length is a
        parameter or a result of any program that carries the state."""

        def lane(dtype, fill=0):
            if _is_wide(dtype):
                return U32Pair.full((self.w,), fill, dtype)
            return jnp.full((self.w,), fill, dtype)

        cols = {n: lane(a.dtype) for n, a in self.schema.empty_batch(1).cols.items()}
        if self.held_cols is not None:
            return {
                "cols": {n: c for n, c in cols.items() if n in self.held_cols},
                "total": jnp.zeros((), jnp.int64),
            }
        state = {
            "cols": cols,
            "ts": lane(jnp.int64),
            "wts": lane(jnp.int64),
            "seq": lane(jnp.int64, -1),
            "total": jnp.zeros((), jnp.int64),
        }
        if self.t is not None:
            # a time window is a FIFO (reference: ExternalTimeWindowProcessor
            # walks its queue from the head and stops at the first row not
            # yet due): the live rows are seq in [head, total), and `wts`
            # holds the running maximum of window time, so it is sorted
            state.update({k: jnp.zeros((), jnp.int64) for k in _FIFO_SCALARS})
            state["wmax"] = jnp.full((), NO_TIME, jnp.int64)
        return state

    def _window_time(self, b: EventBatch):
        """Window time of each batch row: the event ts, or externalTime's
        attribute."""
        if self.time_attr is not None:
            return b.cols[self.time_attr].astype(jnp.int64)
        return b.ts

    def apply(self, state, flow: Flow):
        b = flow.batch
        bsz = b.capacity
        w = self.w
        k = w + bsz

        valid_cur = b.valid & (b.kind == KIND_CURRENT)
        is_timer = b.valid & (b.kind == KIND_TIMER)
        bwts = self._window_time(b)
        rank = jnp.cumsum(valid_cur.astype(jnp.int32)) - valid_cur.astype(jnp.int32)
        c = valid_cur.sum(dtype=jnp.int32)

        if self.t is None:
            # Pure length window: deaths pair 1:1 with insertions (the
            # insertion of seq_e + W evicts seq_e), so the EXPIRED/CURRENT
            # interleaving is pure rank arithmetic — no candidate lexsort
            # (reference behavior: LengthWindowProcessor.java emits the
            # displaced event then the arriving one, per event). The step
            # adapts on a shape: with capacity >= batch no row can expire
            # inside the batch that brought it, and the step touches only
            # the ring rows it replaces.
            self.ring_step = self._pick_ring_step(bsz)
            step = (
                self._apply_length_slice
                if self.ring_step == "slice"
                else self._apply_length
            )
            return step(state, flow, valid_cur, bwts, rank, c)

        self.time_step = "matrix"
        trigger_ok = valid_cur | is_timer
        # window time as the reference's loop meets it: it expires from the
        # head of its queue and stops at the first row not yet due, so a row
        # leaves once the running maximum of the triggers' time has passed
        # the running maximum of the rows' up to it, in any order of arrival
        bwts = jnp.maximum(
            state["wmax"],
            _cummax(jnp.where(trigger_ok, bwts, np.int64(NO_TIME))),
        )
        ev = self._element_view(state, b, bwts, valid_cur, rank, c)
        elem_ts, elem_seq, elem_cols, present = ev.ts, ev.seq, ev.cols, ev.present
        own_row = jnp.concatenate(
            [jnp.full((w,), -1, jnp.int32), jnp.arange(bsz, dtype=jnp.int32)]
        )
        trig_row_len = jnp.where(
            ev.len_trig_valid, ev.perm[jnp.clip(ev.trig_rank, 0, bsz - 1)], BIG
        )

        due = (
            trigger_ok[None, :]
            & present[:, None]
            & (bwts[None, :] - ev.wts[:, None] >= self.t)
            & (jnp.arange(bsz, dtype=jnp.int32)[None, :] >= own_row[:, None])
        )
        has_time_trig = due.any(axis=1)
        trig_row_time = jnp.where(has_time_trig, jnp.argmax(due, axis=1).astype(jnp.int32), BIG)

        trig_row = jnp.minimum(trig_row_len, trig_row_time)
        evict = present & (trig_row < BIG)

        # --- candidate assembly: K expired + B current candidates ---
        death_key = jnp.where(evict, trig_row * 2, BIG)

        cand_key = jnp.concatenate(
            [death_key, jnp.where(valid_cur, jnp.arange(bsz, dtype=jnp.int32) * 2 + 1, BIG)]
        )
        cand_elem = jnp.concatenate(
            [jnp.arange(k, dtype=jnp.int32), jnp.arange(w, k, dtype=jnp.int32)]
        )
        cand_is_exp = jnp.concatenate(
            [jnp.ones((k,), bool), jnp.zeros((bsz,), bool)]
        )
        cand_valid = jnp.concatenate([evict, valid_cur])
        cand_seq = elem_seq[cand_elem]

        order = jnp.lexsort((cand_seq, jnp.where(cand_valid, cand_key, BIG)))
        o_elem = cand_elem[order]
        o_exp = cand_is_exp[order]
        o_valid = cand_valid[order]
        o_key = jnp.where(o_valid, cand_key[order], BIG)

        trigger_ts = b.ts  # trigger row's event ts stands in for "currentTime"
        o_trig_row = jnp.clip(o_key // 2, 0, bsz - 1)
        out = EventBatch(
            ts=jnp.where(o_exp, trigger_ts[o_trig_row], elem_ts[o_elem]),
            kind=jnp.where(o_exp, np.int8(KIND_EXPIRED), np.int8(KIND_CURRENT)),
            valid=o_valid,
            cols={n: elem_cols[n][o_elem] for n in elem_cols},
        )

        # --- membership matrix for exact min/max/distinct ---
        # position-based: element is "in the window" from its CURRENT output row
        # (ring elements: from the start) until its EXPIRED output row, which
        # reproduces the reference's one-by-one add/remove ordering exactly.
        inv = jnp.argsort(order)  # candidate index -> sorted output position
        birth_pos = jnp.where(
            own_row >= 0, inv[k + jnp.clip(own_row, 0, bsz - 1)], np.int32(-1)
        )
        death_pos = jnp.where(evict, inv[jnp.arange(k)], BIG)
        member, member_env = self._member(flow, ev, birth_pos, death_pos, k + bsz)

        new_state = self._ring_state(state, evict, valid_cur, rank, c, b, bwts, ev)
        # the rows that left are the oldest live ones, by either rule
        early = evict & (trig_row_len < trig_row_time)
        left = evict.sum(dtype=jnp.int64)
        new_state.update(
            head=state["head"] + left,
            wmax=bwts[-1],
            expired=state["expired"] + left,
            passes=state["passes"],
            early=state["early"] + early.sum(dtype=jnp.int64),
        )

        aux = dict(flow.aux)
        if self.capacity_is_limit:
            aux["window_early_expiry"] = early.any()
        if self.needs_scheduler:
            kept = self.lanes(new_state)
            surv_wts = jnp.where(
                kept["seq"] >= new_state["head"], kept["wts"], NO_TIMER - self.t
            )
            aux["next_timer"] = surv_wts.min() + self.t

        cause = None
        if flow.cause is not None:
            # an EXPIRED row's is its trigger's, a CURRENT row's its own
            own = jnp.clip(o_elem - w, 0, bsz - 1)
            cause = flow.cause[jnp.where(o_exp, o_trig_row, own)]
        return new_state, Flow(
            batch=out,
            ref=flow.ref,
            now=flow.now,
            extra_cols={},
            member=member,
            member_env=member_env,
            aux=aux,
            tables=flow.tables,
            cause=cause,
        )

    def _element_view(self, state, b, bwts, valid_cur, rank, c):
        """The [W + B] element view, ring slots then batch rows, with each
        element's length-eviction trigger: an element is evicted by the
        insertion of seq + W, which has rank `trig_rank` in this batch."""
        w = self.w
        state = self.lanes(state)
        total = state["total"]
        seq = jnp.concatenate(
            [state["seq"], jnp.where(valid_cur, total + rank, np.int64(-1))]
        )
        present = seq >= 0
        if "head" in state:
            # a time-bounded ring: the fifo step moves the head on and
            # leaves the slots behind it as they were
            present &= seq >= state["head"]
        trig_rank = (seq + w - total).astype(jnp.int32)
        return _ElementView(
            ts=jnp.concatenate([state["ts"], b.ts]),
            wts=jnp.concatenate([state["wts"], bwts]),
            seq=seq,
            cols={n: jnp.concatenate([state["cols"][n], b.cols[n]]) for n in b.cols},
            present=present,
            trig_rank=trig_rank,
            len_trig_valid=present & (trig_rank >= 0) & (trig_rank < c),
            perm=jnp.argsort(~valid_cur, stable=True).astype(jnp.int32),
        )

    def _member(self, flow, ev, birth_pos, death_pos, n_out):
        """[n_out, W + B] membership matrix and the Env over the element
        view's columns: an element is a member from output row `birth_pos`
        until output row `death_pos`."""
        pos_row = jnp.arange(n_out)
        member = (
            ev.present[None, :]
            & (birth_pos[None, :] <= pos_row[:, None])
            & (pos_row[:, None] < death_pos[None, :])
        )
        member_cols = {(self.ref, None, n): col for n, col in ev.cols.items()}
        member_cols[(self.ref, None, TS_ATTR)] = ev.ts
        return member, Env(member_cols, now=flow.now)

    def _ring_state(self, state, evict, valid_cur, rank, c, b, bwts, ev):
        """Post-step ring buffers, shared by the sorted and the scatter
        length paths.
        Rows already evicted within this batch (expired before the batch
        ended) must NOT be re-inserted, or they would expire a second time."""
        w = self.w
        total = state["total"]
        ring_evicted = evict[:w]
        batch_evicted = evict[w:]
        insert = valid_cur & ~batch_evicted & (rank >= c - w)
        slots = jnp.where(insert, (total + rank) % w, np.int64(w)).astype(jnp.int32)
        return {
            "cols": {
                n: _place_ring(state["cols"][n], ring_evicted, slots, b.cols[n])
                for n in b.cols
            },
            "ts": _place_ring(state["ts"], ring_evicted, slots, b.ts),
            "wts": _place_ring(state["wts"], ring_evicted, slots, bwts),
            "seq": _place_ring(state["seq"], ring_evicted, slots, ev.seq[w:], -1),
            "total": total + c,
        }

    def _length_positions(self, total, c, bsz):
        """Output positions of the length step, by rank: insertion i evicts
        iff the window is full at that point; with E the inclusive count of
        evictions it emits its EXPIRED at i + E_i - 1 and its CURRENT at
        i + E_i."""
        ranks = jnp.arange(bsz, dtype=jnp.int32)
        e = (ranks < c) & (total + ranks >= self.w)
        E = jnp.cumsum(e.astype(jnp.int32))
        return ranks, e, E

    def _length_member(self, flow, ev, valid_cur, rank, c, E):
        """Membership of the length step (same contract as the sorted
        path). Only min / max / distinctCount read it; when none does,
        nothing here reaches the output or the state, and XLA drops it
        together with the element view's concatenations."""
        bsz = valid_cur.shape[0]
        cur_pos_row = (rank + E[jnp.clip(rank, 0, bsz - 1)]).astype(jnp.int32)
        birth_pos = jnp.concatenate(
            [
                jnp.full((self.w,), -1, jnp.int32),
                jnp.where(valid_cur, cur_pos_row, np.int32(-1)),
            ]
        )
        E_at = E[jnp.clip(ev.trig_rank, 0, bsz - 1)]
        death_pos = jnp.where(ev.len_trig_valid, ev.trig_rank + E_at - 1, BIG)
        return self._member(flow, ev, birth_pos, death_pos, 2 * bsz)

    def _apply_length(self, state, flow, valid_cur, bwts, rank, c):
        """Sort-free length-window step for any capacity (see apply): the
        ring is read by gathers and written by scatters."""
        b = flow.batch
        bsz = b.capacity
        w = self.w
        total = state["total"]
        ev = self._element_view(state, b, bwts, valid_cur, rank, c)
        perm = ev.perm
        with jax.named_scope("ring_emit"):
            ranks, e, E = self._length_positions(total, c, bsz)
            cur_pos_rank = ranks + E
            exp_pos_rank = jnp.where(e, cur_pos_rank - 1, BIG)

            # evicted element (seq = total + i - w): a ring slot if it predates
            # this batch, else the batch row of rank i - w
            seq_ev = total + ranks.astype(jnp.int64) - w
            from_ring = seq_ev < total
            ring_slot = jnp.where(seq_ev >= 0, seq_ev % w, 0).astype(jnp.int32)
            batch_rank = jnp.clip(ranks - w, 0, bsz - 1)
            elem_idx = jnp.where(
                from_ring, ring_slot, w + perm[batch_rank]
            ).astype(jnp.int32)

            n_out = 2 * bsz
            trig_ts = b.ts[perm[jnp.clip(ranks, 0, bsz - 1)]]  # trigger row ts
            out_ts = jnp.zeros((n_out,), jnp.int64)
            out_kind = jnp.zeros((n_out,), jnp.int8)
            out_valid = jnp.zeros((n_out,), jnp.bool_)
            out_cols = {n: jnp.zeros((n_out,), a.dtype) for n, a in b.cols.items()}

            # scatter EXPIREDs (rank space); set_at keeps int64 lanes fast
            exp_dst = jnp.where(e, exp_pos_rank, n_out)
            out_ts = _set_at(out_ts, exp_dst, trig_ts)
            out_kind = out_kind.at[exp_dst].set(np.int8(KIND_EXPIRED), mode="drop")
            out_valid = out_valid.at[exp_dst].set(True, mode="drop")
            for n in out_cols:
                out_cols[n] = _set_at(out_cols[n], exp_dst, ev.cols[n][elem_idx])
            # scatter CURRENTs (row space: row r has rank[r], position via gather)
            cur_pos_row = cur_pos_rank[jnp.clip(rank, 0, bsz - 1)]
            cur_dst = jnp.where(valid_cur, cur_pos_row, n_out)
            out_ts = _set_at(out_ts, cur_dst, b.ts)
            out_valid = out_valid.at[cur_dst].set(True, mode="drop")
            for n in out_cols:
                out_cols[n] = _set_at(out_cols[n], cur_dst, b.cols[n])
            out = EventBatch(ts=out_ts, kind=out_kind, valid=out_valid, cols=out_cols)
            member, member_env = self._length_member(flow, ev, valid_cur, rank, c, E)
            cause = None
            if flow.cause is not None:
                # as the ts lane: an EXPIRED row takes its trigger's
                trig = flow.cause[perm[jnp.clip(ranks, 0, bsz - 1)]]
                cause = jnp.zeros((n_out,), jnp.int32)
                cause = cause.at[exp_dst].set(trig, mode="drop")
                cause = cause.at[cur_dst].set(flow.cause, mode="drop")

        with jax.named_scope("ring_update"):
            new_state = self._ring_state(
                state, ev.len_trig_valid, valid_cur, rank, c, b, bwts, ev
            )
        return new_state, Flow(
            batch=out,
            ref=flow.ref,
            now=flow.now,
            extra_cols={},
            member=member,
            member_env=member_env,
            aux=dict(flow.aux),
            tables=flow.tables,
            cause=cause,
        )

    def _pick_ring_step(self, bsz: int) -> str:
        return "slice" if self.w >= bsz else "scatter"

    def _apply_length_slice(self, state, flow, valid_cur, bwts, rank, c):
        """Length-window step in O(batch), for capacity >= batch. Insertion
        of rank r goes to slot (total + r) % W, and the row it evicts, seq
        total + r - W, holds that very slot. So the slots written are one
        run (mod W) from total % W, the expired rows are the run's old
        contents in order, and every evicted slot is overwritten in the
        same step: each ring lane is read and written through slices of
        the run alone, 64-bit lanes included. The output is an interleave
        by arithmetic: the first n0 = clip(W - total, 0, c) insertions
        evict nothing and emit their CURRENT at position p = rank; after
        them rank n0 + j emits EXPIRED at n0 + 2j and CURRENT at n0 + 2j + 1.
        Output and state are identical to _apply_length's."""
        b = flow.batch
        bsz = b.capacity
        w = self.w
        total = state["total"]
        ring = {k: state[k] for k in ("cols", "ts", "wts", "seq") if k in state}
        # under `vmap` over a partition's slots, each with a ring of its
        # own (core/partition.py), the run is read and written by places
        per_slot = flow.slot_rows
        with jax.named_scope("ring_emit"):
            new = _compact_front(
                valid_cur, {"cols": dict(b.cols), "ts": b.ts, "wts": bwts}
            )
            new["seq"] = total + jnp.arange(bsz, dtype=jnp.int64)
            # the long lanes' new rows as halves, like the ring holds them
            if self.held_cols is None:
                new_rows = _split_like(ring, new)
            else:
                new_rows = _split_like(ring, {
                    "cols": {n: new["cols"][n] for n in ring["cols"]}
                })

            if per_slot:
                # the run's places, rank by rank: read and written at once
                ranks = jnp.arange(bsz, dtype=jnp.int32)
                run = ((total % w).astype(jnp.int32) + ranks) % w
                held, new_state = _ring_swap(per_slot)(
                    ring, jnp.where(ranks < c, run, np.int32(w)), new_rows
                )
                expired = _join_pairs(held["cols"])
            else:
                # the run [start, start + B) mod W as two slices: `tail`
                # from s1 (start, held inside the lane) and the lane's
                # first B rows; the run begins `off` rows into their
                # concatenation
                s1, off = _run_start(total, w, bsz)
                tails = jax.tree_util.tree_map(
                    lambda lane: jax.lax.dynamic_slice(lane, (s1,), (bsz,)),
                    ring,
                )
                # the rows the run held: a long column's halves are joined
                # here, B rows of them
                expired = _join_pairs(jax.tree_util.tree_map(
                    lambda lane, tail: _run_read(lane, tail, off),
                    ring["cols"], tails["cols"],
                ))

            n0 = jnp.clip(w - total, 0, c).astype(jnp.int32)
            pos = jnp.arange(2 * bsz, dtype=jnp.int32)
            fill = pos < n0
            out_valid = jnp.where(fill, pos, n0 + (pos - n0) // 2) < c
            is_exp = out_valid & ~fill & ((pos - n0) % 2 == 0)

            def emit(expired, current):
                if per_slot and bsz <= _PICK_ROWS:
                    return emit_by_rank(expired, current)
                pairs = jnp.stack([expired, current], axis=1).reshape(-1)
                pairs = jax.lax.dynamic_slice(
                    jnp.pad(pairs, (0, bsz)), (n0,), (2 * bsz,)
                )
                lane = jnp.where(fill, jnp.pad(current, (0, bsz)), pairs)
                return jnp.where(out_valid, lane, jnp.zeros((), lane.dtype))

            def emit_by_rank(expired, current):
                """The same lane where the step runs under `vmap` over
                slots: a shift by a per-slot `n0` would lower on the chip
                to a loop over the slots (7 ms a lane at 4,096 slots), so
                each place picks its rank's row through a one-hot
                [2B', B'] select, where sub-batches are short (many slots);
                long ones (few slots) keep the shift."""
                rank = jnp.where(fill, pos, n0 + (pos - n0) // 2)
                hot = rank[:, None] == jnp.arange(bsz, dtype=jnp.int32)[None, :]
                both = jnp.where(
                    (is_exp[:, None] & hot)[None], _pick_bits(expired),
                    _pick_bits(current),
                )
                lane = jnp.where(hot[None], both, 0).sum(
                    axis=-1, dtype=jnp.int32)
                return jnp.where(
                    out_valid, _unpick_bits(lane, current.dtype),
                    jnp.zeros((), current.dtype),
                )

            out = EventBatch(
                # an EXPIRED row carries its trigger row's ts: the CURRENT
                # of the same rank
                ts=emit(new["ts"], new["ts"]),
                kind=jnp.where(
                    is_exp, np.int8(KIND_EXPIRED), np.int8(KIND_CURRENT)
                ),
                valid=out_valid,
                cols={
                    # a column the ring does not hold is read of no
                    # EXPIRED row (`held_cols`): its own stands in
                    n: emit(expired.get(n, col), col)
                    for n, col in new["cols"].items()
                },
            )
            member = member_env = None
            if self.held_cols is None:
                _, _, E = self._length_positions(total, c, bsz)
                member, member_env = self._length_member(
                    flow,
                    self._element_view(state, b, bwts, valid_cur, rank, c),
                    valid_cur, rank, c, E,
                )
            cause = None
            if flow.cause is not None:
                # as the ts lane: an EXPIRED row takes its trigger's
                by_rank = _compact_front(valid_cur, flow.cause)
                cause = emit(by_rank, by_rank)

        with jax.named_scope("ring_update"):
            if not per_slot:
                written = (pos >= off) & (pos - off < c)
                new_state = jax.tree_util.tree_map(
                    lambda lane, tail, vals: _run_write(
                        lane, tail, vals, written, s1, off
                    ),
                    ring, tails, new_rows,
                )
            new_state["total"] = total + c
        return new_state, Flow(
            batch=out,
            ref=flow.ref,
            now=flow.now,
            extra_cols={},
            member=member,
            member_env=member_env,
            aux=dict(flow.aux),
            tables=flow.tables,
            cause=cause,
        )

    # ---- the time-bounded step in O(batch): a FIFO, taken in passes ------

    def takes_fifo(self, bsz: int) -> bool:
        """Whether a step over batches of `bsz` rows can be the fifo one: an
        event-time window (no TIMER rows arrive) whose ring holds a batch.
        A window shorter than that keeps `apply`'s matrix, as `length(N <
        batch)` keeps its scatter step; so does the caller whose selector
        reads the membership matrix (min / max / distinctCount)."""
        return self.t is not None and not self.needs_scheduler and self.w >= bsz

    def fifo_plan(self, state, flow: Flow) -> dict:
        """What the passes of one step share: the batch's CURRENT rows moved
        to the front, their window time as a running maximum (sorted), and
        each one's threshold. The logical queue of the step is the ring's
        live rows, seq head..total-1, then these."""
        b = flow.batch
        bsz = b.capacity
        self.time_step = "fifo"
        valid_cur = b.valid & (b.kind == KIND_CURRENT)
        bwts = self._window_time(b)
        big = np.int64(NO_TIMER)  # never due: pads the sorted lanes
        with jax.named_scope("ring_expire"):
            mwts = jnp.maximum(
                state["wmax"],
                _cummax(jnp.where(valid_cur, bwts, np.int64(NO_TIME))),
            )
        with jax.named_scope("ring_emit"):
            new = _compact_front(
                valid_cur, {"cols": dict(b.cols), "ts": b.ts, "wts": mwts}
            )
        c = valid_cur.sum(dtype=jnp.int32)
        in_c = jnp.arange(bsz, dtype=jnp.int32) < c
        return {
            "new": new,
            "c": c,
            "wts": jnp.where(in_c, new["wts"], big),
            "theta": jnp.where(in_c, new["wts"] - self.t, big),
            "wmax": mwts[-1],
            "live": (state["total"] - state["head"]).astype(jnp.int32),
        }

    @staticmethod
    def fifo_cursor() -> dict:
        """Where a step's passes stand: `k` rows of the queue have left,
        `r` CURRENT rows are out."""
        return {
            "k": jnp.zeros((), jnp.int32),
            "r": jnp.zeros((), jnp.int32),
            "passes": jnp.zeros((), jnp.int32),
            "early": jnp.zeros((), jnp.int32),
            "more": jnp.ones((), jnp.bool_),
        }

    def fifo_pass(self, state, plan: dict, cur: dict, flow: Flow):
        """One pass: the next B rows of the queue against the batch's
        thresholds. Row k of the queue leaves at the first rank r whose
        threshold has reached its window time, or whose insertion needs its
        slot (r = k + W - live: the length rule, and a time window's early
        expiry). Both are sorted in k and in r, so "how many rows has rank r
        let go" is a merge of two sorted lanes: one sort of 2B keys, no
        [W, B] matrix. A rank at which the queue's next row, the one behind
        this pass, is due too lets go of more than the pass holds: it and
        the ranks behind it wait for the next pass, which reads on from
        where this one stopped. The flow, 2B rows,
        is the pass's EXPIRED rows, each before the CURRENT row that let it
        go, by one payload sort on positions that follow by arithmetic."""
        bsz = plan["wts"].shape[0]
        w = self.w
        new, c, live = plan["new"], plan["c"], plan["live"]
        k0, r0 = cur["k"], cur["r"]
        j = jnp.arange(bsz, dtype=jnp.int32)
        with jax.named_scope("ring_expire"):
            # the ring's run from head + k0, as two slices (see the length
            # step); behind the live rows, the batch's own
            s1, off = _run_start(state["head"] + k0, w, bsz)
            run = _join_pairs(jax.tree_util.tree_map(
                lambda lane: _run_read(
                    lane, jax.lax.dynamic_slice(lane, (s1,), (bsz,)), off
                ),
                {"cols": state["cols"], "wts": state["wts"]},
            ))
            n_ring = jnp.clip(live - k0, -bsz, bsz)

            def behind(x):  # batch rank (j - n_ring) at place j
                return jax.lax.dynamic_slice(
                    jnp.pad(x, (bsz, bsz)), (bsz - n_ring,), (bsz,)
                )

            from_ring = j < n_ring
            cand_cols = {
                n: jnp.where(from_ring, run["cols"][n], behind(col))
                for n, col in new["cols"].items()
            }
            n_cand = jnp.clip(live + c - k0, 0, bsz)
            cand_wts = jnp.where(
                j < n_cand,
                jnp.where(from_ring, run["wts"], behind(plan["wts"])),
                np.int64(NO_TIMER),
            )
            first_t, held_t = _merge_counts(cand_wts, plan["theta"])
            # the length rule, in 64 bits: W may be near 2**31
            room = np.int64(w) - live
            first_l = jnp.minimum(k0 + j + room, BIG).astype(jnp.int32)
            first = jnp.where(j < n_cand, jnp.minimum(first_t, first_l), BIG)
            held = jnp.maximum(
                held_t, jnp.clip(j - k0 - room + 1, 0, n_cand).astype(jnp.int32)
            )
            # a rank is whole in this pass unless the queue's next row, the
            # one behind the pass, is due at it too
            q = k0 + bsz
            slot = ((state["head"] + q) % w).astype(jnp.int32)
            next_wts = jnp.where(
                q < live,
                _join64(*(
                    jax.lax.dynamic_index_in_dim(half, slot, keepdims=False)
                    for half in (state["wts"].lo, state["wts"].hi)
                ), jnp.int64),
                jax.lax.dynamic_index_in_dim(
                    plan["wts"], jnp.clip(q - live, 0, bsz - 1), keepdims=False
                ),
            )
            next_first = jnp.minimum(
                (plan["theta"] < next_wts).sum(dtype=jnp.int32),
                jnp.minimum(q + room, BIG).astype(jnp.int32),
            )
            r1 = jnp.where(q < live + c, jnp.minimum(c, next_first), c)
            due = first < c
            n_due = due.sum(dtype=jnp.int32)
            early = (due & (first_l < first_t)).sum(dtype=jnp.int32)

        with jax.named_scope("ring_emit"):
            n_cur = r1 - r0
            # places past the flow's end, all different, for what stays
            pos_exp = jnp.where(
                due, j + jnp.clip(jnp.minimum(first, r1) - r0, 0, bsz),
                2 * bsz + j,
            )
            pos_cur = jnp.where(
                (j >= r0) & (j < r1), j - r0 + held, 3 * bsz + j
            )
            flowed = _permute_tree(
                jnp.concatenate([pos_exp, pos_cur]),
                {
                    "cols": {
                        n: jnp.concatenate([cand_cols[n], col])
                        for n, col in new["cols"].items()
                    },
                    "ts": jnp.concatenate(
                        [jnp.zeros((bsz,), jnp.int64), new["ts"]]
                    ),
                    "cur": jnp.concatenate(
                        [jnp.zeros((bsz,), jnp.bool_), jnp.ones((bsz,), jnp.bool_)]
                    ),
                },
            )
            pos = jnp.arange(2 * bsz, dtype=jnp.int32)
            valid = pos < n_due + n_cur
            is_cur = flowed["cur"] & valid
            # an EXPIRED row carries its trigger's ts: the next CURRENT row
            # of the flow, or rank r1, which waits for the next pass
            lo, hi = _split64(flowed["ts"])
            back = is_cur[::-1]
            lo, hi = _segmented_carry((lo[::-1], hi[::-1]), back)
            follows = _cummax(back.astype(jnp.int32))[::-1] > 0
            waiting = jax.lax.dynamic_index_in_dim(
                new["ts"], jnp.clip(r1, 0, bsz - 1), keepdims=False
            )
            out = EventBatch(
                ts=jnp.where(
                    follows, _join64(lo[::-1], hi[::-1], jnp.int64), waiting
                ),
                kind=jnp.where(
                    is_cur, np.int8(KIND_CURRENT), np.int8(KIND_EXPIRED)
                ),
                valid=valid,
                cols=flowed["cols"],
            )
        cur = {
            "k": k0 + n_due,
            "r": r1,
            "passes": cur["passes"] + 1,
            "early": cur["early"] + early,
            "more": r1 < c,
        }
        return cur, Flow(
            batch=out,
            ref=flow.ref,
            now=flow.now,
            extra_cols={},
            aux=dict(flow.aux),
            tables=flow.tables,
        )

    def fifo_commit(self, state, plan: dict, cur: dict):
        """The ring after the step's last pass: the batch's CURRENT rows
        written as one run from total % W (the length step's write), the
        head moved past the rows that left; and the step's aux flags."""
        bsz = plan["wts"].shape[0]
        w = self.w
        total, c = state["total"], plan["c"]
        ring = {k: state[k] for k in ("cols", "ts", "wts", "seq")}
        with jax.named_scope("ring_update"):
            new_rows = _split_like(ring, {
                **plan["new"], "seq": total + jnp.arange(bsz, dtype=jnp.int64)
            })
            s1, off = _run_start(total, w, bsz)
            pos = jnp.arange(2 * bsz, dtype=jnp.int32)
            written = (pos >= off) & (pos - off < c)
            new_state = jax.tree_util.tree_map(
                lambda lane, vals: _run_write(
                    lane, jax.lax.dynamic_slice(lane, (s1,), (bsz,)), vals,
                    written, s1, off,
                ),
                ring, new_rows,
            )
        left = cur["k"].astype(jnp.int64)
        new_state.update(
            total=total + c,
            head=state["head"] + left,
            wmax=plan["wmax"],
            expired=state["expired"] + left,
            passes=state["passes"] + (cur["passes"] - 1),
            early=state["early"] + cur["early"],
        )
        aux = {}
        if self.capacity_is_limit:
            aux["window_early_expiry"] = cur["early"] > 0
        return new_state, aux

    @staticmethod
    def _view_perm(state):
        """THE ring-slot -> logical-insertion-order permutation, shared by
        view() and view_seq(): join lineage pairs view_seq's seq lane with
        view's cols/mask by position, so the two must never drift."""
        seq = state["seq"].join()
        mask = seq >= 0
        if "head" in state:  # a time-bounded ring: see _element_view
            mask &= seq >= state["head"]
        perm = jnp.argsort(
            jnp.where(mask, seq, jnp.iinfo(jnp.int64).max)
        ).astype(jnp.int32)
        return mask, perm

    def view(self, state):
        if self.held_cols is not None:
            raise NotImplementedError("a ring that holds some columns only")
        mask, perm = self._view_perm(state)
        lanes = self.lanes({k: state[k] for k in ("cols", "ts")})
        cols = {n: c[perm] for n, c in lanes["cols"].items()}
        return cols, lanes["ts"][perm], mask[perm]

    def view_seq(self, state):
        _mask, perm = self._view_perm(state)
        return state["seq"].join()[perm]


def _pick_bits(x):
    """A [B] lane as [halves, 1, B] 32-bit integers, whatever its dtype: a
    one-hot select-and-sum over them moves a row bit for bit."""
    if _is_wide(x.dtype):
        lo, hi = _split64(x)
        return jnp.stack([lo.astype(jnp.int32), hi])[:, None, :]
    if x.dtype == jnp.float32:
        x = jax.lax.bitcast_convert_type(x, jnp.int32)
    return x.astype(jnp.int32)[None, None, :]


def _unpick_bits(bits, dtype):
    """`_pick_bits` undone on the picked [halves, K] rows."""
    if _is_wide(dtype):
        return _join64(bits[0].astype(jnp.uint32), bits[1], dtype)
    if dtype == jnp.float32:
        return jax.lax.bitcast_convert_type(bits[0], jnp.float32)
    return bits[0].astype(dtype)


def _run_start(seq, w: int, bsz: int):
    """Where the run of B ring slots that starts at slot seq % W lies, as
    two slices: (`s1`, the start of a slice of B rows held inside the lane;
    `off`, how far into that slice and the lane's first B rows, laid end to
    end, the run begins)."""
    start = (seq % w).astype(jnp.int32)
    s1 = jnp.minimum(start, np.int32(w - bsz))
    return s1, start - s1


def _run_read(lane, tail, off):
    """The run's B rows of `lane`; `tail` is the lane's slice at `s1`."""
    bsz = tail.shape[0]
    return jax.lax.dynamic_slice(
        jnp.concatenate([tail, lane[:bsz]]), (off,), (bsz,)
    )


def _run_write(lane, tail, vals, written, s1, off):
    """`lane` with the run's rows set to `vals` where `written` ([2B], in
    the places of the two slices) says so."""
    bsz = tail.shape[0]
    vals = jax.lax.dynamic_slice(
        jnp.pad(vals, (bsz, bsz)), (bsz - off,), (2 * bsz,)
    )
    lane = jax.lax.dynamic_update_slice(
        lane, jnp.where(written[:bsz], vals[:bsz], tail), (s1,)
    )
    # the head is read again: it overlaps the tail when the run starts
    # inside the lane's first B rows
    return jax.lax.dynamic_update_slice(
        lane, jnp.where(written[bsz:], vals[bsz:], lane[:bsz]), (0,)
    )


def _place_ring(old, evicted, slots, vals, empty=0):
    """Ring lane `old` with its evicted slots set to `empty` and `vals`
    scattered to `slots`. A long lane's halves (U32Pair) are scattered
    where they are: a raw 64-bit scatter-set serializes on TPU
    (ops/scatter.py), and joining them again would cost a pass over the
    ring."""
    if _is_pair(old):
        v = U32Pair.split(vals.astype(old.dtype))
        e = U32Pair.split(np.full((), empty, old.dtype))
        return U32Pair(
            _place_ring(old.lo, evicted, slots, v.lo, e.lo),
            _place_ring(old.hi, evicted, slots, v.hi, e.hi),
            old.dtype,
        )
    # `empty` typed to the lane dtype: a weak `0` literal promotes BOOL
    # lanes to int64, which breaks the fused scan carry (bool cols reach
    # the fused path since the bit-packed wire, core/wire.py)
    return _set_at(
        jnp.where(evicted, jnp.asarray(empty, old.dtype), old), slots, vals
    )


def ring_from_legacy(snap: dict) -> dict:
    """A time-bounded ring saved before it kept a head (a host tree of
    logical lanes, or a [P, W] stack of them), in today's layout: its live
    rows (seq >= 0) in seq order take the seqs total - n .. total - 1 and the
    slots that go with them, `wts` becomes their running maximum, the head
    is the oldest, the counters start at 0."""
    seq = np.asarray(snap["seq"])
    if seq.ndim > 1:
        rings = [
            ring_from_legacy(jax.tree_util.tree_map(lambda x: np.asarray(x)[p], snap))
            for p in range(seq.shape[0])
        ]
        return jax.tree_util.tree_map(lambda *xs: np.stack(xs), *rings)
    w = seq.shape[0]
    total = np.int64(snap["total"])
    order = np.argsort(np.where(seq >= 0, seq, np.iinfo(np.int64).max), kind="stable")
    order = order[: int((seq >= 0).sum())]
    head = total - len(order)
    slots = (head + np.arange(len(order))) % w

    def relaid(rows, like, fill=0):
        out = np.full_like(np.asarray(like), fill)
        out[slots] = rows
        return out

    def moved(lane):
        return relaid(np.asarray(lane)[order], lane)

    wts = np.maximum.accumulate(np.asarray(snap["wts"])[order])
    return {
        "cols": {n: moved(c) for n, c in snap["cols"].items()},
        "ts": moved(snap["ts"]),
        "wts": relaid(wts, snap["wts"]),
        "seq": relaid(head + np.arange(len(order)), seq, -1),
        "total": total,
        "head": np.int64(head),
        "wmax": np.int64(wts[-1] if len(order) else NO_TIME),
        **{k: np.int64(0) for k in _FIFO_COUNTERS},
    }


def _merge_counts(a, b):
    """For two ascending int64 lanes, each padded with the type's maximum:
    (for every a[j], how many of b are below it; for every b[r], how many of
    a are no higher), int32. One sort of both lanes' keys, a before b where
    they tie, then each lane's entries taken out again in their order: a
    64-bit key rides as its two halves."""
    n, m = a.shape[0], b.shape[0]
    lo, hi = _split64(jnp.concatenate([a, b]))
    of_b = jnp.concatenate([jnp.zeros((n,), jnp.int32), jnp.ones((m,), jnp.int32)])
    of_b = jax.lax.sort((hi, lo, of_b), num_keys=3)[2] > 0
    b_before = jnp.cumsum(of_b.astype(jnp.int32))
    a_before = jnp.arange(1, n + m + 1, dtype=jnp.int32) - b_before
    return (
        _compact_front(~of_b, b_before)[:n],
        _compact_front(of_b, a_before)[:m],
    )


def _lanes32(tree):
    """(`tree`'s lanes as operands a sort carries at full speed: a 64-bit
    lane as its halves, a bool lane as int32; the function that makes the
    tree again of such lanes)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    parts = []
    for x in leaves:
        if _is_wide(x.dtype):
            parts.extend(_split64(x))
        else:
            parts.append(x.astype(jnp.int32) if x.dtype == jnp.bool_ else x)

    def rejoin(moved):
        moved = iter(moved)
        out = []
        for x in leaves:
            if _is_wide(x.dtype):
                lo, hi = next(moved), next(moved)
                out.append(_join64(lo, hi, x.dtype))
            else:
                out.append(next(moved).astype(x.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return parts, rejoin


def _permute_tree(key, tree):
    """Every lane of `tree` in the order that sorts `key`, whose values are
    all different: payload sorts of at most six 32-bit lanes each (one sort
    of many operands compiles for minutes, PERF.md PR 25), a 64-bit lane as
    its halves and a bool lane as int32."""
    parts, rejoin = _lanes32(tree)
    moved = []
    for i in range(0, len(parts), 6):
        moved.extend(_permute_by(key, *parts[i:i + 6]))
    return rejoin(moved)


@jax.jit
def _ring_summary(seq: U32Pair, ts: U32Pair, head=None):
    """(fill, oldest ts, newest ts) of a ring, or of a [P, W] stack of
    rings: a slot is live while its seq is not negative and, in a
    time-bounded ring, not behind the head."""
    live = seq.hi >= 0
    if head is not None:
        live &= seq.join() >= jnp.asarray(head)[..., None]
    t = ts.join()
    return (
        live.sum(),
        jnp.where(live, t, jnp.iinfo(t.dtype).max).min(),
        jnp.where(live, t, jnp.iinfo(t.dtype).min).max(),
    )


# ---------------------------------------------------------------------------
# batch (tumbling) family: lengthBatch / timeBatch / externalTimeBatch
# ---------------------------------------------------------------------------


class BatchWindow(WindowStage):
    """Tumbling buckets. Flush every `length` events (lengthBatch) or at each
    `duration` boundary of the window-time (timeBatch / externalTimeBatch).
    On flush the reference emits: prev-bucket EXPIREDs, RESET, then the closing
    bucket's CURRENTs (LengthBatchWindowProcessor.java:108-160); sort keys
    (trigger_row*4 + {0 expired, 1 reset, 2 current}) reproduce that order.

    State invariant: the open bucket holds < flush size (cur_n < n for
    lengthBatch); `prev` holds the last flushed bucket awaiting expiry.

    `emit_expired`: the query runtime clears this when nothing downstream can
    observe EXPIRED rows (output is `insert [current] into`, no rate limiter,
    no membership-consuming aggregator) — the expired candidate lanes are then
    omitted entirely, halving the flow every downstream selector op runs over.
    """

    is_batch = True
    emit_expired = True

    def __init__(
        self,
        schema: StreamSchema,
        ref: str,
        capacity: int,
        length: Optional[int] = None,
        duration_ms: Optional[int] = None,
        time_attr: Optional[str] = None,
        use_scheduler: bool = False,
        start_time: Optional[int] = None,
        timeout_ms: Optional[int] = None,
    ):
        if (length is None) == (duration_ms is None):
            raise SiddhiAppCreationError("batch window needs length xor duration")
        self.schema = schema
        self.ref = ref
        self.w = int(capacity)
        self.n = length
        self.t = duration_ms
        self.time_attr = time_attr
        # externalTimeBatch idle timeout: a WALL-CLOCK deadline re-armed on
        # every event; a TIMER arriving with a nonempty open bucket force-
        # closes it (reference: ExternalTimeBatchWindowProcessor timeout
        # scheduling, lines 243-258)
        self.timeout_ms = timeout_ms
        self.needs_scheduler = use_scheduler or timeout_ms is not None
        self.start_time = start_time

    def share_signature(self):
        if self.needs_scheduler:
            return None  # timer-armed: host scheduling owns per-query state
        # emit_expired is part of the identity: the query runtime clears it
        # per query, and a no-expired bucket may skip prev-bucket writes
        return (
            "BatchWindow", self.w, self.n, self.t, self.time_attr,
            self.start_time, self.emit_expired,
        )

    def init_state(self):
        w = self.w
        zero_cols = {
            n: jnp.zeros((w,), a.dtype)
            for n, a in self.schema.empty_batch(1).cols.items()
        }
        return {
            "cur_cols": zero_cols,
            "cur_ts": jnp.zeros((w,), jnp.int64),
            "cur_n": jnp.zeros((), jnp.int32),
            "prev_cols": {n: jnp.zeros_like(a) for n, a in zero_cols.items()},
            "prev_ts": jnp.zeros((w,), jnp.int64),
            "prev_n": jnp.zeros((), jnp.int32),
            # open-bucket start time (timeBatch family); -1 = no bucket yet
            "bucket_start": jnp.full((), -1, jnp.int64),
            # externalTimeBatch idle timeout: the latest armed WALL-CLOCK
            # deadline; a TIMER flushes only when it has genuinely elapsed
            # (the scheduler cannot extend a pending deadline, so stale
            # early timers must be ignored here)
            "timeout_deadline": jnp.full((), NO_TIMER, jnp.int64),
        }

    def apply(self, state, flow: Flow):
        b = flow.batch
        bsz = b.capacity
        w = self.w
        rows = jnp.arange(bsz, dtype=jnp.int32)
        valid_cur = b.valid & (b.kind == KIND_CURRENT)
        is_timer = b.valid & (b.kind == KIND_TIMER)
        bwts = (
            b.cols[self.time_attr].astype(jnp.int64)
            if self.time_attr is not None
            else b.ts
        )
        rank = jnp.cumsum(valid_cur.astype(jnp.int32)) - valid_cur.astype(jnp.int32)
        c = valid_cur.sum(dtype=jnp.int32)
        perm = jnp.argsort(~valid_cur, stable=True).astype(jnp.int32)  # rank -> row
        cur_n0 = state["cur_n"]

        new_bucket_start = state["bucket_start"]
        if self.n is not None:
            # --- lengthBatch: flush f triggers at the row completing (f+1)*n ---
            # at most bsz//n + 1 flushes can occur per batch (carried bucket
            # holds < n), so the flush bookkeeping lanes are [F], not [bsz] —
            # every downstream candidate lane and the selector's whole flow
            # shrink with them
            n = self.n
            F = min(bsz // n + 2, bsz)
            pos = cur_n0 + rank  # fill position of each current row
            e_row = pos // n  # flush index at which the row's bucket closes
            n_flush = (cur_n0 + c) // n
            f_arr = jnp.arange(F, dtype=jnp.int32)
            trig_rank_f = (f_arr + 1) * n - 1 - cur_n0
            flush_exists = (trig_rank_f >= 0) & (trig_rank_f < c)
            row_of_flush = jnp.where(
                flush_exists, perm[jnp.clip(trig_rank_f, 0, bsz - 1)], bsz - 1
            )
        else:
            # --- timeBatch: flush when a trigger row enters a later bucket ---
            trigger_ok = valid_cur | is_timer
            if self.start_time is not None:
                start0 = np.int64(self.start_time)
            else:
                first_trig = jnp.argmax(trigger_ok)
                start0 = jnp.where(
                    state["bucket_start"] >= 0,
                    state["bucket_start"],
                    jnp.where(trigger_ok.any(), bwts[first_trig], np.int64(-1)),
                )
            F = bsz  # time-driven flush count is bounded only by trigger rows
            rel = jnp.maximum(bwts - start0, 0)
            g = jnp.where(trigger_ok & (start0 >= 0), rel // self.t, np.int64(0))
            # the open bucket's index carries ACROSS batches: with an
            # explicit start time, start0 is a constant, so the first row of
            # every batch would otherwise compare against bucket 0 and flush
            # spuriously (for first-event starts, bucket_start == start0 and
            # the carried index is 0 — unchanged)
            carried_g = jnp.where(
                state["bucket_start"] >= 0,
                jnp.maximum(state["bucket_start"] - start0, 0) // self.t,
                np.int64(0),
            )
            open_g = _cummax(jnp.maximum(g, carried_g))
            prev_open = jnp.concatenate([carried_g[None], open_g[:-1]])
            had_bucket = (state["bucket_start"] >= 0) | (
                jnp.cumsum(trigger_ok.astype(jnp.int32)) - trigger_ok.astype(jnp.int32) > 0
            )
            flush_here = trigger_ok & (g > prev_open) & had_bucket
            if self.timeout_ms is not None:
                # an ELAPSED idle-timeout TIMER force-closes a nonempty open
                # bucket WITHOUT advancing the bucket grid: later events whose
                # external time falls in the same grid bucket open a fresh
                # bucket there (reference: ExternalTimeBatchWindowProcessor
                # clears currentEventChunk but keeps endTime). Positional: a
                # CURRENT row earlier in this batch re-arms the deadline to
                # now + timeout (which cannot have elapsed at this same now),
                # so only a TIMER with no prior CURRENT row (`rank == 0`) can
                # see a genuinely stale deadline — a stale timer after a
                # same-batch refill must not force-close the bucket
                timeout_flush = (
                    is_timer
                    & (rank == 0)
                    & (cur_n0 > 0)
                    & (jnp.asarray(flow.now, jnp.int64)
                       >= state["timeout_deadline"])
                )
                flush_here = flush_here | timeout_flush
            e_row = jnp.cumsum(flush_here.astype(jnp.int32))  # inclusive: flush at i precedes row i
            n_flush = flush_here.sum(dtype=jnp.int32)
            row_of_flush = jnp.where(
                rows < n_flush,
                jnp.argsort(jnp.where(flush_here, rows, BIG)).astype(jnp.int32),
                bsz - 1,
            )
            flush_exists = rows < n_flush
            new_bucket_start = jnp.where(
                trigger_ok.any() & (start0 >= 0), start0 + open_g[-1] * self.t, start0
            )
            e_row = jnp.where(valid_cur, e_row, 0)

        any_flush = n_flush > 0

        def flush_key(f, kindbit):
            return row_of_flush[jnp.clip(f, 0, F - 1)] * 4 + kindbit

        # --- candidates ---
        # carried open bucket: CURRENT at flush 0, EXPIRED at flush 1
        cw = jnp.arange(w, dtype=jnp.int32)
        carried_valid = cw < cur_n0
        cc_cur_key = jnp.where(carried_valid & any_flush, flush_key(0, 2), BIG)
        cc_exp_key = jnp.where(carried_valid & (n_flush > 1), flush_key(1, 0), BIG)
        # prev bucket: EXPIRED at flush 0
        prev_valid = cw < state["prev_n"]
        pv_exp_key = jnp.where(prev_valid & any_flush, flush_key(0, 0), BIG)
        # batch rows: CURRENT at their closing flush, EXPIRED one flush later
        row_emit = valid_cur & (e_row < n_flush)
        bt_cur_key = jnp.where(row_emit, flush_key(e_row.astype(jnp.int32), 2), BIG)
        bt_exp_key = jnp.where(
            row_emit & (e_row + 1 < n_flush), flush_key(e_row.astype(jnp.int32) + 1, 0), BIG
        )
        # resets: one per flush ([F] lanes)
        rs_key = jnp.where(flush_exists, row_of_flush * 4 + 1, BIG)

        # element table: [0,w) carried-cur, [w,2w) prev, [2w,2w+bsz) batch
        # (used by the membership env only; the candidate VALUE lanes below
        # are built by concatenating the same slices, so the big sort carries
        # them as payloads instead of per-lane [order] gathers — gathers
        # serialize on the TPU scalar core, sort payloads ride the VPU)
        elem_cols = {
            nm: jnp.concatenate([state["cur_cols"][nm], state["prev_cols"][nm], b.cols[nm]])
            for nm in b.cols
        }
        elem_ts = jnp.concatenate([state["cur_ts"], state["prev_ts"], b.ts])

        if self.emit_expired:
            cand_key = jnp.concatenate([cc_cur_key, cc_exp_key, pv_exp_key, bt_cur_key, bt_exp_key, rs_key])
            lanes = lambda cur, prev, bat: jnp.concatenate(  # noqa: E731
                [cur, cur, prev, bat, bat, jnp.broadcast_to(cur[0], (F,))]
            )
            cand_kind = jnp.concatenate(
                [
                    jnp.full((w,), KIND_CURRENT, jnp.int8),
                    jnp.full((w,), KIND_EXPIRED, jnp.int8),
                    jnp.full((w,), KIND_EXPIRED, jnp.int8),
                    jnp.full((bsz,), KIND_CURRENT, jnp.int8),
                    jnp.full((bsz,), KIND_EXPIRED, jnp.int8),
                    jnp.full((F,), KIND_RESET, jnp.int8),
                ]
            )
            tie = jnp.concatenate([cw, cw, cw, rows + w, rows + w, jnp.arange(F, dtype=jnp.int32)])
            bt_cur_off = 3 * w
        else:
            # CURRENT-only consumers: drop the three expired lanes
            cand_key = jnp.concatenate([cc_cur_key, bt_cur_key, rs_key])
            lanes = lambda cur, prev, bat: jnp.concatenate(  # noqa: E731
                [cur, bat, jnp.broadcast_to(cur[0], (F,))]
            )
            cand_kind = jnp.concatenate(
                [
                    jnp.full((w,), KIND_CURRENT, jnp.int8),
                    jnp.full((bsz,), KIND_CURRENT, jnp.int8),
                    jnp.full((F,), KIND_RESET, jnp.int8),
                ]
            )
            tie = jnp.concatenate([cw, rows + w, jnp.arange(F, dtype=jnp.int32)])
            bt_cur_off = w
        cand_valid = cand_key < BIG
        # ONE payload sort orders the candidates AND carries kind/valid/ts and
        # every attribute value lane
        ncand_i = cand_key.shape[0]
        cidx = jnp.arange(ncand_i, dtype=jnp.int32)
        col_names = list(b.cols)
        sorted_ops = jax.lax.sort(
            (
                jnp.where(cand_valid, cand_key, BIG), tie, cidx,
                cand_kind, cand_valid, cand_key,
                lanes(state["cur_ts"], state["prev_ts"], b.ts),
                *(
                    lanes(state["cur_cols"][nm], state["prev_cols"][nm], b.cols[nm])
                    for nm in col_names
                ),
            ),
            num_keys=2, is_stable=False,
        )
        (_, _, order, o_kind, o_valid, o_key_raw, o_ts) = sorted_ops[:7]
        o_cols = dict(zip(col_names, sorted_ops[7:]))
        o_key = jnp.where(o_valid, o_key_raw, BIG)
        if self.emit_expired:
            # EXPIRED rows carry their flush trigger's timestamp
            trig_ts = b.ts[jnp.clip(o_key // 4, 0, bsz - 1)]
            out_ts = jnp.where(o_kind == KIND_EXPIRED, trig_ts, o_ts)
        else:
            out_ts = o_ts
        out = EventBatch(
            ts=out_ts,
            kind=o_kind,
            valid=o_valid,
            cols=o_cols,
        )

        # --- membership (bucket contents; position-based, see SlidingWindow) ---
        # An element is a member from its CURRENT output row (which follows its
        # flush's RESET) until its own EXPIRED row at the NEXT flush — the
        # reference's one-by-one add/remove ordering: reset clears, the
        # bucket's currents accumulate, the next flush's expireds remove.
        # Prev-bucket elements are never members (their bucket's reset already
        # cleared the deque; their EXPIRED events remove from empty — a no-op).
        # candidate index -> sorted output position, via a payload sort; the
        # per-lane reads below are SLICES of inv (cw/rows are aranges), not
        # gathers
        (inv,) = _permute_by(order, cidx)
        ncand = ncand_i
        birth_cc = jnp.where(carried_valid & any_flush, inv[:w], BIG)
        birth_bt = jnp.where(
            row_emit, inv[bt_cur_off : bt_cur_off + bsz], BIG
        )
        # without expired lanes there are no death positions, so membership
        # cannot be expressed — hand downstream None and any (future) member
        # consumer degrades to its memberless path (`member is None` guards)
        if self.emit_expired:
            death_cc = jnp.where(
                carried_valid & (n_flush > 1), inv[w : 2 * w], BIG
            )
            death_bt = jnp.where(
                row_emit & (e_row + 1 < n_flush),
                inv[3 * w + bsz : 3 * w + 2 * bsz],
                BIG,
            )
            e_birth = jnp.concatenate([birth_cc, jnp.full((w,), BIG, jnp.int32), birth_bt])
            e_death = jnp.concatenate([death_cc, jnp.full((w,), -1, jnp.int32), death_bt])
            e_alive = jnp.concatenate([carried_valid & any_flush, jnp.zeros((w,), bool), row_emit])
            pos_row = jnp.arange(ncand)
            member = (
                e_alive[None, :]
                & (e_birth[None, :] <= pos_row[:, None])
                & (pos_row[:, None] < e_death[None, :])
            )
            member_cols = {(self.ref, None, nm): elem_cols[nm] for nm in elem_cols}
            member_cols[(self.ref, None, TS_ATTR)] = elem_ts
            member_env = Env(member_cols, now=flow.now)
        else:
            member = None
            member_env = None

        # --- new buffers ---
        # open bucket: elements whose bucket index == n_flush (not yet closed)
        remaining = valid_cur & (e_row == n_flush)
        keep_carried = ~any_flush  # carried stays only if nothing flushed
        if self.n is not None:
            rem_slot = jnp.where(remaining, pos - n_flush * self.n, w)
        else:
            rem_rank = jnp.cumsum(remaining.astype(jnp.int32)) - remaining.astype(jnp.int32)
            rem_slot = jnp.where(
                remaining, rem_rank + jnp.where(keep_carried, cur_n0, 0), w
            )
        rem_slot = rem_slot.astype(jnp.int32)

        def place_cur(old, vals):
            kept = jnp.where(keep_carried, old, jnp.zeros_like(old))
            return _compact_set_at(kept, rem_slot, vals)

        new_cur_n = jnp.where(keep_carried, cur_n0, 0) + remaining.sum(dtype=jnp.int32)

        # prev bucket: last flushed bucket (carried if it closed last, + rows)
        in_last = row_emit & (e_row == n_flush - 1)
        carried_in_last = carried_valid & (n_flush == 1)
        n_carried_last = jnp.where(n_flush == 1, cur_n0, 0)
        lb_rank = jnp.cumsum(in_last.astype(jnp.int32)) - in_last.astype(jnp.int32)
        lb_slot_c = jnp.where(carried_in_last, cw, w).astype(jnp.int32)
        lb_slot_b = jnp.where(in_last, n_carried_last + lb_rank, w).astype(jnp.int32)

        def place_prev(old_prev, carried_vals, batch_vals):
            base = jnp.where(any_flush, jnp.zeros_like(old_prev), old_prev)
            base = _set_at(base, lb_slot_c, carried_vals)
            return _compact_set_at(base, lb_slot_b, batch_vals)

        new_prev_n = jnp.where(
            any_flush, n_carried_last + in_last.sum(dtype=jnp.int32), state["prev_n"]
        )

        new_state = {
            "cur_cols": {nm: place_cur(state["cur_cols"][nm], b.cols[nm]) for nm in b.cols},
            "cur_ts": place_cur(state["cur_ts"], b.ts),
            "cur_n": new_cur_n,
            "prev_cols": {
                nm: place_prev(state["prev_cols"][nm], state["cur_cols"][nm], b.cols[nm])
                for nm in b.cols
            },
            "prev_ts": place_prev(state["prev_ts"], state["cur_ts"], b.ts),
            "prev_n": new_prev_n,
            "bucket_start": new_bucket_start,
            "timeout_deadline": state["timeout_deadline"],
        }

        aux = dict(flow.aux)
        if self.n is None:
            # a time bucket holds @app:timeCapacity rows: what does not fit
            # is dropped from it, and says so
            aux["window_overflow"] = (
                (remaining & (rem_slot >= w)).any()
                | (in_last & (lb_slot_b >= w)).any()
            )
        if self.timeout_ms is not None:
            # wall-clock idle deadline: every arriving CURRENT event pushes
            # it forward; with an empty open bucket there is none. A stale
            # timer (armed before the push) re-arms the true deadline via
            # next_timer below.
            now64 = jnp.asarray(flow.now, jnp.int64)
            new_state["timeout_deadline"] = jnp.where(
                valid_cur.any(),
                now64 + self.timeout_ms,
                jnp.where(
                    new_state["cur_n"] > 0,
                    state["timeout_deadline"],
                    np.int64(NO_TIMER),
                ),
            )
            aux["next_timer"] = jnp.where(
                new_state["cur_n"] > 0,
                new_state["timeout_deadline"],
                np.int64(NO_TIMER),
            )
        elif self.needs_scheduler and self.t is not None:
            aux["next_timer"] = jnp.where(
                new_state["bucket_start"] >= 0,
                new_state["bucket_start"] + self.t,
                np.int64(NO_TIMER),
            )

        return new_state, Flow(
            batch=out,
            ref=flow.ref,
            now=flow.now,
            extra_cols={},
            member=member,
            member_env=member_env,
            aux=aux,
            tables=flow.tables,
        )


    def view(self, state):
        # the open (current) bucket is the probe-able window content
        # (reference: LengthBatchWindowProcessor.find over currentEventQueue)
        mask = jnp.arange(self.w, dtype=jnp.int32) < state["cur_n"]
        return state["cur_cols"], state["cur_ts"], mask


# ---------------------------------------------------------------------------
# factory
# ---------------------------------------------------------------------------


def make_window(
    spec: WindowSpec,
    schema: StreamSchema,
    ref: str,
    scope: Scope,
    time_capacity: Optional[int] = None,
    held_cols=None,
) -> WindowStage:
    """Reference: SingleInputStreamParser.generateProcessor window dispatch.
    `time_capacity`: the rows a time-bounded ring or bucket holds
    (`@app:timeCapacity`; None = the default). `held_cols`: the columns a
    `length` window's ring holds where its caller reads no other of an
    EXPIRED row (`SlidingWindow.held_cols`; None = all)."""
    time_capacity = time_capacity or DEFAULT_TIME_CAPACITY
    name = spec.name.lower() if spec.namespace is None else f"{spec.namespace}:{spec.name}"
    if name == "length":
        n = _const_param(spec, 0, "length")
        return SlidingWindow(schema, ref, capacity=n, held_cols=held_cols)
    if name == "time":
        t = _const_param(spec, 0, "duration")
        return SlidingWindow(
            schema, ref, capacity=time_capacity, duration_ms=t,
            use_scheduler=True, capacity_is_limit=True,
        )
    if name == "timelength":
        t = _const_param(spec, 0, "duration")
        n = _const_param(spec, 1, "length")
        return SlidingWindow(
            schema, ref, capacity=n, duration_ms=t, use_scheduler=True
        )
    if name == "externaltime":
        attr = _time_attr(spec, 0, schema)
        scope.record_key((ref, None, attr))
        t = _const_param(spec, 1, "duration")
        return SlidingWindow(
            schema, ref, capacity=time_capacity, duration_ms=t, time_attr=attr,
            capacity_is_limit=True,
        )
    if name == "lengthbatch":
        n = _const_param(spec, 0, "length")
        return BatchWindow(schema, ref, capacity=n, length=n)
    if name == "timebatch":
        t = _const_param(spec, 0, "duration")
        start = _const_param(spec, 1, "start time") if len(spec.parameters) > 1 else None
        return BatchWindow(
            schema, ref, capacity=time_capacity, duration_ms=t,
            use_scheduler=True, start_time=start,
        )
    if name == "externaltimebatch":
        attr = _time_attr(spec, 0, schema)
        scope.record_key((ref, None, attr))
        t = _const_param(spec, 1, "duration")
        start = _const_param(spec, 2, "start time") if len(spec.parameters) > 2 else None
        timeout = (
            _const_param(spec, 3, "timeout")
            if len(spec.parameters) > 3 else None
        )
        return BatchWindow(
            schema, ref, capacity=time_capacity, duration_ms=t, time_attr=attr,
            start_time=start, timeout_ms=timeout,
        )
    if name == "sort":
        from siddhi_tpu.core.windows_special import SortWindow
        from siddhi_tpu.query_api.expression import Constant, Variable

        n = _const_param(spec, 0, "length")
        keys: list[tuple[str, bool]] = []
        i = 1
        params = spec.parameters
        while i < len(params):
            p = params[i]
            if not isinstance(p, Variable):
                raise SiddhiAppCreationError(
                    "sort window parameters after the length must be "
                    "attribute [, 'asc'|'desc'] pairs"
                )
            desc = False
            if i + 1 < len(params) and isinstance(params[i + 1], Constant) and str(
                params[i + 1].value
            ).lower() in ("asc", "desc"):
                desc = str(params[i + 1].value).lower() == "desc"
                i += 1
            keys.append((p.attribute, desc))
            i += 1
        for a, _d in keys:
            scope.record_key((ref, None, a))
        return SortWindow(schema, ref, n, keys)
    if name == "frequent":
        from siddhi_tpu.core.windows_special import FrequentWindow
        from siddhi_tpu.query_api.expression import Variable

        n = _const_param(spec, 0, "count")
        attrs = []
        for p in spec.parameters[1:]:
            if not isinstance(p, Variable):
                raise SiddhiAppCreationError("frequent window keys must be attributes")
            attrs.append(p.attribute)
        for a in (attrs or schema.attr_names):  # no keys = whole-event key
            scope.record_key((ref, None, a))
        return FrequentWindow(schema, ref, n, attrs)
    if name == "lossyfrequent":
        from siddhi_tpu.core.windows_special import LossyFrequentWindow
        from siddhi_tpu.query_api.expression import Variable

        support = _const_raw(spec, 0, "support threshold")
        if len(spec.parameters) > 1 and not isinstance(spec.parameters[1], Variable):
            error = _const_raw(spec, 1, "error bound")
            rest = spec.parameters[2:]
        else:
            error = float(support) / 10.0  # reference default error bound
            rest = spec.parameters[1:]
        attrs = []
        for p in rest:
            if not isinstance(p, Variable):
                raise SiddhiAppCreationError(
                    "lossyFrequent window keys must be attributes"
                )
            attrs.append(p.attribute)
        for a in (attrs or schema.attr_names):  # no keys = whole-event key
            scope.record_key((ref, None, a))
        return LossyFrequentWindow(schema, ref, float(support), float(error), attrs)
    if name == "cron":
        from siddhi_tpu.core.windows_special import CronWindow

        expr = _const_raw(spec, 0, "cron expression")
        return CronWindow(schema, ref, str(expr), capacity=time_capacity)
    raise SiddhiAppCreationError(f"unknown window type '{spec.name}'")


def _time_attr(spec: WindowSpec, i: int, schema: StreamSchema) -> str:
    from siddhi_tpu.query_api.expression import Variable

    p = spec.parameters[i]
    if not isinstance(p, Variable):
        raise SiddhiAppCreationError(f"window {spec.name}: parameter {i} must be an attribute")
    if schema.type_of(p.attribute) not in (AttrType.LONG, AttrType.INT):
        raise SiddhiAppCreationError("external time attribute must be long")
    return p.attribute
