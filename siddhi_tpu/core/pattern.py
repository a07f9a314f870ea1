"""Pattern / sequence NFA engine over token matrices.

The reference implements temporal patterns as a per-event interpreter over linked
Pre/Post state-processor chains, each holding a `pendingStateEventList` of partial
matches (reference: query/input/stream/state/StreamPreStateProcessor.java:43-359,
StreamPostStateProcessor.java:29-140, CountPreStateProcessor.java:34-150,
LogicalPreStateProcessor.java:35, AbsentStreamPreStateProcessor.java:37-140).

Here the whole NFA lives in one fixed-capacity **token table** on device: every
partial match is a row holding (current slot, capture columns for every state
ref, occurrence counts, timestamps). The general path processes a micro-batch
as a `lax.scan` over event rows; each scan step runs a static, vectorized pass
per NFA slot — eligibility mask -> compiled condition over the token table ->
capture/advance scatter. Simple chains take one vectorized program per batch
instead (`apply_batch_fast`; a count state first, `apply_batch_count`). `every`
is modelled as *persistent* slots whose tokens fork into free rows instead of
being consumed (reference semantics: `every` re-arms via
nextEveryStatePreProcessor, StreamPostStateProcessor.java:100-120).

Count states `<m:n>` follow the reference's shared-token model exactly
(CountPatternTestCase 1-15 are golden tests): one token is simultaneously
absorbing at the count slot and pending at the next slot once min is reached
(`_eligible` count-skip), the next state is checked before absorption for the
same event (descending slot order, matching
PatternMultiProcessStreamReceiver's reversed eventSequence), a trailing count
emits at exactly min and is consumed, and min-0 counts forward/emit at arrival.

Tokens that one event completes are emitted in the order in which their
FIRST events arrived, the order of the reference's pending list
(StreamPreStateProcessor walks `pendingStateEventList` oldest first), on every
path. The batch kernel (`apply_batch_fast`) keeps the table as a log: tokens
are armed into the lanes behind `head` in arrival order and the live ones are
moved to the front, in order, when the tail has no room left, so lane order IS
arming order there; the count kernel and the per-event scan hand out free
lanes and order completions by the token's `seq`, its arming number.

A state whose condition ties the arriving row to an earlier capture by
equality (`row.attr == eK.attr`, any number of attributes) finds its tokens by
key (`_match_keyed`): tokens and rows are sorted together on the key, a token
placed behind the row that brought it there, and each token meets the rows of
its own key alone, one candidate a pass, the residual evaluated on those
pairs; nothing of T x B elements exists. A state with no such conjunct keeps
the dense [T, B] match matrix (`_match_matrix`). `match_kind` says which a
program took: `keyed`, `matrix`, `count` or `scan`.

Deliberate deviations from the reference interpreter (documented, test-covered):
- with three states or more, the matches of one event come in the order of
  their FIRST events, where the reference's last pending list holds them in
  the order in which they advanced into it; with two states the two are one;
- token/capture capacity is static (`@app:patternCapacity`, `@app:countCapacity`)
  with overflow surfaced via aux flags, where the reference grows lists unboundedly;
- `every` over a count state arms a fresh virgin token when a token's count
  reaches min. The reference's addEveryState clone at that point shares its
  capture chains with the parent (StateEventCloner.copyStateEvent is shallow)
  and is never re-forwarded — a structural dead end no reference test covers —
  so the clean generation-chain semantics is used instead;
- counts absorb past the capture capacity on both execution paths (the
  occurrence counter keeps counting while capture writes drop), so `<m:>`
  with m above `@app:countCapacity` still fires — only the first `cap`
  occurrences are retrievable;
- absent states with a waiting time are supported standalone (`A -> not B for 5
  sec`) and inside logical elements (`A and not B for t` completes at the
  deadline once every present side arrived; `A or not B for t` completes via
  the present side immediately or at the deadline with the absent ref null —
  reference: AbsentLogicalPreStateProcessor, LogicalAbsentPatternTestCase
  testQueryAbsent11-16). Logical elements whose BOTH sides are absent
  (`not A for t1 and/or not B for t2`) complete on timers: AND at the later
  deadline iff neither side arrived inside its window; OR at each side's own
  deadline iff that side never arrived (an `every` generator fires once per
  clean side; non-every completes once at the earliest —
  LogicalAbsentPatternTestCase testQueryAbsent25-40, 46-50).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.lax as lax
import jax.numpy as jnp
import numpy as np

from siddhi_tpu.core.errors import SiddhiAppCreationError
from siddhi_tpu.core.event import (
    EventBatch,
    KIND_CURRENT,
    KIND_TIMER,
    StreamSchema,
)
from siddhi_tpu.core.executor import (
    TS_ATTR,
    Env,
    Scope,
    compile_expression,
)
from siddhi_tpu.core.types import AttrType, InternTable, PHYSICAL_DTYPE, null_value
from siddhi_tpu.query_api.execution import (
    AbsentStreamStateElement,
    CountStateElement,
    EveryStateElement,
    Filter,
    LogicalStateElement,
    LogicalType,
    NextStateElement,
    StateElement,
    StateInputStream,
    StateStreamType,
    StreamStateElement,
)
from siddhi_tpu.ops.group import permute_by, permute_in_groups
from siddhi_tpu.ops.prefix import (
    compact_front,
    cummax,
    first_indices,
    segmented_carry,
)
from siddhi_tpu.ops.scatter import set_at as _set_at
from siddhi_tpu.query_api.expression import (
    And,
    Compare,
    CompareOp,
    Expression,
    Variable,
)

NO_TIMER = np.int64(np.iinfo(np.int64).max)

DEFAULT_TOKEN_CAPACITY = 128
DEFAULT_COUNT_CAPACITY = 8

# Test hook: force every pattern onto the per-event scan path (the batch
# kernels' differential oracle). Read at step-build time.
FORCE_SCAN = False

# How a program finds each token's first matching row
# (`snapshot_status()["queries"][q]["pattern"]["match"]`).
MATCH_KEYED, MATCH_MATRIX, MATCH_COUNT, MATCH_SCAN = (
    "keyed", "matrix", "count", "scan"
)

# attribute types whose `==` is an equality of bits, so that a sort on the
# value finds the equal ones (floats are not: -0.0 == 0.0, NaN != NaN)
_KEY_TYPES = (AttrType.INT, AttrType.LONG, AttrType.BOOL, AttrType.STRING)

def _conjuncts(expr: Expression) -> list:
    if isinstance(expr, And):
        return _conjuncts(expr.left) + _conjuncts(expr.right)
    return [expr]


@dataclasses.dataclass
class SlotPlan:
    """A slot's condition cut into its conjuncts: those that read the
    arriving row alone (`row`), equalities between an attribute of the row
    and one of an earlier capture (`keys`: row attribute, the capture's ref
    index and attribute, the type) and the rest, which reads both (`rest`,
    with the VarKeys it reads)."""

    row: list
    keys: list
    rest: list
    rest_keys: frozenset


def _min_within(slot_ms, global_ms):
    """Effective within bound: a token dies when EITHER the slot's or the
    pattern-global within is exceeded (matching the scan path's kill list)."""
    if slot_ms is None:
        return global_ms
    if global_ms is None:
        return slot_ms
    return min(slot_ms, global_ms)


@dataclasses.dataclass
class Atom:
    """One stream obligation inside a slot (reference: a single
    Stream/AbsentStream state element)."""

    ref: str
    ref_idx: int
    stream_id: str
    filters: list  # raw Expression list, compiled in PatternProgram
    absent: bool = False
    waiting_ms: Optional[int] = None
    cap: int = 1  # occurrence capture capacity K


@dataclasses.dataclass
class Slot:
    """One linearized NFA state (reference: one Pre/Post state-processor pair)."""

    index: int
    atoms: list  # [Atom] — two entries for logical elements
    logical: Optional[LogicalType] = None
    min_count: int = 1
    max_count: int = 1  # -1 == unbounded
    persistent: bool = False  # `every` entry: matches fork, token stays
    within_ms: Optional[int] = None

    @property
    def is_count(self) -> bool:
        return not (self.min_count == 1 and self.max_count == 1)

    @property
    def is_absent(self) -> bool:
        return len(self.atoms) == 1 and self.atoms[0].absent


def _flatten_state(
    elem: StateElement,
    slots: list,
    refs: list,
    schemas: dict,
    count_cap: int,
    every_blocks: list,
) -> None:
    """Linearize the state-element tree into the slot chain (reference:
    StateInputStreamParser.parseInputStream recursive walk,
    util/parser/StateInputStreamParser.java:134-430)."""

    def new_atom(stream, absent=False, waiting=None, cap=1) -> Atom:
        sid = stream.stream_id
        if sid not in schemas:
            raise SiddhiAppCreationError(f"stream '{sid}' is not defined")
        ref = stream.alias
        if ref is None:
            # unaliased: referenceable by stream name when that stream appears
            # exactly once in the pattern; otherwise synthetic
            uses = sum(1 for r in refs if r.stream_id == sid)
            ref = sid if uses == 0 else f"__p{len(refs)}"
        if any(r.ref == ref for r in refs):
            raise SiddhiAppCreationError(f"duplicate pattern event reference '{ref}'")
        filters = [
            h.expression for h in stream.handlers if isinstance(h, Filter)
        ]
        if len(filters) != len(stream.handlers):
            raise SiddhiAppCreationError(
                "pattern sources support only filters (no windows/stream functions)"
            )
        a = Atom(ref, len(refs), sid, filters, absent=absent, waiting_ms=waiting, cap=cap)
        refs.append(a)
        return a

    if isinstance(elem, NextStateElement):
        first = len(slots)
        _flatten_state(elem.state, slots, refs, schemas, count_cap, every_blocks)
        _flatten_state(elem.next, slots, refs, schemas, count_cap, every_blocks)
        if elem.within_ms is not None:
            for s in slots[first:]:
                s.within_ms = s.within_ms or elem.within_ms
    elif isinstance(elem, EveryStateElement):
        first = len(slots)
        _flatten_state(elem.state, slots, refs, schemas, count_cap, every_blocks)
        if len(slots) == first + 1:
            # single-slot every: persistent generator slot (forks per match)
            slots[first].persistent = True
        elif len(slots) > first + 1:
            # multi-slot every BLOCK: re-arms when the block COMPLETES
            # (reference: EveryInnerStateRuntime wires the block's last post
            # processor's nextEveryStatePreProcessor back to the block's
            # first pre — matches are strictly serial, EveryPatternTestCase
            # testQuery5/7)
            every_blocks.append((first, len(slots) - 1))
        if elem.within_ms is not None:
            for s in slots[first:]:
                s.within_ms = s.within_ms or elem.within_ms
    elif isinstance(elem, CountStateElement):
        mx = elem.max_count
        cap = mx if 0 < mx <= count_cap else count_cap
        atom = new_atom(elem.stream.stream, cap=cap)
        slots.append(
            Slot(
                len(slots),
                [atom],
                min_count=elem.min_count,
                max_count=mx,
                within_ms=elem.within_ms,
            )
        )
    elif isinstance(elem, LogicalStateElement):
        atoms = []
        for side in (elem.left, elem.right):
            if isinstance(side, AbsentStreamStateElement):
                atoms.append(
                    new_atom(
                        side.stream, absent=True,
                        waiting=side.waiting_time_ms,
                    )
                )
            elif isinstance(side, StreamStateElement):
                atoms.append(new_atom(side.stream))
            else:
                raise SiddhiAppCreationError(
                    "'and'/'or' sides must be plain or absent streams"
                )
        if all(a.absent for a in atoms) and any(
            a.waiting_ms is None for a in atoms
        ):
            raise SiddhiAppCreationError(
                "a logical element with both sides absent needs "
                "'for <time>' on each side "
                "(reference: AbsentLogicalPreStateProcessor waiting times)"
            )
        slots.append(
            Slot(len(slots), atoms, logical=elem.type, within_ms=elem.within_ms)
        )
    elif isinstance(elem, AbsentStreamStateElement):
        if elem.waiting_time_ms is None:
            raise SiddhiAppCreationError(
                "a standalone absent stream needs 'for <time>' "
                "(reference: AbsentStreamPreStateProcessor waiting time)"
            )
        atom = new_atom(elem.stream, absent=True, waiting=elem.waiting_time_ms)
        slots.append(Slot(len(slots), [atom], within_ms=elem.within_ms))
    elif isinstance(elem, StreamStateElement):
        atom = new_atom(elem.stream)
        slots.append(Slot(len(slots), [atom], within_ms=elem.within_ms))
    else:
        raise SiddhiAppCreationError(f"unsupported state element {type(elem).__name__}")


def _key_words(x: jnp.ndarray) -> list:
    """An integral lane as the 32-bit words a sort compares: equal values
    have equal words (the order among unequal ones does not matter)."""
    if x.dtype == jnp.bool_:
        return [x.astype(jnp.int32)]
    if x.dtype.itemsize < 8:
        return [x.astype(jnp.int32)]
    return [
        (x >> 32).astype(jnp.int32),
        (x & np.int64(0xFFFFFFFF)).astype(jnp.uint32),
    ]


def _pair_lanes(tl: dict, rl: dict, T: int, B: int) -> list:
    """Token-side [T] lanes and row-side [B] lanes as [T + B] lanes, a token
    lane and a row lane of one dtype sharing one (a token element reads the
    one, a row element the other): (lane, token key or None, row key or
    None), so that a payload sort carries as few operands as it can."""
    out = []
    tk = sorted(tl, key=repr)
    rk = sorted(rl, key=repr)
    for dt in sorted({str(v.dtype) for v in (*tl.values(), *rl.values())}):
        ts_ = [k for k in tk if str(tl[k].dtype) == dt]
        rs_ = [k for k in rk if str(rl[k].dtype) == dt]
        for i in range(max(len(ts_), len(rs_))):
            t = ts_[i] if i < len(ts_) else None
            r = rs_[i] if i < len(rs_) else None
            out.append((
                jnp.concatenate([
                    tl[t] if t is not None else jnp.zeros((T,), dt),
                    rl[r] if r is not None else jnp.zeros((B,), dt),
                ]),
                t, r,
            ))
    return out


def tokens_from_legacy(tok: dict, like: dict) -> dict:
    """A token table saved before its tokens carried their arming order
    (before PR 43: any free lane took a new token), in the layout of `like`:
    the live tokens move to the front, virgins first and the others in the
    order of their first events (`start_ts`, then the lane they held: what
    such a snapshot still knows of it), `seq` numbers them so and `head`
    stands behind them; the counters start at zero. On the host, in numpy;
    leading axes (a partition's [P]) pass through."""
    active = np.asarray(tok["active"])
    T = active.shape[-1]
    lead = active.ndim - 1
    lanes = np.broadcast_to(np.arange(T), active.shape)
    start = np.asarray(tok["start_ts"])
    order = np.lexsort((lanes, np.where(start < 0, -1, start), ~active), axis=-1)

    def move(x):
        x = np.asarray(x)
        if x.ndim <= lead or x.shape[lead] != T:
            return x
        idx = order.reshape(order.shape + (1,) * (x.ndim - lead - 1))
        return np.take_along_axis(x, idx, axis=lead)

    out = jax.tree_util.tree_map(move, dict(tok))
    n = active.sum(axis=-1)
    out["seq"] = np.broadcast_to(np.arange(T, dtype=np.int64), active.shape).copy()
    out["next_seq"] = n.astype(np.int64) + 1
    out["head"] = n.astype(np.int32)
    for k in ("armed", "expired", "completed", "refused", "max_row"):
        out[k] = np.zeros(n.shape, like[k].dtype)
    return out


def _longest_run(keys_sorted: jnp.ndarray, n) -> jnp.ndarray:
    """The longest run of equal values among the first `n` of a sorted
    [M] lane, int32: the most matches one row completed."""
    m = keys_sorted.shape[0]
    idx = jnp.arange(m, dtype=jnp.int32)
    opens = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), keys_sorted[1:] != keys_sorted[:-1]]
    )
    start = cummax(jnp.where(opens, idx, 0))
    return jnp.max(jnp.where(idx < n, idx - start + 1, 0))


class PatternProgram:
    """Compiled NFA: slot chain + per-atom conditions + token-table layout."""

    def __init__(
        self,
        state_stream: StateInputStream,
        schemas: dict[str, StreamSchema],
        interner: InternTable,
        token_capacity: int = DEFAULT_TOKEN_CAPACITY,
        count_capacity: int = DEFAULT_COUNT_CAPACITY,
    ):
        self.sequence = state_stream.type is StateStreamType.SEQUENCE
        self.within_ms = state_stream.within_ms
        self.T = token_capacity
        self.schemas = schemas
        self.interner = interner

        self.slots: list[Slot] = []
        self.refs: list[Atom] = []
        self.every_blocks: list[tuple[int, int]] = []
        _flatten_state(
            state_stream.state, self.slots, self.refs, schemas, count_capacity,
            self.every_blocks,
        )
        if not self.slots:
            raise SiddhiAppCreationError("empty pattern")

        # name-resolution scope over every ref (reference: each state's
        # MatchingMetaInfoHolder exposes all earlier stream events)
        self.scope = Scope(interner)
        for a in self.refs:
            self.scope.add_stream(a.ref, schemas[a.stream_id].attr_types)
        self.scope.default_ref = self.refs[0].ref

        # compiled per-atom condition: AND of the atom's filters, evaluated over
        # the token table with the current event broadcast as the atom's own ref.
        # _cond_keys records which VarKeys each slot's conditions read — the
        # count fast path gates on conditions being row-only (no token-table
        # dependence) for slots 0 and 1.
        self._conds = {}
        self._cond_keys: dict[tuple, set] = {}
        for slot in self.slots:
            for atom in slot.atoms:
                conds = []
                keys: set = set()
                for f in atom.filters:
                    s = self.scope.child()
                    s.default_ref = atom.ref
                    s.prefer_default = True
                    c = compile_expression(f, s)
                    if c.type is not AttrType.BOOL:
                        raise SiddhiAppCreationError("pattern filter must be boolean")
                    conds.append(c)
                    keys |= s.used_keys
                self._conds[(slot.index, atom.ref_idx)] = conds
                self._cond_keys[(slot.index, atom.ref_idx)] = keys

        self._plans = {
            slot.index: self._plan_slot(slot) for slot in self.slots
        }

        self.stream_ids = sorted({a.stream_id for a in self.refs})
        self.needs_scheduler = any(
            a.waiting_ms is not None for a in self.refs
        )
        # keys read from the EMISSION buffer (selector/having/order-by) —
        # set by the owning runtime from the selector's child scope; None
        # means unknown, which disables projection (keep everything).
        # capture_keep() combines these with indexed keys and cross-ref
        # condition reads to project the token capture lanes (TPU gathers
        # and scatters run near one element per scalar-core cycle, so every
        # unread [T, cap] lane is pure wall-clock)
        self._capture_readers: Optional[frozenset] = None
        self._keep_cache = None
        # sequences with count slots carry an explicit per-token forwarding
        # lane (reference: SEQUENCE addState accepts ONE new state per event,
        # so next-slot pending membership is a contended, per-event win —
        # SequenceTestCase testQuery6/11). Patterns keep implicit count-skip.
        self._use_fwd = self.sequence and any(s.is_count for s in self.slots)

    # ---- the keyed match's plan ------------------------------------------

    def _plan_slot(self, slot: Slot) -> Optional[SlotPlan]:
        """Cut the slot's condition into row-only, key-equality and other
        conjuncts, or None where the batch kernel cannot take it apart (a
        logical or count slot, an indexed capture read)."""
        if len(slot.atoms) != 1 or slot.is_count or slot.is_absent:
            return None
        atom = slot.atoms[0]
        if atom.cap != 1:
            return None
        by_ref = {a.ref: a for a in self.refs}
        plan = SlotPlan([], [], [], frozenset())
        rest_keys: set = set()
        for f in atom.filters:
            for e in _conjuncts(f):
                s = self.scope.child()
                s.default_ref = atom.ref
                s.prefer_default = True
                c = compile_expression(e, s)
                keys = set(s.used_keys)
                if any(
                    k[1] not in (None, 0) or k[0] not in by_ref
                    or k[2] == "__arrived__" for k in keys
                ):
                    return None
                if all(k[0] == atom.ref for k in keys):
                    plan.row.append(c)
                    continue
                pair = self._key_pair(e, s, atom, by_ref)
                if pair is not None:
                    plan.keys.append(pair)
                    continue
                plan.rest.append(c)
                rest_keys |= keys
        plan.rest_keys = frozenset(rest_keys)
        return plan

    def _key_pair(self, e, scope, atom: Atom, by_ref: dict):
        """(row attribute, capture ref index, capture attribute, type) of a
        conjunct `row.attr == eK.attr` (either way round) over one integral
        type, eK an earlier single capture; else None."""
        if not (
            isinstance(e, Compare) and e.op is CompareOp.EQ
            and isinstance(e.left, Variable) and isinstance(e.right, Variable)
        ):
            return None
        (lk, lt), (rk, rt) = scope.resolve(e.left), scope.resolve(e.right)
        if lt is not rt or lt not in _KEY_TYPES:
            return None
        if rk[0] == atom.ref:
            lk, rk = rk, lk
        other = by_ref.get(rk[0])
        if (
            lk[0] != atom.ref or other is None or other.ref_idx >= atom.ref_idx
            or other.cap != 1 or TS_ATTR in (lk[2], rk[2])
        ):
            return None
        return (lk[2], other.ref_idx, rk[2], lt)

    def slot_keyed(self, p: int) -> bool:
        """Whether the batch kernel matches slot p's tokens by key."""
        plan = self._plans.get(p)
        return (
            plan is not None and bool(plan.keys) and not self.sequence
            and not (p == 0 and self.slots[0].persistent)
        )

    def slot_row_only(self, p: int) -> bool:
        """Whether slot p's whole condition reads the arriving row alone."""
        plan = self._plans.get(p)
        return plan is not None and not plan.keys and not plan.rest

    @property
    def match_kind(self) -> str:
        """How the program built for this pattern finds a token's first
        matching row: `keyed` when every state the batch kernel matches ties
        the row to a capture by equality (an `every` first state filters rows
        alone), `matrix` when some state keeps the dense [T, B] matrix,
        `count` for the closed-form count kernel, `scan` for the per-event
        scan."""
        if FORCE_SCAN:
            return MATCH_SCAN
        if self.fast_path_ok:
            arm = self.slots[0].persistent
            ok = all(
                self.slot_row_only(p) if (p == 0 and arm) else self.slot_keyed(p)
                for p in range(len(self.slots))
            )
            return MATCH_KEYED if ok else MATCH_MATRIX
        return MATCH_COUNT if self.count_fast_ok else MATCH_SCAN

    # ---- capture projection ---------------------------------------------

    def set_capture_readers(self, keys: frozenset) -> None:
        """Declare the emission-buffer reader keys (selector/having/order-by).

        Must run before any state/kernel builder calls capture_keep(): a
        keep-set memoized earlier is left in place (state shapes must stay
        consistent across traces) and the missed projection is logged loudly
        instead of silently vanishing."""
        if self._keep_cache is not None:
            import logging

            logging.getLogger(__name__).warning(
                "pattern capture projection disabled: capture_keep() was "
                "memoized before set_capture_readers() — a state or kernel "
                "builder ran too early; all capture lanes stay materialized"
            )
            return
        self._capture_readers = frozenset(keys)

    def capture_keep(self):
        """Per-ref projection of the capture lanes: (keep_cols, ts_used).

        keep_cols[ref_idx] — attribute names whose captured values some
        compiled expression actually reads; every other attribute lane is
        never materialized in the token table or the emission buffer.
        ts_used[ref_idx] — whether the ref's captured-timestamp lane is read
        (selector/conditions) or structurally required (absent deadlines,
        next_timer reads caps ts[:, 0]).

        A key counts as a CAPTURE read when it is indexed (e1[2].price /
        e1[last]), recorded after pattern construction (selector / having /
        order-by resolve against the emission buffer), or recorded by a
        condition of a DIFFERENT ref (cross-ref reads see the partner's
        captures); an atom's own un-indexed keys read the live event, which
        the env builders substitute directly. Reference analog: every
        StateEvent carries all captured StreamEvents
        (event/state/StateEvent.java) — here only the read subset exists.
        """
        if self._keep_cache is not None:
            return self._keep_cache
        used = set(self.scope.root_used_keys())
        by_ref = {a.ref: a for a in self.refs}
        if self._capture_readers is None:
            needed = used  # owner never told us — keep everything
        else:
            cross = set()
            for (_slot_idx, ref_idx), keys in self._cond_keys.items():
                me = self.refs[ref_idx].ref
                cross |= {k for k in keys if k[0] != me}
            needed = (
                {k for k in used if k[1] is not None}
                | set(self._capture_readers)
                | cross
            )
        keep_cols = {a.ref_idx: set() for a in self.refs}
        ts_used = {
            a.ref_idx: bool(a.absent and a.waiting_ms is not None)
            for a in self.refs
        }
        for ref, _k, attr in needed:
            a = by_ref.get(ref)
            if a is None:
                continue
            if attr == TS_ATTR:
                ts_used[a.ref_idx] = True
            elif attr in self.schemas[a.stream_id].attr_types:
                keep_cols[a.ref_idx].add(attr)
        self._keep_cache = (keep_cols, ts_used)
        return self._keep_cache

    # ---- token table ----------------------------------------------------

    def init_state(self, now: int = 0):
        T = self.T
        keep_cols, _ts_used = self.capture_keep()
        caps = []
        for a in self.refs:
            schema = self.schemas[a.stream_id]
            cols = {
                name: jnp.full(
                    (T, a.cap), null_value(t), dtype=PHYSICAL_DTYPE[t]
                )
                for name, t in schema.attrs
                if name in keep_cols[a.ref_idx]
            }
            caps.append(
                {
                    "n": jnp.zeros((T,), dtype=jnp.int32),
                    "ts": jnp.zeros((T, a.cap), dtype=jnp.int64),
                    "cols": cols,
                }
            )
        tok = {
            "active": jnp.zeros((T,), dtype=jnp.bool_).at[0].set(True),
            "slot": jnp.zeros((T,), dtype=jnp.int32),
            # -1 == virgin (no event captured yet); 0 is a legitimate epoch ts
            "start_ts": jnp.full((T,), -1, dtype=jnp.int64),
            "entry_ts": jnp.full((T,), now, dtype=jnp.int64).at[1:].set(0),
            "caps": caps,
            # the token's arming number: completions of one event are
            # emitted in its order (the count kernel and the scan; the batch
            # kernel keeps the lanes themselves in that order, behind `head`)
            "seq": jnp.zeros((T,), dtype=jnp.int64),
            "next_seq": jnp.ones((), dtype=jnp.int64),
            "head": jnp.ones((), dtype=jnp.int32),
            # since deploy: tokens armed, tokens whose `within` ran out,
            # and the most matches one row has completed
            "armed": jnp.zeros((), dtype=jnp.int64),
            "expired": jnp.zeros((), dtype=jnp.int64),
            "max_row": jnp.zeros((), dtype=jnp.int32),
            # kept by the runtime's step: matches emitted, and arms and
            # emissions refused for want of a lane or of room
            "completed": jnp.zeros((), dtype=jnp.int64),
            "refused": jnp.zeros((), dtype=jnp.int64),
        }
        if self._use_fwd:
            # a min-0 count start state forwards its virgin immediately
            # (reference: CountPreStateProcessor.addState minCount==0 branch)
            fwd0 = self.slots[0].is_count and self.slots[0].min_count == 0
            tok["fwd"] = jnp.zeros((T,), dtype=jnp.bool_).at[0].set(fwd0)
        return tok

    # ---- environments ----------------------------------------------------

    def _synth_capture_cols(self, cols, col_of, ts_of, n_of, expand=None):
        """Synthesize columns for used capture keys outside the stored range:
        e1[k] with k >= cap reads null, e1[last]/e1[last-i] gather by the live
        occurrence count (reference: StateEvent.getStreamEvent(position) walks
        the chain and returns null past the end; `last` indexes the tail).

        col_of(a, attr) -> [N, cap], ts_of(a) -> [N, cap], n_of(a) -> [N].
        """
        by_ref = {a.ref: a for a in self.refs}
        for key in self.scope.root_used_keys():
            ref, k, attr = key
            a = by_ref.get(ref)
            if a is None or k is None or key in cols:
                continue
            n = n_of(a)
            if attr == "__arrived__":
                col = (n > k) if k >= 0 else (n >= -k)
            else:
                if attr == TS_ATTR:
                    arr = ts_of(a)
                    nv = np.asarray(null_value(AttrType.LONG), dtype=arr.dtype)
                else:
                    t = self.schemas[a.stream_id].attr_types.get(attr)
                    if t is None:
                        continue
                    arr = col_of(a, attr)
                    nv = np.asarray(null_value(t), dtype=arr.dtype)
                if k >= a.cap:
                    col = jnp.full(arr.shape[:1], nv, dtype=arr.dtype)
                elif k >= 0:
                    col = arr[:, k]
                else:
                    idx = n + k  # last == -1 -> n-1, last-i -> n-1-i
                    col = jnp.full(arr.shape[:1], nv, dtype=arr.dtype)
                    for i in range(a.cap):
                        col = jnp.where(idx == i, arr[:, i], col)
            cols[key] = expand(col) if expand else col

    def _token_env(self, tok, now, override_ref: Optional[int] = None,
                   event_cols: Optional[dict] = None, event_ts=None) -> Env:
        """Column view of the token table; `override_ref` substitutes the
        current event (broadcast scalars) for that ref's columns."""
        T = self.T
        cols = {}
        for a in self.refs:
            c = tok["caps"][a.ref_idx]
            for name in c["cols"]:
                cols[(a.ref, None, name)] = c["cols"][name][:, 0]
                for k in range(a.cap):
                    cols[(a.ref, k, name)] = c["cols"][name][:, k]
            cols[(a.ref, None, TS_ATTR)] = c["ts"][:, 0]
            for k in range(a.cap):
                cols[(a.ref, k, TS_ATTR)] = c["ts"][:, k]
            cols[(a.ref, None, "__arrived__")] = c["n"] > 0
        self._synth_capture_cols(
            cols,
            lambda a, attr: tok["caps"][a.ref_idx]["cols"][attr],
            lambda a: tok["caps"][a.ref_idx]["ts"],
            lambda a: tok["caps"][a.ref_idx]["n"],
        )
        if override_ref is not None:
            a = self.refs[override_ref]
            for name, v in event_cols.items():
                cols[(a.ref, None, name)] = jnp.broadcast_to(v, (T,))
            cols[(a.ref, None, TS_ATTR)] = jnp.broadcast_to(event_ts, (T,))
            cols[(a.ref, None, "__arrived__")] = jnp.ones((T,), dtype=jnp.bool_)
        return Env(cols, now=now)

    # ---- per-event application -------------------------------------------

    def _eligible(self, tok, p: int) -> jnp.ndarray:
        """Tokens that may match slot p: at p, or parked at preceding count
        slots whose min is satisfied (count-skip, reference:
        CountPreStateProcessor min-count forwarding).

        SEQUENCE type keeps only the OLDEST forwarded token: the reference's
        addState accepts a single new state per event for sequences
        (StreamPreStateProcessor.addState SEQUENCE branch), so a contended
        forward is won by the earlier chain — SequenceTestCase testQuery11."""
        active, slot = tok["active"], tok["slot"]
        elig = active & (slot == p)
        skip = jnp.zeros_like(elig)
        q = p - 1
        while q >= 0 and self.slots[q].is_count:
            sat = tok["caps"][self.slots[q].atoms[0].ref_idx]["n"] >= max(
                self.slots[q].min_count, 0
            )
            skip = skip | (active & (slot == q) & sat)
            if self.slots[q].min_count > 0:
                break
            q -= 1
        if self._use_fwd:
            # sequence forwarding is explicit: a token reaches the next
            # slot's pending only by winning its forward event's contention
            # (the fwd lane, updated at each event's end)
            skip = skip & tok["fwd"]
        return elig | skip

    def _capture(self, caps_r, atom: Atom, match, ts, event_cols):
        """Write the current event into ref r's next occurrence slot."""
        T = self.T
        n = caps_r["n"]
        pos = jnp.clip(n, 0, atom.cap - 1)
        write = match & (n < atom.cap)
        rowi = jnp.arange(T)
        new_cols = {}
        for name, arr in caps_r["cols"].items():
            upd = arr.at[rowi, pos].set(
                jnp.broadcast_to(event_cols[name], (T,)).astype(arr.dtype)
            )
            new_cols[name] = jnp.where(write[:, None], upd, arr)
        upd_ts = caps_r["ts"].at[rowi, pos].set(jnp.broadcast_to(ts, (T,)))
        return {
            "n": jnp.where(match, n + 1, n),
            "ts": jnp.where(write[:, None], upd_ts, caps_r["ts"]),
            "cols": new_cols,
        }

    def apply_event(
        self, tok, ts, kind, valid, stream_cols: dict[str, dict], out, out_n,
        overflow, timer_seen=None,
    ):
        """One scan step: apply a single event row to the token table.

        stream_cols: {stream_id: {attr: scalar}} — the row's columns, keyed by
        the stream this step function serves (one entry).

        timer_seen: max TIMER timestamp already processed. Deadline blocks
        fire on any valid row whose effective time max(ts, timer_seen)
        passes the deadline — redundant when timers arrive in order (the
        scheduler fires first), but it rescues tokens whose deadlines fall at
        or before an already-processed timer (late/out-of-order event
        timestamps), which next_timer's `after` filter would otherwise
        silently drop.
        """
        is_cur = valid & (kind == KIND_CURRENT)
        is_timer = valid & (kind == KIND_TIMER)
        if timer_seen is None:
            timer_seen = np.int64(-(1 << 62))
        eff_now = jnp.maximum(ts, timer_seen)
        can_fire = is_timer | is_cur

        # within expiry (reference: StreamPreStateProcessor.isExpired :102-121)
        active = tok["active"]
        kills = []
        started = tok["start_ts"] >= 0
        if self.within_ms is not None:
            kills.append(started & (ts - tok["start_ts"] > self.within_ms))
        for slot in self.slots:
            if slot.within_ms is not None:
                kills.append(
                    (tok["slot"] == slot.index)
                    & started
                    & (ts - tok["start_ts"] > slot.within_ms)
                )
        if kills:
            dead = kills[0]
            for k in kills[1:]:
                dead = dead | k
            dead = tok["active"] & dead & valid
            active = tok["active"] & ~dead
            tok = {
                **tok, "active": active,
                "expired": tok["expired"] + dead.sum(dtype=jnp.int64),
            }

        touched = jnp.zeros((self.T,), dtype=jnp.bool_)
        last = len(self.slots) - 1

        # ---- sequence start-state re-init: the reference clears every
        # pending list per event and re-inits the start state when its
        # pending empties (SequenceSingleProcessStreamReceiver.stabilizeStates
        # -> resetAndUpdate -> StreamPreStateProcessor.init). For an
        # every-rooted sequence that means a fresh virgin must exist whenever
        # no slot-0 token is still pending there (virgin, or a count still
        # absorbing below max) — SequenceTestCase testQuery6.
        if self.sequence and self.slots[0].persistent:
            s0 = self.slots[0]
            n0 = tok["caps"][s0.atoms[0].ref_idx]["n"]
            pend = tok["active"] & (tok["slot"] == 0) & (tok["start_ts"] < 0)
            if s0.is_count:
                mx0 = s0.max_count if s0.max_count > 0 else (1 << 30)
                pend = pend | (
                    tok["active"] & (tok["slot"] == 0) & (n0 < mx0)
                )
            need = is_cur & ~pend.any()
            mask0 = jnp.zeros((self.T,), dtype=jnp.bool_).at[0].set(True) & need
            tok, overflow = self._arm_virgins(tok, mask0, 0, ts, overflow)

        # ---- timer handling: absent deadlines emit/advance
        for slot in self.slots:
            atom = slot.atoms[0]
            p = slot.index
            if slot.is_absent and atom.waiting_ms is not None:
                at_p = tok["active"] & (tok["slot"] == p)
                deadline = tok["entry_ts"] + atom.waiting_ms
                fire = at_p & can_fire & (eff_now >= deadline)
                # a token completed by an absence has no captured event to
                # start its within clock: the deadline starts it (so `within`
                # can expire absent-first patterns — AbsentPatternTestCase
                # testQueryAbsent42)
                started = jnp.where(
                    fire & (tok["start_ts"] < 0), deadline, tok["start_ts"]
                )
                tok = {**tok, "start_ts": started}
                if p == last:
                    # emit with this ref not arrived; output ts = deadline
                    out, out_n, overflow = self._write_emits(
                        out, out_n, overflow, fire, tok, deadline
                    )
                    if slot.persistent:
                        # `every not X for t`: the generator re-arms with a
                        # fresh window starting at the fired deadline
                        # (EveryAbsentPatternTestCase testQueryAbsent1)
                        tok = self._clear_slot_caps(
                            tok, fire, slot, ts=deadline
                        )
                    else:
                        tok = self._consume(tok, fire, slot)
                elif slot.persistent:
                    # fork the completion downstream; generator re-arms
                    tok, overflow, _dest = self._fork(
                        tok, tok, fire, p + 1, deadline, overflow
                    )
                    tok = self._clear_slot_caps(tok, fire, slot, ts=deadline)
                else:
                    tok = self._advance_rows(tok, fire, slot, deadline)
                touched = touched | fire
            elif slot.logical is not None and all(
                a.absent and a.waiting_ms is not None for a in slot.atoms
            ):
                # both sides absent (`not A for t1 and/or not B for t2`) —
                # reference: AbsentLogicalPreStateProcessor with two absent
                # partners (LogicalAbsentPatternTestCase 25-40, 46-50).
                # AND completes at the LATER deadline iff neither side
                # arrived inside its window; OR completes at each side's own
                # deadline iff that side never arrived (an `every` generator
                # fires once per side — two pendings when both are clean;
                # a non-every element completes once, at the earliest).
                a1, a2 = slot.atoms[0], slot.atoms[1]
                at_p = tok["active"] & (tok["slot"] == p)
                arr1 = tok["caps"][a1.ref_idx]["n"] > 0
                arr2 = tok["caps"][a2.ref_idx]["n"] > 0
                if p == 0:
                    # start-of-pattern: an arrival re-arms that side's
                    # window from the arrival (marker ts lane), it does not
                    # block completion forever
                    last1 = tok["caps"][a1.ref_idx]["ts"][:, 0]
                    last2 = tok["caps"][a2.ref_idx]["ts"][:, 0]
                    dl1 = jnp.maximum(tok["entry_ts"], last1) + a1.waiting_ms
                    dl2 = jnp.maximum(tok["entry_ts"], last2) + a2.waiting_ms
                    arr1 = jnp.zeros_like(arr1)
                    arr2 = jnp.zeros_like(arr2)
                else:
                    dl1 = tok["entry_ts"] + a1.waiting_ms
                    dl2 = tok["entry_ts"] + a2.waiting_ms
                if slot.logical is LogicalType.AND:
                    both_dl = jnp.maximum(dl1, dl2)
                    fires = [
                        (
                            at_p & can_fire & ~arr1 & ~arr2 & (eff_now >= both_dl),
                            both_dl,
                        )
                    ]
                else:
                    f1 = at_p & can_fire & ~arr1 & (eff_now >= dl1)
                    f2 = at_p & can_fire & ~arr2 & (eff_now >= dl2)
                    if slot.persistent:
                        fires = [(f1, dl1), (f2, dl2)]
                    else:
                        fires = [(f1 | f2, jnp.where(f1, dl1, dl2))]
                for fire, dts in fires:
                    if p == last:
                        out, out_n, overflow = self._write_emits(
                            out, out_n, overflow, fire, tok, dts
                        )
                        if slot.persistent:
                            # every-generator: window restarts at the fired
                            # deadline
                            tok = self._clear_slot_caps(
                                tok, fire, slot, ts=dts
                            )
                        else:
                            tok = self._consume(tok, fire, slot)
                    elif slot.persistent:
                        # fork the pending completion; the generator stays
                        # armed with its window restarted at the deadline
                        tok, overflow, _dest = self._fork(
                            tok, tok, fire, p + 1, dts, overflow
                        )
                        tok = self._clear_slot_caps(tok, fire, slot, ts=dts)
                    else:
                        tok = self._advance_rows(tok, fire, slot, dts)
                    touched = touched | fire
                continue
            elif slot.logical is not None:
                # `A and not B for t`: completes at the deadline once every
                # present side has arrived. `A or not B for t`: completes at
                # the deadline iff B never arrived inside the window (an A
                # arrival would have advanced the token immediately).
                # (reference: AbsentLogicalPreStateProcessor waiting-time
                # scheduling for both logical types)
                ab = next(
                    (
                        a for a in slot.atoms
                        if a.absent and a.waiting_ms is not None
                    ),
                    None,
                )
                if ab is None:
                    continue
                at_p = tok["active"] & (tok["slot"] == p)
                deadline = tok["entry_ts"] + ab.waiting_ms
                if slot.logical is LogicalType.OR:
                    # B's arrival was recorded as a capture marker (it must
                    # not kill the token — A can still complete the or)
                    b_arrived = tok["caps"][ab.ref_idx]["n"] > 0
                    fire = at_p & can_fire & ~b_arrived & (eff_now >= deadline)
                else:
                    arrived = jnp.ones((self.T,), dtype=jnp.bool_)
                    for a2 in slot.atoms:
                        if not a2.absent:
                            arrived = arrived & (
                                tok["caps"][a2.ref_idx]["n"] > 0
                            )
                    fire = at_p & can_fire & arrived & (eff_now >= deadline)
                if p == last:
                    out, out_n, overflow = self._write_emits(
                        out, out_n, overflow, fire, tok, deadline
                    )
                    tok = self._consume(tok, fire, slot)
                    if slot.persistent:
                        # surviving every-generator re-arms fresh, window
                        # restarting at the deadline — NOT the row's raw
                        # timestamp: a late row firing through the eff_now
                        # rescue (ts < deadline <= timer_seen) would re-arm
                        # the generator in the past, leaving its next
                        # deadline already expired so every subsequent row
                        # re-fires it (the resurrected-deadline hazard)
                        tok = self._clear_slot_caps(
                            tok, fire, slot, ts=deadline
                        )
                elif slot.persistent:
                    # `every` generator: fork the completion downstream and
                    # keep the generator armed with a fresh window
                    tok, overflow, _dest = self._fork(
                        tok, tok, fire, p + 1, deadline, overflow
                    )
                    tok = self._clear_slot_caps(tok, fire, slot, ts=deadline)
                else:
                    tok = self._advance_rows(tok, fire, slot, deadline)
                touched = touched | fire

        # ---- event matching, descending slot order so one event moves a
        # token at most one hop (reference: next-event semantics)
        for slot in reversed(self.slots):
            p = slot.index
            # touched accumulates per SLOT: both sides of a logical element
            # may consume the same event (reference: LogicalPatternTestCase
            # testQuery5 — one event satisfies both sides of an `and`)
            slot_touch = jnp.zeros((self.T,), dtype=jnp.bool_)
            for atom in slot.atoms:
                if atom.stream_id not in stream_cols:
                    continue
                ev = stream_cols[atom.stream_id]
                elig = self._eligible(tok, p) & ~touched & is_cur
                if slot.is_count and atom.cap:
                    mx = slot.max_count
                    if mx > 0:
                        # cannot absorb beyond max (only tokens AT p absorb)
                        n_here = tok["caps"][atom.ref_idx]["n"]
                        elig = elig & ~((tok["slot"] == p) & (n_here >= mx))
                env = self._token_env(
                    tok, None, override_ref=atom.ref_idx,
                    event_cols=ev, event_ts=ts,
                )
                match = elig
                for c in self._conds[(p, atom.ref_idx)]:
                    match = match & c(env)
                if atom.absent:
                    both_absent = slot.logical is not None and all(
                        a2.absent for a2 in slot.atoms
                    )
                    if atom.waiting_ms is not None and (
                        slot.logical is LogicalType.OR or both_absent
                    ):
                        # `A or not B for t` / `not A for t1 and not B for
                        # t2`: an arrival inside the window must not kill the
                        # token (the other side may still satisfy the element,
                        # and an `every` generator must survive) — record it
                        # as a capture marker so the TIMER path knows this
                        # absent side can never fire
                        # (reference: AbsentLogicalPreStateProcessor —
                        # the partner processor keeps waiting)
                        mark = match & (
                            ts <= tok["entry_ts"] + atom.waiting_ms
                        )
                        if p == 0 and both_absent:
                            # start-of-pattern both-absent: an arrival
                            # re-arms THAT SIDE's window from the arrival
                            # (reference: the initial state always re-waits;
                            # LogicalAbsentPatternTestCase 46, 34/35) — track
                            # the latest arrival in the marker's ts lane
                            c = dict(tok["caps"][atom.ref_idx])
                            c["n"] = jnp.where(mark, 1, c["n"]).astype(
                                c["n"].dtype
                            )
                            c["ts"] = c["ts"].at[:, 0].set(
                                jnp.where(
                                    mark,
                                    jnp.maximum(c["ts"][:, 0], ts),
                                    c["ts"][:, 0],
                                )
                            )
                            new_caps = list(tok["caps"])
                            new_caps[atom.ref_idx] = c
                            tok = {**tok, "caps": new_caps}
                        else:
                            new_caps = list(tok["caps"])
                            new_caps[atom.ref_idx] = self._capture(
                                tok["caps"][atom.ref_idx], atom, mark, ts, ev
                            )
                            tok = {**tok, "caps": new_caps}
                        slot_touch = slot_touch | mark
                        continue
                    # arrival on an absent stream kills the token
                    # (reference: AbsentStreamPreStateProcessor.process kill);
                    # with a waiting time, only arrivals INSIDE the window
                    if atom.waiting_ms is not None:
                        match = match & (
                            ts <= tok["entry_ts"] + atom.waiting_ms
                        )
                    if p == 0 and atom.waiting_ms is not None:
                        # start-of-pattern absent: the initial/generator
                        # token RE-ARMS instead of dying — the reference's
                        # init state always re-waits from the violating
                        # arrival, captures cleared
                        # (LogicalAbsentPatternTestCase testQueryAbsent10)
                        rearm = match & (tok["start_ts"] < 0)
                        kill = match & ~rearm
                        tok = {**tok, "active": tok["active"] & ~kill}
                        tok = self._clear_slot_caps(tok, rearm, slot, ts=ts)
                    else:
                        tok = {**tok, "active": tok["active"] & ~match}
                    slot_touch = slot_touch | match
                    continue

                # capture the event into the atom's ref
                new_caps = list(tok["caps"])
                new_caps[atom.ref_idx] = self._capture(
                    tok["caps"][atom.ref_idx], atom, match, ts, ev
                )
                adv_tok = {
                    **tok,
                    "caps": new_caps,
                    "slot": jnp.where(match, p, tok["slot"]),
                    "start_ts": jnp.where(
                        match & (tok["start_ts"] < 0), ts, tok["start_ts"]
                    ),
                    # a virgin that captures its first event in place is
                    # armed by it (forks take their number in _alloc_lanes)
                    "seq": jnp.where(
                        match & (tok["start_ts"] < 0),
                        tok["next_seq"], tok["seq"],
                    ),
                    "next_seq": tok["next_seq"] + 1,
                }

                if slot.logical is not None:
                    arrived = [
                        new_caps[a2.ref_idx]["n"] > 0
                        for a2 in slot.atoms
                        if not a2.absent
                    ]
                    if slot.logical is LogicalType.OR:
                        complete = match
                    else:
                        allv = arrived[0]
                        for v in arrived[1:]:
                            allv = allv & v
                        complete = match & allv
                        wait_ab = next(
                            (
                                a for a in slot.atoms
                                if a.absent and a.waiting_ms is not None
                            ),
                            None,
                        )
                        if wait_ab is not None:
                            # completion defers to the absent deadline; an
                            # early present arrival stays captured and the
                            # TIMER path completes it
                            complete = complete & (
                                eff_now
                                >= tok["entry_ts"] + wait_ab.waiting_ms
                            )
                    advance = complete
                elif slot.is_count:
                    # absorb in place; a trailing count emits (and dies) at
                    # exactly min occurrences (reference:
                    # CountPostStateProcessor.process -> processMinCountReached
                    # when streamEvents == minCount, stateChanged consumes)
                    n_after = new_caps[atom.ref_idx]["n"]
                    if slot.min_count >= 1:
                        count_armed = match & (n_after == slot.min_count)
                    else:
                        count_armed = jnp.zeros_like(match)
                    if p == last and slot.min_count >= 1:
                        advance = count_armed
                    else:
                        advance = jnp.zeros_like(match)
                else:
                    advance = match

                stay = match & ~advance
                blk = next(
                    (b for b in self.every_blocks if b[1] == p), None
                )
                if p == last:
                    out, out_n, overflow = self._write_emits(
                        out, out_n, overflow, advance, adv_tok, ts
                    )
                    new_tok = self._merge(tok, adv_tok, stay)
                    new_tok = self._consume(
                        new_tok, advance, slot, force=slot.is_count
                    )
                    tok = new_tok
                    if blk is not None:
                        tok, overflow, rearmed = self._rearm_block(
                            tok, adv_tok, advance, blk, ts, overflow
                        )
                        touched = touched | rearmed
                elif slot.persistent and not slot.is_count:
                    # fork: advanced copy goes to a free row; the source
                    # (virgin/generator) stays armed
                    tok, overflow, dest_mask = self._fork(
                        tok, adv_tok, advance, p + 1, ts, overflow
                    )
                    tok = self._merge(tok, adv_tok, stay)
                    touched = touched | dest_mask
                    tok, out, out_n, overflow = self._arrival_effects(
                        tok, dest_mask, p + 1, ts, out, out_n, overflow
                    )
                else:
                    moved = self._merge(tok, adv_tok, match)
                    moved = {
                        **moved,
                        "slot": jnp.where(advance, p + 1, moved["slot"]),
                        "entry_ts": jnp.where(advance, ts, moved["entry_ts"]),
                    }
                    tok = moved
                    tok, out, out_n, overflow = self._arrival_effects(
                        tok, advance, p + 1, ts, out, out_n, overflow
                    )
                    if blk is not None:
                        tok, overflow, rearmed = self._rearm_block(
                            tok, tok, advance, blk, ts, overflow
                        )
                        touched = touched | rearmed
                slot_touch = slot_touch | match

                if slot.persistent and slot.logical is not None:
                    # the surviving generator re-arms FRESH: a completed
                    # logical pair's partial captures clear and its absence
                    # window restarts (reference: the every re-arm is a clean
                    # addEveryState virgin — LogicalPatternTestCase
                    # testQuery15/19)
                    tok = self._clear_slot_caps(tok, advance, slot, ts=ts)

                if (
                    slot.persistent and slot.is_count
                    and slot.min_count >= 1 and not self.sequence
                ):
                    # (sequences never call processMinCountReached — the token
                    # is shared via the SEQUENCE re-add branch instead)
                    # `every` over a count: a fresh virgin is armed exactly
                    # when a token's occurrence count reaches min (reference:
                    # CountPostStateProcessor.processMinCountReached ->
                    # nextEveryStatePreProcessor.addEveryState; the reference's
                    # shallow clone is replaced by a clean virgin — PARITY.md)
                    tok, overflow = self._arm_virgins(
                        tok, count_armed, p, ts, overflow
                    )
            touched = touched | slot_touch

        # ---- sequence strictness: any unconsumed CURRENT event kills
        # non-virgin, non-generator tokens (reference: sequence
        # StreamPreStateProcessor resetState on mismatch)
        if self.sequence:
            # (non-virgin tokens at persistent slots are NOT exempt: the
            # reference drops a full count tail that fails to re-add —
            # SequenceTestCase testQuery6)
            virgin = tok["start_ts"] < 0
            kill = is_cur & tok["active"] & ~touched & ~virgin
            tok = {**tok, "active": tok["active"] & ~kill}

        if self._use_fwd:
            # end-of-event forwarding: each count slot's absorbers with min
            # satisfied contend for the ONE pending spot at the next slot;
            # the oldest chain wins (reference: SEQUENCE addState drops all
            # but the first add per event). Min-0 virgins keep their
            # arm-time forward.
            T = self.T
            lanes64 = jnp.arange(T, dtype=jnp.int64)
            new_fwd = tok["fwd"] & tok["active"] & (tok["start_ts"] < 0)
            for q, cslot in enumerate(self.slots):
                if not cslot.is_count:
                    continue
                n_q = tok["caps"][cslot.atoms[0].ref_idx]["n"]
                cand = (
                    tok["active"] & (tok["slot"] == q) & touched
                    & (n_q >= max(cslot.min_count, 0))
                    & (tok["start_ts"] >= 0)
                )
                key = jnp.where(
                    cand, tok["start_ts"] * T + lanes64, np.int64(1) << 62
                )
                winner = cand & (jnp.arange(T) == jnp.argmin(key))
                new_fwd = new_fwd | winner
            # padding/timer rows are no-ops, not forward contests
            tok = {**tok, "fwd": jnp.where(is_cur, new_fwd, tok["fwd"])}

        return tok, out, out_n, overflow

    # ---- token-table update helpers --------------------------------------

    @staticmethod
    def _merge(old, new, mask):
        """Per-row select between two token tables."""

        def sel(a, b):
            if a.ndim == 1:
                return jnp.where(mask, b, a)
            return jnp.where(mask[:, None], b, a)

        caps = [
            {
                "n": sel(o["n"], n_["n"]),
                "ts": sel(o["ts"], n_["ts"]),
                "cols": {k: sel(o["cols"][k], n_["cols"][k]) for k in o["cols"]},
            }
            for o, n_ in zip(old["caps"], new["caps"])
        ]
        merged = {
            # the scalars only ever count up: either side may be the newer
            **{
                k: jnp.maximum(old[k], new[k])
                for k in ("next_seq", "head", "armed", "expired", "max_row",
                          "completed", "refused")
            },
            "active": sel(old["active"], new["active"]),
            "slot": sel(old["slot"], new["slot"]),
            "start_ts": sel(old["start_ts"], new["start_ts"]),
            "entry_ts": sel(old["entry_ts"], new["entry_ts"]),
            "seq": sel(old["seq"], new["seq"]),
            "caps": caps,
        }
        if "fwd" in old:
            merged["fwd"] = sel(old["fwd"], new["fwd"])
        return merged

    def _consume(self, tok, mask, slot: Slot, force: bool = False):
        """Tokens that emitted: die, unless at a persistent slot (the `every`
        generator stays armed). Trailing count slots force-consume: their
        re-arm is the virgin forked at min, not the emitting token."""
        if slot.persistent and not force:
            return tok
        return {**tok, "active": tok["active"] & ~mask}

    def _arrival_effects(self, tok, arrived, q: int, ts, out, out_n, overflow):
        """Effects of tokens arriving AT slot q: a trailing min-0 count emits
        immediately with empty captures and is consumed (reference:
        CountPreStateProcessor.addState minCount==0 ->
        processMinCountReached at add time)."""
        if q >= len(self.slots):
            return tok, out, out_n, overflow
        nxt = self.slots[q]
        if not (nxt.is_count and nxt.min_count == 0 and q == len(self.slots) - 1):
            return tok, out, out_n, overflow
        out, out_n, overflow = self._write_emits(
            out, out_n, overflow, arrived, tok, ts
        )
        return (
            {**tok, "active": tok["active"] & ~arrived},
            out, out_n, overflow,
        )

    def _clear_slot_caps(self, tok, mask, slot: Slot, ts=None):
        """Reset a slot's atom captures on `mask` rows (the re-arming
        generator of a persistent logical slot becomes virgin again). `ts`
        restarts the slot clock — a fresh absence window measures from the
        re-arm, not the original arm."""
        caps = list(tok["caps"])
        for a in slot.atoms:
            c = caps[a.ref_idx]
            schema = self.schemas[a.stream_id]
            caps[a.ref_idx] = {
                "n": jnp.where(mask, 0, c["n"]),
                "ts": jnp.where(mask[:, None], np.int64(0), c["ts"]),
                "cols": {
                    name: jnp.where(
                        mask[:, None],
                        np.asarray(
                            null_value(schema.attr_types[name]), arr.dtype
                        ),
                        arr,
                    )
                    for name, arr in c["cols"].items()
                },
            }
        out = {**tok, "caps": caps}
        if ts is not None:
            out["entry_ts"] = jnp.where(mask, ts, out["entry_ts"])
        if slot.index == 0:
            out["start_ts"] = jnp.where(
                mask, np.int64(-1), out["start_ts"]
            )
        return out

    def _rearm_block(self, tok, src_tok, mask, block, ts, overflow):
        """Fork re-armed copies at a completed every block's first slot:
        captures of slots OUTSIDE the block are retained, block captures are
        cleared (reference: addEveryState clones the completing StateEvent
        back into the block's first pre-state; block recaptures overwrite).
        Matches are strictly serial — EveryPatternTestCase testQuery5/7."""
        first, last = block
        T = self.T
        dest, overflow, took = self._alloc_lanes(tok, mask, overflow)
        block_refs = {
            a.ref_idx for s in self.slots[first:last + 1] for a in s.atoms
        }
        caps = []
        for a in self.refs:
            c = tok["caps"][a.ref_idx]
            if a.ref_idx in block_refs:
                schema = self.schemas[a.stream_id]
                cols = {
                    name: arr.at[dest].set(
                        np.asarray(
                            null_value(schema.attr_types[name]), arr.dtype
                        ),
                        mode="drop",
                    )
                    for name, arr in c["cols"].items()
                }
                caps.append(
                    {
                        "n": c["n"].at[dest].set(0, mode="drop"),
                        "ts": c["ts"].at[dest].set(np.int64(0), mode="drop"),
                        "cols": cols,
                    }
                )
            else:
                s = src_tok["caps"][a.ref_idx]
                caps.append(
                    {
                        "n": c["n"].at[dest].set(s["n"], mode="drop"),
                        "ts": c["ts"].at[dest].set(s["ts"], mode="drop"),
                        "cols": {
                            name: arr.at[dest].set(s["cols"][name], mode="drop")
                            for name, arr in c["cols"].items()
                        },
                    }
                )
        # a re-armed whole-pattern block is virgin again; a mid-pattern block
        # keeps the match start (within measures from the first capture)
        start = (
            src_tok["start_ts"]
            if first > 0
            else jnp.full((T,), -1, jnp.int64)
        )
        dest_mask = jnp.zeros((T,), jnp.bool_).at[dest].set(True, mode="drop")
        res = {
            **self._armed(tok, dest, took),
            "active": tok["active"].at[dest].set(True, mode="drop"),
            "slot": tok["slot"].at[dest].set(first, mode="drop"),
            "start_ts": tok["start_ts"].at[dest].set(start, mode="drop"),
            "entry_ts": tok["entry_ts"].at[dest].set(
                jnp.broadcast_to(ts, (T,)).astype(jnp.int64), mode="drop"
            ),
            "caps": caps,
        }
        if "fwd" in tok:
            res["fwd"] = tok["fwd"].at[dest].set(False, mode="drop")
        return res, overflow, dest_mask

    def _arm_virgins(self, tok, mask, p: int, ts, overflow):
        """Scatter fresh virgin tokens (slot p, no captures) into free rows."""
        T = self.T
        dest, overflow, took = self._alloc_lanes(tok, mask, overflow)
        caps = []
        for a in self.refs:
            c = tok["caps"][a.ref_idx]
            schema = self.schemas[a.stream_id]
            cols = {
                name: arr.at[dest].set(
                    np.asarray(null_value(schema.attr_types[name]), arr.dtype),
                    mode="drop",
                )
                for name, arr in c["cols"].items()
            }
            caps.append(
                {
                    "n": c["n"].at[dest].set(0, mode="drop"),
                    "ts": c["ts"].at[dest].set(np.int64(0), mode="drop"),
                    "cols": cols,
                }
            )
        res = {
            **self._armed(tok, dest, took),
            "active": tok["active"].at[dest].set(True, mode="drop"),
            "slot": tok["slot"].at[dest].set(p, mode="drop"),
            "start_ts": tok["start_ts"].at[dest].set(np.int64(-1), mode="drop"),
            "entry_ts": tok["entry_ts"].at[dest].set(
                jnp.broadcast_to(ts, (T,)).astype(jnp.int64), mode="drop"
            ),
            "caps": caps,
        }
        if "fwd" in tok:
            fwd0 = self.slots[p].is_count and self.slots[p].min_count == 0
            res["fwd"] = tok["fwd"].at[dest].set(fwd0, mode="drop")
        return res, overflow

    def _advance_rows(self, tok, mask, slot: Slot, ts):
        p = slot.index
        return {
            **tok,
            "slot": jnp.where(mask, p + 1, tok["slot"]),
            "entry_ts": jnp.where(mask, ts, tok["entry_ts"]),
        }

    def _alloc_lanes(self, tok, mask, overflow):
        """Allocate one free token lane per set row of `mask`; rows that don't
        fit scatter to index T (dropped by mode='drop') and raise overflow."""
        T = self.T
        free = ~tok["active"]
        order = jnp.argsort(~free)  # free row indices first (stable)
        nfree = jnp.sum(free)
        rank = jnp.cumsum(mask.astype(jnp.int32)) - 1
        ok = mask & (rank < nfree)
        dest = jnp.where(ok, order[jnp.clip(rank, 0, T - 1)], T)
        refused = (mask & ~ok).sum(dtype=jnp.int32)
        return dest, overflow + refused, ok.sum(dtype=jnp.int64)

    @staticmethod
    def _armed(tok, dest, took):
        """The table's scalars and `seq` lane once `took` tokens have been
        armed into the lanes `dest` (row r's into dest[r], T where it got
        none): each takes the next arming number, in row order."""
        T = tok["seq"].shape[0]
        rank = jnp.cumsum((dest < T).astype(jnp.int64)) - 1
        return {
            **tok,
            "seq": _set_at(tok["seq"], dest, tok["next_seq"] + rank),
            "next_seq": tok["next_seq"] + took,
            "armed": tok["armed"] + took,
        }

    def _fork(self, tok, adv_tok, mask, next_slot: int, ts, overflow):
        """Scatter advanced copies of `mask` rows into free rows
        (reference: every re-arm keeps the pre-state armed while the matched
        StateEvent moves on)."""
        T = self.T
        dest, overflow, took = self._alloc_lanes(tok, mask, overflow)

        def scat(lane, adv_lane, fill=None):
            return lane.at[dest].set(adv_lane, mode="drop")

        caps = [
            {
                "n": scat(o["n"], a["n"]),
                "ts": scat(o["ts"], a["ts"]),
                "cols": {k: scat(o["cols"][k], a["cols"][k]) for k in o["cols"]},
            }
            for o, a in zip(tok["caps"], adv_tok["caps"])
        ]
        dest_mask = jnp.zeros((T,), dtype=jnp.bool_).at[dest].set(True, mode="drop")
        res = {
            **self._armed(tok, dest, took),
            "active": tok["active"].at[dest].set(True, mode="drop"),
            "slot": tok["slot"].at[dest].set(
                jnp.full((T,), next_slot, dtype=jnp.int32), mode="drop"
            ),
            "start_ts": scat(tok["start_ts"], adv_tok["start_ts"]),
            "entry_ts": tok["entry_ts"].at[dest].set(
                jnp.broadcast_to(ts, (T,)), mode="drop"
            ),
            "caps": caps,
        }
        if "fwd" in tok:
            res["fwd"] = tok["fwd"].at[dest].set(False, mode="drop")
        return res, overflow, dest_mask

    # ---- emission --------------------------------------------------------

    def out_capacity(self, batch_capacity: int) -> int:
        return max(batch_capacity, 64)

    # ---- vectorized batch fast path --------------------------------------
    #
    # Simple chains (single-atom slots, no counts/absent/logical, `every`
    # only as the arming slot) admit a fully vectorized batch kernel
    # (`apply_batch_fast`, further down): per NFA state every token advances
    # to its FIRST matching row, found among the rows of its own key where
    # the state's condition ties the row to a capture by equality
    # (`_match_keyed`) and in a dense [T, B] match matrix where it does not
    # (`_match_matrix`). One device program per batch instead of a B-step
    # scan.

    @property
    def fast_path_ok(self) -> bool:
        if self.every_blocks:
            return False
        for i, s in enumerate(self.slots):
            if len(s.atoms) != 1 or s.is_count or s.is_absent or s.logical:
                return False
            if s.persistent and i != 0:
                return False
            if s.atoms[0].cap != 1:
                return False
        if self.sequence and len({a.stream_id for a in self.refs}) > 1:
            # multi-stream sequence strictness (an unconsumed event of ANY
            # participating stream kills waiting tokens) needs the scan path
            return False
        return True

    @property
    def count_fast_ok(self) -> bool:
        """Closed-form count kernel applies to: PATTERN type, slot 0 a count
        state (min >= 1, optionally `every`), simple single-atom tail slots,
        no within bounds, and row-only conditions for slots 0 and 1 (slot-1
        matching is folded into slot-0's closed form, so neither may read the
        token table). The key insight making this O(1) device passes instead
        of a per-event scan: all absorbing tokens absorb every matching event
        (reference: CountPreStateProcessor.processAndReturn iterates every
        pending state), so capture sets are pure rank arithmetic over the
        batch's match sequence."""
        if self.sequence or len(self.slots) < 2 or self.within_ms is not None:
            return False
        if self.every_blocks:
            return False
        s0 = self.slots[0]
        if not s0.is_count or s0.min_count < 1 or s0.is_absent or s0.logical:
            return False
        for s in self.slots:
            if s.within_ms is not None:
                return False
        for s in self.slots[1:]:
            if (
                len(s.atoms) != 1 or s.is_count or s.is_absent
                or s.logical or s.persistent or s.atoms[0].cap != 1
            ):
                return False
        for p in (0, 1):
            ref = self.slots[p].atoms[0].ref
            keys = self._cond_keys[(p, self.slots[p].atoms[0].ref_idx)]
            if any(k[0] != ref or k[1] is not None for k in keys):
                return False
        return True

    def _row_env(self, ev: dict, batch_ts, now, atom: Atom) -> Env:
        """[B]-shaped env exposing only the current event as the atom's ref."""
        cols = {(atom.ref, None, name): v for name, v in ev.items()}
        cols[(atom.ref, None, TS_ATTR)] = batch_ts
        cols[(atom.ref, None, "__arrived__")] = jnp.ones(
            batch_ts.shape, dtype=jnp.bool_
        )
        return Env(cols, now=now)

    def apply_batch_count(
        self, tok, batch_ts, batch_kind, batch_valid, stream_cols: dict,
        out, out_n, overflow, now,
    ):
        """Whole-batch count-pattern kernel (see count_fast_ok).

        Per chunk: enumerate slot-0's condition matches as a rank sequence
        (midx), derive every token's absorption span and slot-1 advance row in
        closed form, materialize the `every` generation chain armed at each
        min-count crossing, then run the remaining simple slots with the
        ordinary [T, B] token-matrix passes.
        """
        T = self.T
        B = batch_ts.shape[0]
        S = len(self.slots)
        slot0, slot1 = self.slots[0], self.slots[1]
        atom0, atom1 = slot0.atoms[0], slot1.atoms[0]
        _keep_cols, _ts_used = self.capture_keep()
        K = atom0.cap
        m = slot0.min_count
        # occurrence COUNTING runs to the true max (unbounded -> huge), while
        # capture WRITES stop at the capture capacity K — matching the scan
        # path, whose n keeps counting as writes drop (module docstring)
        M = slot0.max_count if slot0.max_count > 0 else (1 << 30)

        rows = jnp.arange(B, dtype=jnp.int32)
        toks = jnp.arange(T, dtype=jnp.int32)
        qpos = jnp.arange(K, dtype=jnp.int32)
        v = batch_valid & (batch_kind == KIND_CURRENT)
        at0 = tok["active"] & (tok["slot"] == 0)
        n0 = tok["caps"][atom0.ref_idx]["n"]

        # ---- slot-0 match sequence over the batch ----
        ev0 = stream_cols.get(atom0.stream_id)
        if ev0 is not None:
            env0 = self._row_env(ev0, batch_ts, now, atom0)
            Mc = v
            for c in self._conds[(0, atom0.ref_idx)]:
                Mc = Mc & jnp.broadcast_to(c(env0), (B,))
        else:
            Mc = jnp.zeros((B,), dtype=jnp.bool_)
        midx_excl = jnp.cumsum(Mc.astype(jnp.int32)) - Mc.astype(jnp.int32)
        k_total = midx_excl[-1] + Mc[-1].astype(jnp.int32)
        mrow = first_indices(Mc, B, fill=B)
        mrow_c = jnp.clip(mrow, 0, B - 1)
        mts = batch_ts[mrow_c]

        # ---- slot-1 advance row per row (row-only by gate) ----
        ev1 = stream_cols.get(atom1.stream_id)
        if ev1 is not None:
            env1 = self._row_env(ev1, batch_ts, now, atom1)
            Madv = v
            for c in self._conds[(1, atom1.ref_idx)]:
                Madv = Madv & jnp.broadcast_to(c(env1), (B,))
        else:
            Madv = jnp.zeros((B,), dtype=jnp.bool_)

        # token t advances at the first row b with Madv[b] and enter-count
        # n0 + min(midx_excl[b], room) >= m — equivalently midx_excl[b] >=
        # m - n0, since room = M - n0 with M >= m never blocks reaching m
        # (midx_excl: the reference forwards at min via newAndEvery, pending
        # only from the NEXT event, and checks the next state first — so the
        # row that reaches min is itself not advance-eligible).
        # midx_excl is NON-DECREASING, so "first b with Madv[b] and
        # midx_excl[b] >= v" factors into two [B]/[T] primitives: a suffix-min
        # scan (madv_next[b] = first advance row at or after b) and a
        # searchsorted for the threshold crossing. This replaces the r4 dense
        # [T, B] pred compare + argmax, whose HLO materialized ~750 MB of
        # [T, B] s32/u32/pred per chunk (the whole kernel's wall — 7.7 ms vs
        # ~0.6 ms of everything else). method='sort' keeps searchsorted
        # vectorized (one bitonic sort of T+B keys); the default 'scan'
        # serializes into scalar-space gathers.
        room = (M - jnp.clip(n0, 0, M)).astype(jnp.int32)
        thresh = (m - jnp.clip(n0, 0, m)).astype(midx_excl.dtype)
        madv_next = lax.cummin(
            jnp.where(Madv, rows, B).astype(jnp.int32), reverse=True
        )
        b0_t = jnp.searchsorted(
            midx_excl, thresh, side="left", method="sort"
        ).astype(jnp.int32)
        jt = jnp.where(b0_t < B, madv_next[jnp.clip(b0_t, 0, B - 1)], B)
        has_adv = at0 & (jt < B)
        j = jt.astype(jnp.int32)
        jc = jnp.clip(j, 0, B - 1)

        # absorption span: stops at the advance row (reference:
        # removeIfNextStateProcessed drops the token from the count pending
        # once the next state captured)
        A = jnp.clip(jnp.where(has_adv, midx_excl[jc], k_total), 0, room)
        A = jnp.where(at0, A, 0)

        # ---- capture writes for existing slot-0 tokens ----
        caps = [dict(c) for c in tok["caps"]]
        src = qpos[None, :] - n0[:, None]
        wmask = at0[:, None] & (src >= 0) & (src < A[:, None])
        srcc = jnp.clip(src, 0, B - 1)
        cr = dict(caps[atom0.ref_idx])
        cr["n"] = jnp.where(at0, n0 + A, n0).astype(cr["n"].dtype)
        if _ts_used[atom0.ref_idx]:
            cr["ts"] = jnp.where(wmask, mts[srcc], cr["ts"])
        if ev0 is not None:
            cr["cols"] = {
                name: jnp.where(wmask, ev0[name][mrow_c].astype(arr.dtype)[srcc], arr)
                for name, arr in cr["cols"].items()
            }
        caps[atom0.ref_idx] = cr
        start_ts = jnp.where(
            at0 & (tok["start_ts"] < 0) & (A > 0), mts[0], tok["start_ts"]
        )

        # ---- slot-1 capture + transition for advancing tokens ----
        advD = at0 & has_adv
        if ev1 is not None:
            c1 = dict(caps[atom1.ref_idx])
            c1["n"] = jnp.where(advD, 1, c1["n"]).astype(c1["n"].dtype)
            # column-0 writes via static slice update, not arange scatter
            if _ts_used[atom1.ref_idx]:
                c1["ts"] = c1["ts"].at[:, 0].set(
                    jnp.where(advD, batch_ts[jc], c1["ts"][:, 0])
                )
            c1["cols"] = {
                name: arr.at[:, 0].set(
                    jnp.where(advD, ev1[name][jc].astype(arr.dtype), arr[:, 0])
                )
                for name, arr in c1["cols"].items()
            }
            caps[atom1.ref_idx] = c1
        entry_row = jnp.where(advD, j, -1)
        tok = {
            **tok,
            "active": tok["active"],
            "slot": jnp.where(advD, 2, tok["slot"]),
            "start_ts": start_ts,
            "entry_ts": jnp.where(advD, batch_ts[jc], tok["entry_ts"]),
            "caps": caps,
        }

        # ---- `every` generation chain (armed at each min crossing) ----
        if slot0.persistent:
            tail = at0 & (n0 < m)
            tail_exists = tail.any()
            ny = jnp.min(jnp.where(tail, n0, m)).astype(jnp.int32)
            # generations beyond the token-lane count T can never be armed
            # (they overflow either way), so the generation axis is capped at
            # T — [G]-shaped gathers/scatters cost ~1 element/cycle on the
            # TPU scalar core, and modeling unarmable generations is pure
            # waste; the cap's dropped generations raise the same overflow
            # flag lane exhaustion would have
            Gmax = min(B // max(m, 1) + 1, T)
            g = jnp.arange(Gmax, dtype=jnp.int32)
            s_g = (m - ny) + g * m
            valid_g = tail_exists & (s_g <= k_total)
            overflow = overflow + (
                tail_exists & ((m - ny) + Gmax * m <= k_total)
            ).astype(jnp.int32)
            # generation g advances at the first row b with Madv[b] and
            # midx_excl[b] >= s_g + m (room never blocks, see above). Same
            # suffix-min + sorted-searchsorted factoring as the per-token
            # advance: s_g is increasing and midx_excl non-decreasing, so
            # this is a sorted-sorted merge — no [G, B] matrix.
            b0_g = jnp.searchsorted(
                midx_excl, (s_g + m).astype(midx_excl.dtype),
                side="left", method="sort",
            ).astype(jnp.int32)
            jg_row = jnp.where(
                b0_g < B, madv_next[jnp.clip(b0_g, 0, B - 1)], B
            )
            has_advg = valid_g & (jg_row < B)
            jg = jg_row.astype(jnp.int32)
            jgc = jnp.clip(jg, 0, B - 1)
            Ag = jnp.clip(
                jnp.where(has_advg, midx_excl[jgc], k_total) - s_g, 0, M
            )
            Ag = jnp.where(valid_g, Ag, 0)

            # scatter generations into free lanes
            free = ~tok["active"]
            nfree = jnp.sum(free)
            free_idx = first_indices(free, Gmax)
            grank = (jnp.cumsum(valid_g.astype(jnp.int32)) - 1).astype(jnp.int32)
            okg = valid_g & (grank < nfree) & (free_idx[jnp.clip(grank, 0, Gmax - 1)] >= 0)
            overflow = overflow + (valid_g & ~okg).sum(dtype=jnp.int32)
            dst = jnp.where(okg, free_idx[jnp.clip(grank, 0, Gmax - 1)], T)

            src_g = s_g[:, None] + qpos[None, :]
            wm_g = (qpos[None, :] < Ag[:, None])
            src_gc = jnp.clip(src_g, 0, B - 1)
            caps = [dict(c) for c in tok["caps"]]
            cr = dict(caps[atom0.ref_idx])
            cr["n"] = cr["n"].at[dst].set(Ag, mode="drop")
            if _ts_used[atom0.ref_idx]:
                cr["ts"] = _set_at(
                    cr["ts"], dst, jnp.where(wm_g, mts[src_gc], np.int64(0))
                )
            if ev0 is not None:
                new_cols = {}
                for name, arr in cr["cols"].items():
                    t = self.schemas[atom0.stream_id].attr_types[name]
                    nv = np.asarray(null_value(t), dtype=arr.dtype)
                    genv = jnp.where(wm_g, ev0[name][mrow_c][src_gc].astype(arr.dtype), nv)
                    new_cols[name] = arr.at[dst].set(genv, mode="drop")
                cr["cols"] = new_cols
            caps[atom0.ref_idx] = cr
            if ev1 is not None:
                c1 = dict(caps[atom1.ref_idx])
                c1["n"] = c1["n"].at[dst].set(
                    has_advg.astype(c1["n"].dtype), mode="drop"
                )
                if _ts_used[atom1.ref_idx]:
                    c1["ts"] = c1["ts"].at[:, 0].set(
                        _set_at(
                            c1["ts"][:, 0], dst,
                            jnp.where(has_advg, batch_ts[jgc], np.int64(0)),
                        )
                    )
                new_cols = {}
                for name, arr in c1["cols"].items():
                    t = self.schemas[atom1.stream_id].attr_types[name]
                    nv = np.asarray(null_value(t), dtype=arr.dtype)
                    gv = jnp.where(has_advg, ev1[name][jgc].astype(arr.dtype), nv)
                    new_cols[name] = arr.at[:, 0].set(_set_at(arr[:, 0], dst, gv))
                c1["cols"] = new_cols
                caps[atom1.ref_idx] = c1
            # untouched refs: clear stale lane contents
            written = {atom0.ref_idx} | (
                {atom1.ref_idx} if ev1 is not None else set()
            )
            for ridx, a in enumerate(self.refs):
                if ridx in written:
                    continue
                c = dict(caps[ridx])
                c["n"] = c["n"].at[dst].set(0, mode="drop")
                if _ts_used[ridx]:
                    c["ts"] = _set_at(
                        c["ts"], dst,
                        jnp.zeros(dst.shape + c["ts"].shape[1:], c["ts"].dtype),
                    )
                c["cols"] = {
                    name: _set_at(
                        arr, dst,
                        jnp.full(
                            dst.shape + arr.shape[1:],
                            np.asarray(
                                null_value(self.schemas[a.stream_id].attr_types[name]),
                                arr.dtype,
                            ),
                            arr.dtype,
                        ),
                    )
                    for name, arr in c["cols"].items()
                }
                caps[ridx] = c
            g_start = jnp.where(Ag > 0, mts[jnp.clip(s_g, 0, B - 1)], np.int64(-1))
            tok = {
                # a generation is armed by the match that opens it: in order
                **self._armed(tok, dst, okg.sum(dtype=jnp.int64)),
                "active": tok["active"].at[dst].set(True, mode="drop"),
                "slot": tok["slot"].at[dst].set(
                    jnp.where(has_advg, 2, 0), mode="drop"
                ),
                "start_ts": _set_at(tok["start_ts"], dst, g_start),
                "entry_ts": _set_at(
                    tok["entry_ts"], dst, mts[jnp.clip(s_g - 1, 0, B - 1)]
                ),
                "caps": caps,
            }
            entry_row = entry_row.at[dst].set(
                jnp.where(has_advg, jg, -1), mode="drop"
            )

        # ---- remaining simple slots (ordinary token-matrix passes) ----
        for p in range(2, S):
            slot = self.slots[p]
            atom = slot.atoms[0]
            if atom.stream_id not in stream_cols:
                continue
            ev = stream_cols[atom.stream_id]
            elig = tok["active"] & (tok["slot"] == p)
            env = self._matrix_env(tok, ev, batch_ts, now, atom.ref_idx)
            cond = jnp.ones((T, B), dtype=jnp.bool_)
            for c in self._conds[(p, atom.ref_idx)]:
                cond = cond & jnp.broadcast_to(c(env), (T, B))
            Mm = elig[:, None] & v[None, :] & (rows[None, :] > entry_row[:, None]) & cond
            has = Mm.any(axis=1)
            jj = jnp.argmax(Mm, axis=1).astype(jnp.int32)
            jjc = jnp.clip(jj, 0, B - 1)
            caps = [dict(c) for c in tok["caps"]]
            crp = dict(caps[atom.ref_idx])
            crp["n"] = jnp.where(has, 1, crp["n"]).astype(crp["n"].dtype)
            if _ts_used[atom.ref_idx]:
                crp["ts"] = crp["ts"].at[:, 0].set(
                    jnp.where(has, batch_ts[jjc], crp["ts"][:, 0])
                )
            crp["cols"] = {
                name: arr.at[:, 0].set(
                    jnp.where(has, ev[name][jjc].astype(arr.dtype), arr[:, 0])
                )
                for name, arr in crp["cols"].items()
            }
            caps[atom.ref_idx] = crp
            tok = {
                **tok,
                "slot": jnp.where(has, p + 1, tok["slot"]),
                "entry_ts": jnp.where(has, batch_ts[jjc], tok["entry_ts"]),
                "caps": caps,
            }
            entry_row = jnp.where(has, jj, entry_row)

        # ---- completions, ordered by completion row, those of one row by
        # the tokens' arming numbers ----
        done = tok["active"] & (tok["slot"] == S)
        cap = out["valid"].shape[0]
        row_key = jnp.where(done, entry_row, np.iinfo(np.int32).max)
        rows_s, _, order = lax.sort(
            (row_key, tok["seq"], toks), num_keys=2, is_stable=True
        )
        d_sorted = done[order]
        rank = (jnp.cumsum(d_sorted.astype(jnp.int32)) - d_sorted).astype(jnp.int32)
        dest = jnp.where(d_sorted & (out_n + rank < cap), out_n + rank, cap)
        overflow = overflow + (d_sorted & (out_n + rank >= cap)).sum(
            dtype=jnp.int32
        )
        tok = {**tok, "max_row": jnp.maximum(
            tok["max_row"], _longest_run(rows_s, done.sum(dtype=jnp.int32))
        )}
        src_t = order
        out = dict(out)
        emit_ts = jnp.where(
            entry_row[src_t] >= 0,
            batch_ts[jnp.clip(entry_row[src_t], 0, B - 1)],
            now,
        )
        out["ts"] = _set_at(out["ts"], dest, emit_ts)
        out["valid"] = out["valid"].at[dest].set(True, mode="drop")
        for a in self.refs:
            c = tok["caps"][a.ref_idx]
            out[f"n{a.ref_idx}"] = out[f"n{a.ref_idx}"].at[dest].set(
                c["n"][src_t], mode="drop"
            )
            if f"ts{a.ref_idx}" in out:
                out[f"ts{a.ref_idx}"] = _set_at(
                    out[f"ts{a.ref_idx}"], dest, c["ts"][src_t]
                )
            for name in c["cols"]:
                out[f"c{a.ref_idx}.{name}"] = _set_at(
                    out[f"c{a.ref_idx}.{name}"], dest, c["cols"][name][src_t]
                )
        out_n = jnp.minimum(
            out_n + done.sum(dtype=jnp.int32), cap
        ).astype(jnp.int32)
        tok = {**tok, "active": tok["active"] & ~done}
        return tok, out, out_n, overflow

    def _matrix_env(self, tok, row_cols: dict, row_ts, now, override_ref: int) -> Env:
        """[T, 1] token columns vs [1, B] event columns -> [T, B] broadcasts."""
        T = self.T
        cols = {}
        for a in self.refs:
            c = tok["caps"][a.ref_idx]
            cols[(a.ref, None, TS_ATTR)] = c["ts"][:, 0][:, None]
            cols[(a.ref, 0, TS_ATTR)] = c["ts"][:, 0][:, None]
            for name in c["cols"]:
                cols[(a.ref, None, name)] = c["cols"][name][:, 0][:, None]
                cols[(a.ref, 0, name)] = c["cols"][name][:, 0][:, None]
            cols[(a.ref, None, "__arrived__")] = (c["n"] > 0)[:, None]
        self._synth_capture_cols(
            cols,
            lambda a, attr: tok["caps"][a.ref_idx]["cols"][attr],
            lambda a: tok["caps"][a.ref_idx]["ts"],
            lambda a: tok["caps"][a.ref_idx]["n"],
            expand=lambda col: col[:, None],
        )
        a = self.refs[override_ref]
        for name, v in row_cols.items():
            cols[(a.ref, None, name)] = v[None, :]
            cols[(a.ref, 0, name)] = v[None, :]
        cols[(a.ref, None, TS_ATTR)] = row_ts[None, :]
        cols[(a.ref, 0, TS_ATTR)] = row_ts[None, :]
        cols[(a.ref, None, "__arrived__")] = jnp.ones((1, 1), dtype=jnp.bool_)
        return Env(cols, now=now)

    # ---- the batch kernel --------------------------------------------------
    #
    # One pass over a whole batch of one stream's rows: per NFA state each
    # token advances to its FIRST matching row; several hops inside one batch
    # fall out of the ascending state loop (a token that advanced at state p
    # on row j meets only rows behind j at state p+1). The table is a log:
    # `every` arms its tokens into the lanes behind `head` in row order, and
    # when the tail has no room the live tokens move to the front, in order
    # (`_make_room`); so lane order is arming order, which is the order the
    # completions of one row are emitted in, and nothing is scattered.

    @staticmethod
    def _lanes_of(tok) -> dict:
        """The table's per-token lanes as flat [T] arrays (the batch kernel
        takes patterns of single captures: a [T, 1] lane is its column;
        `seq` stays behind: lane order is arming order here)."""
        flat = {k: tok[k] for k in ("slot", "start_ts", "entry_ts")}
        for i, c in enumerate(tok["caps"]):
            flat[f"n{i}"] = c["n"]
            flat[f"ts{i}"] = c["ts"][:, 0]
            for name, arr in c["cols"].items():
                flat[f"c{i}.{name}"] = arr[:, 0]
        return flat

    @staticmethod
    def _with_lanes(tok, flat: dict) -> dict:
        caps = [
            {
                "n": flat[f"n{i}"],
                "ts": flat[f"ts{i}"][:, None],
                "cols": {
                    name: flat[f"c{i}.{name}"][:, None] for name in c["cols"]
                },
            }
            for i, c in enumerate(tok["caps"])
        ]
        return {
            **tok,
            **{k: flat[k] for k in ("slot", "start_ts", "entry_ts")},
            "caps": caps,
        }

    def _make_room(self, tok, need):
        """Room for `need` more tokens behind `head`: where the tail lacks
        it, the live tokens move to the front in lane order (log2 T passes of
        shifted selects, `compact_front`) and `head` falls to their number:
        every few batches, as often as the dead lanes behind `head` and the
        free ones ahead of it add up to a batch's arms."""
        T = self.T

        def compact(tok):
            alive = tok["active"]
            n = alive.sum(dtype=jnp.int32)
            flat = compact_front(alive, self._lanes_of(tok))
            return {
                **self._with_lanes(tok, flat),
                "active": jnp.arange(T, dtype=jnp.int32) < n,
                "head": n,
            }

        return lax.cond(tok["head"] + need > T, compact, lambda t: t, tok)

    @staticmethod
    def _place(lane, block, at, n):
        """`lane` with its places at..at+n-1 taken from the front of
        `block`: a shifted read and a select, no scatter."""
        size, have = lane.shape[0], block.shape[0]
        blk = block[:size] if have >= size else jnp.concatenate(
            [block, jnp.zeros((size - have,), block.dtype)]
        )
        moved = lax.dynamic_slice(
            jnp.concatenate([jnp.zeros((size,), blk.dtype), blk]),
            (size - at,), (size,),
        )
        idx = jnp.arange(size, dtype=jnp.int32)
        return jnp.where(
            (idx >= at) & (idx < at + n), moved.astype(lane.dtype), lane
        )

    def _arm(self, tok, fork, ev, batch_ts, rows, entry_row, overflow):
        """`every`: each passing row arms a fresh token one state on, into
        the lanes behind `head`, in row order."""
        T = self.T
        atom = self.slots[0].atoms[0]
        _keep_cols, ts_used = self.capture_keep()
        fork = fork & tok["active"][0] & (tok["slot"][0] == 0)
        want = fork.sum(dtype=jnp.int32)
        tok = self._make_room(tok, want)
        head = tok["head"]
        took = jnp.minimum(want, T - head)
        overflow = overflow + (want - took)
        cr = tok["caps"][atom.ref_idx]
        front = compact_front(fork, {
            "ts": batch_ts, "row": rows,
            "cols": {n: ev[n].astype(a.dtype) for n, a in cr["cols"].items()},
        })

        def put(lane, block):
            return self._place(lane, block, head, took)

        lanes = jnp.arange(T, dtype=jnp.int32)
        fresh = (lanes >= head) & (lanes < head + took)
        caps = []
        for i, c in enumerate(tok["caps"]):
            if i != atom.ref_idx:
                # a lane's last tenant may have left later captures behind
                caps.append({**c, "n": jnp.where(fresh, 0, c["n"])})
                continue
            caps.append({
                "n": jnp.where(fresh, 1, c["n"]),
                "ts": put(c["ts"][:, 0], front["ts"])[:, None]
                if ts_used[i] else c["ts"],
                "cols": {
                    n: put(a[:, 0], front["cols"][n])[:, None]
                    for n, a in c["cols"].items()
                },
            })
        tok = {
            **tok,
            "active": tok["active"] | fresh,
            "slot": jnp.where(fresh, 1, tok["slot"]),
            "start_ts": put(tok["start_ts"], front["ts"]),
            "entry_ts": put(tok["entry_ts"], front["ts"]),
            "caps": caps,
            "head": head + took,
            "armed": tok["armed"] + took.astype(jnp.int64),
        }
        return tok, put(entry_row, front["row"]), overflow

    def _match_matrix(self, tok, p, ev, batch_ts, v, rows, entry_row, now):
        """Slot p's tokens against the batch as a dense [T, B] matrix: (tok,
        which tokens found a row, the row, its time, its captured values)."""
        T, B = self.T, batch_ts.shape[0]
        slot = self.slots[p]
        atom = slot.atoms[0]
        elig = tok["active"] & (tok["slot"] == p)
        env = self._matrix_env(tok, ev, batch_ts, now, atom.ref_idx)
        cond = jnp.ones((T, B), dtype=jnp.bool_)
        for c in self._conds[(p, atom.ref_idx)]:
            cond = cond & jnp.broadcast_to(c(env), (T, B))
        M = elig[:, None] & v[None, :] & (rows[None, :] > entry_row[:, None]) & cond
        win = _min_within(slot.within_ms, self.within_ms)
        if win is not None:
            started = tok["start_ts"] >= 0
            M = M & ~(
                started[:, None]
                & (batch_ts[None, :] - tok["start_ts"][:, None] > win)
            )
        if self.sequence and not slot.persistent and p > 0:
            # strict continuity: the match must be the FIRST valid row
            # after the token's entry; a non-matching next row kills it
            nxt_ok = v[None, :] & (rows[None, :] > entry_row[:, None])
            has_next = nxt_ok.any(axis=1)
            jnext = jnp.argmax(nxt_ok, axis=1).astype(jnp.int32)
            M = M & (rows[None, :] == jnext[:, None])
            die = elig & has_next & ~M.any(axis=1)
            tok = {**tok, "active": tok["active"] & ~die}
        if p == 0 and slot.persistent:
            return tok, M.any(axis=0) & v, None, None, None
        has = M.any(axis=1)
        j = jnp.argmax(M, axis=1).astype(jnp.int32)  # first match row
        jc = jnp.clip(j, 0, B - 1)
        vals = {
            name: ev[name][jc]
            for name in tok["caps"][atom.ref_idx]["cols"]
        }
        return tok, has, j, batch_ts[jc], vals

    def _match_keyed(self, tok, p, ev, batch_ts, v, rows, entry_row, now):
        """Slot p's tokens against the rows of their own key: (tok, which
        tokens found a row, the row, its time, its captured values).

        Tokens and rows are sorted together on (key, place), a token placed
        just behind the row that brought it to this slot (ahead of every row
        when an earlier batch did), so that the rows a token may match are
        the rows behind it in its run of equal keys. Pass k hands every token
        the k-th of them (a segmented carry along the runs, read from the
        end) and evaluates the rest of the condition on those pairs; a token
        keeps the first row that passes. The passes end when no token that
        still looks has a row left: as many as the rows one key has in the
        batch. Sorts, carries and selects over T + B elements; no gather, and
        nothing of T x B."""
        T, B = self.T, batch_ts.shape[0]
        N = T + B
        slot = self.slots[p]
        atom = slot.atoms[0]
        plan = self._plans[p]
        by_ref = {a.ref: a for a in self.refs}
        rowok = v
        renv = self._row_env(ev, batch_ts, now, atom)
        for c in plan.row:
            rowok = rowok & jnp.broadcast_to(c(renv), (B,))
        elig = tok["active"] & (tok["slot"] == p)

        # ---- one sort on the key's words and the place
        words = []
        for row_attr, ridx, cap_attr, t in plan.keys:
            tk = tok["caps"][ridx]["cols"][cap_attr][:, 0]
            rk = ev[row_attr].astype(tk.dtype)
            if t is not AttrType.BOOL:
                # a null operand makes any comparison false
                nv = np.asarray(null_value(t), dtype=tk.dtype)
                elig = elig & (tk != nv)
                rowok = rowok & (rk != nv)
            words += _key_words(jnp.concatenate([tk, rk]))
        place = jnp.concatenate([2 * (entry_row + 1), 2 * rows + 1])
        who = jnp.arange(N, dtype=jnp.int32)
        *ks, _, who_s = lax.sort(
            (*words, place, who), num_keys=len(words) + 1, is_stable=False
        )
        differs = ks[0][1:] != ks[0][:-1]
        for w in ks[1:]:
            differs = differs | (w[1:] != w[:-1])
        # read from the end: a run's first element there is its last here
        opens = jnp.concatenate([differs, jnp.ones((1,), jnp.bool_)])[::-1]
        (inv,) = permute_by(who_s, who)

        # ---- what a pair's test reads, token side and row side
        win = _min_within(slot.within_ms, self.within_ms)
        # a row's lanes ride once, whoever reads them: ("col", attribute),
        # the event time "__ts" and the row's place in the batch "__row"
        tl, rl, reads = {}, {"__ts": batch_ts, "__row": rows}, {}
        for key in plan.rest_keys:
            ref, _k, attr = key
            if ref == atom.ref:
                reads[key] = "__ts" if attr == TS_ATTR else ("col", attr)
            else:
                c = tok["caps"][by_ref[ref].ref_idx]
                tl[key] = (
                    c["ts"][:, 0] if attr == TS_ATTR else c["cols"][attr][:, 0]
                )
        captured = tok["caps"][atom.ref_idx]["cols"]
        for _, attr in {*(r for r in reads.values() if r != "__ts"),
                        *(("col", n) for n in captured)}:
            rl[("col", attr)] = ev[attr]
        if win is not None:
            tl["__dl"] = jnp.where(
                tok["start_ts"] >= 0, tok["start_ts"] + win,
                np.iinfo(np.int64).max,
            )
        merged = _pair_lanes(tl, rl, T, B)
        merged.append((
            jnp.concatenate([elig, rowok]).astype(jnp.int32), "__on", "__on"
        ))
        ts_lanes, rs_lanes = {}, {}
        for (_, tkey, rkey), lane in zip(
            merged, permute_in_groups(inv, [lane for lane, _, _ in merged])
        ):
            lane = lane[::-1]
            if tkey is not None:
                ts_lanes[tkey] = lane
            if rkey is not None:
                rs_lanes[rkey] = lane
        # flags ride as int32: a [N] lane of PRED that outlives its fusion is
        # laid out by bits and read on the scalar path (ops/prefix.py)
        is_row = (who_s >= T)[::-1]
        on = ts_lanes.pop("__on") > 0
        rs_lanes.pop("__on")
        row_on = (is_row & on).astype(jnp.int32)
        tok_on = (~is_row & on).astype(jnp.int32)
        inner = (~opens).astype(jnp.int32)   # has its run's next element behind
        restart = (row_on > 0) | opens
        rest = plan.rest
        names = sorted(rs_lanes, key=repr)
        keep = ["__ts", "__row", *(("col", n) for n in captured)]

        def looking(state):
            _x, xv, _best, found = state
            return (xv > 0).any() & ((tok_on > 0) & (found == 0)).any()

        def shifted(lane):
            return jnp.concatenate([jnp.zeros((1,), lane.dtype), lane[:-1]])

        def one_pass(state):
            x, xv, best, found = state
            carried = segmented_carry((xv, *x), restart)
            rv, r = carried[0], dict(zip(names, carried[1:]))
            hit = (tok_on > 0) & (found == 0) & (rv > 0)
            if win is not None:
                hit = hit & (r["__ts"] <= ts_lanes["__dl"])
            if rest:
                cols = {k: lane for k, lane in ts_lanes.items() if k != "__dl"}
                cols.update({k: r[src] for k, src in reads.items()})
                env = Env(cols, now=now)
                for c in rest:
                    hit = hit & jnp.broadcast_to(c(env), (N,))
            best = tuple(jnp.where(hit, r[n], b) for n, b in zip(keep, best))
            # a row's next candidate: what the element behind it was handed
            return (
                tuple(shifted(r[n]) for n in names),
                shifted(rv) * inner * row_on, best,
                found | hit.astype(jnp.int32),
            )

        x0 = tuple(rs_lanes[n] for n in names)
        _, _, best, found = lax.while_loop(
            looking, one_pass,
            (x0, row_on, tuple(jnp.zeros_like(rs_lanes[n]) for n in keep),
             jnp.zeros((N,), jnp.int32)),
        )
        best = dict(zip(keep, best))

        # ---- back to lane order
        back = [("__found", found[::-1])] + [
            (n, best[n][::-1]) for n in keep
        ]
        res = {
            n: lane[:T] for (n, _), lane in zip(
                back, permute_in_groups(who_s, [lane for _, lane in back]))
        }
        vals = {n: res[("col", n)] for n in captured}
        return tok, res["__found"] > 0, res["__row"], res["__ts"], vals

    def apply_batch_fast(
        self, tok, batch_ts, batch_kind, batch_valid, stream_cols: dict,
        out, out_n, overflow, now,
    ):
        """One vectorized pass over a whole batch of one stream's rows."""
        T = self.T
        B = batch_ts.shape[0]
        S = len(self.slots)
        _keep_cols, _ts_used = self.capture_keep()
        rows = jnp.arange(B, dtype=jnp.int32)
        v = batch_valid & (batch_kind == KIND_CURRENT)
        entry_row = jnp.full((T,), -1, jnp.int32)  # batch-local hop cursor

        for p, slot in enumerate(self.slots):
            atom = slot.atoms[0]
            if atom.stream_id not in stream_cols:
                continue
            ev = stream_cols[atom.stream_id]
            if p == 0 and slot.persistent:
                with jax.named_scope("pattern.arm"):
                    if self.slot_row_only(0):
                        fork = v
                        renv = self._row_env(ev, batch_ts, now, atom)
                        for c in self._plans[0].row:
                            fork = fork & jnp.broadcast_to(c(renv), (B,))
                    else:
                        tok, fork, _, _, _ = self._match_matrix(
                            tok, p, ev, batch_ts, v, rows, entry_row, now
                        )
                    tok, entry_row, overflow = self._arm(
                        tok, fork, ev, batch_ts, rows, entry_row, overflow
                    )
                continue
            match = self._match_keyed if self.slot_keyed(p) else self._match_matrix
            with jax.named_scope("pattern.match"):
                tok, adv, j, mts, vals = match(
                    tok, p, ev, batch_ts, v, rows, entry_row, now
                )
                caps = [dict(c) for c in tok["caps"]]
                cr = dict(caps[atom.ref_idx])
                cr["n"] = jnp.where(adv, 1, cr["n"])
                # column-0 writes via static slice update, not arange scatter
                if _ts_used[atom.ref_idx]:
                    cr["ts"] = cr["ts"].at[:, 0].set(
                        jnp.where(adv, mts, cr["ts"][:, 0])
                    )
                cr["cols"] = {
                    name: arr.at[:, 0].set(
                        jnp.where(adv, vals[name].astype(arr.dtype), arr[:, 0])
                    )
                    for name, arr in cr["cols"].items()
                }
                caps[atom.ref_idx] = cr
                tok = {
                    **tok,
                    "slot": jnp.where(adv, p + 1, tok["slot"]),
                    "start_ts": jnp.where(
                        adv & (tok["start_ts"] < 0), mts, tok["start_ts"]
                    ),
                    "entry_ts": jnp.where(adv, mts, tok["entry_ts"]),
                    "caps": caps,
                }
                entry_row = jnp.where(adv, j, entry_row)

        with jax.named_scope("pattern.emit"):
            tok, out, out_n, overflow = self._emit_done(
                tok, entry_row, out, out_n, overflow
            )

        # purge tokens whose within expired by the end of the batch (the scan
        # path kills them on the next arrival; purging bounds table growth)
        with jax.named_scope("pattern.purge"):
            last_ts = jnp.max(jnp.where(v, batch_ts, np.int64(0)))
            win_by_slot = np.full(
                (S + 1,), np.iinfo(np.int64).max, dtype=np.int64
            )
            for p, slot in enumerate(self.slots):
                w = _min_within(slot.within_ms, self.within_ms)
                if w is not None:
                    win_by_slot[p] = w
            # select-chain over the (tiny) slot count: keeps the per-slot
            # window durations as scalar literals, not a device-array const
            slot_c = jnp.clip(tok["slot"], 0, S)
            win_t = jnp.full(slot_c.shape, win_by_slot[S], dtype=jnp.int64)
            for p in range(S):
                win_t = jnp.where(slot_c == p, win_by_slot[p], win_t)
            started = tok["start_ts"] >= 0
            expired = (
                tok["active"] & started & (last_ts - tok["start_ts"] > win_t)
            )
            tok = {
                **tok,
                "active": tok["active"] & ~expired,
                "expired": tok["expired"] + expired.sum(dtype=jnp.int64),
            }
        return tok, out, out_n, overflow

    def _emit_done(self, tok, entry_row, out, out_n, overflow):
        """Tokens past the last slot emit, ordered by their completion row;
        those of one row in lane order, the order their first events arrived
        in. The done tokens move to the front (`compact_front`), the first
        `cap` of them are sorted on the row, stably, and laid behind the
        emissions the step already holds."""
        T = self.T
        S = len(self.slots)
        cap = out["valid"].shape[0]
        done = tok["active"] & (tok["slot"] == S)
        nd = done.sum(dtype=jnp.int32)
        src = {"__row": entry_row, "ts": tok["entry_ts"]}
        for i, c in enumerate(tok["caps"]):
            if f"ts{i}" in out:
                src[f"ts{i}"] = c["ts"][:, 0]
            for name, arr in c["cols"].items():
                src[f"c{i}.{name}"] = arr[:, 0]
        front = compact_front(done, src)
        E = min(T, cap)
        idx = jnp.arange(E, dtype=jnp.int32)
        key = jnp.where(idx < nd, front["__row"][:E], np.iinfo(np.int32).max)
        key_s, order = lax.sort((key, idx), num_keys=1, is_stable=True)
        (inv,) = permute_by(order, idx)
        names = [k for k in front if k != "__row"]
        took = jnp.minimum(jnp.minimum(nd, E), cap - out_n)
        overflow = overflow + (nd - took)
        out = dict(out)
        for k, lane in zip(
            names, permute_in_groups(inv, [front[k][:E] for k in names])
        ):
            flat = out[k] if out[k].ndim == 1 else out[k][:, 0]
            flat = self._place(flat, lane, out_n, took)
            out[k] = flat if out[k].ndim == 1 else flat[:, None]
        at = jnp.arange(cap, dtype=jnp.int32)
        fresh = (at >= out_n) & (at < out_n + took)
        out["valid"] = out["valid"] | fresh
        for i in range(len(self.refs)):
            out[f"n{i}"] = jnp.where(fresh, 1, out[f"n{i}"])
        tok = {
            **tok,
            "active": tok["active"] & ~done,
            "max_row": jnp.maximum(
                tok["max_row"], _longest_run(key_s, jnp.minimum(nd, E))
            ),
        }
        return tok, out, out_n + took, overflow

    def init_out(self, cap: int):
        keep_cols, ts_used = self.capture_keep()
        out = {
            "ts": jnp.zeros((cap,), dtype=jnp.int64),
            "valid": jnp.zeros((cap,), dtype=jnp.bool_),
        }
        for a in self.refs:
            schema = self.schemas[a.stream_id]
            out[f"n{a.ref_idx}"] = jnp.zeros((cap,), dtype=jnp.int32)
            if ts_used[a.ref_idx]:
                out[f"ts{a.ref_idx}"] = jnp.zeros(
                    (cap, a.cap), dtype=jnp.int64
                )
            for name, t in schema.attrs:
                if name in keep_cols[a.ref_idx]:
                    out[f"c{a.ref_idx}.{name}"] = jnp.full(
                        (cap, a.cap), null_value(t), dtype=PHYSICAL_DTYPE[t]
                    )
        return out

    def _write_emits(self, out, out_n, overflow, emit, tok, ts):
        cap = out["valid"].shape[0]
        # the emitting tokens in the order they were armed (the source walks
        # its pending list oldest first), lane order among equals
        order = jnp.argsort(
            jnp.where(emit, tok["seq"], np.iinfo(np.int64).max)
        )
        rank = jnp.zeros((self.T,), jnp.int32).at[order].set(
            jnp.arange(self.T, dtype=jnp.int32)
        )
        dest_raw = out_n + rank
        ok = emit & (dest_raw < cap)
        dest = jnp.where(ok, dest_raw, cap)
        overflow = overflow + (emit & ~ok).sum(dtype=jnp.int32)
        out = dict(out)
        out["ts"] = out["ts"].at[dest].set(jnp.broadcast_to(ts, (self.T,)), mode="drop")
        out["valid"] = out["valid"].at[dest].set(True, mode="drop")
        for a in self.refs:
            c = tok["caps"][a.ref_idx]
            out[f"n{a.ref_idx}"] = out[f"n{a.ref_idx}"].at[dest].set(c["n"], mode="drop")
            if f"ts{a.ref_idx}" in out:
                out[f"ts{a.ref_idx}"] = out[f"ts{a.ref_idx}"].at[dest].set(c["ts"], mode="drop")
            for name in c["cols"]:
                key = f"c{a.ref_idx}.{name}"
                out[key] = out[key].at[dest].set(c["cols"][name], mode="drop")
        return (
            out,
            jnp.minimum(out_n + jnp.sum(emit).astype(jnp.int32), cap).astype(jnp.int32),
            overflow,
        )

    def out_env_cols(self, out) -> dict:
        """VarKeys for the selector over the emission buffer (projected: only
        lanes capture_keep() retained exist — every key the selector resolves
        is in the kept set by construction)."""
        cols = {}
        for a in self.refs:
            for name in self.schemas[a.stream_id].attr_names:
                arr = out.get(f"c{a.ref_idx}.{name}")
                if arr is None:
                    continue
                cols[(a.ref, None, name)] = arr[:, 0]
                for k in range(a.cap):
                    cols[(a.ref, k, name)] = arr[:, k]
            tsr = out.get(f"ts{a.ref_idx}")
            if tsr is not None:
                cols[(a.ref, None, TS_ATTR)] = tsr[:, 0]
                for k in range(a.cap):
                    cols[(a.ref, k, TS_ATTR)] = tsr[:, k]
            cols[(a.ref, None, "__arrived__")] = out[f"n{a.ref_idx}"] > 0
        self._synth_capture_cols(
            cols,
            lambda a, attr: out[f"c{a.ref_idx}.{attr}"],
            lambda a: out[f"ts{a.ref_idx}"],
            lambda a: out[f"n{a.ref_idx}"],
        )
        return cols

    def next_timer(self, tok, after=None) -> jnp.ndarray:
        """Earliest absent-slot deadline over active tokens, NO_TIMER if none.

        `after`: deadlines at or before this (the max timer timestamp already
        processed) are excluded — they were handled by that timer pass, and
        re-arming them would loop forever on a logical element whose absent
        deadline passed while its present side is still pending."""
        t = NO_TIMER
        for slot in self.slots:
            absents = [
                a
                for a in slot.atoms
                if a.absent and a.waiting_ms is not None
            ]
            if not absents or (len(slot.atoms) == 1 and not slot.is_absent):
                continue
            both_absent = len(absents) == len(slot.atoms) >= 2
            at_p = tok["active"] & (tok["slot"] == slot.index)
            for a in absents:  # both-absent elements wait per side
                base = tok["entry_ts"]
                if slot.index == 0 and both_absent:
                    # arrivals re-arm that side's window (see apply_event)
                    base = jnp.maximum(
                        base, tok["caps"][a.ref_idx]["ts"][:, 0]
                    )
                dl = jnp.where(at_p, base + a.waiting_ms, NO_TIMER)
                if after is not None:
                    dl = jnp.where(dl > after, dl, NO_TIMER)
                t = jnp.minimum(t, jnp.min(dl))
        return t
