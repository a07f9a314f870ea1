"""Incremental multi-duration aggregation.

Reference: core/aggregation/ — `define aggregation A from S select ... group by
... aggregate by ts every sec...year` builds a chain of per-duration executors
(IncrementalExecutor.java:49-580): the finest absorbs events into an in-memory
bucket store; when event time crosses a bucket boundary the closed bucket is
spilled to an auto-created table (`<id>_<DURATION>`, AGG_TIMESTAMP first column
— AggregationParser.java:400,695-708) and rolled up into the next coarser
executor. Query path merges table rows with in-flight buckets
(AggregationRuntime.java:176, IncrementalDataAggregator.java).

TPU-native design: the whole duration chain is one carried state pytree; a
`lax.scan` over the batch rows performs close/rollup/absorb per row (each a
masked [G] / [G,G] slot-table op), spilling closed buckets into a bounded
per-batch buffer that is table-inserted vectorized after the scan. Calendar
(month/year) alignment uses integer civil-date math on device.
"""

from __future__ import annotations

import re
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
    CompiledExpr,
    Env,
    Scope,
    TS_ATTR,
    compile_expression,
    is_aggregator,
)
from siddhi_tpu.core.flow import Flow
from siddhi_tpu.core.table import InMemoryTable
from siddhi_tpu.core.types import AttrType, PHYSICAL_DTYPE
from siddhi_tpu.query_api.definition import (
    Attribute,
    Duration,
    TableDefinition,
)
from siddhi_tpu.query_api.expression import AttributeFunction, Variable

AGG_TS = "AGG_TIMESTAMP"
DEFAULT_AGG_GROUPS = 64
SPILLS_PER_BATCH = 4

_I64MIN = jnp.iinfo(jnp.int64).min
_I64MAX = jnp.iinfo(jnp.int64).max


# ---------------------------------------------------------------------------
# civil-calendar device math (Howard Hinnant's algorithms, integer-only)
# ---------------------------------------------------------------------------

_DAY_MS = 86_400_000


def _civil_from_days(z):
    z = z + 719468
    era = jnp.floor_divide(z, 146097)
    doe = z - era * 146097
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = mp + jnp.where(mp < 10, 3, -9)
    return y + (m <= 2), m, d


def _days_from_civil(y, m, d):
    y = y - (m <= 2)
    era = jnp.floor_divide(y, 400)
    yoe = y - era * 400
    mp = jnp.where(m > 2, m - 3, m + 9)
    doy = (153 * mp + 2) // 5 + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return era * 146097 + doe - 719468


def align_bucket(ts_ms, duration: Duration):
    """Bucket start (epoch ms, GMT) containing ts — device-traceable
    (reference: util/IncrementalTimeConverterUtil.getStartTimeOfAggregates)."""
    ts_ms = jnp.asarray(ts_ms, jnp.int64)
    if duration not in (Duration.MONTHS, Duration.YEARS):
        step = np.int64(duration.millis)
        return jnp.floor_divide(ts_ms, step) * step
    days = jnp.floor_divide(ts_ms, _DAY_MS)
    y, m, _d = _civil_from_days(days)
    if duration is Duration.MONTHS:
        start = _days_from_civil(y, m, jnp.ones_like(m))
    else:
        start = _days_from_civil(y, jnp.ones_like(y), jnp.ones_like(y))
    return start * _DAY_MS


# ---------------------------------------------------------------------------
# base decomposition (reference: executor/incremental/*IncrementalAttributeAggregator)
# ---------------------------------------------------------------------------


def _sum_type(t: AttrType) -> AttrType:
    return AttrType.DOUBLE if t in (AttrType.FLOAT, AttrType.DOUBLE) else AttrType.LONG


class _OutSpec:
    """One selected attribute: bases it needs + how to recompose."""

    def __init__(self, name, kind, arg: Optional[CompiledExpr], out_type):
        self.name = name
        self.kind = kind  # sum|count|avg|min|max|last
        self.arg = arg
        self.out_type = out_type


class AggregationRuntime:
    def __init__(
        self,
        definition,
        in_schema: StreamSchema,
        interner,
        group_capacity: int = DEFAULT_AGG_GROUPS,
    ):
        self.definition = definition
        self.agg_id = definition.id
        self.in_schema = in_schema
        self.interner = interner
        self.g = int(group_capacity)

        stream = definition.basic_single_input_stream
        self.stream_id = stream.stream_id
        ref = stream.ref
        self.ref = ref
        scope = Scope(interner)
        scope.add_stream(ref, in_schema.attr_types)
        scope.default_ref = ref
        self.scope = scope

        from siddhi_tpu.query_api.execution import Filter

        self.filters = []
        for h in stream.handlers:
            if isinstance(h, Filter):
                c = compile_expression(h.expression, scope)
                if c.type is not AttrType.BOOL:
                    raise SiddhiAppCreationError("filter must be boolean")
                self.filters.append(c)
            else:
                raise SiddhiAppCreationError(
                    "aggregation inputs support filters only"
                )

        # timestamp source: `aggregate by <attr>` or the event timestamp
        if definition.aggregate_attribute is not None:
            c = compile_expression(definition.aggregate_attribute, scope)
            if c.type not in (AttrType.LONG, AttrType.INT):
                raise SiddhiAppCreationError("aggregate by attribute must be long")
            self.ts_expr = c
        else:
            self.ts_expr = None
        # lineage recorder (observability/lineage.py AggregationLineage):
        # per-bucket contributing seq ranges; None = one check per receive
        self.lineage = None
        from siddhi_tpu.query_api.expression import Variable as _Var

        self._lin_ts_attr = (
            definition.aggregate_attribute.attribute
            if isinstance(definition.aggregate_attribute, _Var)
            else None
        )

        self.durations: list[Duration] = list(definition.time_period.durations)

        # selected attributes -> base columns + recompose
        self.group_by: list[Variable] = list(definition.selector.group_by)
        self.group_keys: list[CompiledExpr] = [
            compile_expression(v, scope) for v in self.group_by
        ]
        self.out_specs: list[_OutSpec] = []
        self.bases: dict[str, tuple[str, Optional[CompiledExpr], AttrType]] = {}
        # base store columns: name -> (kind, arg expr, stored type)
        for oa in definition.selector.selection_list:
            e = oa.expression
            name = oa.name
            if is_aggregator(e):
                assert isinstance(e, AttributeFunction)
                fn = e.name.lower()
                if fn in ("sum", "min", "max", "avg"):
                    arg = compile_expression(e.parameters[0], scope)
                    if arg.type not in (
                        AttrType.INT, AttrType.LONG, AttrType.FLOAT, AttrType.DOUBLE
                    ):
                        raise SiddhiAppCreationError(f"{fn} needs a numeric argument")
                elif fn == "count":
                    arg = None
                else:
                    raise SiddhiAppCreationError(
                        f"'{e.name}' cannot be aggregated incrementally "
                        "(reference supports sum/count/avg/min/max)"
                    )
                if fn in ("sum", "avg"):
                    self._base(f"sum_{name}", "sum", arg, _sum_type(arg.type))
                if fn in ("count", "avg"):
                    self._base("count_", "count", None, AttrType.LONG)
                if fn in ("min", "max"):
                    self._base(f"{fn}_{name}", fn, arg, arg.type)
                out_type = (
                    AttrType.DOUBLE if fn == "avg"
                    else AttrType.LONG if fn == "count"
                    else (_sum_type(arg.type) if fn == "sum" else arg.type)
                )
                self.out_specs.append(_OutSpec(name, fn, arg, out_type))
            else:
                c = compile_expression(e, scope)
                self._base(f"last_{name}", "last", c, c.type)
                self.out_specs.append(_OutSpec(name, "last", c, c.type))

        # group-by attributes must be recoverable for the spill tables: store
        # them as last-value columns too
        self.group_names: list[str] = []
        for v, c in zip(self.group_by, self.group_keys):
            gname = v.attribute
            self.group_names.append(gname)
            self._base(f"last__g_{gname}", "last", c, c.type)

        # per-duration spill tables <id>_<DURATION>
        # (reference: AggregationParser.java:701)
        self.tables: dict[Duration, InMemoryTable] = {}
        table_attrs = [Attribute(AGG_TS, AttrType.LONG)]
        for gname, v in zip(self.group_names, self.group_by):
            t = dict(self.bases)[f"last__g_{gname}"][2]
            table_attrs.append(Attribute(gname, t))
        for bname, (kind, _arg, t) in self.bases.items():
            if bname.startswith("last__g_"):
                continue
            table_attrs.append(Attribute(f"AGG_{bname}", t))
        # @store on the aggregation rides through to every duration table
        # (reference: AggregationParser initDefaultTables passes the
        # aggregation's annotations to each internal table definition)
        from siddhi_tpu.query_api.annotation import find_annotation

        store_ann = find_annotation(
            getattr(definition, "annotations", []) or [], "store"
        )
        for d in self.durations:
            tid = f"{self.agg_id}_{d.name}"
            anns = []
            if store_ann is not None:
                # each duration table needs its OWN store namespace: a shared
                # store.id would make the tables clobber each other's rows
                from siddhi_tpu.query_api.annotation import Annotation

                els = [
                    (k, v) for k, v in store_ann.elements
                    if k != "store.id"
                ]
                base_id = store_ann.element("store.id") or self.agg_id
                els.append(("store.id", f"{base_id}__{d.name}"))
                anns.append(Annotation(store_ann.name, els))
            td = TableDefinition(tid, list(table_attrs), annotations=anns)
            self.tables[d] = InMemoryTable(td, interner)

        # output schema of the find path: AGG_TIMESTAMP + selected attrs
        self.out_schema = StreamSchema(
            self.agg_id,
            [(AGG_TS, AttrType.LONG)] + [(s.name, s.out_type) for s in self.out_specs],
        )

        self._empty = self._empty_store()
        self._store_dtypes = {b: self._empty["vals"][b].dtype for b in self.bases}
        self.state = self.init_state()
        self._step = jax.jit(self._step_impl)
        self._finds = {}
        self.rebuild_from_tables()

    def _base(self, name, kind, arg, t):
        if name not in self.bases:
            self.bases[name] = (kind, arg, t)

    # ---- restart rebuild ---------------------------------------------------

    def rebuild_from_tables(self):
        """Rebuild each coarser duration's OPEN bucket from the next finer
        duration's table rows (reference: aggregation/RecreateInMemoryData.java
        wired at SiddhiAppRuntime.java:380-382). A @store-backed aggregation
        restarting without a snapshot recovers its in-flight coarse buckets
        from the persisted fine spills; the finest duration's open bucket is
        irrecoverable in the reference too (its raw events were never spilled).

        Host-side one-shot: the duration tables were just loaded from the
        record store; rows are small and this runs once at creation."""
        import numpy as np

        for i in range(1, len(self.durations)):
            d = self.durations[i]
            src = self.tables[self.durations[i - 1]].state
            valid = np.asarray(src["valid"])
            if not valid.any():
                continue  # only skip durations whose OWN source is empty
            ts = np.asarray(src["cols"][AGG_TS])[valid]
            # the open bucket is judged by each SOURCE table's latest row —
            # an empty finest table must not suppress coarser rebuilds from
            # the intermediate duration tables
            latest = int(ts.max())
            open_bucket = int(align_bucket(jnp.asarray(latest), d))
            own = self.tables[d].state
            own_valid = np.asarray(own["valid"])
            if own_valid.any() and (
                np.asarray(own["cols"][AGG_TS])[own_valid] == open_bucket
            ).any():
                # this bucket already closed and spilled into d's own table
                # (e.g. the finer table's tail predates the spill); treating
                # it as in-flight again would double-insert it at the next
                # close — spill is a plain insert with no AGG_TS dedupe
                continue
            in_open = np.asarray(
                align_bucket(jnp.asarray(ts), d)
            ) == open_bucket
            if not in_open.any():
                # nothing to rebuild; _merge_into initializes the bucket on
                # the next live merge
                continue
            cols = {
                n: np.asarray(c)[valid][in_open]
                for n, c in src["cols"].items()
            }
            row_ts = ts[in_open]
            order = np.argsort(row_ts, kind="stable")

            # group rows by the stored group attributes
            gvals = [cols[g] for g in self.group_names]
            groups: dict = {}
            for ri in order:
                gk = tuple(v[ri].item() for v in gvals)
                groups.setdefault(gk, []).append(ri)

            store = self._empty_store()
            keys = np.asarray(store["keys"]).copy()
            used = np.asarray(store["used"]).copy()
            vals = {b: np.asarray(v).copy() for b, v in store["vals"].items()}
            for slot_i, (gk, ridx) in enumerate(groups.items()):
                if slot_i >= self.g:
                    break
                # the device key: float group cols bitcast to int32, mixed
                kcols = []
                for gname, gv in zip(self.group_names, gvals):
                    t = dict(self.bases)[f"last__g_{gname}"][2]
                    v = np.asarray([gv[ridx[0]]])
                    if t in (AttrType.FLOAT, AttrType.DOUBLE):
                        v = v.astype(np.float32).view(np.int32).astype(np.int64)
                    kcols.append(jnp.asarray(v, jnp.int64))
                if kcols:
                    from siddhi_tpu.ops.group import mix_keys

                    keys[slot_i] = int(mix_keys(kcols)[0])
                used[slot_i] = True
                for bname, (kind, _arg, _t) in self.bases.items():
                    col = (
                        cols[bname[len("last__g_"):]]
                        if bname.startswith("last__g_")
                        else cols[f"AGG_{bname}"]
                    )
                    sel = col[ridx]
                    if kind in ("sum", "count"):
                        vals[bname][slot_i] = sel.sum()
                    elif kind == "min":
                        vals[bname][slot_i] = sel.min()
                    elif kind == "max":
                        vals[bname][slot_i] = sel.max()
                    elif kind == "first":
                        vals[bname][slot_i] = sel[0]
                    else:  # last
                        vals[bname][slot_i] = sel[-1]
            self.state["stores"][i] = {
                "keys": jnp.asarray(keys),
                "used": jnp.asarray(used),
                "vals": {b: jnp.asarray(v) for b, v in vals.items()},
                "bucket": jnp.asarray(open_bucket, jnp.int64),
            }

    # ---- state -----------------------------------------------------------

    def _empty_store(self):
        g = self.g
        vals = {}
        for bname, (kind, _arg, t) in self.bases.items():
            dt = PHYSICAL_DTYPE[t]
            if kind == "min":
                init = jnp.full((g,), jnp.inf if t in (AttrType.FLOAT, AttrType.DOUBLE) else jnp.iinfo(dt).max, dt)
            elif kind == "max":
                init = jnp.full((g,), -jnp.inf if t in (AttrType.FLOAT, AttrType.DOUBLE) else jnp.iinfo(dt).min, dt)
            else:
                init = jnp.zeros((g,), dt)
            vals[bname] = init
        return {
            "keys": jnp.zeros((g,), jnp.int64),
            "used": jnp.zeros((g,), jnp.bool_),
            "vals": vals,
            "bucket": jnp.full((), -1, jnp.int64),
        }

    def _empty_spill(self):
        g, s = self.g, SPILLS_PER_BATCH
        return {
            "ts": jnp.zeros((s,), jnp.int64),
            "keys": jnp.zeros((s, g), jnp.int64),
            "used": jnp.zeros((s, g), jnp.bool_),
            "vals": {
                bname: jnp.zeros((s, g), self._store_dtypes[bname])
                for bname in self.bases
            },
        }

    def init_state(self):
        return {
            "stores": [self._empty_store() for _ in self.durations],
            # spill buffers are zeroed per step; kept in state for pytree shape
            "spill": [self._empty_spill() for _ in self.durations],
            "spill_n": [jnp.zeros((), jnp.int32) for _ in self.durations],
        }

    # ---- device step ------------------------------------------------------

    def _merge_into(self, store, src_keys, src_used, src_vals, src_bucket_ts, init_bucket):
        """Merge a child store's groups into `store` (masked [G,G] op)."""
        g = self.g
        keys, used = store["keys"], store["used"]
        eq = src_used[:, None] & used[None, :] & (src_keys[:, None] == keys[None, :])
        hit = eq.any(axis=1)
        hit_slot = jnp.argmax(eq, axis=1).astype(jnp.int32)
        # allocate misses in order
        miss = src_used & ~hit
        n_used = used.sum(dtype=jnp.int32)
        rank = (jnp.cumsum(miss.astype(jnp.int32)) - miss).astype(jnp.int32)
        new_slot = n_used + rank
        overflow = (jnp.where(miss, new_slot, 0) >= g).any()
        slot = jnp.where(hit, hit_slot, jnp.where(new_slot < g, new_slot, g))
        slot = jnp.where(src_used, slot, g)
        keys2 = keys.at[slot].set(src_keys, mode="drop")
        used2 = used.at[slot].set(True, mode="drop")
        vals2 = {}
        for bname, (kind, _arg, _t) in self.bases.items():
            dst = store["vals"][bname]
            sv = src_vals[bname]
            if kind in ("sum", "count"):
                vals2[bname] = dst.at[slot].add(jnp.where(src_used, sv, 0), mode="drop")
            elif kind == "min":
                vals2[bname] = dst.at[slot].min(sv, mode="drop")
            elif kind == "max":
                vals2[bname] = dst.at[slot].max(sv, mode="drop")
            else:  # last
                vals2[bname] = dst.at[slot].set(sv, mode="drop")
        bucket = jnp.where(store["bucket"] < 0, init_bucket, store["bucket"])
        return (
            {"keys": keys2, "used": used2, "vals": vals2, "bucket": bucket},
            overflow,
        )

    def _step_impl(self, state, batch: EventBatch, now):
        b = batch.capacity
        env_cols = {(self.ref, None, n): c for n, c in batch.cols.items()}
        env_cols[(self.ref, None, TS_ATTR)] = batch.ts
        env = Env(env_cols, now=now)

        live = batch.valid & (batch.kind == KIND_CURRENT)
        for f in self.filters:
            live = live & f(env)
        is_timer = batch.valid & (batch.kind == KIND_TIMER)
        ev_ts = self.ts_expr(env).astype(jnp.int64) if self.ts_expr else batch.ts
        ev_ts = jnp.where(is_timer, batch.ts, ev_ts)

        # per-row group key + base contributions
        from siddhi_tpu.ops.group import mix_keys

        if self.group_keys:
            kcols = []
            for c in self.group_keys:
                col = c(env)
                if c.type in (AttrType.FLOAT, AttrType.DOUBLE):
                    col = jnp.asarray(col).view(jnp.int32).astype(jnp.int64)
                kcols.append(col.astype(jnp.int64))
            row_key = mix_keys(kcols)
        else:
            row_key = jnp.zeros((b,), jnp.int64)
        contribs = {}
        for bname, (kind, arg, t) in self.bases.items():
            dt = PHYSICAL_DTYPE[t]
            if kind == "count":
                contribs[bname] = jnp.ones((b,), dt)
            else:
                contribs[bname] = jnp.broadcast_to(arg(env).astype(dt), (b,))

        g = self.g
        n_dur = len(self.durations)
        spill0 = [self._empty_spill() for _ in range(n_dur)]
        spill_n0 = [jnp.zeros((), jnp.int32) for _ in range(n_dur)]

        def body(carry, row):
            stores, spills, spill_ns, ovf = carry
            r_live = row["live"]
            r_timer = row["timer"]
            r_ts = row["ts"]
            advance = r_live | r_timer

            # the event itself is the finest "rollup": one pseudo-group
            roll_keys = jnp.where(
                jnp.arange(g) == 0, row["key"], 0
            ).astype(jnp.int64)
            roll_used = (jnp.arange(g) == 0) & r_live
            roll_vals = {
                bname: jnp.zeros((g,), contribs[bname].dtype).at[0].set(row[f"v.{bname}"])
                for bname in self.bases
            }
            roll_ts = r_ts

            def do_close(st, di, close, sp, sn, ovf):
                """Spill the open bucket and reset; returns closed snapshot."""
                pos = jnp.where(close & (sn < SPILLS_PER_BATCH), sn, SPILLS_PER_BATCH)
                sp = {
                    "ts": sp["ts"].at[pos].set(st["bucket"], mode="drop"),
                    "keys": sp["keys"].at[pos].set(st["keys"], mode="drop"),
                    "used": sp["used"].at[pos].set(st["used"], mode="drop"),
                    "vals": {
                        bn: sp["vals"][bn].at[pos].set(st["vals"][bn], mode="drop")
                        for bn in self.bases
                    },
                }
                ovf = ovf | (close & (sn >= SPILLS_PER_BATCH))
                sn = sn + close.astype(jnp.int32)
                closed = (st["keys"], st["used"], st["vals"], st["bucket"])
                empty = self._empty
                nb = align_bucket(r_ts, self.durations[di])
                st = {
                    "keys": jnp.where(close, empty["keys"], st["keys"]),
                    "used": jnp.where(close, empty["used"], st["used"]),
                    "vals": {
                        bn: jnp.where(close, empty["vals"][bn], st["vals"][bn])
                        for bn in self.bases
                    },
                    "bucket": jnp.where(close, nb, st["bucket"]),
                }
                return st, sp, sn, ovf, closed

            new_stores, new_spills, new_spill_ns = [], [], []
            for di, dur in enumerate(self.durations):
                st = stores[di]
                nb = align_bucket(r_ts, dur)
                crossed = advance & (st["bucket"] >= 0) & (nb > st["bucket"])
                sp, sn = spills[di], spill_ns[di]
                if di == 0:
                    # the event belongs to the NEW bucket: close, then absorb
                    st, sp, sn, ovf, closed = do_close(st, di, crossed, sp, sn, ovf)
                    merged, mo = self._merge_into(
                        st, roll_keys, roll_used, roll_vals, roll_ts,
                        align_bucket(roll_ts, dur),
                    )
                    close = crossed
                else:
                    # a child rollup belongs to the OPEN bucket: absorb first,
                    # then close on the row's own time
                    st, mo = self._merge_into(
                        st, roll_keys, roll_used, roll_vals, roll_ts,
                        align_bucket(roll_ts, dur),
                    )
                    close = advance & (st["bucket"] >= 0) & (nb > st["bucket"])
                    st, sp, sn, ovf, closed = do_close(st, di, close, sp, sn, ovf)
                    merged = st
                ovf = ovf | (mo & roll_used.any())
                new_stores.append(merged)
                new_spills.append(sp)
                new_spill_ns.append(sn)
                # the rollup for the NEXT coarser duration is this close
                closed_keys, closed_used, closed_vals, closed_bucket = closed
                roll_keys = jnp.where(close, closed_keys, jnp.zeros_like(closed_keys))
                roll_used = closed_used & close
                roll_vals = {bn: closed_vals[bn] for bn in self.bases}
                roll_ts = jnp.where(close, closed_bucket, r_ts)

            return (new_stores, new_spills, new_spill_ns, ovf), None

        xs = {
            "ts": ev_ts,
            "live": live,
            "timer": is_timer,
            "key": row_key,
            **{f"v.{bn}": contribs[bn] for bn in self.bases},
        }
        (stores, spills, spill_ns, ovf), _ = lax.scan(
            body,
            (state["stores"], spill0, spill_n0, np.bool_(False)),
            xs,
        )

        aux = {"agg_overflow": ovf}
        # schedule the next root-bucket close — only when bucketing by the
        # events' own wall timestamps. With an explicit `aggregate by attr`
        # the event clock is decoupled from the scheduler's wall clock (think
        # replays of historical data), so closes are driven purely by event
        # arrival and find() merging the in-flight buckets.
        d0 = self.durations[0]
        if self.ts_expr is None and d0 not in (Duration.MONTHS, Duration.YEARS):
            aux["next_timer"] = jnp.where(
                stores[0]["bucket"] >= 0,
                stores[0]["bucket"] + d0.millis,
                np.int64(_I64MAX),
            )
        return (
            {"stores": stores, "spill": spills, "spill_n": spill_ns},
            aux,
        )

    def _spill_to_tables(self, new_state, tstates):
        """Vectorized insert of this step's closed buckets into the duration
        tables; returns updated tstates."""
        g = self.g
        for di, dur in enumerate(self.durations):
            sp = new_state["spill"][di]
            table = self.tables[dur]
            rows_used = (
                sp["used"]
                & (jnp.arange(SPILLS_PER_BATCH)[:, None] < new_state["spill_n"][di])
            ).reshape(-1)
            ts_flat = jnp.broadcast_to(
                sp["ts"][:, None], (SPILLS_PER_BATCH, g)
            ).reshape(-1)
            cols = {AGG_TS: ts_flat}
            for gname in self.group_names:
                cols[gname] = sp["vals"][f"last__g_{gname}"].reshape(-1)
            for bname in self.bases:
                if bname.startswith("last__g_"):
                    continue
                cols[f"AGG_{bname}"] = sp["vals"][bname].reshape(-1)
            dtypes = {n: a.dtype for n, a in table.schema.empty_batch(1).cols.items()}
            batch = EventBatch(
                ts=ts_flat,
                kind=jnp.zeros_like(ts_flat, jnp.int8),
                valid=rows_used,
                cols={n: cols[n].astype(dtypes[n]) for n in table.schema.attr_names},
            )
            aux = {}
            tstates[table.table_id] = table.insert(tstates[table.table_id], batch, aux)
        return tstates

    # ---- host -------------------------------------------------------------

    def describe_state(self) -> dict:
        """Introspection: per-granularity bucket state — open (in-flight)
        group count, the open bucket's start (the duration's watermark: all
        coarser output up to it is final), and the duration table's closed
        row count (see observability/introspect.py)."""
        import numpy as np

        out: dict = {"group_capacity": self.g, "durations": {}}
        try:
            for di, dur in enumerate(self.durations):
                store = self.state["stores"][di]
                bucket = int(np.asarray(store["bucket"]))
                entry = {
                    "open_groups": int(np.asarray(store["used"]).sum()),
                    "watermark_ms": bucket if bucket >= 0 else None,
                }
                tbl = self.tables.get(dur)
                if tbl is not None:
                    entry["closed_rows"] = int(
                        np.asarray(tbl.state["valid"]).sum()
                    )
                out["durations"][dur.name] = entry
        except Exception:
            out["durations"] = None  # mid-dispatch buffer churn: degrade
        return out

    def arm_lineage(self, cfg) -> None:
        """Enable per-bucket provenance (@app:lineage): contributing seq
        ranges + counts per finest-duration time bucket. Host-side only —
        aggregations always ride the per-batch dispatch path."""
        from siddhi_tpu.observability.lineage import AggregationLineage

        self.lineage = AggregationLineage(
            cfg, self.agg_id, self.stream_id, self.durations[0]
        )

    def receive(self, batch: EventBatch, now: int):
        lin = self.lineage
        if lin is not None:
            try:
                import numpy as _np

                ts_col = (
                    _np.asarray(batch.cols[self._lin_ts_attr]).astype("int64")
                    if self._lin_ts_attr is not None
                    else None
                )
                lin.observe_batch(batch, ts_col)
            except Exception:  # provenance must never break dispatch
                import logging

                logging.getLogger(__name__).debug(
                    "aggregation lineage observe failed", exc_info=True
                )
        tstates = {t.table_id: t.state for t in self.tables.values()}
        new_state, aux, tstates = self._step_full(batch, now, tstates)
        self.state = new_state
        for t in self.tables.values():
            t.state = tstates[t.table_id]
            if t.record_store is not None:
                t.notify_change()  # spills write through to the record store
        return aux

    def apply_late(self, ts_ms: int, row: dict) -> bool:
        """Best-effort merge of ONE late event (late.policy='apply',
        core/watermark.py). Each duration whose open bucket still covers the
        event absorbs it through the same masked merge the device step uses;
        an already-closed bucket is corrected IN PLACE in its duration table
        (sum/count add, min/max fold; `last` keeps the newer value already
        there), inserting a fresh row when the group never reached that
        bucket. find() returns table rows verbatim, so in-place update is
        the only shape that keeps store-query results correction-consistent.

        Host-side and rare by construction (each call is one metered late
        row); returns False when the event fails the aggregation's filters."""
        from siddhi_tpu.ops.group import mix_keys

        batch = self.in_schema.to_batch_cols(
            np.asarray([ts_ms], np.int64),
            {n: np.asarray([row[n]]) for n in self.in_schema.attr_names},
            self.interner,
        )
        env_cols = {(self.ref, None, n): c for n, c in batch.cols.items()}
        env_cols[(self.ref, None, TS_ATTR)] = batch.ts
        env = Env(env_cols, now=jnp.asarray(ts_ms, jnp.int64))
        for f in self.filters:
            if not bool(np.asarray(f(env))[0]):
                return False
        ev_ts = (
            int(np.asarray(self.ts_expr(env).astype(jnp.int64))[0])
            if self.ts_expr is not None
            else ts_ms
        )
        if self.group_keys:
            kcols = []
            for c in self.group_keys:
                col = jnp.asarray(c(env))
                if c.type in (AttrType.FLOAT, AttrType.DOUBLE):
                    col = col.view(jnp.int32).astype(jnp.int64)
                kcols.append(col.astype(jnp.int64))
            key = int(np.asarray(mix_keys(kcols))[0])
        else:
            key = 0
        contribs: dict = {}
        for bname, (kind, arg, _t) in self.bases.items():
            dt = self._store_dtypes[bname]
            if kind == "count":
                contribs[bname] = np.ones((), dt)[()]
            else:
                contribs[bname] = np.asarray(arg(env)).astype(dt).reshape(-1)[0]

        g = self.g
        for di, dur in enumerate(self.durations):
            b = int(np.asarray(align_bucket(jnp.asarray(ev_ts, jnp.int64), dur)))
            store = self.state["stores"][di]
            open_bucket = int(np.asarray(store["bucket"]))
            if open_bucket < 0 or b == open_bucket:
                # still in flight here: a one-hot [G] source through the
                # regular merge (opens the bucket at `b` when none is open)
                src_keys = jnp.zeros((g,), jnp.int64).at[0].set(key)
                src_used = jnp.zeros((g,), jnp.bool_).at[0].set(True)
                src_vals = {
                    bn: jnp.zeros((g,), self._store_dtypes[bn]).at[0].set(
                        contribs[bn]
                    )
                    for bn in self.bases
                }
                merged, _ovf = self._merge_into(
                    store, src_keys, src_used, src_vals,
                    jnp.asarray(ev_ts, jnp.int64), jnp.asarray(b, jnp.int64),
                )
                self.state["stores"][di] = merged
                continue
            if b > open_bucket:
                # not actually late for this duration; the live path owns
                # the close/rollup sequencing — never fast-forward it here
                continue
            # closed bucket: correct the spilled row in the duration table
            table = self.tables[dur]
            tstate = table.state
            valid = np.asarray(tstate["valid"])
            tcols = {n: np.asarray(c) for n, c in tstate["cols"].items()}
            match = valid & (tcols[AGG_TS] == b)
            for gname in self.group_names:
                gv = contribs[f"last__g_{gname}"]
                match = match & (tcols[gname] == tcols[gname].dtype.type(gv))
            idx = np.flatnonzero(match)
            if idx.size:
                ri = int(idx[0])
                new_cols = dict(tstate["cols"])
                for bname, (kind, _arg, _t) in self.bases.items():
                    if bname.startswith("last__g_") or kind == "last":
                        # group cols identify the row; a late event is never
                        # the newest by event time, so `last` stays put
                        continue
                    cname = f"AGG_{bname}"
                    col = tcols[cname].copy()
                    if kind in ("sum", "count"):
                        col[ri] += contribs[bname]
                    elif kind == "min":
                        col[ri] = min(col[ri], contribs[bname])
                    else:  # max
                        col[ri] = max(col[ri], contribs[bname])
                    new_cols[cname] = jnp.asarray(col)
                table.state = {**tstate, "cols": new_cols}
            else:
                # the group never reached this bucket: a fresh closed row
                # through the table's own insert (seq/index bookkeeping)
                dtypes = {
                    n: a.dtype
                    for n, a in table.schema.empty_batch(1).cols.items()
                }
                cols = {AGG_TS: np.asarray([b], np.int64)}
                for gname in self.group_names:
                    cols[gname] = np.asarray([contribs[f"last__g_{gname}"]])
                for bname in self.bases:
                    if bname.startswith("last__g_"):
                        continue
                    cols[f"AGG_{bname}"] = np.asarray([contribs[bname]])
                ins = EventBatch(
                    ts=jnp.asarray([b], jnp.int64),
                    kind=jnp.zeros((1,), jnp.int8),
                    valid=jnp.ones((1,), jnp.bool_),
                    cols={
                        n: jnp.asarray(cols[n].astype(dtypes[n]))
                        for n in table.schema.attr_names
                    },
                )
                table.state = table.insert(table.state, ins, {})
            if table.record_store is not None:
                table.notify_change()
        return True

    def _step_full(self, batch, now, tstates):
        if not hasattr(self, "_jit_full"):
            def full(state, batch, now, tstates):
                new_state, aux = self._step_impl(state, batch, now)
                tstates = self._spill_to_tables(new_state, tstates)
                return new_state, aux, tstates

            self._jit_full = jax.jit(full)
        return self._jit_full(self.state, batch, jnp.asarray(now, jnp.int64), tstates)

    # ---- find (store query / join) ---------------------------------------

    def find(self, per: Duration, within: Optional[tuple[int, int]], now: int):
        """Rows for `from A within .. per '<dur>'`: closed buckets from the
        duration table merged with the in-flight buckets of this and all finer
        durations (reference: AggregationRuntime.find:176 +
        IncrementalDataAggregator)."""
        if per not in self.tables:
            raise SiddhiAppCreationError(
                f"aggregation '{self.agg_id}' has no '{per.name}' duration"
            )
        key = per
        if key not in self._finds:
            self._finds[key] = jax.jit(lambda st, ts, now: self._find_impl(per, st, ts, now))
        tstate = self.tables[per].state
        out = self._finds[key](self.state, tstate, jnp.asarray(now, jnp.int64))
        if within is not None:
            lo, hi = within
            valid = out.valid & (out.ts >= lo) & (out.ts < hi)
            out = EventBatch(out.ts, out.kind, valid, out.cols)
        return out

    def _find_impl(self, per: Duration, state, tstate, now):
        g = self.g
        per_idx = self.durations.index(per)
        # merge in-flight stores (finest..per) into one temp store aligned to per
        temp = dict(self._empty)
        temp = {**temp, "bucket": jnp.full((), -1, jnp.int64)}
        ovf = np.bool_(False)
        for di in range(per_idx + 1):
            st = state["stores"][di]
            has = st["bucket"] >= 0
            aligned = jnp.where(has, align_bucket(jnp.maximum(st["bucket"], 0), per), -1)
            temp, mo = self._merge_into(
                temp,
                st["keys"],
                st["used"] & has,
                st["vals"],
                aligned,
                aligned,
            )
            ovf = ovf | mo

        # recompose output columns for a store: (used[G], vals) -> cols
        def recompose(vals):
            cols = {}
            for s in self.out_specs:
                if s.kind == "avg":
                    # logical DOUBLE runs as f32 on TPU (types.PHYSICAL_DTYPE)
                    num = vals[f"sum_{s.name}"].astype(jnp.float32)
                    den = vals["count_"].astype(jnp.float32)
                    cols[s.name] = jnp.where(den != 0, num / den, jnp.nan)
                elif s.kind == "sum":
                    cols[s.name] = vals[f"sum_{s.name}"]
                elif s.kind == "count":
                    cols[s.name] = vals["count_"]
                elif s.kind in ("min", "max"):
                    cols[s.name] = vals[f"{s.kind}_{s.name}"]
                else:
                    cols[s.name] = vals[f"last_{s.name}"]
            return cols

        inflight_cols = recompose(temp["vals"])
        inflight_ts = jnp.full((g,), temp["bucket"], jnp.int64)
        inflight_valid = temp["used"] & (temp["bucket"] >= 0)

        # table rows: recompose from AGG_<base> columns
        tvals = {}
        for bname in self.bases:
            if bname.startswith("last__g_"):
                gname = bname[len("last__g_"):]
                tvals[bname] = tstate["cols"][gname]
            else:
                tvals[bname] = tstate["cols"][f"AGG_{bname}"]
        table_cols = recompose(tvals)
        table_ts = tstate["cols"][AGG_TS]
        table_valid = tstate["valid"]

        out_dtypes = {
            n: a.dtype for n, a in self.out_schema.empty_batch(1).cols.items()
        }
        cols = {AGG_TS: jnp.concatenate([table_ts, inflight_ts]).astype(out_dtypes[AGG_TS])}
        for s in self.out_specs:
            cols[s.name] = jnp.concatenate(
                [
                    table_cols[s.name].astype(out_dtypes[s.name]),
                    inflight_cols[s.name].astype(out_dtypes[s.name]),
                ]
            )
        return EventBatch(
            ts=jnp.concatenate([table_ts, inflight_ts]),
            kind=jnp.zeros((table_ts.shape[0] + g,), jnp.int8),
            valid=jnp.concatenate([table_valid, inflight_valid]),
            cols=cols,
        )


class AggFindable:
    """Findable adapter exposing an aggregation's merged view (closed buckets
    + in-flight) as a passive join side (reference: AggregationRuntime
    participating in joins via compileExpression/find,
    AggregationRuntime.java:176-300)."""

    is_named_window = False  # passive probe target, like a table

    def __init__(self, agg: "AggregationRuntime", per: Duration, within):
        if per not in agg.tables:
            raise SiddhiAppCreationError(
                f"aggregation '{agg.agg_id}' has no '{per.name}' duration"
            )
        self.agg = agg
        self.per = per
        self.within = within  # (start_ms, end_ms) or None (static bounds)
        self.table_id = f"__aggview_{agg.agg_id}_{per.name}"
        self.schema = agg.out_schema

    @property
    def state(self):
        return {
            "agg": self.agg.state,
            "table": self.agg.tables[self.per].state,
        }

    @state.setter
    def state(self, value):  # joins never write through; writeback is a no-op
        pass

    def view(self, packed):
        out = self.agg._find_impl(
            self.per, packed["agg"], packed["table"], np.int64(0)
        )
        valid = out.valid
        if self.within is not None:
            lo, hi = self.within
            valid = valid & (out.ts >= lo) & (out.ts < hi)
        return out.cols, out.ts, valid


# ---------------------------------------------------------------------------
# within / per parsing (host)
# ---------------------------------------------------------------------------

_DUR_NAMES = {
    "sec": Duration.SECONDS, "second": Duration.SECONDS, "seconds": Duration.SECONDS,
    "min": Duration.MINUTES, "minute": Duration.MINUTES, "minutes": Duration.MINUTES,
    "hour": Duration.HOURS, "hours": Duration.HOURS,
    "day": Duration.DAYS, "days": Duration.DAYS,
    "month": Duration.MONTHS, "months": Duration.MONTHS,
    "year": Duration.YEARS, "years": Duration.YEARS,
}


def parse_per(value) -> Duration:
    d = _DUR_NAMES.get(str(value).strip().lower())
    if d is None:
        raise SiddhiAppCreationError(f"unknown aggregation duration {value!r}")
    return d


_TIME_RE = re.compile(
    r"^(\d{4}|\*{1,4})-(\d{2}|\*{1,2})-(\d{2}|\*{1,2})"
    r"(?:[ T](\d{2}|\*{1,2}):(\d{2}|\*{1,2}):(\d{2}|\*{1,2}))?"
    r"(?:\s*(?:Z|([+-])(\d{2}):(\d{2})))?$"
)


def parse_within_value(v) -> tuple[int, int]:
    """One `within` operand -> [start, end) ms. Longs are exact instants;
    strings follow the reference's `yyyy-MM-dd HH:mm:ss` (GMT default) with
    `**` wildcards expanding to the containing range."""
    import calendar
    import datetime as dt

    if isinstance(v, (int, float)):
        return int(v), int(v) + 1
    m = _TIME_RE.match(str(v).strip())
    if not m:
        raise SiddhiAppCreationError(f"cannot parse within time {v!r}")
    y, mo, d, h, mi, s = m.group(1, 2, 3, 4, 5, 6)
    off_sign, off_h, off_m = m.group(7, 8, 9)
    offset_ms = 0
    if off_sign:
        offset_ms = (int(off_h) * 3600 + int(off_m) * 60) * 1000
        if off_sign == "-":
            offset_ms = -offset_ms

    def wild(x):
        return x is None or "*" in x

    parts = [y, mo, d, h, mi, s]
    # find the first wildcarded component; everything after must be wild too
    level = 6
    for i, p in enumerate(parts):
        if wild(p):
            level = i
            break
    for p in parts[level + 1 :] if level < 6 else []:
        if not wild(p):
            raise SiddhiAppCreationError(
                f"within {v!r}: components after a wildcard must be wildcards"
            )
    vals = [int(p) if not wild(p) else 0 for p in parts]
    y_, mo_, d_, h_, mi_, s_ = vals
    if level == 0:
        raise SiddhiAppCreationError(f"within {v!r}: year cannot be a wildcard")
    start = dt.datetime(
        y_, mo_ if level > 1 else 1, d_ if level > 2 else 1,
        h_ if level > 3 else 0, mi_ if level > 4 else 0, s_ if level > 5 else 0,
        tzinfo=dt.timezone.utc,
    )
    if level == 1:
        end = start.replace(year=start.year + 1)
    elif level == 2:
        end = (
            start.replace(year=start.year + 1, month=1)
            if start.month == 12
            else start.replace(month=start.month + 1)
        )
    elif level == 3:
        end = start + dt.timedelta(days=1)
    elif level == 4:
        end = start + dt.timedelta(hours=1)
    elif level == 5:
        end = start + dt.timedelta(minutes=1)
    else:
        end = start + dt.timedelta(seconds=1)
    start_ms = int(start.timestamp() * 1000) - offset_ms
    end_ms = int(end.timestamp() * 1000) - offset_ms
    return start_ms, end_ms
