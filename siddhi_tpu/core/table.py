"""Device-resident columnar tables.

Reference: core/table/InMemoryTable.java:55-220 + table/holder/IndexEventHolder.java
— list/indexed/primary-key event holders with CRUD under compiled conditions, and
util/collection/ (CollectionExecutors/Operators) — the lookup planner.

TPU-native design: a table is a fixed-capacity columnar arena on device
(`cols/ts/valid/seq` lanes). Lookups are dense masked [B, C] condition
evaluations (one fused XLA kernel — the MXU-friendly analog of the reference's
per-event holder scans); the primary-key "index" is the same dense compare used
for overwrite-on-conflict semantics rather than a host hash map, so every CRUD
op stays inside the jitted query step. Sequential update semantics (later
events in a chunk see earlier events' writes, as in the reference's per-event
loop) are kept via a `lax.scan` over the probe batch.
"""

from __future__ import annotations

import threading
from typing import Callable, Optional

import jax
import jax.lax as lax
import jax.numpy as jnp
import numpy as np

from siddhi_tpu.core.errors import SiddhiAppCreationError
from siddhi_tpu.ops.prefix import first_indices
from siddhi_tpu.core.event import EventBatch, KIND_CURRENT, StreamSchema
from siddhi_tpu.core.executor import (
    CompiledExpr,
    Env,
    Scope,
    TS_ATTR,
    compile_expression,
)
from siddhi_tpu.core.types import AttrType
from siddhi_tpu.query_api.annotation import find_all, find_annotation
from siddhi_tpu.query_api.definition import TableDefinition
from siddhi_tpu.query_api.execution import UpdateSetAttribute

DEFAULT_TABLE_CAPACITY = 4096


class InMemoryTable:
    """Host handle for one table: schema + device state + compiled-op builders.

    State pytree:
      cols:  {attr: [C] array}
      ts:    [C] int64   insertion timestamps
      valid: [C] bool    row occupancy
      seq:   [C] int64   insertion order (stable find/iteration order)
      next:  scalar int64 next sequence number
    """

    def __init__(
        self,
        definition: TableDefinition,
        interner,
        capacity: int = DEFAULT_TABLE_CAPACITY,
    ):
        self.definition = definition
        self.table_id = definition.id
        self.schema = StreamSchema(
            definition.id, [(a.name, a.type) for a in definition.attributes]
        )
        self.interner = interner
        cap_ann = find_annotation(definition.annotations, "capacity")
        self.capacity = (
            int(cap_ann.element("size") or cap_ann.element(None))
            if cap_ann
            else int(capacity)
        )
        pks = find_all(definition.annotations or [], "PrimaryKey")
        if len(pks) > 1:
            # reference: DuplicateAnnotationException for repeated @PrimaryKey
            raise SiddhiAppCreationError(
                f"table '{self.table_id}': @PrimaryKey annotation is repeated"
            )
        pk = pks[0] if pks else None
        self.primary_keys: list[str] = [v for _, v in pk.elements] if pk else []
        if pk is not None and not self.primary_keys:
            raise SiddhiAppCreationError(
                f"table '{self.table_id}': @PrimaryKey needs at least one "
                "attribute"
            )
        for k in self.primary_keys:
            if k not in self.schema.attr_names:
                raise SiddhiAppCreationError(
                    f"table '{self.table_id}': @PrimaryKey attribute '{k}' undefined"
                )
        idxs = find_all(definition.annotations or [], "Index") + find_all(
            definition.annotations or [], "IndexBy"
        )
        if len(idxs) > 1:
            # reference: DuplicateAnnotationException for repeated @Index
            raise SiddhiAppCreationError(
                f"table '{self.table_id}': @Index annotation is repeated"
            )
        idx = idxs[0] if idxs else None
        self.indexes: list[str] = [v for _, v in idx.elements] if idx else []
        if len(set(self.indexes)) != len(self.indexes):
            raise SiddhiAppCreationError(
                f"table '{self.table_id}': @Index lists an attribute twice"
            )
        for k in self.indexes:
            if k not in self.schema.attr_names:
                raise SiddhiAppCreationError(
                    f"table '{self.table_id}': @Index attribute '{k}' undefined"
                )
        # declared @Index columns are maintained from creation (reference:
        # IndexEventHolder builds declared indexes eagerly); equality-probed
        # columns additionally auto-index at query-compile time
        self._indexed_cols = tuple(dict.fromkeys(self.indexes))

        self.lock = threading.RLock()
        self.state = self.init_state()
        # observability hooks (wired by the app runtime when @app:statistics
        # is on): mutation_stats counts mutating steps committed to this
        # table; flush_latency times record-store write-through snapshots
        self.mutation_stats = None
        self.flush_latency = None
        # @OnError on the table definition (wired by the app runtime):
        # mutation failures — the mutating query's dispatch AND record-store
        # flushes here — route to the error store ('STORE') or the log
        # ('LOG') instead of propagating to the sender; None keeps the
        # propagate-to-sender behavior
        self.fault_policy = None
        self.app_name = ""
        self.error_store_fn = None

        # @store(type='...'): external record store — load initial contents,
        # write a snapshot through after each mutation (reference:
        # AbstractRecordTable SPI; see core/record_table.py)
        self.record_store = None
        self.lazy = False
        store_ann = find_annotation(definition.annotations, "store")
        if store_ann is not None:
            from siddhi_tpu.core.record_table import build_record_store

            self.record_store = build_record_store(
                store_ann, self.table_id, self.schema
            )
            rows = self.record_store.load()
            if rows is None:
                # lazy/queryable store: finds push conditions down, nothing
                # materializes (see record_table.RecordStore)
                self.lazy = True
            else:
                if len(rows) > self.capacity:
                    raise SiddhiAppCreationError(
                        f"table '{self.table_id}': record store holds "
                        f"{len(rows)} rows but capacity is {self.capacity}; "
                        "raise it with @capacity(size='N') before restarting"
                    )
                if rows:
                    batch = self.schema.to_batch(
                        [0] * len(rows), rows, interner, capacity=len(rows)
                    )
                    aux: dict = {}
                    self.state = self.insert(self.state, batch, aux)
        self._dirty = False
        self._last_flush = 0.0
        self._flush_lock = threading.Lock()
        self._flush_timer = None

    def notify_change(self) -> None:
        """Mark dirty; snapshots coalesce to at most one per second (the
        full-table host decode would otherwise stall the dispatch pipeline on
        every mutating step). flush_record_store() forces the write."""
        if self.mutation_stats is not None:
            self.mutation_stats(1)
        if self.record_store is None:
            return
        if self.lazy:
            raise SiddhiAppCreationError(
                f"table '{self.table_id}': a lazy (queryable) record store "
                "cannot accept streaming writes; materialize it or write to "
                "the store directly"
            )
        import threading as _threading
        import time as _time

        with self._flush_lock:
            self._dirty = True
            due = _time.monotonic() - self._last_flush >= 1.0
            arm = not due and self._flush_timer is None
            if arm:
                # coalesced: schedule a deferred flush so a final mutation in
                # a quiet period still reaches the store without a clean
                # shutdown
                t = _threading.Timer(1.0, self._deferred_flush)
                t.daemon = True
                self._flush_timer = t
                t.start()
        if due:
            self.flush_record_store()

    def _deferred_flush(self) -> None:
        with self._flush_lock:
            self._flush_timer = None
        self.flush_record_store()

    def flush_record_store(self) -> None:
        import time as _time

        with self._flush_lock:
            store = self.record_store
            if store is None or not self._dirty:
                return
            if self._flush_timer is not None:
                self._flush_timer.cancel()
                self._flush_timer = None
            from siddhi_tpu.observability.metrics import timed

            try:
                with timed(self.flush_latency):
                    rows = self.rows()
                    store.on_change(rows)
            except Exception as e:
                # @OnError on the table owns flush failures too (a record
                # store outage must not poison the mutating dispatch or the
                # deferred-flush timer thread); the table stays dirty so
                # the next flush retries
                if self.fault_policy is None:
                    raise
                import logging

                log = logging.getLogger(__name__)
                # flush failures are NOT stored even under STORE: the table
                # stays dirty and the next flush retries with the full
                # current rows, so nothing is lost — while a stored flush
                # entry carries no events and no input stream (sink_ref),
                # can never be replayed or purged, and a sustained outage
                # would flood the FIFO store, evicting genuinely
                # replayable entries. STORE applies to MUTATION failures
                # (wired by the app runtime around the mutating dispatch,
                # with the query's input batch attached).
                log.error(
                    "table '%s': record-store flush failed (@OnError "
                    "action='%s'); the table stays dirty and the next "
                    "flush retries: %s", self.table_id, self.fault_policy, e,
                )
                return
            self._dirty = False
            self._last_flush = _time.monotonic()

    def close_record_store(self) -> None:
        """Final flush + disconnect; later flush attempts become no-ops."""
        self.flush_record_store()
        with self._flush_lock:
            store, self.record_store = self.record_store, None
            if self._flush_timer is not None:
                self._flush_timer.cancel()
                self._flush_timer = None
        if store is not None:
            store.disconnect()

    # ---- state ------------------------------------------------------------

    # columns carrying a sorted index in state (set at query-compile time by
    # enable_index; a table never probed through an index must not pay an
    # O(C log C) sort per ingest batch). Reference analog: the
    # IndexEventHolder's per-column TreeMap/HashMap indexes
    # (table/holder/IndexEventHolder.java:59-110), here one sorted
    # permutation per column + a duplicate flag (the probe path requires
    # currently-unique keys; duplicates fall back to the dense compare).
    _indexed_cols: tuple = ()

    def describe_state(self) -> dict:
        """Introspection: live row count, capacity, index wiring (see
        observability/introspect.py). One host read per call."""
        import numpy as np

        d: dict = {
            "capacity": self.capacity,
            "primary_keys": list(self.primary_keys),
            "indexes": list(self._indexed_cols),
            "record_store": self.record_store is not None,
        }
        try:
            with self.lock:
                d["rows"] = int(np.asarray(self.state["valid"]).sum())
        except Exception:
            d["rows"] = None  # mid-dispatch buffer churn: degrade
        return d

    @property
    def _pk_indexed(self) -> bool:
        return (
            len(self.primary_keys) == 1
            and self.primary_keys[0] in self._indexed_cols
        )

    def enable_index(self, col: str) -> None:
        """Called at query-compile time when an equality probe on `col`
        compiles (PK update, @Index column, or auto-indexed equality update
        probe); upgrades live state in place."""
        if col in self._indexed_cols:
            return
        if col not in self.schema.attr_names:
            raise SiddhiAppCreationError(
                f"table '{self.table_id}': cannot index undefined column '{col}'"
            )
        self._indexed_cols = tuple(self._indexed_cols) + (col,)
        with self.lock:
            self.state = self._rebuild_index(dict(self.state), col)

    def enable_pk_index(self) -> None:
        if len(self.primary_keys) == 1:
            self.enable_index(self.primary_keys[0])

    def init_state(self):
        c = self.capacity
        st = {
            "cols": {
                n: jnp.zeros((c,), a.dtype)
                for n, a in self.schema.empty_batch(1).cols.items()
            },
            "ts": jnp.zeros((c,), jnp.int64),
            "valid": jnp.zeros((c,), jnp.bool_),
            "seq": jnp.full((c,), jnp.iinfo(jnp.int64).max, jnp.int64),
            "next": jnp.zeros((), jnp.int64),
        }
        for col in self._indexed_cols:
            kd = st["cols"][col].dtype
            st[f"ix_order.{col}"] = jnp.arange(c, dtype=jnp.int32)
            st[f"ix_sorted.{col}"] = jnp.full((c,), _sort_sentinel(kd), kd)
            st[f"ix_dups.{col}"] = jnp.zeros((), jnp.bool_)
        return st

    def _rebuild_index(self, state, col: str):
        keys = state["cols"][col]
        sent = _sort_sentinel(keys.dtype)
        # valid rows first then keys ascending: a genuine max-valued key
        # still sorts before the invalid tail, so it remains findable
        order = jnp.lexsort((keys, ~state["valid"])).astype(jnp.int32)
        sk = jnp.where(state["valid"][order], keys[order], sent)
        svalid = state["valid"][order]
        dups = ((sk[1:] == sk[:-1]) & svalid[1:] & svalid[:-1]).any()
        return {
            **state,
            f"ix_order.{col}": order,
            f"ix_sorted.{col}": sk,
            f"ix_dups.{col}": dups,
        }

    def _rebuild_pk_index(self, state):
        for col in self._indexed_cols:
            state = self._rebuild_index(dict(state), col)
        return state

    def view(self, state):
        """(cols, ts, mask) — probe view, same contract as WindowStage.view."""
        return state["cols"], state["ts"], state["valid"]

    # ---- device ops (traced inside query steps) ---------------------------

    def insert(self, state, batch: EventBatch, aux: dict):
        """Insert valid CURRENT rows. Primary-key conflicts DROP the arriving
        row — first writer wins, the duplicate is discarded with a warning
        (reference: IndexEventHolder.add uses putIfAbsent and logs 'dropping
        event ... already an event stored with primary key',
        table/holder/IndexEventHolder.java:177-186). `update or insert into`
        is the overwriting form."""
        rows = batch.valid & (batch.kind == KIND_CURRENT)
        b = rows.shape[0]
        c = self.capacity

        if self.primary_keys:
            # [B, C] key equality against stored rows
            pk_match = jnp.ones((b, c), jnp.bool_)
            for k in self.primary_keys:
                pk_match = pk_match & (batch.cols[k][:, None] == state["cols"][k][None, :])
            pk_match = pk_match & rows[:, None] & state["valid"][None, :]
            # within-batch dedupe: the FIRST row per key wins the slot, later
            # duplicates are dropped like table-resident conflicts
            same_key = jnp.ones((b, b), jnp.bool_)
            for k in self.primary_keys:
                same_key = same_key & (batch.cols[k][:, None] == batch.cols[k][None, :])
            earlier_dup = same_key & rows[None, :] & (
                jnp.arange(b)[None, :] < jnp.arange(b)[:, None]
            )
            is_first = rows & ~earlier_dup.any(axis=1)
            fresh = is_first & ~pk_match.any(axis=1)
            aux["table_pk_duplicate_dropped"] = jnp.asarray(
                aux.get("table_pk_duplicate_dropped", False)
            ) | jnp.any(rows & ~fresh)
            return self._append(state, batch, fresh, aux)
        return self._append(state, batch, rows, aux)

    def _append(self, state, batch: EventBatch, rows, aux: dict):
        b = rows.shape[0]
        c = self.capacity
        # free slots in order; rows ranked by position
        free = ~state["valid"]
        n_free = free.sum()
        n_rows = rows.sum()
        aux["table_overflow"] = aux.get(
            "table_overflow", jnp.zeros((), jnp.bool_)
        ) | (n_rows > n_free)
        free_idx = first_indices(free, b)  # first B free slots
        rank = jnp.cumsum(rows.astype(jnp.int32)) - 1  # rank of each inserting row
        slot = jnp.where(rows, free_idx[jnp.clip(rank, 0, b - 1)], -1)
        ok = rows & (slot >= 0)
        # non-inserting rows scatter out of bounds and are dropped
        slot_c = jnp.where(ok, slot, c)

        def scatter(dst, src):
            # 64-bit lanes (ts/seq/long cols) ride the int32-pair scatter path
            from siddhi_tpu.ops.scatter import set_at

            return set_at(dst, slot_c, src.astype(dst.dtype))

        new_seq = state["next"] + rank
        out = {
            **state,
            "cols": {n: scatter(state["cols"][n], batch.cols[n]) for n in state["cols"]},
            "ts": scatter(state["ts"], batch.ts),
            "valid": scatter(state["valid"], jnp.ones((b,), jnp.bool_)),
            "seq": scatter(state["seq"], new_seq),
            "next": state["next"] + n_rows.astype(jnp.int64),
        }
        return self._rebuild_pk_index(out)

    def match(
        self,
        state,
        probe_cols: dict[str, jnp.ndarray],
        probe_ts,
        probe_ref: str,
        on: Optional[CompiledExpr],
        now,
        extra_probe_cols: Optional[dict] = None,
    ) -> jnp.ndarray:
        """[B, C] condition mask of probe rows against table rows."""
        b = probe_ts.shape[0]
        c = self.capacity
        if on is None:
            return jnp.broadcast_to(state["valid"][None, :], (b, c))
        env_cols = {(probe_ref, None, n): v[:, None] for n, v in probe_cols.items()}
        env_cols[(probe_ref, None, TS_ATTR)] = probe_ts[:, None]
        if extra_probe_cols:
            env_cols.update(
                {k: v[:, None] for k, v in extra_probe_cols.items()}
            )
        env_cols.update(
            {(self.table_id, None, n): v[None, :] for n, v in state["cols"].items()}
        )
        env_cols[(self.table_id, None, TS_ATTR)] = state["ts"][None, :]
        env = Env(env_cols, now=now)
        return jnp.broadcast_to(on(env), (b, c)) & state["valid"][None, :]

    def delete(self, state, batch: EventBatch, on, probe_ref, now, aux: dict):
        rows = batch.valid & (batch.kind == KIND_CURRENT)
        pair = self.match(state, batch.cols, batch.ts, probe_ref, on, now)
        doomed = (pair & rows[:, None]).any(axis=0)
        # rebuild indexes: a deleted row that shadowed a same-key duplicate
        # would otherwise make the sorted probe miss the surviving row
        return self._rebuild_pk_index(
            {**state, "valid": state["valid"] & ~doomed}
        )

    def update(
        self,
        state,
        batch: EventBatch,
        on,
        set_fns: list[tuple[str, Callable]],
        probe_ref,
        now,
        aux: dict,
        parallel_ok: bool = False,
        pk_probe=None,
        reindex_after: bool = False,
        pk_guard: Optional[str] = None,
    ):
        """Update matching table rows from each probe row.

        `parallel_ok` (decided at compile time by
        `_update_parallel_vectorizable`) selects a fully vectorized one-pass
        form: per table slot, the LAST matching probe row wins — provably
        equal to the reference's event-by-event iteration when the set
        values are independent of table state and the on-condition's table
        reads are stable under the update. Otherwise the sequential scan
        reproduces InMemoryTable.update's row-at-a-time semantics exactly."""
        rows = batch.valid & (batch.kind == KIND_CURRENT)
        if parallel_ok and pk_probe is not None:
            col, probe_fn, unique = pk_probe
            if unique:
                out = self._update_indexed(
                    state, batch, col, probe_fn, set_fns, probe_ref, now, rows
                )
            else:
                # the sorted probe is exact only while the indexed column is
                # duplicate-free; tables holding duplicates of the probed key
                # fall back to the dense all-matches compare
                def fast(st):
                    return self._update_indexed(
                        st, batch, col, probe_fn, set_fns, probe_ref, now,
                        rows,
                    )

                def dense(st):
                    return self._update_dense(
                        st, batch, on, set_fns, probe_ref, now, rows
                    )

                out = lax.cond(
                    state[f"ix_dups.{col}"], dense, fast, state
                )
            return self._rebuild_pk_index(out) if reindex_after else out
        if parallel_ok:
            out = self._update_dense(
                state, batch, on, set_fns, probe_ref, now, rows
            )
            return self._rebuild_pk_index(out) if reindex_after else out

        any_conflict0 = jnp.zeros((), jnp.bool_)

        def body(carry, xs):
            cols, any_conflict = carry
            row_cols, row_ts, row_on = xs
            env_cols = {(probe_ref, None, n): v[None] for n, v in row_cols.items()}
            env_cols[(probe_ref, None, TS_ATTR)] = row_ts[None]
            env_cols.update(
                {(self.table_id, None, n): v for n, v in cols.items()}
            )
            env_cols[(self.table_id, None, TS_ATTR)] = state["ts"]
            env = Env(env_cols, now=now)
            m = state["valid"] if on is None else (
                jnp.broadcast_to(on(env), (self.capacity,)) & state["valid"]
            )
            m = m & row_on
            if pk_guard is not None:
                # an update that REKEYS a row onto an existing primary key
                # fails atomically for this update event (the matched set is
                # left untouched) — reference: IndexOperator.update walks the
                # current key set, removes each row's old key, and aborts the
                # whole event on the first colliding add
                # (util/collection/operator/IndexOperator.java:119-161)
                kcol = cols[pk_guard]
                fn = dict(set_fns)[pk_guard]
                vals = jnp.broadcast_to(
                    fn(env).astype(kcol.dtype), (self.capacity,)
                )
                changed = m & (vals != kcol)
                n_changed = changed.sum(dtype=jnp.int32)
                i0 = jnp.argmax(changed)
                new0 = vals[i0]
                exists_other = jnp.any(
                    state["valid"] & (kcol == new0)
                    & (jnp.arange(self.capacity) != i0)
                )
                # >=2 rekeys collide with each other in the reference's
                # one-value-per-event model; per-row-varying values (our
                # extension) conservatively fail the same way
                fail = (n_changed >= 2) | ((n_changed == 1) & exists_other)
                m = jnp.where(fail, jnp.zeros_like(m), m)
                any_conflict = any_conflict | fail
            new_cols = dict(cols)
            for name, fn in set_fns:
                new_cols[name] = jnp.where(m, fn(env).astype(cols[name].dtype), cols[name])
            return (new_cols, any_conflict), None

        xs = (batch.cols, batch.ts, rows)
        (new_cols, any_conflict), _ = lax.scan(
            body, (state["cols"], any_conflict0), xs
        )
        if pk_guard is not None:
            aux["table_pk_conflict"] = (
                jnp.asarray(aux.get("table_pk_conflict", False)) | any_conflict
            )
        out = {**state, "cols": new_cols}
        return self._rebuild_pk_index(out) if reindex_after else out

    def _update_dense(self, state, batch, on, set_fns, probe_ref, now, rows):
        """Vectorized last-writer-wins update via the dense [B, C] match."""
        b = rows.shape[0]
        c = self.capacity
        pair = self.match(
            state, batch.cols, batch.ts, probe_ref, on, now
        ) & rows[:, None]
        # keep every [C]-sized intermediate 2D ([C/128, 128]): 1D
        # reductions/selects of this shape get placed in TPU scalar
        # space (S(1)) and run ~1000x slower (profiled at C=1M)
        two_d = c % 128 == 0 and c >= 128
        if two_d:
            pair = pair.reshape(b, c // 128, 128)
        writer = jnp.where(
            pair,
            jnp.arange(b, dtype=jnp.int32).reshape(
                (b, 1, 1) if two_d else (b, 1)
            ),
            -1,
        ).max(axis=0)  # last matching probe row per slot, -1 if none
        return self._apply_winner(
            state, batch, writer, two_d, set_fns, probe_ref, now
        )

    def _update_indexed(
        self, state, batch, col, probe_fn, set_fns, probe_ref, now, rows
    ):
        """O(B log C + B log B) indexed update: binary-search each probe key
        in the column's sorted index, dedupe writers with a [B] sort, and
        scatter the B set-values — everything is [B]-sized except the final
        column scatters (reference: IndexEventHolder key get/put,
        table/holder/IndexEventHolder.java:59-110). Exact when the indexed
        column is currently duplicate-free (PK uniqueness, or the caller's
        ix_dups cond guard)."""
        b = rows.shape[0]
        c = self.capacity
        keys = state["cols"][col]
        order = state[f"ix_order.{col}"]
        sk = state[f"ix_sorted.{col}"]

        env_cols = {(probe_ref, None, n): v for n, v in batch.cols.items()}
        env_cols[(probe_ref, None, TS_ATTR)] = batch.ts
        probe_raw = probe_fn(Env(env_cols, now=now))
        # cast only to LOCATE the candidate; the hit test compares under
        # numeric promotion so a fractional float probe cannot "match" the
        # integer key it truncates to (parity with the dense-compare path)
        probe = probe_raw.astype(keys.dtype)
        pos = jnp.clip(
            jnp.searchsorted(sk, probe, side="left"), 0, c - 1
        ).astype(jnp.int32)
        cand = order[pos]
        from siddhi_tpu.core.executor import _notnull

        probe_t = getattr(probe_fn, "type", self.schema.attr_types[col])
        hit = (
            rows
            & (keys[cand] == probe_raw)
            & state["valid"][cand]
            & _notnull(probe_raw, probe_t)
        )
        # last duplicate probe key wins, like the sequential iteration:
        # group probes by candidate slot (misses sort before hits), the
        # segment end is the winning probe
        idx = jnp.arange(b, dtype=jnp.int32)
        perm = jnp.lexsort((idx, hit.astype(jnp.int32), cand)).astype(
            jnp.int32
        )
        sc = cand[perm]
        seg_end = jnp.concatenate(
            [sc[1:] != sc[:-1], jnp.ones((1,), jnp.bool_)]
        )
        win_sorted = hit[perm] & seg_end
        win = jnp.zeros((b,), jnp.bool_).at[perm].set(win_sorted)

        # per-probe env: probe row beside ITS candidate table row — all [B]
        env_cols.update(
            {
                (self.table_id, None, n): v[cand]
                for n, v in state["cols"].items()
            }
        )
        env_cols[(self.table_id, None, TS_ATTR)] = state["ts"][cand]
        env = Env(env_cols, now=now)
        target = jnp.where(win, cand, c)
        new_cols = dict(state["cols"])
        from siddhi_tpu.ops.scatter import set_at

        for name, fn in set_fns:
            new_cols[name] = set_at(
                state["cols"][name], target,
                fn(env).astype(state["cols"][name].dtype),
            )
        return {**state, "cols": new_cols}

    def _apply_winner(
        self, state, batch, winner, two_d, set_fns, probe_ref, now
    ):
        """Shared tail of the vectorized update paths: gather each slot's
        winning probe row, build the per-slot env, apply the set clauses.
        `winner` is [C] (or [C/128,128] when two_d) with -1 = no match."""
        b = batch.valid.shape[0]
        c = self.capacity
        has = winner >= 0
        wi = jnp.clip(winner, 0, b - 1)
        env_cols = {(probe_ref, None, n): v[wi] for n, v in batch.cols.items()}
        env_cols[(probe_ref, None, TS_ATTR)] = batch.ts[wi]
        if two_d:
            env_cols = {k: v.reshape(c) for k, v in env_cols.items()}
            has = has.reshape(c)
        env_cols.update(
            {(self.table_id, None, n): v for n, v in state["cols"].items()}
        )
        env_cols[(self.table_id, None, TS_ATTR)] = state["ts"]
        env = Env(env_cols, now=now)
        new_cols = dict(state["cols"])
        for name, fn in set_fns:
            new_cols[name] = jnp.where(
                has, fn(env).astype(state["cols"][name].dtype),
                state["cols"][name],
            )
        return {**state, "cols": new_cols}

    def update_or_insert(
        self,
        state,
        batch: EventBatch,
        on,
        set_fns: list[tuple[str, Callable]],
        probe_ref,
        now,
        aux: dict,
        insert_names: Optional[list[str]] = None,
    ):
        """Per-probe-row: update matches, else insert the row
        (reference: InMemoryTable.updateOrAdd). `insert_names` maps probe
        columns to table columns positionally (selector output order)."""
        rows = batch.valid & (batch.kind == KIND_CURRENT)
        c = self.capacity
        # probe column feeding each table column, by position
        src_of = dict(
            zip(self.schema.attr_names, insert_names or self.schema.attr_names)
        )
        overflow0 = aux.get("table_overflow", jnp.zeros((), jnp.bool_))

        def body(carry, xs):
            cols, ts, valid, seq, nxt, ovf = carry
            row_cols, row_ts, row_on = xs
            env_cols = {(probe_ref, None, n): v[None] for n, v in row_cols.items()}
            env_cols[(probe_ref, None, TS_ATTR)] = row_ts[None]
            env_cols.update({(self.table_id, None, n): v for n, v in cols.items()})
            env_cols[(self.table_id, None, TS_ATTR)] = ts
            env = Env(env_cols, now=now)
            m = valid if on is None else (jnp.broadcast_to(on(env), (c,)) & valid)
            m = m & row_on
            hit = m.any()
            # update path
            upd_cols = dict(cols)
            for name, fn in set_fns:
                upd_cols[name] = jnp.where(m, fn(env).astype(cols[name].dtype), cols[name])
            # insert path: first free slot
            free = ~valid
            has_free = free.any()
            slot = jnp.argmax(free)
            do_insert = row_on & ~hit & has_free
            ovf = ovf | (row_on & ~hit & ~has_free)
            ins_cols = {
                n: jnp.where(
                    do_insert,
                    cols[n].at[slot].set(row_cols[src_of[n]].astype(cols[n].dtype)),
                    upd_cols[n],
                )
                for n in cols
            }
            new_ts = jnp.where(do_insert, ts.at[slot].set(row_ts), ts)
            new_valid = jnp.where(do_insert, valid.at[slot].set(True), valid)
            new_seq = jnp.where(do_insert, seq.at[slot].set(nxt), seq)
            new_next = nxt + do_insert.astype(jnp.int64)
            return (ins_cols, new_ts, new_valid, new_seq, new_next, ovf), None

        carry = (
            state["cols"], state["ts"], state["valid"], state["seq"],
            state["next"], overflow0,
        )
        xs = (batch.cols, batch.ts, rows)
        (cols, ts, valid, seq, nxt, ovf), _ = lax.scan(body, carry, xs)
        aux["table_overflow"] = ovf
        return self._rebuild_pk_index(
            {
                **state,
                "cols": cols, "ts": ts, "valid": valid, "seq": seq,
                "next": nxt,
            }
        )

    # ---- host-side convenience (tests / record-table parity) --------------

    def rows(self) -> list[tuple]:
        """Decode current contents in insertion order (host)."""
        import numpy as np

        with self.lock:
            st = self.state
        valid = np.asarray(st["valid"])
        seq = np.asarray(st["seq"])
        cols = {n: np.asarray(c) for n, c in st["cols"].items()}
        order = np.argsort(np.where(valid, seq, np.iinfo(np.int64).max), kind="stable")
        from siddhi_tpu.core.event import decode_value

        out = []
        for i in order:
            if not valid[i]:
                continue
            out.append(
                tuple(
                    decode_value(cols[n][i], t, self.interner)
                    for n, t in self.schema.attrs
                )
            )
        return out


def compile_table_output(
    output_stream,
    out_schema: StreamSchema,
    tables: dict[str, InMemoryTable],
    interner,
) -> Optional[Callable]:
    """Compile a query/store-query output stream into a table op
    `(tstates, out_batch, now, aux) -> tstates'`, or None when the output
    does not target a table (reference: OutputParser constructing
    Insert/Update/Delete/UpdateOrInsertIntoTableCallback)."""
    from siddhi_tpu.core.errors import DefinitionNotExistError
    from siddhi_tpu.query_api.execution import (
        DeleteStream,
        InsertIntoStream,
        UpdateOrInsertStream,
        UpdateStream,
    )

    target = getattr(output_stream, "target", None)

    if isinstance(output_stream, InsertIntoStream):
        if target not in tables:
            return None
        table = tables[target]
        _check_positional_schema(out_schema, table, "insert into")
        names = table.schema.attr_names
        dtypes = {n: a.dtype for n, a in table.schema.empty_batch(1).cols.items()}
        from siddhi_tpu.query_api.execution import OutputEventsFor

        want = output_stream.output_events

        def op(tstates, out_batch, now, aux, _t=table, _tid=target):
            # honor `insert [current|expired|all] events into T`
            # (reference: InsertIntoTableCallback event-type filtering)
            if want is OutputEventsFor.CURRENT:
                keep = out_batch.kind == KIND_CURRENT
            elif want is OutputEventsFor.EXPIRED:
                keep = out_batch.kind == np.int8(1)  # KIND_EXPIRED
            else:
                keep = jnp.ones_like(out_batch.valid)
            # positional mapping rides the OUT SCHEMA order, not the cols
            # dict order (jit pytree reconstruction sorts dict keys, so a
            # batch crossing a jit boundary arrives alphabetized)
            cols = {
                n: out_batch.cols[sn].astype(dtypes[n])
                for n, sn in zip(names, out_schema.attr_names)
            }
            renamed = EventBatch(
                out_batch.ts,
                jnp.zeros_like(out_batch.kind),  # inserted rows become CURRENT
                out_batch.valid & keep,
                cols,
            )
            tstates = dict(tstates)
            tstates[_tid] = _t.insert(tstates[_tid], renamed, aux)
            return tstates

        return op

    if isinstance(output_stream, (UpdateStream, DeleteStream, UpdateOrInsertStream)):
        table = tables.get(target)
        if table is None:
            raise DefinitionNotExistError(f"'{target}' is not a defined table")
        if isinstance(output_stream, UpdateOrInsertStream):
            _check_positional_schema(out_schema, table, "update or insert into")
        scope = Scope(interner)
        scope.add_stream("__out__", dict(out_schema.attrs))
        scope.add_stream(table.table_id, table.schema.attr_types)
        scope.default_ref = "__out__"
        scope.prefer_default = True
        on = (
            compile_expression(output_stream.on, scope)
            if output_stream.on is not None
            else None
        )
        if on is not None and on.type is not AttrType.BOOL:
            raise SiddhiAppCreationError("'on' must be a boolean expression")
        if isinstance(output_stream, DeleteStream):
            def op(tstates, out_batch, now, aux, _t=table, _tid=target):
                tstates = dict(tstates)
                tstates[_tid] = _t.delete(
                    tstates[_tid], out_batch, on, "__out__", now, aux
                )
                return tstates
        else:
            set_fns = compile_set_attributes(
                table, output_stream.set_attributes, scope
            )
            if isinstance(output_stream, UpdateOrInsertStream):
                ins_names = list(out_schema.attr_names)

                def op(tstates, out_batch, now, aux, _t=table, _tid=target):
                    tstates = dict(tstates)
                    tstates[_tid] = _t.update_or_insert(
                        tstates[_tid], out_batch, on, set_fns, "__out__", now,
                        aux, insert_names=ins_names,
                    )
                    return tstates
            else:
                par_ok = _update_parallel_vectorizable(
                    output_stream.on, output_stream.set_attributes,
                    table, out_schema,
                )
                # single-@PrimaryKey tables whose update writes the key
                # column take the sequential path with the atomic rekey-
                # collision guard (reference: IndexOperator.update aborts an
                # update event whose new key collides) — EXCEPT when the
                # on-clause equality-pins the written key to the same
                # expression (`on T.pk == e` with `set pk = e`): the key
                # provably cannot change, so the vectorized fast path stays
                pk_guard = None
                if len(table.primary_keys) == 1:
                    pk_col = table.primary_keys[0]
                    if pk_col in {n for n, _ in set_fns}:
                        found0 = _eq_probe_expr(
                            output_stream.on, table, out_schema
                        )
                        smap = _set_map(
                            output_stream.set_attributes, table, out_schema
                        )
                        pinned = (
                            found0 is not None
                            and found0[0] == pk_col
                            and found0[1] == smap.get(pk_col)
                        )
                        if not pinned:
                            pk_guard = pk_col
                            par_ok = False
                pk_probe = None
                if par_ok:
                    found = _eq_probe_expr(output_stream.on, table, out_schema)
                    if found is not None:
                        col, p_side = found
                        # planner decision (reference: util/collection
                        # CollectionExecutors choosing an indexed lookup):
                        # a single-column equality probe auto-indexes that
                        # column; @PrimaryKey uniqueness skips the dup guard
                        unique = table.primary_keys == [col]
                        pk_probe = (
                            col, compile_expression(p_side, scope), unique
                        )
                        table.enable_index(col)
                def op(tstates, out_batch, now, aux, _t=table, _tid=target):
                    # reindex decided at TRACE time (not compile time): later
                    # queries may have enabled more indexes by then, and an
                    # update that can rewrite an indexed column to a value
                    # the match does not pin must rebuild its sorted index
                    reindex = _index_written_unpinned(
                        output_stream.on, output_stream.set_attributes,
                        _t, out_schema,
                    )
                    tstates = dict(tstates)
                    tstates[_tid] = _t.update(
                        tstates[_tid], out_batch, on, set_fns, "__out__", now,
                        aux, parallel_ok=par_ok, pk_probe=pk_probe,
                        reindex_after=reindex, pk_guard=pk_guard,
                    )
                    return tstates

        return op

    return None


def _sort_sentinel(dtype):
    """Largest value of a column dtype (numpy, never a device const) — used
    to push invalid rows to the tail of the sorted-key view."""
    dt = np.dtype(dtype)
    if dt.kind == "f":
        return np.asarray(np.inf, dt)
    return np.asarray(np.iinfo(dt).max, dt)


def _conjuncts(e):
    from siddhi_tpu.query_api.expression import And

    if isinstance(e, And):
        yield from _conjuncts(e.left)
        yield from _conjuncts(e.right)
    else:
        yield e


def _eq_probe_expr(on_expr, table: InMemoryTable, out_schema: StreamSchema):
    """(column, probe expression) when the condition is exactly
    `T.col == <probe expr>` over one table column, else None."""
    from siddhi_tpu.query_api.expression import Compare, CompareOp, Variable

    if on_expr is None:
        return None
    conj = list(_conjuncts(on_expr))
    if len(conj) != 1 or not (
        isinstance(conj[0], Compare) and conj[0].op is CompareOp.EQ
    ):
        return None
    c = conj[0]
    for t_side, p_side in ((c.left, c.right), (c.right, c.left)):
        if (
            isinstance(t_side, Variable)
            and _reads_table(t_side, table, out_schema)
            and t_side.attribute in table.schema.attr_names
            and not _reads_table(p_side, table, out_schema)
        ):
            return t_side.attribute, p_side
    return None


def _set_map(set_attributes, table, out_schema):
    from siddhi_tpu.query_api.expression import Variable

    if set_attributes:
        return {
            sa.table_variable.attribute: sa.expression for sa in set_attributes
        }
    return {
        name: Variable(name)
        for name, _t in table.schema.attrs
        if name in out_schema.attr_names
    }


def _eq_sources(on_expr, table, out_schema):
    from siddhi_tpu.query_api.expression import Compare, CompareOp, Variable

    out: dict = {}
    if on_expr is None:
        return out
    for c in _conjuncts(on_expr):
        if isinstance(c, Compare) and c.op is CompareOp.EQ:
            for t_side, p_side in ((c.left, c.right), (c.right, c.left)):
                if (
                    isinstance(t_side, Variable)
                    and _reads_table(t_side, table, out_schema)
                    and not _reads_table(p_side, table, out_schema)
                ):
                    out[t_side.attribute] = p_side
    return out


def _index_written_unpinned(on_expr, set_attributes, table, out_schema) -> bool:
    """True when an update's set clause may change ANY indexed column to a
    value the on-condition does not pin to its current value — the sorted
    indexes must be rebuilt after such an update."""
    sm = _set_map(set_attributes, table, out_schema)
    eq = _eq_sources(on_expr, table, out_schema)
    return any(
        col in sm and eq.get(col) != sm[col]
        for col in table._indexed_cols
    )


def _reads_table(expr, table: InMemoryTable, out_schema: StreamSchema) -> bool:
    """True when an expression AST can read a column of `table` under the
    update scope (prefer_default resolves unqualified names to the output
    stream first, so a table read needs `T.col` or an attr only the table
    has)."""
    import dataclasses as _dc

    from siddhi_tpu.query_api.expression import Variable

    if isinstance(expr, Variable):
        if expr.stream_id == table.table_id:
            return True
        return (
            expr.stream_id is None
            and expr.attribute not in out_schema.attr_names
            and expr.attribute in table.schema.attr_names
        )
    if _dc.is_dataclass(expr) and not isinstance(expr, type):
        return any(
            _reads_table(getattr(expr, f.name), table, out_schema)
            for f in _dc.fields(expr)
        )
    if isinstance(expr, (list, tuple)):
        return any(_reads_table(x, table, out_schema) for x in expr)
    return False


def _update_parallel_vectorizable(
    on_expr, set_attributes, table: InMemoryTable, out_schema: StreamSchema
) -> bool:
    """Decide whether `update T on <cond> [set ...]` may run as one
    vectorized last-writer-wins pass instead of the reference's sequential
    row-at-a-time iteration. Safe iff

    1. every set VALUE is independent of table state (so the last matching
       probe row's values equal what the sequential loop would leave), and
    2. every table column the on-condition reads is either not written, or
       is written from exactly the probe expression it is equated with in a
       top-level conjunct (`on T.c == e ... set T.c = e` / the positional
       default set) — so earlier updates within the batch cannot change
       later rows' match results.
    """
    from siddhi_tpu.query_api.expression import Variable

    set_map = _set_map(set_attributes, table, out_schema)
    for src in set_map.values():
        if _reads_table(src, table, out_schema):
            return False

    # table columns read by the condition, and the equality conjuncts
    if on_expr is None:
        return True

    eq_sources = _eq_sources(on_expr, table, out_schema)

    def table_cols_read(e, acc):
        import dataclasses as _dc

        if isinstance(e, Variable):
            if _reads_table(e, table, out_schema):
                acc.add(e.attribute)
            return acc
        if _dc.is_dataclass(e) and not isinstance(e, type):
            for f in _dc.fields(e):
                table_cols_read(getattr(e, f.name), acc)
        elif isinstance(e, (list, tuple)):
            for x in e:
                table_cols_read(x, acc)
        return acc

    for col in table_cols_read(on_expr, set()):
        if col not in set_map:
            continue  # not written: always stable
        if eq_sources.get(col) != set_map[col]:
            return False  # written to a value the match does not pin
    return True


def collect_used_tables(query, tables: dict[str, InMemoryTable]) -> set[str]:
    """Table ids a query touches: `in <table>` conditions anywhere in its AST,
    table-backed join sides, and the table-output target."""
    import dataclasses as _dc

    from siddhi_tpu.query_api.execution import JoinInputStream
    from siddhi_tpu.query_api.expression import In

    used: set[str] = set()

    def walk(obj):
        if isinstance(obj, In):
            if obj.source_id in tables:
                used.add(obj.source_id)
            walk(obj.expression)
        elif _dc.is_dataclass(obj) and not isinstance(obj, type):
            for f in _dc.fields(obj):
                walk(getattr(obj, f.name))
        elif isinstance(obj, (list, tuple)):
            for x in obj:
                walk(x)
        elif isinstance(obj, dict):
            for x in obj.values():
                walk(x)

    walk(query)
    target = getattr(query.output_stream, "target", None)
    if target in tables:
        used.add(target)
    ins = query.input_stream
    if isinstance(ins, JoinInputStream):
        for s in (ins.left, ins.right):
            if s.stream_id in tables:
                used.add(s.stream_id)
    return used


def _check_positional_schema(
    out_schema: StreamSchema, table: InMemoryTable, what: str
) -> None:
    """Positional attribute mapping requires matching arity and types, with
    Java implicit numeric widening allowed (reference: DefinitionParserHelper
    validateOutputStream; StoreQueryParser coerces numeric constants into
    wider columns — e.g. an INT literal inserts into a LONG column)."""
    from siddhi_tpu.core.types import NUMERIC_TYPES, promote

    if len(out_schema.attrs) != len(table.schema.attrs):
        raise SiddhiAppCreationError(
            f"{what} table '{table.table_id}': selector emits "
            f"{len(out_schema.attrs)} attributes, table has "
            f"{len(table.schema.attrs)}"
        )
    for (on_, ot), (tn, tt) in zip(out_schema.attrs, table.schema.attrs):
        if ot is tt:
            continue
        if (
            ot in NUMERIC_TYPES
            and tt in NUMERIC_TYPES
            and promote(ot, tt) is tt
        ):
            continue  # widening coercion; the op's astype performs it
        raise SiddhiAppCreationError(
            f"{what} table '{table.table_id}': output attribute "
            f"'{on_}' is {ot.name} but table column '{tn}' is {tt.name}"
        )


def compile_set_attributes(
    table: InMemoryTable,
    set_attributes: Optional[list[UpdateSetAttribute]],
    scope: Scope,
) -> list[tuple[str, CompiledExpr]]:
    """`set T.a = expr, ...`; absent => overwrite every table column with the
    same-named output attribute (reference: InMemoryTable default update)."""
    out: list[tuple[str, CompiledExpr]] = []
    if set_attributes:
        for sa in set_attributes:
            name = sa.table_variable.attribute
            if name not in table.schema.attr_names:
                raise SiddhiAppCreationError(
                    f"set target '{name}' is not a column of '{table.table_id}'"
                )
            out.append((name, compile_expression(sa.expression, scope)))
    else:
        from siddhi_tpu.query_api.expression import Variable

        for name, _t in table.schema.attrs:
            try:
                out.append((name, compile_expression(Variable(name), scope)))
            except KeyError:
                continue  # no same-named output attribute: column untouched
    return out
