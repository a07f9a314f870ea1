"""Pattern/sequence query runtime: NFA token table + selector as one jitted step.

Reference analog: the per-query object graph built by
util/parser/StateInputStreamParser.java + QueryParser.java for state streams,
with Pattern*ProcessStreamReceiver per input stream. Here each input stream gets
its own jitted step `(state, batch, now) -> (state', out, aux)` sharing the same
token-table state; TIMER delivery for absent states is a third step variant.
"""

from __future__ import annotations

import threading
from typing import Optional

import jax
import jax.lax as lax
import jax.numpy as jnp
import numpy as np

from siddhi_tpu.core.errors import SiddhiAppCreationError
from siddhi_tpu.core.event import EventBatch, KIND_TIMER, StreamSchema
from siddhi_tpu.core.flow import Flow
from siddhi_tpu.core.pattern import NO_TIMER, PatternProgram
from siddhi_tpu.core.query_runtime import BaseQueryRuntime
from siddhi_tpu.core.selector import CompiledSelector
from siddhi_tpu.core.types import InternTable
from siddhi_tpu.query_api.execution import Query, StateInputStream


class PatternQueryRuntime(BaseQueryRuntime):
    def __init__(
        self,
        query: Query,
        query_id: str,
        schemas: dict[str, StreamSchema],
        interner: InternTable,
        group_capacity: Optional[int] = None,
        token_capacity: int = 128,
        count_capacity: int = 8,
        batch_size: int = 64,
        tables: Optional[dict] = None,
        pattern_chunk: Optional[int] = None,
    ):
        self._pattern_chunk = pattern_chunk
        self.query = query
        self.query_id = query_id
        state_stream = query.input_stream
        assert isinstance(state_stream, StateInputStream)
        self.prog = PatternProgram(
            state_stream,
            schemas,
            interner,
            token_capacity=token_capacity,
            count_capacity=count_capacity,
        )
        # selector/having `in <table>` conditions resolve against these
        # (pattern node filters are compiled before tables attach — the
        # reference allows them there too; that lands with the NFA env rework)
        for t in (tables or {}).values():
            self.prog.scope.add_table(t)
        # the emission buffer holds what one micro-batch may emit: two
        # matches for every row of it, or the whole table where that is
        # smaller. It no longer grows with @app:patternCapacity (a table of
        # 163,840 pending matches made a fused chunk pack 32 x 163,840 row
        # places of which a twentieth were filled); an emission that finds no
        # room raises the overflow flag and its ERROR, never dropped silently
        self.out_cap = max(batch_size, 64, min(token_capacity, 2 * batch_size))

        # select * over a pattern exposes every ref's attributes in order
        # (duplicate names require explicit projection)
        flat_attrs = []
        seen = set()
        dup = set()
        for a in self.prog.refs:
            for name, t in schemas[a.stream_id].attrs:
                if name in seen:
                    dup.add(name)
                else:
                    seen.add(name)
                    flat_attrs.append((name, t))
        if query.selector.select_all and dup:
            raise SiddhiAppCreationError(
                f"select * over this pattern is ambiguous for {sorted(dup)}; "
                "project explicitly"
            )
        # the selector resolves against a CHILD scope so its key set is known
        # exactly — those keys (plus cross-ref condition reads) are the only
        # capture lanes the token table / emission buffer materialize
        # (PatternProgram.capture_keep)
        sel_scope = self.prog.scope.child()
        self.selector = CompiledSelector(
            query.selector,
            sel_scope,
            flat_attrs,
            batch_mode=False,
            group_capacity=group_capacity,
        )
        self._sel_used_keys = frozenset(sel_scope.used_keys)
        self.prog.set_capture_readers(self._sel_used_keys)
        self._setup_output(query, query_id)
        self._attach_tables(tables, interner)
        self._scope = self.prog.scope
        self.needs_scheduler = self.prog.needs_scheduler
        self.timer_target = None
        self._steps = {
            sid: jax.jit(self._make_step(sid), donate_argnums=(0,))
            for sid in self.prog.stream_ids
        }
        self._timer_step = jax.jit(self._make_step(None), donate_argnums=(0,))
        self._census_jit = jax.jit(self._census)

    def arm_lineage(self, cfg) -> None:
        """Enable provenance recording (@app:lineage): force every ref's
        captured-timestamp lane to materialize (the emission buffer then
        carries, per match, exactly which input row filled each linearized
        slot) and surface them as `__lin.*` lanes feeding a
        PatternQueryLineage. Must run before anything traces the steps
        (capture projection memoizes at first trace); emissions are
        untouched."""
        from siddhi_tpu.core.executor import TS_ATTR
        from siddhi_tpu.observability.lineage import PatternQueryLineage

        keys = set(self._sel_used_keys)
        keys |= {(a.ref, None, TS_ATTR) for a in self.prog.refs}
        self.prog.set_capture_readers(frozenset(keys))
        self.lineage = PatternQueryLineage(
            cfg, self.query_id, self._published_kinds(),
            refs=[(a.ref, a.stream_id) for a in self.prog.refs],
        )

    # ---- device program --------------------------------------------------

    def init_state(self, now: int = 0):
        return {
            "tok": self.prog.init_state(now),
            "sel": self.selector.init_state(),
            # max TIMER timestamp already processed: next_timer never re-arms
            # a deadline at or before this (a logical-and element whose absent
            # deadline passed but whose present side is still pending would
            # otherwise re-arm the same past deadline forever)
            "timer_ts": jnp.full((), -(1 << 62), jnp.int64),
        }

    def _make_step(self, stream_id: Optional[str]):
        prog = self.prog
        from siddhi_tpu.core import pattern as pattern_mod

        kernel = None
        chunk = None
        if stream_id is not None and not pattern_mod.FORCE_SCAN:
            if prog.fast_path_ok:
                # chunks no larger than half the token table, so that the
                # lanes of the tokens one chunk completes are there for the
                # next one's arms (the scan path recycles a lane per event);
                # a table of two batches or more is stepped in one piece
                kernel, chunk = prog.apply_batch_fast, max(1, prog.T // 2)
            elif prog.count_fast_ok:
                # chunk = T*min_count keeps the no-spurious-overflow bound
                # (arming demand per chunk <= chunk/min <= T lanes) while
                # amortizing the per-chunk [B]-shaped fixed cost — bigger
                # chunks cut the kernel's gather/scatter element traffic per
                # event, the TPU wall (scalar-core, ~1 element/cycle).
                # @app:patternChunk overrides for workloads whose match rate
                # is known to be low (overflow still detected + warned).
                m0 = max(1, prog.slots[0].min_count)
                kernel = prog.apply_batch_count
                chunk = self._pattern_chunk or max(1, prog.T * m0)

        if kernel is not None:
            ker, C0 = kernel, chunk

            def fast_step(state, tstates, batch: EventBatch, now):
                out0 = prog.init_out(self.out_cap)
                B = batch.capacity
                # chunk so completed tokens free their lanes BETWEEN chunks:
                # per-chunk fork pressure is bounded by the chunk size, which
                # approximates the scan path's per-event lane recycling;
                # pad (valid=False) rather than shrink chunks so odd batch
                # sizes keep the wide vectorized shape
                C = min(B, C0)
                pad = (-B) % C
                if pad:
                    def padded(x, fill=0):
                        return jnp.concatenate(
                            [x, jnp.full((pad,) + x.shape[1:], fill, x.dtype)]
                        )

                    batch = EventBatch(
                        ts=padded(batch.ts),
                        kind=padded(batch.kind),
                        valid=padded(batch.valid, False),
                        cols={n: padded(c) for n, c in batch.cols.items()},
                    )
                    B = B + pad

                def chunk_body(carry, xs):
                    tok, out, out_n, ovf = carry
                    tok, out, out_n, ovf = ker(
                        tok, xs["ts"], xs["kind"], xs["valid"],
                        {stream_id: {n: xs[f"c.{n}"] for n in batch.cols}},
                        out, out_n, ovf, now,
                    )
                    return (tok, out, out_n, ovf), None

                xs = {
                    "ts": batch.ts.reshape(B // C, C),
                    "kind": batch.kind.reshape(B // C, C),
                    "valid": batch.valid.reshape(B // C, C),
                    **{
                        f"c.{n}": c.reshape(B // C, C)
                        for n, c in batch.cols.items()
                    },
                }
                (tok, out, _n, ovf), _ = lax.scan(
                    chunk_body,
                    (state["tok"], out0, np.int32(0), np.int32(0)),
                    xs,
                )
                # fast-path patterns have no waiting atoms -> no timers
                return self._finish_step(
                    state, tok, out, ovf, tstates, now, state["timer_ts"],
                    in_batch=batch,
                )

            return fast_step

        def step(state, tstates, batch: EventBatch, now):
            out0 = prog.init_out(self.out_cap)
            carry0 = (
                state["tok"],
                out0,
                np.int32(0),
                np.int32(0),
            )
            xs = {
                "ts": batch.ts,
                "kind": batch.kind,
                "valid": batch.valid,
                **{f"c.{n}": c for n, c in batch.cols.items()},
            }

            def body(carry, row):
                tok, out, out_n, ovf = carry
                stream_cols = (
                    {
                        stream_id: {
                            n: row[f"c.{n}"] for n in batch.cols
                        }
                    }
                    if stream_id is not None
                    else {}
                )
                tok, out, n_after, ovf = prog.apply_event(
                    tok,
                    row["ts"],
                    row["kind"],
                    row["valid"],
                    stream_cols,
                    out,
                    out_n,
                    ovf,
                    timer_seen=state["timer_ts"],
                )
                tok = {**tok, "max_row": jnp.maximum(
                    tok["max_row"], n_after - out_n
                )}
                return (tok, out, n_after, ovf), None

            (tok, out, _, ovf), _ = lax.scan(body, carry0, xs)
            timer_rows = batch.valid & (batch.kind == KIND_TIMER)
            timer_ts = jnp.maximum(
                state["timer_ts"],
                jnp.max(
                    jnp.where(timer_rows, batch.ts, -(np.int64(1) << 62))
                ),
            )
            return self._finish_step(
                state, tok, out, ovf, tstates, now, timer_ts, in_batch=batch
            )

        return step

    def _finish_step(
        self, state, tok, out, ovf, tstates, now, timer_ts, in_batch=None
    ):
        """Shared step tail: emission buffer -> selector -> table op -> aux."""
        prog = self.prog
        emit_batch = EventBatch(
            ts=out["ts"],
            kind=jnp.zeros_like(out["ts"], dtype=jnp.int8),
            valid=out["valid"],
            cols={},
        )
        flow = Flow(
            batch=emit_batch,
            ref=prog.refs[0].ref,
            now=now,
            extra_cols=prog.out_env_cols(out),
            tables=tstates,
        )
        sel_state, out_batch = self.selector.apply(state["sel"], flow)
        if self.table_op is not None:
            tstates = self.table_op(tstates, out_batch, now, flow.aux)
        aux = dict(flow.aux)
        # `ovf` counts the arms refused for want of a lane and the emissions
        # refused for want of room in this step
        aux["pattern_overflow"] = ovf > 0
        tok = {
            **tok,
            "completed": tok["completed"] + out["valid"].sum(dtype=jnp.int64),
            "refused": tok["refused"] + ovf.astype(jnp.int64),
        }
        aux["next_timer"] = prog.next_timer(tok, after=timer_ts)
        if self.lineage is not None:
            # provenance lanes: the emission buffer's per-ref capture
            # timestamps (arm_lineage forced every ts lane to materialize)
            # — extra program outputs only, emissions untouched
            from siddhi_tpu.core.event import KIND_CURRENT
            from siddhi_tpu.observability.lineage import LIN

            aux[LIN + "out_valid"] = out_batch.valid
            aux[LIN + "out_kind"] = out_batch.kind
            aux[LIN + "out_ts"] = out_batch.ts
            for i, _a in enumerate(prog.refs):
                aux[f"{LIN}p_n{i}"] = out[f"n{i}"]
                tsr = out.get(f"ts{i}")
                if tsr is not None:
                    aux[f"{LIN}p_ts{i}"] = tsr
            if in_batch is not None:
                aux[LIN + "in"] = in_batch.valid & (
                    in_batch.kind == KIND_CURRENT
                )
                aux[LIN + "in_ts"] = in_batch.ts
        return (
            {"tok": tok, "sel": sel_state, "timer_ts": timer_ts},
            tstates,
            out_batch,
            aux,
        )

    # ---- host side -------------------------------------------------------

    def receive(self, batch: EventBatch, now: int, stream_id: str):
        with self._receive_lock:
            if self.state is None:
                self.state = self._fresh(self.init_state(now))
            step = self._steps[stream_id]
            tstates = self._collect_table_states()
            with self._step_stage() as clock:
                self.state, tstates, out, aux = step(
                    self.state, tstates, batch,
                    jnp.asarray(now, dtype=jnp.int64),
                )
            # one jitted program per pattern stream: the telemetry
            # component embeds the stream id (see _observe_compile)
            self._observe_compile(
                step, (stream_id, int(batch.ts.shape[0])), clock.ns
            )
            self._writeback_table_states(tstates)
            lin = self.lineage
            if lin is not None:
                # under the receive lock: recorder order == dispatch order
                aux = self._lin_observe(lin, aux, now, tag=stream_id)
        self._warn_aux(aux)
        return out, aux

    def receive_timer(self, schema_batch: EventBatch, t_ms: int):
        with self._receive_lock:
            if self.state is None:
                self.state = self._fresh(self.init_state(t_ms))
            tstates = self._collect_table_states()
            self.state, tstates, out, aux = self._timer_step(
                self.state, tstates, schema_batch, jnp.asarray(t_ms, dtype=jnp.int64)
            )
            self._writeback_table_states(tstates)
            lin = self.lineage
            if lin is not None:
                aux = self._lin_observe(lin, aux, t_ms, tag=None)
        self._warn_aux(aux)
        return out, aux

    def describe_state(self) -> dict:
        """NFA introspection. `pattern`: how the program matches (`match`),
        the table's size and its counters, kept on the device by the step and
        read here as scalars: `tokens` (partial matches alive now), `armed`,
        `completed`, `expired`, `overflow` (arms refused for want of a lane
        plus emissions refused for want of room) and `max_emits_per_row`
        (the most matches one row has completed), all since deploy. Per
        linearized slot the instances at it, counted on the device, and the
        earliest pending absent deadline. No [T] lane comes to the host."""
        d = super().describe_state()
        prog = self.prog
        d["token_capacity"] = prog.T
        d["pattern"] = {
            "match": prog.match_kind, "token_capacity": prog.T,
            "emit_capacity": self.out_cap,
        }
        slots = []
        for s in prog.slots:
            slots.append({
                "refs": [a.ref for a in s.atoms],
                "absent": s.is_absent,
                "count": [s.min_count, s.max_count] if s.is_count else None,
            })
        if self.state is None:
            d["states"] = [dict(s, active=0) for s in slots]
            d["pattern"].update(tokens=0, armed=0, completed=0, expired=0,
                                overflow=0, max_emits_per_row=0)
            return d
        try:
            with self._receive_lock:
                tok = self.state["tok"]
                per_state, pending, deadline, counters = jax.device_get(
                    self._census_jit(tok, self.state["timer_ts"])
                )
        except Exception:
            # a concurrent donated-state dispatch (fused ingest) can delete
            # the buffers under us; introspection degrades, never raises
            d["states"] = [dict(s, active=None) for s in slots]
            return d
        d["states"] = [
            dict(s, active=int(per_state[i])) for i, s in enumerate(slots)
        ]
        d["active_instances"] = int(per_state.sum())
        d["next_deadline_ms"] = (
            int(deadline) if int(deadline) < int(NO_TIMER) else None
        )
        d["pattern"].update(
            tokens=int(pending),
            **{k: int(v) for k, v in counters.items()},
        )
        return d

    def _census(self, tok, timer_ts):
        """On the device: instances per slot, partial matches alive (tokens
        that hold a first event), the earliest deadline, and the counters."""
        prog = self.prog
        active, slot = tok["active"], tok["slot"]
        per_state = jnp.stack([
            (active & (slot == i)).sum() for i in range(len(prog.slots))
        ])
        return (
            per_state,
            (active & (tok["start_ts"] >= 0)).sum(),
            prog.next_timer(tok, after=timer_ts),
            {"armed": tok["armed"], "completed": tok["completed"],
             "expired": tok["expired"], "overflow": tok["refused"],
             "max_emits_per_row": tok["max_row"]},
        )

    def prime(self, now: int) -> dict:
        """Arm the initial token's clock (absent-at-start patterns need a timer
        before any event arrives — reference:
        AbsentStreamPreStateProcessor.start scheduling)."""
        with self._receive_lock:
            if self.state is None:
                self.state = self._fresh(self.init_state(now))
            t = self.prog.next_timer(
                self.state["tok"], after=self.state["timer_ts"]
            )
        return {"next_timer": t}
