"""Fused columnar ingest: K micro-batches per transfer + dispatch.

Reference analog: the @async Disruptor consumer batching events into
EventExchangeHolders before the query chain runs them
(stream/StreamJunction.java:262-298, util/event/handler/StreamHandler.java) —
the TPU-shaped version aggregates K whole micro-batches into ONE contiguous
host buffer, ONE host->device transfer, and ONE jitted dispatch whose
`lax.scan` runs the junction's entire subscriber fan-out over the K batches
with carried state.

Why it exists: every transfer and every dispatch pays a fixed host-side
cost (Python dispatch, PJRT submit, one device_put) whatever the batch
holds, so per-micro-batch dispatch caps throughput regardless of device
speed. Fusing K=32 batches pays that cost once per 32 (how large it is on a
directly attached chip is unmeasured) and keeps everything
else identical: the scan body decodes sub-batch k and runs the same
`_step_impl` chains the per-batch path runs, in the same order.

Engagement is conservative: the fused path is used only when nothing
host-side observes per-batch boundaries — no stream callbacks, no query
callbacks, no rate limiters, no scheduler-armed windows/patterns, no live
debugger, and the queries' insert targets have no consumers. Anything else
falls back to the per-batch path with identical semantics.

Chunk stages (encode -> h2d -> dispatch -> drain) run double-buffered by
default through core/pipeline.py: chunk N+1 is encoded into a pooled wire
buffer and device_put while chunk N's donated-state dispatch is in flight,
and deliver-mode decode+callbacks run on a bounded background drain worker
in chunk order, which awaits the readback the sender started when it
dispatched the chunk. A re-entrant send (a callback or failure
handler that sends again from inside a send) runs the same chunk loop
against the pipeline's inline side: fresh buffers, drained on the caller.
"""

from __future__ import annotations

import functools
import itertools
import threading
import time
from typing import Callable, Optional

import jax
import jax.lax as lax
import jax.numpy as jnp
import numpy as np

from siddhi_tpu.core.event import WireNarrowMisfit
from siddhi_tpu.native import (
    event_builder,
    keep_host_blocks,
    load_event_builder,
)
from siddhi_tpu.observability.profiler import (
    CAUSE_DELIVER_SET,
    CAUSE_FULL_WIDTH,
    CAUSE_TAIL_K,
    inherited_ids,
    stage,
)
from siddhi_tpu.testing import faults as _faults


class FuseEndpoint:
    """One junction subscriber in fused form.

    impl_factory() must return a pure step
    `(state, tstates, batch, now) -> (state', tstates', out, aux)` — the same
    function object the per-batch jit wraps.
    """

    def __init__(
        self,
        qr,
        impl_factory: Callable[[], Callable],
        init_state: Callable[[int], object],
        latency_tracker=None,
    ):
        self.qr = qr
        self.impl_factory = impl_factory
        self.init_state = init_state
        # in fused mode per-batch markIn/markOut is impossible (K batches run
        # in one dispatch), so the tracker records the CHUNK dispatch wall
        # time instead — the engine's actual unit of processing latency here
        self.latency_tracker = latency_tracker


class _RebuildFailed(Exception):
    """Internal: a full-width rebuild after a narrow-wire misfit failed
    mid-pipelined-send; `cause` carries the original build error."""

    def __init__(self, cause: Exception):
        super().__init__(str(cause))
        self.cause = cause


def _front_words(dv, lanes: dict) -> dict:
    """{lane: its words} of one micro-batch's deliver lanes with the `dv`
    rows moved to the front in row order (`compact_front`): a 64-bit lane as
    its two 32-bit halves, low first (the chip has no 64-bit moves; the order
    is the lane's bytes on the host), a bool as u8, every other lane as
    itself. Lanes narrower than 32 bits make the moves as int32."""
    from siddhi_tpu.ops.prefix import compact_front
    from siddhi_tpu.ops.scatter import U32Pair

    words = {}
    for name, lane in lanes.items():
        if lane.dtype == jnp.bool_:
            lane = lane.astype(jnp.uint8)
        if lane.dtype.itemsize == 8:
            pair = U32Pair.split(lane)
            words[name] = (pair.lo, pair.hi)
        else:
            words[name] = (lane,)

    def narrow(x) -> bool:
        return x.dtype.itemsize < 4 and jnp.issubdtype(x.dtype, jnp.integer)

    tmap = jax.tree_util.tree_map
    front = compact_front(
        dv, tmap(lambda x: x.astype(jnp.int32) if narrow(x) else x, words)
    )
    return tmap(lambda f, x: f.astype(x.dtype), front, words)


def _as_bytes(word) -> jnp.ndarray:
    """[R, itemsize] u8: the bytes of a packed [R] word, row by row."""
    u8 = lax.bitcast_convert_type(word, jnp.uint8)
    return u8[:, None] if u8.ndim == 1 else u8


# the least a read of a packed buffer asks for (rows, some 30 KB): under
# that a read costs its round trip whatever it carries, and a floor keeps
# the sizes read, a program each, few (`_first_read_rows`, `_note_total`)
_LEAST_READ_ROWS = 1024


def _bucket(rows: int, R: int) -> int:
    """The power of two at or above `rows`, at most `R`: the sizes a read of
    a packed buffer's rows comes in, so that few slice programs are built."""
    return min(R, 1 << max(0, int(rows - 1).bit_length()))


@functools.lru_cache(maxsize=None)
def _prefix_program(n: int, W: int):
    """The small program every read of a packed `[rows, W]` u8 buffer goes
    through: `n` rows from a row offset, laid out row-major on the device as
    one vector of `n * W` bytes. The buffer lies there with its rows as the
    minor dimension, and a slice of it read as it lies reaches the host in
    that order, to be transposed there byte by byte (some 25 ms for a
    chunk's 16.8 MB); the vector arrives dense. One program per (n, W) and
    buffer shape: `_bucket` keeps the `n` few."""

    def readback_prefix(buf, start):
        return lax.dynamic_slice_in_dim(buf, start, n, 0).reshape(-1)

    return jax.jit(readback_prefix)


def start_dense_read(buf, start: int, n: int):
    """Queue, without waiting, the read of rows `start : start + n` of a
    packed buffer: the prefix program behind whatever wrote the buffer, the
    copy to the host behind the program. Returns the vector on its device,
    for `finish_dense_read`."""
    vec = _prefix_program(n, buf.shape[1])(buf, np.int32(start))
    vec.copy_to_host_async()
    return vec


def finish_dense_read(vec, n: int) -> np.ndarray:
    """Wait for a started read's bytes and see them as the `[n, W]` u8 rows
    the decode's `.view(dtype)` lanes are cut from: a view, no copy."""
    return np.asarray(vec).reshape(n, -1)


def read_dense(buf, start: int, n: int) -> np.ndarray:
    """Rows `start : start + n` of a packed buffer, read now."""
    return finish_dense_read(start_dense_read(buf, start, n), n)


def _needs_scheduler(qr) -> bool:
    ns = getattr(qr, "needs_scheduler", False)
    if isinstance(ns, dict):
        return any(ns.values())
    return bool(ns)


class FusedJunctionIngest:
    """Per-junction fused ingest engine (built at app start)."""

    def __init__(
        self,
        app,
        junction,
        endpoints,
        chunk_batches: int = 32,
        pipeline_depth: int = 2,
        component: str = None,
        residual=None,
        share_sets=None,
        plan_group=None,
        wire_spec=None,
        wire_enabled: bool = True,
    ):
        self.app = app
        self.junction = junction
        self.endpoints = list(endpoints)
        self.K = max(2, int(chunk_batches))
        # plan-driven group mode (core/fusion_exec.py): `residual` holds the
        # junction subscribers NOT in the fused group — after a fused send
        # commits, every micro-batch is re-dispatched to them per batch, so
        # blocked (SA124) queries keep byte-identical per-batch semantics
        self.component = component or (
            f"stream.{junction.schema.stream_id}.fused"
        )
        self.residual = list(residual or [])
        self.plan_group = plan_group
        # cross-query state sharing: each share set is a list of endpoint
        # indices whose filter+window chain states are provably identical —
        # the chunk program carries ONE canonical chain per set (the first
        # member's) and every member reads it (see _build / _pack_arg0)
        self.share_sets = [list(s) for s in (share_sets or []) if len(s) >= 2]
        self._share_of = {
            i: g for g, idxs in enumerate(self.share_sets) for i in idxs
        }
        self._share_leader = {
            g: idxs[0] for g, idxs in enumerate(self.share_sets)
        }
        # surface the sharing in each member's describe_state(): one ring,
        # refcounted across the set (observability/introspect.py), and arm
        # the unshare guard: EVERY per-batch entry point that can donate a
        # member's state funnels through QueryRuntime.receive (row sends,
        # non-numeric send_columns, insert-into publishes, timer fires), so
        # the guard there — under the same app process lock the fused
        # writeback aliases chains under — is the one sound split point
        for idxs in self.share_sets:
            qids = [
                getattr(self.endpoints[i].qr, "query_id", i) for i in idxs
            ]
            for i in idxs:
                self.endpoints[i].qr.shared_ring = {
                    "queries": qids,
                    "leader": qids[0],
                    "refcount": len(idxs),
                }
                self.endpoints[i].qr._unshare_guard = self._maybe_unshare
        # True once a fused dispatch wrote back aliased chain states: the
        # per-batch path donates per-query states independently, so any
        # fall-back first un-aliases follower chains (_maybe_unshare)
        self._aliased = False
        # achieved-dispatch accounting (vs the plan's n*K -> 1 prediction)
        self.chunks_dispatched = 0
        # of those, by the depth K of the program variant that ran them
        # (`_chunk_K`: full chunks at self.K, a send's short tail at less)
        self.chunks_by_depth: dict = {}
        # the `chunk` id of the stage spans (observability/profiler.py):
        # what ties a chunk's drain-worker spans to its sender's
        self._chunk_ids = itertools.count(1)
        self.batches_fused = 0
        self.events_fused = 0
        # the drain's Event builder is compiled here, where the engine is
        # built, so that no send ever waits for a compiler
        load_event_builder()
        # and the allocator is told here to keep the chunks' host buffers
        self.host_blocks = keep_host_blocks()
        self.decode_native_rows = 0
        # per endpoint and chunk depth: (the total its last drained chunk of
        # that depth had, the slack its reads have come to need), which size
        # a chunk's first read (`_first_read_rows`; the drain writes, the
        # sender reads). By depth, because a send's short tail holds a few
        # hundred rows where a full chunk holds half a million
        self._drain_guess: dict = {}
        # chunks whose first read was started at dispatch; those of them
        # whose bytes lay on the host when the drain asked; chunks whose
        # guess fell short of their total (a top-up read). Counts for the
        # status, bumped without a lock like `chunks_dispatched`
        self.readback_started = 0
        self.readback_ready = 0
        self.readback_topups = 0
        self._fused = None
        self._fused_deliver = None
        self._disabled = False
        # wire encodings (core/wire.py): None = not chosen yet (decided at
        # the first engaged send: the static WireSpec's analyzer-chosen
        # encoders overlaid on the sampled narrow dtypes when enabled; {}
        # when wire encoding is off OR permanently after any misfit =
        # full-width wire)
        self._narrow = None
        self.wire_spec = wire_spec
        self.wire_enabled = bool(wire_enabled)
        self._lock = threading.Lock()
        # double-buffered chunk pipeline (core/pipeline.py): built lazily on
        # the first engaged send; senders serialize on _send_lock so the
        # pooled wire buffers and the drain queue see one producer
        self.pipeline_depth = max(1, int(pipeline_depth))
        self.pipeline = None
        self._send_lock = threading.Lock()
        self._sender = None  # thread holding _send_lock (re-entrancy guard)
        # compile-telemetry cause hints for the NEXT compiling dispatch,
        # keyed per program mode (deliver bool): a full-width rebuild
        # invalidates BOTH programs, and each must attribute its own
        # rebuild compile (tail-variant hints are computed per call)
        self._cause_hints: dict = {}
        # key-sharded members (parallel/keyshard.py, @app:shard axis='keys'):
        # set by _build when an endpoint's state lives on the keys mesh —
        # (per-endpoint state shardings, replicated, the sharded members'
        # KeyShardedGroupExecs). The chunk program then
        # is one program over that mesh: [D] states sharded, everything
        # else (wire, other members' states, packs) replicated. None = one
        # device, one attribute check per chunk.
        self._mesh_place = None
        # lineage (observability/lineage.py): True when any endpoint has a
        # recorder armed — the chunk program then returns stacked `__lin.*`
        # lanes consumed per micro-batch; False = one check per chunk
        self._lin_any = any(
            getattr(ep.qr, "lineage", None) is not None
            for ep in self.endpoints
        )
        ps = getattr(junction, "pipeline_stats", None)
        if ps is not None:
            ps.depth = self.pipeline_depth

    def describe_state(self) -> dict:
        """Introspection: chunking, pipeline depth/occupancy, slots in
        flight (see observability/introspect.py)."""
        d: dict = {
            "chunk_batches": self.K,
            # chunk executions since deploy by the depth of the variant
            # that ran them: {K: n}; a key below `chunk_batches` is a tail
            "chunks_by_depth": {
                str(k): n for k, n in sorted(self.chunks_by_depth.items())
            },
            "enabled": not self._disabled,
            "pipeline_enabled": True,
            "depth": self.pipeline_depth,
            "component": self.component,
            "mesh_devices": self._mesh_devices(),
            # which body `events_from_arrays` runs for a query callback's
            # rows (native/decode.cpp or Python), and how many the native
            # builder has made
            "decode": "python" if event_builder() is None else "native",
            "decode_native_rows": self.decode_native_rows,
            # a chunk's first read is started when the chunk is dispatched
            # (`_start_reads`): chunks it was started for, those of them
            # whose bytes lay on the host, dense, when the drain asked (the
            # hit share), and chunks whose prefix fell short of their rows
            # (a second, blocking read)
            "readback_started": self.readback_started,
            "readback_ready": self.readback_ready,
            "readback_topups": self.readback_topups,
            # how a read's bytes reach the host: laid out row-major on the
            # device by the program that cuts them (`_prefix_program`), so
            # that the host only views them
            "readback_layout": "dense",
            # whether glibc's allocator keeps what the chunks' large host
            # buffers free (`native.keep_host_blocks`): kept / as_set / default
            "host_blocks": self.host_blocks,
            # how the chunk program places delivered rows in its packed
            # buffer: each micro-batch's as one run (`_build`, deliver_pack)
            "pack": "slice",
        }
        gr = self.group_report()
        if gr is not None:
            d["fusedgroup"] = gr
        ps = getattr(self.junction, "pipeline_stats", None)
        if ps is not None:
            d["occupancy"] = round(ps.occupancy(), 3)
        pl = self.pipeline
        if pl is not None:
            d.update(pl.describe_state())
        if self._narrow is not None:
            # per-column wire-encoding choices + encoded-vs-logical
            # bytes/event (core/wire.py), surfaced in /status.json,
            # explain(), and /profile
            from siddhi_tpu.core.wire import wire_report

            d["wire"] = wire_report(
                self.junction.schema, getattr(self, "_keep", None),
                self._narrow, self.wire_spec,
                capacity=self.junction.batch_size,
            )
        return d

    def _mesh_devices(self) -> int:
        """Devices the chunk program runs on: the keys mesh's when a member
        is key-sharded, else 1."""
        place = self._mesh_place
        return 1 if place is None else place[1].mesh.devices.size

    def force_full_width(self) -> None:
        """Pin the wire full-width permanently, discarding any chosen
        encodings (tests; the same state a
        runtime misfit fallback lands in). The next send rebuilds the
        programs against the wide codec; call between sends only."""
        with self._lock:
            self._narrow = {}
            self._fused = None
            self._fused_deliver = None

    def group_report(self) -> Optional[dict]:
        """Achieved-vs-predicted dispatch reduction for a plan-driven fused
        group (None for the legacy whole-junction engine): chunk/batch/event
        counters, dispatches-per-chunk before/after, shared-ring refcounts.
        Surfaced through describe_state(), runtime.explain(), and /profile."""
        if self.plan_group is None:
            return None
        n = len(self.endpoints)
        rep: dict = {
            "component": self.component,
            "queries": list(self.plan_group.get("queries", ())),
            "chunks": self.chunks_dispatched,
            "batches": self.batches_fused,
            "events": self.events_fused,
            "dispatches_per_chunk_before": self.plan_group.get(
                "dispatches_per_chunk_before", n * self.K
            ),
            "dispatches_per_chunk_after": 1,
            "predicted_dispatch_reduction": self.plan_group.get(
                "est_dispatch_reduction"
            ),
        }
        if self.batches_fused:
            # per-batch equivalence: every fused micro-batch would have cost
            # one dispatch per group member on the unfused path
            rep["achieved_dispatch_reduction"] = round(
                1.0 - self.chunks_dispatched / (self.batches_fused * n), 4
            )
        if self.residual:
            rep["residual"] = [name for _fn, name in self.residual]
        if self.share_sets:
            rep["shared_state"] = [
                {
                    "queries": [
                        getattr(self.endpoints[i].qr, "query_id", i)
                        for i in idxs
                    ],
                    "refcount": len(idxs),
                }
                for idxs in self.share_sets
            ]
        return rep

    # ---- eligibility (cheap dynamic checks, every send) ------------------

    def eligible(self) -> bool:
        j = self.junction
        if j.is_async or j.stream_callbacks:
            return False
        if getattr(self.app, "_debugger", None) is not None:
            return False
        if len(j.subscribers) != len(self.endpoints) + len(self.residual):
            return False  # an uncovered subscriber is attached
        for ep in self.endpoints:
            qr = ep.qr
            if getattr(qr, "rate_limiter", None) is not None:
                return False
            # query callbacks are OK: the deliver-mode program packs outputs
            # device-side and drains them once per chunk (see _build_deliver)
            if _needs_scheduler(qr) or getattr(qr, "host_next_timer", None):
                return False
            tj = getattr(qr, "_insert_target_junction", None)
            if tj is not None and (
                tj.subscribers or tj.stream_callbacks
                or tj.on_publish_stats is not None
            ):
                return False
        return True

    def _delivery_set(self) -> frozenset:
        """Indices of endpoints whose outputs must be packed/drained."""
        return frozenset(
            i
            for i, ep in enumerate(self.endpoints)
            if getattr(ep.qr, "query_callbacks", None)
        )

    # ---- device program --------------------------------------------------

    def _compute_keep(self) -> frozenset | None:
        """Projected wire: ship only attributes some subscriber reads."""
        schema = self.junction.schema
        used: set | None = set()
        for ep in self.endpoints:
            ua = getattr(ep.qr, "used_attrs", None)
            if ua is None:
                used = None  # unknown/select * — keep everything
                break
            used |= ua
        self._keep = (
            None if used is None
            else frozenset(n for n in schema.attr_names if n in used)
        )
        return self._keep

    def _mesh_placement(self):
        """(per-endpoint state shardings, replicated, sharded members'
        execs) when an endpoint is key-sharded (its `impl` is
        KeyShardedGroupExec's shard_map step over a `[D]` state), else None.
        Share sets need a window and a key-shardable query has none, so a
        sharded member is never in one."""
        execs = [getattr(ep.qr, "_keyshard", None) for ep in self.endpoints]
        sharded = [e for e in execs if e is not None]
        if not sharded:
            return None
        repl = sharded[0].replicated
        return (
            tuple(repl if e is None else e.state_sharding for e in execs),
            repl,
            sharded,
        )

    def _place_on_mesh(self, arg0, tstates):
        """The chunk program's state arguments where the mesh program wants
        them: a no-op for states a dispatch of either path wrote back, a
        transfer for a fresh or restored one (and for the unsharded members'
        of a mixed group, which the per-batch path may have left on one
        device)."""
        state_sh, repl, _execs = self._mesh_place
        states, shared = arg0 if self.share_sets else (arg0, None)
        states = tuple(
            jax.device_put(st, sh) for st, sh in zip(states, state_sh)
        )
        if shared is not None:
            states = (states, jax.device_put(shared, repl))
        return states, jax.device_put(tstates, repl)

    def _build(self, deliver_set: Optional[frozenset] = None):
        deliver = deliver_set is not None
        B = self.junction.batch_size
        schema = self.junction.schema
        self._compute_keep()
        _encode, decode, self._wire_bytes = schema.wire_codec(
            B, self._keep, self._narrow or {}
        )
        # roofline numerators: encoded bytes ship over the link; logical
        # bytes are what the full-width packed wire would have carried
        # (int64 ts + every column at physical width) — the live
        # logical-vs-encoded gauges divide both by h2d_events
        from siddhi_tpu.core.wire import logical_row_bytes

        self._logical_row_bytes = logical_row_bytes(schema.attrs)
        impls = [ep.impl_factory() for ep in self.endpoints]
        impls_want = [ep.qr.output_events for ep in self.endpoints]
        qids = [
            getattr(ep.qr, "query_id", i) for i, ep in enumerate(self.endpoints)
        ]
        # deliver lanes ship only the out-schema columns: a lineage-armed
        # group-by step carries a __group_key__ col beside its outputs,
        # which the host deliver layout must never see
        out_names = [
            frozenset(ep.qr.out_schema.attr_names) for ep in self.endpoints
        ]
        share_of = dict(self._share_of)
        share_leader = dict(self._share_leader)
        has_share = bool(self.share_sets)

        def fused(states_all, tstates, wire, counts, bases, now):
            # with share sets, arg0 = (per-endpoint states with shared-member
            # chains STRIPPED, one canonical chain per set): the duplicate
            # ring is carried (and donated) exactly once, and every member's
            # window update reads the same buffers — XLA CSE collapses the
            # identical update computations into one
            if has_share:
                states, shared0 = states_all
            else:
                states, shared0 = states_all, ()

            def body(carry, xs):
                (sts, shr), tst = carry
                with jax.named_scope("wire_decode"):
                    batch = decode(xs[0], xs[1], xs[2])
                new_states = []
                new_shr = list(shr)
                auxes = []
                lins = []
                outs = []
                for ei, (impl, st) in enumerate(zip(impls, sts)):
                    g = share_of.get(ei)
                    if g is not None:
                        # every member consumes the PREVIOUS iteration's
                        # canonical chain — exactly what its own chain would
                        # hold, by the share-set identity invariant
                        st = dict(st)
                        st["chain"] = shr[g]
                    with jax.named_scope(f"q.{qids[ei]}"):
                        st2, tst, out, aux = impl(st, tst, batch, now)
                    if g is not None:
                        st2 = dict(st2)
                        ch = st2.pop("chain")
                        if ei == share_leader[g]:
                            new_shr[g] = ch
                    new_states.append(st2)
                    auxes.append(
                        tuple(
                            jnp.asarray(v).astype(bool).any()
                            for k, v in sorted(aux.items())
                            if k != "next_timer"
                            and not k.startswith("__lin")
                        )
                    )
                    # lineage lanes (observability/lineage.py) bypass the
                    # boolean aux reduction: the scan STACKS them across
                    # the K micro-batches for the host recorder
                    lins.append({
                        k: v for k, v in aux.items()
                        if k.startswith("__lin")
                    })
                    if deliver and ei in deliver_set:
                        # this micro-batch's deliverable rows move to the
                        # front here and are written behind the scan as one
                        # run: 2.8 ms a chunk of 32 x 65,536 rows where the
                        # chunk-wide scatter took 78 (PERF.md, PR 33). Kind-
                        # filter device-side when the query emits one kind.
                        from siddhi_tpu.core.event import (
                            KIND_CURRENT as _KC,
                            KIND_EXPIRED as _KE,
                        )
                        from siddhi_tpu.query_api.execution import (
                            OutputEventsFor as _OEF,
                        )

                        want = impls_want[ei]
                        with jax.named_scope("deliver_mask"):
                            if want is _OEF.CURRENT:
                                dv = out.valid & (out.kind == _KC)
                            elif want is _OEF.EXPIRED:
                                dv = out.valid & (out.kind == _KE)
                            else:
                                dv = out.valid & (
                                    (out.kind == _KC) | (out.kind == _KE)
                                )
                        lanes = {"ts": out.ts}
                        if want is _OEF.ALL:
                            lanes["kind"] = out.kind
                        lanes.update(
                            {
                                f"c.{n}": c
                                for n, c in out.cols.items()
                                if n in out_names[ei]
                            }
                        )
                        with jax.named_scope("deliver_pack"):
                            outs.append((
                                _front_words(dv, lanes),
                                dv.sum(dtype=jnp.int32),
                            ))
                return (
                    ((tuple(new_states), tuple(new_shr)), tst),
                    (tuple(auxes), tuple(lins), tuple(outs)),
                )

            (
                ((states, shared), tstates),
                (aux_stack, lin_stack, out_stack),
            ) = lax.scan(
                body, ((states, shared0), tstates), (wire, counts, bases)
            )
            states_out = (states, shared) if has_share else states
            aux_red = tuple(
                tuple(v.any() for v in a) for a in aux_stack
            )
            if not deliver:
                return states_out, tstates, aux_red, lin_stack, ()
            # pack each endpoint's K compacted segments into ONE contiguous
            # ROW-MAJOR byte buffer [R, row_bytes]: the host drains exactly
            # the filled row prefix with a single contiguous slice transfer
            # (per-lane buffers would need one transfer each)
            packs = []
            with jax.named_scope("deliver_pack"):
                for stacked, cnt in out_stack:
                    # micro-batch k's `cap` rows go as one run to the sum of
                    # the counts before it, in order: a run overwrites what
                    # lay behind the delivered rows of the one before, and
                    # ends inside R (its start is at most k * cap). Rows
                    # past the chunk's total are unspecified.
                    K = cnt.shape[0]  # shape-driven: one traced fn serves any K
                    start = jnp.cumsum(cnt) - cnt

                    def put(k, bufs):
                        return jax.tree_util.tree_map(
                            lambda buf, runs: lax.dynamic_update_slice(
                                buf,
                                lax.dynamic_index_in_dim(
                                    runs, k, keepdims=False
                                ),
                                (start[k],),
                            ),
                            bufs, stacked,
                        )

                    packed = lax.fori_loop(
                        0, K, put,
                        jax.tree_util.tree_map(
                            lambda runs: jnp.zeros((runs.size,), runs.dtype),
                            stacked,
                        ),
                    )
                    data_buf = jnp.concatenate(
                        [
                            _as_bytes(word)
                            for name in sorted(packed)
                            for word in packed[name]
                        ],
                        axis=1,
                    )
                    W = data_buf.shape[1]
                    # header rows carry the per-iteration counts INSIDE the
                    # buffer: the steady-state drain is then ONE d2h transfer
                    # (each is a blocking host round trip of its own)
                    cnt_u8 = jax.lax.bitcast_convert_type(
                        cnt, jnp.uint8
                    ).reshape(-1)  # [4K]
                    hdr_rows = -(-cnt_u8.shape[0] // W)
                    hdr = jnp.pad(
                        cnt_u8, (0, hdr_rows * W - cnt_u8.shape[0])
                    ).reshape(hdr_rows, W)
                    packs.append(
                        {"buf": jnp.concatenate([hdr, data_buf], axis=0)}
                    )
            return states_out, tstates, aux_red, lin_stack, tuple(packs)

        # donate the per-endpoint states (exclusively owned); tstates may
        # alias read-only findables shared with other runtimes — not donated
        place = self._mesh_place = self._mesh_placement()
        if place is None:
            prog = jax.jit(fused, donate_argnums=(0,))
        else:
            # a key-sharded member: ONE program over the keys mesh. Its [D]
            # state stays sharded (in: _place_on_mesh, out: here), the rest
            # is replicated — every device decodes the whole wire, runs the
            # unsharded members and packs, so the drain reads one device
            state_sh, repl, _execs = place
            prog = jax.jit(
                fused, donate_argnums=(0,),
                out_shardings=(
                    (state_sh, repl) if has_share else state_sh,
                    repl, repl, repl, repl,
                ),
            )
        if deliver:
            self._fused_deliver = prog
            self._deliver_set = deliver_set
            self._deliver_idx = sorted(deliver_set)
            # host-side byte layout of each endpoint's drain buffer, in the
            # same sorted-lane order the device concatenated
            from siddhi_tpu.query_api.execution import OutputEventsFor

            self._deliver_layout = []
            for ep in self.endpoints:
                qr = ep.qr
                dtypes = {
                    f"c.{n}": np.dtype(a.dtype)
                    for n, a in qr.out_schema.empty_batch(1).cols.items()
                }
                dtypes["ts"] = np.dtype(np.int64)
                if qr.output_events is OutputEventsFor.ALL:
                    dtypes["kind"] = np.dtype(np.int8)
                layout = []
                off = 0
                for name in sorted(dtypes):
                    dt = dtypes[name]
                    layout.append((name, dt, off))
                    off += dt.itemsize
                self._deliver_layout.append((layout, off))
        else:
            self._fused = prog
        self._aux_keys = [self._probe_aux_keys(i) for i in range(len(impls))]

    # ---- host side -------------------------------------------------------

    def _chunk_K(self, remaining_batches: int) -> int:
        """Smallest K variant covering the remainder: full chunks use self.K;
        a short tail picks the smallest power-of-two variant that holds it, so
        chunk-granularity producers stay on the fused path without paying a
        full K-iteration scan of empty batches. jax.jit retraces per wire
        shape, so each variant compiles once and is cached — a workload whose
        tail sizes alternate pays each variant's one-time compile the first
        time that tail size appears mid-traffic (at most log2(K) compiles)."""
        if remaining_batches >= self.K:
            return self.K
        k = 2
        while k < remaining_batches:
            k *= 2
        return min(k, self.K)

    def try_send(self, timestamps, cols, now: int, send_id=None) -> bool:
        """Attempt fused ingest of the whole call. Returns False to make the
        caller fall back to the per-batch path. `send_id` is the junction's
        number for this call (the `send` id of its stage spans)."""
        n = len(timestamps)
        B = self.junction.batch_size
        # engage for any call of at least two micro-batches: shorter tails
        # ride a smaller-K variant of the same program (see _chunk_K)
        if n < 2 * B or self._disabled or not self.eligible():
            return False
        dset = self._delivery_set()
        deliver = bool(dset)
        ts_arr = np.asarray(timestamps)
        if n and int(ts_arr.max()) - int(ts_arr.min()) >= (1 << 31):
            return False  # int32 ts-delta wire can't span >24 days per call
        with self._lock:
            if deliver and getattr(self, "_deliver_set", None) != dset:
                if self._fused_deliver is not None:
                    self._cause_hints[True] = CAUSE_DELIVER_SET
                self._fused_deliver = None  # callback set changed: rebuild
            if (self._fused_deliver if deliver else self._fused) is None:
                try:
                    if self._narrow is None:
                        # wire-encoding decision at first engagement
                        # (core/wire.py): the static WireSpec's
                        # analyzer-chosen encoders (dict/delta/range-narrow/
                        # bitpack) overlaid on dtypes sampled from the first
                        # micro-batch; {} (full width) when disabled. Any
                        # later misfit rebuilds full-width (once).
                        from siddhi_tpu.core.wire import choose_encodings

                        self._narrow = choose_encodings(
                            self.junction.schema, self._compute_keep(),
                            self.wire_spec, self.wire_enabled,
                            ts_arr[:B],
                            {k: np.asarray(v)[:B] for k, v in cols.items()},
                        )
                    self._build(deliver_set=dset if deliver else None)
                except Exception:
                    import logging

                    logging.getLogger(__name__).warning(
                        "fused ingest disabled for stream '%s' (build failed)",
                        self.junction.schema.stream_id, exc_info=True,
                    )
                    self._disabled = True
                    return False
            # snapshot the (program, encode) PAIR under the lock: a misfit
            # rebuild in another thread swaps both _narrow and the programs,
            # and an unlocked read could pair a full-width encode with the
            # old narrow-decoding program (silent corruption)
            prog = self._fused_deliver if deliver else self._fused
            encode, _decode, _nb = self.junction.schema.wire_codec(
                B, self._keep, self._narrow or {}
            )

        if send_id is None:
            send_id = next(self.junction.send_ids)
        with stage(
            "send", send=send_id, stream=self.junction.schema.stream_id,
            rows=n, path="fused",
        ):
            return self._send_engaged(
                prog, encode, deliver, dset, ts_arr, cols, n, B, now
            )

    def _send_engaged(
        self, prog, encode, deliver, dset, ts_arr, cols, n, B, now
    ) -> bool:
        """The engaged send: program and codec are chosen, pick the side of
        the pipeline the chunk loop runs against."""
        pl = self._pipeline()
        me = threading.current_thread()
        if pl.is_drain_thread() or self._sender is me:
            # re-entrant: a query callback that sends again from the drain
            # worker must not wait on the pipeline it is draining; neither
            # must the thread that already holds the send lock (a failure
            # handler run on the sending thread). The pooled wire slots and
            # the drain queue belong to the outer send, so this one runs
            # the loop against the pipeline's inline side.
            return self._send_committed(
                prog, encode, deliver, dset, ts_arr, cols, n, B, now,
                pl.inline(),
            )
        with self._send_lock:
            self._sender = me
            try:
                return self._send_committed(
                    prog, encode, deliver, dset, ts_arr, cols, n, B, now, pl
                )
            finally:
                self._sender = None

    def _send_committed(
        self, prog, encode, deliver, dset, ts_arr, cols, n, B, now, side
    ) -> bool:
        """The chunk loop against `side` of the pipeline and, once it has
        committed, the side records of the send. A method of its own and
        not the tail of `_send_engaged`: the chunk program is first called,
        and so lowered, under these frames, and with one frame fewer every
        process's first send ran longer (13 s of `q1-plug.bulk`'s set-up on
        the chip; PERF.md, PR 46)."""
        if not self._send_chunks(
            prog, encode, deliver, dset, ts_arr, cols, n, B, now, side
        ):
            # nothing committed: the caller re-sends the same events through
            # the per-batch path, whose publish_batch records them —
            # recording here too would record them twice
            return False
        # the fused path never materializes an EventBatch host-side, so the
        # flight recorder, the black-box ring and the lineage stamp record
        # straight from the (host, physical) columns, once per committed
        # send: the fused commit is this send's one publish
        j = self.junction
        for ring in (j.flight, j.blackbox, j.lineage):
            if ring is not None:
                ring.record_columns(ts_arr, cols, n)
        if self.residual:
            # fused chunks committed (group callbacks delivered at the chunk
            # loop's barrier); now the blocked consumers get the same events
            # per batch, preserving their unfused semantics
            self._residual_dispatch(ts_arr, cols, n, now)
        return True

    def _pipeline(self):
        pl = self.pipeline
        if pl is None:
            from siddhi_tpu.core.pipeline import IngestPipeline

            pl = self.pipeline = IngestPipeline(
                self.junction, depth=self.pipeline_depth,
                drain_fn=self._drain,
            )
            pl.stats = getattr(self.junction, "pipeline_stats", None)
        # the wire goes where the chunk program runs: replicated on the
        # keys mesh when a member is key-sharded (set by _build, which every
        # engaged send has been through by now)
        place = self._mesh_place
        pl.wire_sharding = None if place is None else place[1]
        return pl

    def close(self) -> None:
        """Stop the pipeline's drain worker (app shutdown). Serialized with
        senders so no in-flight send can enqueue behind the stop sentinel
        and strand its barrier."""
        with self._send_lock:
            pl = self.pipeline
            if pl is not None:
                pl.close()

    def _rebuild_full_width(self, deliver: bool, dset):
        """A value outgrew the sampled narrow wire: rebuild the fused program
        full-width (once, permanent). Program and encode are swapped under
        the same lock so no reader pairs a full-width encode with the old
        narrow-decoding program. Raises on rebuild failure (caller disables
        the fused path)."""
        with self._lock:
            self._narrow = {}
            self._fused = None
            self._fused_deliver = None
            self._build(deliver_set=dset if deliver else None)
            prog = self._fused_deliver if deliver else self._fused
            encode, _decode, _nb = self.junction.schema.wire_codec(
                self.junction.batch_size, self._keep, {}
            )
            # both programs were discarded: each mode's next compile is
            # rebuild-caused
            self._cause_hints[False] = CAUSE_FULL_WIDTH
            self._cause_hints[True] = CAUSE_FULL_WIDTH
        return prog, encode

    def _dispatch_chunk(
        self, prog, wire, counts, bases, now,
        ps=None, wf=None, deliver=False, chunk=None,
    ):
        """One donated-state dispatch under the app lock: collect states,
        run the program, write back, publish stats, surface aux flags.
        Returns (packs, completion) — completion is one device output of
        the dispatch, whose readiness implies the program (and so its read
        of the wire buffer) finished; the pipelined path hands it to
        IngestPipeline.retire. On a dispatch failure owned by the
        junction's exception handler returns (None, None) and the caller
        skips to the next chunk, like per-batch send_columns would."""
        # observability hooks: the junction's device-budget tracker and
        # tracer. None when statistics are off: one check each per chunk.
        j = self.junction
        ds = j.device_stats
        tr = j.tracer
        lock = self.app._process_lock
        with stage("lock_wait", chunk=chunk):
            lock.acquire()
        try:
            states = []
            for ep in self.endpoints:
                if ep.qr.state is None:
                    ep.qr.state = ep.qr._fresh(ep.init_state(now))
                states.append(ep.qr.state)
            arg0 = self._pack_arg0(states)
            tstates = {}
            ep_tids = []
            for ep in self.endpoints:
                ts_ep = ep.qr._collect_table_states()
                ep_tids.append(list(ts_ep))
                tstates.update(ts_ep)
            if self._mesh_place is not None:
                arg0, tstates = self._place_on_mesh(arg0, tstates)
                for ks in self._mesh_place[2]:
                    ks.path = "fused"
            span = (
                tr.start_span(
                    f"stream.{j.schema.stream_id}", int(counts.sum())
                )
                if tr is not None
                else None
            )
            # the chunk is the unit of processing here, so the endpoints'
            # latency trackers record the chunk's dispatch wall time
            clock = stage(
                "dispatch", ds and ds.step, ps and ps.dispatch,
                *(
                    ep.latency_tracker
                    for ep in self.endpoints
                    if ep.latency_tracker is not None
                ),
                wf=wf, chunk=chunk,
            )
            try:
                # fault-injection site `device_dispatch` (testing/faults.py):
                # inside the try so an injected failure rides the exact
                # donated-state reset + junction-failure-policy path a real
                # chunk-program explosion takes
                if _faults.ACTIVE is not None:
                    _faults.ACTIVE.check("device_dispatch", self.component)
                with clock:
                    new_all, tstates, aux_red, lin_stack, packs = prog(
                        arg0, tstates, wire,
                        counts, bases, np.int64(now),
                    )
                if ds is not None:
                    ds.h2d_bytes.add(int(wire.nbytes))
                    ds.h2d_chunks.add(1)
                    # live roofline numerator/denominator pair: the
                    # always-on wire bytes/event gauge rides these
                    n_ev = int(counts.sum())
                    ds.h2d_events.add(n_ev)
                    # logical-vs-encoded split (core/wire.py): what the
                    # full-width wire would have shipped for the same
                    # events, so the encoded gauge has a denominator
                    ds.h2d_logical.add(n_ev * self._logical_row_bytes)
                ct = self.junction.compile_telemetry
                if ct is not None and clock.ns:
                    # fused compile telemetry: the chunk program retraces
                    # per (K, wire width); rebuild paths leave a cause
                    # hint, short tails are tail-variant compiles
                    K = int(counts.shape[0])
                    hint = self._cause_hints.pop(deliver, None)
                    if hint is None and K < self.K:
                        hint = CAUSE_TAIL_K
                    ct.observe(
                        self.component + ("_deliver" if deliver else ""),
                        prog, (K, int(wire.shape[1])), clock.ns,
                        cause_hint=hint,
                    )
            except Exception as e:
                # the call donated the state buffers: they are gone either
                # way, so reset to fresh state (lazily re-initialized on
                # the next receive) instead of leaving every later send
                # crashing on deleted arrays; then honor the junction's
                # failure policy like the per-batch path does (which
                # drops at most the failing batch and keeps going)
                for ep in self.endpoints:
                    ep.qr.state = None
                self._aliased = False
                handler = self.junction.exception_handler
                if handler is None:
                    raise
                handler(e)
                return None, None
            finally:
                if span is not None:
                    tr.end_span(span)
            self._writeback_states(new_all)
            for ep, tids in zip(self.endpoints, ep_tids):
                ep.qr._writeback_table_states(
                    {tid: tstates[tid] for tid in tids}
                )
        finally:
            lock.release()
        self.chunks_dispatched += 1
        K = int(counts.shape[0])
        self.chunks_by_depth[K] = self.chunks_by_depth.get(K, 0) + 1
        self.batches_fused += K
        self.events_fused += int(counts.sum())
        if self.junction.on_publish_stats is not None:
            self.junction.on_publish_stats(int(counts.sum()))
        for i, ep in enumerate(self.endpoints):
            flags = dict(zip(self._aux_keys[i], aux_red[i]))
            if flags:
                ep.qr._warn_aux(flags)
        if self._lin_any:
            # provenance readback (one d2h when lineage is on): feed each
            # armed endpoint's recorder per micro-batch, in chunk order
            self._lin_observe_chunk(lin_stack, counts, now)
        # completion: ONLY leaves that are never donated to a later dispatch
        # (aux flags, output packs, table states). The query states are
        # donated at the NEXT dispatch's submit — which deletes the array
        # long before THIS dispatch completes, so gating a wire slot on one
        # would free the buffer while the program still reads it. With no
        # such leaf the caller gets None and retire() abandons the aliased
        # buffer instead of reusing it.
        leaves = jax.tree_util.tree_leaves((aux_red, packs, tstates))
        return packs, (leaves[0] if leaves else None)

    # ---- lineage observation (observability/lineage.py) ------------------

    def _lin_observe_chunk(self, lin_stack, counts, now) -> None:
        """Feed each armed endpoint's recorder the chunk's stacked `__lin.*`
        lanes, one micro-batch at a time."""
        import numpy as _np

        K = int(counts.shape[0])
        for i, ep in enumerate(self.endpoints):
            lin = getattr(ep.qr, "lineage", None)
            stacks = lin_stack[i] if i < len(lin_stack) else None
            if lin is None or not stacks:
                continue
            host = {k: _np.asarray(v) for k, v in stacks.items()}
            tag = getattr(ep, "lineage_tag", None)
            for k in range(K):
                if int(counts[k]) == 0:
                    continue  # padding iteration: no valid rows
                lanes = {kk: v[k] for kk, v in host.items()}
                try:
                    lin.observe(lanes, now, tag)
                except Exception:  # provenance must never break dispatch
                    import logging

                    logging.getLogger(__name__).debug(
                        "fused lineage observe failed", exc_info=True
                    )

    # ---- cross-query state sharing (plan share sets) ---------------------

    def _pack_arg0(self, full_states):
        """Program arg0 from the per-endpoint full states: with share sets,
        shared members' chains are stripped and each set's canonical chain
        (the leader's) rides once — so the shared ring's buffers are donated
        exactly once per dispatch."""
        if not self.share_sets:
            return tuple(full_states)
        stripped = tuple(
            {k: v for k, v in st.items() if k != "chain"}
            if i in self._share_of else st
            for i, st in enumerate(full_states)
        )
        shared = tuple(
            full_states[idxs[0]]["chain"] for idxs in self.share_sets
        )
        return (stripped, shared)

    def _writeback_states(self, new_all) -> None:
        """Write the program's output states back onto the runtimes; shared
        members get the canonical chain re-attached (ALIASED across the set
        — one ring serves every member until _maybe_unshare splits it)."""
        if not self.share_sets:
            for ep, st in zip(self.endpoints, new_all):
                ep.qr.state = st
            return
        new_states, new_shared = new_all
        for i, (ep, st) in enumerate(zip(self.endpoints, new_states)):
            g = self._share_of.get(i)
            if g is not None:
                st = {**st, "chain": new_shared[g]}
            ep.qr.state = st
        self._aliased = True

    def _maybe_unshare(self) -> None:
        """Split aliased chain states before any per-batch dispatch can
        donate them: each per-query jitted step donates its own state, and
        two runtimes donating the SAME ring buffers would use-after-free.
        Followers get a device copy; by the share-set identity invariant the
        values stay equal, so a later fused send re-shares losslessly.

        Called from each member's QueryRuntime.receive (the `_unshare_guard`
        hook) INSIDE the app process lock — the lock the fused dispatch's
        writeback aliases chains under — so the check cannot race an
        in-flight fused send: either the writeback happened-before (the
        guard splits here) or happens-after (our per-batch step ran on
        unaliased state). Only share-set members pay the call; the lock is
        an RLock the receive path already holds."""
        with self.app._process_lock:
            if not self._aliased:
                return
            self._aliased = False
            for idxs in self.share_sets:
                for i in idxs[1:]:
                    qr = self.endpoints[i].qr
                    st = qr.state
                    if st is None or "chain" not in st:
                        continue
                    st = dict(st)
                    st["chain"] = jax.tree_util.tree_map(
                        lambda x: jnp.array(x, copy=True)
                        if hasattr(x, "dtype") else x,
                        st["chain"],
                    )
                    qr.state = st

    # ---- residual per-batch dispatch (blocked queries) -------------------

    def _residual_dispatch(self, ts_arr, cols, n: int, now: int) -> None:
        """Re-dispatch the committed send per micro-batch to the junction
        subscribers OUTSIDE the fused group (the plan's SA124-blocked
        queries, aggregations): their per-batch semantics — rate limiters,
        schedulers, observed insert targets — are preserved exactly, while
        the group still collapsed its own n*K dispatches into one per chunk.
        Events were already flight-recorded and throughput-counted by the
        fused commit; dispatch_subset skips both."""
        j = self.junction
        B = j.batch_size
        encode, decode = j.schema.packed_codec(B)
        for ofs in range(0, n, B):
            end = min(ofs + B, n)
            m = end - ofs
            buf = encode(
                ts_arr[ofs:end],
                {k: v[ofs:end] for k, v in cols.items()},
                m,
            )
            j.dispatch_subset(decode(buf, np.int32(m)), now, self.residual)

    def _send_chunks(
        self, prog, encode, deliver, dset, ts_arr, cols, n, B, now, pl
    ) -> bool:
        """THE chunk loop of a fused send, written against the pipeline's
        verbs (core/pipeline.py). `pl` decides where a chunk's wire buffer
        comes from and where its drain runs. The IngestPipeline: chunk N+1
        is encoded into a pooled buffer and device_put while chunk N's
        dispatch is in flight, and deliver-mode drains run on its bounded
        worker in chunk order. Its inline side (a re-entrant send): fresh
        buffers, each chunk drained on this thread once the next one is
        dispatched. Either way delivery is flushed before returning, so
        callbacks fire in chunk order and complete before the send does."""
        ps = pl.stats
        wall0 = time.perf_counter_ns() if ps is not None else 0
        err = None
        dispatched = False
        try:
            c_off = 0
            staged, c_off, prog, encode = self._stage_chunk(
                pl, prog, encode, deliver, dset, ts_arr, cols,
                c_off, n, B, ps,
            )
            while staged is not None:
                dev_wire, counts, bases, K, slot, wf, chunk = staged
                staged = None
                packs, completion = self._dispatch_chunk(
                    prog, dev_wire, counts, bases, now,
                    ps, wf=wf, deliver=deliver, chunk=chunk,
                )
                pl.retire(slot, completion)
                dispatched = True
                if deliver and packs is not None:
                    # hand the packs to the drain BEFORE staging the next
                    # chunk: nothing downstream can lose them, and the
                    # worker's decode overlaps the encode below. Their first
                    # read is started here, behind the program just
                    # dispatched, so that it runs while the drain is still
                    # busy with the chunk before
                    reads = self._start_reads(packs, K, chunk)
                    pl.submit(packs, reads, K, wf, chunk)
                elif wf is not None:
                    prof = self.junction.profiler
                    if prof is not None:
                        prof.end(wf)
                if deliver and pl.pending_error():
                    # an unguarded delivery failure is waiting at the
                    # barrier: stop ingesting further chunks (inline, the
                    # drain raised out of submit() instead)
                    break
                if c_off < n:
                    # overlap: this encode + h2d ride alongside the
                    # in-flight dispatch above
                    staged, c_off, prog, encode = self._stage_chunk(
                        pl, prog, encode, deliver, dset, ts_arr, cols,
                        c_off, n, B, ps,
                    )
        except _RebuildFailed as rf:
            err = rf
        except BaseException as e:
            err = e
        # always flush delivery before returning or raising: callbacks fire
        # in chunk order and complete before send_columns returns
        try:
            pl.barrier()
        except Exception as be:
            if err is None:
                err = be
        if wall0:
            ps.add_wall(time.perf_counter_ns() - wall0)
        if isinstance(err, _RebuildFailed):
            if not dispatched:
                return False  # nothing ingested: per-batch fallback
            handler = self.junction.exception_handler
            if handler is None:
                raise err.cause
            handler(err.cause)
            return True
        if err is not None:
            raise err
        return True

    def _stage_chunk(
        self, pl, prog, encode, deliver, dset, ts_arr, cols, c_off, n, B, ps
    ):
        """Encode the next chunk into a wire buffer of `pl` and start its
        async h2d transfer. Returns ((dev_wire, counts, bases, K, slot, wf,
        chunk), next_off, prog, encode) — prog/encode may have been swapped by a
        full-width rebuild on a narrow-wire misfit; the caller must
        pl.retire(slot, ...) once the chunk's dispatch is submitted."""
        K = self._chunk_K(-(-(n - c_off) // B))
        c_end = min(c_off + K * B, n)
        prof = self.junction.profiler
        wf = (
            prof.begin(self.junction.schema.stream_id, c_end - c_off)
            if prof is not None
            else None
        )
        chunk = next(self._chunk_ids)
        enc = ps and ps.encode
        slot = pl.acquire(K, self._wire_bytes, chunk)
        try:
            wire, counts, bases = self._encode_chunk(
                encode, ts_arr, cols, c_off, c_end, B, K, slot.buf,
                enc, wf, chunk,
            )
        except WireNarrowMisfit:
            # drain everything first: the pending packs were produced by the
            # narrow program and must decode under the OLD deliver layout
            pl.barrier()
            try:
                prog, encode = self._rebuild_full_width(deliver, dset)
            except Exception as e:
                import logging

                logging.getLogger(__name__).warning(
                    "fused ingest disabled for stream '%s' (full-width "
                    "rebuild failed)", self.junction.schema.stream_id,
                    exc_info=True,
                )
                self._disabled = True
                raise _RebuildFailed(e) from e
            slot = pl.acquire(K, self._wire_bytes, chunk)
            wire, counts, bases = self._encode_chunk(
                encode, ts_arr, cols, c_off, c_end, B, K, slot.buf,
                enc, wf, chunk,
            )
        with stage(
            "h2d", ps and ps.h2d, wf=wf, chunk=chunk,
            devices=self._mesh_devices(),
        ):
            dev_wire = pl.ship(slot)
        return (
            (dev_wire, counts, bases, K, slot, wf, chunk), c_end, prog, encode
        )

    def _encode_chunk(
        self, encode, ts_arr, cols, c_off, c_end, B, K, out,
        tracker=None, wf=None, chunk=None,
    ):
        """Encode one K-batch chunk into `out`, the [K, bytes] wire buffer
        the pipeline handed out: the `encode` stage of the chunk loop."""
        with stage("encode", tracker, wf=wf, chunk=chunk):
            counts = np.zeros((K,), dtype=np.int32)
            bases = np.zeros((K,), dtype=np.int64)
            for k in range(K):
                lo = c_off + k * B
                hi = min(lo + B, c_end)
                m = max(hi - lo, 0)
                counts[k] = m
                if m > 0:
                    buf, base = encode(
                        ts_arr[lo:hi],
                        {kk: v[lo:hi] for kk, v in cols.items()},
                        m,
                    )
                    bases[k] = base
                    out[k, :] = buf
                else:
                    out[k, :] = 0
            return out, counts, bases

    def _start_reads(self, packs, K: int, chunk=None) -> dict:
        """Start the first read of one dispatched chunk's packed outputs, on
        the sender's thread, waiting for nothing: {endpoint: _start_read's
        tuple} for the endpoints that have callbacks (a chunk nobody listens
        to starts nothing). The prefix program is queued behind the chunk
        program on the device, the copy behind it and the host's part behind
        both on the pipeline's reader thread, so the read runs while the
        drain is still building the `Event`s of the chunk before; `_drain`
        awaits it. The `readback_start` stage."""
        reads = {}
        with stage("readback_start", chunk=chunk):
            for i, pack in zip(self._deliver_idx, packs):
                if not getattr(self.endpoints[i].qr, "query_callbacks", None):
                    continue
                try:
                    reads[i] = self._start_read(i, pack, K)
                except Exception:
                    # not this thread's failure to report: the drain starts
                    # the read again and meets what went wrong with the
                    # chunk where it always has, at its own read
                    continue
        if reads:
            self.readback_started += 1
        return reads

    def _start_read(self, i: int, pack, K: int):
        """Queue, without waiting, endpoint `i`'s first read of a chunk's
        packed buffer: ONE round trip in the steady state, because the
        buffer's header rows carry the per-iteration counts and the prefix
        is sized from the total of the endpoint's last drained chunk (all
        `R` rows when none is known). Returns (buf, hdr_rows, guess, head):
        the buffer on its device, its header rows, the rows asked for
        behind them and the Future of the prefix as dense rows on the
        host."""
        _layout, row_bytes = self._deliver_layout[i]
        hdr_rows = -(-4 * K // row_bytes)
        buf = pack["buf"]
        if self._mesh_place is not None:
            # replicated over the mesh: read one copy, on its device
            buf = buf.addressable_data(0)
        R = buf.shape[0] - hdr_rows
        guess = self._first_read_rows(i, K, R)
        vec = start_dense_read(buf, 0, hdr_rows + guess)
        # the reader's span carries the `send` and `chunk` of the span this
        # is called under (`readback_start`, or the drain's own)
        return buf, hdr_rows, guess, self.pipeline.read_ahead(
            self._finish_read, vec, hdr_rows + guess, inherited_ids()
        )

    def _first_read_rows(self, i: int, K: int, R: int) -> int:
        """Rows a chunk's first read asks for: the bucket of the total the
        endpoint's last drained chunk of this depth had (all `R` when none
        is known), never under `_LEAST_READ_ROWS`. Once a read of this depth
        has fallen short, `slack` rows more than the bucket of that total
        less `slack`: a total that hovers round a power of two (half of a
        chunk's rows, say) then neither falls short by a few rows nor asks
        for twice as many. The floor holds under the slack too: a total that
        hovers round the slack itself (a tail's thousand rows round a slack
        of 1,024) read `slack + 16`, `+ 32`, `+ 64` ... rows, a program
        each, built while a send waited."""
        total, slack = self._drain_guess.get((i, K), (R, 0))
        return min(R, _bucket(max(total - slack, _LEAST_READ_ROWS), R) + slack)

    def _note_total(self, i: int, K: int, total: int, guess: int, R: int) -> int:
        """A drained chunk's total, kept for the next read of its depth.
        Returns the rows a second read has to fetch behind the `guess` the
        first one asked for, 0 where the first held them all: what is
        missing in its own bucket, never under `_LEAST_READ_ROWS`; the reads
        of this depth ask for that much more from now on (`slack`)."""
        slack = self._drain_guess.get((i, K), (0, 0))[1]
        more = 0
        if total > guess:
            more = _bucket(max(total - guess, _LEAST_READ_ROWS), R - guess)
            slack = max(slack, more)
        self._drain_guess[(i, K)] = (max(total, 1), slack)
        return more

    @staticmethod
    def _finish_read(vec, n, ids):
        """The host's half of a read, on the pipeline's reader thread: wait
        for the bytes, which the prefix program laid out row-major on the
        device, and see them as rows. The wait holds no interpreter lock, so
        it runs beside the drain's decode. The `readback_copy` stage."""
        with stage("readback_copy", **ids):
            return finish_dense_read(vec, n)

    def _drain(
        self, packs, reads, K: int, wf=None, ids=None, t_submit=0,
        tracker=None,
    ) -> None:
        """Deliver one chunk's packed outputs to query callbacks: per
        endpoint-with-callbacks the read that `_start_reads` began when the
        chunk was dispatched (`reads`) is awaited, topped up where its
        prefix fell short of the header's counts, then a vectorized host
        decode, preserving per-micro-batch callback grouping
        (reference: QueryCallback.receive per chunk,
        query/output/callback/QueryCallback.java:52-105). `K` is the chunk's
        batch count (variable: short tails ride smaller-K programs).

        The `drain` stage, on the drain worker or, re-entrant, on the caller;
        `ids` are the sender's `send` and `chunk`, and `t_submit`
        (perf_counter_ns at the hand-off) gives the time the chunk waited,
        which no span can cross threads to show.
        Inside it: `readback_wait` (the FIRST await of a chunk: the program,
        where it still runs, then the bytes; the waterfall's `device`),
        `readback` (further endpoints' awaits and top-up transfers), then
        `decode` and `callback` in deliver_endpoint (the waterfall's
        `deliver`); closes the chunk's waterfall record. A chunk program
        that failed raises here, at the await."""
        queued = time.perf_counter_ns() - t_submit if t_submit else 0
        if wf is not None and queued:
            wf.stage("queue", queued)
        sync = self.junction.device_stats
        sync = sync and sync.sync_stall
        first_get = True
        topped_up = False
        with stage("drain", tracker, queued_us=queued // 1000, **(ids or {})):
            if reads and all(r[3].done() for r in reads.values()):
                self.readback_ready += 1
            # packs align with the endpoints the program was built to deliver
            for i, pack in zip(self._deliver_idx, packs):
                qr = self.endpoints[i].qr
                if not getattr(qr, "query_callbacks", None):
                    continue
                # started at dispatch; only now for an endpoint that had no
                # callback then, or whose start failed
                read = reads.get(i) or self._start_read(i, pack, K)
                buf, hdr_rows, guess, head = read
                R = buf.shape[0] - hdr_rows
                with stage(
                    "readback_wait" if first_get else "readback", sync,
                    wf=wf, wf_name="device" if first_get else "readback",
                ):
                    head = head.result()
                first_get = False
                cnts = head[:hdr_rows].reshape(-1)[: 4 * K].view(np.int32)
                total = int(cnts.sum())
                more = self._note_total(i, K, total, guess, R)
                if total == 0:
                    continue
                if not more:
                    host = head[hdr_rows:]
                else:
                    # the guess undershot: a second, blocking read of what
                    # is missing. It waits for whatever the sender has
                    # queued on the device meanwhile (the send's next chunk:
                    # 80 ms at a deployment's size), which is why the reads
                    # of this depth ask for that much more from now on
                    if not topped_up:
                        topped_up = True
                        self.readback_topups += 1
                    with stage("readback", sync, wf=wf):
                        tail = read_dense(buf, hdr_rows + guess, more)
                    host = np.concatenate([head[hdr_rows:], tail])
                self.deliver_endpoint(i, host, cnts, total, wf)
        prof = self.junction.profiler
        if wf is not None and prof is not None:
            prof.end(wf)

    def deliver_endpoint(self, i: int, host, cnts, total: int, wf=None) -> None:
        """Decode endpoint `i`'s packed output rows and fire its callbacks
        per micro-batch segment. `host` is the header-stripped byte buffer
        (rows at the front, `row_bytes` wide per `_deliver_layout[i]`),
        `cnts` the deliverable-row count per micro-batch IN DELIVERY ORDER,
        `total` their sum. Rows are decoded one micro-batch at a time, just
        before that micro-batch's callbacks, and dropped right after them:
        the host never holds more than one segment's `Event`s, so their memory is
        reused from segment to segment instead of being mapped and unmapped
        once per chunk (half a million rows are some 140 MB of small objects,
        and the page faults they cost grow fewer as the process ages, so a
        send's time drifted through a run). Stages: one
        `decode` for the chunk's lane views, then per micro-batch a `decode`
        (host decode of its rows), a `callback` and a `release` (dropping
        what no callback kept); together the waterfall's `deliver`."""
        from siddhi_tpu.core.event import (
            KIND_CURRENT,
            KIND_EXPIRED,
            events_from_arrays,
            rows_from_arrays,
        )
        from siddhi_tpu.query_api.execution import OutputEventsFor

        qr = self.endpoints[i].qr
        sm = getattr(self.app, "statistics_manager", None)
        if sm is not None and total:
            # fused insert targets are dead-end junctions (eligible()
            # excludes subscribed targets), so the per-publish throughput
            # hook never fires for them; meter delivered rows here so the
            # calibration ledger can pair predicted selectivity against an
            # actual out-rate on the fused path
            sm.throughput_tracker(
                f"stream.{qr.out_schema.stream_id}"
            ).add(total)
        layout, _row_bytes = self._deliver_layout[i]
        want = qr.output_events
        raw = getattr(qr, "raw_query_callbacks", None)
        # single-kind fast path: decode straight to Event lists and invoke
        # the USER callbacks (skips the triple intermediate)
        fast = want is not OutputEventsFor.ALL and raw is not None and len(
            raw
        ) == len(qr.query_callbacks)
        split = want is OutputEventsFor.ALL
        expired = want is OutputEventsFor.EXPIRED
        native = fast and event_builder() is not None
        impl = "native" if native else "python"
        with stage("decode", wf=wf, wf_name="deliver"):
            lanes = {}
            for name, dt, off in layout:
                # a view of the packed rows (strided, unaligned), no copy:
                # the decode reads each row's lanes where they lie
                lanes[name] = host[:total, off : off + dt.itemsize].view(
                    dt
                )[:, 0]
            cols = {n: lanes[f"c.{n}"] for n in qr.out_schema.attr_names}
        off = 0
        for k in range(len(cnts)):
            c = int(cnts[k])
            if c == 0:
                continue
            with stage("decode", wf=wf, wf_name="deliver", impl=impl):
                ts_k = lanes["ts"][off : off + c]
                cols_k = {n: a[off : off + c] for n, a in cols.items()}
                if fast:
                    seg = events_from_arrays(
                        qr.out_schema, ts_k, cols_k, c, qr._interner
                    )
                    ins, removed = (None, seg) if expired else (seg, None)
                else:
                    kind = (
                        lanes["kind"][off : off + c]
                        if split
                        else int(KIND_EXPIRED if expired else KIND_CURRENT)
                    )
                    seg = rows_from_arrays(
                        qr.out_schema, ts_k, kind, cols_k, c, qr._interner
                    )
                    if split:
                        ins = [e for e in seg if e[1] == KIND_CURRENT] or None
                        removed = [
                            e for e in seg if e[1] == KIND_EXPIRED
                        ] or None
                    else:
                        ins, removed = (None, seg) if expired else (seg, None)
            off += c
            if native:
                self.decode_native_rows += c
            ts = seg[-1][0]
            with stage("callback", wf=wf, wf_name="deliver", batch=k, rows=c):
                for cb in raw if fast else qr.query_callbacks:
                    cb(ts, ins, removed)
            with stage("release", wf=wf, wf_name="deliver"):
                # what no callback kept of the segment's rows is freed here
                seg = ins = removed = None

    def _probe_aux_keys(self, i: int) -> list:
        """Sorted non-timer aux keys for endpoint i, discovered by tracing
        the impl's aux output structure once (abstract eval, no device)."""
        ep = self.endpoints[i]
        impl = ep.impl_factory()
        B = self.junction.batch_size
        schema = self.junction.schema
        batch = schema.empty_batch(B)
        st = ep.init_state(0)
        tst = {}
        for e2 in self.endpoints:
            tst.update(e2.qr._collect_table_states())
        closed = jax.eval_shape(
            lambda s, t, bb: impl(s, t, bb, np.int64(0))[3], st, tst, batch
        )
        return sorted(
            k
            for k in closed.keys()
            if k != "next_timer" and not k.startswith("__lin")
        )
