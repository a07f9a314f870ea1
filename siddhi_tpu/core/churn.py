"""Zero-downtime churn: hot deploy/undeploy, rolling upgrade, rebalancing.

PR 9 delivered the supervision half of production serving (auto-checkpoint,
crash recovery, admission control, fault injection); this module is the
churn half — what lets a multi-tenant manager run for weeks while tenants
add and remove queries daily, without draining live traffic:

* **Hot deploy / undeploy of individual queries** —
  `runtime.add_query(siddhiql)` builds the new query runtime fully OFF-LINE
  (parse -> SA130 lint against the live app's symbols -> construct ->
  prewarm the jitted step so the compile never lands inside the splice
  window), then splices it into the junction fan-out under the app process
  lock (the same lock PR 9's torn-checkpoint fix established), seeding its
  windows/patterns from the last checkpoint through the existing snapshot
  SPI when a structurally-compatible `query:<id>` element exists.
  `runtime.remove_query(qid)` is the inverse. Both re-run fusion-group
  formation: the affected junctions' fused engines are torn down
  (unshare-then-reshare of shared rings, via PR 8's `_maybe_unshare`) and
  rebuilt from the NEW wiring + FusionPlan, so the group grows/shrinks
  while surviving queries' emissions stay byte-identical across the splice
  (their carried window states ride through untouched; the teardown window
  runs the per-batch path, whose byte parity with the fused path is the
  PR 8 CI contract).

* **Rolling app upgrade** — `manager.redeploy(name, new_app)` does
  checkpoint -> build the replacement runtime off-line -> restore every
  structurally-compatible component's state (per-component snapshot keys
  matched by id; incompatible or dropped components start cold, surfaced
  in the returned report) -> atomic swap under the supervisor's
  `_rebuilding` guard, with ingress BUFFERED (bounded `IngressGate`s on
  every stream junction, admission-metered) rather than dropped during the
  swap window, then drained into the new runtime in arrival order. Stale
  input handlers obtained before the swap keep working: the released gate
  forwards them to the new runtime.

* **Shard rebalancing** — when `@app:shard` mesh size changes on redeploy,
  partitioned `[P]` state migrates between device placements through the
  host snapshot (the `[P]` axis is capacity-shaped, not device-shaped, so
  the state restores bit-exact and the new mesh's `in_shardings` re-places
  it on first dispatch); the redeploy report carries the before/after
  placement and the per-device counters prove the new placement.

Everything is supervisor-aware (a failure mid-splice rolls back to the
pre-churn runtime; a failed swap rebuilds the old app from its retained
AST + the checkpoint just taken) and fault-injectable through the
`churn_splice` / `churn_restore` sites (testing/faults.py). Churn counters
live on the MANAGER (they must survive redeploys and supervised restarts)
and surface in `/status.json`, `runtime.explain()`, and the
`siddhi_churn_total{op=}` Prometheus family.
"""

from __future__ import annotations

import collections
import logging
import threading
import time
from dataclasses import dataclass, field
from typing import Optional, Union

from siddhi_tpu.core.errors import (
    DefinitionNotExistError,
    SiddhiAppCreationError,
)
from siddhi_tpu.testing import faults as _faults

log = logging.getLogger(__name__)

DEFAULT_GATE_CAPACITY = 8192
DEFAULT_GATE_BLOCK_S = 10.0


# ---------------------------------------------------------------------------
# churn counters (manager-owned: they outlive any one runtime)
# ---------------------------------------------------------------------------


@dataclass
class ChurnStats:
    """Per-app churn ledger, owned by the SiddhiManager so it survives both
    operator redeploys and supervised restarts."""

    deploys: int = 0
    undeploys: int = 0
    redeploys: int = 0
    rollbacks: int = 0
    last_splice_ms: Optional[float] = None
    # component -> outcome of the last state-seeding pass ('seeded',
    # 'restored', 'cold', 'incompatible', 'dropped', ...)
    last_seed: dict = field(default_factory=dict)
    events: collections.deque = field(
        default_factory=lambda: collections.deque(maxlen=32)
    )

    def record(self, op: str, detail: str = "") -> None:
        self.events.append((int(time.time() * 1000), op, detail))

    def describe_state(self) -> dict:
        d: dict = {
            "deploys": self.deploys,
            "undeploys": self.undeploys,
            "redeploys": self.redeploys,
            "rollbacks": self.rollbacks,
        }
        if self.last_splice_ms is not None:
            d["last_splice_ms"] = round(self.last_splice_ms, 3)
        if self.last_seed:
            d["last_seed"] = dict(self.last_seed)
        if self.events:
            d["events"] = [list(e) for e in self.events]
        return d


# ---------------------------------------------------------------------------
# SA130 — hot add_query candidate lint (shared rule set, like SA125-SA129)
# ---------------------------------------------------------------------------


def _candidate_info_name(query) -> Optional[str]:
    from siddhi_tpu.query_api.annotation import find_annotation

    info = find_annotation(query.annotations, "info")
    return info.element("name") if info else None


def _taken_query_ids(app) -> set:
    from siddhi_tpu.query_api.execution import assign_execution_ids

    taken = set()
    for ent in assign_execution_ids(app):
        if ent[0] == "query":
            taken.add(ent[1])
        else:
            taken.add(ent[1])  # partition id
            taken.update(qid for qid, _q in ent[3])
    return taken


def iter_add_query_problems(app, query):
    """Yield one message per problem with a hot `add_query` candidate
    against the LIVE app's symbols — THE validation rules, shared by the
    runtime (`runtime.add_query` raises on the first) and the analyzer's
    SA130 diagnostic (`siddhi_tpu.analysis.analyze_add_query` reports them
    all), following the SA125–SA129 shared-rule-set pattern.

    Rules: a hot-deployed query needs an explicit @info name (auto-numbered
    `queryN` ids are POSITIONAL over the AST — they renumber as other
    unnamed queries churn in and out and across supervised rebuilds, so an
    auto id is not a stable handle for remove_query/seeding/metrics); a
    duplicate query id would collide with a deployed query (ids key
    callbacks, metrics, and snapshot elements); an undeclared input stream
    would die at construction with less context — and hot deploy cannot
    define new streams, only consume declared ones."""
    from siddhi_tpu.analysis.symbols import build_symbols
    from siddhi_tpu.query_api.execution import (
        JoinInputStream,
        SingleInputStream,
        StateInputStream,
        iter_state_streams,
    )

    name = _candidate_info_name(query)
    if not name:
        yield (
            "hot add_query candidates need an explicit @info(name='...'): "
            "auto-numbered query ids renumber as unnamed queries churn"
        )
    elif name in _taken_query_ids(app):
        yield (
            f"duplicate query name '{name}': a query with this @info name "
            "is already deployed"
        )

    sym = build_symbols(app, [])  # diagnostics of the APP are not ours here
    stream = query.input_stream
    if isinstance(stream, SingleInputStream):
        sid = stream.stream_id
        if sid not in sym.streams and sid not in sym.windows:
            what = sym.describe(sid)
            hint = f" ('{sid}' is a {what})" if what else ""
            yield (
                f"undeclared stream '{sid}': hot add_query can only consume "
                f"streams/windows the live app defines{hint}"
            )
    elif isinstance(stream, JoinInputStream):
        for s in (stream.left, stream.right):
            sid = s.stream_id
            if (
                sid not in sym.streams
                and sid not in sym.windows
                and sid not in sym.tables
                and sid not in sym.aggregations
            ):
                yield (
                    f"undeclared stream '{sid}': hot add_query join sides "
                    "must reference declared streams, tables, windows, or "
                    "aggregations"
                )
    elif isinstance(stream, StateInputStream):
        for s in iter_state_streams(stream.state):
            if s.stream_id not in sym.streams:
                yield (
                    f"undeclared stream '{s.stream_id}': pattern streams "
                    "must be declared by the live app"
                )


def candidate_query_id(app, query) -> str:
    """The qid this candidate gets: its @info name, which
    iter_add_query_problems guarantees present and unique — the ONE id
    assignment that is stable across later splices and supervised
    rebuilds (assign_execution_ids reserves explicit names app-wide, so
    the rebuild derives the identical id; positional `queryN` ids would
    renumber)."""
    name = _candidate_info_name(query)
    if not name:  # belt and braces; the lint rejected this already
        raise SiddhiAppCreationError(
            "hot add_query candidates need an explicit @info(name='...')"
        )
    return name


# ---------------------------------------------------------------------------
# ingress gate: bounded buffered hold on a stream's input handlers
# ---------------------------------------------------------------------------


class IngressGate:
    """Bounded hold-then-drain gate in front of one junction's input
    handlers (`StreamJunction.ingress_gate`, checked by InputHandler.send/
    send_many/send_columns).

    States:
      * holding — sends buffer in arrival order; a full buffer BLOCKS the
        sender (admission-gate hold, not drop) until space frees or the
        hold ends; past `block_timeout_s` the overflow is shed and counted
        (and metered on the app's AdmissionController when one exists).
      * released with a redirect — stale handles bound to the OLD junction
        keep working: their sends forward to the redirect handler (the
        replacement runtime's input handler after a redeploy).
      * released without a redirect — pass-through (the paused-replay gate:
        the same junction resumes normal dispatch).

    The installing thread is exempt: the redeploy drain and the paused
    replay run on it and must reach the junction directly."""

    def __init__(
        self,
        junction,
        capacity: int = DEFAULT_GATE_CAPACITY,
        block_timeout_s: float = DEFAULT_GATE_BLOCK_S,
        admission=None,
    ):
        self.junction = junction
        self.capacity = int(capacity)
        self.block_timeout_s = float(block_timeout_s)
        self._admission = admission
        self._cv = threading.Condition()
        self._buf: collections.deque = collections.deque()
        self._buffered = 0  # events currently held
        self._owner = threading.current_thread()
        self.released = False
        self.redirect = None  # post-release forward target (InputHandler-like)
        self.held_total = 0
        self.shed = 0
        self.blocked_ms = 0.0

    # ---- sender side -----------------------------------------------------

    def intercept(self, kind: str, args: tuple, n: int) -> bool:
        """Called by InputHandler with one send. Returns True when the gate
        consumed it (buffered or forwarded); False = proceed normally."""
        if self.released:
            # post-release the redirect applies to EVERY thread (the owner
            # exemption below exists only so the drain/replay can reach
            # the junction while the hold is up)
            r = self.redirect
            if r is None:
                return False
            if kind == "rows":
                ts, rows, now = args
                r.send_many(rows, timestamps=ts)
            else:
                ts, cols, now = args
                r.send_columns(ts, cols, now)
            return True
        if threading.current_thread() is self._owner:
            return False
        t0 = time.monotonic()
        deadline = t0 + self.block_timeout_s
        with self._cv:
            while (
                not self.released
                and self._buffered + n > self.capacity
                and time.monotonic() < deadline
            ):
                self._cv.wait(timeout=min(0.05, self.block_timeout_s))
            self.blocked_ms += (time.monotonic() - t0) * 1000.0
            if self.released:
                pass  # re-enter the released branch below, outside the lock
            elif self._buffered + n > self.capacity:
                # held past the bound: shed, counted here AND on the app's
                # admission meter so operators see the loss where they
                # already watch overload
                self.shed += n
                if self._admission is not None:
                    self._admission.shed += n
                return True
            else:
                self._buf.append((kind, args))
                self._buffered += n
                self.held_total += n
                return True
        return self.intercept(kind, args, n)  # released while we waited

    # ---- owner side ------------------------------------------------------

    def release(self, target=None, redirect=None) -> int:
        """Drain every buffered send in arrival order into `target` (an
        InputHandler-like; defaults to direct junction delivery), then open
        the gate — with `redirect` set, later sends on stale handles
        forward there instead of hitting the (dead) junction. Returns the
        number of events drained. Buffering stays armed WHILE draining, so
        live senders cannot overtake the backlog."""
        drained = 0
        while True:
            with self._cv:
                if not self._buf:
                    self.redirect = redirect
                    self.released = True
                    self._cv.notify_all()
                    return drained
                kind, args = self._buf.popleft()
                n = len(args[0])
                self._buffered -= n
                self._cv.notify_all()
            drained += n
            try:
                if target is not None:
                    if kind == "rows":
                        ts, rows, now = args
                        target.send_many(rows, timestamps=ts)
                    else:
                        ts, cols, now = args
                        target.send_columns(ts, cols, now)
                else:
                    if kind == "rows":
                        ts, rows, now = args
                        self.junction.send_rows(ts, rows, now=now)
                    else:
                        ts, cols, now = args
                        from siddhi_tpu.core.stream_junction import (
                            InputHandler,
                        )

                        InputHandler(
                            self.junction, lambda _n=now: _n
                        ).send_columns(ts, cols, now)
            except Exception:
                log.exception(
                    "ingress gate for stream '%s': draining a buffered send "
                    "failed; the entry was dropped",
                    self.junction.schema.stream_id,
                )
                self.shed += n

    def describe_state(self) -> dict:
        return {
            "buffered": self._buffered,
            "held_total": self.held_total,
            "shed": self.shed,
            "blocked_ms": round(self.blocked_ms, 3),
            "released": self.released,
            "redirected": self.redirect is not None,
        }


def _gate_streams(runtime, capacity: int, block_timeout_s: float) -> dict:
    """Install an IngressGate on every DEFINED stream's junction (external
    ingress points; internal insert-into junctions keep flowing so the old
    runtime finishes what it already accepted)."""
    gates: dict = {}
    for sid in runtime.app.stream_definitions:
        j = runtime.junctions.get(sid)
        if j is None:
            j = runtime._junction(sid)
        g = IngressGate(
            j, capacity=capacity, block_timeout_s=block_timeout_s,
            admission=runtime._admission,
        )
        j.ingress_gate = g
        gates[sid] = g
    return gates


# ---------------------------------------------------------------------------
# state seeding through the snapshot SPI
# ---------------------------------------------------------------------------


def _tree_compatible(fresh, value) -> bool:
    """Structural compatibility of a snapshot element against a freshly
    initialized state tree: identical path sets, identical leaf shapes and
    dtypes. Anything else starts cold (surfaced, never guessed at). A
    snapshot holds the 64-bit lane where the state holds a U32Pair."""
    import jax
    import numpy as np

    from siddhi_tpu.core.persistence import _flat_with_paths, without_indexes
    from siddhi_tpu.ops.scatter import join_pairs

    try:
        fa = _flat_with_paths(
            jax.eval_shape(join_pairs, without_indexes(fresh)))
        fb = _flat_with_paths(value)
    except Exception:
        return False
    if set(fa) != set(fb):
        return False
    for k, a in fa.items():
        b = fb[k]
        a_arr = hasattr(a, "shape")
        if a_arr != hasattr(b, "shape"):
            return False
        if a_arr and (
            tuple(a.shape) != tuple(b.shape)
            or np.dtype(a.dtype) != np.dtype(b.dtype)
        ):
            return False
    return True


def _fresh_state_of(qr):
    try:
        return qr.init_state()
    except TypeError:
        return qr.init_state(0)


def _element_component(rt, key: str):
    """Resolve a snapshot element key to (component_kind, live_object) in
    `rt`, or (kind, None) when the component no longer exists."""
    kind, _, name = key.partition(":")
    if kind in ("query", "rate"):
        return kind, rt.queries.get(name)
    if kind == "table":
        return kind, rt.tables.get(name)
    if kind == "window":
        return kind, rt.named_windows.get(name)
    if kind == "aggregation":
        return kind, rt.aggregations.get(name)
    if kind == "partition":
        idx = int(name.split(":")[0])
        return kind, rt.partitions[idx] if idx < len(rt.partitions) else None
    return kind, None


def seed_runtime_from_snapshot(rt, payload: dict) -> dict:
    """Restore every structurally-compatible element of a full-snapshot
    payload into runtime `rt` (per-component keys matched by id); returns
    {element_key: outcome} with outcomes 'restored' | 'incompatible' |
    'dropped' (component gone) plus 'cold' rows for new components the
    snapshot does not cover. Incompatible components START COLD — state is
    never coerced across a definition change."""
    svc = rt.snapshot_service
    report: dict = {}
    elements = dict(payload.get("elements", {}))
    rates = dict(payload.get("rates", {}))
    restorable: dict = {}
    for key, value in elements.items():
        kind, comp = _element_component(rt, key)
        if comp is None:
            report[key] = "dropped"
            continue
        if kind == "query":
            fresh = comp.state if comp.state is not None else _fresh_state_of(comp)
        elif kind == "partition":
            fresh = comp.ptable
        else:
            fresh = comp.state
        if _tree_compatible(fresh, value):
            restorable[key] = value
            report[key] = "restored"
        else:
            report[key] = "incompatible"
    for key, value in rates.items():
        _kind, comp = _element_component(rt, key)
        rl = getattr(comp, "rate_limiter", None) if comp is not None else None
        if rl is None:
            report[key] = "dropped"
        else:
            restorable[key] = value
            report[key] = "restored"
    with rt._process_lock:
        svc._restore_elements(
            {k: v for k, v in restorable.items() if not k.startswith("rate:")}
        )
        svc._restore_elements(
            {k: v for k, v in restorable.items() if k.startswith("rate:")}
        )
    # components the snapshot does not know start cold — surfaced so the
    # operator can tell "new component" from "lost state"
    for qid in rt.queries:
        report.setdefault(f"query:{qid}", "cold")
    for tid in rt.tables:
        report.setdefault(f"table:{tid}", "cold")
    for wid in rt.named_windows:
        report.setdefault(f"window:{wid}", "cold")
    for aid in rt.aggregations:
        report.setdefault(f"aggregation:{aid}", "cold")
    return report


def _seed_query_state(runtime, qid: str, qr, seed) -> str:
    """Seed a hot-deployed query's windows/patterns from the app's last
    checkpoint via the snapshot SPI. Returns the outcome: 'seeded' when a
    structurally-compatible `query:<qid>` element restored, 'cold'
    otherwise (no store / no revision / element absent / incompatible)."""
    import pickle

    if seed in (None, False, "cold"):
        return "cold"
    store = runtime.manager.persistence_store
    if store is None:
        return "cold"
    from siddhi_tpu.core.persistence import (
        _to_device,
        merge_snapshot_elements,
        merge_snapshot_interner,
    )

    try:
        last = store.get_last_revision(runtime.name)
        if last is None:
            return "cold"
        if getattr(store, "incremental", False):
            chain = runtime._incremental_chain(store, upto=last)
        else:
            data = store.load(runtime.name, last)
            chain = [data] if data is not None else []
        if not chain:
            return "cold"
        payloads = [pickle.loads(s) for s in chain]
        # interner first: a checkpoint from a PREVIOUS process carries ids
        # minted by that process's interner — without the merge the seeded
        # state's string ids would decode to the wrong (or no) strings.
        # Same helpers SnapshotService.restore uses, so the two cannot
        # drift.
        with runtime._process_lock:
            merge_snapshot_interner(runtime.interner, payloads[-1])
        elements, _rates = merge_snapshot_elements(payloads)
    except Exception:
        log.exception(
            "add_query '%s': reading the last checkpoint failed; starting "
            "cold", qid,
        )
        return "cold"
    value = elements.get(f"query:{qid}")
    if value is None:
        return "cold"
    # fault-injection site `churn_restore`: a failing seed is a failing
    # splice — the caller rolls back to the pre-churn runtime
    _faults.hit("churn_restore", f"{runtime.name}:{qid}")
    fresh = _fresh_state_of(qr)
    if not _tree_compatible(fresh, value):
        return "incompatible"
    qr.state = _to_device(value, fresh)
    return "seeded"


# ---------------------------------------------------------------------------
# prewarm: compile the jitted step(s) off the splice path
# ---------------------------------------------------------------------------


def _prewarm_query(runtime, qr) -> None:
    """Compile every per-batch jitted step of a freshly built query runtime
    with an all-invalid batch on THROWAWAY state, so the XLA compile
    happens BEFORE the splice (a cold compile inside the splice window
    would stall every live stream for seconds). The live jits are invoked
    directly rather than through `receive`: receive's table-state
    writeback would race live mutations of the shared tables the new
    query reads (lost update), and its carried-state update would need
    undoing. Table states are COPIED under the process lock first — live
    donated dispatches delete their old buffers, so the compile call must
    not read the live arrays off-lock. Best-effort: a prewarm failure
    only costs the first live batch the compile."""
    import jax
    import jax.numpy as jnp

    from siddhi_tpu.core.pattern_runtime import PatternQueryRuntime

    B = runtime.batch_size
    now = jnp.asarray(runtime.clock(), jnp.int64)
    try:
        with runtime._process_lock:
            tstates = jax.tree_util.tree_map(
                lambda x: jnp.array(x, copy=True) if hasattr(x, "dtype") else x,
                qr._collect_table_states(),
            )
        if isinstance(qr, PatternQueryRuntime):
            for sid in qr.prog.stream_ids:
                st = qr._fresh(qr.init_state(int(now)))
                qr._steps[sid](
                    st, tstates, runtime.stream_schemas[sid].empty_batch(B),
                    now,
                )
        elif hasattr(qr, "side_schemas"):  # join runtime
            for side, schema in qr.side_schemas.items():
                st = qr._fresh(qr.init_state())
                qr._steps[side](st, tstates, schema.empty_batch(B), now)
        else:
            st = qr._fresh(qr.init_state())
            qr._step(st, tstates, qr.in_schema.empty_batch(B), now)
    except Exception:
        log.debug(
            "prewarm of query '%s' failed; the first live batch pays the "
            "compile", qr.query_id, exc_info=True,
        )


# ---------------------------------------------------------------------------
# hot deploy / undeploy
# ---------------------------------------------------------------------------


def add_query(runtime, query: Union[str, object], seed="checkpoint") -> str:
    """Hot-deploy one query into a (possibly running) app runtime. See the
    module docstring for the build-offline / splice-under-lock protocol.
    Returns the assigned query id."""
    from siddhi_tpu.compiler.siddhi_compiler import SiddhiCompiler
    from siddhi_tpu.query_api.execution import Query

    if isinstance(query, str):
        query = SiddhiCompiler.parse_query(query)
    if not isinstance(query, Query):
        raise SiddhiAppCreationError(
            f"add_query expects SiddhiQL text or a Query AST, got "
            f"{type(query).__name__}"
        )
    # SA130 lint against the LIVE app's symbols (shared rule set)
    for problem in iter_add_query_problems(runtime.app, query):
        raise SiddhiAppCreationError(problem)
    qid = candidate_query_id(runtime.app, query)
    stats = runtime.manager.churn_stats(runtime.name)
    t0 = time.perf_counter()

    # ---- build fully off-line: construct + stage the wiring. The build
    # is host-side compilation (no XLA jit — that's the prewarm below),
    # but it inserts into runtime.queries / junctions / stream_schemas,
    # which concurrent readers (auto-persist's _elements walk,
    # snapshot_status) iterate under the process lock — so the insertions
    # hold it too.
    pre_schemas = set(runtime.stream_schemas)
    pre_junctions = set(runtime.junctions)
    staged: list = []
    try:
        with runtime._process_lock:
            runtime._staged_wiring = staged
            runtime._add_query(qid, query)
    except BaseException:
        with runtime._process_lock:
            # pop only OUR half-built entry — a build that failed on a
            # collision must not evict the live query holding the key
            existing = runtime.queries.get(qid)
            if existing is not None and getattr(
                existing, "query", None
            ) is query:
                runtime.queries.pop(qid, None)
            for sid in set(runtime.stream_schemas) - pre_schemas:
                runtime.stream_schemas.pop(sid, None)
            for sid in set(runtime.junctions) - pre_junctions:
                runtime.junctions.pop(sid, None)
        raise
    finally:
        runtime._staged_wiring = None
    qr = runtime.queries[qid]
    if runtime._running:
        _prewarm_query(runtime, qr)

    seed_outcome = "cold"
    tore_down = False
    try:
        seed_outcome = _seed_query_state(runtime, qid, qr, seed)

        # ---- splice under the app process lock ---------------------------
        # The fused engines are disabled+closed OUTSIDE the lock first: a
        # pipelined sender holds the engine's send lock while taking the
        # process lock per chunk, so closing under the process lock would
        # deadlock. The per-batch path that covers the gap is byte-parity
        # with the fused path by the PR 8 CI contract.
        if runtime._running and runtime._fuse_enabled:
            runtime._teardown_fused_ingest()
            tore_down = True
        with runtime._process_lock:
            # fault-injection site `churn_splice`: fires mid-splice, after
            # construction and before the wiring commits — the except arm
            # below proves the rollback contract
            _faults.hit("churn_splice", f"{runtime.name}:+{qid}")
            for action in staged:
                action()
            runtime.app.execution_elements.append(query)
    except BaseException as e:
        # roll back to the pre-churn runtime: un-apply the wiring, drop the
        # query, rebuild the fused engines from the (restored) wiring
        with runtime._process_lock:
            _unwire_query(runtime, qid, qr)
            runtime.queries.pop(qid, None)
            if runtime.app.execution_elements and (
                runtime.app.execution_elements[-1] is query
            ):
                runtime.app.execution_elements.pop()
            for sid in set(runtime.stream_schemas) - pre_schemas:
                runtime.stream_schemas.pop(sid, None)
            for sid in set(runtime.junctions) - pre_junctions:
                runtime.junctions.pop(sid, None)
        if tore_down:
            runtime._build_fused_ingest()
        stats.rollbacks += 1
        stats.record("rollback", f"add_query {qid}: {type(e).__name__}: {e}")
        raise
    # ---- re-form fusion groups over the grown wiring ---------------------
    if runtime._running and runtime._fuse_enabled:
        runtime._build_fused_ingest()
    # arm schedulers / rate limiter exactly as start() would have
    if runtime._running:
        if getattr(qr, "needs_scheduler", False) and hasattr(qr, "prime"):
            aux = qr.prime(runtime.clock())
            runtime._maybe_schedule(qr, aux)
        if getattr(qr, "host_next_timer", None) and getattr(
            qr, "timer_target", None
        ):
            runtime._scheduler.start()
            runtime._scheduler.notify_at(
                qr.host_next_timer(runtime.clock()), qr.timer_target
            )
        runtime._arm_rate_limiter(qr)
    stats.deploys += 1
    stats.last_splice_ms = (time.perf_counter() - t0) * 1000.0
    stats.last_seed = {f"query:{qid}": seed_outcome}
    stats.record("deploy", f"{qid} (seed={seed_outcome})")
    return qid


def _unwire_query(runtime, qid: str, qr) -> None:
    """Remove every junction subscription and fuse candidate of one query
    (caller holds the process lock)."""
    name = f"query.{qid}"
    for j in list(runtime.junctions.values()):
        j.unsubscribe(name)
        j.fuse_candidates = [ep for ep in j.fuse_candidates if ep.qr is not qr]
    for nw in runtime.named_windows.values():
        nw.out_junction.unsubscribe(name)


def remove_query(runtime, qid: str) -> None:
    """Hot-undeploy one top-level query: unsplice it from the junction
    fan-out under the app process lock, drop it from the retained AST (a
    supervised rebuild must not resurrect it), and re-form fusion groups
    over the shrunk wiring. Queries inside partitions are not individually
    removable (their state shares one [P] table)."""
    qr = runtime.queries.get(qid)
    if qr is None:
        raise DefinitionNotExistError(
            f"no query '{qid}' in app '{runtime.name}'"
        )
    for pr in runtime.partitions:
        if qr in pr.queries:
            raise SiddhiAppCreationError(
                f"query '{qid}' lives inside a partition; redeploy the app "
                "to change partition contents"
            )
    stats = runtime.manager.churn_stats(runtime.name)
    t0 = time.perf_counter()
    if runtime._running and runtime._fuse_enabled:
        runtime._teardown_fused_ingest()  # outside the lock; see add_query
    with runtime._process_lock:
        # fault site `churn_splice` BEFORE any mutation: an injected fault
        # leaves the runtime exactly as it was (consistent, never torn)
        _faults.hit("churn_splice", f"{runtime.name}:-{qid}")
        _unwire_query(runtime, qid, qr)
        runtime.queries.pop(qid, None)
        qr._removed = True  # pending timer/rate-limit fires become no-ops
        runtime.app.execution_elements = [
            e for e in runtime.app.execution_elements if e is not qr.query
        ]
        runtime._user_callbacks = [
            (n, cb) for n, cb in runtime._user_callbacks if n != qid
        ]
    if runtime._running and runtime._fuse_enabled:
        runtime._build_fused_ingest()
    stats.undeploys += 1
    stats.last_splice_ms = (time.perf_counter() - t0) * 1000.0
    stats.record("undeploy", qid)


# ---------------------------------------------------------------------------
# rolling redeploy
# ---------------------------------------------------------------------------


def redeploy(
    manager,
    name: str,
    app,
    strict: bool = False,
    gate_capacity: int = DEFAULT_GATE_CAPACITY,
    gate_block_s: float = DEFAULT_GATE_BLOCK_S,
) -> dict:
    """Rolling upgrade of one deployed app: checkpoint -> build the
    replacement off-line -> restore compatible state -> atomic swap with
    ingress buffered (never dropped) across the swap window. Returns the
    redeploy report; raises (with the OLD app rolled back and serving)
    when the replacement cannot be built or started."""
    from siddhi_tpu.compiler.siddhi_compiler import SiddhiCompiler
    from siddhi_tpu.core.app_runtime import SiddhiAppRuntime

    old = manager.get_siddhi_app_runtime(name)
    if old is None:
        raise DefinitionNotExistError(f"no app '{name}' on this manager")
    if isinstance(app, str):
        app = SiddhiCompiler.parse(app)
    if strict:
        from siddhi_tpu.analysis import analyze

        analyze(app).raise_if_errors()
    new_name = app.name if app.name else None
    if new_name is not None and new_name != name:
        raise SiddhiAppCreationError(
            f"redeploy('{name}') got an app named '{new_name}'; a rename is "
            "a deploy of a new app, not a redeploy"
        )
    stats = manager.churn_stats(name)
    t0 = time.perf_counter()
    import pickle

    # 1. gate ingress FIRST: live senders buffer (bounded,
    # admission-metered) from here on, so nothing the old runtime
    # processes can slip in between the checkpoint below and the swap —
    # state it advanced past the snapshot would be silently discarded
    gates = _gate_streams(old, gate_capacity, gate_block_s)

    # 2. checkpoint the gated runtime (bytes; flushed like persist()).
    # snapshot() takes the process lock, serializing after any dispatch
    # already in flight when the gates went up. @async rings admitted
    # events before the gates: wait (bounded) for their workers to drain
    # so those events reach the snapshot instead of dying with the old
    # runtime.
    drain_deadline = time.monotonic() + 5.0
    while time.monotonic() < drain_deadline and any(
        g.junction.queued() for g in gates.values()
    ):
        time.sleep(0.005)
    for sid, g in gates.items():
        leftover = g.junction.queued()
        if leftover:
            # ring events the workers could not drain in time die with
            # the old runtime — they are metered as shed (never silent)
            g.shed += leftover
            log.warning(
                "redeploy of app '%s': stream '%s' still holds %d "
                "@async-queued events past the drain window; they are "
                "counted as shed", name, sid, leftover,
            )
    for t in old.tables.values():
        t.flush_record_store()
    snap = old.snapshot()
    shard_before = (
        old._shard.describe_state() if old._shard is not None else None
    )
    sup = manager.supervisor
    new_rt = None
    started = False
    try:
        # 3. build the replacement fully off-line (NOT registered yet)
        new_rt = SiddhiAppRuntime(app, manager)
        # 4. restore compatible state through the snapshot SPI
        # (fault site `churn_restore`: a failing restore aborts the
        # redeploy with the old app still serving)
        _faults.hit("churn_restore", name)
        seed_report = seed_runtime_from_snapshot(new_rt, pickle.loads(snap))
        # carry user callbacks / exception handler over (same contract as
        # the supervisor's restart)
        cb_failed = []
        for cb_name, cb in list(getattr(old, "_user_callbacks", [])):
            try:
                new_rt.add_callback(cb_name, cb)
            except Exception:
                cb_failed.append(cb_name)
        handler = getattr(old, "_exception_handler", None)
        if handler is not None:
            new_rt.set_exception_handler(handler)

        # 5. atomic swap under the supervisor's _rebuilding guard: the
        # supervisor must not race a crash-restart of `name` against the
        # teardown below (core/supervision.Supervisor._check_all skips the
        # app while the guard names it)
        if sup is not None:
            sup._rebuilding = name
        try:
            old.shutdown()
            manager._runtimes[name] = new_rt
        finally:
            if sup is not None:
                sup._rebuilding = None
        if sup is not None:
            # operator redeploy: fresh supervision life (attempt streak and
            # gave-up verdicts reset — Supervisor.attach documents this)
            sup.attach(new_rt)
        new_rt.start()
        started = True
    except BaseException as e:
        stats.rollbacks += 1
        stats.record("rollback", f"redeploy: {type(e).__name__}: {e}")
        if manager.get_siddhi_app_runtime(name) is new_rt or started is False:
            _rollback_redeploy(manager, name, old, snap, gates, sup)
        raise
    # 6. drain the gated backlog into the replacement IN ARRIVAL ORDER,
    # then leave each gate redirecting so stale handles keep working.
    # The DRAIN bypasses the new app's admission gate (these events were
    # admitted once already — re-charging the burst against the token
    # bucket would shed an already-accepted backlog, the same hazard
    # PR 9's replay bypass closed); the REDIRECT for later live sends is
    # the admitted handler, so new traffic pays admission as usual.
    from siddhi_tpu.core.stream_junction import InputHandler as _RawHandler

    drained = 0
    for sid, gate in gates.items():
        if sid in new_rt.stream_schemas:
            raw = _RawHandler(
                new_rt._junction(sid), lambda _rt=new_rt: _rt.clock()
            )
            drained += gate.release(
                target=raw, redirect=new_rt.get_input_handler(sid)
            )
        else:
            # the stream no longer exists: shed the backlog (counted)
            # BEFORE release — draining it into the shut-down old
            # junction would run dead query steps
            with gate._cv:
                gate.shed += gate._buffered
                gate._buf.clear()
                gate._buffered = 0
                gate._cv.notify_all()
            gate.release(target=None, redirect=None)
    stats.redeploys += 1
    stats.last_splice_ms = (time.perf_counter() - t0) * 1000.0
    stats.last_seed = dict(seed_report)
    stats.record("redeploy", f"{drained} buffered events drained")
    shard_after = (
        new_rt._shard.describe_state() if new_rt._shard is not None else None
    )
    report = {
        "app": name,
        "state": seed_report,
        "restored": sorted(
            k for k, v in seed_report.items() if v == "restored"
        ),
        "cold": sorted(k for k, v in seed_report.items() if v == "cold"),
        "incompatible": sorted(
            k for k, v in seed_report.items() if v == "incompatible"
        ),
        "dropped": sorted(
            k for k, v in seed_report.items() if v == "dropped"
        ),
        "buffered_events_drained": drained,
        "gates": {sid: g.describe_state() for sid, g in gates.items()},
        "wall_ms": round(stats.last_splice_ms, 3),
        "callbacks_not_reregistered": cb_failed,
    }
    if shard_before is not None or shard_after is not None:
        report["shard"] = {"before": shard_before, "after": shard_after}
    return report


def _rollback_redeploy(manager, name, old, snap, gates, sup) -> None:
    """A failed swap must leave the OLD app serving: if its runtime is
    still up, just release the gates; if it was already torn down, rebuild
    it from the retained AST and the checkpoint taken at redeploy entry
    (mirroring the supervisor's restart sequence)."""
    current = manager.get_siddhi_app_runtime(name)
    if current is old and old._running:
        for g in gates.values():
            g.release(target=None, redirect=None)
        for j in old.junctions.values():
            j.ingress_gate = None
        return
    try:
        from siddhi_tpu.core.app_runtime import SiddhiAppRuntime

        if sup is not None:
            sup._rebuilding = name
        try:
            rebuilt = SiddhiAppRuntime(old.app, manager)
            rebuilt.restore(snap)
            for cb_name, cb in list(getattr(old, "_user_callbacks", [])):
                try:
                    rebuilt.add_callback(cb_name, cb)
                except Exception:
                    pass
            handler = getattr(old, "_exception_handler", None)
            if handler is not None:
                rebuilt.set_exception_handler(handler)
            manager._runtimes[name] = rebuilt
        finally:
            if sup is not None:
                sup._rebuilding = None
        if sup is not None:
            sup.attach(rebuilt)
        rebuilt.start()
        from siddhi_tpu.core.stream_junction import InputHandler as _Raw

        for sid, gate in gates.items():
            if sid in rebuilt.stream_schemas:
                # raw drain (admitted once already) + admitted redirect,
                # same split as the success path
                gate.release(
                    target=_Raw(
                        rebuilt._junction(sid), lambda _rt=rebuilt: _rt.clock()
                    ),
                    redirect=rebuilt.get_input_handler(sid),
                )
            else:
                gate.release(target=None, redirect=None)
        log.warning(
            "redeploy of app '%s' failed; rolled back to the previous "
            "deployment (state from the redeploy-entry checkpoint)", name,
        )
    except Exception:
        for g in gates.values():
            g.release(target=None, redirect=None)
        log.exception(
            "redeploy rollback for app '%s' failed; the app is DOWN", name,
        )
