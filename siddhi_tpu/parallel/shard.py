"""First-class sharded execution: `@app:shard(devices='N', axis=...)`.

The multichip dryrun (`__graft_entry__.py` + `parallel/mesh.py`) proved the
hard part — an 8-device mesh with the partition axis sharded and the batch
axis key-routed per device, checksum-identical to unsharded execution — but
none of it was reachable from a real app. This module promotes that contract
to an engine runtime mode, resolved at `start()`:

* **axis='part'** — every `PartitionedQueryRuntime`'s existing leading `[P]`
  state axis is placed on a `jax.sharding.Mesh` over the first N devices:
  windows/aggregators of different partition keys advance in parallel on
  different chips, with XLA inserting the cross-device collectives (the
  psum/min aux reduction, the output gather at decode). The input batch is
  REPLICATED to every device — emission order is part of the engine contract,
  and the dryrun's key-routed batch pre-pass compacts each device's
  sub-batch, which reorders emissions ACROSS partition slots within a batch
  (set-identical, order-different). The routed variant stays available as
  `mesh.shard_partitioned_query(routed=True)` for checksum workloads.

* **axis='batch'** — junctions whose fused endpoints are all STATELESS
  (filter / projection / stream-function chains: no window, no aggregator,
  no group-by, no table, no rate limiter) get a `BatchShardRouter`:
  each `send_columns` call's micro-batches are round-robin-routed
  (micro-batch k -> device k % D) into per-device wire chunks, dispatched
  as per-device chunk programs, and the packed outputs are merged back in
  ORIGINAL batch order before callback delivery — byte-identical to the
  unsharded path, because a stateless chain's output for a micro-batch
  depends only on that micro-batch. Stateful non-partitioned queries keep
  the single-device fused path (key-routed sharding for those is the
  partition construct: `partition with (key of S)` + axis='part').

* **axis='auto'** (default) applies both.

`SIDDHI_TPU_SHARD=N` overrides the annotation process-wide (0 forces off) —
the verify-parity CI leg runs the whole suite under `SIDDHI_TPU_SHARD=8`
with `XLA_FLAGS=--xla_force_host_platform_device_count=8` and diffs every
case's rows against the unsharded run.

Validation is ONE rule set (`iter_shard_annotation_problems`) shared by the
runtime resolver (raises at app creation) and the analyzer's SA129
diagnostic, like SA125–SA128.

Grounding: the cloud-native pattern-detection framework shards detection by
key exactly this way (PAPERS.md, arxiv 2401.09960); "To Share, or not to
Share" (arxiv 2101.00361) motivates keeping shared state local to a shard —
here each device owns its partition slots' windows outright.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Optional

import numpy as np

from siddhi_tpu.observability.profiler import stage

log = logging.getLogger(__name__)

SHARD_ENV = "SIDDHI_TPU_SHARD"
SHARD_AXIS_ENV = "SIDDHI_TPU_SHARD_AXIS"
MAX_DEVICES = 64
_AXES = ("auto", "part", "batch", "keys")


# ---------------------------------------------------------------------------
# annotation / env resolution (one rule set for runtime + analyzer SA129)
# ---------------------------------------------------------------------------


def shard_env_override() -> Optional[int]:
    """Process-wide device-count override: N (force N-device sharding),
    0 (force off), or None (defer to the app's @app:shard annotation)."""
    v = os.environ.get(SHARD_ENV, "").strip().lower()
    if not v:
        return None
    if v in ("off", "false", "no"):
        return 0
    try:
        return max(0, int(v))
    except ValueError:
        log.warning("ignoring malformed %s=%r", SHARD_ENV, v)
        return None


def shard_axis_override() -> Optional[str]:
    """Process-wide axis override (SIDDHI_TPU_SHARD_AXIS): one of the
    `_AXES` names, or None to defer to the app's @app:shard annotation.
    Lets CI drive the same app through every placement strategy."""
    v = os.environ.get(SHARD_AXIS_ENV, "").strip().lower()
    if not v:
        return None
    if v not in _AXES:
        log.warning(
            "ignoring malformed %s=%r (expected one of %s)",
            SHARD_AXIS_ENV, v, ", ".join(_AXES),
        )
        return None
    return v


def iter_shard_annotation_problems(ann):
    """Yield one message per malformed `@app:shard` element — THE validation
    rules, shared by the runtime resolver (raises on the first) and the
    analyzer's SA129 diagnostics (reports them all), so the two can never
    drift. Accepted shapes:
    @app:shard(devices='N'[, axis='part|batch|keys|auto'])
    or the sole-positional @app:shard('N')."""
    sole_positional = len(ann.elements) == 1 and ann.elements[0][0] is None
    for k, v in ann.elements:
        if k == "devices" or (k is None and sole_positional):
            try:
                ok = 1 <= int(v) <= MAX_DEVICES
            except (TypeError, ValueError):
                ok = False
            if not ok:
                yield (
                    f"@app:shard devices '{v}' must be an integer in "
                    f"1..{MAX_DEVICES}"
                )
        elif k == "axis":
            if str(v).strip().lower() not in _AXES:
                yield (
                    f"@app:shard axis '{v}' must be one of "
                    f"{', '.join(_AXES)}"
                )
        else:
            yield (
                f"unknown @app:shard option '{k if k is not None else v}' "
                "(expected devices, axis)"
            )


def resolve_shard_annotation(ann) -> tuple[int, str]:
    """(requested_devices, axis) for one app from its `@app:shard`
    annotation (or None) plus the SIDDHI_TPU_SHARD env override (which wins,
    in both directions). requested_devices == 0 means sharding is off.
    Raises SiddhiAppCreationError on malformed options — the runtime analog
    of the analyzer's SA129 diagnostic."""
    from siddhi_tpu.core.errors import SiddhiAppCreationError

    devices = 0
    axis = "auto"
    if ann is not None:
        for problem in iter_shard_annotation_problems(ann):
            raise SiddhiAppCreationError(problem)
        v = ann.element("devices")
        if v is None and len(ann.elements) == 1 and ann.elements[0][0] is None:
            v = ann.elements[0][1]  # strict sole-positional fallback
        devices = int(v) if v is not None else 0
        ax = ann.element("axis")
        if ax is not None:
            axis = str(ax).strip().lower()
    env = shard_env_override()
    if env is not None:
        devices = env
    env_axis = shard_axis_override()
    if env_axis is not None:
        axis = env_axis
    return devices, axis


# ---------------------------------------------------------------------------
# batch-axis router eligibility
# ---------------------------------------------------------------------------


def shardable_stateless(qr) -> bool:
    """True when a fused endpoint's query carries NO cross-batch state, so
    its output for a micro-batch depends only on that micro-batch and
    micro-batches can be routed to different devices and merged back in
    batch order with byte-identical results. The contract lives on
    `QueryRuntime.stateless_chain`; anything else (patterns, joins,
    partitioned runtimes — all stateful) is never shardable this way."""
    from siddhi_tpu.core.query_runtime import QueryRuntime

    return type(qr) is QueryRuntime and qr.stateless_chain


def router_eligible(fi) -> bool:
    """May a junction's fused ingest engine be batch-axis sharded? Every
    endpoint must be provably stateless, and there must be no residual
    per-batch consumers and no cross-query shared rings (both exist only
    for stateful chains anyway)."""
    if fi.residual or fi.share_sets:
        return False
    if not fi.endpoints:
        return False
    return all(shardable_stateless(ep.qr) for ep in fi.endpoints)


# ---------------------------------------------------------------------------
# batch-axis round-robin router
# ---------------------------------------------------------------------------


class BatchShardRouter:
    """Round-robin batch-axis data parallelism for one junction's fused
    ingest: micro-batch k of a columnar send routes to device k % D, each
    device's batches are encoded into per-device wire chunks (one fresh
    buffer per chunk — see `_send` on why in-flight chunks must not share
    pooled slots) shipped through the SAME jitted chunk program (jax
    compiles one executable per device), and the packed outputs merge back
    in ORIGINAL batch order before delivery.

    Armed only on junctions whose endpoints are all stateless
    (`router_eligible`), so per-device execution order cannot change any
    result. Per-device dispatch/event counters feed `/status.json`,
    `/profile`, explain(), and the Prometheus shard gauges."""

    def __init__(self, junction, devices):
        self.junction = junction
        self.devices = list(devices)
        self.dispatches = [0] * len(self.devices)
        self.events = [0] * len(self.devices)
        self.sends = 0
        self._lock = threading.Lock()
        # senders serialize on _send_gate (the counters and the merge drain
        # assume one producer); a callback that re-enters send_columns from
        # inside the merged drain falls back to the single-device path
        # instead of deadlocking on its own gate
        self._send_gate = threading.Lock()
        self._sender = None

    # ---- observability ---------------------------------------------------

    def describe_state(self) -> dict:
        total = max(1, sum(self.events))
        d = len(self.devices)
        return {
            "devices": d,
            "sends": self.sends,
            "per_device_dispatches": list(self.dispatches),
            "per_device_events": list(self.events),
            # occupancy: each device's event share normalized so 1.0 means a
            # perfectly even split across the D devices
            "occupancy": [round(e * d / total, 3) for e in self.events],
        }

    # ---- send ------------------------------------------------------------

    def try_send(
        self, fi, prog, encode, deliver, ts_arr, cols, n: int, B: int, now,
        ds, tracked, tr, stream_span,
    ) -> Optional[bool]:
        """Sharded fused send of one columnar call. Returns None when the
        call should fall back to the single-device fused path (too few
        micro-batches for >= 2 devices, or a narrow-wire misfit before
        anything was dispatched), True once the sharded send committed."""
        M = -(-n // B)  # micro-batches in this call
        D = min(len(self.devices), M)
        if D < 2:
            return None
        if self._sender is threading.current_thread():
            return None  # re-entrant send from a drain callback
        with self._send_gate:
            self._sender = threading.current_thread()
            try:
                return self._send(
                    fi, prog, encode, deliver, ts_arr, cols, n, B, now,
                    ds, tracked, tr, stream_span, M, D,
                )
            finally:
                self._sender = None

    def _send(
        self, fi, prog, encode, deliver, ts_arr, cols, n: int, B: int, now,
        ds, tracked, tr, stream_span, M: int, D: int,
    ) -> Optional[bool]:
        from siddhi_tpu.core.event import WireNarrowMisfit

        # round-robin assignment: micro-batch k -> device k % D, kept in
        # per-device order so each device's chunk iterations align with its
        # assigned global batches
        assigned = [list(range(d, M, D)) for d in range(D)]

        # encode EVERY device's chunks first (pure host work), each into a
        # FRESH wire buffer: a narrow-wire misfit here falls back to the
        # unsharded path with NOTHING dispatched (which owns the full-width
        # rebuild), and a fresh buffer per in-flight chunk means no reuse
        # gate is needed at all — a pooled slot would be re-acquired before
        # its first occupant shipped, overwriting staged bytes (the
        # single-device pipeline can pool because it ships each slot before
        # acquiring the next)
        staged: list[list] = []
        try:
            for d in range(D):
                idxs = assigned[d]
                chunks = []
                for ofs in range(0, len(idxs), fi.K):
                    part = idxs[ofs : ofs + fi.K]
                    K = fi._chunk_K(len(part))
                    wire = np.zeros((K, fi._wire_bytes), dtype=np.uint8)
                    counts = np.zeros((K,), dtype=np.int32)
                    bases = np.zeros((K,), dtype=np.int64)
                    for j, k in enumerate(part):
                        lo = k * B
                        hi = min(lo + B, n)
                        counts[j] = hi - lo
                        buf, base = encode(
                            ts_arr[lo:hi],
                            {kk: v[lo:hi] for kk, v in cols.items()},
                            hi - lo,
                        )
                        bases[j] = base
                        wire[j, :] = buf
                    chunks.append((wire, counts, bases, len(part), part))
                staged.append(chunks)
        except WireNarrowMisfit:
            return None

        # dispatch round-robin across devices so all D run concurrently
        # (jax dispatch is async; each chunk's submit returns immediately)
        import jax

        results: list[list] = [[] for _ in range(D)]
        rounds = max(len(c) for c in staged)
        # lineage: chunks dispatch round-robin (NOT global batch order), so
        # observations park keyed by global batch index and replay in order
        # at _lin_end_send (observability/lineage.py)
        fi._lin_begin_send()
        try:
            for r in range(rounds):
                for d in range(D):
                    if r >= len(staged[d]):
                        continue
                    wire, counts, bases, nb, part = staged[d][r]
                    dev_wire = jax.device_put(wire, self.devices[d])
                    packs, completion = fi._dispatch_chunk(
                        prog, dev_wire, counts, bases, now, ds, tracked, tr,
                        stream_span, deliver=deliver, lin_ks=part,
                    )
                    if packs is None and completion is None:
                        # guarded dispatch failure: the junction's policy
                        # owned it; this chunk's batches deliver nothing
                        # (the exact per-batch-path semantics of a dropped
                        # failing batch)
                        results[d].append((None, counts, nb))
                        continue
                    with self._lock:
                        self.dispatches[d] += 1
                        self.events[d] += int(counts.sum())
                    results[d].append((packs, counts, nb))
        finally:
            # even when an unguarded dispatch failure propagates to the
            # sender, the already-dispatched chunks' parked observations
            # must replay — dropping them would desync every recorder's
            # seq accounting for all later sends
            fi._lin_end_send()
        with self._lock:
            self.sends += 1
        if deliver:
            # same failure contract as every single-device drain
            # (_drain_guarded): a guarded junction's machinery owns callback
            # errors, an unguarded one re-raises to the sender
            try:
                with stage("shard.merge", devices=D):
                    self._merged_drain(fi, results, M, D)
            except Exception as e:
                j = self.junction
                if j.exception_handler is None and j.fault_policy is None:
                    raise
                j._on_worker_error(e, "sharded drain")
        return True

    # ---- ordered merge drain --------------------------------------------

    def _merged_drain(self, fi, results, M: int, D: int) -> None:
        """Read back every device's packed outputs and deliver each
        endpoint's rows in ORIGINAL micro-batch order: global batch k's
        segment comes from device k % D's next undelivered iteration, so
        the interleaved row stream (and the per-segment callback grouping)
        is byte-identical to the single-device drain."""
        from siddhi_tpu.core.ingest import _bucket, read_dense

        for pos, i in enumerate(fi._deliver_idx):
            qr = fi.endpoints[i].qr
            if not getattr(qr, "query_callbacks", None):
                continue
            _layout, row_bytes = fi._deliver_layout[i]
            dev_rows: list[np.ndarray] = []
            dev_cnts: list[np.ndarray] = []
            for d in range(D):
                parts: list[np.ndarray] = []
                cnt_parts: list[np.ndarray] = []
                for packs, counts, nb in results[d]:
                    K = counts.shape[0]
                    if packs is None:  # dropped chunk: zero rows, kept
                        cnt_parts.append(np.zeros((nb,), np.int32))
                        continue  # alignment with its assigned batches
                    hdr_rows = -(-4 * K // row_bytes)
                    buf = packs[pos]["buf"]
                    # header first, then the filled row prefix by the sizes
                    # the fused drain reads in, never the whole [K*cap]
                    # buffer: the one way to read a packed buffer
                    hdr = read_dense(buf, 0, hdr_rows)
                    cnts = hdr.reshape(-1)[: 4 * K].view(np.int32)
                    total = int(cnts.sum())
                    if total:
                        L = _bucket(total, buf.shape[0] - hdr_rows)
                        parts.append(read_dense(buf, hdr_rows, L)[:total])
                    # padding iterations (j >= nb) carry count 0 and no rows
                    cnt_parts.append(np.asarray(cnts[:nb], np.int32))
                dev_rows.append(
                    np.concatenate(parts)
                    if parts
                    else np.zeros((0, row_bytes), np.uint8)
                )
                dev_cnts.append(
                    np.concatenate(cnt_parts)
                    if cnt_parts
                    else np.zeros((0,), np.int32)
                )
            seq_parts: list[np.ndarray] = []
            cseq = np.zeros((M,), dtype=np.int32)
            offs = [0] * D
            iters = [0] * D
            for k in range(M):
                d = k % D
                ci = iters[d]
                iters[d] += 1
                c = int(dev_cnts[d][ci]) if ci < len(dev_cnts[d]) else 0
                cseq[k] = c
                if c:
                    seq_parts.append(dev_rows[d][offs[d] : offs[d] + c])
                    offs[d] += c
            total = int(cseq.sum())
            if not total:
                continue
            host = np.concatenate(seq_parts)
            fi.deliver_endpoint(i, host, cseq, total)


# ---------------------------------------------------------------------------
# partition-axis mesh placement
# ---------------------------------------------------------------------------


def apply_partition_mesh(app_runtime, devices) -> dict:
    """Place every plain `PartitionedQueryRuntime`'s `[P]` state axis on a
    mesh over `devices`, swapping the runtime's outer jitted step for one
    with explicit in/out shardings (the replicated-batch mode: each device
    advances only its own partition slots; the merged flat output — and so
    delivery order — is bit-identical to the unsharded step's). Returns
    qid -> placement info for `/status.json` and explain()."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from siddhi_tpu.core.partition import PartitionedQueryRuntime

    D = len(devices)
    placed: dict = {}
    mesh = None
    for pr in app_runtime.partitions:
        for qr in pr.queries:
            if type(qr) is not PartitionedQueryRuntime or qr.key_of is None:
                # joins/patterns/#inner-fed queries keep the single-device
                # vmapped step (their [P] axes are shardable the same way;
                # scoped out until the mesh contract covers their timers)
                continue
            qid = qr.query_id
            padded = 0
            if qr.p % D != 0:
                if qr.state is not None:
                    # live [P] buffers can't be resized in place; only a
                    # pre-first-event placement pads
                    placed[qid] = {
                        "sharded": False,
                        "reason": (
                            f"partitionCapacity {qr.p} % devices {D} != 0 "
                            "with live state"
                        ),
                    }
                    continue
                # pad the [P] axis to the next multiple of D with DEAD
                # slots: the shared ptable keeps its original capacity so
                # key->slot allocation (and its overflow threshold) is
                # untouched, and the padded lanes behave exactly like
                # never-allocated lanes — timer rows run on fresh init
                # state and emit nothing, so emissions stay byte-identical
                target = -(-qr.p // D) * D
                padded = target - qr.p
                log.info(
                    "query '%s': padding @app:partitionCapacity %d to %d "
                    "(%d dead slot(s)) for the %d-device mesh",
                    qid, qr.p, target, padded, D,
                )
                qr.p = target
            if mesh is None:
                mesh = Mesh(np.array(devices), ("part",))
            shard = NamedSharding(mesh, P("part"))
            repl = NamedSharding(mesh, P())
            # same computation as the unsharded _pstep_outer (identical
            # emission lanes), state resharded [P] across the mesh; the aux
            # any()/min() reductions become XLA cross-device collectives and
            # the output decode gathers — the cross-device merge step.
            # donate_argnums matches the unsharded jit: the [P] state is the
            # largest tensor set in the system and must update in place
            # (the first call's host-built state isn't donatable — one
            # ignorable warning — every later call donates sharded buffers)
            # (ptable, states, route counters, batch, now) ->
            # (ptable, states, route counters, flat output, its slots, aux):
            # the routed [P, B'] sub-batches and the [P, K'] emissions
            # follow the state's sharding inside the program
            qr._pstep_outer = jax.jit(
                qr._pstep_outer_impl,
                in_shardings=(repl, shard, repl, repl, repl),
                out_shardings=(repl, shard, repl, repl, repl, repl),
                donate_argnums=(1,),
            )
            placed[qid] = {
                "sharded": True,
                "devices": D,
                "axis": "part",
                "local_slots": qr.p // D,
            }
            if padded:
                placed[qid]["padded_slots"] = padded
    return placed


# ---------------------------------------------------------------------------
# the app-level shard runtime (built at start())
# ---------------------------------------------------------------------------


class ShardRuntime:
    """Resolved sharded-execution mode of one app. Built by
    `SiddhiAppRuntime.start()` from the creation-time `@app:shard` /
    SIDDHI_TPU_SHARD resolution; `apply()` places partitioned state on the
    mesh and arms batch routers on eligible junctions."""

    def __init__(self, app_runtime, requested: int, axis: str):
        import jax

        self.app = app_runtime
        self.axis = axis
        self.requested = int(requested)
        devs = jax.devices()
        n = min(self.requested, len(devs))
        if n < self.requested:
            log.warning(
                "app '%s': @app:shard requested %d devices but only %d are "
                "visible; clamping (set XLA_FLAGS="
                "--xla_force_host_platform_device_count=N for a virtual "
                "CPU mesh)",
                app_runtime.name, self.requested, len(devs),
            )
        self.devices = devs[:n]
        self.partitioned: dict = {}
        self.routers: dict = {}
        self.keyshard: dict = {}
        self.joins: dict = {}

    @property
    def n(self) -> int:
        return len(self.devices)

    def apply(self) -> None:
        if self.n < 2:
            log.warning(
                "app '%s': sharded execution disabled (%d device(s) "
                "available)", self.app.name, self.n,
            )
            return
        if self.axis in ("auto", "part", "keys"):
            self.partitioned = apply_partition_mesh(self.app, self.devices)
        self.rearm_keyshard()
        self.rearm_routers()

    def rearm_keyshard(self) -> None:
        """(Re)arm key-sharded group-by and join state (axis='keys' only —
        parallel/keyshard.py). Called by apply() at start AND by the churn
        splice after fused engines are rebuilt: a hot-deployed grouped
        query (state still None) gets armed before its first event;
        already-armed queries keep their live [D] state and jitted step."""
        if self.n < 2 or self.axis != "keys":
            return
        from siddhi_tpu.parallel.keyshard import (
            apply_join_mesh,
            apply_keyshard,
        )

        self.keyshard.update(apply_keyshard(self.app, self.devices))
        self.joins.update(apply_join_mesh(self.app, self.devices))

    def rearm_routers(self) -> None:
        """(Re)arm batch-axis routers on every eligible fused ingest
        engine. Called by apply() at start AND by the churn splice
        (core/churn.py) after fused engines are rebuilt: a hot
        deploy/undeploy can change a junction's eligibility (a stateful
        query joining the group vetoes the router; its removal restores
        it), and the rebuilt engines start with `shard_router = None`."""
        if self.n < 2 or self.axis not in ("auto", "batch"):
            return
        sm = self.app.statistics_manager
        prev_routers = self.routers
        self.routers = {}
        for sid, j in list(self.app.junctions.items()):
            fi = j.fused_ingest
            if fi is None or not router_eligible(fi):
                continue
            r = BatchShardRouter(j, self.devices)
            prev = prev_routers.get(sid)
            if prev is not None and len(prev.devices) == len(self.devices):
                # carry the cumulative counters into the replacement: the
                # siddhi_shard_device_*_total families are Prometheus
                # COUNTERS — zeroing them on every churn splice would read
                # as counter resets in rate()/increase() and break the
                # per-device-sums == everything-sent invariant
                r.dispatches = list(prev.dispatches)
                r.events = list(prev.events)
                r.sends = prev.sends
            fi.shard_router = r
            self.routers[sid] = r
            if sm is not None:
                sm.register_shard(f"stream.{sid}", r)

    def describe_state(self) -> dict:
        d: dict = {
            "devices": self.n,
            "requested": self.requested,
            "axis": self.axis,
        }
        if self.partitioned:
            d["partitioned"] = dict(self.partitioned)
        if self.routers:
            d["streams"] = {
                sid: r.describe_state() for sid, r in self.routers.items()
            }
        if self.keyshard:
            ks = {}
            for qid, info in self.keyshard.items():
                qr = self.app.queries.get(qid)
                ex = getattr(qr, "_keyshard", None)
                live = ex.describe_state() if ex is not None else {}
                ks[qid] = {**info, **live}
            d["keyshard"] = ks
        if self.joins:
            d["joins"] = dict(self.joins)
        return d
