"""Double-buffered ingest pipeline: overlap encode, h2d, dispatch, drain.

The fused ingest path (core/ingest.py) runs three host-visible stages per
chunk — host encode, host->device transfer, jitted dispatch — plus, in
deliver mode, a blocking d2h readback + decode + callback delivery. Run
strictly serialized, the sender's wall-clock per chunk is
`encode + h2d + device + d2h` even though the stages use disjoint resources
(Python/numpy on the host, the wire, the device, and the readback path).

This module keeps those stages concurrently busy (the Hazelcast Jet
"pipeline stages must stay busy" argument, PAPERS.md):

1. host encode writes into one of `depth` POOLED wire buffers, so chunk
   N+1's encode can start while chunk N's buffer is still being shipped
   (a slot is reused only after its transfer completed);
2. chunk N+1 is encoded and `jax.device_put` while chunk N's donated-state
   dispatch is still in flight — JAX dispatch is already async, so the win
   is moving encode (and the transfer submit) off the dispatch critical
   path;
3. a bounded background drain worker syncs each chunk's packed output
   buffer, decodes it, and runs query-callback delivery in chunk order,
   with backpressure (at most `depth` undrained chunks in flight) so state
   donation stays safe and device memory for packed outputs is bounded;
4. the read of a chunk's packed output is STARTED by the sender when the
   chunk is handed to the drain (`submit`'s `reads`: a slice queued behind
   the chunk program, its copy to the host behind the slice, and on the
   reader thread, `read_ahead`, the wait for the bytes and their relayout
   into dense rows, which hold no interpreter lock) and only AWAITED by
   whoever drains the chunk, so the read of chunk N+1 runs while the
   worker is still decoding chunk N.

Ordering and failure semantics:

* `try_send` BARRIERS on the drain before returning, so callbacks fire in
  chunk order and complete before `send_columns` returns — any later
  per-batch `send` observes the same ordering as the per-batch path;
* a delivery failure on the drain worker goes through the junction's
  existing failure machinery (`_on_worker_error`: log + error stats +
  exception handler), mirroring the @async drain workers; when the
  junction has NO handler and NO @OnError policy the error is re-raised
  to the sender at the barrier.

This module owns ONE decision of the fused send: where a chunk's wire
buffer comes from and where its drain runs. core/ingest.py's chunk loop is
written once against the verbs `acquire` / `ship` / `retire` / `submit` /
`pending_error` / `barrier`; IngestPipeline answers them with the pooled
slots and the worker above, and `IngestPipeline.inline()` answers them for
a RE-ENTRANT send (a query callback sending from the drain worker, or a
failure handler on the thread that holds the send lock), which must not
wait on the pipeline it runs inside: fresh unpooled buffers, each chunk
drained on the calling thread one chunk late. Which side a send gets
follows from which thread is calling, never from an option.

Configuration: the `@pipeline(depth='N')` stream annotation.
"""

from __future__ import annotations

import queue
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Optional

import numpy as np

from siddhi_tpu.observability.profiler import inherited_ids, stage
from siddhi_tpu.testing import faults as _faults

DEFAULT_DEPTH = 2
_MAX_DEPTH = 8

def iter_pipeline_annotation_problems(ann):
    """Yield one message per malformed `@pipeline` element — THE validation
    rules, shared by the runtime resolver (raises on the first) and the
    analyzer's SA112 diagnostics (reports them all), so the two can never
    drift."""
    for k, v in ann.elements:
        if k == "depth":
            try:
                ok = 1 <= int(v) <= _MAX_DEPTH
            except (TypeError, ValueError):
                ok = False
            if not ok:
                yield (
                    f"@pipeline depth '{v}' must be an integer in "
                    f"1..{_MAX_DEPTH}"
                )
        else:
            yield (
                f"unknown @pipeline option '{k if k is not None else v}' "
                "(expected depth)"
            )


def resolve_pipeline_annotation(ann) -> int:
    """The pipeline depth of one stream from its `@pipeline` annotation (or
    None). Raises SiddhiAppCreationError on malformed options — the runtime
    analog of the analyzer's SA112 diagnostic."""
    from siddhi_tpu.core.errors import SiddhiAppCreationError

    if ann is None:
        return DEFAULT_DEPTH
    for problem in iter_pipeline_annotation_problems(ann):
        raise SiddhiAppCreationError(problem)
    return int(ann.element("depth", str(DEFAULT_DEPTH)))


class _WireSlot:
    """One pooled host wire buffer + the device array gating its reuse.

    `jax.device_put` of a numpy array may ALIAS the host buffer instead of
    copying (the CPU backend does, depending on the buffer's size and
    alignment — so it cannot be probed once globally). ship() detects it
    per shipment by comparing buffer POINTERS (host-only, no device
    work):

    * copied: `ref` is the shipped device array — reuse is safe once the
      TRANSFER completed;
    * aliased (or unknown): retire() swaps `ref` for a completion array of
      the dispatch that READ the wire — only the program finishing frees
      the buffer for overwrite.

    Copied shipments additionally form a device-side STAGING RING: the
    slot keeps `dev` (the device wire) and `dev_gate` (a completion array
    of the consuming dispatch), and the next acquire() of the slot
    explicitly deletes the retired device buffer once the dispatch that
    read it finished — steady-state ingest then cycles `depth` device
    staging buffers through the allocator deterministically instead of
    letting GC lag grow device memory (the h2d-wall work's
    "persistent donated device-side staging rings")."""

    __slots__ = ("buf", "ref", "aliased", "dev", "dev_gate")

    def __init__(self, shape):
        self.buf = np.zeros(shape, dtype=np.uint8)
        self.ref = None
        self.aliased = True
        self.dev = None
        self.dev_gate = None


class IngestPipeline:
    """Per-junction pipeline engine owned by a FusedJunctionIngest.

    Senders are serialized by the ingest's send lock, so acquire/ship run
    from one thread at a time; the drain worker is the only other thread
    touching this object (via the queue/condvar only).
    """

    def __init__(self, junction, depth: int = DEFAULT_DEPTH, drain_fn=None):
        self.junction = junction
        self.depth = max(1, int(depth))
        # fn(packs, reads, K, wf, ids, t_submit, tracker): the ingest's _drain
        self.drain_fn = drain_fn
        self.stats = None  # PipelineStats | None, set by the owner
        # where ship() puts the wire: None = the default device; the owner
        # sets the keys mesh's replicated sharding when the chunk program
        # runs on it (a key-sharded member, parallel/keyshard.py)
        self.wire_sharding = None
        self._pool: dict[tuple, dict] = {}  # (K, nb) -> {slots, next}
        self._cv = threading.Condition()
        self._inflight = 0  # submitted, not yet drained
        self._error: Optional[BaseException] = None
        self._q: Optional[queue.Queue] = None
        self._thread: Optional[threading.Thread] = None
        # one thread that finishes the reads the sender starts (read_ahead);
        # it starts with the first of them
        self._reader = ThreadPoolExecutor(
            max_workers=1,
            thread_name_prefix=f"siddhi-readback-{junction.schema.stream_id}",
        )
        self._closed = False

    # ---- wire buffer pool ------------------------------------------------

    def acquire(self, K: int, wire_bytes: int, chunk=None) -> _WireSlot:
        """A host buffer for one [K, wire_bytes] chunk, safe to overwrite:
        pooled, blocking on the slot's reuse gate (see _WireSlot): the
        `slot_wait` stage of chunk `chunk`."""
        key = (int(K), int(wire_bytes))
        ent = self._pool.get(key)
        if ent is None:
            ent = self._pool[key] = {
                "slots": [
                    _WireSlot(key) for _ in range(max(2, self.depth))
                ],
                "next": 0,
            }
        slots = ent["slots"]
        slot = slots[ent["next"]]
        ent["next"] = (ent["next"] + 1) % len(slots)
        if slot.ref is not None:
            with stage("slot_wait", chunk=chunk):
                try:
                    slot.ref.block_until_ready()
                except Exception:
                    # failed execution: the gating work is no longer
                    # running, so the buffer is free (gate arrays are never
                    # donated — see _dispatch_chunk's completion contract —
                    # so deletion cannot race this wait)
                    pass
            slot.ref = None
        if slot.dev is not None:
            # staging ring: free the previous cycle's device wire once the
            # dispatch that READ it completed (dev_gate) — but only when
            # that completion is ALREADY ready (steady state): a blocking
            # wait here would re-serialize the encode-under-dispatch
            # overlap the pipeline exists for. Not-yet-ready (or gateless:
            # failed submit / donated-only outputs) buffers are abandoned
            # to GC — deleting under a possibly-running program would be a
            # device UAF.
            gate, slot.dev_gate = slot.dev_gate, None
            dev, slot.dev = slot.dev, None
            if gate is not None:
                try:
                    if gate.is_ready():
                        dev.delete()
                except Exception:
                    pass
        return slot

    def ship(self, slot: _WireSlot):
        """Start the async host->device transfer of the slot's buffer and
        return the device array; detects per shipment whether the backend
        aliased the host buffer (see _WireSlot) and gates the slot
        accordingly."""
        import jax

        if self.wire_sharding is None:
            dev = jax.device_put(slot.buf)
            copies = (dev,)
        else:
            # one copy per device of the mesh; the slot is free again only
            # when every device has read its copy — `ref` is the whole
            # array, whose readiness (or, aliased, the program's) is all of
            # theirs
            dev = jax.device_put(slot.buf, self.wire_sharding)
            copies = tuple(s.data for s in dev.addressable_shards)
        try:
            host = slot.buf.ctypes.data
            slot.aliased = any(
                c.unsafe_buffer_pointer() == host for c in copies
            )
        except Exception:
            slot.aliased = True  # can't tell: assume the worst
        slot.ref = dev
        return dev

    def retire(self, slot: _WireSlot, completion) -> None:
        """For an ALIASED shipment, swap the slot's reuse gate for an
        output array of the dispatch that consumed the wire (acquire()
        then waits for the program, not the no-op transfer). With no
        non-donated completion available (None: the dispatch failed at
        submit, or its only outputs are donated query states) there is
        nothing safe to gate on — the aliased buffer is ABANDONED to the
        shipped array's reference and the slot gets a virgin buffer, so a
        still-running program can never see the next chunk's bytes. No-op
        for copied shipments: ship()'s transfer gate suffices."""
        if not slot.aliased:
            # copied shipment: the host buffer only needs the transfer
            # gate (ship() set it), but the DEVICE wire joins the staging
            # ring — record the consuming dispatch's completion so the
            # next cycle can free it deterministically (see acquire())
            slot.dev = slot.ref
            slot.dev_gate = completion
            return
        if completion is not None:
            slot.ref = completion
        else:
            slot.buf = np.zeros_like(slot.buf)
            slot.ref = None

    def in_flight(self) -> int:
        """Chunks submitted but not yet drained."""
        with self._cv:
            return self._inflight

    def describe_state(self) -> dict:
        """Introspection: depth, slots in flight, pooled wire slots, drain
        worker started (see observability/introspect.py)."""
        return {
            "depth": self.depth,
            "in_flight": self.in_flight(),
            "wire_slots": sum(
                len(ent["slots"]) for ent in self._pool.values()
            ),
            "drain_thread": self._thread is not None,
            "closed": self._closed,
        }

    # ---- drain -----------------------------------------------------------

    def is_drain_thread(self) -> bool:
        return (
            self._thread is not None
            and threading.current_thread() is self._thread
        )

    def read_ahead(self, fn, *args) -> Future:
        """Run `fn(*args)` on the reader thread: the host's half of a read
        the sender has queued on the device (waiting for the bytes, laying
        them out), so that it is done, or under way, when the drain asks.
        Reads finish in the order they were started; a failure is kept in
        the Future and raised where its result is asked for, at the drain."""
        return self._reader.submit(fn, *args)

    def submit(self, packs, reads, K: int, wf=None, chunk=None) -> None:
        """Queue one chunk's packed outputs for ordered delivery, with the
        reads of them the sender has started (`reads`, awaited by the
        drain; `wf`: the chunk's stage waterfall, closed by the drain;
        `chunk`: its id in the stage spans). Blocks while `depth` chunks
        are already in flight (backpressure): the `submit_wait` stage."""
        if self._thread is None:
            self._start_thread()
        with self._cv:
            if self._inflight >= self.depth and not self._closed:
                with stage("submit_wait", chunk=chunk):
                    while self._inflight >= self.depth and not self._closed:
                        self._cv.wait()
            self._inflight += 1
        ids = {**inherited_ids(), "chunk": chunk}
        self._q.put((packs, reads, K, wf, ids, time.perf_counter_ns()))

    def pending_error(self) -> bool:
        """True once an unguarded drain failure is stashed for barrier():
        the sender polls this per chunk and stops ingesting, bounding the
        extra chunks committed past a poisoned delivery to the pipeline
        depth (the inline side's drain-one-late commits one extra)."""
        with self._cv:
            return self._error is not None

    def barrier(self) -> None:
        """Wait until every submitted chunk has been delivered; re-raise a
        drain failure here when the junction has no handler/policy to own
        it."""
        with self._cv:
            if self._inflight > 0:
                with stage("barrier"):
                    while self._inflight > 0:
                        self._cv.wait()
        err, self._error = self._error, None
        if err is not None:
            raise err

    def _start_thread(self) -> None:
        self._q = queue.Queue()
        self._thread = threading.Thread(
            target=self._drain_loop,
            daemon=True,
            name=f"siddhi-pipeline-{self.junction.schema.stream_id}",
        )
        self._thread.start()

    def _drain_loop(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            try:
                self._drain_one(*item)
            except Exception as exc:  # must not kill the worker
                self._on_drain_error(exc)
            finally:
                with self._cv:
                    self._inflight -= 1
                    self._cv.notify_all()

    def _drain_one(self, packs, reads, K: int, wf, ids, t_submit) -> None:
        # fault-injection site `drain_worker` (testing/faults.py): the
        # pipelined analog of the @async drain-worker site — an injected
        # fault rides the same guarded/unguarded routing a poisoned
        # delivery takes (_on_drain_error / barrier re-raise)
        if _faults.ACTIVE is not None:
            _faults.ACTIVE.check(
                "drain_worker", self.junction.schema.stream_id
            )
        ps = self.stats
        self.drain_fn(packs, reads, K, wf, ids, t_submit, ps and ps.drain)

    def _junction_owns(self, exc: Exception, where: str) -> bool:
        """Hand a delivery failure to a guarded junction's failure
        machinery — the same as the @async drain workers' (log + error
        stats + exception handler). False on an unguarded junction: the
        failure goes back to the sender."""
        j = self.junction
        if j.exception_handler is None and j.fault_policy is None:
            return False
        j._on_worker_error(exc, where)
        return True

    def _on_drain_error(self, exc: Exception) -> None:
        if self._junction_owns(exc, "pipeline drain"):
            return
        with self._cv:
            if self._error is None:
                self._error = exc  # surfaces to the sender at barrier()

    def inline(self) -> "_InlineDrain":
        """The verbs of this pipeline for ONE re-entrant send (see the
        module docstring)."""
        return _InlineDrain(self)

    def close(self) -> None:
        """Flush nothing (callers barrier first); stop the drain worker
        and the reader."""
        self._closed = True
        self._reader.shutdown(wait=False)
        with self._cv:
            self._cv.notify_all()
        t = self._thread
        if t is not None and t is not threading.current_thread():
            self._q.put(None)
            t.join(timeout=2.0)
        self._thread = None


class _InlineDrain:
    """IngestPipeline's verbs for one re-entrant send, on the calling
    thread: it takes no lock (the outer sender holds the send lock), starts
    no thread and touches neither the pooled slots nor the drain queue,
    which belong to the outer send and may be staging concurrently."""

    stats = None  # a stage of the outer send already holds this time

    def __init__(self, pl: IngestPipeline):
        self._pl = pl
        self._parked = None  # the last chunk's packs, drained one late

    def acquire(self, K: int, wire_bytes: int, chunk=None) -> _WireSlot:
        return _WireSlot((int(K), int(wire_bytes)))

    def ship(self, slot: _WireSlot):
        import jax

        return jax.device_put(slot.buf, self._pl.wire_sharding)

    def retire(self, slot: _WireSlot, completion) -> None:
        """Nothing to gate: the buffer is never written again."""

    def submit(self, packs, reads, K: int, wf=None, chunk=None) -> None:
        """Park this chunk and drain the PREVIOUS one now that this chunk's
        device work is launched: the host decode overlaps device compute
        and this chunk's read, and callbacks still fire in order before the
        send returns."""
        prev, self._parked = self._parked, (
            packs, reads, K, wf, {"chunk": chunk}, time.perf_counter_ns(),
        )
        if prev is not None:
            self._drain(prev)

    def pending_error(self) -> bool:
        return False  # an unguarded failure raised out of submit()

    def barrier(self) -> None:
        prev, self._parked = self._parked, None
        if prev is not None:
            self._drain(prev)

    def _drain(self, item) -> None:
        try:
            self._pl.drain_fn(*item, None)
        except Exception as exc:
            if not self._pl._junction_owns(exc, "fused drain"):
                raise
