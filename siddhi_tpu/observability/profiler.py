"""Continuous profiler: JIT compile telemetry + per-chunk latency waterfalls.

Two collectors, both owned by the app's `StatisticsManager` (so the
registry's `enabled` flag is their gate — `enable_stats(False)` stops them
at one attribute check, the same contract as every tracker):

* `CompileTelemetry` — the engine's device programs are `jax.jit`-compiled
  per argument-shape signature, and a recompile mid-traffic is a silent
  multi-hundred-ms stall that the latency histograms attribute to the wrong
  place. Every profiled dispatch site reports its call wall time plus the
  program's jit-cache size before/after (`PjitFunction._cache_size()`, no
  device work); a cache-size growth IS a compile, and the cause taxonomy
  below names why it happened. Wall time is attributed to the compile only
  for compiling calls; non-compiling calls count as cache hits.

* `Profiler` — per-chunk stage waterfalls. The fused ingest path reports
  encode → h2d → dispatch → queue → device → readback → deliver spans per
  chunk (core/ingest.py + core/pipeline.py); the per-batch path reports the
  coarser encode → dispatch → device → readback breakdown via a
  thread-local active-chunk context (stream_junction.py send_columns +
  query_runtime.py). A bounded top-K ring keeps the SLOWEST chunks with
  their full breakdowns, so "what did the p99.99 chunk spend its time on"
  is answerable after the fact without logging every chunk.

* `stage()` — THE instrumentation site of a host stage: one context manager
  that, while a `jax.profiler` session is open, writes a `siddhi:<name>`
  span into the profiler's own trace (same file and clock as the device
  plane), and, when the statistics gate is on, records the same interval
  into the trackers and the chunk waterfall it was handed. Span names are
  listed in PERF.md §3 and the README.

* `CompileEvents` — process-wide count of XLA backend compiles (builds and
  persistent-cache loads), fed by `jax.monitoring`; needs no
  `@app:statistics` and sees eager programs. `CompileTelemetry` keeps the
  causes.

Recompile-cause taxonomy (stable strings, documented in the README):

    first_compile       the program's first call (expected, once)
    shape_change        a batch/argument shape this program had not seen
                        (per-batch path: timer batches, downstream cap-64
                        re-publishes, @app:batch drift)
    tail_variant_k      fused ingest compiled a smaller-K tail variant of
                        the chunk program (core/ingest.py _chunk_K)
    full_width_rebuild  a value outgrew the sampled narrow wire and the
                        fused program was rebuilt full-width
    deliver_set_change  the set of endpoints with query callbacks changed,
                        forcing a deliver-mode rebuild
    donation_mismatch   a recompile at an ALREADY-SEEN signature: the only
                        way that happens is the carried state pytree
                        changing under the program (donated buffer dtype/
                        shape/sharding drift) — worth an alert, it means
                        every chunk may be paying it

Served as `/profile` on the MetricsServer (manager.profile_reports()) and
folded into `runtime.explain()` node annotations (observability/explain.py).
"""

from __future__ import annotations

import collections
import gc
import threading
import time
from typing import Optional

import jax
import jax.monitoring
from jax.profiler import TraceAnnotation

from siddhi_tpu.observability.metrics import LogHistogram

CAUSE_FIRST = "first_compile"
CAUSE_SHAPE = "shape_change"
CAUSE_TAIL_K = "tail_variant_k"
CAUSE_FULL_WIDTH = "full_width_rebuild"
CAUSE_DELIVER_SET = "deliver_set_change"
CAUSE_DONATION = "donation_mismatch"

_RECENT_CAP = 32  # per-component ring of recent compile events


def jit_cache_size(prog) -> Optional[int]:
    """Entries in a jitted callable's trace/compile cache, or None when the
    backend object does not expose it (telemetry then falls back to the
    signature-set heuristic: first sighting of a signature = compile)."""
    try:
        return int(prog._cache_size())
    except Exception:
        return None


class _ComponentCompiles:
    """Per-component compile ledger (one per profiled program)."""

    __slots__ = (
        "compiles", "cache_hits", "wall_ms_total", "causes", "signatures",
        "last_cache_size", "last_prog_id", "recent",
    )

    def __init__(self) -> None:
        self.compiles = 0
        self.cache_hits = 0
        self.wall_ms_total = 0.0
        self.causes: dict[str, int] = {}
        self.signatures: set = set()
        self.last_cache_size = 0
        self.last_prog_id = 0  # id() of the jitted object last observed
        self.recent: list[dict] = []


class CompileTelemetry:
    """Compile ledger for every profiled jitted program in one app."""

    def __init__(self, gate) -> None:
        self._gate = gate
        self._lock = threading.Lock()
        self._components: dict[str, _ComponentCompiles] = {}

    def observe(
        self,
        component: str,
        prog,
        signature,
        wall_ns: int,
        cause_hint: Optional[str] = None,
    ) -> None:
        """Report one call of `prog` (already made): wall time + cache-size
        delta decide compile vs hit; `cause_hint` labels rebuild-driven
        compiles (fused ingest passes tail/rebuild hints). One gate check
        when statistics are disabled."""
        if not self._gate.enabled:
            return
        size = jit_cache_size(prog)
        with self._lock:
            ent = self._components.get(component)
            if ent is None:
                ent = self._components[component] = _ComponentCompiles()
            new_sig = signature not in ent.signatures
            ent.signatures.add(signature)
            if ent.last_prog_id != id(prog):
                # a REBUILT program (fused full-width/deliver-set rebuilds
                # swap the jit object) starts with an empty cache: comparing
                # its size against the old program's would count the rebuild
                # compile as a cache hit and drop its cause hint
                ent.last_prog_id = id(prog)
                ent.last_cache_size = 0
            if size is not None:
                compiled = size > ent.last_cache_size
                ent.last_cache_size = size
            else:
                compiled = new_sig  # fallback heuristic
            if not compiled:
                ent.cache_hits += 1
                return
            if cause_hint is not None and not (
                cause_hint == CAUSE_TAIL_K and ent.compiles == 0
            ):
                # rebuild hints always win; a tail hint on the program's
                # very first compile is just the first compile happening to
                # land on a short send
                cause = cause_hint
            elif ent.compiles == 0:
                cause = CAUSE_FIRST
            elif new_sig:
                cause = CAUSE_SHAPE
            else:
                cause = CAUSE_DONATION
            ent.compiles += 1
            wall_ms = round(wall_ns / 1e6, 3)
            ent.wall_ms_total += wall_ms
            ent.causes[cause] = ent.causes.get(cause, 0) + 1
            ent.recent.append({
                "cause": cause,
                "wall_ms": wall_ms,
                "signature": repr(signature),
                "at_ms": int(time.time() * 1000),
            })
            if len(ent.recent) > _RECENT_CAP:
                del ent.recent[0]

    def report(self) -> dict:
        """component -> {compiles, cache_hits, wall_ms_total, causes,
        signatures, recent[]} (recent: oldest first, bounded)."""
        with self._lock:
            return {
                name: {
                    "compiles": ent.compiles,
                    "cache_hits": ent.cache_hits,
                    "wall_ms_total": round(ent.wall_ms_total, 3),
                    "causes": dict(ent.causes),
                    "signatures": len(ent.signatures),
                    "recent": list(ent.recent),
                }
                for name, ent in self._components.items()
            }

    def component(self, name: str) -> Optional[dict]:
        """Combined ledger summary for a component and its sub-programs —
        `name` plus every `name[...]` entry (pattern per-stream steps, join
        sides each jit their own program). For explain annotations."""
        with self._lock:
            # "_" variants: fused groups compile mode-specific programs
            # under suffixed names (e.g. `...fusedgroup.0_deliver`) — same
            # logical component, so summaries and calibration pair them
            ents = [
                e for k, e in self._components.items()
                if k == name or k.startswith(name + "[")
                or k.startswith(name + "_")
            ]
            if not ents:
                return None
            causes: dict[str, int] = {}
            for e in ents:
                for c, n in e.causes.items():
                    causes[c] = causes.get(c, 0) + n
            return {
                "compiles": sum(e.compiles for e in ents),
                "cache_hits": sum(e.cache_hits for e in ents),
                "wall_ms_total": round(
                    sum(e.wall_ms_total for e in ents), 3
                ),
                "causes": causes,
            }


class StageWaterfall:
    """One chunk's stage breakdown. Stages accumulate in call order; the
    chunk's total is wall-clock begin→end (stages may nest/overlap — e.g.
    the per-batch 'device' span sits inside 'dispatch' — so the total is
    NOT the stage sum)."""

    __slots__ = (
        "stream", "seq", "events", "path", "t0_ns", "total_ns", "stages",
    )

    def __init__(
        self, stream: str, seq: int, events: int, path: str = "fused"
    ) -> None:
        self.stream = stream
        self.seq = seq
        self.events = int(events)
        self.path = path  # "fused" chunk or per-"batch" send_columns slice
        self.t0_ns = time.perf_counter_ns()
        self.total_ns = 0
        self.stages: dict[str, int] = {}

    def stage(self, name: str, ns: int) -> None:
        self.stages[name] = self.stages.get(name, 0) + int(ns)

    def to_dict(self) -> dict:
        return {
            "stream": self.stream,
            "seq": self.seq,
            "events": self.events,
            "total_ms": round(self.total_ns / 1e6, 3),
            "stages_ms": {
                k: round(v / 1e6, 3) for k, v in self.stages.items()
            },
        }


class Profiler:
    """Bounded top-K ring of the slowest chunks, with full stage
    breakdowns, plus chunk/event counters and, per path and stage, a
    histogram over EVERY chunk (`stages_ms`: the per-stage mean an operator
    reads without a profiler session).

    `begin()` returns None when the gate is off — every downstream
    `wf.stage(...)` site is already behind an `if wf is not None` (or the
    thread-local equivalent), so a disabled profiler costs exactly one
    gate check per chunk.
    """

    def __init__(self, gate, top_k: int = 8) -> None:
        self._gate = gate
        self.top_k = int(top_k)
        self._lock = threading.Lock()
        self._seq = 0
        self.chunks = 0
        self.events = 0
        self._top: list[StageWaterfall] = []  # sorted slowest-first
        self._stage_hist: dict[tuple, LogHistogram] = {}  # (path, stage)
        self._tls = threading.local()

    # ---- chunk lifecycle --------------------------------------------------

    def begin(
        self, stream: str, events: int, path: str = "fused"
    ) -> Optional[StageWaterfall]:
        if not self._gate.enabled:
            return None
        with self._lock:
            self._seq += 1
            seq = self._seq
        return StageWaterfall(stream, seq, events, path)

    def end(self, wf: Optional[StageWaterfall]) -> None:
        if wf is None or not self._gate.enabled:
            return
        wf.total_ns = time.perf_counter_ns() - wf.t0_ns
        with self._lock:
            self.chunks += 1
            self.events += wf.events
            for name, ns in (*wf.stages.items(), ("total", wf.total_ns)):
                h = self._stage_hist.get((wf.path, name))
                if h is None:
                    h = self._stage_hist[(wf.path, name)] = LogHistogram()
                h.record(ns)
            top = self._top
            if len(top) < self.top_k:
                top.append(wf)
                top.sort(key=lambda w: -w.total_ns)
            elif wf.total_ns > top[-1].total_ns:
                top[-1] = wf
                top.sort(key=lambda w: -w.total_ns)

    # ---- thread-local context (per-batch path) ----------------------------

    def tls_begin(self, wf: Optional[StageWaterfall]) -> None:
        """Make `wf` the calling thread's active chunk so downstream
        components (query step, decode) can attribute sub-stages without
        plumbing the object through every call signature."""
        self._tls.wf = wf

    def tls_end(self) -> None:
        self._tls.wf = None

    def tls_wf(self) -> Optional[StageWaterfall]:
        """The calling thread's active chunk, for `stage(..., wf=...)`."""
        return getattr(self._tls, "wf", None)

    # ---- reporting --------------------------------------------------------

    def report(self) -> dict:
        with self._lock:
            stages: dict = {}
            for (path, name), h in self._stage_hist.items():
                p50, p99 = h.quantiles([0.5, 0.99])
                stages.setdefault(path, {})[name] = {
                    "count": h.count,
                    "mean": round(h.mean / 1e6, 4),
                    "p50": round(p50 / 1e6, 4),
                    "p99": round(p99 / 1e6, 4),
                }
            return {
                "chunks": self.chunks,
                "events": self.events,
                "slowest": [w.to_dict() for w in self._top],
                "stages_ms": stages,
            }


# ---------------------------------------------------------------------------
# stage spans on the profiler's clock
# ---------------------------------------------------------------------------

SPAN_PREFIX = "siddhi:"
# ids a span hands down to the spans opened inside it on the same thread:
# the request (`send`, a per-junction counter) and, on the fused path, the
# chunk, which is also what ties the drain worker's spans to the sender's
_INHERITED = ("send", "chunk")
_ctx = threading.local()


def inherited_ids() -> dict:
    """The calling thread's open `send` / `chunk`, for a hand-off to
    another thread (empty with no profiler session open)."""
    ctx = _ctx.__dict__
    return {k: ctx[k] for k in _INHERITED if ctx.get(k) is not None}


class stage:
    """`with stage("encode", ps and ps.encode, wf=wf, chunk=7): ...`

    While a `jax.profiler` session is open the block is a `siddhi:<name>`
    span in the profiler's trace, with `ids` (and the enclosing spans'
    `send` / `chunk`) as its stats. Independently, when any tracker (an
    object with `record_ns`; None entries are skipped) or a waterfall `wf`
    is given, the block's wall time is recorded into them — under
    `wf_name` in the waterfall, whose stage names predate the spans — and
    left in `.ns` for the caller (compile telemetry wants it). With neither
    a session nor a collector the site costs the `is_enabled` check."""

    __slots__ = (
        "name", "ns", "_trackers", "_wf", "_wf_name", "_ids", "_ann",
        "_saved", "_t0",
    )

    def __init__(self, name: str, *trackers, wf=None, wf_name=None, **ids):
        self.name = name
        self.ns = 0
        self._trackers = trackers
        self._wf = wf
        self._wf_name = wf_name or name
        self._ids = ids
        self._ann = None
        self._saved = None
        self._t0 = 0

    def __enter__(self):
        if TraceAnnotation.is_enabled():
            ids, ctx = self._ids, _ctx.__dict__
            self._saved = {k: ctx.get(k) for k in _INHERITED}
            for k in _INHERITED:
                if k in ids:
                    ctx[k] = ids[k]
                elif ctx.get(k) is not None:
                    ids[k] = ctx[k]
            self._ann = TraceAnnotation(SPAN_PREFIX + self.name, **ids)
            self._ann.__enter__()
        if self._wf is not None:
            self._t0 = time.perf_counter_ns()
        else:
            for t in self._trackers:
                if t is not None:
                    self._t0 = time.perf_counter_ns()
                    break
        return self

    def set(self, **ids) -> None:
        """Stats known only once the block ran (`collected` of a gc)."""
        if self._ann is not None:
            self._ann.set_metadata(**ids)

    def __exit__(self, *exc) -> None:
        if self._t0:
            self.ns = dt = time.perf_counter_ns() - self._t0
            for t in self._trackers:
                if t is not None:
                    t.record_ns(dt)
            if self._wf is not None:
                self._wf.stage(self._wf_name, dt)
        if self._ann is not None:
            self._ann.__exit__(*exc)
            _ctx.__dict__.update(self._saved)


class _GcSpans:
    """`siddhi:gc` spans round the collections of Python's collector, from a
    `gc.callbacks` hook held while any app runtime runs. A collection
    starts and stops on one thread and no second one starts meanwhile, so
    one open span is all there is to keep."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._users = 0
        self._open: Optional[stage] = None

    def acquire(self) -> None:
        with self._lock:
            self._users += 1
            if self._users == 1:
                gc.callbacks.append(self._hook)

    def release(self) -> None:
        with self._lock:
            self._users -= 1
            if self._users == 0:
                gc.callbacks.remove(self._hook)

    def _hook(self, phase: str, info: dict) -> None:
        if phase == "start":
            if TraceAnnotation.is_enabled():
                self._open = stage("gc", generation=info["generation"])
                self._open.__enter__()
        elif self._open is not None:
            span, self._open = self._open, None
            span.set(collected=info["collected"])
            span.__exit__(None, None, None)


GC_SPANS = _GcSpans()


# ---------------------------------------------------------------------------
# compile events, process-wide
# ---------------------------------------------------------------------------

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileEvents:
    """Every XLA backend compile of the process, as `jax.monitoring`
    reports it: `compiles` counts programs built OR loaded from the
    persistent cache (either stalls the caller), `cache_loads` the loads
    among them, `compile_s` their summed wall time; `recent` is a ring of
    the last 64 with the `time.perf_counter()` reading at which each
    ended, so a reader can ask which fell inside an interval of its own."""

    RING = 64

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.compiles = 0
        self.cache_loads = 0
        self.compile_s = 0.0
        self._recent: collections.deque = collections.deque(maxlen=self.RING)

    def install(self) -> None:
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **kw) -> None:
        if event != _BACKEND_COMPILE:
            return
        with self._lock:
            self.compiles += 1
            self.compile_s += seconds
            self._recent.append({
                "t": time.perf_counter(),
                "name": kw.get("fun_name"),
                "seconds": round(seconds, 6),
            })

    def _event(self, event: str, **kw) -> None:
        if event == _CACHE_HIT:
            with self._lock:
                self.cache_loads += 1

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "compiles": self.compiles,
                "cache_loads": self.cache_loads,
                "compile_s": round(self.compile_s, 6),
                "recent": list(self._recent),
            }


COMPILE_EVENTS = CompileEvents()
COMPILE_EVENTS.install()

# The device scopes are HLO metadata, which JAX leaves out of its
# persistent-cache key by default: an executable that a build without them
# (or with other names) cached would then be loaded in place of this build's
# and show that build's names in every profile (seen on the chip, PR 24: the
# parent commit's cached chunk program served this tree, and its trace had
# no scope). With the metadata in the key, two builds share an entry only
# where their programs carry the same names and source lines.
jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
