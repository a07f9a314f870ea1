"""The engine's own spans and scopes in the traced run's `.xplane.pb`.

The engine writes a `siddhi:<stage>` span round every host stage into the
profiler's trace (`siddhi_tpu/observability/profiler.py` `stage`) and names
the device program's stages with `jax.named_scope`. Both land in the file the
device plane is in, on its clock, so nothing has to be lined up. This module
gives
- per chunk (fused path) or per send the time of each stage,
- the device's idle seconds by the innermost span open on the sender and on
  the drain worker while it idled (`idle_by_span`), and
- device time by scope, exclusive: a `while` counts only what its children
  leave, so nothing is counted twice (`trace_reduce.op_totals` counts both).

The scope of a device operation is not in what `ProfileData` shows of it: it
is the `tf_op` stat of the operation's *metadata* (`jit(fused)/while/body/
q.q/window.length/ring_update/...`; looked at by hand in a v5e trace), so the
planes' metadata tables are read from the file's bytes with the few lines of
protobuf wire format below. A trace of a program without the spans or the
scopes (any commit before they came) reduces to empty tables, and every
reader built on them returns None.
"""

from __future__ import annotations

import re
from pathlib import Path

import numpy as np

import trace_reduce
from harness import say

SPAN = "siddhi:"
# a line (thread) of the host plane is the sender's if it holds one of these,
# else the drain worker's if it holds one of those: all lines carry the
# process's name, so the spans tell the threads apart
SENDER_MARK, DRAIN_MARK = SPAN + "send", SPAN + "drain"
NO_SPAN = "(no span open)"
UNSCOPED = "(unscoped)"
# the scopes the engine sets, outermost first in an operation's `tf_op`
SCOPE = re.compile(
    r"^(wire_decode|deliver_mask|deliver_pack|filter|selector|table_op|"
    r"ring_emit|ring_update|keyshard\.exchange|q\..+|fn\..+|window\..+)$")


# ---- the planes' metadata tables, from the file's bytes ---------------------

def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: ints for varints,
    memoryviews for length-delimited and fixed-width fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {kind} in an xplane file")
        yield key >> 3, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(view):
    key, value = 0, memoryview(b"")
    for no, v in _fields(view):
        if no == 1:
            key = v
        elif no == 2:
            value = v
    return key, value


def op_scopes(xplane_path: str) -> dict:
    """{(program name, operation name): `tf_op`} of the device planes.
    XSpace.planes=1; XPlane.name=2 .event_metadata=4 .stat_metadata=5;
    XEventMetadata.name=2 .display_name=4 .stats=5; XStatMetadata.name=2;
    XStat.metadata_id=1 .uint64=3 .int64=4 .str=5 .ref=7."""
    data = memoryview(Path(xplane_path).read_bytes())
    out = {}
    for no, plane in _fields(data):
        if no != 1:
            continue
        name, event_md, stat_names = "", [], {}
        for pno, v in _fields(plane):
            if pno == 2:
                name = _text(v)
            elif pno == 4:
                event_md.append(_map_entry(v)[1])
            elif pno == 5:
                key, md = _map_entry(v)
                stat_names[key] = next(
                    (_text(x) for n, x in _fields(md) if n == 2), "")
        if not name.startswith(trace_reduce.DEVICE_PLANE):
            continue
        programs, ops = {}, []
        for md in event_md:
            md_name = display = tf_op = ""
            program_id = None
            for mno, v in _fields(md):
                if mno == 2:
                    md_name = _text(v)
                elif mno == 4:
                    display = _text(v)
                elif mno == 5:
                    stat = dict(_fields(v))
                    which = stat_names.get(stat.get(1))
                    if which == "tf_op":
                        tf_op = (_text(stat[5]) if 5 in stat
                                 else stat_names.get(stat.get(7), ""))
                    elif which == "program_id":
                        program_id = stat.get(3, stat.get(4))
            if md_name.startswith("%"):  # an operation: its HLO text
                if program_id is not None:
                    ops.append((program_id & (2**64 - 1), display
                                or trace_reduce.op_name(md_name), tf_op))
                continue
            module = re.match(r"^(.*)\((\d+)\)$", md_name)
            if module:  # a program: `jit_name(program id)`
                programs[int(module.group(2))] = module.group(1)
        for program_id, op, tf_op in ops:
            out[(programs.get(program_id, ""), op)] = tf_op
    return out


def scope_of(tf_op: str) -> tuple:
    """The engine's scopes on an operation's path, outermost first."""
    return tuple(p for p in tf_op.split("/") if SCOPE.match(p))


# ---- host spans -------------------------------------------------------------

def host_spans(xplane_path: str) -> list:
    """One list per thread of its `siddhi:*` spans, by start:
    {name, t0, t1 (ns, the trace's clock), and the span's stats}."""
    from jax.profiler import ProfileData

    threads = []
    for plane in ProfileData.from_file(xplane_path).planes:
        if plane.name.startswith(trace_reduce.DEVICE_PLANE):
            continue
        for line in plane.lines:
            spans = [
                {"name": ev.name, "t0": ev.start_ns,
                 "t1": ev.start_ns + ev.duration_ns, **dict(ev.stats)}
                for ev in line.events if ev.name.startswith(SPAN)]
            if spans:
                threads.append(sorted(spans, key=lambda s: (s["t0"], -s["t1"])))
    return threads


def innermost(spans: list) -> list:
    """Properly nested spans of one thread -> disjoint (t0, t1, name)
    segments, each named after the innermost span open in it."""
    out, stack, cursor = [], [], 0.0

    def upto(t):
        # the time since the last boundary belongs to the span on top
        nonlocal cursor
        if stack and t > cursor:
            out.append((cursor, t, stack[-1][0]))
        cursor = max(cursor, t)

    for s in spans:
        while stack and stack[-1][1] <= s["t0"]:
            upto(stack[-1][1])
            stack.pop()
        upto(s["t0"])
        stack.append((s["name"], s["t1"]))
    while stack:
        upto(stack[-1][1])
        stack.pop()
    return out


def overlap_by_name(gaps: np.ndarray, segments: list) -> dict:
    """Seconds of the disjoint sorted `gaps` under each segment name."""
    out: dict = {}
    if not len(gaps) or not segments:
        return out
    seg = np.asarray([(a, b) for a, b, _ in segments], dtype=np.float64)
    for (a, b), (_, _, name) in zip(seg, segments):
        lo = np.searchsorted(gaps[:, 1], a, side="right")
        hi = np.searchsorted(gaps[:, 0], b, side="left")
        if hi > lo:
            part = np.clip(gaps[lo:hi], a, b)
            out[name] = out.get(name, 0.0) + float(
                (part[:, 1] - part[:, 0]).sum()) / 1e9
    return out


# ---- device time by scope ---------------------------------------------------

def exclusive_ns(ops: np.ndarray) -> np.ndarray:
    """Per operation of a device line (spans sorted by start, nested by
    time): its duration less its children's."""
    own = ops[:, 1] - ops[:, 0]
    stack = []
    for i, (t0, t1) in enumerate(ops):
        while stack and ops[stack[-1], 1] <= t0:
            stack.pop()
        if stack:
            own[stack[-1]] -= t1 - t0
        stack.append(i)
    return own


class ProgramSpans:
    """What one traced window holds of the engine's spans and scopes."""

    def __init__(self, xplane_path: str, trace):
        self.trace = trace
        w = trace.window_ns if trace is not None else None
        self.window = w
        threads = host_spans(xplane_path)
        if w is not None:
            threads = [[s for s in t if s["t0"] >= w[0] and s["t1"] <= w[1]]
                       for t in threads]
        self.threads = [t for t in threads if t]
        self.scopes = op_scopes(xplane_path)
        self._by_scope: dict = {}

    # -- host side
    def spans(self, name: str) -> list:
        return [s for t in self.threads for s in t if s["name"] == SPAN + name]

    def total_ms(self, *names) -> float | None:
        """Summed time of the named spans in the window; None if the trace
        holds none of them (not 0: the stage was not traced)."""
        found = [s for n in names for s in self.spans(n)]
        if not found:
            return None
        return sum(s["t1"] - s["t0"] for s in found) / 1e6

    def chunks(self) -> int:
        """Chunks of the fused path dispatched in the window."""
        return len({s.get("chunk") for s in self.spans("dispatch")})

    def per_chunk_ms(self, *names) -> float | None:
        """Mean, over the window's chunks, of the named spans' summed time:
        None where the first of them never occurs or no chunk ran."""
        n = self.chunks()
        if not n or not self.spans(names[0]):
            return None
        return self.total_ms(*names) / n

    def by_chunk(self) -> dict:
        """{chunk: {stage: ms}}; `queued` is the drain's `queued_us`."""
        out: dict = {}
        for t in self.threads:
            for s in t:
                if s.get("chunk") is None:
                    continue
                row = out.setdefault(s["chunk"], {})
                stage = s["name"][len(SPAN):]
                row[stage] = row.get(stage, 0.0) + (s["t1"] - s["t0"]) / 1e6
                if stage == "drain":
                    row["queued"] = s.get("queued_us", 0) / 1e3
        return out

    def self_ms(self, name: str) -> list:
        """Per span `name`: its time less what the spans inside it cover."""
        out = []
        for t in self.threads:
            segments = innermost(t)
            for s in t:
                if s["name"] != SPAN + name:
                    continue
                own = sum(b - a for a, b, n in segments
                          if n == s["name"] and a >= s["t0"] and b <= s["t1"])
                out.append(own / 1e6)
        return out

    def idle_by_span(self) -> dict:
        """{"sender"|"drain": {span: idle s}}: the device's idle time in
        the window by the innermost span open on each of the two threads."""
        if self.trace is None or not self.trace.devices or self.window is None:
            return {}
        gaps = trace_reduce.idle_spans(self.trace.devices[0], self.window)
        idle_s = float((gaps[:, 1] - gaps[:, 0]).sum()) / 1e9
        out = {}
        for role, mark, unless in (("sender", SENDER_MARK, None),
                                   ("drain", DRAIN_MARK, SENDER_MARK)):
            segments = sorted(
                seg for t in self.threads
                if any(s["name"] == mark for s in t)
                and not any(s["name"] == unless for s in t)
                for seg in innermost(t))
            if not segments:
                continue
            by = overlap_by_name(gaps, segments)
            by[NO_SPAN] = max(idle_s - sum(by.values()), 0.0)
            out[role] = by
        return out

    # -- device side
    def device_ms_by_scope(self, program: str) -> dict | None:
        """{scope path: ms} of exclusive device time inside the executions
        of `program` that lie wholly in the window, on the first device;
        None where no operation of the program carries a scope."""
        if program not in self._by_scope:
            self._by_scope[program] = self._device_ms_by_scope(program)
        return self._by_scope[program]

    def _device_ms_by_scope(self, program: str) -> dict | None:
        if self.trace is None or not self.trace.devices:
            return None
        dev = self.trace.devices[0]
        ex = trace_reduce.executions(self.trace, program)
        if not len(ex) or not any(
                p == program and scope_of(t) for (p, _), t in self.scopes.items()):
            return None
        own = exclusive_ns(dev.ops)
        k = np.searchsorted(ex[:, 0], dev.ops[:, 0], side="right") - 1
        inside = (k >= 0) & (dev.ops[:, 1] <= ex[np.maximum(k, 0), 1])
        out: dict = {}
        for i in np.flatnonzero(inside):
            path = scope_of(self.scopes.get((program, dev.op_names[i]), ""))
            key = "/".join(path) or UNSCOPED
            out[key] = out.get(key, 0.0) + own[i] / 1e6
        return out

    def scope_ms(self, program: str, per: float, *leaves) -> float | None:
        """Device ms of `program` under any scope path that holds a part
        starting with one of `leaves` (`UNSCOPED`: under none), over `per`
        (executions x micro-batches, or sends)."""
        table = self.device_ms_by_scope(program)
        if table is None or not per:
            return None

        def under(path: str) -> bool:
            if path == UNSCOPED:
                return UNSCOPED in leaves
            return any(part.startswith(leaf) for part in path.split("/")
                       for leaf in leaves if leaf != UNSCOPED)

        return sum(ms for path, ms in table.items() if under(path)) / per

    def report(self) -> None:
        """The tables, said once per run."""
        rows = self.by_chunk()
        stages = sorted({k for r in rows.values() for k in r})
        if rows:
            say(f"program spans: {len(rows)} chunks; mean ms per chunk: " + ", ".join(
                f"{st} {np.mean([r.get(st, 0.0) for r in rows.values()]):.3f}"
                for st in stages))
        for role, by in self.idle_by_span().items():
            total = sum(by.values())
            say(f"idle by program span, {role} thread ({total:.4f} s idle):")
            for name, s in sorted(by.items(), key=lambda kv: -kv[1]):
                say(f"  {name:<24} {s:9.4f} s  {100 * s / max(total, 1e-12):5.1f} %")
        for program in (self.trace.devices[0].programs
                        if self.trace is not None and self.trace.devices else ()):
            table = self.device_ms_by_scope(program)
            if table:
                n = len(trace_reduce.executions(self.trace, program))
                say(f"device ms by scope, {program}, per execution of {n}:")
                for path, ms in sorted(table.items(), key=lambda kv: -kv[1]):
                    say(f"  {path:<44} {ms / n:10.4f}")


_CACHE: dict = {}


def of(cell: dict, trace) -> ProgramSpans | None:
    """The spans of `cell`'s traced run, where `run.py` put its trace
    (`bench_out/<cell>/trace`); parsed once. None where there is no trace."""
    trace_dir = cell["bench_dir"].parent / "bench_out" / cell["name"] / "trace"
    key = str(trace_dir)
    if key not in _CACHE:
        try:
            path = trace_reduce.find_xplane(key)
        except FileNotFoundError:
            _CACHE[key] = None
        else:
            _CACHE[key] = ProgramSpans(path, trace)
            _CACHE[key].report()
    return _CACHE[key]


# the per-batch path's jitted step, as the trace shows it
STEP_PROGRAM = "jit__step_impl"


def device_scope_ms(trace, spans, counters, cell, *leaves) -> float | None:
    """Device ms under `leaves`: per micro-batch of the chunk program where
    it ran in the window, else per send of the per-batch step."""
    import readers

    ps = of(cell, trace)
    if ps is None or trace is None:
        return None
    runs = len(trace_reduce.executions(trace, readers.CHUNK_PROGRAM))
    if runs:
        depth = readers.chunk_batches(counters, cell)
        return ps.scope_ms(readers.CHUNK_PROGRAM, runs * (depth or 0), *leaves)
    return ps.scope_ms(STEP_PROGRAM, len(spans["sends"]), *leaves)
