"""From a profiler trace (`.xplane.pb`) to what the metrics need: when the
device was busy, each program's executions, each operation's total time, and
the idle gaps by what the harness was doing in them.

Read with `jax.profiler.ProfileData` alone. A device plane is one whose name
starts with `/device:TPU:`; its `XLA Ops` line holds one event per executed
operation and its `XLA Modules` line one event per executed program
(`jit_name(fingerprint)`). The harness's own clock is put on the trace's
clock through the `bench:window` annotation it writes (see `clock_offset`).
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

import numpy as np

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench:window"
# idle time goes to the first of these the harness was in
GAP_ORDER = ("in_callback", "in_generator_wait", "in_send_columns")
GAP_REST = "between_sends"


@dataclass
class DeviceTrace:
    name: str
    ops: np.ndarray                      # [n, 2] start, end (ns), by start
    op_names: list
    programs: dict = field(default_factory=dict)  # name -> [m, 2] ns


@dataclass
class Trace:
    devices: list
    window_ns: tuple | None              # the `bench:window` span, if found

    @property
    def window_s(self) -> float:
        w = self.window_ns
        return (w[1] - w[0]) / 1e9 if w else 0.0


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def program_name(event_name: str) -> str:
    """`jit__step_impl(1234567)` -> `jit__step_impl`."""
    return re.sub(r"\(\d+\)$", "", event_name)


def op_name(event_name: str) -> str:
    """`%fusion.5 = f32[65536]{...} fusion(...)` -> `fusion.5`."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, window = [], None
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            spans, names, programs = [], [], {}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        spans.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                        names.append(op_name(ev.name))
                elif line.name == MODULES_LINE:
                    for ev in line.events:
                        programs.setdefault(program_name(ev.name), []).append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
            order = np.argsort([s for s, _ in spans], kind="stable")
            devices.append(DeviceTrace(
                name=plane.name,
                ops=np.asarray(spans, dtype=np.float64).reshape(-1, 2)[order],
                op_names=[names[i] for i in order],
                programs={k: np.asarray(sorted(v), dtype=np.float64)
                          for k, v in programs.items()},
            ))
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(WINDOW_SPAN):
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
    return Trace(devices=devices, window_ns=window)


def union(spans: np.ndarray) -> np.ndarray:
    """Sorted [n, 2] spans -> disjoint sorted spans."""
    if not len(spans):
        return spans.reshape(0, 2)
    ends = np.maximum.accumulate(spans[:, 1])
    fresh = np.concatenate([[True], spans[1:, 0] > ends[:-1]])
    starts = spans[fresh, 0]
    last = np.concatenate([np.flatnonzero(fresh)[1:] - 1, [len(spans) - 1]])
    return np.stack([starts, ends[last]], axis=1)


def clip(spans: np.ndarray, lo: float, hi: float) -> np.ndarray:
    s = np.clip(spans, lo, hi)
    return s[s[:, 1] > s[:, 0]]


def busy_spans(dev: DeviceTrace, window: tuple) -> np.ndarray:
    return union(clip(dev.ops, *window))


def busy_seconds(trace: Trace) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    if not trace.devices or not trace.window_ns:
        return 0.0
    per = [busy_spans(d, trace.window_ns) for d in trace.devices]
    return float(np.mean([(b[:, 1] - b[:, 0]).sum() for b in per])) / 1e9


def idle_spans(dev: DeviceTrace, window: tuple) -> np.ndarray:
    b = busy_spans(dev, window)
    edges = np.concatenate([[window[0]], b.reshape(-1), [window[1]]])
    gaps = edges.reshape(-1, 2)
    return gaps[gaps[:, 1] > gaps[:, 0]]


def covered(points: np.ndarray, spans: np.ndarray) -> np.ndarray:
    """Which points lie inside one of the disjoint sorted spans."""
    if not len(spans):
        return np.zeros(len(points), dtype=bool)
    i = np.searchsorted(spans[:, 0], points, side="right") - 1
    return (i >= 0) & (points < spans[np.maximum(i, 0), 1])


def attribute_gaps(gaps: np.ndarray, host: dict) -> dict:
    """Idle seconds by what the harness was doing: `host` maps each name of
    GAP_ORDER to [n, 2] spans on the trace's clock."""
    cuts = [gaps.reshape(-1)]
    sets = {}
    for name in GAP_ORDER:
        spans = np.asarray(host.get(name, []), dtype=np.float64).reshape(-1, 2)
        sets[name] = union(spans[np.argsort(spans[:, 0], kind="stable")])
        cuts.append(sets[name].reshape(-1))
    edges = np.unique(np.concatenate(cuts))
    mid, width = (edges[:-1] + edges[1:]) / 2, np.diff(edges)
    left = covered(mid, gaps)
    out = {}
    for name in GAP_ORDER:
        here = left & covered(mid, sets[name])
        out[name] = float(width[here].sum()) / 1e9
        left &= ~here
    out[GAP_REST] = float(width[left].sum()) / 1e9
    return out


def op_totals(trace: Trace) -> dict:
    """Seconds per operation and per program (`program:<name>`), inside the
    window, summed over the devices."""
    out: dict = {}
    w = trace.window_ns
    for dev in trace.devices:
        inside = (dev.ops[:, 1] > w[0]) & (dev.ops[:, 0] < w[1])
        dur = np.clip(dev.ops[:, 1], *w) - np.clip(dev.ops[:, 0], *w)
        for i in np.flatnonzero(inside):
            out[dev.op_names[i]] = out.get(dev.op_names[i], 0.0) + dur[i] / 1e9
        for name, spans in dev.programs.items():
            s = clip(spans, *w)
            out["program:" + name] = out.get("program:" + name, 0.0) + float(
                (s[:, 1] - s[:, 0]).sum()) / 1e9
    return out


def executions(trace: Trace, name: str) -> np.ndarray:
    """[m, 2] spans of the executions of program `name` that lie wholly
    inside the window, on the first device."""
    if not trace.devices or not trace.window_ns:
        return np.zeros((0, 2))
    spans = trace.devices[0].programs.get(name, np.zeros((0, 2)))
    w = trace.window_ns
    return spans[(spans[:, 0] >= w[0]) & (spans[:, 1] <= w[1])]


def clock_offset(trace: Trace, window_entered_ns: int) -> float:
    """Add this to a `time.perf_counter_ns()` reading to put it on the
    trace's clock: the harness reads its clock right before it opens the
    `bench:window` annotation."""
    return trace.window_ns[0] - window_entered_ns


def breakdown(trace: Trace, host: dict, top: int = 10) -> dict:
    totals = op_totals(trace)
    programs = sorted(((k, v) for k, v in totals.items()
                       if k.startswith("program:")), key=lambda kv: -kv[1])
    ops = sorted(((k, v) for k, v in totals.items()
                  if not k.startswith("program:")), key=lambda kv: -kv[1])
    device_ops = (programs[:3] + ops)[:top]
    gaps = attribute_gaps(idle_spans(trace.devices[0], trace.window_ns), host)
    return {
        "device_ops": [[k, v] for k, v in device_ops],
        "idle_gaps": [[k, v] for k, v in
                      sorted(gaps.items(), key=lambda kv: -kv[1])],
    }
