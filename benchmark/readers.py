"""What several per-layer readers share. A reader is
`read(trace, spans, counters, cell) -> float | None`:
- `trace`: `trace_reduce.Trace`, or None where no device was traced;
- `spans`: the harness's own log of the window, on its clock in seconds:
  `sends` [n, 4] (start, end, first row, end row), `callbacks` [m, 3]
  (entry, rows, rows delivered before it), `info` (what the driver
  returned), `stream`, and `to_trace_ns` (harness seconds -> the trace's ns);
- `counters`: `status` (`snapshot_status()` after the window),
  `compile_before` / `compile_after` (`profile_report()["compile"]`) and
  `programs_built_in_window` (JAX's own compile-or-load events);
- `cell`: `name`, `config`, `sizes`, `traffic`.
A reader that finds nothing to read returns None."""

import json
from pathlib import Path

import numpy as np

import trace_reduce

# the programs' jit names, as the trace shows them
CHUNK_PROGRAM = "jit_fused"


def peaks(device_kind: str) -> dict:
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(
            f"device kind {device_kind!r} is not in peaks.json; add it with "
            f"its source, there is no default")
    return table["devices"][device_kind]


def compiles_in_window(counters: dict) -> float:
    """Programs compiled (or loaded from the cache) inside the window: the
    larger of CompileTelemetry's delta and JAX's own count, which also sees
    the small programs of eager slicing that the telemetry does not wrap."""
    before, after = counters["compile_before"], counters["compile_after"]
    delta = sum(e["compiles"] for e in after.values()) - sum(
        e["compiles"] for e in before.values())
    return float(max(delta, counters["programs_built_in_window"]))


def chunk_batches(counters: dict, cell: dict):
    """Micro-batches per execution of the chunk program, as the engine's
    own status reports its chunk depth; None off the fused path."""
    stream = counters["status"]["streams"][cell["config"]["stream"]]
    return (stream.get("pipeline") or {}).get("chunk_batches")


def chunk_executions(trace) -> np.ndarray:
    if trace is None:
        return np.zeros((0, 2))
    return trace_reduce.executions(trace, CHUNK_PROGRAM)


def chunk_device_ms(trace):
    """Mean device time of one execution of the chunk program."""
    ex = chunk_executions(trace)
    return float((ex[:, 1] - ex[:, 0]).mean()) / 1e6 if len(ex) else None


def deliver_lag_ms(trace, spans, counters, cell):
    """Mean, over the chunk program's executions, of: its end on the device
    -> entry of the first callback that receives its rows. Executions and
    chunks are matched in order: the window starts with nothing in flight."""
    ex = chunk_executions(trace)
    depth = chunk_batches(counters, cell)
    if not len(ex) or not depth or "to_trace_ns" not in spans:
        return None
    batch = cell["sizes"]["batch"]
    stream, cb = spans["stream"], spans["callbacks"]
    firsts = []
    for _, _, lo, hi in spans["sends"]:
        firsts.extend(np.arange(lo, hi, depth * batch))
    before = stream.kept_before(np.asarray(firsts, dtype=np.int64))
    # the callback in which emission number `before` arrives
    j = np.searchsorted(cb[:, 2] + cb[:, 1], before, side="right")
    m = min(len(ex), len(j))
    j = j[:m]
    ok = j < len(cb)
    entry = spans["to_trace_ns"](cb[j[ok], 0])
    return float((entry - ex[:m][ok, 1]).mean()) / 1e6 if ok.any() else None


def emission_of_sends(spans) -> np.ndarray:
    """Per send: entry of the callback that delivers its last row, on the
    harness clock (nan where it never came)."""
    cb, stream = spans["callbacks"], spans["stream"]
    upto = stream.kept_before(spans["sends"][:, 3].astype(np.int64))
    j = np.searchsorted(cb[:, 2] + cb[:, 1], upto, side="left")
    out = np.full(len(j), np.nan)
    ok = j < len(cb)
    out[ok] = cb[j[ok], 0]
    return out
