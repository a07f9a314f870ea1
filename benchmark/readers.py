"""What several per-layer readers share. A reader is
`read(trace, spans, counters, cell) -> float | None`:
- `trace`: `trace_reduce.Trace`, or None where no device was traced;
- `spans`: the harness's own log of the window, on its clock in seconds:
  `sends` [n, 4] (start, end, first row, end row), `callbacks` [m, 3]
  (entry, rows, rows delivered before it), `info` (what the driver
  returned), `stream` (`harness.Stream`; `emit_share` is the share of the
  rows sent that emit), and `to_trace_ns` (harness seconds -> the trace's ns);
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
