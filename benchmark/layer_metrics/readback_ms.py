"""Per-batch path: the blocking read of a step's output (`siddhi:readback`),
per send. Program spans in the device trace."""

import program_spans


def read(trace, spans, counters, cell):
    ps = program_spans.of(cell, trace)
    if ps is None:
        return None
    total = ps.total_ms("readback")
    return total / len(spans["sends"]) if total is not None and len(spans["sends"]) else None
