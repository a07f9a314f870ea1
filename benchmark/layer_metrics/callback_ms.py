"""Time inside the query callbacks, summed over a chunk's per-micro-batch calls
(`siddhi:callback`), mean per chunk. Program spans in the device trace."""

import program_spans


def read(trace, spans, counters, cell):
    ps = program_spans.of(cell, trace)
    if ps is None:
        return None
    return ps.per_chunk_ms("callback")
