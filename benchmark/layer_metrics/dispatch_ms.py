"""Sender time to get the app's process lock and submit the chunk program:
`siddhi:lock_wait` + `siddhi:dispatch`, mean per chunk. Program spans in the
device trace."""

import program_spans


def read(trace, spans, counters, cell):
    ps = program_spans.of(cell, trace)
    if ps is None:
        return None
    return ps.per_chunk_ms("dispatch", "lock_wait")
