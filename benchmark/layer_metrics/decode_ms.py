"""Host decode of a chunk's packed rows into `Event` objects
(`siddhi:decode`), mean per chunk. Program spans in the device trace."""

import program_spans


def read(trace, spans, counters, cell):
    ps = program_spans.of(cell, trace)
    if ps is None:
        return None
    return ps.per_chunk_ms("decode")
